"""local_step_ms: median device time of the compiled train step on steps
that do not sync (fwd/bwd through ``unravel``, the optimizer update), from
the trace's ``XLA Modules`` line. Layer: local step. Moves tokens_per_s."""
from tracefile import step_ms


def read(ctx):
    return step_ms(ctx, want_sync=False)
