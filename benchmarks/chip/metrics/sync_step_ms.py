"""sync_step_ms: median device time of the compiled train step on sync
steps (the local step plus trigger norm, compression, x_hat update and
gossip mix), from the trace's ``XLA Modules`` line. Layer: sync. Moves
step_s_p90."""
from tracefile import step_ms


def read(ctx):
    return step_ms(ctx, want_sync=True)
