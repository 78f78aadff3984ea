"""host_batch_ms: host milliseconds per step spent building the step's
batch (the token generator) and placing it on the devices (``device_put``),
by the host clock, averaged over the traced window's steps. Layer: host loop
and data. Moves tokens_per_s where the host, not the chip, sets the pace."""


def read(ctx):
    host = ctx.window.host_s
    return 1e3 * sum(host) / len(host) if host else None
