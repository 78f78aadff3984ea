"""device_idle_share: the share of the traced window in which no op ran on
the device, 1 - (union of the device's op intervals) / window, averaged
over the chips. Layer: device. Moves tokens_per_s."""
import tracefile as tr


def read(ctx):
    if not ctx.trace.devices or ctx.interval is None:
        return None
    span = ctx.interval[1] - ctx.interval[0]
    busy = [tr.busy(d, ctx.interval) for d in ctx.trace.devices]
    if span <= 0 or not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)
