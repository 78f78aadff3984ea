"""sign_topk_roofline: the Pallas SignTopK compression's share of its
roofline: the least time any implementation of the operator needs
(``kernel_bytes`` over the HBM peak of ``peaks.json``) over the summed
device time of the kernel's events, per chip, averaged over the chips.
Layer: kernel (``kernels/sign_topk.py``). Moves tokens_per_s."""
import math

import tracefile as tr

KERNEL = r"sign_topk"
TILE = 1024


def kernel_bytes(d: int, frac: float) -> int:
    """A lower bound on the HBM bytes of one SignTopK of a d-element float32
    row at fraction frac, for any implementation. It must read every element
    once (4 B each; a value it does not read could be the largest). In each
    whole tile of 1,024 it keeps k = ceil(frac 1024) entries, and must write
    at least what tells them apart: which k of the 1,024 (log2 C(1024, k)
    bits, less than any index list), their k signs and the tile's float32
    scale. The program's payload (10-bit indices, about 1.4 B a kept entry)
    and a dense q or x_hat + q are larger, so no fused or sparse-payload
    kernel can read over 100 %."""
    k = max(1, min(TILE, math.ceil(frac * TILE)))
    support = math.comb(TILE, k).bit_length() - 1     # floor(log2 C)
    tile_bits = support + k + 32
    return 4 * d + (d // TILE) * tile_bits // 8


def read(ctx):
    n_sync = sum(ctx.window.sync_flags)
    if not n_sync:
        return None
    bound_s = (n_sync * ctx.nodes_per_device
               * kernel_bytes(ctx.d_model, ctx.frac)
               / ctx.peaks["hbm_bytes_per_s"])
    shares = []
    for dev in ctx.trace.devices:
        ops = [o for o in tr.kernel_ops(dev, KERNEL)
               if ctx.interval[0] <= o.start < ctx.interval[1]]
        t = sum(o.end - o.start for o in ops) * 1e-9
        if t <= 0:
            return None
        shares.append(100.0 * bound_s / t)
    return sum(shares) / len(shares) if shares else None
