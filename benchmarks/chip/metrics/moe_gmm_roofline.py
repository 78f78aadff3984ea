"""moe_gmm_roofline: the dropless expert layer's grouped products (the
Pallas kernels ``moe_gmm`` and ``moe_gmm_tgmm`` of ``kernels/gmm.py``) as a
share of their roofline: the least time the chip needs for the least FLOPs
of the held experts' products over the window (``gmm_flops`` at the bf16
peak of ``peaks.json``) over the summed device time of the kernels' events,
per chip, averaged over the chips. The products' bytes (the held experts'
weights once a product, the routed rows in and out) take less time at the
HBM peak than their FLOPs at the bf16 peak, so FLOPs bound it. Layer:
kernel. Moves tokens_per_s.

The only cell that runs the kernels is deepseek-v2-lite's, whose
configuration file gives the sizes: the reader reads nothing in a cell whose
row (``ctx.d_model``) is not that configuration's, so another cell that
lists the metric fails its traced run until the harness hands the reader the
cell's own configuration."""
import json
import os

import tracefile as tr
from reference import mla_moe_lm, sparq

KERNEL = r"moe_gmm"
CONFIG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "configs", "deepseek-v2-lite.json")


def gmm_flops(cfg: dict, tokens: float) -> float:
    """The least FLOPs of the held experts' grouped products for
    ``tokens`` tokens of one chip: per expert layer three products of R
    rows (gate and up, d -> fe; down, fe -> d), each run forward and twice
    backward (the input's and the weights' gradients) at 2 FLOPs a
    multiply-add: 18 R d fe. R is the expected routings into the held
    experts, tokens x top_k x held / the router's width (the expected
    count, not the routings the program counts in ``moe_rows``).
    Recomputation and the tiles' padding rows are not counted."""
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    rows = (tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["published"]["n_routed_experts"])
    return (18.0 * rows * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * layers)


def row_size(cfg: dict) -> int:
    """The flat row's length of the configuration's parameters."""
    segs = sparq.layout(mla_moe_lm.init_params, cfg)
    return segs[-1][1] + segs[-1][2]


def read(ctx):
    if not ctx.window.steps:
        return None
    with open(CONFIG) as f:
        cfg = json.load(f)
    if ctx.d_model != row_size(cfg):
        return None
    tokens = ctx.window.steps * ctx.tokens_per_step / ctx.chips
    bound_s = gmm_flops(cfg, tokens) / ctx.peaks["bf16_flops_per_s"]
    shares = []
    for dev in ctx.trace.devices:
        ops = [o for o in tr.kernel_ops(dev, KERNEL)
               if ctx.interval[0] <= o.start < ctx.interval[1]]
        t = sum(o.end - o.start for o in ops) * 1e-9
        if t <= 0:
            return None
        shares.append(100.0 * bound_s / t)
    return sum(shares) / len(shares) if shares else None
