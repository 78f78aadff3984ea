"""Fused trigger-gated blockwise SignTopK kernel (the paper's compression
hot-spot, TPU-native) with a compiled XLA leg.

One pass over HBM per sync: reads (x_half, x_hat) tiles into VMEM, computes
diff, the per-tile EXACT-k Top-k support (radix-select threshold on the f32
bit patterns + index-ordered tie break — pure VPU, no MXU, no sort), the
SignTopK message q = trig * scale * sign(diff) on the support, and the
updated estimate x_hat + q — all in one kernel, instead of the 4 separate
HBM sweeps an unfused implementation costs (diff, top_k, scatter, add).
``sign_topk_q`` is the same kernel body on one input, the difference
itself, returning only q and the scales (the ensemble path gates and adds
outside, so it needs neither an x_hat row nor x_hat + q).

Selection contract (shared by the kernel, the XLA leg and kernels/ref.py):
per tile, the support is EXACTLY the index set ``jax.lax.top_k(|diff|, k_b)``
would return — every |diff| strictly above the k_b-th largest, plus
lowest-index ties at the threshold until exactly k_b are chosen — EXCEPT that
zero lanes are never selected (|diff| == 0 carries no mass; this keeps
zero-padded tail tiles silent instead of emitting +scale on every padded
lane). |support| <= k_b always, so a (vals, idx) payload of k_b entries per
tile reconstructs q exactly, ties included.

Layout: the flat parameter shard is padded and reshaped to (n_blocks, BLOCK)
with BLOCK = 1024 = 8 sublanes x 128 lanes; BlockSpec tiles one tall
(rows, BLOCK) slab of up to BLOCK_ROWS tiles per grid step. Each selection
pass is a compare plus a cross-lane row count; the count's latency on the
XLU is what a pass waits for, so a slab of many independent 8-row groups
lets the scheduler issue the other groups' passes meanwhile.

GPU-vs-TPU note (DESIGN §3): the reference CUDA Top-k is a global radix select;
here selection is per 1024-element tile (same total k) — no cross-tile traffic,
sort runs on 8x128 vregs.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_lowering

BLOCK = 1024
# tiles per grid step, the fastest of 8..256 on a TPU v5e (PERF.md); a
# multiple of 8 sublanes. 512 tiles overflow Mosaic's 16 MiB scoped VMEM.
BLOCK_ROWS = 256
# the Pallas call's fixed name, which the HLO custom call and the profiler
# trace carry; it starts with sign_topk, which the benchmark's kernel reader
# matches
KERNEL_NAME = "sign_topk_tiles"


def _row_count(mask: jax.Array) -> jax.Array:
    """Per-row count of a (rows, B) bool mask as (rows, 1) float32: exact up
    to 2**24, and one cross-lane reduce where an int32 sum takes two."""
    return jnp.sum(mask.astype(jnp.float32), axis=1, keepdims=True)


def _row_threshold(av: jax.Array, k_b: int) -> jax.Array:
    """Per-row k_b-th largest of nonnegative f32 rows, by EXACT radix select
    on the float bit patterns (for av >= 0 the int32 pattern order equals
    numeric order, and bit 31 is clear, so the select starts at bit 30).
    31 compare+count passes instead of a full sort — on CPU XLA this is ~20x
    faster than ``lax.sort`` at (64, 1024), and the passes are plain
    elementwise-compare + row-sum, VPU-friendly under Mosaic where
    ``lax.sort`` has no lowering at all. The returned value is an achieved
    element (the largest t with count(av >= t) >= k_b >= 1), so it is
    bit-equal to ``sort(av)[..., -k_b]`` — every lowering leg shares this
    function and therefore the exact same threshold floats.
    av: (rows, B) -> (rows, 1)."""
    u = jax.lax.bitcast_convert_type(av, jnp.int32)

    def body(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(_row_count(u >= cand) >= k_b, cand, prefix)

    bits = jax.lax.fori_loop(0, 31, body,
                             jnp.zeros((av.shape[0], 1), jnp.int32))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _first_ties(tie: jax.Array, quota: jax.Array) -> jax.Array:
    """The lowest-index ``quota`` lanes of ``tie`` per row, i.e. exactly
    ``tie & (cumsum(tie) <= quota)``, without cumsum (Mosaic has no
    lowering for it): an 11-step bitwise search finds the largest lane
    cutoff m in [0, BLOCK] with count(tie[:, :m]) <= quota by compare +
    row-sum passes, and the prefix count is nondecreasing, so the lanes
    below m are the ones whose rank is within quota.
    tie: (rows, B) bool; quota: (rows, 1) float32, a whole number >= 0."""
    lane = jax.lax.broadcasted_iota(jnp.int32, tie.shape, 1)
    tie_f = tie.astype(jnp.float32)
    top = tie.shape[1].bit_length() - 1

    def body(i, m):
        cand = m | jnp.left_shift(jnp.int32(1), top - i)
        # a select on the f32 mask: fewer VPU ops a pass than and + convert
        cnt = jnp.sum(jnp.where(lane < cand, tie_f, 0.0), axis=1,
                      keepdims=True)
        return jnp.where((cand <= tie.shape[1]) & (cnt <= quota), cand, m)

    cut = jax.lax.fori_loop(0, top + 1, body,
                            jnp.zeros(quota.shape, jnp.int32))
    return jnp.logical_and(tie, lane < cut)


def _block_compress(diff: jax.Array, trig: jax.Array, k_b: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """Exact-k blockwise SignTopK on f32 rows.

    diff: (rows, BLOCK) f32; trig: scalar f32 in {0., 1.}. Returns
    (q (rows, BLOCK) f32, per-row scale (rows, 1) f32 — already trig-gated).
    The selected index set per row equals ``jax.lax.top_k(|diff|, k_b)``'s
    (strictly-above-threshold entries first, then lowest-index ties)
    restricted to nonzero lanes, so |support| <= k_b and a k_b-entry payload
    is always exact."""
    av = jnp.abs(diff)
    pos = av > 0.0
    # per-row threshold: k_b-th largest |diff| via exact radix select
    thr = _row_threshold(av, k_b)                               # (rows, 1)
    gt = jnp.logical_and(av > thr, pos)
    tie = jnp.logical_and(jnp.logical_and(av >= thr,
                                          jnp.logical_not(gt)), pos)
    # fill the remaining quota with the LOWEST-index ties (top_k order)
    quota = k_b - _row_count(gt)
    mask = jnp.logical_or(gt, _first_ties(tie, quota))
    nsel = _row_count(mask)
    scale = (jnp.sum(jnp.where(mask, av, 0.0), axis=1, keepdims=True)
             / jnp.maximum(nsel, 1.0))
    signs = jnp.where(diff >= 0, 1.0, -1.0)
    q = jnp.where(mask, trig * scale * signs, 0.0)
    return q, (trig * scale).astype(jnp.float32)


def _sign_topk_kernel(xh_ref, xe_ref, trig_ref, q_ref, xe_new_ref, scale_ref,
                      *, k_b: int):
    xh = xh_ref[...]
    xe = xe_ref[...]
    trig = trig_ref[0]
    # subtract in fp32 by spec (interpret mode stores bf16 refs as f32;
    # casting first makes kernel and oracle bit-identical on both paths)
    diff = xh.astype(jnp.float32) - xe.astype(jnp.float32)
    q32, scale = _block_compress(diff, trig, k_b)
    q = q32.astype(xh.dtype)
    q_ref[...] = q
    xe_new_ref[...] = xe + q
    scale_ref[...] = scale


def _sign_topk_q_kernel(x_ref, q_ref, scale_ref, *, k_b: int):
    q32, scale = _block_compress(x_ref[...].astype(jnp.float32),
                                 jnp.float32(1.0), k_b)
    q_ref[...] = q32.astype(q_ref.dtype)
    scale_ref[...] = scale


def _sign_topk_xla(x_half: jax.Array, x_hat: jax.Array, trig: jax.Array,
                   k_b: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compiled leg: the same per-row block math over the whole (n, BLOCK)
    array as one jnp program. Row reductions are independent, so results are
    bit-identical to the interpreter slab-by-slab path."""
    diff = x_half.astype(jnp.float32) - x_hat.astype(jnp.float32)
    q32, scale = _block_compress(diff, trig, k_b)
    q = q32.astype(x_half.dtype)
    return q, x_hat + q, scale[:, 0]


def slab_rows(n: int) -> int:
    """Tiles per grid step for n tiles: all n where they fit one slab, else
    the tallest slab of at most BLOCK_ROWS tiles, a multiple of 8 sublanes,
    that divides n (every tile row is computed alone, so the height changes
    no result)."""
    if n <= BLOCK_ROWS:
        return n
    rows = next((r for r in range(BLOCK_ROWS, 0, -8) if n % r == 0), 0)
    assert rows, f"{n} tiles: no multiple of 8 up to {BLOCK_ROWS} divides it"
    return rows


@functools.partial(jax.jit, static_argnames=("k_b", "interpret", "lowering"))
def sign_topk_blocks(x_half: jax.Array, x_hat: jax.Array, trig: jax.Array,
                     k_b: int, interpret: Optional[bool] = None,
                     lowering: Optional[str] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x_half, x_hat: (n_blocks, BLOCK); trig: () f32 in {0., 1.}.

    Returns (q, x_hat_new, per-block scale). ``lowering=None`` resolves via
    :func:`repro.kernels.resolve_lowering` (env/backend, never a literal)."""
    lw = resolve_lowering(lowering, interpret)
    n, b = x_half.shape
    assert b == BLOCK, f"inner dim must be {BLOCK}"
    trig_arr = jnp.asarray(trig, jnp.float32)
    if lw == "xla":
        return _sign_topk_xla(x_half, x_hat, trig_arr, k_b)
    q, xe_new, scale = _tile_call(
        functools.partial(_sign_topk_kernel, k_b=k_b), 2, x_half,
        [x_half, x_hat], [trig_arr.reshape(1)], lw)
    return q, xe_new, scale[:, 0]


def _tile_call(kernel, n_rows_out: int, like: jax.Array, rows_in, scalars,
               lw: str):
    """The Pallas call of a SignTopK kernel body over (n, BLOCK) tiles in
    slabs of ``slab_rows(n)``: row-sized inputs, then (1,) scalars; row
    outputs like ``like``, then the (n, 1) float32 scales."""
    n = like.shape[0]
    rows = slab_rows(n)
    tiles = pl.BlockSpec((rows, BLOCK), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(n // rows,),
        in_specs=[tiles] * len(rows_in)
        + [pl.BlockSpec((1,), lambda i: (0,))] * len(scalars),
        # 2-D (rows, 1) scale block: Mosaic refuses rank-1 blocks that are
        # not a multiple of 128 lanes
        out_specs=[tiles] * n_rows_out
        + [pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, BLOCK), like.dtype)] * n_rows_out
        + [jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        interpret=(lw == "interpret"),
        name=KERNEL_NAME,
    )(*rows_in, *scalars)


@functools.partial(jax.jit, static_argnames=("k_b", "interpret", "lowering"))
def sign_topk_q(x_half: jax.Array, k_b: int, interpret: Optional[bool] = None,
                lowering: Optional[str] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """x_half: (n_blocks, BLOCK), the difference to compress. Returns (q,
    per-block scale): ``sign_topk_blocks(x_half, 0, 1.)``'s q and scale, bit
    for bit, with no x_hat input and no x_hat + q output."""
    lw = resolve_lowering(lowering, interpret)
    n, b = x_half.shape
    assert b == BLOCK, f"inner dim must be {BLOCK}"
    if lw == "xla":
        q32, scale = _block_compress(x_half.astype(jnp.float32),
                                     jnp.float32(1.0), k_b)
        return q32.astype(x_half.dtype), scale[:, 0]
    q, scale = _tile_call(functools.partial(_sign_topk_q_kernel, k_b=k_b),
                          1, x_half, [x_half], [], lw)
    return q, scale[:, 0]
