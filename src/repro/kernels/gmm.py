"""Grouped matrix products of the dropless expert layer (``models/moe.py``),
a Pallas kernel with a compiled XLA leg.

The rows of ``lhs`` are laid out in whole tiles of ``TILE_M`` rows, each
tile holding rows of one group (expert) only, the groups' tiles in group
order; ``tile_group[i]`` names the group of tile i and the first
``n_active`` tiles are in use. ``gmm`` multiplies each tile by its group's
matrix; the tiles past ``n_active`` come out zero and cost no matrix work,
so the work follows the routed rows and not the static buffer. Its
gradient is two more grouped products: the input's (``gmm`` against the
transposed matrices) and the matrices' (``tgmm``: per group, the sum over
its tiles of lhs_tile^T @ cotangent_tile).

Legs (``repro.kernels.resolve_lowering``): ``pallas`` and ``interpret`` run
the kernels below, whose calls carry the fixed names ``moe_gmm`` and
``moe_gmm_tgmm``, so the HLO custom calls and the profiler's events are
found by name; ``xla`` runs ``jax.lax.ragged_dot`` and lets JAX
differentiate it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_lowering

TILE_M = 128
# fixed names of the Pallas calls; both start with moe_gmm, which the
# benchmark's kernel reader matches
GMM_NAME = "moe_gmm"
TGMM_NAME = "moe_gmm_tgmm"
# a group's whole (K, N) matrix is one block: 5.8 MB in bfloat16 at
# 2048 x 1408, twice for the pipeline, above Mosaic's 16 MiB default
VMEM_LIMIT = 64 * 1024 * 1024


def layout(group_sizes: jax.Array, rows: int):
    """Tile layout of groups of ``group_sizes`` rows in a buffer of ``rows``
    rows (a multiple of TILE_M): each group takes max(1, ceil(size /
    TILE_M)) whole tiles, in group order (every group holds a tile, so each
    of ``tgmm``'s output blocks is written).

    Returns (row offset of each group's first tile, tile_group (tiles,),
    n_active (1,)), all int32."""
    tiles = jnp.maximum(1, -(-group_sizes // TILE_M)).astype(jnp.int32)
    ends = jnp.cumsum(tiles)
    starts = ends - tiles
    idx = jnp.arange(rows // TILE_M, dtype=jnp.int32)
    tile_group = jnp.minimum(jnp.searchsorted(ends, idx, side="right"),
                             group_sizes.shape[0] - 1).astype(jnp.int32)
    return starts * TILE_M, tile_group, ends[-1:].astype(jnp.int32)


def _tile(dim: int) -> int:
    """Block extent along a matrix dim of tgmm's output: 512 where it
    divides, else the whole dim."""
    return 512 if dim % 512 == 0 else dim


def _gmm_kernel(tile_group_ref, n_active_ref, lhs_ref, rhs_ref, out_ref, *,
                transpose_rhs: bool):
    del tile_group_ref
    active = pl.program_id(0) < n_active_ref[0]

    @pl.when(active)
    def _():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs else \
            (((1,), (0,)), ((), ()))
        out_ref[...] = lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(active))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _gmm_call(lhs, rhs, tile_group, n_active, transpose_rhs: bool,
              interpret: bool):
    m, k = lhs.shape
    _, rk, rn = rhs.shape
    n = rk if transpose_rhs else rn

    def last(i, na):
        return jnp.minimum(i, na[0] - 1)

    # tiles past n_active keep the last active tile's blocks: no new copy
    def lhs_map(i, tg, na):
        return last(i, na), 0

    def rhs_map(i, tg, na):
        return tg[last(i, na)], 0, 0

    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // TILE_M,),
            in_specs=[pl.BlockSpec((TILE_M, k), lhs_map),
                      pl.BlockSpec((None, rk, rn), rhs_map)],
            out_specs=pl.BlockSpec((TILE_M, n), lambda i, tg, na: (i, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=GMM_NAME,
    )(tile_group, n_active, lhs, rhs)


def _tgmm_kernel(tile_group_ref, n_active_ref, lhs_ref, dout_ref, out_ref):
    i = pl.program_id(2)
    na = n_active_ref[0]
    here = jnp.minimum(i, na - 1)
    first = jnp.logical_or(
        i == 0,
        tile_group_ref[here] != tile_group_ref[jnp.maximum(here - 1, 0)])

    @pl.when(i < na)
    def _():
        acc = lax.dot_general(lhs_ref[...], dout_ref[...],
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)

        @pl.when(first)
        def _():
            out_ref[...] = acc

        @pl.when(jnp.logical_not(first))
        def _():
            out_ref[...] += acc


def _tgmm_call(lhs, dout, tile_group, n_active, groups: int,
               interpret: bool):
    m, k = lhs.shape
    n = dout.shape[1]
    tk, tn = _tile(k), _tile(n)

    def last(i, na):
        return jnp.minimum(i, na[0] - 1)

    def lhs_map(a, b, i, tg, na):
        return last(i, na), a

    def dout_map(a, b, i, tg, na):
        return last(i, na), b

    def out_map(a, b, i, tg, na):
        return tg[last(i, na)], a, b

    return pl.pallas_call(
        _tgmm_kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, m // TILE_M),
            in_specs=[pl.BlockSpec((TILE_M, tk), lhs_map),
                      pl.BlockSpec((TILE_M, tn), dout_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=TGMM_NAME,
    )(tile_group, n_active, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm_pallas(lhs, rhs, tile_group, n_active, interpret: bool):
    return _gmm_call(lhs, rhs, tile_group, n_active, False, interpret)


def _gmm_pallas_fwd(lhs, rhs, tile_group, n_active, interpret):
    out = _gmm_call(lhs, rhs, tile_group, n_active, False, interpret)
    return out, (lhs, rhs, tile_group, n_active)


def _gmm_pallas_bwd(interpret, res, dout):
    lhs, rhs, tile_group, n_active = res
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm_call(dout, rhs, tile_group, n_active, True, interpret)
    drhs = _tgmm_call(lhs, dout, tile_group, n_active, rhs.shape[0],
                      interpret)
    return dlhs, drhs.astype(rhs.dtype), None, None


_gmm_pallas.defvjp(_gmm_pallas_fwd, _gmm_pallas_bwd)


def _gmm_xla(lhs, rhs, tile_group, n_active):
    """The same products by ``ragged_dot``: each group's tiles as one run of
    rows, the unused tail given to the last group and zeroed after."""
    m = lhs.shape[0]
    g = rhs.shape[0]
    tiles = jnp.zeros((g,), jnp.int32).at[tile_group].add(
        (jnp.arange(tile_group.shape[0]) < n_active[0]).astype(jnp.int32))
    sizes = tiles * TILE_M
    sizes = sizes.at[g - 1].add(m - jnp.sum(sizes))
    out = lax.ragged_dot(lhs, rhs, sizes,
                         preferred_element_type=jnp.float32)
    live = jnp.arange(m) < n_active[0] * TILE_M
    return jnp.where(live[:, None], out, 0.0).astype(lhs.dtype)


def gmm(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
        n_active: jax.Array, lowering: Optional[str] = None) -> jax.Array:
    """lhs (M, K) in tiles of TILE_M rows (``layout``); rhs (G, K, N).
    Returns (M, N) in lhs's dtype: each active tile times its group's
    matrix, float32 accumulation; inactive tiles zero. Differentiable in
    lhs and rhs."""
    lw = resolve_lowering(lowering)
    assert lhs.shape[0] % TILE_M == 0, f"rows must be whole {TILE_M}-tiles"
    if lw == "xla":
        return _gmm_xla(lhs, rhs, tile_group, n_active)
    return _gmm_pallas(lhs, rhs, tile_group, n_active, lw == "interpret")
