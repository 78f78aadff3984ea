"""jit'd public wrappers around the blockwise compression kernels.

These provide flat-vector semantics over the blockwise kernels (padding to
BLOCK=1024 tiles), the interface the distributed gossip path consumes.
``lowering=None`` resolves through :func:`repro.kernels.resolve_lowering`
(the ``REPRO_KERNEL_LOWERING`` env var, else pallas on TPU / compiled XLA
elsewhere) — never a hard-coded literal, the K2 hygiene contract.

Payload contract: per 1024-element tile the exact-k selection (see
sign_topk.py) supports AT MOST k_b nonzeros whose index set is contained in
``jax.lax.top_k(|q_tile|, k_b)``'s, so a fixed (n_tiles * k_b)-entry
(vals, idx) payload gathered from the dense q reconstructs q exactly —
scatter(vals, idx) == q, ties and sub-k_b tiles included (surplus payload
slots carry explicit zeros at padding/zero positions).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import resolve_lowering
from repro.kernels.qsgd import BLOCK, qsgd_blocks
from repro.kernels.sign_topk import BLOCK_ROWS, sign_topk_blocks, sign_topk_q


def _to_blocks(x: jax.Array) -> Tuple[jax.Array, int, int]:
    d = x.shape[0]
    n = max(1, -(-d // BLOCK))
    pad = n * BLOCK - d
    return jnp.pad(x, (0, pad)).reshape(n, BLOCK), d, n


@functools.partial(jax.jit, static_argnames=("k", "interpret", "lowering"))
def sign_topk(flat: jax.Array, k: int, interpret: Optional[bool] = None,
              lowering: Optional[str] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Blockwise SignTopK of a flat vector, k total (ceil-split across blocks).

    Returns (q dense (d,), values (n*k_b,), indices (n*k_b,) global int32) —
    the (q, vals, idx) contract dist/sparq_dist.py's gossip uses. The payload
    is gathered per tile from the dense q (top_k over |q| covers the exact-k
    support; unused slots hold zero values), so scatter(vals, idx) into a
    zeroed padded buffer reconstructs q exactly even under threshold ties."""
    lw = resolve_lowering(lowering, interpret)
    xb, d, n = _to_blocks(flat)
    k_b = max(1, -(-k // n))
    q, _ = sign_topk_q(xb, k_b, lowering=lw)
    # compact payload per tile: |support| <= k_b (exact-k selection), so the
    # tile-local top_k index set contains the whole support; gathering VALUES
    # from q keeps zeros in surplus slots -> scatter is lossless
    _, idx_loc = jax.lax.top_k(jnp.abs(q.astype(jnp.float32)), k_b)
    vals = jnp.take_along_axis(q, idx_loc, axis=1)              # (n, k_b)
    gidx = jnp.arange(n, dtype=jnp.int32)[:, None] * BLOCK + idx_loc
    qf = q.reshape(-1)[:d]
    return qf, vals.reshape(-1), gidx.reshape(-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k_b", "interpret", "lowering"))
def trigger_compress_update(x_half: jax.Array, x_hat: jax.Array,
                            threshold: jax.Array, k_b: int,
                            interpret: Optional[bool] = None,
                            lowering: Optional[str] = None
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full fused SPARQ sync compute for one flat shard:

    trig = [||x_half - x_hat||^2 > threshold];  q = trig * SignTopK_b(diff);
    x_hat_new = x_hat + q.    Returns (q, x_hat_new, trig)."""
    lw = resolve_lowering(lowering, interpret)
    xh, d, n = _to_blocks(x_half)
    xe, _, _ = _to_blocks(x_hat)
    diff = (x_half - x_hat).astype(jnp.float32)
    trig = (jnp.sum(diff * diff) > threshold).astype(jnp.float32)
    q, xe_new, _ = sign_topk_blocks(xh, xe, trig, k_b, lowering=lw)
    return (q.reshape(-1)[:d], xe_new.reshape(-1)[:d], trig)


@functools.partial(jax.jit, static_argnames=("k_b", "interpret", "lowering"))
def sign_topk_ensemble(diff: jax.Array, k_b: int,
                       interpret: Optional[bool] = None,
                       lowering: Optional[str] = None) -> jax.Array:
    """ONE fused SignTopK dispatch over a whole node ensemble.

    diff: (n_nodes, d) — one row per node's flat (already trigger-gated or
    ungated) parameter difference. Each row is padded to nb*BLOCK tiles (nb
    rounded up so the stacked (n_nodes*nb, BLOCK) grid divides BLOCK_ROWS)
    and every tile is compressed in a single q-only kernel call; the
    caller applies any per-node trigger gate outside (q is linear in the
    gate). Zero-padded tail tiles emit q == 0 by the exact-k zero-lane rule.
    Returns q: (n_nodes, d), same dtype as diff."""
    lw = resolve_lowering(lowering, interpret)
    n, d = diff.shape
    nb = max(1, -(-d // BLOCK))
    rows = min(BLOCK_ROWS, n * nb)
    while (n * nb) % rows:
        nb += 1  # grow the per-node tile count until the grid divides
        rows = min(BLOCK_ROWS, n * nb)
    xb = jnp.pad(diff, ((0, 0), (0, nb * BLOCK - d))).reshape(n * nb, BLOCK)
    q, _ = sign_topk_q(xb, k_b, lowering=lw)
    return q.reshape(n, nb * BLOCK)[:, :d]


@functools.partial(jax.jit, static_argnames=("s", "interpret", "lowering"))
def qsgd(flat: jax.Array, key: jax.Array, s: int = 16,
         interpret: Optional[bool] = None,
         lowering: Optional[str] = None) -> jax.Array:
    """Blockwise QSGD quantization of a flat vector."""
    lw = resolve_lowering(lowering, interpret)
    xb, d, n = _to_blocks(flat)
    u = jax.random.uniform(key, xb.shape, dtype=jnp.float32)
    out = qsgd_blocks(xb, u, s=s, lowering=lw)
    return out.reshape(-1)[:d]
