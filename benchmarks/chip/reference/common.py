"""Pieces shared by the plain references: the weights' key of a seed, the
seeded initial weights, the matmul with its precision, norms and the streamed
cross-entropy.

Everything is float32 at ``Precision.HIGHEST``. ``prec`` names the precision
of the matmuls: ``f32`` for the reference, ``fp8`` for its control, the way
fp8 training runs a matmul: in the forward pass each operand is scaled per
tensor to the float8_e4m3fn range, rounded to it and back; in the backward
pass the output's cotangent is so rounded to float8_e5m2, and the two
gradient matmuls take it with the rounded operands.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0     # largest float8_e4m3fn (forward operands)
E5M2_MAX = 57344.0   # largest float8_e5m2 (cotangents)
INIT_SCALE = 0.02    # the initial weights' standard scale
CE_CHUNK = 256       # sequence positions per streamed cross-entropy block


def model_key(seed: int):
    """The weights' PRNG key of a seed of up to 64 bits (PRNGKey alone keeps
    only the low 32). The harness seeds the program's initialisation with
    it too."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              (seed // 2 ** 32) % 2 ** 32)


def _round(x, dtype, top: float):
    """x scaled per tensor to a float8 type's range, rounded to it and
    back."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / top, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _fp8_einsum(spec: str):
    def plain(a, b):
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    @jax.custom_vjp
    def f(a, b):
        return fwd(a, b)[0]

    def fwd(a, b):
        ra = _round(a, jnp.float8_e4m3fn, E4M3_MAX)
        rb = _round(b, jnp.float8_e4m3fn, E4M3_MAX)
        return plain(ra, rb), (ra, rb)

    def bwd(res, g):
        return jax.vjp(plain, *res)[1](_round(g, jnp.float8_e5m2, E5M2_MAX))

    f.defvjp(fwd, bwd)
    return f


def einsum(spec: str, a, b, prec: str):
    if prec == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if prec != "fp8":
        raise ValueError(f"unknown precision {prec!r}")
    return _fp8_einsum(spec)(a, b)


def tn(key, shape, scale: float = INIT_SCALE):
    """A seeded weight: ``scale`` times a normal truncated at two sigma."""
    return scale * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                               jnp.float32)


def rms_norm(x, scale=None, eps: float = 1e-5):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if scale is None else y * scale


def embed_params(key, vocab: int, d: int, tied: bool) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"embedding": tn(k1, (vocab, d))}
    if not tied:
        p["lm_head"] = tn(k2, (d, vocab))
    return p


def head_matrix(embed: dict):
    return embed["embedding"].T if "lm_head" not in embed else embed["lm_head"]


def mean_ce(h, w_head, labels, prec: str):
    """Mean next-token cross-entropy of final hidden states h (B, S, d)
    against labels (B, S), streamed over blocks of positions so that the
    (B, S, V) logits never exist at once."""
    b, s, d = h.shape
    c = min(CE_CHUNK, s)
    nc = s // c
    hc = jnp.moveaxis(h[:, :nc * c].reshape(b, nc, c, d), 1, 0)
    lc = jnp.moveaxis(labels[:, :nc * c].reshape(b, nc, c), 1, 0)

    @jax.checkpoint
    def block(args):
        hb, lb = args
        logits = einsum("bcd,dv->bcv", hb, w_head, prec)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
        return jnp.sum(lse - tgt)

    return jnp.sum(jax.lax.map(block, (hc, lc))) / (b * nc * c)
