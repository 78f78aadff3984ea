"""Shared layers: norms, RoPE, MLPs, embeddings. Pure functions over dict pytrees."""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

Params = Dict[str, jax.Array]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, shape, dtype, scale: float = 0.02):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


# ---------------------------------------------------------------- norms

def init_norm(cfg: ModelConfig, key) -> Params:
    d = cfg.d_model
    p = {"scale": jnp.ones((d,), _dtype(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), _dtype(cfg))
    return p


def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array):
    eps = cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(xf * xf, -1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_vec(x: jax.Array, eps: float = 1e-6):
    """Scale-free RMS norm over the last dim (qk-norm uses per-head)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, -1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype)


# ---------------------------------------------------------------- rope

def rope_frequencies(head_dim: int, rope_pct: float, theta: float):
    rot = int(head_dim * rope_pct)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


# YaRN's correction range and original context, as DeepSeek-V2 and V3
# publish them (rope_scaling beta_fast, beta_slow,
# original_max_position_embeddings)
YARN_BETA_FAST = 32
YARN_BETA_SLOW = 1
YARN_ORIGINAL_MAX_POS = 4096


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(cfg: ModelConfig, dim: int):
    """YaRN's inverse frequencies over ``dim`` rotated dims (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``): the original frequencies above the
    correction range, those divided by the factor below it, a linear ramp
    between. Plain RoPE's where ``rope_factor`` is 1."""
    theta, factor = cfg.rope_theta, cfg.rope_factor
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if factor == 1.0:
        return extra

    def corr_dim(rotations):
        return (dim * math.log(YARN_ORIGINAL_MAX_POS
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(YARN_BETA_FAST)), 0)
    high = min(math.ceil(corr_dim(YARN_BETA_SLOW)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / factor * (1.0 - keep) + extra * keep


def yarn_score_mscale(cfg: ModelConfig) -> float:
    """YaRN's factor on the softmax scale head_dim**-0.5: mscale(all_dim)
    squared (1 without YaRN)."""
    m = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
         if cfg.rope_mscale_all_dim else 1.0)
    return m * m


def apply_rope(x: jax.Array, positions: jax.Array, rope_pct: float, theta: float,
               inv: Optional[jax.Array] = None, mscale: float = 1.0):
    """x: (..., S, H, hd); positions: (..., S) int32. ``inv``: inverse
    frequencies of the rotated dims (default: plain RoPE at ``theta``);
    ``mscale`` scales cos and sin."""
    hd = x.shape[-1]
    if inv is None:
        inv, rot = rope_frequencies(hd, rope_pct, theta)
    else:
        rot = 2 * inv.shape[0]
    if rot == 0:
        return x
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, rot/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------- mlp

def init_mlp(cfg: ModelConfig, key, d_ff: int = 0) -> Params:
    d, f = cfg.d_model, (d_ff or cfg.d_ff)
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    p = {"w_out": dense_init(ks[2], (f, d), dt)}
    if cfg.act == "swiglu":
        p["w_in"] = dense_init(ks[0], (d, f), dt)
        p["w_gate"] = dense_init(ks[1], (d, f), dt)
    else:
        p["w_in"] = dense_init(ks[0], (d, f), dt)
    return p


def apply_mlp(cfg: ModelConfig, p: Params, x: jax.Array):
    cd = jnp.dtype(cfg.compute_dtype)
    x = x.astype(cd)
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"].astype(cd)) * (x @ p["w_in"].astype(cd))
    elif cfg.act == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["w_in"].astype(cd)))
    else:
        h = jax.nn.gelu(x @ p["w_in"].astype(cd))
    return h @ p["w_out"].astype(cd)


# ---------------------------------------------------------------- embeddings

def init_embed(cfg: ModelConfig, key) -> Params:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {"embedding": dense_init(k1, (cfg.vocab_size, cfg.d_model), dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(k2, (cfg.d_model, cfg.vocab_size), dt)
    return p


def embed_tokens(cfg: ModelConfig, p: Params, tokens: jax.Array):
    cd = jnp.dtype(cfg.compute_dtype)
    return p["embedding"].astype(cd)[tokens]


def lm_logits(cfg: ModelConfig, p: Params, h: jax.Array):
    cd = jnp.dtype(cfg.compute_dtype)
    w = (p["embedding"].T if cfg.tie_embeddings else p["lm_head"]).astype(cd)
    return h @ w
