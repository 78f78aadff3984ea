"""Attention: GQA (optional QKV-bias / qk-norm / sliding window / partial RoPE),
memory-linear chunked ("flash-style") attention for train/prefill, cached decode,
and DeepSeek-V3 MLA (latent attention) with the absorbed decode formulation.

Caches carry absolute positions so full-window and sliding-window (ring-buffer)
decode share one code path.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import (apply_rope, dense_init, rms_norm_vec,
                                 yarn_frequencies, yarn_mscale,
                                 yarn_score_mscale)

Params = Dict[str, jax.Array]

NEG_INF = -1e30


# ------------------------------------------------------------------ GQA params

def init_attention(cfg: ModelConfig, key) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dt),
        "wk": dense_init(ks[1], (d, kv * hd), dt),
        "wv": dense_init(ks[2], (d, kv * hd), dt),
        "wo": dense_init(ks[3], (h * hd, d), dt, scale=0.02 / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((kv * hd,), dt)
        p["bv"] = jnp.zeros((kv * hd,), dt)
    return p


def _qkv(cfg: ModelConfig, p: Params, x: jax.Array):
    cd = jnp.dtype(cfg.compute_dtype)
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = x @ p["wq"].astype(cd)
    k = x @ p["wk"].astype(cd)
    v = x @ p["wv"].astype(cd)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q, k = rms_norm_vec(q), rms_norm_vec(k)
    return q, k, v


# ------------------------------------------------------- chunked causal attention

def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_pos: jax.Array, k_pos: jax.Array,
                      window: Optional[int] = None,
                      q_chunk: int = 1024, k_chunk: int = 2048,
                      score_dtype=jnp.bfloat16,
                      scale: Optional[float] = None) -> jax.Array:
    """Memory-linear causal attention (flash-style running softmax).

    q: (B, Sq, H, hd); k: (B, Sk, Hkv, hd); v: (B, Sk, Hkv, hdv).
    GQA: H must be a multiple of Hkv. Mask: k_pos <= q_pos (< window back).
    ``scale`` multiplies the scores (default 1/sqrt(hd)).
    Returns (B, Sq, H, hdv) in q.dtype.

    Scores and softmax weights are carried in `score_dtype` (bf16) with f32
    row statistics and f32 output accumulation — the FA2 convention. §Perf:
    fp32 score tensors were the single largest HBM-traffic term for 128-head
    training; bf16 halves it. Chunk sizes trade VMEM for fewer accumulator
    materializations in the scan carry.
    """
    b, sq, h, hd = q.shape
    _, sk, hkv, hdv = v.shape
    g = h // hkv

    def _divisor_chunk(s, target):
        c = min(target, s)
        while s % c:
            c -= 1
        return c

    qc = _divisor_chunk(sq, q_chunk)
    kc = _divisor_chunk(sk, k_chunk)
    nq, nk = sq // qc, sk // kc
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(b, nq, qc, hkv, g, hd)
    kg = k.reshape(b, nk, kc, hkv, hd)
    vg = v.reshape(b, nk, kc, hkv, hdv)
    qp = q_pos.reshape(nq, qc)
    kp = k_pos.reshape(nk, kc)

    def one_q_chunk(qi, q_blk, qp_blk):
        # q_blk: (b, qc, hkv, g, hd)
        m0 = jnp.full((b, qc, hkv, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, qc, hkv, g), jnp.float32)
        a0 = jnp.zeros((b, qc, hkv, g, hdv), jnp.float32)

        def kv_step(carry, inp):
            m, l, acc = carry
            k_blk, v_blk, kp_blk = inp
            neg = jnp.asarray(-3e38 if score_dtype == jnp.bfloat16 else NEG_INF,
                              score_dtype)
            s = jnp.einsum("bqhgd,bkhd->bqhgk", q_blk.astype(score_dtype),
                           k_blk.astype(score_dtype),
                           preferred_element_type=score_dtype) * \
                jnp.asarray(scale, score_dtype)
            mask = kp_blk[None, None, None, None, :] <= qp_blk[None, :, None, None, None]
            mask = jnp.logical_and(mask, kp_blk[None, None, None, None, :] >= 0)
            if window is not None:
                mask = jnp.logical_and(
                    mask, kp_blk[None, None, None, None, :]
                    > qp_blk[None, :, None, None, None] - window)
            s = jnp.where(mask, s, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1).astype(jnp.float32))
            p = jnp.exp(s - m_new[..., None].astype(score_dtype))  # score_dtype
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1, dtype=jnp.float32)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p, v_blk.astype(score_dtype),
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.moveaxis(kg, 1, 0), jnp.moveaxis(vg, 1, 0), kp))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out  # (b, qc, hkv, g, hdv)

    outs = jax.lax.map(lambda args: one_q_chunk(*args),
                       (jnp.arange(nq), jnp.moveaxis(qg, 1, 0), qp))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, hdv)
    return out.astype(q.dtype)


# ------------------------------------------------------------------ GQA forward

def causal_parts_attention(cfg: ModelConfig, q, k, v, positions,
                           scale: Optional[float] = None):
    """Causal attention in P query parts, part i attending only its kv prefix
    [0, (i+1)S/P) — cuts the quadratic term to ~(P+1)/2P of full S^2
    (EXPERIMENTS.md §Perf: beyond-paper prefill compute optimization).
    Falls back to one part when S doesn't split."""
    P = cfg.causal_parts
    b, s, h, hd = q.shape
    if P <= 1 or s % P or s // P < 128:
        return chunked_attention(q, k, v, positions, positions,
                                 window=cfg.sliding_window, scale=scale)
    part = s // P
    outs = []
    for i in range(P):
        q_i = q[:, i * part:(i + 1) * part]
        kv_end = (i + 1) * part
        outs.append(chunked_attention(
            q_i, k[:, :kv_end], v[:, :kv_end],
            positions[i * part:(i + 1) * part], positions[:kv_end],
            window=cfg.sliding_window, scale=scale))
    return jnp.concatenate(outs, axis=1)


def attention_forward(cfg: ModelConfig, p: Params, x: jax.Array,
                      positions: jax.Array) -> jax.Array:
    """Training/prefill path. x: (B, S, D); positions: (S,)."""
    b, s, d = x.shape
    q, k, v = _qkv(cfg, p, x)
    q = apply_rope(q, positions[None, :], cfg.rope_pct, cfg.rope_theta)
    k = apply_rope(k, positions[None, :], cfg.rope_pct, cfg.rope_theta)
    out = causal_parts_attention(cfg, q, k, v, positions)
    cd = jnp.dtype(cfg.compute_dtype)
    return out.reshape(b, s, -1) @ p["wo"].astype(cd)


# ------------------------------------------------------------------ KV cache

def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int,
                  n_layers: Optional[int] = None) -> Params:
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    L = cfg.n_layers if n_layers is None else n_layers
    cd = jnp.dtype(cfg.compute_dtype)
    return {
        "k": jnp.zeros((L, batch, cache_len, kv, hd), cd),
        "v": jnp.zeros((L, batch, cache_len, kv, hd), cd),
        "pos": jnp.full((L, cache_len), -1, jnp.int32),
    }


def decode_attention(cfg: ModelConfig, p: Params, x: jax.Array,
                     cache_k: jax.Array, cache_v: jax.Array,
                     cache_pos: jax.Array, pos: jax.Array
                     ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array, jax.Array]]:
    """One-token decode. x: (B, 1, D); cache_k/v: (B, C, Hkv, hd); cache_pos: (C,).

    pos: scalar int32 absolute position of the new token. Sliding window uses the
    ring-buffer slot pos % C; full attention uses slot pos (C == max_len).
    """
    b, _, d = x.shape
    c = cache_k.shape[1]
    q, k, v = _qkv(cfg, p, x)
    pos_arr = jnp.full((1,), pos, jnp.int32)
    q = apply_rope(q, pos_arr[None, :], cfg.rope_pct, cfg.rope_theta)
    k = apply_rope(k, pos_arr[None, :], cfg.rope_pct, cfg.rope_theta)
    slot = pos % c
    cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))
    cache_pos = jax.lax.dynamic_update_slice(cache_pos, pos_arr, (slot,))

    h, kv_h, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = h // kv_h
    qg = q.reshape(b, kv_h, g, hd)
    s = jnp.einsum("bhgd,bchd->bhgc", qg.astype(jnp.float32),
                   cache_k.astype(jnp.float32)) / math.sqrt(hd)
    valid = jnp.logical_and(cache_pos >= 0, cache_pos <= pos)
    if cfg.sliding_window is not None:
        valid = jnp.logical_and(valid, cache_pos > pos - cfg.sliding_window)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgc,bchd->bhgd", a, cache_v.astype(jnp.float32))
    cd = jnp.dtype(cfg.compute_dtype)
    o = o.reshape(b, 1, h * hd).astype(cd) @ p["wo"].astype(cd)
    return o, (cache_k, cache_v, cache_pos)


# ------------------------------------------------------------------ MLA (DeepSeek-V2/V3)

def init_mla(cfg: ModelConfig, key) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    r_kv, dr, dn, dv = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    p = {
        "w_dkv": dense_init(ks[0], (d, r_kv), dt),            # down: latent c_kv
        "w_kr": dense_init(ks[1], (d, dr), dt),               # shared rope key
        "w_uk": dense_init(ks[2], (r_kv, h * dn), dt),        # up: per-head k_nope
        "w_uv": dense_init(ks[3], (r_kv, h * dv), dt),        # up: per-head v
        "wo": dense_init(ks[4], (h * dv, d), dt,
                         scale=0.02 / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.q_lora_rank:
        p["w_dq"] = dense_init(ks[5], (d, cfg.q_lora_rank), dt)
        p["w_uq"] = dense_init(ks[6], (cfg.q_lora_rank, h * (dn + dr)), dt)
    else:
        p["w_q"] = dense_init(ks[7], (d, h * (dn + dr)), dt)
    p["kv_norm"] = jnp.ones((r_kv,), dt)
    return p


def _mla_latent(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    """c_kv = x W_dkv, RMS-normed (kv_a_layernorm)."""
    cd = jnp.dtype(cfg.compute_dtype)
    c_kv = x @ p["w_dkv"].astype(cd)
    cf = c_kv.astype(jnp.float32)
    cf = cf * jax.lax.rsqrt(jnp.mean(cf * cf, -1, keepdims=True)
                            + cfg.norm_eps)
    return (cf * p["kv_norm"].astype(jnp.float32)).astype(cd)


def _mla_rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    """RoPE on the MLA rope dims, YaRN-scaled where the config says."""
    m = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return apply_rope(x, positions, 1.0, cfg.rope_theta,
                      inv=yarn_frequencies(cfg, cfg.qk_rope_dim), mscale=m)


def _mla_q(cfg: ModelConfig, p: Params, x: jax.Array):
    cd = jnp.dtype(cfg.compute_dtype)
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        q = (x @ p["w_dq"].astype(cd)) @ p["w_uq"].astype(cd)
    else:
        q = x @ p["w_q"].astype(cd)
    q = q.reshape(b, s, h, dn + dr)
    return q[..., :dn], q[..., dn:]


def mla_forward(cfg: ModelConfig, p: Params, x: jax.Array,
                positions: jax.Array) -> jax.Array:
    """Training/prefill: expand the latent into per-head K/V and run chunked attn."""
    cd = jnp.dtype(cfg.compute_dtype)
    b, s, _ = x.shape
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(cfg, p, x)
    q_rope = _mla_rope(cfg, q_rope, positions[None, :])
    c_kv = _mla_latent(cfg, p, x)                                     # (B,S,r)
    k_rope = (x @ p["w_kr"].astype(cd)).reshape(b, s, 1, dr)
    k_rope = _mla_rope(cfg, k_rope, positions[None, :])
    k_nope = (c_kv @ p["w_uk"].astype(cd)).reshape(b, s, h, dn)
    v = (c_kv @ p["w_uv"].astype(cd)).reshape(b, s, h, dv)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))], -1)
    m2 = yarn_score_mscale(cfg)
    out = causal_parts_attention(
        cfg, q, k, v, positions,
        scale=None if m2 == 1.0 else m2 / math.sqrt(dn + dr))
    return out.reshape(b, s, -1) @ p["wo"].astype(cd)


def init_mla_cache(cfg: ModelConfig, batch: int, cache_len: int,
                   n_layers: Optional[int] = None) -> Params:
    L = cfg.n_layers if n_layers is None else n_layers
    cd = jnp.dtype(cfg.compute_dtype)
    return {
        "ckv": jnp.zeros((L, batch, cache_len, cfg.kv_lora_rank), cd),
        "kr": jnp.zeros((L, batch, cache_len, cfg.qk_rope_dim), cd),
        "pos": jnp.full((L, cache_len), -1, jnp.int32),
    }


def mla_decode(cfg: ModelConfig, p: Params, x: jax.Array,
               cache_ckv: jax.Array, cache_kr: jax.Array, cache_pos: jax.Array,
               pos: jax.Array):
    """Absorbed MLA decode: attention runs in the latent (r_kv) space; the per-head
    up-projections are folded into q and the output — the cache stays compressed.
    x: (B, 1, D); cache_ckv: (B, C, r); cache_kr: (B, C, dr)."""
    cd = jnp.dtype(cfg.compute_dtype)
    b, _, _ = x.shape
    c = cache_ckv.shape[1]
    h, dn, dr, dv, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    q_nope, q_rope = _mla_q(cfg, p, x)                        # (B,1,H,dn/dr)
    pos_arr = jnp.full((1,), pos, jnp.int32)
    q_rope = _mla_rope(cfg, q_rope, pos_arr[None, :])
    ckv_new = _mla_latent(cfg, p, x)                          # (B,1,r)
    kr_new = (x @ p["w_kr"].astype(cd)).reshape(b, 1, 1, dr)
    kr_new = _mla_rope(cfg, kr_new, pos_arr[None, :])
    slot = pos % c
    cache_ckv = jax.lax.dynamic_update_slice(cache_ckv, ckv_new, (0, slot, 0))
    cache_kr = jax.lax.dynamic_update_slice(cache_kr, kr_new[:, :, 0, :],
                                            (0, slot, 0))
    cache_pos = jax.lax.dynamic_update_slice(cache_pos, pos_arr, (slot,))

    w_uk = p["w_uk"].astype(cd).reshape(r, h, dn)
    # absorb: q_lat[b,h,r] = sum_dn q_nope[b,h,dn] * w_uk[r,h,dn]
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
    s_lat = jnp.einsum("bhr,bcr->bhc", q_lat.astype(jnp.float32),
                       cache_ckv.astype(jnp.float32))
    s_rope = jnp.einsum("bhd,bcd->bhc", q_rope[:, 0].astype(jnp.float32),
                        cache_kr.astype(jnp.float32))
    s = (s_lat + s_rope) / math.sqrt(dn + dr)
    m2 = yarn_score_mscale(cfg)
    if m2 != 1.0:
        s = s * m2
    valid = jnp.logical_and(cache_pos >= 0, cache_pos <= pos)
    if cfg.sliding_window is not None:
        valid = jnp.logical_and(valid, cache_pos > pos - cfg.sliding_window)
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhc,bcr->bhr", a, cache_ckv.astype(jnp.float32))  # (B,H,r)
    w_uv = p["w_uv"].astype(cd).reshape(r, h, dv)
    o = jnp.einsum("bhr,rhd->bhd", o_lat.astype(cd), w_uv)
    o = o.reshape(b, 1, h * dv) @ p["wo"].astype(cd)
    return o, (cache_ckv, cache_kr, cache_pos)
