"""Plain reference of the DeepSeek-V2 block (DeepSeek-V2-Lite): pre-RMSNorm,
multi-head latent attention (MLA) with YaRN RoPE, then a dense SwiGLU FFN in
the first layers and a mixture of experts after them, final RMSNorm, untied
output head, mean next-token cross-entropy plus the router's balance loss.

Written from the published equations (arXiv:2405.04434 and the model's
``modeling_deepseek.py``), in float32 at the highest matmul precision, with
full softmax attention (queries in blocks, each block over all keys), no
kernels, no cache. It imports nothing of the program.

* MLA without query compression: q = x W_q split into a 128-wide part and
  a 64-wide RoPE part; the latent c = RMSNorm(x W_dkv) (kv_a_layernorm,
  512 wide) gives per-head keys c W_uk and values c W_uv; one shared 64-wide
  RoPE key x W_kr. RoPE over interleaved pairs, with YaRN's inverse
  frequencies (a linear ramp between the correction dims of beta_fast and
  beta_slow) and cos / sin scaled by mscale / mscale_all_dim; the softmax
  scale is 192^-0.5 times mscale_all_dim's factor squared.
* Mixture of experts: a float32 softmax over all the router's experts, the
  greedy top 6, gates not renormalized (norm_topk_prob false), times
  routed_scaling_factor; the experts held here (ids 0 to n_routed_experts -
  1 of the router's width) each computed densely over every token and
  weighted by its gate where it was chosen, zero elsewhere (no dispatch, no
  sort, nothing dropped); two shared experts fused into one SwiGLU of twice
  the width, on every token. Balance loss: DeepSeek-V2's sequence-wise
  ``seq_aux`` one over all router experts, times aux_loss_alpha, summed over
  the expert layers.

The weights are the ones the program's initialisation draws from the same
seed (truncated normal, 0.02; attention, expert and shared-expert output
projections scaled by 1/sqrt(2 L); norm scales one), in the program's leaf
layout, so the flat row is comparable element by element.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.common import einsum, mean_ce, rms_norm, tn

Q_BLOCK = 1024     # query positions per attention block


def sizes(cfg: dict) -> dict:
    rs = cfg["rope_scaling"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "E": cfg["published"]["n_routed_experts"],
            "held": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "L": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"],
            "V": cfg["vocab_size"], "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]), "yarn": rs,
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "scaling": float(cfg["routed_scaling_factor"]),
            "alpha": float(cfg["aux_loss_alpha"])}


def init_params(cfg: dict, key) -> dict:
    z = sizes(cfg)
    d, h, r, dn, dr, dv = (z[k] for k in ("d", "h", "r", "dn", "dr", "dv"))
    L = z["L"]
    out_scale = 0.02 / math.sqrt(2 * L)
    keys = jax.random.split(key, 8)

    def attn(k):
        ks = jax.random.split(k, 8)
        return {"w_dkv": tn(ks[0], (d, r)), "w_kr": tn(ks[1], (d, dr)),
                "w_uk": tn(ks[2], (r, h * dn)), "w_uv": tn(ks[3], (r, h * dv)),
                "wo": tn(ks[4], (h * dv, d), out_scale),
                "w_q": tn(ks[7], (d, h * (dn + dr))),
                "kv_norm": jnp.ones((r,))}

    def block(bk, moe: bool):
        ks = jax.random.split(bk, 6)
        p = {"norm1": {"scale": jnp.ones((d,))},
             "norm2": {"scale": jnp.ones((d,))}, "attn": attn(ks[2])}
        if moe:
            km = jax.random.split(ks[3], 7)
            n, fe, fs = z["held"], z["fe"], z["fs"]
            p["moe"] = {
                "router": tn(km[0], (d, z["E"])),
                "w_gate": tn(km[1], (n, d, fe)), "w_in": tn(km[2], (n, d, fe)),
                "w_out": tn(km[3], (n, fe, d), out_scale),
                "shared_gate": tn(km[4], (d, fs)),
                "shared_in": tn(km[5], (d, fs)),
                "shared_out": tn(km[6], (fs, d), out_scale)}
        else:
            km = jax.random.split(ks[3], 3)
            p["mlp"] = {"w_in": tn(km[0], (d, z["f"])),
                        "w_gate": tn(km[1], (d, z["f"])),
                        "w_out": tn(km[2], (z["f"], d))}
        return p

    ek = jax.random.split(keys[0])
    params = {"embed": {"embedding": tn(ek[0], (z["V"], d)),
                        "lm_head": tn(ek[1], (d, z["V"]))},
              "final_norm": {"scale": jnp.ones((d,))}}
    for si, (moe, n) in enumerate(((False, z["dense"]),
                                   (True, L - z["dense"]))):
        bkeys = jax.random.split(jax.random.fold_in(keys[2], si), n)
        params[f"seg{si}"] = jax.vmap(lambda k, m=moe: block(k, m))(bkeys)
    return params


def _yarn(z: dict):
    """YaRN's inverse frequencies of the RoPE dims, and the factors on
    cos / sin and on the softmax scale."""
    rs, dim, base = z["yarn"], z["dr"], z["theta"]
    factor = float(rs["factor"])
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / factor

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    inv = inter * (1.0 - mask) + extra * mask

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    cos_sin = mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"])
    score = mscale(rs["mscale_all_dim"]) ** 2 if rs["mscale_all_dim"] else 1.0
    return inv, cos_sin, score


def _rope(x, inv, m: float):
    """x: (B, S, H, Q); rotates interleaved pairs by position."""
    s = x.shape[1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = m * jnp.cos(ang)[:, None, :], m * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _mla(z: dict, a: dict, y, prec: str):
    b, s, _ = y.shape
    h, dn, dr, dv = z["h"], z["dn"], z["dr"], z["dv"]
    inv, m, score = _yarn(z)
    q = einsum("bsd,de->bse", y, a["w_q"], prec).reshape(b, s, h, dn + dr)
    c = rms_norm(einsum("bsd,dr->bsr", y, a["w_dkv"], prec), a["kv_norm"],
                 z["eps"])
    k_nope = einsum("bsr,re->bse", c, a["w_uk"], prec).reshape(b, s, h, dn)
    v = einsum("bsr,re->bse", c, a["w_uv"], prec).reshape(b, s, h, dv)
    k_pe = _rope(einsum("bsd,de->bse", y, a["w_kr"], prec)[:, :, None], inv, m)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv, m)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, h, dr))], -1)
    scale = score / math.sqrt(dn + dr)
    qb = min(Q_BLOCK, s)

    @jax.checkpoint
    def block(args):
        i, qi = args
        sc = einsum("bqhe,bkhe->bhqk", qi, k, prec) * scale
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(s)[None]
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return einsum("bhqk,bkhe->bqhe", pr, v, prec)

    qs = jnp.moveaxis(q.reshape(b, s // qb, qb, h, dn + dr), 1, 0)
    o = jax.lax.map(block, (jnp.arange(s // qb), qs))
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, h * dv)
    return einsum("bse,ed->bsd", o, a["wo"], prec)


def _swiglu(y, w_gate, w_in, w_out, prec: str):
    g = jax.nn.silu(einsum("bsd,df->bsf", y, w_gate, prec))
    u = einsum("bsd,df->bsf", y, w_in, prec)
    return einsum("bsf,fd->bsd", g * u, w_out, prec)


def _moe(z: dict, p: dict, y, prec: str):
    """-> (the held experts' and the shared experts' output, balance loss)."""
    b, s, _ = y.shape
    E, k = z["E"], z["k"]
    probs = jax.nn.softmax(einsum("bsd,de->bse", y, p["router"], prec), -1)
    top, idx = jax.lax.top_k(probs, k)                      # (B, S, k)
    if z["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * z["scaling"]
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # (B, S, k, E)
    gate = jnp.sum(top[..., None] * chosen, 2)              # (B, S, E)
    out = _swiglu(y, p["shared_gate"], p["shared_in"], p["shared_out"], prec)
    for e in range(z["held"]):
        out = out + gate[..., e:e + 1] * _swiglu(
            y, p["w_gate"][e], p["w_in"][e], p["w_out"][e], prec)
    f = jnp.sum(chosen, axis=(1, 2)) * E / (s * k)           # (B, E)
    aux = jnp.mean(jnp.sum(f * jnp.mean(probs, 1), -1))
    return out, aux


def loss(cfg: dict, params: dict, batch: dict, prec: str = "f32"):
    """Mean next-token cross-entropy of one node's rows plus aux_loss_alpha
    times the expert layers' balance losses."""
    z = sizes(cfg)
    eps = z["eps"]
    x = params["embed"]["embedding"][batch["tokens"]]

    def attn_part(x, p):
        return x + _mla(z, p["attn"], rms_norm(x, p["norm1"]["scale"], eps),
                        prec)

    @jax.checkpoint
    def dense(x, p):
        x = attn_part(x, p)
        m = p["mlp"]
        y = rms_norm(x, p["norm2"]["scale"], eps)
        return x + _swiglu(y, m["w_gate"], m["w_in"], m["w_out"], prec), None

    @jax.checkpoint
    def moe(x, p):
        x = attn_part(x, p)
        y, aux = _moe(z, p["moe"], rms_norm(x, p["norm2"]["scale"], eps),
                      prec)
        return x + y, aux

    x, _ = jax.lax.scan(dense, x, params["seg0"])
    x, auxs = jax.lax.scan(moe, x, params["seg1"])
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    ce = mean_ce(x, params["embed"]["lm_head"], batch["labels"], prec)
    return ce + z["alpha"] * jnp.sum(auxs)


def n_params(cfg: dict) -> int:
    """Every parameter the model holds here, counted from the sizes."""
    z = sizes(cfg)
    d, h, r, dn, dr, dv = (z[k] for k in ("d", "h", "r", "dn", "dr", "dv"))
    attn = d * r + d * dr + r * h * dn + r * h * dv + h * dv * d \
        + d * h * (dn + dr) + r
    dense = attn + 3 * d * z["f"] + 2 * d
    moe = (attn + d * z["E"] + 3 * z["held"] * d * z["fe"]
           + 3 * d * z["fs"] + 2 * d)
    return (z["dense"] * dense + (z["L"] - z["dense"]) * moe + d
            + 2 * z["V"] * d)


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token (forward and backward): 6 N_active
    plus attention. N_active counts every parameter but the input embedding,
    with the held experts' weights at the share of a token they take in
    expectation (top_k of the router's width, held of them: 6 x 8 / 64 =
    0.75 expert-rows a token); attention after PaLM appendix B with the
    query-key width 192 and the value width 128: 6 L H (Qk + Qv) T."""
    z = sizes(cfg)
    experts = (z["L"] - z["dense"]) * 3 * z["held"] * z["d"] * z["fe"]
    share = z["k"] / z["E"]
    n = n_params(cfg) - z["V"] * z["d"] - experts + share * experts
    return 6.0 * n + 6.0 * z["L"] * z["h"] * (
        z["dn"] + z["dr"] + z["dv"]) * seq_len
