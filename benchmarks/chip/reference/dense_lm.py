"""Plain reference of a dense decoder LM with QKV bias (the Qwen1.5/Qwen2
block): pre-RMSNorm, multi-head causal attention with RoPE, SwiGLU MLP,
final RMSNorm, output head (tied or not), mean next-token cross-entropy.

Straightforward jnp in float32 at the highest matmul precision, full
softmax attention, no kernels, no cache. It imports nothing of the program.
It follows the program's stated departures from the source (the
configuration file lists them): RMSNorm eps 1e-5 and RoPE over interleaved
pairs.

The weights are the ones the program's initialisation draws from the same
seed (truncated normal, 0.02, the output projection scaled by
1/sqrt(2 L); norm scales one, biases zero), in the program's leaf layout,
so the flat row is comparable element by element.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.common import (einsum, embed_params, head_matrix, mean_ce,
                              rms_norm, tn)


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"], "q": d // h,
            "f": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"], "tied": bool(cfg["tie_word_embeddings"]),
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"])}


def init_params(cfg: dict, key) -> dict:
    z = sizes(cfg)
    d, h, kv, q, f, L = z["d"], z["h"], z["kv"], z["q"], z["f"], z["L"]
    keys = jax.random.split(key, 8)

    def block(bk):
        ks = jax.random.split(bk, 6)
        ka = jax.random.split(ks[2], 4)
        km = jax.random.split(ks[3], 3)
        return {
            "norm1": {"scale": jnp.ones((d,))},
            "norm2": {"scale": jnp.ones((d,))},
            "attn": {"wq": tn(ka[0], (d, h * q)), "wk": tn(ka[1], (d, kv * q)),
                     "wv": tn(ka[2], (d, kv * q)),
                     "wo": tn(ka[3], (h * q, d), 0.02 / math.sqrt(2 * L)),
                     "bq": jnp.zeros((h * q,)), "bk": jnp.zeros((kv * q,)),
                     "bv": jnp.zeros((kv * q,))},
            "mlp": {"w_in": tn(km[0], (d, f)), "w_gate": tn(km[1], (d, f)),
                    "w_out": tn(km[2], (f, d))},
        }

    bkeys = jax.random.split(jax.random.fold_in(keys[2], 0), L)
    return {"embed": embed_params(keys[0], z["V"], d, z["tied"]),
            "final_norm": {"scale": jnp.ones((d,))},
            "seg0": jax.vmap(block)(bkeys)}


def _rope(x, theta: float):
    """x: (B, S, H, Q); rotates interleaved pairs by position."""
    s, q = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, q, 2, dtype=jnp.float32) / q))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def loss(cfg: dict, params: dict, batch: dict, prec: str = "f32"):
    """Mean next-token cross-entropy of one node's rows."""
    z = sizes(cfg)
    h, kv, q, eps = z["h"], z["kv"], z["q"], z["eps"]
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    causal = jnp.tril(jnp.ones((s, s), bool))
    x = params["embed"]["embedding"][tokens]

    @jax.checkpoint
    def layer(x, p):
        a = p["attn"]
        y = rms_norm(x, p["norm1"]["scale"], eps)
        qh = (einsum("bsd,de->bse", y, a["wq"], prec) + a["bq"]
              ).reshape(b, s, h, q)
        kh = (einsum("bsd,de->bse", y, a["wk"], prec) + a["bk"]
              ).reshape(b, s, kv, q)
        vh = (einsum("bsd,de->bse", y, a["wv"], prec) + a["bv"]
              ).reshape(b, s, kv, q)
        qh, kh = _rope(qh, z["theta"]), _rope(kh, z["theta"])
        kh = jnp.repeat(kh, h // kv, axis=2)
        vh = jnp.repeat(vh, h // kv, axis=2)
        sc = einsum("bqhe,bkhe->bhqk", qh, kh, prec) / math.sqrt(q)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = einsum("bhqk,bkhe->bqhe", pr, vh, prec).reshape(b, s, h * q)
        x = x + einsum("bse,ed->bsd", o, a["wo"], prec)
        m = p["mlp"]
        y = rms_norm(x, p["norm2"]["scale"], eps)
        g = jax.nn.silu(einsum("bsd,df->bsf", y, m["w_gate"], prec))
        u = einsum("bsd,df->bsf", y, m["w_in"], prec)
        return x + einsum("bsf,fd->bsd", g * u, m["w_out"], prec), None

    x, _ = jax.lax.scan(layer, x, params["seg0"])
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    return mean_ce(x, head_matrix(params["embed"]), labels, prec)


def n_params(cfg: dict) -> int:
    """Every parameter the model holds, counted from the sizes."""
    z = sizes(cfg)
    d, h, kv, q, f, L, V = (z[k] for k in ("d", "h", "kv", "q", "f", "L",
                                           "V"))
    layer = (d * h * q + 2 * d * kv * q + h * q * d      # wq, wk, wv, wo
             + h * q + 2 * kv * q                        # QKV bias
             + 3 * d * f + 2 * d)                        # SwiGLU, 2 norms
    return L * layer + d + V * d * (1 if z["tied"] else 2)


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token (forward and backward), after PaLM
    appendix B: 6 N + 12 L H Q T, N without the input embedding but with
    the output head's matmul."""
    z = sizes(cfg)
    n = n_params(cfg) - z["V"] * z["d"] * (0 if z["tied"] else 1)
    return 6.0 * n + 12.0 * z["L"] * z["h"] * z["q"] * seq_len
