"""Plain reference of a Mamba-2 LM (arXiv:2405.21060): pre-RMSNorm SSD
blocks (input projection to z, x, B, C, dt; depthwise causal conv with SiLU
over x, B, C; softplus dt; the SSD map; D skip; gated RMSNorm; output
projection), final RMSNorm, output head, mean next-token cross-entropy.

The SSD map is computed in its quadratic ("attention") form of the paper's
section 3, straight from the definition

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < k <= t} dt_k A) dt_s x_s,

in float32 at the highest matmul precision, over blocks of heads so that it
fits; no chunking, no scan, no kernels. It imports nothing of the program.
The weights are the ones the program's initialisation draws from the same
seed, in the program's leaf layout.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.common import (einsum, embed_params, head_matrix, mean_ce,
                              rms_norm, tn)

HEAD_BLOCK = 8   # heads per block of the quadratic SSD map


def sizes(cfg: dict) -> dict:
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    p = cfg["headdim"]
    return {"d": d, "d_in": d_in, "h": d_in // p, "p": p,
            "g": cfg["ngroups"], "n": cfg["d_state"], "k": cfg["d_conv"],
            "L": cfg["n_layer"], "V": cfg["vocab_size"],
            "Q": cfg["chunk_size"], "tied": bool(cfg["tie_embeddings"])}


def init_params(cfg: dict, key) -> dict:
    z = sizes(cfg)
    d, d_in, h, g, n, k, L = (z[x] for x in ("d", "d_in", "h", "g", "n",
                                             "k", "L"))
    chans = d_in + 2 * g * n
    keys = jax.random.split(key, 8)

    def block(bk):
        ks = jax.random.split(jax.random.split(bk, 6)[1], 5)
        return {
            "norm": {"scale": jnp.ones((d,))},
            "ssm": {
                "w_in": tn(ks[0], (d, 2 * d_in + 2 * g * n + h)),
                "conv_w": tn(ks[1], (k, chans), 0.5),
                "conv_b": jnp.zeros((chans,)),
                "a_log": jnp.log(jnp.linspace(1.0, 16.0, h)),
                "d_skip": jnp.ones((h,)),
                "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 0.01))),
                "norm_scale": jnp.ones((d_in,)),
                "w_out": tn(ks[2], (d_in, d), 0.02 / math.sqrt(2 * L)),
            },
        }

    bkeys = jax.random.split(jax.random.fold_in(keys[2], 0), L)
    return {"embed": embed_params(keys[0], z["V"], d, z["tied"]),
            "final_norm": {"scale": jnp.ones((d,))},
            "seg0": jax.vmap(block)(bkeys)}


def _ssd(x, dt, a, bm, cm, prec: str):
    """x: (B, T, H, P); dt: (B, T, H); a: (H,) negative; bm, cm: (B, T, G, N).
    Returns y (B, T, H, P) by the quadratic form, over blocks of heads."""
    b, t, h, p = x.shape
    g = bm.shape[2]
    r = h // g
    hb = min(HEAD_BLOCK, h)
    causal = jnp.tril(jnp.ones((t, t), bool))
    cb = einsum("btgn,bsgn->bgts", cm, bm, prec)               # (B, G, T, T)

    @jax.checkpoint
    def heads(i):
        sl = lambda v: jax.lax.dynamic_slice_in_dim(v, i * hb, hb, axis=2)
        dth = sl(dt)                                           # (B, T, hb)
        cum = jnp.cumsum(dth * jax.lax.dynamic_slice_in_dim(a, i * hb, hb),
                         axis=1)
        seg = cum[:, :, None, :] - cum[:, None, :, :]          # (B, T, S, hb)
        decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        grp = (i * hb + jnp.arange(hb)) // r
        m = jnp.moveaxis(cb[:, grp], 1, -1) * decay            # (B, T, S, hb)
        xs = sl(x) * dth[..., None]                            # (B, S, hb, P)
        return einsum("btsh,bshp->bthp", m, xs, prec)

    ys = jax.lax.map(heads, jnp.arange(h // hb))               # (nb,B,T,hb,P)
    return jnp.moveaxis(ys, 0, 2).reshape(b, t, h, p)


def loss(cfg: dict, params: dict, batch: dict, prec: str = "f32"):
    """Mean next-token cross-entropy of one node's rows."""
    z = sizes(cfg)
    d_in, h, p, g, n, k = (z[x] for x in ("d_in", "h", "p", "g", "n", "k"))
    tokens, labels = batch["tokens"], batch["labels"]
    b, t = tokens.shape
    x = params["embed"]["embedding"][tokens]

    @jax.checkpoint
    def layer(x, prm):
        s = prm["ssm"]
        y = rms_norm(x, prm["norm"]["scale"], 1e-5)
        zx = einsum("btd,de->bte", y, s["w_in"], prec)
        zg, xbc, dt_raw = jnp.split(zx, [d_in, 2 * d_in + 2 * g * n], axis=-1)
        pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + t] * s["conv_w"][i] for i in range(k))
        xbc = jax.nn.silu(conv + s["conv_b"])
        xs, bm, cm = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
        xs = xs.reshape(b, t, h, p)
        dt = jax.nn.softplus(dt_raw + s["dt_bias"])
        yh = _ssd(xs, dt, -jnp.exp(s["a_log"]), bm.reshape(b, t, g, n),
                  cm.reshape(b, t, g, n), prec)
        yh = yh + xs * s["d_skip"][:, None]
        yh = yh.reshape(b, t, d_in) * jax.nn.silu(zg)
        yh = rms_norm(yh, None, 1e-6) * s["norm_scale"]
        return x + einsum("bte,ed->btd", yh, s["w_out"], prec), None

    x, _ = jax.lax.scan(layer, x, params["seg0"])
    x = rms_norm(x, params["final_norm"]["scale"], 1e-5)
    return mean_ce(x, head_matrix(params["embed"]), labels, prec)


def n_params(cfg: dict) -> int:
    """Every parameter the model holds, counted from the sizes."""
    z = sizes(cfg)
    d, d_in, h, g, n, k, L, V = (z[x] for x in ("d", "d_in", "h", "g", "n",
                                                "k", "L", "V"))
    chans = d_in + 2 * g * n
    layer = (d * (2 * d_in + 2 * g * n + h) + k * chans + chans + 3 * h
             + d_in + d_in * d + d)
    return L * layer + d + V * d * (1 if z["tied"] else 2)


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token (forward and backward): 6 N (N
    without the input embedding but with the output head's matmul) plus the
    SSD chunked algorithm's matmuls (arXiv:2405.21060 section 6): per token
    and layer, C B^T within the chunk (Q G S), its product with x (Q H P),
    the chunk states B^T x and their read-out C h (H P S each); two FLOPs
    to a multiply-add, three passes for forward and backward."""
    z = sizes(cfg)
    n = n_params(cfg) - z["V"] * z["d"] * (0 if z["tied"] else 1)
    q = min(z["Q"], seq_len)
    ssd = (q * z["g"] * z["n"] + q * z["h"] * z["p"]
           + 2 * z["h"] * z["p"] * z["n"])
    return 6.0 * n + 3.0 * z["L"] * 2.0 * ssd
