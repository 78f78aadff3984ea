"""Model assembly for all six families.

A model is a sequence of homogeneous *segments*, each a stack of identical blocks
scanned with stacked parameters (small HLO even at 81 layers):

  dense family : [("dense", L)]
  moe family   : [("dense", first_k_dense), ("moe", L - first_k_dense)]
  ssm family   : [("ssm", L)]
  hybrid       : [("hybrid", L)]  — Mamba2 blocks; a SHARED attention block (one
                  parameter set, reused) is applied after every `attn_every`-th layer
                  (Zamba2 [arXiv:2411.15242])

audio / vlm backbones are "dense" (their modality frontends are stubs per DESIGN §5).
deepseek-v3 additionally has an MTP (multi-token-prediction) head: one extra dense
block over [h_t ; emb(x_{t+1})] predicting x_{t+2} with weight cfg.mtp_coef.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (apply_mlp, apply_norm, dense_init, embed_tokens,
                                 init_embed, init_mlp, init_norm, lm_logits)

Params = Dict[str, Any]


def segments(cfg: ModelConfig) -> List[Tuple[str, int]]:
    if cfg.family == "ssm":
        return [("ssm", cfg.n_layers)]
    if cfg.family == "hybrid":
        return [("hybrid", cfg.n_layers)]
    if cfg.family == "moe":
        segs = []
        if cfg.first_k_dense:
            segs.append(("dense", cfg.first_k_dense))
        segs.append(("moe", cfg.n_layers - cfg.first_k_dense))
        return segs
    return [("dense", cfg.n_layers)]


# ------------------------------------------------------------------ block params

def _init_block(cfg: ModelConfig, kind: str, key) -> Params:
    ks = jax.random.split(key, 6)
    if kind == "ssm" or kind == "hybrid":
        return {"norm": init_norm(cfg, ks[0]), "ssm": ssm_mod.init_ssm(cfg, ks[1])}
    p = {"norm1": init_norm(cfg, ks[0]), "norm2": init_norm(cfg, ks[1])}
    if cfg.use_mla:
        p["attn"] = attn.init_mla(cfg, ks[2])
    else:
        p["attn"] = attn.init_attention(cfg, ks[2])
    if kind == "moe":
        p["moe"] = moe_mod.init_moe(cfg, ks[3])
    else:
        p["mlp"] = init_mlp(cfg, ks[3])
    return p


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg: ModelConfig, key) -> Params:
    keys = jax.random.split(key, 8)
    p: Params = {"embed": init_embed(cfg, keys[0]),
                 "final_norm": init_norm(cfg, keys[1])}
    for si, (kind, n) in enumerate(segments(cfg)):
        bkeys = jax.random.split(jax.random.fold_in(keys[2], si), n)
        p[f"seg{si}"] = _stack([_init_block(cfg, kind, bk) for bk in bkeys])
    if cfg.family == "hybrid":
        p["shared_attn"] = {
            "norm1": init_norm(cfg, keys[3]), "norm2": init_norm(cfg, keys[4]),
            "attn": attn.init_attention(cfg, keys[5]),
            "mlp": init_mlp(cfg, keys[6]),
        }
    if cfg.use_mtp:
        k7 = jax.random.split(keys[7], 3)
        p["mtp"] = {
            "proj": dense_init(k7[0], (2 * cfg.d_model, cfg.d_model),
                               jnp.dtype(cfg.param_dtype)),
            "block": _init_block(cfg, "dense", k7[1]),
            "norm": init_norm(cfg, k7[2]),
        }
    return p


# ------------------------------------------------------------------ forward blocks

def _apply_dense_block(cfg: ModelConfig, bp: Params, x, positions):
    h = apply_norm(cfg, bp["norm1"], x)
    if cfg.use_mla:
        x = x + attn.mla_forward(cfg, bp["attn"], h, positions)
    else:
        x = x + attn.attention_forward(cfg, bp["attn"], h, positions)
    h2 = apply_norm(cfg, bp["norm2"], x)
    x = x + apply_mlp(cfg, bp["mlp"], h2)
    return x


def _apply_moe_block(cfg: ModelConfig, bp: Params, x, positions):
    h = apply_norm(cfg, bp["norm1"], x)
    if cfg.use_mla:
        x = x + attn.mla_forward(cfg, bp["attn"], h, positions)
    else:
        x = x + attn.attention_forward(cfg, bp["attn"], h, positions)
    h2 = apply_norm(cfg, bp["norm2"], x)
    if cfg.dropless:
        y, aux, stats = moe_mod.dropless_forward(cfg, bp["moe"], h2)
        return x + y, (aux, stats)
    y, aux = moe_mod.moe_forward(cfg, bp["moe"], h2)
    return x + y, aux


def _apply_ssm_block(cfg: ModelConfig, bp: Params, x, positions):
    h = apply_norm(cfg, bp["norm"], x)
    return x + ssm_mod.ssm_forward(cfg, bp["ssm"], h, positions)


def _apply_shared_attn(cfg: ModelConfig, sp: Params, x, positions):
    h = apply_norm(cfg, sp["norm1"], x)
    x = x + attn.attention_forward(cfg, sp["attn"], h, positions)
    h2 = apply_norm(cfg, sp["norm2"], x)
    return x + apply_mlp(cfg, sp["mlp"], h2)


def forward_hidden(cfg: ModelConfig, params: Params,
                   tokens: Optional[jax.Array] = None,
                   embeds: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Backbone forward. tokens: (B, S) int32, or `embeds` (B, S, D) precomputed
    frontend embeddings (audio/VLM stub carve-out) -> (hidden (B,S,D) final-
    norm'd, aux)."""
    x, aux, _ = _backbone(cfg, params, tokens, embeds)
    return x, aux


def _backbone(cfg: ModelConfig, params: Params, tokens: Optional[jax.Array],
              embeds: Optional[jax.Array]
              ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """forward_hidden, plus the dropless expert layers' counters summed
    over them: ``moe_rows`` (pairs routed to held experts) and
    ``moe_load_max`` (the worst layer's busiest held expert over the held
    mean); none for other models."""
    if embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.compute_dtype))
        b, s, _ = embeds.shape
    else:
        b, s = tokens.shape
        x = embed_tokens(cfg, params["embed"], tokens)
    if cfg.batch_axes is not None:
        from jax.sharding import PartitionSpec as _P
        x = jax.lax.with_sharding_constraint(
            x, _P(cfg.batch_axes, *([None] * (x.ndim - 1))))
    positions = jnp.arange(s, dtype=jnp.int32)
    aux_total = jnp.zeros((), jnp.float32)
    stats: Dict[str, jax.Array] = {}

    for si, (kind, _n) in enumerate(segments(cfg)):
        seg_params = params[f"seg{si}"]

        if kind == "dense":
            def body(h, bp):
                return _apply_dense_block(cfg, bp, h, positions), None
        elif kind == "moe":
            def body(h, bp):
                h, aux = _apply_moe_block(cfg, bp, h, positions)
                return h, aux
        elif kind == "ssm":
            def body(h, bp):
                return _apply_ssm_block(cfg, bp, h, positions), None
        elif kind == "hybrid":
            shared = params["shared_attn"]
            every = cfg.attn_every

            def body(carry, bp):
                h, idx = carry
                h = _apply_ssm_block(cfg, bp, h, positions)
                h = jax.lax.cond(
                    (idx % every) == (every - 1),
                    lambda hh: _apply_shared_attn(cfg, shared, hh, positions),
                    lambda hh: hh, h)
                return (h, idx + 1), None
        else:
            raise ValueError(kind)

        wrapped = jax.checkpoint(body) if cfg.remat else body
        if kind == "hybrid":
            (x, _), _ = jax.lax.scan(wrapped, (x, jnp.int32(0)), seg_params)
        else:
            if kind == "moe":
                x, auxs = jax.lax.scan(wrapped, x, seg_params)
                if cfg.dropless:
                    auxs, st = auxs
                    stats = {"moe_rows": jnp.sum(st["rows"]),
                             "moe_load_max": jnp.max(st["load_max"])}
                aux_total = aux_total + jnp.sum(auxs)
            else:
                x, _ = jax.lax.scan(wrapped, x, seg_params)

    x = apply_norm(cfg, params["final_norm"], x)
    return x, aux_total, stats


def forward(cfg: ModelConfig, params: Params, tokens: Optional[jax.Array] = None,
            embeds: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Full forward with LM head: -> (logits (B,S,V), aux)."""
    h, aux = forward_hidden(cfg, params, tokens, embeds=embeds)
    return lm_logits(cfg, params["embed"], h), aux


def mtp_hidden(cfg: ModelConfig, params: Params, tokens: jax.Array,
               h_final: jax.Array) -> jax.Array:
    """DeepSeek-V3 MTP head hidden states: position i predicts tokens[i+2].

    h_final: (B, S, D) final-norm'd hidden states. Returns (B, S-1, D)."""
    mp = params["mtp"]
    cd = jnp.dtype(cfg.compute_dtype)
    emb_next = embed_tokens(cfg, params["embed"], tokens[:, 1:])     # (B,S-1,D)
    h = jnp.concatenate([h_final[:, :-1], emb_next], axis=-1)
    h = h @ mp["proj"].astype(cd)
    positions = jnp.arange(h.shape[1], dtype=jnp.int32)
    h = _apply_dense_block(cfg, mp["block"], h, positions)
    return apply_norm(cfg, mp["norm"], h)


# ------------------------------------------------------------------ loss

LOSS_CHUNK = 256  # sequence chunk for the streamed cross-entropy


def chunked_ce(cfg: ModelConfig, embed_params: Params, h: jax.Array,
               labels: jax.Array, chunk: int = LOSS_CHUNK) -> jax.Array:
    """Mean next-token CE WITHOUT materializing (B, S, V) logits.

    The LM head matmul + logsumexp run per sequence chunk inside a remat'd scan,
    so peak memory is one (B, chunk, V) tile and backward recomputes it. This is
    what makes 151k-vocab models fit (EXPERIMENTS.md §Perf iteration 0)."""
    b, s, d = h.shape
    if s <= chunk:
        logits = lm_logits(cfg, embed_params, h).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(lse - tgt)
    nc = s // chunk
    if nc * chunk != s:  # truncate the ragged tail (documented deviation)
        h, labels, s = h[:, :nc * chunk], labels[:, :nc * chunk], nc * chunk
    hc = jnp.moveaxis(h.reshape(b, nc, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, nc, chunk), 1, 0)

    def body(tot, xs):
        hcb, lcb = xs
        logits = lm_logits(cfg, embed_params, hcb).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lcb[..., None], -1)[..., 0]
        return tot + jnp.sum(lse - tgt), None

    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                            (hc, lc))
    return total / (b * s)


def lm_loss(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token CE (+ router aux + MTP). batch: tokens (B,S) or embeds (B,S,D)
    [audio/VLM frontend-stub inputs], labels (B,S)."""
    labels = batch["labels"]
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    hidden, aux, stats = _backbone(cfg, params, tokens, embeds)
    loss = chunked_ce(cfg, params["embed"], hidden, labels)
    metrics = {"ce": loss, "aux": aux, **stats}
    if cfg.n_experts:
        loss = loss + cfg.router_aux_coef * aux
    if cfg.use_mtp:
        mh = mtp_hidden(cfg, params, tokens, hidden)          # (B, S-1, D)
        mtp_loss = chunked_ce(cfg, params["embed"], mh[:, :-1], labels[:, 2:])
        metrics["mtp"] = mtp_loss
        loss = loss + cfg.mtp_coef * mtp_loss
    metrics["loss"] = loss
    return loss, metrics


# ------------------------------------------------------------------ decode

@dataclasses.dataclass
class CacheSpec:
    kind: str            # kv | mla | ssm | hybrid


def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> Params:
    """cache_len: full context for full attention; window size for SWA."""
    if cfg.family == "ssm":
        return {"ssm": ssm_mod.init_ssm_cache(cfg, batch)}
    if cfg.family == "hybrid":
        n_apps = cfg.n_layers // cfg.attn_every
        return {"ssm": ssm_mod.init_ssm_cache(cfg, batch),
                "attn": attn.init_kv_cache(cfg, batch, cache_len, n_layers=n_apps)}
    if cfg.use_mla:
        return {"mla": attn.init_mla_cache(cfg, batch, cache_len)}
    return {"kv": attn.init_kv_cache(cfg, batch, cache_len)}


def _decode_dense_block(cfg, bp, x, kv_slice, pos):
    h = apply_norm(cfg, bp["norm1"], x)
    if cfg.use_mla:
        o, new_cache = attn.mla_decode(cfg, bp["attn"], h, kv_slice["ckv"],
                                       kv_slice["kr"], kv_slice["pos"], pos)
    else:
        o, new_cache = attn.decode_attention(cfg, bp["attn"], h, kv_slice["k"],
                                             kv_slice["v"], kv_slice["pos"], pos)
    x = x + o
    h2 = apply_norm(cfg, bp["norm2"], x)
    if "moe" in bp:
        y, _ = moe_mod.moe_forward(cfg, bp["moe"], h2)
        x = x + y
    else:
        x = x + apply_mlp(cfg, bp["mlp"], h2)
    return x, new_cache


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                tokens: Optional[jax.Array], pos: jax.Array,
                embeds: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Params]:
    """One decode step for the whole stack. tokens: (B, 1) (or embeds (B,1,D)
    for audio/VLM frontend-stub inputs); pos: scalar int32."""
    if embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.compute_dtype))
    else:
        x = embed_tokens(cfg, params["embed"], tokens)
    new_cache = {k: dict(v) for k, v in cache.items()}

    if cfg.family in ("ssm", "hybrid"):
        ssm_c = cache["ssm"]

        if cfg.family == "ssm":
            def body(h, inp):
                bp, st, cv = inp
                hh = apply_norm(cfg, bp["norm"], h)
                o, (st2, cv2) = ssm_mod.ssm_decode(cfg, bp["ssm"], hh, st, cv)
                return h + o, (st2, cv2)

            x, (st, cv) = jax.lax.scan(
                body, x, (params["seg0"], ssm_c["state"], ssm_c["conv"]))
            new_cache["ssm"] = {"state": st, "conv": cv}
        else:
            shared = params["shared_attn"]
            every = cfg.attn_every
            ac = cache["attn"]

            def body(carry, inp):
                h, idx, ak, av, ap = carry
                bp, st, cv = inp
                hh = apply_norm(cfg, bp["norm"], h)
                o, (st2, cv2) = ssm_mod.ssm_decode(cfg, bp["ssm"], hh, st, cv)
                h = h + o

                def do_attn(args):
                    h, ak, av, ap = args
                    app = idx // every
                    k_sl = jax.lax.dynamic_index_in_dim(ak, app, 0, False)
                    v_sl = jax.lax.dynamic_index_in_dim(av, app, 0, False)
                    p_sl = jax.lax.dynamic_index_in_dim(ap, app, 0, False)
                    hh = apply_norm(cfg, shared["norm1"], h)
                    o, (k2, v2, p2) = attn.decode_attention(
                        cfg, shared["attn"], hh, k_sl, v_sl, p_sl, pos)
                    h = h + o
                    h2 = apply_norm(cfg, shared["norm2"], h)
                    h = h + apply_mlp(cfg, shared["mlp"], h2)
                    ak = jax.lax.dynamic_update_index_in_dim(ak, k2, app, 0)
                    av = jax.lax.dynamic_update_index_in_dim(av, v2, app, 0)
                    ap = jax.lax.dynamic_update_index_in_dim(ap, p2, app, 0)
                    return h, ak, av, ap

                h, ak, av, ap = jax.lax.cond(
                    (idx % every) == (every - 1), do_attn,
                    lambda args: args, (h, ak, av, ap))
                return (h, idx + 1, ak, av, ap), (st2, cv2)

            (x, _, ak, av, ap), (st, cv) = jax.lax.scan(
                body, (x, jnp.int32(0), ac["k"], ac["v"], ac["pos"]),
                (params["seg0"], ssm_c["state"], ssm_c["conv"]))
            new_cache["ssm"] = {"state": st, "conv": cv}
            new_cache["attn"] = {"k": ak, "v": av, "pos": ap}
    else:
        # dense / moe: per-segment scan with per-layer cache slices
        ckey = "mla" if cfg.use_mla else "kv"
        cc = cache[ckey]
        layer_off = 0
        outs = {k: [] for k in cc}
        for si, (_kind, n) in enumerate(segments(cfg)):
            seg_params = params[f"seg{si}"]
            sl = {k: v[layer_off:layer_off + n] for k, v in cc.items()}

            def body(h, inp):
                bp, kv_slice = inp
                h, new_kv = _decode_dense_block(cfg, bp, h, kv_slice, pos)
                if cfg.use_mla:
                    names = ("ckv", "kr", "pos")
                else:
                    names = ("k", "v", "pos")
                return h, dict(zip(names, new_kv, strict=True))

            x, seg_new = jax.lax.scan(body, x, (seg_params, sl))
            for k in outs:
                outs[k].append(seg_new[k])
            layer_off += n
        new_cache[ckey] = {k: jnp.concatenate(v, 0) for k, v in outs.items()}

    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_logits(cfg, params["embed"], x)
    return logits, new_cache
