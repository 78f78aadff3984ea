"""Fine-grained Mixture-of-Experts (DeepSeek-MoE / DeepSeek-V2 / DeepSeek-V3 style).

Shared experts (always-on) + routed experts with top-k softmax gating (normalized over
the selected set where ``norm_topk_prob``), and one of two dispatches:

* capacity (``capacity_factor`` > 0): each expert takes at most its capacity of
  tokens by gather/scatter (no (T,E,C) one-hot tensor is ever materialized), the
  rest are dropped; switch-style load-balance auxiliary loss. Expert weights are
  stacked (E, D, F) so the expert dimension can be sharded over the 'model' mesh
  axis (expert parallelism); the dispatch gather/combine scatter become
  all-to-all-class collectives under GSPMD.
* dropless (``capacity_factor`` == 0): the layer holds experts [0, experts_held)
  (one chip's share under expert parallelism; all where experts_held is 0) and
  routes over all ``n_experts``. Every (token, choice) pair whose expert is held
  is computed: the pairs sorted by expert, a grouped matrix product over the held
  experts (``kernels/gmm.py``), the gate-weighted rows scatter-added back to their
  tokens. Nothing is dropped; the products' work follows the routed rows, and the
  static buffers hold at most T x min(top_k, held) rows plus a tile per expert.
  Balance loss: DeepSeek-V2's sequence-wise one over the whole router.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import gmm as gmm_mod
from repro.models.config import ModelConfig
from repro.models.layers import dense_init

Params = Dict[str, jax.Array]


def init_moe(cfg: ModelConfig, key) -> Params:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    eh = cfg.resolved_experts_held if cfg.dropless else e
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (d, e), dt, scale=0.02),
        "w_gate": dense_init(ks[1], (eh, d, f), dt),
        "w_in": dense_init(ks[2], (eh, d, f), dt),
        "w_out": dense_init(ks[3], (eh, f, d), dt,
                            scale=0.02 / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        p["shared_gate"] = dense_init(ks[4], (d, fs), dt)
        p["shared_in"] = dense_init(ks[5], (d, fs), dt)
        p["shared_out"] = dense_init(ks[6], (fs, d), dt,
                                     scale=0.02 / math.sqrt(2 * cfg.n_layers))
    return p


def capacity(cfg: ModelConfig, tokens: int) -> int:
    cap = int(math.ceil(tokens * cfg.moe_top_k * cfg.capacity_factor
                        / cfg.n_experts))
    return max(8, -(-cap // 8) * 8)  # round up to 8 for TPU-friendly shapes


def _gates(cfg: ModelConfig, top: jax.Array) -> jax.Array:
    """The top-k softmax values as gates: renormalized over the selected
    set where ``norm_topk_prob``."""
    if cfg.norm_topk_prob:
        top = top / jnp.maximum(jnp.sum(top, -1, keepdims=True), 1e-9)
    return top


def route(cfg: ModelConfig, router_w: jax.Array, x: jax.Array):
    """x: (T, D) -> gate values, expert ids, slot table, aux loss.

    Returns:
      token_for_slot: (E*C,) int32 index into [0, T] (T = dropped sentinel)
      gate_for_slot:  (E*C,) f32
      aux: scalar load-balance loss
    """
    t_count, _ = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    cap = capacity(cfg, t_count)
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)               # (T, k)
    gate_vals = _gates(cfg, gate_vals)

    # load-balance aux (switch): E * sum_e f_e * P_e
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32).sum(1)  # (T, E)
    f_e = jnp.mean(onehot, axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)

    # position of each (t, choice) within its expert queue
    flat_expert = expert_idx.reshape(-1)                          # (T*k,)
    eo = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)          # (T*k, E)
    pos = jnp.cumsum(eo, axis=0) - 1                              # (T*k, E)
    pos_in_e = jnp.take_along_axis(pos, flat_expert[:, None], 1)[:, 0]
    keep = pos_in_e < cap
    slot = flat_expert * cap + pos_in_e                           # (T*k,)
    slot = jnp.where(keep, slot, e * cap)                         # overflow bin
    token_ids = jnp.repeat(jnp.arange(t_count, dtype=jnp.int32), k)
    token_for_slot = jnp.full((e * cap + 1,), t_count, jnp.int32)
    token_for_slot = token_for_slot.at[slot].set(token_ids)
    gate_for_slot = jnp.zeros((e * cap + 1,), jnp.float32)
    gate_for_slot = gate_for_slot.at[slot].set(gate_vals.reshape(-1))
    return token_for_slot[:-1], gate_for_slot[:-1], aux, cap


def _shared_experts(cfg: ModelConfig, p: Params, xt: jax.Array) -> jax.Array:
    cd = jnp.dtype(cfg.compute_dtype)
    hs = (jax.nn.silu(xt @ p["shared_gate"].astype(cd))
          * (xt @ p["shared_in"].astype(cd)))
    return hs @ p["shared_out"].astype(cd)


def dropless_forward(cfg: ModelConfig, p: Params, x: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) -> (y, aux_loss, stats): the held experts' part of the
    routed result for every token routed to them, plus the shared experts.
    stats: ``rows``, the (token, choice) pairs routed to held experts, and
    ``load_max``, the busiest held expert's pairs over the held mean."""
    cd = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    t_count = b * s
    e, k, eh = cfg.n_experts, cfg.moe_top_k, cfg.resolved_experts_held
    xt = x.reshape(t_count, d)
    tm = gmm_mod.TILE_M
    rows = (-(-t_count * min(k, eh) // tm) + eh) * tm    # static buffer
    with jax.named_scope("sparq_moe_route"):
        logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
        top, idx = jax.lax.top_k(probs, k)                        # (T, k)
        gates = _gates(cfg, top)
        # DeepSeek-V2's sequence-wise balance loss over all E experts:
        # mean over rows of sum_e (count_e E / (S k)) * mean prob_e
        cnt = jax.nn.one_hot(idx, e, dtype=jnp.float32).reshape(
            b, s * k, e).sum(1)
        aux = jnp.mean(jnp.sum(cnt * (e / (s * k))
                               * probs.reshape(b, s, e).mean(1), -1))

        # the (token, choice) pairs sorted by expert, those of experts not
        # held here last; each held pair's row in its expert's tiles. The
        # rank within an expert comes from the inverse permutation, so the
        # gates reach their rows by one scatter, whose gradient is a gather;
        # gathering them in sorted order instead (a scatter-add in the
        # backward pass) ran 6 ms a step slower on deepseek-v2-lite's cell
        flat_e = idx.reshape(-1)                                  # (T*k,)
        key = jnp.where(flat_e < eh, flat_e, eh)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((eh + 1,), jnp.int32).at[key].add(1)[:eh]
        first_row, tile_group, n_active = gmm_mod.layout(sizes, rows)
        pos = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        start = jnp.cumsum(sizes) - sizes
        e_c = jnp.minimum(flat_e, eh - 1)
        dest = jnp.where(flat_e < eh, first_row[e_c] + pos - start[e_c], rows)
        token = jnp.repeat(jnp.arange(t_count, dtype=jnp.int32), k)
        row_token = jnp.full((rows,), t_count, jnp.int32).at[dest].set(
            token, mode="drop")
        row_gate = jnp.zeros((rows,), jnp.float32).at[dest].set(
            gates.reshape(-1), mode="drop")
        xe = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])[row_token]
    with jax.named_scope("sparq_moe_experts"):
        def prod(a, w):
            return gmm_mod.gmm(a, w.astype(cd), tile_group, n_active)
        xe = xe.astype(cd)
        h = jax.nn.silu(prod(xe, p["w_gate"])) * prod(xe, p["w_in"])
        ye = prod(h, p["w_out"])                                  # (rows, D)
    with jax.named_scope("sparq_moe_route"):
        ye = ye * row_gate[:, None].astype(cd)
        y = jnp.zeros((t_count + 1, d), cd).at[row_token].add(ye)[:-1]
    if cfg.n_shared_experts:
        y = y + _shared_experts(cfg, p, xt)
    sizes_f = sizes.astype(jnp.float32)
    total = jnp.sum(sizes_f)
    stats = {"rows": total,
             "load_max": jnp.max(sizes_f) * eh / jnp.maximum(total, 1.0)}
    return y.reshape(b, s, d), aux.astype(jnp.float32), stats


def moe_forward(cfg: ModelConfig, p: Params, x: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss)."""
    if cfg.dropless:
        y, aux, _ = dropless_forward(cfg, p, x)
        return y, aux
    if cfg.moe_route_blocks > 1:
        return _moe_forward_blocked(cfg, p, x)
    cd = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    token_for_slot, gate_for_slot, aux, cap = route(cfg, p["router"], xt)
    e = cfg.n_experts
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], 0)  # sentinel row

    def _pin_experts(t):
        if cfg.expert_axis is None:
            return t
        from jax.sharding import PartitionSpec as P
        return jax.lax.with_sharding_constraint(
            t, P(cfg.expert_axis, *([None] * (t.ndim - 1))))

    slots = _pin_experts(token_for_slot.reshape(e, cap))
    xe = _pin_experts(xt_pad[slots])                                # (E, C, D)
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(cd)))
         * jnp.einsum("ecd,edf->ecf", xe, p["w_in"].astype(cd)))
    ye = _pin_experts(jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(cd)))
    token_for_slot = slots.reshape(-1)
    ye = ye.reshape(e * cap, d) * gate_for_slot[:, None].astype(cd)
    y = jnp.zeros((b * s + 1, d), cd).at[token_for_slot].add(ye)[:-1]
    if cfg.n_shared_experts:
        y = y + _shared_experts(cfg, p, xt)
    return y.reshape(b, s, d), aux.astype(jnp.float32)


def _moe_forward_blocked(cfg: ModelConfig, p: Params, x: jax.Array
                         ) -> Tuple[jax.Array, jax.Array]:
    """Blocked routing: tokens split into `moe_route_blocks` independent
    groups, each with capacity/nb slots per expert. The cumsum/one-hot
    position bookkeeping is per block, so when blocks align with the fsdp
    token sharding GSPMD keeps routing shard-local. Same operator family as
    per-device capacity in production MoEs (slightly different drop pattern
    than global routing; tested equal at ample capacity)."""
    cd = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    nb = cfg.moe_route_blocks
    e = cfg.n_experts
    t_all = b * s
    assert t_all % nb == 0, "tokens must split into route blocks"
    xt = x.reshape(nb, t_all // nb, d)

    tfs, gfs, auxs, cap = jax.vmap(
        lambda xb: route(cfg, p["router"], xb))(xt)
    cap = capacity(cfg, t_all // nb)

    def one_block(xb, token_for_slot, gate_for_slot):
        xb_pad = jnp.concatenate([xb, jnp.zeros((1, d), xb.dtype)], 0)
        xe = xb_pad[token_for_slot].reshape(e, cap, d)
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe,
                                    p["w_gate"].astype(cd)))
             * jnp.einsum("ecd,edf->ecf", xe, p["w_in"].astype(cd)))
        ye = jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(cd))
        ye = ye.reshape(e * cap, d) * gate_for_slot[:, None].astype(cd)
        return jnp.zeros((xb.shape[0] + 1, d), cd).at[token_for_slot].add(
            ye)[:-1]

    y = jax.vmap(one_block)(xt, tfs, gfs).reshape(b * s, d)
    if cfg.n_shared_experts:
        y = y + _shared_experts(cfg, p, x.reshape(b * s, d))
    return y.reshape(b, s, d), jnp.mean(auxs).astype(jnp.float32)
