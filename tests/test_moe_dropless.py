"""The dropless expert layer that holds a share of the experts
(``models/moe.py`` ``dropless_forward``), DeepSeek-V2's latent attention as
published (``models/attention.py``: the latent RMSNorm, YaRN), and the
layer's counters and scopes in the SPARQ train step, on the CPU at small
widths with seeded random weights."""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.layers import yarn_frequencies

T = 48          # tokens: 2 rows of 24


def small(**kw) -> "object":
    """deepseek-v2-lite at small widths: a 64-way router, top 6, two shared
    experts, MLA with YaRN, in float32."""
    cfg = dataclasses.replace(
        get_config("deepseek-v2-lite"), n_layers=3, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=48, vocab_size=64, moe_d_ff=16, kv_lora_rank=16,
        qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8, compute_dtype="float32",
        n_nodes=1)
    return dataclasses.replace(cfg, **kw)


def _x(seed=0, positive=False):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, T // 2, 32))
    return jnp.abs(x) if positive else x


def _swiglu(x, wg, wi, wo):
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wo


def dense_layer(cfg, p, x, experts):
    """The layer written out: every expert in ``experts`` (global ids; the
    i-th is p's i-th) run on every token, weighted by its gate where it was
    chosen; the shared experts on every token."""
    xt = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(xt @ p["router"], -1)
    top, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.norm_topk_prob:
        top = top / jnp.sum(top, -1, keepdims=True)
    y = _swiglu(xt, p["shared_gate"], p["shared_in"], p["shared_out"])
    for i, e in enumerate(experts):
        g = jnp.sum(jnp.where(idx == e, top, 0.0), -1, keepdims=True)
        y = y + g * _swiglu(xt, p["w_gate"][i], p["w_in"][i], p["w_out"][i])
    return y.reshape(x.shape)


def _params(cfg, seed=1):
    return moe_mod.init_moe(cfg, jax.random.PRNGKey(seed))


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, atol=tol * np.abs(b).max(), rtol=0)


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips of an expert-parallel deployment each hold 8 of the 64
    experts (the held ids are [0, 8), so chip s's layer is the uncut one
    with its router's columns rotated by 8 s). What the eight shares
    compute, the shared experts counted once, is the uncut layer, which is
    the layer written out."""
    whole = small(experts_held=0)
    p = _params(whole)
    x = _x()
    y_all, aux_all, st_all = moe_mod.dropless_forward(whole, p, x)
    _close(y_all, dense_layer(whole, p, x, range(64)))
    assert float(st_all["rows"]) == T * 6

    share = small(experts_held=8)
    shared = dense_layer(share, p, x, [])
    total, rows = shared, 0.0
    for s in range(8):
        ps = dict(p, router=jnp.roll(p["router"], -8 * s, axis=1),
                  **{k: p[k][8 * s:8 * s + 8]
                     for k in ("w_gate", "w_in", "w_out")})
        y, aux, st = moe_mod.dropless_forward(share, ps, x)
        total = total + (y - shared)
        rows += float(st["rows"])
        # every chip sees the whole router: the same balance loss
        np.testing.assert_allclose(float(aux), float(aux_all), rtol=1e-6)
    _close(total, y_all)
    assert rows == T * 6


def test_dropless_under_forced_imbalance():
    """A router that sends every token to experts 0-5: expert 0 gets all T
    tokens (a capacity layer at 1.25 keeps T * 6 * 1.25 / 64 of them), and
    every one of them is computed."""
    cfg = small(experts_held=8)
    p = _params(cfg)
    w = -jnp.ones((32, 64)) + 0.01 * jnp.arange(64)[None]
    p["router"] = w.at[:, :6].set(1.0 + 0.01 * jnp.arange(6)[None])
    x = _x(positive=True)
    y, _, st = moe_mod.dropless_forward(cfg, p, x)
    _close(y, dense_layer(cfg, p, x, range(8)))
    assert float(st["rows"]) == T * 6
    np.testing.assert_allclose(float(st["load_max"]), 8 / 6, rtol=1e-6)
    assert moe_mod.capacity(dataclasses.replace(cfg, capacity_factor=1.25),
                            T) < T


@pytest.mark.parametrize("norm", [False, True])
def test_gates_with_and_without_renormalization(norm):
    cfg = small(experts_held=8, norm_topk_prob=norm)
    p = _params(cfg)
    x = _x(2)
    y, _, _ = moe_mod.dropless_forward(cfg, p, x)
    _close(y, dense_layer(cfg, p, x, range(8)))
    other, _, _ = moe_mod.dropless_forward(
        dataclasses.replace(cfg, norm_topk_prob=not norm), p, x)
    assert not np.allclose(np.asarray(y), np.asarray(other), rtol=1e-3)


def test_the_published_configs_gates():
    assert not get_config("deepseek-v2-lite").norm_topk_prob
    assert not get_config("deepseek-moe-16b").norm_topk_prob
    assert get_config("deepseek-v3-671b").norm_topk_prob


def _yarn_transcribed(dim, base, factor, beta_fast, beta_slow, orig):
    """DeepSeek-V2's DeepseekV2YarnRotaryEmbedding inverse frequencies."""
    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


@pytest.mark.parametrize("dim", [8, 64])
def test_yarn_frequencies_as_published(dim):
    cfg = small(qk_rope_dim=dim)
    want = _yarn_transcribed(dim, 10000.0, 40.0, 32, 1, 4096)
    np.testing.assert_allclose(np.asarray(yarn_frequencies(cfg, dim)), want,
                               rtol=1e-6)
    assert not np.allclose(want, 1.0 / 10000.0 ** (np.arange(0, dim, 2)
                                                    / dim))


def _mla_transcribed(cfg, p, x):
    """DeepSeek-V2's attention (modeling_deepseek.py) in numpy float64: the
    latent RMSNorm, rotary embedding on the interleaved pairs permuted into
    halves with rotate_half, YaRN's cos / sin and softmax scale."""
    x, p = np.asarray(x, np.float64), {k: np.asarray(v, np.float64)
                                       for k, v in p.items()}
    b, s, _ = x.shape
    h, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    q = (x @ p["w_q"]).reshape(b, s, h, dn + dr)
    c = x @ p["w_dkv"]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + cfg.norm_eps) \
        * p["kv_norm"]
    k_nope = (c @ p["w_uk"]).reshape(b, s, h, dn)
    v = (c @ p["w_uv"]).reshape(b, s, h, dv)
    k_pe = (x @ p["w_kr"]).reshape(b, s, 1, dr)

    def mscale(m):
        return 0.1 * m * math.log(cfg.rope_factor) + 1.0

    inv = _yarn_transcribed(dr, cfg.rope_theta, cfg.rope_factor, 32, 1, 4096)
    freqs = np.arange(s)[:, None] * inv[None]
    emb = np.concatenate([freqs, freqs], -1)
    m = mscale(cfg.rope_mscale) / mscale(cfg.rope_mscale_all_dim)
    cos, sin = np.cos(emb) * m, np.sin(emb) * m

    def rotary(t):                       # (b, s, heads, dr)
        t = t.reshape(b, s, -1, dr // 2, 2).swapaxes(-1, -2).reshape(
            t.shape)
        half = np.concatenate([-t[..., dr // 2:], t[..., :dr // 2]], -1)
        return t * cos[None, :, None] + half * sin[None, :, None]

    q = np.concatenate([q[..., :dn], rotary(q[..., dn:])], -1)
    k = np.concatenate([k_nope, np.broadcast_to(rotary(k_pe),
                                                (b, s, h, dr))], -1)
    scale = (dn + dr) ** -0.5 * mscale(cfg.rope_mscale_all_dim) ** 2
    sc = np.einsum("bqhe,bkhe->bhqk", q, k) * scale
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr = pr / pr.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhe->bqhe", pr, v).reshape(b, s, h * dv)
    return o @ p["wo"]


def test_mla_as_published(monkeypatch):
    """The program's MLA (attention scores in float32 here; the program
    keeps them in bfloat16) against a direct transcription of DeepSeek-V2's
    equations, at positions past the RoPE's fastest wavelengths."""
    import functools
    monkeypatch.setattr(attn, "chunked_attention", functools.partial(
        attn.chunked_attention, score_dtype=jnp.float32))
    cfg = small()
    p = attn.init_mla(cfg, jax.random.PRNGKey(3))
    p["kv_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                                 p["kv_norm"].shape)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 32))
    got = attn.mla_forward(cfg, p, x, jnp.arange(40, dtype=jnp.int32))
    _close(got, _mla_transcribed(cfg, p, x), tol=1e-5)
    # each published part matters: the latent norm makes the output blind
    # to the latent's scale (but for its eps; without the norm v, and so
    # the output, would triple), and without YaRN the output is another
    scaled = attn.mla_forward(cfg, dict(p, w_dkv=3.0 * p["w_dkv"]), x,
                              jnp.arange(40, dtype=jnp.int32))
    _close(scaled, got, tol=1e-3)
    other = attn.mla_forward(dataclasses.replace(cfg, rope_factor=1.0), p, x,
                             jnp.arange(40, dtype=jnp.int32))
    assert not np.allclose(np.asarray(other), np.asarray(got), rtol=1e-3)


def _step(cfg, use_kernel=True):
    from repro.core.schedule import decaying
    from repro.dist import sharding as sh
    from repro.dist.sparq_dist import DistSparqConfig, build_sparq
    from repro.launch.mesh import make_mesh
    mesh = sh.train_mesh(make_mesh((1, 1), ("data", "model"),
                                   devices=jax.devices()[:1]), cfg)
    dcfg = DistSparqConfig(H=2, frac=0.1, lr=decaying(0.5, 100.0),
                           use_kernel=use_kernel, variant="ring")
    init_fn, train_step, _, _ = build_sparq(cfg, mesh, dcfg)
    return init_fn, train_step


@pytest.mark.parametrize("held", [4, 8])
def test_moe_rows_is_a_host_count(held):
    """With the router's weights zero every token's gates tie, and its top
    6 are experts 0-5: the counter of routings into the held experts is
    then T x min(6, held) per expert layer, and the busiest held expert
    over the held mean is read off the host count too."""
    cfg = small(experts_held=held)
    init_fn, train_step = _step(cfg)
    state = init_fn(jax.random.PRNGKey(0))
    tree = train_step.unravel(state["params"][0])
    tree["seg1"]["moe"]["router"] = jnp.zeros_like(
        tree["seg1"]["moe"]["router"])
    row = train_step.ravel(tree)
    state["params"] = jnp.pad(row, (0, train_step.d_pad - row.size))[None]
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 2, 25), 0, 64)
    batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    _, metrics = jax.jit(train_step)(state, batch)
    counts = np.zeros(held)
    counts[:min(6, held)] = T
    layers = cfg.n_layers - cfg.first_k_dense
    assert float(metrics["moe_rows"]) == layers * counts.sum()
    np.testing.assert_allclose(float(metrics["moe_load_max"]),
                               counts.max() / counts.mean(), rtol=1e-6)


def test_other_models_emit_no_moe_counter():
    for cfg in (dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                                    n_nodes=1),
                dataclasses.replace(get_config("deepseek-v3-671b").reduced(),
                                    n_nodes=1)):
        init_fn, train_step = _step(cfg, use_kernel=False)
        state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        batch = {k: jax.ShapeDtypeStruct((1, 2, 16), jnp.int32)
                 for k in ("tokens", "labels")}
        _, metrics = jax.eval_shape(train_step, state, batch)
        assert not {"moe_rows", "moe_load_max"} & set(metrics), cfg.arch_id


def test_moe_scopes_reach_the_compiled_step():
    """sparq_moe_route (logits to the combine) and sparq_moe_experts (the
    grouped products) reach the op_name metadata of the compiled step, which
    the profiler keeps as each op's tf_op, inside sparq_grad (the
    rematerialized copies of the backward pass carry the remat's own
    prefix instead)."""
    init_fn, train_step = _step(small(experts_held=8))
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((1, 2, 16), jnp.int32)
             for k in ("tokens", "labels")}
    hlo = jax.jit(train_step).lower(state, batch).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("sparq_moe_route", "sparq_moe_experts"):
        held = [n for n in names
                if any(scope in part for part in n.split("/"))]
        assert held, scope
        assert any("sparq_grad" in n for n in held), scope
