"""deepseek-v2-lite's configuration, its plain reference
(``reference/mla_moe_lm.py``) against the program's own model, a toy-size
run of its cell, and the grouped products' roofline reader, without a
chip."""
import copy
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import program
import run
from reference import sparq

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
CELL = "deepseek-v2-lite.local-h8-4k"
# toy widths: a 16-way router holding 4, top 6 (more choices than held
# experts), MLA with YaRN over 8 rope dims
SMALL = ({"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
          "num_attention_heads": 4, "kv_lora_rank": 32,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "moe_intermediate_size": 32, "n_routed_experts": 4,
          "vocab_size": 256},
         {"n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
          "d_ff": 96, "kv_lora_rank": 32, "qk_nope_dim": 16,
          "qk_rope_dim": 8, "v_head_dim": 16, "moe_d_ff": 32,
          "n_experts": 16, "experts_held": 4, "vocab_size": 256})


def _cfg(small: bool = False, **over) -> dict:
    with open(os.path.join(BENCH, "configs", "deepseek-v2-lite.json")) as f:
        cfg = json.load(f)
    if small:
        cfg.update(SMALL[0])
        cfg["published"] = dict(cfg["published"], n_routed_experts=16)
        cfg["program"] = {"arch": cfg["program"]["arch"],
                          "overrides": dict(SMALL[1], **over)}
    return cfg


def test_the_cut_is_the_published_model_at_its_widths():
    """Every number of the published config.json is in the file, changed
    only where ``reduced`` says; the program's row at the cut holds
    535,060,992 parameters, which the reference counts from the sizes; the
    uncut model holds the published 15.7B."""
    from repro.configs.registry import get_config
    from repro.models.transformer import init_params
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 12800}
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64, "vocab_size": 102400}
    model = run.reference_model(cfg)
    mcfg = program.model_config(cfg, 1)
    assert (mcfg.n_experts, mcfg.moe_top_k, mcfg.moe_d_ff, mcfg.d_model,
            mcfg.kv_lora_rank) == (64, 6, 1408, 2048, 512)

    def count(c):
        shapes = jax.eval_shape(lambda k: init_params(c, k),
                                jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    assert count(mcfg) == model.n_params(cfg) == 535_060_992
    segs = sparq.layout(model.init_params, cfg)
    assert segs[-1][1] + segs[-1][2] == 535_060_992
    assert count(get_config("deepseek-v2-lite")) == 15_706_484_224


def test_flops_per_token_from_an_independent_count():
    cfg = _cfg()
    model = run.reference_model(cfg)
    d, f, fe, L, V = 2048, 10944, 1408, 5, 12800
    attn = (d * 512 + d * 64 + 512 * 16 * 128 * 2 + 16 * 128 * d
            + d * 16 * 192 + 512)
    dense = attn + 3 * d * f + 2 * d
    # a held expert's weights count at the 6 x 8 / 64 expert-rows a token
    # takes of them
    moe = attn + d * 64 + 0.75 * 3 * d * fe + 3 * d * 2 * fe + 2 * d
    n = dense + 4 * moe + d + V * d
    want = 6 * n + 6 * L * 16 * (128 + 64 + 128) * 4096
    assert model.flops_per_token(cfg, 4096) == pytest.approx(want, rel=1e-12)
    assert 2.0e9 < want < 2.3e9


def test_reference_weights_are_the_programs():
    from repro.models.transformer import init_params
    cfg = _cfg(small=True)
    model = run.reference_model(cfg)
    key = program.model_key(2 ** 33 + 5)
    mine = jax.tree_util.tree_flatten_with_path(model.init_params(cfg, key))
    theirs = jax.tree_util.tree_flatten_with_path(
        init_params(program.model_config(cfg, 1), key))
    assert [p for p, _ in mine[0]] == [p for p, _ in theirs[0]]
    for (_, a), (_, b) in zip(mine[0], theirs[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_program_loss_and_gradients_are_the_references(monkeypatch):
    """At toy widths, the program in float32 (attention scores too: the
    program keeps them in bfloat16) and the reference give the same loss and
    the same gradient in every leaf, to float32 round-off: the routing, the
    held experts' dispatch and combine, MLA with YaRN and the balance loss
    are the published equations in both."""
    from repro.models import attention
    from repro.models.transformer import init_params, lm_loss
    monkeypatch.setattr(attention, "chunked_attention", functools.partial(
        attention.chunked_attention, score_dtype=jnp.float32))
    cfg = _cfg(small=True, compute_dtype="float32")
    model = run.reference_model(cfg)
    mcfg = program.model_config(cfg, 1)
    key = program.model_key(7)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 65), 0, 256)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(lambda p: model.loss(cfg, p, batch))(
            model.init_params(cfg, key))
        lp, gp = jax.value_and_grad(lambda p: lm_loss(mcfg, p, batch)[0])(
            init_params(mcfg, key))
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, atol=2e-5 * np.abs(b).max())


def _files():
    with open(os.path.join(BENCH, "traffic", "local-h8-4k.json")) as f:
        job = json.load(f)
    job.update(batch_per_node=2, seq_len=64)
    return {"cfg": _cfg(small=True), "job": job,
            "limits": {k: {"limit": v}
                       for k, v in bench_tiny.LIMITS.items()},
            "end_to_end": [{"name": n, "unit": "u"} for n in
                           ("tokens_per_s", "step_s_p90", "setup_s")],
            "per_layer": []}


def test_a_toy_run_of_the_cell_is_correct_and_the_control_is_not():
    """The whole cell at toy size through the harness (the chip check
    skipped): the sound program meets the toy limits, and the fp8 control,
    half the rows and an unchanged state do not."""
    import calibrate
    import check
    f = _files()
    r = bench_tiny.run(f, seed=2 ** 31 + 11)
    assert r["correct"], r["check"]
    assert r["attempted"] % f["job"]["H"] == 0
    got = calibrate.faults(copy.deepcopy(f), seed=2 ** 32 + 3,
                           devices=jax.devices()[:1])
    for name, numbers in got.items():
        assert not check.is_correct(check.judge(numbers, f["limits"])), name


def _ctx(ops, d_model=535_060_992):
    import tracefile as tr
    from types import SimpleNamespace
    window = program.Window(t0=0.0, done=[1.0, 2.0], host_s=[0.0, 0.0],
                            first_step=0, sync_flags=[False, False],
                            losses=[1.0, 1.0])
    trace = tr.Trace([tr.Device(0, ops, [])], [])
    return SimpleNamespace(trace=trace, window=window, interval=(0, 1000),
                           tokens_per_step=8192, chips=1, d_model=d_model,
                           peaks={"bf16_flops_per_s": 197e12})


def test_the_grouped_products_roofline_reader():
    """18 R d fe a step and expert layer, R the expected routings into the
    held experts (8,192 tokens x 6 x 8 / 64), at the bf16 peak, over the
    summed time of the moe_gmm kernels' events (both kinds)."""
    import tracefile as tr
    reader = run.reader("moe_gmm_roofline")
    flops = reader.gmm_flops(_cfg(), 8192)
    assert flops == 18 * 6144 * 2048 * 1408 * 4
    ops = [tr.Op("moe_gmm.3", 0, 300), tr.Op("moe_gmm_tgmm.1", 300, 500),
           tr.Op("fusion.2", 500, 900)]
    got = reader.read(_ctx(ops))
    assert got == pytest.approx(100 * 2 * flops / 197e12 / 500e-9)
    assert reader.read(_ctx([tr.Op("fusion.2", 0, 10)])) is None
    # another configuration's row: its widths are not these
    assert reader.read(_ctx(ops, d_model=463_987_712)) is None
