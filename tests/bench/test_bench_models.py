"""The configurations' sizes, FLOPs per token and plain references against
the program's own model, without a chip."""
import json
import math
import os

import jax
import numpy as np
import pytest

import program
import run
from reference import sparq

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
CONFIGS = ["qwen1.5-0.5b", "mamba2-370m"]


def _cfg(name):
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _program_count(cfg):
    """Parameters of the program's own model, by ``jax.eval_shape`` of its
    ``init_params`` (nothing is allocated)."""
    from repro.models.transformer import init_params
    mcfg = program.model_config(cfg, 1)
    shapes = jax.eval_shape(lambda k: init_params(mcfg, k),
                            jax.random.PRNGKey(0))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)), mcfg


@pytest.mark.parametrize("name", CONFIGS)
def test_parameter_count_matches_the_program(name):
    cfg = _cfg(name)
    model = run.reference_model(cfg)
    count, mcfg = _program_count(cfg)
    assert model.n_params(cfg) == count
    segs = sparq.layout(model.init_params, cfg)
    assert segs[-1][1] + segs[-1][2] == count
    assert model.sizes(cfg)["V"] == mcfg.vocab_size


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_per_token_from_an_independent_count(name):
    cfg = _cfg(name)
    model = run.reference_model(cfg)
    count, mcfg = _program_count(cfg)
    head = mcfg.vocab_size * mcfg.d_model    # counted once, as the head
    n = count - head * (0 if mcfg.tie_embeddings else 1)
    seq = 2048
    if name.startswith("qwen"):
        extra = (12 * mcfg.n_layers * mcfg.n_heads * mcfg.resolved_head_dim
                 * seq)
    else:
        q = mcfg.ssm_chunk
        hp = mcfg.ssm_heads * mcfg.ssm_head_dim
        extra = 3 * mcfg.n_layers * 2 * (
            q * mcfg.ssm_groups * mcfg.ssm_state + q * hp
            + 2 * hp * mcfg.ssm_state)
    assert model.flops_per_token(cfg, seq) == pytest.approx(6 * n + extra,
                                                           rel=1e-12)
    # the published scale: ~0.46e9 and ~0.37e9 parameters
    assert 0.3e9 < count < 0.5e9


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_weights_are_the_programs(name):
    """At a small size the reference draws, from the same seed, the very
    row the program's initialisation makes (the layout and the values)."""
    from repro.models.transformer import init_params
    cfg = dict(_cfg(name))
    small = (
        {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "vocab_size": 128}
        if name.startswith("qwen") else
        {"d_model": 64, "n_layer": 2, "vocab_size": 128, "d_state": 16,
         "headdim": 16, "chunk_size": 16})
    cfg.update(small)
    over = dict(cfg["program"]["overrides"])
    over.update({"n_layers": 2, "d_model": 64, "vocab_size": 128},
                **({"n_heads": 4, "n_kv_heads": 4, "d_ff": 96}
                   if name.startswith("qwen") else
                   {"ssm_state": 16, "ssm_head_dim": 16, "ssm_chunk": 16}))
    cfg["program"] = {"arch": cfg["program"]["arch"], "overrides": over}
    model = run.reference_model(cfg)
    key = program.model_key(2 ** 33 + 5)
    mine = jax.tree.leaves(model.init_params(cfg, key))
    theirs = jax.tree.leaves(init_params(program.model_config(cfg, 1), key))
    assert len(mine) == len(theirs)
    for a, b in zip(mine, theirs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_seeds_past_32_bits_give_other_weights():
    a = np.asarray(program.model_key(7))
    b = np.asarray(program.model_key(2 ** 33 + 7))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, np.asarray(program.model_key(7)))


@pytest.mark.parametrize("config,traffic", [
    ("qwen1.5-0.5b", "sync-every-step"), ("mamba2-370m", "local-h8")])
def test_the_program_starts_from_the_reference_row(config, traffic):
    """The harness builds the program from a seed as a run does, and its
    initial flat row is the reference's, element by element."""
    import bench_tiny
    import traffic as traffic_mod
    f = bench_tiny.files(config, traffic)
    cfg, job = f["cfg"], f["job"]
    seed = 2 ** 33 + 5
    mcfg = program.model_config(cfg, int(job["nodes"]))
    gen = traffic_mod.from_spec(job, mcfg.vocab_size, seed)
    prog = program.build(mcfg, job, jax.devices()[:1], seed,
                         gen.global_batch(0))
    row = program.flat_rows(prog.state["params"])
    model = run.reference_model(cfg)
    want = np.asarray(sparq.initial_row(model, cfg, row.shape[1])(seed))
    assert row.shape[0] == 1
    np.testing.assert_array_equal(row[0], want)
    # another seed, past 32 bits, gives another row
    prog.reset(seed + 2 ** 32)
    assert not np.array_equal(program.flat_rows(prog.state["params"])[0],
                              want)
