"""Every P rule (spmd_lint) fires on an intentionally-broken fixture and
stays silent on the clean twin.

The sharded fixtures are REAL compiled SPMD modules: a subprocess (the same
8-simulated-device pattern as test_dist_multidevice.py — XLA_FLAGS must be
set before jax initializes) compiles four small programs on a (4, 2)
(data, model) mesh and hands back their optimized HLO; the lint functions
then run in-process on that text. P3 exercises the real
``compiled_memory_stats`` on an in-process lowering. The repo gate runs the
serve-side P1-P4 audit exactly as CI does (``--engine none --spmd``).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import spmd_lint
from repro.core.engine import compiled_memory_stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AXES = [("data", 4), ("model", 2)]
ROLES = {"data": "batch", "model": "tensor"}

FIXTURE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    W = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)   # 4 MB
    X = jax.ShapeDtypeStruct((8, 1024), jnp.float32)

    def f(w, x):
        return x @ w

    hlos = {}
    # P1/P4 broken: w DECLARED P(None, 'model') by the test, but compiled
    # fully replicated here
    hlos["replicated"] = jax.jit(
        f, in_shardings=(ns(None, None), ns("data", None))
    ).lower(W, X).compile().as_text()
    # clean twin: compiled exactly as declared
    hlos["sharded"] = jax.jit(
        f, in_shardings=(ns(None, "model"), ns("data", None))
    ).lower(W, X).compile().as_text()

    # P2 broken: resharding dim0->dim1 over the batch ('data') axis moves
    # ~1 MB through an all-to-all no declared intent explains
    X2 = jax.ShapeDtypeStruct((4, 262144), jnp.float32)

    def reshard(x):
        return jax.lax.with_sharding_constraint(x, ns(None, "data"))

    hlos["reshard"] = jax.jit(
        reshard, in_shardings=(ns("data", None),)
    ).lower(X2).compile().as_text()

    # P2 clean twin: a model-axis ('tensor' role) all-reduce from a
    # contraction over the model-sharded dim — declared TP intent
    A = jax.ShapeDtypeStruct((256, 1024), jnp.float32)
    B = jax.ShapeDtypeStruct((1024, 256), jnp.float32)

    def tp_matmul(a, b):
        return a @ b

    hlos["tensor"] = jax.jit(
        tp_matmul, in_shardings=(ns(None, "model"), ns("model", None))
    ).lower(A, B).compile().as_text()

    print(json.dumps(hlos))
""")


@pytest.fixture(scope="module")
def hlos():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", FIXTURE_SCRIPT], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout)


# ------------------------------------------------------------------ helpers

def test_spec_shard_counts():
    sizes = dict(AXES)
    assert spmd_lint.spec_shard_counts(P(None, "model"), 2, sizes) == (1, 2)
    assert spmd_lint.spec_shard_counts(P("data"), 2, sizes) == (4, 1)
    assert spmd_lint.spec_shard_counts(
        P(("data", "model"), None), 2, sizes) == (8, 1)
    assert spmd_lint.spec_shard_counts(P(), 3, sizes) == (1, 1, 1)


# ------------------------------------------------------------------ P1

EXPECTED = [("w", P(None, "model"), 2), ("x", P("data", None), 2)]


def test_p1_silently_replicated_big_param_is_error(hlos):
    out, meta = spmd_lint.lint_param_shardings(
        hlos["replicated"], EXPECTED, AXES, program="t")
    assert len(out) == 1
    f = out[0]
    assert f.rule_id == "P1" and f.severity == "error"
    assert "silently replicated" in f.message and "(w, " in f.message
    assert meta["replicated_bytes"] == 4 * 1024 * 1024


def test_p1_matching_shardings_pass(hlos):
    out, meta = spmd_lint.lint_param_shardings(
        hlos["sharded"], EXPECTED, AXES, program="t")
    assert out == []
    assert meta["checked"] == 2 and meta["mismatches"] == 0


def test_p1_axis_drift_is_warning(hlos):
    # declared on the WRONG dim: actual (1, 2) vs want (2, 1) — drift, but
    # not replicated, so a warning not an error
    drifted = [("w", P("model", None), 2), ("x", P("data", None), 2)]
    out, _ = spmd_lint.lint_param_shardings(
        hlos["sharded"], drifted, AXES, program="t")
    assert len(out) == 1
    assert out[0].severity == "warning" and "drift" in out[0].message


def test_p1_leaf_count_mismatch_is_warning(hlos):
    out, _ = spmd_lint.lint_param_shardings(
        hlos["sharded"], EXPECTED[:1], AXES, program="t")
    assert len(out) == 1 and "leaf count" in out[0].message


def test_p1_unannotated_but_declared_sharded_fires():
    # single-device lowering: no sharding annotations at all; a declared-
    # sharded spec then has nothing backing it
    hlo = jax.jit(lambda x: x + 1.0).lower(
        jnp.ones((8, 8), jnp.float32)).compile().as_text()
    out, _ = spmd_lint.lint_param_shardings(
        hlo, [("x", P("data", None), 2)], AXES, program="t")
    assert len(out) == 1 and "no sharding annotation" in out[0].message
    clean, _ = spmd_lint.lint_param_shardings(
        hlo, [("x", P(), 2)], AXES, program="t")
    assert clean == []


# ------------------------------------------------------------------ P2

def test_p2_unexplained_batch_axis_reshard_fires(hlos):
    out, meta = spmd_lint.lint_reshards(
        hlos["reshard"], AXES, axis_roles=ROLES, program="t")
    assert out and all(f.rule_id == "P2" for f in out)
    assert "data" in out[0].message
    assert meta["unexplained_bytes"] > 0


def test_p2_gossip_role_is_r11_domain(hlos):
    # the same op, with the data axis declared as the gossip axis, belongs
    # to R11's bits budget — not a P2 finding
    out, meta = spmd_lint.lint_reshards(
        hlos["reshard"], AXES, axis_roles={"data": "gossip"}, program="t")
    assert out == []
    assert meta["gossip_domain_bytes"] > 0


def test_p2_allowance_covers_small_reshards(hlos):
    out, meta = spmd_lint.lint_reshards(
        hlos["reshard"], AXES, axis_roles=ROLES, program="t",
        allowance_bytes=1 << 30)
    assert out == []
    assert meta["small_reshard_bytes"] > 0


def test_p2_tensor_axis_allreduce_is_explained(hlos):
    out, meta = spmd_lint.lint_reshards(
        hlos["tensor"], AXES, axis_roles=ROLES, program="t")
    assert out == []
    assert meta["tensor_bytes"] > 0 and meta["unexplained_bytes"] == 0


# ------------------------------------------------------------------ P3

def test_p3_watermark_against_budget():
    compiled = jax.jit(lambda x: x * 2.0).lower(
        jnp.ones((256, 256), jnp.float32)).compile()
    mem = compiled_memory_stats(compiled)
    assert mem is not None and mem["peak_hbm_bytes"] > 0
    ok, meta = spmd_lint.lint_memory(mem, program="t")
    assert ok == [] and meta["budget_bytes"] == spmd_lint.HBM_BUDGET_BYTES
    bad, _ = spmd_lint.lint_memory(mem, program="t", budget_bytes=1)
    assert len(bad) == 1 and bad[0].rule_id == "P3"
    assert str(mem["peak_hbm_bytes"]) in bad[0].message


def test_p3_missing_analysis_is_warning():
    out, meta = spmd_lint.lint_memory(None, program="t")
    assert len(out) == 1 and out[0].severity == "warning"
    assert meta == {}


# ------------------------------------------------------------------ P4

def test_p4_replicated_must_shard_operand_fires(hlos):
    out, meta = spmd_lint.lint_serve_layout(
        hlos["replicated"], [(0, "cache")], program="t")
    assert len(out) == 1 and out[0].rule_id == "P4"
    assert "replicated" in out[0].message and meta["replicated"] == 1


def test_p4_sharded_operand_passes(hlos):
    out, meta = spmd_lint.lint_serve_layout(
        hlos["sharded"], [(0, "w"), (1, "x")], program="t")
    assert out == []
    assert meta == {"must_shard": 2, "replicated": 0}


def test_p4_missing_operand_fires(hlos):
    out, _ = spmd_lint.lint_serve_layout(
        hlos["sharded"], [(99, "ghost")], program="t")
    assert len(out) == 1 and "missing" in out[0].message


# ------------------------------------------------------------- repo gate

@pytest.mark.slow
def test_repo_gate_serve_spmd_audit_passes():
    """The committed serve lowerings pass P1-P4 — the CI command."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--engine", "none",
         "--spmd"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]
    assert "dist/serve_prefill: 0 error(s)" in out.stdout
    assert "dist/serve_decode: 0 error(s)" in out.stdout
