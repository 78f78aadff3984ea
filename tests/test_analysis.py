"""Every rule in the repro.analysis catalog fires on an intentionally-broken
fixture and stays silent on the clean twin.

The broken fixtures are REAL lowered programs wherever jax lets us build one
(a dtype-drifting donation genuinely drops the alias at compile; a
``jax.debug.print`` in a scan body genuinely lowers to a host-callback
custom-call inside the while loop); only the transfer ops jax never emits on
CPU (infeed, cross-memory-space copy-start) are spliced into real HLO text.
"""
import re
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import hlo_lint, jaxpr_lint
from repro.analysis.rules import (Report, apply_suppressions,
                                  default_suppressions, finding,
                                  render_report)
from repro.launch import hlo_walk


def _compiled_hlo(fn, *args, donate=()):
    with warnings.catch_warnings():
        # the broken-donation fixture provokes XLA's "buffer donor" warning
        # on purpose; the lint rule is what turns it into a failure
        warnings.simplefilter("ignore")
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile() \
                  .as_text()


# ------------------------------------------------------------------ R1

BIG = jnp.ones((512, 1024), jnp.float32)  # 2 MB: over the 1 MB threshold


def test_r1_clean_donation_passes():
    hlo = _compiled_hlo(lambda x: x + 1.0, BIG, donate=(0,))
    assert hlo_walk.parse_alias_map(hlo)  # sanity: alias really present
    assert hlo_lint.lint_donation(hlo, [0]) == []


def test_r1_fires_when_dtype_drift_drops_the_alias():
    # output dtype != input dtype -> XLA silently drops the donation
    hlo = _compiled_hlo(lambda x: x.astype(jnp.bfloat16) * 1, BIG,
                        donate=(0,))
    out = hlo_lint.lint_donation(hlo, [0], program="fixture")
    assert len(out) == 1
    assert out[0].rule_id == "R1" and out[0].severity == "error"


def test_r1_fires_per_param_when_one_alias_survives():
    def f(x, y):
        return x + 1.0, y.astype(jnp.bfloat16) * 1
    hlo = _compiled_hlo(f, BIG, BIG, donate=(0, 1))
    aliased = {p for p, _, _ in hlo_walk.parse_alias_map(hlo).values()}
    assert aliased == {0}  # x kept, y dropped
    out = hlo_lint.lint_donation(hlo, [0, 1])
    assert [f_.rule_id for f_ in out] == ["R1"]
    assert "parameter 1" in out[0].message


def test_r1_fires_on_alias_map_stripped_module():
    hlo = _compiled_hlo(lambda x: x + 1.0, BIG, donate=(0,))
    stripped = re.sub(r"input_output_alias=\{[^}]*\},?\s*", "", hlo)
    assert not hlo_walk.parse_alias_map(stripped)
    out = hlo_lint.lint_donation(stripped, [0])
    assert len(out) == 1 and "no input_output_alias" in out[0].message


def test_r1_ignores_small_unaliased_donations():
    small = jnp.ones((8, 8), jnp.float32)  # 256 B
    hlo = _compiled_hlo(lambda x, y: (x + 1.0, y.astype(jnp.bfloat16) * 1),
                        BIG, small, donate=(0, 1))
    assert hlo_lint.lint_donation(hlo, [0, 1]) == []


# ------------------------------------------------------------------ R2

def test_r2_fires_on_f64_outside_sanctioned_files():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x.astype(jnp.float64) * 2.0)(
            jnp.ones(4, jnp.float32))
    out = jaxpr_lint.lint_dtypes(closed, program="fixture")
    assert out and all(f.rule_id == "R2" for f in out)
    assert any("f64" in f.message for f in out)


def test_r2_sanctioned_file_is_exempt():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x.astype(jnp.float64) * 2.0)(
            jnp.ones(4, jnp.float32))
    # this test file is the emitting user frame; sanction it
    assert jaxpr_lint.lint_dtypes(
        closed, sanctioned_f64=("test_analysis.py",)) == []


def test_r2_fires_on_weak_scalar_leak():
    closed = jax.make_jaxpr(lambda x, s: x * s)(jnp.ones(3), 2.0)
    out = jaxpr_lint.lint_weak_scalars(closed)
    assert len(out) == 1 and "weak-typed scalar" in out[0].message


def test_r2_strong_scalar_passes():
    closed = jax.make_jaxpr(lambda x, s: x * s)(jnp.ones(3), jnp.ones(()))
    assert jaxpr_lint.lint_weak_scalars(closed) == []


def test_r2_carry_dtype_drift():
    a = [jax.ShapeDtypeStruct((4,), jnp.bfloat16)]
    b = [jax.ShapeDtypeStruct((4,), jnp.float32)]
    out = jaxpr_lint.lint_carry_dtypes(a, b, labels=["x_hat"])
    assert len(out) == 1 and "bfloat16 -> float32" in out[0].message


def test_r2_carry_shape_and_structure_drift():
    a = [jax.ShapeDtypeStruct((4,), jnp.float32)]
    b = [jax.ShapeDtypeStruct((8,), jnp.float32)]
    assert "shape" in jaxpr_lint.lint_carry_dtypes(a, b)[0].message
    assert "structure" in jaxpr_lint.lint_carry_dtypes(a, a + a)[0].message
    assert jaxpr_lint.lint_carry_dtypes(a, list(a)) == []


# ------------------------------------------------------------------ R3

def test_r3_fires_on_alternating_scalar_types():
    counter = jaxpr_lint.TraceCounter(lambda x, s: x * s)
    jf = jax.jit(counter)
    vals = iter([2, 2.0])  # int-weak then float-weak: two cache keys
    out = jaxpr_lint.audit_retrace(
        lambda: jf(jnp.ones(3), next(vals)), counter, calls=2)
    assert len(out) == 1 and out[0].rule_id == "R3"
    assert "2 traces" in out[0].message


def test_r3_clean_repeat_call_passes():
    counter = jaxpr_lint.TraceCounter(lambda x: x + 1)
    jf = jax.jit(counter)
    assert jaxpr_lint.audit_retrace(lambda: jf(jnp.ones(3)), counter,
                                    calls=3) == []
    assert counter.count == 1


def test_r3_engine_runner_traces_once():
    from repro.core import sparq
    from repro.core.compression import TopFrac
    from repro.core.engine import make_runner
    from repro.core.schedule import decaying, fixed
    from repro.core.topology import make_topology

    cfg = sparq.SparqConfig(topology=make_topology("ring", 4),
                            compressor=TopFrac(0.25),
                            threshold=decaying(1.0, 10.0),
                            lr=fixed(0.05), H=2, gamma=0.3, momentum=0.9)
    step = sparq.make_step(cfg, lambda x, t, key: x)
    runner = make_runner(step, 4, record_every=2,
                         eval_fn=lambda x: jnp.mean(x * x))
    key = jax.random.PRNGKey(0)
    for _ in range(2):  # fresh donated state each call, same shapes
        runner(cfg.init_state(jnp.zeros((4, 32), jnp.float32)), key)
    assert runner.trace_count() == 1


# ------------------------------------------------------------------ R4

def _scan_hlo(with_callback: bool) -> str:
    def body(c, _):
        if with_callback:
            jax.debug.print("s={s}", s=c.sum())
        return c + 1.0, None
    return _compiled_hlo(
        lambda x: jax.lax.scan(body, x, None, length=4)[0],
        jnp.ones(8, jnp.float32))


def test_r4_fires_on_debug_callback_in_scan_body():
    out = hlo_lint.lint_transfers(_scan_hlo(True), program="fixture")
    assert out and all(f.rule_id == "R4" for f in out)
    assert any("callback" in f.message for f in out)


def test_r4_clean_scan_passes():
    assert hlo_lint.lint_transfers(_scan_hlo(False)) == []


def _inject_into_while_body(hlo: str, line: str) -> str:
    """Splice an instruction line into a while-reachable computation of a
    real module (for ops jax never emits on CPU: infeed, S()-copy-start)."""
    target = sorted(hlo_walk.while_reachable(hlo))[0]
    out, cur = [], None
    for raw in hlo.splitlines():
        out.append(raw)
        m = hlo_walk._HDR_RE.match(raw.strip())
        if m and ("->" in raw or m.group(1)):
            cur = m.group(2)
            if cur == target:
                out.append("  " + line)
    return "\n".join(out)


def test_r4_fires_on_infeed_in_while_body():
    hlo = _inject_into_while_body(
        _scan_hlo(False),
        "%inf = ((f32[8]{0}, token[])) infeed(token[] %tok)")
    out = hlo_lint.lint_transfers(hlo)
    assert len(out) == 1 and "`infeed`" in out[0].message


def test_r4_copy_start_needs_memory_space_annotation():
    plain = ("%cp = (f32[8]{0}, f32[8]{0}, u32[]) "
             "copy-start(f32[8]{0} %add.1)")
    host = ("%cp = (f32[8]{0:S(5)}, f32[8]{0}, u32[]) "
            "copy-start(f32[8]{0} %add.1)")
    base = _scan_hlo(False)
    assert hlo_lint.lint_transfers(_inject_into_while_body(base, plain)) == []
    out = hlo_lint.lint_transfers(_inject_into_while_body(base, host))
    assert len(out) == 1 and "`copy-start`" in out[0].message


def test_r4_scope_override_audits_outside_while():
    # a callback OUTSIDE any scan is fine by default, flagged with scope=all
    def f(x):
        jax.debug.print("x0={s}", s=x[0])
        return x + 1.0
    hlo = _compiled_hlo(f, jnp.ones(8, jnp.float32))
    assert hlo_lint.lint_transfers(hlo) == []
    everything = hlo_walk.computation_bodies(hlo)
    out = hlo_lint.lint_transfers(hlo, scope=everything)
    assert out and "callback" in out[0].message


def test_r4_internal_custom_calls_not_flagged():
    # XLA lowers TopK to an internal custom-call on CPU — must NOT count
    def body(c, _):
        v, _i = jax.lax.top_k(c, 4)
        return c + v.sum(), None
    hlo = _compiled_hlo(
        lambda x: jax.lax.scan(body, x, None, length=4)[0],
        jnp.ones(32, jnp.float32))
    if "custom-call" not in hlo:
        pytest.skip("backend inlined top_k; nothing to assert")
    assert hlo_lint.lint_transfers(hlo) == []


# ------------------------------------------------------------------ R5

def test_r5_fires_when_interpret_flag_set():
    out = hlo_lint.lint_pallas("ENTRY e { ROOT a = f32[] add(b, c) }",
                               use_kernel=True, interpret=True)
    assert len(out) == 1 and out[0].rule_id == "R5"
    assert "interpret" in out[0].message


def test_r5_fires_when_no_kernel_call_in_module():
    out = hlo_lint.lint_pallas("ENTRY e { ROOT a = f32[] add(b, c) }",
                               use_kernel=True, interpret=False)
    assert len(out) == 1 and "no Pallas custom call" in out[0].message


def test_r5_passes_with_real_kernel_call():
    hlo = ('ENTRY e { ROOT a = f32[] custom-call(b), '
           'custom_call_target="tpu_custom_call" }')
    assert hlo_lint.lint_pallas(hlo, use_kernel=True, interpret=False) == []


def test_r5_silent_without_kernel_request():
    assert hlo_lint.lint_pallas("ENTRY e { }",
                                use_kernel=False, interpret=True) == []


# --------------------------------------------------- suppressions / report

def test_suppression_string_form_suppresses_rule():
    fs = [finding("R5", "interpret-mode"), finding("R1", "unaliased")]
    apply_suppressions(fs, {"R5": "documented fallback"})
    assert fs[0].suppressed and fs[0].suppression_reason
    assert not fs[1].suppressed


def test_suppression_match_form_is_selective():
    fs = [finding("R4", "infeed inside body"),
          finding("R4", "callback inside body")]
    apply_suppressions(fs, {"R4": {"match": "infeed", "reason": "known"}})
    assert fs[0].suppressed and not fs[1].suppressed


def test_default_suppressions_empty_on_every_backend():
    # the compiled XLA leg is the sanctioned off-TPU lowering now, so no
    # backend ships a default waiver: interpret-only findings are hard errors
    for backend in ("cpu", "gpu", "tpu"):
        assert default_suppressions(backend) == {}


def test_r5_silent_on_sanctioned_xla_leg():
    # lowering="xla" is a compiled leg with deliberately no custom call —
    # R5's no-Pallas-custom-call check does not apply to it
    assert hlo_lint.lint_pallas("ENTRY e { ROOT a = f32[] add(b, c) }",
                                use_kernel=True, interpret=False,
                                lowering="xla") == []
    # ... but the interpreter is still flagged when named explicitly
    out = hlo_lint.lint_pallas("ENTRY e { ROOT a = f32[] add(b, c) }",
                               use_kernel=True, interpret=False,
                               lowering="interpret")
    assert len(out) == 1 and out[0].rule_id == "R5"


def test_report_ok_tracks_unsuppressed_errors():
    r = Report(program="p").extend([finding("R1", "boom")])
    assert not r.ok and r.counts()["errors"] == 1
    apply_suppressions(r.findings, {"R1": "waived"})
    assert r.ok and r.counts() == {"errors": 0, "warnings": 0, "info": 0,
                                   "suppressed": 1}


def test_render_report_document_shape():
    r = Report(program="p", meta={"backend": "cpu"})
    r.extend([finding("R5", "interpret-mode leak")])
    # defaults are {} on every backend now — waivers must be explicit
    sup = {"R5": "test waiver: fixture exercises the suppressed rendering"}
    apply_suppressions(r.findings, sup)
    doc = render_report([r], sup, extra={"jax_version": jax.__version__})
    assert doc["ok"] and doc["schema_version"] == 4
    assert set(doc["rules"]) == {"R1", "R2", "R3", "R4", "R5",
                                 "R6", "R7", "R8", "R9", "R10", "R11",
                                 "S1", "S2", "S3", "S4", "S5", "S6",
                                 "K1", "K2", "K3", "K4",
                                 "P1", "P2", "P3", "P4"}
    assert doc["programs"][0]["counts"]["suppressed"] == 1
    assert doc["jax_version"] == jax.__version__


def test_run_lint_counts_unsuppressed_errors_only(capsys):
    hlo = _compiled_hlo(lambda x: x.astype(jnp.bfloat16) * 1, BIG,
                        donate=(0,))
    res = hlo_lint.run_lint(hlo, donated_params=[0], use_kernel=True,
                            interpret=True, program="fixture")
    # BOTH R1 and the R5 interpret finding count: default_suppressions is
    # empty on every backend now, so interpret-only is a hard error on CPU
    assert res["errors"] == 2
    ids = {f["rule_id"]: f["suppressed"] for f in res["findings"]}
    assert ids["R1"] is False and ids["R5"] is False
    assert "[lint R1/ERROR]" in capsys.readouterr().out
