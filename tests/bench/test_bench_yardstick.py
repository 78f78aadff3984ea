"""The yardstick without a chip: the peaks table, the kernel's byte count,
the trace's interval arithmetic and the readers on a synthetic trace."""
from types import SimpleNamespace

import numpy as np
import pytest

import program
import run
import tracefile as tr


def test_peaks_refuse_an_unknown_device_kind():
    with pytest.raises(run.BenchError):
        run.peaks_of("TPU v9 imaginary")
    with pytest.raises(run.BenchError):
        run.peaks_of("source")
    v5e = run.peaks_of("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_kernel_bytes_is_one_read_and_the_kept_entries():
    kb = run.reader("sign_topk_roofline").kernel_bytes
    from reference.sparq import payload_bits
    d = 463_987_712
    tiles = d // 1024
    # which 103 of 1,024 (log2 C(1024, 103) = 477.6 bits), 103 signs, a scale
    assert kb(d, 0.1) == 4 * d + tiles * (477 + 103 + 32) // 8
    # under the program's own payload, over the read alone
    assert 4 * d < kb(d, 0.1) < 4 * d + payload_bits(d, 0.1) / 8
    # every entry kept: the support says nothing, signs and scale remain
    assert kb(2048, 1.0) == 4 * 2048 + 2 * (1024 + 32) // 8
    assert "lower bound" in kb.__doc__


def test_interval_arithmetic():
    u = tr.union([(5, 7), (0, 2), (1, 3), (10, 12)], clip=(0, 11))
    assert u == [(0, 3), (5, 7), (10, 11)]
    assert tr.length(u) == 6
    assert tr.minus([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5), (6, 10)]
    assert tr.minus([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.minus([(0, 4)], []) == [(0, 4)]


def _synthetic():
    """One chip, 4 steps (H = 2): step k runs [100k, 100k + 60) ns, the
    kernel runs 10 ns in each sync step, a collective 8 ns of which 3 ns
    overlap the kernel."""
    ops, mods = [], []
    for k in range(4):
        s = 100.0 * k
        mods.append(tr.Op(f"jit_train_step({k})", s, s + 60))
        ops.append(tr.Op("fusion.1", s, s + 40))
        if k % 2 == 1:
            ops.append(tr.Op("sign_topk_blocks.2", s + 40, s + 50))
            ops.append(tr.Op("collective-permute-start", s + 47, s + 55))
    host = [tr.Op("batch", -10, -5), tr.Op("wait", 390, 400)]
    trace = tr.Trace([tr.Device(0, ops, mods)], host)
    window = program.Window(t0=0.0, done=[1.0, 2.0, 3.0, 4.0],
                            host_s=[0.001, 0.003, 0.001, 0.003],
                            first_step=0, sync_flags=[False, True] * 2,
                            losses=[1.0] * 4)
    return SimpleNamespace(
        trace=trace, window=window, interval=trace.window,
        tokens_per_step=1000, chips=1, flops_per_token=1e9,
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        d_model=1000, frac=0.1, nodes_per_device=1)


@pytest.mark.parametrize("metric,want", [
    ("host_batch_ms", 2.0),
    ("device_idle_share", 100.0 * (1 - (40 + 55 + 40 + 55) / 410)),
    ("local_step_ms", 60e-6),
    ("sync_step_ms", 60e-6),
    ("sign_topk_roofline", 100.0 * 2 * 4000 / 1e11 / 20e-9),
    ("mfu", 100.0 * 1e9 * 1000 / 1e12),
])
def test_readers_on_a_synthetic_trace(metric, want):
    assert run.reader(metric).read(_synthetic()) == pytest.approx(want)


def test_readers_find_nothing_where_nothing_is():
    ctx = _synthetic()
    ctx.trace.devices[0].ops = [o for o in ctx.trace.devices[0].ops
                                if o.name == "fusion.1"]
    assert run.reader("sign_topk_roofline").read(ctx) is None
    ctx.window.sync_flags = [False] * 4
    assert run.reader("sync_step_ms").read(ctx) is None


def test_op_totals_take_a_body_off_its_loop():
    ops = [tr.Op("while.1", 0, 100), tr.Op("fusion.2", 10, 30),
           tr.Op("fusion.3", 40, 50), tr.Op("copy.4", 120, 130)]
    trace = tr.Trace([tr.Device(0, ops, [])], [])
    got = tr.op_totals(trace, (0, 125))
    assert got == pytest.approx({"while.1": 70e-9, "fusion.2": 20e-9,
                                 "fusion.3": 10e-9, "copy.4": 5e-9})


def test_op_names_are_the_instruction_names():
    assert tr._op_name("%sign_topk_blocks.2 = (f32[8,1024]) custom-call("
                       "f32[8,1024] %fusion.3)") == "sign_topk_blocks.2"
    assert tr._op_name("jit_train_step(7)") == "jit_train_step(7)"


def test_breakdown_names_what_the_host_did():
    ctx = _synthetic()
    b = run.breakdown(ctx.trace, ctx.interval)
    assert b["device_ops"][0][0] == "fusion.1"
    assert len(b["idle_gaps"]) <= 10 and b["idle_gaps"][0][1] > 0


def test_host_norms_sum_in_float64():
    from reference.sparq import norm64, seg_norms
    rng = np.random.default_rng(0)
    x = rng.standard_normal(3_000_000).astype(np.float32)
    want = float(np.sqrt(np.sum(x.astype(np.float64) ** 2)))
    assert norm64(x, chunk=1 << 16) == pytest.approx(want, rel=1e-12)
    got = seg_norms(x, [("a", 0, 10), ("b", 10, x.size - 10)])
    assert got[0] == pytest.approx(np.linalg.norm(x[:10].astype(np.float64)))
