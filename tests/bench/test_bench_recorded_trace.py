"""Each per-layer reader on a trace recorded on the chip
(``benchmarks/chip/testdata``: a reduced qwen1.5-0.5b as a 4-node ring on
one TPU v5e, six traced steps at H = 2), against what the harness read from
it when it was recorded (``tools/record_testdata.py``)."""
import json
import os

import pytest

import tracefile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
DATA = os.path.join(ROOT, "benchmarks", "chip", "testdata")


@pytest.fixture(scope="module")
def recorded():
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip", "tools"))
    import record_testdata
    with open(os.path.join(DATA, "ring4_reduced.json")) as f:
        rec = json.load(f)
    trace = tracefile.load(os.path.join(DATA, "ring4_reduced.xplane.pb"))
    ctx = record_testdata.context(trace, rec["window"], rec["device_kind"])
    return rec, ctx, record_testdata


def test_the_trace_holds_one_chip_and_the_harness_spans(recorded):
    rec, ctx, _ = recorded
    assert [d.index for d in ctx.trace.devices] == [0]
    assert ctx.interval is not None
    names = {s.name for s in ctx.trace.host}
    assert names == set(tracefile.HOST_SPANS)
    execs = tracefile.step_executions(ctx.trace.devices[0], ctx.interval)
    assert len(execs) == len(rec["window"]["done"])


@pytest.mark.parametrize("metric", [
    "host_batch_ms", "device_idle_share", "local_step_ms", "sync_step_ms",
    "sign_topk_roofline", "mfu"])
def test_reader_reads_what_was_recorded(recorded, metric):
    rec, ctx, tool = recorded
    got = tool.read_all(ctx)[metric]
    want = rec["readings"][metric]
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)


def test_shares_are_shares(recorded):
    rec, _, _ = recorded
    r = rec["readings"]
    assert 0.0 < r["sign_topk_roofline"] <= 100.0
    assert 0.0 < r["mfu"] <= 100.0
    assert 0.0 <= r["device_idle_share"] < 100.0
    assert r["sync_step_ms"] > 0 and r["local_step_ms"] > 0
