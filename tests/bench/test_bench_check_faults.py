"""A whole run of each one-chip cell at toy size on the CPU, the chip check
skipped: a sound program comes out correct, and the timed path broken
underneath (a step that returns its state unchanged; half of each node's
rows left out, the mean taken over the rest) comes out not correct."""
import pytest

import bench_tiny

CELLS = [("qwen1.5-0.5b", "sync-every-step"), ("mamba2-370m", "local-h8")]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_sound_program_is_correct(config, traffic):
    f = bench_tiny.files(config, traffic)
    r = bench_tiny.run(f, seed=2 ** 31 + 11)
    assert r["correct"], r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    # the window is whole sync periods
    assert r["attempted"] % f["job"]["H"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "step_s_p90", "setup_s"}
    assert list(r)[-1] == "check"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("config,traffic", CELLS)
def test_broken_program_is_not_correct(monkeypatch, config, traffic, fault):
    with bench_tiny.planted(monkeypatch, fault):
        r = bench_tiny.run(bench_tiny.files(config, traffic),
                           seed=2 ** 31 + 13)
    assert not r["correct"], r["check"]


def test_a_listed_metric_that_reads_nothing_gives_no_result():
    """A traced run whose trace holds no chip (the CPU) finds nothing for
    the kernel's reader, which the cell lists: the run ends with no
    result rather than leave the metric out."""
    import jax
    import run as harness
    f = bench_tiny.files("qwen1.5-0.5b", "sync-every-step")
    f["per_layer"] = [{"name": "sign_topk_roofline", "unit": "%"}]
    with pytest.raises(harness.BenchError, match="sign_topk_roofline"):
        harness.run_cell(f, seed=2 ** 31 + 17, seconds=0.5, trace=True,
                         devices=jax.devices()[:1], require_pallas=False,
                         peaks=bench_tiny.PEAKS)
