"""The control at toy size: the plain reference computed with its matmuls
in fp8, put in the program's place, fails the cell's check; so does each
fault put in the reference."""
import jax
import pytest

import bench_tiny
import calibrate
import check

CELLS = [("qwen1.5-0.5b", "sync-every-step"), ("mamba2-370m", "local-h8")]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_control_and_faults_fail(config, traffic):
    f = bench_tiny.files(config, traffic)
    got = calibrate.faults(f, seed=2 ** 32 + 3, devices=jax.devices()[:1])
    assert set(got) >= {"control", "half_batch", "unchanged"}
    for name, numbers in got.items():
        judged = check.judge(numbers, f["limits"])
        assert not check.is_correct(judged), (name, numbers)
