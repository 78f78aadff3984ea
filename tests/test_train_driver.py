"""The train driver's in-process entry (`launch/train.run`) and its compile
cache placement.

The driver used to clamp the node count to the device count, so ``--nodes 4``
on one device silently trained a single node that never gossiped."""
import os
import subprocess
import sys

import numpy as np

from repro.launch import train

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_run_honours_nodes_above_device_count():
    import jax
    assert len(jax.devices()) == 1
    res = train.run(train.parse_args([
        "--reduced", "--nodes", "4", "--steps", "5", "--H", "5",
        "--seq-len", "16", "--batch-per-node", "1", "--use-kernel"]))
    assert res.train_step.n_nodes == 4
    assert res.state["params"].shape[0] == 4
    m = res.metrics
    assert m["sync_rounds"] == 1 and m["triggers"] > 0
    assert m["bits"] > 0
    assert np.isfinite(m["loss"])
    assert res.compile_seconds > 0 and res.seconds_per_step > 0


def _cache_dir_after_run(tmp_path, env_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    code = ("import jax\n"
            "from repro.launch import train\n"
            "train.setup_compile_cache()\n"
            + ("jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(3))\n"
               if env_dir else "")
            + "print(jax.config.jax_compilation_cache_dir)\n")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.strip().splitlines()[-1]


def test_compile_cache_follows_env_var(tmp_path):
    """Set: JAX's own setting is the directory in use, and entries land
    there. Unset: a fixed directory of the checkout, whatever the cwd."""
    cache = tmp_path / "cache"
    assert _cache_dir_after_run(tmp_path, str(cache)) == str(cache)
    assert any(cache.iterdir())
    assert (_cache_dir_after_run(tmp_path, None)
            == os.path.join(ROOT, ".jax_cache"))


def _host_spans(log_dir):
    """The host planes' ``train`` step spans of the trace under log_dir, as
    their step numbers, and the names of all host spans."""
    import glob
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1, found
    steps, names = [], set()
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                names.add(e.name)
                if e.name == "train":
                    steps.append(int(dict(e.stats)["step_num"]))
    return sorted(steps), names


def test_profile_steps_trace_the_named_steps(tmp_path):
    out = tmp_path / "trace"
    res = train.run(train.parse_args([
        "--reduced", "--nodes", "1", "--steps", "4", "--H", "2",
        "--seq-len", "16", "--batch-per-node", "1",
        "--profile-dir", str(out), "--profile-steps", "1:3"]))
    assert res.metrics is not None
    steps, names = _host_spans(out)
    assert steps == [1, 2]
    assert {"batch", "device_put", "dispatch", "wait"} <= names


def test_without_profile_steps_no_trace_starts(monkeypatch, tmp_path):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append(a))
    monkeypatch.chdir(tmp_path)
    res = train.run(train.parse_args([
        "--reduced", "--nodes", "1", "--steps", "2", "--H", "2",
        "--seq-len", "16", "--batch-per-node", "1"]))
    assert res.metrics is not None
    assert calls == [] and list(tmp_path.iterdir()) == []


def test_profile_steps_need_a_directory_and_a_range():
    import pytest
    for argv in (["--profile-steps", "1:3"], ["--profile-dir", "d"],
                 ["--profile-dir", "d", "--profile-steps", "3:1"],
                 ["--profile-dir", "d", "--profile-steps", "x"]):
        with pytest.raises(SystemExit):
            train.profile_range(train.parse_args(argv))
    assert train.profile_range(train.parse_args([])) is None
