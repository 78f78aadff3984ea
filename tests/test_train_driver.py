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
