"""Checkpoint-resume regressions for the train driver.

The driver used to checkpoint only state["params"], so a resumed run silently
reset optimizer momentum, the step counter t, and the bits/trigger accounting.
It now round-trips the FULL train state through checkpoint/ckpt.py; --resume
restores onto the state shardings and continues the exact trajectory. Also
covers the `--steps 0` empty-run path (the final log line used to hit
NameError on the undefined loop variable)."""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs.registry import get_config
from repro.core.schedule import fixed
from repro.core.triggers import zero
from repro.dist import sharding as sh
from repro.dist.sparq_dist import DistSparqConfig, build_sparq
from repro.launch.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _engine():
    cfg = dataclasses.replace(
        get_config("qwen1.5-0.5b").reduced(n_layers=1, d_model=128, vocab=256),
        n_nodes=4)
    prod = make_mesh((1, 1), ("data", "model"))
    mesh = sh.train_mesh(prod, cfg)
    # momentum > 0 so the opt subtree carries real (non-empty) buffers —
    # exactly the state the old params-only checkpoint lost
    dcfg = DistSparqConfig(H=2, variant="dense", frac=0.25, threshold=zero(),
                           lr=fixed(0.05), gamma=0.3, momentum=0.9)
    init_fn, train_step, _, _ = build_sparq(cfg, mesh, dcfg)
    rng = np.random.default_rng(0)
    batch = {k: rng.integers(0, cfg.vocab_size, (4, 2, 16)).astype(np.int32)
             for k in ("tokens", "labels")}
    return init_fn, jax.jit(train_step), batch


def test_full_state_checkpoint_roundtrip(tmp_path):
    """Every leaf of the train state — params, x_hat, opt momentum buffers,
    t, bits/bits_c, sync_rounds, triggers — survives save/restore exactly."""
    init_fn, step, batch = _engine()
    state = init_fn(jax.random.PRNGKey(0))
    for _ in range(3):
        state, _ = step(state, batch)
    assert int(state["t"]) == 3 and float(state["bits"]) > 0

    ckpt.save(str(tmp_path), 3, jax.device_get(state))
    assert ckpt.latest_step(str(tmp_path)) == 3

    fresh = init_fn(jax.random.PRNGKey(0))   # a fresh t=0 state to restore onto
    restored = ckpt.restore(str(tmp_path), 3, like=fresh)

    flat_a = jax.tree_util.tree_leaves_with_path(state)
    flat_b = jax.tree_util.tree_leaves_with_path(restored)
    assert [p for p, _ in flat_a] == [p for p, _ in flat_b]
    for (path, a), (_, b) in zip(flat_a, flat_b, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))
    # the scalars the old params-only checkpoint silently reset
    assert int(restored["t"]) == 3
    assert int(restored["sync_rounds"]) == int(state["sync_rounds"])
    assert int(restored["triggers"]) == int(state["triggers"])
    assert float(restored["bits"]) == float(state["bits"])
    # momentum buffers are real data, not zeros
    opt_norm = sum(float(np.abs(np.asarray(leaf)).sum())
                   for leaf in jax.tree_util.tree_leaves(restored["opt"]))
    assert opt_norm > 0


def test_resumed_trajectory_matches_unbroken_run(tmp_path):
    """save at t=2, restore, run 2 more == one unbroken 4-step run."""
    init_fn, step, batch = _engine()
    state = init_fn(jax.random.PRNGKey(0))
    for _ in range(2):
        state, _ = step(state, batch)
    ckpt.save(str(tmp_path), 2, jax.device_get(state))
    for _ in range(2):
        state, _ = step(state, batch)          # unbroken steps 3-4

    resumed = ckpt.restore(str(tmp_path), 2, like=init_fn(jax.random.PRNGKey(0)))
    for _ in range(2):
        resumed, _ = step(resumed, batch)      # resumed steps 3-4

    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(resumed), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _run_train(args, timeout=600):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--reduced",
         "--seq-len", "32", "--batch-per-node", "1"] + args,
        env=env, capture_output=True, text=True, timeout=timeout)


def test_train_steps_zero_exits_cleanly():
    """--steps 0 used to crash with NameError on the final metrics log."""
    r = _run_train(["--steps", "0"])
    assert r.returncode == 0, r.stderr[-3000:]
    assert "no steps run" in r.stdout
    assert "NameError" not in r.stderr


@pytest.mark.slow
def test_train_resume_e2e(tmp_path):
    """Full driver: run 2 steps with checkpointing, then --resume 2 more;
    the resumed process reports the restored step counter and bits."""
    ck = str(tmp_path / "ck")
    r1 = _run_train(["--steps", "2", "--ckpt-dir", ck, "--ckpt-every", "2",
                     "--momentum", "0.9", "--log-every", "1"])
    assert r1.returncode == 0, r1.stderr[-3000:]
    assert ckpt.latest_step(ck) == 2
    r2 = _run_train(["--steps", "4", "--ckpt-dir", ck, "--ckpt-every", "2",
                     "--momentum", "0.9", "--log-every", "1", "--resume"])
    assert r2.returncode == 0, r2.stderr[-3000:]
    assert "resumed full train state from step 2 (t=2" in r2.stdout
    assert ckpt.latest_step(ck) == 4
    # resuming past the end is the empty-run path, not a crash
    r3 = _run_train(["--steps", "4", "--ckpt-dir", ck, "--momentum", "0.9",
                     "--resume"])
    assert r3.returncode == 0, r3.stderr[-3000:]
    assert "no steps run" in r3.stdout
