"""Golden-trace regression harness: fixed-seed short runs pinned against
committed trajectories.

Tier-1 equivalence tests (engine == loop, dist == reference) catch the two
sides drifting apart, but a numerics regression that moves BOTH sides the
same way — a changed reduction order in the scan engines, a silently
different PRNG split, a broken compressor — sails straight through them.
This module closes that hole: every record point of a short SPARQ / SQuARM /
CHOCO / faulty-SPARQ run is compared field-for-field against
``tests/golden/<case>.json``, including a final-iterate fingerprint, so any
silent trajectory change fails loudly.

Regenerate with ``pytest tests/test_golden_traces.py --regen-golden`` ONLY
when the numerics are supposed to move (new algorithmic default, changed
accumulation order) and commit the JSON diff alongside the change that
explains it — see the README "Testing" section.

Comparison tolerances: integer channels (t, sync_rounds, triggers) and bit
totals are exact; losses and the iterate fingerprint allow small float slack
(rtol 2e-4) for cross-platform BLAS/codegen variation — real regressions
move trajectories by far more.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines
from repro.core.compression import SignTopK
from repro.core.faults import DropoutWindow, FaultPlan
from repro.core.schedule import decaying
from repro.core.sparq import SparqConfig, run, squarm_config
from repro.core.topology import make_topology
from repro.core.triggers import piecewise
from repro.data.synthetic import convex_dataset, logistic_loss_and_grad

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "golden")
N, F, C = 6, 16, 4
D = F * C
T, REC = 60, 10


def _problem():
    X, Y = convex_dataset(N, 40, n_features=F, n_classes=C, seed=0)
    Xj, Yj = jnp.asarray(X), jnp.asarray(Y)
    _, make_grad_fn, full_loss = logistic_loss_and_grad(C)
    grad_fn = make_grad_fn(Xj, Yj, 4)
    return grad_fn, lambda xbar: full_loss(xbar, Xj, Yj)


def _case_config(name):
    topo = make_topology("ring", N)
    lr = decaying(1.0, 50.0)
    comp = SignTopK(k=6)
    thr = piecewise(30.0 * D, 30.0 * D, every=10, until=T)
    if name == "sparq":
        return SparqConfig(topology=topo, compressor=comp, threshold=thr,
                           lr=lr, H=5, gamma=0.3)
    if name == "squarm":
        return squarm_config(topo, comp, lr, H=5, threshold=thr, beta=0.9,
                             nesterov=True, gamma=0.3)
    if name == "choco":
        return baselines.choco_config(topo, comp, lr, gamma=0.3)
    if name == "sparq_faults":
        return SparqConfig(
            topology=topo, compressor=comp, threshold=thr, lr=lr, H=5,
            gamma=0.3,
            faults=FaultPlan(link_drop=0.3, stragglers=(1,),
                             straggler_frac=0.5,
                             dropout=(DropoutWindow(2, 10, 25),), seed=4))
    raise ValueError(name)


def _run_case(name):
    grad_fn, eval_fn = _problem()
    cfg = _case_config(name)
    state, trace = run(cfg, grad_fn, jnp.zeros(D), T, jax.random.PRNGKey(0),
                       record_every=REC, eval_fn=eval_fn)
    xbar = np.asarray(jnp.mean(state.x, axis=0), np.float64)
    return {
        "schema": 1,
        "case": name,
        "T": T, "record_every": REC, "n": N, "d": D,
        "trace": {k: v for k, v in trace.to_dict().items()},
        "final": {
            "bits": float(state.bits),
            "sync_rounds": int(state.sync_rounds),
            "triggers": int(state.triggers),
            # leaf-for-leaf fingerprint of the final averaged iterate: norm +
            # first/last coordinates pin the trajectory endpoint without
            # committing the whole vector
            "x_bar_norm": float(np.linalg.norm(xbar)),
            "x_bar_head": [float(v) for v in xbar[:4]],
            "x_bar_tail": [float(v) for v in xbar[-4:]],
        },
    }


CASES = ["sparq", "squarm", "choco", "sparq_faults"]


@pytest.fixture
def threefry_streams():
    """The goldens pin the non-partitionable threefry key streams (JAX's
    default before 0.5); newer JAX defaults to the partitionable ones, which
    draw other minibatches. Pin the setting so the trajectories compare on
    either."""
    was = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", False)
    yield
    jax.config.update("jax_threefry_partitionable", was)


@pytest.mark.parametrize("case", CASES)
def test_golden_trace(case, request, threefry_streams):
    got = _run_case(case)
    path = os.path.join(GOLDEN_DIR, f"{case}.json")
    if request.config.getoption("--regen-golden"):
        os.makedirs(GOLDEN_DIR, exist_ok=True)
        with open(path, "w") as f:
            json.dump(got, f, indent=1, sort_keys=True)
        pytest.skip(f"regenerated {path}")
    assert os.path.exists(path), (
        f"missing golden file {path} — run pytest tests/test_golden_traces.py "
        f"--regen-golden and commit it")
    with open(path) as f:
        want = json.load(f)
    assert got["schema"] == want["schema"]
    for k in ("T", "record_every", "n", "d"):
        assert got[k] == want[k], (
            f"{case}: harness constant {k} changed ({want[k]} -> {got[k]}) — "
            f"the run is no longer comparable; regenerate the goldens with "
            f"--regen-golden in the same commit")
    # integer channels and bit totals: exact
    for col in ("t", "sync_rounds", "triggers"):
        assert got["trace"][col] == want["trace"][col], (
            f"{case}: golden {col} column drifted")
    np.testing.assert_allclose(got["trace"]["bits"], want["trace"]["bits"],
                               rtol=1e-9,
                               err_msg=f"{case}: golden bits drifted")
    # losses + final fingerprint: small float slack only
    np.testing.assert_allclose(got["trace"]["loss"], want["trace"]["loss"],
                               rtol=2e-4, atol=1e-6,
                               err_msg=f"{case}: golden loss drifted")
    fin, wfin = got["final"], want["final"]
    assert fin["sync_rounds"] == wfin["sync_rounds"]
    assert fin["triggers"] == wfin["triggers"]
    np.testing.assert_allclose(fin["bits"], wfin["bits"], rtol=1e-9)
    np.testing.assert_allclose(fin["x_bar_norm"], wfin["x_bar_norm"],
                               rtol=2e-4)
    np.testing.assert_allclose(fin["x_bar_head"], wfin["x_bar_head"],
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(fin["x_bar_tail"], wfin["x_bar_tail"],
                               rtol=2e-4, atol=1e-6)


def test_golden_files_committed():
    """Every case has its committed golden file (a fresh checkout must not
    silently skip the regression net)."""
    missing = [c for c in CASES
               if not os.path.exists(os.path.join(GOLDEN_DIR, f"{c}.json"))]
    assert not missing, (
        f"golden files missing for {missing}: run "
        f"pytest tests/test_golden_traces.py --regen-golden and commit them")
