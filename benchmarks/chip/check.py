"""What decides ``correct``: the program's first steps against the plain
reference, at the timed sizes.

Set-up drives the compiled step through its first ``S = max(3, H)`` steps
(so every cell reaches its first sync) through the window's own call and
feed, and keeps, per node, the segment norms (leaves, those stacked over
layers split per layer) of

* the first gradient as the optimizer got it, worked out from the state
  after one step: (x_0 - x_1) / eta_0 (plain SGD; the first step mixes
  nothing: a one-node ring's mix is zero, and H > 1 cells do not sync);
* the parameters' change x_S - x_0, as step S + 1 receives them;
* x_hat after step S (the compressed estimate: support and values);

the mean loss of each step, and the counters ``sync_rounds``, ``triggers``
and ``bits``. The reference follows the same S steps from the seed, and the
numbers compared are:

* ``loss``: the largest relative gap of a step's mean loss;
* ``grad``, ``change``: the worst segment's gap between the program's norm
  and the reference's, over the larger of the reference's norm of that
  segment and of the median segment; ``grad_median``, ``change_median``:
  the median segment's gap. Segments whose reference gradient is under a
  thousandth of the median segment's (a key's bias under softmax) are left
  out;
* ``xhat``: the relative gap of the norm of a node's whole x_hat row (a
  segment's share of a tile's kept entries turns on round-off where a leaf
  starts out constant, as Mamba-2's D skip, so x_hat is not split);
* ``counters``: how many of the three counters differ (``bits``, a float32
  sum of integers past 2**24, by more than a millionth).

A cell's limits file names the numbers it compares; the others are printed
for the look, with the segments that read worst.
"""
from __future__ import annotations

import time
from typing import Dict, List, Sequence

import numpy as np

from reference.sparq import norm64, seg_norms

NUMBERS = ("loss", "grad", "grad_median", "change", "change_median", "xhat",
           "counters")
QUIET = 1e-3          # segments whose reference gradient is below this
                      # share of the median segment's are left out
BITS_RTOL = 1e-6      # float32 accumulation of the bit count


def sync_steps(H: int) -> int:
    return max(3, int(H))


def program_readings(prog, batch_of, steps: int, eta0: float,
                     segs: Sequence[tuple]) -> Dict[str, object]:
    """Drive the first ``steps`` steps and keep what the comparison needs.
    ``check_s`` is the time spent copying state out for the comparison."""
    from program import flat_rows

    def norms_of(rows):
        return [seg_norms(r, segs) for r in rows]

    t = time.perf_counter()
    x0 = flat_rows(prog.state["params"])
    check_s = time.perf_counter() - t
    losses, grad = [], None
    for s in range(steps):
        metrics = prog.step(prog.put(batch_of(s)))
        losses.append(float(metrics["loss"]))
        if s == 0:
            t = time.perf_counter()
            x1 = flat_rows(prog.state["params"])
            grad = norms_of((x0 - x1) / np.float32(eta0))
            del x1
            check_s += time.perf_counter() - t
    t = time.perf_counter()
    change = norms_of(flat_rows(prog.state["params"]) - x0)
    del x0
    xhat = [np.array([norm64(r)]) for r in flat_rows(prog.state["x_hat"])]
    st = prog.state
    counters = {"sync_rounds": int(st["sync_rounds"]),
                "triggers": int(st["triggers"]), "bits": float(st["bits"])}
    check_s += time.perf_counter() - t
    return {"losses": losses, "grad": grad, "change": change, "xhat": xhat,
            "counters": counters, "check_s": check_s}


def _keep(ref: dict) -> List[np.ndarray]:
    """Per node, the segments whose reference gradient is not quiet."""
    return [np.asarray(g) >= QUIET * np.median(g) for g in ref["grad"]]


def _gaps(prog: List[np.ndarray], ref: List[np.ndarray],
          keep: List[np.ndarray]) -> List[np.ndarray]:
    """Per node, each kept segment's gap between the two norms over the
    larger of the reference's norm and the median segment's."""
    out = []
    for p, r, k in zip(prog, ref, keep):
        p, r = np.asarray(p)[k], np.asarray(r)[k]
        floor = np.maximum(r, np.median(r))
        out.append(np.abs(p - r) / np.where(floor > 0, floor, 1.0))
    return out


def _worst(gaps: List[np.ndarray]) -> float:
    return max(float(np.max(g)) for g in gaps)


def _median(gaps: List[np.ndarray]) -> float:
    return max(float(np.median(g)) for g in gaps)


def worst_segments(prog: dict, ref: dict, segs, n: int = 3) -> dict:
    """For the look: the segments that read worst in ``grad`` and
    ``change``, with their gaps."""
    keep = _keep(ref)
    names = np.array([s[0] for s in segs])
    out = {}
    for key in ("grad", "change"):
        rows = []
        for node, (g, k) in enumerate(zip(_gaps(prog[key], ref[key], keep),
                                          keep)):
            top = np.argsort(-g)[:n]
            rows += [(node, str(names[k][i]), float(g[i])) for i in top]
        out[key] = sorted(rows, key=lambda r: -r[2])[:n]
    return out


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers compared, from the program's and the reference's
    readings (either may be the reference under a control or a fault)."""
    keep = _keep(ref)
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    pc, rc = prog["counters"], ref["counters"]
    bits_off = abs(pc["bits"] - rc["bits"]) > BITS_RTOL * max(rc["bits"], 1)
    grad = _gaps(prog["grad"], ref["grad"], keep)
    change = _gaps(prog["change"], ref["change"], keep)
    return {
        "loss": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad": _worst(grad),
        "grad_median": _median(grad),
        "change": _worst(change),
        "change_median": _median(change),
        "xhat": max(float(abs(p[0] - r[0]) / r[0])
                    for p, r in zip(prog["xhat"], ref["xhat"])),
        "counters": float((pc["sync_rounds"] != rc["sync_rounds"])
                          + (pc["triggers"] != rc["triggers"]) + bits_off),
    }


def unchanged(ref: dict) -> dict:
    """The readings of a step that returns its state unchanged, which need
    no run; its losses would, and read NaN here."""
    n = len(ref["grad"])
    zero = [np.zeros_like(np.asarray(g)) for g in ref["grad"]]
    return {"losses": [float("nan")] * len(ref["losses"]),
            "grad": zero, "change": zero,
            "xhat": [np.zeros(1) for _ in range(n)],
            "counters": {"sync_rounds": 0, "triggers": 0, "bits": 0.0}}


def judge(numbers: Dict[str, float], limits: dict) -> Dict[str, dict]:
    """Each number the cell compares beside its limit (a limits file may
    say under ``not_compared`` why it leaves a number out)."""
    unknown = set(limits) - set(NUMBERS) - {"not_compared"}
    if unknown or not set(limits) & set(NUMBERS):
        raise KeyError(f"limits name no number or unknown ones: {unknown}")
    return {name: {"value": numbers[name],
                   "limit": float(limits[name]["limit"])}
            for name in NUMBERS if name in limits}


def is_correct(judged: Dict[str, dict]) -> bool:
    return all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in judged.values())
