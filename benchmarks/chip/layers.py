"""Device time of the train step's layers, read from the scope of each op.

The program runs each layer of ``train_step`` under a ``jax.named_scope``
(``dist/sparq_dist.py``); the trace carries it as each device op's ``tf_op``
(``xspace.py``). Within each execution of the step's program, a layer's time
is the self time of the ops whose scope holds the layer's name as a whole
path component, also inside a transform wrapper such as
``transpose(jvp(sparq_unravel))``. Self time follows ``tracefile.op_totals``:
a ``while`` or ``conditional`` op loses the ops of its body. A fused op
carries its root instruction's scope, so a fusion across a layer boundary
counts to one layer.

``tracefile.load`` keeps no scope, so ``load`` here reads the trace with it.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import tracefile as tr
import xspace

# the five layers that partition the step (sparq_unravel lies in sparq_grad)
TOP = ("sparq_grad", "sparq_optimizer", "sparq_trigger", "sparq_compress",
       "sparq_mix")
# layer scope -> the steps it is read on: every step, or sync steps alone
READ_ON = {"sparq_grad": None, "sparq_unravel": None,
           "sparq_optimizer": None, "sparq_trigger": True,
           "sparq_compress": True, "sparq_mix": True}


@dataclasses.dataclass
class ScopedOp(tr.Op):
    scope: str = ""    # the op's JAX name stack (tf_op); "" where none


def load(path: str) -> tr.Trace:
    """``tracefile.load``, with each device op's scope."""
    trace = tr.load(path)
    scopes = xspace.device_op_scopes(path)
    for dev in trace.devices:
        evs = scopes.get(dev.index, [])
        if len(evs) != len(dev.ops):
            raise ValueError(f"device {dev.index}: {len(dev.ops)} ops but "
                             f"{len(evs)} events in the file")
        for o, e in zip(dev.ops, evs):
            if abs((o.end - o.start) - e.duration_ps * 1e-3) > 1.0:
                raise ValueError(f"device {dev.index}: op {o.name} does not "
                                 f"line up with the file's event")
        dev.ops = [ScopedOp(o.name, o.start, o.end, e.scope)
                   for o, e in zip(dev.ops, evs)]
    return trace


def matcher(name: str):
    """A test of whether a scope path holds ``name`` as a whole component
    (``sparq_grad`` is not ``sparq_gradx``), bare or wrapped."""
    rx = re.compile(r"(?<![\w.-])" + re.escape(name) + r"(?![\w.-])")
    return lambda path: any(rx.search(p) for p in path.split("/"))


def self_times(ops: Iterable[tr.Op], clip: tr.Interval
               ) -> List[Tuple[tr.Op, float]]:
    """Each op with its self ns within clip: the time it covers, less that
    of the ops nested inside it (a ``while`` or ``conditional`` op holds
    the ops of its body)."""
    out: List[list] = []
    stack: List[list] = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        a, b = max(o.start, clip[0]), min(o.end, clip[1])
        if b <= a:
            continue
        while stack and stack[-1][2] <= a:
            stack.pop()
        if stack:
            b = min(b, stack[-1][2])
            stack[-1][3] += b - a
        entry = [o, a, b, 0.0]
        stack.append(entry)
        out.append(entry)
    return [(o, b - a - inner) for o, a, b, inner in out]


def step_layers(dev: tr.Device, execs: Sequence[tr.Op],
                names: Sequence[str]) -> List[Dict[str, float]]:
    """For each step execution: ms of each named layer, ``module`` (the
    execution's device time) and ``matched`` (how many ops held a name)."""
    ops = sorted(dev.ops, key=lambda o: o.start)
    starts = [o.start for o in ops]
    tests = {n: matcher(n) for n in names}
    out = []
    for ex in execs:
        lo = bisect.bisect_left(starts, ex.start)
        hi = bisect.bisect_left(starts, ex.end)
        row = {n: 0.0 for n in names}
        row["matched"] = 0
        for o, ns in self_times(ops[lo:hi], (ex.start, ex.end)):
            scope = getattr(o, "scope", "")
            hit = [n for n, test in tests.items() if scope and test(scope)]
            for n in hit:
                row[n] += ns * 1e-6
            row["matched"] += bool(hit)
        row["module"] = (ex.end - ex.start) * 1e-6
        out.append(row)
    return out


def layer_ms(ctx, scope: str, want_sync: Optional[bool]) -> Optional[float]:
    """Median ms of one layer over the window's steps of the wanted kind
    (sync or local by step index; every step where want_sync is None),
    pooled over the chips; None where no op holds the scope or the trace's
    step count is not the window's."""
    flags = ctx.window.sync_flags
    vals, matched = [], 0
    for dev in ctx.trace.devices:
        execs = tr.step_executions(dev, ctx.interval)
        if len(execs) != len(flags):
            return None
        for row, sync in zip(step_layers(dev, execs, [scope]), flags):
            if want_sync is None or sync == want_sync:
                vals.append(row[scope])
                matched += row["matched"]
    return statistics.median(vals) if vals and matched else None
