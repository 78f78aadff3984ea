"""Reading the profiler's trace: the device planes' ops and program
executions, the harness's host spans, and the interval arithmetic the
per-layer readers share.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` alone. On a TPU each chip is a plane
``/device:TPU:<i>`` with a line ``XLA Modules`` (one event per execution of
a compiled program, named after its HLO module, e.g. ``jit_train_step(7)``)
and a line ``XLA Ops`` (one event per HLO op; a Pallas kernel is a custom
call whose ``tf_op`` stat names the ``pallas_call``). The host plane holds
the harness's ``TraceAnnotation`` spans (``batch``, ``device_put``,
``dispatch``, ``wait``) on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
HOST_SPANS = ("batch", "device_put", "dispatch", "wait")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Op:
    name: str          # the HLO instruction's name, e.g. sign_topk_blocks.2
    start: float       # ns on the trace's clock
    end: float


@dataclasses.dataclass
class Device:
    index: int
    ops: List[Op]
    modules: List[Op]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Op]          # the harness's spans

    @property
    def window(self) -> Optional[Interval]:
        """From the first batch span to the end of the last wait span."""
        b = [s.start for s in self.host if s.name == "batch"]
        w = [s.end for s in self.host if s.name == "wait"]
        return (min(b), max(w)) if b and w else None


def _op_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text, ``%name = type
    op(...)``; keep the instruction's name."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Op(_op_name(e.name), e.start_ns, e.end_ns)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [Op(e.name, e.start_ns, e.end_ns)
                            for e in line.events]
            devices.append(Device(int(m.group(1)), ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Op(e.name, e.start_ns, e.end_ns)
                         for e in line.events if e.name in HOST_SPANS]
    devices.sort(key=lambda d: d.index)
    host.sort(key=lambda s: s.start)
    return Trace(devices, host)


# ------------------------------------------------------------ intervals

def union(intervals: Iterable[Interval],
          clip: Optional[Interval] = None) -> List[Interval]:
    """The union of intervals as sorted disjoint intervals, within clip."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if clip is not None:
            a, b = max(a, clip[0]), min(b, clip[1])
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def minus(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Disjoint sorted intervals a with disjoint sorted intervals b removed."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ------------------------------------------------------------ selections

def step_executions(dev: Device, window: Optional[Interval] = None
                    ) -> List[Op]:
    """The train step's program executions on one device, in time order."""
    mods = [m for m in dev.modules if "train_step" in m.name]
    if window is not None:
        mods = [m for m in mods if m.start >= window[0] - 1e3
                and m.end <= window[1] + 1e3]
    return sorted(mods, key=lambda m: m.start)


def kernel_ops(dev: Device, pattern: str) -> List[Op]:
    """Ops of one device whose instruction name matches ``pattern``."""
    rx = re.compile(pattern)
    return [o for o in dev.ops if rx.match(o.name)]




def busy(dev: Device, window: Interval) -> float:
    """ns in which some op ran on the device, within the window."""
    return length(union(((o.start, o.end) for o in dev.ops), window))


def idle_gaps(dev: Device, window: Interval) -> List[Interval]:
    return minus([window], union(((o.start, o.end) for o in dev.ops),
                                 window))


def host_doing(trace: Trace, t: float) -> str:
    """The harness span the host was in at time t (innermost, latest)."""
    inside = [s for s in trace.host if s.start <= t < s.end]
    return inside[-1].name if inside else "other"


def op_totals(trace: Trace, window: Interval) -> Dict[str, float]:
    """Self seconds of device time per op name (a ``while`` or
    ``conditional`` op holds the ops of its body; those are taken off it),
    within the window, averaged over the devices."""
    tot: Dict[str, float] = {}

    def close(entry):
        op, a, b, inner = entry
        tot[op.name] = tot.get(op.name, 0.0) + (b - a - inner) * 1e-9

    for dev in trace.devices:
        stack: list = []
        for o in sorted(dev.ops, key=lambda o: (o.start, -o.end)):
            a, b = max(o.start, window[0]), min(o.end, window[1])
            if b <= a:
                continue
            while stack and stack[-1][2] <= a:
                close(stack.pop())
            if stack:
                b = min(b, stack[-1][2])
                stack[-1][3] += b - a
            stack.append([o, a, b, 0.0])
        while stack:
            close(stack.pop())
    n = max(1, len(trace.devices))
    return {k: v / n for k, v in tot.items()}


def step_ms(ctx, want_sync: bool) -> Optional[float]:
    """Median ms of the train step's program executions on the wanted kind
    of step (sync or local, by step index), pooled over the chips; None
    where the trace holds none or their count is not the window's."""
    import statistics
    flags = ctx.window.sync_flags
    out = []
    for dev in ctx.trace.devices:
        execs = step_executions(dev, ctx.interval)
        if len(execs) != len(flags):
            return None
        out += [(e.end - e.start) * 1e-6 for e, s in zip(execs, flags)
                if s == want_sync]
    return statistics.median(out) if out else None
