"""The JAX name stack of every device op in a profiler trace, read from the
``.xplane.pb`` wire format with the standard library alone.

``jax.profiler.ProfileData`` gives each ``XLA Ops`` event its HLO text and
times, but not the stats of the event's metadata. There the TPU profiler
keeps ``tf_op``: the op's JAX name stack, the ``op_name`` of its HLO
metadata, e.g. ``jit(train_step)/cond/branch_1_fun/sparq_compress/...``
(a fused op carries its root instruction's). So every ``jax.named_scope``
of the program reaches the trace, and this module reads it out per event:
instruction names repeat across the HLO modules of one trace, so the scope
is resolved through each event's own ``metadata_id``, never by name.

The messages read (``tsl/profiler/protobuf/xplane.proto``): ``XSpace.planes``
(1); ``XPlane`` name (2), lines (3), event_metadata (4, a map), stat_metadata
(5, a map); ``XLine`` name (2), events (4); ``XEvent`` metadata_id (1),
duration_ps (3); ``XEventMetadata`` id (1), stats (5);
``XStat`` metadata_id (1), str_value (5), ref_value (7, the id of a stat
metadata whose name is the string); ``XStatMetadata`` id (1), name (2).
"""
from __future__ import annotations

import re
from typing import Dict, Iterator, List, NamedTuple, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SCOPE_STAT = "tf_op"


class Event(NamedTuple):
    duration_ps: int
    scope: str           # the tf_op name stack; "" where the op has none


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for varint
    and fixed fields, a memoryview slice for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wt == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wt} in an xplane message")
        yield num, wt, v


def _map_entries(entries: List) -> Iterator[Tuple[int, object]]:
    """(key, value message) of a protobuf map<int64, message>."""
    for entry in entries:
        key, val = 0, b""
        for num, _, v in _fields(entry):
            if num == 1:
                key = v
            elif num == 2:
                val = v
        yield key, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _plane_events(plane) -> Tuple[str, List[Event]]:
    name, lines, ev_meta, st_meta = "", [], [], []
    for num, _, v in _fields(plane):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            ev_meta.append(v)
        elif num == 5:
            st_meta.append(v)
    if not DEVICE_PLANE.match(name):
        return name, []
    stat_names = {}
    for key, msg in _map_entries(st_meta):
        for num, _, v in _fields(msg):
            if num == 2:
                stat_names[key] = _text(v)
    scope_id = next((k for k, s in stat_names.items() if s == SCOPE_STAT),
                    None)
    scopes: Dict[int, str] = {}
    if scope_id is not None:
        for key, msg in _map_entries(ev_meta):
            for num, _, stat in _fields(msg):
                if num != 5:
                    continue
                sid, text = None, ""
                for snum, _, sv in _fields(stat):
                    if snum == 1:
                        sid = sv
                    elif snum == 5:
                        text = _text(sv)
                    elif snum == 7:
                        text = stat_names.get(sv, "")
                if sid == scope_id:
                    scopes[key] = text
    events: List[Event] = []
    for line in lines:
        lname, evs = "", []
        for num, _, v in _fields(line):
            if num == 2:
                lname = _text(v)
            elif num == 4:
                evs.append(v)
        if lname != OPS_LINE:
            continue
        for ev in evs:
            mid = dur = 0
            for num, _, v in _fields(ev):
                if num == 1:
                    mid = v
                elif num == 3:
                    dur = v
            events.append(Event(dur, scopes.get(mid, "")))
    return name, events


def device_op_scopes(path: str) -> Dict[int, List[Event]]:
    """For each ``/device:TPU:<i>`` plane, keyed by i, every event of its
    ``XLA Ops`` line in the file's order, with its ``tf_op`` scope."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[int, List[Event]] = {}
    for num, _, plane in _fields(buf):
        if num != 1:
            continue
        name, events = _plane_events(plane)
        m = DEVICE_PLANE.match(name)
        if m:
            out[int(m.group(1))] = events
    return out
