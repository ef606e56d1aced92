"""A profiler trace (.xplane.pb) read from its bytes.

`jax.profiler.ProfileData` shows an event's own statistics and hides
those of its METADATA — and that is where libtpu 0.0.34 puts an `XLA
Ops` event's op path (`tf_op`: `jit(ompi_train_step)/transpose(jvp(
layer_0))/attn_core/dot_general:`, the `jax.named_scope` names with the
`jvp(...)`/`transpose(...)` jax adds; looked at on the chip, PR 24). So
this reads the protobuf wire format itself: the few messages of tsl's
xplane.proto, no schema module, nothing but Python.

    XSpace.planes=1
    XPlane.name=2 .lines=3 .event_metadata=4 (map) .stat_metadata=5 (map)
    XLine.id=1 .name=2 .timestamp_ns=3 .events=4
    XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3 .stats=4
    XEventMetadata.id=1 .name=2 .stats=5
    XStatMetadata.id=1 .name=2
    XStat.metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7

`planes(path, keep)` gives, per plane whose name `keep` accepts, its
lines as lists of `Event(name, start_ns, end_ns, stats)`; `stats`
merges the metadata's statistics with the event's own.
"""

from __future__ import annotations

import struct
from typing import Callable, Dict, Iterator, List, NamedTuple, Tuple


class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    stats: dict


def _varint(b, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def fields(b) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message's bytes: a
    varint's number, or the bytes of a length-delimited or fixed
    field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane message")
        yield key >> 3, wire, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(b, stat_names: Dict[int, str]):
    """(name, value) of one XStat."""
    name, value = None, None
    for f, _, v in fields(b):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _text(v)
        elif f == 6:
            value = bytes(v)
        elif f == 7:  # a string kept once, among the stat names
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(b):
    """The value message of one map<int64, message> entry."""
    for f, _, v in fields(b):
        if f == 2:
            return v
    return b""


def planes(path: str, keep: Callable[[str], bool],
           keep_line: Callable[[str, str], bool] = lambda p, ln: True,
           keep_event: Callable[[str, str], bool] = lambda p, n: True
           ) -> Dict[str, Dict[str, List[Event]]]:
    """plane name -> line name (with `#<id>` where two lines share a
    name) -> events in the file's order. `keep_event(plane, event
    name)` drops events before their statistics are decoded: a host
    plane holds thousands of the runtime's own."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, List[Event]]] = {}
    for f, _, plane in fields(space):
        if f != 1:
            continue
        name, lines, emeta, smeta = "", [], [], []
        for f2, _, v in fields(plane):
            if f2 == 2:
                name = _text(v)
            elif f2 == 3:
                lines.append(v)
            elif f2 == 4:
                emeta.append(_map_entry(v))
            elif f2 == 5:
                smeta.append(_map_entry(v))
        if not keep(name):
            continue
        stat_names: Dict[int, str] = {}
        for m in smeta:
            sid, sname = 0, ""
            for f3, _, v in fields(m):
                if f3 == 1:
                    sid = v
                elif f3 == 2:
                    sname = _text(v)
            stat_names[sid] = sname
        meta: Dict[int, Tuple[str, list]] = {}
        for m in emeta:
            mid, mname, mstats = 0, "", []
            for f3, _, v in fields(m):
                if f3 == 1:
                    mid = v
                elif f3 == 2:
                    mname = _text(v)
                elif f3 == 5:
                    mstats.append(v)
            meta[mid] = (mname, mstats)
        meta_stats: Dict[int, dict] = {}  # decoded on first use
        got: Dict[str, List[Event]] = {}
        for line in lines:
            lid, lname, t0, events = 0, "", 0, []
            for f3, _, v in fields(line):
                if f3 == 1:
                    lid = v
                elif f3 == 2:
                    lname = _text(v)
                elif f3 == 3:
                    t0 = _signed(v)
                elif f3 == 4:
                    events.append(v)
            if not keep_line(name, lname):
                continue
            rows = []
            for ev in events:
                mid = off = dur = 0
                own = []
                for f4, _, v in fields(ev):
                    if f4 == 1:
                        mid = v
                    elif f4 == 2:
                        off = _signed(v)
                    elif f4 == 3:
                        dur = _signed(v)
                    elif f4 == 4:
                        own.append(v)
                ename, mstats = meta.get(mid, (str(mid), []))
                if not keep_event(name, ename):
                    continue
                if mid not in meta_stats:
                    meta_stats[mid] = dict(
                        _stat(s, stat_names) for s in mstats)
                stats = dict(meta_stats[mid])
                stats.update(_stat(s, stat_names) for s in own)
                start = t0 + off / 1e3
                rows.append(Event(ename, start, start + dur / 1e3, stats))
            key = lname if lname not in got else f"{lname}#{lid}"
            got[key] = rows
        out[name] = got
    return out
