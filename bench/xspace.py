"""A reader of the profiler's ``.xplane.pb`` for what
``jax.profiler.ProfileData`` leaves out: the stats of an event's metadata.
A trace stores what every run of one HLO instruction shares -- its HLO
text, its scope path -- once, on the instruction's event metadata, and
``ProfileData`` shows an event's own stats alone.

The file is an ``XSpace`` protobuf (``tsl/profiler/protobuf/xplane.proto``);
this reads its wire format directly, by the field numbers below, so that it
needs nothing beyond the standard library.
"""
from __future__ import annotations

import struct
from types import SimpleNamespace
from typing import Callable, Dict, Iterator, List, Tuple

# field numbers of xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_META, PLANE_STAT_META = 2, 3, 4, 5
LINE_NAME, LINE_TIMESTAMP_NS, LINE_EVENTS = 2, 3, 4
EVENT_META_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS, EVENT_STATS = 1, 2, 3, 4
STAT_META_ID, STAT_DOUBLE, STAT_UINT64, STAT_INT64 = 1, 2, 3, 4
STAT_STR, STAT_BYTES, STAT_REF = 5, 6, 7
META_ID, META_NAME, META_DISPLAY_NAME, META_STATS = 1, 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2


def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a message: an int for varint and fixed
    fields, a memoryview for length-delimited ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 1:
            v, i = int.from_bytes(b[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(b[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stats(msgs, stat_names: Dict[int, str]) -> List[Tuple[str, object]]:
    out = []
    for msg in msgs:
        f = dict(fields(msg))
        name = stat_names.get(f.get(STAT_META_ID, 0), "")
        if STAT_REF in f:
            value = stat_names.get(f[STAT_REF], "")
        elif STAT_STR in f:
            value = _text(f[STAT_STR])
        elif STAT_DOUBLE in f:
            value = struct.unpack("<d",
                                  f[STAT_DOUBLE].to_bytes(8, "little"))[0]
        elif STAT_BYTES in f:
            value = bytes(f[STAT_BYTES])
        elif STAT_INT64 in f:       # two's complement in a varint
            value = f[STAT_INT64] - (f[STAT_INT64] >> 63 << 64)
        else:
            value = f.get(STAT_UINT64)
        out.append((name, value))
    return out


def _plane(b) -> SimpleNamespace:
    parts: Dict[int, list] = {}
    for num, v in fields(b):
        parts.setdefault(num, []).append(v)
    stat_names = {}
    for entry in parts.get(PLANE_STAT_META, []):
        meta = dict(fields(dict(fields(entry))[MAP_VALUE]))
        stat_names[meta.get(META_ID, 0)] = _text(meta.get(META_NAME, b""))
    events_meta = {}
    for entry in parts.get(PLANE_EVENT_META, []):
        value = dict(fields(entry)).get(MAP_VALUE, b"")
        meta = {}
        for num, v in fields(value):
            meta.setdefault(num, []).append(v)
        name = _text((meta.get(META_NAME) or meta.get(META_DISPLAY_NAME)
                      or [b""])[0])
        events_meta[(meta.get(META_ID) or [0])[0]] = (
            name, _stats(meta.get(META_STATS, []), stat_names))
    lines = []
    for lb in parts.get(PLANE_LINES, []):
        name, t0, events = "", 0, []
        for num, v in fields(lb):
            if num == LINE_NAME:
                name = _text(v)
            elif num == LINE_TIMESTAMP_NS:
                t0 = v
            elif num == LINE_EVENTS:
                events.append(v)
        out = []
        for eb in events:
            mid = offset = dur = 0
            stats = []
            for num, v in fields(eb):
                if num == EVENT_META_ID:
                    mid = v
                elif num == EVENT_OFFSET_PS:
                    offset = v
                elif num == EVENT_DURATION_PS:
                    dur = v
                elif num == EVENT_STATS:
                    stats.append(v)
            ename, mstats = events_meta.get(mid, ("", []))
            # whole nanoseconds, as ProfileData gives them
            start = float(t0 + offset // 1000)
            out.append(SimpleNamespace(
                name=ename, start_ns=start, end_ns=start + dur // 1000,
                stats=_stats(stats, stat_names) + mstats))
        lines.append(SimpleNamespace(name=name, events=out))
    return SimpleNamespace(name=_text(parts.get(PLANE_NAME, [b""])[0]),
                           lines=lines)


def planes(path: str, keep: Callable[[str], bool]) -> List[SimpleNamespace]:
    """The planes of the trace at ``path`` whose name ``keep`` accepts,
    shaped like ``ProfileData``'s; each event's ``stats`` holds its own
    stats, then its metadata's."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = []
    for num, b in fields(data):
        if num != SPACE_PLANES:
            continue
        name = next((_text(v) for n, v in fields(b) if n == PLANE_NAME), "")
        if keep(name):
            out.append(_plane(b))
    return out
