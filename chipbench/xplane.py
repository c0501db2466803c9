"""A minimal reader of the profiler's ``.xplane.pb`` (XSpace protobuf).

``jax.profiler.ProfileData`` gives each event's own stats but not the
stats of its *metadata*, which is where the device ops keep their HLO
metadata (the ``jax.named_scope`` path).  This decodes the protobuf wire
format directly for the few messages the harness needs:

    XSpace.planes(1) → XPlane{name(2), lines(3), event_metadata(4),
    stat_metadata(5)}; XLine{name(2), timestamp_ns(3), events(4)};
    XEvent{metadata_id(1), offset_ps(2), duration_ps(3), stats(4)};
    XEventMetadata{id(1), name(2), display_name(4), stats(5)};
    XStat{metadata_id(1), double(2), uint64(3), int64(4), str(5), bytes(6),
    ref(7)}; XStatMetadata{id(1), name(2)}.
"""
from __future__ import annotations

import dataclasses


def _varint(b: bytes, i: int) -> tuple[int, int]:
    out, shift = 0, 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes):
    """Yield (field number, wire type, value) of one message."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = b[i : i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i : i + ln], i + ln
        elif wt == 5:
            v, i = b[i : i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield f, wt, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float
    meta_stats: dict  # stats of the event's metadata, by stat name


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _stat(b: bytes, stat_names: dict) -> tuple[str, object]:
    mid, val = 0, None
    for f, wt, v in _fields(b):
        if f == 1:
            mid = v
        elif f == 5:
            val = v.decode("utf-8", "replace")
        elif f == 7:
            val = ("ref", v)
        elif f in (3, 4):
            val = v
    if isinstance(val, tuple):
        val = stat_names.get(val[1], "")
    return stat_names.get(mid, str(mid)), val


def _plane(b: bytes, want_lines) -> Plane:
    name, line_bufs, em_bufs, stat_names = "", [], [], {}
    for f, wt, v in _fields(b):
        if f == 2:
            name = v.decode("utf-8", "replace")
        elif f == 3:
            line_bufs.append(v)
        elif f == 4:
            em_bufs.append(v)
        elif f == 5:
            sid, sname = 0, ""
            for kf, _, kv in _fields(v):
                if kf == 2:
                    for mf, _, mv in _fields(kv):
                        if mf == 1:
                            sid = mv
                        elif mf == 2:
                            sname = mv.decode("utf-8", "replace")
            stat_names[sid] = sname
    meta = {}
    for buf in em_bufs:
        for kf, _, kv in _fields(buf):
            if kf != 2:
                continue
            mid, mname, stats = 0, "", {}
            for mf, _, mv in _fields(kv):
                if mf == 1:
                    mid = mv
                elif mf == 2:
                    mname = mv.decode("utf-8", "replace")
                elif mf == 5:
                    k, val = _stat(mv, stat_names)
                    stats[k] = val
            meta[mid] = (mname, stats)
    lines = []
    for lb in line_bufs:
        lname, ts, ev_bufs = "", 0, []
        for f, wt, v in _fields(lb):
            if f == 2:
                lname = v.decode("utf-8", "replace")
            elif f == 3:
                ts = _signed(v)
            elif f == 4:
                ev_bufs.append(v)
        if not want_lines(name, lname):
            continue
        events = []
        for eb in ev_bufs:
            mid, off, dur = 0, 0, 0
            for f, wt, v in _fields(eb):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = _signed(v)
                elif f == 3:
                    dur = _signed(v)
            mname, stats = meta.get(mid, ("", {}))
            start = ts + off / 1e3
            events.append(Event(mname, start, start + dur / 1e3, stats))
        lines.append(Line(lname, events))
    return Plane(name, lines)


def read(path: str, want_lines=lambda plane, line: True) -> list[Plane]:
    """The planes of an ``.xplane.pb``, keeping the lines ``want_lines``
    accepts (by plane and line name)."""
    with open(path, "rb") as fh:
        data = fh.read()
    return [_plane(v, want_lines) for f, wt, v in _fields(data) if f == 1]
