"""A reader of the JAX profiler's ``.xplane.pb`` (an ``XSpace`` protocol
buffer) in plain Python, for what ``jax.profiler.ProfileData`` leaves out:
the stats of each event's metadata. On the device planes these hold, for
every XLA operation, ``tf_op`` (its ``op_name`` path, such as
``jit(segment_small)/while/body/mmdit/mlp/dot_general``), ``flops`` and
``bytes_accessed``.

It decodes the protobuf wire format itself (no ``protobuf`` package, no
TensorFlow), following ``tsl/profiler/protobuf/xplane.proto``:

- XSpace: planes = 1
- XPlane: id = 1, name = 2, lines = 3, event_metadata = 4 (map), stat_metadata
  = 5 (map), stats = 6
- XLine: id = 1, name = 2, timestamp_ns = 3, events = 4
- XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3, stats = 4
- XStat: metadata_id = 1, double = 2, uint64 = 3, int64 = 4, str = 5, bytes
  = 6, ref = 7 (the name of another stat metadata)
- XEventMetadata: id = 1, name = 2, metadata = 3, display_name = 4, stats = 5
- XStatMetadata: id = 1, name = 2
"""
from __future__ import annotations

import struct
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional


def _varint(buf: bytes, i: int):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: Optional[int] = None):
    """(field number, value) of each field of the message buf[i:end]; a
    length-delimited value is its (start, end) in buf."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire} at byte {i}")
        yield key >> 3, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


@dataclass
class Event:
    metadata_id: int
    offset_ps: int
    duration_ps: int
    stats: Dict[str, object]


@dataclass
class Line:
    name: str
    timestamp_ns: int
    events: List[Event]


@dataclass
class EventMetadata:
    name: str
    display_name: str
    stats: Dict[str, object]


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)
    event_metadata: Dict[int, EventMetadata] = field(default_factory=dict)


def _stat(buf: bytes, span, stat_names: Dict[int, str]):
    name, value = "", None
    for f, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _text(buf, v)
        elif f == 6:
            value = buf[v[0]:v[1]]
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _stats(buf: bytes, spans, stat_names) -> Dict[str, object]:
    return dict(_stat(buf, s, stat_names) for s in spans)


def _plane(buf: bytes, span, wanted) -> Optional[Plane]:
    name, lines, emeta, smeta = "", [], [], []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            emeta.append(v)
        elif f == 5:
            smeta.append(v)
    if not wanted(name):
        return None
    stat_names: Dict[int, str] = {}
    for entry in smeta:
        for f, v in _fields(buf, *entry):
            if f == 2:
                sid, sname = 0, ""
                for g, w in _fields(buf, *v):
                    if g == 1:
                        sid = w
                    elif g == 2:
                        sname = _text(buf, w)
                stat_names[sid] = sname
    plane = Plane(name)
    for entry in emeta:
        for f, v in _fields(buf, *entry):
            if f == 2:
                mid, mname, display, stats = 0, "", "", []
                for g, w in _fields(buf, *v):
                    if g == 1:
                        mid = w
                    elif g == 2:
                        mname = _text(buf, w)
                    elif g == 4:
                        display = _text(buf, w)
                    elif g == 5:
                        stats.append(w)
                plane.event_metadata[mid] = EventMetadata(
                    mname, display, _stats(buf, stats, stat_names))
    for lspan in lines:
        lname, ts, events = "", 0, []
        for f, v in _fields(buf, *lspan):
            if f == 2:
                lname = _text(buf, v)
            elif f == 3:
                ts = _signed(v)
            elif f == 4:
                mid = off = dur = 0
                stats = []
                for g, w in _fields(buf, *v):
                    if g == 1:
                        mid = w
                    elif g == 2:
                        off = _signed(w)
                    elif g == 3:
                        dur = _signed(w)
                    elif g == 4:
                        stats.append(w)
                events.append(Event(mid, off, dur,
                                    _stats(buf, stats, stat_names)))
        plane.lines.append(Line(lname, ts, events))
    return plane


def read(path: Path, wanted=lambda name: True) -> List[Plane]:
    """The planes of an ``.xplane.pb`` whose names ``wanted`` accepts."""
    buf = Path(path).read_bytes()
    planes = []
    for f, v in _fields(buf):
        if f == 1:
            plane = _plane(buf, v, wanted)
            if plane is not None:
                planes.append(plane)
    return planes
