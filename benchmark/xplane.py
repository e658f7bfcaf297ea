"""A minimal reader of the profiler's ``.xplane.pb`` (an ``XSpace``
protobuf), with nothing but the standard library.

``jax.profiler.ProfileData`` reads the same file but does not hand out
the stats kept with an event's METADATA — the HLO category, JAX's name
stack (``tf_op``), the short name — and those are what the reduction
classifies ops by. The wire format is simple enough to read directly;
the field numbers below are those of ``xplane.proto`` (tsl/profiler):

    XSpace          planes=1
    XPlane          name=2 lines=3 event_metadata=4 stat_metadata=5
    XLine           name=2 timestamp_ns=3 events=4
    XEvent          metadata_id=1 offset_ps=2 duration_ps=3
    XEventMetadata  id=1 name=2 display_name=4 stats=5
    XStatMetadata   id=1 name=2
    XStat           metadata_id=1 double=2 uint64=3 int64=4 str=5 ref=7
"""

import gzip
import struct
from dataclasses import dataclass, field


@dataclass
class Event:
    name: str           # short name (``fusion.12``) or the span's name
    text: str           # full name: an op's HLO text
    start: float        # ns, on the trace's one clock
    end: float
    stats: dict         # the metadata's stats, by name

    @property
    def seconds(self):
        return (self.end - self.start) / 1e9


@dataclass
class Line:
    name: str
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)

    def line(self, name):
        for line in self.lines:
            if line.name == name:
                return line
        return None


def _varint(buf, pos):
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, pos
        shift += 7


def _fields(buf, pos, end):
    """``(number, wire_type, value)`` of each field in ``buf[pos:end]``;
    a length-delimited value is its ``(start, end)``."""
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = (pos, pos + size)
            pos += size
        elif wire == 1:
            value = buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            value = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield number, wire, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(value):
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, span, stat_names):
    """``(name, value)`` of one XStat; a ``ref`` value names a string
    interned among the stat metadata."""
    name = value = None
    for number, _, v in _fields(buf, *span):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = _text(buf, v)
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf, span):
    key = value = None
    for number, _, v in _fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane(buf, span, want_line):
    lines, event_meta, stat_meta = [], [], []
    for number, _, v in _fields(buf, *span):
        if number == 3:
            lines.append(v)
        elif number == 4:
            event_meta.append(v)
        elif number == 5:
            stat_meta.append(v)
    stat_names = {}
    for entry in stat_meta:
        _, value = _map_entry(buf, entry)
        ident = name = None
        for number, _, v in _fields(buf, *value):
            if number == 1:
                ident = v
            elif number == 2:
                name = _text(buf, v)
        stat_names[ident] = name
    metadata = {}
    for entry in event_meta:
        _, value = _map_entry(buf, entry)
        ident, name, display, stats = None, "", "", {}
        for number, _, v in _fields(buf, *value):
            if number == 1:
                ident = v
            elif number == 2:
                name = _text(buf, v)
            elif number == 4:
                display = _text(buf, v)
            elif number == 5:
                key, val = _stat(buf, v, stat_names)
                if val is not None:
                    stats[key] = val
        metadata[ident] = (display or name, name, stats)
    out = []
    for span_ in lines:
        name, t0_ns, events = "", 0, []
        for number, _, v in _fields(buf, *span_):
            if number == 2:
                name = _text(buf, v)
            elif number == 3:
                t0_ns = _signed(v)
            elif number == 4:
                events.append(v)
        if not want_line(name):
            continue
        line = Line(name)
        for ev in events:
            ident = offset_ps = duration_ps = 0
            for number, _, v in _fields(buf, *ev):
                if number == 1:
                    ident = v
                elif number == 2:
                    offset_ps = v
                elif number == 3:
                    duration_ps = v
            short, text, stats = metadata.get(ident, ("", "", {}))
            start = t0_ns + offset_ps / 1e3
            line.events.append(Event(short, text, start,
                                     start + duration_ps / 1e3, stats))
        out.append(line)
    return out


def read(path, want_plane=lambda name: True, want_line=lambda name: True):
    """The planes of the trace at ``path`` (``.gz`` or not) whose names
    ``want_plane`` accepts, with the lines ``want_line`` accepts."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    planes = []
    for number, _, v in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name = ""
        for n, _, pv in _fields(buf, *v):
            if n == 2:
                name = _text(buf, pv)
                break
        if want_plane(name):
            planes.append(Plane(name, _plane(buf, v, want_line)))
    return planes
