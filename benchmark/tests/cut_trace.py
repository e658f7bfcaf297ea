"""Cut a profiler trace down to what ``benchmark/tests/data/`` keeps: how
the recorded trace there was made (PR 35), for whoever records the next.

    python3 benchmark/tests/cut_trace.py <in.xplane.pb> <out.xplane.pb.gz> [steps]

Kept: each chip's plane with its lines ``XLA Modules``, ``XLA Ops`` and
``Async XLA Ops`` from the first execution of the step program (the one
that takes most time, as ``trace_reduce._step_window`` finds it) up to
the start of execution ``steps + 1`` (default 2 steps), and of the host
plane the benchmark's and the program's spans (``bench/``, ``hvd/``) in
the same time. Of an event's metadata the name, the display name and
the stats the reductions read (``KEEP_STATS``); metadata no kept event
uses goes. Everything else is copied byte for byte: the file is filtered
on the wire (``xplane.py``'s field numbers), nothing is re-measured."""

import gzip
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

from benchmark import trace_reduce, xplane  # noqa: E402

DEVICE_LINES = (trace_reduce.MODULES_LINE, trace_reduce.OPS_LINE,
                trace_reduce.ASYNC_LINE)
SPAN_PREFIXES = (trace_reduce.SPAN_PREFIX, "hvd/")
KEEP_STATS = ("hlo_category", "tf_op")


def _varint(value):
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _field(number, wire, payload):
    """One field on the wire; ``payload`` is an int for a varint, bytes
    otherwise."""
    key = _varint(number << 3 | wire)
    if wire == 0:
        return key + _varint(payload)
    if wire == 2:
        return key + _varint(len(payload)) + payload
    return key + payload


def _copy(buf, number, wire, value):
    if wire == 2:
        return _field(number, wire, bytes(buf[value[0]:value[1]]))
    return _field(number, wire, value if wire == 0 else bytes(value))


def _line(buf, span, keep_event):
    """The line with the events ``keep_event(metadata_id, start_ns)``
    accepts; ``(bytes, ids used)`` or ``None`` if none is."""
    out, used, t0_ns = bytearray(), set(), 0
    fields = list(xplane._fields(buf, *span))
    for number, _, v in fields:
        if number == 3:
            t0_ns = xplane._signed(v)
    for number, wire, v in fields:
        if number != 4:
            out += _copy(buf, number, wire, v)
            continue
        ident = offset_ps = 0
        for n, _, ev in xplane._fields(buf, *v):
            if n == 1:
                ident = ev
            elif n == 2:
                offset_ps = ev
        if keep_event(ident, t0_ns + offset_ps / 1e3):
            used.add(ident)
            out += _copy(buf, number, wire, v)
    return (bytes(out), used) if used else None


def _metadata(buf, entry, used, keep_stat_ids):
    """An ``event_metadata`` map entry without the stats no reduction
    reads; ``None`` if no kept event uses it."""
    key, value = xplane._map_entry(buf, entry)
    if key not in used:
        return None
    meta = bytearray()
    for number, wire, v in xplane._fields(buf, *value):
        if number == 5:
            stat_id = next((sv for n, _, sv in xplane._fields(buf, *v)
                            if n == 1), None)
            if stat_id not in keep_stat_ids:
                continue
        meta += _copy(buf, number, wire, v)
    return _field(1, 0, key) + _field(2, 2, bytes(meta))


def cut(path, steps=2):
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    planes = xplane.read(
        path, want_plane=lambda n: bool(trace_reduce.DEVICE_PLANE.match(n)),
        want_line=lambda n: n == trace_reduce.MODULES_LINE)
    lo, hi = None, None
    for plane in planes:
        modules = plane.line(trace_reduce.MODULES_LINE).events
        first, _, _ = trace_reduce._step_window(modules)
        name = next(m.name for m in modules if m.start == first)
        runs = sorted(m.start for m in modules if m.name == name)
        lo = first if lo is None else min(lo, first)
        hi = runs[steps] if hi is None else max(hi, runs[steps])
    out = bytearray()
    for number, wire, v in xplane._fields(buf, 0, len(buf)):
        if number != 1:
            continue
        fields = list(xplane._fields(buf, *v))
        name = next((xplane._text(buf, pv) for n, _, pv in fields
                     if n == 2), "")
        device = bool(trace_reduce.DEVICE_PLANE.match(name))
        if not device and not name.startswith("/host:CPU"):
            continue
        stat_names, meta_names = {}, {}
        for n, _, pv in fields:
            if n not in (4, 5):
                continue
            key, value = xplane._map_entry(buf, pv)
            text = next((xplane._text(buf, mv)
                         for mn, _, mv in xplane._fields(buf, *value)
                         if mn == 2), "")
            (meta_names if n == 4 else stat_names)[key] = text
        keep_stat_ids = {k for k, text in stat_names.items()
                         if text in KEEP_STATS}

        def keep_event(ident, start):
            # the step's execution that opens step ``steps + 1`` stays,
            # as the window's end; of an op only what starts inside
            if not lo <= start <= hi:
                return False
            if device:
                return start < hi or meta_names.get(ident, "").startswith(
                    "jit_")
            return meta_names.get(ident, "").startswith(SPAN_PREFIXES)

        plane, used = bytearray(), set()
        for n, _, pv in fields:
            if n != 3:
                continue
            line_name = next((xplane._text(buf, lv) for ln, _, lv
                              in xplane._fields(buf, *pv) if ln == 2), "")
            if device and line_name not in DEVICE_LINES:
                continue
            kept = _line(buf, pv, keep_event)
            if kept is not None:
                plane += _field(3, 2, kept[0])
                used |= kept[1]
        for n, w, pv in fields:
            if n == 3:
                continue
            if n == 4:
                entry = _metadata(buf, pv, used, keep_stat_ids)
                if entry is not None:
                    plane += _field(4, 2, entry)
            else:
                plane += _copy(buf, n, w, pv)
        out += _field(1, 2, bytes(plane))
    return bytes(out)


if __name__ == "__main__":
    data = cut(sys.argv[1], int(sys.argv[3]) if len(sys.argv) > 3 else 2)
    with gzip.open(sys.argv[2], "wb", compresslevel=9) as f:
        f.write(data)
    print(f"{sys.argv[2]}: {len(data)} bytes, "
          f"{Path(sys.argv[2]).stat().st_size} packed")
