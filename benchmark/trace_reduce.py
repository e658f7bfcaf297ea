"""From the profiler's ``.xplane.pb`` to numbers: the one reduction every
PR's traced run goes through (checked on a trace recorded on the v5e,
``tests/data/``).

What a v5e trace holds (looked at by hand, PR 25; ``xplane.read`` lists
it): every chip is a plane ``/device:TPU:<n>``. Its line ``XLA
Modules`` has one event per executed program; ``XLA Ops`` one event per
executed HLO op on the core — the op's HLO text as its name, its short
name, HLO category and JAX name stack (``tf_op``) in its metadata —
where a ``while`` CONTAINS the ops of its body; ``Async XLA Ops`` the
spans of asynchronous copies and collectives from start to done, which
overlap the core's ops. The benchmark's own host spans (``bench/...``,
``jax.profiler.TraceAnnotation``) are events of the ``/host:CPU`` plane
on the same clock, to within a few tenths of a millisecond.

- The traced window of a chip runs from the start of the step program's
  first execution to the start of its last, so it holds whole steps with
  the gaps between them. ``busy`` is the union of the ``XLA Ops``
  intervals inside it (the wait of a ``*-done`` op counts as busy: the
  core is held). An idle gap is shared among the host spans that
  overlap it, by their overlap.
- An op's time in the breakdown is its SELF time (a ``while`` without
  its body's ops), under a stable class name (``classify``).
- A collective's time is the union of its intervals on both lines; its
  exposed part is what no other leaf op of the core overlaps."""

import collections
import re
from dataclasses import dataclass, field

from . import xplane

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench/"
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
CONTAINERS = ("while", "conditional", "call")
MIN_GAP_NS = 1000.0          # shorter holes between ops are not gaps
SHAPE = re.compile(r"\b(bf16|f32|f16|s32|u32|s8|u8)\[([0-9,]*)\]")


def union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def covered(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, holes):
    """The parts of merged ``intervals`` that merged ``holes`` do not
    cover."""
    out = []
    for s, e in intervals:
        cur = s
        for hs, he in holes:
            if he <= cur or hs >= e:
                continue
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
        if cur < e:
            out.append((cur, e))
    return out


def self_seconds(ops):
    """``[(op, seconds)]`` with each op's time less that of the ops it
    contains (events of one line nest, they do not cross)."""
    out, stack = [], []          # stack of [op, self_ns]
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0].end <= op.start:
            done = stack.pop()
            out.append((done[0], done[1] / 1e9))
        if stack:
            stack[-1][1] -= op.end - op.start
        stack.append([op, op.end - op.start])
    out.extend((op, ns / 1e9) for op, ns in stack)
    return out


def category(op):
    return str(op.stats.get("hlo_category", ""))


def is_collective(op):
    if COLLECTIVE.search(op.name) or COLLECTIVE.search(category(op)):
        return True
    # an asynchronous collective may be wrapped: async-start(...),
    # calls=%wrapped_all_reduce
    return category(op).startswith("async") and bool(
        COLLECTIVE.search(op.text.rsplit("calls=", 1)[-1]))


def is_mosaic(op):
    return 'custom_call_target="tpu_custom_call"' in op.text


def phase_of(op):
    """``fwd``, ``bwd`` or ``remat`` from JAX's name stack; nothing for
    what is outside the differentiated function (the optimizer)."""
    where = str(op.stats.get("tf_op", ""))
    if "rematted_computation" in where:
        return "remat"
    if "transpose(" in where:
        return "bwd"
    if "jvp(" in where:
        return "fwd"
    return ""


def output_shapes(op):
    """``[(dtype, dims), ...]`` an op returns, from its HLO text."""
    head = op.text.split(" = ", 1)[-1]
    head = re.split(r"\s[a-z][a-z0-9\-]*\(", head, maxsplit=1)[0]
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in SHAPE.findall(head)]


def flash_kind(op):
    """``(kind, [bh, S, hd])`` of a flash kernel's event, by the shapes
    it returns: forward (out, lse), dK/dV (dk, dv), dQ (dq)."""
    shapes = [dims for _, dims in output_shapes(op)]
    big = [s for s in shapes if len(s) == 3 and s[-1] > 1]
    if len(shapes) == 2 and len(big) == 1:
        return "fwd", big[0]
    if len(shapes) == 2 and len(big) == 2:
        return "dkv", big[0]
    if len(shapes) == 1 and len(big) == 1:
        return "dq", big[0]
    return None, None


def classify(op):
    """A stable class name: what kind of op, and in which phase."""
    tag = phase_of(op)
    suffix = f"__{tag}_" if tag else ""
    if is_mosaic(op):
        shapes = "__".join(f"{d}_{'_'.join(map(str, dims))}"
                           for d, dims in output_shapes(op))
        return f"mosaic_{shapes}{suffix}"
    if is_collective(op):
        return COLLECTIVE.search(
            op.name + " " + category(op) + " " + op.text).group(1)
    cat = category(op)
    if cat.startswith("convolution"):
        return f"matmul_fusion{suffix}"
    # ``copy.1368.remat_compressed`` -> ``copy``: XLA's numbering and
    # its own rematerialization suffixes are not part of a stable name
    base = op.name.lstrip("%").split(".", 1)[0]
    if base == "fusion" and cat:
        base = cat.replace(" ", "_")
    return f"{base}{suffix}"


@dataclass
class Summary:
    devices: int = 0
    window_s: float = 0.0
    busy_s: float = 0.0
    steps: int = 0
    class_seconds: dict = field(default_factory=dict)
    gap_seconds: dict = field(default_factory=dict)
    flash: dict = field(default_factory=dict)   # kind -> (calls, s, shape)
    collective_s: float = 0.0
    collective_exposed_s: float = 0.0

    def top_ops(self, n):
        return [[k, v] for k, v in sorted(
            self.class_seconds.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n):
        return [[k, v] for k, v in sorted(
            self.gap_seconds.items(), key=lambda kv: -kv[1])[:n]]


def _shares(gap, spans):
    """``(span name, ns)`` for each of the benchmark's host spans that
    overlaps ``gap``, and ``host/other`` for what none of them covers
    (the spans of one thread do not overlap each other)."""
    left = gap[1] - gap[0]
    for s, e, name in spans:
        if s >= gap[1]:
            break
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > 0:
            left -= cover
            yield name, cover
    if left > 0:
        yield "host/other", left


def _step_window(modules):
    """``(lo, hi, steps)``: from the first execution of the program
    that takes most time to the start of its last one."""
    total = collections.Counter()
    for m in modules:
        total[m.name] += m.end - m.start
    if not total:
        return None
    step_name = total.most_common(1)[0][0]
    runs = sorted((m for m in modules if m.name == step_name),
                  key=lambda m: m.start)
    if len(runs) < 2:
        return runs[0].start, runs[0].end, 1
    return runs[0].start, runs[-1].start, len(runs) - 1


def _clipped(events, lo, hi):
    out = []
    for e in events:
        if e.end > lo and e.start < hi:
            out.append(xplane.Event(e.name, e.text, max(e.start, lo),
                                    min(e.end, hi), e.stats))
    return out


def summarize(path):
    """Reduce the trace at ``path``; values are means over the chips."""
    return summarize_planes(xplane.read(
        path,
        want_plane=lambda n: bool(DEVICE_PLANE.match(n))
        or n.startswith("/host:CPU")))


def summarize_planes(planes):
    spans = sorted((e.start, e.end, e.name)
                   for p in planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.startswith(SPAN_PREFIX))
    out = Summary()
    classes = collections.Counter()
    gaps = collections.Counter()
    flash = {}
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops_line, modules = plane.line(OPS_LINE), plane.line(MODULES_LINE)
        if ops_line is None or modules is None:
            continue
        window = _step_window(modules.events)
        if window is None:
            continue
        lo, hi, steps = window
        ops = _clipped(ops_line.events, lo, hi)
        async_line = plane.line(ASYNC_LINE)
        async_ops = _clipped(async_line.events, lo, hi) if async_line \
            else []
        busy = union((op.start, op.end) for op in ops)
        out.devices += 1
        out.steps = steps
        out.window_s += (hi - lo) / 1e9
        out.busy_s += covered(busy) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge - gs >= MIN_GAP_NS:
                for name, ns in _shares((gs, ge), spans):
                    gaps[name] += ns / 1e9
        coll = union((op.start, op.end) for op in ops + async_ops
                     if is_collective(op))
        compute = union((op.start, op.end) for op in ops
                        if not is_collective(op)
                        and category(op) not in CONTAINERS)
        out.collective_s += covered(coll) / 1e9
        out.collective_exposed_s += covered(subtract(coll, compute)) / 1e9
        for op, seconds in self_seconds(ops):
            classes[classify(op)] += seconds
            if is_mosaic(op):
                kind, shape = flash_kind(op)
                if kind is not None:
                    calls, secs, _ = flash.get(kind, (0, 0.0, None))
                    flash[kind] = (calls + 1, secs + seconds, shape)
    n = max(out.devices, 1)
    out.window_s /= n
    out.busy_s /= n
    out.collective_s /= n
    out.collective_exposed_s /= n
    out.class_seconds = {k: v / n for k, v in classes.items()}
    out.gap_seconds = {k: v / n for k, v in gaps.items()}
    out.flash = {k: (c / n, s / n, shape)
                 for k, (c, s, shape) in flash.items()}
    return out
