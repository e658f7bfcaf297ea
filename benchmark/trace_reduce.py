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
  exposed part is what no other leaf op of the core overlaps.
- A piece of the program's work is found by the NAME the program put on
  it (``docs/tracing.md#names`` in the program's docs): the innermost
  ``hvd_<name>`` of the op's name stack (``name_of``), whatever the
  model. A flash kernel is a Mosaic call whose innermost name is
  ``hvd_flash_fwd``, ``_dkv`` or ``_dq``; any other Mosaic call (XLA's
  own lowering of ``lax.ragged_dot``, a later kernel) is a class of the
  breakdown like every op and no flash kernel, whatever it returns."""

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
# An ``hvd_<name>`` in a name stack, as a whole word. The jitted
# function's own name heads every stack (``jit(hvd_train_step)/...``)
# and is no scope: a name directly inside ``jit(`` is skipped.
NAME = re.compile(r"(?<![A-Za-z0-9_])(?<!jit\()hvd_[a-z0-9_]+"
                  r"(?![A-Za-z0-9_])")
UNSCOPED = "unscoped"
KERNEL_PREFIX = "hvd_flash_"
# the ``name=`` of the program's three ``pallas_call``s
FLASH_KERNELS = {"hvd_flash_fwd": "fwd", "hvd_flash_dkv": "dkv",
                 "hvd_flash_dq": "dq"}


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


def name_of_stack(stack):
    """The innermost of the program's names in a name stack (a string);
    ``unscoped`` with none."""
    found = NAME.findall(stack)
    return found[-1] if found else UNSCOPED


def name_of(op):
    return name_of_stack(str(op.stats.get("tf_op", "")))


def _shapes(text):
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in SHAPE.findall(text)]


def _head_and_operands(op):
    """An op's HLO text on either side of its opcode's ``(``: what it
    returns, and its operands up to the ``)`` that closes the list (a
    tiled layout, ``{2,1,0:T(8,128)(2,1)}``, holds parentheses of its
    own)."""
    text = op.text.split(" = ", 1)[-1]
    parts = re.split(r"\s[a-z][a-z0-9\-]*\(", text, maxsplit=1)
    if len(parts) < 2:
        return parts[0], ""
    depth = 1
    for i, c in enumerate(parts[1]):
        depth += (c == "(") - (c == ")")
        if not depth:
            return parts[0], parts[1][:i]
    return parts[0], parts[1]


def output_shapes(op):
    """``[(dtype, dims), ...]`` an op returns, from its HLO text."""
    return _shapes(_head_and_operands(op)[0])


def operand_shapes(op):
    """``[(dtype, dims), ...]`` of an op's operands, from its HLO text
    (layouts in braces hold no brackets, so a shape is found whole)."""
    return _shapes(_head_and_operands(op)[1])


def flash_kind(op):
    """``(kind, (bh, S, d_qk, d_v))`` of a flash kernel's event, or
    ``(None, None)``. WHETHER the call is one is decided by the name the
    program gave it, never by what it returns. Its sizes are its
    operands': all three kernels take ``q, k, v`` first, ``[bh, S, d]``
    each; ``d_qk`` is ``q``'s width and ``d_v`` is ``v``'s."""
    kind = FLASH_KERNELS.get(name_of(op)) if is_mosaic(op) else None
    if kind is None:
        return None, None
    big = [dims for _, dims in operand_shapes(op) if len(dims) == 3]
    if len(big) < 3:
        return None, None
    (bh, seq, d_qk), (_, _, d_v) = big[0], big[2]
    return kind, (bh, seq, d_qk, d_v)


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
    # (kind, bh, S, d_qk, d_v) -> (calls, seconds): the flash kernels,
    # call by call where a model calls them at more than one shape
    flash: dict = field(default_factory=dict)
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


def read_planes(path):
    """The planes of the trace at ``path`` that any reduction here or in
    ``program_trace`` reads: the chips and the host. A run parses its
    trace ONCE, here."""
    return xplane.read(
        path, want_plane=lambda n: bool(DEVICE_PLANE.match(n))
        or n.startswith("/host:"))


def summarize(path):
    """Reduce the trace at ``path``; values are means over the chips."""
    return summarize_planes(read_planes(path))


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
            kind, sizes = flash_kind(op)
            if kind is not None:
                calls, secs = flash.get((kind, *sizes), (0, 0.0))
                flash[(kind, *sizes)] = (calls + 1, secs + seconds)
    n = max(out.devices, 1)
    out.window_s /= n
    out.busy_s /= n
    out.collective_s /= n
    out.collective_exposed_s /= n
    out.class_seconds = {k: v / n for k, v in classes.items()}
    out.gap_seconds = {k: v / n for k, v in gaps.items()}
    out.flash = {k: (c / n, s / n) for k, (c, s) in flash.items()}
    return out
