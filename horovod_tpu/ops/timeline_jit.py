"""Jit-path timeline observability.

The reference's timeline instruments every collective it executes with
negotiation + activity phases (timeline.h:33-121, operations.cc:728-740)
— possible because its collectives are discrete library calls. On the
TPU-native jit path the collectives live INSIDE compiled XLA programs
(`DistributedOptimizer`'s in-jit psum route, everything in `parallel/`),
where Python cannot emit per-op events. This module closes that
observability gap with the two pieces that are possible from outside a
compiled program, writing into the SAME Chrome trace the engine's
negotiation phases land in:

1. ``step(name)`` — brackets each compiled-step execution as an
   ``XLA_STEP`` span on the Horovod timeline (native writer when the C++
   core owns the timeline, the Python writer otherwise), so the trace
   shows exactly when the jit path was on device.
2. ``merge_profiler_trace(...)`` — merges a ``jax.profiler.trace``
   capture (its ``*.trace.json.gz`` is already Chrome-trace JSON, with
   per-device lanes carrying the compiled programs' device time) into
   the Horovod timeline file: pids are re-interned after the engine's,
   and timestamps are shifted so the capture aligns with the first
   ``XLA_STEP`` bracket (clock bases differ; alignment is anchored, not
   clock-exact — the device lanes' durations and internal structure are
   the payload).

Usage (also docs/timeline.md):

    with jax.profiler.trace(logdir):
        for _ in range(steps):
            with hvd.timeline_jit_step("train"):
                state = train_step(state, batch)
    hvd.shutdown()   # close the timeline file
    hvd.merge_profiler_trace(timeline_path, logdir)

CLI: ``python -m horovod_tpu.ops.timeline_jit TIMELINE LOGDIR [-o OUT]``.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
from typing import List, Optional

_PID_GAP = 10000  # profiler pids re-based above the engine's interned pids


@contextlib.contextmanager
def step(name: str = "step"):
    """Bracket a compiled-step execution on the Horovod timeline as an
    ``XLA_STEP`` span under process ``jit::<name>``. When no timeline
    path is configured this is a no-op that never touches the engine —
    the always-on usage (bracketing every training step) must not add
    lock traffic to the hot path."""
    from ..utils import env as _env
    if not _env.timeline_path():
        yield
        return
    from . import collective as _c
    eng = _c.engine()
    tensor = f"jit::{name}"
    core = eng._ensure_native()
    if core is not None and core.timeline_enabled():
        core.timeline_activity_start(tensor, "XLA_STEP")
        try:
            yield
        finally:
            core.timeline_activity_end(tensor)
        return
    tl = eng._ensure_timeline()
    if tl is not None:
        tl.start(tensor, "XLA_STEP")
        try:
            yield
        finally:
            tl.end(tensor)
        return
    yield


def _load_timeline(path: str) -> List[dict]:
    """Read a (possibly unterminated — see PyTimeline.close) Chrome
    trace array."""
    txt = open(path).read().strip()
    if txt.endswith(","):
        txt = txt[:-1]
    if not txt.endswith("]"):
        txt += "\n]"
    return json.loads(txt)


def _newest_capture(profile_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        profile_dir, "**", "*.trace.json.gz"), recursive=True),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(
            f"no *.trace.json.gz under {profile_dir} — did "
            "jax.profiler.trace() run?")
    return paths[-1]


def merge_profiler_trace(timeline_path: str, profile_dir: str,
                         out_path: Optional[str] = None) -> str:
    """Merge the newest ``jax.profiler`` capture under ``profile_dir``
    into the Horovod timeline at ``timeline_path``.

    Returns the merged file's path (``out_path`` or
    ``<timeline>.merged.json``). Call after the timeline file is closed
    (``hvd.shutdown()``) — merging a live file would race its writer.
    """
    base = _load_timeline(timeline_path)
    capture = json.loads(gzip.open(_newest_capture(profile_dir)).read())
    prof = capture.get("traceEvents", [])

    max_pid = max((e.get("pid", 0) for e in base), default=0)
    pid_off = max_pid + _PID_GAP

    # Anchor: align the capture's earliest timestamp with the first
    # XLA_STEP bracket (the step the user profiled); fall back to the
    # timeline's own start.
    anchor_ts = None
    jit_pids = {e["pid"] for e in base
                if e.get("name") == "process_name"
                and str(e.get("args", {}).get("name", "")).startswith("jit::")}
    for e in base:
        if e.get("ph") == "B" and e.get("pid") in jit_pids:
            anchor_ts = e.get("ts", 0)
            break
    if anchor_ts is None:
        anchor_ts = min((e.get("ts", 0) for e in base
                         if e.get("ph") != "M"), default=0)
    prof_ts = [e["ts"] for e in prof
               if e.get("ph") not in (None, "M") and "ts" in e]
    ts_off = anchor_ts - (min(prof_ts) if prof_ts else 0)

    merged = list(base)
    for e in prof:
        e = dict(e)
        if "pid" in e:
            e["pid"] = e["pid"] + pid_off
        if "ts" in e and e.get("ph") != "M":
            e["ts"] = e["ts"] + ts_off
        merged.append(e)

    out = out_path or timeline_path + ".merged.json"
    with open(out, "w") as f:
        json.dump(merged, f)
    return out


def _main(argv=None):  # pragma: no cover - thin CLI
    import argparse
    ap = argparse.ArgumentParser(
        description="Merge a jax.profiler capture into a Horovod "
                    "timeline (Chrome trace)")
    ap.add_argument("timeline")
    ap.add_argument("profile_dir")
    ap.add_argument("-o", "--out", default=None)
    args = ap.parse_args(argv)
    print(merge_profiler_trace(args.timeline, args.profile_dir, args.out))


if __name__ == "__main__":  # pragma: no cover
    _main()
