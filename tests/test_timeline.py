"""Timeline test — structural mirror of the reference's
test/test_timeline.py:41-57: run collectives with HOROVOD_TIMELINE set,
then grep the Chrome-trace JSON for the negotiation and execution phases."""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.ops import collective

hvd.init()
hvd.allreduce(jnp.ones((16, 16)), name="timeline.test.allreduce")
hvd.allgather(jnp.ones((4, 4)), name="timeline.test.allgather")
hvd.broadcast(jnp.ones((4,)), 0, name="timeline.test.broadcast")
collective.engine().shutdown()   # flush + close the timeline writer
"""


def test_timeline_records_phases(tmp_path):
    tl = tmp_path / "timeline.json"
    env = dict(os.environ)
    env["HOROVOD_TIMELINE"] = str(tl)
    env["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-2000:]
    text = tl.read_text()
    # Negotiation + op phases (reference test_timeline.py greps
    # NEGOTIATE_ALLREDUCE / ALLREDUCE / CYCLE_START).
    assert "NEGOTIATE_ALLREDUCE" in text
    assert '"ALLREDUCE"' in text
    assert "NEGOTIATE_ALLGATHER" in text
    assert "NEGOTIATE_BROADCAST" in text
    assert "CYCLE_START" in text
    assert "XLA_ALLREDUCE" in text
    # Tensor names became Chrome "processes" (timeline.cc:70-90 parity).
    assert "timeline.test.allreduce" in text

    # Every line between the brackets must be valid JSON records.
    body = text.strip()
    assert body.startswith("[")
    records = [ln.rstrip(",") for ln in body.splitlines()[1:] if ln.strip()
               and ln.strip() not in ("[", "]")]
    for ln in records[:50]:
        json.loads(ln)


class TestPythonTimeline:
    """The Python timeline writer covers the two paths the native core
    cannot: the Python control-plane fallback and multi-process mode."""

    def test_python_fallback_timeline(self, tmp_path):
        tl = tmp_path / "py_timeline.json"
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "HOROVOD_TPU_DISABLE_NATIVE": "1",
            "HOROVOD_TPU_TIMELINE": str(tl),
            "PYTHONPATH": os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
        })
        script = (
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "import jax.numpy as jnp\n"
            "import horovod_tpu as hvd\n"
            "from horovod_tpu.ops import collective\n"
            "hvd.init()\n"
            "hvd.allreduce(jnp.ones((8, 8)), name='pytl.allreduce')\n"
            "hvd.broadcast(jnp.ones((4,)), 0, name='pytl.broadcast')\n"
            "collective.engine().shutdown()\n")
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, timeout=300,
                              env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        text = tl.read_text()
        events = json.loads(text)   # valid catapult JSON
        assert any(e.get("name") == "NEGOTIATE_ALLREDUCE" for e in events)
        assert any(e.get("name") == "XLA_ALLREDUCE" for e in events)
        assert "pytl.allreduce" in text and "pytl.broadcast" in text

    @pytest.mark.slow
    def test_multiprocess_timeline(self, tmp_path):
        """Rank 0 writes the timeline in multi-process mode (reference:
        rank-0-only, operations.cc:1824-1829)."""
        from horovod_tpu.runner.api import run

        tl = tmp_path / "mp_timeline.json"

        def worker(path):
            import os

            import jax.numpy as jnp

            import horovod_tpu as hvd
            from horovod_tpu.ops import collective

            os.environ["HOROVOD_TPU_TIMELINE"] = path
            hvd.init()
            hvd.allreduce(jnp.ones((8,)), name="mptl.sum")
            hvd.allgather(jnp.ones((2, 2)), name="mptl.gather")
            collective.engine().shutdown()
            return hvd.process_rank()

        env = {"JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
        results = run(worker, args=(str(tl),), np=2, extra_env=env,
                      start_timeout=300)
        assert sorted(results) == [0, 1]
        text = tl.read_text()
        assert "NEGOTIATE_ALLREDUCE" in text
        assert "XLA_ALLREDUCE" in text and "XLA_ALLGATHER" in text
        assert "mptl.sum" in text and "mptl.gather" in text


JIT_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import optax
import horovod_tpu as hvd
from horovod_tpu.ops import collective

logdir = sys.argv[1]

hvd.init()
mesh = hvd.mesh()
from jax.sharding import NamedSharding, PartitionSpec as P

params = {"w": jnp.ones((16, 16))}
opt = hvd.DistributedGradientTransformation(optax.sgd(0.1))
opt_state = opt.init(params)
x = jax.device_put(jnp.ones((8, 16)), NamedSharding(mesh, P("dp")))

@jax.jit
def train_step(params, opt_state, x):
    def loss(p):
        return jnp.sum((x @ p["w"]) ** 2)
    grads = jax.grad(loss)(params)
    updates, opt_state = opt.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state

params, opt_state = train_step(params, opt_state, x)  # compile outside
with jax.profiler.trace(logdir):
    for _ in range(2):
        with hvd.timeline_jit_step("train"):
            params, opt_state = train_step(params, opt_state, x)
        jax.block_until_ready(params)
collective.engine().shutdown()   # close the timeline writer
"""


class TestJitPathTimeline:
    """The jit path (in-jit psum via
    DistributedGradientTransformation) must be visible in the timeline —
    XLA_STEP brackets from hvd.timeline_jit_step plus the device lanes
    of a jax.profiler capture merged into the same Chrome trace."""

    @pytest.mark.parametrize("native", ["0", "1"])
    def test_jit_step_brackets_and_profiler_merge(self, tmp_path, native):
        tl = tmp_path / "timeline.json"
        logdir = tmp_path / "profile"
        env = dict(os.environ)
        env["HOROVOD_TIMELINE"] = str(tl)
        env["HOROVOD_TPU_DISABLE_NATIVE"] = (
            "0" if native == "1" else "1")
        proc = subprocess.run(
            [sys.executable, "-c", JIT_SCRIPT, str(logdir)], env=env,
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert proc.returncode == 0, proc.stderr[-3000:]

        from horovod_tpu.ops import timeline_jit
        events = timeline_jit._load_timeline(str(tl))
        # XLA_STEP brackets exist under a jit:: process
        jit_pids = {e["pid"] for e in events
                    if e.get("name") == "process_name"
                    and str(e.get("args", {}).get("name", ""))
                    .startswith("jit::")}
        assert jit_pids, "no jit:: process in the timeline"
        steps = [e for e in events
                 if e.get("name") == "XLA_STEP" and e.get("ph") == "B"]
        assert len(steps) >= 2, "expected one XLA_STEP span per step"

        out = timeline_jit.merge_profiler_trace(str(tl), str(logdir))
        merged = json.load(open(out))
        # profiler lanes are merged, re-based above the engine's pids
        # (on TPU these include '/device:TPU:*' with the programs'
        # device time; the pure-CPU test backend exposes '/host:CPU')
        lanes = [e for e in merged
                 if e.get("name") == "process_name"
                 and e.get("pid", 0) >= timeline_jit._PID_GAP]
        assert lanes, "no profiler lanes merged into the timeline"
        # and the merged duration events are ts-anchored at the first
        # XLA_STEP bracket, not on the profiler's own clock base
        anchor = steps[0]["ts"]
        prof_x = [e for e in merged if e.get("ph") == "X"
                  and e.get("pid", 0) >= timeline_jit._PID_GAP]
        assert prof_x, "no duration events merged"
        assert min(e["ts"] for e in prof_x) >= anchor - 1


class TestCycleMarkerScope:
    def test_mark_cycle_emits_global_scope_instant(self, tmp_path):
        """Chrome/Perfetto render "ph": "i" instant events thread-scoped
        unless "s" says otherwise; cycle markers are trace-wide
        boundaries, so they must carry "s": "g" (Trace Event Format
        §Instant Events). Asserts the emitted JSON directly."""
        from horovod_tpu.ops.timeline_py import PyTimeline

        path = tmp_path / "cycles.json"
        tl = PyTimeline(str(path))
        tl.mark_cycle()
        tl.mark_cycle()
        tl.close()
        events = json.loads(path.read_text())
        cycles = [e for e in events
                  if e.get("name") == "CYCLE_START" and e.get("ph") == "i"]
        assert len(cycles) == 2
        for e in cycles:
            assert e.get("s") == "g", e
        # The _cycles pseudo-process is still named for the viewer.
        assert any(e.get("ph") == "M"
                   and e.get("args", {}).get("name") == "_cycles"
                   for e in events)


class TestWriterExitSafety:
    """Satellite: events buffered in the writer deque must not be lost
    when a rank exits without a clean shutdown() (crash/SIGTERM paths of
    the elastic driver)."""

    def test_atexit_flushes_unclosed_writer(self, tmp_path):
        """Interpreter exit without close(): the atexit hook drains the
        deque and terminates the JSON array."""
        path = tmp_path / "atexit.json"
        script = (
            "import sys\n"
            "sys.path.insert(0, sys.argv[2])\n"
            "from horovod_tpu.ops.timeline_py import PyTimeline\n"
            "tl = PyTimeline(sys.argv[1])\n"
            "for i in range(200):\n"
            "    tl.negotiate_start(f'exit.t{i}', 'allreduce')\n"
            "    tl.negotiate_end(f'exit.t{i}', group=i)\n"
            "sys.exit(0)\n")   # NO close() — atexit must flush
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run([sys.executable, "-c", script, str(path),
                               root],
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        events = json.loads(path.read_text())   # strict parse: complete
        assert sum(e.get("ph") == "B" for e in events) == 200
        assert any(e.get("args", {}).get("group") == 199 for e in events)

    def test_killed_writer_leaves_valid_prefix(self, tmp_path):
        """SIGKILL mid-stream: the file must be valid JSON up to the
        last drained event (the tolerant loader the merge tool uses),
        with every drained record intact — no torn lines."""
        import signal
        import time as _time

        path = tmp_path / "killed.json"
        script = (
            "import sys, time\n"
            "sys.path.insert(0, sys.argv[2])\n"
            "from horovod_tpu.ops.timeline_py import PyTimeline\n"
            "tl = PyTimeline(sys.argv[1])\n"
            "for i in range(500):\n"
            "    tl.negotiate_start(f'kill.t{i}', 'allreduce')\n"
            "    tl.negotiate_end(f'kill.t{i}', group=i)\n"
            "time.sleep(0.5)\n"           # let the drain thread flush
            "print('DRAINED', flush=True)\n"
            "time.sleep(60)\n")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.Popen([sys.executable, "-c", script, str(path),
                                 root],
                                stdout=subprocess.PIPE, text=True)
        try:
            assert proc.stdout.readline().strip() == "DRAINED"
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        _time.sleep(0.1)
        from horovod_tpu.ops import timeline_jit
        events = timeline_jit._load_timeline(str(path))  # tolerant parse
        bs = [e for e in events if e.get("ph") == "B"]
        assert len(bs) == 500   # everything drained before the kill
        for e in events[:50]:
            assert "ph" in e or e.get("name") in ("process_name",
                                                  "horovod_tpu_trace_meta")


class TestPerRankCapture:
    """Tentpole: HOROVOD_TPU_TIMELINE with a {rank} placeholder makes
    EVERY rank write a trace, each carrying a clock header + sidecar for
    the offline merger (docs/tracing.md)."""

    def test_placeholder_resolution(self, monkeypatch):
        from horovod_tpu.utils import env as _env
        monkeypatch.setenv("HOROVOD_TPU_TIMELINE", "/tmp/t.{rank}.json")
        assert _env.resolved_timeline_path(0) == "/tmp/t.0.json"
        assert _env.resolved_timeline_path(3) == "/tmp/t.3.json"
        monkeypatch.setenv("HOROVOD_TPU_TIMELINE", "/tmp/t.json")
        assert _env.resolved_timeline_path(0) == "/tmp/t.json"
        assert _env.resolved_timeline_path(1) is None   # rank-0-only mode

    def test_single_process_placeholder_writes_rank0_with_meta(
            self, tmp_path):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "HOROVOD_TPU_DISABLE_NATIVE": "1",
            "HOROVOD_TPU_TIMELINE": str(tmp_path / "t.{rank}.json"),
            "PYTHONPATH": os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
        })
        script = (
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "import jax.numpy as jnp\n"
            "import horovod_tpu as hvd\n"
            "from horovod_tpu.ops import collective\n"
            "hvd.init()\n"
            "hvd.allreduce(jnp.ones((8,)), name='prk.allreduce')\n"
            "collective.engine().shutdown()\n")
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, timeout=300,
                              env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        path = tmp_path / "t.0.json"
        events = json.loads(path.read_text())
        meta = [e for e in events
                if e.get("name") == "horovod_tpu_trace_meta"]
        assert meta, "no clock header in the per-rank trace"
        args = meta[-1]["args"]
        assert args["rank"] == 0 and args["clock_synced"] is True
        assert args["start_mono_us"] > 0
        # Sidecar for the merge tool (and for native-writer parity).
        sidecar = json.loads((tmp_path / "t.0.json.clock.json")
                             .read_text())
        assert sidecar["rank"] == 0
        # Fused-group ids recorded on the NEGOTIATE spans.
        assert any("group" in (e.get("args") or {}) for e in events
                   if e.get("ph") in ("E", "X"))


class TestMergeCli:
    """The timeline_jit merge CLI on SYNTHETIC inputs: no profiler run,
    no engine — just a timeline file and a fake jax.profiler capture
    directory, exercising exactly what the CLI does."""

    def _make_inputs(self, tmp_path):
        import gzip

        tl = tmp_path / "timeline.json"
        # An unterminated file (PyTimeline.close's slow-writer escape
        # hatch) — _load_timeline must tolerate the missing bracket.
        tl.write_text(
            '[\n'
            '{"name": "process_name", "ph": "M", "pid": 0,'
            ' "args": {"name": "jit::train"}},\n'
            '{"ph": "B", "ts": 1000, "pid": 0, "tid": 0,'
            ' "name": "XLA_STEP"},\n'
            '{"ph": "E", "ts": 5000, "pid": 0, "tid": 0},\n')
        profdir = tmp_path / "profile" / "plugins" / "profile" / "run1"
        profdir.mkdir(parents=True)
        capture = {
            "traceEvents": [
                {"name": "process_name", "ph": "M", "pid": 3,
                 "args": {"name": "/device:TPU:0"}},
                {"ph": "X", "ts": 777000, "dur": 300, "pid": 3,
                 "tid": 1, "name": "fusion.1"},
                {"ph": "X", "ts": 777400, "dur": 200, "pid": 3,
                 "tid": 1, "name": "all-reduce.2"},
            ]}
        with gzip.open(profdir / "host.trace.json.gz", "wt") as f:
            json.dump(capture, f)
        return tl, tmp_path / "profile"

    def test_cli_merges_and_interleaves(self, tmp_path, capsys):
        from horovod_tpu.ops import timeline_jit

        tl, profdir = self._make_inputs(tmp_path)
        out = tmp_path / "merged.json"
        timeline_jit._main([str(tl), str(profdir), "-o", str(out)])
        assert capsys.readouterr().out.strip() == str(out)

        merged = json.loads(out.read_text())
        # Both streams present: the timeline's own events...
        assert any(e.get("name") == "XLA_STEP" for e in merged)
        # ...and the capture's device lanes, pid-rebased above the gap.
        prof = [e for e in merged
                if e.get("pid", 0) >= timeline_jit._PID_GAP]
        assert any(e.get("name") == "all-reduce.2" for e in prof)
        assert any(e.get("args", {}).get("name") == "/device:TPU:0"
                   for e in prof if e.get("ph") == "M")
        # Interleaved on ONE clock: the capture's earliest event is
        # anchored at the first XLA_STEP bracket (ts 1000), so its
        # duration events sit inside the step span, not at ts 777000.
        prof_x = [e for e in prof if e.get("ph") == "X"]
        assert prof_x
        assert min(e["ts"] for e in prof_x) == 1000
        assert max(e["ts"] for e in prof_x) <= 5000

    def test_cli_default_output_path(self, tmp_path, capsys):
        from horovod_tpu.ops import timeline_jit

        tl, profdir = self._make_inputs(tmp_path)
        timeline_jit._main([str(tl), str(profdir)])
        printed = capsys.readouterr().out.strip()
        assert printed == str(tl) + ".merged.json"
        json.loads(open(printed).read())

    def test_cli_missing_capture_errors(self, tmp_path):
        from horovod_tpu.ops import timeline_jit

        tl = tmp_path / "t.json"
        tl.write_text("[\n]")
        empty = tmp_path / "empty"
        empty.mkdir()
        with pytest.raises(FileNotFoundError):
            timeline_jit._main([str(tl), str(empty)])
