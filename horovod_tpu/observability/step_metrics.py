"""Per-step training telemetry shared by the framework shims.

The Horovod paper's headline diagnostic is *collective share of step
time* — the number that tells you whether you are compute-bound or
communication-bound, and whether tensor fusion / compression is paying
off (PAPERS.md, arxiv 1802.05799 §5). :class:`StepTimer` computes it
from the registry itself: the engine accounts every fused collective's
execution seconds into ``hvdtpu_op_execute_seconds_total`` (across ALL
ops — allreduce, allgather, broadcast) and its control-plane wait into
the ``negotiate`` phase of ``hvdtpu_op_phase_seconds``, so the
breakdown needs no framework-specific hooks into the collective path.

Per-step attribution (docs/metrics.md, docs/postmortem.md): each step
is decomposed into

  - ``input``      the gap between the previous step's ``end()`` and
                   this step's ``begin()`` — time spent waiting on the
                   data pipeline,
  - ``h2d``        host→device transfer, measured when the loop calls
                   :meth:`mark_h2d_done` after staging the batch,
  - ``collective`` fused-program execute seconds plus negotiate-phase
                   wait (the engine's own counters, delta over the
                   step),
  - ``compute``    the step remainder.

exported as ``hvdtpu_step_phase_seconds{phase=}`` histograms and
``hvdtpu_step_phase_share{phase=}`` gauges, plus an MFU gauge (FLOPs
from ``lowered.cost_analysis()`` via :func:`flops_of_lowered` or a
user-supplied ``flops_per_step``) and HBM live/peak gauges from
``device.memory_stats()``. When the engine's Python timeline is active,
the same breakdown is emitted as ``STEP_*`` spans so ``python -m
horovod_tpu.tools.trace report`` can render a per-rank input-bound vs
compute-bound vs comm-bound verdict (docs/tracing.md).

One class serves all three shims:

  - Keras: :class:`horovod_tpu.keras.callbacks.MetricsCallback` wraps it
    in the callback API.
  - torch / TF: exported as ``horovod_tpu.torch.StepMetrics`` /
    ``horovod_tpu.tensorflow.StepMetrics`` — use as a context manager
    around each step::

        metrics = hvd.torch.StepMetrics(batch_size=64)
        for batch in loader:
            with metrics:
                train_step(batch)

Recorded metrics (all labeled ``framework=...`` unless noted):
  - ``hvdtpu_step_seconds`` (histogram)
  - ``hvdtpu_step_phase_seconds`` / ``hvdtpu_step_phase_share``
    (histogram / gauge, also labeled ``phase=``)
  - ``hvdtpu_samples_total`` (counter)
  - ``hvdtpu_samples_per_second`` (gauge, last step)
  - ``hvdtpu_collective_step_share`` (gauge in [0, 1], last step;
    ``hvdtpu_allreduce_step_share`` remains as a deprecated alias)
  - ``hvdtpu_mfu`` / ``hvdtpu_model_flops_per_second`` (gauges, only
    when a FLOPs-per-step figure is known; MFU additionally needs a
    peak — HOROVOD_TPU_PEAK_FLOPS or the TPU device-kind table)
  - ``hvdtpu_hbm_bytes_in_use`` / ``hvdtpu_hbm_peak_bytes`` (gauges,
    labeled ``device=``; falls back to host RSS when the backend has no
    ``memory_stats``, labeled ``device="host"``)
"""

from __future__ import annotations

import time
from typing import Optional

from . import registry as _reg
from ..utils import env as _env

STEP_PHASES = ("input", "h2d", "compute", "collective")

# Published peak dense bf16 FLOP/s per chip by device kind: the
# program's one table (the MFU denominator when HOROVOD_TPU_PEAK_FLOPS
# is unset, and bench.py's). Matching is substring-based on jax's
# Device.device_kind (v5 lite == v5e; v6 lite == v6e/Trillium). CPU
# backends have no entry — MFU is simply not exported there unless the
# env var supplies a peak. benchmark/peaks.py keeps its own copy by
# design; tests/test_metrics.py holds the two equal.
PEAK_FLOPS_BY_KIND = (
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5 lite", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_of_kind(device_kind: str) -> Optional[float]:
    """Published peak of one chip of ``device_kind``; None for a kind
    the table does not name (the CPU included)."""
    kind = str(device_kind).lower()
    for marker, peak in PEAK_FLOPS_BY_KIND:
        if marker in kind:
            return peak
    return None


def flops_of_lowered(lowered) -> Optional[float]:
    """FLOPs of one invocation of a lowered/compiled jax computation,
    from XLA's ``cost_analysis()`` — pass the result as
    ``StepTimer(..., flops_per_step=...)``::

        lowered = jax.jit(train_step).lower(params, batch)
        timer = StepTimer("torch", flops_per_step=flops_of_lowered(
            lowered.compile()))

    Accepts a ``jax.stages.Lowered`` or ``Compiled``; returns None when
    the backend exposes no cost analysis (the caller then supplies its
    own analytic figure)."""
    try:
        ca = lowered.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not ca:
        return None
    flops = ca.get("flops", 0.0)
    return float(flops) if flops else None


def _local_peak_flops() -> Optional[float]:
    """Peak FLOP/s across this process's devices (env override first,
    then the device-kind table); None when unknown."""
    env_peak = _env.peak_flops()
    if env_peak is not None:
        return env_peak
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return None
    total = sum(peak_flops_of_kind(getattr(d, "device_kind", "")) or 0.0
                for d in devices)
    return total or None


def _collective_execute_seconds() -> float:
    """Execute-seconds across ALL collective ops (allreduce, allgather,
    broadcast — fused groups of any kind count; the old implementation
    read only ``op="allreduce"`` and under-reported mixed workloads)."""
    fam = _reg.registry().counter(
        "hvdtpu_op_execute_seconds_total",
        "Cumulative wall seconds executing fused collective groups")
    return sum(child.value for _, child in fam.items())


def _negotiate_wait_seconds() -> float:
    """Cumulative negotiate-phase seconds across all ops — the
    control-plane wait (enqueue → group delivered), which is where time
    waiting on a late peer lands."""
    fam = _reg.registry().histogram(
        "hvdtpu_op_phase_seconds",
        "Per-collective latency by lifecycle phase (negotiate = "
        "enqueue until the group is agreed/delivered; queue = "
        "delivery until XLA dispatch; execute = fused program wall "
        "time)", buckets=_reg.LATENCY_BUCKETS)
    return sum(child.sum for key, child in fam.items()
               if 'phase="negotiate"' in key)


class StepTimer:
    """Brackets one training step; records step time, samples/sec, the
    collective share of step time, and the input/h2d/compute/collective
    attribution. Cheap enough to leave on: a few ``time.perf_counter``
    calls and registry writes per step.

    ``flops_per_step`` (model FLOPs executed per step, e.g. from
    :func:`flops_of_lowered`) enables the ``hvdtpu_mfu`` /
    ``hvdtpu_model_flops_per_second`` gauges."""

    def __init__(self, framework: str, batch_size: Optional[int] = None,
                 flops_per_step: Optional[float] = None):
        self.framework = framework
        self.batch_size = batch_size
        self.flops_per_step = flops_per_step
        r = _reg.registry()
        labels = {"framework": framework}
        self._h_step = r.histogram(
            "hvdtpu_step_seconds", "Training step wall time",
            buckets=_reg.LATENCY_BUCKETS).labels(**labels)
        phase_h = r.histogram(
            "hvdtpu_step_phase_seconds",
            "Per-step attribution: input (data-pipeline wait before the "
            "step), h2d (host-to-device staging, via mark_h2d_done), "
            "collective (fused execute + negotiate wait), compute (the "
            "remainder)", buckets=_reg.LATENCY_BUCKETS)
        phase_g = r.gauge(
            "hvdtpu_step_phase_share",
            "Fraction of the last step cycle (input wait + step wall "
            "time) spent in each phase")
        self._h_phase = {p: phase_h.labels(framework=framework, phase=p)
                         for p in STEP_PHASES}
        self._g_phase = {p: phase_g.labels(framework=framework, phase=p)
                         for p in STEP_PHASES}
        self._c_samples = r.counter(
            "hvdtpu_samples_total", "Training samples processed"
        ).labels(**labels)
        self._g_rate = r.gauge(
            "hvdtpu_samples_per_second",
            "Samples/sec of the most recent step").labels(**labels)
        self._g_share = r.gauge(
            "hvdtpu_collective_step_share",
            "Fraction of the last step's wall time spent executing "
            "fused collective groups (all ops)").labels(**labels)
        # DEPRECATION ALIAS: the canonical series is
        # hvdtpu_collective_step_share (it counts every collective op,
        # not just allreduce); this name stays for existing dashboards
        # and now carries the same all-ops value.
        self._g_share_legacy = r.gauge(
            "hvdtpu_allreduce_step_share",
            "DEPRECATED alias of hvdtpu_collective_step_share").labels(
            **labels)
        # MFU/FLOPs children are resolved lazily on first set: an
        # eagerly-created child would export a misleading 0.0 for
        # timers that never supply a flops figure or have no known
        # peak.
        self._fam_mfu = r.gauge(
            "hvdtpu_mfu",
            "Model FLOPs utilization of the last step: flops_per_step / "
            "step seconds / local peak FLOP/s (needs flops_per_step and "
            "a known peak)")
        self._fam_flops = r.gauge(
            "hvdtpu_model_flops_per_second",
            "Model FLOP/s of the last step (needs flops_per_step)")
        self._g_mfu = None
        self._g_flops = None
        self._g_hbm = r.gauge(
            "hvdtpu_hbm_bytes_in_use",
            "Device memory currently allocated, per local device "
            "(device='host': process RSS fallback when the backend has "
            "no memory_stats)")
        self._g_hbm_peak = r.gauge(
            "hvdtpu_hbm_peak_bytes",
            "Peak device memory allocated, per local device (host "
            "fallback: peak RSS)")
        self._peak_flops = _local_peak_flops() if flops_per_step else None
        self._t0: Optional[float] = None
        self._t_prev_end: Optional[float] = None
        self._h2d_mark: Optional[float] = None
        self._h2d_credit = 0.0
        self._ar0 = 0.0
        self._neg0 = 0.0
        self._step_idx = 0
        self.last_step_s = 0.0
        self.last_samples_per_s = 0.0
        self.last_collective_share = 0.0
        self.last_phases = {p: 0.0 for p in STEP_PHASES}

    # Back-compat: pre-attribution callers read last_allreduce_share.
    @property
    def last_allreduce_share(self) -> float:
        return self.last_collective_share

    def begin(self) -> None:
        self._ar0 = _collective_execute_seconds()
        self._neg0 = _negotiate_wait_seconds()
        self._h2d_mark = None
        from . import flight_recorder as _fr
        _fr.recorder().note("step", (self._step_idx,))
        self._t0 = time.perf_counter()

    def mark_h2d_done(self) -> None:
        """Optional: call once the batch is staged on device — the time
        from ``begin()`` to this mark is attributed to ``h2d`` instead
        of ``compute``."""
        if self._t0 is not None:
            self._h2d_mark = time.perf_counter()

    def credit_h2d(self, seconds: float) -> None:
        """Attribute ``seconds`` of the NEXT step's pre-step gap to
        ``h2d`` instead of ``input``. The device prefetcher
        (docs/data.md#prefetch) calls this when the consumer blocked on
        a batch whose host→device copy was not fully overlapped: the
        wait happened before ``begin()``, where only the input phase
        could otherwise see it. Capped at the actual gap in ``end()``
        — crediting more than was waited cannot mint h2d time."""
        if seconds > 0:
            self._h2d_credit += seconds

    def _timeline(self):
        """The engine's Python timeline writer, if one is live (never
        creates an engine). Imported lazily: observability must stay
        importable before ops."""
        from ..ops import collective as _coll
        eng = _coll._engine
        return eng.timeline if eng is not None else None

    def _sample_memory(self) -> None:
        """HBM live/peak per local device; host-RSS fallback keeps the
        gauges present on backends without memory_stats (CPU tests)."""
        sampled = False
        try:
            import jax
            for d in jax.local_devices():
                stats_fn = getattr(d, "memory_stats", None)
                stats = stats_fn() if stats_fn is not None else None
                if not stats:
                    continue
                label = f"{d.platform}:{d.id}"
                in_use = stats.get("bytes_in_use")
                peak = stats.get("peak_bytes_in_use")
                if in_use is not None:
                    self._g_hbm.labels(device=label).set(float(in_use))
                    sampled = True
                if peak is not None:
                    self._g_hbm_peak.labels(device=label).set(float(peak))
        except Exception:
            pass
        if not sampled:
            try:
                import resource
                rss_page = 0
                try:
                    with open("/proc/self/statm") as f:
                        rss_page = int(f.read().split()[1])
                except OSError:
                    pass
                page = resource.getpagesize()
                peak_kb = resource.getrusage(
                    resource.RUSAGE_SELF).ru_maxrss
                if rss_page:
                    self._g_hbm.labels(device="host").set(
                        float(rss_page * page))
                self._g_hbm_peak.labels(device="host").set(
                    float(peak_kb) * 1024.0)
            except Exception:
                pass

    def end(self, samples: Optional[int] = None) -> None:
        if self._t0 is None:
            return
        t_end = time.perf_counter()
        t0 = self._t0
        dt = max(t_end - t0, 1e-9)
        self._t0 = None
        n = samples if samples is not None else self.batch_size
        self.last_step_s = dt
        self._h_step.observe(dt)
        if n:
            self.last_samples_per_s = n / dt
            self._c_samples.inc(n)
            self._g_rate.set(self.last_samples_per_s)

        # Attribution: input is the pre-step gap; collective is the
        # engine's own execute + negotiate-wait accounting over the
        # step; compute is what remains of the in-step wall time.
        input_s = (max(0.0, t0 - self._t_prev_end)
                   if self._t_prev_end is not None else 0.0)
        self._t_prev_end = t_end
        h2d_s = (max(0.0, self._h2d_mark - t0)
                 if self._h2d_mark is not None else 0.0)
        # Prefetcher-credited staging time: part of the pre-step gap was
        # an unoverlapped device copy, not the data source.
        credit = min(self._h2d_credit, input_s)
        self._h2d_credit = 0.0
        input_s -= credit
        h2d_s += credit
        exec_s = _collective_execute_seconds() - self._ar0
        neg_s = _negotiate_wait_seconds() - self._neg0
        collective_s = min(max(exec_s + neg_s, 0.0), dt)
        compute_s = max(0.0, dt - collective_s - h2d_s)
        phases = {"input": input_s, "h2d": h2d_s,
                  "compute": compute_s, "collective": collective_s}
        cycle = input_s + dt
        for p, v in phases.items():
            self._h_phase[p].observe(v)
            self._g_phase[p].set(v / cycle if cycle > 0 else 0.0)
        self.last_phases = phases

        share = min(max(exec_s, 0.0) / dt, 1.0)
        self.last_collective_share = max(share, 0.0)
        self._g_share.set(self.last_collective_share)
        self._g_share_legacy.set(self.last_collective_share)

        if self.flops_per_step:
            rate = self.flops_per_step / dt
            if self._g_flops is None:
                self._g_flops = self._fam_flops.labels(
                    framework=self.framework)
            self._g_flops.set(rate)
            if self._peak_flops:
                if self._g_mfu is None:
                    self._g_mfu = self._fam_mfu.labels(
                        framework=self.framework)
                self._g_mfu.set(rate / self._peak_flops)
        self._sample_memory()

        # Step spans into the live trace (Python writer only) so the
        # cross-rank report can attribute input/compute per rank; and a
        # step event into the flight recorder so the postmortem knows
        # the phase a dead rank was in (docs/postmortem.md).
        idx = self._step_idx
        self._step_idx += 1
        try:
            tl = self._timeline()
        except Exception:
            tl = None
        if tl is not None:
            # perf_counter and monotonic share the clock on CPython/
            # Linux; anchor the spans on monotonic to match the writer.
            now_m = time.monotonic()
            m_end = now_m - (time.perf_counter() - t_end)
            m_t0 = m_end - dt
            if input_s > 0:
                tl.execute_span("_step", "STEP_INPUT",
                                m_t0 - input_s, m_t0)
            if h2d_s > 0:
                tl.execute_span("_step", "STEP_H2D", m_t0, m_t0 + h2d_s)
            tl.execute_span("_step", "STEP_COMPUTE", m_t0 + h2d_s,
                            m_t0 + h2d_s + compute_s)
        from . import flight_recorder as _fr
        _fr.recorder().note("step_end", (
            idx, round(dt * 1e3, 3), round(input_s * 1e3, 3),
            round(h2d_s * 1e3, 3), round(compute_s * 1e3, 3),
            round(collective_s * 1e3, 3)))

    # Context-manager sugar for the torch/TF step loop.

    def __enter__(self) -> "StepTimer":
        self.begin()
        return self

    def __exit__(self, *exc) -> None:
        self.end()
