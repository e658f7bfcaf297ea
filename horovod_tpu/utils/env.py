"""Environment-variable config system.

The reference configures its runtime entirely via ``HOROVOD_*`` env vars
read once at background-thread start (horovod/common/operations.cc:1824-1909,
operations.h:57-66). We honor the same names (for drop-in compatibility)
plus ``HOROVOD_TPU_*`` overrides.
"""

from __future__ import annotations

import os
from typing import Optional

# Defaults — operations.cc:1838 (64 MiB) and :1846 (5 ms). The TPU engine
# defaults the cycle to 1 ms: there is no MPI negotiation round-trip to
# amortize within a single-controller process.
DEFAULT_FUSION_THRESHOLD_MB = 64
DEFAULT_CYCLE_TIME_MS = 1.0
DEFAULT_STALL_WARNING_SECS = 60  # STALL_WARNING_TIME, operations.cc:258


def _get(name: str) -> Optional[str]:
    v = os.environ.get("HOROVOD_TPU_" + name)
    if v is None:
        v = os.environ.get("HOROVOD_" + name)
    return v


def fusion_threshold_bytes() -> int:
    v = _get("FUSION_THRESHOLD")
    if v is not None:
        return int(v)
    return DEFAULT_FUSION_THRESHOLD_MB * 1024 * 1024


def torch_bucket_mb() -> float:
    """Gradient-bucket size target for the torch DistributedOptimizer's
    backward-overlap bucketing (docs/torch.md). Default 64 MB — matched
    to the engine's fusion threshold so each bucket fills one fused
    group; 0 disables bucketing (per-tensor hooks, the pre-bucketing
    path). Overridden per-optimizer by ``bucket_cap_mb=``."""
    v = _get("TORCH_BUCKET_MB")
    if v is not None:
        return float(v)
    return float(DEFAULT_FUSION_THRESHOLD_MB)


def torch_grad_view() -> bool:
    """Default for the torch DistributedOptimizer's
    ``gradient_as_bucket_view`` (docs/torch.md): alias each ``p.grad``
    into its bucket's flat wire buffer at wrap time so autograd
    accumulates straight into the fused-collective payload and the
    hook-time pack memcpy (and the post-allreduce scatter-back)
    disappear. Off by default — it changes the identity of ``p.grad``
    tensors, which code that stashes or replaces gradient tensors may
    not expect."""
    return _get("TORCH_GRAD_VIEW") not in (None, "", "0")


def torch_skip_nonfinite() -> bool:
    """Default for the torch DistributedOptimizer's
    ``skip_nonfinite_steps`` (docs/numerics.md#torch): when the bucket
    pack observed nonfinite gradient elements this step, ``step()``
    still synchronizes (collective parity across ranks) but skips the
    inner optimizer update, so one rank's NaN batch does not poison
    the weights. Off by default; needs HOROVOD_TPU_NUMERICS=1 for the
    counts to exist."""
    return _get("TORCH_SKIP_NONFINITE") not in (None, "", "0")


def cycle_time_ms() -> float:
    v = _get("CYCLE_TIME")
    if v is not None:
        return float(v)
    return DEFAULT_CYCLE_TIME_MS


def stall_warning_secs() -> float:
    if _get("STALL_CHECK_DISABLE") not in (None, "", "0"):
        return 0.0
    # HOROVOD_TPU_STALL_WARNING overrides the 60 s default — short
    # windows let the stall→failure escalation (docs/adaptation.md)
    # react in seconds on jobs whose steps are subsecond.
    v = _get("STALL_WARNING")
    if v not in (None, ""):
        return float(v)
    return DEFAULT_STALL_WARNING_SECS


def failure_timeout_secs() -> float:
    """Window after which the stall detector / coordinator heartbeats
    escalate to a typed WorkerFailure (elastic recovery) instead of the
    warn-only behavior. 0 (the default) disables escalation — exactly
    the seed's coordinated-shutdown-only semantics. Exported to workers
    by the elastic driver as HOROVOD_TPU_FAILURE_TIMEOUT."""
    v = _get("FAILURE_TIMEOUT")
    if v in (None, ""):
        return 0.0
    return float(v)


def fault_spec() -> Optional[str]:
    """Declarative per-rank fault-injection spec (docs/adaptation.md):
    ``rank=2:delay=80ms:from_step=50; rank=1:crash_at=30``. None/empty
    disables injection entirely — the engine then carries a single
    ``is None`` check on the enqueue path and nothing else."""
    v = _get("FAULT_SPEC")
    return v or None


def adaptation_enabled() -> bool:
    """Rank-0 closed-loop adaptation policy (docs/adaptation.md):
    HOROVOD_TPU_ADAPTATION=1 arms the coordinator-side control loop that
    escalates graceful-degradation tiers on sustained straggler
    lateness. Default off — observability stays passive."""
    return _get("ADAPTATION") in ("1",)


def adapt_threshold_s() -> float:
    """Straggler lateness (decay-weighted mean seconds) above which the
    adaptation policy starts its sustain clock."""
    v = _get("ADAPT_THRESHOLD")
    return float(v) if v not in (None, "") else 0.1


def adapt_sustain_s() -> float:
    """Seconds the lateness must stay above threshold before EACH
    escalation step (hysteresis against transient spikes)."""
    v = _get("ADAPT_SUSTAIN")
    return float(v) if v not in (None, "") else 5.0


def adapt_cooldown_s() -> float:
    """Seconds the lateness must stay below threshold *
    deescalate-ratio before each de-escalation step."""
    v = _get("ADAPT_COOLDOWN")
    return float(v) if v not in (None, "") else 30.0


def adapt_interval_s() -> float:
    """Policy evaluation cadence (piggybacked on coordinator fetches)."""
    v = _get("ADAPT_INTERVAL")
    return float(v) if v not in (None, "") else 1.0


def adapt_tiers() -> Optional[str]:
    """Comma-separated degradation ladder override
    (HOROVOD_TPU_ADAPT_TIERS, e.g. "shrink,int8x256,evict"); None keeps
    the default shrink → bf16 → int8x256 → fp8x256 → evict ladder."""
    return _get("ADAPT_TIERS")


def coord_retries() -> int:
    """Post-rendezvous coordinator RPC retry budget (each retried with
    exponential backoff + jitter before CoordinatorUnreachableError)."""
    v = _get("COORD_RETRIES")
    return int(v) if v not in (None, "") else 6


def coord_backoff_s() -> float:
    """Base backoff between coordinator RPC retries (doubles per
    attempt, capped at ~2 s, ±50% deterministic per-rank jitter)."""
    v = _get("COORD_BACKOFF")
    return float(v) if v not in (None, "") else 0.1


def checkpoint_keep() -> int:
    """Keep-last-N retention for committed checkpoints (both the elastic
    pickle backend and the sharded engine, docs/checkpoint.md). 0 means
    unlimited — the seed's keep-everything behavior. Default 10: spot
    jobs commit often and nothing ever deleted old steps before."""
    v = _get("CHECKPOINT_KEEP")
    if v in (None, ""):
        return 10
    return int(v)


def blackbox_dir() -> Optional[str]:
    """Directory for flight-recorder crash dumps (docs/postmortem.md):
    on a crash, SIGTERM, stall escalation or eviction, each rank writes
    ``blackbox-rank{rank}.jsonl`` here. None/empty disables dumping —
    the in-memory ring buffer still records (its cost is one tuple
    append), but nothing ever reaches disk."""
    v = _get("BLACKBOX")
    return v or None


def blackbox_window_secs() -> float:
    """How many seconds of history a blackbox dump carries (the ring
    buffer is additionally bounded by ``blackbox_capacity`` events)."""
    v = _get("BLACKBOX_WINDOW")
    if v in (None, ""):
        return 120.0
    return float(v)


def blackbox_interval_secs() -> float:
    """Cadence of the periodic in-flight blackbox dump. The JAX
    coordination service hard-kills surviving clients (LOG(FATAL))
    within ~100 ms of any peer's death — no Python exit hook can run —
    so the recorder continuously persists its ring like a real flight
    recorder; the final-gasp dump overwrites with the precise reason
    when the process does get a last word. 0 disables the periodic
    writer (death-path dumps only)."""
    v = _get("BLACKBOX_INTERVAL")
    if v in (None, ""):
        return 5.0
    return float(v)


def blackbox_capacity() -> int:
    """Ring-buffer size (events) of the always-on flight recorder."""
    v = _get("BLACKBOX_EVENTS")
    if v in (None, ""):
        return 4096
    return int(v)


def history_dir() -> Optional[str]:
    """Directory for the telemetry history ring (docs/health.md): when
    set, a background sampler appends windowed registry deltas to
    ``history-rank{rank}.jsonl`` here every history_interval_secs and
    the online health detectors run over the live window. None/empty
    disables the whole plane — no thread, no file, no detectors."""
    v = _get("HISTORY")
    return v or None


def history_interval_secs() -> float:
    """Cadence of the telemetry history sampler (and therefore the
    detector window granularity). Default 5 s — fine enough to catch a
    minutes-scale regression, coarse enough that a day of history fits
    in a few rotated segments."""
    v = _get("HISTORY_INTERVAL")
    if v in (None, ""):
        return 5.0
    return float(v)


def history_max_bytes() -> int:
    """Per-segment size cap of a history file; past it the writer
    rotates (``.1`` .. ``.N`` suffixes, oldest deleted). Default 4 MiB."""
    v = _get("HISTORY_MAX_BYTES")
    if v in (None, ""):
        return 4 * 1024 * 1024
    return int(v)


def history_segments() -> int:
    """Rotated history segments kept per rank (on top of the live
    file). Total on-disk bound = (segments + 1) * max_bytes per rank."""
    v = _get("HISTORY_SEGMENTS")
    if v in (None, ""):
        return 4
    return int(v)


def health_detectors_enabled() -> bool:
    """Online anomaly detectors over the live history window
    (docs/health.md). Default on whenever the history sampler runs;
    HOROVOD_TPU_HEALTH=0 keeps the history file but fires no alerts."""
    return _get("HEALTH") not in ("0",)


def numerics_enabled() -> bool:
    """Numerics observability plane (docs/numerics.md):
    HOROVOD_TPU_NUMERICS=1 arms the nonfinite sentinels, gradient/loss
    telemetry and fingerprint probes at hvd.init(). Default off — every
    hook site then carries a single flag check."""
    return _get("NUMERICS") in ("1",)


def numerics_fp_interval() -> int:
    """Cross-rank param-fingerprint cadence in training steps
    (docs/numerics.md#fingerprints). 0 disables the probe while keeping
    the rest of the numerics plane armed."""
    v = _get("NUMERICS_FP_INTERVAL")
    if v in (None, ""):
        return 50
    return int(v)


def alert_url() -> Optional[str]:
    """Optional webhook for health alerts (docs/health.md#webhook):
    rank 0 / the fleet supervisor POSTs each typed alert as JSON here,
    fire-and-forget with a short timeout — an unreachable receiver can
    never stall the sampler."""
    v = _get("ALERT_URL")
    return v or None


def adapt_alert_hold_s() -> float:
    """How long a health alert (step-time regression / HBM leak) keeps
    exerting ladder pressure on the adaptation policy after it fired —
    the alert-triggered escalation input, hysteresis-guarded exactly
    like measured lateness (docs/health.md#adaptation)."""
    v = _get("ADAPT_ALERT_HOLD")
    return float(v) if v not in (None, "") else 30.0


def peak_flops() -> Optional[float]:
    """Peak FLOP/s of this process's devices for the MFU gauge
    (HOROVOD_TPU_PEAK_FLOPS, total across local devices). None =
    autodetect from the device kind (TPU generations only); MFU is not
    exported when neither source yields a number."""
    v = _get("PEAK_FLOPS")
    if v in (None, ""):
        return None
    return float(v)


def timeline_path() -> Optional[str]:
    return _get("TIMELINE")


def resolved_timeline_path(rank: int) -> Optional[str]:
    """Timeline file this process should write, or None.

    A ``{rank}`` placeholder in HOROVOD_TPU_TIMELINE expands to the
    process index and EVERY rank writes its own trace (the cross-rank
    capture mode, docs/tracing.md — mirroring the
    HOROVOD_TPU_METRICS_FILE convention). Without a placeholder only
    process 0 writes, the reference's single-viewpoint behavior
    (operations.cc:1824-1829): a second writer on one path would
    truncate rank 0's file."""
    path = timeline_path()
    if not path:
        return None
    if "{rank}" in path:
        return path.replace("{rank}", str(rank))
    return path if rank == 0 else None


def trace_clock_probes() -> int:
    """Clock-alignment handshake pings per rank (NTP-style, min-RTT
    sample wins) recorded in each per-rank trace's clock metadata;
    0 disables the handshake (offset recorded as unsynced)."""
    v = _get("TRACE_CLOCK_PROBES")
    if v in (None, ""):
        return 8
    return int(v)


def exemplar_ttl_secs() -> float:
    """How long a histogram exemplar (the trace id of the worst recent
    observation, docs/metrics.md#exemplars) stays champion before ANY
    newer exemplar-carrying observation may replace it regardless of
    value — "worst recent", not "worst ever". Default 60 s."""
    v = _get("EXEMPLAR_TTL")
    if v in (None, ""):
        return 60.0
    return float(v)


def metrics_enabled() -> bool:
    """Metrics registry recording (docs/metrics.md). Default ON — a
    guarded counter add is nanoseconds (the BENCH_METRICS overhead test
    holds it under 3% of the fused-allreduce hot loop);
    HOROVOD_TPU_METRICS=0 turns every mutator into a single flag
    check."""
    return _get("METRICS") not in ("0", "")


def metrics_file() -> Optional[str]:
    """Path for periodic JSON metric snapshots (atomic rewrite every
    metrics_interval_secs). A ``{rank}`` placeholder expands to the
    process index; without one only process 0 writes."""
    return _get("METRICS_FILE")


def metrics_port(rank: int = 0) -> Optional[int]:
    """Prometheus/JSON HTTP endpoint port for ``rank`` (0 = ephemeral);
    None disables the endpoint.

    Three forms (docs/metrics.md):
      - ``9091``        — plain port, served by process 0 only.
      - ``909{rank}``   — ``{rank}`` placeholder, every rank serves its
                          substituted port.
      - ``9091+rank``   — base + process index, every rank serves
                          ``base + rank``.
    The per-rank forms make every process scrapeable in multi-process
    mode instead of aggregates-through-rank-0 only."""
    v = _get("METRICS_PORT")
    if v in (None, ""):
        return None
    v = v.strip()
    if "{rank}" in v:
        return int(v.replace("{rank}", str(rank)))
    if v.endswith("+rank"):
        return int(v[: -len("+rank")]) + rank
    return int(v)


def metrics_port_per_rank() -> bool:
    """True when HOROVOD_TPU_METRICS_PORT uses a per-rank form
    (``{rank}`` placeholder or ``base+rank``), i.e. every process — not
    just 0 — should bind its endpoint."""
    v = _get("METRICS_PORT")
    if v in (None, ""):
        return False
    v = v.strip()
    return "{rank}" in v or v.endswith("+rank")


def metrics_interval_secs() -> float:
    v = _get("METRICS_INTERVAL")
    if v in (None, ""):
        return 15.0
    return float(v)


def serving_port() -> int:
    """HTTP port of the serving front end (``python -m
    horovod_tpu.serving``); 0 binds an ephemeral port. Default 8400 —
    distinct from the metrics endpoint, which stays on
    HOROVOD_TPU_METRICS_PORT (the serving tier never binds a second
    metrics port; docs/serving.md)."""
    v = _get("SERVING_PORT")
    if v in (None, ""):
        return 8400
    return int(v)


def serving_queue() -> int:
    """Bounded admission-queue depth of the serving engine (requests
    past it are rejected with HTTP 429). Default 32."""
    v = _get("SERVING_QUEUE")
    if v in (None, ""):
        return 32
    return int(v)


def serving_tick_budget_ms() -> Optional[float]:
    """Target decode-tick gap for chunked prefill (docs/serving.md):
    when set, the engine's chunk budget policy shrinks prefill-chunk
    size (down to ``min_prefill_bucket``) until the measured per-chunk
    prefill time fits under this many milliseconds, bounding how long
    any live decode slot waits behind an interleaved chunk. None (the
    default) keeps the configured ``prefill_chunk`` cap as-is."""
    v = _get("SERVING_TICK_BUDGET_MS")
    if v in (None, ""):
        return None
    return float(v)


def reqtrace_dir() -> Optional[str]:
    """Directory for per-process serving request traces
    (docs/serving.md#request-tracing): when set, the fleet router
    writes ``reqtrace-router.trace.json`` and every replica writes
    ``reqtrace-replica{id}-gen{g}.trace.json`` there (one catapult file
    per process, the PR 5 tuple-enqueue writer), merged and analyzed by
    ``python -m horovod_tpu.tools.trace``. None/empty disables request
    tracing entirely — the serving hot path then carries one ``is
    None`` check per decode step."""
    v = _get("REQTRACE")
    return v or None


def replica_id() -> Optional[int]:
    """This process's serving-fleet replica id, exported by the fleet
    supervisor (docs/serving.md#fleet): blackbox dumps are named
    ``blackbox-rank{replica}.jsonl`` and fault-spec ``rank=`` clauses
    target it. None outside a fleet."""
    v = _get("REPLICA_ID")
    if v in (None, ""):
        return None
    return int(v)


def fleet_probe_interval_secs() -> float:
    """Cadence of the fleet supervisor's replica health probes and the
    router's queue-gauge scrapes (docs/serving.md#fleet)."""
    v = _get("FLEET_PROBE_INTERVAL")
    if v in (None, ""):
        return 0.25
    return float(v)


def fleet_probe_failures() -> int:
    """Consecutive failed health probes before the supervisor declares
    a replica dead and restarts it (crash-via-process-exit is detected
    immediately; this catches the hung-but-alive case)."""
    v = _get("FLEET_PROBE_FAILURES")
    if v in (None, ""):
        return 4
    return int(v)


def slo_ttft_ms() -> Optional[float]:
    """Fleet-default time-to-first-token SLO target in milliseconds
    (docs/serving.md#slo). Used when a request carries no explicit
    ``slo`` field and its tenant has no entry in the SLO config file.
    None (the default) attaches no TTFT target."""
    v = _get("SLO_TTFT_MS")
    if v in (None, ""):
        return None
    return float(v)


def slo_tpot_ms() -> Optional[float]:
    """Fleet-default time-per-output-token SLO target in milliseconds
    (docs/serving.md#slo), same resolution order as
    :func:`slo_ttft_ms`. None attaches no TPOT target."""
    v = _get("SLO_TPOT_MS")
    if v in (None, ""):
        return None
    return float(v)


def slo_config() -> Optional[str]:
    """Path to the fleet SLO config file (docs/serving.md#slo): JSON
    ``{"tenants": {name: {"ttft_ms", "tpot_ms"}}, "default": {...}}``
    giving per-tenant default targets. None/empty means no per-tenant
    defaults — only the env-level targets apply."""
    v = _get("SLO_CONFIG")
    return v or None


def serving_reserved_slots() -> int:
    """Decode-batch slots reserved for the top priority class
    (docs/serving.md#qos): bulk/default admissions stop once occupancy
    would leave fewer than this many slots for ``interactive`` work.
    Default 0 — no reservation."""
    v = _get("SERVING_RESERVED_SLOTS")
    if v in (None, ""):
        return 0
    return max(0, int(v))


def qos_scale_high() -> float:
    """Autoscaler scale-up threshold: fleet queued+active work per
    decode slot above which sustained load triggers a scale-up
    (docs/serving.md#qos). Default 1.5."""
    v = _get("QOS_SCALE_HIGH")
    if v in (None, ""):
        return 1.5
    return float(v)


def qos_scale_low() -> float:
    """Autoscaler scale-down threshold: load per slot below which the
    fleet shrinks after the cooldown (docs/serving.md#qos).
    Default 0.25."""
    v = _get("QOS_SCALE_LOW")
    if v in (None, ""):
        return 0.25
    return float(v)


def qos_scale_sustain_s() -> float:
    """Seconds the scale-up pressure must hold before the autoscaler
    acts (docs/serving.md#qos) — brief spikes don't grow the fleet.
    Default 3."""
    v = _get("QOS_SCALE_SUSTAIN_S")
    if v in (None, ""):
        return 3.0
    return float(v)


def qos_scale_cooldown_s() -> float:
    """Seconds of continuously low load before the autoscaler drains a
    replica, and the minimum gap after any scale action before the next
    (docs/serving.md#qos). Default 15."""
    v = _get("QOS_SCALE_COOLDOWN_S")
    if v in (None, ""):
        return 15.0
    return float(v)


def qos_scale_interval_s() -> float:
    """Autoscaler observation period in seconds (docs/serving.md#qos).
    Default 1."""
    v = _get("QOS_SCALE_INTERVAL_S")
    if v in (None, ""):
        return 1.0
    return float(v)


def max_tenants() -> int:
    """Cardinality cap on the ``tenant`` metric label
    (docs/serving.md#slo): the first N distinct tenant names keep
    their own label value; later ones collapse into the ``"other"``
    overflow bucket so a client fabricating tenant names cannot grow
    the registry without bound. Default 16."""
    v = _get("MAX_TENANTS")
    if v in (None, ""):
        return 16
    return max(1, int(v))


def timeline_mark_cycles() -> bool:
    return _get("TIMELINE_MARK_CYCLES") not in (None, "", "0")


def shm_data_plane() -> bool:
    """Shared-memory data plane for same-host eager collectives (the
    reference's MPI shared-memory CPU path). HOROVOD_TPU_SHM=1/0 forces;
    default follows the launcher's placement verdict
    (HOROVOD_TPU_ALL_LOCAL) — every process of a job sees the same
    launcher env, so the fleet gates identically."""
    v = _get("SHM")
    if v is not None:
        return v not in ("", "0")
    return os.environ.get("HOROVOD_TPU_ALL_LOCAL") == "1"


def producer_fence() -> Optional[bool]:
    """Force (1) or suppress (0) the eager engine's producer fence —
    blocking on input producers before launching a fused collective.
    Default None = automatic: fence only when this process addresses
    more than one device (see CollectiveEngine._fence_producers — with
    one device every launch lands in one FIFO queue and the rendezvous
    inversion the fence prevents cannot occur)."""
    v = _get("PRODUCER_FENCE")
    if v in (None, ""):
        return None
    return v != "0"


def device_pack() -> Optional[bool]:
    """Force (1) or suppress (0) device-resident MP fusion-buffer
    packing. Default None = automatic: on for accelerator backends,
    off on CPU (executor._device_pack)."""
    v = _get("DEVICE_PACK")
    if v in (None, ""):
        return None
    return v != "0"


def ordered_launch() -> bool:
    """HOROVOD_TPU_ORDERED_LAUNCH=1: replace the producer completion
    fence with enqueue-ordering under a process-global launch lock
    (ops.collective.launch_lock()). PROTOTYPE, default off: measured on
    the CPU backend (experiments/ordered_launch_ab.py), PJRT's
    cross-device fan-out happens after the Python execute call returns,
    so host-side ordering cannot prevent rendezvous inversion there —
    the completion fence remains the safe default. The flag exists for
    real multi-chip TPU experimentation, where per-device enqueue is
    host-call-ordered."""
    return _get("ORDERED_LAUNCH") == "1"


def dlpack_boundary() -> bool:
    """DLPack zero-copy at the framework-shim boundary (utils/interop).
    Default on; HOROVOD_TPU_DLPACK=0 forces the numpy fallback path —
    the A/B lever for measuring the shim tax."""
    return _get("DLPACK") not in ("0",)


def hierarchical_allreduce() -> bool:
    return _get("HIERARCHICAL_ALLREDUCE") not in (None, "", "0")


def hierarchical_allgather() -> bool:
    return _get("HIERARCHICAL_ALLGATHER") not in (None, "", "0")


def autotune() -> bool:
    """The LEGACY eager-path Bayesian tuner (parameter_manager parity).
    Reads ONLY ``HOROVOD_AUTOTUNE`` — deliberately not the usual
    ``HOROVOD_TPU_`` override chain, because ``HOROVOD_TPU_AUTOTUNE``
    enables the GLOBAL online tuner (:func:`autotune_global`,
    docs/autotune.md) and the two switches must not alias."""
    return os.environ.get("HOROVOD_AUTOTUNE") not in (None, "", "0")


def autotune_global() -> bool:
    """The global online autotuner (docs/autotune.md):
    ``HOROVOD_TPU_AUTOTUNE=1`` (or the runner's ``--autotune``) turns
    on the knob-registry driver guarded by the health plane."""
    return os.environ.get("HOROVOD_TPU_AUTOTUNE") not in (None, "", "0")


def autotune_log() -> Optional[str]:
    return _get("AUTOTUNE_LOG")


def autotune_guard_rel() -> float:
    """Rollback guard threshold for global-tuner moves: a post-move
    window worse than the pre-move baseline by more than this fraction
    rolls the move back (docs/autotune.md). Default matches the
    ``tools/health --baseline`` regression threshold."""
    v = _get("AUTOTUNE_GUARD_REL")
    return float(v) if v is not None else 0.10


def autotune_trial_budget() -> int:
    """Measurement windows the global tuner scores each candidate on."""
    v = _get("AUTOTUNE_TRIAL_BUDGET")
    return int(v) if v is not None else 2


def log_level() -> str:
    return (_get("LOG_LEVEL") or "warning").lower()


def log_hide_time() -> bool:
    return _get("LOG_HIDE_TIME") not in (None, "", "0")
