#!/usr/bin/env python
"""Synthetic ResNet-50 benchmark — the TPU-native equivalent of
examples/tensorflow_synthetic_benchmark.py (the reference's in-tree
benchmark driver, :88-107): ResNet-50 on synthetic ImageNet-shaped data,
warmup batches then timed iterations, reporting img/sec — plus MFU
(model FLOPs utilization) and an optional weak-scaling sweep, the two
numbers BASELINE.md actually cares about (docs/benchmarks.md:5-38).

Method: the reference's window STRUCTURE (10 timed windows; mean +/-
1.96 sigma also reported) with two corrections — 40 batches per window
(see the NUM_BATCHES_PER_ITER comment) and a median headline (one
stalled window out of 10 drags a mean by tens of percent; the raw
per-window values are in the JSON so the choice is auditable). At
least 3 warmup calls reach the jit donation/sharding fixpoint. Trains
through the framework path: mesh over all available devices, batch
sharded over 'dp', DistributedOptimizer.

MFU methodology: FLOPs per optimizer step are taken from XLA's own cost
analysis of the compiled single-step program (no hand-counted model
constants), divided by measured step time and the chip's peak bf16
FLOP/s looked up from ``device_kind``. Peak numbers are the published
per-chip bf16 figures (v2 45, v3 123, v4 275, v5e 197, v5p 459,
v6e 918 TFLOP/s); a device the table does not name is an error, so
the chip bench does not run on the CPU.

Weak scaling (--scaling N1,N2,... or HVD_BENCH_SCALING): for each N, a
runner-launched N-process job (1 virtual CPU device per process — the
same launch path a real multi-host pod uses, SURVEY.md §4) trains the
same model; efficiency(N) = throughput(N) / (N * throughput(1)), the
shape of the reference's 90%-at-512-GPUs headline (docs/benchmarks.md:
5-6). CPU-mesh numbers measure the framework's collective/control-plane
overhead, not ICI hardware.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "img/sec/chip", "vs_baseline": N,
   "mfu": ..., "tflops_per_chip": ..., "peak_tflops": ...[,
   "weak_scaling": {...}]}
Baseline: the reference's sample run reports "total images/sec: 1656.82"
on 16 Pascal GPUs (docs/benchmarks.md:22-38) = 103.55 img/sec/GPU.
"""

import argparse
import json
import os
import time
from functools import partial

import numpy as np

BASELINE_IMG_SEC_PER_CHIP = 1656.82 / 16  # docs/benchmarks.md:22-38

BATCH_PER_CHIP = int(os.environ.get("HVD_BENCH_BATCH", 256))
IMAGE_SIZE = int(os.environ.get("HVD_BENCH_IMAGE", 224))
WARMUP_BATCHES = int(os.environ.get("HVD_BENCH_WARMUP", 10))  # ref :88-92
NUM_ITERS = int(os.environ.get("HVD_BENCH_ITERS", 10))
# 40 batches per timed window, up from the reference's 10: each host
# call carries a fixed dispatch + readback cost that is not chip time —
# the number BASELINE.md compares is chip throughput, so the window
# must amortize it. The reference's 10-iteration window STRUCTURE
# (mean/median over 10 timed windows) is unchanged.
NUM_BATCHES_PER_ITER = int(os.environ.get("HVD_BENCH_BATCHES", 40))

def peak_tflops(device) -> float:
    """Published peak bf16 TFLOP/s of ``device``, from the program's one
    table (observability/step_metrics.py); a kind the table does not
    name (the CPU included) raises — an MFU against a guessed peak is
    worse than none."""
    from horovod_tpu.observability.step_metrics import peak_flops_of_kind
    kind = getattr(device, "device_kind", "")
    peak = peak_flops_of_kind(kind)
    if peak is None:
        raise ValueError(
            f"no published peak for device kind {kind!r}: the chip "
            "benches run on a TPU named in step_metrics."
            "PEAK_FLOPS_BY_KIND, not on "
            f"{getattr(device, 'platform', 'this device')!r}")
    return peak / 1e12


# Windows whose wall time exceeds the median by this factor are host
# stalls, not chip behavior (one 16.7 s window in a ~6.6 s-median run
# blew ci95 from ±16 to ±1118). Overridable for environments with
# different stall shapes.
STALL_FACTOR = float(os.environ.get("HVD_BENCH_STALL_FACTOR", 1.5))


def annotate_stalled_windows(window_s, stall_factor=None):
    """Detect wall-time outlier windows against the run's own median.

    Returns ``(stalled_indices, ok_indices)``. The raw windows stay in
    the JSON untouched — this only *annotates* them so round-over-round
    ci95 comparisons can exclude stalls instead of reading a host
    hiccup as a throughput regression. If every window would be flagged
    (degenerate tiny medians), nothing is: a uniformly slow run is slow,
    not stalled."""
    factor = STALL_FACTOR if stall_factor is None else stall_factor
    if not window_s:
        return [], []
    med = float(np.median(window_s))
    stalled = [i for i, w in enumerate(window_s) if w > factor * med]
    if len(stalled) == len(window_s):
        stalled = []
    ok = [i for i in range(len(window_s)) if i not in set(stalled)]
    return stalled, ok


def build_step(model, opt):
    """One jitted k-step training program (state donated; the k optimizer
    steps run inside a single lax.fori_loop so host dispatch latency never
    sits between device steps)."""
    import jax
    import jax.numpy as jnp
    import optax

    @partial(jax.jit, donate_argnums=(0, 1, 2), static_argnums=(5,))
    def train_k(params, batch_stats, opt_state, images, labels, k):
        def body(_, carry):
            params, batch_stats, opt_state = carry

            def loss_fn(p):
                logits, new_state = model.apply(
                    {"params": p, "batch_stats": batch_stats}, images,
                    train=True, mutable=["batch_stats"])
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels).mean()
                return loss, new_state["batch_stats"]

            (_, new_bs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, new_opt = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_bs, new_opt

        return jax.lax.fori_loop(0, k, body,
                                 (params, batch_stats, opt_state))

    return train_k


def run_chip_bench():
    """Single-process benchmark over all local devices (the driver's
    real-TPU run). Returns the result dict."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50

    hvd.init()
    peak = peak_tflops(jax.devices()[0])   # unknown device: fail early
    n = hvd.size()
    mesh = hvd.mesh()
    batch = BATCH_PER_CHIP * n

    model = ResNet50(num_classes=1000)
    rng = jax.random.PRNGKey(0)
    images = jax.random.normal(rng, (batch, IMAGE_SIZE, IMAGE_SIZE, 3),
                               jnp.float32)
    labels = jax.random.randint(rng, (batch,), 0, 1000)

    variables = model.init(rng, images[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    # Framework path: broadcast initial state from rank 0, then wrap the
    # optimizer (grads are averaged over the mesh inside the jitted step).
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt = hvd.DistributedGradientTransformation(
        optax.sgd(0.01 * n, momentum=0.9))
    opt_state = opt.init(params)

    if n > 1:
        images = jax.device_put(images, NamedSharding(mesh, P("dp")))
        labels = jax.device_put(labels, NamedSharding(mesh, P("dp")))

    train_k = build_step(model, opt)

    # FLOPs per optimizer step from XLA's cost analysis of a k=1
    # program. This is a second, dedicated compile on purpose: cost
    # analysis of a k>1 executable reports a NON-linear flop total
    # (measured: k=10 gives ~1.5x the k=1 figure, not 10x — loop
    # canonicalization), so the k=1 program is the only unambiguous
    # per-step basis. A failed or empty analysis raises: an MFU of 0 is
    # not a measurement.
    cost = train_k.lower(params, batch_stats, opt_state, images,
                         labels, 1).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops_per_step = float(cost["flops"])

    def run_batches(k):
        nonlocal params, batch_stats, opt_state
        params, batch_stats, opt_state = train_k(
            params, batch_stats, opt_state, images, labels, k)
        # Block with a device-to-host read of the updated parameters.
        return float(jnp.sum(jax.tree_util.tree_leaves(params)[0]))

    # Warmup (compile + stabilize), reference :88-92. Warmup calls use
    # the SAME static k as the timed iterations: a different k would
    # compile a different executable, pushing the timed k's compile into
    # the first measured window — so WARMUP_BATCHES rounds up to whole
    # iterations, with a floor of 3 calls: the jit signature reaches its
    # donation/committed-sharding fixpoint only after ~3 calls, and a
    # recompile inside window 0 shows up as a 6x wall-time outlier
    # (visible in windows_wall_s of any run that skips this).
    for _ in range(max(-(-WARMUP_BATCHES // NUM_BATCHES_PER_ITER), 3)):
        run_batches(NUM_BATCHES_PER_ITER)

    # Timed iterations (reference :94-101). Raw per-window times are
    # recorded in the JSON so a future reader can tell a drifting host
    # from a real regression.
    img_secs = []
    window_s = []
    for _ in range(NUM_ITERS):
        t0 = time.perf_counter()
        run_batches(NUM_BATCHES_PER_ITER)
        dt = time.perf_counter() - t0
        window_s.append(round(dt, 4))
        img_secs.append(batch * NUM_BATCHES_PER_ITER / dt)

    # Median over the iteration windows as the headline (one stalled
    # window out of 10 drags a mean by tens of percent — measured ci95
    # of ±63% with a single stalled window); the reference's
    # mean ± 1.96σ (tensorflow_synthetic_benchmark.py:88-107) is still
    # reported so round-over-round deltas stay interpretable on its
    # convention too.
    per_chip = float(np.median(img_secs)) / n
    mean = float(np.mean(img_secs)) / n
    ci95 = float(1.96 * np.std(img_secs)) / n
    # Stall annotation: keep every raw window, but flag wall-time
    # outliers and report a trimmed mean/CI over the clean windows so
    # cross-round ci95 comparisons don't read one stalled window as a
    # regression. The median headline is already stall-robust and
    # unchanged.
    stalled_idx, ok_idx = annotate_stalled_windows(window_s)
    ok_rates = [img_secs[i] for i in ok_idx] or img_secs
    trimmed_mean = float(np.mean(ok_rates)) / n
    trimmed_ci95 = float(1.96 * np.std(ok_rates)) / n
    # MFU on the same basis as the reported rate: sustained FLOP/s =
    # (reported img/sec/chip) x (FLOPs per image), so the two headline
    # numbers cannot disagree about what was measured. cost_analysis
    # reports the PER-DEVICE partitioned executable's flops, so divide
    # by the per-device batch, not the global one.
    flops_per_img = flops_per_step / (batch / n)
    tflops = per_chip * flops_per_img / 1e12
    mfu = tflops / peak
    return {
        "metric": "resnet50_synthetic_img_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "img/sec/chip",
        "vs_baseline": round(per_chip / BASELINE_IMG_SEC_PER_CHIP, 3),
        "mean": round(mean, 2),
        "ci95": round(ci95, 2),
        "iters": NUM_ITERS,
        "batches_per_iter": NUM_BATCHES_PER_ITER,
        "windows_img_sec_per_chip": [round(v / n, 2) for v in img_secs],
        "windows_wall_s": window_s,
        "stalled_windows": stalled_idx,
        "stall_factor": STALL_FACTOR,
        "trimmed_mean": round(trimmed_mean, 2),
        "trimmed_ci95": round(trimmed_ci95, 2),
        "mfu": round(mfu, 4),
        "tflops_per_chip": round(tflops, 1),
        "peak_tflops": peak,
        "batch_per_chip": BATCH_PER_CHIP,
    }


def _scaling_worker():
    """Per-process weak-scaling workload: a small bottleneck ResNet so the
    CPU mesh turns steps in seconds, with full-size-realistic gradient
    traffic through the same DistributedOptimizer/allreduce path.

    HVD_BENCH_SCALE_MODEL=vgg swaps in a VGG-shaped proxy — conv stack
    plus a deliberately fat fc head — preserving VGG-16's defining
    ratio (the reference's worst-scaling family, 68% at 512 GPUs,
    docs/benchmarks.md:5-6): far more gradient bytes per unit compute
    than the ResNet proxy, i.e. the tensor-fusion stress case."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models.resnet import ResNet

    hvd.init()
    n = hvd.size()
    batch_per = int(os.environ.get("HVD_BENCH_SCALE_BATCH", 8))
    image = int(os.environ.get("HVD_BENCH_SCALE_IMAGE", 32))
    steps = int(os.environ.get("HVD_BENCH_SCALE_STEPS", 4))

    if os.environ.get("HVD_BENCH_SCALE_MODEL") == "vgg":
        import flax.linen as nn

        class _VGGProxy(nn.Module):
            @nn.compact
            def __call__(self, x, train=True):
                for ch in (32, 64):
                    x = nn.relu(nn.Conv(ch, (3, 3))(x))
                    x = nn.max_pool(x, (2, 2), strides=(2, 2))
                x = x.reshape(x.shape[0], -1)
                x = nn.relu(nn.Dense(2048)(x))   # the VGG fc mass:
                x = nn.relu(nn.Dense(2048)(x))   # ~17M params vs ~0.1M
                return nn.Dense(100)(x)          # of conv compute

        class _NoBN:
            """Match the ResNet worker's (logits, batch_stats) apply
            contract with an empty-stats model."""
            def __init__(self, m):
                self._m = m

            def init(self, rng, x, train=True):
                return {"params": self._m.init(rng, x)["params"],
                        "batch_stats": {}}

            def apply(self, variables, x, train=True, mutable=()):
                out = self._m.apply({"params": variables["params"]}, x)
                return out, {"batch_stats": {}}

        model = _NoBN(_VGGProxy())
    else:
        model = ResNet(stage_sizes=[1, 1, 1, 1], num_classes=100,
                       dtype=jnp.float32)
    rng = jax.random.PRNGKey(hvd.process_rank())
    images = jax.random.normal(rng, (batch_per, image, image, 3),
                               jnp.float32)
    labels = jax.random.randint(rng, (batch_per,), 0, 100)

    variables = model.init(jax.random.PRNGKey(0), images[:2], train=True)
    params, bs = variables["params"], variables["batch_stats"]
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt = optax.sgd(0.01)
    opt_state = opt.init(params)

    def loss_fn(p, bs):
        logits, new_state = model.apply(
            {"params": p, "batch_stats": bs}, images,
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, new_state["batch_stats"]

    grad_fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))

    def step(params, bs, opt_state, i):
        (_, bs), grads = grad_fn(params, bs)
        # Eager cross-process gradient averaging — the multi-host
        # DistributedOptimizer hook path (fusion + control plane live).
        grads = hvd.allreduce_gradients(grads, name_prefix=f"ws{i}")
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, bs, opt_state

    # Warmup (compile both programs + prime the engine). THREE steps, not
    # one: the first step's outputs are committed engine/device arrays
    # while the init pytree is uncommitted, so jit sees a different
    # argument signature for ~2 steps before the executable set reaches
    # its fixpoint — a single warmup left a full recompile (measured
    # ~7 s on the CPU mesh) inside the timed window.
    for w in range(3):
        params, bs, opt_state = step(params, bs, opt_state, f"w{w}")
    jax.block_until_ready(params)
    # Two timed windows of `steps` each, median window throughput
    # (reference method: mean over iteration windows,
    # tensorflow_synthetic_benchmark.py:94-101). Windows, not per-step
    # sync: blocking every step would forbid the step pipelining real
    # training has; the median across windows still rejects a
    # descheduling stall on the shared CI host.
    import numpy as _np
    rates = []
    for w in range(2):
        t0 = time.perf_counter()
        for i in range(steps):
            params, bs, opt_state = step(params, bs, opt_state,
                                         f"{w}.{i}")
        jax.block_until_ready(params)
        rates.append(batch_per * steps * n / (time.perf_counter() - t0))
    return float(_np.median(rates))  # global img/sec


def run_weak_scaling(sizes):
    """Launch an N-process job per N and print the BASELINE.md-shaped
    table.

    Two efficiency columns:
      - ``efficiency`` = thr(N) / (N * thr(1)) — the reference's headline
        shape (docs/benchmarks.md:5-6), meaningful when every process has
        its own chip.
      - ``capacity_adjusted`` = thr(N) / (min(N, cores) * thr(1)) — on a
        CI host with fewer cores than processes, compute capacity does
        not grow with N, so the perfect-framework ceiling is
        min(N, cores) * thr(1); this column isolates the framework's
        collective/control-plane overhead from plain CPU contention.
    """
    from horovod_tpu.runner.api import run as hvd_run

    env = dict(SCALING_WORKER_ENV)
    cores = os.cpu_count() or 1
    if 1 not in sizes:
        # Efficiency is defined against thr(1); measure it rather than
        # fabricating a perfect-scaling baseline from the smallest N.
        sizes = [1] + list(sizes)
    # Efficiency is a RATIO of two jobs, and absolute throughput on a
    # shared host drifts between runs minutes apart — measuring all of
    # thr(1) and then all of thr(N) bakes that drift into every ratio.
    # So rounds INTERLEAVE the sizes ([1, N1, N2, ..] per round), each
    # round's ratios use ITS OWN thr(1), and the reported number is the
    # median ratio across rounds (the in-process A/B discipline; the
    # reference's mean-over-iterations, synthetic_benchmark.py:94-101,
    # assumes a dedicated machine this host is not).
    repeats = int(os.environ.get("HVD_BENCH_SCALE_REPEATS", 3))
    rounds = []
    for _ in range(max(1, repeats)):
        rnd = {}
        for n in sizes:
            out = hvd_run(_scaling_worker, np=n, extra_env=dict(env),
                          start_timeout=600)
            rnd[n] = float(np.median(out))
        rounds.append(rnd)
    table = {}
    for n in sizes:
        effs = [r[n] / (n * r[1]) for r in rounds if r[1]]
        caps = [r[n] / (min(n, cores) * r[1]) for r in rounds if r[1]]
        table[str(n)] = {
            "img_sec": round(float(np.median([r[n] for r in rounds])), 1),
            "efficiency": round(float(np.median(effs)), 3),
            "capacity_adjusted": round(float(np.median(caps)), 3),
            "capacity_adjusted_runs": [round(c, 3) for c in caps],
        }
    table["_host_cores"] = cores
    return table


# Worker launch env shared by every scaling-path job (weak scaling and
# the autotune A/B): plain CPU, one device per process — the same
# launch shape a real multi-host pod uses.
SCALING_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}


def run_autotune_ab():
    """Certify the autotuner on the REAL training workload, not only on engine microbenches: interleaved rounds of the
    weak-scaling ResNet job (eager allreduce_gradients through the full
    engine/control-plane stack) with HOROVOD_AUTOTUNE=1 vs default
    knobs, per-round tuned/default ratio, median across rounds (the
    in-process-A/B discipline adapted to read-once engine knobs — the
    knob set forces a fresh process per arm, so the interleaving is
    between adjacent jobs rather than within one)."""
    from horovod_tpu.runner.api import run as hvd_run

    env_base = dict(SCALING_WORKER_ENV)
    # enough steps for the BO to sample several cycles and freeze
    env_base["HVD_BENCH_SCALE_STEPS"] = os.environ.get(
        "HVD_BENCH_SCALE_STEPS", "8")
    nproc = int(os.environ.get("HVD_BENCH_AUTOTUNE_NP", 2))
    repeats = int(os.environ.get("HVD_BENCH_AUTOTUNE_REPEATS", 3))
    tuned_r, default_r, ratios = [], [], []
    for _ in range(max(1, repeats)):
        env_t = dict(env_base)
        env_t["HOROVOD_AUTOTUNE"] = "1"
        tuned = float(np.median(hvd_run(
            _scaling_worker, np=nproc, extra_env=env_t,
            start_timeout=600)))
        default = float(np.median(hvd_run(
            _scaling_worker, np=nproc, extra_env=dict(env_base),
            start_timeout=600)))
        tuned_r.append(tuned)
        default_r.append(default)
        ratios.append(tuned / default if default else 0.0)
    return {
        "metric": "autotune_real_workload_ratio",
        "value": round(float(np.median(ratios)), 3),
        "unit": "tuned/default throughput",
        "np": nproc,
        "tuned_img_sec": round(float(np.median(tuned_r)), 1),
        "default_img_sec": round(float(np.median(default_r)), 1),
        "rounds": [round(r, 3) for r in ratios],
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--np", type=int, default=0, metavar="N",
                    help="run ONLY the weak-scaling job at N processes")
    ap.add_argument("--autotune-ab", action="store_true",
                    help="run ONLY the autotune-vs-default A/B on the "
                         "real scaling workload")
    ap.add_argument("--scaling", type=str, default=os.environ.get(
        "HVD_BENCH_SCALING", ""), metavar="N1,N2,...",
        help="weak-scaling sweep process counts (e.g. 1,2,4,8)")
    ap.add_argument("--scaling-only", action="store_true",
                    help="skip the single-chip bench")
    args = ap.parse_args()

    if args.autotune_ab:
        print(json.dumps(run_autotune_ab()))
        return

    if args.np:
        sizes = [args.np] if args.np == 1 else [1, args.np]
        table = run_weak_scaling(sizes)
        # Headline = capacity-adjusted (the framework-overhead number a
        # shared CI host can honestly produce; on a real pod with a chip
        # per process the two columns coincide).
        # Same normalized check the worker uses — any value other than
        # exactly "vgg" runs (and must be labeled as) the ResNet proxy.
        family = ("vgg" if os.environ.get("HVD_BENCH_SCALE_MODEL") == "vgg"
                  else "resnet")
        print(json.dumps({
            "metric": f"{family}_weak_scaling",
            "value": table[str(args.np)]["capacity_adjusted"],
            "unit": "efficiency",
            "vs_baseline": round(
                table[str(args.np)]["capacity_adjusted"] / 0.90, 3),
            "weak_scaling": table,
        }))
        return

    if args.scaling_only and not args.scaling:
        ap.error("--scaling-only requires --scaling (or HVD_BENCH_SCALING)")

    result = None
    if not args.scaling_only:
        result = run_chip_bench()

    if args.scaling:
        sizes = sorted({int(s) for s in args.scaling.split(",") if s})
        table = run_weak_scaling(sizes)
        if result is None:
            top = str(max(sizes))
            result = {
                "metric": "resnet_weak_scaling",
                "value": table[top]["capacity_adjusted"],
                "unit": "efficiency",
                # reference headline: 90% scaling efficiency
                # (docs/benchmarks.md:5-6)
                "vs_baseline": round(
                    table[top]["capacity_adjusted"] / 0.90, 3),
            }
        result["weak_scaling"] = table

    print(json.dumps(result))


if __name__ == "__main__":
    main()
