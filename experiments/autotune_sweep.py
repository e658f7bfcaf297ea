"""Autotune pay-rent sweep.

Round 4 measured tuned/default = 0.951 on the np=2 real training
workload — the tuner wasn't earning its ~1.1k LoC. Before retiring it,
sweep the regimes where the knobs PLAUSIBLY matter: multiprocess eager
with many small tensors (per-group control-plane round trips dominate;
cycle time and fusion threshold set the batching), np=2/4, shm plane
on. Grid over (cycle_ms, threshold_MB) with interleaved defaults, then
an HOROVOD_AUTOTUNE=1 arm on the same workload: if the grid shows a
>=1.1x pocket the tuner must find it; a flat grid is the documented
negative (the knobs themselves have no headroom on this plane, so no
tuner could).

Run: python experiments/autotune_sweep.py > experiments/autotune_sweep.log
(one JSON line on stdout; progress markers on stderr)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

NP = int(os.environ.get("SWEEP_NP", 4))
STEPS = int(os.environ.get("SWEEP_STEPS", 6))
ROUNDS = int(os.environ.get("SWEEP_ROUNDS", 2))

# Many-small-tensors step: 120 tensors, 4 KB - 1 MB (the torch-hook /
# fine-tune-head regime the 64 MiB threshold was NOT chosen for; total
# ~12 MB so cycle batching, not bandwidth, decides group count).
N_SMALL, SMALL_MAX = 120, 1 << 18


def _small_tensor_worker():
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    rng = np.random.RandomState(0)
    sizes = rng.randint(1 << 10, SMALL_MAX, size=N_SMALL)
    tensors = [rng.randn(s).astype(np.float32) for s in sizes]

    def step(tag):
        hs = [hvd.allreduce_async(t, average=True, name=f"{tag}.{i}")
              for i, t in enumerate(tensors)]
        for h in hs:
            h.wait()

    for w in range(2):
        step(f"w{w}")
    t0 = time.perf_counter()
    for i in range(STEPS):
        step(f"s{i}")
    return STEPS / (time.perf_counter() - t0)


def run_job(extra_env):
    from horovod_tpu.runner.api import run as hvd_run
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }
    env.update(extra_env)
    out = hvd_run(_small_tensor_worker, np=NP, extra_env=env,
                  start_timeout=600)
    return float(np.median(out))


def main():
    grid = []
    for cyc in ("1", "5", "20"):
        for thr_mb in ("8", "64"):
            grid.append((cyc, thr_mb))
    results = {}
    defaults = []
    for rnd in range(ROUNDS):
        defaults.append(run_job({}))
        for cyc, thr in grid:
            key = f"cycle{cyc}ms_thr{thr}mb"
            results.setdefault(key, []).append(run_job({
                "HOROVOD_TPU_CYCLE_TIME": cyc,
                "HOROVOD_TPU_FUSION_THRESHOLD": str(int(thr) << 20),
            }))
        print(f"# round {rnd} done", file=sys.stderr, flush=True)
    tuned = [run_job({"HOROVOD_AUTOTUNE": "1"}) for _ in range(ROUNDS)]

    base = float(np.median(defaults))
    table = {k: round(float(np.median(v)) / base, 3)
             for k, v in sorted(results.items())}
    best_key = max(table, key=table.get)
    print(json.dumps({
        "metric": "autotune_knob_headroom",
        "value": table[best_key],
        "unit": "best-grid/default step rate "
                f"(np={NP}, {N_SMALL} small tensors)",
        "best": best_key,
        "grid_vs_default": table,
        "autotune_vs_default": round(float(np.median(tuned)) / base, 3),
        "default_steps_per_s": round(base, 3),
        "rounds": ROUNDS,
    }))


if __name__ == "__main__":
    main()
