"""Ordered-launch prototype A/B + hazard record.

Three measurements on the 8-device CPU mesh:

1. HAZARD (the reason the fence exists): an unrelated mesh-wide jit
   stream concurrent with eager collectives — with the fence OFF and
   every Python-level launch serialized under one lock, XLA CPU still
   aborts at the collective rendezvous (7-of-8). PJRT's cross-device
   fan-out happens on its own threadpool AFTER the Python execute call
   returns, so no host-side ordering (token-threading included — a
   data-dependency token cannot reorder FIFO device queues) can close
   the inversion window on this backend. Run with MODE=hazard to
   reproduce (the process ABORTS — that is the result).

2. A/B (async-submitter / producer-feeding workload): mesh-wide jit
   producers feeding eager async allreduces, fence (default) vs
   ordered-launch (HOROVOD_TPU_ORDERED_LAUNCH=1 + launch_lock around
   producers). Interleaved rounds, median ratio.

3. REGRESSION: the 4-of-8 producer-feeding scenario must complete with
   ordered-launch on (it does — also pinned in
   tests/test_engine_overlap.py::test_ordered_launch_*).

Conclusion recorded in docs/concepts.md + utils/env.py: the fence stays
the default on multi-device processes; ordered-launch is an opt-in
prototype for platforms whose per-device enqueue is host-call-ordered
(real TPU PJRT — unverifiable on this 1-chip box).
"""
import json
import os
import subprocess
import sys
import time

MODE = os.environ.get("MODE", "ab")

WORKER = r"""
import os, sys, time, threading
import numpy as np
sys.path.insert(0, ".")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
mode = sys.argv[1]          # "fence" | "ordered"
if mode == "ordered":
    os.environ["HOROVOD_TPU_ORDERED_LAUNCH"] = "1"
    os.environ["HOROVOD_TPU_PRODUCER_FENCE"] = "0"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import horovod_tpu as hvd
from horovod_tpu.ops import launch_lock
hvd.init()
mesh = hvd.mesh()

@jax.jit
def producer(x, i):
    for _ in range(6):
        x = jnp.tanh(x) @ jnp.eye(x.shape[-1], dtype=x.dtype)
    return x * 0 + i

x = jax.device_put(jnp.ones((512, 512), jnp.float32),
                   NamedSharding(mesh, P()))
ITERS = int(os.environ.get("AB_ITERS", 25))
WARM = 5

def step(r):
    if mode == "ordered":
        with launch_lock():
            ys = [producer(x, float(i)) for i in range(8)]
    else:
        ys = [producer(x, float(i)) for i in range(8)]
    hs = [hvd.allreduce_async(y, name=f"ol.{r}.{i}", average=False)
          for i, y in enumerate(ys)]
    for i, h in enumerate(hs):
        np.testing.assert_allclose(
            np.asarray(h.wait(timeout=60.0))[0, 0], float(i) * hvd.size())

for w in range(WARM):
    step(f"w{w}")
t0 = time.perf_counter()
for r in range(ITERS):
    step(r)
print(ITERS / (time.perf_counter() - t0))
"""


def run_arm(mode: str) -> float:
    out = subprocess.run([sys.executable, "-c", WORKER, mode],
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    if out.returncode != 0:
        raise RuntimeError(f"{mode} arm failed:\n{out.stderr[-2000:]}")
    return float(out.stdout.strip().splitlines()[-1])


HAZARD = r"""
import os, sys, time, threading
import numpy as np
sys.path.insert(0, ".")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["HOROVOD_TPU_ORDERED_LAUNCH"] = "1"
os.environ["HOROVOD_TPU_PRODUCER_FENCE"] = "0"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import horovod_tpu as hvd
from horovod_tpu.ops import launch_lock
hvd.init()
mesh = hvd.mesh()

@jax.jit
def unrelated(x):
    for _ in range(8):
        x = jnp.tanh(x) @ jnp.eye(x.shape[-1], dtype=x.dtype)
    return x

stop = [False]
def background():
    y = jax.device_put(jnp.ones((64, 64), jnp.float32),
                       NamedSharding(mesh, P()))
    while not stop[0]:
        with launch_lock():   # even fully locked: still aborts
            y = unrelated(y)
threading.Thread(target=background, daemon=True).start()
for r in range(40):
    hs = [hvd.allreduce_async(np.full(4096, float(i), np.float32),
                              name=f"hz.{r}.{i}", average=False)
          for i in range(4)]
    for h in hs:
        h.wait(timeout=60.0)
stop[0] = True
print("NO-ABORT (hazard did not reproduce this run)")
"""


def main():
    import numpy as np
    if MODE == "hazard":
        out = subprocess.run([sys.executable, "-c", HAZARD],
                             capture_output=True, text=True, timeout=900,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        print(json.dumps({
            "metric": "ordered_launch_hazard_repro",
            "aborted": out.returncode != 0,
            "returncode": out.returncode,
            "tail": out.stderr[-400:],
        }))
        return
    rounds = int(os.environ.get("AB_ROUNDS", 3))
    fence_r, ordered_r, ratios = [], [], []
    for _ in range(rounds):
        f = run_arm("fence")
        o = run_arm("ordered")
        fence_r.append(f)
        ordered_r.append(o)
        ratios.append(o / f)
    print(json.dumps({
        "metric": "ordered_launch_vs_fence",
        "value": round(float(np.median(ratios)), 3),
        "unit": "ordered/fence step-rate ratio (producer-feeding "
                "workload, 8-dev CPU mesh)",
        "ordered_steps_per_s": round(float(np.median(ordered_r)), 3),
        "fence_steps_per_s": round(float(np.median(fence_r)), 3),
        "rounds": [round(r, 3) for r in ratios],
        "hazard_note": "unrelated-stream scenario still aborts at XLA "
                       "rendezvous under full Python-side launch "
                       "locking (MODE=hazard); fence remains default",
    }))


if __name__ == "__main__":
    main()
