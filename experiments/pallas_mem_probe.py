"""Isolate Pallas kernel HBM throughput: trivial copy vs the fused-BN
component kernels, over block sizes. All timings are chained-k-loop
in-process A/B."""
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

sys.path.insert(0, ".")
from horovod_tpu.ops import fused_bn  # noqa: E402

M2, C2 = 802816, 256
K = 20
SIZE_MB = M2 * C2 * 2 / 1e6


def loop(step):
    @jax.jit
    def run(x, g):
        def body(_, carry):
            x, g = carry
            return step(x, g), x
        x, g = jax.lax.fori_loop(0, K, body, (x, g))
        return x
    return run


def timed(fn, args, reps=3):
    out = fn(*args)
    _ = float(jnp.sum(out[:8, :8].astype(jnp.float32)))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        _ = float(jnp.sum(out[:8, :8].astype(jnp.float32)))
        ts.append((time.perf_counter() - t0) / K)
    return float(np.median(ts))


def copy_kernel(x_ref, y_ref):
    y_ref[:] = x_ref[:]


def addone_kernel(x_ref, y_ref):
    y_ref[:] = x_ref[:] + jnp.bfloat16(1.0)


def stats_like_kernel(x_ref, y_ref):
    # reduce-only: read block, accumulate channel sums (writes tiny)
    @pl.when(pl.program_id(0) == 0)
    def _():
        y_ref[:] = jnp.zeros_like(y_ref)
    xf = x_ref[:].astype(jnp.float32)
    y_ref[:] += jnp.sum(xf, axis=0, keepdims=True)
    y_ref[:] += jnp.sum(xf * xf, axis=0, keepdims=True)


def make_pallas_map(kernel, bm, out_c=None, out_dtype=jnp.bfloat16):
    grid = (M2 // bm,)
    if out_c is None:  # elementwise map
        out_specs = pl.BlockSpec((bm, C2), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((M2, C2), out_dtype)
    else:
        out_specs = pl.BlockSpec((1, C2), lambda i: (0, 0))
        out_shape = jax.ShapeDtypeStruct((1, C2), jnp.float32)
    f = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[pl.BlockSpec((bm, C2), lambda i: (i, 0))],
        out_specs=out_specs, out_shape=out_shape)

    def step(x, g):
        out = f(x)
        if out_c is not None:
            # feed something x-shaped back for the chain
            return x + out[0, :C2].astype(x.dtype)
        return out
    return step


def main():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (M2, C2), jnp.bfloat16)
    g = jax.random.normal(key, (M2, C2), jnp.bfloat16)
    print("device:", jax.devices()[0].device_kind, flush=True)
    base = SIZE_MB * 1e6 / 819e9 * 1e3
    print(f"tensor: {SIZE_MB:.0f} MB; 1 pass = {base:.2f} ms", flush=True)

    def xla_add(x, g):
        return x + jnp.bfloat16(1.0)

    progs = {"xla y=x+1 (2 passes)": loop(xla_add)}
    for bm in (256, 512, 1024, 2048):
        progs[f"pallas copy bm={bm} (2 passes)"] = loop(
            make_pallas_map(copy_kernel, bm))
    for bm in (512, 1024, 2048):
        progs[f"pallas stats bm={bm} (1 pass)"] = loop(
            make_pallas_map(stats_like_kernel, bm, out_c=C2))

    for rnd in range(2):
        for name, prog in progs.items():
            t = timed(prog, (x, g))
            print(f"[{rnd}] {name}: {t*1e3:.2f} ms "
                  f"(~{t*1e3/base:.1f} passes)", flush=True)


if __name__ == "__main__":
    main()
