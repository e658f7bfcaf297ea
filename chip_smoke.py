#!/usr/bin/env python
"""chip_smoke.py — the standing proof that the training main path starts
on the chip: ``hvd.init()`` -> eager collectives -> the Pallas flash
kernel -> ``parallel.build_train_step`` at the full width and depth of
the 1.08B flagship (``bench_lm.py``'s ``wide1b_dotsmu`` row) -> a few
AdamW steps, all in ONE process through the public entry points.

    python chip_smoke.py              # one chip, every phase below
    python chip_smoke.py --chips 4    # ONLY the dp=4 phase, four chips

Default phases: (1) environment report; (2) ``hvd.init()``, one eager
``hvd.allreduce`` and ``hvd.broadcast_parameters`` of the real model's
parameter tree, checked against numpy; (3) flash attention forward and
all three gradients against float32 ``full_attention``; (4) the donated
``build_train_step`` program compiled once (kernel present, buffers
aliased), then steps on a repeated batch (losses finite, starting in a
stated band, falling; peak memory under the chip's HBM).

``--chips 4`` runs the same configuration on a ``dp=4`` mesh of the four
local chips (batch 2 a chip) against the same global batch on a
one-device mesh, and checks per-step loss agreement and that parameters,
optimizer state and batch really live on all four devices.

Everything printed is smoke output, not a performance number. The LAST
line of stdout is ``{"ok": true, "device": {...}}`` only when every
check held; any failure raises, so the exit code is non-zero and that
line is absent. Without an accelerator the script fails at once. Phase
sizes are arguments of the phase functions (tests/test_chip_smoke.py
runs them tiny on the CPU); the script itself has no size switch.
"""

import argparse
import dataclasses
import json
import math
import sys
import time

import numpy as np

SEED = 0
BATCH_PER_CHIP = 2
SEQ = 2048
FLASH_SHAPE = (2, 2048, 16, 128)        # [B, S, H, hd] of the 1.08B row
# bf16 keeps 8 significant bits (eps 2^-8 = 3.9e-3); the kernel and the
# float32 reference may differ by a few roundings of values of the
# tensor's own scale, never by a tenth of it.
FLASH_TOL = 2e-2
# First loss of THIS init at full size, +-10%. The flagship ties a
# N(0,1) embedding to the output head, so logits scale with d_model and
# the first loss sits far above ln(vocab) = 10.37. A CPU forward of the
# same seed and batch (XLA attention, PR 22) gives 478.27 in float32 and
# 478.31 in bf16 at batch 2, 477.79 at batch 8; the tiny rehearsal of
# the same init (tests/test_chip_smoke.py) gives 88.6 at d_model 128.
FIRST_LOSS_BAND = (430.0, 530.0)
# dp=4 against one device: same arithmetic, different batch split and
# remat policy, so only bf16 reassociation separates the losses.
FOUR_CHIP_RTOL = 1e-2


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def wide1b_config(**overrides):
    """``bench_lm.py``'s ``wide1b_dotsmu`` row as a TransformerConfig."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as tfm
    base = dict(vocab=32000, d_model=2048, n_layers=20, n_heads=16,
                d_ff=8192, max_seq=SEQ, dtype=jnp.bfloat16, remat=True,
                remat_policy="dots", use_flash=True, logits_bf16=True,
                loss_chunk=512)
    base.update(overrides)
    return tfm.TransformerConfig(**base)


def device_report():
    """The ``device`` object of the last line, as JAX reports it."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def environment_phase():
    """Phase 1: what this process runs on. Returns the device report."""
    import jax
    import jaxlib

    from horovod_tpu.topology import compile_cache_dir

    dev = device_report()
    print(f"[env] jax {jax.__version__} jaxlib {jaxlib.__version__} "
          f"python {sys.version.split()[0]}")
    print(f"[env] devices: {dev['count']} x {dev['kind']} "
          f"(platform {dev['platform']})")
    print(f"[env] compile cache: {compile_cache_dir()}")
    return dev


def control_plane_report():
    """Which control plane the eager engine gets: the loader falls back
    to Python with only a log warning, so say it here (not a failure)."""
    from horovod_tpu.runtime import native
    t0 = time.perf_counter()
    plane = ("native core, built from runtime/src"
             if native.load() is not None else "Python control plane")
    print(f"[env] control plane: {plane} "
          f"(loaded in {time.perf_counter() - t0:.1f} s)")


def load_tokens(cfg, batch, seq):
    """One seeded [batch, seq] int32 batch from the repo's own loader."""
    from horovod_tpu import data as hvd_data
    src = hvd_data.synthetic("tokens", n=max(batch, 256), seq_len=seq,
                             vocab=cfg.vocab, seed=1)
    loader = hvd_data.build_loader(src, batch_size=batch, rank=0,
                                   world_size=1, seed=1)
    tokens = np.asarray(next(loader).data[0], np.int32)
    check(tokens.shape == (batch, seq), f"loader gave {tokens.shape}")
    return tokens


def eager_phase(cfg):
    """Phase 2: ``hvd.init()``, one eager allreduce and one
    ``broadcast_parameters`` over the model's real parameter tree, each
    checked against numpy. Returns the broadcast parameters."""
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm

    hvd.init()
    n = hvd.size()
    print(f"[eager] hvd.init(): size {n}, local_size {hvd.local_size()}, "
          f"processes {hvd.process_count()}")

    x = np.random.RandomState(SEED).randn(cfg.d_model, cfg.d_ff).astype(
        np.float32)                                  # the wi leaf's shape
    t0 = time.perf_counter()
    y = np.asarray(hvd.allreduce(jnp.asarray(x), average=False,
                                 name="smoke.allreduce"))
    print(f"[eager] allreduce {x.shape} float32: "
          f"{time.perf_counter() - t0:.2f} s")
    # Every virtual rank contributes the replicated x: the sum is n * x.
    np.testing.assert_allclose(y, x * n, rtol=1e-6)

    params = tfm.init_params(cfg, jax.random.PRNGKey(SEED))
    want = [np.asarray(l) for l in jax.tree_util.tree_leaves(params)]
    n_params = sum(w.size for w in want)
    t0 = time.perf_counter()
    params = hvd.broadcast_parameters(params, root_rank=0)
    jax.block_until_ready(params)
    print(f"[eager] broadcast_parameters: {len(want)} leaves, "
          f"{n_params / 1e6:.1f}M parameters, "
          f"{time.perf_counter() - t0:.2f} s")
    got = jax.tree_util.tree_leaves(params)
    check(len(got) == len(want), "broadcast changed the tree")
    for g, w in zip(got, want):
        check(g.dtype == w.dtype and g.shape == w.shape,
              f"broadcast changed a leaf: {g.shape} {g.dtype}")
        np.testing.assert_array_equal(np.asarray(g), w)
    print("[eager] allreduce and broadcast match numpy")
    return params


def flash_phase(shape, *, interpret, tol=FLASH_TOL):
    """Phase 3: the flash kernel against float32 ``full_attention`` at
    ``shape`` = [B, S, H, hd] in bf16 — forward and dq, dk, dv."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.ring_attention import full_attention

    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), 4)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.float32).astype(
        jnp.bfloat16) for kk in keys)

    # w (the cotangent) is an ARGUMENT: closed over, it would be baked
    # into both executables as a constant (70 MB compile-cache entries).
    def flash(q, k, v, w):
        out = flash_attention(q, k, v, True, None, None, None, interpret)
        return (out.astype(jnp.float32) * w).sum(), out

    def reference(q, k, v, w):
        out = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), causal=True)
        return (out * w).sum(), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        flash, argnums=(0, 1, 2), has_aux=True))(q, k, v, w)
    with jax.default_matmul_precision("highest"):
        (_, ref_out), ref_grads = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1, 2), has_aux=True))(q, k, v, w)
    for name, a, b in zip(("out", "dq", "dk", "dv"), (out,) + grads,
                          (ref_out,) + ref_grads):
        a = np.asarray(a.astype(jnp.float32))
        b = np.asarray(b.astype(jnp.float32))
        check(np.isfinite(a).all(), f"flash {name} is not finite")
        err = float(np.max(np.abs(a - b)))
        rel = err / float(np.max(np.abs(b)))
        print(f"[flash] {name}: max abs err {err:.3e}, over the "
              f"reference's max {rel:.3e} (tolerance {tol:.0e})")
        check(rel < tol, f"flash {name} is off by {rel:.3e} of scale")


def _placement(tree):
    """Set of devices every array leaf of ``tree`` has a shard on."""
    import jax
    on_all = None
    for leaf in jax.tree_util.tree_leaves(tree):
        devs = {s.device for s in leaf.addressable_shards}
        on_all = devs if on_all is None else on_all & devs
    return on_all


def train_steps(cfg, mesh, params, tokens, *, steps, min_kernels):
    """The trainer: ``build_train_step`` on ``mesh``, lowered and
    compiled once, then ``steps`` steps on the repeated ``tokens`` batch,
    each ended with ``block_until_ready``. ``params`` are consumed (the
    step donates them). Returns a dict of what it saw."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel.train import build_train_step

    n_dev = mesh.devices.size
    opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    make, shard_params, shard_batch = build_train_step(cfg, mesh, opt)
    params = shard_params(params)
    # make() says how the step wants the optimizer state laid out (on a
    # dp mesh: moments as 1/dp shards); the compiled executable below
    # takes that layout and no other.
    step, opt_specs = make(params, jax.eval_shape(opt.init, params))
    opt_state = jax.jit(opt.init, out_shardings=jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), opt_specs,
        is_leaf=lambda x: isinstance(x, P)))(params)
    tok = shard_batch(jnp.asarray(tokens))
    tgt = shard_batch(jnp.asarray(np.roll(tokens, -1, axis=1)))

    t0 = time.perf_counter()
    lowered = step.lower(params, opt_state, tok, tgt)
    lower_s = time.perf_counter() - t0
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0 - lower_s
    mem = compiled.memory_analysis()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    alias = int(mem.alias_size_in_bytes)
    print(f"[train] dp={n_dev} batch {tokens.shape[0]} x seq "
          f"{tokens.shape[1]}: lowered in {lower_s:.1f} s, compiled in "
          f"{compile_s:.1f} s")
    print(f"[train] memory_analysis: argument "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, output "
          f"{mem.output_size_in_bytes / 1e9:.2f} GB, alias "
          f"{alias / 1e9:.2f} GB, temp "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB (per device)")
    print(f"[train] tpu_custom_call count: {n_kernels}; "
          f"alias_size_in_bytes: {alias}")
    check(n_kernels >= min_kernels,
          f"{n_kernels} Pallas kernels in the step, expected at least "
          f"{min_kernels}: attention took another path")
    check(alias > 0, "the step aliases no buffer: nothing was donated")

    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, tok, tgt)
        jax.block_until_ready((params, opt_state, loss))
        dt = time.perf_counter() - t0
        losses.append(float(loss))
        print(f"[train] step {i}: {dt:.3f} s, loss {losses[-1]:.4f}")
    check(all(math.isfinite(l) for l in losses),
          f"non-finite loss: {losses}")

    placed = {"params": _placement(params),
              "opt_state": _placement(opt_state),
              "batch_shards": sorted(
                  (s.device.id, s.data.shape)
                  for s in tok.addressable_shards)}
    peak = limit = None
    stats = mesh.devices.flat[0].memory_stats()
    if stats is None:
        check(mesh.devices.flat[0].platform != "tpu",
              "the TPU reported no memory stats")
        print("[train] peak_bytes_in_use: not reported by this backend")
    else:
        peak, limit = stats["peak_bytes_in_use"], stats["bytes_limit"]
        print(f"[train] peak_bytes_in_use: {peak} "
              f"({peak / 1e9:.2f} GB of {limit / 1e9:.2f} GB)")
        check(peak < limit, "peak memory is not under the device's HBM")
    return {"losses": losses, "compile_s": compile_s, "alias": alias,
            "kernels": n_kernels, "placed": placed, "peak": peak}


def train_phase(cfg, mesh, params, *, batch, seq, steps, first_loss_band,
                min_kernels):
    """Phase 4: the trainer on one chip; the first loss in
    ``first_loss_band``, the last below the first."""
    tokens = load_tokens(cfg, batch, seq)
    res = train_steps(cfg, mesh, params, tokens, steps=steps,
                      min_kernels=min_kernels)
    first, last = res["losses"][0], res["losses"][-1]
    lo, hi = first_loss_band
    check(lo <= first <= hi,
          f"first loss {first:.4f} outside the band [{lo}, {hi}]")
    check(last < first, f"loss did not fall: {res['losses']}")
    print(f"[train] loss {first:.4f} -> {last:.4f} over {steps} steps "
          f"(first-loss band [{lo}, {hi}])")
    return res


def four_chip_phase(cfg, mesh, *, per_chip_batch, seq, steps, rtol,
                    min_kernels):
    """The ``--chips 4`` phase: ``cfg`` on the dp mesh ``mesh`` (every
    local chip, ``per_chip_batch`` each) against the same global batch
    on a one-device mesh (full remat, so the whole batch fits one chip).
    Per-step losses must agree to ``rtol``; parameters and optimizer
    state must sit on every device and the batch be split evenly."""
    import jax
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm

    devices = list(mesh.devices.flat)
    n = len(devices)
    batch = per_chip_batch * n
    tokens = load_tokens(cfg, batch, seq)

    # Reference first, and its arrays are gone (train_steps keeps none)
    # before the dp state is placed: both do not fit device 0 at once.
    ref_cfg = dataclasses.replace(cfg, remat_policy="full")
    ref = train_steps(ref_cfg, Mesh(np.asarray(devices[:1]), ("dp",)),
                      tfm.init_params(ref_cfg, jax.random.PRNGKey(SEED)),
                      tokens, steps=steps, min_kernels=min_kernels)

    params = hvd.broadcast_parameters(
        tfm.init_params(cfg, jax.random.PRNGKey(SEED)), root_rank=0)
    got = train_steps(cfg, mesh, params, tokens, steps=steps,
                      min_kernels=min_kernels)

    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        print(f"[dp{n}] step {i}: loss {a:.4f} on {n} devices, {b:.4f} "
              f"on one (rel diff {abs(a - b) / abs(b):.2e})")
    np.testing.assert_allclose(got["losses"], ref["losses"], rtol=rtol)
    want = set(devices)
    for name in ("params", "opt_state"):
        check(got["placed"][name] == want,
              f"{name} not on every device: "
              f"{sorted(d.id for d in got['placed'][name])}")
    shards = got["placed"]["batch_shards"]
    check(len({d for d, _ in shards}) == n
          and all(shape == (per_chip_batch, seq) for _, shape in shards),
          f"batch not split {n} ways: {shards}")
    print(f"[dp{n}] params and optimizer state on all {n} devices; batch "
          f"shards {shards}")
    return got, ref


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the dp=4 phase on four chips")
    args = ap.parse_args(argv)

    dev = environment_phase()
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (platform "
                         f"{dev['platform']!r}); nothing was checked")
    check(dev["count"] == args.chips,
          f"{dev['count']} devices here; run with --chips {dev['count']}")

    control_plane_report()

    import horovod_tpu as hvd
    cfg = wide1b_config()
    kernels = 3 * cfg.n_layers              # fwd, dkv, dq in every layer
    if args.chips == 1:
        params = eager_phase(cfg)
        flash_phase(FLASH_SHAPE, interpret=False)
        train_phase(cfg, hvd.mesh(), params, batch=BATCH_PER_CHIP,
                    seq=SEQ, steps=5, first_loss_band=FIRST_LOSS_BAND,
                    min_kernels=kernels)
    else:
        hvd.init()
        four_chip_phase(cfg, hvd.mesh(), per_chip_batch=BATCH_PER_CHIP,
                        seq=SEQ, steps=3, rtol=FOUR_CHIP_RTOL,
                        min_kernels=kernels)
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
