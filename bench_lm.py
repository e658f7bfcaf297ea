#!/usr/bin/env python
"""Transformer-LM single-chip benchmark — tok/s + MFU for the flagship
GPT-style model (111M params: 12 layers, d_model 768, vocab 32000),
fwd+bwd+AdamW per step.

Method: K steps per jitted fori_loop (host dispatch off the timed path),
host-readback sync, per-config median over R timed windows, and all
configs run in ONE process so rows share one chip and one warm-up.

Input: tokens flow through the real input pipeline (horovod_tpu/data
sharded loader over a data.synthetic token source, docs/data.md), one
loader batch per fori_loop step, pre-staged as a [K,B,S] device stack
so per-step host dispatch stays off the timed path.

MFU uses the MODEL-FLOPs convention (6·N·T + attention FLOPs), NOT
XLA cost_analysis: with rematerialization the executed-FLOP count
includes recomputation, which would inflate "utilization" for doing
redundant work. Peak bf16 from the device kind (bench.py table); a
device that table does not name is an error.

Usage:
    python bench_lm.py                 # default config sweep, one JSON line
    python bench_lm.py --configs base,tuned
"""

import argparse
import json
import os
import time
from functools import partial

import numpy as np

# Each host call carries a fixed dispatch + readback cost — K must be
# large enough that it amortizes below the noise floor.
K = int(os.environ.get("HVD_BENCH_LM_K", 20))
WINDOWS = int(os.environ.get("HVD_BENCH_LM_WINDOWS", 3))

# (name, dict of TransformerConfig overrides + batch). The cumulative
# tuning ladder of rounds 2-5 (docs/benchmarks.md LM section; last
# measured before PR 1, not on current code). Dead ends kept out:
# remat (full or dots policy)
# at batch 16/32 always lost to batch-8 no-remat, and batch>=16
# without flash OOMs (the XLA attention score tensors + fp32 logits
# exceed the 15.75G HBM).
CONFIGS = {
    # Round-2 recorded configuration (the ladder's baseline row).
    # Every pre-flash ladder row pins use_flash=False: the auto-select
    # now turns flash on from seq 1024, which would smuggle the flash
    # step into earlier rows and make the ladder non-cumulative.
    "base": dict(n_heads=12, batch=8, remat=True, use_flash=False),
    # head_dim 128 (MXU-filling contraction).
    "heads128": dict(n_heads=6, batch=8, remat=True, use_flash=False),
    # + no recompute (activations fit HBM at seq 2048).
    "noremat": dict(n_heads=6, batch=8, remat=False, use_flash=False),
    # + bf16 logits matmul (softmax stays fp32).
    "bf16logits": dict(n_heads=6, batch=8, remat=False,
                       logits_bf16=True, use_flash=False),
    # + chunked cross-entropy: the fp32 [B,S,V] never materializes.
    # use_flash pinned OFF so this row isolates the loss change (the
    # auto-select would otherwise already turn flash on at seq 2048).
    "chunked": dict(n_heads=6, batch=8, remat=False,
                    logits_bf16=True, loss_chunk=512, use_flash=False),
    # + Pallas flash attention (the 512-block kernel crossover is ~1k).
    "flash": dict(n_heads=6, batch=8, remat=False,
                  logits_bf16=True, loss_chunk=512, use_flash=True),
    # batch-16 variant (fits only once flash kills the score tensor);
    # measured within ~15% of batch-8 "flash" across runs, sometimes
    # ahead, sometimes behind — batch is a weak knob past batch 8.
    "tuned": dict(n_heads=6, batch=16, remat=False,
                  logits_bf16=True, loss_chunk=512, use_flash=True),
    # Long-context row (seq 8192, batch 2 — pass --seq 8192): the
    # round-2 recorded config (left) vs + bf16 logits + chunked loss.
    "long_base": dict(n_heads=6, batch=2, remat=False, use_flash=True),
    "long_tuned": dict(n_heads=6, batch=2, remat=False, use_flash=True,
                       logits_bf16=True, loss_chunk=512),
    # In-process A/B control: "flash" minus the flash kernel (batch 8).
    "tuned_xla_attn": dict(n_heads=6, batch=8, remat=False,
                           logits_bf16=True, loss_chunk=512,
                           use_flash=False),
}

# The documented seq-2048 cumulative ladder (docs/benchmarks.md table).
LADDER = ["base", "heads128", "noremat", "bf16logits", "chunked",
          "flash", "tuned", "tuned_xla_attn"]

CONFIGS.update({
    # Long-context lever ladder at seq 8192 (round 4):
    # flash backward block size and loss-chunk sweeps on top of
    # long_tuned, plus a batch-4 row (more rows amortize per-step
    # fixed work).
    "long_fb256": dict(n_heads=6, batch=2, remat=False, use_flash=True,
                       logits_bf16=True, loss_chunk=512,
                       flash_block=256),
    "long_fb1024": dict(n_heads=6, batch=2, remat=False, use_flash=True,
                        logits_bf16=True, loss_chunk=512,
                        flash_block=1024),
    "long_lc2048": dict(n_heads=6, batch=2, remat=False, use_flash=True,
                        logits_bf16=True, loss_chunk=2048),
    "long_batch4": dict(n_heads=6, batch=4, remat=False, use_flash=True,
                        logits_bf16=True, loss_chunk=512),
    # Single row for the 16k demonstration (`--seq 16384 --configs
    # long16k`): batch 1 is what fits; flash + chunked loss are what
    # make it fit at all.
    "long16k": dict(n_heads=6, batch=1, remat=False, use_flash=True,
                    logits_bf16=True, loss_chunk=512),
    # Width demonstration (`--configs wide`, seq 2048): a 392M-param
    # shape whose [1536, 6144] FFN tiles actually fill the MXU — shows
    # the ~57% plateau of the 111M ladder is the model shape, not the
    # framework (docs/benchmarks.md "next lever is model width").
    "wide": dict(d_model=1536, d_ff=6144, batch=8, remat=False,
                 use_flash=True, logits_bf16=True, loss_chunk=512),
    # ~1B-param follow-through (`--configs wide1b`):
    # does the measured width lever (64.7% MFU at 392M) hold at a
    # realistic scale, and what binds next? 20 layers x d 2048
    # (head_dim 128) + tied embeddings = 1.03B params. fp32 AdamW
    # state is 3 x 4.1 GB, so remat is back on (activations must
    # shrink to fit the 15.75G HBM) and batch drops to 4.
    "wide1b": dict(d_model=2048, d_ff=8192, n_layers=20, n_heads=16,
                   batch=4, remat=True, use_flash=True,
                   logits_bf16=True, loss_chunk=512),
    # Next-lever probes on the 1B shape (measured round 5): dots-policy
    # remat at batch 2 wins (16.7k tok/s, 59.2% MFU — saving matmul
    # outputs recovers ~3 MFU points over full remat at batch 4);
    # batch 8 full-remat loses (14.6k, 51.8%); dots at batch 4 fails to
    # compile (exceeds HBM — fp32 AdamW state 12.3 GB + dots-saved
    # activations). The binding constraint after width is optimizer-
    # state memory: sharding it (ZeRO-style over 'dp') or bf16 moments
    # is what would let remat off entirely at 1B.
    "wide1b_dots": dict(d_model=2048, d_ff=8192, n_layers=20, n_heads=16,
                        batch=2, remat=True, remat_policy="dots",
                        use_flash=True, logits_bf16=True, loss_chunk=512),
    "wide1b_b8": dict(d_model=2048, d_ff=8192, n_layers=20, n_heads=16,
                      batch=8, remat=True, use_flash=True,
                      logits_bf16=True, loss_chunk=512),
    # bf16 first moment: frees ~2 GB of AdamW state and halves mu's
    # read+write traffic in the optimizer update. At batch 4 + dots it
    # STILL exceeds HBM (measured: compile fails — the dots-saved
    # activations are the bigger term); the batch-2 row below measures
    # the bandwidth side.
    "wide1b_dotsmu": dict(d_model=2048, d_ff=8192, n_layers=20,
                          n_heads=16, batch=2, remat=True,
                          remat_policy="dots", mu_bf16=True,
                          use_flash=True, logits_bf16=True,
                          loss_chunk=512),
})


def model_flops_per_step(n_params, batch, seq, n_layers, d_model):
    """6·N·T parameter FLOPs + causal attention FLOPs (fwd is
    2·B·S²·d per layer for QK^T+AV halved by causality; bwd doubles)."""
    tokens = batch * seq
    param_f = 6.0 * n_params * tokens
    attn_fwd = n_layers * 2.0 * batch * seq * seq * d_model / 2.0 * 2.0
    return param_f + 3.0 * attn_fwd


def bench_config(name, overrides, seq, peak):
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import transformer as tfm

    batch = overrides.pop("batch")
    # Optimizer-side knob (not a TransformerConfig field): bf16 first
    # moment — halves mu's HBM share (the 1B memory lever's cheap half;
    # optax stores nu in fp32 regardless).
    mu_bf16 = overrides.pop("mu_bf16", False)
    base = dict(vocab=32000, d_model=768, n_layers=12, d_ff=3072,
                max_seq=seq, dtype=jnp.bfloat16)
    base.update(overrides)  # rows may resize the model (e.g. "wide")
    cfg = tfm.TransformerConfig(**base)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    # Tokens come through the real input pipeline (horovod_tpu/data:
    # synthetic source -> sharded loader), not a jax.random bypass —
    # one loader batch per fori_loop step, staged as a [K,B,S] stack up
    # front so the timed window keeps measuring the device, not host
    # dispatch (docs/data.md). Seeded: every run draws the same stack.
    from horovod_tpu import data as hvd_data
    src = hvd_data.synthetic("tokens", n=max(K * batch, 256),
                             seq_len=seq, vocab=base["vocab"], seed=1)
    loader = hvd_data.build_loader(src, batch_size=batch, rank=0,
                                   world_size=1, seed=1)
    tokens_k = jnp.asarray(np.stack(
        [next(loader).data[0] for _ in range(K)]))   # [K, B, S] int32
    opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16 if mu_bf16 else None)
    state = opt.init(params)

    def loss_fn(p, tokens):
        targets = jnp.roll(tokens, -1, axis=1)
        return tfm.loss_fn(p, tokens, targets, cfg)

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_k(p, s):
        def body(i, carry):
            p, s = carry
            _, g = jax.value_and_grad(loss_fn)(p, tokens_k[i])
            up, s = opt.update(g, s, p)
            return optax.apply_updates(p, up), s
        return jax.lax.fori_loop(0, K, body, (p, s))

    # 3 warm calls: compile, then reach the jit donation/sharding
    # fixpoint (a recompile lands on call ~2-3 otherwise — bench.py
    # learned the same lesson; a mid-window recompile skews a median
    # of only 3 windows).
    for _ in range(3):
        params, state = train_k(params, state)
    float(jnp.sum(params["ln_f"]))          # force sync
    rates = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        params, state = train_k(params, state)
        float(jnp.sum(params["ln_f"]))
        dt = time.perf_counter() - t0
        rates.append(batch * seq * K / dt)
    tok_s = float(np.median(rates))
    flops = model_flops_per_step(n_params, batch, seq, cfg.n_layers,
                                 cfg.d_model)
    tf_s = tok_s / (batch * seq) * flops / 1e12
    return {"tok_s": round(tok_s, 0), "tflops": round(tf_s, 1),
            "mfu": round(tf_s / peak, 4),
            "params_m": round(n_params / 1e6, 1), "batch": batch,
            "heads": cfg.n_heads, "remat": cfg.remat}


def main():
    ap = argparse.ArgumentParser()
    # Default = the 8-row seq-2048 ladder; the long_* sweep rows are
    # seq-8192-only and run via the explicit --configs list the docs
    # show (at 2048 they would waste minutes and skew the recorded
    # configs dict).
    ap.add_argument("--configs", default=",".join(LADDER))
    ap.add_argument("--seq", type=int, default=2048)
    args = ap.parse_args()

    import jax
    from bench import peak_tflops
    from horovod_tpu.topology import compile_cache_dir
    compile_cache_dir()
    peak = peak_tflops(jax.devices()[0])

    results, failed = {}, []
    for name in args.configs.split(","):
        try:
            results[name] = bench_config(name, dict(CONFIGS[name]),
                                         args.seq, peak)
        except Exception as e:
            # A sweep row that OOMs (e.g. a flash block past the VMEM
            # budget) must not kill the other rows' measurements — but
            # the process exits non-zero below, and prints no result.
            print(f"# {name}: FAILED {str(e)[:200]}", flush=True)
            failed.append(name)
            continue
        print(f"# {name}: {results[name]}", flush=True)
    if failed:
        raise SystemExit(
            f"bench_lm: {len(failed)} requested config(s) failed: "
            f"{','.join(failed)}")
    best = max(results, key=lambda n: results[n]["tok_s"])
    # One-line-JSON schema convention (bench.py): value over a recorded
    # baseline, keyed on sequence length — the round-2 numbers for this
    # model were 44.3k tok/s at seq 2048 and 21.5k at 8192
    # (docs/benchmarks.md LM section). Unknown seq -> no ratio rather
    # than a ratio against the wrong baseline.
    baselines = {2048: 44300.0, 8192: 21500.0}
    out = {
        "metric": "transformer_lm_tok_s",
        "value": results[best]["tok_s"],
        "unit": "tok/s",
        "mfu": results[best]["mfu"],
        "seq": args.seq, "best_config": best, "peak_tflops": peak,
        "configs": results,
    }
    # The recorded baselines are for the 111M ladder shape; a row that
    # resizes the model (e.g. "wide") must not record a ratio against
    # the wrong model's baseline.
    resized = any(key in CONFIGS[best]
                  for key in ("d_model", "d_ff", "n_layers", "vocab"))
    if args.seq in baselines and not resized:
        out["vs_baseline"] = round(
            results[best]["tok_s"] / baselines[args.seq], 3)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
