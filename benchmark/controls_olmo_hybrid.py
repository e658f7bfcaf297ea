"""Controls of the ``olmo_hybrid`` cell's comparison with the reference:
the SAME comparison the kind makes (``kinds/train_olmo_hybrid.py::
against_reference`` and ``within``), at the cell's sizes, on the
program as it is and on the program made wrong on purpose: a part of it
computed at a precision below the stated one, or a planted fault in
each thing this model adds. A limit of ``olmo_hybrid_reference.tolerances``
is worth what these readings say: the sound program has to pass every
check on every seed, and every control has to fail at least one.

    python3 benchmark/controls_olmo_hybrid.py --workload <cell> --seed <n>
        [--only sound,decays_bf16,...]

The program is patched from outside, for the time of one comparison;
nothing of it knows of a control. The reference always reads the true
weights and configuration. One process; prints one JSON line a control
and writes all of them to
``chiprun_out/controls_olmo_hybrid/<cell>-seed-<n>.json``."""

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.controls_nemotron_h import _patched  # noqa: E402


def _controls():
    """name -> ``(what it is, patch() context manager, cfg -> cfg)``;
    the last two may be None. Every one but ``sound`` has to fail the
    comparison."""
    import jax.numpy as jnp
    from jax import lax

    from benchmark import controls_qwen3_next
    from horovod_tpu.models import gated_deltanet as gdn
    from horovod_tpu.models import olmo_hybrid as oh
    from horovod_tpu.models import qwen3_next as qn
    from horovod_tpu.ops import delta_rule as rule_mod

    bf16 = jnp.bfloat16

    def decays_bf16():
        return _patched(rule_mod, "DECAY_DTYPE", bf16)

    @contextlib.contextmanager
    def float32_parts_bf16():
        def rmsnorm(x, scale, eps):
            # in the type it is given: bfloat16 where the activations are
            x = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + jnp.asarray(eps, x.dtype))
            return x * jnp.asarray(scale, x.dtype)
        with decays_bf16(), _patched(gdn, "rmsnorm32", rmsnorm):
            yield

    def no_delta_term():
        # the accepted control's gated linear attention (u = beta v),
        # taken from where it plants it: this model's recurrence is
        # called as that one's, q and k at the value heads
        with controls_qwen3_next._controls()["no_delta_term"][2]():
            linear_attention = qn.delta_rule
        return _patched(oh, "delta_rule", linear_attention)

    def qk_norm_per_head():
        def per_head(q, k, params, cfg):
            def norm(t, w):
                shape = t.shape
                t = t.reshape(shape[:2] + (cfg.n_heads, cfg.head_dim))
                return oh._norm(t, w.reshape(cfg.n_heads, cfg.head_dim),
                                cfg.eps).reshape(shape)
            return norm(q, params["q_norm"]), norm(k, params["k_norm"])
        return _patched(oh, "_qk_norm", per_head)

    def norm_on_the_input():
        return _patched(oh, "_residual", lambda x, w, eps, sublayer:
                        x + sublayer(oh._norm(x, w, eps)))

    def gate_left_out():
        return _patched(gdn, "gated_norm", lambda o, z, gate_w, eps:
                        gdn.rmsnorm32(o, gate_w, eps))

    return {
        "sound": ("the program as it is", None, None),
        "decays_bf16": (
            "the delta rule's log-decays, their cumulative sums and "
            "exponentials in bfloat16", decays_bf16, None),
        "float32_parts_bf16": (
            "the nearest precision below the stated one as a whole: the "
            "parts the configuration states as float32 that a patch can "
            "reach (the delta rule's decays, every RMSNorm) in bfloat16",
            float32_parts_bf16, None),
        "beta_without_its_factor": (
            "planted in the DeltaNet mixer: beta = sigmoid(b), the factor "
            "2 of linear_allow_neg_eigval left out", None,
            lambda cfg: dataclasses.replace(cfg, allow_neg_eigval=False)),
        "no_delta_term": (
            "planted in the DeltaNet mixer: the delta term S^T k left out "
            "(u = beta v)", no_delta_term, None),
        "gate_left_out": (
            "planted in the DeltaNet mixer: the head's normed output not "
            "multiplied by silu(W_g x)", gate_left_out, None),
        "qk_norm_per_head": (
            "planted in attention: q and k normed per head of 128, not "
            "over the whole projection", qk_norm_per_head, None),
        "norm_on_the_input": (
            "planted in the block: the norm moved to each sublayer's "
            "input, x + f(RMS(x))", norm_on_the_input, None),
    }


def run(cell, seed, only=None, out=print):
    """Every control (or those named) on ``cell`` (``manifest.cell``):
    a list of ``against_reference``'s numbers with ``control``, ``what``
    and ``checks`` beside them."""
    import jax
    import numpy as np

    from benchmark import tokens as token_gen
    from benchmark.kinds import train_olmo_hybrid as kind
    from horovod_tpu import topology

    # the reference's layer programs are the same for every control:
    # found again after the caches are cleared, not compiled again
    topology.compile_cache_dir()
    config, traffic = cell["config"], cell["traffic"]
    cfg = kind.model_config(config, traffic)
    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    toks, tgts = token_gen.make_tokens(seed, traffic["sequences"], seq,
                                       cfg.vocab)
    tok, tgt = (jax.numpy.asarray(t[:batch], jax.numpy.int32)
                for t in (toks, tgts))
    params = jax.jit(cfg.init_params)(jax.random.PRNGKey(int(seed)))
    loss_tol = kind.reference.tolerances(batch * seq)["loss_rel"]
    controls = _controls()
    rows = []
    for name in only or controls:
        what, patch, change_cfg = controls[name]
        t0 = time.perf_counter()
        # a traced layer function is cached by its identity: nothing of
        # an earlier variant's program may be found again
        jax.clear_caches()
        with (patch() if patch else contextlib.nullcontext()):
            numbers = kind.against_reference(
                change_cfg(cfg) if change_cfg else cfg, config, params,
                tok, tgt)
        checks = kind.within(numbers, batch * seq)
        checks["first_loss_matches_reference"] = bool(
            numbers["loss_rel"] <= loss_tol)
        rows.append(dict(numbers, control=name, what=what, checks=checks,
                         must_fail=name != "sound",
                         wall_s=time.perf_counter() - t0))
        by_leaf = numbers["grad_rel_by_leaf"]
        out(json.dumps({
            "control": name, "correct": all(checks.values()),
            "failed": [k for k, ok in checks.items() if not ok],
            "loss_rel": numbers["loss_rel"], "grad_rel": numbers["grad_rel"],
            "grad_rel_worst_leaf": numbers["grad_rel_worst_leaf"],
            "worst_leaf": numbers["worst_leaf"],
            "grad_rel_median_leaf": float(np.median(list(by_leaf.values()))),
            "wall_s": round(rows[-1]["wall_s"], 1)}))
    return rows


def main():
    from benchmark import manifest
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    cell = manifest.cell(args.workload, ROOT)
    rows = run(cell, args.seed, [n for n in args.only.split(",") if n],
               out=lambda line: print(line, flush=True))
    out_dir = ROOT / "chiprun_out" / "controls_olmo_hybrid"
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"{args.workload}-seed-{args.seed}.json", "w",
              encoding="utf-8") as f:
        json.dump(rows, f, indent=1)
    ok = all(all(r["checks"].values()) != r["must_fail"] for r in rows)
    print(json.dumps({"controls_separate": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
