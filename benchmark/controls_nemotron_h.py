"""Controls of the ``nemotron_h`` cells' comparison with the reference:
the SAME comparison the kind makes (``kinds/train_nemotron_h.py::
against_reference`` and ``within``), at the cell's sizes, on the
program as it is and on the program made wrong on purpose — a part of
it computed at a precision below the stated one, or a planted fault in
each new kind of layer. A limit of ``nemotron_h_reference.tolerances``
is worth what these readings say: the sound program has to pass every
check on every seed, and every control has to fail at least one, but
for those the stated precision's own noise hides (``_controls``).

    python3 benchmark/controls_nemotron_h.py --workload <cell> --seed <n>
        [--only sound,decays_bf16,...]

The program is patched from outside, for the time of one comparison;
nothing of it knows of a control. The reference always reads the true
weights and configuration, under each variant's own routing choices
(as the kind's comparison does). One process; prints one JSON
line a control and writes all of them to
``chiprun_out/controls_nemotron_h/<cell>-seed-<n>.json``."""

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


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _controls():
    """name -> ``(what it is, whether the comparison has to fail it,
    patch() context manager, cfg -> cfg, params -> params)``; the last
    three may be None. A control the comparison need not fail is one
    the stated precision's own noise hides: bfloat16 activations put
    about 3% on every leaf's gradient, and a bfloat16 router table or
    norm adds less than that (PERF.md section 6, PR 31)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import nemotron_h as nh
    from horovod_tpu.ops import ssd_scan as scan_mod

    bf16 = jnp.bfloat16

    def router_bf16():
        route = nh.route

        def rounded(u, router, b_corr, cfg):
            # u is bfloat16 already; with the table rounded too the
            # float32 matmul of the two IS the bfloat16 matmul
            return route(u, router.astype(bf16).astype(jnp.float32),
                         b_corr, cfg)
        return _patched(nh, "route", rounded)

    def norms_bf16():
        def rmsnorm(x, w, eps):
            x = x * jax.lax.rsqrt(
                jnp.mean(x * x, -1, keepdims=True) + jnp.asarray(eps, x.dtype))
            return x * w.astype(x.dtype)
        return _patched(nh, "_rmsnorm", rmsnorm)

    def scan_drops_state():
        scan = nh.ssd_scan

        def by_chunk(x, dt, a, b, c, d, *, chunk):
            # every chunk a sequence of its own: no state crosses
            def fold(t):
                return t.reshape((-1, chunk) + t.shape[2:])
            y = scan(fold(x), fold(dt), a, fold(b), fold(c), d, chunk=chunk)
            return y.reshape(x.shape)
        return _patched(nh, "ssd_scan", by_chunk)

    def attn_wrong_group():
        attend = nh.tfm.local_attention

        def shifted(q, k, v, cfg):
            # every query head reads the NEXT group's keys and values
            rep = cfg.n_heads // cfg.n_kv_heads
            return attend(q, jnp.roll(k, rep, axis=2),
                          jnp.roll(v, rep, axis=2), cfg)
        return _patched(nh.tfm, "local_attention", shifted)

    def weights_fp8(params):
        # to the 3 mantissa bits of float8_e4m3, to nearest even, at any
        # scale (as a scaled float8 matmul holds them), with integer ops:
        # XLA:TPU drops an astype(float8).astype(float32) round trip on
        # a v5e, which has no float8 (my chip run, PR 31: that control
        # read the sound program's numbers to the last digit)
        drop = 23 - 3

        def round_leaf(x):
            if x.ndim < 2:
                return x
            u = jax.lax.bitcast_convert_type(x, jnp.uint32)
            u = ((u + jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & 1))
                 & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF))
            return jax.lax.bitcast_convert_type(u, jnp.float32)
        return jax.jit(lambda p: jax.tree_util.tree_map(round_leaf, p))(
            params)

    return {
        "sound": ("the program as it is", False, None, None, None),
        "decays_bf16": (
            "the scan's dt * A, cumulative sums and exponentials in "
            "bfloat16",
            True, lambda: _patched(scan_mod, "DECAY_DTYPE", bf16), None,
            None),
        "router_bf16": ("the router's table in bfloat16: a bfloat16 matmul",
                        False, router_bf16, None, None),
        "norms_bf16": ("every RMSNorm, the gated one too, in bfloat16",
                       False, norms_bf16, None, None),
        "weights_fp8": (
            "every matrix rounded to float8_e4m3's 3 mantissa bits, the "
            "nearest precision below bfloat16, before the program sees it",
            True, None, None, weights_fp8),
        "scan_drops_state": (
            "planted in M: no state crosses a chunk boundary",
            True, scan_drops_state, None, None),
        "attn_wrong_group": (
            "planted in *: query heads read the next group's K and V",
            True, attn_wrong_group, None, None),
        "routed_unscaled": (
            "planted in E: routed_scaling_factor left out",
            True, None,
            lambda cfg: dataclasses.replace(cfg, routed_scaling=1.0), None),
    }


def run(cell, seed, only=None, out=print):
    """Every control (or those named) on ``cell`` (``manifest.cell``):
    a list of ``against_reference``'s numbers with ``control``, ``what``
    and ``checks`` beside them."""
    import jax
    import numpy as np

    from benchmark import tokens as token_gen
    from benchmark.kinds import train_nemotron_h as kind
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
    controls = _controls()
    rows = []
    for name in only or controls:
        what, must_fail, patch, change_cfg, change_params = controls[name]
        t0 = time.perf_counter()
        # a traced layer function is cached by its identity: nothing of
        # an earlier variant's program may be found again
        jax.clear_caches()
        the_cfg = change_cfg(cfg) if change_cfg else cfg
        the_params = change_params(params) if change_params else params
        with (patch() if patch else contextlib.nullcontext()):
            numbers = kind.against_reference(the_cfg, config, the_params,
                                             tok, tgt, true_params=params)
        del the_params
        numbers.pop("pairs_by_layer_and_expert")
        checks = kind.within(numbers, batch * seq)
        checks["first_loss_matches_reference"] = bool(
            numbers["loss_rel"]
            <= kind.reference.tolerances(batch * seq)["loss_rel"])
        rows.append(dict(numbers, control=name, what=what, checks=checks,
                         must_fail=must_fail,
                         wall_s=time.perf_counter() - t0))
        by_leaf = numbers["grad_rel_by_leaf"]
        out(json.dumps({
            "control": name, "must_fail": must_fail,
            "correct": all(checks.values()),
            "failed": [k for k, ok in checks.items() if not ok],
            "loss_rel": numbers["loss_rel"], "grad_rel": numbers["grad_rel"],
            "grad_rel_worst_leaf": numbers["grad_rel_worst_leaf"],
            "worst_leaf": numbers["worst_leaf"],
            "grad_rel_median_leaf": float(np.median(list(by_leaf.values()))),
            "choices_differing_share": numbers["choices_differing_share"],
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
    out_dir = ROOT / "chiprun_out" / "controls_nemotron_h"
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"{args.workload}-seed-{args.seed}.json", "w",
              encoding="utf-8") as f:
        json.dump(rows, f, indent=1)
    ok = all(all(r["checks"].values()) != r["must_fail"] for r in rows
             if r["must_fail"] or r["control"] == "sound")
    print(json.dumps({"controls_separate": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
