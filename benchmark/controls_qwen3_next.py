"""Controls of the ``qwen3_next`` cell's comparison with the reference:
the SAME comparison the kind makes (``kinds/train_qwen3_next.py::
against_reference`` and ``within``), at the cell's sizes, on the
program as it is and on the program made wrong on purpose — a part of
it computed at a precision below the stated one, or a planted fault in
each new kind of layer. A limit of ``qwen3_next_reference.tolerances``
is worth what these readings say: the sound program has to pass every
check on every seed, and every control has to fail at least one, but
for those the stated precision's own noise hides (``_controls``).

    python3 benchmark/controls_qwen3_next.py --workload <cell> --seed <n>
        [--only sound,decays_bf16,...]

The program is patched from outside, for the time of one comparison;
nothing of it knows of a control. The reference always reads the true
weights and configuration, under each variant's own routing choices
(as the kind's comparison does). One process; prints one JSON line a
control and writes all of them to
``chiprun_out/controls_qwen3_next/<cell>-seed-<n>.json``."""

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
    """name -> ``(what it is, whether the comparison has to fail it,
    patch() context manager, cfg -> cfg, params -> params)``; the last
    three may be None. A control the comparison need not fail is one
    the stated precision's own noise hides: a bfloat16 router table
    reads the sound program's numbers (PERF.md section 6, PR 37)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.models import qwen3_next as qn
    from horovod_tpu.ops import delta_rule as rule_mod

    bf16 = jnp.bfloat16

    def router_bf16():
        route = qn.route

        def rounded(u, router, cfg):
            # u is bfloat16 already; with the table rounded too the
            # float32 matmul of the two IS the bfloat16 matmul
            return route(u, router.astype(bf16).astype(jnp.float32), cfg)
        return _patched(qn, "route", rounded)

    def decays_bf16():
        return _patched(rule_mod, "DECAY_DTYPE", bf16)

    @contextlib.contextmanager
    def float32_parts_bf16():
        with decays_bf16(), router_bf16():
            yield

    def weights_fp8(params):
        # to float8_e4m3's 3 mantissa bits with integer ops
        # (controls_nemotron_h.py says why not by astype)
        drop = 23 - 3

        def round_leaf(x):
            if x.ndim < 2:
                return x
            u = lax.bitcast_convert_type(x, jnp.uint32)
            u = ((u + jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & 1))
                 & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF))
            return lax.bitcast_convert_type(u, jnp.float32)
        return jax.jit(lambda p: jax.tree_util.tree_map(round_leaf, p))(
            params)

    def no_delta_term():
        def linear_attention(q, k, v, g, beta, *, chunk):
            # u_t = beta_t v_t: the state is never asked what it holds
            # for the key; what is left is gated linear attention,
            # chunk by chunk
            bsz, s, h, dk = q.shape
            f32, c, dtype = jnp.float32, chunk, v.dtype

            def chunks(t):
                t = t.reshape((bsz, s // c, c, h) + t.shape[3:])
                return jnp.moveaxis(t, 3, 1)
            qc, kc = chunks(q).astype(f32), chunks(k).astype(f32)
            u = chunks(v).astype(f32) * chunks(beta)[..., None]
            gc = jnp.cumsum(chunks(g), axis=-1)
            seg = gc[..., :, None] - gc[..., None, :]
            decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((c, c), bool)),
                                      seg, -jnp.inf))
            k_end = kc * jnp.exp(gc[..., -1:] - gc)[..., None]

            def carry(state, inp):
                k_c, u_c, dec = inp
                nxt = (state * dec[..., None, None]
                       + jnp.einsum("bhid,bhie->bhde", k_c, u_c))
                return nxt, state
            _, starts = lax.scan(
                carry, jnp.zeros((bsz, h, dk, v.shape[-1]), f32),
                tuple(jnp.moveaxis(t, 2, 0)
                      for t in (k_end, u, jnp.exp(gc[..., -1]))))
            o = (jnp.einsum("bhnid,bhnde->bhnie",
                            qc * jnp.exp(gc)[..., None],
                            jnp.moveaxis(starts, 0, 2))
                 + jnp.einsum("bhnij,bhnje->bhnie",
                              jnp.einsum("bhnid,bhnjd->bhnij", qc, kc)
                              * decay, u))
            return jnp.moveaxis(o.astype(dtype), 1, 3).reshape(v.shape)
        return _patched(qn, "delta_rule", linear_attention)

    def shared_gate_left_out():
        def ungated(params, u, cfg):
            dt_ = cfg.dtype
            return (qn._gated(u @ params["shared_in"].astype(dt_))
                    @ params["shared_out"].astype(dt_)).astype(jnp.float32)
        return _patched(qn, "shared_expert", ungated)

    def weights_unnormalised():
        def route(u, router, cfg):
            scores = jax.nn.softmax(jnp.dot(
                u.astype(jnp.float32), router,
                precision=lax.Precision.HIGHEST), axis=-1)
            _, idx = lax.top_k(scores, cfg.top_k)
            return idx, jnp.take_along_axis(scores, idx, axis=-1)
        return _patched(qn, "route", route)

    return {
        "sound": ("the program as it is", False, None, None, None),
        "decays_bf16": (
            "the delta rule's log-decays, their cumulative sums and "
            "exponentials in bfloat16",
            True, decays_bf16, None, None),
        "router_bf16": ("the router's table in bfloat16: a bfloat16 matmul",
                        False, router_bf16, None, None),
        "float32_parts_bf16": (
            "the nearest precision below the stated one as a whole: both "
            "parts the configuration states as float32 (decays, router) "
            "in bfloat16",
            True, float32_parts_bf16, None, None),
        "weights_fp8": (
            "every matrix rounded to float8_e4m3's 3 mantissa bits, the "
            "nearest precision below bfloat16, before the program sees it",
            True, None, None, weights_fp8),
        "no_delta_term": (
            "planted in D: the delta term S^T k left out (u = beta v)",
            True, no_delta_term, None, None),
        "attn_gate_left_out": (
            "planted in A: the output is not multiplied by sigmoid(gate)",
            True, lambda: _patched(qn, "_output_gate",
                                   lambda attn, gate: attn), None, None),
        "rotary_on_all_dims": (
            "planted in A: rotary positions on all 256 dims of a head",
            True, None,
            lambda cfg: dataclasses.replace(cfg, rotary_dim=cfg.head_dim),
            None),
        "shared_gate_left_out": (
            "planted in the expert layer: the shared expert's sigmoid "
            "gate left out", True, shared_gate_left_out, None, None),
        "weights_unnormalised": (
            "planted in the expert layer: the chosen scores not divided "
            "by their sum", True, weights_unnormalised, None, None),
    }


def run(cell, seed, only=None, out=print):
    """Every control (or those named) on ``cell`` (``manifest.cell``):
    a list of ``against_reference``'s numbers with ``control``, ``what``
    and ``checks`` beside them."""
    import jax
    import numpy as np

    from benchmark import tokens as token_gen
    from benchmark.kinds import train_qwen3_next as kind
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
        numbers.pop("rows_by_layer_and_expert")
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
    out_dir = ROOT / "chiprun_out" / "controls_qwen3_next"
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
