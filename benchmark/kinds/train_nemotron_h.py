"""Traffic kind ``train_nemotron_h``: the training loop of kind
``train`` over the program's in-jit step (``build_train_step``) on a
``nemotron_h`` hybrid configuration (Mamba-2 mixers, grouped-query
attention, a latent expert layer of which this chip holds a share).

It owns its set-up — configuration file to the program's
``NemotronHConfig``, weights from the seed in one jitted call, the plain
reference (``benchmark/nemotron_h_reference.py``) and the routing probe
on the first batch, lowering and compile, warm-up — and takes the
window, the traced window, the mesh, the optimizer and the replica
check from ``kinds/train.py`` as they are. What it returns carries the
keys kind ``train`` returns, so every reader that reads those finds
them; beside them ``moe_pairs_per_step`` and ``moe_layers`` for the
readers of this kind's own metrics.

``correct`` is kind ``train``'s (the first step's loss of the timed
program at the timed sizes against the reference on the same weights
and batch; losses finite; the loss fell; no compile in the window; on
more than one chip, equal replicas) and, because at a random
initialisation the loss is ln(vocab) + 1/2 almost whatever the mixers
and the experts compute, two numbers that see every layer
(``against_reference``): the program's GRADIENT on the first batch
(``jax.grad`` of ``cfg.loss_fn``, the function the step differentiates)
against the reference's, over all parameters and in the worst leaf, and
the share of (token, held expert) choices on which the two routings
differ. Their limits are ``nemotron_h_reference.tolerances``;
``benchmark/controls_nemotron_h.py`` runs this comparison on the
program at precisions below the stated ones and with planted
faults."""

import math
import statistics
import time

import numpy as np

from benchmark import nemotron_h_flops as flops
from benchmark import nemotron_h_reference as reference
from benchmark import tokens as token_gen
from benchmark.harness import Refused, say
from benchmark.kinds import train


def model_config(config, traffic):
    """The program's ``NemotronHConfig`` of a ``nemotron_h``
    configuration file under a traffic file's numerics."""
    import jax.numpy as jnp
    try:
        from horovod_tpu.models import nemotron_h
    except ImportError as e:
        raise Refused(f"the program has no nemotron_h model: {e}") from e
    if config.get("family") != "nemotron_h":
        raise Refused(f"configuration family {config.get('family')!r}: "
                      "kind 'train_nemotron_h' runs 'nemotron_h' files")
    if traffic["seq"] > config["max_position_embeddings"]:
        raise Refused(f"traffic seq {traffic['seq']} exceeds the "
                      f"configuration's max_position_embeddings")
    if set(traffic["layout"]) != {"dp"}:
        raise Refused(f"layout {traffic['layout']}: the hybrid model "
                      "trains under 'dp' alone")
    policy = traffic["remat_policy"]
    return nemotron_h.NemotronHConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        eps=config["layer_norm_epsilon"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        mamba_groups=config["n_groups"],
        state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk=config["chunk_size"],
        time_step_min=config["time_step_min"],
        time_step_max=config["time_step_max"],
        time_step_floor=config["time_step_floor"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        n_routed_experts=config["published"]["n_routed_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        routed_scaling=float(config["routed_scaling_factor"]),
        moe_latent=config["moe_latent_size"],
        moe_ff=config["moe_intermediate_size"],
        shared_ff=config["moe_shared_expert_intermediate_size"],
        dtype=getattr(jnp, traffic["dtype"]), remat=policy != "none",
        remat_policy=policy if policy != "none" else "full",
        use_flash=traffic["use_flash"],
        logits_bf16=traffic["logits_bf16"],
        loss_chunk=traffic["loss_chunk"])


def against_reference(cfg, config, params, tok, tgt, true_params=None):
    """The program's loss, gradient and routing on one batch against
    the plain reference's, as numbers: ``loss_rel``; ``grad_rel``, the
    norm of the gradients' difference over the norm of the reference's
    gradient, all parameters as one vector; ``grad_rel_worst_leaf``
    (and ``worst_leaf``), the same of the leaf where it is largest (a
    leaf whose gradient is zero or left out reads 1; the leaves on
    which the reference's gradient is exactly zero, the routing's
    correction bias, are left out of both and named in
    ``gradient_where_reference_has_none`` if the program's is not);
    ``choices_differing_share``, the (token, held expert) choices on
    which the reference's own routing differs from the program's, over
    the program's pairs. Loss and gradient of the reference are taken
    under the PROGRAM's choices (``loss_and_grads`` says why).

    The reference reads ``true_params`` where a control hands the
    program other weights than the true ones. It goes first and its
    gradients wait on the host, so the two never share the device."""
    import jax

    from horovod_tpu.models import nemotron_h

    seconds, t0 = {}, time.perf_counter()

    def lap(name):
        nonlocal t0
        seconds[name], t0 = time.perf_counter() - t0, time.perf_counter()

    routing = np.asarray(jax.jit(
        lambda p, t: nemotron_h.routing_choices(p, t, cfg))(params, tok))
    lap("routing_probe_s")
    ref_loss, own, ref_grads = reference.loss_and_grads(
        params if true_params is None else true_params, np.asarray(tok),
        np.asarray(tgt), config, routing)
    ref_grads = {key: jax.device_get(g) for key, g in ref_grads}
    lap("reference_s")
    loss, grads = jax.jit(jax.value_and_grad(cfg.loss_fn))(params, tok, tgt)
    leaves, bad_zero = {}, []
    for key, want in ref_grads.items():
        got = grads[key[0]] if len(key) == 1 else grads[key[0]][key[1]]
        flat, _ = jax.tree_util.tree_flatten_with_path(want)
        for (path, w), g in zip(flat, jax.tree_util.tree_leaves(got)):
            name = ".".join([str(k) for k in key]
                            + [str(getattr(k, "key", k)) for k in path])
            g = np.asarray(jax.device_get(g), np.float64).ravel()
            w = np.asarray(w, np.float64).ravel()
            if not w.any():
                if g.any():
                    bad_zero.append(name)
                continue
            diff = g - w
            leaves[name] = (float(diff @ diff), float(w @ w))
    del grads, ref_grads
    lap("program_gradient_s")
    by_leaf = {name: math.sqrt(e / r) for name, (e, r) in leaves.items()}
    worst = max(by_leaf, key=by_leaf.get)
    held = config["experts_held"]
    chosen = np.asarray(reference.pairs(routing, held))
    pairs = int(chosen.sum())
    differ = int((chosen != np.asarray(reference.pairs(own, held))).sum())
    loss = float(loss)
    return {
        "reference_loss": ref_loss, "grad_program_loss": loss,
        "loss_rel": abs(loss - ref_loss) / abs(ref_loss),
        "grad_rel": math.sqrt(sum(e for e, _ in leaves.values())
                              / sum(r for _, r in leaves.values())),
        "grad_rel_worst_leaf": by_leaf[worst], "worst_leaf": worst,
        "grad_rel_by_leaf": by_leaf,
        "gradient_where_reference_has_none": bad_zero,
        "pairs": pairs, "choices_differing": differ,
        "choices_differing_share": differ / max(pairs, 1),
        "pairs_by_layer_and_expert": chosen.sum(2).tolist(),
        "seconds": seconds,
    }


def within(numbers, tokens_in_batch):
    """Which of ``against_reference``'s numbers are within their limits:
    the checks by name."""
    tol = reference.tolerances(tokens_in_batch)
    return {
        "gradients_match_reference":
            numbers["grad_rel"] <= tol["grad_rel"]
            and numbers["grad_rel_worst_leaf"] <= tol["grad_rel_worst_leaf"]
            and not numbers["gradient_where_reference_has_none"],
        "routing_matches_reference":
            numbers["choices_differing_share"]
            <= tol["choices_differing_share"],
    }


def run(ctx):
    config, traffic = ctx.cell["config"], ctx.cell["traffic"]
    cfg = model_config(config, traffic)

    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import data as hvd_data
    from horovod_tpu.parallel.train import build_train_step

    opt = train.optimizer(traffic["optimizer"])
    hvd.init(devices=ctx.devices)
    feed = None
    try:
        mesh = train.build_mesh(traffic["layout"], ctx.devices)
        batch = traffic["batch_per_chip"] * mesh.devices.size
        toks, tgts = token_gen.make_tokens(ctx.seed, traffic["sequences"],
                                           traffic["seq"], cfg.vocab)
        loader = hvd_data.build_loader(
            hvd_data.ArraySource(toks, tgts), batch_size=batch, rank=0,
            world_size=1, seed=int(ctx.seed) % (2 ** 31))
        make, shard_params, shard_batch = build_train_step(cfg, mesh, opt)
        feed = hvd_data.prefetch_to_device(
            loader, NamedSharding(mesh, P("dp", None)),
            depth=traffic["prefetch_depth"])

        def next_batch():
            b = next(feed)
            return shard_batch(b.data[0]), shard_batch(b.data[1])

        return _run(ctx, mesh, cfg, opt, make, shard_params, next_batch,
                    batch * traffic["seq"])
    finally:
        if feed is not None:
            feed.close()
            thread = getattr(feed, "_thread", None)
            if thread is not None:
                thread.join(timeout=10)
        hvd.shutdown()


def _run(ctx, mesh, cfg, opt, make, shard_params, next_batch,
         tokens_per_step):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    config, traffic, watch = ctx.cell["config"], ctx.cell["traffic"], ctx.watch
    chips = mesh.devices.size
    moe_layers = cfg.pattern.count("E")
    spans = {}

    def on_mesh(specs):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

    # Weights: one jitted call from the seed, on the device.
    t0 = time.perf_counter()
    params = jax.jit(cfg.init_params,
                     out_shardings=NamedSharding(mesh, P()))(
                         jax.random.PRNGKey(int(ctx.seed)))
    params = shard_params(params)
    jax.block_until_ready(params)
    spans["weights_s"] = time.perf_counter() - t0

    # The plain reference on the first batch, and the program's
    # gradient and routing on it (programs of their own, not the step),
    # before the first step donates the weights.
    tok, tgt = next_batch()
    numbers = against_reference(cfg, config, params, tok, tgt)
    spans.update(numbers.pop("seconds"))
    ref_loss, pairs = numbers["reference_loss"], numbers["pairs"]
    tol = reference.tolerances(tokens_per_step)
    say(f"routing on the first batch: {pairs} (token, held expert) "
        f"pairs over {moe_layers} expert layers "
        f"({pairs / tokens_per_step / moe_layers:.4f} a token and "
        f"layer); by layer and expert "
        f"{numbers['pairs_by_layer_and_expert']}; "
        f"{numbers['choices_differing']} choices differ from the "
        f"reference's, {numbers['choices_differing_share']:.4f} of the "
        f"pairs (tolerance {tol['choices_differing_share']})")
    say(f"gradient on the first batch against the reference's: relative "
        f"difference {numbers['grad_rel']:.3e} over all parameters "
        f"(tolerance {tol['grad_rel']}), "
        f"{numbers['grad_rel_worst_leaf']:.3e} in the worst leaf, "
        f"{numbers['worst_leaf']} (tolerance "
        f"{tol['grad_rel_worst_leaf']})")

    step, opt_specs = make(params, jax.eval_shape(opt.init, params))
    opt_state = jax.jit(opt.init, out_shardings=on_mesh(opt_specs))(params)
    hits, misses = watch.cache_hits, watch.cache_misses
    t0 = time.perf_counter()
    lowered = step.lower(params, opt_state, tok, tgt)
    spans["lower_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    spans["compile_s"] = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    say(f"lowered in {spans['lower_s']:.2f} s, compiled in "
        f"{spans['compile_s']:.2f} s (compile cache: "
        f"{watch.cache_hits - hits} hit, {watch.cache_misses - misses} "
        f"written)")
    if mem is not None:
        say("memory_analysis per device: argument "
            f"{mem.argument_size_in_bytes / 1e9:.3f} GB, output "
            f"{mem.output_size_in_bytes / 1e9:.3f} GB, alias "
            f"{mem.alias_size_in_bytes / 1e9:.3f} GB, temp "
            f"{mem.temp_size_in_bytes / 1e9:.3f} GB")

    # First step (held to the reference), then warm-up until settled.
    warm, losses = [], []
    while True:
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, tok, tgt)
        losses.append(float(loss))
        warm.append(time.perf_counter() - t0)
        last = warm[-3:]
        settled = (len(warm) >= train.WARMUP_MIN_STEPS and
                   (max(last) - min(last)) <= train.WARMUP_SETTLED
                   * statistics.median(last))
        if settled or len(warm) >= train.WARMUP_MAX_STEPS:
            break
        tok, tgt = next_batch()
    first_loss = losses[0]
    step_s = statistics.median(warm[-3:])
    say(f"warm-up: {len(warm)} steps, "
        f"{' '.join(f'{1e3 * w:.1f}' for w in warm)} ms; step "
        f"{1e3 * step_s:.2f} ms")
    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    say(f"first loss {first_loss:.5f}, reference {ref_loss:.5f}: relative "
        f"difference {rel:.2e} (tolerance {tol['loss_rel']:.2e})")

    requests_before = watch.requests
    setup_s = time.perf_counter() - ctx.t_start
    if ctx.trace:
        window = train._traced_window(ctx, step_s, compiled, params,
                                      opt_state, next_batch)
    else:
        window = train._window(ctx.seconds, step_s, compiled, params,
                               opt_state, next_batch)
    params = window.pop("params")
    window_losses = window.pop("losses")

    checks = {
        "first_loss_matches_reference": rel <= tol["loss_rel"],
        **within(numbers, tokens_per_step),
        "losses_finite": all(math.isfinite(l)
                             for l in losses + window_losses),
        "loss_fell": window_losses[-1] < first_loss,
        "no_compile_in_window": watch.requests == requests_before,
    }
    if chips > 1:
        checks["replicas_equal"] = train.replicas_equal(
            params, cfg.param_specs(), mesh)

    out = {"setup_s": setup_s, "checks": checks,
           "attempted": window["steps"],
           "failed": sum(1 for l in window_losses
                         if not math.isfinite(l)),
           "record": {"spans": spans, "warmup_step_s": warm,
                      "first_loss": first_loss, "reference_loss": ref_loss,
                      "tokens_per_step": tokens_per_step,
                      "window_losses": window_losses,
                      "moe_pairs_first_batch": pairs,
                      "against_reference": numbers}}
    if ctx.trace:
        say(f"synced steps outside the profiler: "
            f"{len(window['step_seconds'])} samples")
        out["record"]["step_seconds"] = window["step_seconds"]
        out["layer_run"] = {
            "spans": dict(spans, input_wait_s=window["input_wait_s"]),
            "step_seconds": window["step_seconds"],
            "tokens_per_step": tokens_per_step,
            "model_flops_per_step": flops.model_flops_per_step(
                config, tokens_per_step // traffic["seq"], traffic["seq"]),
            "moe_pairs_per_step": pairs, "moe_layers": moe_layers,
        }
    else:
        out["values"] = train._end_to_end(window, setup_s, tokens_per_step,
                                          chips, out["record"])
    return out
