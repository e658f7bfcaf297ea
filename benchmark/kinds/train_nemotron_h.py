"""Traffic kind ``train_nemotron_h``: the training loop of kind
``train`` (``train.run_model``: mesh, feed, weights, lowering and
compile, warm-up, both windows, the checks and the result) over the
program's in-jit step on a ``nemotron_h`` hybrid configuration (Mamba-2
mixers, grouped-query attention, a latent expert layer of which this
chip holds a share).

It owns what differs from the dense model and nothing else:
configuration file to the program's ``NemotronHConfig``
(``model_config``), the comparison with the plain reference
(``benchmark/nemotron_h_reference.py``) and the routing probe on the
first batch (``against_reference``, ``within``), the FLOPs of a step,
and ``moe_pairs_per_step`` / ``moe_layers`` for the readers of this
kind's own metrics (``hybrid_model``).

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
import time
from types import SimpleNamespace

import numpy as np

from benchmark import nemotron_h_flops as flops
from benchmark import nemotron_h_reference as reference
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


def hybrid_model(cfg, config):
    """What ``train._run`` asks of a model (its docstring), for this
    one: the comparison above with its limits as further checks, the
    routing's pairs for the readers of this kind's own metrics."""
    moe_layers = cfg.pattern.count("E")

    def compare(params, tok, tgt, tokens_per_step):
        numbers = against_reference(cfg, config, params, tok, tgt)
        seconds = numbers.pop("seconds")
        tol = reference.tolerances(tokens_per_step)
        pairs = numbers["pairs"]
        say(f"routing on the first batch: {pairs} (token, held expert) "
            f"pairs over {moe_layers} expert layers "
            f"({pairs / tokens_per_step / moe_layers:.4f} a token and "
            f"layer); by layer and expert "
            f"{numbers['pairs_by_layer_and_expert']}; "
            f"{numbers['choices_differing']} choices differ from the "
            f"reference's, {numbers['choices_differing_share']:.4f} of the "
            f"pairs (tolerance {tol['choices_differing_share']})")
        say(f"gradient on the first batch against the reference's: "
            f"relative difference {numbers['grad_rel']:.3e} over all "
            f"parameters (tolerance {tol['grad_rel']}), "
            f"{numbers['grad_rel_worst_leaf']:.3e} in the worst leaf, "
            f"{numbers['worst_leaf']} (tolerance "
            f"{tol['grad_rel_worst_leaf']})")
        return {
            "reference_loss": numbers["reference_loss"],
            "loss_tolerance": tol["loss_rel"],
            "spans": seconds,
            "checks": within(numbers, tokens_per_step),
            "compared": {key: (numbers[key], tol[key]) for key in
                         ("grad_rel", "grad_rel_worst_leaf",
                          "choices_differing_share")},
            "record": {"moe_pairs_first_batch": pairs,
                       "against_reference": numbers},
            "layer_run": {"moe_pairs_per_step": pairs,
                          "moe_layers": moe_layers},
        }

    return SimpleNamespace(
        init_params=cfg.init_params, param_specs=cfg.param_specs,
        against_reference=compare,
        flops_per_step=lambda batch, seq: flops.model_flops_per_step(
            config, batch, seq))


def run(ctx):
    config, traffic = ctx.cell["config"], ctx.cell["traffic"]
    cfg = model_config(config, traffic)
    return train.run_model(ctx, cfg, hybrid_model(cfg, config))
