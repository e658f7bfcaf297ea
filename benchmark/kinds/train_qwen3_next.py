"""Traffic kind ``train_qwen3_next``: the training loop of kind
``train`` (``train.run_model``: mesh, feed, weights, lowering and
compile, warm-up, both windows, the checks and the result) over the
program's in-jit step on a ``qwen3_next`` configuration (Gated DeltaNet
mixers, gated attention with partial rotary positions, a softmax
top-k expert layer of which this chip holds a share, over rows sorted
by expert).

It owns what differs from the other models and nothing else:
configuration file to the program's ``Qwen3NextConfig``
(``model_config``), the comparison with the plain reference
(``benchmark/qwen3_next_reference.py``) on the first batch, the
router's choices being an output of the program's one gradient program
(``against_reference``, ``within``), the FLOPs of a step, and the rows
the held experts got on that batch for the readers (``model``).

``correct`` is kind ``train``'s and, as in the hybrid cell and for its
reason (at a random initialisation the loss is ln(vocab) + 1/2 almost
whatever the layers compute), the program's GRADIENT on the first batch
against the reference's, over all parameters and in the worst leaf, and
the share of (token, held expert) choices on which the two routings
differ. Their limits are ``qwen3_next_reference.tolerances``;
``benchmark/controls_qwen3_next.py`` runs this comparison on the
program at precisions below the stated ones and with planted faults."""

import math
import time
from types import SimpleNamespace

import numpy as np

from benchmark import qwen3_next_flops as flops
from benchmark import qwen3_next_reference as reference
from benchmark.harness import Refused, say
from benchmark.kinds import train


def model_config(config, traffic):
    """The program's ``Qwen3NextConfig`` of a ``qwen3_next``
    configuration file under a traffic file's numerics."""
    import jax.numpy as jnp
    try:
        from horovod_tpu.models import qwen3_next
    except ImportError as e:
        raise Refused(f"the program has no qwen3_next model: {e}") from e
    if config.get("family") != "qwen3_next":
        raise Refused(f"configuration family {config.get('family')!r}: "
                      "kind 'train_qwen3_next' runs 'qwen3_next' files")
    if traffic["seq"] > config["max_position_embeddings"]:
        raise Refused(f"traffic seq {traffic['seq']} exceeds the "
                      f"configuration's max_position_embeddings")
    if set(traffic["layout"]) != {"dp"}:
        raise Refused(f"layout {traffic['layout']}: the model trains "
                      "under 'dp' alone")
    policy = traffic["remat_policy"]
    return qwen3_next.Qwen3NextConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        full_attention_interval=config["full_attention_interval"],
        eps=config["rms_norm_eps"],
        gdn_key_heads=config["linear_num_key_heads"],
        gdn_value_heads=config["linear_num_value_heads"],
        gdn_key_dim=config["linear_key_head_dim"],
        gdn_value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        chunk=config["program"]["gdn_chunk"],
        gdn_groups=config["program"]["gdn_groups"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rotary_dim=int(config["partial_rotary_factor"] * config["head_dim"]),
        rope_theta=float(config["rope_theta"]),
        n_experts=config["published"]["num_experts"],
        experts_held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        moe_ff=config["moe_intermediate_size"],
        shared_ff=config["shared_expert_intermediate_size"],
        dtype=getattr(jnp, traffic["dtype"]), remat=policy != "none",
        remat_policy=policy if policy != "none" else "full",
        use_flash=traffic["use_flash"],
        logits_bf16=traffic["logits_bf16"],
        loss_chunk=traffic["loss_chunk"])


def _at(tree, key):
    for k in key:
        tree = tree[k]
    return tree


def against_reference(cfg, config, params, tok, tgt, true_params=None):
    """The program's loss, gradient and routing on one batch against
    the plain reference's, as numbers: ``loss_rel``; ``grad_rel``, the
    norm of the gradients' difference over the norm of the reference's
    gradient, all parameters as one vector; ``grad_rel_worst_leaf``
    (and ``worst_leaf``), the same of the leaf where it is largest (a
    leaf whose gradient is zero or left out reads 1);
    ``choices_differing_share``, the (token, held expert) choices on
    which the reference's own routing differs from the program's, over
    the program's rows. Loss and gradient of the reference are taken
    under the PROGRAM's choices (``loss_and_grads`` says why).

    The reference reads ``true_params`` where a control hands the
    program other weights than the true ones. The program goes first:
    ONE jitted program gives its loss, its gradient and, as an auxiliary
    output of the same forward pass, its router's choices
    (``qwen3_next.loss_and_choices``); the gradient waits on the host
    while the reference runs, so the two never share the device."""
    import jax

    from horovod_tpu.models import qwen3_next

    seconds, t0 = {}, time.perf_counter()

    def lap(name):
        nonlocal t0
        seconds[name], t0 = time.perf_counter() - t0, time.perf_counter()

    (loss, routing), grads = jax.jit(jax.value_and_grad(
        lambda p, t, g: qwen3_next.loss_and_choices(p, t, g, cfg),
        has_aux=True))(params, tok, tgt)
    loss, routing = float(loss), np.asarray(routing)
    grads = jax.device_get(grads)
    lap("program_gradient_s")
    ref_loss, own, ref_grads = reference.loss_and_grads(
        params if true_params is None else true_params, np.asarray(tok),
        np.asarray(tgt), config, routing)
    leaves = {}
    # the reference makes a part's gradient when asked for it
    for key, want in ref_grads:
        flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(want))
        for (path, w), g in zip(flat,
                                jax.tree_util.tree_leaves(_at(grads, key))):
            name = ".".join([str(k) for k in key]
                            + [str(getattr(k, "key", k)) for k in path])
            w = np.asarray(w, np.float64).ravel()
            diff = np.asarray(g, np.float64).ravel() - w
            leaves[name] = (float(diff @ diff), float(w @ w))
    del grads
    lap("reference_s")
    by_leaf = {name: math.sqrt(e / r) if r else float(e > 0)
               for name, (e, r) in leaves.items()}
    worst = max(by_leaf, key=by_leaf.get)
    held = config["experts_held"]
    chosen = np.asarray(reference.rows(routing, held))
    rows = int(chosen.sum())
    differ = int((chosen != np.asarray(reference.rows(own, held))).sum())
    return {
        "reference_loss": ref_loss, "grad_program_loss": loss,
        "loss_rel": abs(loss - ref_loss) / abs(ref_loss),
        "grad_rel": math.sqrt(sum(e for e, _ in leaves.values())
                              / sum(r for _, r in leaves.values())),
        "grad_rel_worst_leaf": by_leaf[worst], "worst_leaf": worst,
        "grad_rel_by_leaf": by_leaf,
        "rows": rows, "choices_differing": differ,
        "choices_differing_share": differ / max(rows, 1),
        "rows_by_layer_and_expert": chosen.sum(2).tolist(),
        "seconds": seconds,
    }


def within(numbers, tokens_in_batch):
    """Which of ``against_reference``'s numbers are within their limits:
    the checks by name."""
    tol = reference.tolerances(tokens_in_batch)
    return {
        "gradients_match_reference":
            numbers["grad_rel"] <= tol["grad_rel"]
            and numbers["grad_rel_worst_leaf"] <= tol["grad_rel_worst_leaf"],
        "routing_matches_reference":
            numbers["choices_differing_share"]
            <= tol["choices_differing_share"],
    }


def model(cfg, config):
    """What ``train._run`` asks of a model (its docstring), for this
    one: the comparison above with its limits as further checks, the
    held experts' rows for the readers of this kind's own metrics."""
    layers = cfg.n_layers

    def compare(params, tok, tgt, tokens_per_step):
        numbers = against_reference(cfg, config, params, tok, tgt)
        seconds = numbers.pop("seconds")
        tol = reference.tolerances(tokens_per_step)
        rows = numbers["rows"]
        say(f"routing on the first batch: {rows} rows for the held "
            f"experts over {layers} expert layers "
            f"({rows / tokens_per_step / layers:.4f} a token and layer); "
            f"by layer and expert {numbers['rows_by_layer_and_expert']}; "
            f"{numbers['choices_differing']} choices differ from the "
            f"reference's, {numbers['choices_differing_share']:.4f} of the "
            f"rows (tolerance {tol['choices_differing_share']})")
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
            "record": {"moe_rows_first_batch": rows,
                       "against_reference": numbers},
            # under the name the accepted ``moe_pairs_per_token`` reads: a
            # row here is a (token, held expert) pair there
            "layer_run": {"moe_pairs_per_step": rows, "moe_layers": layers},
        }

    return SimpleNamespace(
        init_params=cfg.init_params, param_specs=cfg.param_specs,
        against_reference=compare,
        flops_per_step=lambda batch, seq: flops.model_flops_per_step(
            config, batch, seq))


def run(ctx):
    config, traffic = ctx.cell["config"], ctx.cell["traffic"]
    cfg = model_config(config, traffic)
    return train.run_model(ctx, cfg, model(cfg, config))
