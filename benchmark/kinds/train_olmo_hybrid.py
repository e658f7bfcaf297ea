"""Traffic kind ``train_olmo_hybrid``: the training loop of kind
``train`` (``train.run_model``: mesh, feed, weights, lowering and
compile, warm-up, both windows, the checks and the result) over the
program's in-jit step on an ``olmo_hybrid`` configuration (a dense
post-norm decoder: Gated DeltaNet mixers with a write strength up to 2
at heads of 96 / 192, ungated attention with a norm over the whole
projection and no position term, a gated SiLU MLP).

It owns what differs from the other models and nothing else:
configuration file to the program's ``OlmoHybridConfig``
(``model_config``), the comparison with the plain reference
(``benchmark/olmo_hybrid_reference.py``) on the first batch
(``against_reference``, ``within``) and the FLOPs of a step
(``model``). It owns no loop.

``correct`` is kind ``train``'s and, as in the other hybrid cells and
for their reason (at a random initialisation the loss is ln(vocab) +
1/2 almost whatever the layers compute), the program's GRADIENT on the
first batch against the reference's, over all parameters as one vector
and in the worst leaf. There are no discrete choices here, so nothing
is fixed between the two. The limits are
``olmo_hybrid_reference.tolerances``; ``benchmark/controls_olmo_hybrid.py``
runs this comparison on the program at precisions below the stated ones
and with planted faults."""

import math
import time
from types import SimpleNamespace

import numpy as np

from benchmark import olmo_hybrid_flops as flops
from benchmark import olmo_hybrid_reference as reference
from benchmark.harness import Refused, say
from benchmark.kinds import train
from benchmark.kinds.train_qwen3_next import _at


def model_config(config, traffic):
    """The program's ``OlmoHybridConfig`` of an ``olmo_hybrid``
    configuration file under a traffic file's numerics."""
    import jax.numpy as jnp
    try:
        from horovod_tpu.models import olmo_hybrid
    except ImportError as e:
        raise Refused(f"the program has no olmo_hybrid model: {e}") from e
    if config.get("family") != "olmo_hybrid":
        raise Refused(f"configuration family {config.get('family')!r}: "
                      "kind 'train_olmo_hybrid' runs 'olmo_hybrid' files")
    if traffic["seq"] > config["max_position_embeddings"]:
        raise Refused(f"traffic seq {traffic['seq']} exceeds the "
                      f"configuration's max_position_embeddings")
    if set(traffic["layout"]) != {"dp"}:
        raise Refused(f"layout {traffic['layout']}: the model trains "
                      "under 'dp' alone")
    if config["num_key_value_heads"] != config["num_attention_heads"] \
            or config["rope_parameters"]["rope_theta"] is not None:
        raise Refused("the program's olmo_hybrid attention has as many "
                      "key/value heads as query heads and no position term")
    policy = traffic["remat_policy"]
    return olmo_hybrid.OlmoHybridConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        # the file keeps the published list whole; the layers held here
        # are its first ``num_hidden_layers``
        layer_types=tuple(
            config["layer_types"][:config["num_hidden_layers"]]),
        d_ff=config["intermediate_size"], eps=config["rms_norm_eps"],
        gdn_key_heads=config["linear_num_key_heads"],
        gdn_value_heads=config["linear_num_value_heads"],
        gdn_key_dim=config["linear_key_head_dim"],
        gdn_value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        allow_neg_eigval=config["linear_allow_neg_eigval"],
        chunk=config["program"]["gdn_chunk"],
        gdn_groups=config["program"]["gdn_groups"],
        n_heads=config["num_attention_heads"],
        dtype=getattr(jnp, traffic["dtype"]), remat=policy != "none",
        remat_policy=policy if policy != "none" else "full",
        use_flash=traffic["use_flash"],
        logits_bf16=traffic["logits_bf16"],
        loss_chunk=traffic["loss_chunk"])


def against_reference(cfg, config, params, tok, tgt, true_params=None):
    """The program's loss and gradient on one batch against the plain
    reference's, as numbers: ``loss_rel``; ``grad_rel``, the norm of
    the gradients' difference over the norm of the reference's
    gradient, all parameters as one vector; ``grad_rel_worst_leaf``
    (and ``worst_leaf``), the same of the leaf where it is largest (a
    leaf whose gradient is zero or left out reads 1).

    The reference reads ``true_params`` where a control hands the
    program other weights than the true ones. The program goes first,
    one jitted program for its loss and gradient; the gradient waits on
    the host while the reference runs, so the two never share the
    device."""
    import jax

    seconds, t0 = {}, time.perf_counter()

    def lap(name):
        nonlocal t0
        seconds[name], t0 = time.perf_counter() - t0, time.perf_counter()

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, t, g: cfg.loss_fn(p, t, g)))(params, tok, tgt)
    loss = float(loss)
    grads = jax.device_get(grads)
    lap("program_gradient_s")
    ref_loss, ref_grads = reference.loss_and_grads(
        params if true_params is None else true_params, np.asarray(tok),
        np.asarray(tgt), config)
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
    return {
        "reference_loss": ref_loss, "grad_program_loss": loss,
        "loss_rel": abs(loss - ref_loss) / abs(ref_loss),
        "grad_rel": math.sqrt(sum(e for e, _ in leaves.values())
                              / sum(r for _, r in leaves.values())),
        "grad_rel_worst_leaf": by_leaf[worst], "worst_leaf": worst,
        "grad_rel_by_leaf": by_leaf,
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
    }


def model(cfg, config):
    """What ``train._run`` asks of a model (its docstring), for this
    one: the comparison above with its limits as a further check."""
    def compare(params, tok, tgt, tokens_per_step):
        numbers = against_reference(cfg, config, params, tok, tgt)
        seconds = numbers.pop("seconds")
        tol = reference.tolerances(tokens_per_step)
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
            "compared": {key: (numbers[key], tol[key])
                         for key in ("grad_rel", "grad_rel_worst_leaf")},
            "record": {"against_reference": numbers},
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
