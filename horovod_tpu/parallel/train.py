"""SPMD training-step builder — composes dp/tp/sp/ep into one jitted
program over the mesh.

This is the jit-native counterpart of the reference's DistributedOptimizer
(torch/__init__.py:42-151) generalized beyond data parallelism. The whole
step — forward (ring attention over 'sp', Megatron column/row splits over
'tp', MoE all_to_all over 'ep'), backward, gradient cross-shard reduction,
and the optimizer update — is ONE shard_map'ed, jitted program; XLA
schedules every collective on ICI.

Gradient reduction rule (manual SPMD). shard_map-of-grad computes the VJP
of the per-shard outputs with a cotangent seed of 1 on EVERY shard, i.e.
the gradient of sum-over-shards of the returned scalar, treating each
shard's copy of a replicated parameter as independent. To make that sum
equal the global batch-mean loss exactly once:

  - each data shard returns local_mean / n_data_shards, and
  - the value is masked to zero except on model-rank 0 (tp/ep index 0),
    so duplicated outputs across model axes don't overcount (the masked
    ranks still receive their cotangent shares through the transposes of
    the model's own collectives — row-parallel psum, ring ppermute,
    expert all_to_all).

Then the true gradient of a parameter sharded with spec S is a plain psum
of the per-shard gradients over every mesh axis NOT in S (the chain rule
for tied parameters), with no extra scaling anywhere.

The rule has two forms (``reduce_gradients``). An all-reduce is a
reduce-scatter followed by an all-gather, and every data shard would run
the same update on the same reduced gradient; so where 'dp' is larger
than 1 and the optimizer is elementwise, the sum over 'dp' is the
reduce-scatter alone (``psum_scatter`` on one dimension of the leaf),
the update runs on the 1/dp of the leaf that comes back, with 1/dp of
its moments, and the all-gather carries the NEW PARAMETER (the sharded
weight update of arXiv:2004.13336; ``build_train_step`` says when).
Same bytes on the wire, the same float32 expression on every element,
1/dp of the update's memory traffic and of the moments a chip. Every
other missing axis, and every leaf no dimension of which divides by dp,
keeps the psum.
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import transformer as tfm
from ..observability import registry as _reg
from .zero import (is_elementwise, shard_dim, spec_axes,
                   state_specs_by_structure, update_shard_specs)

DATA_AXES = ("dp", "sp")
MODEL_AXES = ("tp", "ep")


def _grad_reduce_bytes():
    """The gauge ``hvdtpu_jit_grad_reduce_bytes`` of the in-jit step."""
    return _reg.registry().gauge(
        "hvdtpu_jit_grad_reduce_bytes",
        "Bytes of every gradient leaf one device reduces over more than "
        "one device in one step of the traced program, before any "
        "compression. 0 on one device"
    ).labels()


def reduce_gradients(grads, specs, mesh: Mesh, hierarchical=None,
                     dcn_wire=None, shard_specs=None):
    """Apply the reduction rule leaf-by-leaf (see module docstring).

    ``shard_specs`` (``zero.update_shard_specs``) gives the rule its
    second form: a leaf to whose spec it adds 'dp' on one dimension is
    summed over 'dp' by a ``psum_scatter`` on that dimension, and the
    1/dp of the sum that this shard updates comes back; its other
    missing axes, and every other leaf, keep the psum.

    ``hierarchical=(ici_axis, dcn_axis)`` routes leaves that reduce
    over BOTH axes through the two-stage in-slice-then-cross-slice
    reduction (collectives.hierarchical_psum: reduce-scatter on ICI,
    1/ici_size-sized — optionally ``dcn_wire``-quantized — psum on DCN,
    all-gather back), instead of one flat psum over the pair. Leaves
    missing only one of the two keep the plain psum.

    Runs once per traced program, and there sets the gauge
    ``hvdtpu_jit_grad_reduce_bytes``: the bytes of every leaf reduced
    over more than one device (whichever form its 'dp' sum takes), per
    step and device, before any compression (docs/metrics.md)."""
    reduced_bytes = 0

    def red(g, spec, shard_spec):
        nonlocal reduced_bytes
        have = spec_axes(spec)
        missing = [ax for ax in mesh.axis_names if ax not in have]
        if math.prod(int(mesh.shape[ax]) for ax in missing) > 1:
            reduced_bytes += g.size * g.dtype.itemsize
        if hierarchical is not None:
            ici_ax, dcn_ax = hierarchical
            if ici_ax in missing and dcn_ax in missing:
                from .collectives import hierarchical_psum
                g = hierarchical_psum(g, ici_ax, dcn_ax, wire=dcn_wire)
                missing = [ax for ax in missing
                           if ax not in (ici_ax, dcn_ax)]
        d = shard_dim(spec, shard_spec)
        if d is not None:
            # First, so that the other axes sum 1/dp of the bytes.
            g = lax.psum_scatter(g, "dp", scatter_dimension=d, tiled=True)
            missing.remove("dp")
        if missing:
            g = lax.psum(g, tuple(missing))
        return g

    with jax.named_scope("hvd_grad_reduce"):
        grads = jax.tree_util.tree_map(
            red, grads, specs, specs if shard_specs is None else shard_specs,
            is_leaf=lambda x: isinstance(x, P))
    _grad_reduce_bytes().set(reduced_bytes)
    return grads


def build_train_step(cfg, mesh: Mesh, optimizer,
                     *, dcn_axis: Optional[str] = None,
                     dcn_wire: Optional[str] = None):
    """Returns ``(step_fn, shard_params, shard_batch)``.

    ``cfg`` is a model's configuration object, and the model is reached
    through it alone: ``cfg.param_specs()``, ``cfg.loss_fn(params,
    tokens, targets)``, its ``tp_axis`` / ``sp_axis`` / ``ep_axis``
    fields and ``cfg.layouts`` (what the model allows beside 'dp').
    ``models.transformer.TransformerConfig`` and
    ``models.nemotron_h.NemotronHConfig`` both provide them.

    step_fn(params, opt_state, tokens, targets) -> (params, opt_state, loss)
    — jitted over the mesh; tokens/targets are [B, S] global arrays sharded
    batch-over-'dp', sequence-over-'sp'. ``params`` and ``opt_state`` are
    DONATED: rebind them to the returned trees and copy first anything
    that must outlive the call.

    ``make(params, opt_state)`` (arrays or their shapes) returns the
    jitted step and the optimizer state's specs. **On a mesh whose 'dp'
    axis is larger than 1 the weight update is sharded** (module
    docstring): per parameter leaf, from its shape and spec alone, the
    last dimension that no mesh axis shards and whose size divides by
    dp is chosen (``zero.update_shard_specs`` says why the last); the
    gradient is reduce-scattered on it, ``optimizer.update`` and
    ``apply_updates`` run on that 1/dp of the leaf and of its moments,
    and the new parameter is all-gathered. The returned specs carry 'dp' on
    that dimension of every moment leaf (scalars replicated), in optax's
    own structure. A state made in that layout (``jax.jit(opt.init,
    out_shardings=...)``, as benchmark/kinds/train.py does) holds 1/dp
    of the moments a chip from the first step; a replicated
    ``opt.init(params)`` handed to the jitted step is resharded at the
    first call and comes back sharded (an executable compiled ahead of
    time takes the one layout it was compiled for: make the state in
    the returned layout). A leaf with no such dimension (an odd-sized
    vector, a scalar) keeps the psum and the whole update on every
    shard. All of it is keyed on what the code observes, with no switch:

    - dp = 1: nothing is sharded; the traced program is the one without
      the mechanism.
    - The optimizer must be ELEMENTWISE: every element of an update and
      of a new moment computed from that element's gradient, parameter
      and moments alone (AdamW, Adam, SGD with momentum, RMSProp, Lion).
      ``zero.is_elementwise`` reads that off the optimizer's traced
      ``update``, once in ``make``. One that looks across elements (a
      global-norm clip, a trust ratio, a finite-guard) would be silently
      wrong on shards: it keeps the psum and the replicated update, and
      the specs come back replicated.
    - ``dcn_axis`` set: ``hierarchical_psum`` owns the 'dp' reduction
      and already ends in an all-gather; the update stays whole and the
      specs come back replicated.

    ``dcn_axis`` names an OUTER data-parallel mesh axis that crosses
    slice/host boundaries (``"auto"`` discovers one via
    :func:`horovod_tpu.parallel.mesh.dcn_axes`): the batch shards over
    ``(dcn_axis, 'dp')`` jointly and the gradient reduction runs
    hierarchically — in-slice reduce-scatter over 'dp' first, then the
    1/dp-sized (optionally ``dcn_wire``-block-quantized, docs/compression.md)
    cross-slice psum, then the in-slice all-gather (docs/pipeline.md)."""
    specs = cfg.param_specs()
    axis_names = set(mesh.axis_names)
    for layout in ("tp", "sp", "ep"):
        if getattr(cfg, f"{layout}_axis"):
            _check_layout(cfg, layout, f"a bound {layout}_axis")

    if dcn_axis == "auto":
        from .mesh import dcn_axes as _dcn_axes
        found = [a for a in _dcn_axes(mesh) if a not in
                 (cfg.tp_axis, cfg.sp_axis, cfg.ep_axis)]
        dcn_axis = found[0] if found else None
    if dcn_axis is not None:
        if dcn_axis not in axis_names:
            raise ValueError(f"dcn_axis {dcn_axis!r} is not a mesh axis "
                             f"(axes: {sorted(axis_names)})")
        if "dp" not in axis_names:
            raise ValueError("hierarchical reduction needs an in-slice "
                             "'dp' axis under dcn_axis "
                             f"{dcn_axis!r}")

    batch_axes = ((dcn_axis, "dp") if dcn_axis is not None
                  else ("dp" if "dp" in axis_names else None))
    data_spec = P(batch_axes, cfg.sp_axis if cfg.sp_axis else None)

    world = 1
    for _ax in mesh.axis_names:
        world *= int(mesh.shape[_ax])

    def _dedup_sq(tree, tree_specs):
        """Global squared L2 norm contribution of this shard: per-leaf
        local sum-of-squares divided by the leaf's replication factor
        (product of mesh axes NOT in its spec), so a psum over every
        axis counts each unique element exactly once."""
        def leaf_sq(x, s):
            d = 1
            have = spec_axes(s)
            for ax in mesh.axis_names:
                if ax not in have:
                    d *= int(mesh.shape[ax])
            return jnp.sum(jnp.square(x.astype(jnp.float32))) / d
        parts = jax.tree_util.tree_map(
            leaf_sq, tree, tree_specs, is_leaf=lambda x: isinstance(x, P))
        return sum(jax.tree_util.tree_leaves(parts))

    def _numerics_aux(g_for_norm, updates, params, nf_local, upd_specs):
        """In-graph numerics telemetry (docs/numerics.md): ONE small
        psum of a [3 + world] vector piggybacked on the step — global
        grad/update/param squared norms plus a per-device nonfinite
        vector (each shard deposits its LOCAL pre-reduction count at
        its linear mesh index, so the host alert can name the producing
        rank). ``upd_specs`` are the specs of the gradients and updates
        handed in: the parameters' own, or under the sharded update
        those with 'dp' in them, where a shard holds 1/dp of a leaf."""
        idx = jnp.int32(0)
        for ax in mesh.axis_names:
            idx = idx * int(mesh.shape[ax]) + lax.axis_index(ax)
        nf_vec = jnp.zeros((world,), jnp.float32).at[idx].set(
            nf_local.astype(jnp.float32))
        packed = jnp.concatenate([
            jnp.stack([_dedup_sq(g_for_norm, upd_specs),
                       _dedup_sq(updates, upd_specs),
                       _dedup_sq(params, specs)]), nf_vec])
        packed = lax.psum(packed, tuple(mesh.axis_names))
        return {
            "grad_norm": jnp.sqrt(packed[0]),
            "update_ratio": jnp.sqrt(packed[1])
            / jnp.maximum(jnp.sqrt(packed[2]), 1e-12),
            "nonfinite_by_rank": packed[3:],
        }

    def _per_shard_step(upd_specs, with_numerics=False):
        """``upd_specs``: the specs of what the optimizer updates, the
        parameters' own or ``zero.update_shard_specs``' (a leaf whose
        spec gained 'dp' is updated as this shard's 1/dp of it)."""
        def dp_shard(p, spec, upd_spec):
            d = shard_dim(spec, upd_spec)
            if d is None:
                return p
            n = p.shape[d] // int(mesh.shape["dp"])
            return lax.dynamic_slice_in_dim(
                p, lax.axis_index("dp") * n, n, axis=d)

        def dp_gather(p, spec, upd_spec):
            d = shard_dim(spec, upd_spec)
            if d is None:
                return p
            return lax.all_gather(p, "dp", axis=d, tiled=True)

        def per_leaf(fn, tree):
            return jax.tree_util.tree_map(
                fn, tree, specs, upd_specs,
                is_leaf=lambda x: isinstance(x, P))

        # The function's name is the compiled module's: traces find the
        # step by it (docs/tracing.md#names).
        def hvd_train_step(params, opt_state, tokens, targets):
            n_data = 1
            for ax in DATA_AXES:
                if ax in axis_names:
                    n_data *= mesh.shape[ax]
            if dcn_axis is not None:
                n_data *= mesh.shape[dcn_axis]

            def local_loss(p):
                loss = cfg.loss_fn(p, tokens, targets) / n_data
                # Mask to model-rank 0 so sum-over-shards counts each
                # data shard's loss exactly once (module docstring).
                for ax in MODEL_AXES:
                    if ax in axis_names:
                        loss = jnp.where(lax.axis_index(ax) == 0,
                                         loss, 0.0)
                return loss

            loss, grads = jax.value_and_grad(local_loss)(params)
            if with_numerics:
                # Count on the LOCAL, pre-reduction gradients — after
                # the psum a NaN has spread to every shard and the
                # producer is unidentifiable.
                nf_local = sum(
                    jnp.sum(~jnp.isfinite(g)) for g in
                    jax.tree_util.tree_leaves(grads))
            # Under the sharded weight update (module docstring) the
            # gradients come back as the 1/dp this shard updates, and
            # the moments arrive as that 1/dp.
            hier = ("dp", dcn_axis) if dcn_axis is not None else None
            grads = reduce_gradients(grads, specs, mesh,
                                     hierarchical=hier,
                                     dcn_wire=dcn_wire,
                                     shard_specs=upd_specs)
            # What the optimizer updates: the parameters, or under the
            # sharded update this shard's 1/dp of each.
            with jax.named_scope("hvd_optimizer"):
                p_upd = per_leaf(dp_shard, params)
                updates, opt_state = optimizer.update(
                    grads, opt_state, p_upd)
            aux = None
            if with_numerics:
                aux = _numerics_aux(grads, updates, params,
                                    nf_local, upd_specs)
            import optax
            with jax.named_scope("hvd_optimizer"):
                params = optax.apply_updates(p_upd, updates)
            # The second half of what was the all-reduce: every shard's
            # new 1/dp of a leaf, gathered into the new parameter.
            with jax.named_scope("hvd_grad_reduce"):
                params = per_leaf(dp_gather, params)
            # Reported loss: global mean (sum of masked, scaled shards).
            loss = lax.psum(loss, tuple(mesh.axis_names))
            if with_numerics:
                return params, opt_state, loss, aux
            return params, opt_state, loss

        return hvd_train_step

    def make(params, opt_state):
        dp = int(mesh.shape["dp"]) if "dp" in axis_names else 1
        # The sharded weight update wherever it is the same arithmetic
        # (docstring): moments laid out as 1/dp shards.
        upd_specs = specs
        if (dp > 1 and dcn_axis is None
                and is_elementwise(optimizer, params, opt_state)):
            upd_specs = update_shard_specs(params, specs, dp)
        opt_specs = state_specs_by_structure(opt_state, params, upd_specs)
        from ..observability import numerics as _numerics
        numerics_on = _numerics.enabled()
        out_specs = (specs, opt_specs, P())
        if numerics_on:
            # Aux leaves are psum'ed over every axis inside the step —
            # replicated outputs, so plain P() specs.
            out_specs = out_specs + ({"grad_norm": P(),
                                      "update_ratio": P(),
                                      "nonfinite_by_rank": P()},)
        # Params and optimizer state are donated: the step returns their
        # successors, and without aliasing the program holds two copies
        # of both (at 1.08B width ~22 GB against a v5e's 16 GB of HBM).
        # Callers rebind — an input array is dead after the call.
        step = jax.jit(jax.shard_map(
            _per_shard_step(upd_specs, with_numerics=numerics_on),
            mesh=mesh,
            in_specs=(specs, opt_specs, data_spec, data_spec),
            out_specs=out_specs,
            check_vma=False), donate_argnums=(0, 1))
        if numerics_on:
            step = _wrap_numerics_step(step)
        return step, opt_specs

    def shard_params(params):
        return _put_tree(params, specs, mesh)

    def shard_batch(batch):
        return jax.device_put(batch, NamedSharding(mesh, data_spec))

    return make, shard_params, shard_batch


def _wrap_numerics_step(inner):
    """Host-side shell of the numerics aux channel (docs/numerics.md):
    keeps the public ``(params, opt_state, loss)`` contract while
    feeding the deferred :class:`~horovod_tpu.observability.numerics
    .StepStats` sink (step N's device scalars materialize while step
    N+1 runs — no added host sync), running the periodic cross-rank
    fingerprint probe, and honoring an armed ``bitflip_param`` fault
    clause."""
    from ..observability import numerics as _numerics
    counter = itertools.count()

    def step(params, opt_state, tokens, targets):
        i = next(counter)
        params = _numerics.maybe_bitflip(params, i)
        params, opt_state, loss, aux = inner(params, opt_state,
                                             tokens, targets)
        _numerics.step_stats().note(i, loss, aux)
        _numerics.maybe_send_fingerprint(params, i)
        return params, opt_state, loss

    return step


def _put_tree(tree, specs, mesh: Mesh):
    flat_t, treedef = jax.tree_util.tree_flatten(tree)
    flat_s = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    out = [jax.device_put(x, NamedSharding(mesh, s))
           for x, s in zip(flat_t, flat_s)]
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------
# Pipeline-parallel training step — cuts the flagship transformer into
# stage_fns over 'pp' automatically (docs/pipeline.md, docs/autotune.md).
# --------------------------------------------------------------------------

_DENSE_LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "wi", "wo_mlp")


def _check_layout(cfg, layout: str, what: str):
    """Refuse ``what`` for a model whose configuration does not list
    ``layout`` among its ``layouts``."""
    if layout not in cfg.layouts:
        raise ValueError(
            f"{what} is not built for {type(cfg).__name__}: it trains "
            f"under {list(cfg.layouts)} (docs/parallelism.md)")


def _check_pipeline_cfg(cfg: tfm.TransformerConfig, mesh: Mesh,
                        num_virtual: int) -> int:
    _check_layout(cfg, "pp", "the pipeline train step")
    if "pp" not in mesh.axis_names:
        raise ValueError("build_pipeline_train_step needs a 'pp' mesh "
                         f"axis (axes: {sorted(mesh.axis_names)})")
    for ax, name in ((cfg.tp_axis, "tp"), (cfg.sp_axis, "sp"),
                     (cfg.ep_axis, "ep")):
        if ax:
            raise ValueError(
                f"pipeline train step does not compose with {name} "
                "parallelism yet; build the config with "
                f"{name}_axis=None")
    if cfg.num_experts:
        raise ValueError("pipeline train step supports dense layers "
                         "only (num_experts=0): MoE layer dicts are not "
                         "homogeneous across the stage stack")
    n = int(mesh.shape["pp"])
    extra = [a for a in mesh.axis_names
             if a != "pp" and int(mesh.shape[a]) > 1]
    if extra:
        raise ValueError("pipeline train step shards over 'pp' only; "
                         f"fold or drop mesh axes {extra}")
    if cfg.n_layers % (n * num_virtual):
        raise ValueError(
            f"n_layers ({cfg.n_layers}) must divide evenly into "
            f"pp ({n}) x num_virtual ({num_virtual}) stage chunks")
    return n


def to_pipeline_params(cfg: tfm.TransformerConfig, params, num_stages: int,
                       num_virtual: int = 1):
    """Re-pack ``init_params`` layout into the pipeline layout:
    ``{"embed", "pos", "ln_f", "stages"}`` where each stages leaf is
    ``[n_pp, V, layers_per_chunk, ...]`` — slot ``[r, v]`` holds
    chunk-stage ``v·n + r``'s layers in order (the interleaved
    chunk-stage convention; V=1 collapses to contiguous stages)."""
    nV = num_stages * num_virtual
    lpc = cfg.n_layers // nV
    layers = params["layers"]
    chunks = [jax.tree_util.tree_map(
                  lambda *ls: jnp.stack(ls), *layers[c * lpc:(c + 1) * lpc])
              for c in range(nV)]
    stages = jax.tree_util.tree_map(
        lambda *cs: jnp.stack(cs).reshape(
            (num_virtual, num_stages) + cs[0].shape).swapaxes(0, 1),
        *chunks)
    return {"embed": params["embed"], "pos": params["pos"],
            "ln_f": params["ln_f"], "stages": stages}


def from_pipeline_params(cfg: tfm.TransformerConfig, pparams,
                         num_stages: int, num_virtual: int = 1):
    """Inverse of :func:`to_pipeline_params` (checkpoint interop)."""
    nV = num_stages * num_virtual
    lpc = cfg.n_layers // nV
    flat = jax.tree_util.tree_map(
        lambda l: l.swapaxes(0, 1).reshape((nV * lpc,) + l.shape[3:]),
        pparams["stages"])
    layers = [jax.tree_util.tree_map(lambda l: l[i], flat)
              for i in range(nV * lpc)]
    return {"embed": pparams["embed"], "pos": pparams["pos"],
            "ln_f": pparams["ln_f"], "layers": layers}


def pipeline_param_specs(cfg: tfm.TransformerConfig):
    """PartitionSpecs for the pipeline layout: stage stacks shard their
    leading n_pp axis over 'pp'; embed/pos/ln_f replicate (they are the
    loss head + embedding, applied on every rank)."""
    stage_spec = {k: P("pp") for k in _DENSE_LAYER_KEYS}
    return {"embed": P(), "pos": P(), "ln_f": P(), "stages": stage_spec}


def build_pipeline_train_step(cfg: tfm.TransformerConfig, mesh: Mesh,
                              optimizer, *, schedule: str = "1f1b",
                              num_virtual: int = 1,
                              cost_backward: float = 2.0):
    """Returns ``(make, shard_params, shard_batch)`` for a
    pipeline-parallel train step over a 'pp' mesh.

    ``step(params, opt_state, tokens_mb, targets_mb) ->
    (params, opt_state, loss)`` (``params``/``opt_state`` donated, as in
    :func:`build_train_step`) where ``tokens_mb``/``targets_mb`` are
    ``[num_micro, micro_batch, S]`` int32 (replicated — 'pp' shards
    layers, not data) and ``params`` is the
    :func:`to_pipeline_params` layout. The flagship transformer is cut
    automatically: every rank's stage_fn scans its
    ``n_layers / (pp · V)`` decoder blocks, the embedding runs
    replicated on every rank with its gradient recovered from the
    pipeline's stage-0 input grads, and the final layernorm + tied
    softmax head ride the schedule's ``loss_params`` channel. The
    microbatch count is whatever leading axis the batch carries — the
    autotuner varies it (and ``schedule``) per trial by rebuilding this
    step (docs/autotune.md)."""
    from ..models.transformer import (_layernorm, _project_logits,
                                      remat_block)
    from .pipeline import pipeline_value_and_grad

    n = _check_pipeline_cfg(cfg, mesh, num_virtual)
    interleaved = schedule == "interleaved"
    if interleaved and num_virtual < 2:
        raise ValueError("interleaved needs num_virtual >= 2")
    if not interleaved and num_virtual != 1:
        raise ValueError(f"schedule {schedule!r} uses num_virtual=1")
    specs = pipeline_param_specs(cfg)
    dt = cfg.dtype

    block = remat_block(cfg)

    def stage_fn(p, x):
        def body(h, layer):
            return block(layer, h, cfg, 0), None
        h, _ = lax.scan(body, x, p)
        return h

    def embed_all(ep, tokens_mb):
        s = tokens_mb.shape[-1]
        pos = ep["pos"][jnp.arange(s)]
        return (ep["embed"].astype(dt)[tokens_mb]
                + pos.astype(dt)[None, None])

    def head_loss(lp, y, targets):
        h = _layernorm(y, lp["ln_f"])
        logits = _project_logits({"embed": lp["embed"]}, h, cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -ll.mean()

    def hvd_pipeline_train_step(params, opt_state, tokens_mb, targets_mb):
        ep = {"embed": params["embed"], "pos": params["pos"]}
        x_mb, emb_vjp = jax.vjp(lambda e: embed_all(e, tokens_mb), ep)
        lp = {"ln_f": params["ln_f"], "embed": params["embed"]}
        # Local stage stack [1, V, lpc, ...] -> the engine's view.
        p_stage = jax.tree_util.tree_map(lambda l: l[0],
                                         params["stages"])
        if not interleaved:
            p_stage = jax.tree_util.tree_map(lambda l: l[0], p_stage)
        loss, g_stage, extras = pipeline_value_and_grad(
            stage_fn, head_loss, p_stage, x_mb, axis_name="pp",
            schedule=schedule, num_virtual=num_virtual,
            cost_backward=cost_backward, loss_aux=targets_mb,
            loss_params=lp, return_input_grads=True)
        (d_ep,) = emb_vjp(extras["input_grads"])
        lp_g = extras["loss_params_grads"]
        if not interleaved:
            g_stage = jax.tree_util.tree_map(lambda l: l[None], g_stage)
        grads = {
            # Tied embedding: input-path pullback + softmax-head path.
            "embed": d_ep["embed"] + lp_g["embed"],
            "pos": d_ep["pos"],
            "ln_f": lp_g["ln_f"],
            "stages": jax.tree_util.tree_map(lambda l: l[None], g_stage),
        }
        import optax
        with jax.named_scope("hvd_optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def make(params, opt_state):
        opt_specs = state_specs_by_structure(opt_state, params, specs)
        data_spec = P()
        step = jax.jit(jax.shard_map(
            hvd_pipeline_train_step, mesh=mesh,
            in_specs=(specs, opt_specs, data_spec, data_spec),
            out_specs=(specs, opt_specs, P()),
            check_vma=False), donate_argnums=(0, 1))
        return step, opt_specs

    def shard_params(params):
        return _put_tree(params, specs, mesh)

    def shard_batch(batch):
        return jax.device_put(batch, NamedSharding(mesh, P()))

    return make, shard_params, shard_batch
