"""ZeRO-1 optimizer-state sharding over the data axis.

Motivated by measurement (round 5, docs/benchmarks.md "1B
follow-through"): at
1B params the binding constraint on a chip is optimizer-state memory —
fp32 AdamW moments are 2 x 4.1 GB of a 15.75 GB HBM, forcing
rematerialization that costs ~5-9 MFU points. The reference has no
analogue (its data parallelism replicates optimizer state per rank,
torch/__init__.py:42-151); this is the standard modern extension
(ZeRO stage 1) expressed TPU-natively: moments live sharded over
'dp' (stacked with the parameter's own model axes), gradients arrive
via ``psum_scatter`` (reduce+shard in one collective, riding ICI),
each rank updates only its 1/N shard, and the parameter updates
return by ``all_gather``.

Layout. Every moment leaf is a FLAT vector. For a parameter whose
spec uses model axes with combined size m (tp/ep blocks), the global
state leaf has length ``m * padded_local`` where ``padded_local`` is
the parameter's per-model-shard element count padded to a multiple of
dp, and it is sharded ``P((model_axes..., 'dp'))`` — each model shard
owns one contiguous ``padded_local`` block, split contiguously over
dp, which is exactly the block order ``psum_scatter(tiled=True)``
produces inside that model shard. Per-device the leaf is the
``[padded_local/dp]`` shard ``zero1_update`` works on. Values never
need to correspond ACROSS model shards, only within one, so the
flattening of a tp block vs the full matrix never matters.

Constraints: parameter specs must not already use the dp axis (this
framework's layouts never do), and the inner transformation must be
elementwise per parameter with a value-independent ``init``
(Adam/AdamW/SGD/momentum/rmsprop qualify — their init is
zeros/ones_like; global-norm clipping must be composed OUTSIDE the
wrapper since it needs the full gradient).

Use (see parallel/train.py::build_train_step, which wires this in
automatically when handed a Zero1State):

    state = zero1_init(opt, params, n_shards=dp,
                       param_specs=specs, mesh=mesh)
    step, _ = make(params, state)      # build_train_step's make
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import optax


class Zero1State(NamedTuple):
    inner: Any          # inner optimizer state over flat sharded leaves
    # Shard count the state was built for (zero1_init's n_shards).
    # Recorded so build_train_step.make() can reject a state whose
    # padding/layout disagrees with the mesh's 'dp' size with a clear
    # error instead of an opaque jit sharding failure. A pytree LEAF
    # (NamedTuple fields always are), so it travels through jit as a
    # replicated scalar; None only for hand-built legacy states.
    n_shards: Any = None


def _spec_axes_ordered(spec):
    out = []
    if isinstance(spec, P):
        for entry in spec:
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                out.extend(entry)
            else:
                out.append(entry)
    return out


def _padded_size(n_elem: int, n_shards: int) -> int:
    return ((n_elem + n_shards - 1) // n_shards) * n_shards


def _model_factor(spec, mesh: Mesh) -> int:
    m = 1
    for ax in _spec_axes_ordered(spec):
        m *= int(mesh.shape[ax])
    return m


def _flat_pad(x, n_shards: int):
    flat = jnp.ravel(x)
    pad = _padded_size(flat.size, n_shards) - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def state_specs_by_structure(opt_state, params, param_like_specs):
    """Spec tree for an optax state by STRUCTURE: subtrees sharing the
    params' treedef (optax moment subtrees — mu/nu/trace) get
    ``param_like_specs`` wholesale; any other leaf (counts, scalars)
    replicates. Shared by build_train_step's replicated path and
    zero1_state_specs so the subtle matching rule lives once."""
    ptreedef = jax.tree_util.tree_structure(params)

    def is_param_like(x):
        try:
            return jax.tree_util.tree_structure(x) == ptreedef
        except Exception:
            return False

    return jax.tree_util.tree_map(
        lambda x: param_like_specs if is_param_like(x) else P(),
        opt_state, is_leaf=is_param_like)


def zero1_init(inner: optax.GradientTransformation, params,
               n_shards: int, param_specs=None,
               mesh: Mesh | None = None) -> Zero1State:
    """Host-side init. Builds the inner state over flat vectors shaped
    [m * padded_local] per parameter (see module docstring); requires
    the inner init to be value-independent (zeros/ones_like)."""
    if (param_specs is None) != (mesh is None):
        raise ValueError(
            "zero1_init needs BOTH param_specs and mesh to size "
            "model-sharded moments (or neither, for fully replicated "
            "parameters) — got only one of them")

    def flat_zero(p, spec):
        m = _model_factor(spec, mesh) if mesh is not None else 1
        assert p.size % m == 0, (p.shape, spec)
        local = p.size // m
        return jnp.zeros((m * _padded_size(local, n_shards),), p.dtype)

    if param_specs is None:
        flat_params = jax.tree_util.tree_map(
            lambda p: flat_zero(p, P()), params)
    else:
        flat_params = jax.tree_util.tree_map(
            flat_zero, params, param_specs,
            is_leaf=lambda x: isinstance(x, P))
        # tree_map over (params, specs) keys off params' structure; the
        # result has params' treedef, which is what optax init expects.
    return Zero1State(inner=inner.init(flat_params),
                      n_shards=int(n_shards))


def zero1_state_specs(state: Zero1State, params, param_specs,
                      mesh: Mesh, axis: str = "dp"):
    """PartitionSpec tree for the wrapper state: each moment subtree
    (params' treedef — the optax convention) gets, per parameter, the
    flat-leaf spec ``P((param's model axes..., axis))``; anything else
    (count scalars) replicates."""
    ptreedef = jax.tree_util.tree_structure(params)
    spec_leaves = [
        P(tuple(_spec_axes_ordered(s)) + (axis,))
        for s in jax.tree_util.tree_flatten(
            param_specs, is_leaf=lambda x: isinstance(x, P))[0]]
    per_param_specs = jax.tree_util.tree_unflatten(ptreedef, spec_leaves)
    # n_shards mirrors the state's structure: a replicated scalar spec
    # when recorded, None (empty subtree) for legacy states — the spec
    # tree must stay a structural match for shard_map's in/out_specs.
    return Zero1State(
        inner=state_specs_by_structure(state.inner, params,
                                       per_param_specs),
        n_shards=None if state.n_shards is None else P())


def zero1_update(inner: optax.GradientTransformation, grads,
                 state: Zero1State, params, axis: str = "dp"):
    """Per-shard update (call INSIDE shard_map, with ``grads`` already
    reduced over every mesh axis except ``axis`` — the psum_scatter
    here performs the ``axis`` reduction). ``grads``/``params`` are the
    per-shard (model-local) views. Returns ``(updates, new_state)``
    with updates in the per-shard param shapes."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)

    def to_shard(g):
        # Sum across data shards AND shard the result, one collective.
        return lax.psum_scatter(_flat_pad(g, n), axis, tiled=True)

    def param_shard(p):
        flat = _flat_pad(p, n)
        shard = flat.size // n
        return lax.dynamic_slice(flat, (idx * shard,), (shard,))

    with jax.named_scope("hvd_grad_reduce"):
        g_shards = jax.tree_util.tree_map(to_shard, grads)
    p_shards = jax.tree_util.tree_map(param_shard, params)
    upd_shards, new_inner = inner.update(g_shards, state.inner, p_shards)

    def to_full(u, p):
        full = lax.all_gather(u, axis, tiled=True)
        return full[: p.size].reshape(p.shape).astype(p.dtype)

    updates = jax.tree_util.tree_map(to_full, upd_shards, params)
    return updates, Zero1State(inner=new_inner, n_shards=state.n_shards)
