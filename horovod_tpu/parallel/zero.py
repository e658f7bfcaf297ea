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

What ``build_train_step`` does WITHOUT a ``Zero1State`` (the default
since PR 32, arXiv:2004.13336). On a mesh whose 'dp' axis is larger than
1, with an optimizer that :func:`is_elementwise`, a plain optax state is
sharded too, in optax's own structure and with no flat vectors: every
moment leaf carries 'dp' on one dimension of the parameter's own shape
(:func:`update_shard_specs`: the last that divides), the gradient is
reduce-scattered on that dimension, the chip updates its 1/dp of the
parameter and the NEW PARAMETER is all-gathered (here the updates are
gathered and ``apply_updates`` then runs over the whole tree on every
chip). A
``Zero1State`` remains for callers who built one: it keeps the flat
padded layout and the path above, and is the only form that shards a
leaf no dimension of which divides by dp.
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


def update_shard_specs(params, param_specs, n_shards: int,
                       axis: str = "dp"):
    """The spec of each parameter's 1/``n_shards`` under the sharded
    weight update (the layout of its moments, and of its gradient and
    parameter shard inside the step): the parameter's own spec with
    ``axis`` on the LAST dimension that no mesh axis shards and whose
    size divides by ``n_shards``; nothing is raveled or padded. Decided
    from the leaf's shape and spec alone. The last and not the leading
    dimension because of what XLA:TPU makes of each on a v5e 2x2
    (PERF.md section 6, PR 32): a reduce-scatter on the minor dimension
    stays the native collective, one on the leading dimension is
    rewritten into an all-reduce over the leaf padded by 1/64, a slice,
    and a halo exchange to move the shard edges back, which cost more
    than the sharded update saves. A leaf with no such dimension (an
    odd-sized vector, a scalar), or one whose spec already uses
    ``axis``, keeps its own spec: it is reduced with a psum and updated
    whole on every shard."""
    def one(p, spec):
        entries = tuple(spec) + (None,) * (len(p.shape) - len(spec))
        if axis in _spec_axes_ordered(spec):
            return spec
        for d in reversed(range(len(p.shape))):
            if (entries[d] is None and p.shape[d]
                    and p.shape[d] % n_shards == 0):
                return P(*entries[:d], axis, *entries[d + 1:])
        return spec

    return jax.tree_util.tree_map(one, params, param_specs)


def shard_dim(spec, shard_spec, axis: str = "dp"):
    """The dimension on which :func:`update_shard_specs` put ``axis``
    into a leaf's spec, or None where it left the spec alone."""
    if axis in _spec_axes_ordered(spec):
        return None
    for d, entry in enumerate(shard_spec):
        if entry == axis:
            return d
    return None


# Primitives that compute each output element from the operands' elements
# at the same index (a scalar operand counts as broadcast).
_ELEMENTWISE = frozenset((
    "abs", "add", "and", "cbrt", "ceil", "clamp", "convert_element_type",
    "copy", "cos", "div", "eq", "erf", "exp", "exp2", "expm1", "floor",
    "ge", "gt", "integer_pow", "is_finite", "le", "log", "log1p",
    "logistic", "lt", "max", "min", "mul", "ne", "neg", "nextafter", "not",
    "or", "pow", "rem", "round", "rsqrt", "select_n", "sign", "sin", "sqrt",
    "square", "sub", "tanh", "xor"))
# Primitives that only wrap jaxprs which run on the same operands.
_CALLS = frozenset(("jit", "pjit", "closed_call", "custom_jvp_call",
                    "custom_vjp_call", "checkpoint", "cond"))


def sub_jaxprs(eqn):
    """The jaxprs among an equation's parameters (a call's body, a
    ``cond``'s branches)."""
    found = []
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            item = getattr(item, "jaxpr", item)
            if hasattr(item, "eqns"):
                found.append(item)
    return found


def _jaxpr_is_elementwise(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        shapes = [tuple(v.aval.shape) for v in eqn.invars]
        out_shapes = [tuple(v.aval.shape) for v in eqn.outvars]
        if not any(shapes + out_shapes):
            continue                        # scalars in, scalars out
        name = eqn.primitive.name
        if name in _CALLS:
            subs = sub_jaxprs(eqn)
            if not subs or not all(map(_jaxpr_is_elementwise, subs)):
                return False
        elif name == "broadcast_in_dim":
            if shapes[0]:
                return False
        elif name not in _ELEMENTWISE or any(
                s and s != out_shapes[0] for s in shapes):
            return False
    return True


def is_elementwise(optimizer: optax.GradientTransformation, params,
                   opt_state) -> bool:
    """Whether ``optimizer.update`` computes every element of every
    update and of every new moment from that element's gradient,
    parameter and moments alone (and scalars such as the step count):
    then the update of a shard is the shard of the update, and the
    weight update may be sharded. Decided from the optimizer itself:
    its ``update`` is traced on the shapes of ``params`` and
    ``opt_state`` (nothing runs), and every equation that touches a
    non-scalar must be an elementwise primitive over operands of one
    shape. AdamW, Adam, SGD with momentum, RMSProp, Lion, value clipping
    and ``MultiSteps`` pass; a norm, a trust ratio, a factored moment, a
    finite-guard or a random draw (``clip_by_global_norm``, LAMB, LARS,
    Adafactor, ``apply_if_finite``, ``add_noise``) does not, nor does
    any primitive this list does not know. (A numerical probe on a
    random vector and its halves would pass a clip whose threshold the
    probe's norm does not reach; the trace does not depend on values.)"""
    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    closed = jax.make_jaxpr(optimizer.update)(
        shapes(params), shapes(opt_state), shapes(params))
    return _jaxpr_is_elementwise(closed.jaxpr)


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
