"""The sharded weight update's rule (arXiv:2004.13336; what the ZeRO
papers call stage 1): which dimension of each parameter leaf takes 'dp',
and which optimizers allow it.

An all-reduce is a reduce-scatter followed by an all-gather, and every
data shard would run the same update on the same reduced gradient. So on
a mesh whose 'dp' axis is larger than 1, ``build_train_step``
(parallel/train.py) reduce-scatters each gradient leaf on ONE dimension
of the leaf's own shape, updates that 1/dp of the parameter with 1/dp of
its moments, and all-gathers the new parameter: nothing is raveled or
padded, and the optimizer state keeps optax's own structure with 'dp' in
the spec of every moment leaf. The reference has no analogue (its data
parallelism replicates optimizer state per rank,
torch/__init__.py:42-151).

This module holds what decides it, from what the code can observe:
:func:`update_shard_specs` and :func:`shard_dim` (the leaf's shape and
spec: the LAST dimension that divides by dp, and why the last),
:func:`is_elementwise` (the optimizer's traced ``update``: only where
the update of a shard is the shard of the update) and
:func:`state_specs_by_structure` (the optimizer state's specs). A leaf
no dimension of which divides by dp keeps the psum and the whole update
on every shard.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

import optax


def spec_axes(spec):
    """The mesh axes a PartitionSpec names, in order."""
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


def state_specs_by_structure(opt_state, params, param_like_specs):
    """Spec tree for an optax state by STRUCTURE: subtrees sharing the
    params' treedef (optax moment subtrees — mu/nu/trace) get
    ``param_like_specs`` wholesale; any other leaf (counts, scalars)
    replicates. Matching by shape would be ambiguous: wq and wo share
    shapes with transposed specs."""
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
        if axis in spec_axes(spec):
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
    if axis in spec_axes(spec):
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
