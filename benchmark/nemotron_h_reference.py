"""The plain reference of the ``nemotron_h`` hybrid decoder: forward
pass and loss in straightforward ``jax.numpy`` and float32, independent
of the code under test — it imports nothing of the program. No kernel,
no chunked scan, no remat, no bfloat16, no sorted or grouped matmul;
matmuls at "highest" precision (on a TPU a float32 matmul otherwise
runs in bfloat16 passes).

Every layer is ``x <- x + f(RMSNorm(x))``, ``f`` by the character of
``hybrid_override_pattern``:

- ``M``, Mamba-2 mixer. ``[z | xBC | dt] = u W_in``; ``xBC`` through a
  causal depthwise convolution (the sum over its taps of the shifted
  input), bias and SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head
  the recurrence AS WRITTEN, one step at a time (``lax.scan`` over the
  positions): ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = S_t C_t + D x_t``; then ``RMSNorm`` over each of ``n_groups``
  slices of ``y * silu(z)``, and ``W_out``.
- ``*``, causal attention: query heads in groups over the key/value
  heads (no broadcast copy), no position term, scale ``head_dim^-1/2``;
  in blocks of query positions against the whole context.
- ``E``, latent expert layer, one chip's share: ``s = sigmoid(u W_r)``
  over ALL experts; the ``num_experts_per_tok`` largest of ``s +
  b_corr`` are chosen (a stable descending sort: the lower id wins a
  tie); weights are the chosen ``s`` over their sum, times
  ``routed_scaling_factor``; in the latent space ``h = u W_down`` every
  expert held here (``experts_held``) is computed for every token, under
  a dense mask of those weights; then ``W_up``, plus the shared expert
  on the full width. What the absent experts would add is left out.

It reads the program's parameter tree (``embed``, ``head``, ``norm_f``,
``layers[i]``) because the check is made on the program's own weights,
and the configuration file's keys. One sequence at a time, the loss in
chunks of positions, so that it fits at 8192 tokens.

``loss`` is differentiable (``jax.grad`` of it is the reference's
gradient: the CPU tests use it). At the published widths that gradient
does not fit beside the parameters, so ``loss_and_grads`` walks the
same layer functions one layer at a time (``jax.vjp`` of each, from
the head down), and hands each layer's gradient out before the next is
made. ``TOLERANCES`` are the limits of the comparison that decides
``correct`` (``kinds/train_nemotron_h.py``), each between two readings
taken on the chip (PERF.md section 6, PR 31)."""

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import reference as dense_reference

Dims = collections.namedtuple(
    "Dims", "pattern eps heads head_dim groups state q_heads kv_heads "
            "attn_dim held top_k scaling")


def dims(config):
    """What the layers need of a configuration file, hashable."""
    return Dims(
        pattern=config["hybrid_override_pattern"],
        eps=float(config["layer_norm_epsilon"]),
        heads=config["mamba_num_heads"], head_dim=config["mamba_head_dim"],
        groups=config["n_groups"], state=config["ssm_state_size"],
        q_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        attn_dim=config["head_dim"],
        held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"],
        scaling=float(config["routed_scaling_factor"]))


def _rmsnorm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def mamba_layer(layer, x, d):
    """``x`` ``[S, hidden]`` through one Mamba-2 mixer layer."""
    seq = x.shape[0]
    h, p, g, n = d.heads, d.head_dim, d.groups, d.state
    inner = h * p
    u = _rmsnorm(x, layer["norm"], d.eps)
    zxbcdt = u @ layer["in_proj"]
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:inner + inner + 2 * g * n]
    dt = zxbcdt[:, inner + inner + 2 * g * n:]
    # causal depthwise convolution as its definition reads: K - 1 zeros
    # in front, one filter a channel, the filter's last tap on the
    # current position (XLA:TPU cannot compile the filter gradient of a
    # 10240-group lax.conv_general_dilated: PERF.md, PR 31)
    k = layer["conv_w"].shape[1]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(padded[j:j + seq] * layer["conv_w"][:, j] for j in range(k))
    xbc = jax.nn.silu(conv + layer["conv_b"])
    xs = xbc[:, :inner].reshape(seq, h, p)
    bm = jnp.repeat(xbc[:, inner:inner + g * n].reshape(seq, g, n),
                    h // g, axis=1)                     # [S, H, N]
    cm = jnp.repeat(xbc[:, inner + g * n:].reshape(seq, g, n),
                    h // g, axis=1)
    dt = jax.nn.softplus(dt + layer["dt_bias"])         # [S, H]
    a = -jnp.exp(layer["A_log"])

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, (state * c_t[:, None, :]).sum(-1)

    # One step at a time. The steps go in blocks under jax.checkpoint
    # only so that a derivative of this holds one state a block and one
    # block's states, not one a step (4096 x 4 MB at the published
    # widths); the values are the plain scan's.
    blk = dense_reference._block_size(seq, 64)
    _, y = lax.scan(
        jax.checkpoint(lambda state, inp: lax.scan(step, state, inp)),
        jnp.zeros((h, p, n), jnp.float32),
        tuple(t.reshape((seq // blk, blk) + t.shape[1:])
              for t in (xs, dt, bm, cm)))
    y = y.reshape(seq, h, p) + layer["D"][:, None] * xs
    y = y.reshape(seq, inner) * jax.nn.silu(z)
    y = _rmsnorm(y.reshape(seq, g, inner // g),
                 layer["gate_norm"].reshape(g, inner // g), d.eps)
    return x + y.reshape(seq, inner) @ layer["out_proj"]


def attention_layer(layer, x, d, q_block):
    seq = x.shape[0]
    hd, kv, rep = d.attn_dim, d.kv_heads, d.q_heads // d.kv_heads
    u = _rmsnorm(x, layer["norm"], d.eps)
    q = (u @ layer["wq"]).reshape(seq, kv, rep, hd)
    k = (u @ layer["wk"]).reshape(seq, kv, hd)
    v = (u @ layer["wv"]).reshape(seq, kv, hd)
    k_pos = jnp.arange(seq)

    @jax.checkpoint          # a derivative holds no block's scores
    def attend(i):
        qs = lax.dynamic_slice_in_dim(q, i * q_block, q_block, axis=0)
        scores = jnp.einsum("qgrd,kgd->grqk", qs, k) / math.sqrt(hd)
        q_pos = i * q_block + jnp.arange(q_block)
        scores = jnp.where(q_pos[:, None] >= k_pos[None, :], scores,
                           -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, -1), v)

    out = lax.map(attend, jnp.arange(seq // q_block))   # [nb, qb, kv, rep, hd]
    return x + out.reshape(seq, kv * rep * hd) @ layer["wo"]


def moe_layer(layer, x, d, given=None):
    """Returns ``(x, top)``: ``top`` ``[S, top_k]``, the experts each
    token chooses here. With ``given`` (such an array) the layer is
    computed under THOSE choices instead, and ``top`` still says what
    this routing would have chosen."""
    u = _rmsnorm(x, layer["norm"], d.eps)
    scores = jax.nn.sigmoid(u @ layer["router"])            # [S, experts]
    order = jnp.argsort(-(scores + layer["b_corr"]), axis=-1, stable=True)
    own = order[:, :d.top_k]
    top = own if given is None else given
    picked = jnp.take_along_axis(scores, top, axis=-1)
    picked = picked / picked.sum(-1, keepdims=True) * d.scaling
    h = u @ layer["down"]
    # every held expert on every token, and a dense mask of weights
    held = jnp.asarray(d.held)
    weight = jnp.where(top[:, :, None] == held, picked[:, :, None],
                       0.0).sum(1)                          # [S, held]
    y = jnp.einsum("sef,efl->sel",
                   _relu2(jnp.einsum("sl,elf->sef", h, layer["w1"])),
                   layer["w2"])
    routed = (weight[:, :, None] * y).sum(1)
    shared = _relu2(u @ layer["shared_in"]) @ layer["shared_out"]
    return x + routed @ layer["up"] + shared, own


def pairs(top, held):
    """``[..., held, S]`` bool of choices ``top`` ``[..., S, top_k]``:
    which (token, held expert) pairs they make."""
    top = jnp.asarray(top)
    return jnp.stack([(top == e).any(-1) for e in held], axis=-2)


def _nll_sum(head, norm_f, x, targets, d, chunk):
    h = _rmsnorm(x, norm_f, d.eps)

    def chunk_nll(c):
        hs = lax.dynamic_slice_in_dim(h, c * chunk, chunk, axis=0)
        tg = lax.dynamic_slice_in_dim(targets, c * chunk, chunk, axis=0)
        logits = hs @ head.T
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tg[:, None], axis=-1)[:, 0]
        return (lse - picked).sum()

    return lax.map(chunk_nll, jnp.arange(x.shape[0] // chunk)).sum()


def _layer(kind, layer, x, d, q_block, given=None):
    """``(x, top)`` after one layer of ``kind``; ``top`` (and ``given``:
    ``moe_layer``) is None but for an expert layer."""
    if kind == "M":
        return mamba_layer(layer, x, d), None
    if kind == "*":
        return attention_layer(layer, x, d, q_block), None
    return moe_layer(layer, x, d, given)


@functools.partial(jax.jit, static_argnames=("d", "q_block", "chunk"))
def sequence_nll(params, tokens, targets, *, d, q_block, chunk):
    """``(summed next-token loss, top)`` of ONE sequence (``tokens``,
    ``targets`` ``[S]``): differentiable in ``params``; ``top``
    ``[expert layers, S, top_k]``."""
    x = params["embed"][tokens]
    chosen = []
    for kind, layer in zip(d.pattern, params["layers"]):
        x, c = _layer(kind, layer, x, d, q_block)
        if c is not None:
            chosen.append(c)
    nll = _nll_sum(params["head"], params["norm_f"], x, targets, d, chunk)
    return nll, jnp.stack(chosen) if chosen else None


def loss(params, tokens, targets, config, *, q_block=512, chunk=512):
    """Mean next-token cross-entropy of ``tokens`` ``[B, S]`` under
    ``params`` (float32), as a traced scalar (``jax.grad`` of it is the
    reference's gradient), with every expert layer's choices
    ``[expert layers, B * S, top_k]``."""
    d = dims(config)
    batch, seq = tokens.shape
    qb, ch = (dense_reference._block_size(seq, q_block),
              dense_reference._block_size(seq, chunk))
    total, chosen = 0.0, []
    with jax.default_matmul_precision("highest"):
        for b in range(batch):
            nll, c = sequence_nll(params, tokens[b], targets[b], d=d,
                                  q_block=qb, chunk=ch)
            total = total + nll
            chosen.append(c)
    if chosen[0] is not None:
        chosen = jnp.concatenate(chosen, axis=1)
    return total / (batch * seq), chosen


@functools.partial(jax.jit, static_argnames=("kind", "d", "q_block"))
def _layer_forward(layer, x, given, *, kind, d, q_block):
    return _layer(kind, layer, x, d, q_block, given)


@functools.partial(jax.jit, static_argnames=("kind", "d", "q_block"))
def _layer_vjp(layer, x, given, dy, *, kind, d, q_block):
    """``(d layer, d x)`` of one layer under the cotangent ``dy``."""
    _, pull = jax.vjp(
        lambda l, xx: _layer(kind, l, xx, d, q_block, given)[0], layer, x)
    return pull(dy)


@functools.partial(jax.jit, static_argnames=("d", "chunk"))
def _head_vjp(head, norm_f, x, targets, scale, *, d, chunk):
    """The summed loss of one sequence, and ``scale`` times its
    derivative in ``(head, norm_f, x)``."""
    nll, pull = jax.vjp(
        lambda hd, nf, xx: _nll_sum(hd, nf, xx, targets, d, chunk),
        head, norm_f, x)
    return nll, pull(scale)


def _sum_trees(trees):
    return functools.reduce(
        lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), trees)


def loss_and_grads(params, tokens, targets, config, routing=None, *,
                   q_block=512, chunk=512):
    """``loss`` and its gradient, one layer at a time: returns ``(loss,
    top, grads)`` with ``loss`` a float, ``top`` (this routing's own
    choices, as ``loss`` gives them) on the host and ``grads`` an
    iterator over ``(key, gradient)`` from the head down —
    ``("head",)``, ``("norm_f",)``, ``("layers", i)`` for ``i`` from the
    last layer to the first, ``("embed",)`` — each the gradient of the
    mean loss in ``params[key[0]]`` (``[key[1]]``), made when asked
    for.

    With ``routing`` (``[expert layers, B * S, top_k]`` expert ids: the
    PROGRAM's choices) every expert layer is computed under those
    choices. Routing is discrete: bfloat16 activations move a score
    across the last place chosen for a few tokens in a hundred, those
    tokens then meet another expert, and a gradient compared across
    that difference says how many choices differed and little about
    the arithmetic. So the comparison fixes the choices and counts,
    apart, on how many the reference would have chosen otherwise."""
    d = dims(config)
    batch, seq = tokens.shape
    qb, ch = (dense_reference._block_size(seq, q_block),
              dense_reference._block_size(seq, chunk))
    kinds, given, e = [], [], 0
    for kind, layer in zip(d.pattern, params["layers"]):
        kinds.append((kind, layer))
        given.append(None if routing is None or kind != "E"
                     else jnp.asarray(routing[e]).reshape(batch, seq, -1))
        e += kind == "E"
    scale = jnp.float32(1.0 / (batch * seq))
    with jax.default_matmul_precision("highest"):
        inputs, chosen, total, head, dx = [], [], 0.0, [], []
        for b in range(batch):
            xs, cs = [params["embed"][tokens[b]]], []
            for (kind, layer), g in zip(kinds, given):
                x, c = _layer_forward(layer, xs[-1],
                                      None if g is None else g[b],
                                      kind=kind, d=d, q_block=qb)
                xs.append(x)
                if c is not None:
                    cs.append(c)
            nll, (d_head, d_norm, d_x) = _head_vjp(
                params["head"], params["norm_f"], xs.pop(), targets[b],
                scale, d=d, chunk=ch)
            total += float(nll)
            inputs.append(xs)
            chosen.append(jnp.stack(cs) if cs else None)
            head.append((d_head, d_norm))
            dx.append(d_x)
    if chosen[0] is not None:
        chosen = jax.device_get(jnp.concatenate(chosen, axis=1))

    def grads():
        d_head, d_norm = _sum_trees(head)
        yield ("head",), d_head
        yield ("norm_f",), d_norm
        with jax.default_matmul_precision("highest"):
            for i in reversed(range(len(kinds))):
                (kind, layer), g = kinds[i], given[i]
                parts = []
                for b in range(batch):
                    part, dx[b] = _layer_vjp(
                        layer, inputs[b].pop(), None if g is None else g[b],
                        dx[b], kind=kind, d=d, q_block=qb)
                    parts.append(part)
                yield ("layers", i), _sum_trees(parts)
        embed = jnp.zeros_like(params["embed"])
        for b in range(batch):
            embed = embed.at[tokens[b]].add(dx[b])
        yield ("embed",), embed

    return total / (batch * seq), chosen, grads()


def tolerances(tokens_in_batch):
    """The limits of the comparison that decides ``correct``
    (``kinds/train_nemotron_h.py::against_reference``), by the name of
    the number each one holds. Each lies between two readings taken at
    the cell's sizes on the chip (my chip runs, PR 31: the sound program
    on eight seeds, ``controls_nemotron_h.py`` on one or two; PERF.md
    section 6 has the table):

    - ``loss_rel``: ``benchmark/reference.py``'s, for its reason
      (bfloat16 activations: 5.1e-4 from 4096 tokens up). Sound reads
      1.4e-6 to 8.2e-5. At a random initialisation the loss is ln(vocab)
      + 1/2 almost whatever the layers compute: of eight controls it
      fails none, so it is the least of the four here.
    - ``grad_rel``: sound 0.0261 to 0.0293 (bfloat16 activations put
      about 3% on every dense leaf's gradient); the scan's decays in
      bfloat16 0.0751 and 0.0797, float8's mantissa on the matrices
      0.156, a planted fault in any kind of layer 0.18 to 0.31.
    - ``grad_rel_worst_leaf``: sound 0.19 to 0.32, always a router's
      table (its gradient is what few tokens near a tie give it); a
      leaf left out or zeroed reads 1, the planted faults 0.71 to 1.45.
      The limit leaves the more room above the sound readings: fresh
      seeds read higher.
    - ``choices_differing_share``: sound 0.016 to 0.022 (bfloat16
      activations move a score across the 22nd place); float8's
      mantissa 0.16, the planted faults 0.13 to 0.26. (bfloat16 decays
      read 0.052 and 0.056 and fail by ``grad_rel`` alone.)

    What no limit here can tell from the sound program: a bfloat16
    router table (``grad_rel`` 0.0266) and bfloat16 norms (0.0265); the
    stated precision's own noise is larger than what they add."""
    return {
        "loss_rel": dense_reference.loss_tolerance(tokens_in_batch),
        "grad_rel": 0.05,
        "grad_rel_worst_leaf": 0.6,
        "choices_differing_share": 0.06,
    }
