"""The plain reference of the ``qwen3_next`` decoder: forward pass and
loss in straightforward ``jax.numpy`` and float32, independent of the
code under test — it imports nothing of the program. No kernel, no
chunked recurrence, no remat, no bfloat16, no sorted rows or grouped
matmul; matmuls at "highest" precision.

``x`` is ``[S, hidden]``; ``norm(x, w) = x / sqrt(mean(x^2) + eps) *
(1 + w)`` (zero-centred). Layer ``i``: ``h = x + Mixer_i(norm(x))``,
``out = h + MoE(norm(h))``; ``Mixer_i`` is full attention where ``(i +
1) % full_attention_interval == 0``, else Gated DeltaNet.

- **Gated DeltaNet** (``H_k`` key heads, ``H_v`` value heads, widths
  ``d_k``, ``d_v``; each key head serves ``H_v / H_k`` consecutive
  value heads). ``in_proj`` gives per key head ``q | k | v | z``;
  ``in_ba`` gives ``b`` then ``a`` per value head. ``q | k | v`` of a
  head go through a causal depthwise convolution (no bias; the sum over
  its taps of the shifted input) and SiLU. ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; ``q <- q / |q| d_k^-1/2``, ``k
  <- k / |k|`` (``x / sqrt(sum x^2 + eps)``). Per value head, the
  recurrence AS WRITTEN, one step at a time (``lax.scan`` over the
  positions), on a state ``S [d_k, d_v]`` from 0: ``S <- exp(g_t) S``;
  ``u_t = beta_t (v_t - S^T k_t)``; ``S <- S + k_t u_t^T``; ``o_t = S^T
  q_t``. Then ``y = w_n o / sqrt(mean(o^2) + eps) * silu(z)`` per head
  and ``out_proj``.
- **Gated attention**: ``wq`` gives per head ``q | gate``; ``q`` and
  ``k`` are normed per head (the zero-centred norm); rotary positions on
  the first ``partial_rotary_factor x head_dim`` dims in the half-split
  form (dim ``j`` with ``j + r/2``, ``inv_freq_j = theta^(-2j/r)``);
  causal softmax attention as a masked softmax at scale
  ``head_dim^-1/2``, query heads in groups over the key/value heads, in
  blocks of query positions against the whole context; times
  ``sigmoid(gate)``; ``wo``.
- **Expert layer**, one chip's share: ``p = softmax(u W_r)`` over ALL
  experts; the ``num_experts_per_tok`` largest (a stable descending
  sort: the lower id wins a tie); weights the chosen ``p`` over their
  sum; a LOOP over the experts held here (``experts_held``), each
  computed for every token and added under its column of a dense mask
  of those weights: ``W_out,e (silu(W_gate,e u) * W_up,e u)`` with
  ``w_in = [W_gate | W_up]``; plus ``sigmoid(u . w_s) W_out,s
  (silu(W_gate,s u) * W_up,s u)``, the shared expert. What the absent
  experts would add is left out.

It reads the program's parameter tree (``embed``, ``head``, ``norm_f``,
``layers[i]["mixer" | "moe"]``), because the check is made on the
program's own weights, and the configuration file's keys. One sequence
at a time, the loss in chunks of positions, so that it fits at 8192
tokens.

``loss`` is differentiable (the CPU tests use ``jax.grad`` of it). At
the published widths ``loss_and_grads`` walks the same functions one
mixer or expert layer at a time (``jax.vjp`` of each, from the head
down). ``tolerances`` are the limits of the comparison that decides
``correct`` (``kinds/train_qwen3_next.py``), each between two readings
taken on the chip (PERF.md section 6, PR 37)."""

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import reference as dense_reference
from benchmark.nemotron_h_reference import _sum_trees

Dims = collections.namedtuple(
    "Dims", "layers interval eps key_heads value_heads key_dim value_dim "
            "q_heads kv_heads attn_dim rotary theta held top_k")


def dims(config):
    """What the layers need of a configuration file, hashable."""
    return Dims(
        layers=config["num_hidden_layers"],
        interval=config["full_attention_interval"],
        eps=float(config["rms_norm_eps"]),
        key_heads=config["linear_num_key_heads"],
        value_heads=config["linear_num_value_heads"],
        key_dim=config["linear_key_head_dim"],
        value_dim=config["linear_value_head_dim"],
        q_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        attn_dim=config["head_dim"],
        rotary=int(config["partial_rotary_factor"] * config["head_dim"]),
        theta=float(config["rope_theta"]),
        held=tuple(config["experts_held"]),
        top_k=config["num_experts_per_tok"])


def mixer_kinds(d):
    """``A`` (full attention) or ``D`` (Gated DeltaNet), a layer."""
    return ["A" if (i + 1) % d.interval == 0 else "D"
            for i in range(d.layers)]


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _l2(x, eps):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _gated_mlp(u, w_in, w_out):
    f = w_in.shape[-1] // 2
    return (jax.nn.silu(u @ w_in[:, :f]) * (u @ w_in[:, f:])) @ w_out


def deltanet_layer(layer, x, d):
    """``x`` ``[S, hidden]`` through one Gated DeltaNet mixer."""
    seq = x.shape[0]
    hk, hv, dk, dv = d.key_heads, d.value_heads, d.key_dim, d.value_dim
    rep = hv // hk
    u = _norm(x, layer["norm"], d.eps)
    per_head = (u @ layer["in_proj"]).reshape(seq, hk, 2 * dk + 2 * rep * dv)
    conv_in = per_head[:, :, :2 * dk + rep * dv]          # q | k | v
    z = per_head[:, :, 2 * dk + rep * dv:].reshape(seq, hv, dv)
    ba = u @ layer["in_ba"]
    beta = jax.nn.sigmoid(ba[:, :hv])                     # [S, H_v]
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(
        ba[:, hv:] + layer["dt_bias"])
    # causal depthwise convolution as its definition reads: K - 1 zeros
    # in front, one filter a channel, the last tap on the current step
    taps = layer["conv_w"].reshape(hk, 2 * dk + rep * dv, -1)
    k_taps = taps.shape[-1]
    padded = jnp.pad(conv_in, ((k_taps - 1, 0), (0, 0), (0, 0)))
    conv = jax.nn.silu(sum(padded[j:j + seq] * taps[:, :, j]
                           for j in range(k_taps)))
    q = jnp.repeat(_l2(conv[:, :, :dk], d.eps) * dk ** -0.5, rep, axis=1)
    k = jnp.repeat(_l2(conv[:, :, dk:2 * dk], d.eps), rep, axis=1)
    v = conv[:, :, 2 * dk:].reshape(seq, hv, dv)

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, None, None] * state
        u_t = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", state, k_t))
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q_t)

    # One step at a time. The steps go in blocks under jax.checkpoint
    # only so that a derivative of this holds one state a block and one
    # block's states, not one a step; the values are the plain scan's.
    blk = dense_reference._block_size(seq, 64)
    _, o = lax.scan(
        jax.checkpoint(lambda state, inp: lax.scan(step, state, inp)),
        jnp.zeros((hv, dk, dv), jnp.float32),
        tuple(t.reshape((seq // blk, blk) + t.shape[1:])
              for t in (q, k, v, g, beta)))
    o = o.reshape(seq, hv, dv)
    y = (layer["gate_norm"] * o
         / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + d.eps)
         * jax.nn.silu(z))
    return x + y.reshape(seq, hv * dv) @ layer["out_proj"]


def _rotary(x, d):
    """``x`` ``[S, heads, head_dim]``: positions on the first
    ``d.rotary`` dims, dim ``j`` paired with ``j + rotary / 2``."""
    half = d.rotary // 2
    inv_freq = d.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:d.rotary], x[..., d.rotary:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def attention_layer(layer, x, d, q_block):
    seq = x.shape[0]
    hd, kv, rep = d.attn_dim, d.kv_heads, d.q_heads // d.kv_heads
    u = _norm(x, layer["norm"], d.eps)
    qg = (u @ layer["wq"]).reshape(seq, d.q_heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (u @ layer["wk"]).reshape(seq, kv, hd)
    v = (u @ layer["wv"]).reshape(seq, kv, hd)
    q = _rotary(_norm(q, layer["q_norm"], d.eps), d).reshape(seq, kv, rep, hd)
    k = _rotary(_norm(k, layer["k_norm"], d.eps), d)
    k_pos = jnp.arange(seq)

    @jax.checkpoint          # a derivative holds no block's scores
    def attend(i):
        qs = lax.dynamic_slice_in_dim(q, i * q_block, q_block, axis=0)
        scores = jnp.einsum("qgrd,kgd->grqk", qs, k) / math.sqrt(hd)
        q_pos = i * q_block + jnp.arange(q_block)
        scores = jnp.where(q_pos[:, None] >= k_pos[None, :], scores,
                           -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, -1), v)

    out = lax.map(attend, jnp.arange(seq // q_block))
    out = out.reshape(seq, d.q_heads, hd) * jax.nn.sigmoid(gate)
    return x + out.reshape(seq, d.q_heads * hd) @ layer["wo"]


def moe_layer(layer, x, d, given=None):
    """Returns ``(x, top)``: ``top`` ``[S, top_k]``, the experts each
    token chooses here. With ``given`` (such an array) the layer is
    computed under THOSE choices instead, and ``top`` still says what
    this routing would have chosen."""
    u = _norm(x, layer["norm"], d.eps)
    scores = jax.nn.softmax(u @ layer["router"], axis=-1)   # [S, experts]
    own = jnp.argsort(-scores, axis=-1, stable=True)[:, :d.top_k]
    top = own if given is None else given
    picked = jnp.take_along_axis(scores, top, axis=-1)
    picked = picked / picked.sum(-1, keepdims=True)
    # a loop over the held experts: each on every token, under its
    # column of a dense mask of the weights
    weight = jnp.where(top[:, :, None] == jnp.asarray(d.held),
                       picked[:, :, None], 0.0).sum(1)      # [S, held]

    @jax.checkpoint
    def one(total, expert):
        w_in, w_out, w = expert
        return total + w[:, None] * _gated_mlp(u, w_in, w_out), None

    routed, _ = lax.scan(one, jnp.zeros_like(u),
                         (layer["w_in"], layer["w_out"], weight.T))
    shared = (jax.nn.sigmoid(u @ layer["shared_gate"])[:, None]
              * _gated_mlp(u, layer["shared_in"], layer["shared_out"]))
    return x + routed + shared, own


def rows(top, held):
    """``[..., held, S]`` bool of choices ``top`` ``[..., S, top_k]``:
    which (token, held expert) pairs they make: the rows the held
    experts get."""
    top = jnp.asarray(top)
    return jnp.stack([(top == e).any(-1) for e in held], axis=-2)


def _nll_sum(head, norm_f, x, targets, d, chunk):
    h = _norm(x, norm_f, d.eps)

    def chunk_nll(c):
        hs = lax.dynamic_slice_in_dim(h, c * chunk, chunk, axis=0)
        tg = lax.dynamic_slice_in_dim(targets, c * chunk, chunk, axis=0)
        logits = hs @ head.T
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tg[:, None], axis=-1)[:, 0]
        return (lse - picked).sum()

    return lax.map(chunk_nll, jnp.arange(x.shape[0] // chunk)).sum()


def _part(kind, part, x, d, q_block, given=None):
    """``(x, top)`` after one mixer (``D`` / ``A``) or expert layer
    (``E``); ``top`` (and ``given``) is None but for an expert layer."""
    if kind == "D":
        return deltanet_layer(part, x, d), None
    if kind == "A":
        return attention_layer(part, x, d, q_block), None
    return moe_layer(part, x, d, given)


def _parts(params, d):
    """The mixers and expert layers in order: ``(kind, layer index,
    name in the layer, parameters)``."""
    out = []
    for i, (kind, layer) in enumerate(zip(mixer_kinds(d), params["layers"])):
        out.append((kind, i, "mixer", layer["mixer"]))
        out.append(("E", i, "moe", layer["moe"]))
    return out


@functools.partial(jax.jit, static_argnames=("d", "q_block", "chunk"))
def sequence_nll(params, tokens, targets, *, d, q_block, chunk):
    """``(summed next-token loss, top)`` of ONE sequence (``tokens``,
    ``targets`` ``[S]``): differentiable in ``params``; ``top``
    ``[layers, S, top_k]``."""
    x = params["embed"][tokens]
    chosen = []
    for kind, _, _, part in _parts(params, d):
        x, c = _part(kind, part, x, d, q_block)
        if c is not None:
            chosen.append(c)
    nll = _nll_sum(params["head"], params["norm_f"], x, targets, d, chunk)
    return nll, jnp.stack(chosen)


def loss(params, tokens, targets, config, *, q_block=512, chunk=512):
    """Mean next-token cross-entropy of ``tokens`` ``[B, S]`` under
    ``params`` (float32), as a traced scalar (``jax.grad`` of it is the
    reference's gradient), with every expert layer's choices ``[layers,
    B * S, top_k]``."""
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
    return total / (batch * seq), jnp.concatenate(chosen, axis=1)


@functools.partial(jax.jit, static_argnames=("kind", "d", "q_block"))
def _part_forward(part, x, given, *, kind, d, q_block):
    return _part(kind, part, x, d, q_block, given)


@functools.partial(jax.jit, static_argnames=("kind", "d", "q_block"))
def _part_vjp(part, x, given, dy, *, kind, d, q_block):
    """``(d part, d x)`` of one mixer or expert layer under ``dy``."""
    _, pull = jax.vjp(
        lambda p, xx: _part(kind, p, xx, d, q_block, given)[0], part, x)
    return pull(dy)


@functools.partial(jax.jit, static_argnames=("d", "chunk"))
def _head_vjp(head, norm_f, x, targets, scale, *, d, chunk):
    """The summed loss of one sequence, and ``scale`` times its
    derivative in ``(head, norm_f, x)``."""
    nll, pull = jax.vjp(
        lambda hd, nf, xx: _nll_sum(hd, nf, xx, targets, d, chunk),
        head, norm_f, x)
    return nll, pull(scale)


def loss_and_grads(params, tokens, targets, config, routing=None, *,
                   q_block=512, chunk=512):
    """``loss`` and its gradient, one mixer or expert layer at a time:
    returns ``(loss, top, grads)`` with ``loss`` a float, ``top`` (this
    routing's own choices, as ``loss`` gives them) on the host and
    ``grads`` an iterator over ``(key, gradient)`` from the head down —
    ``("head",)``, ``("norm_f",)``, ``("layers", i, "moe")`` and
    ``("layers", i, "mixer")`` for ``i`` from the last layer to the
    first, ``("embed",)`` — each the gradient of the mean loss in that
    part of ``params``, made when asked for.

    With ``routing`` (``[layers, B * S, top_k]`` expert ids: the
    PROGRAM's choices) every expert layer is computed under those
    choices. Routing is discrete: bfloat16 activations move a score
    across the last place chosen for a few tokens in a hundred, those
    tokens then meet another expert, and a gradient compared across
    that difference says how many choices differed and little about
    the arithmetic (PERF.md section 2). So the comparison fixes the
    choices and counts, apart, on how many the reference would have
    chosen otherwise."""
    d = dims(config)
    batch, seq = tokens.shape
    qb, ch = (dense_reference._block_size(seq, q_block),
              dense_reference._block_size(seq, chunk))
    parts = _parts(params, d)
    given = [None if routing is None or kind != "E"
             else jnp.asarray(routing[i]).reshape(batch, seq, -1)
             for kind, i, _, _ in parts]
    scale = jnp.float32(1.0 / (batch * seq))
    with jax.default_matmul_precision("highest"):
        inputs, chosen, total, head, dx = [], [], 0.0, [], []
        for b in range(batch):
            xs, cs = [params["embed"][tokens[b]]], []
            for (kind, _, _, part), g in zip(parts, given):
                x, c = _part_forward(part, xs[-1],
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
            chosen.append(jnp.stack(cs))
            head.append((d_head, d_norm))
            dx.append(d_x)
    chosen = jax.device_get(jnp.concatenate(chosen, axis=1))

    def grads():
        d_head, d_norm = _sum_trees(head)
        yield ("head",), d_head
        yield ("norm_f",), d_norm
        with jax.default_matmul_precision("highest"):
            for j in reversed(range(len(parts))):
                (kind, i, name, part), g = parts[j], given[j]
                sums = []
                for b in range(batch):
                    got, dx[b] = _part_vjp(
                        part, inputs[b].pop(), None if g is None else g[b],
                        dx[b], kind=kind, d=d, q_block=qb)
                    sums.append(got)
                yield ("layers", i, name), _sum_trees(sums)
        embed = jnp.zeros_like(params["embed"])
        for b in range(batch):
            embed = embed.at[tokens[b]].add(dx[b])
        yield ("embed",), embed

    return total / (batch * seq), chosen, grads()


def tolerances(tokens_in_batch):
    """The limits of the comparison that decides ``correct``
    (``kinds/train_qwen3_next.py::against_reference``), by the name of
    the number each one holds. Each lies between two readings taken at
    the cell's sizes on the chip (my chip runs, PR 37; PERF.md section 6
    has the table). The readings are those of the comparison as it is
    since the review of PR 37: the program's choices come out of the
    gradient program itself. (Before, a forward program of its own gave
    them, the two programs' choices differed on a few tokens in a
    hundred, and that put 0.022 in quadrature on every ``grad_rel`` and
    0.15 on a router's table: sound read 0.033 to 0.035 then.)

    - ``loss_rel``: ``benchmark/reference.py``'s, for its reason
      (bfloat16 activations: 5.1e-4 from 4096 tokens up). Sound reads
      4.6e-7 to 4.6e-5. At a random initialisation the loss is ln(vocab)
      + 1/2 almost whatever the layers compute: of eight controls it
      fails none, so it is the least of the four here.
    - ``grad_rel``: sound 0.02520 to 0.02563 on four seeds (bfloat16
      activations put about 2.5% on every dense leaf's gradient); the
      precision below the stated one, 0.0406 to 0.0429 on two seeds: the
      delta rule's decays in bfloat16 (0.04293, 0.04067), and decays and
      router both (0.04288, 0.04056: the router's table adds nothing
      one can see); every matrix rounded to float8's mantissa 0.238.
      The limit is the geometric middle of the first seed's 0.0256 and
      0.0429: 1.29 times of room over the highest sound reading, 1.23
      under the lowest control's (which would have passed 0.042). The
      planted faults read 0.057 (rotary positions on all 256 dims) to
      1.03 under the earlier comparison and were not run again.
    - ``grad_rel_worst_leaf``: sound 0.033 to 0.041 (a DeltaNet layer's
      ``dt_bias``, ``A_log`` or ``in_ba``); bfloat16 decays 0.080, with
      the router 0.106; a leaf left out or zeroed reads 1, the planted
      faults 0.87 to 1.62 (earlier comparison). The limit is there for
      the leaf left out and leaves the more room above the sound
      readings: fresh seeds read higher.
    - ``choices_differing_share``: sound 0.0256 to 0.0268 (bfloat16
      activations move a score across the tenth place); bfloat16 decays
      0.039, with the router 0.040 (they fail by ``grad_rel``), float8
      weights 0.210; a gate or the normalisation left out 0.115 to
      0.159, the delta term or the shared expert's gate left out 0.79
      and 0.82 (earlier comparison).

    What no limit here can tell from the sound program: a bfloat16
    router table ALONE (``grad_rel`` 0.0338 against the sound 0.033 to
    0.035 of the earlier comparison, and 0.04288 against 0.04293 beside
    bfloat16 decays): the stated precision's own noise is larger than
    what it adds. The stated precision as a whole is told."""
    return {
        "loss_rel": dense_reference.loss_tolerance(tokens_in_batch),
        "grad_rel": 0.033,
        "grad_rel_worst_leaf": 0.6,
        "choices_differing_share": 0.06,
    }
