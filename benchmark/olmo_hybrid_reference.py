"""The plain reference of the ``olmo_hybrid`` decoder: forward pass and
loss in straightforward ``jax.numpy`` and float32, independent of the
code under test: it imports nothing of the program. No kernel, no
chunked recurrence, no head groups, no checkpointed layers, no
bfloat16; matmuls at "highest" precision.

``x`` is ``[S, hidden]``; ``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``;
no bias anywhere. The norms sit on each sublayer's OUTPUT, before the
residual add (``modeling_olmo3.py::Olmo3DecoderLayer``):

    layer i:  h = x + RMS(Mixer_i(x); w1_i)   Mixer_i by layer_types[i]
              y = h + RMS(MLP(h);     w2_i)   MLP(h) = W_down (silu(W_gate h)
                                                                * W_up h)
    model:     embed -> layers -> RMS(.; w_f) -> untied head, cross-entropy
               over the held slice of the vocabulary

- ``full_attention``: ``q = RMS(W_q x; w_q)``, ``k = RMS(W_k x; w_k)``,
  each norm over ALL columns of the projection, before the split;
  ``v = W_v x``; ``num_attention_heads`` heads; causal ``softmax(q k^T /
  sqrt(head_dim)) v`` as a masked softmax, in blocks of query positions
  against the whole context; ``W_o``. No position term
  (``rope_parameters.rope_theta`` is null).
- ``linear_attention`` (Gated DeltaNet, ``H`` heads of ``d_k`` /
  ``d_v``, one value head a key head): ``in_proj`` gives per head ``q |
  k | v | z`` (the program's packing of ``W_q``, ``W_k``, ``W_v``,
  ``W_g``), ``in_ba`` gives ``b`` of every head, then ``a``. ``q | k |
  v`` go through a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps a channel (no bias; the sum over its
  taps of the shifted input) and SiLU. ``q <- q / |q| d_k^-1/2``, ``k
  <- k / |k|`` (``x / sqrt(sum x^2 + eps)``); ``beta = 2 sigmoid(b)``
  with ``linear_allow_neg_eigval`` (else ``sigmoid(b)``); ``g =
  -exp(A_log) softplus(a + dt_bias)``. Per head, the recurrence AS
  WRITTEN, one position at a time (``lax.scan``), on a state ``S [d_k,
  d_v]`` from 0: ``S <- exp(g_t) S``; ``u_t = beta_t (v_t - S^T k_t)``;
  ``S <- S + k_t u_t^T``; ``o_t = S^T q_t``. Then ``out = W_o (RMS(o_t;
  w_o of d_v) * silu(z))`` per head.

It reads the program's parameter tree (``embed``, ``head``, ``norm_f``,
``layers[i]["mixer" | "mlp"]``; ``norm`` of a part is the weight of the
norm on its output), because the check is made on the program's own
weights, and the configuration file's keys. One sequence at a time, the
loss in chunks of positions.

``loss`` is differentiable (the CPU tests use ``jax.grad`` of it). At
the published widths ``loss_and_grads`` walks the same functions one
mixer or MLP at a time (``jax.vjp`` of each, from the head down), so
that it fits beside nothing else on the chip at 16384 tokens.
``tolerances`` are the limits of the comparison that decides ``correct``
(``kinds/train_olmo_hybrid.py``)."""

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import reference as dense_reference
from benchmark.nemotron_h_reference import _sum_trees

Dims = collections.namedtuple(
    "Dims", "kinds eps heads key_dim value_dim beta_scale attn_heads")


def dims(config):
    """What the layers need of a configuration file, hashable."""
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("the reference is written for one value head a "
                         "key head")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("the reference is written for as many key/value "
                         "heads as query heads")
    if config["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("the reference has no position term")
    return Dims(
        kinds=tuple(config["layer_types"][:config["num_hidden_layers"]]),
        eps=float(config["rms_norm_eps"]),
        heads=config["linear_num_value_heads"],
        key_dim=config["linear_key_head_dim"],
        value_dim=config["linear_value_head_dim"],
        beta_scale=2.0 if config["linear_allow_neg_eigval"] else 1.0,
        attn_heads=config["num_attention_heads"])


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _l2(x, eps):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def deltanet_mixer(layer, x, d):
    """``x`` ``[S, hidden]`` through one Gated DeltaNet mixer, up to and
    with ``W_o``."""
    seq = x.shape[0]
    h, dk, dv = d.heads, d.key_dim, d.value_dim
    per_head = (x @ layer["in_proj"]).reshape(seq, h, 2 * dk + 2 * dv)
    conv_in, z = per_head[:, :, :2 * dk + dv], per_head[:, :, 2 * dk + dv:]
    ba = x @ layer["in_ba"]
    beta = d.beta_scale * jax.nn.sigmoid(ba[:, :h])       # [S, H]
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(
        ba[:, h:] + layer["dt_bias"])
    # causal depthwise convolution as its definition reads: K - 1 zeros
    # in front, one filter a channel, the last tap on the current step
    taps = layer["conv_w"].reshape(h, 2 * dk + dv, -1)
    k_taps = taps.shape[-1]
    padded = jnp.pad(conv_in, ((k_taps - 1, 0), (0, 0), (0, 0)))
    conv = jax.nn.silu(sum(padded[j:j + seq] * taps[:, :, j]
                           for j in range(k_taps)))
    q = _l2(conv[:, :, :dk], d.eps) * dk ** -0.5
    k = _l2(conv[:, :, dk:2 * dk], d.eps)
    v = conv[:, :, 2 * dk:]

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, None, None] * state
        u_t = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", state, k_t))
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q_t)

    # One position at a time. The steps go in blocks under
    # jax.checkpoint only so that a derivative of this holds one state
    # a block and one block's states, not one a step; the values are
    # the plain scan's.
    blk = dense_reference._block_size(seq, 64)
    _, o = lax.scan(
        jax.checkpoint(lambda state, inp: lax.scan(step, state, inp)),
        jnp.zeros((h, dk, dv), jnp.float32),
        tuple(t.reshape((seq // blk, blk) + t.shape[1:])
              for t in (q, k, v, g, beta)))
    y = _rms(o.reshape(seq, h, dv), layer["gate_norm"], d.eps) \
        * jax.nn.silu(z)
    return y.reshape(seq, h * dv) @ layer["out_proj"]


def attention_mixer(layer, x, d, q_block):
    seq, width = x.shape
    heads = d.attn_heads
    hd = width // heads
    q = _rms(x @ layer["wq"], layer["q_norm"], d.eps).reshape(seq, heads, hd)
    k = _rms(x @ layer["wk"], layer["k_norm"], d.eps).reshape(seq, heads, hd)
    v = (x @ layer["wv"]).reshape(seq, heads, hd)
    k_pos = jnp.arange(seq)

    @jax.checkpoint          # a derivative holds no block's scores
    def attend(i):
        qs = lax.dynamic_slice_in_dim(q, i * q_block, q_block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qs, k) / math.sqrt(hd)
        q_pos = i * q_block + jnp.arange(q_block)
        scores = jnp.where(q_pos[:, None] >= k_pos[None, :], scores,
                           -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    out = lax.map(attend, jnp.arange(seq // q_block))
    return out.reshape(seq, width) @ layer["wo"]


def gated_mlp(layer, x):
    f = layer["w_in"].shape[-1] // 2
    return (jax.nn.silu(x @ layer["w_in"][:, :f])
            * (x @ layer["w_in"][:, f:])) @ layer["w_out"]


def _part(kind, part, x, d, q_block):
    """``x`` after one mixer (``linear_attention`` / ``full_attention``)
    or MLP (``mlp``): the sublayer's output normed, then added."""
    if kind == "linear_attention":
        out = deltanet_mixer(part, x, d)
    elif kind == "full_attention":
        out = attention_mixer(part, x, d, q_block)
    else:
        out = gated_mlp(part, x)
    return x + _rms(out, part["norm"], d.eps)


def _parts(params, d):
    """The mixers and MLPs in order: ``(kind, layer index, name in the
    layer, parameters)``."""
    out = []
    for i, (kind, layer) in enumerate(zip(d.kinds, params["layers"])):
        out.append((kind, i, "mixer", layer["mixer"]))
        out.append(("mlp", i, "mlp", layer["mlp"]))
    return out


def _nll_sum(head, norm_f, x, targets, d, chunk):
    h = _rms(x, norm_f, d.eps)

    def chunk_nll(c):
        hs = lax.dynamic_slice_in_dim(h, c * chunk, chunk, axis=0)
        tg = lax.dynamic_slice_in_dim(targets, c * chunk, chunk, axis=0)
        logits = hs @ head.T
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tg[:, None], axis=-1)[:, 0]
        return (lse - picked).sum()

    return lax.map(chunk_nll, jnp.arange(x.shape[0] // chunk)).sum()


@functools.partial(jax.jit, static_argnames=("d", "q_block", "chunk"))
def sequence_nll(params, tokens, targets, *, d, q_block, chunk):
    """The summed next-token loss of ONE sequence (``tokens``,
    ``targets`` ``[S]``), differentiable in ``params``."""
    x = params["embed"][tokens]
    for kind, _, _, part in _parts(params, d):
        x = _part(kind, part, x, d, q_block)
    return _nll_sum(params["head"], params["norm_f"], x, targets, d, chunk)


def _blocks(seq, q_block, chunk):
    return (dense_reference._block_size(seq, q_block),
            dense_reference._block_size(seq, chunk))


def loss(params, tokens, targets, config, *, q_block=512, chunk=512):
    """Mean next-token cross-entropy of ``tokens`` ``[B, S]`` under
    ``params`` (float32), as a traced scalar (``jax.grad`` of it is the
    reference's gradient)."""
    d = dims(config)
    batch, seq = tokens.shape
    qb, ch = _blocks(seq, q_block, chunk)
    with jax.default_matmul_precision("highest"):
        total = sum(sequence_nll(params, tokens[b], targets[b], d=d,
                                 q_block=qb, chunk=ch)
                    for b in range(batch))
    return total / (batch * seq)


@functools.partial(jax.jit, static_argnames=("kind", "d", "q_block"))
def _part_forward(part, x, *, kind, d, q_block):
    return _part(kind, part, x, d, q_block)


@functools.partial(jax.jit, static_argnames=("kind", "d", "q_block"))
def _part_vjp(part, x, dy, *, kind, d, q_block):
    """``(d part, d x)`` of one mixer or MLP under ``dy``."""
    _, pull = jax.vjp(lambda p, xx: _part(kind, p, xx, d, q_block), part, x)
    return pull(dy)


@functools.partial(jax.jit, static_argnames=("d", "chunk"))
def _head_vjp(head, norm_f, x, targets, scale, *, d, chunk):
    """The summed loss of one sequence, and ``scale`` times its
    derivative in ``(head, norm_f, x)``."""
    nll, pull = jax.vjp(
        lambda hd, nf, xx: _nll_sum(hd, nf, xx, targets, d, chunk),
        head, norm_f, x)
    return nll, pull(scale)


def loss_and_grads(params, tokens, targets, config, *, q_block=512,
                   chunk=512):
    """``loss`` and its gradient, one mixer or MLP at a time: returns
    ``(loss, grads)`` with ``loss`` a float and ``grads`` an iterator
    over ``(key, gradient)`` from the head down: ``("head",)``,
    ``("norm_f",)``, ``("layers", i, "mlp")`` and ``("layers", i,
    "mixer")`` for ``i`` from the last layer to the first,
    ``("embed",)``; each the gradient of the mean loss in that part of
    ``params``, made when asked for."""
    d = dims(config)
    batch, seq = tokens.shape
    qb, ch = _blocks(seq, q_block, chunk)
    parts = _parts(params, d)
    scale = jnp.float32(1.0 / (batch * seq))
    with jax.default_matmul_precision("highest"):
        inputs, total, head, dx = [], 0.0, [], []
        for b in range(batch):
            xs = [params["embed"][tokens[b]]]
            for kind, _, _, part in parts:
                xs.append(_part_forward(part, xs[-1], kind=kind, d=d,
                                        q_block=qb))
            nll, (d_head, d_norm, d_x) = _head_vjp(
                params["head"], params["norm_f"], xs.pop(), targets[b],
                scale, d=d, chunk=ch)
            total += float(nll)
            inputs.append(xs)
            head.append((d_head, d_norm))
            dx.append(d_x)

    def grads():
        d_head, d_norm = _sum_trees(head)
        yield ("head",), d_head
        yield ("norm_f",), d_norm
        with jax.default_matmul_precision("highest"):
            for kind, i, name, part in reversed(parts):
                sums = []
                for b in range(batch):
                    got, dx[b] = _part_vjp(part, inputs[b].pop(), dx[b],
                                           kind=kind, d=d, q_block=qb)
                    sums.append(got)
                yield ("layers", i, name), _sum_trees(sums)
        embed = jnp.zeros_like(params["embed"])
        for b in range(batch):
            embed = embed.at[tokens[b]].add(dx[b])
        yield ("embed",), embed

    return total / (batch * seq), grads()


def tolerances(tokens_in_batch):
    """The limits of the comparison that decides ``correct``
    (``kinds/train_olmo_hybrid.py::against_reference``), by the name of
    the number each one holds. Each lies between two readings taken at
    the cell's sizes on the chip (my chip runs, PR 39: eleven sound runs
    on eleven seeds, ``benchmark/controls_olmo_hybrid.py`` on seeds
    2718281829 and 3000000019; PERF.md section 6 has the table).

    - ``loss_rel``: ``benchmark/reference.py``'s, for its reason
      (bfloat16 activations: 5.1e-4 from 4096 tokens up). Sound reads
      5.8e-7 to 1.6e-5. At a random initialisation the loss is ln(vocab)
      + 1/2 almost whatever the layers compute: of seven controls it
      fails two (the output gate left out 1.1e-3, the norm moved to the
      input 1.6e-3), so it is the least of the three here.
    - ``grad_rel``: sound 0.02289 to 0.02763 on eleven seeds (bfloat16
      activations put about 2.5% on every dense leaf's gradient, as in
      the other hybrid cells, and the seeds spread wider here: four
      layers, fewer leaves to average over); the precision below the
      stated one, on two seeds: the delta rule's decays in bfloat16
      0.05684 and 0.04154, decays and every RMSNorm in bfloat16 0.05815
      and 0.04196. The limit is the geometric middle of the highest
      sound reading and the LOWEST of those: 1.23 times of room over
      sound, 1.22 under the control (the first seed alone had put it at
      0.039, which the second seed's decays would have failed by 6%:
      tightened, as PR 37's was). The planted faults read 0.463
      (``beta`` without its factor 2), 0.721 (the delta term left out),
      0.854 (the norm on the sublayer's input) and 1.055 (the output
      gate left out): 13 times the limit and more. ONE planted fault
      lies at the limit: the QK-norm per head reads 0.0349 and 0.0307
      (whole-projection and per-head norms differ by the heads' own
      RMS scatter, in one layer of four); the next limit is there for
      it.
    - ``grad_rel_worst_leaf``: sound 0.0383 to 0.0598 (a DeltaNet
      layer's ``A_log``, ``dt_bias``, ``in_ba`` or ``in_proj``: the
      first two are 30 numbers each, so this reading is the noisiest
      and gets the most room above it, 2.5 times); the QK-norm per head
      0.245 and 0.240 (``layers.3.mixer.wk``), 1.6 times over the
      limit; a leaf left out or zeroed reads 1, the other planted
      faults 0.80 to 1.50. The bfloat16 decays read 0.113 to 0.210
      here: they fail by ``grad_rel`` on every seed."""
    return {
        "loss_rel": dense_reference.loss_tolerance(tokens_in_batch),
        "grad_rel": 0.034,
        "grad_rel_worst_leaf": 0.15,
    }
