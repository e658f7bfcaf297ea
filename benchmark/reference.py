"""The plain reference: a GPT-2-shaped decoder's forward pass and loss
in straightforward ``jax.numpy`` and float32, independent of the code
under test — no kernel, no remat, no bfloat16, matmuls at "highest"
precision (on a TPU a float32 matmul otherwise runs in bfloat16 passes).

Departures from the published GPT-2 block, as the configuration files
list them: no biases, LayerNorm with a scale and no shift, the tanh form
of GELU. It reads the program's parameter tree (``embed``, ``pos``,
``ln_f``, ``layers[i]`` with ``ln1 ln2 wq wk wv wo wi wo_mlp``) because
the check is made on the program's own weights.

One sequence at a time; attention in blocks of query positions against
the whole context and the loss in chunks of positions, so that at
S = 16384 neither the [12, S, S] scores (12.9 GB) nor the [S, 50257]
logits (3.3 GB) ever exist whole."""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax


def _layernorm(x, scale, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_head", "q_block", "eps"))
def _layer(layer, x, *, n_head, q_block, eps):
    seq, d = x.shape
    hd = d // n_head

    def heads(w):
        return (y @ w).reshape(seq, n_head, hd).transpose(1, 0, 2)

    y = _layernorm(x, layer["ln1"], eps)
    q, k, v = heads(layer["wq"]), heads(layer["wk"]), heads(layer["wv"])
    k_pos = jnp.arange(seq)

    def attend(i):
        qs = lax.dynamic_slice_in_dim(q, i * q_block, q_block, axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", qs, k) / math.sqrt(hd)
        q_pos = i * q_block + jnp.arange(q_block)
        scores = jnp.where(q_pos[:, None] >= k_pos[None, :], scores,
                           -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), v)

    out = lax.map(attend, jnp.arange(seq // q_block))  # [nb, H, qb, hd]
    x = x + out.transpose(0, 2, 1, 3).reshape(seq, d) @ layer["wo"]
    y = _layernorm(x, layer["ln2"], eps)
    return x + _gelu_tanh(y @ layer["wi"]) @ layer["wo_mlp"]


@functools.partial(jax.jit, static_argnames=("chunk", "eps"))
def _nll_sum(embed, ln_f, x, targets, *, chunk, eps):
    h = _layernorm(x, ln_f, eps)

    def chunk_nll(c):
        hs = lax.dynamic_slice_in_dim(h, c * chunk, chunk, axis=0)
        tg = lax.dynamic_slice_in_dim(targets, c * chunk, chunk, axis=0)
        logits = hs @ embed.T
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tg[:, None], axis=-1)[:, 0]
        return (lse - picked).sum()

    return lax.map(chunk_nll, jnp.arange(x.shape[0] // chunk)).sum()


@jax.jit
def _embed(embed, pos, tokens):
    return embed[tokens] + pos[:tokens.shape[0]]


def _block_size(seq, want):
    """The largest divisor of ``seq`` that is at most ``want``."""
    b = min(want, seq)
    while seq % b:
        b -= 1
    return b


def reference_loss(params, tokens, targets, config, *, q_block=512,
                   chunk=512):
    """Mean next-token cross-entropy of ``tokens`` ``[B, S]`` (host
    int32) under ``params`` (float32, on the device), as a float."""
    n_head = config["n_head"]
    eps = float(config.get("layer_norm_epsilon", 1e-5))
    batch, seq = tokens.shape
    qb, ch = _block_size(seq, q_block), _block_size(seq, chunk)
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for b in range(batch):
            x = _embed(params["embed"], params["pos"], tokens[b])
            for layer in params["layers"]:
                x = _layer(layer, x, n_head=n_head, q_block=qb, eps=eps)
            total += float(_nll_sum(params["embed"], params["ln_f"], x,
                                    targets[b], chunk=ch, eps=eps))
    return total / (batch * seq)


def loss_tolerance(tokens_in_batch):
    """Relative tolerance between the program's first loss and the
    reference's, with its reason.

    The configuration computes activations and the logits' matmul in
    bfloat16 (8 significant bits, half an ulp = 2^-9 relative) and the
    softmax and the loss in float32. A token's loss is the difference
    of two logit-sized numbers of about the loss's own magnitude, so
    bfloat16 rounding puts a RANDOM error of about 2^-9 of the loss on
    each token, and the mean over T tokens shrinks it by sqrt(T) — as
    far as the tokens' errors are independent, which they stop being:
    the same rounded weights meet the same token ids again and again.
    On the chip (PERF.md, PR 25) the first losses of six seeds at
    T = 4096 differed by up to 1.4e-4, and of twelve at T = 16384 (six
    in each of two cells) by up to 2.1e-4, no less. So the shrinking
    is credited up to 4096 tokens and no further. Sixteen
    such standard deviations (the roundings of the layers below add to
    the last one's), plus 2e-5 for float32 reassociation, is the
    tolerance: 5.1e-4 from T = 4096 up (1e-3 at the tests' T = 1024).
    A precision below the configuration's — int8 matmuls, a bfloat16
    softmax or loss — errs SYSTEMATICALLY, by 2^-8 of the loss (3.9e-3)
    or more on every token alike, which no averaging shrinks: over
    seven times the tolerance."""
    return (16.0 * 2.0 ** -9 / math.sqrt(min(tokens_in_batch, 4096))
            + 2e-5)
