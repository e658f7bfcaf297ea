"""A decoder in the ``qwen3_next`` shape: every layer is a token mixer
and then an expert layer,

    h   = x + Mixer_i(norm(x))
    out = h + MoE(norm(h))

``Mixer_i`` full attention where ``(i + 1) % full_attention_interval ==
0``, else a Gated DeltaNet linear-attention mixer; ``norm`` the
zero-centred RMSNorm ``x / rms(x) * (1 + w)``; a final norm and an
untied head.

- **Gated DeltaNet**: ``models/gated_deltanet.py``'s mixer, which
  ``models/olmo_hybrid.py`` shares, after this model's norm: two value
  heads a key head, a write strength ``beta = sigmoid(b)``, the
  delta-rule recurrence ``ops/delta_rule.py``'s as THIS module holds it
  (``_recurrence``).
- **Gated attention**: the query projection emits a gate beside each
  head's query; queries and keys are normed per head; rotary positions
  (half-split form) on the first ``rotary_dim`` of each head; causal
  softmax attention, the flash kernels or XLA's by ``flash_choice``,
  K and V broadcast to the query heads outside them; the output times
  ``sigmoid(gate)``.
- **Expert layer**, ONE CHIP'S SHARE of an expert-parallel layer (as
  ``models/nemotron_h.py``'s): told which experts it holds, it routes
  every token over ALL experts (float32 softmax, top ``top_k``, the
  chosen scores over their sum) and adds only its own experts' terms
  (gated SiLU MLPs), plus one shared expert under a per-token sigmoid
  gate. The routed part runs over ROWS SORTED BY EXPERT: one row a
  (token, chosen expert) pair, the held experts' rows first by expert,
  in a buffer of the worst case ``T x top_k`` rows (dropless), through
  ``ops/grouped_matmul.py``, whose kernels skip the rows that are not
  real. Everything else that passes over the rows (the sort, the
  gather, the gated activation, the weighted sum back to the tokens,
  their backwards) has the buffer's static shape and no loop whose
  trip count is data: its time is a function of the shapes, not of
  the routing. Nothing stands in for the absent experts' chips or the
  exchange.

It trains through ``parallel.train.build_train_step`` by the door the
other models use (``cfg.param_specs()``, ``cfg.loss_fn(...)``, the axis
fields, ``layouts``), under 'dp' alone. Mixer and expert layer each run
under ``remat_block``'s checkpoint, which HOLDS the small arrays that
are dear to recompute (``HELD_NAMES``): the router's choices and the
row plan of the two sorts, and the output of the mixer's loop over its
head groups.

Not built: multi-token prediction, the auxiliary balance loss, serving
state for the recurrent layers, the ``ep`` exchange.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops import grouped_matmul as gmm
from ..ops.delta_rule import delta_rule
from . import gated_deltanet as gdn
from . import transformer as tfm

# Checkpoint names of what a layer's checkpoint HOLDS for its backward
# (``remat_block(names=)``): the groups' output of a DeltaNet mixer
# (``gated_deltanet.HELD_NAME``), the router's choices (a top-k) and the
# plan of the rows (two sorts). NOT the router's float32 logits: 16.8 MB
# a layer that the step's memory lacks.
HELD_NAMES = (gdn.HELD_NAME, "hvd_moe_router_idx", "hvd_moe_row_token",
              "hvd_moe_row_weight", "hvd_moe_pair_row", "hvd_moe_group_sizes")


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig(gdn.GdnFields):
    vocab: int = 1024
    d_model: int = 128
    n_layers: int = 4
    full_attention_interval: int = 4
    eps: float = 1e-6
    # Gated DeltaNet mixers
    gdn_key_heads: int = 2
    gdn_value_heads: int = 4
    gdn_key_dim: int = 16
    gdn_value_dim: int = 16
    conv_kernel: int = 4
    chunk: int = 16
    gdn_groups: int = 1
    # gated attention
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    rotary_dim: int = 8
    rope_theta: float = 1e7
    # expert layer: the router's width, the experts held here, the sizes
    n_experts: int = 16
    experts_held: Tuple[int, ...] = tuple(range(16))
    top_k: int = 4
    moe_ff: int = 32
    shared_ff: int = 32
    # numerics and memory, as TransformerConfig's fields of these names
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"
    use_flash: Optional[bool] = None
    flash_block: Optional[int] = None
    logits_bf16: bool = False
    loss_chunk: int = 0
    # the axis fields build_train_step reads; no layer here is split
    # over one, and the step builders refuse a bound one by ``layouts``
    tp_axis: Optional[str] = None
    sp_axis: Optional[str] = None
    ep_axis: Optional[str] = None

    # what build_train_step / build_pipeline_train_step may do with it
    layouts = ("dp",)

    def __post_init__(self):
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        if self.n_layers < 1 or self.full_attention_interval < 1:
            raise ValueError("n_layers and full_attention_interval must "
                             "be at least 1")
        self.check_gdn()
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads ({self.n_heads}) must be a multiple of "
                f"n_kv_heads ({self.n_kv_heads})")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"rotary_dim ({self.rotary_dim}) must be even and at most "
                f"head_dim ({self.head_dim})")
        held = self.experts_held
        if (not held or len(set(held)) != len(held)
                or min(held) < 0 or max(held) >= self.n_experts):
            raise ValueError(
                f"experts_held {held} must be distinct ids out of "
                f"{self.n_experts}")
        if not 0 < self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} out of "
                             f"{self.n_experts} experts")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy must be 'full' or 'dots', got "
                f"{self.remat_policy!r}")
        if self.loss_chunk < 0:
            raise ValueError(
                f"loss_chunk must be >= 0, got {self.loss_chunk}")

    @property
    def mixers(self) -> str:
        """One character a layer: ``A`` full attention, ``D`` a Gated
        DeltaNet mixer."""
        return "".join(
            "A" if (i + 1) % self.full_attention_interval == 0 else "D"
            for i in range(self.n_layers))

    # the door build_train_step comes through
    def init_params(self, rng):
        return init_params(self, rng)

    def param_specs(self):
        return param_specs(self)

    def loss_fn(self, params, tokens, targets):
        return loss_fn(params, tokens, targets, self)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

_dense = gdn.dense


def _init_gdn(cfg, key):
    return {"norm": jnp.zeros((cfg.d_model,), jnp.float32),
            **gdn.init_gdn(cfg, key)}


def _init_attn(cfg, key):
    d, hd = cfg.d_model, cfg.head_dim
    k = jax.random.split(key, 4)
    return {
        "norm": jnp.zeros((d,), jnp.float32),
        # columns by query head: the head's query, then its gate
        "wq": _dense(k[0], (d, cfg.n_heads * 2 * hd), d),
        "wk": _dense(k[1], (d, cfg.n_kv_heads * hd), d),
        "wv": _dense(k[2], (d, cfg.n_kv_heads * hd), d),
        "q_norm": jnp.zeros((hd,), jnp.float32),
        "k_norm": jnp.zeros((hd,), jnp.float32),
        "wo": _dense(k[3], (cfg.n_heads * hd, d), cfg.n_heads * hd),
    }


def _init_moe(cfg, key):
    d, f, sf = cfg.d_model, cfg.moe_ff, cfg.shared_ff
    held = len(cfg.experts_held)
    k = jax.random.split(key, 6)
    return {
        "norm": jnp.zeros((d,), jnp.float32),
        "router": _dense(k[0], (d, cfg.n_experts), d),
        # columns: the gate's, then the up projection's
        "w_in": _dense(k[1], (held, d, 2 * f), d),
        "w_out": _dense(k[2], (held, f, d), f),
        "shared_in": _dense(k[3], (d, 2 * sf), d),
        "shared_out": _dense(k[4], (sf, d), sf),
        "shared_gate": _dense(k[5], (d,), d),
    }


def init_params(cfg: Qwen3NextConfig, rng):
    """GLOBAL parameters (every leaf replicated under ``param_specs``),
    the program's own seeded initialisation."""
    keys = jax.random.split(rng, 2 * cfg.n_layers + 2)
    mixer = {"D": _init_gdn, "A": _init_attn}
    return {
        "embed": jax.random.normal(keys[-2], (cfg.vocab, cfg.d_model),
                                   jnp.float32),
        "head": _dense(keys[-1], (cfg.vocab, cfg.d_model), cfg.d_model),
        "norm_f": jnp.zeros((cfg.d_model,), jnp.float32),
        "layers": [{"mixer": mixer[kind](cfg, keys[2 * i]),
                    "moe": _init_moe(cfg, keys[2 * i + 1])}
                   for i, kind in enumerate(cfg.mixers)],
    }


def param_specs(cfg: Qwen3NextConfig):
    """Every leaf replicated: ``dp`` shards the batch, not the model."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(lambda _: P(), shapes)


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

_rmsnorm32 = gdn.rmsnorm32


def _norm(x, w, eps):
    """The zero-centred RMSNorm: the weight is held as ``scale - 1``."""
    return _rmsnorm32(x, 1.0 + w, eps).astype(x.dtype)


def _recurrence(*args, **kwargs):
    """``delta_rule`` as THIS module holds it when the layer is traced
    (``gated_deltanet``'s docstring says why)."""
    return delta_rule(*args, **kwargs)


def _gdn_layer(params, x, cfg: Qwen3NextConfig):
    with jax.named_scope("hvd_gdn"):
        u = _norm(x, params["norm"], cfg.eps)
        return x + gdn.gdn_mixer(params, u, cfg, _recurrence)


def _rotary(x, cfg: Qwen3NextConfig):
    """Rotary positions on the first ``rotary_dim`` of each head of
    ``x`` ``[B, S, H, head_dim]``, half-split form: dim ``j`` pairs
    with ``j + rotary_dim / 2``, ``inv_freq_j = theta^(-2j /
    rotary_dim)``; the other dims pass."""
    half = cfg.rotary_dim // 2
    inv_freq = cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = (t[None, :, None, :] for t in (jnp.cos(angle), jnp.sin(angle)))
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:cfg.rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin,
         x32[..., cfg.rotary_dim:]], axis=-1).astype(x.dtype)


def _output_gate(attn, gate):
    """The attention's output under the sigmoid of the gate its query
    projection emitted, per head and dim."""
    return (attn.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(attn.dtype)


def _attn_layer(params, x, cfg: Qwen3NextConfig):
    dt_, hd = cfg.dtype, cfg.head_dim
    with jax.named_scope("hvd_attn"):
        u = _norm(x, params["norm"], cfg.eps)
        b, s, _ = u.shape
        qg = (u @ params["wq"].astype(dt_)).reshape(b, s, cfg.n_heads, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = (u @ params["wk"].astype(dt_)).reshape(b, s, cfg.n_kv_heads, hd)
        v = (u @ params["wv"].astype(dt_)).reshape(b, s, cfg.n_kv_heads, hd)
        q = _rotary(_norm(q, params["q_norm"], cfg.eps), cfg)
        k = _rotary(_norm(k, params["k_norm"], cfg.eps), cfg)
        # K and V are broadcast to the query heads OUTSIDE the kernel,
        # as the hybrid model does: the flash kernels run at
        # [B * n_heads, S, head_dim] as they are.
        rep = cfg.n_heads // cfg.n_kv_heads
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        attn = _output_gate(tfm.local_attention(q, k, v, cfg), gate)
        return x + attn.reshape(b, s, cfg.n_heads * hd) \
            @ params["wo"].astype(dt_)


def route(u, router, cfg: Qwen3NextConfig):
    """Softmax top-k routing over ALL experts, in float32: ``idx``
    ``[T, top_k]``, the experts each token chose, and ``weight`` ``[T,
    top_k]``, the chosen scores over their sum. The choices carry a
    checkpoint name (``HELD_NAMES``); logits and scores are recomputed."""
    scores = jax.nn.softmax(jnp.dot(
        u.astype(jnp.float32), router, precision=lax.Precision.HIGHEST),
        axis=-1)
    _, idx = lax.top_k(scores, cfg.top_k)
    idx = checkpoint_name(idx, HELD_NAMES[1])
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, picked / picked.sum(-1, keepdims=True)


def buffer_rows(tokens: int, cfg: Qwen3NextConfig) -> int:
    """Rows of the sorted buffer: the worst case, every token's every
    choice a held expert, in whole tiles."""
    pairs = tokens * min(cfg.top_k, len(cfg.experts_held))
    return -(-pairs // gmm.TILE) * gmm.TILE


def plan_rows(idx, weight, cfg: Qwen3NextConfig):
    """Where each (token, choice) pair lies in the buffer of rows
    sorted by held expert, from the choices alone, all of it integers
    (and one float that is not differentiated) of static shape:

    - ``row_token`` ``[rows]``: the token whose copy each row is (the
      held experts' pairs first, by expert and then by pair; the pairs
      of experts not held after them, and beyond the buffer's end never
      made);
    - ``row_weight`` ``[rows]`` float32: the pair's weight, 0 for a row
      that is not real;
    - ``pair_row`` ``[T, top_k]``: the row of each pair, and ``hit``
      whether its expert is held here;
    - ``group_sizes`` ``[held]``: the rows each held expert got."""
    t, k = idx.shape
    held = jnp.asarray(cfg.experts_held, idx.dtype)
    n_held = held.shape[0]
    match = idx[..., None] == held                        # [T, k, held]
    hit = match.any(-1)
    local = jnp.where(hit, jnp.argmax(match, -1), n_held).reshape(-1)
    pairs = t * k
    bits = max(1, (pairs - 1).bit_length())
    if (n_held + 1) << bits >= 2 ** 31:
        raise ValueError("too many (token, choice) pairs for one int32 key")
    pair = jnp.arange(pairs, dtype=jnp.int32)
    # ONE sort of (held expert, pair) as one integer: held experts are
    # few, so the key fits beside the pair's index
    key = (local.astype(jnp.int32) << bits) | pair
    w_flat = lax.stop_gradient(jnp.where(hit, weight, 0.0)).reshape(-1)
    key, row_weight = lax.sort((key, w_flat), num_keys=1)
    order = key & ((1 << bits) - 1)                       # row -> pair
    _, pair_row = lax.sort((order, pair), num_keys=1)     # pair -> row
    group_sizes = (local[None, :] == jnp.arange(n_held)[:, None]).sum(1)
    rows = buffer_rows(t, cfg)
    row_token, row_weight = (
        jnp.pad(x, (0, rows - pairs)) if rows > pairs else x[:rows]
        for x in (order // k, row_weight))
    return (checkpoint_name(row_token, HELD_NAMES[2]),
            checkpoint_name(row_weight, HELD_NAMES[3]),
            checkpoint_name(jnp.minimum(pair_row, rows - 1).reshape(t, k),
                            HELD_NAMES[4]),
            hit,
            checkpoint_name(group_sizes.astype(jnp.int32), HELD_NAMES[5]))


@jax.custom_vjp
def _to_rows(u, row_token, pair_row, hit):
    """``u`` ``[T, d]`` copied into the buffer: row ``r`` is token
    ``row_token[r]``. Its backward is the gather the other way (each
    token sums the rows of its held pairs), not a scatter."""
    return u[row_token]


def _to_rows_fwd(u, row_token, pair_row, hit):
    return u[row_token], (pair_row, hit)


def _to_rows_bwd(res, d_rows):
    pair_row, hit = res
    d_u = jnp.where(hit[..., None], d_rows[pair_row], 0).sum(
        1, dtype=jnp.float32)
    return d_u.astype(d_rows.dtype), None, None, None


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@jax.custom_vjp
def _from_rows(y, weight, row_token, row_weight, pair_row, hit):
    """The weighted sum back to the tokens: ``out[t] = sum_k weight[t,
    k] y[pair_row[t, k]]`` over the held pairs, float32. Its backward
    towards the rows is again a gather: row ``r`` gets ``row_weight[r]``
    times its token's cotangent (0 for a row that is not real)."""
    picked = jnp.where(hit[..., None], y[pair_row], 0).astype(jnp.float32)
    return (picked * weight[..., None]).sum(1)


def _from_rows_fwd(y, weight, row_token, row_weight, pair_row, hit):
    return (_from_rows(y, weight, row_token, row_weight, pair_row, hit),
            (y, row_token, row_weight, pair_row, hit))


def _from_rows_bwd(res, d_out):
    y, row_token, row_weight, pair_row, hit = res
    # ONE gather of the tokens' cotangents serves both: the rows' own
    # cotangent and, row by row against y, the weights'
    d_rows = d_out.astype(y.dtype)[row_token]
    d_y = (d_rows * row_weight[:, None]).astype(y.dtype)
    d_row_weight = (y.astype(jnp.float32) * d_rows).sum(-1)
    d_weight = jnp.where(hit, d_row_weight[pair_row], 0.0)
    return d_y, d_weight, None, None, None, None


_from_rows.defvjp(_from_rows_fwd, _from_rows_bwd)


def _gated(h):
    """``silu(gate) * up`` of ``h = [gate | up]``."""
    f = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :f].astype(jnp.float32))
            * h[..., f:].astype(jnp.float32)).astype(h.dtype)


def routed_experts(u, idx, weight, w_in, w_out, cfg: Qwen3NextConfig):
    """The held experts' terms ``sum_e weight_e W_out,e (silu(W_gate,e
    u) * W_up,e u)`` of ``u`` ``[T, d]`` over rows sorted by expert;
    float32 ``[T, d]``."""
    with jax.named_scope("hvd_moe_dispatch"):
        row_token, row_weight, pair_row, hit, sizes = plan_rows(
            idx, weight, cfg)
        lay = gmm.layout(sizes, row_token.shape[0])
        rows = _to_rows(u, row_token, pair_row, hit)
    y = gmm.grouped_matmul(
        _gated(gmm.grouped_matmul(rows, w_in, lay)), w_out, lay)
    with jax.named_scope("hvd_moe_dispatch"):
        return _from_rows(y, weight, row_token, row_weight, pair_row, hit)


def shared_expert(params, u, cfg: Qwen3NextConfig):
    """The shared expert of ``u`` ``[T, d]`` under its per-token
    sigmoid gate; float32 ``[T, d]``."""
    dt_ = cfg.dtype
    gate = jax.nn.sigmoid(
        (u @ params["shared_gate"].astype(dt_)[:, None]).astype(jnp.float32))
    shared = (_gated(u @ params["shared_in"].astype(dt_))
              @ params["shared_out"].astype(dt_))
    return gate * shared.astype(jnp.float32)


def _moe_layer(params, x, cfg: Qwen3NextConfig):
    dt_ = cfg.dtype
    with jax.named_scope("hvd_moe"):
        u = _norm(x, params["norm"], cfg.eps)
        b, s, d = u.shape
        u = u.reshape(b * s, d)
        with jax.named_scope("hvd_moe_router"):
            idx, weight = route(u, params["router"], cfg)
        out = routed_experts(u, idx, weight, params["w_in"],
                             params["w_out"], cfg)
        with jax.named_scope("hvd_moe_shared"):
            out = out + shared_expert(params, u, cfg)
        return x + out.astype(dt_).reshape(b, s, d), idx


_MIXER = {"D": _gdn_layer, "A": _attn_layer}


def _embed(params, tokens, cfg):
    with jax.named_scope("hvd_embed"):
        return params["embed"][tokens].astype(cfg.dtype)


def loss_and_choices(params, tokens, targets, cfg: Qwen3NextConfig):
    """Next-token cross-entropy, mean over the local tokens (the
    flagship's chunked loss head over the untied ``head``), and what
    ``route`` chose in every expert layer on the way,
    ``[layers, B * S, top_k]`` expert ids (a real row is an id among
    ``experts_held``): the probe behind the count of rows the held
    experts really get, as an auxiliary output of the one forward pass
    (``jax.value_and_grad(..., has_aux=True)``)."""
    x = _embed(params, tokens, cfg)
    # a name that a kind of layer does not make is inert in its policy
    blocks = {kind: tfm.remat_block(cfg, fn, static_argnums=(2,),
                                    names=HELD_NAMES)
              for kind, fn in dict(_MIXER, moe=_moe_layer).items()}
    choices = []
    for kind, layer in zip(cfg.mixers, params["layers"]):
        x = blocks[kind](layer["mixer"], x, cfg)
        x, idx = blocks["moe"](layer["moe"], x, cfg)
        choices.append(idx)
    with jax.named_scope("hvd_loss_head"):
        h = _norm(x, params["norm_f"], cfg.eps)
    return (tfm.nll_from_hidden(params["head"], h, targets, cfg),
            jnp.stack(choices))


def loss_fn(params, tokens, targets, cfg: Qwen3NextConfig):
    """The loss alone: what the step differentiates (the choices are
    dead code there)."""
    return loss_and_choices(params, tokens, targets, cfg)[0]


def routing_choices(params, tokens, cfg: Qwen3NextConfig):
    """The choices alone, for a caller without targets; not part of the
    step."""
    return loss_and_choices(params, tokens, tokens, cfg)[1]
