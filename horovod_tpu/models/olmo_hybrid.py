"""A dense decoder in the ``olmo_hybrid`` shape: every layer is a token
mixer and then a gated MLP, each normed on its OUTPUT, before the
residual add (the Olmo 2 / Olmo 3 family's post-norm),

    h = x + RMS(Mixer_i(x); w1_i)     Mixer_i by layer_types[i]
    y = h + RMS(MLP(h);     w2_i)     MLP(h) = W_down (silu(W_gate h) * W_up h)

with ``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w`` (``w`` starts at 1),
no bias anywhere, a final norm and an untied head.

- ``full_attention``: ``q = RMS(W_q x; w_q)``, ``k = RMS(W_k x; w_k)``,
  each norm over ALL columns of the projection, before the split into
  heads; ``v = W_v x``; causal softmax attention at ``head_dim ** -1/2``,
  as many key/value heads as query heads, the flash kernels or XLA's by
  ``flash_choice``; ``W_o``. NO position term: the source's
  ``rope_theta`` is null, and the linear-attention layers order the
  sequence.
- ``linear_attention``: ``models/gated_deltanet.py``'s mixer, which
  ``models/qwen3_next.py`` shares, on the layer's input as it is: one
  value head a key head (``dk`` 96, ``dv`` 192 as published), and a
  write strength ``beta = 2 sigmoid(W_b x)`` (``allow_neg_eigval``:
  the transition ``exp(g)(I - beta k k^T)`` has eigenvalues down to
  -1). The delta-rule recurrence is ``ops/delta_rule.py``'s as THIS
  module holds it (``_recurrence``).

It trains through ``parallel.train.build_train_step`` by the door the
other models use (``cfg.param_specs()``, ``cfg.loss_fn(...)``, the axis
fields, ``layouts``), under 'dp' alone. Mixer and MLP each run under
``remat_block``'s checkpoint, which holds the output of a DeltaNet
mixer's loop over its head groups (``gated_deltanet.HELD_NAME``).

Not built: serving state for the recurrent layers, packed documents (a
reset of state and convolution at a boundary), the other stages of a
pipeline (``build_pipeline_train_step`` refuses the model by
``layouts``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.delta_rule import delta_rule
from . import gated_deltanet as gdn
from . import transformer as tfm

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(gdn.GdnFields):
    vocab: int = 1024
    d_model: int = 128
    # one entry a layer: ``linear_attention`` or ``full_attention``
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    d_ff: int = 256
    eps: float = 1e-6
    # Gated DeltaNet mixers (``gated_deltanet.GdnFields``)
    gdn_key_heads: int = 2
    gdn_value_heads: int = 2
    gdn_key_dim: int = 12
    gdn_value_dim: int = 24
    conv_kernel: int = 4
    chunk: int = 16
    gdn_groups: int = 1
    allow_neg_eigval: bool = True
    # full attention: ``n_heads`` heads of ``d_model / n_heads``
    n_heads: int = 4
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
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - {LINEAR, FULL}
        if not self.layer_types or unknown:
            raise ValueError(
                f"layer_types must be a non-empty sequence of {LINEAR!r} "
                f"and {FULL!r}, got {sorted(unknown) or 'none'}")
        self.check_gdn()
        if self.d_model % self.n_heads:
            raise ValueError(
                f"d_model ({self.d_model}) must be a multiple of n_heads "
                f"({self.n_heads})")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy must be 'full' or 'dots', got "
                f"{self.remat_policy!r}")
        if self.loss_chunk < 0:
            raise ValueError(
                f"loss_chunk must be >= 0, got {self.loss_chunk}")

    @property
    def gdn_beta_scale(self) -> float:
        return 2.0 if self.allow_neg_eigval else 1.0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

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

def _ones(n):
    return jnp.ones((n,), jnp.float32)


def _init_gdn(cfg, key):
    return {"norm": _ones(cfg.d_model), **gdn.init_gdn(cfg, key)}


def _init_attn(cfg, key):
    d = cfg.d_model
    k = jax.random.split(key, 4)
    return {"norm": _ones(d), "q_norm": _ones(d), "k_norm": _ones(d),
            **{name: gdn.dense(k[i], (d, d), d)
               for i, name in enumerate(("wq", "wk", "wv", "wo"))}}


def _init_mlp(cfg, key):
    d, f = cfg.d_model, cfg.d_ff
    k = jax.random.split(key, 2)
    return {"norm": _ones(d),
            # columns: the gate's, then the up projection's
            "w_in": gdn.dense(k[0], (d, 2 * f), d),
            "w_out": gdn.dense(k[1], (f, d), f)}


_INIT = {LINEAR: _init_gdn, FULL: _init_attn}


def init_params(cfg: OlmoHybridConfig, rng):
    """GLOBAL parameters (every leaf replicated under ``param_specs``),
    the program's own seeded initialisation. ``norm`` of a mixer or an
    MLP is the weight of the norm on ITS output."""
    n = len(cfg.layer_types)
    keys = jax.random.split(rng, 2 * n + 2)
    return {
        "embed": jax.random.normal(keys[-2], (cfg.vocab, cfg.d_model),
                                   jnp.float32),
        "head": gdn.dense(keys[-1], (cfg.vocab, cfg.d_model), cfg.d_model),
        "norm_f": _ones(cfg.d_model),
        "layers": [{"mixer": _INIT[kind](cfg, keys[2 * i]),
                    "mlp": _init_mlp(cfg, keys[2 * i + 1])}
                   for i, kind in enumerate(cfg.layer_types)],
    }


def param_specs(cfg: OlmoHybridConfig):
    """Every leaf replicated: ``dp`` shards the batch, not the model."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(lambda _: P(), shapes)


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

def _norm(x, w, eps):
    return gdn.rmsnorm32(x, w, eps).astype(x.dtype)


def _residual(x, w, eps, sublayer):
    """The post-norm residual: ``x + RMS(sublayer(x); w)``."""
    return x + _norm(sublayer(x), w, eps)


def _recurrence(*args, **kwargs):
    """``delta_rule`` as THIS module holds it when the layer is traced
    (``gated_deltanet``'s docstring says why)."""
    return delta_rule(*args, **kwargs)


def _gdn_layer(params, x, cfg: OlmoHybridConfig):
    with jax.named_scope("hvd_gdn"):
        return _residual(
            x, params["norm"], cfg.eps,
            lambda u: gdn.gdn_mixer(params, u, cfg, _recurrence))


def _qk_norm(q, k, params, cfg: OlmoHybridConfig):
    """``q``, ``k`` ``[B, S, d_model]`` normed over the WHOLE
    projection, before the split into heads."""
    return (_norm(q, params["q_norm"], cfg.eps),
            _norm(k, params["k_norm"], cfg.eps))


def _attn_layer(params, x, cfg: OlmoHybridConfig):
    dt_ = cfg.dtype

    def attention(u):
        b, s, d = u.shape
        q, k = _qk_norm(u @ params["wq"].astype(dt_),
                        u @ params["wk"].astype(dt_), params, cfg)
        v = u @ params["wv"].astype(dt_)
        q, k, v = (t.reshape(b, s, cfg.n_heads, cfg.head_dim)
                   for t in (q, k, v))
        attn = tfm.local_attention(q, k, v, cfg).reshape(b, s, d)
        return attn @ params["wo"].astype(dt_)

    with jax.named_scope("hvd_attn"):
        return _residual(x, params["norm"], cfg.eps, attention)


def _mlp_layer(params, x, cfg: OlmoHybridConfig):
    dt_, f = cfg.dtype, cfg.d_ff

    def mlp(u):
        h = u @ params["w_in"].astype(dt_)
        h = (jax.nn.silu(h[..., :f].astype(jnp.float32))
             * h[..., f:].astype(jnp.float32)).astype(dt_)
        return h @ params["w_out"].astype(dt_)

    with jax.named_scope("hvd_mlp"):
        return _residual(x, params["norm"], cfg.eps, mlp)


def loss_fn(params, tokens, targets, cfg: OlmoHybridConfig):
    """Next-token cross-entropy, mean over the local tokens: the
    flagship's chunked loss head over the untied ``head``."""
    with jax.named_scope("hvd_embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    # a name that a kind of layer does not make is inert in its policy
    blocks = {kind: tfm.remat_block(cfg, fn, static_argnums=(2,),
                                    names=(gdn.HELD_NAME,))
              for kind, fn in {LINEAR: _gdn_layer, FULL: _attn_layer,
                               "mlp": _mlp_layer}.items()}
    for kind, layer in zip(cfg.layer_types, params["layers"]):
        x = blocks[kind](layer["mixer"], x, cfg)
        x = blocks["mlp"](layer["mlp"], x, cfg)
    with jax.named_scope("hvd_loss_head"):
        h = _norm(x, params["norm_f"], cfg.eps)
    return tfm.nll_from_hidden(params["head"], h, targets, cfg)
