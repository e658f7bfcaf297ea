"""A hybrid decoder in the ``nemotron_h`` shape: every layer is ONE
mixer or ONE feed-forward part, chosen by a per-layer pattern —
``M`` a Mamba-2 mixer, ``*`` grouped-query attention, ``E`` a latent
mixture of experts with a shared expert — each as
``x <- x + f(RMSNorm(x))``, then a final RMSNorm and an untied head.

It trains through ``parallel.train.build_train_step`` by the same door
as the flagship (``cfg.param_specs()``, ``cfg.loss_fn(...)``, the axis
fields, ``layouts``) and shares with it the chunked loss head,
``remat_block``, the choice between the flash kernels and XLA attention
and the kernels themselves. Data parallelism alone: ``layouts`` lists
'dp' only, and the step builders refuse a bound ``tp``/``sp``/``ep``
axis and the pipeline step by it.

The expert layer is ONE CHIP'S SHARE of an expert-parallel layer
(model-configs guide, section 4): it is told which experts it holds
(``experts_held``, ids out of ``n_routed_experts``), routes every token
over ALL experts at the published width, and adds only its own experts'
terms; what the absent experts would add is left out, and nothing
stands in for their chips or the exchange. It is dropless: a token
chooses an expert at most once, so one row a token and held expert
holds every pair whatever the routing. ``routed_experts`` computes
exactly that worst case DENSELY: every token under every held expert,
an unchosen pair at weight 0, about 23 times the chosen pairs' work at
uniform routing. That is a stand-in, not what a worker of the
deployment would run: a grouped matmul over rows sorted by expert that
skips the rows not chosen (``lax.ragged_dot``) is the ``perf_opt`` that
replaces it (PERF.md section 7 (8)).

Every layer runs under ``remat_block``'s checkpoint, and that
checkpoint HOLDS the few arrays of a layer that are small beside what
recomputing them costs (``HELD_NAMES``, named where they are made): the
output of the mixer's loop over its groups, so the groups run twice a
step and not three times (``_mamba_layer``), and the router's choices,
logits and chosen scores, so ``top_k``, the float32 matmul and the
gather run once (``route``). Everything else is recomputed in the
backward; the arithmetic is the same with the names held, with none,
and with no checkpoint at all.

Not built: multi-token prediction (the auxiliary next-token head),
serving state for the recurrent layers, the ``ep`` exchange.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops.ssd_scan import causal_conv1d, ssd_scan
from . import transformer as tfm

LAYER_KINDS = ("M", "*", "E")

# Checkpoint names of what a layer's checkpoint HOLDS for the backward
# (``remat_block(names=)``): the few intermediate arrays that are small
# beside what recomputing them costs. At the published widths and 4096
# tokens, a layer: the groups' output, 67 MB for a second run of every
# group's convolution, scan and gated norm; the router's choices, logits
# and chosen scores, 0.4 + 8.4 + 0.4 MB for a top-22-of-512, a
# HIGHEST-precision matmul and a gather. Everything else is recomputed.
# Weighed and NOT held, because with them the full-depth step no longer
# compiles without more of the compiler's own rematerializations than
# before (PERF.md section 6, PR 34): the shared expert's pre-activation
# (44 MB a layer; on the chip holding it LOST 0.7%) and, under
# ``"full"``, the flash kernel's ``RESIDUAL_NAMES`` (34 MB).
HELD_NAMES = ("hvd_ssm_y", "hvd_moe_router_idx", "hvd_moe_router_logits",
              "hvd_moe_router_picked")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab: int = 1024
    d_model: int = 256
    pattern: str = "ME*E"            # one character a layer
    eps: float = 1e-5
    # M: Mamba-2 mixer (d_inner = mamba_heads * mamba_head_dim)
    mamba_heads: int = 8
    mamba_head_dim: int = 16
    mamba_groups: int = 2
    state_size: int = 16
    conv_kernel: int = 4
    chunk: int = 16
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # *: grouped-query attention, no position term
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    # E: the router's width, the experts held here, and the expert sizes
    n_routed_experts: int = 16
    experts_held: Tuple[int, ...] = tuple(range(16))
    top_k: int = 4
    routed_scaling: float = 1.0
    moe_latent: int = 64
    moe_ff: int = 96
    shared_ff: int = 128
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
        bad = sorted(set(self.pattern) - set(LAYER_KINDS))
        if bad or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: a layer is one of "
                             f"{LAYER_KINDS}, got {bad}")
        if self.mamba_heads % self.mamba_groups:
            raise ValueError(
                f"mamba_heads ({self.mamba_heads}) must divide into "
                f"mamba_groups ({self.mamba_groups})")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads ({self.n_heads}) must be a multiple of "
                f"n_kv_heads ({self.n_kv_heads})")
        held = self.experts_held
        if (not held or len(set(held)) != len(held)
                or min(held) < 0 or max(held) >= self.n_routed_experts):
            raise ValueError(
                f"experts_held {held} must be distinct ids out of "
                f"{self.n_routed_experts}")
        if not 0 < self.top_k <= self.n_routed_experts:
            raise ValueError(f"top_k {self.top_k} out of "
                             f"{self.n_routed_experts} experts")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy must be 'full' or 'dots', got "
                f"{self.remat_policy!r}")
        if self.loss_chunk < 0:
            raise ValueError(
                f"loss_chunk must be >= 0, got {self.loss_chunk}")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_groups * self.state_size

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

def _dense(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def _init_mamba(cfg, key):
    d, di, h = cfg.d_model, cfg.d_inner, cfg.mamba_heads
    k = jax.random.split(key, 6)
    # dt log-uniform in [time_step_min, time_step_max], floored; the
    # bias is its inverse softplus (the Mamba-2 scheme)
    dt = jnp.exp(jax.random.uniform(k[2], (h,), jnp.float32)
                 * (math.log(cfg.time_step_max)
                    - math.log(cfg.time_step_min))
                 + math.log(cfg.time_step_min))
    dt = jnp.maximum(dt, cfg.time_step_floor)
    return {
        "norm": jnp.ones((d,), jnp.float32),
        "in_proj": _dense(k[0], (d, di + cfg.conv_dim + h), d),
        "conv_w": _dense(k[1], (cfg.conv_dim, cfg.conv_kernel),
                         cfg.conv_kernel),
        "conv_b": 0.1 * jax.random.normal(k[5], (cfg.conv_dim,),
                                          jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k[3], (h,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((h,), jnp.float32),
        "gate_norm": jnp.ones((di,), jnp.float32),
        "out_proj": _dense(k[4], (di, d), di),
    }


def _init_attn(cfg, key):
    d, hd = cfg.d_model, cfg.head_dim
    k = jax.random.split(key, 4)
    return {
        "norm": jnp.ones((d,), jnp.float32),
        "wq": _dense(k[0], (d, cfg.n_heads * hd), d),
        "wk": _dense(k[1], (d, cfg.n_kv_heads * hd), d),
        "wv": _dense(k[2], (d, cfg.n_kv_heads * hd), d),
        "wo": _dense(k[3], (cfg.n_heads * hd, d), cfg.n_heads * hd),
    }


def _init_moe(cfg, key):
    d, lat, f, sf = cfg.d_model, cfg.moe_latent, cfg.moe_ff, cfg.shared_ff
    held = len(cfg.experts_held)
    k = jax.random.split(key, 7)
    return {
        "norm": jnp.ones((d,), jnp.float32),
        "router": _dense(k[0], (d, cfg.n_routed_experts), d),
        # a buffer, not trained: its gradient is stopped, and from zero
        # AdamW's update of it is exactly zero (the source's balance
        # rule that moves it is not in config.json)
        "b_corr": jnp.zeros((cfg.n_routed_experts,), jnp.float32),
        "down": _dense(k[1], (d, lat), d),
        "up": _dense(k[2], (lat, d), lat),
        "w1": _dense(k[3], (held, lat, f), lat),
        "w2": _dense(k[4], (held, f, lat), f),
        "shared_in": _dense(k[5], (d, sf), d),
        "shared_out": _dense(k[6], (sf, d), sf),
    }


_INIT = {"M": _init_mamba, "*": _init_attn, "E": _init_moe}


def init_params(cfg: NemotronHConfig, rng):
    """GLOBAL parameters (every leaf replicated under ``param_specs``),
    the program's own seeded initialisation."""
    keys = jax.random.split(rng, len(cfg.pattern) + 2)
    return {
        "embed": jax.random.normal(keys[-2], (cfg.vocab, cfg.d_model),
                                   jnp.float32),
        "head": _dense(keys[-1], (cfg.vocab, cfg.d_model), cfg.d_model),
        "norm_f": jnp.ones((cfg.d_model,), jnp.float32),
        "layers": [_INIT[kind](cfg, keys[i])
                   for i, kind in enumerate(cfg.pattern)],
    }


def param_specs(cfg: NemotronHConfig):
    """Every leaf replicated: ``dp`` shards the batch, not the model."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(lambda _: P(), shapes)


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

def _rmsnorm(x, w, eps):
    """RMSNorm over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    x32 = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * w).astype(x.dtype)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _mamba_group(z, xbc, dt, conv_w, conv_b, dt_bias, a_log, d, gate_w,
                 cfg: NemotronHConfig):
    """One group of a Mamba-2 mixer between its projections: ``z``
    ``[B, S, d_inner / G]``, ``xbc`` ``[B, S, d_inner / G + 2 N]`` (the
    group's x, B and C before the convolution), ``dt`` ``[B, S, H / G]``
    and the group's slices of the layer's parameters. Returns the gated,
    normalised ``y`` ``[B, S, d_inner / G]``."""
    b, s, width = z.shape
    p, n = cfg.mamba_head_dim, cfg.state_size
    with jax.named_scope("hvd_ssm_conv"):
        xbc = jax.nn.silu(causal_conv1d(xbc, conv_w, conv_b))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
    y = ssd_scan(xbc[..., :width].reshape(b, s, width // p, p), dt,
                 -jnp.exp(a_log), xbc[..., width:width + n],
                 xbc[..., width + n:], d, chunk=cfg.chunk)
    return _rmsnorm(y.reshape(b, s, width) * jax.nn.silu(z), gate_w,
                    cfg.eps)


def _mamba_layer(params, x, cfg: NemotronHConfig):
    """The groups share nothing between the two projections (the gated
    norm is over each group's channels too). So ``in_proj`` writes its
    output group by group (an einsum batched over the groups, no copy
    to regroup it), and the groups are computed one after another
    (``lax.map``), each under its own ``jax.checkpoint``: the backward
    then holds ONE group's convolution, ``[chunks, heads, Q, Q]`` decay
    matrices, chunk states and float32 norm, not eight (at 8192 tokens
    of 128 heads x 64 x 128 the chunk states alone are 268 MB in
    float32, several times over).

    The map's output ``y`` carries a checkpoint name, and the layer's
    checkpoint holds it (``HELD_NAMES``; ``[G, B, S, d_inner / G]``, 67
    MB a layer at the published widths and 4096 tokens). The two
    checkpoints nest: without ``y`` the layer's backward first reruns
    the whole forward, all groups included, only to have ``y`` for
    ``out_proj``'s weight gradient, and then the map's backward reruns
    each group again for its own residuals: three runs of convolution,
    scan and gated norm a step. With ``y`` held the first rerun is dead
    code, and the groups run TWICE a step: forward, and once a group in
    the backward."""
    dt_, d, di, g = cfg.dtype, cfg.d_model, cfg.d_inner, cfg.mamba_groups
    gn = g * cfg.state_size

    def xbc_by_group(t, axis):
        """``t``'s ``axis`` holds the channels ``x | B | C``: split it
        in two, ``[G, x_g | B_g | C_g]``."""
        return jnp.concatenate(
            [part.reshape(part.shape[:axis] + (g, -1)
                          + part.shape[axis + 1:])
             for part in jnp.split(t, [di, di + gn], axis=axis)],
            axis=axis + 1)

    with jax.named_scope("hvd_ssm"):
        u = _rmsnorm(x, params["norm"], cfg.eps)
        w_in = params["in_proj"].astype(dt_)
        z = jnp.einsum("bsd,dgw->gbsw", u,
                       w_in[:, :di].reshape(d, g, -1))
        xbc = jnp.einsum(
            "bsd,dgw->gbsw", u,
            xbc_by_group(w_in[:, di:di + cfg.conv_dim], 1))
        dt = jnp.moveaxis(
            (u @ w_in[:, di + cfg.conv_dim:]).reshape(
                u.shape[:2] + (g, -1)), 2, 0)
        group = jax.checkpoint(
            lambda args: _mamba_group(*args, cfg))
        y = checkpoint_name(lax.map(group, (
            z, xbc, dt,
            xbc_by_group(params["conv_w"], 0),
            xbc_by_group(params["conv_b"], 0),
            params["dt_bias"].reshape(g, -1), params["A_log"].reshape(g, -1),
            params["D"].reshape(g, -1), params["gate_norm"].reshape(g, -1))),
            HELD_NAMES[0])
        return x + jnp.einsum(
            "gbsw,gwd->bsd", y,
            params["out_proj"].astype(dt_).reshape(g, -1, d))


def _attn_layer(params, x, cfg: NemotronHConfig):
    dt_, hd = cfg.dtype, cfg.head_dim
    with jax.named_scope("hvd_attn"):
        u = _rmsnorm(x, params["norm"], cfg.eps)
        b, s, _ = u.shape
        q = (u @ params["wq"].astype(dt_)).reshape(b, s, cfg.n_heads, hd)
        k = (u @ params["wk"].astype(dt_)).reshape(b, s, cfg.n_kv_heads, hd)
        v = (u @ params["wv"].astype(dt_)).reshape(b, s, cfg.n_kv_heads, hd)
        # K and V are broadcast to the query heads OUTSIDE the kernel:
        # the flash kernels run at [B * n_heads, S, head_dim] as they
        # are, and their gradients sum back over each group here.
        rep = cfg.n_heads // cfg.n_kv_heads
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        attn = tfm.local_attention(q, k, v, cfg)
        attn = attn.reshape(b, s, cfg.n_heads * hd)
        return x + attn @ params["wo"].astype(dt_)


def route(u, router, b_corr, cfg: NemotronHConfig):
    """Sigmoid top-k routing over ALL experts, in float32: returns
    ``idx`` ``[T, top_k]``, the experts each token chose, and for the
    experts held here ``weight`` ``[held, T]`` (the chosen scores
    without the correction, over their sum, times the scaling; 0 where
    not chosen). Tokens lie on the last axis: 8 experts there would be
    padded to a tile of 128. ``idx``, the router's logits and the
    chosen scores carry checkpoint names (``HELD_NAMES``): the backward
    of the weights needs all three, and a layer that holds them runs
    neither ``top_k``, the matmul nor the gather again. (The LOGITS,
    not the scores: the sigmoid's derivative reads the sigmoid's own
    output, which a name put after it would not cover, and the matmul
    would run again to feed it.)"""
    scores = jax.nn.sigmoid(checkpoint_name(jnp.dot(
        u.astype(jnp.float32), router, precision=lax.Precision.HIGHEST),
        HELD_NAMES[2]))
    _, idx = lax.top_k(scores + lax.stop_gradient(b_corr), cfg.top_k)
    idx = checkpoint_name(idx, HELD_NAMES[1])
    picked = checkpoint_name(
        jnp.take_along_axis(scores, idx, axis=-1), HELD_NAMES[3])  # [T, k]
    picked = picked / picked.sum(-1, keepdims=True) * cfg.routed_scaling
    held = jnp.asarray(cfg.experts_held, idx.dtype)
    hit = idx.T[:, None, :] == held[None, :, None]              # [k,held,T]
    return idx, (picked.T[:, None, :] * hit).sum(0)


def routed_experts(h, weight, w1, w2):
    """The held experts' terms, ``sum_e weight[e] * relu(h W1_e)^2
    W2_e``, with ONE row a token and held expert — the worst case,
    which holds any routing, so nothing is ever dropped and the step's
    time does not depend on the routing. At that row count rows sorted
    by expert are every token under every expert, and the grouped
    matmuls are two plain ones: ``h`` against the experts' ``W1`` side
    by side, the gated activations against their ``W2`` stacked (the
    sum over experts is the contraction). A pair not chosen has weight
    0. ``h`` ``[T, latent]``, ``weight`` ``[held, T]`` float32."""
    held, lat, ff = w1.shape
    t = h.shape[0]
    a = h @ w1.astype(h.dtype).swapaxes(0, 1).reshape(lat, held * ff)
    a = _relu2(a).reshape(t, held, ff) * weight.T[..., None]
    return (a.reshape(t, held * ff).astype(h.dtype)
            @ w2.astype(h.dtype).reshape(held * ff, lat))


def _moe_layer(params, x, cfg: NemotronHConfig):
    dt_ = cfg.dtype
    with jax.named_scope("hvd_moe"):
        u = _rmsnorm(x, params["norm"], cfg.eps)
        b, s, d = u.shape
        u = u.reshape(b * s, d)
        with jax.named_scope("hvd_moe_router"):
            _, weight = route(u, params["router"], params["b_corr"], cfg)
        h = u @ params["down"].astype(dt_)
        with jax.named_scope("hvd_moe_routed"):
            r = routed_experts(h, weight, params["w1"], params["w2"])
        out = r @ params["up"].astype(dt_)
        with jax.named_scope("hvd_moe_shared"):
            out = out + (_relu2(u @ params["shared_in"].astype(dt_))
                         @ params["shared_out"].astype(dt_))
        return x + out.reshape(b, s, d)


_LAYER = {"M": _mamba_layer, "*": _attn_layer, "E": _moe_layer}


def _embed(params, tokens, cfg):
    with jax.named_scope("hvd_embed"):
        return params["embed"][tokens].astype(cfg.dtype)


def loss_fn(params, tokens, targets, cfg: NemotronHConfig):
    """Next-token cross-entropy, mean over the local tokens (the
    flagship's chunked loss head over the untied ``head``)."""
    x = _embed(params, tokens, cfg)
    # a name that a kind of layer does not make is inert in its policy
    blocks = {kind: tfm.remat_block(cfg, fn, static_argnums=(2,),
                                    names=HELD_NAMES)
              for kind, fn in _LAYER.items()}
    for kind, layer in zip(cfg.pattern, params["layers"]):
        x = blocks[kind](layer, x, cfg)
    with jax.named_scope("hvd_loss_head"):
        h = _rmsnorm(x, params["norm_f"], cfg.eps)
    return tfm.nll_from_hidden(params["head"], h, targets, cfg)


def routing_choices(params, tokens, cfg: NemotronHConfig):
    """The probe behind the count of (token, held expert) pairs: a
    forward pass that returns what ``route`` chose on every expert
    layer's input, ``[expert layers, B * S, top_k]`` expert ids (a
    pair is an id among ``experts_held``). Not part of the step."""
    x = _embed(params, tokens, cfg)
    out = []
    for kind, layer in zip(cfg.pattern, params["layers"]):
        if kind == "E":
            u = _rmsnorm(x, layer["norm"], cfg.eps)
            out.append(route(u.reshape(-1, u.shape[-1]), layer["router"],
                             layer["b_corr"], cfg)[0])
        x = _LAYER[kind](layer, x, cfg)
    return jnp.stack(out)
