"""Flagship Transformer LM — the model that exercises every parallelism
axis the framework offers (dp / tp / sp / ep; pp via parallel.pipeline).

The reference carries no model code (SURVEY.md §2: "no model code"); this
is the TPU-native flagship used by __graft_entry__ and the long-context
benchmarks. Design:

  - Decoder-only pre-norm Transformer, GPT-style.
  - bf16 activations, fp32 params/layernorms, MXU-shaped matmuls.
  - Written shard_map-style: the *functional* apply takes the mesh axis
    names active for tensor ('tp') and sequence ('sp') parallelism; the
    attention runs ring attention when 'sp' is active.
  - Optional MoE MLP every other block over 'ep'.
  - ``jax.checkpoint`` (remat) around each block: HBM-for-FLOPs trade.

Parameters are created with plain ``init`` and sharded by
:func:`param_specs`, so jit-level code can use ordinary NamedSharding
constraint-based partitioning.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel.ring_attention import ring_attention, full_attention
from ..parallel.expert import moe_apply, moe_init


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    # TPU sizing: when n_heads is None it is derived as
    # max(1, d_model // 128) so head_dim == 128 — the MXU is 128 lanes
    # wide, and every attention matmul contracts over head_dim, so
    # head_dim 64 runs the systolic array half empty (every benchmark
    # cell runs head_dim 128; no cell measures 64). QKV projection
    # shapes are d_model x d_model whatever the head count, so a
    # checkpoint LOADS cleanly under another one but computes different
    # attention — pass n_heads explicitly when restoring a checkpoint
    # trained under an explicit head count.
    n_heads: Optional[int] = None
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16
    # parallelism axis names; None disables that axis
    tp_axis: Optional[str] = None
    sp_axis: Optional[str] = None
    ep_axis: Optional[str] = None
    # sequence-parallel attention: 'ring' (K/V ppermute ring, any head
    # count) or 'ulysses' (two all_to_alls, heads % sp_size == 0)
    sp_impl: str = "ring"
    # single-shard attention via the Pallas flash kernel
    # (ops/flash_attention.py) instead of XLA full attention. None (the
    # default) auto-selects by sequence length (flash_choice: compiled
    # flash from 1024 attended positions): XLA's attention holds the
    # [S, S] scores, which is cheap below about 1k positions and
    # quadratic above. Every benchmark cell sets it on; the kernels'
    # readings there are flash_roofline 35.6-36.8% at seq 2048 and
    # 49.6% at 16384 (PERF.md section 5; ledger, PR 32). The
    # threshold itself has no cell on either side of it.
    use_flash: Optional[bool] = None
    # Flash kernel block size (block_q == block_k, overriding EVERY
    # kernel). None = the tuned per-kernel defaults (fwd 1024x1024,
    # dkv 512x1024, dq 1024x512 compiled / 128 interpreted —
    # ops/flash_attention.py _default_block). Exposed for
    # long-sequence block sweeps — the optimum can shift with seq
    # length and head_dim (1024 everywhere intermittently fails to
    # compile at larger batch*heads: scoped vmem). Applies
    # to the single-shard and Ulysses paths; ring attention is its own
    # blockwise schedule (shard-sized blocks) and takes no flash block.
    flash_block: Optional[int] = None
    # MoE: when set, every other block's MLP is a top-1 MoE
    num_experts: int = 0
    capacity_factor: float = 2.0
    # jax.checkpoint around each block. Default ON (the safe choice for
    # long sequences / big models); when activations fit HBM, turn it
    # off: the 16k cell runs without it, and dots remat there costs
    # 10.7% of the step (PERF.md sections 4 and 6, PR 25).
    remat: bool = True
    # Checkpoint policy when remat is on: "full" recomputes everything;
    # "dots" saves what is dear to recompute and cheap to hold — matmul
    # outputs (jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    # and the flash kernel's output and row statistics (the checkpoint
    # names hvd_flash_out / hvd_flash_lse, ~17 MB a layer at 2 x 2048 x
    # 2048 beside ~150 MB of dots), so the backward recomputes only
    # elementwise ops and runs no kernel twice — the standard middle
    # ground that buys most of no-remat's speed at a fraction of its
    # memory.
    remat_policy: str = "full"
    # Compute the vocab-projection matmul in the activation dtype (bf16)
    # instead of fp32, casting to fp32 only for the softmax. The [d,V]
    # contraction is the single largest matmul in the model and fp32
    # runs the MXU at a fraction of its bf16 rate; loss numerics keep an
    # fp32 softmax either way. Off by default (bit-compatibility with
    # checkpointed logits).
    logits_bf16: bool = False
    # Chunked cross-entropy: compute the vocab projection + log-softmax
    # over sequence chunks of this many tokens (0 = whole sequence).
    # The fp32 [B, S, V] logits tensor is the largest allocation of an
    # LM step (batch 32, seq 2048, vocab 32000: 8.4 GB — more than the
    # model); chunking with per-chunk rematerialization caps it at
    # [B, chunk, V] and unlocks batch sizes the monolithic loss cannot
    # fit. Applies to loss_fn (training); apply() still returns full
    # logits for inference callers.
    loss_chunk: int = 0

    def __post_init__(self):
        if self.n_heads is None:
            # Largest head count that DIVIDES d_model with head_dim >=
            # 128 (a blind d_model // 128 can fail the divisibility
            # check, e.g. d_model=448 -> 3).
            n = max(1, self.d_model // 128)
            while self.d_model % n:
                n -= 1
            object.__setattr__(self, "n_heads", n)
        if self.num_experts and not self.ep_axis:
            raise ValueError(
                "num_experts > 0 requires ep_axis (the expert-parallel mesh "
                "axis the MoE all_to_all routes over)")
        if self.sp_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"sp_impl must be 'ring' or 'ulysses', got "
                f"{self.sp_impl!r}")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy must be 'full' or 'dots', got "
                f"{self.remat_policy!r}")
        if self.loss_chunk < 0:
            raise ValueError(
                f"loss_chunk must be >= 0, got {self.loss_chunk}")

    # What parallel/train.py may do with this model, and the door it
    # reaches it by: a second model (models/nemotron_h.py) provides the
    # same three methods, so the step builder tests no model's name.
    layouts = ("dp", "tp", "sp", "ep", "pp")

    def init_params(self, rng):
        return init_params(self, rng)

    def param_specs(self):
        return param_specs(self)

    def loss_fn(self, params, tokens, targets):
        return loss_fn(params, tokens, targets, self)


def _axis_size(axis: Optional[str]) -> int:
    return lax.axis_size(axis) if axis else 1


def init_params(cfg: TransformerConfig, rng) -> Dict:
    """Initialize GLOBAL parameters (unsharded; shard via param_specs)."""
    keys = jax.random.split(rng, cfg.n_layers + 2)
    d, h, f = cfg.d_model, cfg.n_heads, cfg.d_ff
    scale = d ** -0.5

    def dense(key, shape, s):
        return jax.random.normal(key, shape, jnp.float32) * s

    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i], 8)
        layer = {
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "wq": dense(k[0], (d, d), scale),
            "wk": dense(k[1], (d, d), scale),
            "wv": dense(k[2], (d, d), scale),
            "wo": dense(k[3], (d, d), scale),
        }
        if cfg.num_experts and i % 2 == 1:
            layer["moe"] = moe_init(
                k[4], num_experts=cfg.num_experts,
                experts_per_shard=cfg.num_experts,  # global at init
                features=d, hidden=f)
        else:
            layer["wi"] = dense(k[5], (d, f), scale)
            layer["wo_mlp"] = dense(k[6], (f, d), f ** -0.5)
        layers.append(layer)

    return {
        "embed": dense(keys[-2], (cfg.vocab, d), 1.0),
        "pos": dense(keys[-1], (cfg.max_seq, d), 0.02),
        "ln_f": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


def param_specs(cfg: TransformerConfig) -> Dict:
    """PartitionSpecs for jit-level sharding (scaling-book style):
    tensor-parallel weights split on the head/ff dimension over 'tp',
    experts over 'ep', everything else replicated (dp shards data, not
    params)."""
    tp = cfg.tp_axis
    ep = cfg.ep_axis
    layer_specs = []
    for i in range(cfg.n_layers):
        spec = {
            "ln1": P(), "ln2": P(),
            # Column-parallel QKV (split output dim), row-parallel out-proj
            # (split input dim) — Megatron pairing, one psum per block.
            "wq": P(None, tp), "wk": P(None, tp), "wv": P(None, tp),
            "wo": P(tp, None),
        }
        if cfg.num_experts and i % 2 == 1:
            spec["moe"] = {"router": P(), "wi": P(ep, None, None),
                           "wo": P(ep, None, None)}
        else:
            spec["wi"] = P(None, tp)
            spec["wo_mlp"] = P(tp, None)
        layer_specs.append(spec)
    return {"embed": P(), "pos": P(), "ln_f": P(), "layers": layer_specs}


def _layernorm(x, g):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-5) * g).astype(x.dtype)


def flash_choice(cfg, attended_s: int):
    """``(use_flash, interpret)`` for a trace that attends over
    ``attended_s`` positions. Auto policy (``cfg.use_flash is None``):
    compiled flash from 1k attended positions (config field comment);
    never auto-select the interpreter off-TPU,
    and key on this trace's length, not max_seq — a short batch under a
    long-context config stays on XLA attention."""
    interpret = jax.default_backend() != "tpu"       # interpret off-TPU
    if cfg.use_flash is not None:
        return cfg.use_flash, interpret
    return not interpret and attended_s >= 1024, interpret


def local_attention(q, k, v, cfg):
    """Causal attention of one shard's ``[B, S, H, hd]`` heads: the
    Pallas flash kernels or XLA full attention, by ``flash_choice``."""
    use_flash, interpret = flash_choice(cfg, q.shape[1])
    if not use_flash:
        return full_attention(q, k, v, causal=True)
    from ..ops.flash_attention import flash_attention
    # block sizes None -> tuned defaults (512 compiled / 128 interp)
    return flash_attention(q, k, v, True, None, cfg.flash_block,
                           cfg.flash_block, interpret)


def remat_block(cfg, block=None, static_argnums=(2, 3), names=()):
    """``block`` (``_block(params, x, cfg, layer_idx)`` by default; any
    ``f(params, x, ...)`` whose further arguments ``static_argnums``
    are static) under the configuration's rematerialization: the one
    place the policy is built, for the unrolled stack here, the
    pipeline step's scanned stages (parallel/train.py) and the hybrid
    model's three kinds of layer (models/nemotron_h.py).

    ``names`` are the checkpoint names (``checkpoint_name``) of what
    the layer holds for its backward because it is small beside what
    recomputing it costs: ``"full"`` then keeps exactly those and
    recomputes the rest, ``"dots"`` keeps them besides what it keeps
    anyway. With none the policy is what it was without the argument."""
    block = _block if block is None else block
    if not cfg.remat:
        return block
    cp = jax.checkpoint_policies
    policy = cp.save_only_these_names(*names) if names else None  # "full"
    if cfg.remat_policy == "dots":
        from ..ops.flash_attention import RESIDUAL_NAMES
        policy = cp.save_from_both_policies(
            cp.checkpoint_dots_with_no_batch_dims,
            cp.save_only_these_names(*RESIDUAL_NAMES, *names))
    return jax.checkpoint(block, static_argnums=static_argnums,
                          policy=policy)


def _block(params, x, cfg: TransformerConfig, layer_idx: int):
    """One decoder block, shard_map-level (per-shard views).

    x: [B, S_local, D]. Attention: heads are split over 'tp' (the wq/wk/wv
    shards produce local heads), sequence over 'sp' (ring attention).
    """
    d = cfg.d_model
    tp_n = _axis_size(cfg.tp_axis)
    if cfg.n_heads % tp_n:
        raise ValueError(
            f"n_heads ({cfg.n_heads}) must be divisible by the tensor-"
            f"parallel axis size ({tp_n})")
    if d % cfg.n_heads:
        raise ValueError(
            f"d_model ({d}) must be divisible by n_heads ({cfg.n_heads})")
    h_local = cfg.n_heads // tp_n
    hd = d // cfg.n_heads
    dt = cfg.dtype

    # Scope names are what the trace reduction finds the step's parts
    # by (docs/tracing.md#names): metadata only, no arithmetic.
    with jax.named_scope("hvd_attn"):
        y = _layernorm(x, params["ln1"])
        b, s, _ = y.shape
        q = (y @ params["wq"].astype(dt)).reshape(b, s, h_local, hd)
        k = (y @ params["wk"].astype(dt)).reshape(b, s, h_local, hd)
        v = (y @ params["wv"].astype(dt)).reshape(b, s, h_local, hd)

        # Under Ulysses the local attention runs over the GLOBAL sequence
        # (all-to-all gathers it), so the threshold compares s * sp_size.
        attended_s = s
        if cfg.sp_axis and cfg.sp_impl == "ulysses":
            attended_s = s * lax.axis_size(cfg.sp_axis)
        use_flash, flash_interp = flash_choice(cfg, attended_s)
        if cfg.sp_axis and cfg.sp_impl == "ulysses":
            from ..parallel.ulysses import ulysses_attention
            attn = ulysses_attention(q, k, v, axis_name=cfg.sp_axis,
                                     causal=True, use_flash=use_flash,
                                     flash_block=cfg.flash_block,
                                     flash_interpret=flash_interp)
        elif cfg.sp_axis:
            # Ring attention is blockwise ACROSS shards, but its plain
            # inner op still materializes [shard, shard] scores; use_flash
            # keys the per-shard-pair computation on this trace's SHARD
            # length (each ring step attends q-shard x kv-shard).
            attn = ring_attention(q, k, v, axis_name=cfg.sp_axis, causal=True,
                                  use_flash=use_flash,
                                  flash_block=cfg.flash_block,
                                  flash_interpret=flash_interp)
        else:
            attn = local_attention(q, k, v, cfg)
        attn = attn.reshape(b, s, h_local * hd)
        o = attn @ params["wo"].astype(dt)
        if cfg.tp_axis:
            o = lax.psum(o, cfg.tp_axis)   # row-parallel out-proj
        x = x + o

    with jax.named_scope("hvd_mlp"):
        y = _layernorm(x, params["ln2"])
        if cfg.num_experts and layer_idx % 2 == 1:
            tokens = y.reshape(b * s, d)
            # Under tp, split tokens across the tp axis so expert work is done
            # once per tp group (not duplicated per rank) and every parameter's
            # gradient stays a PARTIAL sum over tp — keeping the train-step's
            # uniform reduction rule (psum over model axes) correct.
            if cfg.tp_axis and tp_n > 1:
                t_local = tokens.shape[0] // tp_n
                i = lax.axis_index(cfg.tp_axis)
                tokens = lax.dynamic_slice_in_dim(tokens, i * t_local, t_local)
            out = moe_apply(params["moe"], tokens,
                            num_experts=cfg.num_experts,
                            capacity_factor=cfg.capacity_factor,
                            axis_name=cfg.ep_axis, act=jax.nn.gelu, dtype=dt)
            if cfg.tp_axis and tp_n > 1:
                out = lax.all_gather(out, cfg.tp_axis, axis=0, tiled=True)
            m = out.reshape(b, s, d)
        else:
            hmid = jax.nn.gelu(y @ params["wi"].astype(dt))
            m = hmid @ params["wo_mlp"].astype(dt)
            if cfg.tp_axis:
                m = lax.psum(m, cfg.tp_axis)
        return x + m


def apply_hidden(params, tokens, cfg: TransformerConfig):
    """Forward pass up to the final layernorm (shard_map-level).
    tokens: [B, S_local] int32; returns hidden [B, S_local, d]."""
    dt = cfg.dtype
    s_local = tokens.shape[1]
    if cfg.sp_axis:
        offset = lax.axis_index(cfg.sp_axis) * s_local
    else:
        offset = 0
    with jax.named_scope("hvd_embed"):
        pos = params["pos"][offset + jnp.arange(s_local)]
        x = params["embed"].astype(dt)[tokens] + pos.astype(dt)

    block = remat_block(cfg)
    for i, layer in enumerate(params["layers"]):
        x = block(layer, x, cfg, i)

    with jax.named_scope("hvd_loss_head"):
        return _layernorm(x, params["ln_f"])


def _project_logits(params, x, cfg: TransformerConfig):
    if cfg.logits_bf16:
        return (x @ params["embed"].astype(cfg.dtype).T).astype(
            jnp.float32)
    return x.astype(jnp.float32) @ params["embed"].T


def apply(params, tokens, cfg: TransformerConfig):
    """Forward pass (shard_map-level). tokens: [B, S_local] int32.
    Returns logits [B, S_local, vocab] (fp32)."""
    h = apply_hidden(params, tokens, cfg)
    with jax.named_scope("hvd_loss_head"):
        return _project_logits(params, h, cfg)


# --------------------------------------------------------------------------
# Incremental decode — block-sliced KV cache (the serving tier's forward)
# --------------------------------------------------------------------------
#
# The cache is a list (one entry per layer) of {"k", "v"} arrays of shape
# [n_blocks, block_size, n_heads, head_dim]: a flat pool of fixed-size
# token blocks, vLLM-style, so sequences of any length share one
# allocation and freeing a finished request returns whole blocks to the
# pool instead of fragmenting a contiguous [B, S_max] cache. A sequence
# addresses its tokens through a *block table*: entry ``j`` of its table
# names the pool block holding absolute positions ``[j*bs, (j+1)*bs)``.
# Block 0 is reserved as a scratch block (serving/kv_cache.py never
# hands it out): padded or inactive slots write their garbage K/V there,
# where no live sequence can read it.
#
# Quantized pool (``kv_quant``): the same layout with the payload held
# in int8 / fp8-e4m3 and fp32 absmax scales per channel block — the
# wire format of quantization.py (EQuARX, arXiv 2506.17615) applied at
# rest instead of in flight. Scales are per (block, token, head,
# head_dim-chunk) with the chunk = ``channel_block(head_dim, 256)``, so
# blocks never straddle heads and a tensor-parallel head shard
# quantizes bit-identically to the same head at tp=1. Dequantization
# happens on read, fused into the attention program; the block-table
# indirection (and with it every allocator/eviction invariant) is
# untouched.


def _kv_spec(kv_quant):
    from .. import quantization as q
    return q.parse(kv_quant)


def init_cache(cfg: TransformerConfig, n_blocks: int, block_size: int,
               kv_quant=None):
    """Zeroed GLOBAL KV pool (shard via :func:`cache_specs`): per layer
    ``{"k", "v"}`` of [n_blocks, block_size, n_heads, head_dim] in the
    activation dtype — or, with ``kv_quant`` ("int8"/"fp8"/a WireSpec),
    the wire-dtype payload plus ``{"ks", "vs"}`` fp32 channel-block
    scales."""
    from .. import quantization as q
    hd = cfg.d_model // cfg.n_heads
    shape = (int(n_blocks), int(block_size), cfg.n_heads, hd)
    spec = _kv_spec(kv_quant)
    if spec is None:
        return [{"k": jnp.zeros(shape, cfg.dtype),
                 "v": jnp.zeros(shape, cfg.dtype)}
                for _ in range(cfg.n_layers)]
    qdt = getattr(jnp, spec.wire_dtype)
    sshape = shape[:3] + (hd // q.channel_block(hd, spec.block_size),)
    return [{"k": jnp.zeros(shape, qdt), "v": jnp.zeros(shape, qdt),
             "ks": jnp.ones(sshape, jnp.float32),
             "vs": jnp.ones(sshape, jnp.float32)}
            for _ in range(cfg.n_layers)]


def cache_specs(cfg: TransformerConfig, kv_quant=None):
    """PartitionSpecs for the KV pool — heads over 'tp' (the same axis
    the wq/wk/wv column splits produce the local heads on), block and
    token dims replicated. Quantized pools shard the scales on the same
    head axis, so each shard's payload travels with its scales."""
    spec = P(None, None, cfg.tp_axis, None)
    if _kv_spec(kv_quant) is None:
        return [{"k": spec, "v": spec} for _ in range(cfg.n_layers)]
    return [{"k": spec, "v": spec, "ks": spec, "vs": spec}
            for _ in range(cfg.n_layers)]


def kv_bytes_per_block(cfg: TransformerConfig, block_size: int,
                       kv_quant=None) -> int:
    """Resident HBM bytes ONE pool block costs across all layers (K and
    V, scales included) — what the engine's ``kv_bytes_resident`` gauge
    multiplies in-use blocks by, and what the 4x-sequences-per-byte
    claim of the quantized pool is measured against."""
    from .. import quantization as q
    hd = cfg.d_model // cfg.n_heads
    elems = int(block_size) * cfg.n_heads * hd
    spec = _kv_spec(kv_quant)
    import numpy as _np
    if spec is None:
        per = elems * _np.dtype(cfg.dtype).itemsize
    else:
        scales = elems // q.channel_block(hd, spec.block_size)
        per = elems * 1 + scales * 4
    return 2 * per * cfg.n_layers


def _decode_block(params, x, layer_cache, tables, pos,
                  cfg: TransformerConfig, kv_spec=None,
                  exact_chunk: bool = False):
    """One decoder block over the KV cache (shard_map-level, per-shard
    views: under 'tp' the projections produce local heads and the cache
    holds the matching head shard).

    x: [B, Q, D] new-token activations; pos: [B, Q] absolute positions;
    tables: [B, T] block ids. Writes this chunk's K/V into the pool,
    then attends causally over everything cached so far (numerics mirror
    :func:`full_attention` so incremental logits match the full-context
    ``apply`` bit-for-bit up to fp reassociation).

    With ``kv_spec`` the pool holds wire-dtype payload + fp32 channel
    scales; the write quantizes, the read dequantizes inside this same
    program. ``exact_chunk`` additionally overwrites THIS chunk's rows
    of the gathered K/V with the exact pre-quantization values — the
    prefill mode, making a from-empty prefill bit-identical to the fp32
    pool (only *past* tokens ever pay quantization error). Decode and
    speculative verification run with it OFF, so a [slots, k] verify
    reads the chunk exactly as the [slots, 1] decode path would have
    re-read it — the greedy token-identity guarantee between the two.
    """
    from .. import quantization as quant
    kc, vc = layer_cache["k"], layer_cache["v"]
    d = cfg.d_model
    tp_n = _axis_size(cfg.tp_axis)
    if cfg.n_heads % tp_n:
        raise ValueError(
            f"n_heads ({cfg.n_heads}) must be divisible by the tensor-"
            f"parallel axis size ({tp_n})")
    h_local = cfg.n_heads // tp_n
    hd = d // cfg.n_heads
    dt = cfg.dtype
    b, q_len, _ = x.shape
    bs = kc.shape[1]

    with jax.named_scope("hvd_attn"):
        y = _layernorm(x, params["ln1"])
        q = (y @ params["wq"].astype(dt)).reshape(b, q_len, h_local, hd)
        k = (y @ params["wk"].astype(dt)).reshape(b, q_len, h_local, hd)
        v = (y @ params["wv"].astype(dt)).reshape(b, q_len, h_local, hd)

        # Scatter the chunk's K/V into its blocks: position p lives at
        # (table[p // bs], p % bs). Distinct live sequences own disjoint
        # blocks (the allocator's invariant), so the scatter never collides
        # except on the shared scratch block 0 — whose content is never
        # visible under the causal mask below. Positions past the table
        # (a speculative chunk overrunning the reserved region) divert to
        # scratch instead of clobbering a neighbour's block.
        T = tables.shape[1]
        blk = jnp.take_along_axis(tables, jnp.minimum(pos // bs, T - 1),
                                  axis=1)                           # [B, Q]
        blk = jnp.where(pos < T * bs, blk, 0)
        off = pos % bs
        out_cache = {}
        if kv_spec is None:
            kc = kc.at[blk, off].set(k.astype(kc.dtype))
            vc = vc.at[blk, off].set(v.astype(vc.dtype))
        else:
            qk, sk = quant.quantize_channels(k, kv_spec)
            qv, sv = quant.quantize_channels(v, kv_spec)
            kc = kc.at[blk, off].set(qk)
            vc = vc.at[blk, off].set(qv)
            ks = layer_cache["ks"].at[blk, off].set(sk)
            vs = layer_cache["vs"].at[blk, off].set(sv)
            out_cache["ks"], out_cache["vs"] = ks, vs
        out_cache["k"], out_cache["v"] = kc, vc

        # Gather the sequence's pages back in table order — entry j covers
        # positions [j*bs, (j+1)*bs), so the flattened page axis IS the
        # absolute-position axis and the causal mask is a plain arange
        # comparison. Unwritten tail blocks are masked off (their positions
        # exceed every query position).
        s_pad = T * bs
        if kv_spec is None:
            keys = kc[tables].reshape(b, s_pad, h_local, hd)
            vals = vc[tables].reshape(b, s_pad, h_local, hd)
        else:
            # Dequant-on-read, fused into this attention program: payload
            # pages and their scales gather through the same table.
            keys = quant.dequantize_channels(
                kc[tables], ks[tables], kv_spec).reshape(
                b, s_pad, h_local, hd).astype(dt)
            vals = quant.dequantize_channels(
                vc[tables], vs[tables], kv_spec).reshape(
                b, s_pad, h_local, hd).astype(dt)
            if exact_chunk:
                # Prefill: this chunk's own rows attend at full precision
                # (mode="drop" skips the scratch-diverted overrun rows).
                rows = jnp.arange(b)[:, None]
                keys = keys.at[rows, pos].set(k, mode="drop")
                vals = vals.at[rows, pos].set(v, mode="drop")
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, keys.astype(q.dtype),
                            preferred_element_type=jnp.float32) * (hd ** -0.5)
        visible = (jnp.arange(s_pad)[None, None, None, :]
                   <= pos[:, None, :, None])
        scores = jnp.where(visible, scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vals.dtype), vals,
                          preferred_element_type=jnp.float32).astype(x.dtype)
        o = attn.reshape(b, q_len, h_local * hd) @ params["wo"].astype(dt)
        if cfg.tp_axis:
            o = lax.psum(o, cfg.tp_axis)   # row-parallel out-proj
        x = x + o

    with jax.named_scope("hvd_mlp"):
        y = _layernorm(x, params["ln2"])
        hmid = jax.nn.gelu(y @ params["wi"].astype(dt))
        m = hmid @ params["wo_mlp"].astype(dt)
        if cfg.tp_axis:
            m = lax.psum(m, cfg.tp_axis)
        return x + m, out_cache


def prefill_spans(n_tokens: int, chunk: int, start: int = 0):
    """``(start, length)`` spans that consume ``n_tokens`` prompt
    positions (from absolute position ``start``) in chunks of at most
    ``chunk`` — the calling convention for multi-chunk prefill through
    :func:`apply_decode`: feed each span's tokens with ``starts`` set
    to the span start, same block tables every call. Pure host-side
    arithmetic; the serving engine's budget policy sizes chunks
    adaptively instead, but composes calls the same way."""
    if n_tokens < 0:
        raise ValueError(f"n_tokens must be >= 0, got {n_tokens}")
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    out = []
    pos = int(start)
    end = int(start) + int(n_tokens)
    while pos < end:
        n = min(int(chunk), end - pos)
        out.append((pos, n))
        pos += n
    return out


def apply_decode(params, tokens, starts, block_tables, cache,
                 cfg: TransformerConfig, kv_quant=None,
                 exact_chunk: bool = False):
    """Incremental forward through the block-sliced KV cache — the
    serving counterpart of :func:`apply`, sharing its weights and
    :func:`param_specs` (shard_map-level; wrap in shard_map over 'tp'
    for tensor-parallel decode, or call directly on one device).

    tokens: [B, Q] int32 — the NEW tokens only (a prompt chunk at
    prefill, one token per live slot at decode, the draft chunk at a
    speculative verify); starts: [B] int32 — absolute position of
    ``tokens[:, 0]`` per sequence; block_tables: [B, T] int32 block ids
    (entry j covers positions [j*bs, (j+1)*bs)); cache: from
    :func:`init_cache`. Returns ``(logits, cache)`` with logits
    [B, Q, vocab] fp32 — at prefill, row ``n-1`` is the first-token
    distribution; at decode, row 0 is the next-token one.

    ``kv_quant`` must match the ``init_cache`` the pool was built with;
    ``exact_chunk`` (prefill only — see :func:`_decode_block`) keeps a
    from-empty quantized prefill bit-identical to the fp32 pool.

    Multi-chunk prefill: a prompt may be consumed as several calls —
    ``tokens`` the next span, ``starts`` where the previous call ended
    (:func:`prefill_spans` computes the split). Each call's causal
    attention covers its own chunk exactly plus everything already
    resident in the blocks, so the composition is the same computation
    as one monolithic call; under ``kv_quant`` the earlier chunks are
    read back dequantized (``exact_chunk`` covers only the current
    span), which the serving tier treats like the prefix-cache case:
    greedy-token-identical in practice, not bitwise on logits.
    """
    if cfg.sp_axis:
        raise ValueError(
            "apply_decode does not support sequence parallelism; build "
            "the serving config with sp_axis=None (decode is one token "
            "per sequence — there is no sequence to shard)")
    if cfg.num_experts:
        raise ValueError(
            "apply_decode does not support MoE layers yet; serve a "
            "dense checkpoint (num_experts=0)")
    kv_spec = _kv_spec(kv_quant)
    dt = cfg.dtype
    b, q_len = tokens.shape
    pos = starts[:, None] + jnp.arange(q_len)[None, :]
    with jax.named_scope("hvd_embed"):
        x = (params["embed"].astype(dt)[tokens]
             + params["pos"][pos].astype(dt))
    new_cache = []
    for i, layer in enumerate(params["layers"]):
        x, out = _decode_block(layer, x, cache[i], block_tables, pos,
                               cfg, kv_spec, exact_chunk)
        new_cache.append(out)
    with jax.named_scope("hvd_loss_head"):
        h = _layernorm(x, params["ln_f"])
        return _project_logits(params, h, cfg), new_cache


def nll_from_hidden(table, h, targets, cfg):
    """Mean next-token cross-entropy of the normalised hidden state
    ``h`` ``[B, S, d]`` under the vocabulary projection ``table``
    ``[vocab, d]`` (the tied embedding here, the hybrid model's untied
    head): the loss head every model shares.

    With ``cfg.loss_chunk`` the vocab projection + log-softmax run over
    sequence chunks under per-chunk rematerialization, so the fp32
    [B, S, V] logits tensor — the largest allocation of an LM train
    step — never materializes (memory: [B, chunk, V])."""
    head = {"embed": table}
    if not cfg.loss_chunk:
        with jax.named_scope("hvd_loss_head"):
            logits = _project_logits(head, h, cfg)
        with jax.named_scope("hvd_loss_head"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, targets[..., None],
                                     axis=-1)[..., 0]
            return -ll.mean()

    b, s, _ = h.shape
    chunk = min(cfg.loss_chunk, s)
    if s % chunk:
        raise ValueError(
            f"loss_chunk ({chunk}) must divide the local sequence ({s})")

    @jax.checkpoint
    def chunk_nll(c):
        hs = lax.dynamic_slice_in_dim(h, c * chunk, chunk, axis=1)
        tg = lax.dynamic_slice_in_dim(targets, c * chunk, chunk, axis=1)
        logits = _project_logits(head, hs, cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, tg[..., None], axis=-1)[..., 0]
        return -ll.sum()

    with jax.named_scope("hvd_loss_head"):
        total = lax.map(chunk_nll, jnp.arange(s // chunk))
        return total.sum() / (b * s)


def loss_fn(params, tokens, targets, cfg: TransformerConfig):
    """Next-token cross-entropy, mean over local tokens; psum-mean over
    'dp'/'sp' happens via the caller's pmean."""
    h = apply_hidden(params, tokens, cfg)
    return nll_from_hidden(params["embed"], h, targets, cfg)
