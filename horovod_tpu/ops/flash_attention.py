"""Pallas TPU flash attention — the hot-op kernel for the flagship model.

No reference equivalent (the reference ships no model/attention code at
all — SURVEY.md §5.7); this is the TPU-native kernel for the attention
the transformer (models/transformer.py) runs, written per the Pallas TPU
playbook: blockwise online softmax so the [S, S] score matrix never
materializes in HBM, fp32 accumulation on the MXU, static shapes, grid
iterated sequentially so the running (m, l, acc) statistics live in VMEM
scratch across k-blocks (FlashAttention-2 schedule).

Forward saves the per-row logsumexp; backward recomputes block scores
(the rematerialization trade: O(S) memory instead of O(S^2), extra FLOPs
the MXU has to spare) in two passes — one accumulating dK/dV per
key-block, one accumulating dQ per query-block.

Layout matches the rest of the stack: [batch, seq, heads, head_dim],
internally reshaped to [batch*heads, seq, head_dim]. ``interpret=True``
runs the same kernels through the Pallas interpreter — used by the CPU
test mesh; on TPU they compile to Mosaic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Checkpoint names of the two residuals only the kernel can produce, its
# output and row statistics: what a jax.checkpoint policy saves to spare
# the backward a second forward kernel (models/transformer.py::remat_block).
RESIDUAL_NAMES = ("hvd_flash_out", "hvd_flash_lse")

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() exact zero
#                   without nan from (-inf) - (-inf) in masked-out rows


def _row_ok(start_block: int, block: int, limit: int):
    """[block, 1] validity mask for rows of a cdiv-padded block. Padded
    rows read uninitialized (NaN in interpret mode) memory; every load is
    masked with where() because 0 * NaN still poisons matmul accumulations."""
    rows = start_block * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, 1), 0)
    return rows < limit


def _scores(q, k):
    """q k^T block scores (q pre-scaled), fp32 accumulation.

    The ONE score convention, shared by the masked and unmasked paths
    of the forward and both backward kernels so a convention change
    (bias term, different scaling, ...) cannot desynchronize them."""
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # [BQ, BK]


def _masked_scores(q, k, qi, kj, *, causal, block_q, block_k,
                   seq_q, seq_k):
    """_scores with the bounds+causal mask applied.

    Shared by the forward and both backward kernels so a mask change
    (sliding window, segment ids, ...) cannot desynchronize them.
    Returns (scores, valid)."""
    s = _scores(q, k)
    q_pos = (qi * block_q
             + jax.lax.broadcasted_iota(jnp.int32,
                                        (block_q, block_k), 0))
    k_pos = (kj * block_k
             + jax.lax.broadcasted_iota(jnp.int32,
                                        (block_q, block_k), 1))
    # Bounds mask handles block-padded tails (grid is cdiv-rounded);
    # the causal mask stacks on top.
    valid = (q_pos < seq_q) & (k_pos < seq_k)
    if causal:
        valid = valid & (q_pos >= k_pos)
    return jnp.where(valid, s, _NEG_INF), valid


def _block_dispatch(update, *, qi, kj, causal, block_q, block_k,
                    n_q, n_k, seq_q, seq_k):
    """Run ``update(masked)`` with per-block mask specialization.

    Mask construction (two [BQ, BK] iotas + compares + wheres) costs
    several VPU passes over the score block — comparable to the block's
    MXU time — yet only blocks straddling the causal diagonal or a
    cdiv-padded tail need any of it. Interior blocks (the vast majority
    at long sequence: all-but-one block per row for causal 8k/512) take
    the unmasked path. Both specializations are compiled; pl.when on
    the (scalar) block coordinates picks one per grid step."""
    tail = None
    if seq_q % block_q != 0:
        tail = qi == n_q - 1
    if seq_k % block_k != 0:
        t2 = kj == n_k - 1
        tail = t2 if tail is None else (tail | t2)
    if causal:
        # active: block reaches at or below the diagonal.
        active = kj * block_k <= (qi + 1) * block_q - 1
        # edge: block straddles the diagonal (its top-right corner is
        # strictly above it) — the only active blocks with invalid pairs.
        edge = (kj + 1) * block_k - 1 > qi * block_q
        if tail is not None:
            edge = edge | tail

        @pl.when(active & edge)
        def _():
            update(True)

        @pl.when(active & jnp.logical_not(edge))
        def _():
            update(False)
    elif tail is not None:
        @pl.when(tail)
        def _():
            update(True)

        @pl.when(jnp.logical_not(tail))
        def _():
            update(False)
    else:
        update(False)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, scale: float, causal: bool, block_q: int, block_k: int,
                n_q: int, n_k: int, seq_q: int, seq_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    def _update(masked):
        # scale folded into q ([BQ, D] pass) instead of into the
        # [BQ, BK] score block.
        if masked:
            q_ok = _row_ok(qi, block_q, seq_q)
            k_ok = _row_ok(kj, block_k, seq_k)
            q = jnp.where(q_ok, q_ref[0], 0) * scale   # [BQ, D]
            k = jnp.where(k_ok, k_ref[0], 0)           # [BK, D]
            v = jnp.where(k_ok, v_ref[0], 0)
            s, valid = _masked_scores(
                q, k, qi, kj, causal=causal, block_q=block_q,
                block_k=block_k, seq_q=seq_q, seq_k=seq_k)
        else:
            q = q_ref[0] * scale
            k = k_ref[0]
            v = v_ref[0]
            s = _scores(q, k)

        m_prev = m_sc[:, 0]                                # [BQ]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])                    # [BQ, BK]
        if masked:
            p = jnp.where(valid, p, 0.0)
        l_sc[:, 0] = l_sc[:, 0] * corr + p.sum(axis=-1)
        acc_sc[:] = (acc_sc[:] * corr[:, None]
                     + jax.lax.dot_general(
                         p.astype(v.dtype), v,
                         (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32))
        m_sc[:, 0] = m_new

    _block_dispatch(_update, qi=qi, kj=kj, causal=causal,
                    block_q=block_q, block_k=block_k, n_q=n_q, n_k=n_k,
                    seq_q=seq_q, seq_k=seq_k)

    @pl.when(kj == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_sc[:, 0], 1e-30)
        o_ref[0] = (acc_sc[:] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_sc[:, 0] + jnp.log(l))[:, None]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc,
                *, scale: float, causal: bool, block_q: int, block_k: int,
                n_q: int, n_k: int, seq_q: int, seq_k: int):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _update(masked):
        # All matmul operands stay bf16 (fp32 accumulation via
        # preferred_element_type) — fp32 operands run the MXU at a
        # fraction of its bf16 rate. q is pre-scaled, which
        # also absorbs dK's trailing `* scale` (dK = dS^T (scale Q)).
        if masked:
            q_ok = _row_ok(qi, block_q, seq_q)
            k_ok = _row_ok(kj, block_k, seq_k)
            q = jnp.where(q_ok, q_ref[0], 0) * scale   # [BQ, D]
            k = jnp.where(k_ok, k_ref[0], 0)           # [BK, D]
            v = jnp.where(k_ok, v_ref[0], 0)
            do = jnp.where(q_ok, do_ref[0], 0)
            lse = jnp.where(q_ok, lse_ref[0], 0)
            delta = jnp.where(q_ok, delta_ref[0], 0)
            s, valid = _masked_scores(
                q, k, qi, kj, causal=causal, block_q=block_q,
                block_k=block_k, seq_q=seq_q, seq_k=seq_k)
        else:
            q = q_ref[0] * scale
            k = k_ref[0]
            v = v_ref[0]
            do = do_ref[0]
            lse = lse_ref[0]
            delta = delta_ref[0]
            s = _scores(q, k)
        p = jnp.exp(s - lse)                              # [BQ, BK]
        if masked:
            p = jnp.where(valid, p, 0.0)
        p_lo = p.astype(do.dtype)
        # dV += P^T dO
        dv_sc[:] += jax.lax.dot_general(
            p_lo, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dS = P * (dO V^T - delta)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [BQ, BK]
        ds = p * (dp - delta)
        if masked:
            ds = jnp.where(valid, ds, 0.0)
        # dK += dS^T (scale Q)
        dk_sc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _block_dispatch(_update, qi=qi, kj=kj, causal=causal,
                    block_q=block_q, block_k=block_k, n_q=n_q, n_k=n_k,
                    seq_q=seq_q, seq_k=seq_k)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_sc, *, scale: float, causal: bool, block_q: int,
               block_k: int, n_q: int, n_k: int, seq_q: int, seq_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def _update(masked):
        # bf16 matmul operands, fp32 accumulation (see _dkv_kernel).
        # The constant `* scale` on dQ moves to _finalize: one [BQ, D]
        # pass per q-block instead of one per (q, k) block pair.
        if masked:
            q_ok = _row_ok(qi, block_q, seq_q)
            k_ok = _row_ok(kj, block_k, seq_k)
            q = jnp.where(q_ok, q_ref[0], 0) * scale
            k = jnp.where(k_ok, k_ref[0], 0)
            v = jnp.where(k_ok, v_ref[0], 0)
            do = jnp.where(q_ok, do_ref[0], 0)
            lse = jnp.where(q_ok, lse_ref[0], 0)
            delta = jnp.where(q_ok, delta_ref[0], 0)
            s, valid = _masked_scores(
                q, k, qi, kj, causal=causal, block_q=block_q,
                block_k=block_k, seq_q=seq_q, seq_k=seq_k)
        else:
            q = q_ref[0] * scale
            k = k_ref[0]
            v = v_ref[0]
            do = do_ref[0]
            lse = lse_ref[0]
            delta = delta_ref[0]
            s = _scores(q, k)
        p = jnp.exp(s - lse)
        if masked:
            p = jnp.where(valid, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if masked:
            ds = jnp.where(valid, ds, 0.0)
        dq_sc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _block_dispatch(_update, qi=qi, kj=kj, causal=causal,
                    block_q=block_q, block_k=block_k, n_q=n_q, n_k=n_k,
                    seq_q=seq_q, seq_k=seq_k)

    @pl.when(kj == n_k - 1)
    def _finalize():
        dq_ref[0] = (dq_sc[:] * scale).astype(dq_ref.dtype)


def _default_block(block, interpret: bool, head_dim: int = 128,
                   cap: int = 1024) -> int:
    """Default tile size. Compiled Mosaic kernels want LARGE blocks —
    the kernels are bound by re-streaming K/V (fwd, dq) and Q/dO (dkv)
    from HBM once per opposing block row, so doubling the block halves
    that traffic (at these defaults the cells read flash_roofline
    35.6-36.8% at seq 2048 and 49.6% at 16384: ledger, PR 32; no
    cell sweeps the block). The backward kernels each cap the dimension
    they do NOT stream over at 512 (dkv 512x1024, dq 1024x512 — see
    _flash_bwd_rule) because 1024x1024 intermittently fails to compile
    (scoped-vmem) — hence the per-kernel ``cap``. The VMEM
    footprint scales with block*head_dim, so the compiled default
    SHRINKS for larger head dims, rounded DOWN to a multiple of 128 for
    the TPU lane/sublane tiling and floored at 128 (so a huge head_dim
    still gets a legal — if over-budget — block; pass explicit sizes
    there). The interpreter keeps 128 so CPU tests stay fast. Blocks
    are clamped to the sequence length either way."""
    if block is not None:
        return block
    if interpret:
        return 128
    b = cap * 128 // max(head_dim, 1)
    return max(128, min(cap, b // 128 * 128))


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               out_dtype=None):
    # out_dtype: ring attention requests fp32 per-block outputs so its
    # streaming merge accumulates without an n-fold bf16 rounding.
    bh, s, d = q.shape
    sk = k.shape[1]
    block_q = min(_default_block(block_q, interpret, d), s)
    block_k = min(_default_block(block_k, interpret, d), sk)
    n_q = pl.cdiv(s, block_q)
    n_k = pl.cdiv(sk, block_k)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_q=n_q, n_k=n_k, seq_q=s, seq_k=sk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running norm l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        # What a trace finds the kernel by, whatever it returns:
        # pallas_call enters a named_scope of its name, so the op's name
        # stack ends in hvd_flash_fwd/pallas_call (docs/tracing.md#names).
        name="hvd_flash_fwd",
    )(q, k, v)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False):
    """Flash attention over [batch, seq, heads, head_dim] inputs.

    Exact (up to fp) vs full attention; O(seq) memory. ``interpret``
    routes through the Pallas interpreter (CPU tests); on TPU leave
    False for the compiled Mosaic kernel. Compiled block sizes default
    per kernel — forward 1024x1024, dK/dV 512x1024, dQ 1024x512 (the
    largest block each kernel compiles dependably at head_dim 128: see
    _default_block and _flash_bwd_rule);
    explicit ``block_q``/``block_k`` override ALL kernels; interpreted
    defaults stay 128.

    The two residuals only the kernel can produce carry checkpoint
    names (``RESIDUAL_NAMES``: ``hvd_flash_out``, ``hvd_flash_lse``): a
    ``jax.checkpoint`` policy that saves them (``remat_policy="dots"``
    does) spares the backward a second forward kernel. The row
    statistics are held between forward and backward in their compact
    ``[B*H, S]`` form, under any policy and under none: as the kernel
    emits them, ``f32[B*H, S, 1]``, the TPU pads each row to a tile of
    128 lanes, 128 times the bytes.
    """
    out, _ = _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k,
                             interpret)
    return out


def _prep(q, scale):
    b, s, h, d = q.shape
    return (scale if scale is not None else d ** -0.5)


def _to_bh(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret):
    b, s, h, d = q.shape
    sc = _prep(q, scale)
    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)
    out, lse = _flash_fwd(qb, kb, vb, sc, causal, block_q, block_k,
                          interpret)
    # Identities outside jax.checkpoint and under a policy that names
    # nothing (docs/tracing.md#names); q, k, v come back from saved dots.
    out4 = checkpoint_name(_from_bh(out, b, h), RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse[..., 0], RESIDUAL_NAMES[1])    # [bh, s]
    return out4, (q, k, v, out4, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    lse = lse[..., None]                                      # [bh, s, 1]
    b, s, h, d = q.shape
    sc = _prep(q, scale)
    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)
    ob, gb = _to_bh(out), _to_bh(g)

    # delta = rowsum(dO * O) — the softmax-jacobian diagonal term.
    delta = jnp.sum(gb.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [bh, s, 1]

    dq, dk, dv = _flash_bwd(qb, kb, vb, gb, lse, delta, sc, causal,
                            block_q, block_k, interpret)
    return (_from_bh(dq, b, h), _from_bh(dk, b, h), _from_bh(dv, b, h))


def _flash_bwd(qb, kb, vb, gb, lse, delta, sc, causal, block_q, block_k,
               interpret, out_dtype=None):
    """Per-block backward passes on [bh, s, d] operands.

    ``lse``/``delta`` are the GLOBAL per-query-row logsumexp and
    softmax-jacobian diagonal — which is what makes these kernels
    directly reusable by ring attention: each (q-shard, kv-block) pair's
    gradient contribution only needs the block operands plus these two
    global row statistics (p = exp(s - lse) is the true global softmax
    restricted to the block)."""
    bh, s, d = qb.shape
    sk = kb.shape[1]
    # The two backward kernels get opposite geometries: dkv re-streams
    # Q/dO once per K-block row (wants LARGE block_k), dq re-streams
    # K/V once per Q-block row (wants LARGE block_q). Both cap the
    # other dimension at 512 — the [block_q, block_k] fp32
    # intermediates at 1024x1024 blow the scoped-vmem budget.
    # Explicit block_q/block_k override both kernels.
    bq = min(_default_block(block_q, interpret, d, cap=512), s)
    bk = min(_default_block(block_k, interpret, d), sk)
    n_q = pl.cdiv(s, bq)
    n_k = pl.cdiv(sk, bk)

    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=sc, causal=causal,
                          block_q=bq, block_k=bk, n_q=n_q, n_k=n_k,
                          seq_q=s, seq_k=sk),
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), out_dtype or kb.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), out_dtype or vb.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="hvd_flash_dkv",
    )(qb, kb, vb, gb, lse, delta)
    dk, dv = dkv

    bq2 = min(_default_block(block_q, interpret, d), s)
    bk2 = min(_default_block(block_k, interpret, d, cap=512), sk)
    n_q2 = pl.cdiv(s, bq2)
    n_k2 = pl.cdiv(sk, bk2)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=sc, causal=causal,
                          block_q=bq2, block_k=bk2, n_q=n_q2, n_k=n_k2,
                          seq_q=s, seq_k=sk),
        grid=(bh, n_q2, n_k2),
        in_specs=[
            pl.BlockSpec((1, bq2, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk2, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk2, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq2, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq2, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq2, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq2, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), out_dtype or qb.dtype),
        scratch_shapes=[pltpu.VMEM((bq2, d), jnp.float32)],
        interpret=interpret,
        name="hvd_flash_dq",
    )(qb, kb, vb, gb, lse, delta)

    return dq, dk, dv


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
