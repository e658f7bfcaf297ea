"""Ring attention — sequence/context parallelism for long sequences.

No reference equivalent (SURVEY.md §5.7: "Absent — ... For the TPU rebuild
this is green-field"); built on the same mesh-axis collective layer as
everything else, per the survey's guidance that sequence-dimension sharding
rides the comm layer.

Algorithm (Liu et al., "Ring Attention with Blockwise Transformers", and
the blockwise-parallel formulation): the sequence is sharded over the 'sp'
axis; each device holds one query block Q_i and one key/value block
(K_i, V_i). K/V blocks rotate around the ring via ``ppermute`` while each
device accumulates its attention output *online* with the numerically
stable streaming softmax (running max m, normalizer l, weighted numerator):

    for step in 0..n-1:
        scores   = Q_i @ K_j^T          # j = (i - step) mod n
        m_new    = max(m, rowmax(scores))
        corr     = exp(m - m_new)
        p        = exp(scores - m_new)
        num      = num * corr + p @ V_j
        l        = l * corr + rowsum(p)
        (K, V)  <- ring_shift(K, V)

    out = num / l

Communication (one K/V block per step, overlappable with the matmul) rides
the ICI ring — bandwidth-optimal for sequence lengths that do not fit one
chip. Causal masking uses global position offsets per block.

The loop is a ``lax.fori_loop`` (compiler-friendly static trip count); each
step is two MXU matmuls over full blocks — no dynamic shapes.

Two inner-op variants: the plain einsum step above materializes the
[shard, shard] score tensor per step; :func:`ring_flash_attention`
(``use_flash=True``) runs each (q-shard, resident-kv-block) pair through
the Pallas flash kernels instead — O(shard) memory per device in forward
AND backward (the custom backward re-rotates K/V with traveling fp32
dK/dV accumulators and reuses the per-block flash backward kernels with
the global logsumexp/delta row statistics), which is what lets the
per-device shard itself be long.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _ring_step(carry, _, axis_name: str, causal: bool, scale: float,
               q_index, n_shards: int, block_q: int, block_k: int):
    (q, k, v, m, l, num, step) = carry
    # Block j currently resident = (q_index - step) mod n.
    j = (q_index - step) % n_shards

    # scores: [B, H, block_q, block_k] in fp32 for a stable softmax.
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale

    if causal:
        q_pos = q_index * block_q + jnp.arange(block_q)[:, None]
        k_pos = j * block_k + jnp.arange(block_k)[None, :]
        mask = q_pos >= k_pos  # attend to self and the past
        scores = jnp.where(mask[None, None], scores, -jnp.inf)

    m_new = jnp.maximum(m, scores.max(axis=-1))
    # Blocks fully masked out produce -inf rowmax; keep the old statistics.
    m_new = jnp.where(jnp.isfinite(m_new), m_new, m)
    # corr would be exp(-inf - -inf) = nan for rows with no mass yet; they
    # carry zero numerator/normalizer, so force corr to 0 there.
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
    p = jnp.exp(scores - m_new[..., None])
    p = jnp.where(jnp.isfinite(scores), p, 0.0)

    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    num = num * corr.transpose(0, 2, 1)[..., None] + pv
    l = l * corr + p.sum(axis=-1)

    # Rotate K/V to the next rank (ring_shift): each device passes its
    # resident block along, receiving the previous rank's.
    n = n_shards
    perm = [(i, (i + 1) % n) for i in range(n)]
    k = lax.ppermute(k, axis_name, perm)
    v = lax.ppermute(v, axis_name, perm)
    return (q, k, v, m_new, l, num, step + 1), None


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = True,
                   scale: Optional[float] = None,
                   use_flash: bool = False,
                   flash_block: Optional[int] = None,
                   flash_interpret: bool = False):
    """Blockwise ring attention over the ``axis_name`` mesh axis.

    Args (per-shard views inside shard_map):
      q, k, v: [batch, seq_shard, heads, head_dim]
      use_flash: run each (q-shard, resident-kv-block) pair through the
        Pallas flash kernels (:func:`ring_flash_attention`) instead of
        materializing the [seq_shard, seq_shard] score tensor — O(shard)
        memory per step in forward AND backward, which is what lets the
        per-device shard itself be long.
    Returns: [batch, seq_shard, heads, head_dim] attention output for this
    device's query block, exact (up to fp) vs full attention.
    """
    if use_flash:
        return ring_flash_attention(
            q, k, v, axis_name=axis_name, causal=causal, scale=scale,
            block=flash_block, interpret=flash_interpret)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    if scale is None:
        scale = d ** -0.5

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    num0 = jnp.zeros((b, sq, h, d), jnp.float32)

    step_fn = functools.partial(
        _ring_step, axis_name=axis_name, causal=causal, scale=scale,
        q_index=idx, n_shards=n, block_q=sq, block_k=sk)

    (q, k, v, m, l, num, _), _ = lax.scan(
        step_fn, (q, k, v, m0, l0, num0, jnp.zeros((), jnp.int32)),
        None, length=n)

    l = jnp.maximum(l, 1e-20)  # fully-masked rows (shouldn't occur causally)
    out = num / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def _rf_attend_cases(qb, kb, vb, sc, block, interpret, causal, branch):
    """Per-ring-step flash forward: 0 = skip (block entirely above the
    causal diagonal), 1 = diagonal block (intra-shard causal mask),
    2 = past block (every pair valid, no mask). The branches return the
    same shapes (the diagonal's grid visits only the causal pairs, the
    past block's every pair), so lax.switch picks one per step."""
    from ..ops.flash_attention import _flash_fwd

    bh, sq, d = qb.shape

    # fp32 per-block outputs: the merge accumulates across n blocks, and
    # per-block rounding to bf16 would stack n-fold (the plain flash
    # path rounds once over the whole sequence).
    def skip(_):
        # Sentinel contract: skip emits lse = -inf (true "no mass"),
        # while the flash kernels emit _NEG_INF (-1e30, finite) for
        # massless rows. The ring merge's isfinite() guards are pinned
        # to THIS -inf: they zero the weight of never-attended rows so
        # (-inf) - (-inf) can't produce NaN. _NEG_INF rows pass the
        # guard but their exp() underflows to 0 against any real mass.
        # Keep both facts in mind before editing the merge arithmetic.
        return (jnp.zeros((bh, sq, d), jnp.float32),
                jnp.full((bh, sq, 1), -jnp.inf, jnp.float32))

    def diag(_):
        return _flash_fwd(qb, kb, vb, sc, True, block, block, interpret,
                          out_dtype=jnp.float32)

    def past(_):
        return _flash_fwd(qb, kb, vb, sc, False, block, block, interpret,
                          out_dtype=jnp.float32)

    if not causal:
        return past(None)
    return lax.switch(branch, (skip, diag, past), None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def ring_flash_attention(q, k, v, axis_name: str = "sp",
                         causal: bool = True,
                         scale: Optional[float] = None,
                         block: Optional[int] = None,
                         interpret: bool = False):
    """Ring attention with the Pallas flash kernels as the inner op.

    The plain :func:`ring_attention` materializes the
    [seq_shard, seq_shard] score tensor every ring step — O(shard²)
    memory inside a layer whose purpose is O(shard) scaling. This
    variant runs each (q-shard, resident-kv-block) pair through the
    compiled flash forward (returning the per-block output and
    logsumexp) and merges blocks with the streaming logaddexp rule; the
    custom backward re-rotates K/V around the ring and reuses the
    per-block flash backward kernels, which only need the block
    operands plus the GLOBAL per-row (lse, delta) statistics
    (ops/flash_attention._flash_bwd). Per-device memory is O(shard)
    in forward and backward; gradients for each K/V block accumulate
    in fp32 on the tuple that travels the ring and arrive home after
    the full rotation.
    """
    out, _ = _ring_flash_fwd(q, k, v, axis_name, causal, scale, block,
                             interpret)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, block, interpret):
    from ..ops.flash_attention import _from_bh, _to_bh

    b, sq, h, d = q.shape
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    sc = scale if scale is not None else d ** -0.5
    qb = _to_bh(q)
    bh = qb.shape[0]

    def step(carry, _):
        kb_cur, vb_cur, out_r, lse_r, t = carry
        j = (idx - t) % n
        # branch: 0 skip (j > idx), 1 diagonal (j == idx), 2 past
        branch = jnp.where(j > idx, 0, jnp.where(j == idx, 1, 2))
        o_j, lse_j = _rf_attend_cases(
            qb, kb_cur, vb_cur, sc, block, interpret, causal, branch)
        # Streaming merge of normalized per-block outputs: weights are
        # exp(lse_j - lse_tot). The isfinite guards are pinned to the
        # -inf sentinel (skip branch + lse0 init); flash's finite
        # _NEG_INF massless rows pass them and underflow to weight 0.
        lse_new = jnp.logaddexp(lse_r, lse_j)
        w_r = jnp.where(jnp.isfinite(lse_r), jnp.exp(lse_r - lse_new), 0.0)
        w_j = jnp.where(jnp.isfinite(lse_j), jnp.exp(lse_j - lse_new), 0.0)
        out_new = out_r * w_r + o_j * w_j
        k_nxt = lax.ppermute(kb_cur, axis_name, _ring_perm(n))
        v_nxt = lax.ppermute(vb_cur, axis_name, _ring_perm(n))
        return (k_nxt, v_nxt, out_new, lse_new, t + 1), None

    out0 = jnp.zeros((bh, sq, d), jnp.float32)
    lse0 = jnp.full((bh, sq, 1), -jnp.inf, jnp.float32)
    # K/V rotate in [bh, s, d] layout: the transpose to kernel layout
    # happens once here, not once per ring step (ppermute is
    # layout-agnostic).
    (k_fin, v_fin, out_r, lse_r, _), _ = lax.scan(
        step, (_to_bh(k), _to_bh(v), out0, lse0, jnp.zeros((), jnp.int32)),
        None, length=n)
    del k_fin, v_fin  # back at home position after n rotations
    out4 = _from_bh(out_r.astype(q.dtype), b, h)
    return out4, (q, k, v, out4, lse_r)


def _ring_flash_bwd(axis_name, causal, scale, block, interpret, res, g):
    from ..ops.flash_attention import _flash_bwd, _from_bh, _to_bh

    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    sc = scale if scale is not None else d ** -0.5
    qb, gb, ob = _to_bh(q), _to_bh(g), _to_bh(out)
    bh = qb.shape[0]

    # Global softmax-jacobian diagonal, same for every block pair.
    delta = jnp.sum(gb.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # [bh, sq, 1]

    def bwd_cases(kb, vb, branch):
        def skip(_):
            return (jnp.zeros((bh, sq, d), jnp.float32),
                    jnp.zeros((bh, sq, d), jnp.float32),
                    jnp.zeros((bh, sq, d), jnp.float32))

        def run(is_diag):
            def f(_):
                # fp32 kernel outputs: each traveling accumulator sums n
                # per-pair contributions, so per-block bf16 rounding
                # would stack n-fold.
                return _flash_bwd(qb, kb, vb, gb, lse, delta, sc,
                                  is_diag, block, block, interpret,
                                  out_dtype=jnp.float32)
            return f

        if not causal:
            return run(False)(None)
        return lax.switch(branch, (skip, run(True), run(False)), None)

    def step(carry, _):
        kb_cur, vb_cur, dk_acc, dv_acc, dq_acc, t = carry
        j = (idx - t) % n
        branch = jnp.where(j > idx, 0, jnp.where(j == idx, 1, 2))
        dq_j, dk_j, dv_j = bwd_cases(kb_cur, vb_cur, branch)
        dq_acc = dq_acc + dq_j
        dk_acc = dk_acc + dk_j
        dv_acc = dv_acc + dv_j
        # dk/dv accumulators travel WITH their block around the ring.
        perm = _ring_perm(n)
        k_nxt = lax.ppermute(kb_cur, axis_name, perm)
        v_nxt = lax.ppermute(vb_cur, axis_name, perm)
        dk_nxt = lax.ppermute(dk_acc, axis_name, perm)
        dv_nxt = lax.ppermute(dv_acc, axis_name, perm)
        return (k_nxt, v_nxt, dk_nxt, dv_nxt, dq_acc, t + 1), None

    z = jnp.zeros((bh, sq, d), jnp.float32)
    (k_fin, v_fin, dk, dv, dq, _), _ = lax.scan(
        step, (_to_bh(k), _to_bh(v), z, z, z, jnp.zeros((), jnp.int32)),
        None, length=n)
    del k_fin, v_fin  # home again; dk/dv completed the full rotation too
    return (_from_bh(dq.astype(q.dtype), b, h),
            _from_bh(dk.astype(k.dtype), b, h),
            _from_bh(dv.astype(v.dtype), b, h))


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def full_attention(q, k, v, *, causal: bool = True,
                   scale: Optional[float] = None):
    """Single-device reference attention (same layout) for tests."""
    b, sq, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sk = k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
