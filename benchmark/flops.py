"""Operations and bytes computed from shapes: the model's FLOPs per
step for ``mfu`` and each flash kernel's for ``flash_roofline``.

Arithmetic copied from ``bench_lm.model_flops_per_step`` (6 N T plus
causal attention), with the convention for N written down here:

  N = the parameters a token is multiplied by: per layer 4 d^2
      (wq, wk, wv, wo) + 2 d d_ff (wi, wo_mlp), plus vocab x d ONCE for
      the tied output head. The embedding lookup and the learned
      position table are gathers, not matmuls, and LayerNorm scales are
      elementwise: none of them is in N.

Recomputed operations (remat) are not counted: ``mfu`` is the share of
the peak spent on what forward and backward REQUIRE."""


def matmul_params(config):
    d, f = config["n_embd"], config["n_inner"]
    return (config["n_layer"] * (4 * d * d + 2 * d * f)
            + config["vocab_size"] * d)


def total_params(config):
    """Every parameter the program holds (state bytes = 10 x this)."""
    d = config["n_embd"]
    return (matmul_params(config) + config["n_positions"] * d
            + (2 * config["n_layer"] + 1) * d)


def model_flops_per_step(config, batch, seq):
    """6 N T + causal attention: forward is QK^T and PV, 2 S^2 d each
    per layer and sequence, halved by causality; backward is twice
    forward."""
    tokens = batch * seq
    attn_fwd = (config["n_layer"] * batch
                * 2 * (2.0 * seq * seq * config["n_embd"]) / 2.0)
    return 6.0 * matmul_params(config) * tokens + 3.0 * attn_fwd


# Matmuls of [S, hd] x [hd, S] size that each kernel's call NEEDS. The
# backward needs five in all (scores recomputed once, dP, dV, dK, dQ);
# the program's two backward kernels each recompute the scores and dP,
# seven in all, and the two repeats are not credited: dkv gets the
# scores, dV and dK, dq gets dP and dQ.
FLASH_MATMULS = {"fwd": 2, "dkv": 3, "dq": 2}
# [bh, S, hd]-sized bf16 operands each kernel must read and write once
# (q, k, v, o | q, k, v, do, dk, dv | q, k, v, do, dq); the [bh, S, 1]
# float32 row statistics are counted apart.
FLASH_OPERANDS = {"fwd": 4, "dkv": 6, "dq": 5}
FLASH_ROW_STATS = {"fwd": 1, "dkv": 2, "dq": 2}


def flash_kernel_work(kind, bh, seq, head_dim, causal=True):
    """``(flops, bytes)`` one call of a flash kernel needs."""
    one = 2.0 * seq * seq * head_dim * bh
    if causal:
        one /= 2.0
    flops = FLASH_MATMULS[kind] * one
    nbytes = (FLASH_OPERANDS[kind] * bh * seq * head_dim * 2
              + FLASH_ROW_STATS[kind] * bh * seq * 4)
    return flops, float(nbytes)


def least_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which peak bounds it."""
    by_compute = flops / peaks["bf16_flops_per_s"]
    by_memory = nbytes / peaks["hbm_bytes_per_s"]
    if by_compute >= by_memory:
        return by_compute, "compute"
    return by_memory, "memory"
