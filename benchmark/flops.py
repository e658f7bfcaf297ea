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


# What each kernel's call NEEDS, with the query/key width ``d_qk`` and
# the value width ``d_v`` counted apart (a latent-attention model has
# 192 and 128). Matmuls of ``[S, d] x [d, S]`` size, as (of width d_qk,
# of width d_v): the backward needs five in all (scores recomputed once,
# dP, dV, dK, dQ); the program's two backward kernels each recompute the
# scores and dP, seven in all, and the two repeats are not credited.
#   fwd:  QK^T | PV
#   dkv:  scores, dK | dV
#   dq:   dQ | dP
FLASH_MATMULS = {"fwd": (1, 1), "dkv": (2, 1), "dq": (1, 1)}
# bf16 operands each kernel must read and write once, as ([bh, S, d_qk]
# -sized, [bh, S, d_v]-sized): q, k | v, o;  q, k, dk | v, do, dv;
# q, k, dq | v, do. The [bh, S, 1] float32 row statistics are counted
# apart.
FLASH_OPERANDS = {"fwd": (2, 2), "dkv": (3, 3), "dq": (3, 2)}
FLASH_ROW_STATS = {"fwd": 1, "dkv": 2, "dq": 2}


def flash_kernel_work(kind, bh, seq, d_qk, d_v, causal=True):
    """``(flops, bytes)`` one call of a flash kernel needs, whatever
    implements it. At ``d_qk == d_v`` these are 2 / 3 / 2 matmuls of
    ``2 S^2 d bh`` and 4 / 6 / 5 operands of ``bh S d`` for fwd / dkv /
    dq, to the digit what a single head width gave before the widths
    were counted apart (held in ``tests/test_benchmark_arithmetic.py``)."""
    def one(d):
        flops = 2.0 * seq * seq * d * bh
        return flops / 2.0 if causal else flops

    n_qk, n_v = FLASH_MATMULS[kind]
    o_qk, o_v = FLASH_OPERANDS[kind]
    flops = n_qk * one(d_qk) + n_v * one(d_v)
    nbytes = ((o_qk * d_qk + o_v * d_v) * bh * seq * 2
              + FLASH_ROW_STATS[kind] * bh * seq * 4)
    return flops, float(nbytes)


def least_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which peak bounds it."""
    by_compute = flops / peaks["bf16_flops_per_s"]
    by_memory = nbytes / peaks["hbm_bytes_per_s"]
    if by_compute >= by_memory:
        return by_compute, "compute"
    return by_memory, "memory"
