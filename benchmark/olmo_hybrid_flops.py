"""Operations of the ``olmo_hybrid`` decoder computed from shapes: the
model's FLOPs per step for ``mfu``, and the chunked delta rule's work
for ``linattn_kernel_roofline`` (``qwen3_next_flops.delta_rule_work``:
the same count whatever implements it, at THIS model's heads and
widths as published, 96 / 192: the columns of zeros the kernels add up
to whole lanes are not credited). Keys are the configuration file's.

  N = the parameters a token is multiplied by. A Gated DeltaNet mixer:
      W_q, W_k hidden x H d_k, W_v, W_g hidden x H d_v (the program's
      packed in_proj), W_a, W_b hidden x H each, W_o H d_v x hidden. An
      attention mixer: W_q, W_k, W_v, W_o hidden x hidden. Every
      layer's MLP: 3 hidden x intermediate. The untied head, vocab x
      hidden, once. The embedding lookup is a gather and the norms, the
      convolution (4 taps a channel), the decays and the gates are
      elementwise: none of them is in N.

Recomputed operations (remat) are not counted: ``mfu`` is the share of
the peak spent on what forward and backward REQUIRE."""

from benchmark.qwen3_next_flops import delta_rule_work


def layer_kinds(config):
    return config["layer_types"][:config["num_hidden_layers"]]


def layer_matmul_params(config):
    """``{"linear_attention" | "full_attention" | "mlp": N of one}``."""
    d = config["hidden_size"]
    h = config["linear_num_value_heads"]
    hk = config["linear_num_key_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    return {
        "linear_attention": (d * (2 * hk * dk + 2 * h * dv) + d * 2 * h
                             + h * dv * d),
        "full_attention": 4 * d * d,
        "mlp": 3 * d * config["intermediate_size"],
    }


def matmul_params(config):
    per = layer_matmul_params(config)
    return (sum(per[kind] + per["mlp"] for kind in layer_kinds(config))
            + config["vocab_size"] * config["hidden_size"])


def delta_rule_work_of(config, batch, seq):
    """``delta_rule_work`` of one DeltaNet layer of ``config``."""
    return delta_rule_work(
        batch, seq, config["linear_num_value_heads"],
        config["linear_key_head_dim"], config["linear_value_head_dim"],
        config["program"]["gdn_chunk"])


def model_flops_per_step(config, batch, seq):
    """6 N T, plus causal attention (forward QK^T and PV, 2 S^2 hidden
    each per attention layer and sequence, halved by causality;
    backward twice forward), plus the delta rule's matmuls."""
    kinds = layer_kinds(config)
    tokens = batch * seq
    width = config["hidden_size"]
    attn_fwd = (kinds.count("full_attention") * batch
                * 2 * (2.0 * seq * seq * width) / 2)
    delta = (kinds.count("linear_attention")
             * delta_rule_work_of(config, batch, seq)[0])
    return 6.0 * matmul_params(config) * tokens + 3.0 * attn_fwd + delta
