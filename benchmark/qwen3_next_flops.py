"""Operations and bytes of the ``qwen3_next`` decoder computed from
shapes: the model's FLOPs per step for ``mfu``, the chunked delta
rule's work for ``delta_rule_roofline`` and the grouped matmuls' for
``gmm_roofline``. Keys are the configuration file's.

  N = the parameters a token is multiplied by. A Gated DeltaNet mixer:
      in_proj hidden x key heads x (2 d_k + 2 (H_v / H_k) d_v), in_ba
      hidden x 2 H_v, out_proj H_v d_v x hidden. An attention mixer:
      q (with its gate), k, v and o. Every layer's expert layer: the
      router at its PUBLISHED width, the shared expert (3 hidden x
      width) and its gate (hidden), and of the routed experts what a
      token meets HERE on average, num_experts_per_tok x held /
      published experts of 3 hidden x moe_intermediate. The untied
      head, vocab x hidden, once. The embedding lookup is a gather and
      the norms, the convolution (4 taps a channel), the decays and the
      gates are elementwise: none of them is in N.

Recomputed operations (remat) are not counted: ``mfu`` is the share of
the peak spent on what forward and backward REQUIRE."""


def mixer_kinds(config):
    interval = config["full_attention_interval"]
    return ["A" if (i + 1) % interval == 0 else "D"
            for i in range(config["num_hidden_layers"])]


def layer_matmul_params(config):
    """``{"D" | "A" | "E": N of one such mixer or expert layer}``."""
    d = config["hidden_size"]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    heads = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    met = (config["num_experts_per_tok"] * len(config["experts_held"])
           / config["published"]["num_experts"])
    return {
        "D": d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d,
        "A": d * (2 * heads + 2 * kv) + heads * d,
        "E": (d * config["published"]["num_experts"]
              + 3 * d * config["shared_expert_intermediate_size"] + d
              + met * 3 * d * config["moe_intermediate_size"]),
    }


def matmul_params(config):
    per = layer_matmul_params(config)
    return (sum(per[kind] + per["E"] for kind in mixer_kinds(config))
            + config["vocab_size"] * config["hidden_size"])


def delta_rule_work(batch, seq, heads, key_dim, value_dim, chunk):
    """``(flops, bytes)`` ONE Gated DeltaNet layer's recurrence NEEDS
    in a step, forward and backward, computed the chunked (WY) way at
    ``chunk``, whatever implements it; recomputation is not credited.

    Matmul FLOPs of a chunk of C positions and one value head, forward:
    ``K K^T`` and ``Q K^T`` (2 C^2 d_k each); the triangular solve
    applied to the keys and the values, ``T (beta exp(G) K)`` and ``T
    (beta V)`` (2 C^2 d_k and 2 C^2 d_v; forming ``T`` itself, C^3 / 3
    by substitution, is not a matmul any algorithm needs and is not
    counted); what the state gives the writes, ``W S`` (2 C d_k d_v);
    the output from the state and from the chunk (2 C d_k d_v and 2 C^2
    d_v); the state's update (2 C d_k d_v). Backward is twice forward.
    Decays, their cumulative sums and exponentials are elementwise.

    Bytes, the least a fused implementation moves: forward reads q, k,
    v (activation type, 2 bytes) and g, beta (float32) and writes o;
    backward reads those and do and writes dq, dk, dv, dg, dbeta. The
    chunk states stay on the chip."""
    c = chunk
    chunks = -(-seq // c)
    per_chunk = (2 * 2 * c * c * key_dim + 2 * c * c * key_dim
                 + 2 * c * c * value_dim + 3 * 2 * c * key_dim * value_dim
                 + 2 * c * c * value_dim)
    flops = 3.0 * batch * chunks * heads * per_chunk
    tokens = batch * seq
    qk, v, scalars = (tokens * heads * key_dim, tokens * heads * value_dim,
                      tokens * heads)
    forward = (2 * qk + 2 * v) * 2 + 2 * scalars * 4
    backward = (4 * qk + 4 * v) * 2 + 4 * scalars * 4
    return flops, float(forward + backward)


def delta_rule_work_of(config, batch, seq):
    """``delta_rule_work`` of one DeltaNet layer of ``config`` (the
    keys and queries as the recurrence meets them: one a value head)."""
    return delta_rule_work(
        batch, seq, config["linear_num_value_heads"],
        config["linear_key_head_dim"], config["linear_value_head_dim"],
        config["program"]["gdn_chunk"])


def gmm_work(rows, hidden, width, experts):
    """``(flops, bytes)`` ONE expert layer's grouped matmuls NEED in a
    step at ``rows`` real rows, forward and backward: per row the gate
    and up projections (2 x 2 hidden width) and the down projection (2
    width hidden), forward once and backward twice (the rows' and the
    weights' products). Bytes: forward reads the rows (2 bytes an
    element), the hidden activations twice (written and read) and
    writes the output rows, and reads every held expert's weights once
    (float32, 3 hidden width each: they are read whatever the rows);
    backward reads those and the output's cotangent, writes the rows'
    cotangent and the weights' gradient (float32). Rows that are not
    real are no work."""
    flops = 3.0 * rows * 3 * 2 * hidden * width
    weights = experts * 3 * hidden * width * 4
    row_bytes = rows * 2 * (2 * hidden + 2 * 3 * width)
    return flops, float(2 * weights + weights + 3 * row_bytes)


def gmm_work_of(config, rows):
    """``gmm_work`` of one expert layer of ``config`` at ``rows``."""
    return gmm_work(rows, config["hidden_size"],
                    config["moe_intermediate_size"],
                    len(config["experts_held"]))


def model_flops_per_step(config, batch, seq):
    """6 N T, plus causal attention (forward QK^T and PV, 2 S^2 x the
    query heads' width each per attention layer and sequence, halved by
    causality; backward twice forward), plus the delta rule's
    matmuls."""
    mixers = mixer_kinds(config)
    tokens = batch * seq
    width = config["num_attention_heads"] * config["head_dim"]
    attn_fwd = mixers.count("A") * batch * 2 * (2.0 * seq * seq * width) / 2
    delta = mixers.count("D") * delta_rule_work_of(config, batch, seq)[0]
    return 6.0 * matmul_params(config) * tokens + 3.0 * attn_fwd + delta
