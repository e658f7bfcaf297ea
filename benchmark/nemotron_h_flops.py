"""Operations and bytes of the ``nemotron_h`` hybrid computed from
shapes: the model's FLOPs per step for ``mfu`` and the chunked scan's
work for ``ssd_scan_roofline``. Keys are the configuration file's.

  N = the parameters a token is multiplied by. A Mamba-2 layer: in_proj
      hidden x (2 d_inner + 2 n_groups state + heads) + out_proj
      d_inner x hidden. An attention layer: q, k, v and o. An expert
      layer: the router at its PUBLISHED width, the two latent
      projections, the shared expert, and of the routed experts what a
      token meets HERE on average, num_experts_per_tok x held /
      published experts of 2 latent x moe_intermediate. The untied
      head, vocab x hidden, once. The embedding lookup is a gather and
      the norms, the convolution (4 taps a channel), dt, A and D are
      elementwise: none of them is in N.

Recomputed operations (remat) are not counted: ``mfu`` is the share of
the peak spent on what forward and backward REQUIRE."""


def _d_inner(config):
    return config["mamba_num_heads"] * config["mamba_head_dim"]


def layer_matmul_params(config):
    """``{kind: N of one layer of that kind}``."""
    d = config["hidden_size"]
    inner = _d_inner(config)
    in_width = (2 * inner + 2 * config["n_groups"] * config["ssm_state_size"]
                + config["mamba_num_heads"])
    heads = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    latent, ff = config["moe_latent_size"], config["moe_intermediate_size"]
    experts = config["published"]["n_routed_experts"]
    met = (config["num_experts_per_tok"] * len(config["experts_held"])
           / experts)
    return {
        "M": d * in_width + inner * d,
        "*": d * (heads + 2 * kv) + heads * d,
        "E": (d * experts + 2 * d * latent
              + 2 * d * config["moe_shared_expert_intermediate_size"]
              + met * 2 * latent * ff),
    }


def matmul_params(config):
    per = layer_matmul_params(config)
    return (sum(per[kind] for kind in config["hybrid_override_pattern"])
            + config["vocab_size"] * config["hidden_size"])


def ssd_scan_work(batch, seq, heads, head_dim, groups, state, chunk):
    """``(flops, bytes)`` ONE Mamba-2 layer's scan NEEDS in a step,
    forward and backward, computed the chunked way at ``chunk``,
    whatever implements it; recomputation is not credited.

    Matmul FLOPs of a chunk of Q positions, forward: ``C B^T`` (2 Q^2 N
    a group), the masked ``[Q, Q]`` matrix times ``x`` (2 Q^2 P a
    head), the state a chunk adds (2 Q P N a head) and what the
    starting state gives each position (2 Q P N a head). Backward is
    twice forward (two gradient matmuls each). The decays' exponentials
    and cumulative sums are elementwise and not counted.

    Bytes, the least a fused implementation moves: forward reads x, B,
    C (activation type, 2 bytes) and dt (float32) and writes y;
    backward reads those and dy and writes dx, dB, dC and d(dt). The
    chunk states stay on the chip."""
    q = chunk
    chunks = -(-seq // q)
    per_chunk = (groups * 2 * q * q * state + heads * 2 * q * q * head_dim
                 + 2 * heads * 2 * q * head_dim * state)
    flops = 3.0 * batch * chunks * per_chunk
    tokens = batch * seq
    x, bc, dt = tokens * heads * head_dim, tokens * groups * state, \
        tokens * heads
    forward = 2 * x * 2 + 2 * bc * 2 + dt * 4
    backward = 3 * x * 2 + 4 * bc * 2 + 2 * dt * 4
    return flops, float(forward + backward)


def scan_work_of(config, batch, seq):
    """``ssd_scan_work`` of one Mamba-2 layer of ``config``."""
    return ssd_scan_work(batch, seq, config["mamba_num_heads"],
                         config["mamba_head_dim"], config["n_groups"],
                         config["ssm_state_size"], config["chunk_size"])


def model_flops_per_step(config, batch, seq):
    """6 N T, plus causal attention (forward QK^T and PV, 2 S^2 x the
    query heads' width each per attention layer and sequence, halved by
    causality; backward twice forward), plus the scans' matmuls."""
    pattern = config["hybrid_override_pattern"]
    tokens = batch * seq
    width = config["num_attention_heads"] * config["head_dim"]
    attn_fwd = pattern.count("*") * batch * 2 * (2.0 * seq * seq * width) / 2
    scan = pattern.count("M") * scan_work_of(config, batch, seq)[0]
    return 6.0 * matmul_params(config) * tokens + 3.0 * attn_fwd + scan
