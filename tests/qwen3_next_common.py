"""What the test files of the linear-attention model share: a small
float32 configuration, the configuration-file keys the reference reads
of it, and a comparison of two parameter-shaped trees."""

import sys
from pathlib import Path

import jax.numpy as jnp

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from nemotron_h_common import assert_trees_close  # noqa: E402,F401

from horovod_tpu.models import qwen3_next as qn  # noqa: E402


def make_cfg(**over):
    """Two periods of the published pattern in small: three DeltaNet
    layers then one of attention, twice; 8 of 32 experts held."""
    base = dict(
        vocab=96, d_model=32, n_layers=8, full_attention_interval=4,
        dtype=jnp.float32, gdn_key_heads=4, gdn_value_heads=8,
        gdn_key_dim=8, gdn_value_dim=8, chunk=16, gdn_groups=2,
        n_heads=4, n_kv_heads=2, head_dim=16, rotary_dim=4,
        n_experts=32, experts_held=tuple(range(8)), top_k=6,
        moe_ff=24, shared_ff=24, use_flash=False)
    base.update(over)
    return qn.Qwen3NextConfig(**base)


def file_config(cfg):
    """The configuration-file keys the reference reads, of ``cfg``."""
    return {
        "num_hidden_layers": cfg.n_layers,
        "full_attention_interval": cfg.full_attention_interval,
        "rms_norm_eps": cfg.eps,
        "linear_num_key_heads": cfg.gdn_key_heads,
        "linear_num_value_heads": cfg.gdn_value_heads,
        "linear_key_head_dim": cfg.gdn_key_dim,
        "linear_value_head_dim": cfg.gdn_value_dim,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "partial_rotary_factor": cfg.rotary_dim / cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "experts_held": list(cfg.experts_held),
        "num_experts_per_tok": cfg.top_k}
