"""What the test files of the dense linear-attention hybrid share: a
small float32 configuration, the configuration-file keys the reference
reads of it, and a comparison of two parameter-shaped trees."""

import sys
from pathlib import Path

import jax.numpy as jnp

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from nemotron_h_common import assert_trees_close  # noqa: E402,F401

from horovod_tpu.models import olmo_hybrid as oh  # noqa: E402


def make_cfg(**over):
    """The published period in small: three DeltaNet layers at heads of
    12 / 24 (no multiple of anything, ``dk != dv``), then one of
    attention; three heads, which no block of 4 divides."""
    base = dict(
        vocab=96, d_model=48, d_ff=80, dtype=jnp.float32,
        gdn_key_heads=3, gdn_value_heads=3, gdn_key_dim=12,
        gdn_value_dim=24, chunk=16, gdn_groups=1, n_heads=3,
        use_flash=False)
    base.update(over)
    return oh.OlmoHybridConfig(**base)


def file_config(cfg):
    """The configuration-file keys the reference reads, of ``cfg``."""
    return {
        "num_hidden_layers": len(cfg.layer_types),
        "layer_types": list(cfg.layer_types),
        "rms_norm_eps": cfg.eps,
        "linear_num_key_heads": cfg.gdn_key_heads,
        "linear_num_value_heads": cfg.gdn_value_heads,
        "linear_key_head_dim": cfg.gdn_key_dim,
        "linear_value_head_dim": cfg.gdn_value_dim,
        "linear_allow_neg_eigval": cfg.allow_neg_eigval,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_heads,
        "rope_parameters": {"rope_theta": None}}
