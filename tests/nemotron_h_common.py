"""What the two test files of the hybrid model share: a small float32
configuration, the configuration-file keys the reference reads of it,
a comparison of two parameter-shaped trees, and (with
``test_tpu_compile.py``) the program before the layers held anything."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from horovod_tpu.models import nemotron_h as nh  # noqa: E402
from horovod_tpu.models import transformer as tfm  # noqa: E402

PATTERN = "MEMEMEM*EME"        # the published pattern's first period


def make_cfg(**over):
    base = dict(
        vocab=96, d_model=32, pattern=PATTERN, dtype=jnp.float32,
        mamba_heads=8, mamba_head_dim=4, mamba_groups=2, state_size=8,
        chunk=16, n_heads=4, n_kv_heads=2, head_dim=8,
        n_routed_experts=32, experts_held=tuple(range(8)), top_k=6,
        routed_scaling=2.5, moe_latent=16, moe_ff=24, shared_ff=40,
        use_flash=False)
    base.update(over)
    return nh.NemotronHConfig(**base)


def hold_nothing(patch):
    """Under ``patch`` (a ``MonkeyPatch``) the layers' checkpoints hold
    no named array: ``remat_block`` as called without ``names``, the
    behaviour before ``nh.HELD_NAMES``."""
    remat_block = tfm.remat_block
    patch.setattr(tfm, "remat_block",
                  lambda *args, names=(), **kw: remat_block(*args, **kw))


def file_config(cfg):
    """The configuration-file keys the reference reads, of ``cfg``."""
    return {
        "hybrid_override_pattern": cfg.pattern,
        "layer_norm_epsilon": cfg.eps,
        "mamba_num_heads": cfg.mamba_heads,
        "mamba_head_dim": cfg.mamba_head_dim, "n_groups": cfg.mamba_groups,
        "ssm_state_size": cfg.state_size,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "experts_held": list(cfg.experts_held),
        "num_experts_per_tok": cfg.top_k,
        "routed_scaling_factor": cfg.routed_scaling}


def assert_trees_close(got, want, rtol=2e-4, atol=2e-6):
    flat_got, tree = jax.tree_util.tree_flatten_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.abs(b).max()) + atol
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=rtol * scale,
            err_msg=jax.tree_util.keystr(path))
