"""The reader of the flash kernels' visited block pairs
(``flash_blocks_skipped_share``): on the gauge the kernels set where
they are traced, at the cells' shapes and default blocks; and on a
program without the gauge, as the parent of the change that added it
is."""

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest
from conftest import ROOT

NAME = "flash_blocks_skipped_share"


def _reader():
    return manifest.load_reader(ROOT / "benchmark" / "layer_metrics", NAME)


@pytest.fixture
def fresh(monkeypatch):
    from horovod_tpu.observability import registry
    monkeypatch.setattr(registry, "_registry", registry.MetricsRegistry())
    monkeypatch.setattr(registry, "_enabled", True)
    return registry


def test_without_the_gauge_the_reader_reads_nothing(fresh, monkeypatch):
    read = _reader()
    assert read({}) is None
    fresh.registry().gauge("hvdtpu_flash_block_pairs", "x").labels(
        kernel="fwd", visited="yes").set(3)
    monkeypatch.setattr(fresh, "enabled", lambda: False)
    assert read({}) is None


# [B, S, H, hd] of each cell's attention and the share its default
# blocks leave unvisited
CELLS = [
    ((1, 16384, 12, 128), 46.875),     # gpt590m-s16k-1chip, olmo at 30 heads
    ((1, 8192, 16, 256), 46.875),      # qwen3next-s8k-1chip
    ((1, 4096, 32, 128), 37.5),        # nemotron3s-s8k-1chip
    ((2, 2048, 16, 128), 25.0),        # the two 2k cells
]


@pytest.mark.parametrize("shape,share", CELLS,
                         ids=["x".join(map(str, s)) for s, _ in CELLS])
def test_the_share_at_the_cells_shapes(fresh, shape, share):
    from horovod_tpu.ops.flash_attention import flash_attention

    def loss(q):
        o = flash_attention(q, q, q, True, None, None, None, False)
        return o.astype(jnp.float32).sum()

    jax.eval_shape(jax.grad(loss), jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    assert _reader()({}) == pytest.approx(share)


def test_without_causal_nothing_is_skipped(fresh):
    from horovod_tpu.ops.flash_attention import flash_attention

    def loss(q):
        o = flash_attention(q, q, q, False, None, None, None, False)
        return o.astype(jnp.float32).sum()

    jax.eval_shape(jax.grad(loss),
                   jax.ShapeDtypeStruct((1, 4096, 2, 128), jnp.bfloat16))
    assert _reader()({}) == 0.0
