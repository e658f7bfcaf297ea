"""The plain reference against the program at a tiny size on the CPU,
and the tolerance against a precision below the configuration's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, tokens
from benchmark.kinds import train
from conftest import TINY_CONFIG

TRAFFIC = {"seq": 256, "dtype": "bfloat16", "remat_policy": "dots",
           "use_flash": True, "logits_bf16": True, "loss_chunk": 128,
           "layout": {"dp": 1}}


@pytest.fixture(scope="module")
def setting():
    from horovod_tpu.models import transformer as tfm
    cfg = train.transformer_config(TINY_CONFIG, TRAFFIC)
    params = tfm.init_params(cfg, jax.random.PRNGKey(3))
    toks, tgts = tokens.make_tokens(3, 4, 256, cfg.vocab)
    ref = reference.reference_loss(params, toks, tgts, TINY_CONFIG)
    return cfg, params, toks, tgts, ref


def _program_loss(cfg, params, toks, tgts):
    from horovod_tpu.models import transformer as tfm
    return float(jax.jit(lambda p: tfm.loss_fn(
        p, jnp.asarray(toks), jnp.asarray(tgts), cfg))(params))


def test_float32_program_matches_the_reference_closely(setting):
    import dataclasses
    cfg, params, toks, tgts, ref = setting
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32, use_flash=False,
                                logits_bf16=False)
    got = _program_loss(cfg32, params, toks, tgts)
    assert abs(got - ref) / ref < 2e-5


def test_the_configurations_bfloat16_is_inside_the_tolerance(setting):
    cfg, params, toks, tgts, ref = setting
    got = _program_loss(cfg, params, toks, tgts)
    assert abs(got - ref) / ref <= reference.loss_tolerance(toks.size)


def test_a_bfloat16_softmax_is_outside_the_tolerance(setting, monkeypatch):
    from horovod_tpu.models import transformer as tfm
    cfg, params, toks, tgts, ref = setting
    real = tfm._project_logits
    # the logits stay bfloat16, so log_softmax and the loss run in it
    monkeypatch.setattr(
        tfm, "_project_logits",
        lambda p, x, c: real(p, x, c).astype(jnp.bfloat16))
    got = _program_loss(cfg, params, toks, tgts)
    assert abs(got - ref) / ref > reference.loss_tolerance(toks.size)


def test_blocks_and_chunks_do_not_change_the_reference(setting):
    _, params, toks, tgts, ref = setting
    other = reference.reference_loss(params, toks[:1], tgts[:1],
                                     TINY_CONFIG, q_block=64, chunk=32)
    whole = reference.reference_loss(params, toks[:1], tgts[:1],
                                     TINY_CONFIG, q_block=256, chunk=256)
    assert other == pytest.approx(whole, rel=1e-6)


def test_tolerance_shrinks_up_to_4096_tokens_and_stays_under_bf16():
    assert reference.loss_tolerance(1024) > reference.loss_tolerance(4096)
    assert reference.loss_tolerance(4096) == pytest.approx(5.08e-4, rel=0.01)
    # correlated roundings: the chip read 2.1e-4 at 16384 tokens
    assert reference.loss_tolerance(16384) == reference.loss_tolerance(4096)
    assert reference.loss_tolerance(4096) < 2.0 ** -8 / 7
