"""chip_smoke.py rehearsed on the CPU: its phase functions at a tiny
size (2 layers, d_model 128, seq 256, the flash kernel interpreted,
dp=4 over virtual devices), and its entry point refusing to pass
without a chip. The chip run itself is `python chip_smoke.py` through
the builder's chip tool (.claude/skills/verify/SKILL.md)."""

import importlib.util
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from horovod_tpu.models import transformer as tfm


def _load(name):
    """A script at the repo root, as a module."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load("chip_smoke")

SEQ = 256


def _tiny():
    return smoke.wide1b_config(vocab=512, d_model=128, n_layers=2,
                               n_heads=1, d_ff=512, max_seq=SEQ,
                               loss_chunk=128)


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("dp",))


def test_full_size_config_is_the_1b_row():
    """The smoke's configuration is bench_lm.py's wide1b_dotsmu row."""
    row = dict(_load("bench_lm").CONFIGS["wide1b_dotsmu"])
    assert row.pop("batch") == smoke.BATCH_PER_CHIP
    assert row.pop("mu_bf16") is True
    cfg = smoke.wide1b_config()
    for key, value in row.items():
        assert getattr(cfg, key) == value, key
    assert (cfg.vocab, cfg.max_seq) == (32000, smoke.SEQ)
    assert smoke.FLASH_SHAPE == (smoke.BATCH_PER_CHIP, smoke.SEQ,
                                 cfg.n_heads, cfg.d_model // cfg.n_heads)


def test_eager_then_train_phases():
    cfg = _tiny()
    params = smoke.eager_phase(cfg)
    # Same init as the full size: the tied N(0,1) embedding puts the
    # first loss far above ln(vocab), 88.6 at this size.
    res = smoke.train_phase(cfg, _mesh(1), params, batch=2, seq=SEQ,
                            steps=5, first_loss_band=(60.0, 120.0),
                            min_kernels=0)
    assert len(res["losses"]) == 5
    assert res["alias"] > 0


def test_train_phase_rejects_a_wrong_band():
    cfg = _tiny()
    params = tfm.init_params(cfg, jax.random.PRNGKey(smoke.SEED))
    with pytest.raises(smoke.SmokeFailure, match="outside the band"):
        smoke.train_phase(cfg, _mesh(1), params, batch=2, seq=SEQ,
                          steps=1, first_loss_band=(9.0, 11.0),
                          min_kernels=0)


def test_train_steps_demand_the_kernel():
    """A step with no Pallas kernel in it (here: the interpreter) fails
    the count the chip run asks for."""
    cfg = _tiny()
    params = tfm.init_params(cfg, jax.random.PRNGKey(smoke.SEED))
    tokens = smoke.load_tokens(cfg, 2, SEQ)
    with pytest.raises(smoke.SmokeFailure, match="Pallas kernels"):
        smoke.train_steps(cfg, _mesh(1), params, tokens, steps=1,
                          min_kernels=3 * cfg.n_layers)


def test_flash_phase_interpreted():
    smoke.flash_phase((1, SEQ, 2, 128), interpret=True)


def test_flash_phase_rejects_a_wrong_kernel(monkeypatch):
    from horovod_tpu.ops import flash_attention as fa
    real = fa.flash_attention
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, *a: real(q, k, v * 1.5, *a))
    with pytest.raises(smoke.SmokeFailure, match="of scale"):
        smoke.flash_phase((1, SEQ, 2, 128), interpret=True)


def test_four_chip_phase_on_virtual_devices():
    got, ref = smoke.four_chip_phase(
        _tiny(), _mesh(4), per_chip_batch=2, seq=SEQ, steps=3,
        rtol=smoke.FOUR_CHIP_RTOL, min_kernels=0)
    assert len(got["losses"]) == len(ref["losses"]) == 3
    assert len(got["placed"]["params"]) == 4


def test_entry_point_fails_without_a_chip(capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform cpu" in out


def test_entry_point_four_chip_option_fails_without_chips(capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main(["--chips", "4"])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
