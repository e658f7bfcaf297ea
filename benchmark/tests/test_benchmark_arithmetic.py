"""FLOPs, bytes, peaks and tokens: the yardstick's arithmetic."""

import json

import numpy as np
import pytest

from benchmark import flops, manifest, peaks, tokens


def _config(name):
    return json.loads(
        (manifest.ROOT / "benchmark" / "configs" / f"{name}.json")
        .read_text())


def test_parameter_counts_match_the_papers_table():
    c13, c59 = _config("cerebras-gpt-1.3b"), _config("cerebras-gpt-590m")
    assert flops.total_params(dict(c13, n_layer=24)) / 1e9 == \
        pytest.approx(1.316, abs=0.002)
    assert flops.total_params(c13) / 1e9 == pytest.approx(1.114, abs=0.001)
    # 590M as published has 2048 positions
    assert flops.total_params(dict(c59, n_positions=2048)) / 1e6 == \
        pytest.approx(590, abs=1.5)


def test_model_flops_follow_6nt_plus_causal_attention():
    c = _config("cerebras-gpt-1.3b")
    per_token = flops.model_flops_per_step(c, 2, 2048) / 4096
    n = flops.matmul_params(c)
    attn = 6 * 2048 * c["n_embd"] * c["n_layer"]
    assert per_token == pytest.approx(6 * n + attn)
    # bench_lm.model_flops_per_step's form, with N the matmul parameters
    theirs = (6.0 * n * 4096
              + 3.0 * c["n_layer"] * 2.0 * 2 * 2048 * 2048
              * c["n_embd"] / 2.0 * 2.0)
    assert flops.model_flops_per_step(c, 2, 2048) == pytest.approx(theirs)


def test_flash_work_and_its_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    total = 0.0
    for kind, matmuls in (("fwd", 2), ("dkv", 3), ("dq", 2)):
        f, b = flops.flash_kernel_work(kind, 32, 2048, 128, 128)
        assert f == matmuls * 2048 * 2048 * 128 * 32   # causal half of 2S^2d
        assert flops.least_seconds(f, b, v5e)[1] == "compute"
        total += f
    # backward as a whole: 2.5 x forward
    assert total == pytest.approx(3.5 * flops.flash_kernel_work(
        "fwd", 32, 2048, 128, 128)[0])
    # a short sequence is bound by memory
    f, b = flops.flash_kernel_work("fwd", 32, 128, 128, 128)
    assert flops.least_seconds(f, b, v5e)[1] == "memory"


# what a single head width gave before the two were counted apart:
# matmuls of 2 S^2 d bh, [bh, S, d] bf16 operands, [bh, S, 1] f32 rows
OLD_TABLE = {"fwd": (2, 4, 1), "dkv": (3, 6, 2), "dq": (2, 5, 2)}


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kind", ["fwd", "dkv", "dq"])
def test_flash_work_at_equal_widths_is_the_single_width_table(kind, causal):
    matmuls, operands, rows = OLD_TABLE[kind]
    for bh, seq, d in ((32, 2048, 128), (32, 4096, 128), (12, 16384, 128),
                       (4, 256, 64)):
        one = 2.0 * seq * seq * d * bh
        if causal:
            one /= 2.0
        want = (matmuls * one,
                float(operands * bh * seq * d * 2 + rows * bh * seq * 4))
        assert flops.flash_kernel_work(kind, bh, seq, d, d,
                                       causal=causal) == want


def test_flash_work_counts_the_two_widths_apart():
    # 192 / 128: one(d) = S^2 d bh, causal
    one = lambda d: 256 * 256 * d * 4          # noqa: E731
    rows = 4 * 256 * 4
    assert flops.flash_kernel_work("fwd", 4, 256, 192, 128) == (
        one(192) + one(128), 4 * 256 * 2 * (2 * 192 + 2 * 128) + rows)
    assert flops.flash_kernel_work("dkv", 4, 256, 192, 128) == (
        2 * one(192) + one(128),
        4 * 256 * 2 * (3 * 192 + 3 * 128) + 2 * rows)
    assert flops.flash_kernel_work("dq", 4, 256, 192, 128) == (
        one(192) + one(128), 4 * 256 * 2 * (3 * 192 + 2 * 128) + 2 * rows)


def test_an_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 4242424242])
def test_tokens_come_from_the_seed(seed):
    a, ta = tokens.make_tokens(seed, 8, 64, 50257)
    b, tb = tokens.make_tokens(seed, 8, 64, 50257)
    assert a.shape == ta.shape == (8, 64) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a[:, 1:], ta[:, :-1])   # the next token
    assert 0 <= a.min() and a.max() < 50257
    other, _ = tokens.make_tokens(seed + 1, 8, 64, 50257)
    assert other.shape == a.shape and (other != a).any()
