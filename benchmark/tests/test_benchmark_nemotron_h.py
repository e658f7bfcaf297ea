"""The ``nemotron_h`` cell's own files: the kind end to end at a tiny
size on the CPU (a tiny configuration, traffic mix and cell ADDED to a
copy, as ``test_benchmark_harness.py`` does for kind ``train``), the
FLOPs and the scan's work against hand-computed values, and the
open-name reducer."""

import json
import re
import shutil

import pytest

from benchmark import (harness, nemotron_h_flops, program_trace,
                       trace_reduce)
from conftest import ROOT

TINY = {
    "source": "test", "family": "nemotron_h", "hidden_size": 64,
    "hybrid_override_pattern": "ME*", "layer_norm_epsilon": 1e-5,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 8, "conv_kernel": 4, "chunk_size": 16,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 4, "experts_held": [0, 1, 2, 3],
    "published": {"n_routed_experts": 32}, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "moe_latent_size": 32,
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 64,
    "vocab_size": 256, "max_position_embeddings": 4096, "reduced": []}
NEW_METRICS = ("ssm_ms_per_step", "ssd_scan_ms_per_step",
               "ssd_scan_roofline", "moe_ms_per_step",
               "moe_routed_ms_per_step", "moe_pairs_per_token")
# what every cell that trains through build_train_step reports: no list
SHARED_METRICS = ("attn_ms_per_step", "attn_kernel_ms_per_step",
                  "loss_head_ms_per_step", "optimizer_ms_per_step",
                  "scope_coverage", "input_queue_wait_ms_per_step")


@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    """A copy of the benchmark with a tiny hybrid cell added as files
    and entries; the new cell's metrics list it."""
    root = tmp_path_factory.mktemp("hybrid")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = root / "benchmark"
    (bench / "configs" / "tiny-hybrid.json").write_text(json.dumps(TINY))
    traffic = json.loads(
        (bench / "traffic" / "pretrain-s8k-b1.json").read_text())
    # float32: the comparison's limits are set at the cell's sizes; 256
    # tokens at width 64 in bfloat16 read at them (grad_rel 0.050)
    traffic.update(seq=256, sequences=64, loss_chunk=128, dtype="float32",
                   logits_bf16=False)
    (bench / "traffic" / "tiny-hybrid.json").write_text(json.dumps(traffic))
    manifest["configs"].append(
        {"name": "tiny-hybrid", "source": "test",
         "file": "benchmark/configs/tiny-hybrid.json", "reduced": [],
         "why": "test"})
    manifest["workloads"].append(
        {"name": "tiny-hybrid", "config": "tiny-hybrid",
         "traffic": "tiny-hybrid", "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny-hybrid")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def test_the_real_cell_is_made_of_files_that_are_there():
    from benchmark import manifest
    cell = manifest.cell("nemotron3s-s8k-1chip", ROOT)
    assert cell["chips"] == 1
    assert cell["traffic"]["kind"] == "train_nemotron_h"
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= names and "mfu" in names
    assert set(SHARED_METRICS) <= names          # no list, no twin
    assert not [n for n in names if n.startswith("hybrid_")]
    assert "mlp_ms_per_step" not in names        # the dense model's own
    for name in names:
        manifest.load_reader(cell["readers_dir"], name)
    config = cell["config"]
    assert config["hybrid_override_pattern"] == \
        config["published"]["hybrid_override_pattern"][:11]
    assert len(config["experts_held"]) == config["n_routed_experts"] == 8


def test_untraced_run_is_correct_and_counts_every_token(hybrid_root):
    result = harness.run_cell("tiny-hybrid", 2**31 + 5, 1.5, False,
                              root=hybrid_root, allow_cpu=True)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tok_s_per_chip", "setup_s"}
    record = json.loads((hybrid_root / harness.OUT_DIR / "tiny-hybrid"
                         / f"seed-{2**31 + 5}-trace-0.json").read_text())
    assert record["tokens_per_step"] == 256
    # 2 of 32 experts a token, 4 held: a quarter of a pair a token on
    # average
    assert 0 < record["moe_pairs_first_batch"] <= 256 * 2
    numbers = record["against_reference"]
    assert numbers["pairs"] == record["moe_pairs_first_batch"]
    assert numbers["choices_differing"] <= 8
    # every leaf of the three kinds of layer, the ends, and none skipped
    # but the routing's correction bias
    assert len(numbers["grad_rel_by_leaf"]) == 9 + 8 + 5 + 3
    assert not numbers["gradient_where_reference_has_none"]
    assert record["reference_loss"] == pytest.approx(
        record["first_loss"], rel=2e-3)


def test_traced_run_reports_what_its_readers_find(hybrid_root):
    result = harness.run_cell("tiny-hybrid", 7, 1.5, True,
                              root=hybrid_root, allow_cpu=True)
    got = set(result["metrics"])
    assert result["correct"] is True
    # the host-clock readers kind 'train' feeds, and the one counter
    assert {"lower_s", "compile_s", "input_wait_ms_per_step",
            "step_ms_p50", "step_ms_p90", "moe_pairs_per_token"} <= got
    assert 0.05 <= result["metrics"]["moe_pairs_per_token"]["value"] <= 1.0
    # no device plane on the CPU: the trace's readers find nothing
    assert not got & {"ssm_ms_per_step", "ssd_scan_roofline",
                      "scope_coverage", "device_idle_share"}


def test_the_kind_hands_its_model_to_the_one_training_loop(
        hybrid_root, monkeypatch):
    """``train_nemotron_h`` owns no loop: its run goes through
    ``kinds/train.py::_run`` with the model as one argument."""
    import inspect

    from benchmark.kinds import train, train_nemotron_h
    seen = []
    real = train._run

    def spy(ctx, mesh, model, *args):
        seen.append(model)
        return real(ctx, mesh, model, *args)
    monkeypatch.setattr(train, "_run", spy)
    result = harness.run_cell("tiny-hybrid", 11, 1.0, False,
                              root=hybrid_root, allow_cpu=True)
    assert result["correct"] is True and len(seen) == 1
    assert {"init_params", "param_specs", "against_reference",
            "flops_per_step"} <= set(vars(seen[0]))
    # every number the checks compared, beside its limit, comes last
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {
        "loss_rel", "grad_rel", "grad_rel_worst_leaf",
        "choices_differing_share"}
    assert all(number <= limit
               for number, limit in result["compared"].values())
    source = inspect.getsource(train_nemotron_h)
    for loop_piece in (".lower(", ".compile(", "compiled(", "_window(",
                       "WARMUP", "block_until_ready"):
        assert loop_piece not in source, loop_piece


@pytest.mark.parametrize("control,passes", [
    ("sound", True), ("weights_fp8", False), ("scan_drops_state", False),
    ("attn_wrong_group", False), ("routed_unscaled", False)])
def test_the_comparison_tells_a_sound_program_from_a_wrong_one(
        hybrid_root, control, passes):
    """``controls_nemotron_h.run`` puts the program, as it is and made
    wrong from outside, through the kind's own comparison."""
    from benchmark import controls_nemotron_h, manifest
    cell = manifest.cell("tiny-hybrid", hybrid_root)
    row, = controls_nemotron_h.run(cell, 2**31 + 5, [control],
                                   out=lambda line: None)
    assert all(row["checks"].values()) is passes, (
        row["checks"], row["grad_rel"], row["grad_rel_worst_leaf"],
        row["worst_leaf"], row["choices_differing_share"])


def test_a_layout_beyond_dp_is_refused(hybrid_root):
    traffic = json.loads((hybrid_root / "benchmark" / "traffic"
                          / "tiny-hybrid.json").read_text())
    (hybrid_root / "benchmark" / "traffic" / "tiny-hybrid-tp.json"
     ).write_text(json.dumps(dict(traffic, layout={"dp": 1, "tp": 1})))
    bad = json.loads((hybrid_root / "BENCHMARK.json").read_text())
    bad["workloads"].append({"name": "tiny-hybrid-tp",
                             "config": "tiny-hybrid",
                             "traffic": "tiny-hybrid-tp", "chips": 1,
                             "why": "test"})
    (hybrid_root / "BENCHMARK.json").write_text(json.dumps(bad))
    with pytest.raises(harness.Refused, match="'dp' alone"):
        harness.run_cell("tiny-hybrid-tp", 1, 1.0, False,
                         root=hybrid_root, allow_cpu=True)


def _published():
    return json.loads((ROOT / "benchmark" / "configs"
                       / "nemotron-3-super-120b-a12b.json").read_text())


def test_matmul_parameters_by_hand():
    per = nemotron_h_flops.layer_matmul_params(_published())
    # in_proj 4096 x (2 x 8192 + 2 x 8 x 128 + 128) + out_proj 8192 x 4096
    assert per["M"] == 4096 * 18560 + 8192 * 4096 == 109576192
    # q and o 4096 x 4096 each, k and v 4096 x 256 each
    assert per["*"] == 2 * 4096 * 4096 + 2 * 4096 * 256 == 35651584
    # router 4096 x 512, latent 2 x 4096 x 1024, shared 2 x 4096 x 5376,
    # and 22 x 8 / 512 of an expert's 2 x 1024 x 2688 a token
    assert per["E"] == pytest.approx(
        4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
        + 22 * 8 / 512 * 2 * 1024 * 2688)
    # 5 M + 5 E + 1 * and the untied head 16384 x 4096: 932.7M
    assert nemotron_h_flops.matmul_params(_published()) == pytest.approx(
        932.733e6, rel=1e-5)


def test_scan_work_and_model_flops_by_hand():
    # one chunk of 128: C B^T 8 x 2 x 128^2 x 128, M x 128 x 2 x 128^2 x
    # 64, state in and out 2 x 128 x 2 x 128 x 64 x 128 = 838,860,800;
    # 32 chunks at 4096, forward + backward = 3 x
    flops, nbytes = nemotron_h_flops.ssd_scan_work(1, 4096, 128, 64, 8,
                                                   128, 128)
    assert flops == 3 * 32 * 838860800
    # x, y, (x, dy, dx) = 5 x 4096 x 8192 x 2; B, C twice forward and
    # four times backward = 6 x 4096 x 1024 x 2; dt 3 x 4096 x 128 x 4
    assert nbytes == (5 * 4096 * 8192 * 2 + 6 * 4096 * 1024 * 2
                      + 3 * 4096 * 128 * 4)
    config = _published()
    attn = 3 * 2 * (2 * 4096 * 4096 * 4096) / 2
    assert nemotron_h_flops.model_flops_per_step(config, 1, 4096) == \
        pytest.approx(6 * 932.732928e6 * 4096 + attn + 5 * flops)
    # a sequence that ends inside a chunk still pays for the whole chunk
    assert nemotron_h_flops.ssd_scan_work(1, 130, 4, 8, 2, 8, 128)[0] == \
        2 * nemotron_h_flops.ssd_scan_work(1, 128, 4, 8, 2, 8, 128)[0]


@pytest.mark.parametrize("stack,name", [
    ("jit(hvd_train_step)/transpose(jvp(hvd_ssm))/hvd_ssd_scan/dot_general",
     "hvd_ssd_scan"),
    ("jit(hvd_train_step)/jvp(hvd_moe)/hvd_moe_routed/while/body/gather",
     "hvd_moe_routed"),
    ("jit(hvd_train_step)/transpose(jvp(hvd_attn))/hvd_flash_dq/pallas_call",
     "hvd_flash_dq"),
    ("jit(hvd_train_step)/hvd_a_scope_nobody_listed/mul",
     "hvd_a_scope_nobody_listed"),
    # the jitted function's own name is no scope
    ("jit(hvd_train_step)/jit(main)/convert_element_type", "unscoped"),
    ("", "unscoped"),
])
def test_the_open_rule_takes_the_innermost_name(stack, name):
    assert program_trace.name_of_stack(stack) == name


# the closed list ``program_trace.NAME`` was before PR 35
CLOSED = re.compile(
    r"(?<![A-Za-z0-9_])hvd_(?:embed|attn|mlp|loss_head|grad_reduce|"
    r"optimizer|flash_[a-z0-9]+)(?![A-Za-z0-9_])")


def test_the_open_rule_reads_what_the_closed_list_read_on_a_v5e_trace(
        monkeypatch):
    """On the recorded trace of a dense cell every name is one the
    closed list knew, so the open rule splits the window as it did."""
    packed = ROOT / "benchmark" / "tests" / "data" / \
        "gpt1b3-s2k-1chip.v5e.2steps.xplane.pb.gz"
    planes = trace_reduce.read_planes(packed)
    opened = program_trace.reduce(planes)
    monkeypatch.setattr(trace_reduce, "NAME", CLOSED)
    closed = program_trace.reduce(planes)
    assert opened.steps == closed.steps and opened.devices == closed.devices
    assert opened.busy_s == pytest.approx(closed.busy_s)
    assert closed.names is not None and "hvd_flash_dkv" in closed.names
    assert opened.names == closed.names
    assert opened.spans == closed.spans
