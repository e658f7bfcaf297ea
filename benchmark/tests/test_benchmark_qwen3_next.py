"""The ``qwen3_next`` cell's own files: the kind end to end at a tiny
size on the CPU (a tiny configuration, traffic mix and cell ADDED to a
copy, as ``test_benchmark_nemotron_h.py`` does), the comparison's
controls, and the FLOPs, the delta rule's and the grouped matmuls' work
against hand-computed values."""

import json
import shutil

import pytest

from benchmark import harness, qwen3_next_flops
from conftest import ROOT

TINY = {
    "source": "test", "family": "qwen3_next", "hidden_size": 64,
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "rms_norm_eps": 1e-6, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "num_experts": 4, "experts_held": [0, 1, 2, 3],
    "published": {"num_experts": 32}, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "vocab_size": 256, "max_position_embeddings": 4096,
    "program": {"gdn_groups": 2, "gdn_chunk": 16}, "reduced": []}
NEW_METRICS = ("gdn_ms_per_step", "delta_rule_ms_per_step",
               "delta_rule_roofline", "gmm_ms_per_step", "gmm_roofline",
               "moe_dispatch_ms_per_step", "expert_layer_ms_per_step")
SHARED_METRICS = ("attn_ms_per_step", "attn_kernel_ms_per_step",
                  "loss_head_ms_per_step", "optimizer_ms_per_step",
                  "scope_coverage", "input_queue_wait_ms_per_step", "mfu",
                  "flash_ms_per_step", "flash_roofline", "peak_hbm_gb")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of the benchmark with a tiny cell of this kind added as
    files and entries; the new cell's metrics list it."""
    root = tmp_path_factory.mktemp("qwen3next")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = root / "benchmark"
    (bench / "configs" / "tiny-q3n.json").write_text(json.dumps(TINY))
    traffic = json.loads(
        (bench / "traffic" / "pretrain-s8k-b1-q3n.json").read_text())
    # float32: the comparison's limits are set at the cell's sizes; a
    # rate at which 256 tokens a step fall further than batches differ
    traffic.update(seq=256, sequences=64, loss_chunk=128, dtype="float32",
                   logits_bf16=False,
                   optimizer=dict(traffic["optimizer"], learning_rate=0.003))
    (bench / "traffic" / "tiny-q3n.json").write_text(json.dumps(traffic))
    manifest["configs"].append(
        {"name": "tiny-q3n", "source": "test",
         "file": "benchmark/configs/tiny-q3n.json", "reduced": [],
         "why": "test"})
    manifest["workloads"].append(
        {"name": "tiny-q3n", "config": "tiny-q3n", "traffic": "tiny-q3n",
         "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny-q3n")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def _published():
    return json.loads((ROOT / "benchmark" / "configs"
                       / "qwen3-next-80b-a3b.json").read_text())


def test_the_real_cell_is_made_of_files_that_are_there():
    from benchmark import manifest
    cell = manifest.cell("qwen3next-s8k-1chip", ROOT)
    assert cell["chips"] == 1
    assert cell["traffic"]["kind"] == "train_qwen3_next"
    assert cell["traffic"]["seq"] == 8192
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= names and set(SHARED_METRICS) <= names
    # the other models' own metrics are not this cell's
    assert not names & {"mlp_ms_per_step", "ssm_ms_per_step",
                        "moe_pairs_per_token", "grad_reduce_gb_per_step"}
    for name in names:
        manifest.load_reader(cell["readers_dir"], name)
    config = cell["config"]
    assert len(config["experts_held"]) == config["num_experts"] == 32
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}


def test_every_width_is_the_catalog_rows():
    """Only the three keys under ``reduced`` differ from the source's
    ``config.json`` (the catalog row of the model-configs guide)."""
    source = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    config = _published()
    differ = sorted(k for k, v in source.items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert {k: source[k] for k in differ} == config["published"]


def test_untraced_run_is_correct_and_counts_every_token(tiny_root):
    result = harness.run_cell("tiny-q3n", 2**31 + 5, 1.5, False,
                              root=tiny_root, allow_cpu=True)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tok_s_per_chip", "setup_s"}
    record = json.loads((tiny_root / harness.OUT_DIR / "tiny-q3n"
                         / f"seed-{2**31 + 5}-trace-0.json").read_text())
    assert record["tokens_per_step"] == 256
    # 2 of 32 experts a token, 4 held: a quarter of a row a token and
    # layer on average, over 4 layers
    assert 0 < record["moe_rows_first_batch"] <= 4 * 256 * 2
    numbers = record["against_reference"]
    assert numbers["rows"] == record["moe_rows_first_batch"]
    assert numbers["choices_differing"] <= 8
    # every leaf: 3 DeltaNet mixers of 8, 1 attention mixer of 7, 4
    # expert layers of 7, and the three ends
    assert len(numbers["grad_rel_by_leaf"]) == 3 * 8 + 7 + 4 * 7 + 3
    assert record["reference_loss"] == pytest.approx(
        record["first_loss"], rel=2e-3)
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {
        "loss_rel", "grad_rel", "grad_rel_worst_leaf",
        "choices_differing_share"}


def test_traced_run_reports_what_its_readers_find(tiny_root):
    result = harness.run_cell("tiny-q3n", 7, 1.5, True, root=tiny_root,
                              allow_cpu=True)
    got = set(result["metrics"])
    assert result["correct"] is True
    assert {"lower_s", "compile_s", "input_wait_ms_per_step",
            "step_ms_p50", "step_ms_p90"} <= got
    # no device plane on the CPU: the trace's readers find nothing
    assert not got & {"gdn_ms_per_step", "delta_rule_roofline",
                      "gmm_roofline", "moe_dispatch_ms_per_step",
                      "expert_layer_ms_per_step", "scope_coverage"}


def test_the_kind_hands_its_model_to_the_one_training_loop():
    import inspect

    from benchmark.kinds import train_qwen3_next
    source = inspect.getsource(train_qwen3_next)
    assert "train.run_model(ctx, cfg, model(cfg, config))" in source
    for loop_piece in (".lower(", ".compile(", "compiled(", "_window(",
                       "WARMUP", "block_until_ready", "build_train_step"):
        assert loop_piece not in source, loop_piece


@pytest.mark.parametrize("control,passes", [
    ("sound", True), ("no_delta_term", False),
    ("weights_unnormalised", False),
    # both float32 parts in bfloat16 at once: whether the comparison tells
    # it is the chip's to say, at the cell's sizes; here, that it runs
    ("float32_parts_bf16", None)])
def test_the_comparison_tells_a_sound_program_from_a_wrong_one(
        tiny_root, control, passes):
    """``controls_qwen3_next.run`` puts the program, as it is and made
    wrong from outside, through the kind's own comparison."""
    from benchmark import controls_qwen3_next, manifest
    cell = manifest.cell("tiny-q3n", tiny_root)
    row, = controls_qwen3_next.run(cell, 2**31 + 5, [control],
                                   out=lambda line: None)
    assert passes is None or all(row["checks"].values()) is passes, (
        row["checks"], row["grad_rel"], row["grad_rel_worst_leaf"],
        row["worst_leaf"], row["choices_differing_share"])


def test_a_readers_missing_names_give_nothing_and_do_not_raise():
    """On a program without the new scopes (the parent commit) and
    without the probe's counter every new reader returns None."""
    from benchmark import manifest, program_trace
    cell = manifest.cell("qwen3next-s8k-1chip", ROOT)
    trace = program_trace.ProgramTrace(
        steps=2, devices=1, busy_s=1.0,
        names={"hvd_attn": {"fwd": 0.5}, "unscoped": {"": 0.5}})
    run = {"cell": cell, "peaks": {"bf16_flops_per_s": 1e12,
                                   "hbm_bytes_per_s": 1e11},
           "tokens_per_step": 8192, program_trace.CACHE_KEY: trace}
    for name in NEW_METRICS:
        assert manifest.load_reader(cell["readers_dir"], name)(run) is None
    bare = {"cell": cell, "tokens_per_step": 8192}
    for name in NEW_METRICS:
        assert manifest.load_reader(cell["readers_dir"], name)(bare) is None


def test_the_new_readers_read_their_names():
    from benchmark import manifest, program_trace
    cell = manifest.cell("qwen3next-s8k-1chip", ROOT)
    trace = program_trace.ProgramTrace(
        steps=2, devices=1, busy_s=1.0, names={
            "hvd_gdn": {"fwd": 0.010, "bwd": 0.020},
            "hvd_gdn_conv": {"fwd": 0.002}, "hvd_delta_rule": {"bwd": 0.008},
            "hvd_gmm_fwd": {"fwd": 0.001, "remat": 0.001},
            "hvd_gmm_dw": {"bwd": 0.002}, "hvd_moe_dispatch": {"fwd": 0.006},
            "hvd_moe": {"fwd": 0.1}, "hvd_moe_router": {"remat": 0.004}})
    run = {"cell": cell, "peaks": {"bf16_flops_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9},
           "tokens_per_step": 8192, "moe_pairs_per_step": 8 * 5120,
           "moe_layers": 8, program_trace.CACHE_KEY: trace}

    def read(name):
        return manifest.load_reader(cell["readers_dir"], name)(run)

    assert read("gdn_ms_per_step") == pytest.approx(20.0)
    assert read("delta_rule_ms_per_step") == pytest.approx(4.0)
    assert read("gmm_ms_per_step") == pytest.approx(2.0)
    assert read("moe_dispatch_ms_per_step") == pytest.approx(3.0)
    # the layers whole: hvd_moe, what lies under hvd_moe_ and the kernels
    assert read("expert_layer_ms_per_step") == pytest.approx(
        50.0 + 2.0 + 3.0 + 2.0)
    # the rows go by the key the accepted moe_pairs_per_token reads
    assert manifest.load_reader(cell["readers_dir"], "moe_pairs_per_token")(
        run) == pytest.approx(5120 / 8192)
    flops, nbytes = qwen3_next_flops.delta_rule_work_of(
        cell["config"], 1, 8192)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("delta_rule_roofline") == pytest.approx(
        100 * 6 * least / 0.004)
    flops, nbytes = qwen3_next_flops.gmm_work_of(cell["config"], 5120)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("gmm_roofline") == pytest.approx(100 * 8 * least / 0.002)


def test_matmul_parameters_by_hand():
    per = qwen3_next_flops.layer_matmul_params(_published())
    # in_proj 2048 x (2 x 16 x 128 + 2 x 32 x 128 = 12288), in_ba 2048
    # x 64, out_proj 4096 x 2048
    assert per["D"] == 2048 * 12288 + 2048 * 64 + 4096 * 2048 == 33685504
    # q with its gate 2048 x 8192, k and v 2048 x 512 each, o 4096 x 2048
    assert per["A"] == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    # router 2048 x 512, shared expert 3 x 2048 x 512 and its gate, and
    # 10 x 32 / 512 of an expert's 3 x 2048 x 512 a token
    assert per["E"] == pytest.approx(
        2048 * 512 + 3 * 2048 * 512 + 2048 + 10 * 32 / 512 * 3 * 2048 * 512)
    # 6 D + 2 A + 8 E and the untied head 18992 x 2048
    assert qwen3_next_flops.matmul_params(_published()) == pytest.approx(
        6 * 33685504 + 2 * 27262976 + 8 * 6162432 + 18992 * 2048)


def test_delta_rule_and_grouped_matmul_work_by_hand():
    # one chunk of 64, one head of 128 / 128: K K^T and Q K^T 2 x 2 x
    # 64^2 x 128, T on keys and values 2 x 2 x 64^2 x 128, the chunk's
    # output 2 x 64^2 x 128, and three products with the state 3 x 2 x
    # 64 x 128 x 128
    per_chunk = 5 * 2 * 64 * 64 * 128 + 3 * 2 * 64 * 128 * 128
    assert per_chunk == 11534336
    flops, nbytes = qwen3_next_flops.delta_rule_work(1, 8192, 32, 128, 128,
                                                     64)
    assert flops == 3 * 128 * 32 * per_chunk
    # q, k, v, o forward and q, k, v, do, dq, dk, dv, (o) backward: 12
    # arrays of 8192 x 32 x 128 x 2 bytes; g and beta 2 + 4 times float32
    assert nbytes == 12 * 8192 * 32 * 128 * 2 + 6 * 8192 * 32 * 4
    # a sequence that ends inside a chunk still pays for the whole chunk
    assert qwen3_next_flops.delta_rule_work(1, 65, 2, 8, 8, 64)[0] == \
        2 * qwen3_next_flops.delta_rule_work(1, 64, 2, 8, 8, 64)[0]
    # 100 rows through 3 x 2048 x 512 of an expert, forward and twice
    # backward; 32 experts' float32 weights read twice and written once
    flops, nbytes = qwen3_next_flops.gmm_work(100, 2048, 512, 32)
    assert flops == 3 * 100 * 2 * 3 * 2048 * 512
    assert nbytes == (3 * 32 * 3 * 2048 * 512 * 4
                      + 3 * 100 * 2 * (2 * 2048 + 6 * 512))
    config = _published()
    attn = 2 * 3 * 2 * (2 * 8192 * 8192 * 4096) / 2
    assert qwen3_next_flops.model_flops_per_step(config, 1, 8192) == \
        pytest.approx(6 * qwen3_next_flops.matmul_params(config) * 8192
                      + attn + 6 * 3 * 128 * 32 * per_chunk)
