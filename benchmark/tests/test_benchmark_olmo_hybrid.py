"""The ``olmo_hybrid`` cell's own files: the kind end to end at a tiny
size on the CPU (a tiny configuration, traffic mix and cell ADDED to a
copy, as ``test_benchmark_qwen3_next.py`` does), the comparison's
controls, the readers, and the FLOPs and the delta rule's work against
hand-computed values."""

import json
import shutil

import pytest

from benchmark import harness, olmo_hybrid_flops
from conftest import ROOT

CELL = "olmo-hybrid-s16k-1chip"
TINY = {
    "source": "test", "family": "olmo_hybrid", "hidden_size": 48,
    "intermediate_size": 80, "num_hidden_layers": 4,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"]
    + ["linear_attention"] * 4,
    "rms_norm_eps": 1e-6, "linear_num_key_heads": 3,
    "linear_num_value_heads": 3, "linear_key_head_dim": 12,
    "linear_value_head_dim": 24, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "num_attention_heads": 3,
    "num_key_value_heads": 3, "rope_parameters": {"rope_theta": None},
    "vocab_size": 256, "max_position_embeddings": 4096,
    "program": {"gdn_groups": 3, "gdn_chunk": 16}, "reduced": []}
NEW_METRICS = ("linattn_ms_per_step", "linattn_kernel_ms_per_step",
               "linattn_kernel_roofline", "gated_mlp_ms_per_step")
SHARED_METRICS = ("attn_ms_per_step", "attn_kernel_ms_per_step",
                  "loss_head_ms_per_step", "optimizer_ms_per_step",
                  "scope_coverage", "input_queue_wait_ms_per_step", "mfu",
                  "flash_ms_per_step", "flash_roofline", "peak_hbm_gb")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of the benchmark with a tiny cell of this kind added as
    files and entries; the new cell's metrics list it."""
    root = tmp_path_factory.mktemp("olmohybrid")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = root / "benchmark"
    (bench / "configs" / "tiny-olmo.json").write_text(json.dumps(TINY))
    traffic = json.loads(
        (bench / "traffic" / "longctx-s16k-b1-olmo.json").read_text())
    # float32: the comparison's limits are set at the cell's sizes; a
    # rate at which 128 tokens a step fall further than batches differ
    traffic.update(seq=128, sequences=64, loss_chunk=64, dtype="float32",
                   logits_bf16=False,
                   optimizer=dict(traffic["optimizer"], learning_rate=0.003))
    (bench / "traffic" / "tiny-olmo.json").write_text(json.dumps(traffic))
    manifest["configs"].append(
        {"name": "tiny-olmo", "source": "test",
         "file": "benchmark/configs/tiny-olmo.json", "reduced": [],
         "why": "test"})
    manifest["workloads"].append(
        {"name": "tiny-olmo", "config": "tiny-olmo", "traffic": "tiny-olmo",
         "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny-olmo")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def _published():
    return json.loads((ROOT / "benchmark" / "configs"
                       / "olmo-hybrid-7b.json").read_text())


def test_the_real_cell_is_made_of_files_that_are_there():
    from benchmark import manifest
    cell = manifest.cell(CELL, ROOT)
    assert cell["chips"] == 1
    assert cell["traffic"]["kind"] == "train_olmo_hybrid"
    assert cell["traffic"]["seq"] == 16384
    assert cell["traffic"]["batch_per_chip"] == 1
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= names and set(SHARED_METRICS) <= names
    # the other models' own metrics are not this cell's
    assert not names & {"mlp_ms_per_step", "gdn_ms_per_step",
                        "delta_rule_roofline", "ssm_ms_per_step",
                        "gmm_roofline", "grad_reduce_gb_per_step"}
    for name in names:
        manifest.load_reader(cell["readers_dir"], name)
    whole = manifest.load(ROOT)
    for m in whole["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tok_s_per_chip"
    assert len(whole["workloads"]) == 6 and len(whole["configs"]) == 5
    assert all(len(e["why"]) <= 200
               for e in whole["workloads"] + whole["configs"])


def test_every_width_is_the_catalog_rows():
    """Only the two keys under ``reduced`` differ from the source's
    ``config.json`` (the catalog row of the model-configs guide);
    ``layer_types`` is kept whole, its first period is what runs."""
    period = ["linear_attention"] * 3 + ["full_attention"]
    source = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": period * 8, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    config = _published()
    differ = sorted(k for k, v in source.items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    assert {k: source[k] for k in differ} == config["published"]
    assert olmo_hybrid_flops.layer_kinds(config) == period
    assert config["vocab_size"] * 8 == 100352
    assert config["source"].startswith("https://huggingface.co/allenai/")
    for key in ("rope_parameters", "norm_placement"):
        assert key in config["assumed"], key
    assert "8 chips" in config["deployment"]
    assert config["departures"] and config["what_the_cut_costs"]


def test_the_kinds_configuration_is_the_files():
    from benchmark import manifest
    from benchmark.kinds import train_olmo_hybrid
    cell = manifest.cell(CELL, ROOT)
    cfg = train_olmo_hybrid.model_config(cell["config"], cell["traffic"])
    assert cfg.layer_types == ("linear_attention",) * 3 + (
        "full_attention",)
    assert (cfg.d_model, cfg.d_ff, cfg.vocab) == (3840, 11008, 12544)
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.gdn_groups, cfg.chunk) == (
                30, 30, 96, 192, 5, 64)
    assert cfg.gdn_beta_scale == 2.0 and cfg.head_dim == 128
    assert cfg.remat and cfg.remat_policy == "full" and cfg.use_flash
    with pytest.raises(harness.Refused, match="family"):
        train_olmo_hybrid.model_config(
            dict(cell["config"], family="qwen3_next"), cell["traffic"])
    with pytest.raises(harness.Refused, match="dp"):
        train_olmo_hybrid.model_config(
            cell["config"], dict(cell["traffic"], layout={"tp": 1}))
    with pytest.raises(harness.Refused, match="position term"):
        train_olmo_hybrid.model_config(
            dict(cell["config"], rope_parameters={"rope_theta": 5e5}),
            cell["traffic"])


def test_a_program_without_the_model_is_refused_at_once(monkeypatch):
    """On the parent commit ``horovod_tpu.models.olmo_hybrid`` does not
    import: the kind refuses the cell (exit 2 of the command) before
    any program is built."""
    import sys

    import horovod_tpu.models
    from benchmark import manifest
    from benchmark.kinds import train_olmo_hybrid
    monkeypatch.setitem(sys.modules, "horovod_tpu.models.olmo_hybrid", None)
    monkeypatch.delattr(horovod_tpu.models, "olmo_hybrid", raising=False)
    cell = manifest.cell(CELL, ROOT)
    with pytest.raises(harness.Refused, match="no olmo_hybrid model"):
        train_olmo_hybrid.model_config(cell["config"], cell["traffic"])


def test_untraced_run_is_correct_and_counts_every_token(tiny_root):
    result = harness.run_cell("tiny-olmo", 2**31 + 5, 1.5, False,
                              root=tiny_root, allow_cpu=True)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tok_s_per_chip", "setup_s"}
    record = json.loads((tiny_root / harness.OUT_DIR / "tiny-olmo"
                         / f"seed-{2**31 + 5}-trace-0.json").read_text())
    assert record["tokens_per_step"] == 128
    numbers = record["against_reference"]
    # every leaf: 3 DeltaNet mixers of 8, 1 attention mixer of 7, 4
    # MLPs of 3, and the three ends
    assert len(numbers["grad_rel_by_leaf"]) == 3 * 8 + 7 + 4 * 3 + 3
    assert numbers["grad_rel"] < 1e-2
    assert record["reference_loss"] == pytest.approx(
        record["first_loss"], rel=2e-3)
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {
        "loss_rel", "grad_rel", "grad_rel_worst_leaf"}


def test_traced_run_reports_what_its_readers_find(tiny_root):
    result = harness.run_cell("tiny-olmo", 7, 1.5, True, root=tiny_root,
                              allow_cpu=True)
    got = set(result["metrics"])
    assert result["correct"] is True
    assert {"lower_s", "compile_s", "input_wait_ms_per_step",
            "step_ms_p50", "step_ms_p90"} <= got
    # no device plane on the CPU: the trace's readers find nothing
    assert not got & (set(NEW_METRICS) | {"scope_coverage"})


def test_the_kind_hands_its_model_to_the_one_training_loop():
    import inspect

    from benchmark.kinds import train_olmo_hybrid
    source = inspect.getsource(train_olmo_hybrid)
    assert "train.run_model(ctx, cfg, model(cfg, config))" in source
    for loop_piece in (".lower(", ".compile(", "compiled(", "_window(",
                       "WARMUP", "block_until_ready", "build_train_step"):
        assert loop_piece not in source, loop_piece


@pytest.mark.parametrize("control,passes", [
    # (every planted fault, one by one: tests/test_olmo_hybrid.py)
    ("sound", True), ("no_delta_term", False),
    # the stated float32 parts in bfloat16: whether the comparison tells
    # it is the chip's to say, at the cell's sizes; here, that it runs
    ("float32_parts_bf16", None)])
def test_the_comparison_tells_a_sound_program_from_a_wrong_one(
        tiny_root, control, passes):
    """``controls_olmo_hybrid.run`` puts the program, as it is and made
    wrong from outside, through the kind's own comparison."""
    from benchmark import controls_olmo_hybrid, manifest
    cell = manifest.cell("tiny-olmo", tiny_root)
    row, = controls_olmo_hybrid.run(cell, 2**31 + 5, [control],
                                    out=lambda line: None)
    assert passes is None or all(row["checks"].values()) is passes, (
        row["checks"], row["grad_rel"], row["grad_rel_worst_leaf"],
        row["worst_leaf"])


def test_a_readers_missing_names_give_nothing_and_do_not_raise():
    """On a program without the scopes (the parent commit cannot run
    the cell; a trace that names nothing) every new reader returns
    None."""
    from benchmark import manifest, program_trace
    cell = manifest.cell(CELL, ROOT)
    trace = program_trace.ProgramTrace(
        steps=2, devices=1, busy_s=1.0,
        names={"hvd_attn": {"fwd": 0.5}, "unscoped": {"": 0.5}})
    run = {"cell": cell, "peaks": {"bf16_flops_per_s": 1e12,
                                   "hbm_bytes_per_s": 1e11},
           "tokens_per_step": 16384, program_trace.CACHE_KEY: trace}
    for name in NEW_METRICS:
        assert manifest.load_reader(cell["readers_dir"], name)(run) is None
    bare = {"cell": cell, "tokens_per_step": 16384}
    for name in NEW_METRICS:
        assert manifest.load_reader(cell["readers_dir"], name)(bare) is None


def test_the_new_readers_read_their_names():
    from benchmark import manifest, program_trace
    cell = manifest.cell(CELL, ROOT)
    trace = program_trace.ProgramTrace(
        steps=2, devices=1, busy_s=1.0, names={
            "hvd_gdn": {"fwd": 0.010, "bwd": 0.020},
            "hvd_gdn_conv": {"fwd": 0.002}, "hvd_delta_rule": {"bwd": 0.008},
            "hvd_mlp": {"fwd": 0.1, "bwd": 0.2, "remat": 0.1},
            "hvd_attn": {"fwd": 0.05}})
    run = {"cell": cell, "peaks": {"bf16_flops_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9},
           "tokens_per_step": 16384, program_trace.CACHE_KEY: trace}

    def read(name):
        return manifest.load_reader(cell["readers_dir"], name)(run)

    assert read("linattn_ms_per_step") == pytest.approx(20.0)
    assert read("linattn_kernel_ms_per_step") == pytest.approx(4.0)
    assert read("gated_mlp_ms_per_step") == pytest.approx(200.0)
    flops, nbytes = olmo_hybrid_flops.delta_rule_work_of(
        cell["config"], 1, 16384)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("linattn_kernel_roofline") == pytest.approx(
        100 * 3 * least / 0.004)


def test_matmul_parameters_by_hand():
    per = olmo_hybrid_flops.layer_matmul_params(_published())
    # W_q, W_k 3840 x 2880, W_v, W_g 3840 x 5760, W_a, W_b 3840 x 30,
    # W_o 5760 x 3840
    assert per["linear_attention"] == (
        2 * 3840 * 2880 + 2 * 3840 * 5760 + 2 * 3840 * 30 + 5760 * 3840
    ) == 88704000
    assert per["full_attention"] == 4 * 3840 * 3840
    assert per["mlp"] == 3 * 3840 * 11008 == 126812160
    # three DeltaNet layers, one of attention, four MLPs and the untied
    # head 12544 x 3840: the 880.5M of the issue
    total = olmo_hybrid_flops.matmul_params(_published())
    assert total == (3 * 88704000 + 58982400 + 4 * 126812160
                     + 12544 * 3840)
    assert round(total / 1e6, 1) == 880.5


def test_delta_rule_work_and_model_flops_by_hand():
    # one chunk of 64, one head of 96 / 192 AS PUBLISHED (the columns of
    # zeros are not credited): K K^T and Q K^T 2 x 2 x 64^2 x 96, T on
    # keys 2 x 64^2 x 96 and on values 2 x 64^2 x 192, the chunk's
    # output 2 x 64^2 x 192, and three products with the state 3 x 2 x
    # 64 x 96 x 192
    per_chunk = (3 * 2 * 64 * 64 * 96 + 2 * 2 * 64 * 64 * 192
                 + 3 * 2 * 64 * 96 * 192)
    config = _published()
    flops, nbytes = olmo_hybrid_flops.delta_rule_work_of(config, 1, 16384)
    assert flops == 3 * 256 * 30 * per_chunk
    # q, k forward and q, k, dq, dk backward: 6 arrays of 16384 x 30 x
    # 96 x 2 bytes; v, o and v, do, dv, (o): 6 of 16384 x 30 x 192 x 2;
    # g and beta 2 + 4 times float32
    assert nbytes == (6 * 16384 * 30 * 96 * 2 + 6 * 16384 * 30 * 192 * 2
                      + 6 * 16384 * 30 * 4)
    attn = 3 * 2 * (2 * 16384 * 16384 * 3840) / 2
    want = (6 * olmo_hybrid_flops.matmul_params(config) * 16384 + attn
            + 3 * flops)
    assert olmo_hybrid_flops.model_flops_per_step(config, 1, 16384) == \
        pytest.approx(want)
    # the issue's 93.6 TF a step
    assert round(want / 1e12, 1) == 93.6
