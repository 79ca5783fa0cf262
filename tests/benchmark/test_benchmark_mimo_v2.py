"""The files of the cell `mimo2-train-s8192` (configuration, reference,
formulas, per-layer metrics) against hand counts and the catalog's row, and
at a tiny size on the CPU through the harness's own functions: the command
itself refuses a CPU.  The tiny cell is added to a copy of the benchmark as
a later PR adds one: new files and entries.  Nothing this file measures is
a speed."""

import json
import math
import os
import shutil

import pytest

from benchmark import deepseek_v2, mimo_v2, run
from benchmark.readers import program_counter
from flexflow_tpu.runtime import profiling

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "mimo2-train-s8192"
BIG_SEED = 2 ** 31 + 40   # a seed past 32 signed bits, as a run may get
OWN_METRICS = {
    "gqa_projection_ms_per_step", "gqa_full_attention_ms_per_step",
    "gqa_full_attention_roofline", "gqa_window_attention_ms_per_step",
    "gqa_window_attention_roofline", "swa_sink_share"}
SHARED_METRICS = {
    "moe_route_ms_per_step", "moe_experts_ms_per_step",
    "moe_experts_roofline", "moe_assignments_kept_per_token",
    "moe_dropped_share", "moe_load_max_over_mean", "mfu_block_median",
    "embedding_ms_per_step",
    "forward_ms_per_step", "backward_ms_per_step", "optimizer_ms_per_step",
    "step_prepare_ms_per_step", "step_enqueue_ms_per_step",
    "metric_drain_ms_per_block", "idle_in_update_ms_per_step",
    "idle_in_sync_ms_per_step", "idle_in_drain_ms_per_step",
    "train_step_compiles", "setup_before_program_s", "setup_graph_build_s",
    "setup_init_layers_s", "setup_step_compile_calls_s",
    "setup_step_trace_s", "setup_step_lower_s", "setup_step_backend_s",
    "compile_s", "host_dispatch_ms_per_step", "read_loss_ms_per_block",
    "samples_per_s_per_chip_block_median", "device_idle_share",
    "peak_hbm_gib", "samples_per_s_per_chip_window",
    "attention_fwd_ms_per_step", "attention_bwd_ms_per_step"}
# what this model has none of: a shared expert, latent attention, an index
ABSENT = {"moe_shared_ms_per_step", "mla_projection_ms_per_step",
          "mla_attention_ms_per_step", "attention_ms_per_step",
          "swa_attention_ms_per_step", "dsa_index_ms_per_step"}


@pytest.fixture(autouse=True)
def _own_counters(monkeypatch):
    """The program's counters are process-wide, and other files' tests
    read ratios of them (a worker runs several files in one process):
    what these tests count is put back."""
    monkeypatch.setattr(profiling, "_counters", dict(profiling._counters))


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(REPO, CELL)


def test_the_cell_as_benchmark_json_has_it(cell):
    assert cell["chips"] == 1 and cell["config_name"] == "mimo-v2-flash"
    assert {m["name"] for m in cell["end_to_end"]} == {"step_ms_p90",
                                                       "setup_s"}
    assert set(cell["layer_metrics"]) >= OWN_METRICS | SHARED_METRICS
    assert not ABSENT & set(cell["layer_metrics"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in OWN_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "step_ms_p90"
            assert m["unit"] == cell["layer_metrics"][m["name"]]["unit"]
    entry = next(c for c in bench["configs"] if c["name"] == "mimo-v2-flash")
    assert entry["reduced"] == cell["config"]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "num_attention_heads",
        "num_key_value_heads", "swa_num_attention_heads",
        "swa_num_key_value_heads", "vocab_size", "hybrid_layer_pattern",
        "moe_layer_freq"]
    assert entry["source"] == cell["config"]["source"]
    assert cell["traffic"]["batch_per_chip"] == 1
    assert cell["config"]["builder_kwargs"]["seq_length"] == 8192
    # the kernels' names the two core metrics read are those of the calls
    from flexflow_tpu.kernels.flash_attention import kernel_names
    import re
    for metric, names in (
            ("gqa_full_attention_ms_per_step", kernel_names()),
            ("gqa_window_attention_ms_per_step",
             kernel_names(128, sunk=True))):
        rx = re.compile(cell["layer_metrics"][metric]["scope"])
        assert all(rx.search("ff.kernel." + n) for n in names)
        others = set(kernel_names(128) + kernel_names(selected=True)
                     + kernel_names() + kernel_names(128, sunk=True)) \
            - set(names)
        assert not any(rx.search("ff.kernel." + n) for n in others)


def test_published_keys_are_kept_and_no_width_is_cut(cell):
    config = cell["config"]
    assert config["source"] == ("https://huggingface.co/XiaomiMiMo/"
                                "MiMo-V2-Flash/blob/main/config.json")
    kw = config["builder_kwargs"]
    for key, value in config["published"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key     # every width as published
        if key in kw and key not in config["reduced"]:
            assert kw[key] == value, key
    # what is held: layers 0-6, a quarter of the query heads and the
    # key/value heads they read, 8 experts of the router's 256, an eighth
    # of the vocabulary
    published = config["published"]
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        assert kw[key] == config[key] == published[key][:7]
    assert kw["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert (kw["num_hidden_layers"], kw["num_attention_heads"],
            kw["num_key_value_heads"], kw["swa_num_attention_heads"],
            kw["swa_num_key_value_heads"], kw["experts_held"],
            kw["n_routed_experts"], kw["vocab_size"]) == (
                7, 64 // 4, 4 // 4, 64 // 4, 8 // 4, 8, 256, 152576 // 8)
    # the published groups are kept: 16 and 8 query heads a key/value head
    assert kw["num_attention_heads"] // kw["num_key_value_heads"] == \
        published["num_attention_heads"] // published["num_key_value_heads"]
    assert kw["swa_num_attention_heads"] // kw["swa_num_key_value_heads"] \
        == published["swa_num_attention_heads"] \
        // published["swa_num_key_value_heads"]
    assert int(kw["partial_rotary_factor"] * kw["head_dim"]) == 64
    assert kw["vocab_size"] % 128 == 0
    assert config["deployment"]["chips_per_layer"] == 32
    assert next(iter(config["assumed"])) == "attention_value_scale"


def test_parameters_against_the_hand_count(cell):
    """The held parameters, matrices by hand, and the vectors beside
    them."""
    kw = cell["config"]["builder_kwargs"]
    d = 4096
    full = d * 16 * 192 + d * 192 + d * 128 + 16 * 128 * d
    window = d * 16 * 192 + d * 2 * 192 + d * 2 * 128 + 16 * 128 * d
    assert (full, window) == (22282240, 23592960)
    dense = 3 * d * 16384
    router, experts = d * 256, 8 * 3 * d * 2048
    assert (dense, router, experts) == (201326592, 1048576, 201326592)
    vocab = 2 * 19072 * d
    assert vocab == 156237824
    vectors = 15 * d + 5 * 16                      # norms, window sinks
    total = 2 * full + 5 * window + dense + 6 * (router + experts) + vocab \
        + vectors
    assert mimo_v2.parameters(**kw) == total == 1734406224 \
        == cell["config"]["deployment"]["parameters"]
    # 4 B a parameter resident: 6.94 GB
    assert round(4 * mimo_v2.parameters(**kw) / 1e9, 2) == 6.94


def test_train_flops_and_rooflines_at_the_cell_s_shape(cell):
    kw = cell["config"]["builder_kwargs"]
    t = 8192
    per_token = 19072 * 4096 + 2 * 22282240 + 5 * 23592960 \
        + 3 * 4096 * 16384 + 6 * (4096 * 256 + 0.25 * 3 * 4096 * 2048)
    assert mimo_v2.matmul_params_per_token(**kw) == per_token == 486014976
    band = 128 * 129 // 2 + (t - 128) * 128
    assert band == 1040448
    full = t * t * 16 * 320                        # a layer, forward
    window = 2 * band * 16 * 320
    want = 6 * per_token * t + 3 * (2 * full + 5 * window)
    assert mimo_v2.train_flops(**kw) == pytest.approx(want)
    assert round(want / 1e12, 2) == 26.11                 # TFLOP a step
    flops, nbytes = mimo_v2.full_attention_train(batch=1, **kw)
    assert flops == pytest.approx(2 * 3 * full)
    assert nbytes == 2 * t * (3 * 17 * 192 + 3 * 17 * 128) * 2
    flops, nbytes = mimo_v2.window_attention_train(batch=1, **kw)
    assert flops == pytest.approx(5 * 3 * window)
    assert nbytes == 5 * t * (3 * 18 * 192 + 3 * 18 * 128) * 2
    # the experts' formula is deepseek-v2's at this configuration's sizes:
    # 6 expert layers, no shared expert, a budget of 2048 rows in 16 + 8
    # tiles of 128, the 8 experts' float32 weights read twice and their
    # gradient written once
    spec = cell["layer_metrics"]["moe_experts_roofline"]
    assert spec["formula"] == "deepseek_v2.routed_experts_train"
    flops, nbytes = deepseek_v2.routed_experts_train(batch=1, **kw)
    rows = (math.ceil(2048 / 128) + 8) * 128
    expert = 3 * 4096 * 2048
    assert flops == pytest.approx(6 * 3 * 2 * rows * expert)
    assert nbytes == pytest.approx(6 * (3 * 8 * expert * 4
                                        + rows * 4 * (4096 + 2048) * 2))


def test_formulas_at_a_small_shape():
    """Two full layers and two window layers of 64 tokens, by hand."""
    with open(os.path.join(HERE, "data", "mimo-v2-tiny.json")) as f:
        kw = json.load(f)["builder_kwargs"]
    full = 64 * 8 * 24 + 64 * 2 * 24 + 64 * 2 * 16 + 8 * 16 * 64
    window = 64 * 8 * 24 + 64 * 4 * 24 + 64 * 4 * 16 + 8 * 16 * 64
    moe = 64 * 16 + 4 * 3 * 64 * 32
    vectors = 9 * 64 + 2 * 8
    assert mimo_v2.parameters(**kw) == (
        2 * 128 * 64 + 2 * full + 2 * window + 3 * 64 * 96 + 3 * moe
        + vectors)
    per_token = 128 * 64 + 2 * full + 2 * window + 3 * 64 * 96 \
        + 3 * (64 * 16 + 4 * 4 / 16 * 3 * 64 * 32)
    assert mimo_v2.matmul_params_per_token(**kw) == per_token
    band = 8 * 9 // 2 + 56 * 8
    want = 6 * per_token * 64 + 3 * (2 * 64 * 64 * 8 * 40
                                     + 2 * 2 * band * 8 * 40)
    assert mimo_v2.train_flops(**kw) == pytest.approx(want)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with the tiny configuration as a cell that
    reads every per-layer metric the real cell reads."""
    top = str(tmp_path_factory.mktemp("bench_mimo_v2"))
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(top, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(HERE, "data", "mimo-v2-tiny.json"),
                os.path.join(top, "benchmark", "configs"))
    shutil.copy(os.path.join(HERE, "data", "tiny-resident.json"),
                os.path.join(top, "benchmark", "traffic"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "mimo-v2-tiny", "source": "tests", "reduced": [],
        "why": "tests", "file": "benchmark/configs/mimo-v2-tiny.json"})
    bench["workloads"].append({
        "name": "mimo-v2-tiny.resident", "config": "mimo-v2-tiny",
        "traffic": "tiny-resident", "chips": 1, "why": "tests"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("mimo-v2-tiny.resident")
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return top


def test_tiny_cell_runs_through_the_harness(root):
    import jax

    cell = run.load_cell(root, "mimo-v2-tiny.resident")
    assert set(cell["layer_metrics"]) >= OWN_METRICS | SHARED_METRICS
    cell["peaks"] = {jax.devices()[0].device_kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    lines = []
    res = run.run_cell(cell, BIG_SEED, 1.0, False, say=lines.append)
    assert res["correct"] is True, lines
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"step_ms_p90", "setup_s"}
    assert any("first loss" in ln and "reference" in ln for ln in lines)
    assert not any("CHECK FAILED" in ln for ln in lines)
    # the sink's share came out with the drains, and its reader finds it:
    # the mean over the two window layers and the drained steps, at most
    # what a sink of zero takes beside one key
    ctx = run.Context(say=lines.append)
    share = program_counter.read(ctx, cell["layer_metrics"]["swa_sink_share"])
    assert 1 / 9 / 2 < share < 0.5
    assert share == pytest.approx(profiling.counters()["swa_sink_share"])
    kept = program_counter.read(ctx, cell["layer_metrics"][
        "moe_assignments_kept_per_token"])
    assert 0 < kept <= 4 * 4 / 16
