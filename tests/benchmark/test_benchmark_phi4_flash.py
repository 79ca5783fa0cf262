"""The `phi4flash-train-s8192` cell (Phi-4-mini-flash-reasoning's six-layer
cut, one chip of a pipeline): its files against the contract, the
parameters, FLOPs and the scan's operations and bytes against hand counts,
the metrics it reads, and a tiny configuration of the same builder through
the harness on the CPU.  Nothing measured here is a speed."""

import json
import os
import re
import shutil

import pytest

from benchmark import phi4_flash, run
from benchmark.readers import program_counter
from flexflow_tpu.runtime import profiling

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "phi4flash-train-s8192"
BIG_SEED = 2 ** 31 + 42   # a seed past 32 signed bits, as a run may get
OWN_METRICS = {"ssm_scan_ms_per_step", "ssm_scan_roofline",
               "ssm_mixer_ms_per_step", "gmu_ms_per_step", "ssm_dt_mean"}
SHARED_METRICS = {
    "attention_fwd_ms_per_step", "attention_bwd_ms_per_step",
    "embedding_ms_per_step", "mfu_block_median",
    "samples_per_s_per_chip_window",
    "forward_ms_per_step", "backward_ms_per_step", "optimizer_ms_per_step",
    "step_prepare_ms_per_step", "step_enqueue_ms_per_step",
    "metric_drain_ms_per_block", "idle_in_update_ms_per_step",
    "idle_in_sync_ms_per_step", "idle_in_drain_ms_per_step",
    "train_step_compiles", "setup_before_program_s", "setup_graph_build_s",
    "setup_init_layers_s", "setup_step_compile_calls_s",
    "setup_step_trace_s", "setup_step_lower_s", "setup_step_backend_s",
    "compile_s", "host_dispatch_ms_per_step", "read_loss_ms_per_block",
    "samples_per_s_per_chip_block_median", "device_idle_share",
    "peak_hbm_gib"}
# what this model has none of: experts, latent attention, an index, a sink
ABSENT = {"moe_experts_ms_per_step", "mla_attention_ms_per_step",
          "dsa_index_ms_per_step", "swa_sink_share",
          "gqa_full_attention_ms_per_step", "attention_ms_per_step"}


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
    assert cell["chips"] == 1
    assert cell["config_name"] == "phi-4-mini-flash-reasoning"
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
            assert m["layer"] == "kernels"
    entry = next(c for c in bench["configs"]
                 if c["name"] == "phi-4-mini-flash-reasoning")
    assert entry["reduced"] == cell["config"]["reduced"] == [
        "num_hidden_layers", "vocab_size"]
    assert entry["source"] == cell["config"]["source"]
    assert cell["traffic"]["batch_per_chip"] == 1
    assert cell["config"]["builder_kwargs"]["seq_length"] == 8192


def test_the_metrics_read_the_program_s_names(cell):
    """The scan's metric reads its two kernels and nothing else; the
    mixer's reads the Mamba op's five scopes and not the scan; the
    counter is the one the op counts."""
    from flexflow_tpu.kernels import ssm_scan
    from flexflow_tpu.kernels.flash_attention import kernel_names
    from flexflow_tpu.ops.ssm import Mamba

    metrics = cell["layer_metrics"]
    rx = re.compile(metrics["ssm_scan_ms_per_step"]["scope"])
    assert all(rx.search("ff.kernel." + k) for k in ssm_scan.KERNELS)
    assert not any(rx.search("ff.kernel." + k)
                   for k in kernel_names() + kernel_names(512))
    rx = re.compile(metrics["ssm_mixer_ms_per_step"]["span"])
    for span in ("in_proj", "conv", "dt", "gate", "out_proj"):
        assert rx.search("ff.ssm." + span)
    assert not rx.search("ff.ssm.scan") and not rx.search("ff.gmu.in_proj")
    assert re.search(metrics["gmu_ms_per_step"]["span"], "ff.gmu.gate")
    assert metrics["ssm_dt_mean"]["counter"] in Mamba.COUNTERS
    assert metrics["ssm_scan_roofline"]["time_metric"] == \
        "ssm_scan_ms_per_step"


def test_published_keys_are_kept_and_no_width_is_cut(cell):
    config = cell["config"]
    assert config["source"] == ("https://huggingface.co/microsoft/"
                                "Phi-4-mini-flash-reasoning/blob/main/"
                                "config.json")
    kw = config["builder_kwargs"]
    published = config["published"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key     # every width as published
        if key in kw and key not in config["reduced"]:
            assert kw[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"]) == (6, 25008)
    assert kw["layers"] == [0, 1, 16, 17, 18, 19]
    assert kw["num_hidden_layers"] == published["num_hidden_layers"] == 32
    assert kw["vocab_size"] * 8 == published["vocab_size"]
    # the Mamba family's defaults, stated under assumed
    assert (kw["d_state"], kw["d_conv"], kw["expand"], kw["dt_rank"]) == (
        16, 4, 2, 160)
    assert {"mamba_sizes", "mamba_init", "layout", "memory",
            "differential_attention", "optimizer"} <= set(config["assumed"])
    assert config["optimizer"] == {"class": "SGDOptimizer",
                                   "kwargs": {"lr": 0.01}}


def test_parameters_against_the_hand_count(cell):
    kw = cell["config"]["builder_kwargs"]
    mamba = (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 + 4 * 5120
             + 5120 + 5120 * 16 + 5120 + 5120 * 2560)
    attention = 2 * 2560 * 2560 + 2 * 2560 * 1280
    cross = 2 * 2560 * 2560
    gmu = 2 * 2560 * 5120
    mlp = 3 * 2560 * 10240
    assert (mamba, attention, cross, gmu, mlp) == (
        41241600, 19660800, 13107200, 26214400, 78643200)
    norms = 13 * 2 * 2560
    table = 25008 * 2560
    total = 2 * mamba + 2 * attention + cross + gmu + 6 * mlp + norms + table
    assert phi4_flash.parameters(**kw) == total == 697072640 \
        == cell["config"]["deployment"]["parameters"]
    # the whole model, every layer and the whole vocabulary: 3.8B
    whole = dict(kw, layers=None, vocab_size=200064)
    assert phi4_flash.parameters(**whole) == 3852451840
    assert phi4_flash.layer_kinds(**whole).count("mamba") == 8


def test_train_flops_at_the_cell_s_shape(cell):
    kw = cell["config"]["builder_kwargs"]
    t = 8192
    per_token = 696770560
    assert phi4_flash.matmul_params_per_token(**kw) == per_token
    band = 512 * 513 // 2 + (t - 512) * 512
    assert band == 4063488
    want = 6 * per_token * t + 2 * 3 * t * t * 40 * 128 \
        + 3 * 2 * band * 40 * 128
    assert phi4_flash.train_flops(**kw) == pytest.approx(want)
    assert round(want / 1e12, 2) == 36.43


def test_the_scan_s_operations_and_bytes(cell):
    """Two Mamba layers of 8192 tokens, 5120 channels and 16 states."""
    kw = cell["config"]["builder_kwargs"]
    t, d, n = 8192, 5120, 16
    act = t * d * 2
    bc, a, states = t * 2 * n * 4, d * n * 4, t // 128 * d * n * 4
    layer = (3 * act + bc + a + states) + (5 * act + 2 * bc + 2 * a + states)
    ops, nbytes = phi4_flash.ssm_scan_train(batch=1, **kw)
    assert nbytes == 2 * layer
    assert round(layer / 1e9, 3) == 0.717
    assert ops == 2 * 22 * t * d * n


def test_formulas_at_a_small_shape():
    """The tiny configuration's six layers by hand."""
    with open(os.path.join(HERE, "data", "phi-4-mini-flash-tiny.json")) as f:
        kw = json.load(f)["builder_kwargs"]
    mamba_mm = 64 * 256 + 128 * 12 + 4 * 128 + 128 * 64
    mamba = mamba_mm + 4 * 128 + 128 + 128 + 128 * 4 + 128
    attention = 2 * 64 * 64 + 2 * 64 * 32
    mlp = 3 * 64 * 96
    assert phi4_flash.parameters(**kw) == (
        128 * 64 + 2 * mamba + 2 * attention + 2 * 64 * 64 + 2 * 64 * 128
        + 6 * mlp + 13 * 2 * 64)
    per_token = 128 * 64 + 2 * mamba_mm + 2 * attention + 2 * 64 * 64 \
        + 2 * 64 * 128 + 6 * mlp
    assert phi4_flash.matmul_params_per_token(**kw) == per_token
    band = 8 * 9 // 2 + 56 * 8
    want = 6 * per_token * 64 + 3 * (2 * 64 * 64 * 8 * 16
                                     + 2 * band * 8 * 16)
    assert phi4_flash.train_flops(**kw) == pytest.approx(want)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with the tiny configuration as a cell that
    reads every per-layer metric the real cell reads."""
    top = str(tmp_path_factory.mktemp("bench_phi4_flash"))
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(top, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(HERE, "data", "phi-4-mini-flash-tiny.json"),
                os.path.join(top, "benchmark", "configs"))
    shutil.copy(os.path.join(HERE, "data", "tiny-resident.json"),
                os.path.join(top, "benchmark", "traffic"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "phi-4-mini-flash-tiny", "source": "tests", "reduced": [],
        "why": "tests",
        "file": "benchmark/configs/phi-4-mini-flash-tiny.json"})
    bench["workloads"].append({
        "name": "phi-4-mini-flash-tiny.resident",
        "config": "phi-4-mini-flash-tiny", "traffic": "tiny-resident",
        "chips": 1, "why": "tests"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("phi-4-mini-flash-tiny.resident")
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return top


def test_tiny_cell_runs_through_the_harness(root):
    import jax

    cell = run.load_cell(root, "phi-4-mini-flash-tiny.resident")
    assert set(cell["layer_metrics"]) >= OWN_METRICS | SHARED_METRICS
    cell["peaks"] = {jax.devices()[0].device_kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    lines = []
    res = run.run_cell(cell, BIG_SEED, 1.0, False, say=lines.append)
    assert res["correct"] is True, lines
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"step_ms_p90", "setup_s"}
    assert any("first loss" in ln and "reference" in ln for ln in lines)
    # the step size came out with the drains, and its reader finds it:
    # b_dt is drawn so that softplus of it lies in [1e-3, 1e-1]
    ctx = run.Context(say=lines.append)
    dt = program_counter.read(ctx, cell["layer_metrics"]["ssm_dt_mean"])
    assert 1e-3 < dt < 0.2
    assert dt == pytest.approx(profiling.counters()["ssm_dt_mean"])
