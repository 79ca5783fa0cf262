"""The files of the cell `dots3-train-s8192` (configuration, reference,
formulas, per-layer metrics) against hand counts and the catalog's row, and
at a tiny size on the CPU through the harness's own functions: the command
itself refuses a CPU.  The tiny cell is added to a copy of the benchmark as
a later PR adds one: new files and entries.  Nothing this file measures is
a speed."""

import json
import os
import shutil

import pytest

from benchmark import dots3, run
from benchmark.readers import program_counter
from flexflow_tpu.runtime import profiling

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "dots3-train-s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BIG_SEED = 2 ** 31 + 35   # the driver's seeds pass 32 signed bits
OWN_METRICS = {
    "dsa_index_ms_per_step", "dsa_index_roofline", "dsa_select_ms_per_step",
    "dsa_loss_ms_per_step", "dsa_attention_ms_per_step",
    "dsa_attention_roofline", "swa_attention_ms_per_step",
    "swa_attention_roofline", "dsa_index_kl"}
SHARED_METRICS = {
    "mla_projection_ms_per_step", "moe_route_ms_per_step",
    "moe_experts_ms_per_step", "moe_experts_roofline",
    "moe_shared_ms_per_step", "moe_assignments_kept_per_token",
    "moe_dropped_share", "moe_load_max_over_mean", "mfu_block_median",
    "embedding_ms_per_step",
    "forward_ms_per_step", "backward_ms_per_step", "optimizer_ms_per_step",
    "step_prepare_ms_per_step", "step_enqueue_ms_per_step",
    "metric_drain_ms_per_block", "idle_in_update_ms_per_step",
    "idle_in_sync_ms_per_step", "train_step_compiles",
    "compile_s", "host_dispatch_ms_per_step", "read_loss_ms_per_block",
    "samples_per_s_per_chip_block_median", "device_idle_share",
    "peak_hbm_gib"}


@pytest.fixture(autouse=True)
def _own_counters(monkeypatch):
    """The program's counters are process-wide, and other files' tests
    read ratios of them (a worker runs several files in one process):
    what these tests count is put back."""
    from flexflow_tpu.runtime import profiling
    monkeypatch.setattr(profiling, "_counters", dict(profiling._counters))


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(REPO, CELL)


def test_the_cell_as_benchmark_json_has_it(cell):
    assert cell["chips"] == 1 and cell["config_name"] == "dots3-note-prev"
    assert {m["name"] for m in cell["end_to_end"]} == {"step_ms_p90",
                                                       "setup_s"}
    assert set(cell["layer_metrics"]) >= OWN_METRICS | SHARED_METRICS
    # the flash kernels of plain causal attention are not in its graph
    assert not {"mla_attention_ms_per_step", "attention_ms_per_step"} \
        & set(cell["layer_metrics"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in OWN_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "step_ms_p90"
            assert m["unit"] == cell["layer_metrics"][m["name"]]["unit"]
    entry = next(c for c in bench["configs"] if c["name"] == "dots3-note-prev")
    assert entry["reduced"] == cell["config"]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "num_attention_heads",
        "swa_num_attention_heads", "vocab_size"]
    assert entry["source"] == cell["config"]["source"]
    traffic = cell["traffic"]
    assert traffic["batch_per_chip"] == 1
    assert cell["config"]["builder_kwargs"]["seq_length"] == 8192


def test_published_is_the_catalog_s_row_and_no_width_is_cut(cell):
    config = cell["config"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "dots3-note-prev")
        assert config["published"] == row["config"]
        assert config["source"] == row["source_url"]
    kw = config["builder_kwargs"]
    for key, value in config["published"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key     # every width as published
        if key in kw and key not in config["reduced"] + ["layer_types"]:
            assert kw[key] == value, key
    # what is held: layers 0-4, 4 and 2 heads, 8 experts of the router's
    # 256, an eighth of the vocabulary
    assert kw["layer_types"] == config["published"]["layer_types"][:5]
    assert (kw["num_hidden_layers"], kw["num_attention_heads"],
            kw["swa_num_attention_heads"], kw["experts_held"],
            kw["n_routed_experts"], kw["vocab_size"]) == (
                5, 4, 2, 8, 256, 152064 // 8)
    assert config["deployment"]["chips_per_layer"] == 32
    assert next(iter(config["assumed"])) == "apply_mla_qkv_lora_rescale"


def test_parameters_against_the_table(cell):
    """ISSUE 35's table, matrices by hand, and the vectors beside them."""
    kw = cell["config"]["builder_kwargs"]
    d = 5120
    index = 1024 * 64 * 128 + d * 128 + d * 64                    # 9.37 M
    full = (d * 1024 + d * (512 + 64) + index + 4 * (
        1024 * 192 + 512 * 256 + 128 * d + d))
    window = d * 1024 + d * (1024 + 64) + 2 * (
        1024 * 256 + 1024 * 320 + 128 * d + d)
    assert (round(index / 1e6, 2), round(full / 1e6, 2),
            round(window / 1e6, 2)) == (9.37, 21.52, 13.31)
    dense = 3 * d * 13824
    expert = 3 * d * 1536
    moe = 8 * expert + expert + d * 256
    assert (round(dense / 1e6, 2), round(moe / 1e6, 2)) == (212.34, 213.65)
    vocab = 2 * 19008 * d
    matrices = (full + dense) + (full + moe) + 3 * (window + moe) + vocab
    assert round(matrices / 1e6, 1) == 1344.5                # the table's sum
    vectors = 11 * d + 2 * (1024 + 512 + 2 * 128) + 3 * (1024 + 1024)
    assert dots3.parameters(**kw) == matrices + vectors == 1344608768 \
        == cell["config"]["deployment"]["parameters"]
    # 4 B a parameter resident: 5.4 GB
    assert round(4 * dots3.parameters(**kw) / 1e9, 1) == 5.4


def test_train_flops_and_rooflines_at_the_cell_s_shape(cell):
    kw = cell["config"]["builder_kwargs"]
    t = 8192
    per_token = (19008 * 5120 + 2 * 21516288 + 3 * 13314048 + 3 * 5120 * 13824
                 + 4 * (5120 * 256 + 1.25 * 3 * 5120 * 1536))
    assert dots3.matmul_params_per_token(**kw) == per_token == 515840000
    selected = 2048 * 2049 // 2 + (t - 2048) * 2048               # 14.68 M
    band = 513 * 514 // 2 + (t - 513) * 513                       # 4.07 M
    causal = t * (t + 1) // 2                                     # 33.56 M
    assert (selected, band, causal) == (14681088, 4071168, 33558528)
    assert (dots3.kept_pairs(t, 2048), dots3.kept_pairs(t, 513),
            dots3.kept_pairs(t, t)) == (selected, band, causal)
    index = 2 * causal * 64 * 128                  # 0.55 TFLOP a full layer
    main = 2 * selected * 4 * (192 + 128)
    swa = 2 * band * 2 * (256 + 128)
    assert round(index / 1e12, 2) == 0.55
    want = 6 * per_token * t + 3 * (2 * (index + main) + 3 * swa)
    assert dots3.train_flops(**kw) == pytest.approx(want)
    assert round(want / 1e12, 1) == 28.9                # TFLOP a step
    flops, nbytes = dots3.index_train(batch=1, **kw)
    assert flops == pytest.approx(2 * 3 * (2 * t * 9371648 + index))
    assert nbytes == 2 * (4 * t * 64 * 128 + 2 * t * t) * 4
    flops, nbytes = dots3.selected_attention_train(batch=1, **kw)
    assert flops == pytest.approx(2 * 3 * main)
    assert nbytes == 2 * (t * 4 * 6 * 320 * 2 + 3 * t * t * 2)
    flops, nbytes = dots3.window_attention_train(batch=1, **kw)
    assert flops == pytest.approx(3 * 3 * swa)
    assert nbytes == 3 * t * 2 * 6 * 384 * 2
    # the experts' formula is deepseek-v2's at this configuration's sizes:
    # a budget of 2048 rows in 16 + 8 tiles
    spec = cell["layer_metrics"]["moe_experts_roofline"]
    flops, _ = run.formula(spec["formula"])(batch=1, **kw)
    assert flops == pytest.approx(4 * 6 * (2048 + 8 * 128) * 3 * 5120 * 1536)


def test_formulas_at_a_small_shape():
    """Two full layers and a window layer of 32 tokens, by hand."""
    with open(os.path.join(HERE, "data", "dots3-tiny.json")) as f:
        kw = json.load(f)["builder_kwargs"]
    attn = lambda h, q, kv, nope, rope, v: (
        64 * q + q * h * (nope + rope) + 64 * (kv + rope)
        + kv * h * (nope + v) + h * v * 64 + 64 * h)
    index = 24 * 4 * 16 + 64 * 16 + 64 * 4
    full = attn(2, 24, 16, 16, 8, 16) + index
    window = attn(2, 24, 24, 24, 8, 16)
    moe = 64 * 16 + 3 * 64 * 32 + 4 * 3 * 64 * 32
    vectors = 7 * 64 + 2 * (24 + 16 + 32) + (24 + 24)
    assert dots3.parameters(**kw) == (
        2 * 128 * 64 + 2 * full + window + 3 * 64 * 96 + 2 * moe + vectors)
    per_token = 128 * 64 + 2 * full + window + 3 * 64 * 96 \
        + 2 * (64 * 16 + 3 * 64 * 32 + 4 * 4 / 16 * 3 * 64 * 32)
    assert dots3.matmul_params_per_token(**kw) == per_token
    selected = 8 * 9 // 2 + 24 * 8
    band = 5 * 6 // 2 + 27 * 5
    want = 6 * per_token * 32 + 3 * 2 * (
        2 * (32 * 33 // 2) * 4 * 16 + 2 * selected * 2 * 40) \
        + 3 * 2 * band * 2 * 48
    assert dots3.train_flops(**kw) == pytest.approx(want)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with the tiny configuration as a cell that
    reads every per-layer metric the real cell reads."""
    top = str(tmp_path_factory.mktemp("bench_dots3"))
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(top, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(HERE, "data", "dots3-tiny.json"),
                os.path.join(top, "benchmark", "configs"))
    shutil.copy(os.path.join(HERE, "data", "tiny-resident.json"),
                os.path.join(top, "benchmark", "traffic"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "dots3-tiny", "source": "tests", "reduced": [], "why": "tests",
        "file": "benchmark/configs/dots3-tiny.json"})
    bench["workloads"].append({
        "name": "dots3-tiny.resident", "config": "dots3-tiny",
        "traffic": "tiny-resident", "chips": 1, "why": "tests"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("dots3-tiny.resident")
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return top


def test_tiny_cell_runs_through_the_harness(root):
    import jax

    cell = run.load_cell(root, "dots3-tiny.resident")
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
    # the index's term came out with the drains, and its reader finds it:
    # the mean over the full layers and the drained steps, a KL in nats
    ctx = run.Context(say=lines.append)
    kl = program_counter.read(ctx, cell["layer_metrics"]["dsa_index_kl"])
    assert 0 < kl < 10
    assert kl == pytest.approx(profiling.counters()["dsa_index_kl"])
    kept = program_counter.read(ctx, cell["layer_metrics"][
        "moe_assignments_kept_per_token"])
    assert 0 < kept <= 4 * 4 / 16
