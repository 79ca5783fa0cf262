"""The files of the cell `dsv2-train-s4096` (configuration, reference,
formulas, per-layer metrics and their two new readers) at a tiny size on
the CPU, through the harness's own functions: the command itself refuses a
CPU.  The tiny cell is in the grown copy of the benchmark (`conftest.py`),
added as a later PR adds one: new files and entries.  Nothing this file
measures is a speed."""

import json
import os

import pytest

from benchmark import run
from benchmark.readers import device_span, mfu_of_rate, program_counter
from flexflow_tpu.runtime import profiling

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "dsv2-train-s4096"
BIG_SEED = 2 ** 31 + 11   # the driver's seeds pass 32 signed bits
OWN_METRICS = {
    "mla_attention_ms_per_step", "mla_attention_roofline",
    "mla_projection_ms_per_step", "moe_route_ms_per_step",
    "moe_experts_ms_per_step", "moe_experts_roofline",
    "moe_shared_ms_per_step", "moe_assignments_kept_per_token",
    "moe_dropped_share", "moe_load_max_over_mean", "mfu_block_median",
    "embedding_ms_per_step"}
EVERY_TRAINING_CELL = {
    "forward_ms_per_step", "backward_ms_per_step", "optimizer_ms_per_step",
    "step_prepare_ms_per_step", "step_enqueue_ms_per_step",
    "metric_drain_ms_per_block", "idle_in_update_ms_per_step",
    "idle_in_sync_ms_per_step", "train_step_compiles"}
EVERY_CELL = {"compile_s", "host_dispatch_ms_per_step",
              "read_loss_ms_per_block", "samples_per_s_per_chip_block_median",
              "device_idle_share", "peak_hbm_gib"}


def test_the_cell_as_benchmark_json_has_it(bench_root):
    cell = run.load_cell(bench_root, CELL)
    assert cell["chips"] == 1 and cell["config_name"] == "deepseek-v2"
    assert {m["name"] for m in cell["end_to_end"]} == {"step_ms_p90",
                                                       "setup_s"}
    # what it must read; what else it reads is a later PR's to add
    assert set(cell["layer_metrics"]) >= \
        OWN_METRICS | EVERY_TRAINING_CELL | EVERY_CELL
    config, traffic = cell["config"], cell["traffic"]
    kw = config["builder_kwargs"]
    assert callable(run.resolve(config["builder"]))
    assert run.formula(config["flops"])(**kw) * traffic["batch_per_chip"] \
        == pytest.approx(29.75e12, rel=1e-3)        # some 30 TFLOP a step
    for name in OWN_METRICS:
        spec = cell["layer_metrics"][name]
        if "formula" in spec:
            flops, nbytes = run.formula(spec["formula"])(batch=2, **kw)
            assert flops > 0 and nbytes > 0
    ref = run.load_reference(cell["home"], cell["config_name"])
    assert ref.CHUNK >= traffic["batch_per_chip"]   # the budget is a step's
    with open(os.path.join(bench_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "deepseek-v2")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


@pytest.fixture(scope="module")
def root(grown):
    """A copy of the benchmark with the tiny configuration as a cell that
    reads every per-layer metric the real cell reads (`conftest.py`)."""
    return grown.top


def test_tiny_cell_runs_through_the_harness(root):
    import jax

    cell = run.load_cell(root, "dsv2-tiny.resident")
    cell["peaks"] = {jax.devices()[0].device_kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    lines = []
    res = run.run_cell(cell, BIG_SEED, 1.0, False, say=lines.append)
    assert res["correct"] is True, lines
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"step_ms_p90", "setup_s"}
    assert any("first loss" in ln and "reference" in ln for ln in lines)
    assert not any("CHECK FAILED" in ln for ln in lines)
    # the counters came with the drains, and the readers find them
    ctx = run.Context(say=lines.append)
    kept = program_counter.read(ctx, cell["layer_metrics"][
        "moe_assignments_kept_per_token"])
    dropped = program_counter.read(ctx, cell["layer_metrics"][
        "moe_dropped_share"])
    load = program_counter.read(ctx, cell["layer_metrics"][
        "moe_load_max_over_mean"])
    assert 0 < kept <= 3 * 4 / 16 and 0 <= dropped < 1 and load >= 1
    made = profiling.counters()["moe_assignments_made_per_token"]
    assert kept == pytest.approx(made * (1 - dropped))


def test_mfu_of_the_block_median_rate():
    cell = run.load_cell(REPO, CELL)
    ctx = run.Context(cell=cell, kwargs=cell["config"]["builder_kwargs"],
                      formula=run.formula, peak={"bf16_flops_per_s": 197e12},
                      values={"block_median_samples_per_s_per_chip": 5.0})
    spec = cell["layer_metrics"]["mfu_block_median"]
    # 5 sequences a second x 14.87 TFLOP a sequence over 197 TFLOP/s
    assert mfu_of_rate.read(ctx, spec) == pytest.approx(0.3775, rel=1e-3)
    ctx.values.clear()
    assert mfu_of_rate.read(ctx, spec) is None


def _handmade():
    """One chip, two runs of `jit_step`, the host's window around them."""
    ops = [["%fusion.1 = f32[8]{0} fusion(%p), kind=kOutput", 1000, 40],
           ["%gmm.2 = bf16[8]{0} custom-call(%q)", 1040, 30],
           ["%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop", 1070, 20],
           ["%fusion.1 = f32[8]{0} fusion(%p), kind=kOutput", 1200, 40],
           ["%gmm.2 = bf16[8]{0} custom-call(%q)", 1240, 30],
           ["%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop", 1270, 20]]
    modules = [["jit_step(1)", 1000, 100], ["jit_step(1)", 1200, 100]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.trace_window", 900, 500]]}]}]}


SPANNED = {"jit_step": [{
    "fusion.1": {"scope": "ff.op.routedexperts.moe_1", "phase": "fwd",
                 "kernel": None, "mixed": False, "span": "ff.moe.route"},
    "gmm.2": {"scope": "ff.op.routedexperts.moe_1", "phase": "bwd",
              "kernel": "gmm_t", "mixed": False, "span": "ff.moe.experts"},
    "fusion.3": {"scope": "ff.optimizer", "phase": "opt", "kernel": None,
                 "mixed": False}}]}


def test_device_span_reads_the_ops_own_scopes(monkeypatch):
    from benchmark import reduce

    trace = _handmade()
    lines = []
    ctx = run.Context(trace=trace, trace_steps=2, say=lines.append,
                      trace_window=reduce.span_window(trace,
                                                      "bench.trace_window"))
    monkeypatch.setattr(profiling, "step_scopes", lambda: SPANNED)
    read = lambda **spec: device_span.read(ctx, spec)
    assert read(span=r"ff\.moe\.route") == pytest.approx(40e-9)
    assert read(span=r"ff\.moe\.(route|experts)", scale=1e9) \
        == pytest.approx(70.0)
    assert read(span=r"ff\.moe\.experts", phase="fwd") is None
    assert read(span=r"ff\.mla\.") is None
    # a program older than the spans: its map names none, nothing is read
    older = {"jit_step": [{k: {f: v for f, v in e.items() if f != "span"}
                           for k, e in SPANNED["jit_step"][0].items()}]}
    monkeypatch.setattr(profiling, "step_scopes", lambda: older)
    ctx.__dict__.pop("device_span_join")
    assert read(span=r"ff\.moe\.route") is None
