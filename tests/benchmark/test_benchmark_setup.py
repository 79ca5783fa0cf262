"""The per-layer metrics under `setup_s` that read the program's own
phases and counters (`flexflow_tpu/runtime/profiling.py`), and the
device's idle time inside the metric drain: entries, files, readers and
cells, and a reading of each through the harness at a tiny size on the
CPU.  Nothing here is a speed."""

import json
import os

import jax
import pytest

from benchmark import run
from benchmark.readers import idle_by_span, program_counter
from flexflow_tpu.runtime import profiling

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(__file__), "data")
SETUP_METRICS = {                      # metric -> the program's counter
    "setup_before_program_s": "before_first_model_s",
    "setup_graph_build_s": "graph_build_s",
    "setup_init_layers_s": "span_s.init_layers",
    "setup_step_compile_calls_s": "train_step_compile_call_s",
    "setup_step_trace_s": "train_step_trace_s",
    "setup_step_lower_s": "train_step_lower_s",
    "setup_step_backend_s": "train_step_compile_s"}
DRAIN = "idle_in_drain_ms_per_step"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _spec(name):
    return _json(REPO, "benchmark", "layer_metrics", name + ".json")


@pytest.mark.parametrize("name", sorted(SETUP_METRICS) + [DRAIN])
def test_the_metric_is_an_entry_with_a_file_and_every_cell(bench_root, name):
    bench = _json(bench_root, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = _spec(name)
    assert (spec["layer"], spec["unit"], spec["moves"]) == \
        (entry["layer"], entry["unit"], entry["moves"])
    assert entry["better"] == "lower"
    if name == DRAIN:
        assert spec["reader"] == "idle_by_span"
        assert spec["span"] == "ff.metric_drain"
        assert (entry["layer"], entry["moves"], entry["source"]) == \
            ("device", "step_ms_p90", "device_trace")
    else:
        assert spec["reader"] == "program_counter"
        assert spec["counter"] == SETUP_METRICS[name]
        assert (entry["layer"], entry["moves"], entry["unit"]) == \
            ("graph compile and lowering", "setup_s", "s")
        assert entry["source"] in ("program_counter", "program_span")
    # every cell reports the end-to-end metric it moves, old or new
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == entry["moves"])
    cells = [w["name"] for w in bench["workloads"]]
    assert "workloads" not in moved
    assert sorted(entry["workloads"]) == sorted(cells)
    for cell in cells:
        assert run.load_cell(bench_root, cell)["layer_metrics"][name] == spec


def test_the_setup_metrics_read_the_programs_phases_on_the_cpu():
    """Through the harness's own path at a tiny size: `build_variant`
    (the builder, `compile`, `init_layers`) and three synced steps; then
    every metric reads a positive number of seconds, and the step's
    stages lie inside the calls that held them."""
    cell = run.load_cell(REPO, "alexnet-train-resident")
    cell["config"] = _json(DATA, "alexnet-tiny.json")
    cell["traffic"] = _json(DATA, "tiny-resident.json")
    spans = run.Spans()
    batch = cell["traffic"]["batch_per_chip"]
    before = profiling.counters()
    v = run.build_variant(cell, cell["traffic"]["variants"][0], batch, 0,
                          spans)
    ref = run.load_reference(cell["home"], cell["config"]["reference"])
    run.stage_batch(v.model, ref, jax.random.key(0), batch,
                    cell["config"]["builder_kwargs"])
    for _ in range(3):
        v.step_loss()
    lines = []
    ctx = run.Context(cell=cell, say=lines.append)
    got = {name: run.read_metric(ctx, cell["layer_metrics"][name])
           for name in SETUP_METRICS}
    if os.path.exists("/proc/self/stat"):
        assert got["setup_before_program_s"] > 0
    else:
        assert got["setup_before_program_s"] is None
    del got["setup_before_program_s"]
    assert all(v is not None and v > 0 for v in got.values()), got
    # the program's phase lies inside the harness's span around the call
    assert got["setup_init_layers_s"] - before.get("span_s.init_layers", 0) \
        <= sum(spans.seconds("bench.init_layers"))
    assert profiling.counters()["train_step_compile_calls"] \
        - before.get("train_step_compile_calls", 0) == 2
    step = {name: got[name] - before.get(counter, 0.0)
            for name, counter in SETUP_METRICS.items() if name in got}
    assert step["setup_step_trace_s"] + step["setup_step_lower_s"] \
        + step["setup_step_backend_s"] <= step["setup_step_compile_calls_s"]
    assert not lines


def test_a_program_without_the_phases_reads_nothing(monkeypatch):
    """The parent commit keeps `train_step_compile_s` and none of the
    others: the reader says so for each and raises for none."""
    monkeypatch.setattr(profiling, "counters",
                        lambda: {"train_step_compiles": 2,
                                 "train_step_compile_s": 1.5})
    lines = []
    ctx = run.Context(say=lines.append)
    for name, counter in SETUP_METRICS.items():
        value = program_counter.read(ctx, _spec(name))
        if counter == "train_step_compile_s":
            assert value == 1.5
        else:
            assert value is None
            assert f"no counter {counter!r}" in lines[-1]


def test_the_drains_idle_by_hand():
    """A device busy for 6 of 10 ms, a drain over 4 of them of which 3
    are idle, two steps."""
    ms = 1_000_000
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%a = f32[8]{0} fusion(%p)", 0, 3 * ms],
            ["%b = f32[8]{0} fusion(%p)", 7 * ms, 3 * ms]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["ff.metric_drain", 2 * ms, 4 * ms],
            ["ff.sync", 6 * ms, 1 * ms]]}]}]}
    lines = []
    ctx = run.Context(trace=trace, trace_steps=2, trace_window=(0, 10 * ms),
                      say=lines.append)
    assert idle_by_span.read(ctx, _spec(DRAIN)) == pytest.approx(1.5)
    assert idle_by_span.read(
        ctx, _spec("idle_in_sync_ms_per_step")) == pytest.approx(0.5)
