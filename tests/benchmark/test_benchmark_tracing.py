"""The readers of the program's own spans, scopes and counters
(benchmark/readers/device_scope.py, trace_span.py, idle_by_span.py,
program_counter.py): on a recorded v5e trace of two AlexNet steps taken
with the scopes and spans in the program, on hand-made traces, on the
older recorded trace that has neither, and through the harness's traced
stretch on the CPU.  Nothing here is a speed."""

import json
import os

import jax
import pytest

from benchmark import reduce, run
from benchmark.readers import (device_scope, idle_by_span, program_counter,
                               trace_span)
from flexflow_tpu.runtime import profiling

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(__file__), "data")
NEW_METRICS = {
    "forward_ms_per_step": "device_scope",
    "backward_ms_per_step": "device_scope",
    "optimizer_ms_per_step": "device_scope",
    "attention_fwd_ms_per_step": "device_scope",
    "attention_bwd_ms_per_step": "device_scope",
    "step_prepare_ms_per_step": "trace_span",
    "step_enqueue_ms_per_step": "trace_span",
    "metric_drain_ms_per_block": "trace_span",
    "idle_in_update_ms_per_step": "idle_by_span",
    "idle_in_sync_ms_per_step": "idle_by_span",
    "train_step_compiles": "program_counter"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _spec(name):
    return _json(REPO, "benchmark", "layer_metrics", name + ".json")


def _ctx(trace, steps, window=None):
    lines = []
    return run.Context(
        trace=trace, trace_steps=steps, say=lines.append, lines=lines,
        trace_window=window or reduce.span_window(trace,
                                                  "bench.trace_window"))


@pytest.fixture(scope="module")
def scoped():
    """Two AlexNet-256 steps on a v5e with this PR's scopes and spans."""
    return _json(DATA, "trace_alexnet256_scoped_2steps.json")


@pytest.fixture(scope="module")
def scopes():
    """That program's scope map, as `profiling.step_scopes()` gave it."""
    return _json(DATA, "scopes_alexnet256.json")


@pytest.fixture(scope="module")
def unscoped():
    """PR 24's recording: the same model before the scopes and spans."""
    return _json(DATA, "trace_alexnet256_2steps.json")


# ---------------------------------------------------------------------------
# the entries and their files
# ---------------------------------------------------------------------------

def test_the_new_metrics_are_entries_with_files_and_readers(bench_root):
    bench = _json(bench_root, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name, reader in NEW_METRICS.items():
        spec, entry = _spec(name), entries[name]
        assert spec["reader"] == reader
        assert (spec["layer"], spec["unit"], spec["moves"]) == \
            (entry["layer"], entry["unit"], entry["moves"])
        assert entry["better"] == "lower"
        if name.startswith("attention_"):   # the cells with flash kernels
            assert "gpt2m-train-s1024" in entry["workloads"]
            assert set(entry["workloads"]) < set(cells)
        else:                               # every cell, old or new
            assert sorted(entry["workloads"]) == sorted(cells)
        # a metric of this PR never finds a kernel by its call target
        assert "custom_call_target" not in json.dumps(spec)
    assert {m["name"] for m in bench["per_layer"]
            if m["layer"] == "fused train step"} >= {
        "forward_ms_per_step", "backward_ms_per_step",
        "optimizer_ms_per_step"}
    # every cell gets nine of them, and the two of the flash kernels
    # where those entries list it
    step = {n for n in NEW_METRICS if not n.startswith("attention_")}
    assert len(step) == 9
    for cell in cells:
        got = set(run.load_cell(bench_root, cell)["layer_metrics"]) \
            & set(NEW_METRICS)
        assert got == step | {n for n in set(NEW_METRICS) - step
                              if cell in entries[n]["workloads"]}
    assert len(set(run.load_cell(bench_root, "gpt2m-train-s1024")[
        "layer_metrics"]) & set(NEW_METRICS)) == 11


# ---------------------------------------------------------------------------
# the recorded trace with scopes and spans
# ---------------------------------------------------------------------------

def test_phases_sum_to_the_busy_time(scoped, scopes, monkeypatch):
    monkeypatch.setattr(profiling, "step_scopes", lambda: scopes)
    ctx = _ctx(scoped, scoped["steps"])
    fwd, bwd, opt = (device_scope.read(ctx, _spec(f"{ph}_ms_per_step"))
                     for ph in ("forward", "backward", "optimizer"))
    busy, _ = reduce.busy_seconds(scoped, ctx.trace_window)
    busy_ms = busy * 1e3 / scoped["steps"]
    assert (fwd, bwd, opt) == pytest.approx((3.244281, 6.7544785, 0.0009465))
    assert busy_ms == pytest.approx(10.000694)
    assert fwd + bwd + opt == pytest.approx(busy_ms, rel=0.02)
    assert fwd + bwd + opt <= busy_ms
    # backward is about twice forward; SGD is fused into the weight
    # gradients, so next to nothing is the optimizer's alone
    assert 1.8 < bwd / fwd < 2.4
    assert 0 < opt < 0.01 * bwd
    # the map is joined once, however many metrics read it
    assert sum("scope map of" in ln for ln in ctx.lines) == 1
    log = "\n".join(ctx.lines)
    assert "of its traced time is in the scope map" in log
    assert "in fusions that mix phases" in log
    assert "ff.op.conv2d.conv1.bwd" in log
    # by hand: conv1's backward is its weight-gradient fusion (there is
    # no input gradient for the first layer) and what else is under its
    # scope
    by_hand = sum(d for name, _, d in _ops(scoped)
                  if scopes["jit_step"][0].get(reduce.op_label(name), {})
                  .get("scope") == "ff.op.conv2d.conv1"
                  and scopes["jit_step"][0][reduce.op_label(name)]["phase"]
                  == "bwd") / 1e6 / scoped["steps"]
    conv1 = device_scope.read(ctx, {"phase": "bwd", "scale": 1000.0,
                                    "scope": r"ff\.op\.conv2d\.conv1$"})
    assert conv1 == pytest.approx(by_hand)
    assert 0.2 * bwd > conv1 > 0.1 * bwd
    # nothing of AlexNet is a flash kernel
    assert device_scope.read(ctx, _spec("attention_fwd_ms_per_step")) is None


def _ops(trace):
    window = reduce.span_window(trace, "bench.trace_window")
    step = [(s, s + d) for n, s, d in _line(trace, "XLA Modules")
            if n.startswith("jit_step(")]
    return [(n, s, d) for n, s, d in _line(trace, "XLA Ops")
            if window[0] <= s and any(a <= s < b for a, b in step)]


def _line(trace, name):
    return next(ln["events"] for p in trace["planes"]
                if p["name"] == "/device:TPU:0"
                for ln in p["lines"] if ln["name"] == name)


def test_a_map_of_another_program_reads_nothing(scoped, scopes, monkeypatch):
    other = {"jit_step": [{f"{name}.x": e for name, e in prog.items()}
                          for prog in scopes["jit_step"]]}
    monkeypatch.setattr(profiling, "step_scopes", lambda: other)
    ctx = _ctx(scoped, scoped["steps"])
    assert device_scope.read(ctx, _spec("forward_ms_per_step")) is None
    assert any("is of another program" in ln for ln in ctx.lines)
    # a map that lacks the traced program's name
    monkeypatch.setattr(profiling, "step_scopes",
                        lambda: {"jit_estep": scopes["jit_step"]})
    ctx = _ctx(scoped, scoped["steps"])
    assert device_scope.read(ctx, _spec("backward_ms_per_step")) is None
    assert any("is not among the step programs" in ln for ln in ctx.lines)
    # no step program loaded at all
    monkeypatch.setattr(profiling, "step_scopes", lambda: {})
    ctx = _ctx(scoped, scoped["steps"])
    assert device_scope.read(ctx, _spec("backward_ms_per_step")) is None


def test_of_two_loaded_programs_the_one_that_covers_the_trace(
        scoped, scopes, monkeypatch):
    """The step is loaded once per signature; where one copy's
    instructions differ, the copy that covers the trace is read."""
    good = scopes["jit_step"][0]
    stale = {(name if i % 3 else name + ".old"): e
             for i, (name, e) in enumerate(sorted(good.items()))}
    monkeypatch.setattr(profiling, "step_scopes",
                        lambda: {"jit_step": [good]})
    want = device_scope.read(_ctx(scoped, 2), _spec("backward_ms_per_step"))
    for maps in ([stale, good], [good, stale]):
        monkeypatch.setattr(profiling, "step_scopes",
                            lambda maps=maps: {"jit_step": maps})
        ctx = _ctx(scoped, 2)
        assert device_scope.read(ctx, _spec("backward_ms_per_step")) == want


def test_program_spans_by_hand(scoped):
    ctx = _ctx(scoped, scoped["steps"])
    spans = reduce.host_spans(scoped, "ff.")
    by_name = {}
    for n, s, e in spans:
        by_name.setdefault(n, []).append((s, e))
    assert {n: len(v) for n, v in by_name.items()} == {
        "ff.update": 2, "ff.update.prepare": 2, "ff.update.enqueue": 2,
        "ff.update.finish": 2, "ff.sync": 1, "ff.metric_drain": 1}
    for name, metric, per in (
            ("ff.update.prepare", "step_prepare_ms_per_step", 2),
            ("ff.update.enqueue", "step_enqueue_ms_per_step", 2),
            ("ff.metric_drain", "metric_drain_ms_per_block", 1)):
        by_hand = sum(e - s for s, e in by_name[name]) / 1e6 / per
        assert trace_span.read(ctx, _spec(metric)) == pytest.approx(by_hand)
    # by hand from the recording: ff.update.prepare is 54601778-55932168
    # and 56622478-57656508 ns, ff.update.enqueue 55960798-56441328 and
    # 57672268-57987098, ff.metric_drain 77275397-79066217
    assert trace_span.read(ctx, _spec("step_prepare_ms_per_step")) == \
        pytest.approx((1330390 + 1034030) / 2e6)
    assert trace_span.read(ctx, _spec("step_enqueue_ms_per_step")) == \
        pytest.approx((480530 + 314830) / 2e6)
    assert trace_span.read(ctx, _spec("metric_drain_ms_per_block")) == \
        pytest.approx(1.79082)
    # every program span lies inside the benchmark's span around the call
    outer = {"ff.update": "bench.train_iteration", "ff.sync": "bench.sync",
             "ff.metric_drain": "bench.read_loss"}
    bench = reduce.host_spans(scoped, "bench.")
    for name, around in outer.items():
        for s, e in by_name[name]:
            assert any(n == around and a <= s and e <= b
                       for n, a, b in bench), name
    prepare = trace_span.read(ctx, _spec("step_prepare_ms_per_step"))
    enqueue = trace_span.read(ctx, _spec("step_enqueue_ms_per_step"))
    calls = [e - s for n, s, e in bench if n == "bench.train_iteration"]
    assert prepare + enqueue <= sum(calls) / len(calls) / 1e6
    reads = [e - s for n, s, e in bench if n == "bench.read_loss"]
    assert trace_span.read(ctx, _spec("metric_drain_ms_per_block")) \
        <= sum(reads) / len(reads) / 1e6
    assert trace_span.read(ctx, {"span": "ff.data_wait", "per": "step"}) \
        is None


def test_idle_by_span_by_hand(scoped):
    ctx = _ctx(scoped, scoped["steps"])
    t0, t1 = ctx.trace_window
    busy = reduce.union((s, s + d) for _, s, d in _line(scoped, "XLA Ops"))
    busy = [(max(s, t0), min(e, t1)) for s, e in busy if e > t0 and s < t1]
    total_idle = (t1 - t0) - sum(e - s for s, e in busy)

    def idle_in(name):
        out = 0
        for n, s, e in reduce.host_spans(scoped, "ff."):
            if n != name:
                continue
            covered = sum(max(0, min(e, b) - max(s, a)) for a, b in busy)
            out += (e - s) - covered
        return out / 1e6 / scoped["steps"]

    update = idle_by_span.read(ctx, _spec("idle_in_update_ms_per_step"))
    sync = idle_by_span.read(ctx, _spec("idle_in_sync_ms_per_step"))
    assert update == pytest.approx(idle_in("ff.update"))
    assert sync == pytest.approx(idle_in("ff.sync"))
    assert (update, sync) == pytest.approx((0.2063385, 1.110942))
    # the spans do not overlap, so their idle time is part of the whole
    assert update + sync <= total_idle / 1e6 / scoped["steps"] + 1e-9
    assert idle_by_span.read(ctx, {"span": "ff.data_wait"}) is None


# ---------------------------------------------------------------------------
# hand-made traces
# ---------------------------------------------------------------------------

def _handmade():
    """One chip, two runs of `jit_step` of 100 ns each with a small
    other program between them, and the host's spans around them."""
    ops = [["%fusion.1 = f32[8]{0} fusion(%p), kind=kOutput", 1000, 40],
           ["%fusion.2 = f32[8]{0} fusion(%p), kind=kLoop", 1040, 30],
           ["%flash_fwd.3 = bf16[8]{0} custom-call(%q)", 1070, 30],
           ["%convert.1 = u32[] convert(%s)", 1110, 5],
           ["%fusion.1 = f32[8]{0} fusion(%p), kind=kOutput", 1200, 40],
           ["%fusion.2 = f32[8]{0} fusion(%p), kind=kLoop", 1240, 30],
           ["%flash_fwd.3 = bf16[8]{0} custom-call(%q)", 1270, 30]]
    modules = [["jit_step(1)", 1000, 100], ["jit_convert(2)", 1110, 5],
               ["jit_step(1)", 1200, 100]]
    host = [["bench.trace_window", 900, 500], ["ff.update", 900, 90],
            ["ff.update.enqueue", 950, 30], ["ff.update", 1090, 100],
            ["ff.update.enqueue", 1100, 60], ["ff.sync", 1195, 115],
            ["ff.metric_drain", 1320, 60]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


HANDMADE_SCOPES = {"jit_step": [{
    "fusion.1": {"scope": "ff.op.dense.fc1", "phase": "bwd", "kernel": None,
                 "mixed": True},
    "fusion.2": {"scope": "ff.optimizer", "phase": "opt", "kernel": None,
                 "mixed": False},
    "flash_fwd.3": {"scope": "ff.op.multiheadattention.a", "phase": "fwd",
                    "kernel": "flash_fwd", "mixed": False},
    # an instruction of that name exists in the step too, but the event
    # of that name ran in another program and is not the step's
    "convert.1": {"scope": "ff.input_cast", "phase": "fwd", "kernel": None,
                  "mixed": False}}]}


def test_handmade_device_scope(monkeypatch):
    monkeypatch.setattr(profiling, "step_scopes", lambda: HANDMADE_SCOPES)
    ctx = _ctx(_handmade(), 2)

    def read(**spec):
        return device_scope.read(ctx, spec)

    assert read(phase="bwd") == pytest.approx(40e-9)
    assert read(phase="opt") == pytest.approx(30e-9)
    assert read(phase="fwd") == pytest.approx(30e-9)   # not convert.1
    assert read(phase="other") is None
    assert read(scope=r"ff\.kernel\.flash_fwd", scale=1e9) == \
        pytest.approx(30.0)
    assert read(scope=r"ff\.kernel\.flash_d(q|kv)") is None
    assert read(scope=r"ff\.op\.dense", phase="bwd", scale=1e9) == \
        pytest.approx(40.0)
    assert read(scope=r"ff\.op\.dense", phase="fwd") is None
    log = "\n".join(ctx.lines)
    assert "jit_step: 100.00% of its traced time is in the scope map" in log
    assert "40.0% in fusions that mix phases" in log
    assert "ff.op.dense.fc1.bwd" in log


def test_handmade_spans_and_idle():
    ctx = _ctx(_handmade(), 2)
    assert trace_span.read(ctx, {"span": "ff.update.enqueue", "per": "step",
                                 "scale": 1e9}) == pytest.approx(45.0)
    assert trace_span.read(ctx, {"span": "ff.update.enqueue", "per": "call",
                                 "scale": 1e9}) == pytest.approx(45.0)
    assert trace_span.read(ctx, {"span": "ff.metric_drain", "per": "call",
                                 "scale": 1e9}) == pytest.approx(60.0)
    # the device is busy 1000-1100, 1110-1115 and 1200-1300.  ff.update
    # is 900-990 (all idle) and 1090-1190 (idle but 1090-1100 and
    # 1110-1115): 90 + 85; ff.sync is 1195-1310: idle 1195-1200 and
    # 1300-1310
    assert idle_by_span.read(ctx, {"span": "ff.update", "scale": 1e9}) == \
        pytest.approx((90 + 85) / 2)
    assert idle_by_span.read(ctx, {"span": "ff.sync", "scale": 1e9}) == \
        pytest.approx(15 / 2)
    # a span cut by the window counts as far as the window goes
    cut = _ctx(_handmade(), 2, window=(950, 1400))
    assert idle_by_span.read(cut, {"span": "ff.update", "scale": 1e9}) == \
        pytest.approx((40 + 85) / 2)


# ---------------------------------------------------------------------------
# a program without the scopes, spans and counters: the parent commit
# ---------------------------------------------------------------------------

def test_every_new_reader_reads_nothing_from_an_older_program(
        unscoped, monkeypatch):
    """The older recording has no `ff.` span, and the program that made
    it had no scope map and no counters: every reader says so and
    returns None; none raises."""
    monkeypatch.delattr(profiling, "step_scopes")
    monkeypatch.delattr(profiling, "counters")
    ctx = _ctx(unscoped, unscoped["steps"])
    readers = {"device_scope": device_scope, "trace_span": trace_span,
               "idle_by_span": idle_by_span,
               "program_counter": program_counter}
    for name, reader in NEW_METRICS.items():
        assert readers[reader].read(ctx, _spec(name)) is None, name
    said = "\n".join(ctx.lines)
    assert "the program has no scope map" in said
    assert "no span 'ff.update.enqueue'" in said
    assert "the program keeps no counter 'train_step_compiles'" in said
    # with a map but still no spans, the span readers stay silent
    monkeypatch.undo()
    assert trace_span.read(ctx, _spec("metric_drain_ms_per_block")) is None
    assert idle_by_span.read(ctx, _spec("idle_in_sync_ms_per_step")) is None
    assert program_counter.read(ctx, {"counter": "no_such_counter"}) is None


# ---------------------------------------------------------------------------
# through the harness, on the CPU
# ---------------------------------------------------------------------------

def test_traced_stretch_on_the_cpu_carries_the_programs_spans(tmp_path):
    """The harness's own traced stretch at a tiny size: the program's
    spans are in the same trace as the benchmark's, nested in them, and
    the span readers and the counter read them.  The CPU's trace has no
    device plane, so the device readers read nothing."""
    cell = run.load_cell(REPO, "alexnet-train-resident")
    cell["config"] = _json(DATA, "alexnet-tiny.json")
    cell["traffic"] = _json(DATA, "tiny-resident.json")
    spans = run.Spans()
    batch = cell["traffic"]["batch_per_chip"]
    v = run.build_variant(cell, cell["traffic"]["variants"][0], batch, 0,
                          spans)
    ref = run.load_reference(cell["home"], cell["config"]["reference"])
    run.stage_batch(v.model, ref, jax.random.key(0), batch,
                    cell["config"]["builder_kwargs"])
    before = profiling.counters()["train_step_compiles"]
    for _ in range(3):
        v.step_loss()
    lines = []
    info = run.profiled_stretch(v, 2, spans, lines.append)
    ctx = run.Context(cell=cell, say=lines.append, **info)
    assert ctx.trace_steps == 2 * run.TRACE_BLOCKS
    prepare = trace_span.read(ctx, _spec("step_prepare_ms_per_step"))
    enqueue = trace_span.read(ctx, _spec("step_enqueue_ms_per_step"))
    drain = trace_span.read(ctx, _spec("metric_drain_ms_per_block"))
    assert prepare > 0 and enqueue > 0 and drain > 0
    ff_spans = reduce.host_spans(ctx.trace, "ff.")
    bench = reduce.host_spans(ctx.trace, "bench.")
    for name, around, count in (
            ("ff.update", "bench.train_iteration", ctx.trace_steps),
            ("ff.sync", "bench.sync", run.TRACE_BLOCKS),
            ("ff.metric_drain", "bench.read_loss", run.TRACE_BLOCKS)):
        mine = [(s, e) for n, s, e in ff_spans if n == name]
        assert len(mine) == count, name
        for s, e in mine:
            assert any(n == around and a <= s and e <= b
                       for n, a, b in bench), name
    calls = [e - s for n, s, e in bench if n == "bench.train_iteration"]
    assert prepare + enqueue <= sum(calls) / len(calls) / 1e6
    reads = [e - s for n, s, e in bench if n == "bench.read_loss"]
    assert drain <= sum(reads) / len(reads) / 1e6
    compiles = program_counter.read(ctx, _spec("train_step_compiles"))
    assert compiles - before >= 1 and compiles == int(compiles)
    for name in ("forward_ms_per_step", "idle_in_update_ms_per_step"):
        reader = {"device_scope": device_scope,
                  "idle_by_span": idle_by_span}[NEW_METRICS[name]]
        assert reader.read(ctx, _spec(name)) is None
