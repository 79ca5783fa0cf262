"""The benchmark's reducer (benchmark/reduce.py) and its per-layer
readers, on a small recorded trace and on hand-made ones.  CPU only."""

import json
import os
import types

import pytest

from benchmark import flops, reduce, run
from benchmark.readers import (collective_exposed, device_idle, device_ops,
                               host_span, memory_stat, roofline, value)

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_alexnet256_2steps.json")) as f:
        return json.load(f)


def _line(trace, plane, line):
    return next(ln["events"] for p in trace["planes"] if p["name"] == plane
                for ln in p["lines"] if ln["name"] == line)


def test_recorded_trace_busy_and_idle(recorded):
    window = reduce.span_window(recorded, "bench.trace_window")
    busy, length = reduce.busy_seconds(recorded, window)
    assert length == pytest.approx(0.020181047)
    assert busy == pytest.approx(0.019998361)
    # the two jit_step programs, read off another line, bracket the ops
    steps = [e for e in _line(recorded, "/device:TPU:0", "XLA Modules")
             if e[0].startswith("jit_step")]
    assert len(steps) == recorded["steps"] == 2
    assert busy <= sum(e[2] for e in steps) / 1e9 + 1e-5
    assert busy >= 0.999 * sum(e[2] for e in steps) / 1e9
    assert 1 - busy / length == pytest.approx(0.00905, abs=1e-5)


def test_recorded_trace_pattern_time(recorded):
    window = reduce.span_window(recorded, "bench.trace_window")
    secs, n = reduce.pattern_seconds(recorded, window, "kind=kOutput")
    # 26 output fusions a step (five convolutions and three dense
    # layers: forward, weight gradient, input gradient, less conv1's)
    assert n == 52
    assert secs == pytest.approx(0.015969473)
    by_hand = sum(d for name, _, d in
                  _line(recorded, "/device:TPU:0", "XLA Ops")
                  if "kind=kOutput" in name)
    assert secs == pytest.approx(by_hand / 1e9)
    assert reduce.pattern_seconds(recorded, window, "no such op") == (0.0, 0)
    # one chip: nothing collective in it
    assert reduce.exposed_collective_seconds(recorded, window) == (0.0, 0)


def test_recorded_trace_breakdown(recorded):
    window = reduce.span_window(recorded, "bench.trace_window")
    top = reduce.top_device_ops(recorded, window, n=10)
    assert len(top) == 10
    assert top[0][0] == "multiply_subtract_fusion.7__kOutput"
    assert top[0][1] == pytest.approx(0.002120549)
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    gaps = reduce.idle_gaps(recorded, window)
    assert gaps[0][0].startswith("bench.train_iteration__gaps_1__longest_ms_")
    assert gaps[0][1] == pytest.approx(0.000164903)


def _trace(planes):
    return {"planes": [
        {"name": name, "lines": [{"name": ln, "events": evs}
                                 for ln, evs in lines.items()]}
        for name, lines in planes.items()]}


@pytest.fixture
def handmade():
    """Two chips, a window of 1000 ns.  Chip 0: compute 0-400, an async
    all-reduce in flight 300-700 whose done-op waits 600-700, compute
    700-900.  Chip 1: compute 0-500, a synchronous all-gather 500-600."""
    return _trace({
        "/device:TPU:0": {
            "XLA Ops": [
                ["%fusion.1 = f32[8] fusion(%p), kind=kOutput", 0, 400],
                ["%all-reduce-start.1 = f32[8] all-reduce-start(%x)", 300, 5],
                ["%all-reduce-done.1 = f32[8] all-reduce-done(%s)", 600, 100],
                ["%fusion.2 = f32[8] fusion(%p), kind=kLoop", 700, 200]],
            "Async XLA Ops": [
                ["%all-reduce-start.1 = f32[8] all-reduce-start(%x)",
                 300, 400]]},
        "/device:TPU:1": {
            "XLA Ops": [
                ["%fusion.1 = f32[8] fusion(%p), kind=kOutput", 0, 500],
                ["%all-gather.3 = f32[8] all-gather(%x)", 500, 100]]},
        "/host:CPU": {"python": [["bench.trace_window", 0, 1000],
                                 ["bench.sync", 850, 150],
                                 ["other.span", 0, 1000]]},
    })


def test_handmade_exposed_collectives(handmade):
    window = reduce.span_window(handmade, "bench.trace_window")
    assert window == (0, 1000)
    secs, n = reduce.exposed_collective_seconds(handmade, window)
    # chip 0: in flight 300-700 less compute (0-400) = 300 ns; chip 1:
    # 100 ns; the mean of the two
    assert n == 4
    assert secs == pytest.approx((300 + 100) / 2 / 1e9)
    # a compute op named like the operand of a collective is compute
    busy, length = reduce.busy_seconds(handmade, window)
    assert length == pytest.approx(1e-6)
    # chip 0: 0-400 + 600-900 (300-305 lies inside); chip 1: 0-600
    assert busy == pytest.approx((700 + 600) / 2 / 1e9)


def test_handmade_window_clips(handmade):
    secs, n = reduce.pattern_seconds(handmade, (100, 450), "kind=kOutput")
    assert n == 2
    assert secs == pytest.approx((300 + 350) / 2 / 1e9)
    gaps = reduce.idle_gaps(handmade, (0, 1000), min_ns=50)
    # chip 0 is idle 400-600 (no bench span but the window's) and
    # 900-1000 (inside bench.sync, the innermost)
    assert dict((g[0].split("__")[0], g[1]) for g in gaps) == {
        "bench.trace_window": pytest.approx(200e-9),
        "bench.sync": pytest.approx(100e-9)}


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(5, 6)], [(0, 1), (9, 10)], [(5, 6)]),
])
def test_subtract(a, b, want):
    assert reduce.subtract(a, b) == want


def test_union_merges_and_sorts():
    got = reduce.union([("x", 5, 8), ("y", 0, 2), ("z", 1, 3), ("w", 8, 9)])
    assert got == [(0, 3), (5, 9)]
    assert reduce.length(got) == 7


def test_op_label():
    assert reduce.op_label(
        "%fusion.14 = bf16[2,3]{1,0} fusion(%a), kind=kOutput") == "fusion.14"
    assert reduce.op_label("jit_step(123)") == "jit_step(123)"
    assert reduce.op_kind("%f = f32[2] fusion(%a), kind=kOutput") == "kOutput"
    assert reduce.op_kind('%c = f32[2] custom-call(%a), '
                          'custom_call_target="tpu_custom_call"') \
        == "tpu_custom_call"
    assert reduce.op_kind("%copy.1 = f32[2] copy(%a)") == ""


def test_load_reads_what_the_profiler_writes(tmp_path):
    """`load()` on a real .xplane.pb: a CPU profile has no device plane,
    but it has the host plane and the process's TraceAnnotations."""
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        with jax.profiler.TraceAnnotation("bench.sync"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    trace = reduce.load(path)
    assert reduce.device_planes(trace) == []
    assert reduce.busy_seconds(trace, (0, 1)) == (None, None)
    window = reduce.span_window(trace, "bench.trace_window")
    assert window is not None and window[1] > window[0]
    names = [n for n, _, _ in reduce.host_spans(trace)]
    assert names == ["bench.trace_window", "bench.sync"]


# --------------------------------------------------------------------------
# every reader kind, on the recorded trace
# --------------------------------------------------------------------------

class _Spans:
    def seconds(self, name, variant=None, phase=None):
        rows = {("bench.train_iteration", "main", "window"): [0.001, 0.003],
                ("bench.compile", "searched", "setup"): [0.4]}
        return rows.get((name, variant, phase), [])


@pytest.fixture
def ctx(recorded):
    said = []
    c = types.SimpleNamespace(
        trace=recorded,
        trace_window=reduce.span_window(recorded, "bench.trace_window"),
        trace_steps=2, spans=_Spans(), reported="main", chips=1,
        global_batch=256, formula=run.formula, say=said.append, said=said,
        kwargs={"height": 229, "width": 229, "num_classes": 10},
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        values={"a": 3.0, "b": 2.0, "zero": 0.0},
        memory_stats=[{"bytes_in_use": 2 ** 30, "bytes_reserved": 2 ** 31},
                      {"bytes_in_use": 2 ** 29, "bytes_reserved": 2 ** 29}])
    c.metric = lambda name: device_ops.read(
        c, {"pattern": "kind=kOutput", "scale": 1000.0}) \
        if name == "convolution_ms_per_step" else None
    return c


def test_reader_device_ops(ctx):
    ms = device_ops.read(ctx, {"pattern": "kind=kOutput", "scale": 1000.0})
    assert ms == pytest.approx(15.969473 / 2)
    assert device_ops.read(ctx, {"pattern": "tpu_custom_call"}) is None


def test_reader_device_idle(ctx):
    assert device_idle.read(ctx, {}) == pytest.approx(0.00905, abs=1e-5)


def test_reader_collective_exposed_finds_nothing_on_one_chip(ctx):
    assert collective_exposed.read(ctx, {"scale": 1000.0}) is None


def test_reader_host_span(ctx):
    spec = {"span": "bench.train_iteration", "scale": 1000.0}
    assert host_span.read(ctx, spec) == pytest.approx(2.0)
    assert host_span.read(ctx, dict(spec, stat="sum")) == pytest.approx(4.0)
    assert host_span.read(ctx, {"span": "bench.compile", "stat": "sum",
                                "variant": "searched", "phase": "setup"}) \
        == pytest.approx(0.4)
    assert host_span.read(ctx, {"span": "bench.no_such"}) is None


def test_reader_value(ctx):
    assert value.read(ctx, {"value": "a"}) == 3.0
    assert value.read(ctx, {"value": "a", "over": "b"}) == 1.5
    assert value.read(ctx, {"value": "a", "over": "zero"}) is None
    assert value.read(ctx, {"value": "missing"}) is None


def test_reader_memory_stat(ctx):
    spec = {"keys": ["bytes_in_use", "bytes_reserved"], "scale": 2.0 ** -30}
    assert memory_stat.read(ctx, spec) == pytest.approx(3.0)
    assert memory_stat.read(ctx, {"keys": ["absent"]}) is None


def test_reader_roofline(ctx):
    spec = {"time_metric": "convolution_ms_per_step",
            "formula": "alexnet_matmuls"}
    share = roofline.read(ctx, spec)
    need, _ = flops.alexnet_matmuls(batch=256, **ctx.kwargs)
    assert share == pytest.approx(
        100 * need / 197e12 / (0.015969473 / 2))
    assert 60 < share < 75          # 67 % on this trace, bound by operations
    assert "bound by operations" in ctx.said[0]
    ctx.metric = lambda name: None
    assert roofline.read(ctx, spec) is None
