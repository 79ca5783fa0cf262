"""Observability package tests: span nesting + JSONL serialization,
counter aggregation, zero work when disabled, and end-to-end step
records from a real (CPU) training loop under FF_TELEMETRY=1."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, ".")

import flexflow_tpu as ff
from flexflow_tpu.observability import events


@pytest.fixture(autouse=True)
def _isolated_singleton(monkeypatch):
    """Each test gets a fresh process-wide log and a clean env."""
    monkeypatch.delenv("FF_TELEMETRY", raising=False)
    monkeypatch.delenv("FF_TELEMETRY_FILE", raising=False)
    events.reset_active()
    yield
    events.reset_active()


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------------
# EventLog unit tests
# ---------------------------------------------------------------------------

def test_spans_nest_and_serialize(tmp_path):
    ticks = iter(float(i) for i in range(1000))
    log = events.EventLog(str(tmp_path / "t.jsonl"), run_id="r1",
                          clock=lambda: next(ticks))
    with log.span("outer", kind="a"):
        with log.span("inner"):
            pass
    log.close()

    recs = _read_jsonl(log.path)  # every line must be valid JSON
    assert recs[0]["t"] == "meta" and recs[0]["run_id"] == "r1"
    spans = {r["name"]: r for r in recs if r["t"] == "span"}
    assert set(spans) == {"outer", "inner"}
    # inner closes first but records its parent's id
    assert spans["inner"]["parent"] == spans["outer"]["id"]
    assert spans["outer"]["parent"] is None
    assert spans["outer"]["dur"] > spans["inner"]["dur"] > 0
    assert spans["outer"]["attrs"] == {"kind": "a"}


def test_span_attrs_added_inside_body(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"))
    with log.span("work") as at:
        at["result"] = 42
    log.close()
    (span,) = [r for r in _read_jsonl(log.path) if r["t"] == "span"]
    assert span["attrs"] == {"result": 42}


def test_counters_aggregate(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"))
    log.counter("samples", 32.0)
    log.counter("samples", 32.0)
    log.counter("other", 1.0)
    log.close()
    assert log.totals == {"samples": 64.0, "other": 1.0}
    recs = [r for r in _read_jsonl(log.path) if r["t"] == "counter"]
    # each record carries the running total (truncation-safe aggregates)
    assert [r["total"] for r in recs if r["name"] == "samples"] == [32.0, 64.0]


def test_broken_observer_detached_once_under_concurrent_emit(
        tmp_path, capsys):
    import threading

    log = events.EventLog(str(tmp_path / "t.jsonl"))
    healthy = []
    log.add_observer(healthy.append)

    def boom(rec):
        raise RuntimeError("observer bug")

    log.add_observer(boom)
    n_threads, n_recs = 8, 50
    barrier = threading.Barrier(n_threads)

    def writer(i):
        barrier.wait()   # all threads hit the broken observer together
        for j in range(n_recs):
            log.event("tick", worker=i)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    log.close()

    # exactly one thread won the detach race and warned — not 8, not 400
    err = capsys.readouterr().err
    assert err.count("flexflow_tpu: telemetry observer") == 1
    assert "RuntimeError" in err
    assert boom not in log._observers
    # records kept flowing: to the sink AND to the surviving observer
    ticks = [r for r in _read_jsonl(log.path) if r.get("name") == "tick"]
    assert len(ticks) == n_threads * n_recs
    assert sum(r.get("name") == "tick" for r in healthy) \
        == n_threads * n_recs


def test_lazy_open_no_file_without_records(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"))
    assert not os.path.exists(log.path)  # constructing never touches disk
    log.close()
    assert not os.path.exists(log.path)


def test_active_log_disabled_by_default():
    assert events.active_log() is None


def test_for_config_env_and_flag(tmp_path, monkeypatch):
    assert events.for_config(ff.FFConfig()) is None
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(tmp_path / "e.jsonl"))
    log = events.for_config(ff.FFConfig())
    assert log is not None and log.path == str(tmp_path / "e.jsonl")
    events.reset_active()
    monkeypatch.delenv("FF_TELEMETRY")
    monkeypatch.delenv("FF_TELEMETRY_FILE")
    cfg = ff.FFConfig(telemetry=True, telemetry_file=str(tmp_path / "c.jsonl"))
    log = events.for_config(cfg)
    assert log is not None and log.path == str(tmp_path / "c.jsonl")


def test_config_cli_flags():
    cfg = ff.FFConfig()
    rest = cfg.parse_args(["--telemetry-file", "/tmp/x.jsonl", "--extra"])
    assert cfg.telemetry and cfg.telemetry_file == "/tmp/x.jsonl"
    assert rest == ["--extra"]


# ---------------------------------------------------------------------------
# training-loop integration
# ---------------------------------------------------------------------------

def _tiny_model(batch=16):
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="float32")
    m = ff.FFModel(cfg)
    inp = m.create_tensor((batch, 8), nchw=False)
    t = m.dense(inp, 16, activation=ff.ActiMode.RELU)
    m.softmax(m.dense(t, 4))
    return m, inp


def _train_steps(m, inp, steps):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((m.config.batch_size * steps, 8), np.float32)
    y = rng.integers(0, 4, (m.config.batch_size * steps, 1), dtype=np.int32)
    dl = ff.DataLoader(m, {inp: x}, y)
    for _ in range(steps):
        dl.next_batch(m)
        m.train_iteration()


def test_disabled_zero_event_log_calls(devices, tmp_path, monkeypatch):
    """Telemetry off: no trace file anywhere and literally zero event-log
    calls on the hot path (any write would raise)."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(
        events.EventLog, "_write",
        lambda self, rec: (_ for _ in ()).throw(
            AssertionError(f"event-log call while disabled: {rec}")))
    m, inp = _tiny_model()
    m.compile(ff.SGDOptimizer(lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    assert m._telemetry is None and m._stepstats is None
    m.init_layers()
    _train_steps(m, inp, 3)
    m.get_metrics()
    assert not os.path.exists("ff_trace.jsonl")


def test_train_iteration_emits_step_records(devices, tmp_path, monkeypatch):
    trace = tmp_path / "run.jsonl"
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(trace))
    m, inp = _tiny_model()
    m.compile(ff.SGDOptimizer(lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    assert m._telemetry is not None and m._stepstats is not None
    m.init_layers()
    _train_steps(m, inp, 3)
    m.get_metrics()  # the first drain starts the rate's clock
    _train_steps(m, inp, 2)
    m.get_metrics()
    events.reset_active()

    recs = _read_jsonl(str(trace))
    by_name = {}
    for r in recs:
        if r["t"] == "span":
            by_name.setdefault(r["name"], []).append(r)
    assert len(by_name["compile"]) == 1
    steps = by_name["step"]
    assert len(steps) == 5
    assert steps[0]["attrs"]["first"] and not steps[1]["attrs"]["first"]
    for s in steps:
        assert s["dur"] > 0
        # the span times an enqueue: no rate and no utilization is
        # worked out of it
        assert "samples_per_sec" not in s["attrs"]
        assert "mfu" not in s["attrs"]
    assert len(by_name["data_wait"]) == 5
    assert len(by_name["metric_drain"]) == 2
    # the step's inner spans are logged under the names the profiler has
    for name in ("step_build", "update.prepare", "update.enqueue",
                 "update.finish"):
        assert by_name[name], name
    assert len(by_name["step_build"]) == 1
    assert len(by_name["update.enqueue"]) == 5
    gauges = [r["name"] for r in recs if r["t"] == "gauge"]
    assert {"samples_per_sec", "samples_per_sec_per_chip",
            "first_step_wall_s", "est_collective_bytes_per_step"} \
        <= set(gauges)
    # once a drain, from the second on: one record for two drains
    assert gauges.count("samples_per_sec") == 1
    # a utilization is a share of a chip's peak: absent on the CPU
    assert "mfu" not in gauges
    counters = [r for r in recs if r["t"] == "counter"
                and r["name"] == "samples"]
    assert counters[-1]["total"] == 5 * m.config.batch_size


def test_checkpoint_spans(devices, tmp_path, monkeypatch):
    trace = tmp_path / "run.jsonl"
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(trace))
    m, inp = _tiny_model()
    m.compile(ff.SGDOptimizer(lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    m.init_layers()
    _train_steps(m, inp, 1)
    ckpt = str(tmp_path / "ckpt.npz")
    m.save(ckpt)
    m.load(ckpt)
    events.reset_active()
    names = {r["name"] for r in _read_jsonl(str(trace)) if r["t"] == "span"}
    assert {"checkpoint_save", "checkpoint_restore"} <= names


def test_search_progress_events(devices, tmp_path, monkeypatch):
    trace = tmp_path / "run.jsonl"
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(trace))
    from flexflow_tpu.simulator.search import mcmc_search

    m, _ = _tiny_model()
    m.machine = None
    m.config.workers_per_node = 4
    m.config.num_nodes = 1
    # compile resolves machine; run the search standalone like compile does
    m.compile(ff.SGDOptimizer(lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    mcmc_search(m, budget=5, verbose=False)
    events.reset_active()
    recs = _read_jsonl(str(trace))
    assert any(r["t"] == "event" and r["name"] == "search_progress"
               for r in recs)
    assert any(r["t"] == "span" and r["name"] == "mcmc_search"
               for r in recs)
