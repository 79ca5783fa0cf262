"""Health monitor tests: heartbeat file protocol, straggler / data-
starvation detection on synthetic step streams, injected-NaN detection
through the real (CPU) train step within one sampling window, the
zero-calls-when-disabled invariant, and a byte-exact golden check for
tools/health_report.py."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, ".")

import flexflow_tpu as ff
from flexflow_tpu.observability import events, health
from flexflow_tpu.tools import health_report

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "health_report.md")


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    """Fresh singleton + clean health env per test."""
    for var in ("FF_TELEMETRY", "FF_TELEMETRY_FILE", "FF_HEALTH",
                "FF_HEALTH_SAMPLE_EVERY", "FF_HEALTH_STRAGGLER_K",
                "FF_HEALTH_DATA_WAIT_RATIO", "FF_HEARTBEAT_PATH"):
        monkeypatch.delenv(var, raising=False)
    events.reset_active()
    yield
    events.reset_active()


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _health_events(recs):
    return [r for r in recs if r["t"] == "event" and r["name"] == "health"]


# ---------------------------------------------------------------------------
# heartbeat file
# ---------------------------------------------------------------------------

def test_heartbeat_roundtrip(tmp_path, monkeypatch):
    hb = tmp_path / "hb.json"
    monkeypatch.setenv("FF_HEARTBEAT_PATH", str(hb))
    health.write_heartbeat("compile")
    health.write_heartbeat("step", step=7)
    rec = health.read_heartbeat()
    assert rec["phase"] == "step" and rec["step"] == 7
    desc = health.describe_heartbeat(rec, now=rec["unix_time"] + 12.0)
    assert "phase 'step'" in desc and "step 7" in desc and "12s stale" in desc


def test_heartbeat_disabled_is_noop(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    health.write_heartbeat("anything", step=1)
    assert health.read_heartbeat() is None
    assert os.listdir(tmp_path) == []


def test_heartbeat_corrupt_file_tolerated(tmp_path, monkeypatch):
    hb = tmp_path / "hb.json"
    hb.write_text('{"phase": "ste')  # kill raced the atomic replace
    monkeypatch.setenv("FF_HEARTBEAT_PATH", str(hb))
    assert health.read_heartbeat() is None
    assert health.describe_heartbeat(None) is None


# ---------------------------------------------------------------------------
# straggler / starvation on synthetic step streams (no jax)
# ---------------------------------------------------------------------------

def test_straggler_attributed_to_overlapping_span(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"), clock=lambda: 0.0)
    hm = health.HealthMonitor(None, log, sample_every=0,
                              straggler_k=3.0, min_window=4)
    log.add_observer(hm.observe)
    t = 0.0
    for i in range(6):  # steady 10 ms steps build the rolling median
        hm.on_step(i, t, 0.010, first=(i == 0))
        t += 0.012
    # a slow host gather lands in the gap before the straggler step
    log.span_at("data_wait", t + 0.001, 0.08, batch_size=4)
    hm.on_step(6, t + 0.002, 0.1, first=False)
    log.close()

    evs = _health_events(_read_jsonl(log.path))
    assert len(evs) == 1
    a = evs[0]["attrs"]
    assert a["kind"] == "straggler" and a["step"] == 6
    assert a["attribution"] == "data_wait"
    assert a["ratio"] >= 3.0


def test_straggler_without_overlap_is_unknown(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"), clock=lambda: 0.0)
    hm = health.HealthMonitor(None, log, sample_every=0,
                              straggler_k=3.0, min_window=4)
    t = 0.0
    for i in range(6):
        hm.on_step(i, t, 0.010, first=(i == 0))
        t += 0.012
    hm.on_step(6, t, 0.1, first=False)
    log.close()
    (ev,) = _health_events(_read_jsonl(log.path))
    assert ev["attrs"]["attribution"] == "unknown"


def test_data_starvation_detected_per_window(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"), clock=lambda: 0.0)
    hm = health.HealthMonitor(None, log, sample_every=4, wait_ratio=0.3,
                              min_window=99)
    log.add_observer(hm.observe)
    t = 0.0
    for i in range(5):  # waits comparable to step time -> starved
        log.span_at("data_wait", t, 0.008, batch_size=4)
        hm.on_step(i, t + 0.008, 0.010, first=(i == 0))
        t += 0.02
    log.close()
    evs = _health_events(_read_jsonl(log.path))
    assert [e["attrs"]["kind"] for e in evs] == ["data_starvation"]
    assert evs[0]["attrs"]["ratio"] > 0.3


def test_event_cap_per_kind(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"), clock=lambda: 0.0)
    hm = health.HealthMonitor(None, log, sample_every=0)
    for i in range(health.MAX_EVENTS_PER_KIND + 50):
        hm._emit("nonfinite_loss", step=i)
    log.close()
    evs = _health_events(_read_jsonl(log.path))
    assert len(evs) == health.MAX_EVENTS_PER_KIND
    assert evs[-1]["attrs"].get("suppressing_further") is True


# ---------------------------------------------------------------------------
# real training loop (CPU mesh)
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


def test_injected_nan_flagged_within_one_window(devices, tmp_path,
                                                monkeypatch):
    trace = tmp_path / "run.jsonl"
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(trace))
    monkeypatch.setenv("FF_HEALTH", "1")
    monkeypatch.setenv("FF_HEALTH_SAMPLE_EVERY", "2")
    m, inp = _tiny_model()
    m.compile(ff.SGDOptimizer(lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    assert m._health is not None
    assert set(health.HEALTH_METRIC_KEYS) <= set(m._metric_keys())
    m.init_layers()
    # poison one weight tensor: loss and grads go NaN from step 0
    import jax

    leaves, treedef = jax.tree.flatten(m._params)
    leaves[0] = leaves[0] * np.nan
    m._params = jax.tree.unflatten(treedef, leaves)
    _train_steps(m, inp, 2)  # exactly one sampling window, no get_metrics
    events.reset_active()

    recs = _read_jsonl(str(trace))
    kinds = {e["attrs"]["kind"] for e in _health_events(recs)}
    assert "nonfinite_loss" in kinds
    assert "nonfinite_grad" in kinds
    # the compile-time simulator prediction rode along
    assert any(r["t"] == "event" and r["name"] == "sim_prediction"
               for r in recs)
    # and health_report surfaces the finding
    report = health_report.render_report(recs)
    assert "nonfinite_loss" in report and "## Health findings" in report


def test_healthy_run_emits_no_findings(devices, tmp_path, monkeypatch):
    trace = tmp_path / "run.jsonl"
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(trace))
    monkeypatch.setenv("FF_HEALTH", "1")
    monkeypatch.setenv("FF_HEALTH_SAMPLE_EVERY", "2")
    m, inp = _tiny_model()
    m.compile(ff.SGDOptimizer(lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    m.init_layers()
    _train_steps(m, inp, 4)
    m.get_metrics()
    events.reset_active()
    recs = _read_jsonl(str(trace))
    assert not [e for e in _health_events(recs)
                if e["attrs"]["kind"].startswith("nonfinite")]
    # grad-norm gauge rode the drain
    assert any(r["t"] == "gauge" and r["name"] == "grad_global_norm"
               for r in recs)


def test_disabled_telemetry_zero_health_calls(devices, tmp_path,
                                              monkeypatch):
    """FF_HEALTH=1 alone (telemetry off): no monitor, no event-log or
    health calls anywhere on the hot path — any would raise."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("FF_HEALTH", "1")

    def _boom(*a, **k):
        raise AssertionError("health/event-log call while disabled")

    monkeypatch.setattr(events.EventLog, "_write", _boom)
    monkeypatch.setattr(health.HealthMonitor, "on_step", _boom)
    monkeypatch.setattr(health.HealthMonitor, "on_drain", _boom)
    monkeypatch.setattr(health.HealthMonitor, "observe", _boom)
    m, inp = _tiny_model()
    m.compile(ff.SGDOptimizer(lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    assert m._telemetry is None and m._health is None
    # metric vector stays at its base 9 entries: the isfinite reduction
    # is not even traced into the step
    assert len(m._metric_keys()) == 9
    m.init_layers()
    _train_steps(m, inp, 2)
    m.get_metrics()
    assert not os.path.exists("ff_trace.jsonl")


# ---------------------------------------------------------------------------
# health_report golden
# ---------------------------------------------------------------------------

def synthetic_records():
    """Deterministic trace exercising every health_report section."""
    recs = [{"t": "meta", "version": 1, "run_id": "health-golden",
             "pid": 4242, "unix_time": 1700000000.0}]
    recs.append({"t": "span", "name": "compile", "id": 1, "parent": None,
                 "ts": 0.1, "dur": 1.25, "attrs": {"num_ops": 6}})
    recs.append({"t": "event", "name": "sim_prediction", "ts": 1.4,
                 "attrs": {"predicted_step_ms": 9.0, "num_devices": 8,
                           "batch_size": 64, "compute_dtype": "bfloat16"}})
    durs = [2.0, 0.010, 0.012, 0.011, 0.010, 0.010, 0.050, 0.011]
    ts = 2.0
    for i, d in enumerate(durs):
        recs.append({"t": "span", "name": "data_wait", "id": 100 + i,
                     "parent": None, "ts": round(ts - 0.001, 6),
                     "dur": 0.001, "attrs": {"batch_size": 64}})
        recs.append({"t": "span", "name": "step", "id": 2 + i,
                     "parent": None, "ts": round(ts, 6), "dur": d,
                     "attrs": {"step": i, "first": i == 0,
                               "batch_size": 64}})
        ts += d + 0.002
    recs.append({"t": "event", "name": "health", "ts": 2.1,
                 "attrs": {"kind": "nonfinite_loss", "step": 4, "count": 2,
                           "window_steps": 2}})
    recs.append({"t": "event", "name": "health", "ts": 2.25,
                 "attrs": {"kind": "straggler", "step": 6, "dur_ms": 50.0,
                           "p50_ms": 10.5, "ratio": 4.76,
                           "attribution": "data_wait"}})
    recs.append({"t": "event", "name": "health", "ts": 2.3,
                 "attrs": {"kind": "data_starvation", "step": 7,
                           "wait_s": 0.02, "step_s": 0.05, "ratio": 0.4,
                           "threshold": 0.3}})
    recs.append({"t": "event", "name": "sim_divergence", "ts": 2.4,
                 "attrs": {"scope": "step", "predicted_ms": 9.0,
                           "measured_ms": 10.75, "ratio": 0.8372,
                           "n_steps": 7}})
    for op, which, p, m, src in [
            ("conv1", "forward", 1.2, 1.5, "measured"),
            ("conv1", "backward", 2.4, 3.0, "measured"),
            ("dense1", "forward", 0.4, 0.1, "analytic"),
            ("dense1", "backward", 0.8, 0.9, "analytic")]:
        recs.append({"t": "event", "name": "sim_divergence", "ts": 3.0,
                     "attrs": {"scope": "op", "op": op, "which": which,
                               "predicted_ms": p, "measured_ms": m,
                               "ratio": round(p / m, 4), "src": src}})
    # FF_OPPROF in-training attribution: a cadence pass over two ops,
    # with the matching measured-provenance agreement row for one
    recs.append({"t": "event", "name": "sim_divergence", "ts": 3.5,
                 "attrs": {"scope": "op", "op": "dense2",
                           "which": "forward", "predicted_ms": 0.6,
                           "measured_ms": 0.5, "ratio": 1.2,
                           "src": "analytic", "measured_src": "opprof"}})
    for op, which, m, p in [("dense2", "forward", 0.5, 0.6),
                            ("dense2", "backward", 1.4, 1.2),
                            ("sm", "forward", 0.05, 0.04)]:
        recs.append({"t": "event", "name": "op_runtime", "ts": 3.5,
                     "attrs": {"op": op, "which": which,
                               "measured_ms": m, "predicted_ms": p,
                               "ratio": round(p / m, 4),
                               "src": "analytic", "step": 4}})
    recs.append({"t": "event", "name": "op_runtime_pass", "ts": 3.6,
                 "attrs": {"step": 4, "ops_measured": 2, "ops_total": 6,
                           "elapsed_s": 0.42}})
    return recs


def write_trace(path, recs):
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def test_report_sections(tmp_path):
    path = str(tmp_path / "t.jsonl")
    write_trace(path, synthetic_records())
    report = health_report.main([path, "-o", str(tmp_path / "r.md")])
    assert os.path.exists(tmp_path / "r.md")
    for section in ["## Health findings", "## Step health",
                    "## Data pipeline",
                    "## Simulator agreement (predicted vs measured)",
                    "## Op runtime (in-training attribution)"]:
        assert section in report, f"missing {section}"
    # agreement rows carry both sides' provenance
    assert "| measured | standalone |" in report
    assert "| analytic | opprof |" in report
    assert "cadence coverage: 1 passes, 2 op measurements" in report
    assert "nonfinite_loss" in report
    assert "straggler" in report and "data_wait" in report
    # the straggler (4.76x) beats the op-table worst (dense1 4.00x)
    assert "worst 4.8x p50" in report
    assert "worst-case ratio: 4.00x off (dense1 forward)" in report
    assert "per-op ratio band: 0.80x – 4.00x" in report


def test_report_without_health_monitor_derives_step_row(tmp_path):
    """Trace with sim_prediction but no health events (FF_HEALTH off):
    the step-level agreement row is derived from the step spans."""
    recs = [r for r in synthetic_records()
            if not (r.get("name") in ("health", "sim_divergence"))]
    path = str(tmp_path / "t.jsonl")
    write_trace(path, recs)
    report = health_report.render_report(health_report.parse_trace(path))
    assert "- step: predicted 9.000 ms" in report
    assert "no health findings" in report


def test_empty_trace(tmp_path):
    path = str(tmp_path / "e.jsonl")
    write_trace(path, [])
    report = health_report.main([path])
    assert "no health findings" in report


def test_golden_output(tmp_path):
    """Byte-exact golden: regenerate with
    ``python tests/test_health.py --regen`` after deliberate format
    changes."""
    path = str(tmp_path / "t.jsonl")
    write_trace(path, synthetic_records())
    report = health_report.render_report(health_report.parse_trace(path))
    with open(GOLDEN) as f:
        assert report == f.read()


if __name__ == "__main__" and "--regen" in sys.argv:
    import tempfile

    tmp = os.path.join(tempfile.mkdtemp(), "t.jsonl")
    write_trace(tmp, synthetic_records())
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    with open(GOLDEN, "w") as f:
        f.write(health_report.render_report(health_report.parse_trace(tmp)))
    print(f"regenerated {GOLDEN}")
