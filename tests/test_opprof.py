"""Measured per-op attribution tests: cadence gating, event + corpus
emission from a real CPU training loop, measured-sum sanity against the
measured step wall, and the corpus round-trip through
``calibrate --fit-only``."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, ".")

import flexflow_tpu as ff
from flexflow_tpu.observability import events, opprof


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    for var in ("FF_TELEMETRY", "FF_TELEMETRY_FILE", "FF_OPPROF",
                "FF_OPPROF_BUDGET_S", "FF_OPPROF_CORPUS",
                "FF_METRICS_PORT"):
        monkeypatch.delenv(var, raising=False)
    events.reset_active()
    yield
    events.reset_active()


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _tiny_model(batch=16):
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="float32")
    m = ff.FFModel(cfg)
    inp = m.create_tensor((batch, 8), nchw=False)
    t = m.dense(inp, 16, activation=ff.ActiMode.RELU)
    m.softmax(m.dense(t, 4))
    return m, inp


def _compile(m):
    m.compile(ff.SGDOptimizer(lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])


def _train_steps(m, inp, steps):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((m.config.batch_size * steps, 8), np.float32)
    y = rng.integers(0, 4, (m.config.batch_size * steps, 1), dtype=np.int32)
    dl = ff.DataLoader(m, {inp: x}, y)
    for _ in range(steps):
        dl.next_batch(m)
        m.train_iteration()


# ---------------------------------------------------------------------------
# knob parsing
# ---------------------------------------------------------------------------

def test_cadence_unset_is_none():
    assert opprof.cadence_from_env() is None
    assert opprof.budget_from_env() == opprof.DEFAULT_BUDGET_S


def test_knobs_parse_loudly(monkeypatch):
    monkeypatch.setenv("FF_OPPROF", "every-few")
    with pytest.raises(ValueError, match="FF_OPPROF"):
        opprof.cadence_from_env()
    monkeypatch.setenv("FF_OPPROF", "0")
    with pytest.raises(ValueError, match=">= 1"):
        opprof.cadence_from_env()
    monkeypatch.setenv("FF_OPPROF_BUDGET_S", "-3")
    with pytest.raises(ValueError, match="> 0"):
        opprof.budget_from_env()


def test_disabled_is_none(devices, tmp_path, monkeypatch):
    # unset -> no profiler even with telemetry on
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(tmp_path / "t.jsonl"))
    m, _ = _tiny_model()
    _compile(m)
    assert m._telemetry is not None and m._opprof is None
    events.reset_active()
    # set, but telemetry off -> still None (nothing to attribute into)
    monkeypatch.setenv("FF_OPPROF", "2")
    assert opprof.maybe_profiler(m, None) is None


# ---------------------------------------------------------------------------
# in-training cadence pass
# ---------------------------------------------------------------------------

def test_cadence_emits_events_and_corpus(devices, tmp_path, monkeypatch):
    trace = tmp_path / "run.jsonl"
    corpus = tmp_path / "measured.json"
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(trace))
    monkeypatch.setenv("FF_OPPROF", "2")
    monkeypatch.setenv("FF_OPPROF_BUDGET_S", "30")  # cover all ops on CPU
    monkeypatch.setenv("FF_OPPROF_CORPUS", str(corpus))
    m, inp = _tiny_model()
    _compile(m)
    assert m._opprof is not None and m._opprof.cadence == 2
    m.init_layers()
    _train_steps(m, inp, 5)  # passes fire at steps 2 and 4
    events.reset_active()

    recs = _read_jsonl(str(trace))
    runtime = [r for r in recs if r["t"] == "event"
               and r["name"] == "op_runtime"]
    passes = [r for r in recs if r["t"] == "event"
              and r["name"] == "op_runtime_pass"]
    assert not [r for r in recs if r["t"] == "event"
                and r["name"] == "op_runtime_error"]
    assert passes and {p["attrs"]["step"] for p in passes} == {2, 4}
    assert runtime
    for r in runtime:
        a = r["attrs"]
        assert a["measured_ms"] > 0
        assert a["which"] in ("forward", "backward")
        assert a["src"] in ("measured", "analytic")
        assert a["step"] in (2, 4)
    # every compute op got both directions within the wide budget
    op_names = {op.name for op in m.ops
                if getattr(op, "pc", None) is not None
                and not op.pc.host_placed}
    assert {r["attrs"]["op"] for r in runtime} == op_names
    assert passes[0]["attrs"]["ops_measured"] == len(op_names)

    # agreement rows carry in-training measurement provenance
    div = [r for r in recs if r["t"] == "event"
           and r["name"] == "sim_divergence"
           and r["attrs"].get("scope") == "op"]
    assert div and all(d["attrs"]["measured_src"] == "opprof" for d in div)

    # corpus entries: measured=True, tagged with the REAL backend (cpu
    # under the test harness — never masquerading as chip timings)
    with open(corpus) as f:
        entries = json.load(f)
    assert entries
    for key, v in entries.items():
        assert v["measured"] is True
        assert v["platform"] == "cpu"
        assert v["t"] > 0

    # measured per-op sum is the same order of magnitude as the measured
    # step wall (CPU dispatch overhead dominates tiny fragments, so the
    # tolerance is deliberately wide: two decades either way)
    last = {}
    for r in runtime:
        last[(r["attrs"]["op"], r["attrs"]["which"])] = \
            r["attrs"]["measured_ms"]
    sum_ms = sum(last.values())
    # the FASTEST later step: step 1 re-compiles once more (its inputs
    # carry shardings step 0's lacked) and any step may pay a cache
    # load, so a median of four can land on a compile; the minimum is a
    # steady step whatever the compile cache held
    step_ms = min(r["dur"] for r in recs if r["t"] == "span"
                  and r["name"] == "step"
                  and not r["attrs"].get("first")) * 1e3
    assert step_ms > 0 and sum_ms > 0
    assert step_ms / 100.0 < sum_ms < step_ms * 100.0


def test_broken_op_skipped_permanently(devices, tmp_path, monkeypatch):
    trace = tmp_path / "run.jsonl"
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(trace))
    m, inp = _tiny_model()
    _compile(m)
    m.init_layers()
    log = m._telemetry
    prof = opprof.OpProfiler(m, log, cadence=1, budget_s=30.0,
                             corpus_path=str(tmp_path / "c.json"))
    first = next(op for op in m.ops
                 if getattr(op, "pc", None) is not None
                 and not op.pc.host_placed)
    orig = prof._fragment

    def boom(op):
        if op.name == first.name:
            raise RuntimeError("no fragment for you")
        return orig(op)

    prof._fragment = boom
    prof.on_step(1)
    prof.on_step(2)
    assert first.name in prof._broken
    events.reset_active()
    runtime_ops = {r["attrs"]["op"] for r in _read_jsonl(str(trace))
                   if r["t"] == "event" and r["name"] == "op_runtime"}
    assert first.name not in runtime_ops
    assert runtime_ops  # the rest of the list still measured


# ---------------------------------------------------------------------------
# corpus round-trip: opprof entries -> calibrate --fit-only
# ---------------------------------------------------------------------------

def test_corpus_roundtrips_through_calibrate_fit_only(
        devices, tmp_path, monkeypatch, capsys):
    trace = tmp_path / "run.jsonl"
    corpus = str(tmp_path / "measured.json")
    fit_out = str(tmp_path / "fit.json")
    monkeypatch.setenv("FF_TELEMETRY", "1")
    monkeypatch.setenv("FF_TELEMETRY_FILE", str(trace))
    monkeypatch.setenv("FF_PERF_LEDGER", str(tmp_path / "ledger.jsonl"))
    m, inp = _tiny_model()
    _compile(m)
    m.init_layers()
    # target_platform="tpu" stands in for running on the chip: entries
    # must come back out of calibrate's TPU-filtered load
    prof = opprof.OpProfiler(m, m._telemetry, cadence=1, budget_s=30.0,
                             corpus_path=corpus, target_platform="tpu")
    prof.on_step(1)
    events.reset_active()
    with open(corpus) as f:
        n_entries = len(json.load(f))
    assert n_entries > 0

    from flexflow_tpu.tools import calibrate
    rc = calibrate.main(["--fit-only", "--out", corpus,
                         "--fit-out", fit_out, "--devices", "2",
                         "--alexnet-batch", "64", "--bench-batch", "16",
                         "--models", "alexnet", "--no-inception",
                         "--quiet"])
    assert rc in (None, 0)
    out = capsys.readouterr().out
    # calibrate loaded every opprof-written entry without complaint
    assert f"measured cache: {n_entries} entries" in out

    # and the perf ledger recorded the refit session
    led = _read_jsonl(str(tmp_path / "ledger.jsonl"))
    assert any(e.get("kind") == "calibration" and e.get("fit_only")
               for e in led)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
