"""Chrome-trace export (flexflow_tpu/tools/timeline_export.py).

Well-formedness is the contract: Perfetto rejects a trace whose B/E
pairs don't match or nest, so the fold must stay stack-safe even when
producer clocks overlap (failover/hedge attempts).  The end-to-end test
drives a seeded 2-replica pool with FF_TRACE_SAMPLE=1 and asserts the
exported document carries a request track with prefill + decode child
spans under the attempt span — the acceptance shape from
docs/observability.md.
"""

import collections
import json

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.models.transformer import build_transformer
from flexflow_tpu.observability import events
from flexflow_tpu.serving.config import ServeConfig
from flexflow_tpu.serving.pool import ReplicaPool
from flexflow_tpu.tools import timeline_export
from flexflow_tpu.tools.trace_report import parse_trace

V = 32
MAX_SEQ = 64


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for var in ("FF_TELEMETRY", "FF_TELEMETRY_FILE", "FF_TRACE_SAMPLE",
                "FF_TRACE_CHUNK"):
        monkeypatch.delenv(var, raising=False)
    events.reset_active()
    yield
    events.reset_active()


@pytest.fixture(scope="module")
def model():
    cfg = ff.FFConfig(batch_size=4)
    m = ff.FFModel(cfg)
    build_transformer(m, 4, seq_length=MAX_SEQ, num_layers=1,
                      embed_dim=16, num_heads=2, vocab_size=V)
    m.compile(ff.SGDOptimizer(lr=0.1),
              "sparse_categorical_crossentropy", ["accuracy"])
    m.init_layers(seed=3)
    return m


def _check_wellformed(doc):
    """Perfetto's ground rules: monotone timestamps, every B matched by
    an E on the same track, named processes/threads."""
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    for a, b in zip(evs, evs[1:]):
        assert a["ts"] <= b["ts"], (a, b)
    depth = collections.Counter()
    for e in evs:
        key = (e["pid"], e["tid"])
        if e["ph"] == "B":
            depth[key] += 1
        elif e["ph"] == "E":
            depth[key] -= 1
            assert depth[key] >= 0, f"E without B on {key}"
    assert all(v == 0 for v in depth.values()), depth
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(m["name"] == "process_name" for m in metas)
    return evs


def _tracks(doc):
    """(process name, thread name) -> [events] from the metadata."""
    procs = {e["pid"]: e["args"]["name"]
             for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"]
               for e in doc["traceEvents"]
               if e["ph"] == "M" and e["name"] == "thread_name"}
    out = collections.defaultdict(list)
    for e in doc["traceEvents"]:
        if e["ph"] in ("B", "E", "i"):
            key = (procs[e["pid"]],
                   threads.get((e["pid"], e["tid"]), "?"))
            out[key].append(e)
    return out


# ---------------------------------------------------------------------------
# fold unit tests
# ---------------------------------------------------------------------------

def test_fold_clamps_overlap_to_matched_pairs():
    # child claims to outlive its parent (overlapping producer clocks):
    # the fold must clamp, never emit unmatched/crossing pairs
    spans = [(0, 100, "parent", {}), (50, 100, "child", {})]
    out = timeline_export._fold_spans(spans, pid=1, tid=1)
    assert [e["ph"] for e in out] == ["B", "B", "E", "E"]
    # the child's E lands at the parent's end, not past it
    assert out[2]["ts"] == 100 and out[3]["ts"] == 100


def test_fold_sequential_spans_close_in_order():
    spans = [(0, 10, "a", {}), (20, 10, "b", {})]
    out = timeline_export._fold_spans(spans, pid=1, tid=1)
    assert [(e["ph"], e.get("name")) for e in out] == [
        ("B", "a"), ("E", None), ("B", "b"), ("E", None)]


def test_sampled_traces_needs_span_ids():
    recs = [
        {"t": "span", "name": "step", "ts": 0.0, "dur": 1.0,
         "attrs": {"trace_id": "run" * 8}},          # run-level stamp
        {"t": "span", "name": "serve_prefill", "ts": 0.0, "dur": 0.1,
         "attrs": {"trace_id": "aa" * 16}},          # unsampled request
        {"t": "span", "name": "serve_attempt", "ts": 0.0, "dur": 0.2,
         "attrs": {"trace_id": "bb" * 16, "span_id": "cc" * 8}},
    ]
    assert timeline_export.sampled_traces(recs) == {"bb" * 16}


def test_export_synthetic_track_layout(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"))
    log.span_at("step", 0.0, 0.5, step=0, trace_id="run" * 8)
    log.span_at("mcmc_search", 0.0, 0.2, budget=10)
    log.event("compile_done", op="all")
    log.gauge("serve_batch_occupancy", 1.5, replica="replica-0")
    log.gauge("mfu", 0.3)
    log.close()
    doc = timeline_export.export_records(parse_trace(log.path))
    _check_wellformed(doc)
    tracks = _tracks(doc)
    assert ("training", "train") in tracks    # run-trace stays here
    assert ("search", "search") in tracks
    assert [e["name"] for e in tracks[("compile", "compile")]] \
        == ["compile_done"]
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert {e["name"] for e in counters} \
        == {"occupancy replica-0", "mfu"}
    assert doc["otherData"]["request_tracks"] == []


# ---------------------------------------------------------------------------
# end to end: seeded 2-replica run -> Perfetto-loadable timeline
# ---------------------------------------------------------------------------

def test_two_replica_run_exports_request_tracks(model, tmp_path,
                                               monkeypatch):
    monkeypatch.setenv("FF_TRACE_SAMPLE", "1")
    monkeypatch.setenv("FF_TRACE_CHUNK", "4")
    log = events.EventLog(str(tmp_path / "serve.jsonl"))
    cfg = ServeConfig(max_batch=2, max_seq=MAX_SEQ, replicas=2,
                      replica_timeout_s=120.0)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, size=int(rng.integers(3, 12)))
               .astype(np.int32) for _ in range(6)]
    with ReplicaPool(model, config=cfg, telemetry=log) as pool:
        handles = [pool.submit(p, 8) for p in prompts]
        for h in handles:
            h.result(120)
    log.close()

    # CLI round trip: the written file is plain Chrome-trace JSON
    out = str(tmp_path / "timeline.json")
    assert timeline_export.main([log.path, "-o", out]) == 0
    with open(out) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    _check_wellformed(doc)

    # one request track per trace root + one per attempt
    req_tracks = doc["otherData"]["request_tracks"]
    assert len(req_tracks) >= 6
    tracks = _tracks(doc)
    attempt_tracks = [k for k in tracks
                      if k[0] == "requests" and "/a" in k[1]]
    assert len(attempt_tracks) >= 6
    # every attempt track nests prefill + decode inside the attempt span
    for key in attempt_tracks:
        begins = [e["name"] for e in tracks[key] if e["ph"] == "B"]
        assert begins[0] == "serve_attempt", begins
        assert "serve_prefill" in begins and "serve_decode" in begins
    # root tracks carry the client-level span
    root_tracks = [k for k in tracks
                   if k[0] == "requests" and "/" not in k[1]]
    for key in root_tracks:
        assert [e["name"] for e in tracks[key] if e["ph"] == "B"] \
            == ["serve_request"]
    # replica gauges became counter tracks on the serving process
    counters = {e["name"] for e in doc["traceEvents"] if e["ph"] == "C"}
    assert any(c.startswith("occupancy replica-") for c in counters)


def test_unsampled_run_has_no_request_tracks(model, tmp_path,
                                             monkeypatch):
    monkeypatch.setenv("FF_TRACE_SAMPLE", "0")
    log = events.EventLog(str(tmp_path / "serve.jsonl"))
    cfg = ServeConfig(max_batch=2, max_seq=MAX_SEQ, replicas=2,
                      replica_timeout_s=120.0)
    p = np.arange(5, dtype=np.int32)
    with ReplicaPool(model, config=cfg, telemetry=log) as pool:
        pool.submit(p, 4).result(120)
    log.close()
    doc = timeline_export.export_records(parse_trace(log.path))
    _check_wellformed(doc)
    assert doc["otherData"]["request_tracks"] == []
    # the serve spans still render — on the serving process instead
    tracks = _tracks(doc)
    serving = [k for k in tracks if k[0] == "serving"]
    names = {e["name"] for k in serving for e in tracks[k]
             if e["ph"] == "B"}
    assert "serve_prefill" in names and "serve_decode" in names


def test_cli_empty_trace_fails_loud(tmp_path, capsys):
    p = tmp_path / "empty.jsonl"
    p.write_text("")
    assert timeline_export.main([str(p)]) == 1
    assert "no records" in capsys.readouterr().err
