"""Metrics-plane tests: Prometheus text well-formedness under a live
scrape, percentile agreement with the trace_report reference math,
counter totals under concurrent writer threads, and the zero-observer
guarantee when FF_METRICS_PORT is unset.  That part drives
observability/metrics.py with the stdlib alone.

Last, the training metrics (flexflow_tpu/metrics.py): the sums the
compiled step takes from the logits against those it takes from the
probabilities.
"""

import json
import math
import re
import sys
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, ".")

from flexflow_tpu.metrics import LOG_MIN_VALUE, Metrics, MetricsType
from flexflow_tpu.observability import events, metrics


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    """Fresh env + process-wide singletons per test."""
    for var in ("FF_TELEMETRY", "FF_TELEMETRY_FILE", "FF_METRICS_PORT",
                "FF_METRICS_HOST", "FF_METRICS_WINDOW"):
        monkeypatch.delenv(var, raising=False)
    events.reset_active()
    metrics.stop()
    yield
    metrics.stop()
    events.reset_active()


# one sample line: name{labels} value  (labels optional; value is a
# float literal — the renderer uses %g so no NaN/Inf/timestamps here)
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r' [-+]?[0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?$')


def assert_prom_wellformed(text):
    """Every non-comment line parses as a sample, and every sample's
    base family has a preceding # TYPE declaration."""
    assert text.endswith("\n")
    typed = set()
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            typed.add(line.split()[2])
            continue
        if line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"malformed sample line: {line!r}"
        name = line.split("{")[0].split(" ")[0]
        family_ok = (name in typed
                     or name.rsplit("_", 1)[0] in typed)  # _sum/_count
        assert family_ok, f"sample {name} has no # TYPE declaration"


# ---------------------------------------------------------------------------
# env knob parsing
# ---------------------------------------------------------------------------

def test_port_unset_is_none():
    assert metrics.metrics_port_from_env() is None


def test_port_garbage_is_loud(monkeypatch):
    monkeypatch.setenv("FF_METRICS_PORT", "banana")
    with pytest.raises(ValueError, match="FF_METRICS_PORT"):
        metrics.metrics_port_from_env()
    monkeypatch.setenv("FF_METRICS_PORT", "70000")
    with pytest.raises(ValueError, match="outside"):
        metrics.metrics_port_from_env()


# ---------------------------------------------------------------------------
# zero-cost when disabled
# ---------------------------------------------------------------------------

def test_disabled_registers_no_observer(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"))
    assert metrics.maybe_start(log) is None
    assert log._observers == []
    assert metrics.global_registry() is None
    assert metrics.server_port() is None
    # scrape helper still renders (serving mounts it unconditionally)
    assert "registry disabled" in metrics.scrape_text()


# ---------------------------------------------------------------------------
# registry folding + rendering
# ---------------------------------------------------------------------------

def _feed(reg, recs):
    for r in recs:
        reg.observe(r)


def test_render_prom_wellformed_and_values():
    reg = metrics.MetricsRegistry(window=64)
    _feed(reg, [
        {"t": "counter", "name": "samples", "v": 32.0},
        {"t": "counter", "name": "samples", "v": 32.0},
        {"t": "counter", "name": "serve_failed", "v": 1.0,
         "attrs": {"status": "shed", "request": "r-123"}},
        {"t": "gauge", "name": "mfu", "v": 0.41},
        {"t": "gauge", "name": "serve_batch_occupancy", "v": 0.5,
         "attrs": {"replica": "r0"}},
        {"t": "span", "name": "step", "dur": 0.01},
        {"t": "span", "name": "step", "dur": 0.03},
        {"t": "event", "name": "replica_failover",
         "attrs": {"reason": "health"}},
        {"t": "event", "name": "serve_request_done",
         "attrs": {"ttft_s": 0.12, "tpot_s": 0.004}},
    ])
    text = reg.render_prom()
    assert_prom_wellformed(text)
    assert "ff_samples_total 64" in text
    # allowlisted label kept, request id dropped (cardinality bound)
    assert 'ff_serve_failed_total{status="shed"} 1' in text
    assert 'request="r-123"' not in text
    assert "ff_mfu 0.41" in text
    assert 'ff_serve_batch_occupancy{replica="r0"} 0.5' in text
    # span -> summary with unit suffix
    assert "ff_step_seconds_count 2" in text
    assert "ff_step_seconds_sum 0.04" in text
    # events fold into one family, labelled by event name
    assert 'ff_events_total{event="replica_failover"} 1' in text
    # request-done latencies extracted into histograms
    assert "ff_serve_ttft_seconds_count 1" in text
    assert "ff_serve_tpot_seconds_count 1" in text
    assert "ff_metrics_records_seen_total 9" in text


def test_histogram_percentiles_match_reference():
    from flexflow_tpu.tools.trace_report import percentile as ref_pct
    reg = metrics.MetricsRegistry(window=256)
    durs = [0.001 * (i % 17 + 1) for i in range(100)]
    _feed(reg, [{"t": "span", "name": "step", "dur": d} for d in durs])
    snap = reg.render_vars()["histograms"]["step"]
    vals = sorted(durs)
    for q in (50.0, 95.0, 99.0):
        assert snap[f"p{q:g}"] == pytest.approx(ref_pct(vals, q), abs=1e-9)
        # and the module-local copy agrees with the trace_report math
        assert metrics.percentile(vals, q) == pytest.approx(
            ref_pct(vals, q), abs=1e-12)
    assert snap["count"] == 100
    assert snap["sum"] == pytest.approx(sum(durs), abs=1e-6)


def test_window_bounds_quantiles_but_not_totals():
    reg = metrics.MetricsRegistry(window=8)
    _feed(reg, [{"t": "span", "name": "s", "dur": float(i)}
                for i in range(100)])
    snap = reg.render_vars()["histograms"]["s"]
    assert snap["count"] == 100               # monotonic
    assert snap["sum"] == pytest.approx(sum(range(100)))
    assert snap["p50"] >= 92.0                # quantiles from last 8 only


def test_attach_seeds_preexisting_totals(tmp_path):
    log = events.EventLog(str(tmp_path / "t.jsonl"))
    log.counter("samples", 128.0)
    reg = metrics.MetricsRegistry()
    reg.attach(log)
    log.counter("samples", 32.0)
    log.close()
    assert "ff_samples_total 160" in reg.render_prom()


# ---------------------------------------------------------------------------
# concurrency: writer races + scrape-under-load
# ---------------------------------------------------------------------------

def test_counter_totals_survive_writer_races(tmp_path, monkeypatch):
    monkeypatch.setenv("FF_METRICS_PORT", "0")
    monkeypatch.setenv("FF_METRICS_HOST", "127.0.0.1")
    log = events.EventLog(str(tmp_path / "t.jsonl"))
    reg = metrics.maybe_start(log)
    n_obs = len(log._observers)   # registry tap + SLO evaluator tap
    assert reg is not None and n_obs >= 1
    # second call must not double-attach (idempotence)
    assert metrics.maybe_start(log) is reg
    assert len(log._observers) == n_obs

    port = metrics.server_port()
    n_threads, n_incr = 8, 200
    stop_scraping = threading.Event()
    scrapes = []

    def scrape_loop():
        while not stop_scraping.is_set():
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/plain")
                scrapes.append(r.read().decode())

    def writer():
        for _ in range(n_incr):
            log.counter("races", 1.0)
            log.span_at("step", 0.0, 0.001)

    scraper = threading.Thread(target=scrape_loop)
    scraper.start()
    writers = [threading.Thread(target=writer) for _ in range(n_threads)]
    for t in writers:
        t.start()
    for t in writers:
        t.join()
    stop_scraping.set()
    scraper.join()
    log.close()

    # every mid-load scrape was well-formed
    assert scrapes
    for text in scrapes:
        assert_prom_wellformed(text)
    # no lost increments despite 8 racing observer threads
    final = reg.render_vars()
    assert final["counters"]["races"] == n_threads * n_incr
    assert final["histograms"]["step"]["count"] == n_threads * n_incr
    assert log.totals["races"] == n_threads * n_incr


def test_debug_vars_endpoint(tmp_path, monkeypatch):
    monkeypatch.setenv("FF_METRICS_PORT", "0")
    monkeypatch.setenv("FF_METRICS_HOST", "127.0.0.1")
    log = events.EventLog(str(tmp_path / "t.jsonl"))
    metrics.maybe_start(log)
    log.counter("samples", 16.0)
    log.close()
    port = metrics.server_port()
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/vars", timeout=5) as r:
        body = json.loads(r.read())
    assert body["counters"]["samples"] == 16.0
    assert body["records_seen"] >= 1
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/nope", timeout=5)
    assert ei.value.code == 404


# ---------------------------------------------------------------------------
# serving backend provider (pool-shaped fake; no jax needed)
# ---------------------------------------------------------------------------

class _FakePool:
    def healthz(self):
        return {"status": "ok", "queued": 3, "inflight": 2,
                "replicas": [
                    {"name": "r0", "state": "ready",
                     "incarnation": "r0#1", "restarts": 0},
                    {"name": "r1", "state": "restarting",
                     "incarnation": "r1#4", "restarts": 3},
                ]}


def test_backend_provider_renders_replica_state():
    pool = _FakePool()
    provider = lambda: metrics.render_backend(pool)  # noqa: E731
    metrics.register_provider(provider)
    try:
        text = metrics.scrape_text()
        assert_prom_wellformed(text)
        assert "ff_serve_queue_depth 3" in text
        assert "ff_serve_inflight 2" in text
        assert 'ff_replica_up{replica="r0",state="ready"} 1' in text
        assert 'ff_replica_up{replica="r1",state="restarting"} 0' in text
        # incarnation uid is a string -> info-style series (value 1)
        assert ('ff_replica_incarnation{incarnation="r1#4",replica="r1"} 1'
                in text)
        assert 'ff_replica_restarts{replica="r1"} 3' in text
    finally:
        metrics.unregister_provider(provider)
    assert "ff_replica_up" not in metrics.scrape_text()


def test_broken_backend_never_breaks_scrape():
    class Broken:
        def healthz(self):
            raise RuntimeError("pool wedged")

    text = metrics.render_backend(Broken())
    assert "backend render failed" in text
    assert_prom_wellformed(text)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))


# ---------------------------------------------------------------------------
# the training metrics: from the logits as from the probabilities
# ---------------------------------------------------------------------------

SPARSE, DENSE = "sparse_categorical_crossentropy", "categorical_crossentropy"
CCE_OF = {SPARSE: (MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   "sparse_cce_loss"),
          DENSE: (MetricsType.CATEGORICAL_CROSSENTROPY, "cce_loss")}


def _logits_and_labels(shape, loss_type, seed=0):
    """Seeded logits of `shape` with, planted in the first rows: a tie
    for the maximum between classes 3 and 7 whose label is the first of
    them, the same tie whose label is the second, and a label whose
    probability is under LOG_MIN_VALUE."""
    rng = np.random.default_rng(seed)
    classes = shape[-1]
    logits = rng.standard_normal(shape).astype(np.float32)
    labels = rng.integers(0, classes, shape[:-1])
    rows, lab = logits.reshape(-1, classes), labels.reshape(-1)
    rows[0, [3, 7]] = rows[1, [3, 7]] = 9.0
    lab[0], lab[1] = 3, 7
    rows[2, 5], lab[2] = -60.0, 5
    assert math.exp(-60.0) < LOG_MIN_VALUE
    if loss_type == DENSE:
        labels = np.eye(classes, dtype=np.float32)[labels]
    elif len(shape) == 2:
        labels = labels[:, None]           # (B, 1), as the loaders give it
    return jnp.asarray(logits), jnp.asarray(labels)


@pytest.mark.parametrize("shape", [(16, 12), (4, 6, 12)], ids=["BC", "BTC"])
@pytest.mark.parametrize("loss_type", [SPARSE, DENSE])
@pytest.mark.parametrize("which", ["accuracy", "crossentropy"])
def test_sums_from_logits_equal_those_from_softmax(which, loss_type, shape):
    cce, cce_sum = CCE_OF[loss_type]
    asked = [MetricsType.ACCURACY] if which == "accuracy" else [cce]
    m = Metrics(loss_type, asked)
    assert m.logits_suffice
    logits, labels = _logits_and_labels(shape, loss_type)
    got = jax.jit(lambda x, y: m.compute(x, y, from_logits=True))(
        logits, labels)
    want = jax.jit(m.compute)(jax.nn.softmax(logits, axis=-1), labels)
    assert set(got) == set(want)
    rows = math.prod(shape[:-1])
    assert int(got["train_all"]) == int(want["train_all"]) == rows
    if which == "accuracy":
        assert got["train_correct"].dtype == jnp.int32
        assert int(got["train_correct"]) == int(want["train_correct"])
        # the first maximal index wins: row 0's tie counts, row 1's not
        flat = np.asarray(logits).reshape(rows, -1)
        lab = np.asarray(labels).reshape(rows, -1)
        true = lab.argmax(-1) if loss_type == DENSE else lab[:, 0]
        hits = flat.argmax(-1) == true
        assert hits[0] and not hits[1]
        assert int(got["train_correct"]) == int(hits.sum())
    else:
        np.testing.assert_allclose(got[cce_sum], want[cce_sum], rtol=1e-5)
        # row 2 reads the clamp, not 60
        clamp = -math.log(LOG_MIN_VALUE)
        one = m.compute(
            logits.reshape(rows, -1)[2:3], labels.reshape(rows, -1)[2:3],
            from_logits=True)[cce_sum]
        assert float(one) == pytest.approx(clamp, rel=1e-6)


@pytest.mark.parametrize("asked,suffice", [
    ([MetricsType.ACCURACY], True),
    ([MetricsType.ACCURACY, MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY],
     True),
    ([MetricsType.CATEGORICAL_CROSSENTROPY], True),
    ([], True),
    ([MetricsType.ACCURACY, MetricsType.MEAN_SQUARED_ERROR], False),
    ([MetricsType.ROOT_MEAN_SQUARED_ERROR], False),
    ([MetricsType.MEAN_ABSOLUTE_ERROR], False),
])
def test_which_metrics_the_logits_suffice_for(asked, suffice):
    m = Metrics(SPARSE, asked)
    assert m.logits_suffice is suffice
    if not suffice:  # an error metric needs the probabilities themselves
        logits, labels = _logits_and_labels((4, 12), SPARSE)
        with pytest.raises(AssertionError):
            m.compute(logits, labels, from_logits=True)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_accuracy_reads_the_logits_as_they_are(dtype):
    """No f32 copy of the (B, T, C) tensor and no fold of its leading
    dimensions (on the TPU that reshape was a relayout of 823 MB)."""
    shape = (4, 6, 12)
    m = Metrics(SPARSE, [MetricsType.ACCURACY])
    jaxpr = jax.make_jaxpr(lambda x, y: m.compute(x, y, from_logits=True))(
        jnp.zeros(shape, dtype), jnp.zeros(shape[:-1], jnp.int32))
    reads = {eqn.primitive.name for eqn in jaxpr.eqns
             if any(getattr(v.aval, "shape", None) == shape
                    for v in eqn.invars)}
    assert reads == {"argmax"}, jaxpr
    assert not any(v.aval.shape == shape for eqn in jaxpr.eqns
                   for v in eqn.outvars), jaxpr
