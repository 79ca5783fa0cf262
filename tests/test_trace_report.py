"""trace_report CLI tests: percentile math, report sections on a
synthetic trace, corrupt-tail tolerance, and a byte-exact golden check
(the report is a committed artifact format — changes must be deliberate)."""

import json
import os
import sys

sys.path.insert(0, ".")

from flexflow_tpu.tools import trace_report

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "trace_report.md")


def synthetic_records():
    """Deterministic mini-trace exercising every report section."""
    recs = [{"t": "meta", "version": 1, "run_id": "golden-run", "pid": 4242,
             "unix_time": 1700000000.0}]
    recs.append({"t": "span", "name": "compile", "id": 1, "parent": None,
                 "ts": 0.1, "dur": 1.25,
                 "attrs": {"num_ops": 6, "num_devices": 8}})
    # step 0 carries the jit trace + compile; steps 1..4 steady-state
    durs = [2.0, 0.010, 0.012, 0.011, 0.020]
    ts = 2.0
    for i, d in enumerate(durs):
        recs.append({"t": "span", "name": "step", "id": 2 + i,
                     "parent": None, "ts": round(ts, 6), "dur": d,
                     "attrs": {"step": i, "first": i == 0,
                               "batch_size": 64}})
        recs.append({"t": "counter", "name": "samples", "v": 64.0,
                     "total": 64.0 * (i + 1), "ts": round(ts + d, 6)})
        recs.append({"t": "span", "name": "data_wait", "id": 100 + i,
                     "parent": None, "ts": round(ts - 0.001, 6),
                     "dur": 0.001, "attrs": {"batch_size": 64,
                                             "prefetched": i > 0}})
        ts += d + 0.002
    recs.append({"t": "gauge", "name": "first_step_wall_s", "v": 2.0,
                 "ts": 4.0})
    recs.append({"t": "gauge", "name": "est_collective_bytes_per_step",
                 "v": 1572864.0, "ts": 4.0})
    # two drains of the metrics: the rate and the MFU are gauged once a
    # drain, over the interval since the drain before (here 2 steps of
    # 64 samples in 0.04 s), whatever the enqueues above took
    recs.append({"t": "span", "name": "metric_drain", "id": 49,
                 "parent": None, "ts": 7.96, "dur": 0.003, "attrs": {}})
    recs.append({"t": "span", "name": "metric_drain", "id": 50,
                 "parent": None, "ts": 8.0, "dur": 0.003, "attrs": {}})
    recs.append({"t": "gauge", "name": "samples_per_sec", "v": 3200.0,
                 "ts": 8.001})
    recs.append({"t": "gauge", "name": "samples_per_sec_per_chip",
                 "v": 400.0, "ts": 8.001})
    recs.append({"t": "gauge", "name": "mfu", "v": 0.1, "ts": 8.001})
    recs.append({"t": "span", "name": "checkpoint_save", "id": 51,
                 "parent": None, "ts": 9.0, "dur": 0.5,
                 "attrs": {"path": "/tmp/ckpt.npz", "step": 5}})
    for op, fwd, bwd in [("conv1", 1.5, 3.0), ("dense1", 0.4, 0.8),
                         ("pool1", 0.1, 0.1)]:
        recs.append({"t": "event", "name": "op_profile", "ts": 10.0,
                     "attrs": {"op": op, "forward_ms": fwd,
                               "backward_ms": bwd}})
    for it, best in [(0, 9.5), (100, 7.2), (200, 6.8)]:
        recs.append({"t": "event", "name": "search_progress", "ts": 11.0,
                     "attrs": {"engine": "mcmc", "iter": it,
                               "best_ms": best}})
    recs.append({"t": "span", "name": "mcmc_search", "id": 60,
                 "parent": None, "ts": 11.0, "dur": 2.5,
                 "attrs": {"budget": 250, "best_ms": 6.8}})
    return recs


def write_trace(path, recs):
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def test_percentile():
    assert trace_report.percentile([], 50) == 0.0
    assert trace_report.percentile([3.0], 95) == 3.0
    assert trace_report.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    assert trace_report.percentile([1.0, 2.0, 3.0, 4.0], 100) == 4.0


def test_report_sections(tmp_path):
    path = str(tmp_path / "t.jsonl")
    write_trace(path, synthetic_records())
    report = trace_report.main([path, "-o", str(tmp_path / "r.md")])
    assert os.path.exists(tmp_path / "r.md")
    for section in ["## Steps", "## Phases", "## Counters",
                    "## Gauges (last value)", "## Top ops",
                    "## Search progress"]:
        assert section in report, f"missing {section}"
    # first step reported separately; steady stats over the other 4
    assert "first step (incl. compile): 2000.0 ms" in report
    assert "steady-state enqueue over 4 steps" in report
    assert "golden-run" in report


def test_rate_and_mfu_come_from_the_per_drain_gauges(tmp_path):
    """The summary's throughput and MFU are the last per-drain gauges;
    a step span's duration (the enqueue) yields neither, and attributes
    an older log put on its step spans are not read."""
    recs = synthetic_records()
    report = trace_report.render_report(recs)
    assert "throughput (last drain interval): 3200.0 samples/s" in report
    assert "MFU (analytic FLOPs, last drain interval): 10.00%" in report
    no_gauges = [r for r in recs if r.get("t") != "gauge"]
    for r in no_gauges:
        if r.get("name") == "step":
            r["attrs"].update(samples_per_sec=6400.0, mfu=0.5)
    report = trace_report.render_report(no_gauges)
    assert "steady-state enqueue over 4 steps" in report
    assert "throughput" not in report and "MFU" not in report


def test_corrupt_tail_tolerated(tmp_path):
    path = str(tmp_path / "t.jsonl")
    write_trace(path, synthetic_records())
    with open(path, "a") as f:
        f.write('{"t": "span", "name": "tru')  # watchdog-killed mid-write
    report = trace_report.main([path])
    assert "## Steps" in report


def test_empty_trace(tmp_path):
    path = str(tmp_path / "e.jsonl")
    write_trace(path, [])
    report = trace_report.main([path])
    assert "no span/counter records" in report


def test_golden_output(tmp_path):
    """Byte-exact golden: regenerate with
    ``python tests/test_trace_report.py --regen`` after deliberate
    format changes."""
    path = str(tmp_path / "t.jsonl")
    write_trace(path, synthetic_records())
    report = trace_report.render_report(trace_report.parse_trace(path))
    with open(GOLDEN) as f:
        assert report == f.read()


if __name__ == "__main__" and "--regen" in sys.argv:
    import tempfile

    tmp = os.path.join(tempfile.mkdtemp(), "t.jsonl")
    write_trace(tmp, synthetic_records())
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    with open(GOLDEN, "w") as f:
        f.write(trace_report.render_report(trace_report.parse_trace(tmp)))
    print(f"regenerated {GOLDEN}")
