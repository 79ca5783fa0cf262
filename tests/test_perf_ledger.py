"""Perf-ledger tests: append/read durability, regression detection on
the (metric, backend, proxy, batch) groups, the report renderer, and the
CLI (docs/observability.md "The perf ledger")."""

import json
import sys

sys.path.insert(0, ".")

from flexflow_tpu.tools import perf_ledger as pl  # noqa: E402

METRIC = "alexnet_train_samples_per_sec_per_chip"


def _bench(value, status="ok", proxy=False, backend="tpu", t=0.0, **kw):
    e = {"kind": "bench", "metric": METRIC, "value": value, "unit":
         "samples/s/chip", "backend": backend, "proxy": proxy,
         "status": status, "unix_time": t}
    e.update(kw)
    return e


def test_append_read_roundtrip(tmp_path, monkeypatch):
    ledger = tmp_path / "ledger.jsonl"
    monkeypatch.setenv("FF_PERF_LEDGER", str(ledger))
    stamped = pl.append_entry({"kind": "bench", "metric": METRIC,
                               "value": 100.0, "status": "ok"})
    # schema + wall time stamped on the way in (commit may be None
    # outside a checkout, but the key must exist)
    assert stamped["schema"] == pl.SCHEMA_VERSION
    assert stamped["unix_time"] > 0
    assert "commit" in stamped
    pl.append_entry({"kind": "bench", "metric": METRIC, "value": 90.0,
                     "status": "ok"})
    got = pl.read_entries()
    assert [e["value"] for e in got] == [100.0, 90.0]


def test_corrupt_line_skipped_and_append_recovers(tmp_path):
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text(json.dumps(_bench(100.0)) + "\n"
                      + '{"kind": "bench", "val')  # killed mid-append
    assert len(pl.read_entries(str(ledger))) == 1
    # the next append must start a fresh line, not glue onto the stub
    pl.append_entry(_bench(95.0), path=str(ledger))
    got = pl.read_entries(str(ledger))
    assert [e["value"] for e in got] == [100.0, 95.0]


def test_read_entries_missing_file(tmp_path):
    assert pl.read_entries(str(tmp_path / "nope.jsonl")) == []


def test_regression_flags_20pct_drop():
    entries = [_bench(100.0, t=1.0), _bench(80.0, t=2.0)]
    regs = pl.detect_regressions(entries)
    assert len(regs) == 1
    assert regs[0]["drop_frac"] == 0.2
    assert regs[0]["prev_value"] == 100.0 and regs[0]["value"] == 80.0


def test_regression_ignores_small_drop_and_recovery():
    entries = [_bench(100.0, t=1.0), _bench(95.0, t=2.0),
               _bench(101.0, t=3.0)]
    assert pl.detect_regressions(entries) == []


def test_regression_groups_are_independent():
    # a cheap CPU proxy number must never read as a "regression" vs a
    # chip number, nor a different-batch run vs another batch
    entries = [_bench(100.0, t=1.0),
               _bench(5.0, t=2.0, proxy=True, backend="cpu"),
               _bench(100.0, t=3.0, batch=256),
               _bench(50.0, t=4.0, batch=1024)]
    assert pl.detect_regressions(entries) == []


def test_regression_skips_killed_and_zero_entries():
    # a watchdog kill (value 0) is an availability event, not a 100%
    # perf loss — and must not reset the comparison baseline either
    entries = [_bench(100.0, t=1.0),
               _bench(0.0, status="killed", t=2.0),
               _bench(99.0, t=3.0)]
    assert pl.detect_regressions(entries) == []


def test_last_good_skips_proxy_error_killed():
    entries = [_bench(100.0, t=1.0),
               _bench(0.0, status="killed", t=2.0),
               _bench(7.0, proxy=True, backend="cpu", t=3.0),
               _bench(0.0, status="error", t=4.0)]
    lg = pl.last_good(entries)
    assert lg is not None and lg["value"] == 100.0
    assert pl.last_good([_bench(5.0, proxy=True)]) is None


def test_report_renders_trajectory_and_regression(tmp_path):
    entries = [_bench(100.0, t=1.0, commit="aaa111"),
               _bench(75.0, t=2.0, commit="bbb222"),
               {"kind": "calibration", "backend": "tpu", "entries": 75,
                "fit_points": 52, "fit_log_rmse": 1.03, "unix_time": 3.0}]
    rep = pl.render_report(entries)
    assert "# Perf ledger" in rep
    assert "## Trajectory" in rep
    assert "**REGRESSION**" in rep
    assert "-25.0%" in rep
    assert "## Calibration sessions" in rep
    assert "bbb222" in rep


def test_cli_append_report_last_good(tmp_path, capsys):
    ledger = str(tmp_path / "ledger.jsonl")
    assert pl.main(["append", "--ledger", ledger,
                    "--json", json.dumps(_bench(123.0, t=5.0))]) == 0
    capsys.readouterr()
    assert pl.main(["last-good", "--ledger", ledger]) == 0
    assert json.loads(capsys.readouterr().out)["value"] == 123.0
    out_md = tmp_path / "report.md"
    assert pl.main(["report", "--ledger", ledger,
                    "-o", str(out_md)]) == 0
    assert "## Trajectory" in out_md.read_text()
    # empty ledger -> last-good rc 1
    assert pl.main(["last-good", "--ledger",
                    str(tmp_path / "empty.jsonl")]) == 1


def test_default_log_is_the_programs_own(monkeypatch):
    # PERF_LEDGER.jsonl belongs to the measuring driver: the program's
    # log has another name, and FF_PERF_LEDGER moves it
    import os

    monkeypatch.delenv("FF_PERF_LEDGER", raising=False)
    assert pl.default_path() == os.path.join(pl.repo_root(),
                                             "ff_perf_log.jsonl")
    assert os.path.basename(pl.default_path()) != "PERF_LEDGER.jsonl"
    monkeypatch.setenv("FF_PERF_LEDGER", "/somewhere/else.jsonl")
    assert pl.default_path() == "/somewhere/else.jsonl"
