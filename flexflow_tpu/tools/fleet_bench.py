"""Fleet-scale resilience bench: SLO goodput through incidents.

Replays the seeded scenario suite from ``testing/fleet.py`` — diurnal
ramp, flash crowd, long-tail mix, mid-run zone outage — against a live
pool+autoscaler on CPU, and scores each run by attained-vs-offered RPS
under the SLO, the shed/failed split, replica-count timeline, and (for
the incident scenarios) time-to-recover.

Usage::

    python -m flexflow_tpu.tools.fleet_bench                   # all four
    python -m flexflow_tpu.tools.fleet_bench \
        --scenarios flash_crowd,zone_outage --requests 10      # CI smoke

Outputs:

  * ``BENCH_FLEET.json`` in ``--workdir`` — the full per-scenario score
    dicts under a stable schema,
  * one ``fleet_goodput`` entry per scenario appended to the perf
    ledger (``FF_PERF_LEDGER`` / ``--ledger``; ``--no-ledger`` skips),
  * per-scenario telemetry traces in the workdir (render them with
    ``tools/serve_report.py`` — the "## Fleet" section shows the
    replica timeline and scale events).

Exit code is non-zero when any scenario loses a response (resolved
neither done/shed/failed — must never happen), returns an INCORRECT
response (bitwise vs ``generate()`` — must never happen), or ends with
zero goodput.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

from ..testing import fleet
from . import perf_ledger

BENCH_SCHEMA = 1


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(
        description="fleet resilience bench (SLO goodput through chaos)")
    ap.add_argument("--scenarios", default="all",
                    help="comma list from %s, or 'all'"
                         % ",".join(fleet.SCENARIOS))
    ap.add_argument("--requests", type=int, default=16,
                    help="requests per scenario (default 16)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slo-ms", type=float, default=fleet.DEFAULT_SLO_MS,
                    help="end-to-end SLO for goodput accounting")
    ap.add_argument("--workdir", default="bench_fleet",
                    help="output directory (BENCH_FLEET.json + traces)")
    ap.add_argument("--ledger", default=None,
                    help="perf log path (default: FF_PERF_LEDGER or "
                         "the checkout's ff_perf_log.jsonl)")
    ap.add_argument("--no-ledger", action="store_true",
                    help="skip the perf-ledger append")
    args = ap.parse_args(argv)

    if args.scenarios == "all":
        names = list(fleet.SCENARIOS)
    else:
        names = [s.strip() for s in args.scenarios.split(",") if s.strip()]
        unknown = [s for s in names if s not in fleet.SCENARIOS]
        if unknown:
            ap.error(f"unknown scenario(s) {unknown}; "
                     f"choose from {list(fleet.SCENARIOS)}")
    os.makedirs(args.workdir, exist_ok=True)

    results = {}
    rc = 0
    for name in names:
        trace = os.path.join(args.workdir, f"fleet_{name}.trace.jsonl")
        print(f"[fleet_bench] scenario={name} requests={args.requests} "
              f"seed={args.seed} ...", flush=True)
        res = fleet.run_scenario(
            name, requests=args.requests, seed=args.seed,
            slo_ms=args.slo_ms, telemetry_file=trace)
        results[name] = res
        ttr = res["time_to_recover_s"]
        print(f"[fleet_bench]   goodput {res['goodput_rps']:.2f}/"
              f"{res['offered_rps']:.2f} rps "
              f"(attainment {res['slo_attainment']:.0%}) "
              f"shed={res['n_shed']} failed={res['n_failed']} "
              f"incorrect={res['n_incorrect']} lost={res['n_lost']}"
              + (f" time_to_recover={ttr:.2f}s" if ttr is not None else ""),
              flush=True)
        if res["n_lost"] or res["n_incorrect"]:
            print(f"[fleet_bench]   FAIL: lost={res['n_lost']} "
                  f"incorrect={res['n_incorrect']}", file=sys.stderr)
            rc = 1
        if res["goodput_rps"] <= 0:
            print(f"[fleet_bench]   FAIL: zero goodput in {name}",
                  file=sys.stderr)
            rc = 1

    bench = dict(bench="fleet", schema=BENCH_SCHEMA, seed=args.seed,
                 requests=args.requests, slo_ms=args.slo_ms,
                 scenarios=results)
    out = os.path.join(args.workdir, "BENCH_FLEET.json")
    with open(out, "w") as f:
        json.dump(bench, f, indent=2)
    print(f"[fleet_bench] wrote {out}", flush=True)

    if not args.no_ledger:
        path = args.ledger or perf_ledger.default_path()
        for name, res in results.items():
            entry = dict(
                kind="serving", metric="fleet_goodput",
                value=res["goodput_rps"], unit="req/s",
                backend="cpu", proxy=True,
                status="ok" if rc == 0 else "fail",
                provenance=dict(
                    scenario=name, requests=res["requests"],
                    seed=res["seed"], slo_ms=res["slo_ms"],
                    offered_rps=res["offered_rps"],
                    slo_attainment=res["slo_attainment"],
                    time_to_recover_s=res["time_to_recover_s"],
                    shed=res["n_shed"], failed=res["n_failed"]))
            perf_ledger.append_entry(entry, path=path)
        print(f"[fleet_bench] appended {len(results)} fleet_goodput "
              f"entr{'y' if len(results) == 1 else 'ies'} to {path}",
              flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
