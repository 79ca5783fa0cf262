"""Fold a telemetry JSONL trace into a markdown memory report.

Cross-checks the three HBM views the memory & compile plane records:

  * **predicted** — the analytic per-device model
    (``simulator/memory.py``) stamped as a ``memory_predicted`` event at
    compile time: params + grads + optimizer slots + live activations +
    collective staging, per device under the resolved strategies,
  * **compiled** — what XLA says each executable needs
    (``xla_memory`` / ``xla_cost`` events from
    ``compiled.memory_analysis()``, one row per jit site), plus compile
    walls and the retrace ledger from ``compile_done``,
  * **live** — allocator truth: the last ``hbm_bytes{device,kind}``
    gauges sampled from ``device_memory_stats()`` (absent on CPU, which
    reports no allocator stats) and the serving KV pool's block bytes.

Any two views disagreeing by more than the divergence band (a factor of
|2| either way) get a loud ``!!`` row — that is the signal that either
the analytic model or the deployment assumption is wrong, and it feeds
the calibration loop (see docs/simulator.md).

STDLIB-ONLY: a trace from a TPU pod must be foldable on any laptop.

Usage:
    python -m flexflow_tpu.tools.memory_report ff_trace.jsonl
    python -m flexflow_tpu.tools.memory_report ff_trace.jsonl -o mem.md
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional

# predicted/XLA (and XLA/live) ratios outside [1/BAND, BAND] are flagged
DIVERGENCE_BAND = 2.0


def parse_trace(path: str) -> List[Dict[str, Any]]:
    """Load JSONL records, skipping blank/corrupt lines (a watchdog kill
    can truncate the final line mid-write)."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return records


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0:
            return f"{n:.1f} {unit}"
        n /= 1024.0
    return f"{n:.1f} TiB"


def _fmt_count(n: float) -> str:
    for unit in ("", "K", "M", "G", "T"):
        if abs(n) < 1000.0:
            return f"{n:.1f}{unit}" if unit else f"{n:.0f}"
        n /= 1000.0
    return f"{n:.1f}P"


def fold(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Reduce the record stream to the three views + compile ledger."""
    predicted: Optional[Dict[str, Any]] = None
    # site -> merged row from compile_done/xla_memory/xla_cost; the LAST
    # record per site wins (recompiles supersede)
    sites: Dict[str, Dict[str, Any]] = {}
    compiles: Dict[str, int] = {}
    retraces: Dict[str, int] = {}
    live: Dict[tuple, float] = {}  # (device, kind) -> last gauge value
    for r in records:
        t, name = r.get("t"), r.get("name")
        at = r.get("attrs", {}) or {}
        if t == "event" and name == "memory_predicted":
            predicted = at  # last wins: recompile re-stamps
        elif t == "event" and name in ("compile_done", "xla_memory",
                                       "xla_cost"):
            row = sites.setdefault(at.get("site", "?"), {})
            if name == "compile_done":
                row["wall_s"] = at.get("wall_s")
                row["aot"] = at.get("aot")
            elif name == "xla_memory":
                for k in ("total_bytes", "argument_bytes", "output_bytes",
                          "temp_bytes", "generated_code_bytes"):
                    row[k] = at.get(k)
            else:
                row["flops"] = at.get("flops")
                row["bytes_accessed"] = at.get("bytes_accessed")
        elif t == "counter" and name == "compiles":
            s = at.get("site", "?")
            compiles[s] = compiles.get(s, 0) + int(r.get("v", 0))
        elif t == "counter" and name == "compile_retraces":
            s = at.get("site", "?")
            retraces[s] = retraces.get(s, 0) + int(r.get("v", 0))
        elif t == "gauge" and name == "hbm_bytes":
            live[(str(at.get("device", "?")),
                  str(at.get("kind", "?")))] = float(r.get("v", 0.0))
    return {"predicted": predicted, "sites": sites, "compiles": compiles,
            "retraces": retraces, "live": live}


def render(f: Dict[str, Any], path: str) -> str:
    out: List[str] = [f"# Memory report — `{path}`", ""]
    pred = f["predicted"]

    # -- predicted ------------------------------------------------------
    out.append("## Predicted (analytic model)")
    out.append("")
    if pred:
        out.append(f"- devices: {pred.get('num_devices')}, peak on device "
                   f"{pred.get('peak_device')}: "
                   f"**{_fmt_bytes(pred.get('peak_bytes', 0))}** "
                   f"(dominant term: {pred.get('dominant_term')})")
        terms = pred.get("terms") or {}
        if terms:
            out.append("")
            out.append("| term | bytes (peak device) |")
            out.append("|---|---|")
            for k, v in terms.items():
                out.append(f"| {k} | {_fmt_bytes(v)} |")
        by_op = pred.get("by_op") or {}
        if by_op:
            out.append("")
            out.append("| op | bytes (max over devices) |")
            out.append("|---|---|")
            for opn, b in sorted(by_op.items(), key=lambda kv: -kv[1]):
                out.append(f"| {opn} | {_fmt_bytes(b)} |")
    else:
        out.append("(no `memory_predicted` event in trace — run with "
                   "FF_TELEMETRY=1 and recompile)")
    out.append("")

    # -- headroom -------------------------------------------------------
    out.append("## Headroom")
    out.append("")
    if pred and pred.get("capacity_bytes"):
        cap = float(pred["capacity_bytes"])
        peak = float(pred.get("peak_bytes", 0))
        head = cap - peak
        pct = 100.0 * head / cap if cap else 0.0
        out.append(f"- headroom: **{_fmt_bytes(head)}** of "
                   f"{_fmt_bytes(cap)} HBM free after predicted peak "
                   f"({pct:.1f}%)")
    else:
        out.append("- headroom: unknown (no machine capacity in trace)")
    out.append("")

    # -- XLA executables ------------------------------------------------
    out.append("## XLA executables")
    out.append("")
    sites = f["sites"]
    if sites:
        out.append("| site | total | args | temps | outputs | flops "
                   "| compile wall | compiles | retraces |")
        out.append("|---|---|---|---|---|---|---|---|---|")
        for s in sorted(sites):
            row = sites[s]
            tb = row.get("total_bytes")
            out.append(
                "| {} | {} | {} | {} | {} | {} | {} | {} | {} |".format(
                    s,
                    _fmt_bytes(tb) if tb is not None else "-",
                    _fmt_bytes(row["argument_bytes"])
                    if row.get("argument_bytes") is not None else "-",
                    _fmt_bytes(row["temp_bytes"])
                    if row.get("temp_bytes") is not None else "-",
                    _fmt_bytes(row["output_bytes"])
                    if row.get("output_bytes") is not None else "-",
                    _fmt_count(row["flops"])
                    if row.get("flops") is not None else "-",
                    f"{row['wall_s']:.3f}s"
                    if row.get("wall_s") is not None else "-",
                    f["compiles"].get(s, 0),
                    f["retraces"].get(s, 0)))
        total_retraces = sum(f["retraces"].values())
        if total_retraces:
            out.append("")
            out.append(f"- **{total_retraces} retrace(s)** — same jit site "
                       "recompiled for a new input signature; on a serving "
                       "ladder this means a bucket leak")
    else:
        out.append("(no compile events in trace — run with FF_MEMPLANE=1)")
    out.append("")

    # -- live -----------------------------------------------------------
    out.append("## Live HBM")
    out.append("")
    live = f["live"]
    if live:
        out.append("| device | kind | bytes |")
        out.append("|---|---|---|")
        for (dev, kind), v in sorted(live.items()):
            out.append(f"| {dev} | {kind} | {_fmt_bytes(v)} |")
    else:
        out.append("(no `hbm_bytes` gauges in trace — CPU backend reports "
                   "no allocator stats)")
    out.append("")

    # -- divergence -----------------------------------------------------
    out.append("## Divergence")
    out.append("")
    checks: List[str] = []
    xla_peak = max((row.get("total_bytes") or 0
                    for row in sites.values()), default=0)
    if pred and xla_peak:
        r = float(pred.get("peak_bytes", 0)) / xla_peak
        flag = "!! " if not (1.0 / DIVERGENCE_BAND <= r <= DIVERGENCE_BAND) \
            else ""
        checks.append(f"- {flag}predicted / XLA(largest executable) = "
                      f"{r:.2f} ({_fmt_bytes(pred.get('peak_bytes', 0))} vs "
                      f"{_fmt_bytes(xla_peak)})")
    live_peak = max((v for (_, kind), v in live.items() if kind == "peak"),
                    default=0.0)
    if live_peak and xla_peak:
        r = live_peak / xla_peak
        flag = "!! " if not (1.0 / DIVERGENCE_BAND <= r <= DIVERGENCE_BAND) \
            else ""
        checks.append(f"- {flag}live(peak) / XLA(largest executable) = "
                      f"{r:.2f} ({_fmt_bytes(live_peak)} vs "
                      f"{_fmt_bytes(xla_peak)})")
    if live_peak and pred:
        r = live_peak / max(float(pred.get("peak_bytes", 0)), 1.0)
        flag = "!! " if not (1.0 / DIVERGENCE_BAND <= r <= DIVERGENCE_BAND) \
            else ""
        checks.append(f"- {flag}live(peak) / predicted = {r:.2f}")
    if checks:
        out.extend(checks)
        if any(c.startswith("- !! ") for c in checks):
            out.append("")
            out.append(f"`!!` marks a ratio outside [1/{DIVERGENCE_BAND:g}, "
                       f"{DIVERGENCE_BAND:g}] — the analytic model or the "
                       "deployment assumption is wrong; see docs/simulator.md")
    else:
        out.append("(fewer than two views in trace — nothing to cross-check)")
    out.append("")
    return "\n".join(out)


def main(argv: Optional[List[str]] = None) -> str:
    p = argparse.ArgumentParser(
        description="Fold a telemetry trace into a markdown memory report")
    p.add_argument("trace", help="telemetry JSONL file (FF_TELEMETRY_FILE)")
    p.add_argument("-o", "--output", help="write report here (default stdout)")
    args = p.parse_args(argv)

    report = render(fold(parse_trace(args.trace)), args.trace)
    if args.output:
        with open(args.output, "w") as f:
            f.write(report)
    else:
        sys.stdout.write(report)
    return report


if __name__ == "__main__":
    main()
