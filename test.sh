#!/usr/bin/env bash
# Test-suite driver — the analogue of the reference's python/test.sh
# (which runs ~30 flexflow_python example invocations as the de-facto
# suite).  Here: the pytest suite on a virtual 8-device CPU mesh, then
# (with RUN_EXAMPLES=1) the example apps with VerifyMetrics assertions.
#
# Two gates:
#   ./test.sh          fast gate — `-m "not slow"`, the default loop
#   FULL=1 ./test.sh   everything, including slow integration tests
# (tests/conftest.py enables the persistent XLA compile cache, so warm
# re-runs are much faster than the first.)
set -e
cd "$(dirname "$0")"

JAX_PLATFORMS=cpu python -m flexflow_tpu.tools.doctor

if [ -n "$FULL" ]; then
  python -m pytest tests/ -q "$@"
else
  python -m pytest tests/ -q -m "not slow" "$@"
fi

# Telemetry smoke: a 2-step tiny training run under FF_TELEMETRY +
# FF_HEALTH + FF_MEMPLANE must produce a readable trace (including the
# compile plane's owned-compile and XLA introspection events), a
# heartbeat file, and all three reports must fold it
# (docs/observability.md).
SMOKE_DIR=$(mktemp -d)
TRACE="$SMOKE_DIR/smoke.jsonl"
HEARTBEAT="$SMOKE_DIR/hb.json"
FF_TELEMETRY=1 FF_TELEMETRY_FILE="$TRACE" FF_MEMPLANE=1 \
  FF_HEALTH=1 FF_HEARTBEAT_PATH="$HEARTBEAT" \
  python examples/alexnet.py -b 8 --iterations 2 -e 1 > /dev/null
REPORT=$(python -m flexflow_tpu.tools.trace_report "$TRACE")
echo "$REPORT" | grep -q "## Steps" \
  || { echo "telemetry smoke: report missing step section"; exit 1; }
python -m flexflow_tpu.tools.health_report "$TRACE" > /dev/null \
  || { echo "health smoke: health_report failed"; exit 1; }
grep -q '"phase"' "$HEARTBEAT" \
  || { echo "health smoke: heartbeat file missing/empty"; exit 1; }
grep -q '"name": "compile_done"' "$TRACE" \
  || { echo "memory smoke: no compile_done event in trace"; exit 1; }
grep -q '"name": "xla_memory"' "$TRACE" \
  || { echo "memory smoke: no xla_memory event in trace"; exit 1; }
MEMREPORT=$(python -m flexflow_tpu.tools.memory_report "$TRACE") \
  || { echo "memory smoke: memory_report failed"; exit 1; }
echo "$MEMREPORT" | grep -q "headroom: \*\*" \
  || { echo "memory smoke: report missing headroom line"; exit 1; }
echo "telemetry+health+memory smoke: OK ($(wc -l < "$TRACE") trace records)"

# Search-observability smoke: a seeded tiny-budget search must produce a
# candidate-level trace + provenance sidecar, search_report must explain
# it, and --diff must name changed ops vs the shipped strategy
# (docs/observability.md "Search tracing").  --engine python so every
# proposal is recorded (the native engine logs summaries only).
STRACE="$SMOKE_DIR/search.jsonl"
FF_TELEMETRY=1 FF_TELEMETRY_FILE="$STRACE" \
  python -m flexflow_tpu.tools.offline_search alexnet --devices 16 \
    --budget 20 --seed 0 --engine python --quiet \
    --export "$SMOKE_DIR/alexnet_new.pb" > /dev/null
test -f "$SMOKE_DIR/alexnet_new.pb.meta.json" \
  || { echo "search smoke: provenance sidecar missing"; exit 1; }
SREPORT=$(python -m flexflow_tpu.tools.search_report "$STRACE")
echo "$SREPORT" | grep -q "## Why this config" \
  || { echo "search smoke: report missing why-this-config section"; exit 1; }
python -m flexflow_tpu.tools.search_report \
    --diff strategies/alexnet_16.pb "$SMOKE_DIR/alexnet_new.pb" \
  | grep -q "changed /" \
  || { echo "search smoke: strategy diff failed"; exit 1; }
echo "search smoke: OK ($(wc -l < "$STRACE") trace records)"

# Delta-simulation smoke: the incremental simulator must return the
# IDENTICAL seeded search result as the full rebuild (search_bench exits
# 1 on any mismatch) and append a search_throughput entry to the perf
# ledger (docs/simulator.md "Delta simulation").  Tiny budget: this
# verifies the equality contract and the ledger plumbing, not the 10x
# throughput number — that is search_bench's default-budget job.
DELTA_LEDGER="$SMOKE_DIR/delta_ledger.jsonl"
DELTA_OUT=$(python -m flexflow_tpu.tools.search_bench alexnet --devices 16 \
    --budget 200 --seed 0 --repeats 1 --ledger "$DELTA_LEDGER") \
  || { echo "delta smoke: search_bench failed (delta vs full mismatch?)"; exit 1; }
grep -q '"metric": "search_throughput"' "$DELTA_LEDGER" \
  || { echo "delta smoke: no search_throughput ledger entry"; exit 1; }
echo "delta smoke: OK ($(echo "$DELTA_OUT" | python -c "
import json, sys
b = json.loads(sys.stdin.read())
print(f\"identical={b['identical']}, {b['delta_proposals_per_s']} vs \"
      f\"{b['full_proposals_per_s']} proposals/s, ratio {b['ratio']}x\")"))"

# Population-search smoke: search_bench --mode quality runs the
# single-chain and population engines at an equal (tiny) budget on a
# small transformer, judges both winners under one fresh reference
# simulator, and appends a search_quality entry (value = single_ms /
# population_ms, higher is better) that the perf-ledger report must
# render without flagging a regression (docs/simulator.md
# "Population search").
POP_LEDGER="$SMOKE_DIR/pop_ledger.jsonl"
POP_OUT=$(python -m flexflow_tpu.tools.search_bench transformer --devices 16 \
    --batch-size 32 --budget 600 --seed 0 --mode quality \
    --ledger "$POP_LEDGER") \
  || { echo "population smoke: search_bench --mode quality failed"; exit 1; }
grep -q '"metric": "search_quality"' "$POP_LEDGER" \
  || { echo "population smoke: no search_quality ledger entry"; exit 1; }
python -m flexflow_tpu.tools.perf_ledger report --ledger "$POP_LEDGER" \
  | grep -q "# Perf ledger" \
  || { echo "population smoke: ledger report failed"; exit 1; }
python -m flexflow_tpu.tools.perf_ledger report --ledger "$POP_LEDGER" \
  | grep -q "REGRESSION" \
  && { echo "population smoke: report flags a regression on a fresh ledger"; exit 1; }
echo "population smoke: OK ($(echo "$POP_OUT" | python -c "
import json, sys
b = json.loads(sys.stdin.read())
print(f\"single {b['single_ms']}ms vs population {b['population_ms']}ms, \"
      f\"ratio {b['ratio']}x\")"))"

# Serving smoke: train the toy transformer, serve 8 concurrent HTTP
# requests through the continuous-batching engine, verify every greedy
# output bitwise against one-shot generate(), and fold the serving
# trace into a latency/occupancy report (docs/serving.md).
SERVE_TRACE="$SMOKE_DIR/serve.jsonl"
FF_TELEMETRY=1 FF_TELEMETRY_FILE="$SERVE_TRACE" \
  python -m flexflow_tpu.tools.loadgen --requests 8 --concurrency 4 \
    --seed 0 --train-iters 20 --check-generate \
    --out "$SMOKE_DIR/BENCH_SERVE.json" \
  || { echo "serving smoke: loadgen failed (request error or greedy mismatch)"; exit 1; }
python -m flexflow_tpu.tools.serve_report "$SERVE_TRACE" \
  | grep -q "## Latency" \
  || { echo "serving smoke: serve_report missing latency section"; exit 1; }
python - "$SMOKE_DIR/BENCH_SERVE.json" <<'EOF' \
  || { echo "serving smoke: BENCH_SERVE.json acceptance failed"; exit 1; }
import json, sys
b = json.load(open(sys.argv[1]))
assert b["n_ok"] == 8 and b["greedy_matches"] == 8, b
assert b["mean_batch_occupancy"] > 1.5, b["mean_batch_occupancy"]
EOF
echo "serving smoke: OK ($(python -c "
import json, sys
b = json.load(open('$SMOKE_DIR/BENCH_SERVE.json'))
print(f\"{b['achieved_tokens_s']} tok/s, occupancy {b['mean_batch_occupancy']}\")"))"

# Paged-KV smoke: a shared 16-token system prompt across a mixed-length
# request mix — the block-paged engine must reuse the cached prefix
# (prefix_hit_rate > 0, prefill tokens actually skipped), stay bitwise
# against one-shot generate(), and serve_report must fold the kv gauges
# into its "## KV cache" section (docs/serving.md "Paged KV cache").
PAGED_TRACE="$SMOKE_DIR/paged.jsonl"
FF_TELEMETRY=1 FF_TELEMETRY_FILE="$PAGED_TRACE" \
  python -m flexflow_tpu.tools.loadgen --requests 8 --concurrency 4 \
    --seed 0 --prefix-tokens 16 --len-dist mixed --check-generate \
    --out "$SMOKE_DIR/BENCH_PAGED.json" \
  || { echo "paged smoke: loadgen failed (request error or greedy mismatch)"; exit 1; }
python - "$SMOKE_DIR/BENCH_PAGED.json" <<'EOF' \
  || { echo "paged smoke: BENCH_PAGED.json acceptance failed"; exit 1; }
import json, sys
b = json.load(open(sys.argv[1]))
assert b["paged"] is True and b["n_ok"] == 8 and b["greedy_matches"] == 8, b
assert b["prefix_hit_rate"] > 0, b["prefix_hit_rate"]
assert b["prefill_tokens_saved"] > 0, b["prefill_tokens_saved"]
assert b["kv_blocks_peak"] > 0, b["kv_blocks_peak"]
EOF
python -m flexflow_tpu.tools.serve_report "$PAGED_TRACE" \
  | grep -q "## KV cache" \
  || { echo "paged smoke: serve_report missing KV cache section"; exit 1; }
echo "paged smoke: OK ($(python -c "
import json
b = json.load(open('$SMOKE_DIR/BENCH_PAGED.json'))
print(f\"hit rate {b['prefix_hit_rate']}, \"
      f\"{b['prefill_tokens_saved']} prefill tokens saved, \"
      f\"peak {b['kv_blocks_peak']} blocks\")"))"

# Metrics + tracing smoke: live /metrics while loadgen drives a
# 2-replica pool with every request traced (FF_TRACE_SAMPLE=1) — one
# mid-load scrape must return serving gauges (per-replica health,
# paged-KV block occupancy), training counters, AND the SLO burn-rate
# gauges in valid Prometheus text; afterwards the trace must fold into
# Perfetto-loadable Chrome-trace JSON with request tracks whose attempt
# spans nest prefill + decode children (docs/observability.md "Live
# metrics endpoint", "Request tracing", "Timeline export").
METRICS_PORT=9109
METRICS_TRACE="$SMOKE_DIR/metrics_serve.jsonl"
FF_TELEMETRY=1 FF_TELEMETRY_FILE="$METRICS_TRACE" FF_MEMPLANE=1 \
  FF_METRICS_PORT=$METRICS_PORT FF_METRICS_HOST=127.0.0.1 \
  FF_TRACE_SAMPLE=1 \
  python -m flexflow_tpu.tools.loadgen --requests 24 --concurrency 4 \
    --replicas 2 --seed 0 --train-iters 20 \
    --out "$SMOKE_DIR/BENCH_METRICS.json" > /dev/null &
LOADGEN_PID=$!
python - "$METRICS_PORT" <<'EOF' \
  || { kill $LOADGEN_PID 2>/dev/null; echo "metrics smoke: scrape failed"; exit 1; }
import re, sys, time, urllib.request
url = f"http://127.0.0.1:{sys.argv[1]}/metrics"
want = ("ff_replica_up", "ff_samples_total",   # serving + training series
        "ff_serve_kv_blocks_used", "ff_serve_kv_blocks_free",  # paged KV
        "ff_hbm_bytes",                # KV-pool block bytes (CPU has no
                                       # allocator stats; pool gauge only)
        "ff_compile_retraces_total",   # compile plane: flat-ladder ledger
        "ff_slo_burn_rate",            # SLO evaluator riding the same tap
        "ff_slo_budget_remaining")
sample = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.eE]+$')
deadline = time.time() + 180
while time.time() < deadline:
    try:
        with urllib.request.urlopen(url, timeout=5) as r:
            assert r.headers["Content-Type"].startswith("text/plain"), \
                r.headers["Content-Type"]
            text = r.read().decode()
    except OSError:
        time.sleep(0.5)
        continue
    if all(w in text for w in want):
        n = 0
        for line in text.splitlines():
            if line and not line.startswith("#"):
                assert sample.match(line), f"malformed sample: {line!r}"
                n += 1
        slo = [l for l in text.splitlines()
               if l.startswith("ff_slo_burn_rate")]
        assert slo, "no ff_slo_burn_rate sample"
        print(f"metrics smoke: scraped {n} well-formed samples mid-load "
              f"({len(slo)} SLO burn-rate series)")
        sys.exit(0)
    time.sleep(0.5)
sys.exit(f"never saw {want} at {url}")
EOF
wait $LOADGEN_PID \
  || { echo "metrics smoke: loadgen exited non-zero"; exit 1; }
echo "metrics smoke: OK"

# Timeline smoke: fold the traced run into Chrome trace-event JSON.
TIMELINE="$SMOKE_DIR/timeline.json"
python -m flexflow_tpu.tools.timeline_export "$METRICS_TRACE" -o "$TIMELINE" \
  || { echo "timeline smoke: export failed"; exit 1; }
python - "$TIMELINE" <<'EOF' \
  || { echo "timeline smoke: Chrome-trace acceptance failed"; exit 1; }
import collections, json, sys
doc = json.load(open(sys.argv[1]))
evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
assert evs, "empty timeline"
for a, b in zip(evs, evs[1:]):           # Perfetto ground rule 1
    assert a["ts"] <= b["ts"], (a, b)
depth = collections.Counter()            # ground rule 2: matched B/E
for e in evs:
    k = (e["pid"], e["tid"])
    if e["ph"] == "B":
        depth[k] += 1
    elif e["ph"] == "E":
        depth[k] -= 1
        assert depth[k] >= 0, f"E without B on {k}"
assert all(v == 0 for v in depth.values()), depth
# >=1 request track whose attempt span nests prefill + decode children
tracks = doc["otherData"]["request_tracks"]
assert tracks, "no request tracks despite FF_TRACE_SAMPLE=1"
procs = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
         if e["ph"] == "M" and e["name"] == "process_name"}
req_pids = {p for p, n in procs.items() if n == "requests"}
by_tid = collections.defaultdict(list)
for e in evs:
    if e["pid"] in req_pids and e["ph"] == "B":
        by_tid[e["tid"]].append(e["name"])
nested = [tid for tid, names in by_tid.items()
          if names[0] == "serve_attempt"
          and "serve_prefill" in names and "serve_decode" in names]
assert nested, f"no attempt track nests prefill+decode: {dict(by_tid)}"
print(f"timeline smoke: {len(evs)} events, {len(tracks)} request "
      f"tracks, {len(nested)} attempt tracks with prefill+decode")
EOF
echo "timeline smoke: OK"

# Chaos smoke: one seeded FF_CHAOS run injects a NaN step, a mid-epoch
# SIGTERM, and a failing checkpoint write; the resumed run must finish
# bitwise-equal to an uninterrupted baseline and the trace must narrate
# every recovery (docs/robustness.md).
python -m flexflow_tpu.testing.chaos_smoke --workdir "$SMOKE_DIR/chaos" \
  || { echo "chaos smoke: FAILED"; exit 1; }
python -m flexflow_tpu.tools.trace_report "$SMOKE_DIR/chaos/victim_trace.jsonl" \
  | grep -q "## Resilience" \
  || { echo "chaos smoke: trace report missing resilience section"; exit 1; }
echo "chaos smoke: OK"

# Reshard smoke: chaos kills half the mesh mid-run; the reconfiguration
# controller must re-search on the survivors, hot-swap deterministically,
# leave a diffable swap-record pair, and health_report must narrate the
# swap (docs/robustness.md "Online re-parallelization").
python -m flexflow_tpu.testing.chaos_smoke --workdir "$SMOKE_DIR/reshard" \
    --scenario reshard \
  || { echo "reshard smoke: FAILED"; exit 1; }
python -m flexflow_tpu.tools.health_report "$SMOKE_DIR/reshard/run1/trace.jsonl" \
  | grep -q "## Reconfiguration" \
  || { echo "reshard smoke: health report missing reconfiguration section"; exit 1; }
echo "reshard smoke: OK"

# Serve-failover smoke: chaos kills 1 of 3 pool replicas mid-load; all
# requests (incl. the killed replica's in-flight ones) must complete
# bitwise-equal to one-shot generate(), the monitor must restart the
# replica, serve_report must show the per-replica lens, and the goodput
# headline lands in BENCH_SERVE.json (docs/serving.md "Resilience").
python -m flexflow_tpu.testing.chaos_smoke --workdir "$SMOKE_DIR/serve_failover" \
    --scenario serve_failover \
  || { echo "serve-failover smoke: FAILED"; exit 1; }
python -m flexflow_tpu.tools.serve_report "$SMOKE_DIR/serve_failover/serve_trace.jsonl" \
  | grep -q "## Replicas" \
  || { echo "serve-failover smoke: serve_report missing replicas section"; exit 1; }
python - "$SMOKE_DIR/serve_failover/BENCH_SERVE.json" <<'EOF' \
  || { echo "serve-failover smoke: BENCH_SERVE.json acceptance failed"; exit 1; }
import json, sys
b = json.load(open(sys.argv[1]))
assert b["n_ok"] == b["requests"] and b["n_fail"] == 0, b
assert b["goodput_rps"] > 0, b
assert b["pool"]["replica_downs"] >= 1 and b["pool"]["failovers"] >= 1, b
EOF
echo "serve-failover smoke: OK ($(python -c "
import json
b = json.load(open('$SMOKE_DIR/serve_failover/BENCH_SERVE.json'))
print(f\"goodput {b['goodput_rps']} req/s, \"
      f\"{b['pool']['failovers']} failovers\")"))"

# Zone-outage smoke: chaos downs a WHOLE ZONE of a 4-replica, 2-zone
# pool mid-load with the autoscaler running; every request (incl. the
# dead zone's in-flight ones) must complete bitwise-equal to generate(),
# re-dispatches must avoid the dead zone, and the autoscaler must
# backfill the surviving zone (docs/robustness.md "Zone outages").
python -m flexflow_tpu.testing.chaos_smoke --workdir "$SMOKE_DIR/zone_outage" \
    --scenario zone_outage \
  || { echo "zone-outage smoke: FAILED"; exit 1; }
python -m flexflow_tpu.tools.serve_report "$SMOKE_DIR/zone_outage/zone_trace.jsonl" \
  | grep -q "## Fleet" \
  || { echo "zone-outage smoke: serve_report missing fleet section"; exit 1; }
echo "zone-outage smoke: OK"

# Fleet smoke: the seeded flash-crowd incident scenario against a live
# pool+autoscaler — BENCH_FLEET.json must parse with zero lost/incorrect
# responses and nonzero SLO goodput, and the run lands a fleet_goodput
# perf-ledger entry (docs/serving.md "Fleet scenarios").  The zone
# scenario is exercised (with asserts) by the zone-outage smoke above;
# here the cheap traffic shape keeps the gate fast.
python -m flexflow_tpu.tools.fleet_bench --scenarios flash_crowd \
    --requests 10 --seed 0 --workdir "$SMOKE_DIR/fleet" \
    --ledger "$SMOKE_DIR/fleet_ledger.jsonl" \
  || { echo "fleet smoke: fleet_bench FAILED"; exit 1; }
python - "$SMOKE_DIR/fleet/BENCH_FLEET.json" <<'EOF' \
  || { echo "fleet smoke: BENCH_FLEET.json acceptance failed"; exit 1; }
import json, sys
b = json.load(open(sys.argv[1]))
assert b["bench"] == "fleet" and b["scenarios"], b.keys()
for name, s in b["scenarios"].items():
    assert s["n_lost"] == 0 and s["n_incorrect"] == 0, (name, s)
    assert s["goodput_rps"] > 0, (name, s["goodput_rps"])
EOF
grep -q '"metric": "fleet_goodput"' "$SMOKE_DIR/fleet_ledger.jsonl" \
  || { echo "fleet smoke: no fleet_goodput ledger entry"; exit 1; }
echo "fleet smoke: OK ($(python -c "
import json
b = json.load(open('$SMOKE_DIR/fleet/BENCH_FLEET.json'))
s = b['scenarios']['flash_crowd']
print(f\"goodput {s['goodput_rps']}/{s['offered_rps']} rps, \"
      f\"attainment {s['slo_attainment']:.0%}\")"))"

if [ -n "$RUN_EXAMPLES" ]; then
  for ex in examples/mnist_mlp_native.py \
            examples/keras/seq_mnist_mlp.py \
            examples/keras/func_mnist_mlp_concat.py; do
    echo "== $ex"
    python "$ex" -e 1 -b 64
  done
fi
echo "test.sh: OK"
