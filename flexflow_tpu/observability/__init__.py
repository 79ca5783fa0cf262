"""Structured telemetry (events + per-step stats).

The reference ships two observability channels: per-op ``--profiling``
printouts (conv_2d.cu:448-473) and the Legion profiler behind
``-lg:prof``.  This package is the TPU-native third channel the
reference never had: a structured, machine-readable event log of the
RUN itself — step spans, phase spans (compile / data-wait /
metric-drain / checkpoint), throughput and MFU counters, search
progress — written as JSONL so ``tools/trace_report.py`` can fold any
run into a step-time/MFU breakdown after the fact (including a run a
watchdog killed: records are line-buffered to disk as they happen).

One flag lights up the whole stack: ``FF_TELEMETRY=1`` in the
environment or ``FFConfig.telemetry = True``.  Disabled (the default),
the hot path performs ZERO event-log calls — every site guards on a
``None`` handle resolved once at ``compile()``.

``events``    — the env/flag-gated structured event log (spans +
                counters + gauges, thread-safe, JSONL sink).
``stepstats`` — per-step instrumentation: wall time, first-step
                compile time, samples/s/chip, analytic-FLOP MFU,
                estimated collective bytes, device memory stats.
``health``    — ``FF_HEALTH=1`` live monitor on top of the log:
                non-finite loss/grad sampling, straggler detection
                with phase attribution, data-starvation warnings, and
                the ``FF_HEARTBEAT_PATH`` heartbeat file protocol.
``agreement`` — continuous simulator validation: predicted per-op /
                per-step times diffed against measured walls as
                ``sim_prediction`` / ``sim_divergence`` events.
``metrics``   — the LIVE plane: ``FF_METRICS_PORT``-gated in-process
                registry tapping the event log's observer hook into
                counters / gauges / rolling-window percentiles, served
                as Prometheus text at ``/metrics`` (and JSON at
                ``/debug/vars``) by a stdlib HTTP exporter; also
                mounted on the serving API server.
``opprof``    — ``FF_OPPROF``-cadence measured per-op attribution:
                jitted fwd/bwd fragments timed in-process under a
                step budget, emitted as ``op_runtime`` events, folded
                into the agreement table with measured provenance,
                and appended to the calibration corpus
                ``tools/calibrate.py`` refits from.
``searchtrace`` — the search flight recorder: per-proposal
                ``search_candidate`` events from the MCMC engines,
                per-op "why this config" summaries (incl. best
                rejected alternative), and the provenance payload a
                strategy-file ``.meta.json`` sidecar carries.  Folded
                by ``tools/search_report.py`` (report + strategy
                ``--diff``).
``reqtrace``  — end-to-end request tracing: a ``TraceContext``
                (trace id, span id, ``FF_TRACE_SAMPLE`` sampling
                decision made once at admission) carried on every
                ``InferenceRequest`` and stamped onto the serve
                records, so one request's queue wait, prefill, decode
                chunks, KV events, and failover/hedge attempts join
                under one id — ``tools/timeline_export.py`` folds them
                into a Perfetto timeline.  Training runs carry a
                run-level trace id on step/compile/reconfig spans.
``slo``       — declarative serving SLOs (TTFT / TPOT / queue wait /
                availability via ``FF_SLO_*``) evaluated as multi-
                window burn rates over the same event tap, exported as
                ``ff_slo_burn_rate{slo,window}`` /
                ``ff_slo_budget_remaining{slo}`` gauges plus an
                hysteresis-guarded ``slo_alert`` event.
"""

from . import (events, health, metrics, opprof, reqtrace, searchtrace,
               slo)
from .events import EventLog, active_log, for_config
from .health import HealthMonitor, read_heartbeat, write_heartbeat
from .metrics import MetricsRegistry
from .reqtrace import TraceContext
from .searchtrace import SearchRecorder
from .slo import BurnRateEvaluator, SLOTarget

__all__ = ["BurnRateEvaluator", "EventLog", "HealthMonitor",
           "MetricsRegistry", "SLOTarget", "SearchRecorder",
           "TraceContext", "active_log", "events",
           "for_config", "health", "metrics", "opprof", "read_heartbeat",
           "reqtrace", "searchtrace", "slo", "write_heartbeat"]
