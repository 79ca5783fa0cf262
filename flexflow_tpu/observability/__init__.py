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
the hot path performs ZERO event-log calls — every site opens its span
through ``runtime/profiling.span``, given the handle resolved once at
``compile()``, and with no handle that opens a profiler annotation only.

The step's own timeline lives beside this package, in
``runtime/profiling.py`` (docs/observability.md has the tables):

  * host spans ``ff.compile``, ``ff.init_layers``, ``ff.update`` (with ``ff.step_build``,
    ``ff.update.prepare`` / ``.enqueue`` / ``.finish`` inside),
    ``ff.sync``, ``ff.metric_drain``, ``ff.data_wait``,
    ``ff.checkpoint_save`` / ``_restore``: ``TraceAnnotation``s on the
    profiler's clock, and the log's spans of the same names without
    ``ff.`` when telemetry is on (``ff.update`` is the log's ``step``),
  * ``jax.named_scope``s in the compiled step: ``ff.op.<type>.<name>``
    a graph op, ``ff.input_cast``, ``ff.loss``, ``ff.metrics``,
    ``ff.optimizer``, ``ff.guard``, and the kernels' ``ff.kernel.<name>``:
    ``flash_fwd`` / ``_dq`` / ``_dkv`` (``flash_win_*`` under a window,
    ``flash_sel_*`` under a selection), ``gmm`` / ``gmm_t`` / ``tgmm``,
    ``dsa_index_fwd`` / ``_bwd``; ``profiling.step_scopes()`` maps every
    instruction of the loaded step programs to its scope and phase (fwd
    / bwd / opt / other), and ``profiling.trace(logdir)`` writes that map
    beside the
    trace as ``ff_step_scopes.json``,
  * ``profiling.counters()``: set-up on the host's clock, with or
    without a profiler: ``span_s.<phase>`` for the spans outside the
    step loop (``profiling.phase``), JAX's trace / lower / backend /
    fetch events by the phase they fired in
    (``stage_s.<phase>.<stage>``), the step's own as
    ``train_step_compiles`` / ``_compile_s`` / ``_trace_s`` /
    ``_lower_s`` / ``_compile_call_s``, and ``before_first_model_s`` /
    ``graph_build_s`` for what lies before the first ``compile()``.

``events``    — the env/flag-gated structured event log (spans +
                counters + gauges, thread-safe, JSONL sink).
``stepstats`` — per-step instrumentation: the enqueue's wall time,
                first-step compile time, estimated collective bytes,
                device memory stats; and once a DRAIN of the metrics
                (a sync point), over the interval since the drain
                before: samples/s, samples/s/chip, analytic-FLOP MFU.
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
