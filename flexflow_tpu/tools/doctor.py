"""Environment doctor: one command to sanity-check an install.

    python -m flexflow_tpu.tools.doctor
    JAX_PLATFORMS=cpu python -m flexflow_tpu.tools.doctor   # no chip

Reports versions, the backend and its devices, native-library
availability, every subsystem's effective environment, and runs a tiny
training loop end to end.  One process on one platform — whichever JAX
selects: a chip belongs to one process at a time, so nothing here
starts a child that would need it too.  Exit code 0 iff every required
check passes.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional, Tuple


def _check(name: str, fn, required: bool = True) -> Tuple[str, str, str]:
    try:
        detail = fn()
        return name, "ok", str(detail)
    except Exception as e:  # noqa: BLE001 — report, don't crash the doctor
        return (name, "FAIL" if required else "warn",
                f"{type(e).__name__}: {e}")


def _versions():
    import jax
    import numpy as np

    return f"python {sys.version.split()[0]}, jax {jax.__version__}, numpy {np.__version__}"


def _accelerator():
    import jax
    import jax.numpy as jnp

    d = jax.devices()
    x = jnp.ones((128, 128), jnp.float32)
    s = float((x @ x).sum())
    if s != 128.0 * 128 * 128:
        raise RuntimeError(f"matmul returned {s}")
    return (f"{len(d)} device(s), platform={d[0].platform}, "
            f"[0]={d[0].device_kind}, matmul ok")


def _native_libs():
    from ..utils import native

    return ", ".join(f"{lib}: {how}" for lib, how in
                     native.status(load_all=True).items())


def _optional_deps():
    mods = []
    for m in ("orbax.checkpoint", "torch", "flax", "optax"):
        try:
            __import__(m)
            mods.append(m.split(".")[0])
        except ImportError:
            pass
    return ", ".join(mods) or "none"


def _observability():
    # Effective config as events.py/health.py will see it, plus a
    # write probe of the configured trace sink — a read-only sink
    # otherwise fails silently at flush time, long after launch.
    from ..observability import events

    tel = os.environ.get("FF_TELEMETRY", "")
    sink = events.default_path()
    health = os.environ.get("FF_HEALTH", "")
    hb = os.environ.get("FF_HEARTBEAT_PATH", "")
    bits = [f"FF_TELEMETRY={'on' if events._env_enabled() else tel or 'off'}",
            f"sink={sink}",
            f"FF_HEALTH={health or 'off'}",
            f"FF_HEARTBEAT_PATH={hb or 'off'}"]
    d = os.path.dirname(os.path.abspath(sink)) or "."
    if not os.path.isdir(d):
        bits.append(f"sink dir missing: {d}")
    elif not os.access(d, os.W_OK):
        raise PermissionError(f"trace sink dir not writable: {d} "
                              f"({', '.join(bits)})")
    else:
        bits.append("sink writable")
    return ", ".join(bits)


def _metrics():
    # Effective live-metrics env as observability/metrics.py and
    # opprof.py will see it — a typo'd port or cadence raises HERE
    # (required-style error in the detail), not silently at launch —
    # plus a bind probe of the configured exporter port.
    import socket

    from ..observability import events, metrics, opprof

    port = metrics.metrics_port_from_env()    # ValueError on garbage
    cadence = opprof.cadence_from_env()       # ValueError on garbage
    bits = []
    if port is None:
        bits.append("FF_METRICS_PORT=off")
    else:
        bits.append(f"FF_METRICS_PORT={port}")
        host = os.environ.get("FF_METRICS_HOST", "")
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind((host, port))
            bits.append(f"bind {host or '0.0.0.0'}:{s.getsockname()[1]} ok")
        finally:
            s.close()
        if not events._env_enabled():
            bits.append("WARN: FF_METRICS_PORT set but FF_TELEMETRY off "
                        "— the registry would see no events (training "
                        "series empty; serving state still scrapes)")
    if cadence is None:
        bits.append("FF_OPPROF=off")
    else:
        bits.append(f"FF_OPPROF={cadence} "
                    f"(budget {opprof.budget_from_env()}s, "
                    f"corpus {opprof.corpus_path_from_env()})")
        if not events._env_enabled():
            bits.append("WARN: FF_OPPROF set but FF_TELEMETRY off — "
                        "op attribution emits nothing without a log")
    return ", ".join(bits)


def _tracing():
    # Effective request-tracing + SLO env as reqtrace.py/slo.py will
    # see it — a typo'd sample rate or SLO target raises HERE
    # (required-style error in the detail), not silently at admission
    # time — then a synthetic traced request is round-tripped through
    # tools/timeline_export.py so a broken exporter is a launch-time
    # finding, not a post-incident one.
    from ..observability import events, reqtrace, slo
    from ..tools import timeline_export

    rate = reqtrace.sample_rate_from_env()    # ValueError on garbage
    chunk = reqtrace.chunk_tokens_from_env()  # ValueError on garbage
    targets = slo.targets_from_env()          # ValueError on garbage
    windows = slo.windows_from_env()
    bits = [f"FF_TRACE_SAMPLE={rate:g}",
            f"FF_TRACE_CHUNK={chunk or 'off'}"]
    if rate > 0 and not events._env_enabled():
        bits.append("WARN: FF_TRACE_SAMPLE set but FF_TELEMETRY off — "
                    "no log exists, so no trace is ever recorded")
    if targets:
        bits.append("SLOs: " + ", ".join(
            t.name + (f"<{t.threshold_s * 1e3:g}ms"
                      if t.threshold_s is not None else "")
            for t in targets)
            + f" @ {targets[0].objective:g} over "
            + "/".join(f"{int(w)}s" for w in windows))
    else:
        bits.append("SLOs: all disabled")

    # synthetic traced request -> exporter round trip (in-memory log)
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        log = events.EventLog(os.path.join(d, "probe.jsonl"))
        ctx = reqtrace.TraceContext(reqtrace.new_trace_id(),
                                    reqtrace.new_span_id(), None, True)
        att = ctx.child()
        log.span_at("serve_request", 0.0, 0.01, request_id="probe-0",
                    status="done", **ctx.ids())
        log.span_at("serve_attempt", 0.001, 0.009,
                    request_id="probe-0#a1", **att.ids())
        log.span_at("serve_prefill", 0.002, 0.003,
                    request_id="probe-0#a1", **reqtrace.tag(att))
        log.span_at("serve_decode", 0.005, 0.004,
                    request_id="probe-0#a1", **reqtrace.tag(att))
        log.close()
        from .trace_report import parse_trace

        doc = timeline_export.export_records(
            parse_trace(os.path.join(d, "probe.jsonl")))
    s = timeline_export.summarize(doc)
    if s["request_tracks"] < 1 or s["spans"] < 4:
        raise RuntimeError(
            f"timeline round trip lost the synthetic request: {s}")
    bits.append(f"timeline round trip ok ({s['spans']} spans, "
                f"{s['request_tracks']} request tracks)")
    return ", ".join(bits)


def _memory():
    # The memory & compile plane at a glance: effective FF_MEMPLANE
    # state, whether this backend reports allocator stats at all (TPU:
    # yes; CPU: no — live hbm_bytes gauges will be absent), and an
    # analytic headroom check of the default transformer against the
    # calibrated machine model.  WARN when the serving KV-block budget
    # plus the model's weight state cannot fit HBM — that misconfig
    # otherwise surfaces as an OOM at the first full-load prefill.
    from ..observability import events, memplane
    from ..observability.stepstats import device_memory_stats

    mp = os.environ.get("FF_MEMPLANE", "")
    bits = [f"FF_MEMPLANE={'on' if memplane.enabled_from_env() else mp or 'off'}"]
    if memplane.enabled_from_env() and not events._env_enabled():
        bits.append("WARN: FF_MEMPLANE set but FF_TELEMETRY off — "
                    "compile/memory events have no log to land in (inert)")
    mems = device_memory_stats()
    if mems:
        bits.append(f"allocator stats: {len(mems)} device(s) report")
    else:
        bits.append("allocator stats: unavailable "
                    "(CPU backend reports none)")

    import flexflow_tpu as ff
    from ..models.transformer import build_transformer
    from ..serving.config import ServeConfig
    from ..simulator.machine import TPUMachineModel
    from ..simulator.memory import memory_per_device

    # graph build only — memory_per_device needs no compile
    m = ff.FFModel(ff.FFConfig(batch_size=8))
    layers, embed = 4, 512
    build_transformer(m, 8, seq_length=128, num_layers=layers,
                      embed_dim=embed, num_heads=8)
    mm = TPUMachineModel.calibrated(num_devices=8)
    mem = memory_per_device(m, machine_model=mm)
    peak, cap = mem["peak_bytes"], mem["capacity_bytes"]
    bits.append(f"predicted peak (default transformer, 8 devices): "
                f"{peak / 2**20:.0f} MiB of {cap / 2**30:.0f} GiB HBM "
                f"({100.0 * (cap - peak) / cap:.1f}% headroom, "
                f"dominant {mem['dominant_term']})")

    scfg = ServeConfig.from_env()
    # per-position KV state of the headroom model: K+V, all layers
    kv_bytes_per_block = scfg.kv_block * 2 * embed * layers * 4
    kv_budget = scfg.kv_blocks_resolved() * kv_bytes_per_block
    if kv_budget + peak > cap:
        bits.append(f"WARN: serving KV budget "
                    f"({scfg.kv_blocks_resolved()} blocks ~ "
                    f"{kv_budget / 2**30:.1f} GiB) + model state "
                    f"({peak / 2**30:.1f} GiB) exceeds HBM capacity "
                    f"({cap / 2**30:.0f} GiB) — expect serving OOM at "
                    f"full load")
    else:
        bits.append(f"serving KV budget fits: "
                    f"{scfg.kv_blocks_resolved()} blocks ~ "
                    f"{kv_budget / 2**20:.0f} MiB on top of model state")
    return ", ".join(bits)


def _resilience():
    # Effective chaos/recovery env as chaos.py/resilience.py will see
    # it.  An invalid FF_CHAOS spec fails HERE (required-style error in
    # the detail) instead of silently injecting nothing at train time;
    # the checkpoint dir gets a writability probe — a read-only dir
    # otherwise fails at the first save, hours into the run.
    from ..runtime import resilience
    from ..testing import chaos

    spec = os.environ.get("FF_CHAOS", "")
    bits = []
    if spec:
        # raises ValueError on a bad spec -> the check reports it
        bits.append(f"FF_CHAOS={chaos.ChaosMonkey(spec).describe()}, "
                    f"seed={os.environ.get('FF_CHAOS_SEED', '0')}")
    else:
        bits.append("FF_CHAOS=off")
    nf = resilience.nonfinite_limit()
    bits.append(f"FF_SKIP_NONFINITE={nf if nf else 'off'}")
    bits.append(f"FF_CKPT_RETRIES={resilience.ckpt_retries()}")
    ckpt_dir = os.environ.get("FF_CKPT_DIR", "")
    if ckpt_dir:
        d = os.path.abspath(ckpt_dir)
        probe = d if os.path.isdir(d) else (os.path.dirname(d) or ".")
        if not os.path.isdir(probe):
            raise FileNotFoundError(f"FF_CKPT_DIR parent missing: {probe}")
        if not os.access(probe, os.W_OK):
            raise PermissionError(f"FF_CKPT_DIR not writable: {d}")
        bits.append(f"FF_CKPT_DIR={d} (writable)")
    return ", ".join(bits)


def _reconfiguration():
    # Effective FF_RECONFIG_* env as reconfigure.py will see it — a
    # typo'd threshold fails HERE (ValueError in the detail) instead of
    # at the first divergence window, hours into a run.  When the
    # feature is armed, also probe the search engine the controller's
    # background thread will call: a tiny-budget seeded MCMC over the
    # doctor's toy graph, host-only, so a broken native/simulator stack
    # is a launch-time finding rather than a mid-swap reconfig_error.
    from ..runtime.reconfigure import ReconfigPolicy

    policy = ReconfigPolicy.from_env()  # ValueError on a bad knob
    if policy is None:
        return "FF_RECONFIGURE=off"
    bits = [f"FF_RECONFIGURE=on, {policy.describe()}"]
    import flexflow_tpu as ff
    from ..simulator.search import mcmc_search

    cfg = ff.FFConfig(batch_size=16)
    m = ff.FFModel(cfg)
    t = m.create_tensor((16, 8), nchw=False, name="x")
    t = m.dense(t, 16, name="fc1")
    m.softmax(t, name="sm")
    m.compile(ff.SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy",
              ["accuracy"])
    res = mcmc_search(m, num_devices=4, budget=4, seed=0, verbose=False)
    bits.append(f"search probe: best {res.best_s * 1e3:.3f} ms "
                f"(budget 4, 4 devices)")
    return ", ".join(bits)


def _serving():
    # Effective FF_SERVE_* env as serving/config.py will see it (a bad
    # value raises here, not at server startup), plus a bind probe of
    # the configured HTTP endpoint — a port already taken or a host
    # that doesn't resolve otherwise fails only when traffic arrives.
    import socket

    from ..serving.config import ServeConfig

    cfg = ServeConfig.from_env()  # ValueError on a typo'd env var
    # (this parses + range-checks every replica-pool knob too:
    # FF_SERVE_REPLICAS/MAX_QUEUE/SHED_WAIT_S/REPLICA_TIMEOUT/HEDGE_MS/
    # RESTART_BACKOFF_S/RESTART_CAP_S)
    bits = [cfg.describe()]
    if cfg.hedge_ms and cfg.replicas < 2:
        bits.append("WARN: FF_SERVE_HEDGE_MS set but FF_SERVE_REPLICAS<2 "
                    "— hedging needs a second replica (inert)")
    if cfg.restart_backoff_s > cfg.restart_cap_s > 0:
        bits.append("WARN: FF_SERVE_RESTART_BACKOFF_S exceeds "
                    "FF_SERVE_RESTART_CAP_S (every restart waits the cap)")
    if cfg.paged != "off":
        # FF_SERVE_PAGED/KV_BLOCK/KV_BLOCKS: geometry problems surface
        # here, not as a silent dense fallback at server start
        if cfg.max_seq % cfg.kv_block:
            bits.append(
                f"ERROR: FF_SERVE_KV_BLOCK={cfg.kv_block} does not divide "
                f"max_seq={cfg.max_seq} — paged KV falls back to dense "
                f"(FF_SERVE_PAGED=on would refuse to start)")
        else:
            worst = cfg.max_batch * cfg.blocks_per_seq()
            bits.append(f"paged kv: block={cfg.kv_block} budget="
                        f"{cfg.kv_blocks_resolved()} blocks "
                        + ("(FF_SERVE_KV_BLOCKS)" if cfg.kv_blocks
                           else "(dense worst case)"))
            if cfg.kv_blocks_resolved() < worst:
                bits.append(
                    f"WARN: FF_SERVE_KV_BLOCKS={cfg.kv_blocks} cannot hold "
                    f"max_batch={cfg.max_batch} worst-case sequences "
                    f"(need {worst}) — expect admission sheds at full load")
    probe_port = cfg.port if os.environ.get("FF_SERVE_PORT") else 0
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((cfg.host, probe_port))
        bound = s.getsockname()[1]
        bits.append(f"bind {cfg.host}:{bound} ok"
                    + ("" if probe_port else " (ephemeral probe)"))
    finally:
        s.close()
    return ", ".join(bits)


def _autoscaler():
    # Effective FF_SCALE_* env as serving/autoscaler.py will see it (a
    # typo'd knob raises HERE, not when the scaler thread starts), plus
    # the fleet-shape cross-checks: zones without the headroom to
    # rebuild one, or a scaler flying blind without telemetry.
    from ..serving.autoscaler import ScaleConfig
    from ..serving.config import ServeConfig

    cfg = ScaleConfig.from_env()   # ValueError on a typo'd env var
    bits = [cfg.describe()]
    if not cfg.enabled:
        bits.append("pool size is static")
        return ", ".join(bits)
    serve = ServeConfig.from_env()
    if serve.zones and cfg.max_replicas < 2 * len(serve.zones):
        bits.append(
            f"WARN: FF_SCALE_MAX={cfg.max_replicas} < 2x "
            f"{len(serve.zones)} zones — after a zone outage the "
            f"survivors cannot rebuild full redundancy")
    if not os.environ.get("FF_TELEMETRY") \
            and not os.environ.get("FF_METRICS_PORT"):
        bits.append(
            "WARN: autoscaler enabled without FF_TELEMETRY or "
            "FF_METRICS_PORT — scale decisions and burn-rate inputs "
            "will be invisible")
    return ", ".join(bits)


def _search():
    # Effective FF_SEARCH_* env as simulator/population.py will see it —
    # a typo'd knob fails HERE (ValueError in the detail) instead of at
    # the first population_search call — plus a learned-tier corpus
    # probe: the tier is requested (or on by engine default) but no op
    # family clears the fit threshold, so searches silently price
    # everything analytically.
    from ..simulator.cost_model import LEARNED_MIN_POINTS, LearnedCostTier
    from ..simulator.machine import TPUMachineModel
    from ..simulator.population import PopulationKnobs

    knobs = PopulationKnobs.from_env()  # ValueError on a bad knob
    ladder = (",".join(f"{m:g}" for m in knobs.ladder) if knobs.ladder
              else f"ratio {knobs.ladder_ratio:g}")
    bits = [f"FF_SEARCH_POPULATION={knobs.population}",
            f"ladder {ladder}",
            f"exchange every {knobs.exchange_every or 'off'}",
            f"crossover every {knobs.crossover_every or 'off'}",
            "FF_SEARCH_LEARNED=" + ("auto (population only)"
                                    if knobs.learned is None
                                    else "on" if knobs.learned else "off")]
    if knobs.learned is not False:
        tier = LearnedCostTier.fit_default(
            TPUMachineModel.calibrated(num_devices=8))
        prov = tier.provenance
        if not prov["used_families"]:
            bits.append(f"WARN: learned tier "
                        f"{'forced on' if knobs.learned else 'enabled'} but "
                        f"no family clears it (corpus "
                        f"{prov['corpus_points']} points, need "
                        f"{LEARNED_MIN_POINTS}/family AND a CV win) — "
                        f"searches price analytically")
        else:
            bits.append(f"learned tier: "
                        f"{', '.join(prov['used_families'])} win CV "
                        f"(corpus {prov['corpus_points']} points)")
    return ", ".join(bits)


def _perf():
    # How much of the cost model is grounded in real measurements.
    import json as _json

    from ..simulator import cost_model as cm
    from .report_configs import CALIBRATION_TARGET_ENTRIES

    fams = {}
    n_measured = 0
    try:
        with open(cm.MEASURED_CACHE) as f:
            for k, v in _json.load(f).items():
                if (isinstance(v, dict) and v.get("measured")
                        and v.get("platform", "tpu") == "tpu"):
                    n_measured += 1
                    fams[k.split(":", 1)[0]] = fams.get(
                        k.split(":", 1)[0], 0) + 1
    except (OSError, ValueError):
        pass
    if not n_measured:
        return "measured cache: EMPTY — every op costs analytically"
    by_fam = ", ".join(f"{k}:{fams[k]}"
                       for k in sorted(fams, key=fams.get, reverse=True))
    cov = n_measured / CALIBRATION_TARGET_ENTRIES
    return (f"measured cache: {n_measured} tpu entries "
            f"({by_fam}; {cov:.0%} of the "
            f"{CALIBRATION_TARGET_ENTRIES}-entry target — "
            "the rest costs analytically)")


def _placement():
    # Shipped strategies against the pod-shaped mesh layout for their
    # recorded device count (2+ hosts at 8 chips/host): a WARN whenever
    # one would put a non-sample dim on the ``dcn`` axis
    # (docs/lowering.md) — the placement the search's DCN surcharge
    # exists to prevent.
    from ..parallel.strategy import (DEFAULT_STRATEGY_DIR,
                                     load_strategies_from_file,
                                     read_provenance)
    from ..simulator.machine import TPUMachineModel

    warns = []
    if os.path.isdir(DEFAULT_STRATEGY_DIR):
        for fn in sorted(os.listdir(DEFAULT_STRATEGY_DIR)):
            if not fn.endswith(".pb"):
                continue
            path = os.path.join(DEFAULT_STRATEGY_DIR, fn)
            try:
                nd = int((read_provenance(path) or {}).get("num_devices", 0))
                strategies = load_strategies_from_file(path)
            except Exception:
                continue
            if nd <= 0:
                continue
            mm = TPUMachineModel(num_devices=nd)
            spilled = [op for op, pc in sorted(strategies.items())
                       if mm.dcn_spill(pc.dims)]
            if spilled:
                warns.append(f"{fn}: {', '.join(spilled)}")
    if warns:
        return ("WARN: non-sample dims would land on the dcn axis (a pod "
                "run reshards these over DCN every step): "
                + "; ".join(warns))
    return "shipped strategies: no non-sample dcn placement"


def _train():
    import numpy as np

    import flexflow_tpu as ff

    cfg = ff.FFConfig(batch_size=16)
    m = ff.FFModel(cfg)
    inp = m.create_tensor((16, 8), nchw=False, name="x")
    t = m.dense(inp, 32, activation="relu", name="fc1")
    t = m.dense(t, 4, name="fc2")
    m.softmax(t, name="sm")
    m.compile(ff.SGDOptimizer(lr=0.5), "sparse_categorical_crossentropy",
              ["accuracy"])
    m.init_layers(seed=0)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 8), dtype=np.float32)
    y = np.argmax(x[:, :4], 1).astype(np.int32)[:, None]
    losses = []
    for _ in range(20):
        m.set_batch({inp: x}, y)
        m.train_iteration()
        m.sync()
        m.get_metrics()
        losses.append(m.last_loss)
        m.reset_metrics()
    assert losses[-1] < losses[0] * 0.5, f"loss did not drop: {losses}"
    return f"loss {losses[0]:.3f} -> {losses[-1]:.3f} in 20 steps"


def main(argv: Optional[List[str]] = None) -> int:
    argparse.ArgumentParser(description=__doc__).parse_args(argv)

    plan = [("versions", _versions, True),
            ("accelerator", _accelerator, True),
            ("native libs", _native_libs, False),
             ("optional deps", _optional_deps, False),
             ("observability", _observability, False),
             ("metrics", _metrics, False),
             ("tracing", _tracing, False),
             ("memory", _memory, False),
             ("perf", _perf, False),
             ("search", _search, False),
             ("resilience", _resilience, False),
             ("reconfiguration", _reconfiguration, False),
             ("serving", _serving, False),
             ("autoscaler", _autoscaler, False),
             ("placement", _placement, False),
             ("training", _train, True)]

    # print each line as its check completes — the slow checks (the
    # training loop) must show live progress
    width = max(len(n) for n, _, _ in plan)
    failed = False
    for name, fn, required in plan:
        _, status, detail = _check(name, fn, required)
        print(f"[{status:<4}] {name:<{width}}  {detail}", flush=True)
        failed |= status == "FAIL"
    print("doctor:", "FAIL" if failed else "all required checks passed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
