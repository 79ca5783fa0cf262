"""Benchmark driver: AlexNet (+ extras) training throughput and MFU on
the attached TPU.

One process, one chip owner: the backend is initialised here, once, and
nothing is started that would need the chip as well.  A run that finds
no TPU fails — it prints one parseable error line and exits non-zero; no
number taken on another platform is ever printed under these metrics'
names.

The primary JSON line is printed and flushed THE MOMENT the AlexNet
measurement completes — before any other phase runs — so a later hang or
a kill at the caller's time limit cannot take the number with it.  A
watchdog *thread* (not SIGALRM — Python signal handlers can't fire while
the main thread is blocked inside a C++ device wait) enforces a deadline
per phase and a global wall budget via ``os._exit``.

Output protocol:
  - stdout line 1 (immediate): primary metric, with AlexNet MFU as a
    top-level headline companion (``mfu``) and the device it ran on.
  - stdout line 2 (after the extra phases): the SAME metric/value
    re-printed enriched with all extras — whichever line a tail-parser
    picks, the headline number is identical.  A phase that raised is
    recorded under its name and makes the exit code non-zero.
  - on a watchdog kill the LAST stdout line is still a complete,
    parseable JSON record (the primary re-flushed whole, or the error
    line when no primary exists yet) and the exit code is non-zero.
  - ``BENCH_EXTRA.json`` side file (``FF_BENCH_EXTRA_PATH``): rewritten
    after every phase, so partial extras survive any kill.
Every emitted result is also appended to the program's own perf log
(tools/perf_ledger.py).

Primary metric: AlexNet samples/s/chip against the 375 samples/s/chip
parity bar.  Baseline derivation (BASELINE.md): the reference repo
records no numbers; the driver-defined target is "v5e-16 >= 4x V100 +
NCCL".  A V100 trains reference-config AlexNet (bs 64/gpu, 3x229x229,
f32, cuDNN) at ~1.5k samples/s, so 4xV100 ~= 6k samples/s and the
per-chip parity bar on a 16-chip pod is 6000/16 = 375 samples/s/chip.
That bar saturates early, so the number that carries information is the
MFU: achieved train FLOP/s (3x forward — dgrad + wgrad ~= 2x fwd, the
reference's own backward accounting) over the published bf16 peak of the
chip the run was on (simulator/machine.py ``DEVICE_PEAKS``, keyed by
``device_kind``; an unknown kind is an error).
"""

import json
import os
import sys
import threading
import time

# the repo root by absolute path, not "." — bench must import its own
# package no matter what cwd the driver launches it from
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

PER_CHIP_BASELINE = 375.0  # samples/s/chip parity bar (see docstring)


_tool_mods = {}


def _load_tool(name):
    """Load a stdlib-only flexflow_tpu/tools/ module by file path.
    Importing the package would execute its __init__ (jax + the whole
    framework) at an uncontrolled moment, outside the phase budgets and
    the watchdog's error reporting."""
    if name not in _tool_mods:
        import importlib.util

        p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "flexflow_tpu", "tools", name + ".py")
        spec = importlib.util.spec_from_file_location("_ff_" + name, p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _tool_mods[name] = mod
    return _tool_mods[name]


# Single source with calibrate/soap_report (the agreement check converts
# this phase's samples/s to ms/step with the SAME batch).
BENCH_SINGLE_CHIP_BATCH = int(
    _load_tool("report_configs").BENCH_SINGLE_CHIP_BATCH)
TRANSFORMER_SEQ = 512      # bench transformer sequence length
TRANSFORMER_VOCAB = 32000

GLOBAL_BUDGET = 1080.0     # total wall seconds
PHASE_BUDGETS = {          # per-phase wall seconds (incl. compile)
    "preflight": 150.0,    # backend init + one tiny matmul: a chip that
                           # does not answer fails the run HERE, not
                           # after eating the alexnet budget
    "alexnet": 480.0,
    "inception_v3": 240.0,
    "transformer": 240.0,
    "decode": 180.0,
    "fused_optimizer": 150.0,
    "dlrm_host_embed": 150.0,
}

_t_start = time.monotonic()
_state = {
    "deadline": _t_start + PHASE_BUDGETS["preflight"],
    "phase": "preflight",
    "primary_printed": False,
    "primary_line": None,     # the emitted primary dict, for re-flush
    "device": None,           # {"platform", "kind", "count"} once known
    "peak_flops": None,       # published bf16 peak of that device kind
    "extra": {},
}
_lock = threading.Lock()


def _emit_primary(sps, extra, error=None, mfu=None, fresh_line=False):
    # ``mfu`` is the headline companion; ``vs_baseline`` keeps the 375
    # samples/s/chip parity bar for continuity (see docstring).  A line
    # without a measurement carries ``"value": null`` and an ``error``,
    # never a zero that could be read as one.
    line = {
        "metric": "alexnet_train_samples_per_sec_per_chip",
        "value": round(sps, 2) if sps else None,
        "unit": "samples/s/chip",
        "device": _state["device"],
    }
    if sps:
        line["mfu"] = round(mfu, 4)
        line["vs_baseline"] = round(sps / PER_CHIP_BASELINE, 3)
    line["extra"] = extra
    if error:
        line["error"] = error
    out = json.dumps(line)
    # fresh_line: the watchdog fires while the main thread may be mid-
    # print — a leading newline guarantees THIS record starts at column
    # 0 and stays parseable even glued after a half-written line.
    print(("\n" + out) if fresh_line else out, flush=True)
    return line


def _write_side_file():
    try:
        with open(os.environ.get("FF_BENCH_EXTRA_PATH", "BENCH_EXTRA.json"),
                  "w") as f:
            json.dump(_state["extra"], f, indent=1)
            f.flush()
            os.fsync(f.fileno())
    except OSError as e:
        print(f"bench: side file not written: {e}", file=sys.stderr)


def _ledger_append(line, status="ok"):
    """One perf-log entry per emitted result — measured, failed or
    killed.  Log I/O must never kill a bench (the watchdog calls this
    on its way to ``os._exit``)."""
    try:
        dev = line.get("device") or {}
        entry = {"kind": "bench",
                 "metric": line.get("metric"),
                 "value": line.get("value") or 0.0,
                 "unit": line.get("unit"),
                 "mfu": line.get("mfu"),
                 "backend": dev.get("platform"),
                 "status": status}
        batch = ((line.get("extra") or {}).get("alexnet") or {}).get("batch")
        if batch:
            entry["batch"] = batch
        if dev:
            entry["provenance"] = {"device": dev.get("kind"),
                                   "device_count": dev.get("count")}
        if line.get("error"):
            entry["error"] = str(line["error"])[:300]
        _load_tool("perf_ledger").append_entry(entry)
    except Exception as e:  # noqa: BLE001 — see docstring
        print(f"bench: perf log not written: {type(e).__name__}: {e}",
              file=sys.stderr)


def _heartbeat_detail():
    """Where inside a phase a kill landed, from the FF_HEARTBEAT_PATH
    file (observability/health.py protocol): the framework rewrites it
    at every phase entry and step, so the kill message can say
    "phase 'step' (step 12, 95s stale)" instead of just the bench
    phase.  Returns None when unavailable — never raises."""
    try:
        from flexflow_tpu.observability import health

        return health.describe_heartbeat(health.read_heartbeat())
    except Exception:
        return None


def _watchdog_fire(why, where, exit_fn=os._exit):
    """Emit-then-exit, always non-zero.  Invariant: the LAST stdout line
    is ALWAYS a complete, parseable JSON result — before the primary
    exists the error line itself is that record; after, the primary is
    re-flushed WHOLE on a fresh line (the main thread may have been
    mid-print of the enriched line when the deadline hit).  Every kill
    also leaves a perf-log entry."""
    with _lock:
        if not _state["primary_printed"]:
            _state["extra"]["watchdog"] = f"killed in {where}"
            line = _emit_primary(None, _state["extra"], fresh_line=True,
                                 error=f"watchdog: {why} exceeded in {where}")
            _write_side_file()
            _ledger_append(line, status="killed")
            exit_fn(1)
            return
        # primary already on stdout: record what died, then re-flush the
        # primary whole so the tail line stays parseable
        _state["extra"]["watchdog"] = f"{why} exceeded during '{where}'"
        _write_side_file()
        line = dict(_state.get("primary_line") or {})
        line["watchdog"] = _state["extra"]["watchdog"]
        sys.stdout.write("\n" + json.dumps(line) + "\n")
        sys.stdout.flush()
        exit_fn(1)


def _watchdog():
    while True:
        time.sleep(2.0)
        now = time.monotonic()
        with _lock:
            over_phase = now > _state["deadline"]
            over_global = now > _t_start + GLOBAL_BUDGET
            if not (over_phase or over_global):
                continue
            why = ("global budget" if over_global else
                   f"phase '{_state['phase']}' budget")
            phase = _state["phase"]
        hb = _heartbeat_detail()
        _watchdog_fire(why, phase + (f" at {hb}" if hb else ""))


def _enter_phase(name):
    with _lock:
        _state["phase"] = name
        _state["deadline"] = time.monotonic() + PHASE_BUDGETS.get(name, 180.0)
    _telemetry_heartbeat(name)


def _telemetry_heartbeat(phase):
    """Phase heartbeat into the FF_TELEMETRY trace, so a watchdog kill
    names the stuck phase from the trace alone.  The events module is
    stdlib-only (no jax import before preflight) and the log is
    line-buffered, so the record survives the watchdog's os._exit.
    Never lets telemetry break the bench."""
    try:
        from flexflow_tpu.observability import events, health

        # heartbeat file too (independent of FF_TELEMETRY): the
        # watchdog's kill message names the last phase written here
        health.write_heartbeat(phase)
        log = events.active_log()
        if log is not None:
            log.event("bench_phase", phase=phase)
            log.flush()
    except Exception:
        pass


def _build(name, batch_size, compute_dtype, fused=False):
    import flexflow_tpu as ff

    cfg = ff.FFConfig(batch_size=batch_size, compute_dtype=compute_dtype,
                      fused_optimizer=fused)
    model = ff.FFModel(cfg)
    if name == "transformer":
        # GPT-small-ish block stack; sp=1 so attention runs the fused
        # Pallas flash kernel on-chip (kernels/flash_attention.py)
        from flexflow_tpu.models.transformer import (build_transformer,
                                                     synthetic_lm_batch)
        tok, pos, _ = build_transformer(model, batch_size,
                                        seq_length=TRANSFORMER_SEQ,
                                        num_layers=4, embed_dim=512,
                                        num_heads=8,
                                        vocab_size=TRANSFORMER_VOCAB)
        model.compile(ff.SGDOptimizer(model, lr=0.001),
                      ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                      [ff.MetricsType.ACCURACY])
        model.init_layers()
        toks, posa, labels = synthetic_lm_batch(batch_size, TRANSFORMER_SEQ,
                                                TRANSFORMER_VOCAB)
        model.set_batch({tok: toks, pos: posa}, labels)
        return model
    if name == "alexnet":
        from flexflow_tpu.models.alexnet import build_alexnet
        inp, _ = build_alexnet(model, batch_size)
    else:
        from flexflow_tpu.models.inception import build_inception_v3
        inp, _ = build_inception_v3(model, batch_size)
    model.compile(ff.SGDOptimizer(model, lr=0.001),
                  ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.ACCURACY])
    dl = ff.DataLoader.synthetic(model, inp, num_samples=batch_size)
    model.init_layers()
    dl.next_batch(model)
    return model


def _fwd_flops_per_sample(model):
    return sum(op.flops_per_sample() for op in model.ops)


def _build_warm(name, batch_size, compute_dtype, fused=False):
    """Build + compile + warmup: two steps — the first step's outputs
    carry committed shardings the initial arrays lacked, so step two
    triggers one more (final) compilation before the shapes/shardings
    fixpoint.  One definition for the bench loop, the sweep, and the
    profiler so they always measure the same configuration."""
    _telemetry_heartbeat("compile")
    model = _build(name, batch_size, compute_dtype, fused=fused)
    _telemetry_heartbeat("warmup")
    model.train_iteration()
    model.train_iteration()
    model.sync()
    return model


def run_one(name, batch_size=BENCH_SINGLE_CHIP_BATCH,
            compute_dtype="bfloat16", steps=24,
            fused=False):
    """(samples/s/chip, achieved TFLOPS, MFU) for one model's train loop."""
    import jax

    model = _build_warm(name, batch_size, compute_dtype, fused=fused)
    _telemetry_heartbeat("measure")
    t0 = time.perf_counter()
    for _ in range(steps):
        model.train_iteration()
    model.sync()
    dt = time.perf_counter() - t0
    sps = steps * batch_size / dt / len(jax.devices())
    train_flops = 3.0 * _fwd_flops_per_sample(model)  # fwd + dgrad + wgrad
    tflops = sps * train_flops / 1e12
    return sps, tflops, tflops * 1e12 / _state["peak_flops"]


def run_dlrm_host(batch_size=256, steps=8, tables=8, rows=1_000_000):
    """Reference-config DLRM (global batch 256 — on the single bench
    chip that is the reference's 256/GPU, run_random.sh:3-8 — with
    8x1M-row tables) and the tables host-resident via the ROW-SPARSE
    path: per step only the batch's unique rows cross the host link,
    not the 2 GB of tables (reference: embedding.cc CPU tasks +
    dlrm_strategy_hetero.cc)."""
    import flexflow_tpu as ff
    from flexflow_tpu.config import DeviceType
    from flexflow_tpu.models.dlrm import build_dlrm, synthetic_batch

    sizes = [rows] * tables
    cfg = ff.FFConfig(batch_size=batch_size, compute_dtype="bfloat16")
    for i in range(tables):
        cfg.strategies[f"embedding{i}"] = ff.ParallelConfig(
            DeviceType.CPU, (1, 1), (0,))
    model = ff.FFModel(cfg)
    sparse_in, dense_in, _ = build_dlrm(model, batch_size,
                                        embedding_sizes=sizes)
    model.compile(ff.SGDOptimizer(model, lr=0.01),
                  ff.LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
                  [ff.MetricsType.MEAN_SQUARED_ERROR])
    model.init_layers()
    n_sparse = len(model._host_embed)
    sparse, dense, labels = synthetic_batch(batch_size, sizes, 1, 64)
    inputs = {t: a for t, a in zip(sparse_in, sparse)}
    inputs[dense_in] = dense
    model.set_batch(inputs, labels)
    model.train_iteration()
    model.train_iteration()
    model.sync()
    t0 = time.perf_counter()
    for _ in range(steps):
        model.train_iteration()
    model.sync()
    dt = time.perf_counter() - t0
    # A/B the async scatter-back: serialize it with the step and
    # re-time — the delta is the overlap's measured win
    prior = os.environ.get("FF_HE_SYNC_SCATTER")
    os.environ["FF_HE_SYNC_SCATTER"] = "1"
    try:
        model.train_iteration()
        model.sync()
        t1 = time.perf_counter()
        for _ in range(steps):
            model.train_iteration()
        model.sync()
        dt_sync = time.perf_counter() - t1
    finally:
        if prior is None:
            os.environ.pop("FF_HE_SYNC_SCATTER", None)
        else:
            os.environ["FF_HE_SYNC_SCATTER"] = prior
    # per-step host<->device row traffic (both directions, f32 rows):
    # the wire carries the ADAPTIVE bucket (u_hwm), not the all-unique
    # worst case; report actual unique rows alongside
    infos = list(model._host_embed.values())
    u = sum(info.get("u_hwm", info["u_max"]) for info in infos)
    u_worst = sum(info["u_max"] for info in infos)
    n_steps = max([info.get("uniq_rows_steps", 0) for info in infos] + [1])
    uniq_avg = sum(info.get("uniq_rows_total", 0)
                   for info in infos) / n_steps
    return {"samples_per_sec": round(steps * batch_size / dt, 1),
            "samples_per_sec_sync_scatter": round(
                steps * batch_size / dt_sync, 1),
            "async_scatter_speedup": round(dt_sync / dt, 3),
            "tables_host_sparse": n_sparse,
            "table_bytes_total": int(sum(sizes) * 64 * 4),
            "row_traffic_bytes_per_step": int(u * 64 * 4 * 2),
            "row_traffic_bytes_worst_case": int(u_worst * 64 * 4 * 2),
            "unique_rows_per_step_actual": round(uniq_avg, 1)}


def sweep(out="BENCH_SWEEP.md"):
    """Batch-size x dtype sweep (manual mode: `python bench.py --sweep`).
    Writes the markdown table the single-number bench can't carry."""
    import jax

    lines = [f"# Throughput sweep — {jax.devices()[0].device_kind}",
             "",
             "| model | dtype | batch/chip | samples/s/chip | MFU |",
             "|---|---|---|---|---|"]
    for name in ("alexnet", "inception_v3"):
        for dtype in ("bfloat16", "float32"):
            for bs in (64, 128, 256, 512):
                if name == "inception_v3" and bs > 128:
                    continue  # HBM headroom
                try:
                    sps, _, mfu = run_one(name, batch_size=bs,
                                          compute_dtype=dtype, steps=8)
                    lines.append(f"| {name} | {dtype} | {bs} | "
                                 f"{sps:.0f} | {mfu:.3f} |")
                except Exception as e:
                    lines.append(f"| {name} | {dtype} | {bs} | "
                                 f"error: {type(e).__name__} | |")
                print(lines[-1], flush=True)
                with open(out, "w") as f:  # survive a mid-sweep kill
                    f.write("\n".join(lines) + "\n")
    print(f"-> {out}")


def _phase_inception_v3():
    sps, tf, mfu = run_one("inception_v3", batch_size=128, steps=12)
    return {"samples_per_sec_per_chip": round(sps, 2),
            "achieved_tflops": round(tf, 1), "mfu": round(mfu, 3)}


def _phase_transformer():
    # decoder transformer: MXU-dense matmuls + the Pallas flash-attention
    # kernel (tokens/s = samples/s * seq 512)
    sps, tf, mfu = run_one("transformer", batch_size=16, steps=12)
    return {"tokens_per_sec_per_chip": round(sps * TRANSFORMER_SEQ, 1),
            "achieved_tflops": round(tf, 1), "mfu": round(mfu, 3)}


def _phase_decode():
    # kv-cached decode throughput on-chip: one jitted scan.  A 1-token
    # prompt makes every timed step a decode step, so tokens/s is the
    # pure per-token rate (no prefill share).
    import numpy as np

    model = _build("transformer", 16, "bfloat16")
    prompt = np.random.default_rng(0).integers(
        0, TRANSFORMER_VOCAB, size=(16, 1)).astype(np.int32)
    model.generate(prompt, 64)      # compile + warmup
    t0 = time.perf_counter()
    model.generate(prompt, 64)
    dt = time.perf_counter() - t0
    return {"tokens_per_sec": round(16 * 64 / dt, 1),
            "batch": 16, "new_tokens": 64}


def _phase_fused_optimizer():
    sps, _, _ = run_one("alexnet", steps=8, fused=True,
                        batch_size=BENCH_SINGLE_CHIP_BATCH)
    return {"samples_per_sec_per_chip": round(sps, 2)}


EXTRA_PHASES = (("inception_v3", _phase_inception_v3),
                ("transformer", _phase_transformer),
                ("decode", _phase_decode),
                ("fused_optimizer", _phase_fused_optimizer),
                ("dlrm_host_embed", run_dlrm_host))


def _extra_phases(extra):
    """Run every non-primary phase under its own deadline.  A phase that
    raises is recorded under its name and the others still run; returns
    the names that failed, which make the exit code non-zero."""
    import traceback

    failed = []
    for name, fn in EXTRA_PHASES:
        _enter_phase(name)
        try:
            extra[name] = fn()
        except Exception as e:  # noqa: BLE001 — boundary: record, go on
            traceback.print_exc()
            extra[name] = {"error": f"{type(e).__name__}: {e}"}
            failed.append(name)
        _write_side_file()
    return failed


def profile(out="chiprun_out/alexnet_trace"):
    """Capture an XLA profiler trace of the timed AlexNet loop (manual
    mode: `python bench.py --profile [logdir]`) — the input to the
    measured-optimization work: kernel timeline, HBM traffic, fusion
    boundaries (view with TensorBoard or xprof)."""
    from flexflow_tpu.runtime.profiling import trace

    model = _build_warm("alexnet", BENCH_SINGLE_CHIP_BATCH, "bfloat16")
    with trace(out):
        for _ in range(8):
            model.train_iteration()
        model.sync()
    print(f"-> trace in {out} (tensorboard --logdir {out})")


def lowered_ab(name="alexnet", steps=8):
    """A/B the whole-graph lowering (manual mode: `python bench.py
    --lowered [model]`): the SAME model + strategy timed under per-op
    dispatch (FF_LOWERED=0) and the ONE pjit'd lowered step
    (FF_LOWERED=1, parallel/lowering.py).  Appends the ratio to the
    perf log as ``lowering_speedup``."""
    batch = BENCH_SINGLE_CHIP_BATCH
    prior = os.environ.get("FF_LOWERED")
    res = {}
    try:
        for label, knob in (("dispatch", "0"), ("lowered", "1")):
            os.environ["FF_LOWERED"] = knob
            model = _build_warm(name, batch, "bfloat16")
            assert (model._lowering is not None) == (knob == "1"), \
                "FF_LOWERED knob did not take"
            t0 = time.perf_counter()
            for _ in range(steps):
                model.train_iteration()
            model.sync()
            dt = time.perf_counter() - t0
            res[label] = steps * batch / dt
    finally:
        if prior is None:
            os.environ.pop("FF_LOWERED", None)
        else:
            os.environ["FF_LOWERED"] = prior
    speedup = res["lowered"] / res["dispatch"]
    line = {"metric": "lowering_speedup", "value": round(speedup, 4),
            "unit": "x", "device": _state["device"],
            "model": name, "batch": batch, "steps": steps,
            "samples_per_sec_dispatch": round(res["dispatch"], 2),
            "samples_per_sec_lowered": round(res["lowered"], 2)}
    print(json.dumps(line), flush=True)
    _ledger_append(line)
    return line


def _flag_path(flag, default):
    """Optional path operand after ``flag``: only consume the next argv
    token when it isn't itself a flag (``--sweep --profile`` must not
    write a file literally named ``--profile``)."""
    idx = sys.argv.index(flag)
    nxt = sys.argv[idx + 1] if len(sys.argv) > idx + 1 else None
    return nxt if nxt and not nxt.startswith("-") else default


def _preflight():
    """Backend init, the TPU requirement, one tiny matmul and the
    compile cache — every mode runs this first, so nothing in this file
    measures on another platform.  Fills ``_state["device"]`` and
    ``_state["peak_flops"]``; raises RuntimeError without a TPU and
    ValueError for a device kind with no published peak."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.simulator.machine import device_peak_flops
    from flexflow_tpu.utils.compile_cache import enable_compile_cache

    t0 = time.monotonic()
    dev = jax.devices()[0]
    _state["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found platform={dev.platform} "
                           f"({dev.device_kind}); nothing was measured")
    _state["peak_flops"] = device_peak_flops(dev.device_kind)
    enable_compile_cache()
    jax.block_until_ready(jnp.ones((256, 256)) @ jnp.ones((256, 256)))
    _state["extra"]["preflight"] = {
        "backend_init_s": round(time.monotonic() - t0, 1)}


def main():
    # Heartbeat file for phase-level attribution of a kill (the framework
    # rewrites it at every phase entry / step; the watchdog reads it).
    os.environ.setdefault("FF_HEARTBEAT_PATH", "BENCH_HEARTBEAT.json")
    threading.Thread(target=_watchdog, daemon=True).start()
    # initial phase is set at module load, not via _enter_phase — emit
    # its heartbeat here (stdlib-only module: safe before jax init)
    _telemetry_heartbeat("preflight")
    # Live /metrics exporter (no-op unless FF_METRICS_PORT; stdlib-only
    # module, safe pre-jax).  A bad knob value is loud; a busy port only
    # costs the exporter, never the bench.
    try:
        from flexflow_tpu.observability import metrics as _ff_metrics

        _ff_metrics.maybe_start()
    except OSError as e:
        print(f"bench: metrics exporter unavailable: {e}", file=sys.stderr)
    extra = _state["extra"]

    # ---- preflight: a run that finds no chip fails here ----
    try:
        _preflight()
    except Exception as e:  # noqa: BLE001 — boundary: emit the line, exit
        line = _emit_primary(None, extra,
                             error=f"preflight: {type(e).__name__}: {e}")
        _write_side_file()
        _ledger_append(line, status="error")
        sys.exit(1)

    if "--sweep" in sys.argv:
        sweep(_flag_path("--sweep", "BENCH_SWEEP.md"))
        return
    if "--profile" in sys.argv:
        profile(_flag_path("--profile", "chiprun_out/alexnet_trace"))
        return
    if "--lowered" in sys.argv:
        lowered_ab(_flag_path("--lowered", "alexnet"))
        return

    # ---- primary phase: nothing runs before this number is on stdout ----
    _enter_phase("alexnet")
    try:
        sps_a, tf_a, mfu_a = run_one("alexnet",
                                     batch_size=BENCH_SINGLE_CHIP_BATCH)
    except Exception as e:
        line = _emit_primary(None, extra, error=f"{type(e).__name__}: {e}")
        _write_side_file()
        _ledger_append(line, status="error")
        raise
    extra["alexnet"] = {"samples_per_sec_per_chip": round(sps_a, 2),
                        "achieved_tflops": round(tf_a, 1),
                        "mfu": round(mfu_a, 3),
                        # recorded so the agreement check converts
                        # samples/s -> ms/step with the batch this run
                        # ACTUALLY used
                        "batch": BENCH_SINGLE_CHIP_BATCH}
    with _lock:
        line = _emit_primary(sps_a, {"alexnet": extra["alexnet"]}, mfu=mfu_a)
        _state["primary_printed"] = True
        _state["primary_line"] = line
    _write_side_file()
    _ledger_append(line)

    # ---- extras: each under its own deadline ----
    failed = _extra_phases(extra)

    # Re-print the SAME headline number enriched with all extras (a tail
    # parser picking either line sees the identical metric/value).
    with _lock:
        _state["primary_line"] = _emit_primary(sps_a, extra, mfu=mfu_a)
    if failed:
        sys.exit(f"bench: phase(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
