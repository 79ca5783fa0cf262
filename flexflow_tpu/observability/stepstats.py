"""Per-step training instrumentation.

Records, per ``update()``:

  * the "step" span: the host's time in the call, which with
    asynchronous dispatch is the time to ENQUEUE the step, not the
    device's time to run it (the profiler's ``ff.update`` is the same
    interval).  No rate is worked out of it,
  * first-step wall time separately (jit trace + XLA compile happen
    inside step 0 — the reference's epoch-0 Legion trace capture),
  * estimated per-step collective bytes from each op's RESOLVED
    ``ParallelConfig`` (gradient all-reduce of replicated weights over
    the batch axis + activation redistribution for non-batch splits),
  * device memory stats when the backend reports them (TPU HBM
    ``bytes_in_use`` / ``peak_bytes_in_use``; CPU reports none).

and, per drain of the metrics (``get_metrics()``), over the interval
since the drain before it — a drain reads from the device, so it is a
sync point, and steps since the last drain x batch over the time since
it is all the work over all the time:

  * samples/s and samples/s/chip,
  * analytic-FLOP MFU, on a TPU only: train FLOPs estimated as 3x the
    graph's forward FLOPs (fwd + dgrad + wgrad — the same accounting
    the reference's backward multiplier uses) against the
    published peak of the chip the step ran on
    (``simulator/machine.py`` ``DEVICE_PEAKS``, keyed by
    ``device_kind``).  On any other platform the gauge is absent.

Everything here is reached ONLY through a non-None EventLog resolved at
``compile()`` — with telemetry off this module is never imported.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from .events import EventLog
from .health import write_heartbeat
from .reqtrace import run_trace_id

# Memory gauges are cheap but chatty; sample every N steps.
MEM_GAUGE_EVERY = 8


def estimate_collective_bytes(model) -> int:
    """Rough per-step collective traffic implied by the resolved per-op
    strategies.  Two terms, both analytic:

      * gradient synchronization: weights replicated across a batch
        degree d psum their grads — ring all-reduce moves
        ``2 (d-1)/d * bytes`` per weight (f32 grads),
      * activation redistribution: an output split on a non-batch dim
        with degree d costs ~``(d-1)/d`` of the output's bytes at the
        consumer boundary (allgather/reduce-scatter inserted by GSPMD).

    Halo exchanges and resharding between mismatched consecutive
    configs are NOT modeled — the simulator prices those; this is the
    one-number health gauge.
    """
    dt_bytes = 2 if "16" in model.config.compute_dtype else 4
    total = 0.0
    for op in model.ops:
        pc = getattr(op, "pc", None)
        if pc is None or pc.host_placed:
            continue
        d0 = pc.dims[0]
        if d0 > 1 and op.weights:
            wbytes = sum(float(np.prod(w.dims)) for w in op.weights) * 4.0
            total += 2.0 * (d0 - 1) / d0 * wbytes
        obytes = float(np.prod(op.output.dims)) * dt_bytes
        for d in pc.dims[1:]:
            if d > 1:
                total += (d - 1) / d * obytes
    return int(total)


# allocator-stat keys sampled per device, with the short ``kind`` label
# they export under on /metrics (``ff_hbm_bytes{device,kind}``)
MEM_STAT_KINDS = (("bytes_in_use", "in_use"),
                  ("peak_bytes_in_use", "peak"),
                  ("bytes_limit", "limit"))


def device_memory_stats() -> Optional[list]:
    """Per-device allocator stats across ALL local devices: a list of
    ``{"device": i, "bytes_in_use": ..., "peak_bytes_in_use": ...,
    "bytes_limit": ...}`` rows (keys present when the backend reports
    them).  Devices whose ``memory_stats()`` returns None or raises
    mid-list are skipped — some backends report stats for a subset.
    None when NO device reports (CPU)."""
    try:
        import jax

        devs = jax.local_devices()
    except Exception:
        return None
    out = []
    for i, d in enumerate(devs):
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        rec = {"device": i}
        for k, _ in MEM_STAT_KINDS:
            if k in ms:
                rec[k] = int(ms[k])
        if len(rec) > 1:
            out.append(rec)
    return out or None


class StepStats:
    """Times ``update()`` calls and folds the numbers into the event
    log.  One instance per model, created at ``compile()`` when
    telemetry is on."""

    def __init__(self, model, log: EventLog):
        self.model = model
        self.log = log
        # run-level trace id: step spans join the same timeline as the
        # serving plane's request traces (derived from run_id — stable,
        # zero per-step state)
        self.trace_id = run_trace_id(log.run_id)
        self.steps = 0
        # the last drain: the log's clock when it read the device, and
        # the steps taken by then (None until the first drain)
        self._drain_at: Optional[float] = None
        self._drain_steps = 0
        self._fwd_flops_per_sample: Optional[float] = None
        self._peak_flops: Optional[float] = None
        self._collective_bytes: Optional[int] = None

    # -- lazy statics (graph + machine are fixed after compile) ---------
    def _statics(self):
        if self._fwd_flops_per_sample is None:
            self._fwd_flops_per_sample = float(
                sum(op.flops_per_sample() for op in self.model.ops))
            from ..simulator.machine import device_peak_flops

            # None off-TPU: a CPU step has no chip peak to be a share of
            dev = self.model.machine.devices[0]
            self._peak_flops = (device_peak_flops(dev.device_kind)
                                if dev.platform == "tpu" else None)
            self._collective_bytes = estimate_collective_bytes(self.model)
        return self._fwd_flops_per_sample, self._peak_flops

    def timed_update(self, fn) -> None:
        """Run one training step under a "step" span: the enqueue."""
        log = self.log
        first = self.steps == 0
        step_idx = self.model._step_count
        # Heartbeat BEFORE dispatch: a wedged step leaves "step" (with
        # its index) on disk for the external watchdog to name.
        write_heartbeat("step", step=step_idx)
        t0 = time.perf_counter()
        fn()
        dur = time.perf_counter() - t0
        self.steps += 1

        bs = self.model.config.batch_size
        log.span_at("step", t0, dur, step=step_idx, first=first,
                    trace_id=self.trace_id, batch_size=bs)
        log.counter("samples", float(bs))
        if first:
            self._statics()
            # step 0 wall includes jit trace + XLA compile
            log.gauge("first_step_wall_s", round(dur, 6))
            log.gauge("est_collective_bytes_per_step",
                      float(self._collective_bytes))
        if first or self.steps % MEM_GAUGE_EVERY == 0:
            mems = device_memory_stats()
            if mems:
                for rec in mems:
                    dev = str(rec["device"])
                    for k, kind in MEM_STAT_KINDS:
                        if k in rec:
                            log.gauge("hbm_bytes", float(rec[k]),
                                      device=dev, kind=kind)
                # legacy single-device series (trace_report's summary
                # line and older dashboards key on these)
                for k in ("bytes_in_use", "peak_bytes_in_use"):
                    if k in mems[0]:
                        log.gauge(f"device_{k}", float(mems[0][k]))
        log.flush()
        health = getattr(self.model, "_health", None)
        if health is not None:
            health.on_step(step_idx, log.to_rel(t0), dur, first)
        opprof = getattr(self.model, "_opprof", None)
        if opprof is not None:
            opprof.on_step(step_idx)

    def on_drain(self) -> None:
        """The drain of the metrics has just read the device: gauge the
        rate, and on a TPU the MFU, over the interval since the drain
        before.  The first drain only starts the clock (its interval
        holds the compilation)."""
        now = self.log.now()
        steps = self.steps - self._drain_steps
        last, self._drain_at, self._drain_steps = \
            self._drain_at, now, self.steps
        if last is None or steps <= 0 or now <= last:
            return
        fwd_fps, peak = self._statics()
        nd = self.model.machine.num_devices if self.model.machine else 1
        sps = steps * self.model.config.batch_size / (now - last)
        self.log.gauge("samples_per_sec", round(sps, 2))
        self.log.gauge("samples_per_sec_per_chip", round(sps / nd, 2))
        if peak:
            # fwd + dgrad + wgrad ~= 3x forward (reference accounting)
            self.log.gauge("mfu", round(3.0 * fwd_fps * sps / (nd * peak), 6))
