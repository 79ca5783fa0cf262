"""Simulator-agreement attribution: predicted vs measured times.

FlexFlow's execution simulator is only trustworthy because its inputs
are measured on real hardware (Jia et al., simulator.cc:275-448); this
module closes that loop continuously by diffing the cost model's
predictions against the walls the telemetry log actually records:

  * at ``compile()`` a ``sim_prediction`` event carries the simulator's
    predicted step time for the resolved strategies,
  * the health monitor refreshes a step-level ``sim_divergence`` event
    (predicted vs measured p50) once per sampling window,
  * ``runtime/profiling.op_profile`` emits per-op ``sim_divergence``
    events: the NON-measuring cost model's price (measured cache hit or
    analytic roofline — tagged by ``src``) vs the freshly measured
    standalone wall.

``tools/health_report.py`` folds these into the predicted-vs-measured
agreement table (docs/simulator.md, "Calibrating the cost
model").  Heavy imports stay inside functions: this module is only
reached from post-compile paths, but importing it must stay cheap for
the stdlib-only health monitor.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def _cost_model(model, measure: bool = False):
    from ..simulator.cost_model import CostModel
    from ..simulator.machine import TPUMachineModel

    machine = TPUMachineModel.calibrated(
        num_devices=model.machine.num_devices if model.machine else 1)
    return machine, CostModel(machine, measure=measure,
                              compute_dtype=model.config.compute_dtype)


def predict_op_times(model) -> Dict[str, Dict[str, Any]]:
    """The simulator's a-priori per-op price under each op's resolved
    strategy: ``{op: {forward_ms, forward_src, backward_ms,
    backward_src}}`` where src is "measured" (durable cache hit) or
    "analytic" (roofline fallback)."""
    _, cm = _cost_model(model, measure=False)
    out: Dict[str, Dict[str, Any]] = {}
    for op in model.ops:
        pc = getattr(op, "pc", None)
        entry: Dict[str, Any] = {}
        for which in ("forward", "backward"):
            t = cm.op_time(op, pc, which)
            entry[f"{which}_ms"] = t * 1e3
            entry[f"{which}_src"] = (
                "measured" if cm._key(op, pc, which) in cm._measured
                else "analytic")
        out[op.name] = entry
    return out


def predicted_step_seconds(model) -> float:
    """Simulated seconds/iteration for the model's resolved strategies
    (the number the strategy search optimized)."""
    from ..simulator.simulator import Simulator

    machine, cm = _cost_model(model, measure=False)
    strategies = {op.name: op.pc for op in model.ops
                  if getattr(op, "pc", None) is not None}
    return Simulator(machine, cm).simulate_runtime(model, strategies)


def emit_compile_prediction(model, log) -> Optional[float]:
    """Post-compile hook: record the simulator's step prediction and
    stash it on the model for later step-level divergence.  Never lets
    a simulator failure break compile."""
    try:
        pred = predicted_step_seconds(model)
    except Exception as e:  # prediction is advisory, training is not
        log.event("sim_prediction_error", error=repr(e))
        return None
    model._predicted_step_s = pred
    log.event("sim_prediction",
              predicted_step_ms=round(pred * 1e3, 4),
              num_devices=model.machine.num_devices if model.machine else 1,
              batch_size=model.config.batch_size,
              compute_dtype=model.config.compute_dtype)
    return pred


def emit_step_divergence(model, log, measured_p50_s: float,
                         n_steps: int) -> None:
    """Step-level agreement: compile-time prediction vs the measured
    steady-state p50 (the last record per trace wins in the report)."""
    pred = getattr(model, "_predicted_step_s", None)
    if pred is None or measured_p50_s <= 0:
        return
    log.event("sim_divergence", scope="step",
              predicted_ms=round(pred * 1e3, 4),
              measured_ms=round(measured_p50_s * 1e3, 4),
              ratio=round(pred / measured_p50_s, 4),
              n_steps=int(n_steps))


def emit_op_divergence(log, op_name: str, which: str, predicted_ms: float,
                       measured_ms: float, src: str = "analytic",
                       measured_src: str = "standalone") -> None:
    """Per-op agreement row (emitted by ``op_profile`` next to each
    measured wall, and by ``opprof`` on its in-training cadence).

    Both sides carry provenance: ``src`` names where the PREDICTION came
    from ("measured" cache hit vs "analytic" roofline), ``measured_src``
    names where the MEASUREMENT came from ("standalone" one-shot profile
    vs "opprof" in-training cadence fragments)."""
    if measured_ms <= 0:
        return
    log.event("sim_divergence", scope="op", op=op_name, which=which,
              predicted_ms=round(predicted_ms, 4),
              measured_ms=round(measured_ms, 4),
              ratio=round(predicted_ms / measured_ms, 4), src=src,
              measured_src=measured_src)
