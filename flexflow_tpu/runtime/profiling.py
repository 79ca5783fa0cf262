"""Profiling / tracing hooks.

Reference instrumentation (SURVEY §5.1): per-op cudaEvent timers behind
``--profiling`` (conv_2d.cu:448-473) and the Legion profiler via
``-lg:prof`` CLI flags.  TPU-native equivalents:

  * ``trace(logdir)`` — context manager around ``jax.profiler`` traces:
    the XLA/TensorBoard profile is the ``-lg:prof`` analogue (kernel
    timeline, HBM traffic, ICI collectives).  It writes the scope map
    of the loaded step programs beside the trace
    (``ff_step_scopes.json``),
  * ``span(log, name)`` — the program's host spans: always a
    ``TraceAnnotation`` ``ff.<name>`` (on the profiler's clock while a
    trace runs, an atomic load otherwise), and the ``EventLog`` span
    ``<name>`` as well when a log is active,
  * ``step_scopes()`` — which graph op and which phase (forward,
    backward, optimizer) every instruction of the compiled step came
    from, read from the optimized HLO of the loaded executables: the
    join between a device trace and the ``jax.named_scope``s of
    ``FFModel._build_train_step``,
  * ``phase(log, name)`` — a span outside the step loop (``compile``,
    ``init_layers``, ``step_build``, a checkpoint): no profiler runs
    while a process starts, so a phase also adds its wall seconds to the
    counters, and JAX's trace, lowering, compile and cache-fetch events
    are put down to the phase they fired in by the package's one pair
    of ``jax.monitoring`` listeners,
  * ``counters()`` — process-wide counters: set-up by phase and stage,
    how often the train step was compiled, and for how long, and what
    the graph's ops counted in their steps (``Op.COUNTERS``: the routed
    experts' assignments made, kept and dropped, the tokens with a kept
    choice, and their load), which arrive with the metric drain
    (``count``),
  * ``op_profile(model)`` — per-op forward/backward wall times, measured
    by compiling and timing each op standalone on the real device, the
    way the reference's ``measure_compute_time`` does per-op benchmarks;
    printed like the reference's per-op ``--profiling`` printouts.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax

SPAN_PREFIX = "ff."
SCOPES_FILE = "ff_step_scopes.json"


@contextlib.contextmanager
def trace(logdir: str = "/tmp/flexflow_tpu_trace"):
    """Capture an XLA profiler trace (view with TensorBoard), and write
    the scope map of the step programs loaded at its end to
    ``<logdir>/ff_step_scopes.json``, so that the trace's device
    operations can be put down to graph ops and phases after the process
    is gone."""
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()
        with open(os.path.join(logdir, SCOPES_FILE), "w") as f:
            json.dump(step_scopes(), f)


# ----------------------------------------------------------------------
# host spans
# ----------------------------------------------------------------------
@contextlib.contextmanager
def span(log, name: str, **attrs):
    """One of the program's host spans.  Opens the profiler annotation
    ``ff.<name>`` always and, where ``log`` (an ``EventLog``) is not
    None, the log's span ``<name>`` with ``attrs``.  Yields the
    attribute dict, so that a caller can add what it learns inside."""
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        if log is None:
            yield attrs
        else:
            with log.span(name, **attrs) as at:
                yield at


# ----------------------------------------------------------------------
# phases: set-up on the host's clock, and JAX's compile pipeline by phase
# ----------------------------------------------------------------------
_counters: Dict[str, float] = {}
_NO_PHASE = "none"
_ENQUEUE = "update.enqueue"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_STAGES = {
    _LOWER: "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "fetch",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
# the names the step's share of the pipeline is read by
_TRAIN_STEP = {
    "train_step_compiles": f"stage_n.{_ENQUEUE}.backend",
    "train_step_compile_s": f"stage_s.{_ENQUEUE}.backend",
    "train_step_trace_s": f"stage_s.{_ENQUEUE}.trace",
    "train_step_lower_s": f"stage_s.{_ENQUEUE}.lower",
}


class _Thread(threading.local):
    """What the listener needs to know of the thread an event fires on."""

    def __init__(self):
        self.phases: List[str] = []  # the open phases, innermost last
        self.fired = 0  # compile-pipeline events seen on this thread
        # (start, seconds) of the trace events since the last lowering
        self.traces: List[Tuple[float, float]] = []


_thread = _Thread()
_lock = threading.Lock()  # the counters, wherever two threads may add


def _add(name: str, value: float) -> None:
    _counters[name] = _counters.get(name, 0.0) + value


def _staged(stage: str, secs: Optional[float] = None) -> None:
    """One pipeline event of this thread, to its innermost open phase."""
    where = _thread.phases[-1] if _thread.phases else _NO_PHASE
    with _lock:
        _add(f"stage_n.{where}.{stage}", 1)
        if secs is not None:
            _add(f"stage_s.{where}.{stage}", secs)


@contextlib.contextmanager
def phase(log, name: str, **attrs):
    """A span that runs outside the step loop (``compile``,
    ``init_layers``, ``step_build``, a checkpoint): all that ``span``
    is, and besides its wall seconds (``time.perf_counter``, the
    ``EventLog``'s clock) and its count go to ``counters()`` as
    ``span_s.<name>`` and ``span_n.<name>``, with or without a profiler,
    and while it is open it is the phase that JAX's compile-pipeline
    events on this thread are put down to (``stage_s.<name>.<stage>``).
    The step loop's spans are not phases: a steady step changes no
    counter."""
    _thread.phases.append(name)
    t0 = time.perf_counter()
    try:
        with span(log, name, **attrs) as at:
            yield at
    finally:
        secs = time.perf_counter() - t0
        _thread.phases.pop()
        with _lock:
            _add("span_s." + name, secs)
            _add("span_n." + name, 1)


@contextlib.contextmanager
def step_enqueue(log):
    """The span ``update.enqueue`` around the call of the jitted train
    step, and a phase on condition: a compilation that fires while it
    is open is the step's (``stage_s.update.enqueue.<stage>``, read as
    ``train_step_*``), and only a call in which one fired adds its wall
    time to ``train_step_compile_call_s``: the whole cost of a call that
    traced, lowered, compiled or fetched, loaded and dispatched the
    step.  A steady call reads the clock once and changes no counter."""
    th = _thread
    th.phases.append(_ENQUEUE)
    fired = th.fired
    t0 = time.perf_counter()
    try:
        with span(log, _ENQUEUE):
            yield
    finally:
        th.phases.pop()
        if th.fired != fired:
            secs = time.perf_counter() - t0
            with _lock:
                _add("train_step_compile_call_s", secs)
                _add("train_step_compile_calls", 1)


def _on_duration(event: str, secs: float, **_) -> None:
    # JAX's compile pipeline: fires where a program is traced, lowered,
    # compiled or fetched from the persistent cache (the backend event
    # holds the fetch), and only then: the steady step never comes here
    th = _thread
    if event == _TRACE:
        # one event for every jax.jit traced, an inner one inside its
        # caller's: kept until the lowering says which was the program's
        th.fired += 1
        th.traces.append((time.time() - secs, secs))
        return
    stage = _STAGES.get(event)
    if stage is None:
        return
    th.fired += 1
    if event == _LOWER:
        # the outermost trace ends where lowering begins, so the
        # program's is the last that fired before it: an inner jit's
        # seconds are inside it and are not summed.  (A trace that began
        # after the lowering did is one a lowering rule made.)
        began = time.time() - secs
        traced = [t for start, t in th.traces if start < began]
        th.traces.clear()
        if traced:
            _staged("trace", traced[-1])
    _staged(stage, secs)


def _on_event(event: str, **_) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        _staged(name)


# the package's one pair of listeners, for the life of the process
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def compile_totals() -> Dict[str, float]:
    """The process's compile pipeline over every phase and none, since
    this module was imported: programs compiled or fetched
    (``compilations``) and their seconds (``compile_seconds``, the
    fetches' included), persistent-cache hits and writes."""
    with _lock:
        rows = list(_counters.items())

    def total(kind, stage):
        return sum(v for k, v in rows
                   if k.startswith(kind) and k.endswith("." + stage))

    return {"compilations": int(total("stage_n.", "backend")),
            "cache_hits": int(total("stage_n.", "cache_hits")),
            "cache_writes": int(total("stage_n.", "cache_misses")),
            "compile_seconds": total("stage_s.", "backend")}


_STAT = "/proc/self/stat"
_first_model_seen = False


def process_age_s(stat: str = _STAT) -> Optional[float]:
    """Seconds since the process of that ``/proc/<pid>/stat`` started:
    its start time (field 22, clock ticks after boot) against
    ``CLOCK_BOOTTIME``.  None where either cannot be read."""
    try:
        with open(stat) as f:
            fields = f.read().rpartition(")")[2].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def model_made() -> Optional[float]:
    """Called by ``FFModel.__init__``.  For the process's first model it
    writes ``before_first_model_s``, the process's age: the interpreter,
    ``import jax``, the device client, the caller's imports, which no
    span of the program can cover (absent where the age cannot be read),
    and returns the clock for ``graph_built``; None for every later one."""
    global _first_model_seen
    if _first_model_seen:
        return None
    _first_model_seen = True
    age = process_age_s(_STAT)
    if age is not None:
        _counters["before_first_model_s"] = age
    return time.perf_counter()


def graph_built(since: float) -> None:
    """Called at the entry of the first model's ``compile()``:
    ``graph_build_s``, the builder's Python since ``model_made``."""
    _counters["graph_build_s"] = time.perf_counter() - since


# ----------------------------------------------------------------------
# counters
# ----------------------------------------------------------------------
def counters() -> Dict[str, float]:
    """Process-wide counters.

    Set-up (docs/observability.md, "The step's own timeline"):
    ``span_s.<phase>`` / ``span_n.<phase>``, the wall seconds and count
    of every phase closed; ``stage_s.<phase>.<trace|lower|backend|fetch>``
    and ``stage_n.<...>``, JAX's compile-pipeline events by the
    innermost phase open on the thread they fired on (``none`` outside
    all; ``stage_n`` also counts ``cache_hits`` and ``cache_misses``);
    of those the step's own under their names: ``train_step_compiles``,
    the XLA compilations (or persistent-cache fetches) that happened
    inside a train step's call, over every model of the process,
    ``train_step_compile_s``, their seconds, ``train_step_trace_s`` and
    ``train_step_lower_s``; ``train_step_compile_calls`` and
    ``train_step_compile_call_s``, the step calls in which any of that
    fired and their wall time; ``before_first_model_s`` and
    ``graph_build_s``, written once by the process's first model.

    Where a model with routed
    experts has drained its metrics, also the sums its layers counted
    (``ops/moe.py``, ``RoutedExperts.COUNTERS``) and what follows from
    them over all drained steps: ``moe_assignments_made_per_token`` and
    ``moe_assignments_kept_per_token`` (a token and expert layer),
    ``moe_dropped_share`` (of the assignments made, those the device
    budget dropped), ``moe_tokens_with_choice_share`` (of the tokens,
    those with a kept choice here: how full the combine's compact sums
    are) and ``moe_load_max_over_mean`` (the held experts' largest kept
    load over their mean, averaged over layers and steps).
    Where a model with a learned index (``ops/dsa.py``) has drained,
    ``dsa_index_kl``: the index's objective ``L_I``, the mean over the
    indexed layers and every drained step (the layers summed a term each
    into the step's objective, ``FwdCtx.add_loss``).  Where a model with
    window layers that have a sink (``GroupedQueryAttention``) has
    drained, ``swa_sink_share``: the softmax's mass the sink took, the
    mean over those layers, their heads and queries, and every drained
    step.  Where a model with Mamba layers (``ops/ssm.py``) has drained,
    ``ssm_dt_mean``: the scan's step size Delta, the mean over those
    layers, their tokens and channels, and every drained step."""
    with _lock:
        out = dict(_counters)
    for name, source in _TRAIN_STEP.items():
        out[name] = out.get(source, 0.0)
    if out.get("dsa_layers"):
        out["dsa_index_kl"] /= out["dsa_layers"]
    if out.get("swa_layers"):
        out["swa_sink_share"] /= out["swa_layers"]
    if out.get("ssm_layers"):
        out["ssm_dt_mean"] /= out["ssm_layers"]
    tokens = out.get("moe_tokens")
    if tokens:
        made, kept = out["moe_assignments_made"], out["moe_assignments_kept"]
        out["moe_assignments_made_per_token"] = made / tokens
        out["moe_assignments_kept_per_token"] = kept / tokens
        out["moe_dropped_share"] = (made - kept) / made if made else 0.0
        out["moe_tokens_with_choice_share"] = \
            out["moe_tokens_with_choice"] / tokens
        out["moe_load_max_over_mean"] /= out["moe_layers"]
    return out


def count(sums: Dict[str, float]) -> None:
    """Add what a model's ops counted since its last drain."""
    for name, value in sums.items():
        _add(name, value)


# ----------------------------------------------------------------------
# the scope map
# ----------------------------------------------------------------------
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"\b(calls|to_apply)=%?([\w.\-]+)")
_FF_SCOPE = re.compile(r"ff\.[^/()\"]+")
_KERNEL = SPAN_PREFIX + "kernel."
_FWD_SCOPES = (SPAN_PREFIX + "op.", SPAN_PREFIX + "loss",
               SPAN_PREFIX + "input_cast")
_MATMULS = ("convolution", "dot")


def _opcode(rest: str) -> str:
    """The opcode of an instruction's text after `name = `: what stands
    between the result's shape and the operands' parenthesis."""
    if rest.startswith("("):  # a tuple shape: skip to its matching ")"
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return rest.lstrip().partition("(")[0]


def scope_of(op_name: str) -> Dict[str, Optional[str]]:
    """Scope, phase and kernel of one instruction's ``op_name``; where
    the graph op (the outermost ``ff.`` name, the scope) opened a scope of
    its own inside itself (``ff.mla.q_proj``, ``ff.moe.experts``), also
    ``span``, the innermost of them.

    ``bwd`` where the name holds ``transpose(`` (the recomputed forward
    of a ``jax.checkpoint`` is there too, where its time is spent), else
    ``opt`` under ``ff.optimizer``, else ``fwd`` under a graph op, the
    loss or the input cast, else ``other``."""
    names = _FF_SCOPE.findall(op_name)
    kernel = next((n[len(_KERNEL):] for n in names if n.startswith(_KERNEL)),
                  None)
    outer = [n for n in names if not n.startswith(_KERNEL)]
    scope = outer[0] if outer else None
    if "transpose(" in op_name:
        phase = "bwd"
    elif scope == SPAN_PREFIX + "optimizer":
        phase = "opt"
    elif scope is not None and scope.startswith(_FWD_SCOPES):
        phase = "fwd"
    else:
        phase = "other"
    out = {"scope": scope, "phase": phase, "kernel": kernel}
    if len(outer) > 1 and outer[-1] != scope:
        out["span"] = outer[-1]
    return out


def parse_hlo_scopes(text: str) -> Dict[str, Dict[str, object]]:
    """{instruction name: {"scope", "phase", "kernel", "mixed"}} for
    every instruction of an optimized HLO module that the device runs
    on its own: those of the entry computation and of loop bodies and
    branches, not those inside fused computations or reducers.

    A fusion is attributed by what is inside it: the scope and phase of
    its convolution or dot where it has one (the optimizer update that
    XLA fuses into a weight-gradient fusion does not turn the gradient's
    time into the optimizer's), else of its root, else of the last
    scoped instruction before the root; ``mixed`` where the scoped
    instructions inside come from more than one phase.  An instruction
    that carries no ``op_name`` at all (the compiler's own: the start
    and done of an asynchronous copy or slice) takes the scope and phase
    of the first instruction that uses it, where its wait is spent, or
    failing that (a copy out to the program's result) of its operand."""
    comps: Dict[str, List[tuple]] = {}
    inner = set()  # computations that run inside another instruction
    rows = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and rows is not None:
            rest = m.group(3)
            called = _CALLED.findall(rest)
            inner.update(c for _, c in called)
            name = _OP_NAME.search(rest)
            rows.append((m.group(2), _opcode(rest), bool(m.group(1)),
                         name.group(1) if name else "",
                         [c for _, c in called], _OPERAND.findall(rest)))
            continue
        m = _COMPUTATION.match(line)
        if m:
            rows = comps.setdefault(m.group(1), [])

    def inside(comp, seen):
        """(opcode, is root, op_name) of every instruction of a called
        computation, and of those it calls in turn."""
        for _, opcode, root, op_name, called, _ in comps.get(comp, ()):
            yield opcode, root, op_name
            for c in called:
                if c not in seen:
                    seen.add(c)
                    yield from inside(c, seen)

    out = {}
    for comp, rows in comps.items():
        if comp in inner:
            continue
        first_user: Dict[str, str] = {}
        for name, _, _, _, _, operands in rows:
            for operand in operands:
                first_user.setdefault(operand, name)
        for name, opcode, _, op_name, called, _ in reversed(rows):
            entry = dict(scope_of(op_name), mixed=False)
            if called:
                body = [(oc, root, scope_of(on))
                        for c in called for oc, root, on in inside(c, {c})]
                scoped = [b for b in body if b[2]["scope"] is not None]
                if scoped:
                    pick = (next((b for b in scoped if b[0] in _MATMULS), None)
                            or next((b for b in scoped if b[1]), None)
                            or scoped[-1])
                    entry = dict(pick[2], mixed=len(
                        {b[2]["phase"] for b in scoped}) > 1)
            elif not op_name and first_user.get(name) in out:
                # users stand later in a scheduled computation, so in
                # this reversed walk they are resolved already
                entry = dict(out[first_user[name]], kernel=None, mixed=False)
            out[name] = entry
        for name, _, _, op_name, _, operands in rows:
            if not op_name and out[name]["scope"] is None:
                made = next((out[o] for o in operands
                             if o in out and out[o]["scope"]), None)
                if made is not None:
                    out[name] = dict(made, kernel=None, mixed=False)
    return out


def step_programs() -> List[Tuple[str, str, Dict[str, Dict[str, object]]]]:
    """(module name, optimized HLO text, scope map as ``parse_hlo_scopes``
    gives it) of every step program this process has loaded.  A step
    program is one whose optimized HLO holds an ``ff.`` scope.  Read from
    the executables the client holds: nothing is compiled or loaded."""
    out = []
    for exe in jax.devices()[0].client.live_executables():
        module = exe.hlo_modules()[0]
        text = module.to_string()
        if 'op_name="' not in text or SPAN_PREFIX not in text:
            continue
        scopes = parse_hlo_scopes(text)
        if any(e["scope"] for e in scopes.values()):
            out.append((module.name, text, scopes))
    return out


def step_scopes() -> Dict[str, List[Dict[str, Dict[str, object]]]]:
    """The scope map of every step program this process has loaded:

        {module name: [{instruction name: {"scope": "ff.op.conv2d.conv1",
                                           "phase": "fwd"|"bwd"|"opt"|"other",
                                           "kernel": "flash_fwd"|None,
                                           ["span": "ff.moe.route",]
                                           "mixed": bool}}, ...]}

    one entry of the list for each loaded program of that name: the
    train step is one program a step function (every argument of its
    first call is placed as the step returns it), so several only where
    a ``recompile`` or a second model built another."""
    out: Dict[str, List[Dict[str, Dict[str, object]]]] = {}
    for name, _, scopes in step_programs():
        out.setdefault(name, []).append(scopes)
    return out


def op_profile(model, which: str = "both") -> Dict[str, Dict[str, float]]:
    """Measure each op's standalone fwd (and bwd) time on the real device.

    Uses the simulator's measuring cost model (the measure_compute_time
    analogue) with per-op sub-shapes from the op's resolved strategy.
    Returns {op_name: {"forward_ms": x, "backward_ms": y}}.
    """
    from ..simulator.cost_model import CostModel
    from ..simulator.machine import TPUMachineModel

    cm = CostModel(TPUMachineModel.calibrated(num_devices=model.machine.num_devices),
                   measure=True, compute_dtype=model.config.compute_dtype,
                   target_platform=jax.default_backend())
    out: Dict[str, Dict[str, float]] = {}
    for op in model.ops:
        pc = getattr(op, "pc", None)
        entry = {}
        if which in ("both", "forward"):
            entry["forward_ms"] = cm.op_time(op, pc, "forward") * 1e3
        if which in ("both", "backward"):
            entry["backward_ms"] = cm.op_time(op, pc, "backward") * 1e3
        out[op.name] = entry
    tel = getattr(model, "_telemetry", None)
    if tel is not None:
        from ..observability import agreement

        # the NON-measuring cost model's price for the same shapes —
        # the simulator-agreement side of each measured wall
        try:
            predicted = agreement.predict_op_times(model)
        except Exception:
            predicted = {}
        # one event per op: trace_report folds these into its top-k table
        for name, t in out.items():
            tel.event("op_profile", op=name,
                      forward_ms=round(t.get("forward_ms", 0.0), 4),
                      backward_ms=round(t.get("backward_ms", 0.0), 4))
            pred = predicted.get(name)
            if not pred:
                continue
            for w in ("forward", "backward"):
                if f"{w}_ms" in t:
                    agreement.emit_op_divergence(
                        tel, name, w, pred[f"{w}_ms"], t[f"{w}_ms"],
                        src=pred.get(f"{w}_src", "analytic"))
        tel.flush()
    return out


def print_op_profile(model) -> None:
    """Reference-style per-op ms printout (conv_2d.cu:448-473 style)."""
    prof = op_profile(model)
    for name, t in prof.items():
        fwd = t.get("forward_ms", 0.0)
        bwd = t.get("backward_ms", 0.0)
        print(f"[profiling] {name}: forward {fwd:.3f} ms, backward {bwd:.3f} ms")
