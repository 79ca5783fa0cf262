"""Device time per step by graph op and phase, from the trace and the
program's own scope map, averaged over the chips.

The program names what it compiles (`jax.named_scope`: `ff.op.<type>.<name>`
around each graph op, `ff.loss`, `ff.optimizer`, `ff.kernel.<name>`, ...)
and `flexflow_tpu.runtime.profiling.step_scopes()` says, for every
instruction of the loaded step programs, which scope and phase it came
from.  This reader joins the trace's `XLA Ops` events to that map by
the instruction's name: of the program that fills most of the `XLA
Modules` line and, where the process loaded several of that name (the
step's two signatures), of the one whose instructions cover the trace
best.

spec: `phase` (`fwd`, `bwd`, `opt` or `other`) and/or `scope` (a regular
expression searched in the graph op's scope, e.g. `ff.op.conv2d.conv1`,
and in `ff.kernel.<kernel>`); `scale`.

It returns None, and says why, where the program has no scope map (a
program older than the scopes), where instructions holding over 1 % of
the step program's traced time are missing from the map (a map of
another program), and where nothing matches.  Once a run it logs the
coverage, the share of time in fusions that mix phases, and the ten
graph ops and phases that took most time.
"""

import bisect
import re
import time

from benchmark import reduce

MODULES_LINE = "XLA Modules"
MAX_MISSING = 0.01
PHASES = ("fwd", "bwd", "opt", "other")


def step_scopes(ctx):
    """The program's scope map, or None where it keeps none."""
    try:
        from flexflow_tpu.runtime import profiling
        fn = profiling.step_scopes
    except (ImportError, AttributeError) as e:
        ctx.say(f"device_scope: the program has no scope map ({e})")
        return None
    t0 = time.perf_counter()
    maps = fn()
    ctx.say(f"device_scope: scope map of "
            f"{sum(len(v) for v in maps.values())} step program(s) "
            f"{sorted(maps)} built in {time.perf_counter() - t0:.2f} s")
    return maps


def module_of(name):
    """`jit_step(6172467349304571002)` -> `jit_step`."""
    return name.split("(", 1)[0]


def step_events(trace, window):
    """(module name, [per plane: [(label, ns)] of the `XLA Ops` events
    inside that module's executions]): the program that fills most of
    the `XLA Modules` line within the window."""
    planes = reduce.device_planes(trace)
    totals = {}
    for p in planes:
        for name, start, end in reduce.events(p, MODULES_LINE, window):
            totals[module_of(name)] = totals.get(module_of(name), 0) \
                + end - start
    if not totals:
        return None, []
    module = max(totals, key=totals.get)
    per_plane = []
    for p in planes:
        runs = reduce.union(ev for ev in
                            reduce.events(p, MODULES_LINE, window)
                            if module_of(ev[0]) == module)
        starts = [s for s, _ in runs]
        rows = []
        for name, start, end in reduce.events(p, reduce.OPS_LINE, window):
            i = bisect.bisect_right(starts, (start + end) // 2) - 1
            if i >= 0 and (start + end) // 2 < runs[i][1]:
                rows.append((reduce.op_label(name), end - start))
        per_plane.append(rows)
    return module, per_plane


def join(ctx):
    """{(scope, phase, kernel): seconds a step, mean over the chips} of
    the traced step program, or None; logs the breakdown."""
    maps = step_scopes(ctx)
    if not maps:
        if maps is not None:
            ctx.say("device_scope: no loaded program holds an ff. scope")
        return None
    module, per_plane = step_events(ctx.trace, ctx.trace_window)
    total = sum(ns for rows in per_plane for _, ns in rows)
    if not total:
        ctx.say("device_scope: the trace holds no program's operations")
        return None
    if module not in maps:
        ctx.say(f"device_scope: the traced program {module!r} is not among "
                f"the step programs {sorted(maps)}")
        return None

    def covered(scopes):
        return sum(ns for rows in per_plane for label, ns in rows
                   if label in scopes)

    scopes = max(maps[module], key=covered)
    missing = 1.0 - covered(scopes) / total
    if missing > MAX_MISSING:
        ctx.say(f"device_scope: instructions holding {missing:.1%} of "
                f"{module}'s traced time are not in its scope map: the map "
                f"is of another program")
        return None
    per_step = 1e9 * len(per_plane) * ctx.trace_steps
    out, mixed = {}, 0
    for rows in per_plane:
        for label, ns in rows:
            e = scopes.get(label)
            if e is None:
                continue
            key = (e["scope"], e["phase"], e["kernel"])
            out[key] = out.get(key, 0.0) + ns / per_step
            mixed += ns * bool(e["mixed"])
    by_phase = {ph: sum(s for (_, p, _), s in out.items() if p == ph)
                for ph in PHASES}
    by_op = {}
    for (scope, phase, _), s in out.items():
        name = f"{scope or 'unscoped'}.{phase}"
        by_op[name] = by_op.get(name, 0.0) + s
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    ctx.say(f"device_scope: {module}: {1.0 - missing:.2%} of its traced "
            f"time is in the scope map; {mixed / total:.1%} in fusions "
            f"that mix phases; ms a step by phase: "
            + ", ".join(f"{ph} {by_phase[ph] * 1e3:.3f}" for ph in PHASES))
    ctx.say("device_scope: ms a step by graph op and phase: "
            + ", ".join(f"{name} {s * 1e3:.3f}" for name, s in top))
    return out


def read(ctx, spec):
    cache = ctx.__dict__
    if "device_scope_join" not in cache:  # once a process, not once a metric
        cache["device_scope_join"] = join(ctx)
    joined = cache["device_scope_join"]
    if joined is None:
        return None
    rx = re.compile(spec["scope"]) if "scope" in spec else None
    hits = [s for (scope, phase, kernel), s in joined.items()
            if spec.get("phase") in (None, phase)
            and (rx is None or rx.search(scope or "")
                 or (kernel and rx.search("ff.kernel." + kernel)))]
    if not hits:
        ctx.say(f"device_scope: nothing in the traced step matches {spec}")
        return None
    return sum(hits) * spec.get("scale", 1.0)
