"""Device time per step under the scopes a graph op opens inside itself
(`ff.mla.q_proj`, `ff.moe.experts`, ...), from the trace and the program's
scope map, averaged over the chips: `device_scope`'s join, keyed by the
map's `span` (the innermost `ff.` scope of an instruction that is neither
the graph op's nor a kernel's) where that reader keys by the graph op.

spec: `span` (a regular expression searched in the span's name); `phase`
(optional: `fwd`, `bwd`); `scale`.

None, with the reason logged, where `device_scope` returns None (no scope
map, a map of another program) and where the map names no such span (a
program older than the spans: its entries carry no `span`).
"""

import re

from benchmark.readers import device_scope


def join(ctx):
    """{(span, phase): seconds a step, mean over the chips} of the traced
    step program's instructions that lie under a span, or None."""
    maps = device_scope.step_scopes(ctx)
    if not maps:
        return None
    module, per_plane = device_scope.step_events(ctx.trace, ctx.trace_window)
    if module not in maps:
        ctx.say(f"device_span: the traced program {module!r} is not among "
                f"the step programs {sorted(maps)}")
        return None
    scopes = max(maps[module], key=lambda m: sum(
        ns for rows in per_plane for label, ns in rows if label in m))
    per_step = 1e9 * len(per_plane) * ctx.trace_steps
    out = {}
    for rows in per_plane:
        for label, ns in rows:
            e = scopes.get(label)
            if e is not None and e.get("span"):
                key = (e["span"], e["phase"])
                out[key] = out.get(key, 0.0) + ns / per_step
    ctx.say("device_span: ms a step by span and phase: " + ", ".join(
        f"{span}.{phase} {s * 1e3:.3f}"
        for (span, phase), s in sorted(out.items(), key=lambda kv: -kv[1])))
    return out


def read(ctx, spec):
    cache = ctx.__dict__
    if "device_span_join" not in cache:  # once a process, not once a metric
        cache["device_span_join"] = join(ctx)
    joined = cache["device_span_join"]
    if not joined:
        return None
    rx = re.compile(spec["span"])
    hits = [s for (span, phase), s in joined.items()
            if rx.search(span) and spec.get("phase") in (None, phase)]
    if not hits:
        ctx.say(f"device_span: nothing in the traced step matches {spec}")
        return None
    return sum(hits) * spec.get("scale", 1.0)
