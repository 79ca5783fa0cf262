"""A statistic of one of the PROGRAM's host spans (`ff.update.enqueue`,
`ff.metric_drain`, ...: `flexflow_tpu/runtime/profiling.span`), read
from the host plane of the trace inside the traced window, on the same
clock as the device's operations.

spec: `span`; `per` (`step`: the span's total time over the traced
steps; `call`: its mean time per occurrence); `scale`.  None where the
trace holds no such span (a program older than the spans).
"""

from benchmark import reduce


def intervals(ctx, name):
    """[(start, end)] ns of the program's spans called `name`, clipped
    to the traced window."""
    t0, t1 = ctx.trace_window
    return [(max(s, t0), min(e, t1))
            for n, s, e in reduce.host_spans(ctx.trace, "ff.")
            if n == name and min(e, t1) > max(s, t0)]


def read(ctx, spec):
    spans = intervals(ctx, spec["span"])
    if not spans:
        ctx.say(f"trace_span: no span {spec['span']!r} in the traced window")
        return None
    per = {"step": ctx.trace_steps, "call": len(spans)}[spec["per"]]
    return reduce.length(spans) / 1e9 / per * spec.get("scale", 1.0)
