"""The first device's idle time per step (the traced window minus the
union of its `XLA Ops`, as `reduce.idle_gaps` takes it) that falls
inside the intervals of one of the program's host spans: what the host
was doing while the device had nothing to run.

spec: `span` (`ff.update`: the host still preparing or enqueueing;
`ff.sync`: everything enqueued, so launch gaps between programs);
`scale`.  None where the trace holds no such span or no device.
"""

from benchmark import reduce
from benchmark.readers.trace_span import intervals


def read(ctx, spec):
    planes = reduce.device_planes(ctx.trace)
    spans = reduce.union(intervals(ctx, spec["span"]))
    if not planes or not spans:
        ctx.say(f"idle_by_span: no device or no span {spec['span']!r} in "
                f"the traced window")
        return None
    busy = reduce.union(reduce.events(planes[0], reduce.OPS_LINE,
                                      ctx.trace_window))
    idle = reduce.subtract([tuple(ctx.trace_window)], busy)
    inside = reduce.length(idle) - reduce.length(reduce.subtract(idle, spans))
    return inside / 1e9 / ctx.trace_steps * spec.get("scale", 1.0)
