"""Time per step in collectives during which nothing else ran on that
device, from the trace, averaged over the chips.

spec: `pattern` (optional; default every collective); `scale`.
"""

from benchmark import reduce


def read(ctx, spec):
    secs, count = reduce.exposed_collective_seconds(
        ctx.trace, ctx.trace_window, spec.get("pattern", reduce.COLLECTIVE))
    if not count:
        return None
    return secs / ctx.trace_steps * spec.get("scale", 1.0)
