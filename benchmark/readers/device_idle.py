"""1 - (union of the device's operation intervals) / traced window,
averaged over the chips.  spec: `scale`."""

from benchmark import reduce


def read(ctx, spec):
    busy, length = reduce.busy_seconds(ctx.trace, ctx.trace_window)
    if busy is None:
        return None
    return (1.0 - busy / length) * spec.get("scale", 1.0)
