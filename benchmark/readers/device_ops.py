"""Device time per step in the operations whose instruction text
matches a pattern, from the trace, averaged over the chips.

spec: `pattern` (a regular expression searched in the event's name,
which on the TPU is the HLO instruction's text); `scale`.
"""

from benchmark import reduce


def read(ctx, spec):
    secs, count = reduce.pattern_seconds(ctx.trace, ctx.trace_window,
                                         spec["pattern"])
    if not count:
        return None
    return secs / ctx.trace_steps * spec.get("scale", 1.0)
