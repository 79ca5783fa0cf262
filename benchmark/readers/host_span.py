"""A statistic of one of the benchmark's own host spans.

spec: `span`; `variant` (default: the reported one); `phase` (`setup`,
`warmup`, `window` (default) or `trace`); `stat` (`mean` (default),
`median` or `sum`); `scale`.
"""

import statistics

STATS = {"mean": statistics.fmean, "median": statistics.median, "sum": sum}


def read(ctx, spec):
    secs = ctx.spans.seconds(spec["span"], spec.get("variant", ctx.reported),
                             spec.get("phase", "window"))
    if not secs:
        return None
    return STATS[spec.get("stat", "mean")](secs) * spec.get("scale", 1.0)
