"""The sum of some of `device.memory_stats()`'s keys after the window,
on the fullest chip.  spec: `keys`; `scale`."""


def read(ctx, spec):
    sums = [sum(s[k] for k in spec["keys"]) for s in ctx.memory_stats
            if all(k in s for k in spec["keys"])]
    if not sums:
        return None
    return max(sums) * spec.get("scale", 1.0)
