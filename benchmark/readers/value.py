"""A value or counter the harness recorded, or the ratio of two.

spec: `value`; `over` (optional divisor); `scale`.
"""


def read(ctx, spec):
    v = ctx.values.get(spec["value"])
    if v is None:
        return None
    if "over" in spec:
        d = ctx.values.get(spec["over"])
        if not d:
            return None
        v = v / d
    return v * spec.get("scale", 1.0)
