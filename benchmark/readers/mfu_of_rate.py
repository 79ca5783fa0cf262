"""A rate the harness recorded, in samples a second a chip, as a share of
the chip's peak: times the configuration's FLOPs a sample (its `flops`
formula on its `builder_kwargs`) over the bf16 peak.

spec: `rate` (a name in the harness's values, e.g.
`block_median_samples_per_s_per_chip`).
"""


def read(ctx, spec):
    rate = ctx.values.get(spec["rate"])
    if rate is None:
        return None
    flops = ctx.formula(ctx.cell["config"]["flops"])(**ctx.kwargs)
    return rate * flops / ctx.peak["bf16_flops_per_s"]
