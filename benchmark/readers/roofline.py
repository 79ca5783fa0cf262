"""A kernel family's share of its roofline, in per cent: the least time
one chip could take for the operations and bytes the family needs in a
step (the larger of operations over peak FLOP/s and bytes over peak
bytes/s) over the time the trace shows.

spec: `time_metric` (another per-layer metric of the cell, in ms per
step); `formula` (a function of `flops.py`, or `module.function` of a module
beside it, that takes `batch`, one
chip's samples, and the configuration's sizes, and returns operations
and bytes per step).
"""


def read(ctx, spec):
    ms = ctx.metric(spec["time_metric"])
    if not ms:
        return None
    flops, nbytes = ctx.formula(spec["formula"])(
        batch=ctx.global_batch / ctx.chips, **ctx.kwargs)
    by_flops = flops / ctx.peak["bf16_flops_per_s"]
    by_bytes = nbytes / ctx.peak["hbm_bytes_per_s"]
    ctx.say(f"{spec['formula']}: {flops:.4g} FLOP and {nbytes:.4g} B a "
            f"step a chip; bound by "
            f"{'operations' if by_flops >= by_bytes else 'bytes'} "
            f"({by_flops * 1e3:.3f} ms against {by_bytes * 1e3:.3f} ms)")
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)
