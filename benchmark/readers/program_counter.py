"""A number the program itself keeps, process-wide, in
`flexflow_tpu.runtime.profiling.counters()`.

spec: `counter`; `scale`.  None where the program keeps no such counter.
"""


def read(ctx, spec):
    try:
        from flexflow_tpu.runtime import profiling
        value = profiling.counters()[spec["counter"]]
    except (ImportError, AttributeError, KeyError) as e:
        ctx.say(f"program_counter: the program keeps no counter "
                f"{spec['counter']!r} ({e!r})")
        return None
    return value * spec.get("scale", 1.0)
