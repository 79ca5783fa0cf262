#!/usr/bin/env python3
"""The benchmark's command: one process, one cell, once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It reads the cell from `BENCHMARK.json`, its configuration, traffic mix
and per-layer metrics from the data files beside this one, builds the
model through the program's normal path (`FFConfig.parse_args`, the
builder, `compile`, `init_layers`, `set_batch`), checks the first loss
against the plain reference, warms up, and then times blocks of steps
for `--seconds`.  The last line of standard output is the result, one
JSON object; every other line goes to standard error and names the
device.  It refuses to run without a TPU it knows the peaks of.

A block is `k` calls of `model.train_iteration()`, one `model.sync()`
and one drain of the metrics (`get_metrics()`, which reads the loss),
timed on the host clock from the end of the block before it, so that
the window's blocks leave no time out; `k` is the fewest whole steps
that last `block_min_ms`.  The rate is all the samples of the window's
blocks over all their time, the tail is the 90th percentile over blocks
(`block_stats`).
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # the program, and this directory as a package

MAX_WARMUP_BLOCKS = 12
TRACE_BLOCKS = 10  # blocks of the reported variant the traced run profiles
SEED_MODULUS = 2 ** 31 - 1  # a jax.random.key takes 32 signed bits


class Refused(SystemExit):
    """The run cannot be a measurement; exit non-zero, print no result."""

    def __init__(self, msg):
        super().__init__(f"benchmark: refused: {msg}")


# --------------------------------------------------------------------------
# the cell, from data
# --------------------------------------------------------------------------

def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(root, workload):
    """Everything one cell is made of, found by the names in
    `BENCHMARK.json`: no name of a configuration, traffic mix or
    per-layer metric appears in this file.  The end-to-end metrics are
    the harness's own (`run_cell`, `e2e`)."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    home = os.path.join(root, bench["paths"][0])

    def of_cell(kind):
        return [m for m in bench[kind]
                if workload in m.get("workloads", [workload])]

    layer_metrics = {
        m["name"]: _json(os.path.join(home, "layer_metrics",
                                      m["name"] + ".json"))
        for m in of_cell("per_layer")}
    return {
        "name": workload, "chips": cell["chips"], "home": home,
        "config_name": cell["config"],
        "config": _json(os.path.join(root, entry["file"])),
        "traffic": _json(os.path.join(home, "traffic",
                                      cell["traffic"] + ".json")),
        "end_to_end": of_cell("end_to_end"),
        "layer_metrics": layer_metrics,
        "peaks": _json(os.path.join(home, "peaks.json")),
    }


def load_reference(home, name):
    """The plain reference beside the configuration: `reference/<name>.py`,
    where `name` is the configuration's own unless its file names another
    (the same architecture at another size)."""
    path = os.path.join(home, "reference", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_" + name.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(dotted):
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def formula(name):
    """A function of operations or bytes by name: `f` is `flops.f`, and
    `m.f` is `f` of the module `m` beside this file, so that a later
    configuration brings its formulas in a file of its own."""
    module, _, attr = name.rpartition(".")
    return resolve(f"benchmark.{module or 'flops'}.{attr}")


# --------------------------------------------------------------------------
# block arithmetic
# --------------------------------------------------------------------------

def steps_per_block(step_seconds, block_min_ms):
    """The fewest whole steps that last at least `block_min_ms`."""
    return max(1, math.ceil(block_min_ms / 1e3 / step_seconds - 1e-9))


def percentile(values, q):
    """The q-th percentile by linear interpolation between the sorted
    values (the first is the 0th, the last the 100th)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def block_stats(blocks, k, global_batch, chips):
    """From the window's blocks of one variant, each (seconds, ok) and
    each timed from the end of the one before: samples a second a chip
    over all of them - the samples of every block that completed over
    the time of every block, failed ones too - and the 90th percentile
    over the completed blocks of a step's milliseconds.  Beside them the
    median over blocks of the rate and of a step's milliseconds, which
    one slow block does not move."""
    good = [dt for dt, ok in blocks if ok]
    step_ms = [dt / k * 1e3 for dt in good]
    return {"samples_per_s_per_chip": global_batch * k * len(good)
            / sum(dt for dt, _ in blocks) / chips,
            "step_ms_p90": percentile(step_ms, 90),
            "step_ms_p50": statistics.median(step_ms),
            "block_median_samples_per_s_per_chip": statistics.median(
                global_batch * k / dt / chips for dt in good),
            "blocks": len(good)}


# --------------------------------------------------------------------------
# the benchmark's own spans and counters
# --------------------------------------------------------------------------

class Spans:
    """Host spans around the calls into each layer: (name, variant,
    phase, seconds), kept in memory.  While the profiler runs each is
    also a `TraceAnnotation`, so the device trace carries them."""

    def __init__(self):
        self.rows = []
        self.phase = "setup"
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name, variant=""):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.rows.append((name, variant, self.phase,
                          time.perf_counter() - t0))

    def seconds(self, name, variant=None, phase=None):
        return [s for n, v, p, s in self.rows
                if n == name and variant in (None, v) and phase in (None, p)]


class CompileCounter:
    """This process's XLA compilations, counted the way `chip_smoke.py`
    counts them: `jax.monitoring`'s backend-compile event fires for a
    program compiled or fetched from the persistent cache."""

    def __init__(self):
        import jax.monitoring

        self.compilations = self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compilations += 1
            self.seconds += secs


def enable_compile_cache():
    """JAX's persistent cache through the program's one helper (the
    repo's rule, and a test of it: no other file names the option):
    where `JAX_COMPILATION_CACHE_DIR` is set JAX uses that, else the
    fixed path `<checkout>/.jax_cache`.  Every program is kept, however
    quickly it compiled, so that a second run compiles nothing."""
    import jax
    from flexflow_tpu.utils.compile_cache import \
        enable_compile_cache as program_helper

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return program_helper()


# --------------------------------------------------------------------------
# one variant of the cell: a model built through the program's path
# --------------------------------------------------------------------------

class Variant:
    def __init__(self, name, model):
        self.name = name
        self.model = model
        self.first_loss = None
        self.blocks = []  # (phase, seconds or None, loss)

    def step_loss(self):
        """One synced step; the loss it reports."""
        m = self.model
        m.train_iteration()
        m.sync()
        m.get_metrics()
        return m.last_loss


def build_variant(cell, spec, global_batch, seed, spans):
    import flexflow_tpu as ff

    config = cell["config"]
    cfg = ff.FFConfig()
    left = cfg.parse_args(["-b", str(global_batch)] + list(spec["args"]))
    if left:
        raise Refused(f"variant {spec['name']}: FFConfig does not know {left}")
    model = ff.FFModel(cfg)
    resolve(config["builder"])(model, global_batch,
                               **config["builder_kwargs"])
    opt = getattr(ff, config["optimizer"]["class"])(
        model, **config["optimizer"]["kwargs"])
    with spans.span("bench.compile", spec["name"]):
        model.compile(opt, config["loss"], [ff.MetricsType.ACCURACY])
    with spans.span("bench.init_layers", spec["name"]):
        model.init_layers(seed=seed)
    return Variant(spec["name"], model)


def params_of(model, device):
    """{op: {weight: array}} of the model's live parameters, whole, on
    one device."""
    import jax

    out = {}
    for key, a in model.placement().items():
        op, _, w = key.partition("/")
        if op == "batch":
            continue
        if len(a.sharding.device_set) > 1:
            a = jax.device_put(a, device)
        out.setdefault(op, {})[w] = a
    return out


def stage_batch(model, ref, key, global_batch, kwargs):
    """Make the cell's one batch from the key on the device, straight
    into the shardings `set_batch` gives a host array (the batch degree
    of each input's first consumer, and of the last op for the labels),
    and stage it.  Committed arrays pass through `set_batch` as they
    are, so nothing goes by way of the host.  Returns (inputs, labels)."""
    import jax

    def sharding(degree):
        return model.machine.batch_sharding(degree)

    degrees = [next((op.pc.dims[0] for op in model.ops if t in op.inputs), 1)
               for t in model.input_tensors]
    inputs, labels = jax.jit(
        lambda k: ref.make_batch(k, global_batch, **kwargs),
        out_shardings=(tuple(sharding(d) for d in degrees),
                       sharding(model.ops[-1].pc.dims[0])))(key)
    model.set_batch(dict(zip(model.input_tensors, inputs)), labels)
    return inputs, labels


def reference_loss(ref, params, inputs, labels, kwargs, device):
    """The plain reference's loss on the whole batch, a chunk at a time
    on one device."""
    import jax

    n = labels.shape[0]
    chunk = min(getattr(ref, "CHUNK", n), n)
    if n % chunk:
        chunk = n
    parts = [float(ref.loss(
        params, tuple(jax.device_put(x[i:i + chunk], device) for x in inputs),
        jax.device_put(labels[i:i + chunk], device), **kwargs))
        for i in range(0, n, chunk)]
    return sum(parts) / len(parts)


def check_shards(model):
    """Every parameter sits on all of the model's devices in shards of
    the shape its op's resolved strategy implies (`chip_smoke.py`'s
    `_check_shards`, copied).  Returns the list of faults."""
    n = model.machine.num_devices
    arrays = model.placement()
    faults = []
    for op in model.ops:
        for w in op.weights:
            a = arrays[f"{op.name}/{w.name}"]
            want = tuple(
                full // (op.pc.dims[pd]
                         if pd is not None and pd < len(op.pc.dims) else 1)
                for full, pd in zip(w.dims, w.partition_dims
                                    or (None,) * len(w.dims)))
            devs = {s.device for s in a.addressable_shards}
            shapes = {tuple(s.data.shape) for s in a.addressable_shards}
            if len(devs) != n or shapes != {want}:
                faults.append(f"{op.name}/{w.name} pc {op.pc.dims}: shards "
                              f"{sorted(shapes)} on {len(devs)} device(s), "
                              f"wanted {want} on {n}")
    return faults


def non_dp_ops(model):
    n = model.machine.num_devices
    return {op.name: list(op.pc.dims) for op in model.ops
            if op.pc.num_parts() != n or op.pc.dims[0] != n}


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def run_block(variant, k, spans, say, since=None):
    """k steps, a sync and the drain of the metrics, which reads the
    mean loss of those steps.  Timed from `since`, the end of the block
    before, or from now.  Appends (phase, seconds, loss, ok) to the
    variant's blocks, ok False where a call raised; returns the time
    the block ended."""
    m = variant.model
    t0 = time.perf_counter() if since is None else since
    ok = True
    try:
        with spans.span("bench.block", variant.name):
            for _ in range(k):
                with spans.span("bench.train_iteration", variant.name):
                    m.train_iteration()
            with spans.span("bench.sync", variant.name):
                m.sync()
            with spans.span("bench.read_loss", variant.name):
                m.get_metrics()
                loss = m.last_loss
    except Exception as e:  # a failed block is counted, not hidden
        say(f"block failed: {e!r}")
        ok, loss = False, float("nan")
    t1 = time.perf_counter()
    variant.blocks.append((spans.phase, t1 - t0, loss, ok))
    return t1


def slowest_blocks(spans, variant, rows, n=3):
    """For the log: the window's n longest blocks of a variant, each
    with its place in the window, its milliseconds, and how many of
    them the host spent in the calls that enqueue the steps, in the
    sync and in the drain.  What is left over passed between blocks."""
    parts, inside = [], {}
    for name, var, phase, secs in spans.rows:
        if var != variant or phase != "window":
            continue
        if name == "bench.block":
            parts.append(inside)
            inside = {}
        else:
            inside[name] = inside.get(name, 0.0) + secs
    worst = sorted(range(len(rows)), key=lambda i: rows[i][0])[-n:]
    return [dict({"block": i, "ms": round(rows[i][0] * 1e3, 2)},
                 **{name[len("bench."):] + "_ms": round(secs * 1e3, 2)
                    for name, secs in parts[i].items()})
            for i in reversed(worst) if i < len(parts)]


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

class Context:
    """What a per-layer reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def metric(self, name):
        """Another per-layer metric of this cell, by name (or None)."""
        spec = self.cell["layer_metrics"].get(name)
        return None if spec is None else read_metric(self, spec)


def read_metric(ctx, spec):
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(ctx, spec)


def run_cell(cell, seed, seconds, trace, t0=None, say=None):
    """Run one cell on the devices JAX has; returns the result object.
    The caller decides whether those devices make it a measurement."""
    import jax

    t0 = time.perf_counter() if t0 is None else t0
    devices = jax.devices()
    dev = devices[0]
    tag = (f"[{dev.platform} {dev.device_kind!r} x{len(devices)}] "
           f"{cell['name']}:")
    if say is None:
        def say(msg):
            print(f"{tag} {msg}", file=sys.stderr, flush=True)
    chips = cell["chips"]
    if len(devices) < chips:
        raise Refused(f"{cell['name']} needs {chips} chip(s), JAX has "
                      f"{len(devices)}")
    if dev.device_kind not in cell["peaks"]:
        raise Refused(f"device_kind {dev.device_kind!r} is not in peaks.json")
    peak = cell["peaks"][dev.device_kind]
    config, traffic = cell["config"], cell["traffic"]
    kwargs = config["builder_kwargs"]
    global_batch = traffic["batch_per_chip"] * chips
    ref = load_reference(cell["home"],
                         config.get("reference", cell["config_name"]))
    flops_per_sample = formula(config["flops"])(**kwargs)

    compiles = CompileCounter()
    spans = Spans()
    values = {}
    checks = {}

    # -- set-up: build every variant, stage one batch, check the first loss
    key = jax.random.key(seed % SEED_MODULUS)
    variants, batches = [], {}
    for spec in traffic["variants"]:
        v = build_variant(cell, spec, global_batch, seed % SEED_MODULUS,
                          spans)
        m = v.model
        with spans.span("bench.stage_batch", v.name):
            batches[v.name] = stage_batch(m, ref, key, global_batch, kwargs)
        variants.append(v)
        if m.machine.num_devices != chips:
            raise Refused(f"variant {v.name} runs on "
                          f"{m.machine.num_devices} device(s), the cell "
                          f"has {chips}")
        if chips > 1:
            faults = check_shards(m)
            checks[f"shards.{v.name}"] = not faults
            for f in faults:
                say(f"FAULT {v.name}: {f}")
            say(f"{v.name}: ops not plainly data parallel: "
                f"{json.dumps(non_dp_ops(m))}")
    reported = next(v for v in variants if v.name == traffic["reported"])

    with spans.span("bench.reference"):
        want = reference_loss(ref, params_of(reported.model, dev),
                              *batches[reported.name], kwargs, dev)
    del batches
    if len(variants) > 1:
        from flexflow_tpu.observability.agreement import \
            predicted_step_seconds

        with spans.span("bench.simulate"):
            for v in variants:
                values[f"sim_step_s.{v.name}"] = \
                    predicted_step_seconds(v.model)
    for v in variants:
        with spans.span("bench.first_step", v.name):
            v.first_loss = v.step_loss()
    tol = config["loss_tolerance"]["rel"]
    rel = abs(reported.first_loss - want) / abs(want)
    checks["first_loss_matches_reference"] = bool(rel <= tol)
    say(f"first loss {reported.first_loss!r} reference {want!r} "
        f"rel diff {rel:.2e} (tolerance {tol})")

    # -- warm-up: fix k, then blocks until one compiles nothing
    k = 1
    for v in variants:
        singles = []
        for _ in range(4):
            t = time.perf_counter()
            v.step_loss()
            singles.append(time.perf_counter() - t)
        k = max(k, steps_per_block(min(singles[1:]),
                                   traffic["block_min_ms"]))
    spans.phase = "warmup"
    for v in variants:
        for i in range(MAX_WARMUP_BLOCKS):
            before = compiles.compilations
            run_block(v, k, spans, say)
            if i >= 2 and compiles.compilations == before:
                break
        else:
            raise Refused(f"variant {v.name} still compiles after "
                          f"{MAX_WARMUP_BLOCKS} warm-up blocks")
    values["compile_s_setup"] = compiles.seconds
    setup_s = time.perf_counter() - t0
    say(f"set-up {setup_s:.1f} s, k={k}, {compiles.compilations} "
        f"compilation(s) of which {compiles.cache_hits} from the cache, "
        f"{compiles.seconds:.1f} s compiling")
    parts = {}
    for name, variant, phase, secs in spans.rows:
        if phase == "setup" and name != "bench.train_iteration":
            label = name[len("bench."):] + ("." + variant if variant else "")
            parts[label] = round(parts.get(label, 0.0) + secs, 2)
    say(f"set-up spans (s): {json.dumps(parts)}")

    # -- the window.  The collector first: what set-up left behind is
    # frozen out of its reach, so that it does not scan it mid-window.
    gc.collect()
    gc.freeze()
    spans.phase = "window"
    before = compiles.compilations
    segment = traffic["segment_blocks"] if len(variants) > 1 else 1
    trace_at = seconds / 3.0 if trace else None
    trace_info = None
    t_start = mark = time.perf_counter()
    paused = 0.0
    stop = False
    while not stop:
        for v in variants:
            for _ in range(segment):
                mark = run_block(v, k, spans, say, since=mark)
        elapsed = mark - t_start - paused
        if trace_at is not None and elapsed >= trace_at:
            trace_info = profiled_stretch(reported, k, spans, say)
            trace_at = None
            now = time.perf_counter()
            paused += now - mark
            mark = now
        stop = elapsed >= seconds or \
            sum(1 for ph, _, _, ok in reported.blocks
                if ph == "window" and not ok) >= 3
    gc.unfreeze()
    in_window = compiles.compilations - before
    checks["no_compilation_in_window"] = in_window == 0

    # -- what the blocks say
    stats = {}
    attempted = failed = 0
    for v in variants:
        rows = [(dt, loss, ok) for ph, dt, loss, ok in v.blocks
                if ph == "window"]
        attempted += len(rows)
        bad = [1 for _, loss, ok in rows
               if not ok or loss is None or not math.isfinite(loss)]
        failed += len(bad)
        if not any(ok for _, _, ok in rows):
            raise Refused(f"variant {v.name}: no block completed")
        stats[v.name] = block_stats([(dt, ok) for dt, _, ok in rows], k,
                                    global_batch, chips)
        last = rows[-1][1]
        losses_ok = (not bad and math.isfinite(v.first_loss)
                     and last < v.first_loss)
        checks[f"loss_falls.{v.name}"] = bool(losses_ok)
        say(f"{v.name}: {json.dumps(stats[v.name])} loss "
            f"{v.first_loss!r} -> {last!r}")
        say(f"{v.name}: slowest blocks "
            f"{json.dumps(slowest_blocks(spans, v.name, rows))}")
    if len(variants) > 1:
        # the same weights and batch under two strategies: the mean loss
        # of the first warm-up block (the same k steps) must agree
        a, b = (next(loss for ph, _, loss, _ in v.blocks if ph == "warmup")
                for v in variants[:2])
        checks["variants_agree"] = bool(abs(a - b) <= tol * abs(a))
        say(f"first warm-up block loss {variants[0].name} {a!r} "
            f"{variants[1].name} {b!r}")
    main = stats[reported.name]
    for name in ("samples_per_s_per_chip",
                 "block_median_samples_per_s_per_chip"):
        values[name] = main[name]

    mem = read_memory(devices[:chips], say)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(bytes_held(s) for s in mem)}

    result = {"correct": all(checks.values()) and failed == 0,
              "attempted": attempted, "failed": failed, "metrics": {},
              "device": device}
    for name, ok in checks.items():
        if not ok:
            say(f"CHECK FAILED: {name}")
    if not trace:
        e2e = {
            "samples_per_s_per_chip": main["samples_per_s_per_chip"],
            "mfu": main["samples_per_s_per_chip"] * flops_per_sample
            / peak["bf16_flops_per_s"],
            "step_ms_p90": main["step_ms_p90"],
            "setup_s": setup_s,
        }
        if len(variants) > 1:
            first = variants[0] if variants[0] is not reported else \
                variants[1]
            e2e[f"{reported.name}_over_{first.name}"] = \
                main["samples_per_s_per_chip"] \
                / stats[first.name]["samples_per_s_per_chip"]
        for m in cell["end_to_end"]:
            if m["name"] not in e2e:
                raise Refused(f"end-to-end metric {m['name']} is not one "
                              f"this harness measures")
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
        return result

    # -- the traced run: per-layer metrics through their readers
    if trace_info is None:
        raise Refused("the window ended before the profiled stretch")
    ctx = Context(cell=cell, spans=spans, values=values,
                  reported=reported.name, chips=chips, peak=peak,
                  global_batch=global_batch, kwargs=kwargs,
                  formula=formula, memory_stats=mem, say=say, **trace_info)
    for name, spec in cell["layer_metrics"].items():
        value = read_metric(ctx, spec)
        if value is not None:
            result["metrics"][name] = {"value": value, "unit": spec["unit"]}
    from benchmark import reduce

    tr, window = trace_info["trace"], trace_info["trace_window"]
    busy, length = reduce.busy_seconds(tr, window)
    if not busy:
        raise Refused("the traced stretch holds no device operation")
    device.update(busy_s=busy, window_s=length)
    result["breakdown"] = {
        "device_ops": reduce.top_device_ops(tr, window),
        "idle_gaps": reduce.idle_gaps(tr, window)}
    return result


MEMORY_KEYS = ("bytes_limit", "bytes_in_use", "bytes_reserved",
               "largest_free_block_bytes", "peak_bytes_in_use",
               "peak_bytes_reserved")
MEMORY_SLACK = 2 ** 20


def bytes_held(stats):
    """Bytes of one chip's memory held at the moment `stats` was read:
    the allocator's heap in use (parameters, optimizer state, the staged
    batch) plus what the runtime reserved for the loaded programs' own
    buffers (activations, temporaries), which on the v5e it takes from
    outside the heap.  Both of one reading, so nothing is counted at two
    moments."""
    return int(stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0))


def read_memory(devices, say):
    """Each chip's `memory_stats()` after the window, printed whole, so
    that every run's log carries the evidence for `bytes_held`: if the
    reservation lay inside the heap's `bytes_in_use`, the largest free
    block could pass `bytes_limit - bytes_in_use - bytes_reserved`.  A
    run in which it does is refused."""
    rows = []
    for d in devices:
        s = d.memory_stats() or {}
        rows.append(s)
        if not all(key in s for key in MEMORY_KEYS[:4]):
            say(f"memory device {d.id}: no allocator statistics")
            continue
        over = s["bytes_in_use"] + s["bytes_reserved"] \
            + s["largest_free_block_bytes"] - s["bytes_limit"]
        say(f"memory device {d.id}: "
            + " ".join(f"{key}={s.get(key)}" for key in MEMORY_KEYS)
            + f" in_use+reserved+largest_free-limit={over}")
        if over > MEMORY_SLACK:
            raise Refused(f"device {d.id}: bytes_in_use + bytes_reserved + "
                          f"largest_free_block_bytes pass bytes_limit by "
                          f"{over} B, so the two overlap and their sum is "
                          f"not what the chip holds")
    return rows


def profiled_stretch(variant, k, spans, say):
    """Profile `TRACE_BLOCKS` blocks of the reported variant and read
    the trace back.  The blocks are marked 'trace' and so are left out
    of the window's statistics."""
    import jax

    from benchmark import reduce

    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    phase = spans.phase
    try:
        variant.model.sync()
        jax.profiler.start_trace(logdir)
        spans.phase, spans.annotate = "trace", True
        try:
            with spans.span("bench.trace_window", variant.name):
                for _ in range(TRACE_BLOCKS):
                    run_block(variant, k, spans, say)
        finally:
            spans.phase, spans.annotate = phase, False
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise Refused("the profiler wrote no .xplane.pb")
        tr = reduce.load(paths[0])
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    window = reduce.span_window(tr, "bench.trace_window")
    if window is None:
        raise Refused("the trace does not hold the benchmark's own span")
    say(f"profiled {TRACE_BLOCKS} block(s) of {k} step(s)")
    return {"trace": tr, "trace_window": window,
            "trace_steps": TRACE_BLOCKS * k}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = p.parse_args(argv)

    cell = load_cell(ROOT, args.workload)
    # libtpu would log under /tmp, outside the checkout and TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise Refused(f"no TPU: JAX found platform={dev.platform} "
                      f"({dev.device_kind}); nothing was run")
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t0=_T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
