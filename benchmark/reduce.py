"""From a profiler trace to numbers: the one reduction every PR uses.

`load()` turns the `.xplane.pb` that `jax.profiler` writes into a plain
structure, and everything else works on that structure, so the
arithmetic is checked on small hand-made and recorded traces
(`tests/`) without a chip:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

What a v5e trace holds (looked at by hand, PR 24): one plane per chip
named `/device:TPU:<n>`, whose line `XLA Ops` has one event per executed
HLO instruction, named by the instruction's text (`%fusion.14 = bf16[...]
fusion(...)`), `XLA Modules` one event per executed program, and `Async
XLA Ops` one event per asynchronous copy or collective from its start
to its done.  The plane `/host:CPU` has a line `python` with the
process's `TraceAnnotation`s.  Device and host events share one clock.

Times are nanoseconds in the structure and seconds in every result.
"""

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = (r"all-reduce|all-gather|reduce-scatter|collective-permute"
              r"|all-to-all|collective-broadcast")


def op_label(name):
    """`%fusion.14 = bf16[..] fusion(..)` -> `fusion.14`: the
    instruction's own name, without its text."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


KIND = re.compile(r'kind=(\w+)|custom_call_target="([^"]+)"')


def op_kind(name):
    """The fusion kind or custom-call target in an instruction's text,
    or ''.  On the TPU backend `kOutput` marks a convolution or dot
    with what is fused into its output, `tpu_custom_call` a Pallas
    kernel."""
    m = KIND.search(name)
    return (m.group(1) or m.group(2)) if m else ""


def load(path):
    """Read an `.xplane.pb` into the plain structure above."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [{"name": line.name,
                  "events": [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                             for ev in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace):
    return sorted((p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def events(plane, line_name, window=None):
    """Events of one line of a plane, clipped to `window` = (t0, t1) ns."""
    out = []
    for line in plane["lines"]:
        if line["name"] != line_name:
            continue
        for name, start, dur in line["events"]:
            end = start + dur
            if window is not None:
                start, end = max(start, window[0]), min(end, window[1])
            if end > start:
                out.append((name, start, end))
    return out


def union(intervals):
    """Sorted, merged [(start, end)] of any (.., start, end) tuples."""
    merged = []
    for start, end in sorted((iv[-2], iv[-1]) for iv in intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def length(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of merged intervals `a` that merged intervals `b` do not
    cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        i = j
        while i < len(b) and b[i][0] < e:
            if b[i][0] > cur:
                out.append((cur, b[i][0]))
            cur = max(cur, b[i][1])
            i += 1
        if cur < e:
            out.append((cur, e))
    return out


def host_spans(trace, prefix="bench."):
    """The benchmark's own spans from the host's `python` line:
    [(name, start_ns, end_ns)]."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            out += [(n, s, s + d) for n, s, d in line["events"]
                    if n.startswith(prefix)]
    return sorted(out, key=lambda x: x[1])


def span_window(trace, name):
    """(start, end) ns of the first host span called `name`, or None."""
    for n, s, e in host_spans(trace, name):
        if n == name:
            return (s, e)
    return None


def busy_seconds(trace, window):
    """Seconds in which an operation ran on the device, averaged over
    the device planes, and the window's length in seconds."""
    planes = device_planes(trace)
    if not planes:
        return None, None
    busy = [length(union(events(p, OPS_LINE, window))) for p in planes]
    return sum(busy) / len(busy) / 1e9, (window[1] - window[0]) / 1e9


def pattern_seconds(trace, window, pattern, line=OPS_LINE):
    """Seconds of device time in events whose name matches `pattern`,
    averaged over the device planes, and how many events matched."""
    rx = re.compile(pattern)
    planes = device_planes(trace)
    if not planes:
        return None, 0
    total = count = 0
    for p in planes:
        for name, start, end in events(p, line, window):
            if rx.search(name):
                total += end - start
                count += 1
    return total / len(planes) / 1e9, count


def exposed_collective_seconds(trace, window, pattern=COLLECTIVE):
    """Seconds, averaged over the device planes, in which a collective
    was in flight or being waited for while nothing else ran on that
    device.  A collective is an event of `XLA Ops` or of `Async XLA Ops`
    (start to done) whose instruction name matches `pattern`; everything
    else on `XLA Ops` is compute."""
    rx = re.compile(pattern)
    planes = device_planes(trace)
    if not planes:
        return None, 0
    total = count = 0
    for p in planes:
        ops = events(p, OPS_LINE, window)
        coll = [ev for ev in ops if rx.search(op_label(ev[0]))]
        coll += [ev for ev in events(p, ASYNC_LINE, window)
                 if rx.search(op_label(ev[0]))]
        compute = [ev for ev in ops if not rx.search(op_label(ev[0]))]
        count += len(coll)
        total += length(subtract(union(coll), union(compute)))
    return total / len(planes) / 1e9, count


def top_device_ops(trace, window, n=10):
    """[[label, seconds]] of the instructions that took most device
    time, summed over their executions, averaged over the planes; the
    label is the instruction's name and, where it has one, its kind."""
    planes = device_planes(trace)
    totals = {}
    for p in planes:
        for name, start, end in events(p, OPS_LINE, window):
            label = "__".join(filter(None, (op_label(name), op_kind(name))))
            totals[label] = totals.get(label, 0) + (end - start)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / len(planes) / 1e9] for k, v in top]


def idle_gaps(trace, window, n=10, min_ns=20_000):
    """[[label, seconds]]: the first device's idle gaps of at least
    `min_ns`, grouped by the innermost benchmark span the host was in at
    the gap's middle; the label carries the count and the longest gap."""
    planes = device_planes(trace)
    if not planes:
        return []
    busy = union(events(planes[0], OPS_LINE, window))
    gaps = [g for g in subtract([tuple(window)], busy)
            if g[1] - g[0] >= min_ns]
    spans = host_spans(trace)
    groups = {}
    for s, e in gaps:
        mid = (s + e) // 2
        inside = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside \
            else "outside_spans"
        g = groups.setdefault(name, [0, 0, 0])
        g[0] += e - s
        g[1] += 1
        g[2] = max(g[2], e - s)
    top = sorted(groups.items(), key=lambda kv: -kv[1][0])[:n]
    return [[f"{k}__gaps_{c}__longest_ms_{longest / 1e6:.3f}", tot / 1e9]
            for k, (tot, c, longest) in top]
