"""On-chip cost-model calibration.

Closes the search-reality loop the reference closes inside its MCMC
search (reference: simulator.cc:235-273 — every candidate's per-op time
comes from running the REAL kernels, cached by (op, config) hash;
conv_2d.cu:937-1039 times cudnnFind*AlgorithmEx on the actual shapes).
On TPU a compile costs seconds, so instead of measuring inside the
annealing loop this tool measures the whole candidate sub-shape space
up-front on the real chip, persists the cache, and fits the roofline
constants (mxu_efficiency, HBM bandwidth, launch overhead, backward
multiplier) to the measurements so anything uncached is also calibrated.

Usage (on a machine with the TPU attached):
    python -m flexflow_tpu.tools.calibrate \
        --out flexflow_tpu/simulator/measured_v5e.json \
        --fit-out flexflow_tpu/simulator/machine_v5e.json

Produces/updates:
  * measured_v5e.json — the durable (op type, sub-shape, dtype) → seconds
    cache every search consumes (CostModel reads it by default);
  * machine_v5e.json — fitted TPUMachineModel overrides.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from typing import Dict, List, Optional, Tuple


def _model(name: str, batch_size: int, nd: int):
    from .offline_search import build_model

    return build_model(name, batch_size, nd)


def candidate_jobs(model, nd: int, cost, full: bool) -> List[Tuple]:
    """(op, pc, which) jobs, deduped by cache key.  ``full`` enumerates
    the whole SOAP candidate space (what the search will cost);
    otherwise only the data-parallel configs at nd and 1 device."""
    from ..config import ParallelConfig
    from ..simulator.native_search import enumerate_candidates

    jobs, seen = [], set()

    def add(op, pc):
        pc = op.legalize_pc(pc)
        for which in ("forward", "backward"):
            key = cost._key(op, pc, which)
            if key not in seen and key not in cost._measured:
                seen.add(key)
                jobs.append((op, pc, which, key))

    for op in model.ops:
        if full:
            for pc in enumerate_candidates(op, nd):
                add(op, pc)
        else:
            for parts in {nd, 1}:
                pc = ParallelConfig.data_parallel(op.output.num_dims, parts)
                add(op, pc.with_device_ids(tuple(range(parts))))
    return jobs


def _beat(heartbeat_path: Optional[str], key, i) -> None:
    if not heartbeat_path:
        return
    try:
        # atomic replace: the supervisor polls concurrently and a torn
        # read must never masquerade as a wedged worker
        tmp = heartbeat_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"key": key, "i": i, "t": time.time()}, f)
        os.replace(tmp, heartbeat_path)
    except OSError:
        pass


def measure_host_transfer(cost, verbose: bool = True,
                          heartbeat_path: Optional[str] = None,
                          skip_keys: Optional[set] = None) -> int:
    """Measure the effective host<->device transfer rate over a size
    ladder — the constant the host-resident-embedding cost path prices
    as ``pcie_bandwidth``.  The MEASURED number (not the PCIe spec
    sheet) is the honest input; per-direction time = round-trip / 2,
    and the ladder's slope/intercept separate bandwidth from
    per-transfer latency (fit_host_transfer)."""
    import jax
    import numpy as np

    skip_keys = skip_keys or set()
    done = 0
    for nbytes in (1 << 20, 8 << 20, 64 << 20):
        key = f"host_xfer:{nbytes}"
        if key in cost._measured or key in skip_keys:
            continue
        _beat(heartbeat_path, key, -1)
        arr = np.ones((nbytes // 4,), np.float32)
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            d = jax.device_put(arr)
            np.asarray(jax.device_get(d))  # forces both directions
            ts.append(time.perf_counter() - t0)
        t = float(np.median(ts)) / 2.0  # seconds per direction
        cost._measured[key] = t
        cost._persist(key, t)
        done += 1
        if verbose:
            print(f"[calibrate] {key} -> {t * 1e3:.2f} ms/direction "
                  f"({nbytes / t / 1e9:.2f} GB/s)", flush=True)
    # null done-sentinel: the worker's final beat must never be a real
    # key, or a slow backend teardown reads as that job having hung and
    # the supervisor kills/excludes/restarts for nothing
    _beat(heartbeat_path, None, -1)
    return done


def fit_host_transfer(cost) -> dict:
    """Least-squares t = latency + bytes/bw over the host_xfer ladder;
    returns machine-model overrides ({} when unmeasured)."""
    import numpy as np

    pts = sorted((int(k.split(":")[1]), t)
                 for k, t in cost._measured.items()
                 if k.startswith("host_xfer:"))
    if len(pts) < 2:
        return {}
    x = np.array([p[0] for p in pts], float)
    y = np.array([p[1] for p in pts], float)
    A = np.vstack([np.ones_like(x), x]).T
    (lat, slope), *_ = np.linalg.lstsq(A, y, rcond=None)
    if slope <= 0:
        return {}
    return {"pcie_bandwidth": float(1.0 / slope),
            "host_xfer_latency": float(max(0.0, lat))}


def run_measurements(jobs, cost, max_seconds: float, verbose: bool,
                     heartbeat_path: Optional[str] = None,
                     skip_keys: Optional[set] = None) -> int:
    """Measure every job (worker side — no in-process watchdog).

    A device that stops answering hangs this process inside a blocking
    C++ wait where Python signal handlers can never fire, so the
    watchdog lives in the SUPERVISING process (``supervise_worker``,
    which never touches JAX itself): before each job
    this loop writes a heartbeat record; the supervisor kills this
    whole process when a heartbeat goes stale and restarts it with the
    stuck key excluded.  Every finished measurement is already persisted
    by ``CostModel._persist``, so a kill loses at most the in-flight job."""
    done = 0
    t_start = time.time()
    skip_keys = skip_keys or set()

    def beat(key, i):
        _beat(heartbeat_path, key, i)

    for i, (op, pc, which, key) in enumerate(jobs):
        if time.time() - t_start > max_seconds:
            print(f"[calibrate] time budget hit after "
                  f"{done}/{len(jobs)} jobs", flush=True)
            break
        if key in skip_keys:
            print(f"[{i + 1}/{len(jobs)}] {key} SKIPPED "
                  f"(hung a previous attempt)", flush=True)
            continue
        beat(key, i)
        t = cost.op_time(op, pc, which)
        done += 1
        if verbose:
            src = ("measured" if key in cost._measured
                   else "ANALYTIC(fallback)")
            print(f"[{i + 1}/{len(jobs)}] {key} -> {t * 1e6:.1f} us "
                  f"[{src}]", flush=True)
    beat(None, len(jobs))
    return done


def supervise_worker(argv: List[str], job_timeout: float,
                     max_restarts: int = 2,
                     max_seconds: float = 3600.0) -> int:
    """Parent-side watchdog (a Python alarm can't interrupt a blocked
    device wait, but SIGKILL-ing a subprocess always works).  Spawns
    ``calibrate --worker``; when the per-job heartbeat goes stale past
    ``job_timeout`` — or the worker never produces its FIRST beat within
    the startup deadline (a hang inside backend init comes before any
    job) — the worker is killed, the in-flight key is excluded, and the
    worker restarts (resuming from the durable cache).  A global wall
    budget bounds the whole supervision.  Returns the last worker
    returncode.

    A chip belongs to one process at a time, and here that process is
    the worker: this parent must never initialise a JAX backend — not
    before the worker starts, not while it runs — or the worker finds
    the chip taken.  It imports nothing from jax; the fit it runs after
    the last worker has exited is arithmetic over the cache file."""
    import subprocess
    import tempfile

    hb = tempfile.NamedTemporaryFile(prefix="ffcal_hb_", suffix=".json",
                                     delete=False)
    hb.close()
    skipfile = tempfile.NamedTemporaryFile(prefix="ffcal_skip_",
                                           suffix=".txt", delete=False)
    skipfile.close()
    cmd = [sys.executable, "-m", "flexflow_tpu.tools.calibrate",
           "--worker", "--heartbeat", hb.name,
           "--skip-keys-file", skipfile.name] + argv
    # backend init + imports + job-list build take a while; only a
    # deadline well past that means the worker is stuck
    startup_timeout = max(job_timeout, 420.0)
    t_global = time.time()
    try:
        for attempt in range(max_restarts + 1):
            # reset the heartbeat so the previous attempt's stale record
            # can't get the fresh worker killed at its first poll
            with open(hb.name, "w"):
                pass
            t_spawn = time.time()
            proc = subprocess.Popen(cmd)
            stuck_key = None
            measuring_done = False  # saw the worker's {"key": null} sentinel
            while True:
                try:
                    rc = proc.wait(timeout=5.0)
                    if rc != 0:
                        print(f"[calibrate] worker exited rc={rc}",
                              flush=True)
                    return rc
                except subprocess.TimeoutExpired:
                    pass
                if time.time() - t_global > max_seconds:
                    print("[calibrate] global wall budget exhausted — "
                          "killing worker, keeping measurements so far",
                          flush=True)
                    proc.kill()
                    proc.wait()
                    return 1
                try:
                    with open(hb.name) as f:
                        beat = json.load(f)
                except (OSError, ValueError):
                    beat = None
                if beat and beat.get("key"):
                    if time.time() - beat["t"] > job_timeout:
                        stuck_key = beat["key"]
                        print(f"[calibrate] job hung >{job_timeout:.0f}s "
                              f"({stuck_key}) — killing worker (attempt "
                              f"{attempt + 1}/{max_restarts + 1})",
                              flush=True)
                        proc.kill()
                        proc.wait()
                        break
                elif beat is not None and beat.get("key", "") is None:
                    # measurement loop finished; backend shutdown may
                    # take a while — never kill for it
                    measuring_done = True
                elif not measuring_done \
                        and time.time() - t_spawn > startup_timeout:
                    # no first beat: wedged before the job loop started
                    print(f"[calibrate] worker produced no heartbeat in "
                          f"{startup_timeout:.0f}s (backend init wedged?) "
                          f"— killing (attempt "
                          f"{attempt + 1}/{max_restarts + 1})", flush=True)
                    proc.kill()
                    proc.wait()
                    break
            if stuck_key:
                with open(skipfile.name, "a") as f:
                    f.write(stuck_key + "\n")
            if attempt == max_restarts:
                print("[calibrate] restart budget exhausted — keeping the "
                      "measurements persisted so far", flush=True)
        return 1
    finally:
        for p in (hb.name, skipfile.name):
            try:
                os.unlink(p)
            except OSError:
                pass


def collect_fit_records(models, nds, cost) -> List[Dict]:
    """(flops, bytes, measured fwd/bwd seconds) per measured key."""
    import numpy as np

    from ..simulator.native_search import enumerate_candidates

    recs, seen = [], set()
    for model, nd in zip(models, nds):
        for op in model.ops:
            for pc in enumerate_candidates(op, nd):
                pc = op.legalize_pc(pc)
                sub = cost._sub_output_shape(op, pc)
                kf = cost._key(op, pc, "forward")
                kb = cost._key(op, pc, "backward")
                if kf in seen or kf not in cost._measured:
                    continue
                seen.add(kf)
                scale = np.prod(sub) / max(1, np.prod(op.outputs[0].dims))
                flops = op.flops_per_sample() * op.outputs[0].dims[0] * scale
                in_vol = sum(int(np.prod([hi - lo + 1 for lo, hi
                                          in op.input_ranges(j, pc, 0)]))
                             for j in range(len(op.inputs)))
                w_vol = sum(int(np.prod([hi - lo + 1 for lo, hi
                                         in op.weight_tile(pc, wi, 0)]))
                            for wi in range(len(op.weights)))
                out_vol = int(np.prod(sub))
                recs.append({
                    "key": kf,
                    "op": type(op).__name__,
                    "flops": float(flops),
                    "bytes": cost._dtype_bytes * (in_vol + w_vol + out_vol),
                    "t_fwd": cost._measured[kf],
                    "t_bwd": cost._measured.get(kb),
                })
    return recs


def fit_machine(recs: List[Dict], machine) -> Dict[str, float]:
    """Grid-fit roofline constants minimizing squared log-ratio error of
    ``max(flops/(peak·eff), bytes/(hbm·hbm_eff)) + ovh`` vs measured."""
    import numpy as np

    if not recs:
        return {}
    flops = np.array([r["flops"] for r in recs])
    byts = np.array([r["bytes"] for r in recs])
    meas = np.array([r["t_fwd"] for r in recs])

    best = (None, math.inf)
    for eff in np.arange(0.05, 1.001, 0.01):
        for hbm_eff in np.arange(0.3, 1.001, 0.05):
            for ovh in (1e-6, 2e-6, 4e-6, 8e-6, 16e-6, 32e-6, 64e-6):
                pred = np.maximum(flops / (machine.peak_flops * eff),
                                  byts / (machine.hbm_bandwidth * hbm_eff)) + ovh
                err = float(np.mean(np.log(pred / meas) ** 2))
                if err < best[1]:
                    best = ((float(eff), float(hbm_eff), float(ovh)), err)
    (eff, hbm_eff, ovh), err = best
    ratios = [r["t_bwd"] / r["t_fwd"] for r in recs
              if r["t_bwd"] and r["t_fwd"] > 0]
    bwd_mult = float(np.median(ratios)) if ratios else 2.0
    # Per-family refinement, holding the global memory constants: one
    # global MXU efficiency cannot describe conv im2col, LSTM scan
    # steps, and gather-bound ops at once.  Families with too few points
    # keep the global constants.
    op_eff: Dict[str, float] = {}
    op_bwd: Dict[str, float] = {}
    fams: Dict[str, List[Dict]] = {}
    for r in recs:
        fams.setdefault(r.get("op", "?"), []).append(r)
    for fam, rs in fams.items():
        if len(rs) < 3:
            continue
        ff = np.array([r["flops"] for r in rs])
        fb = np.array([r["bytes"] for r in rs])
        fm = np.array([r["t_fwd"] for r in rs])

        def _fam_err(e):
            pred = np.maximum(ff / (machine.peak_flops * e),
                              fb / (machine.hbm_bandwidth * hbm_eff)) + ovh
            return float(np.mean(np.log(pred / fm) ** 2))

        # Seeded with the GLOBAL efficiency's error: a family whose
        # shapes are all memory-bound has a flat error surface, and a
        # strict grid argmin would record the grid floor (0.05) — such
        # families must keep the global constant instead.
        fbest = (eff, _fam_err(eff))
        for e in np.arange(0.05, 1.001, 0.01):
            e_err = _fam_err(e)
            if e_err < fbest[1]:
                fbest = (float(e), e_err)
        # Only families the grid actually identified get an entry: a
        # kept-global seed written out would pin the family to a STALE
        # snapshot of the global after later refits shift it (the
        # never-erase merge preserves old entries deliberately).
        if fbest[0] != eff:
            op_eff[fam] = fbest[0]
        fr = [r["t_bwd"] / r["t_fwd"] for r in rs
              if r["t_bwd"] and r["t_fwd"] > 0]
        # same minimum-sample bar as the efficiency fit: one noisy
        # backward ratio must not override the robust global median
        if len(fr) >= 3:
            op_bwd[fam] = float(np.median(fr))

    op_types = sorted(fams)
    fit = {
        "mxu_efficiency": eff,
        "hbm_bandwidth": machine.hbm_bandwidth * hbm_eff,
        "kernel_launch_overhead": ovh,
        "backward_multiplier": bwd_mult,
        "op_efficiency": op_eff,
        "op_backward_multiplier": op_bwd,
        "fit_log_rmse": math.sqrt(err),
        "fit_points": len(recs),
        "fit_op_types": op_types,
    }
    from .report_configs import THIN_FIT_OP_TYPES, THIN_FIT_POINTS

    if len(recs) < THIN_FIT_POINTS or len(op_types) < THIN_FIT_OP_TYPES:
        # A thin basis (e.g. one conv family from a short window) still
        # beats dataclass defaults, but its constants extrapolate — say
        # so wherever the fit is consumed (reports echo these fields).
        print(f"[calibrate] WARNING: thin fit basis — {len(recs)} points "
              f"over op types {op_types}; constants extrapolate to "
              "unmeasured op families until more windows land",
              flush=True)
    return fit


def build_job_list(cost, devices: int, alexnet_batch: int, bench_batch: int,
                   models_csv: str, report_batch: Optional[int],
                   inception: bool, inception_jobs: int, fit_only: bool):
    """Measurement jobs ordered for short chip calls, plus the (models,
    nds) lists the roofline fit enumerates records over.

    Chip time is budgeted, so a run is often only a few minutes:
    single-chip bench shapes lead (they are the
    agreement check AND the fit's anchor points), then every report
    model's SOAP candidate space + the Inception spread runs
    cheapest-analytic-first — small shapes compile and run fastest,
    landing the most fit points per minute, and the fitted roofline
    covers whatever a short window leaves unmeasured.  ``fit_only``
    skips job enumeration but still builds the model list (including
    the legacy batch-1024 AlexNet space, so the first converted
    window's cache entries keep feeding every refit)."""
    from .report_configs import REPORT_DEVICES, REPORT_GLOBAL_BATCH

    models, nds = [], []
    mb = _model("alexnet", bench_batch, 1)
    models.append(mb)
    nds.append(1)
    jobs = [] if fit_only else candidate_jobs(mb, 1, cost, full=False)
    rest = []
    wanted = [s.strip() for s in models_csv.split(",") if s.strip()]
    for name in wanted:
        if name == "alexnet":
            bs = alexnet_batch
        elif report_batch is not None:
            bs = report_batch
        else:
            bs = REPORT_GLOBAL_BATCH.get(name, 1024)
        mr = _model(name, bs, devices)
        models.append(mr)
        nds.append(devices)
        if not fit_only:
            rest += candidate_jobs(mr, devices, cost, full=True)
    if "alexnet" in wanted and alexnet_batch != 1024:
        # Fit-records only (never measured): the first converted window
        # (round 5) cached batch-1024 alexnet shapes; enumerate that
        # space too so those points keep feeding every future refit.
        models.append(_model("alexnet", 1024, devices))
        nds.append(devices)
    if inception:
        mi = _model("inception", bench_batch, devices)
        models.append(mi)
        nds.append(devices)
        if not fit_only:
            ijobs = candidate_jobs(mi, devices, cost, full=False)
            if inception_jobs and len(ijobs) > inception_jobs:
                # Even subsample: Inception entries feed the roofline fit
                # and spot-checks, not the AlexNet SOAP search — a spread
                # of its 94 conv shapes is enough (the fitted analytic
                # covers the rest).
                stride = max(1, len(ijobs) // inception_jobs)
                ijobs = ijobs[::stride][:inception_jobs]
            rest += ijobs
    rest.sort(key=lambda j: cost._analytic(j[0], j[1], j[2]))
    # Front the keys the SOAP reports actually price (report_keys.json,
    # written by soap_report on every run): a window lands ~60 of the
    # ~654 jobs, and these are the ones that raise each report's
    # measured-provenance count instead of landing at random.  Both
    # partitions stay cheapest-analytic-first.
    from .report_configs import report_keys_path

    keys_path = report_keys_path()
    try:
        with open(keys_path) as f:
            raw = json.load(f)
        # entries are {"devices": N, "batch": B, "keys": [...]} (legacy
        # plain lists accepted, scale assumed canonical)
        keys_by_model = {
            name: (e if isinstance(e, dict) else
                   {"devices": REPORT_DEVICES.get(name), "batch": None,
                    "keys": e})
            for name, e in raw.items()}
    except Exception as e:
        print(f"[calibrate] no report-key priority hints ({keys_path}: "
              f"{e!r}) — job order falls back to cheapest-analytic-first")
        keys_by_model = {}
    if keys_by_model:
        # Models whose report scale is not enumerated above (either not
        # in --models at all, or in it at a DIFFERENT device count /
        # batch than the report prices — shard-shape keys only match at
        # the same scale) get TARGETED jobs: exactly the keys their
        # reports price, nothing else, so "simulation-only at report
        # scale" becomes measurable without ballooning the job space.
        # Their models also join the fit-record enumeration so landed
        # measurements feed the per-family roofline refits.
        from ..simulator.native_search import enumerate_candidates

        targeted = []
        seen = {j[3] for j in jobs} | {j[3] for j in rest}
        for name, entry in keys_by_model.items():
            nd_r = entry.get("devices") or REPORT_DEVICES.get(name,
                                                              devices)
            b_r = entry.get("batch") or REPORT_GLOBAL_BATCH.get(name,
                                                                1024)
            if name in wanted:
                enum_b = (alexnet_batch if name == "alexnet"
                          else (report_batch if report_batch is not None
                                else REPORT_GLOBAL_BATCH.get(name, 1024)))
                if devices == nd_r and enum_b == b_r:
                    continue  # enumerated space already matches the hint
            try:
                mt = _model(name, b_r, nd_r)
            except Exception:
                continue
            models.append(mt)
            nds.append(nd_r)
            if fit_only:
                continue
            kset = set(entry.get("keys") or [])
            for op in mt.ops:
                for pc in enumerate_candidates(op, nd_r):
                    pc = op.legalize_pc(pc)
                    for which in ("forward", "backward"):
                        key = cost._key(op, pc, which)
                        if (key in kset and key not in seen
                                and key not in cost._measured):
                            seen.add(key)
                            targeted.append((op, pc, which, key))
        prio_keys = set()
        for entry in keys_by_model.values():
            prio_keys.update(entry.get("keys") or [])
        priority = [j for j in rest if j[3] in prio_keys] + targeted
        priority.sort(key=lambda j: cost._analytic(j[0], j[1], j[2]))
        rest = priority + [j for j in rest if j[3] not in prio_keys]
    return jobs + rest, models, nds


def main(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--devices", type=int, default=16,
                   help="machine size the search will target")
    from .report_configs import BENCH_SINGLE_CHIP_BATCH, REPORT_GLOBAL_BATCH

    p.add_argument("--alexnet-batch", type=int,
                   default=REPORT_GLOBAL_BATCH["alexnet"],
                   help="global batch for the 16-chip AlexNet candidate "
                        "space — shared default with soap_report "
                        "(report_configs.py); a mismatch zeroes the "
                        "report's measured provenance")
    p.add_argument("--bench-batch", type=int,
                   default=BENCH_SINGLE_CHIP_BATCH,
                   help="single-chip bench batch (measured for the "
                        "sim-vs-measured agreement check)")
    p.add_argument("--models", default="alexnet,dlrm,nmt",
                   help="comma list of models whose FULL SOAP candidate "
                        "space is measured (the shapes the soap_report "
                        "strategies price — matching configs is what "
                        "makes measured provenance possible)")
    p.add_argument("--report-batch", type=int, default=None,
                   help="override the global batch for every non-alexnet "
                        "candidate space (default: each model's entry in "
                        "report_configs.py, shared with soap_report)")
    p.add_argument("--inception", action="store_true", default=True)
    p.add_argument("--no-inception", dest="inception", action="store_false")
    p.add_argument("--inception-jobs", type=int, default=48,
                   help="subsample the Inception DP job list to this many")
    p.add_argument("--compute-dtype", default="bfloat16")
    p.add_argument("--out", default=None,
                   help="measured cache path (default: the packaged "
                        "measured_v5e.json)")
    p.add_argument("--fit-out", default=None,
                   help="fitted machine params path (default: packaged "
                        "machine_v5e.json)")
    p.add_argument("--max-seconds", type=float, default=3600.0)
    p.add_argument("--fit-only", action="store_true",
                   help="skip measuring; refit the roofline from the "
                        "TPU-tagged entries already in the cache "
                        "(touches no backend — e.g. after a calibration "
                        "run was cut short)")
    p.add_argument("--job-timeout", type=float, default=240.0,
                   help="supervisor kills the measuring worker if one "
                        "job's heartbeat goes stale this long")
    p.add_argument("--max-restarts", type=int, default=2)
    p.add_argument("--no-supervise", action="store_true",
                   help="measure in-process (no watchdog — a device "
                        "that stops answering hangs this process)")
    p.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--heartbeat", default=None, help=argparse.SUPPRESS)
    p.add_argument("--skip-keys-file", default=None, help=argparse.SUPPRESS)
    p.add_argument("--quiet", action="store_true")
    args = p.parse_args(argv)

    if not (args.fit_only or args.worker or args.no_supervise):
        # Supervisor mode: ALL device work happens in a killable worker
        # subprocess, the one process that holds the chip; this parent
        # stays off JAX (see supervise_worker) and afterwards fits from
        # the durable cache.
        fwd = []
        for flag, val in (("--devices", args.devices),
                          ("--alexnet-batch", args.alexnet_batch),
                          ("--bench-batch", args.bench_batch),
                          ("--models", args.models),
                          ("--report-batch", args.report_batch),
                          ("--inception-jobs", args.inception_jobs),
                          ("--compute-dtype", args.compute_dtype),
                          ("--max-seconds", args.max_seconds)):
            if val is not None:
                fwd += [flag, str(val)]
        if not args.inception:
            fwd.append("--no-inception")
        if args.out:
            fwd += ["--out", args.out]
        if args.quiet:
            fwd.append("--quiet")
        supervise_worker(fwd, args.job_timeout, args.max_restarts,
                         max_seconds=args.max_seconds + 900.0)
        args.fit_only = True  # fall through to the CPU-side fit below

    from ..simulator import cost_model as cm
    from ..simulator.machine import CALIBRATION_PATH, TPUMachineModel

    out = args.out or cm.MEASURED_CACHE
    fit_out = args.fit_out or CALIBRATION_PATH
    # --fit-only is arithmetic over the cache and asks JAX for nothing,
    # which is what lets the supervising parent run it
    platform = None
    if not args.fit_only:
        import jax

        platform = jax.default_backend()
    if platform != "tpu" and not args.fit_only:
        print(f"[calibrate] WARNING: measuring on {platform!r}, not TPU — "
              "entries will be tagged accordingly and ignored by searches "
              "targeting TPU")

    mm = TPUMachineModel(num_devices=args.devices)
    cost = cm.CostModel(mm, measure=not args.fit_only, cache_path=out,
                        compute_dtype=args.compute_dtype,
                        measured_cache_path=out,
                        target_platform="tpu" if args.fit_only else platform)

    jobs, models, nds = build_job_list(
        cost, devices=args.devices, alexnet_batch=args.alexnet_batch,
        bench_batch=args.bench_batch, models_csv=args.models,
        report_batch=args.report_batch, inception=args.inception,
        inception_jobs=args.inception_jobs, fit_only=args.fit_only)

    if args.fit_only:
        print("[calibrate] --fit-only: skipping measurement, refitting "
              "from the cached TPU entries")
    else:
        print(f"[calibrate] {len(jobs)} measurement jobs "
              f"(cache: {len(cost._measured)} entries pre-loaded)",
              flush=True)
        skip = set()
        if args.skip_keys_file and os.path.exists(args.skip_keys_file):
            with open(args.skip_keys_file) as f:
                skip = {ln.strip() for ln in f if ln.strip()}
        # ladder first: it is seconds of work, uniquely valuable (the
        # host-embedding path prices the measured host-link rate, not
        # the PCIe spec sheet), and must not sit behind an hour of op
        # jobs
        measure_host_transfer(cost, verbose=not args.quiet,
                              heartbeat_path=args.heartbeat,
                              skip_keys=skip)
        run_measurements(jobs, cost, args.max_seconds,
                         verbose=not args.quiet,
                         heartbeat_path=args.heartbeat, skip_keys=skip)
        if args.worker:
            # fit happens in the supervising parent, from the cache
            print(f"[calibrate] worker done: {len(cost._measured)} "
                  f"entries -> {out}", flush=True)
            return

    recs = collect_fit_records(models, nds, cost)
    fit = fit_machine(recs, mm)
    # the host-transfer ladder fits independently of the roofline — a
    # run cut short during op jobs that finished the ladder still lands
    # the measured host-link rate
    hx = fit_host_transfer(cost)
    merged = {**fit, **hx}
    if merged and platform != "tpu" and not args.fit_only \
            and args.fit_out is None:
        # Never let a CPU-host dry run overwrite the packaged TPU fit —
        # TPUMachineModel.calibrated() has no platform filter of its own.
        print(f"[calibrate] NOT writing machine fit: measured on "
              f"{platform!r}; pass --fit-out explicitly to keep it")
        merged, fit, hx = {}, {}, {}
    if merged:
        # merge over any existing fit so a ladder-only window never
        # erases an earlier full roofline fit (and vice versa)
        prev = {}
        if os.path.exists(fit_out):
            try:
                with open(fit_out) as f:
                    prev = json.load(f)
            except Exception:
                prev = {}
        # per-key merge for the per-family dicts: a refit whose record
        # enumeration no longer covers an earlier family must not erase
        # that family's fitted constants
        for dk in ("op_efficiency", "op_backward_multiplier"):
            if dk in prev or dk in merged:
                merged[dk] = {**prev.get(dk, {}), **merged.get(dk, {})}
        merged = {**prev, **merged}
        # atomic: a kill mid-write must not truncate the machine fit
        # (same rationale as CostModel._persist)
        tmp = f"{fit_out}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(merged, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, fit_out)
        pcie = (f" pcie={merged['pcie_bandwidth'] / 1e9:.1f}GB/s"
                if "pcie_bandwidth" in merged else "")
        if fit:
            print(f"[calibrate] fitted over {fit['fit_points']} points "
                  f"(log-rmse {fit['fit_log_rmse']:.3f}): "
                  f"mxu_eff={fit['mxu_efficiency']:.2f} "
                  f"hbm={fit['hbm_bandwidth'] / 1e9:.0f}GB/s "
                  f"ovh={fit['kernel_launch_overhead'] * 1e6:.0f}us "
                  f"bwd_mult={fit['backward_multiplier']:.2f}{pcie} "
                  f"-> {fit_out}")
        else:
            print(f"[calibrate] roofline unfitted (no op records); "
                  f"host-transfer fit landed:{pcie} -> {fit_out}")
    print(f"[calibrate] measured cache: {len(cost._measured)} entries -> {out}")

    if not args.worker:
        # One perf-log entry per calibration session: doctor's "perf"
        # section reads the measurement trajectory from here.  Never
        # fatal.
        try:
            from . import perf_ledger

            entry = {"kind": "calibration",
                     "backend": platform or "none (fit only)",
                     "entries": len(cost._measured),
                     "fit_only": bool(args.fit_only), "cache": out}
            if fit:
                entry["fit_points"] = fit.get("fit_points")
                entry["fit_log_rmse"] = fit.get("fit_log_rmse")
            perf_ledger.append_entry(entry)
        except Exception as e:  # noqa: BLE001
            print(f"[calibrate] ledger append failed: "
                  f"{type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
