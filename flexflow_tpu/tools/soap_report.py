"""SOAP-vs-data-parallel report generator.

The framework's reason to exist (BASELINE.json north star): SOAP-searched
per-op strategies beating pure data parallelism on a pod.  This tool runs
the search for a model over a simulated v5e machine using the measured
(on-chip, tools/calibrate.py) + calibrated-roofline cost model, and emits:

  * a strategy protobuf (``--export``) loadable via --import-strategy,
  * ``REPORT_SOAP.md`` — DP vs searched simulated step time, the per-op
    strategy table, cost-model provenance (how many entries measured on
    the real chip vs analytic), and the single-chip simulated-vs-measured
    agreement check when a wall-clock number is supplied.

Usage:
    python -m flexflow_tpu.tools.soap_report alexnet --devices 16 \
        --batch-size 1024 --budget 4000 \
        --export strategies/alexnet_16.pb --out REPORT_SOAP.md \
        --measured-single-chip-ms 12.8   # bench-measured, optional
"""

from __future__ import annotations

import argparse
import json
from typing import List, Optional


def main(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("model", default="alexnet", nargs="?")
    p.add_argument("--devices", type=int, default=16)
    p.add_argument("--batch-size", type=int, default=None,
                   help="global batch (default: the per-model config in "
                        "report_configs.py, shared with calibrate so "
                        "measured cache keys match priced shapes)")
    p.add_argument("--budget", type=int, default=None,
                   help="annealing iterations per restart (default: the "
                        "per-model entry in report_configs.py)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--restarts", type=int, default=None,
                   help="independent annealing restarts (seeds seed.."
                        "seed+N-1); the best plan is kept (default: "
                        "report_configs.SEARCH_RESTARTS)")
    from .report_configs import REPORT_COMPUTE_DTYPE
    p.add_argument("--compute-dtype", default=REPORT_COMPUTE_DTYPE)
    p.add_argument("--export", default=None)
    p.add_argument("--out", default="REPORT_SOAP.md")
    p.add_argument("--measured-single-chip-ms", type=float, default=None,
                   help="wall-clock ms/step of the single-chip config on "
                        "the chip, for the agreement check")
    from .report_configs import BENCH_SINGLE_CHIP_BATCH

    p.add_argument("--single-chip-batch", type=int,
                   default=BENCH_SINGLE_CHIP_BATCH)
    args = p.parse_args(argv)
    from .report_configs import (REPORT_GLOBAL_BATCH, SEARCH_BUDGET,
                                 SEARCH_BUDGET_DEFAULT, SEARCH_RESTARTS)
    if args.batch_size is None:
        args.batch_size = REPORT_GLOBAL_BATCH.get(args.model, 1024)
    if args.budget is None:
        args.budget = SEARCH_BUDGET.get(args.model, SEARCH_BUDGET_DEFAULT)
    if args.restarts is None:
        args.restarts = SEARCH_RESTARTS
    args.restarts = max(1, args.restarts)

    from ..config import ParallelConfig
    from ..parallel.strategy import save_strategies_to_file
    from ..simulator.cost_model import CostModel
    from ..simulator.machine import TPUMachineModel
    from ..simulator.native_search import native_mcmc_search
    from ..simulator.search import mcmc_search
    from ..simulator.simulator import Simulator
    from .offline_search import build_model

    model = build_model(args.model, args.batch_size, args.devices)
    model.config.compute_dtype = args.compute_dtype
    mm = TPUMachineModel.calibrated(num_devices=args.devices)
    cost = CostModel(mm, measure=False, compute_dtype=args.compute_dtype)
    sim = Simulator(mm, cost)

    dp = {op.name: ParallelConfig.data_parallel(op.output.num_dims,
                                                args.devices)
          .with_device_ids(tuple(range(args.devices)))
          for op in model.ops}
    dp_rt = sim.simulate_runtime(model, dp)

    # Multi-restart annealing: independent seeds explore different
    # basins and the variance across them is large (measured ~4.4-5.2x
    # on alexnet@16 at the same budget); keep the best plan.  The
    # native engine makes restarts nearly free (~seconds each).
    best = None
    best_rt = float("inf")
    engine = "native (C++ annealing)"
    for rs in range(args.restarts):
        cand = None
        r = native_mcmc_search(model, budget=args.budget, machine_model=mm,
                               seed=args.seed + rs, verbose=False)
        if r is not None:
            cand = r[0]
        if cand is None:
            # The python engine's delta simulator closed most of the gap
            # to native (~20x cheaper per proposal than the old full
            # rebuild), but a native-sized budget is still an order of
            # magnitude slower than C — cap it (and say so in the
            # report).  The cap is 4x the old one, same wall clock.
            py_budget = min(args.budget, 4 * SEARCH_BUDGET_DEFAULT)
            engine = f"python MCMC (budget capped at {py_budget})"
            cand = mcmc_search(model, budget=py_budget, machine_model=mm,
                               measure=False, seed=args.seed + rs,
                               verbose=False)
        cand_rt = sim.simulate_runtime(model, cand)
        if cand_rt < best_rt:
            best, best_rt = cand, cand_rt
    speedup = dp_rt / best_rt if best_rt > 0 else float("inf")

    # the OTHER searched space: GPipe stage assignment
    from ..simulator.pipeline_search import search_pipeline

    pipe_plan = search_pipeline(model, machine_model=mm)

    # hetero host-embedding plan (reference dlrm_strategy_hetero.cc):
    # tables host-resident ROW-SPARSE, everything else data-parallel
    # gate on the same eligibility predicate the runtime enforces —
    # host-placing an ineligible table would price the row-sparse path
    # for a plan that actually executes as full-table streaming
    het_rt = None
    het_pipe = None
    eligible = getattr(model, "_sparse_embed_candidate_ok",
                       lambda _: False)
    elig = {op.name for op in model.ops
            if op._type == "Embedding" and eligible(op)}
    if elig:
        het = {op.name: (ParallelConfig.host_rowsparse(op.output.num_dims)
                         if op.name in elig else dp[op.name])
               for op in model.ops}
        het_rt = sim.simulate_runtime(model, het)
        # the COMBINED layout the runtime executes as a hetero head:
        # host tables ahead of a GPipe ring over the dense rest — built
        # on a twin model whose config carries the host placements, so
        # search_pipeline's intended-placement hoist fires
        mh = build_model(args.model, args.batch_size, args.devices)
        mh.config.compute_dtype = args.compute_dtype
        rank_of = {op.name: op.output.num_dims for op in model.ops}
        for name in elig:
            mh.config.strategies[name] = \
                ParallelConfig.host_rowsparse(rank_of[name])
        het_pipe = search_pipeline(mh, machine_model=mm)
        if het_pipe is not None and pipe_plan is not None \
                and het_pipe == pipe_plan:
            # hoist didn't change the plan — don't print a duplicate
            # row claiming tables were hoisted
            het_pipe = None

    # provenance: how much of the final strategies' costs are measured
    prov_cost = CostModel(mm, measure=False,
                          compute_dtype=args.compute_dtype)
    for op in model.ops:
        for which in ("forward", "backward"):
            prov_cost.op_time(op, best[op.name], which)
            prov_cost.op_time(op, dp[op.name], which)
    measured = prov_cost.stats["measured_hits"]
    analytic = prov_cost.stats["analytic"]

    # Publish the exact cache keys this report prices (best + DP, both
    # directions) so the next calibration window measures THESE first:
    # the candidate space is ~776 jobs and a wedge-prone window lands
    # ~60, so without a priority hint the report's measured-provenance
    # count climbs at random.  Merged per model with the pricing scale
    # recorded; consumed by calibrate.build_job_list.  Only the
    # canonical report config publishes — an experimental
    # --devices/--batch-size run must not replace the committed hints
    # with keys calibrate's job space can never match.
    try:
        import os

        from .report_configs import (REPORT_COMPUTE_DTYPE, REPORT_DEVICES,
                                     report_keys_path)

        # scale AND dtype must match the committed reports: measured
        # cache keys are dtype-tagged, so a float32 run at canonical
        # scale would publish keys calibrate can never match
        canonical = (args.devices == REPORT_DEVICES.get(args.model)
                     and args.batch_size
                     == REPORT_GLOBAL_BATCH.get(args.model)
                     and args.compute_dtype == REPORT_COMPUTE_DTYPE)
        if canonical:
            keys_path = report_keys_path()
            try:
                with open(keys_path) as f:
                    report_keys = json.load(f)
            except Exception:
                report_keys = {}
            wanted = set()
            for op in model.ops:
                for cfg in (best[op.name], dp[op.name]):
                    if cfg.host_placed:
                        # op_time never consults the measured cache for
                        # host-placed embeddings (_host_embedding_time)
                        # — such a key could never raise provenance
                        continue
                    for which in ("forward", "backward"):
                        wanted.add(prov_cost._key(op, cfg, which))
            report_keys[args.model] = {"devices": args.devices,
                                       "batch": args.batch_size,
                                       "keys": sorted(wanted)}
            tmp = keys_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(report_keys, f, indent=1)
            os.replace(tmp, keys_path)  # atomic: a kill mid-write must
            # not drop the other models' committed hints
    except Exception as e:  # a hint file must never fail the report
        print(f"soap_report: report_keys.json not written ({e})")

    # single-chip agreement: simulate the bench config on 1 device
    agree = None
    if args.measured_single_chip_ms:
        m1 = build_model(args.model, args.single_chip_batch, 1)
        m1.config.compute_dtype = args.compute_dtype
        mm1 = TPUMachineModel.calibrated(num_devices=1)
        sim1 = Simulator(mm1, CostModel(mm1, measure=False,
                                        compute_dtype=args.compute_dtype))
        dp1 = {op.name: ParallelConfig.data_parallel(op.output.num_dims, 1)
               for op in m1.ops}
        sim_ms = sim1.simulate_runtime(m1, dp1) * 1e3
        agree = (sim_ms, args.measured_single_chip_ms,
                 sim_ms / args.measured_single_chip_ms)

    if args.export:
        save_strategies_to_file(args.export, best)

    # "fitted" only when the machine model ACTUALLY loaded overrides —
    # a present-but-corrupt machine_v5e.json silently falls back to the
    # dataclass defaults and must not be labeled fitted
    defaults = TPUMachineModel(num_devices=args.devices)
    fitted = any(
        getattr(mm, f) != getattr(defaults, f)
        for f in ("mxu_efficiency", "hbm_bandwidth",
                  "kernel_launch_overhead", "backward_multiplier"))
    roofline = ("FITTED roofline (machine_v5e.json, constants fitted to "
                "on-chip measurements)" if fitted else
                "UNFITTED analytic roofline (dataclass defaults — "
                "machine_v5e.json absent; run tools/calibrate.py on the "
                "chip)")
    if fitted:
        # disclose the fit's basis: a thin basis (few points / one op
        # family) means the constants extrapolate to unmeasured ops
        try:
            from ..simulator.machine import CALIBRATION_PATH
            from .report_configs import THIN_FIT_OP_TYPES, THIN_FIT_POINTS
            with open(CALIBRATION_PATH) as f:
                meta = json.load(f)
            pts = meta.get("fit_points")
            fams = meta.get("fit_op_types")
            if pts:
                basis = f"fit basis: {pts} measured points"
                if fams:
                    basis += f" over {len(fams)} op type(s) ({', '.join(fams)})"
                if pts < THIN_FIT_POINTS or (fams
                                             and len(fams) < THIN_FIT_OP_TYPES):
                    basis += (" — THIN: constants extrapolate to "
                              "unmeasured op families")
                roofline += f"; {basis}"
        except Exception:
            pass
    lines = [
        f"# SOAP search vs data parallel — {args.model}",
        "",
        f"Machine: simulated v5e, {args.devices} chips "
        f"(torus {mm.torus[0]}x{mm.torus[1]}), {roofline} "
        f"(mxu_eff={mm.mxu_efficiency:.2f}, "
        f"hbm={mm.hbm_bandwidth / 1e9:.0f} GB/s, "
        f"ovh={mm.kernel_launch_overhead * 1e6:.1f} us, "
        f"bwd_mult={mm.backward_multiplier:.2f}); "
        f"global batch {args.batch_size}, {args.compute_dtype}.",
        f"Cost provenance over the compared strategies: "
        f"{measured} op-times from REAL on-chip measurements "
        f"(measured_v5e.json), {analytic} from the "
        f"{'fitted' if fitted else 'unfitted analytic'} roofline.",
        f"Search engine: {engine}, budget {args.budget} x "
        f"{args.restarts} restarts, best kept "
        f"(reference: FFModel::optimize MCMC, model.cc:1056-1107).",
    ]
    if any(op._type == "Embedding" for op in model.ops):
        lines += [
            "Assumption: device-placed DP embedding grad sync is priced "
            "rows-touched (a sparse-aware allreduce, as real DP "
            "recommender backends ship); this runtime's jitted DP step "
            "currently all-reduces the dense full-table gradient, so "
            "the simulated DP baseline is a LOWER bound on its cost.",
    ]
    lines += [
        "",
        "| strategy | simulated step | speedup |",
        "|---|---|---|",
        f"| data parallel ({args.devices}-way batch) | "
        f"{dp_rt * 1e3:.3f} ms | 1.00x |",
        f"| SOAP searched | {best_rt * 1e3:.3f} ms | {speedup:.2f}x |",
    ]
    if pipe_plan is not None:
        lines.append(
            f"| pipeline plan ({pipe_plan['num_stages']} stages x "
            f"dp{pipe_plan['dp_degree']}, M={pipe_plan['num_microbatches']}"
            f"{', remat' if pipe_plan.get('remat') else ''}) "
            f"| {pipe_plan['simulated_s'] * 1e3:.3f} ms | "
            f"{dp_rt / pipe_plan['simulated_s']:.2f}x |")
    else:
        lines.append("| pipeline plan | n/a (branching graph or no "
                     "executable partition) | |")
    if het_rt is not None:
        lines.append(
            f"| hetero host-embedding (row-sparse tables, "
            f"dlrm_strategy_hetero) | {het_rt * 1e3:.3f} ms | "
            f"{dp_rt / het_rt:.2f}x |")
    if het_pipe is not None:
        lines.append(
            f"| hetero head + pipeline ({het_pipe['num_stages']} stages "
            f"x dp{het_pipe['dp_degree']}, "
            f"M={het_pipe['num_microbatches']}"
            f"{', remat' if het_pipe.get('remat') else ''}; host tables "
            f"ahead of the ring) | {het_pipe['simulated_s'] * 1e3:.3f} ms "
            f"| {dp_rt / het_pipe['simulated_s']:.2f}x |")
    lines.append("")
    if agree:
        lines += [
            "## Simulated-vs-measured agreement (single chip)",
            "",
            f"Bench config ({args.single_chip_batch}/chip, 1 device): "
            f"simulated {agree[0]:.2f} ms/step vs measured "
            f"{agree[1]:.2f} ms/step — ratio {agree[2]:.2f}.",
            "",
        ]
    lines += ["## Searched per-op strategies", "",
              "| op | dims | parts |", "|---|---|---|"]
    from ..config import DeviceType as _DT
    for op in model.ops:
        pc = best[op.name]
        if pc.device_type == _DT.CPU:
            mark = " **(HOST row-sparse)**"
        else:
            mark = "" if pc.dims == dp[op.name].dims else " **(non-DP)**"
        lines.append(f"| {op.name} | {list(pc.dims)}{mark} | "
                     f"{pc.num_parts()} |")
    lines.append("")
    with open(args.out, "w") as f:
        f.write("\n".join(lines))
    print(f"dp {dp_rt * 1e3:.3f} ms, soap {best_rt * 1e3:.3f} ms "
          f"({speedup:.2f}x), measured entries {measured}, -> {args.out}")
    return {"dp_ms": dp_rt * 1e3, "soap_ms": best_rt * 1e3,
            "speedup": speedup, "measured": measured, "analytic": analytic}


if __name__ == "__main__":
    main()
