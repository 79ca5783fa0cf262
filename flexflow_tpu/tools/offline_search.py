"""Offline (no-hardware) parallelization-strategy search.

TPU-native analogue of the reference's standalone simulator binary
(reference: scripts/simulator.cc — a pure-C++ cost model needing zero
GPUs/Legion that runs 250k simulated-annealing iterations over per-op
configs, using analytic/pre-measured costs).  This CLI builds a model
from the zoo, searches with the analytic roofline cost model over a
configurable TPU machine shape, and exports the best strategy to a
protobuf file loadable with ``--import-strategy`` / FFConfig.strategies.

Usage:
    python -m flexflow_tpu.tools.offline_search alexnet \
        --devices 16 --budget 2000 --export /tmp/alexnet_16.pb
    python -m flexflow_tpu.tools.offline_search dlrm --devices 8 \
        --chips-per-host 4 --budget 1000 --export /tmp/dlrm.pb
"""

from __future__ import annotations

import argparse
from typing import List, Optional


def build_model(name: str, batch_size: int, num_devices: int = 1):
    import flexflow_tpu as ff

    # workers_per_node sizes the simulated machine, not this host's
    # backend — offline search needs no accelerator at all.
    cfg = ff.FFConfig(batch_size=batch_size, workers_per_node=num_devices)
    model = ff.FFModel(cfg)
    if name == "alexnet":
        from ..models.alexnet import build_alexnet
        build_alexnet(model, batch_size)
    elif name == "resnet":
        from ..models.resnet import build_resnet50
        build_resnet50(model, batch_size)
    elif name == "inception":
        from ..models.inception import build_inception_v3
        build_inception_v3(model, batch_size)
    elif name == "dlrm":
        from ..models.dlrm import build_dlrm
        build_dlrm(model, batch_size)
    elif name == "nmt":
        from ..models.nmt import build_nmt
        build_nmt(model, batch_size)
    elif name == "transformer":
        from ..models.transformer import build_transformer
        build_transformer(model, batch_size)
    elif name == "candle_uno":
        from ..models.candle_uno import build_candle_uno
        build_candle_uno(model, batch_size)
    else:
        raise SystemExit(f"unknown model {name!r}")
    return model


def main(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("model", help="alexnet|resnet|inception|dlrm|nmt|"
                                 "transformer|candle_uno")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--chips-per-host", type=int, default=8)
    p.add_argument("--ici-bw", type=float, default=None,
                   help="ICI bytes/s per link per direction "
                        "(default: calibrated/v5e)")
    p.add_argument("--dcn-bw", type=float, default=None,
                   help="DCN bytes/s per host (default: calibrated/v5e)")
    p.add_argument("--peak-flops", type=float, default=None)
    p.add_argument("--hbm-bw", type=float, default=None)
    p.add_argument("--compute-dtype", default="bfloat16",
                   help="dtype the cost model keys on (the bench dtype)")
    from ..config import DEFAULT_SEARCH_BUDGET

    p.add_argument("--budget", type=int, default=DEFAULT_SEARCH_BUDGET,
                   help="MCMC iterations (default sized for the delta "
                        "simulator; FF_SIM_DELTA=0 restores the full "
                        "rebuild per proposal)")
    p.add_argument("--alpha", type=float, default=0.05)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--export", default=None, help="strategy .pb output path")
    p.add_argument("--engine", choices=["native", "python", "population"],
                   default="native",
                   help="native C++ annealing engine (falls back to "
                        "python), or the parallel-tempered population "
                        "engine (simulator/population.py; FF_SEARCH_* "
                        "knobs tune it)")
    p.add_argument("--consider-pipeline", action="store_true",
                   help="also search pipeline stage assignments "
                        "(simulator/pipeline_search.py) and report when a "
                        "dp x pp plan beats the best dim strategy")
    p.add_argument("--quiet", action="store_true")
    args = p.parse_args(argv)

    from ..parallel.strategy import save_strategies_to_file
    from ..simulator.machine import TPUMachineModel
    from ..simulator.search import mcmc_search
    from ..simulator.simulator import Simulator
    from ..simulator.cost_model import CostModel
    from ..config import ParallelConfig

    model = build_model(args.model, args.batch_size, args.devices)
    model.config.compute_dtype = args.compute_dtype
    overrides = {k: v for k, v in [("peak_flops", args.peak_flops),
                                   ("hbm_bandwidth", args.hbm_bw),
                                   ("ici_bandwidth", args.ici_bw),
                                   ("dcn_bandwidth", args.dcn_bw)]
                 if v is not None}
    mm = TPUMachineModel.calibrated(num_devices=args.devices,
                                    chips_per_host=args.chips_per_host,
                                    **overrides)
    sim = Simulator(mm, CostModel(mm, measure=False,
                                  compute_dtype=args.compute_dtype))
    dp = {op.name: ParallelConfig.data_parallel(op.output.num_dims, args.devices)
          .with_device_ids(tuple(range(args.devices)))
          for op in model.ops}
    dp_rt = sim.simulate_runtime(model, dp)

    best = None
    if args.engine == "population":
        from ..simulator.population import population_search

        best = population_search(model, budget=args.budget,
                                 alpha=args.alpha, machine_model=mm,
                                 seed=args.seed, verbose=not args.quiet)
    elif args.engine == "native":
        from ..simulator.native_search import native_mcmc_search

        r = native_mcmc_search(model, budget=args.budget, alpha=args.alpha,
                               machine_model=mm, seed=args.seed,
                               verbose=not args.quiet)
        if r is not None:
            best = r[0]
    if best is None:
        best = mcmc_search(model, budget=args.budget, alpha=args.alpha,
                           machine_model=mm, measure=False, seed=args.seed,
                           verbose=not args.quiet)
    # Both engines return a SearchResult that already carries its
    # simulated best cost — re-simulate only for a plain-dict result.
    best_rt = getattr(best, "best_s", None)
    if best_rt is None:
        best_rt = sim.simulate_runtime(model, best)
    speedup = dp_rt / best_rt if best_rt > 0 else float("inf")
    print(f"data-parallel: {dp_rt * 1e3:.3f} ms/iter; "
          f"searched: {best_rt * 1e3:.3f} ms/iter; "
          f"speedup {speedup:.2f}x on {args.devices} chips "
          f"(torus {mm.torus[0]}x{mm.torus[1]})")

    if args.consider_pipeline:
        from ..simulator.pipeline_search import search_pipeline

        plan = search_pipeline(model, machine_model=mm)
        if plan is not None:
            mark = "<-- beats the dim search" \
                if plan["simulated_s"] < best_rt else ""
            rm = plan.get("remat", False)
            print(f"pipeline plan: {plan['num_stages']} stages x "
                  f"dp{plan['dp_degree']}, M={plan['num_microbatches']}"
                  f"{', remat' if rm else ''}: "
                  f"{plan['simulated_s'] * 1e3:.3f} ms/iter {mark}\n"
                  f"  (apply via FFModel.set_pipeline(num_stages="
                  f"{plan['num_stages']}, dp_degree={plan['dp_degree']}, "
                  f"num_microbatches={plan['num_microbatches']}, "
                  f"remat={rm}))")

    if args.export:
        from ..observability.searchtrace import build_provenance
        from ..parallel.strategy import sidecar_path

        extra = {"model": args.model, "tool": "offline_search"}
        stats = getattr(best, "stats", None)
        if stats:
            extra["population"] = {k: stats[k] for k in
                                   ("population", "ladder", "spent",
                                    "winner_chain", "exchange",
                                    "crossover") if k in stats}
            if stats.get("learned"):
                extra["learned_tier"] = stats["learned"]
        prov = build_provenance(
            model, dict(best),
            engine=getattr(best, "engine", args.engine),
            budget=args.budget, seed=args.seed,
            best_s=best_rt, dp_s=dp_rt, machine_model=mm,
            extra=extra)
        save_strategies_to_file(args.export, best, provenance=prov)
        print(f"exported strategy -> {args.export} "
              f"(+ {sidecar_path(args.export)})")
    return best


if __name__ == "__main__":
    main()
