"""MCMC (simulated annealing) strategy search.

TPU-native analogue of ``FFModel::optimize`` / ``rewrite``
(reference: src/runtime/model.cc:1046-1107) with identical accept
semantics: start from data parallelism; each iteration rewrites one random
op to a random legal config; accept when faster, else with probability
``exp(-alpha * (next - current))``; track the best ever seen.

The proposal distribution is TPU-shaped: candidate configs are random
factorizations of a divisor of the device count over the op's partitionable
dims (the reference's base class proposes batch-only splits,
model.cc:305-334; the richer SOAP space there comes from strategy files —
here the search itself explores it, restricted per op type the way the
reference ops restrict their Legion task grids, e.g. softmax asserts no
channel split, softmax.cu).
"""

from __future__ import annotations

import functools
import math
import os
import random
import time
from typing import Dict, Optional, Tuple

from ..config import DeviceType, ParallelConfig
from .cost_model import CostModel
from .machine import TPUMachineModel
from .simulator import Simulator


@functools.lru_cache(maxsize=None)
def _divisors(n: int) -> Tuple[int, ...]:
    return tuple(d for d in range(1, n + 1) if n % d == 0)


# Per-op-type partitionable dims (natural order, batch first / NHWC).
# Mirrors which Legion task-grid dims each reference op actually splits.
# "last" marks the output channel dim (rank-dependent: a Dense on (B, C)
# splits dim 1, on (B, T, C) dim 2 — linear.cu tensor parallelism).
_SPLITTABLE = {
    "Conv2D": (0, 1, 2),       # n, h, w (reference asserts c unsplit, conv_2d.cu:203)
    "Pool2D": (0, 1, 2),
    "Dense": (0, "last"),      # n, c_out (linear.cu tensor parallelism)
    "Embedding": (0, "last"),  # n, out_dim
    "Concat": (0,),
    "Flat": (0,),
    "Softmax": (0,),           # sample only (softmax.cu asserts)
    "BatchNorm": (0,),
    "Dropout": (0,),
    "ElementUnary": (0,),
    "ElementBinary": (0,),
    "LSTM": (0, 2),            # batch + hidden TP (T stays sequential)
    "MSELoss": (0,),
    "PipelineMLP": (0, 1),     # dim 1 = pipeline (operator-dim) degree
    "ExpertMLP": (0, 1),       # dim 1 = expert-parallel degree
    "MultiHeadAttention": (0, 1, 2),  # batch, seq (ring), head TP
    "LayerNorm": (0, 1),       # batch, seq
    "RMSNorm": (0, 1),         # batch, seq
    "GatedMLP": (0, "last"),   # batch, the width of all three matrices
    "LatentAttention": (0, 2),  # batch, the held heads
    "RoutedExperts": (0, 1),   # dim 1 = expert-parallel degree
}


def splittable_dims(op) -> tuple:
    """Resolve _SPLITTABLE for this op's actual output rank."""
    return _splittable_dims_cached(op._type, op.output.num_dims)


@functools.lru_cache(maxsize=None)
def _splittable_dims_cached(op_type: str, rank: int) -> tuple:
    dims = _SPLITTABLE.get(op_type, (0,))
    out = []
    for d in dims:
        d = rank - 1 if d == "last" else d
        if 0 <= d < rank and d not in out:
            out.append(d)
    return tuple(out)


def random_parallel_config(op, num_devices: int, rng: random.Random,
                           model=None) -> ParallelConfig:
    """Random legal SOAP config for ``op`` over ``num_devices`` chips.
    With ``model``, eligible embeddings also propose HOST placement (the
    row-sparse table path) with small probability — the searched space
    covers the reference's hetero CPU placement instead of leaving it to
    hand-written strategy files."""
    if model is not None and rng.random() < 0.1 \
            and getattr(model, "_sparse_embed_candidate_ok",
                        lambda _: False)(op):
        return ParallelConfig.host_rowsparse(op.output.num_dims)
    rank = op.output.num_dims
    splittable = splittable_dims(op)
    num_parts = rng.choice(_divisors(num_devices))
    # randomly factor num_parts across splittable dims
    degrees = [1] * rank
    remaining = num_parts
    dims_order = list(splittable)
    rng.shuffle(dims_order)
    for d in dims_order:
        if remaining == 1:
            break
        opts = [f for f in _divisors(remaining)
                if d < rank and op.output.dims[d] % (degrees[d] * f) == 0]
        f = rng.choice(opts) if opts else 1
        degrees[d] *= f
        remaining //= f
    if remaining > 1:  # couldn't place everything: dump the rest on batch
        if op.output.dims[0] % (degrees[0] * remaining) == 0:
            degrees[0] *= remaining
        # else: leave fewer parts — still legal
    pc = ParallelConfig(dims=tuple(degrees))
    n = pc.num_parts()
    start = rng.randrange(0, num_devices - n + 1) if num_devices > n else 0
    return pc.with_device_ids(tuple(range(start, start + n)))


class SearchResult(Dict[str, ParallelConfig]):
    """The best strategy map found, plus the search's own account of
    itself: simulated cost of the best plan (``best_s``) and of the
    data-parallel start (``dp_s``), engine/budget/seed/devices.  A dict
    subclass so every pre-existing caller that treats the result as a
    plain {op: ParallelConfig} map keeps working, while ``compile()``
    and the provenance sidecar no longer need to RE-simulate the plan
    the search just finished costing."""

    def __init__(self, strategies: Dict[str, ParallelConfig],
                 engine: str = "", budget: int = 0, seed: int = 0,
                 num_devices: int = 0, best_s: Optional[float] = None,
                 dp_s: Optional[float] = None,
                 proposals_per_s: Optional[float] = None,
                 delta_sim: Optional[bool] = None,
                 chains: Optional[list] = None,
                 stats: Optional[Dict] = None):
        super().__init__(strategies)
        self.engine = engine
        self.budget = budget
        self.seed = seed
        self.num_devices = num_devices
        self.best_s = best_s
        self.dp_s = dp_s
        # throughput telemetry only — never part of result equality
        self.proposals_per_s = proposals_per_s
        self.delta_sim = delta_sim
        # population engine only: per-chain stat dicts + run-level stats
        # (tempering ladder, exchange acceptance, crossover lineage,
        # learned-tier provenance) — None for single-chain engines
        self.chains = chains
        self.stats = stats


def _delta_enabled() -> bool:
    return os.environ.get("FF_SIM_DELTA", "1").lower() \
        not in ("0", "false", "off")


def mcmc_search(model, budget: int, alpha: float = 0.05,
                machine_model: Optional[TPUMachineModel] = None,
                measure: bool = False, seed: int = 0,
                overlap_backward_update: Optional[bool] = None,
                verbose: bool = True,
                cost_model: Optional[CostModel] = None,
                num_devices: Optional[int] = None) -> "SearchResult":
    """Returns the best strategy map found (op name → ParallelConfig),
    as a ``SearchResult`` carrying the simulated best cost.

    Proposals are re-costed incrementally through ``DeltaSimulator``
    (fragment caches keyed on per-op configs) — set ``FF_SIM_DELTA=0``
    to force the full-rebuild reference path.  The RNG stream and accept
    semantics are identical either way: a seeded search returns the same
    SearchResult bit for bit, delta on or off (pinned by
    tests/test_delta_sim.py).  Every ``FF_SIM_DELTA_CHECK`` accepts
    (default 200) the delta cost is cross-checked against a full rebuild;
    a divergence emits a ``sim_delta_divergence`` event and drops to the
    reference path for the rest of the run.

    ``cost_model`` lets a caller that already owns a warmed CostModel
    (pipeline_search's grid pass) share its memo caches with the anneal;
    only honored when its configuration matches what this function would
    build (measure=False path).

    ``num_devices`` overrides the device count the search targets —
    the online-reconfiguration path searches over the *surviving*
    device set without mutating the compiled model's machine.
    """
    nd = int(num_devices) if num_devices is not None \
        else (model.machine.num_devices if model.machine is not None
              else model.config.num_devices)
    mm = machine_model or TPUMachineModel.calibrated(num_devices=nd)
    overlap = model.config.search_overlap_backward_update \
        if overlap_backward_update is None else overlap_backward_update
    # measure=True must tag (and read) entries for the backend it actually
    # times on; measure=False targets the shipped TPU cache regardless of
    # the host backend (offline search on CPU-only machines).
    import jax

    platform = jax.default_backend() if measure else "tpu"
    cost = cost_model if (cost_model is not None and not measure
                          and cost_model.machine is mm) else \
        CostModel(mm, measure=measure,
                  compute_dtype=model.config.compute_dtype,
                  target_platform=platform)
    sim = Simulator(mm, cost, overlap_backward_update=overlap)
    rng = random.Random(seed)

    delta = None
    if _delta_enabled():
        try:
            from .delta import DeltaSimulator
            delta = DeltaSimulator(sim, model)
        except Exception:
            delta = None  # any construction failure -> reference path
    check_every = int(os.environ.get("FF_SIM_DELTA_CHECK", "200") or 0)

    current = {op.name: ParallelConfig.data_parallel(op.output.num_dims, nd)
               .with_device_ids(tuple(range(nd)))
               for op in model.ops}
    current_rt = delta.reset(current) if delta is not None \
        else sim.simulate_runtime(model, current)
    best, best_rt = dict(current), current_rt
    dp_rt = current_rt

    import contextlib

    from ..observability.events import active_log
    from ..observability.searchtrace import SearchRecorder
    tel = active_log()
    rec = SearchRecorder.maybe("mcmc", budget, nd, seed, log=tel)
    if rec is not None:
        rec.start(initial_ms=dp_rt * 1e3)
    span = tel.span("mcmc_search", budget=budget, num_devices=nd) \
        if tel is not None else contextlib.nullcontext({})
    accepts = 0
    anneal_t0 = time.perf_counter()
    with span as span_attrs:
        for it in range(budget):
            op = rng.choice(model.ops)
            old_pc = current[op.name]
            # Legalize through the op hook so configs whose dims carry
            # non-size meaning (PipelineMLP pipe degree) are clamped
            # against the real bound before costing (same as the native
            # engine path).
            new_pc = op.legalize_pc(
                random_parallel_config(op, nd, rng, model=model))
            if delta is not None:
                nxt_rt = delta.propose(op.name, new_pc)
            else:
                # reference path: mutate-in-place + restore beats the old
                # per-proposal dict(current) copy; same simulated graph
                current[op.name] = new_pc
                nxt_rt = sim.simulate_runtime(model, current)
                current[op.name] = old_pc
            if it % 100 == 0:
                if verbose:
                    print(f"iter({it}) cur({current_rt * 1e3:.3f}ms) "
                          f"next({nxt_rt * 1e3:.3f}ms) "
                          f"best({best_rt * 1e3:.3f}ms)")
                if tel is not None:
                    tel.event("search_progress", engine="mcmc", iter=it,
                              best_ms=round(best_rt * 1e3, 3))
            if nxt_rt < best_rt:
                best_rt = nxt_rt
                best = dict(current)
                best[op.name] = new_pc
            # Accept semantics unchanged from the reference (downhill
            # always; uphill with Metropolis probability) — spelled out
            # so the recorder can carry the reason + probability.  The
            # rng draw happens ONLY on uphill moves, exactly as the
            # short-circuited original did: seeded runs reproduce the
            # same strategies with or without telemetry.
            if nxt_rt < current_rt:
                accepted, reason, prob = True, "downhill", None
            else:
                prob = math.exp(-alpha * (nxt_rt - current_rt) * 1e3)
                accepted, reason = rng.random() < prob, "metropolis"
            if rec is not None:
                rec.candidate(it, op.name, old_pc, new_pc,
                              cur_ms=current_rt * 1e3, new_ms=nxt_rt * 1e3,
                              best_ms=best_rt * 1e3, accepted=accepted,
                              reason=reason, prob=prob)
            if accepted:
                current[op.name] = new_pc
                current_rt = nxt_rt
                if delta is not None:
                    delta.commit()
                    accepts += 1
                    if check_every and accepts % check_every == 0:
                        # periodic oracle cross-check: the delta cost of
                        # the committed plan must match a full rebuild
                        full_rt = sim.simulate_runtime(model, current)
                        tol = 1e-9 * max(abs(full_rt), abs(current_rt), 1e-30)
                        if abs(full_rt - current_rt) > tol:
                            import sys as _sys
                            print("WARNING: delta simulation diverged "
                                  f"({current_rt!r} vs {full_rt!r}); "
                                  "falling back to full re-simulation",
                                  file=_sys.stderr)
                            if tel is not None:
                                tel.event("sim_delta_divergence",
                                          engine="mcmc", iter=it,
                                          delta_s=current_rt, full_s=full_rt)
                            delta = None
                            current_rt = full_rt
            elif delta is not None:
                delta.rollback()
        span_attrs["best_ms"] = round(best_rt * 1e3, 3)
        anneal_dt = time.perf_counter() - anneal_t0
        proposals_per_s = budget / anneal_dt if anneal_dt > 0 else 0.0
        span_attrs["proposals_per_s"] = round(proposals_per_s, 1)
    if rec is not None:
        rec.finish(best, best_ms=best_rt * 1e3,
                   proposals_per_s=proposals_per_s,
                   delta=delta is not None)
    if tel is not None:
        tel.flush()
    if verbose:
        print("=========== Best Discovered Strategy ==========")
        for name, pc in best.items():
            print(f"[{name}] dims{list(pc.dims)} parts({pc.num_parts()})")
        print(f"simulated runtime: {best_rt * 1e3:.3f} ms/iter")
    return SearchResult(best, engine="mcmc", budget=budget, seed=seed,
                        num_devices=nd, best_s=best_rt, dp_s=dp_rt,
                        proposals_per_s=proposals_per_s,
                        delta_sim=delta is not None)
