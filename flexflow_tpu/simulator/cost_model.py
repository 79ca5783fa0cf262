"""Per-op compute-cost model: measured on the real chip, cached, with a
calibrated roofline fallback.

TPU analogue of the reference's ``measure_compute_time`` machinery
(reference: Op::measure_compute_time per op, e.g. conv_2d.cu:937-1039,
cached by (op, config) hash in simulator.cc:235-273).  On TPU a compile
costs seconds, not microseconds, so caching is mandatory and durable:

  * measurements key on (op type, per-part sub-shape, dtype, direction)
    and persist to disk — the analogue of the reference's in-memory
    ``hash_to_op_{forward,backward}_time`` maps, made durable;
  * only REAL measurements are persisted, tagged with the platform they
    were taken on (``{"t": sec, "measured": true, "platform": "tpu"}``)
    so CPU-measured values can never masquerade as chip timings;
  * WHEN ``measured_v5e.json`` exists (produced by
    ``tools/calibrate.py`` on the real v5e — see docs/simulator.md,
    "Calibrating the cost model"),
    every search — including offline search on a CPU-only host — costs
    candidates with real chip timings where available;
  * anything uncached falls back to a roofline
    ``max(flops / (peak·eff), bytes / hbm_bw) + overhead`` whose
    ``mxu_efficiency`` / overhead / backward-multiplier constants come
    from ``machine_v5e.json`` when that fit exists, else the dataclass
    DEFAULTS (every report states which — "fitted" vs "unfitted").
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .machine import TPUMachineModel

# Committed on-chip measurement cache, produced by tools/calibrate.py.
MEASURED_CACHE = os.path.join(os.path.dirname(__file__), "measured_v5e.json")

# Minimum measured points an op family needs before the learned tier will
# even attempt a cross-validated fit (also the threshold tools/doctor.py
# warns against when the learned tier is requested on a thin corpus).
LEARNED_MIN_POINTS = 12
LEARNED_FOLDS = 4


def _parse_cost_key(key: str):
    """Decompose a ``CostModel._key`` string back into
    ``(family, sub, ins, extra, dtype, which)`` or None when the key is
    not an op-timing key (the cache also holds e.g. ``host_xfer``
    probes).  The key grammar has exactly six colon-separated fields and
    tuples never contain colons, so a plain split is exact."""
    import ast

    parts = key.split(":")
    if len(parts) != 6:
        return None
    fam, sub_s, ins_s, extra, dtype, which = parts
    if which not in ("forward", "backward"):
        return None
    try:
        sub = ast.literal_eval(sub_s)
        ins = ast.literal_eval(ins_s) if ins_s else ()
    except (ValueError, SyntaxError):
        return None
    if not isinstance(sub, tuple):
        return None
    return fam, sub, tuple(ins), extra, dtype, which


def _key_flops_bytes(fam, sub, ins, extra, dtype_bytes):
    """(flops, bytes) roofline estimate for one PART, reconstructed from
    a cost-cache key alone — the featurization the learned tier shares
    between fit time (corpus keys) and predict time (keys built by
    ``CostModel._key``).  Weight volumes are approximated where the key
    cannot carry them (Embedding tables)."""
    out_elems = float(np.prod(sub)) if sub else 1.0
    in_elems = float(sum(np.prod(s) for s in ins)) if ins else 0.0
    kernel = stride = None
    hidden = None
    if extra.startswith("k"):
        import ast
        try:
            kpart, spart = extra[1:].split("s", 1)
            kernel = ast.literal_eval(kpart)
            stride = ast.literal_eval(spart)
        except (ValueError, SyntaxError):
            pass
    elif extra.startswith("h"):
        try:
            hidden = int(extra[1:])
        except ValueError:
            pass
    weights = 0.0
    if fam == "Conv2D" and kernel and ins:
        cin = ins[0][-1]
        flops = 2.0 * out_elems * kernel[0] * kernel[1] * cin
        weights = float(kernel[0] * kernel[1] * cin * sub[-1] + sub[-1])
    elif fam == "Pool2D" and kernel:
        flops = out_elems * kernel[0] * kernel[1]
    elif fam in ("Dense", "Linear") and ins:
        in_dim = ins[0][-1]
        flops = 2.0 * out_elems * in_dim
        weights = float(in_dim * sub[-1] + sub[-1])
    elif fam == "Embedding":
        flops = out_elems
        weights = out_elems  # rows actually touched ≈ batch × out_dim
    elif fam == "LSTM" and hidden and ins and len(ins[0]) == 3:
        b, t, e = ins[0]
        flops = 2.0 * b * t * (e + hidden) * 4 * hidden
        weights = float(4 * hidden * (e + hidden + 1))
    elif fam == "MultiHeadAttention" and ins:
        flops = 8.0 * out_elems * (1.0 + ins[0][-1] / max(1, sub[-1]))
    else:
        # elementwise-ish fallback: one MAC per output element against
        # the innermost input width
        flops = 2.0 * out_elems * (ins[0][-1] if ins and ins[0] else 1)
    bytes_moved = dtype_bytes * (in_elems + weights + out_elems)
    return float(flops), float(bytes_moved)


class LearnedCostTier:
    """Per-op-family regression over the measured-timing corpus.

    Fits ``log t ≈ w · [1, log1p(flops), log1p(bytes), is_backward]``
    per family (numpy lstsq — stdlib + numpy only) on every measured
    entry whose key parses, then k-fold cross-validates the fit AGAINST
    the key-level analytic roofline: a family's learned model is used
    only when its out-of-fold log-RMSE strictly beats the analytic
    model's on the same folds.  Families below ``LEARNED_MIN_POINTS``
    measured points never fit.  The full account — per-family point
    counts, both OOF errors, used/rejected — lands in ``provenance``
    so a search that priced candidates with learned costs can say so
    (ISSUE 15 / ``FF_SEARCH_LEARNED`` escape hatch in the engines).
    """

    def __init__(self, machine: TPUMachineModel,
                 compute_dtype: str = "float32",
                 corpus: Optional[Dict[str, float]] = None,
                 folds: int = LEARNED_FOLDS,
                 min_points: int = LEARNED_MIN_POINTS,
                 sources: Optional[Dict[str, int]] = None):
        self.machine = machine
        self.compute_dtype = compute_dtype
        self._dtype_bytes = 2.0 if "16" in compute_dtype else 4.0
        self._models: Dict[str, np.ndarray] = {}
        corpus = corpus or {}
        by_fam: Dict[str, list] = {}
        for key, t in sorted(corpus.items()):
            parsed = _parse_cost_key(key)
            if parsed is None or not (t > 0):
                continue
            fam, sub, ins, extra, _dtype, which = parsed
            fl, by = _key_flops_bytes(fam, sub, ins, extra,
                                      self._dtype_bytes)
            feats = (1.0, np.log1p(fl), np.log1p(by),
                     1.0 if which == "backward" else 0.0)
            by_fam.setdefault(fam, []).append(
                (feats, float(np.log(t)),
                 float(np.log(self._analytic_key(fam, fl, by, which)))))
        families: Dict[str, Any] = {}
        for fam, rows in sorted(by_fam.items()):
            n = len(rows)
            rep: Dict[str, Any] = {"points": n}
            if n < min_points:
                rep["used"] = False
                rep["reason"] = f"corpus below fit threshold ({n} < {min_points})"
                families[fam] = rep
                continue
            X = np.asarray([r[0] for r in rows], np.float64)
            y = np.asarray([r[1] for r in rows], np.float64)
            ya = np.asarray([r[2] for r in rows], np.float64)
            k = min(folds, n)
            # deterministic index-order folds: corpus iteration is sorted
            # by key, so the split (and therefore used/rejected and every
            # downstream search decision) is bitwise run-to-run stable
            idx = np.arange(n)
            err_l, err_a = [], []
            for f in range(k):
                test = idx[f::k]
                train = np.setdiff1d(idx, test)
                w, *_ = np.linalg.lstsq(X[train], y[train], rcond=None)
                err_l.extend((X[test] @ w - y[test]).tolist())
                err_a.extend((ya[test] - y[test]).tolist())
            rmse_l = float(np.sqrt(np.mean(np.square(err_l))))
            rmse_a = float(np.sqrt(np.mean(np.square(err_a))))
            rep["oof_log_rmse_learned"] = round(rmse_l, 4)
            rep["oof_log_rmse_analytic"] = round(rmse_a, 4)
            rep["folds"] = int(k)
            if rmse_l < rmse_a:
                w, *_ = np.linalg.lstsq(X, y, rcond=None)
                self._models[fam] = w
                rep["used"] = True
            else:
                rep["used"] = False
                rep["reason"] = "analytic roofline wins out-of-fold"
            families[fam] = rep
        self.provenance: Dict[str, Any] = {
            "tier": "learned",
            "corpus_points": int(sum(len(r) for r in by_fam.values())),
            "min_points": int(min_points),
            "families": families,
            "used_families": sorted(self._models),
        }
        if sources:
            self.provenance["sources"] = dict(sources)

    def _analytic_key(self, fam: str, flops: float, bytes_moved: float,
                      which: str) -> float:
        """Key-level roofline — the CV baseline.  Mirrors
        ``CostModel._analytic`` with the weight volume approximated from
        the key (the op object is not available at fit time)."""
        m = self.machine
        eff = m.op_efficiency.get(fam, m.mxu_efficiency)
        t = max(flops / (m.peak_flops * eff),
                bytes_moved / m.hbm_bandwidth) + m.kernel_launch_overhead
        if which == "backward":
            t *= m.op_backward_multiplier.get(fam, m.backward_multiplier)
        return float(t)

    def predict(self, key: str) -> Optional[float]:
        """Predicted seconds for a cost-cache key, or None when the key's
        family did not win its cross-validation (caller falls through to
        the analytic roofline)."""
        parsed = _parse_cost_key(key)
        if parsed is None:
            return None
        fam, sub, ins, extra, _dtype, which = parsed
        w = self._models.get(fam)
        if w is None:
            return None
        fl, by = _key_flops_bytes(fam, sub, ins, extra, self._dtype_bytes)
        x = np.asarray((1.0, np.log1p(fl), np.log1p(by),
                        1.0 if which == "backward" else 0.0), np.float64)
        return float(np.exp(x @ w))

    @classmethod
    def fit_default(cls, machine: TPUMachineModel,
                    compute_dtype: str = "float32",
                    measured_cache_path: Optional[str] = None
                    ) -> "LearnedCostTier":
        """Fit on the committed measured corpus (``measured_v5e.json``,
        which calibration sessions and FF_OPPROF runs grow)."""
        corpus: Dict[str, float] = {}
        sources: Dict[str, int] = {}
        path = measured_cache_path or MEASURED_CACHE
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
                for k, v in data.items():
                    if isinstance(v, dict) and v.get("measured"):
                        corpus[k] = float(v["t"])
                sources[os.path.basename(path)] = len(corpus)
            except Exception:
                pass
        return cls(machine, compute_dtype=compute_dtype, corpus=corpus,
                   sources=sources)


class CostModel:
    def __init__(self, machine: TPUMachineModel, measure: bool = False,
                 cache_path: str = ".simcache.json",
                 compute_dtype: str = "float32",
                 measured_cache_path: Optional[str] = None,
                 target_platform: str = "tpu"):
        self.machine = machine
        self.measure = measure
        self.cache_path = cache_path
        self.compute_dtype = compute_dtype
        self.target_platform = target_platform
        self._measured: Dict[str, float] = {}
        self._analytic_memo: Dict[str, float] = {}
        self._measure_failed: set = set()  # don't re-compile known failures
        self.stats = {"measured_hits": 0, "measured_runs": 0,
                      "learned": 0, "analytic": 0}
        # optional learned regression tier (LearnedCostTier), consulted
        # between the measured cache and the analytic roofline
        self._learned: Optional["LearnedCostTier"] = None
        # op_time fast path: the string _key is canonical but costs more
        # to BUILD than a memoized lookup saves, so hot callers (the
        # delta simulator re-costing thousands of proposals) hit this
        # (id(op), pc, which) -> (time, stats counter) cache instead.
        # The op objects are pinned in _fast_ops so a freed op's id can
        # never alias a live one.
        self._fast: Dict[tuple, tuple] = {}
        self._fast_ops: Dict[int, object] = {}
        # Packaged calibrated cache first, local cache second (so a fresh
        # recalibration on this machine overrides the shipped numbers).
        for path in (measured_cache_path or MEASURED_CACHE, cache_path):
            if not path or not os.path.exists(path):
                continue
            try:
                with open(path) as f:
                    data = json.load(f)
            except Exception:
                continue
            for k, v in data.items():
                if (isinstance(v, dict) and v.get("measured")
                        and v.get("platform", "tpu") == target_platform):
                    self._measured[k] = float(v["t"])

    def _persist(self, key: str, t: float):
        """Append one measured entry to the local cache (read-modify-write
        so concurrent tools don't clobber each other's keys).

        The write is atomic tmp+rename: calibration runs get KILLED — by
        the supervisor's watchdog, or at a chip call's time limit — and
        a direct ``open(path, "w")`` caught mid-write would truncate
        every entry the run had already paid for.  With the rename, readers
        (and the next resumed worker) always see a complete cache."""
        if not self.cache_path:
            return
        try:
            data = {}
            if os.path.exists(self.cache_path):
                try:
                    with open(self.cache_path) as f:
                        data = json.load(f)
                except Exception:
                    data = {}
            # drop legacy bare-float entries (pre-provenance format)
            data = {k: v for k, v in data.items() if isinstance(v, dict)}
            data[key] = {"t": t, "measured": True,
                         "platform": self.target_platform}
            tmp = f"{self.cache_path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(data, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.cache_path)
        except OSError:
            pass

    # -- shape bookkeeping -------------------------------------------------
    @staticmethod
    def _sub_output_shape(op, pc) -> Tuple[int, ...]:
        dims = op.outputs[0].dims
        return tuple(sz // (pc.dims[i] if i < len(pc.dims) else 1)
                     for i, sz in enumerate(dims))

    def _key(self, op, pc, which: str) -> str:
        """Cache key: op type + per-part OUTPUT and INPUT sub-shapes (+
        attrs).  Input shapes are load-bearing: two Dense ops with the
        same output sub-shape but different in-widths (DLRM 64→512 vs
        512→512) cost very differently — the reference keys its timing
        cache on the whole (op, config) pair (simulator.cc:235-253)."""
        sub = self._sub_output_shape(op, pc)
        ins = tuple(tuple(hi - lo + 1 for lo, hi in op.input_ranges(j, pc, 0))
                    for j in range(len(op.inputs)))
        extra = ""
        if hasattr(op, "kernel"):
            extra = f"k{op.kernel}s{op.stride}"
        if hasattr(op, "hidden_size"):
            extra = f"h{op.hidden_size}"
        if hasattr(op, "cost_key"):  # sizes neither shape nor width shows
            extra = op.cost_key()
        return (f"{op._type}:{sub}:{ins}:{extra}:"
                f"{self.compute_dtype}:{which}")

    @property
    def _dtype_bytes(self) -> float:
        return 2.0 if "16" in self.compute_dtype else 4.0

    # -- analytic roofline -------------------------------------------------
    def _analytic(self, op, pc, which: str) -> float:
        m = self.machine
        sub = self._sub_output_shape(op, pc)
        scale = np.prod(sub) / max(1, np.prod(op.outputs[0].dims))
        flops = op.flops_per_sample() * op.outputs[0].dims[0] * scale
        # bytes: inputs read + weights read + outputs written for this part
        in_vol = sum(int(np.prod([hi - lo + 1 for lo, hi in op.input_ranges(j, pc, 0)]))
                     for j in range(len(op.inputs)))
        # A weight-SHARING op (share_with: embed_dst reads embed_src's
        # table) has no weights of its own, but its forward physically
        # reads the shared tensor — price the owner's weights, not zero.
        # This also makes the cache key honest: owner and sharer have
        # identical shapes AND now identical costs, so their colliding
        # keys describe the same physical computation.
        w_op = op.share_from if getattr(op, "share_from", None) else op
        w_vol = sum(int(np.prod([hi - lo + 1 for lo, hi in w_op.weight_tile(pc, wi, 0)]))
                    for wi in range(len(w_op.weights)))
        out_vol = int(np.prod(sub))
        bytes_moved = self._dtype_bytes * (in_vol + w_vol + out_vol)
        whole = getattr(op, "unsplit_cost_per_sample", None)
        if whole is not None:
            # work every part does whole for its samples whatever the
            # other degrees (a learned index: its scores, the selection)
            w_flops, w_bytes = whole()
            flops += w_flops * sub[0]
            bytes_moved += w_bytes * sub[0]
        fam = type(op).__name__
        eff = m.op_efficiency.get(fam, m.mxu_efficiency)
        t = max(flops / (m.peak_flops * eff),
                bytes_moved / m.hbm_bandwidth) + m.kernel_launch_overhead
        vector = getattr(op, "vector_cost_per_sample", None)
        if vector is not None:
            # element work that no product carries (a scan's recurrence,
            # one token after another): the VPU's, after the MXU's
            t += vector() * sub[0] / m.vpu_ops_per_s
        if which == "backward":
            # dgrad + wgrad (fitted per family where measured; default 2×)
            t *= m.op_backward_multiplier.get(fam, m.backward_multiplier)
        return float(t)

    # -- real measurement --------------------------------------------------
    def _measure_real(self, op, pc, which: str) -> Optional[float]:
        """Compile+time the op's forward (and backward via jax.grad) on the
        per-part sub-shape — per-shard WEIGHTS included (a TP-split Dense
        is measured with its c_out/k weight slice, matching what each chip
        would actually run) — on the default accelerator."""
        try:
            import time as _t

            import jax
            import jax.numpy as jnp
            from ..ops.base import FwdCtx

            cdt = jnp.bfloat16 if "16" in self.compute_dtype else jnp.float32

            sub_ins = []
            for j, t in enumerate(op.inputs):
                rng = op.input_ranges(j, pc, 0)
                sub_ins.append(tuple(hi - lo + 1 for lo, hi in rng))

            key = jax.random.key(0)
            # Non-zero random data: all-zero operands invite XLA to
            # simplify the very computation being measured.
            xs = []
            for j, s in enumerate(sub_ins):
                if "int" in op.inputs[j].dtype:
                    xs.append(jnp.zeros(s, jnp.int32))
                else:
                    key, k = jax.random.split(key)
                    xs.append(jax.random.normal(k, s, cdt))
            owner = op.share_from if op.share_from is not None else op
            params = {}
            for wi, w in enumerate(owner.weights):
                tile = op.weight_tile(pc, wi, 0)
                wshape = tuple(hi - lo + 1 for lo, hi in tile) if tile else w.dims
                key, k = jax.random.split(key)
                params[w.name] = 0.02 * jax.random.normal(k, wshape, cdt)
            ctx = FwdCtx(training=False, rng=key,
                         stats_in={op.name: op.init_stats()} if op.init_stats() else {})

            def fwd(params, xs):
                return op.forward(params, list(xs), ctx)[0]

            from jax import lax

            f32 = jnp.float32

            def loss(params, xs):
                return jnp.sum(fwd(params, xs).astype(f32))

            # The op runs n times inside ONE jitted fori_loop (dynamic
            # trip count — no per-n recompiles), with the inputs
            # perturbed by the loop carry so XLA cannot hoist the
            # loop-invariant computation.  Host dispatch and the
            # host<->device sync are paid once per call and cancelled
            # exactly by the two-point
            # difference below — the reference gets the same isolation
            # from cudaEvent timestamps (conv_2d.cu:937-1039).
            has_float_x = any(x.dtype.kind not in "iu" for x in xs)

            def body(carry, params, xs):
                xs_p = [x if x.dtype.kind in "iu" else x + carry.astype(x.dtype)
                        for x in xs]
                ps = params
                if not has_float_x:  # e.g. embedding: chain via the table
                    ps = {k: v + carry.astype(v.dtype)
                          for k, v in params.items()}
                if which == "forward":
                    out = loss(ps, xs_p)
                else:
                    val, grads = jax.value_and_grad(loss)(ps, xs_p)
                    out = val + sum(jnp.sum(g.astype(f32))
                                    for g in jax.tree.leaves(grads))
                return out * 1e-30  # chains the next iteration's input

            # params/xs are ARGUMENTS (not closure constants): constants
            # would let the simplifier fold the measured op away.
            timed = jax.jit(
                lambda n, params, xs: lax.fori_loop(
                    0, n, lambda i, c: body(c, params, xs),
                    jnp.zeros((), f32)))

            def run(n):
                t0 = _t.perf_counter()
                jax.device_get(timed(n, params, xs))
                return _t.perf_counter() - t0

            run(2)  # compile + warmup

            def attempt():
                base = min(run(4), run(4))
                n = 16
                while True:
                    diff = run(n) - base
                    if diff >= 0.05 or n >= 4096:
                        # spike guards, both directions: confirm with a
                        # second sample (min cancels a spiked numerator);
                        # a spiked BASELINE pushes diff negative — never
                        # persist that
                        diff = min(diff, run(n) - base)
                        return diff / (n - 4) if diff > 0 else None
                    n *= 4

            return attempt() or attempt()  # one retry on a bad baseline
        except TimeoutError:
            raise  # calibrate's wedge watchdog must see its own alarm
        except Exception as e:
            if os.environ.get("FF_COSTMODEL_DEBUG"):
                print(f"[cost_model] measure failed for {op.name} "
                      f"({which}): {type(e).__name__}: {e}", file=sys.stderr)
            return None

    # -- host-placed row-sparse embedding ---------------------------------
    def _host_embedding_time(self, op, which: str) -> float:
        """Row-sparse host-resident table (runtime:
        FFModel._host_embed_swap_in; reference embedding.cc CPU tasks):
        the host gathers the batch's rows from DDR and ships them over
        PCIe; backward returns row grads and scatter-adds the update
        host-side.  Per-step volume scales with the BATCH's rows, never
        the table."""
        m = self.machine
        rows = int(np.prod(op.inputs[0].dims))  # global batch x bag
        # the runtime transfers at most u_max = min(num_entries,
        # round8(n_idx)) unique rows (model.py swap-in) — without this
        # cap, small tables under large batches are overpriced and the
        # search is biased away from host placement
        rows = min(rows, int(op.num_entries))
        vol = 4.0 * rows * op.out_dim           # f32 rows on the wire
        t = (vol / m.host_memory_bandwidth + vol / m.pcie_bandwidth
             + m.kernel_launch_overhead + m.host_xfer_latency)
        if which == "backward":
            # row grads back over PCIe + host scatter-add + state row update
            t *= 2.0
        return float(t)

    # -- public ------------------------------------------------------------
    def attach_learned_tier(self, tier: Optional["LearnedCostTier"]) -> None:
        """Install (or clear) the learned regression tier.  Must happen
        before any costing: the ``op_time`` fast path memoizes results,
        so a tier attached mid-run would only affect never-seen keys."""
        assert not self._fast, \
            "attach_learned_tier must precede the first op_time call"
        self._learned = tier

    def op_time(self, op, pc, which: str) -> float:
        fk = (id(op), pc, which)
        hit = self._fast.get(fk)
        if hit is not None:
            t, stat = hit
            if stat is not None:
                # keep the counters telling the truth: a fast-path hit
                # bumps the same counter the slow path would have
                self.stats[stat] += 1
            return t
        t, stat = self._op_time_slow(op, pc, which)
        t += self._dcn_penalty(op, pc)
        self._fast[fk] = (t, stat)
        self._fast_ops[id(op)] = op
        return t

    def _dcn_penalty(self, op, pc) -> float:
        """Hierarchical-mesh surcharge: when a non-sample dim of this
        config would land on the ``dcn`` axis of the machine's hybrid
        mesh, the lowered step reshards this op's part across hosts
        every step — charge it at DCN bandwidth so the search keeps
        gradient all-reduce as the only DCN-crossing collective.  Added
        OUTSIDE the shape-keyed measured/analytic caches (those are
        placement-blind) and INSIDE the shared (op, pc) fast memo, so
        the full and delta simulators price it identically."""
        if pc is None or pc.host_placed:
            return 0.0
        sub = self._sub_output_shape(op, pc)
        part_bytes = self._dtype_bytes * float(np.prod(sub))
        return self.machine.dcn_spill_time(pc.dims, part_bytes)

    def _op_time_slow(self, op, pc, which: str):
        """Returns (time, stats counter a repeat call would bump)."""
        if pc is not None and pc.host_placed and op._type == "Embedding":
            return self._host_embedding_time(op, which), None
        key = self._key(op, pc, which)
        if key in self._measured:
            self.stats["measured_hits"] += 1
            return self._measured[key], "measured_hits"
        if self.measure and key not in self._measure_failed:
            t = self._measure_real(op, pc, which)
            if t is not None:
                self.stats["measured_runs"] += 1
                self._measured[key] = t
                self._persist(key, t)
                # a repeat call would find it in _measured
                return t, "measured_hits"
            self._measure_failed.add(key)
        if self._learned is not None:
            t = self._learned.predict(key)
            if t is not None:
                self.stats["learned"] += 1
                return t, "learned"
        self.stats["analytic"] += 1
        if key not in self._analytic_memo:
            self._analytic_memo[key] = self._analytic(op, pc, which)
        return self._analytic_memo[key], "analytic"
