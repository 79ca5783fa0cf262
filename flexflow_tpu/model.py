"""FFModel — the graph builder and training runtime.

TPU-native analogue of the reference core (reference: src/runtime/model.cc,
include/model.h:241-434).  The reference FFModel builds an op graph, then
``compile()`` resolves a per-op ``ParallelConfig`` strategy, creates Legion
regions/partitions, and the train loop issues index-task launches per op
with the mapper placing point tasks on GPUs.

Here the same graph compiles to **one fused, jitted SPMD train step**:

  * per-op strategies lower to ``with_sharding_constraint`` annotations on
    op outputs over a factored device mesh (parallel/mesh.py) — XLA GSPMD
    inserts all resharding/halo/gradient collectives over ICI, playing the
    role of Legion's implicit region movement;
  * the backward pass is ``jax.value_and_grad`` of the scalar loss (no
    per-op backward methods);
  * gradient replica aggregation (reference optimizer_kernel.cu:168-180)
    becomes the automatic psum of sharded-graph gradients;
  * the reference's Legion-trace replay (begin_trace/end_trace around the
    hot loop, e.g. examples/cpp/AlexNet/alexnet.cc:110-117) is subsumed by
    XLA compilation caching — every step after the first replays the same
    fused program.

The reference's 4-call driver API (``forward/zero_gradients/backward/
update``) is preserved: the calls stage work and the fused step executes at
``update()``; ``eval_*`` paths run a forward-only jit.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import os
import time
import warnings
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .config import DeviceType, FFConfig, ParallelConfig
from .initializers import DefaultWeightInitializer
from .losses import Loss, LossType
from .metrics import Metrics, MetricsType, PerfMetrics
from .ops.base import FwdCtx, Op
from .ops.conv2d import ActiMode, Conv2D, Pool2D, PoolType
from .ops.embedding import AggrMode, Embedding
from .ops.linear import Linear
from .ops.misc import (BatchNorm, Concat, Dropout, ElementBinary, ElementUnary,
                       Flat, MSELoss, Softmax)
from .parallel.mesh import Machine, dim_roles
from .parallel.strategy import load_strategies_from_file, save_strategies_to_file
from .runtime.profiling import count as _ff_count
from .runtime.profiling import graph_built as _ff_graph_built
from .runtime.profiling import model_made as _ff_model_made
from .runtime.profiling import phase as _ff_phase
from .runtime.profiling import span as _ff_span
from .runtime.profiling import step_enqueue as _ff_step_enqueue
from .tensor import DataType, Parameter, Tensor


class LayerHandle:
    """Deferred layer from the legacy v2 builder API (reference:
    examples/python/native/alexnet_new.py — declare with *_v2, then
    ``init_inout`` builds it onto the graph)."""

    def __init__(self, build):
        self._build = build

    def init_inout(self, ffmodel: "FFModel", input_tensor: Tensor) -> Tensor:
        return self._build(ffmodel, input_tensor)


def _op_scope(op: Op) -> str:
    """The `jax.named_scope` of a graph op in the compiled step:
    `ff.op.<op type>.<op name>`."""
    return f"ff.op.{op._type.lower()}.{op.name}"


def _copy_params_tree(tree):
    """Shallow per-op copy of a params-shaped tree so callers can swap
    individual weight leaves without mutating the caller's tree."""
    return {k: (dict(v) if isinstance(v, dict) else v)
            for k, v in tree.items()}


def _copy_state_tree(state):
    """Shallow copy of an optimizer-state tree (slot -> params-shaped
    subtree), one level deeper than ``_copy_params_tree``."""
    return {k: ({opn: dict(ws) for opn, ws in v.items()}
                if isinstance(v, dict) else v)
            for k, v in state.items()}


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        # the process's first model times what came before it and its
        # own graph's construction (profiling.counters())
        self._graph_since = _ff_model_made()
        self.config = config or FFConfig()
        self._guid = itertools.count(100)  # reference op_global_guid starts at 100
        self.ops: List[Op] = []
        self.input_tensors: List[Tensor] = []
        self._constants: Dict[int, Any] = {}  # guid -> (Tensor, fill value)
        self._offload: Dict[Tuple[str, str], Any] = {}  # host-offloaded weights
        self._offload_warned = False
        self._pipe_host_drop_warned = False
        # Row-sparse host-resident embedding tables (reference:
        # embedding.cc CPU tasks touch only the batch's rows): op name ->
        # {"weight", "input", "input_key", "u_max"}
        self._host_embed: Dict[str, Dict[str, Any]] = {}
        self._host_idx: Dict[str, np.ndarray] = {}  # host copies of index batches
        # async scatter-back of host-table rows (one in-flight step):
        # update() dispatches and returns; the worker forces the row
        # arrays and writes them home; _he_join() is the read barrier
        self._he_pool = None
        self._he_pending = None
        self._he_version = 0  # bumps when host-table rows change
        self._he_dev_cache = None  # decode's device copy of host tables
        self._dp_cache = None      # decode's unpacked-pipe params tree
        self.label_tensor: Optional[Tensor] = None
        self.machine: Optional[Machine] = None
        self.optimizer = None
        self.loss: Optional[Loss] = None
        self.metrics: Optional[Metrics] = None
        self.current_metrics = PerfMetrics()
        self.last_loss: Optional[float] = None
        self._metric_acc = None
        self._params = None
        self._stats = None
        self._opt_state = None
        self._step_count = 0
        self._batch: Optional[Dict[str, Any]] = None
        self._staged = False
        self._train_step_fn = None
        self._eval_step_fn = None
        self._logits_fn = None
        self._fresh_jit = False  # next train-step build bypasses the
        #                          persistent compile cache (recompile)
        self._compiled = False
        self._pipeline_req = None
        self._pipeline_plan = None
        # Telemetry handles, resolved ONCE at compile() (observability/):
        # None when disabled, so the hot path pays a single attribute
        # check and makes zero event-log calls.
        self._telemetry = None
        self._stepstats = None
        # Health monitor (observability/health.py): non-None only when
        # FF_HEALTH rides an enabled telemetry log.
        self._health = None
        # In-training per-op attribution (observability/opprof.py):
        # non-None only when FF_OPPROF rides an enabled telemetry log.
        self._opprof = None
        # Memory & compile plane (observability/memplane.py): non-None
        # only when FF_MEMPLANE rides an enabled telemetry log — wraps
        # the jitted steps with an explicit compile cache that emits
        # compile_done / xla_memory / xla_cost and counts retraces.
        self._memplane = None
        # Fault injector (testing/chaos.py, FF_CHAOS) and non-finite
        # step guard (runtime/resilience.py, FF_SKIP_NONFINITE) — both
        # resolved once at compile(), None when their env knob is unset
        # so every choke point is a single attribute test.
        self._chaos = None
        self._nonfinite_guard = None
        # Simulator's predicted step seconds (observability/agreement.py,
        # set post-compile under telemetry) for sim_divergence events.
        self._predicted_step_s = None

    # ------------------------------------------------------------------
    # graph construction
    # ------------------------------------------------------------------
    def _next_op_guid(self) -> int:
        return next(self._guid)

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.config.compute_dtype == "bfloat16" else jnp.float32

    def create_tensor(self, dims: Sequence[int], name: str = "",
                      dtype: str = DataType.FLOAT, nchw: bool = True) -> Tensor:
        """Create a graph input.  4-D dims are accepted in the reference's
        (N, C, H, W) order by default (include/model.h create_tensor<4>)
        and stored NHWC-native; pass ``nchw=False`` for native order."""
        dims = tuple(int(d) for d in dims)
        if len(dims) == 4 and nchw:
            n, c, h, w = dims
            dims = (n, h, w, c)
        t = Tensor(dims=dims, dtype=dtype, owner_op=None, name=name)
        self.input_tensors.append(t)
        return t

    def create_constant(self, dims: Sequence[int], value: float,
                        name: str = "", dtype: str = DataType.FLOAT,
                        nchw: bool = True) -> Tensor:
        """Graph-constant tensor filled with ``value`` (reference:
        FFModel::create_constant, exercised by tests/PCA/pca.cc:75-78).
        Materialized inside the traced graph, so XLA constant-folds it
        into consumers; it never appears in ``set_batch``."""
        dims = tuple(int(d) for d in dims)
        if len(dims) == 4 and nchw:
            n, c, h, w = dims
            dims = (n, h, w, c)
        t = Tensor(dims=dims, dtype=dtype, owner_op=None,
                   name=name or f"const_{len(self._constants)}")
        self._constants[t.guid] = (t, float(value))
        return t

    def _append(self, op: Op) -> Tensor:
        self.ops.append(op)
        return op.output

    # -- op vocabulary (reference: include/model.h:241-434) ------------
    def conv2d(self, input_tensor: Tensor, out_channels: int, kernel_h: int,
               kernel_w: int, stride_h: int, stride_w: int, padding_h: int,
               padding_w: int, activation: str = ActiMode.NONE,
               use_bias: bool = True, groups: int = 1,
               kernel_initializer=None, bias_initializer=None,
               *, share_with=None, name: Optional[str] = None) -> Tensor:
        return self._append(Conv2D(self, input_tensor, out_channels, kernel_h,
                                   kernel_w, stride_h, stride_w, padding_h,
                                   padding_w, activation, use_bias, groups,
                                   kernel_initializer, bias_initializer,
                                   share_with, name))

    def pool2d(self, input_tensor: Tensor, kernel_h: int, kernel_w: int,
               stride_h: int, stride_w: int, padding_h: int, padding_w: int,
               pool_type: str = PoolType.MAX, activation: str = ActiMode.NONE,
               name: Optional[str] = None) -> Tensor:
        return self._append(Pool2D(self, input_tensor, kernel_h, kernel_w,
                                   stride_h, stride_w, padding_h, padding_w,
                                   pool_type, activation, name))

    def dense(self, input_tensor: Tensor, out_dim: int,
              activation: str = ActiMode.NONE, use_bias: bool = True,
              kernel_initializer=None, bias_initializer=None,
              *, share_with=None, name: Optional[str] = None) -> Tensor:
        return self._append(Linear(self, input_tensor, out_dim, activation,
                                   use_bias, kernel_initializer,
                                   bias_initializer, share_with, name))

    linear = dense

    def embedding(self, input_tensor: Tensor, num_entries: int, out_dim: int,
                  aggr: str = AggrMode.SUM, kernel_initializer=None,
                  share_with=None, name: Optional[str] = None) -> Tensor:
        return self._append(Embedding(self, input_tensor, num_entries, out_dim,
                                      aggr, kernel_initializer, share_with, name))

    def lstm(self, input_tensor: Tensor, hidden_size: int, hx: Optional[Tensor] = None,
             cx: Optional[Tensor] = None, share_with=None,
             name: Optional[str] = None):
        """Sequence LSTM (B,T,E)→(B,T,H); returns (y, h_T, c_T) tensors.
        Reference: nmt/lstm.cu chunk op + SharedVariable weight sharing."""
        from .ops.lstm import LSTM

        op = LSTM(self, input_tensor, hidden_size, hx, cx, share_with, name)
        self.ops.append(op)
        return op.outputs[0], op.outputs[1], op.outputs[2]

    def multihead_attention(self, query: Tensor, key: Optional[Tensor] = None,
                            value: Optional[Tensor] = None,
                            embed_dim: Optional[int] = None, num_heads: int = 8,
                            causal: bool = False, dropout: float = 0.0,
                            use_bias: bool = False, kernel_initializer=None,
                            seq_parallel_mode: str = "ring",
                            name: Optional[str] = None) -> Tensor:
        """Multi-head attention (B,S,E)→(B,S,E); self-attention when key/
        value are omitted.  Sequence-dim partition degrees in this op's
        strategy lower to ring attention over ICI (parallel/sequence.py)."""
        from .ops.attention import MultiHeadAttention

        key = key if key is not None else query
        value = value if value is not None else key
        embed_dim = embed_dim if embed_dim is not None else query.dims[-1]
        return self._append(MultiHeadAttention(
            self, query, key, value, embed_dim, num_heads, causal, dropout,
            use_bias, kernel_initializer, seq_parallel_mode, name))

    def layer_norm(self, input_tensor: Tensor, eps: float = 1e-5,
                   elementwise_affine: bool = True,
                   name: Optional[str] = None) -> Tensor:
        from .ops.attention import LayerNorm

        return self._append(LayerNorm(self, input_tensor, eps,
                                      elementwise_affine, name))

    def rms_norm(self, input_tensor: Tensor, eps: float = 1e-6,
                 name: Optional[str] = None) -> Tensor:
        from .ops.misc import RMSNorm

        return self._append(RMSNorm(self, input_tensor, eps, name))

    def gated_mlp(self, input_tensor: Tensor, width: int,
                  kernel_initializer=None,
                  name: Optional[str] = None) -> Tensor:
        """(silu(x W_gate) * (x W_up)) W_down, no bias; the last config
        dim splits ``width``."""
        from .ops.linear import GatedMLP

        return self._append(GatedMLP(self, input_tensor, width,
                                     kernel_initializer, name))

    def latent_attention(self, input_tensor: Tensor, num_heads: int,
                         name: Optional[str] = None, **sizes) -> Tensor:
        """Causal multi-head latent attention (B,S,E)->(B,S,E) over the
        ``num_heads`` heads held here; ``sizes`` are ``LatentAttention``'s
        (the ranks, the three head dims, ``rope_theta``, ``rope_scaling``)."""
        from .ops.attention import LatentAttention

        return self._append(LatentAttention(self, input_tensor, num_heads,
                                            name=name, **sizes))

    def routed_experts(self, input_tensor: Tensor, n_routed_experts: int,
                       num_experts_per_tok: int, expert_width: int,
                       name: Optional[str] = None, **kw) -> Tensor:
        """Routed experts with shared experts under a device budget, for
        the experts held here (``RoutedExperts``); config dim 1 is the
        expert-parallel degree."""
        from .ops.moe import RoutedExperts

        return self._append(RoutedExperts(
            self, input_tensor, n_routed_experts, num_experts_per_tok,
            expert_width, name=name, **kw))

    def concat(self, tensors: Sequence[Tensor], axis: int,
               name: Optional[str] = None) -> Tensor:
        # Reference axis is in NCHW logical order (concat.cu); convert the
        # channel axis for 4-D tensors to the native NHWC position.
        if tensors[0].num_dims == 4:
            axis = {0: 0, 1: 3, 2: 1, 3: 2}[axis]
        return self._append(Concat(self, tensors, axis, name))

    def flat(self, input_tensor: Tensor, name: Optional[str] = None) -> Tensor:
        return self._append(Flat(self, input_tensor, name))

    def softmax(self, input_tensor: Tensor, name: Optional[str] = None) -> Tensor:
        return self._append(Softmax(self, input_tensor, name))

    def batch_norm(self, input_tensor: Tensor, relu: bool = True,
                   name: Optional[str] = None) -> Tensor:
        return self._append(BatchNorm(self, input_tensor, relu, name))

    # -- legacy "v2" declare-then-wire builders (reference:
    # python/flexflow/core used by examples/python/native/alexnet_new.py:
    # conv2d_v2(...) declares a layer handle, init_inout() wires it) -----
    def conv2d_v2(self, name: str, in_channels: int, out_channels: int,
                  kernel_h: int, kernel_w: int, stride_h: int, stride_w: int,
                  padding_h: int, padding_w: int,
                  activation: str = ActiMode.NONE,
                  use_bias: bool = True) -> "LayerHandle":
        def build(ff, t):
            if t.dims[3] != in_channels:  # NHWC
                raise ValueError(
                    f"{name}: declared in_channels={in_channels}, "
                    f"wired onto a {t.dims[3]}-channel tensor")
            return ff.conv2d(t, out_channels, kernel_h, kernel_w, stride_h,
                             stride_w, padding_h, padding_w,
                             activation=activation, use_bias=use_bias,
                             name=name)
        return LayerHandle(build)

    def pool2d_v2(self, name: str, kernel_h: int, kernel_w: int,
                  stride_h: int, stride_w: int, padding_h: int,
                  padding_w: int, pool_type: str = PoolType.MAX) -> "LayerHandle":
        return LayerHandle(lambda ff, t: ff.pool2d(
            t, kernel_h, kernel_w, stride_h, stride_w, padding_h, padding_w,
            pool_type=pool_type, name=name))

    def dense_v2(self, name: str, in_dim: int, out_dim: int,
                 activation: str = ActiMode.NONE,
                 use_bias: bool = True) -> "LayerHandle":
        def build(ff, t):
            if t.dims[-1] != in_dim:
                raise ValueError(f"{name}: declared in_dim={in_dim}, wired "
                                 f"onto a {t.dims[-1]}-wide tensor")
            return ff.dense(t, out_dim, activation=activation,
                            use_bias=use_bias, name=name)
        return LayerHandle(build)

    def flat_v2(self, name: str) -> "LayerHandle":
        return LayerHandle(lambda ff, t: ff.flat(t, name=name))

    def dropout(self, input_tensor: Tensor, rate: float, seed: int = 0,
                name: Optional[str] = None) -> Tensor:
        return self._append(Dropout(self, input_tensor, rate, seed, name))

    def pipeline_mlp(self, input_tensor: Tensor, num_stages: int,
                     num_microbatches: int = 4, activation: str = "relu",
                     name: Optional[str] = None) -> Tensor:
        """Stack of identical dense stages pipelined over config dim 1
        (GPipe microbatching; the SOAP Operator-dimension analogue of the
        reference's per-op GPU placement, nmt/nmt.cc:269-308)."""
        from .ops.pipeline import PipelineMLP
        return self._append(PipelineMLP(self, input_tensor, num_stages,
                                        num_microbatches, activation, name))

    def expert_mlp(self, input_tensor: Tensor, num_experts: int,
                   hidden_size: int, capacity_factor: float = 1.25,
                   activation: str = "relu",
                   name: Optional[str] = None) -> Tensor:
        """Switch-style MoE layer; config dim 1 is the EXPERT-parallel
        degree (expert weights shard over it, GSPMD emits the token
        all_to_all) — the SOAP hook SURVEY §2.3 marks as design headroom
        over the reference."""
        from .ops.moe import ExpertMLP
        return self._append(ExpertMLP(self, input_tensor, num_experts,
                                      hidden_size, capacity_factor,
                                      activation, name))

    def mse_loss(self, logits: Tensor, labels: Tensor,
                 reduction: str = "average", name: Optional[str] = None) -> Tensor:
        return self._append(MSELoss(self, logits, labels, reduction, name))

    # ------------------------------------------------------------------
    # general pipeline parallelism (operator placement)
    # ------------------------------------------------------------------
    def set_pipeline(self, num_stages: Optional[int] = None,
                     stages: Optional[Sequence[Sequence[str]]] = None,
                     num_microbatches: int = 4,
                     degree: Optional[int] = None,
                     dp_degree: int = 1,
                     remat: Optional[bool] = None) -> None:
        """Assign the op graph to pipeline stages (operator placement).

        The reference pipelines heterogeneous graphs by pinning each op to
        a GPU list (nmt/nmt.cc:269-308 pins encoder ops to one GPU set and
        decoder ops to another; src/mapper/mapper.cc:33-146 places the
        point tasks).  Here each stage is a contiguous run of ops executed
        by one slice of the mesh's pipe axes, with activations crossing
        stage boundaries over a ppermute ring under a GPipe microbatch
        schedule (parallel/pipeline.py pipeline_graph_apply).

        ``stages``: explicit op-name lists (contiguous partition of the
        graph), or ``num_stages`` to auto-balance the chain by per-op
        FLOPs.  ``degree``: ring size (defaults to num_stages; must divide
        it).  ``dp_degree``: batch-parallel degree composed with the
        pipeline (dp x pp).  ``remat``: rematerialize each ring slot so
        only boundary carries are stashed across the scan — the memory
        lever that lets ``num_microbatches`` grow and shrink the GPipe
        bubble fraction (defaults to ``config.remat``; see
        docs/ADR-002-pipeline-schedule.md).  Call before ``compile()``.
        """
        if stages is None:
            assert num_stages is not None and num_stages >= 1
            self._pipeline_req = {"num_stages": int(num_stages), "names": None}
        else:
            self._pipeline_req = {"num_stages": len(stages),
                                  "names": [list(g) for g in stages]}
        self._pipeline_req.update(num_microbatches=int(num_microbatches),
                                  degree=degree, dp_degree=int(dp_degree),
                                  remat=remat)

    def _plan_pipeline(self) -> None:
        """Resolve ``set_pipeline`` into a validated stage plan.

        The pipelined segment is the whole graph, minus a trailing Softmax
        (kept outside so the loss can read the pre-softmax logits).  Each
        stage must consume only tensors produced inside itself, the single
        boundary tensor from the previous stage, or graph constants.
        """
        self._pipeline_plan = None
        req = getattr(self, "_pipeline_req", None)
        if req is None:
            return
        seg = list(self.ops)
        tail: List[Op] = []
        while seg and isinstance(seg[-1], Softmax):
            tail.insert(0, seg.pop())
        # Host-placed row-sparse embeddings run BEFORE the ring as a
        # heterogeneous head (the reference's hetero DLRM: CPU-resident
        # tables + accelerator pipeline, dlrm_strategy_hetero.cc) —
        # packing a host table into the device pipe buffer would
        # silently drop the CPU placement.  Eligible embeddings depend
        # only on graph inputs, so hoisting is always legal; their
        # outputs feed stage 0 like extra segment inputs.
        head: List[Op] = []
        kept: List[Op] = []
        for op in seg:
            # the STRICT runtime predicate: hoisting an op the runtime
            # would not actually execute row-sparse (e.g. a shared index
            # consumed by a device-placed sibling) would exclude it from
            # the ring for no benefit and stream its full table
            if (isinstance(op, Embedding) and op.pc.host_placed
                    and self._sparse_embed_ok(op)):
                head.append(op)
            else:
                kept.append(op)
        seg = kept
        if not seg:
            raise ValueError("pipeline: no ops to pipeline")
        head_names = {op.name for op in head}
        if req["names"] is not None:
            by_name = {op.name: op for op in seg}
            stages = []
            for group in req["names"]:
                g = [by_name[n] for n in group if n not in head_names]
                if g:
                    stages.append(g)
            flat = [op for g in stages for op in g]
            if flat != seg:
                raise ValueError(
                    "pipeline stages must be a contiguous in-order "
                    "partition of the op graph (minus a trailing Softmax "
                    "and host-placed row-sparse embeddings)")
        else:
            from .parallel.pipeline_plan import balanced_stages

            stages = balanced_stages(seg, req["num_stages"])
        S = len(stages)

        # Dataflow plan FIRST (structural errors surface regardless of
        # whether a ring is expressible): each hop carries the k tensors
        # later stages still need (branching graphs, skip connections and
        # multi-input stage 0 welcome).  Shared with the stage-assignment
        # search so it never recommends a plan this planner would reject.
        from .parallel.pipeline_plan import plan_boundaries

        seg_ins, boundaries = plan_boundaries(
            stages, tail, set(self._constants.keys()),
            list(self.input_tensors) + [op.output for op in head])
        final_out = stages[-1][-1].output

        import math
        degree = req["degree"] if req["degree"] else S
        degree = math.gcd(degree, S)
        # Ring size must also be expressible over the mesh axes left after
        # the dp group (e.g. degree 3 can't factor over a 2^k mesh).
        while degree > 1:
            try:
                self.machine.axes_for_degrees([req["dp_degree"], degree])
                break
            except ValueError:
                degree = max(d for d in range(1, degree)
                             if S % d == 0 and degree % d == 0)
        if degree <= 1 or self.machine.num_devices <= 1:
            # No expressible ring: keep the ops' regular (data-parallel)
            # configs rather than forcing no-split placeholders — a
            # silently replicated segment would be a large perf
            # regression versus not pipelining at all.
            if self.machine.num_devices > 1:
                print(f"flexflow_tpu: pipeline degree for {S} stages not "
                      f"expressible over mesh "
                      f"{dict(zip(self.machine.axis_names, self.machine.axis_sizes))}"
                      f"; running without pipelining")
            return
        # warn only once the plan actually commits — bailing out above
        # (inexpressible ring) keeps every placement intact
        for op in seg:
            if op.pc.host_placed and not self._pipe_host_drop_warned:
                self._pipe_host_drop_warned = True
                print(f"flexflow_tpu: host placement for {op.name} is "
                      f"DROPPED inside the pipeline segment (stage "
                      f"weights pack into the device ring buffer); only "
                      f"row-sparse-eligible embeddings run host-side "
                      f"ahead of the ring")
        self._pipeline_plan = {
            "stages": stages, "head": head, "degree": int(degree),
            "dp_degree": int(req["dp_degree"]),
            "num_microbatches": int(req["num_microbatches"]),
            "remat": bool(self.config.remat if req.get("remat") is None
                          else req["remat"]),
            "seg_ins": seg_ins, "boundaries": boundaries,
            "seg_in_guids": {t.guid for t in seg_ins},
            "seg_out": final_out,
            "i0": self.ops.index(stages[0][0]),
            "i1": self.ops.index(stages[-1][-1]) + 1,
        }
        self._pipeline_plan["pack"] = self._plan_pipeline_pack(
            stages, int(degree))
        # Pipelined ops execute inside the pipeline's shard_map: force
        # their configs to no-split so op forwards take the plain jnp path
        # (no nested shard_map) and their weights replicate over the mesh.
        for g in stages:
            for op in g:
                if op.init_stats():
                    raise ValueError(
                        f"pipeline: op {op.name} carries running stats "
                        f"(e.g. BatchNorm) — unsupported inside a pipeline")
                op.pc = ParallelConfig(dims=(1,) * op.output.num_dims)

    def _plan_pipeline_pack(self, stages, ring: int):
        """Stage-weight placement layout: pack each ring slot's weights
        into one row of a (ring, width) float32 buffer sharded over the
        pipe axes, so an S-slot pipeline stores ~1/S of the segment's
        weights per device — the analogue of the reference mapper placing
        each op's weights only on its assigned GPUs
        (src/mapper/mapper.cc:33-146).  Weights shared across slots or
        with ops outside the segment stay replicated (excluded).

        Returns {"entries": {param_key: {wname: (slot, off, shape, n)}},
        "ring": ring, "width": W} or None when nothing is packable.
        """
        S = len(stages)
        k = S // ring
        seg_ops = [op for g in stages for op in g]
        key_slot: Dict[str, int] = {}
        conflict = set()
        for si, g in enumerate(stages):
            r = si // k
            for op in g:
                owner = op.share_from if op.share_from is not None else op
                if not owner.weights:
                    continue
                pk = op.param_key
                if pk in key_slot and key_slot[pk] != r:
                    conflict.add(pk)
                key_slot.setdefault(pk, r)
        seg_ids = {id(op) for op in seg_ops}
        for op in self.ops:
            if id(op) not in seg_ids and op.param_key in key_slot:
                conflict.add(op.param_key)
        slot_off = [0] * ring
        entries: Dict[str, Dict[str, tuple]] = {}
        for op in seg_ops:  # graph order: deterministic offsets
            owner = op.share_from if op.share_from is not None else op
            pk = op.param_key
            if (not owner.weights or pk in conflict or pk in entries
                    or pk not in key_slot):
                continue
            if any(w.dtype != "float32" for w in owner.weights):
                continue  # packing assumes one buffer dtype
            r = key_slot[pk]
            emap = {}
            for w in owner.weights:
                n = int(np.prod(w.dims))
                emap[w.name] = (r, slot_off[r], tuple(w.dims), n)
                slot_off[r] += n
            entries[pk] = emap
        width = max(slot_off) if entries else 0
        if width == 0:
            return None
        return {"entries": entries, "ring": ring, "width": width}

    def _pipe_pack(self):
        plan = getattr(self, "_pipeline_plan", None)
        return plan.get("pack") if plan else None

    # Pack-entry layout (slot, off, shape, n) read/write in one place.
    @staticmethod
    def _pack_read(buf_row, entry):
        _, off, shape, n = entry
        return buf_row[off:off + n].reshape(shape)

    @staticmethod
    def _pack_write(buf, entry, value):
        r, off, _, n = entry
        return buf.at[r, off:off + n].set(value.reshape(-1))

    @staticmethod
    def _pack_write_host(np_buf, entry, value):
        """In-place numpy twin of _pack_write (checkpoint assembly)."""
        r, off, _, n = entry
        np_buf[r, off:off + n] = np.asarray(value).reshape(-1)

    def _pipe_buffer_sharding(self) -> NamedSharding:
        plan = self._pipeline_plan
        groups = self.machine.axes_for_degrees(
            [plan["dp_degree"], plan["degree"]])
        paxes = groups[1]
        return NamedSharding(
            self.machine.mesh,
            PartitionSpec(paxes if len(paxes) > 1 else paxes[0]))

    # -- k-tensor ring-payload bundles (branching pipeline graphs) -----
    @staticmethod
    def _bundle_layout(tensors, pdtype):
        """[(tensor, offset, per-sample flat n, lanes)] + total width.

        The payload rides the compute dtype.  int32 tensors BITCAST in
        exactly: one f32 lane each on a float32 payload, two 16-bit
        lanes each on a bfloat16 payload — never a lossy value cast, and
        no f32 fallback doubling every hop's bandwidth for one token-id
        input (lax.bitcast has a zero JVP, so autodiff treats indices as
        the non-differentiable data they are)."""
        two_lane = jnp.dtype(pdtype).itemsize == 2
        layout, off = [], 0
        for t in tensors:
            n = int(np.prod(t.dims[1:])) if len(t.dims) > 1 else 1
            lanes = n * (2 if two_lane and "int" in t.dtype else 1)
            layout.append((t, off, n, lanes))
            off += lanes
        return layout, max(off, 1)

    @staticmethod
    def _bundle_pack(env, layout, pdtype):
        """Pack boundary tensors into one (B, width) payload."""
        parts = []
        for t, _, n, lanes in layout:
            v = env[t.guid]
            v = v.reshape(v.shape[0], n)
            if "int" in t.dtype:
                v = jax.lax.bitcast_convert_type(v.astype(jnp.int32),
                                                 pdtype)
                v = v.reshape(v.shape[0], lanes)  # (B,n,2)->(B,2n) on bf16
            parts.append(v.astype(pdtype))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 1)

    def _bundle_unpack(self, h, layout, pdtype):
        cdtype = self.compute_dtype
        env = {}
        for t, off, n, lanes in layout:
            v = h[:, off:off + lanes]
            if "int" in t.dtype:
                if lanes != n:  # two 16-bit lanes per int32
                    v = v.reshape(v.shape[0], n, 2)
                v = jax.lax.bitcast_convert_type(v.astype(pdtype), jnp.int32)
            else:
                v = v.astype(cdtype)
            env[t.guid] = v.reshape((h.shape[0],) + tuple(t.dims[1:]))
        return env

    def _stage_fn(self, stage_ops: List[Op], in_layout, out_layout,
                  pdtype):
        const_items = list(self._constants.values())
        pack = self._pipe_pack()

        def resolve(params, op):
            """Op weights: packed stage-local slice of the pipe buffer
            (this device's row of the (ring, W) buffer — inside the
            shard_map the local view is (1, W)), else the plain tree."""
            pk = op.param_key
            if pack and pk in pack["entries"]:
                local = params["_pipe"]["buffer"].reshape(-1)
                return {wn: FFModel._pack_read(local, e)
                        for wn, e in pack["entries"][pk].items()}
            return params.get(pk, {})

        def fn(params, h, ctx, micro_idx):
            # Per-microbatch RNG stream: without the fold, every
            # microbatch (and dp shard) would reuse one dropout mask.
            rng = (jax.random.fold_in(ctx.rng, micro_idx)
                   if ctx.rng is not None else None)
            mctx = FwdCtx(training=ctx.training, rng=rng,
                          stats_in=ctx.stats_in, stats_out=ctx.stats_out)
            env = self._bundle_unpack(h, in_layout, pdtype)
            for t, val in const_items:
                fill_dtype = jnp.int32 if "int" in t.dtype \
                    else self.compute_dtype
                env[t.guid] = jnp.full(t.dims, val, fill_dtype)
            for op in stage_ops:
                xs = [env[t.guid] for t in op.inputs]
                with jax.named_scope(_op_scope(op)):
                    ys = op.forward(resolve(params, op), xs, mctx)
                for t, y in zip(op.outputs, ys):
                    env[t.guid] = y
            return self._bundle_pack(env, out_layout, pdtype)

        return fn

    def _run_pipeline_segment(self, params, env, ctx):
        from .parallel.pipeline import pipeline_graph_apply

        plan = self._pipeline_plan
        stages = plan["stages"]
        seg_ins, boundaries = plan["seg_ins"], plan["boundaries"]
        seg_out = plan["seg_out"]
        pdtype = self.compute_dtype  # ints bitcast in (see _bundle_layout)
        in_bundles = [list(seg_ins)] + [list(h) for h in boundaries]
        out_bundles = [list(h) for h in boundaries] + [[seg_out]]
        fns, in_shapes, out_shapes = [], [], []
        in0_layout = None
        for si, g in enumerate(stages):
            in_l, n_in = self._bundle_layout(in_bundles[si], pdtype)
            out_l, n_out = self._bundle_layout(out_bundles[si], pdtype)
            if si == 0:
                in0_layout = in_l
            f = self._stage_fn(g, in_l, out_l, pdtype)
            fns.append(lambda p, h, mi, f=f: f(p, h, ctx, mi))
            in_shapes.append((n_in,))
            out_shapes.append((n_out,))
        x = self._bundle_pack(env, in0_layout, pdtype)
        groups = self.machine.axes_for_degrees(
            [plan["dp_degree"], plan["degree"]])
        batch_axes = groups[0] if groups[0] else None
        pipe_axes = groups[1]
        # Per-shard microbatch count (the shard_map body sees the batch
        # after dp sharding).
        local_b = x.shape[0] // max(1, plan["dp_degree"])
        mb = min(plan["num_microbatches"], local_b)
        while local_b % mb != 0:
            mb -= 1
        seg_params = {op.param_key: params[op.param_key]
                      for g in stages for op in g if op.param_key in params}
        param_specs = None
        pack = self._pipe_pack()
        if pack:
            seg_params["_pipe"] = params["_pipe"]
            param_specs = {k: jax.tree.map(lambda _: PartitionSpec(), v)
                           for k, v in seg_params.items()}
            param_specs["_pipe"] = {
                "buffer": self._pipe_buffer_sharding().spec}
        y = pipeline_graph_apply(fns, seg_params, x, self.machine.mesh,
                                 pipe_axes, mb, in_shapes, out_shapes,
                                 batch_axes=batch_axes,
                                 param_specs=param_specs,
                                 remat=plan.get("remat", False))
        out_l, _ = self._bundle_layout([seg_out], pdtype)
        return self._bundle_unpack(y.reshape(x.shape[0], -1),
                                   out_l, pdtype)[seg_out.guid]

    def _unary(self, op_name, x, name=None):
        return self._append(ElementUnary(self, x, op_name, name))

    def exp(self, x, name=None):
        return self._unary("exp", x, name)

    def relu(self, x, name=None):
        return self._unary("relu", x, name)

    def sigmoid(self, x, name=None):
        return self._unary("sigmoid", x, name)

    def tanh(self, x, name=None):
        return self._unary("tanh", x, name)

    def elu(self, x, name=None):
        return self._unary("elu", x, name)

    def _binary(self, op_name, x, y, name=None):
        return self._append(ElementBinary(self, x, y, op_name, name))

    def add(self, x, y, name=None):
        return self._binary("add", x, y, name)

    def subtract(self, x, y, name=None):
        return self._binary("subtract", x, y, name)

    def multiply(self, x, y, name=None):
        return self._binary("multiply", x, y, name)

    def divide(self, x, y, name=None):
        return self._binary("divide", x, y, name)

    # ------------------------------------------------------------------
    # compile
    # ------------------------------------------------------------------
    def compile(self, optimizer=None, loss_type: str = LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                metrics: Sequence[str] = (MetricsType.ACCURACY,),
                machine: Optional[Machine] = None) -> None:
        """Resolve strategies, build the mesh, stage the jitted SPMD step.

        Mirrors FFModel::compile (src/runtime/model.cc:986-1046): optional
        strategy import / search, per-op partition resolution, label tensor
        creation, optimizer wiring.

        Telemetry (observability/) is resolved here — the one place a
        model learns whether ``FFConfig.telemetry`` / ``FF_TELEMETRY`` is
        set — so every later step guards on a plain ``None`` handle.
        """
        if self._graph_since is not None:
            _ff_graph_built(self._graph_since)
            self._graph_since = None
        from .observability import events as _ff_events
        from .observability import health as _ff_health
        from .runtime import resilience as _ff_resilience
        from .testing import chaos as _ff_chaos

        # Heartbeat is independent of telemetry (stdlib; no-op unless
        # FF_HEARTBEAT_PATH is set): an external watchdog can name a
        # wedged compile even on an untraced run.
        _ff_health.write_heartbeat("compile")
        self._telemetry = _ff_events.for_config(self.config)
        # Chaos + the non-finite guard are independent of telemetry
        # (recovery must work on untraced runs; events are narration).
        self._chaos = _ff_chaos.from_env()
        _nf = _ff_resilience.nonfinite_limit()
        self._nonfinite_guard = (
            _ff_resilience.NonFiniteGuard(self, _nf, self._telemetry)
            if _nf else None)
        with _ff_phase(self._telemetry, "compile",
                       **self._compile_span_attrs()) as at:
            self._compile_impl(optimizer, loss_type, metrics, machine)
            at["num_devices"] = self.machine.num_devices
            at["batch_size"] = self.config.batch_size
        if self._telemetry is None:
            self._stepstats = None
            self._health = None
            self._opprof = None
            self._memplane = None
            return
        from .observability.stepstats import StepStats

        self._stepstats = StepStats(self, self._telemetry)
        if _ff_health.enabled():
            self._health = _ff_health.HealthMonitor(self, self._telemetry)
            self._telemetry.add_observer(self._health.observe)
        else:
            self._health = None
        from .observability import metrics as _ff_metrics
        from .observability import opprof as _ff_opprof

        # Live metrics plane (FF_METRICS_PORT) + in-training per-op
        # attribution (FF_OPPROF) — both None-handle gated like health.
        _ff_metrics.maybe_start(self._telemetry)
        self._opprof = _ff_opprof.maybe_profiler(self, self._telemetry)
        from .observability import agreement as _ff_agreement
        from .observability import memplane as _ff_memplane

        _ff_agreement.emit_compile_prediction(self, self._telemetry)
        # Memory plane: the predicted view (one event, every telemetry
        # run) + the FF_MEMPLANE-gated compile observatory.
        self._memplane = _ff_memplane.maybe_plane(self._telemetry)
        _ff_memplane.emit_memory_prediction(self, self._telemetry)
        self._telemetry.flush()

    def _compile_span_attrs(self) -> Dict[str, Any]:
        """What the log's compile and recompile spans start with."""
        if self._telemetry is None:
            return {}
        from .observability.reqtrace import run_trace_id

        return dict(num_ops=len(self.ops),
                    trace_id=run_trace_id(self._telemetry.run_id))

    def _compile_impl(self, optimizer=None,
                      loss_type: str = LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                      metrics: Sequence[str] = (MetricsType.ACCURACY,),
                      machine: Optional[Machine] = None) -> None:
        cfg = self.config
        self.optimizer = optimizer
        self.loss = Loss(loss_type)
        self.metrics = Metrics(self.loss.loss_type, list(metrics))
        if machine is not None:
            self.machine = machine
        elif cfg.num_nodes > 1 or jax.process_count() > 1:
            # Multi-host: hybrid ICI×DCN mesh with the DCN axis leading
            # (parallel/distributed.py) — the GASNet-multi-node analogue.
            from .parallel.distributed import hybrid_machine
            self.machine = hybrid_machine(
                dcn_degree=max(cfg.num_nodes, jax.process_count()))
        else:
            if cfg.num_devices > len(jax.devices()):
                raise ValueError(
                    f"{cfg.num_devices} device(s) requested "
                    f"(-ll:tpu {cfg.workers_per_node} x {cfg.num_nodes} "
                    f"node(s)) but jax.devices() has {len(jax.devices())} "
                    f"({jax.devices()[0].platform})")
            self.machine = Machine(num_devices=cfg.num_devices)

        if cfg.import_strategy_file:
            cfg.strategies.update(load_strategies_from_file(
                cfg.import_strategy_file,
                reference_order=cfg.import_strategy_reference_order))
        if cfg.search_budget > 0:
            # Native C++ annealing engine when built, Python MCMC otherwise
            # (reference: compile() launches STRATEGY_SEARCH_TASK,
            # model.cc:991-999).  Both engines must search the REAL
            # machine (self.machine) with the same overlap objective.
            from .simulator.machine import TPUMachineModel
            from .simulator.native_search import native_mcmc_search

            mm = TPUMachineModel.calibrated(num_devices=self.machine.num_devices)
            best = None
            if cfg.search_engine == "population":
                from .simulator.population import population_search

                best = population_search(self, budget=cfg.search_budget,
                                         alpha=cfg.search_alpha,
                                         machine_model=mm, seed=cfg.seed,
                                         verbose=False)
            elif cfg.search_engine not in ("", "mcmc", "native"):
                raise ValueError(
                    f"unknown search_engine {cfg.search_engine!r} "
                    "(expected '', 'native', 'mcmc', or 'population')")
            if best is None and cfg.search_engine in ("", "native"):
                r = native_mcmc_search(self, budget=cfg.search_budget,
                                       alpha=cfg.search_alpha,
                                       machine_model=mm,
                                       seed=cfg.seed,
                                       overlap=cfg.search_overlap_backward_update,
                                       verbose=False)
                if r is not None:
                    best = r[0]
            if best is None:
                from .simulator.search import mcmc_search

                best = mcmc_search(self, budget=cfg.search_budget,
                                   alpha=cfg.search_alpha, machine_model=mm,
                                   seed=cfg.seed)
            cfg.strategies.update(best)
            # Both engines return a SearchResult carrying the simulated
            # cost of the plan they just found — keep it for the
            # provenance sidecar (and the pipeline comparison below)
            # instead of re-simulating.
            self._search_provenance = {
                "engine": getattr(best, "engine", "mcmc"),
                "budget": cfg.search_budget,
                "seed": cfg.seed,
                "best_s": getattr(best, "best_s", None),
                "dp_s": getattr(best, "dp_s", None),
                "machine_model": mm,
                # population engine: per-chain stats + learned-tier CV
                # provenance ride into the exported sidecar
                "search_stats": getattr(best, "stats", None),
            }

            # Stage-assignment search (--search-pipeline): when a GPipe
            # plan beats the best dim strategy AND the user hasn't placed
            # stages by hand, apply it — operator placement discovered by
            # the search, not just by the user (the reference's searched
            # space and placement are one mechanism, mapper.cc:33-146).
            if (cfg.search_pipeline
                    and getattr(self, "_pipeline_req", None) is None):
                from .simulator.pipeline_search import search_pipeline

                dims_t = getattr(best, "best_s", None)
                if dims_t is None:
                    from .simulator.cost_model import CostModel
                    from .simulator.simulator import Simulator

                    sim = Simulator(mm, CostModel(
                        mm, measure=False, compute_dtype=cfg.compute_dtype))
                    dims_t = sim.simulate_runtime(self, dict(best))
                plan = search_pipeline(self, machine_model=mm)
                if plan is not None and plan["simulated_s"] < dims_t:
                    print(f"flexflow_tpu: search selected a pipeline plan "
                          f"({plan['num_stages']} stages x "
                          f"dp{plan['dp_degree']}, "
                          f"M={plan['num_microbatches']}"
                          f"{', remat' if plan.get('remat') else ''}): "
                          f"{plan['simulated_s'] * 1e3:.3f} ms vs "
                          f"{dims_t * 1e3:.3f} ms for the dim strategy")
                    self.set_pipeline(
                        num_stages=plan["num_stages"],
                        dp_degree=plan["dp_degree"],
                        num_microbatches=plan["num_microbatches"],
                        remat=plan.get("remat"))

        # Per-op partition configs (default: data parallel over all devices,
        # reference model.cc:391-401 + strategy.cc:28-85 fallback).
        nd = self.machine.num_devices
        for op in self.ops:
            pc = cfg.find_parallel_config(op.output.num_dims, op.name)
            if pc.num_parts() > nd:
                pc = ParallelConfig.data_parallel(op.output.num_dims, nd)
            op.pc = self._legalize_pc(op, pc)

        # Resolve operator placement (general pipeline parallelism) —
        # overrides the pipelined ops' configs with no-split placeholders.
        self._plan_pipeline()

        # Fused Pallas optimizer kernels: on a multi-device machine each
        # parameter's update runs inside a per-leaf shard_map with its
        # own PartitionSpec (optimizers.Optimizer._shardwise) —
        # init_layers installs the mesh + specs.  Unconditional
        # assignment so an optimizer reused across compiles never
        # carries a stale flag.
        if optimizer is not None:
            optimizer.fused = bool(cfg.fused_optimizer)
            # Mosaic compiles the kernels for a TPU only; anywhere else
            # they can run in the Pallas interpreter, which is a test
            # vehicle — so say it rather than switch silently.
            platform = self.machine.devices[0].platform
            optimizer.fused_interpret = platform != "tpu"
            if optimizer.fused and optimizer.fused_interpret:
                print(f"flexflow_tpu: fused optimizer on "
                      f"platform={platform}: the Pallas kernels run in "
                      f"the interpreter, not compiled")

        # Export AFTER resolution so imported/searched configs are what get
        # written (reference exports from FFConfig::strategies the same way).
        if cfg.export_strategy_file:
            save_strategies_to_file(cfg.export_strategy_file,
                                    self._all_strategies(),
                                    provenance=self._export_provenance())

        # Label tensor (reference creates it in compile; dims follow loss).
        logits = self._loss_input_tensor()
        if self.loss.loss_type == LossType.SPARSE_CATEGORICAL_CROSSENTROPY:
            # (B, 1) for classifiers (reference convention), (B, T) for
            # sequence models.
            ldims = logits.dims[:-1] if logits.num_dims > 2 else (logits.dims[0], 1)
            self.label_tensor = Tensor(ldims, DataType.INT32, name="label")
        else:
            self.label_tensor = Tensor(tuple(self.final_tensor().dims), DataType.FLOAT, name="label")

        self._compiled = True
        self._train_step_fn = None
        self._eval_step_fn = None
        self._logits_fn = None

    def _legalize_pc(self, op: Op, pc: ParallelConfig) -> ParallelConfig:
        """Clamp a config to one the op can execute (op-specific hook:
        ops/base.py Op.legalize_pc)."""
        return op.legalize_pc(pc)

    def _all_strategies(self) -> Dict[str, ParallelConfig]:
        return {op.name: getattr(op, "pc", ParallelConfig.data_parallel(
            op.output.num_dims, self.machine.num_devices)) for op in self.ops}

    def recompile(self, strategies: Optional[Dict[str, ParallelConfig]] = None,
                  machine: Optional[Machine] = None) -> None:
        """Re-parallelize a compiled (and possibly mid-training) model IN
        PLACE: swap the strategy map and/or the machine, re-resolve
        per-op configs, rebuild the jitted step, and migrate the live
        training state onto the new shardings through the same canonical
        host-side form a cross-mesh checkpoint restore uses.

        This is the hot-swap half of online re-parallelization
        (runtime/reconfigure.py): the controller drains, saves, calls
        ``recompile`` with the re-searched strategies (and a shrunken
        ``Machine(devices=survivors)`` after a device loss), then
        restores — the restore targets are built from the model's
        CURRENT shardings, so state re-shards onto the new mesh.

        No search, no import/export: the caller owns strategy selection
        here.  ``config.strategies`` keeps the applied map so later
        exports/provenance reflect what is actually running.

        Limitation: pipelined models repack their stage buffer with the
        PREVIOUS buffer's sharding, so a pipelined swap is only safe
        while the device set is unchanged (divergence-triggered swaps).
        """
        assert self._compiled, "recompile() requires a compiled model"
        import contextlib

        from .runtime.checkpoint import _tree_from_model, place_state

        # Snapshot live state in the canonical layout-portable form
        # (host numpy) BEFORE the mesh/shardings change underneath it.
        state = None
        if self._params is not None:
            state = jax.tree.map(
                lambda a: np.asarray(jax.device_get(a))
                if hasattr(a, "shape") else a, _tree_from_model(self))

        cfg = self.config
        saved = (cfg.search_budget, cfg.import_strategy_file,
                 cfg.export_strategy_file)
        cfg.search_budget = 0
        cfg.import_strategy_file = None
        cfg.export_strategy_file = None
        if strategies is not None:
            cfg.strategies.update(strategies)
        tel = self._telemetry
        try:
            with _ff_phase(tel, "recompile",
                           **self._compile_span_attrs()) as at:
                self._compile_impl(
                    self.optimizer, self.loss.loss_type,
                    list(self.metrics.metrics),
                    machine=machine if machine is not None else self.machine)
                at["num_devices"] = self.machine.num_devices
        finally:
            (cfg.search_budget, cfg.import_strategy_file,
             cfg.export_strategy_file) = saved
        # The swapped-in step function must be compiled fresh, never
        # deserialized from the persistent cache (_bypass_compile_cache).
        self._fresh_jit = True

        # Re-run the optimizer-wiring half of init_layers: mesh, per-leaf
        # specs, and the ZeRO layout all follow the new machine — a stale
        # mesh here would shard-map updates over devices that are gone.
        if self.optimizer is not None and state is not None:
            shardings = self._param_spec_tree()
            specs = {opn: {wn: sh.spec for wn, sh in ws.items()}
                     for opn, ws in shardings.items()}
            multi = self.machine.num_devices > 1
            nonfused = set(self._offload)
            nonfused |= {(opn, info["weight"])
                         for opn, info in self._host_embed.items()}
            zero_specs = (self._zero_state_specs()
                          if cfg.zero_optimizer and multi else None)
            if zero_specs:
                nonfused |= set(zero_specs)
            self.optimizer.set_mesh(self.machine.mesh if multi else None,
                                    specs, nonfused_paths=nonfused)
            self.optimizer.zero_specs = zero_specs

        if state is not None:
            place_state(self, state)
        # Device-resident caches keyed on the old mesh: the staged batch
        # is re-placed by the next set_batch; what the accumulator holds
        # moves to the new mesh, placed as the new step function returns it.
        self._batch = None
        if self._metric_acc is not None:
            self._metric_acc = jax.device_put(
                np.asarray(jax.device_get(self._metric_acc)),
                self.machine.replicated())
        self._dp_cache = None
        self._he_dev_cache = None

        if tel is not None:
            from .observability import agreement as _ff_agreement
            from .observability import memplane as _ff_memplane

            # post-swap divergence must compare against the NEW strategy
            _ff_agreement.emit_compile_prediction(self, tel)
            # ... and so must the predicted-HBM view (the swapped plan
            # may trade step time for residency)
            _ff_memplane.emit_memory_prediction(self, tel)
            tel.flush()

    def _export_provenance(self) -> Optional[Dict[str, Any]]:
        """Provenance sidecar payload for an exported strategy: which
        search produced it (engine/budget/seed + simulated cost when
        compile ran one; "import"/"manual" otherwise) and per-op cost
        attribution.  Advisory — never lets a simulator failure break
        the export itself."""
        sp = getattr(self, "_search_provenance", None)
        try:
            from .observability.searchtrace import build_provenance

            extra = {}
            if self.config.import_strategy_file:
                extra["imported_from"] = self.config.import_strategy_file
            if sp is not None and sp.get("search_stats"):
                ss = sp["search_stats"]
                extra["population"] = {k: ss[k] for k in
                                       ("population", "ladder", "spent",
                                        "winner_chain", "exchange",
                                        "crossover") if k in ss}
                if ss.get("learned"):
                    extra["learned_tier"] = ss["learned"]
            if sp is None:
                engine = "import" if self.config.import_strategy_file \
                    else "manual"
                return build_provenance(self, self._all_strategies(),
                                        engine=engine, budget=0,
                                        seed=self.config.seed, extra=extra)
            return build_provenance(
                self, self._all_strategies(), engine=sp["engine"],
                budget=sp["budget"], seed=sp["seed"], best_s=sp["best_s"],
                dp_s=sp["dp_s"], machine_model=sp["machine_model"],
                extra=extra)
        except Exception as e:  # noqa: BLE001 — sidecar is best-effort
            warnings.warn(f"strategy provenance sidecar not written: {e}")
            return None

    def final_tensor(self) -> Tensor:
        return self.ops[-1].output

    def _loss_input_tensor(self) -> Tensor:
        """Pre-softmax activations when the loss fuses with a trailing
        Softmax (the stable log-softmax+CE path — see losses.py)."""
        last = self.ops[-1]
        if isinstance(last, Softmax) and self.loss is not None and self.loss.wants_logits:
            return last.inputs[0]
        return last.output

    def _loss_head(self):
        """What every step builder ends in, as a pair of functions:
        ``loss_of(env, labels) -> (loss, read)`` under the scope
        ``ff.loss``, and ``metrics_of(read, labels)``, the metric sums
        (the caller opens ``ff.metrics``).

        ``read`` is the tensor the metrics read.  Where the loss takes
        the logits of a trailing Softmax and every metric asked for is a
        function of the logits (``Metrics.logits_suffice``), that is the
        tensor the loss reads: a step that returns nothing else of the
        Softmax's output leaves XLA no use for its forward, so no
        probabilities are computed for a metric.  Otherwise it is the
        final tensor, as the reference's metrics read it."""
        loss_t = self._loss_input_tensor()
        probs_t = self.final_tensor()
        loss_fn, metrics = self.loss, self.metrics
        from_logits = loss_t is not probs_t and metrics.logits_suffice
        read_t = loss_t if from_logits else probs_t

        def loss_of(env, labels):
            with jax.named_scope("ff.loss"):
                return loss_fn(env[loss_t.guid], labels), env[read_t.guid]

        return loss_of, functools.partial(metrics.compute,
                                          from_logits=from_logits)

    # ------------------------------------------------------------------
    # parameter/state initialization (≈ FFModel::init_layers + initializer
    # tasks, src/runtime/initializer.cc)
    # ------------------------------------------------------------------
    def _sparse_embed_structural_ok(self, op) -> bool:
        """Structure-only part of row-sparse eligibility: an Embedding
        with its own table fed straight from a graph input.  Shared with
        the SEARCH paths (search.py / native_search.py propose host
        candidates only for ops the runtime could actually execute
        row-sparse — pricing a candidate batch-scaled and then executing
        it table-scaled would make the search recommend regressions).
        Deliberately does NOT touch ``jax.process_count()``: that
        initializes the backend, and an offline tool must not claim the
        chip for a structure question — the runtime check in
        ``_sparse_embed_ok`` covers multi-process."""
        if not (isinstance(op, Embedding) and op.share_from is None
                and any(op.inputs[0] is t for t in self.input_tensors)):
            return False
        # Swap-in remaps the index input to the compact row space, so
        # row-sparse execution additionally requires every consumer of
        # that input to be an own-table Embedding.  This half of the
        # runtime check is strategy-independent, so search candidates
        # and report rows must apply it too — otherwise they price a
        # batch-scaled host path for a plan the runtime would silently
        # execute table-scaled.
        idx_t = op.inputs[0]
        return all(isinstance(o, Embedding) and o.share_from is None
                   for o in self.ops
                   if any(t is idx_t for t in o.inputs))

    def _sparse_embed_candidate_ok(self, op) -> bool:
        """Search-time eligibility: structural checks plus the optimizer
        check when an optimizer is already known (compile-time search);
        an offline search with no optimizer assumes the built-in SGD
        default."""
        from .optimizers import AdamOptimizer, SGDOptimizer

        if not self._sparse_embed_structural_ok(op):
            return False
        if self.optimizer is None:
            return True
        if not isinstance(self.optimizer, (SGDOptimizer, AdamOptimizer)):
            return False
        flag = getattr(self.config, "sparse_host_embeddings", None)
        if flag is not None:
            return bool(flag)
        opt = self.optimizer
        return (isinstance(opt, SGDOptimizer) and opt.momentum == 0.0
                and opt.weight_decay == 0.0)

    def _sparse_embed_ok(self, op) -> bool:
        """Row-sparse host placement applies when the op is an Embedding
        with its own table fed straight from a graph input, under a
        built-in SGD/Adam optimizer.  Multi-process runs shard the table
        by row range across hosts (reference: run_summit.sh multi-node
        CPU-embedding DLRM) — see ``_host_embed_swap_in``.  Auto mode
        (``config.sparse_host_embeddings is None``) additionally requires
        the update rule to be identity on untouched rows (plain SGD) so
        sparse and dense training are bit-identical; forcing the flag
        True opts into lazy per-touched-row semantics (torch
        SparseAdam-style) for momentum/Adam."""
        from .optimizers import AdamOptimizer, SGDOptimizer

        if not (self._sparse_embed_structural_ok(op)
                and isinstance(self.optimizer, (SGDOptimizer, AdamOptimizer))):
            return False
        # Swap-in REMAPS the index input's batch values to the compact
        # row space, so every consumer of that input must be a
        # host-placed own-table Embedding seeing the same remap — a
        # mixed on-device consumer would silently look up compacted ids.
        idx_t = op.inputs[0]
        for o in self.ops:
            if any(t is idx_t for t in o.inputs):
                if not (isinstance(o, Embedding) and o.share_from is None
                        and o.pc.host_placed):
                    return False
        flag = getattr(self.config, "sparse_host_embeddings", None)
        if flag is not None:
            return bool(flag)
        opt = self.optimizer
        return (isinstance(opt, SGDOptimizer) and opt.momentum == 0.0
                and opt.weight_decay == 0.0)

    def _param_spec_tree(self) -> Dict[str, Dict[str, NamedSharding]]:
        out: Dict[str, Dict[str, NamedSharding]] = {}
        self._offload: Dict[Tuple[str, str], Tuple[NamedSharding, NamedSharding]] = {}
        self._host_embed = {}
        pack = self._pipe_pack()
        packed_keys = set(pack["entries"]) if pack else set()
        if pack:
            # Stage weights live in the pipe buffer: one row per ring
            # slot, sharded over the pipe axes (1/ring per device).
            out["_pipe"] = {"buffer": self._pipe_buffer_sharding()}
        for op in self.ops:
            if not op.weights or op.name in packed_keys:
                continue
            degrees = list(op.pc.dims)
            rank = op.output.num_dims
            degrees += [1] * (rank - len(degrees))
            groups = self.machine.axes_for_degrees(degrees[:rank])
            specs = {}
            for w in op.weights:
                entries = []
                for pd in w.partition_dims:
                    if pd is None or pd >= len(groups) or not groups[pd]:
                        entries.append(None)
                    else:
                        g = groups[pd]
                        entries.append(g if len(g) > 1 else g[0])
                while entries and entries[-1] is None:
                    entries.pop()
                sh = NamedSharding(self.machine.mesh, PartitionSpec(*entries))
                host_placed = op.pc.host_placed
                if host_placed and self._sparse_embed_ok(op):
                    # Row-sparse path (reference: embedding.cc:18-77 CPU
                    # tasks + dlrm_strategy_hetero.cc host ZC tables):
                    # the table lives host-side as numpy; each step
                    # gathers ONLY the batch's unique rows to device and
                    # scatters the updated rows back — per-step transfer
                    # scales with the batch, not the table.  The spec
                    # recorded here shards the per-step GATHERED rows
                    # (replicated: they're batch-sized).
                    idx_t = op.inputs[0]
                    n_idx = int(np.prod(idx_t.dims))
                    # multi-process: each host OWNS a contiguous row
                    # range of the table (reference: run_summit.sh
                    # places per-node CPU embedding shards)
                    P = jax.process_count()
                    N = int(op.num_entries)
                    per = -(-N // P)
                    lo = min(N, jax.process_index() * per)
                    hi = min(N, lo + per)
                    self._host_embed[op.name] = {
                        "weight": w.name,
                        "input": idx_t,
                        "input_key": f"in_{idx_t.guid}",
                        "u_max": int(min(op.num_entries,
                                         -(-n_idx // 8) * 8)),
                        "row_lo": lo, "row_hi": hi, "rows_per": per,
                        "num_entries": N,
                    }
                    specs[w.name] = NamedSharding(self.machine.mesh,
                                                  PartitionSpec())
                    continue
                if host_placed:
                    # Heterogeneous placement (reference: ParallelConfig::
                    # device_type=CPU routes ops to CPU task variants, and
                    # memory_types ZCM entries pin regions to host
                    # zero-copy memory, so DLRM keeps huge embedding
                    # tables off-accelerator — embedding.cc +
                    # dlrm_strategy_hetero.cc).  TPU equivalent: the
                    # weight (and its optimizer state) LIVES in pinned
                    # host memory; each step streams it to device,
                    # computes, and streams the update back.
                    try:
                        host_sh = sh.with_memory_kind("pinned_host")
                        self._offload[(op.name, w.name)] = (host_sh, sh)
                        sh = host_sh
                    except ValueError:
                        # backend without host memory kinds: keep HBM,
                        # but say so — silently dropping offload turns
                        # into an accelerator OOM on real workloads.
                        if not self._offload_warned:
                            self._offload_warned = True
                            print(f"flexflow_tpu: host placement requested "
                                  f"for {op.name}/{w.name} but this backend "
                                  f"has no pinned_host memory; keeping "
                                  f"weights in device memory")
                specs[w.name] = sh
            out[op.name] = specs
        return out

    def _host_embed_swap_in(self, params_in, opt_in, batch):
        """Per-step row gather for host-resident embedding tables
        (reference: embedding.cc:18-77 — CPU tasks touch only the
        batch's rows).  For each registered table: unique the batch's
        indices on host, gather those rows (padded to an ADAPTIVE
        bucket: the smallest power-of-two holding the step's unique
        count, kept as a monotone high-water mark ``u_hwm`` and capped
        at the all-unique ``u_max`` — skewed key distributions, the
        DLRM norm, never pay worst-case all-unique padding, and the
        monotone ladder bounds jit retraces to the handful of distinct
        bucket shapes), remap the index batch to the compact row space,
        and gather the same rows of any table-shaped optimizer slot.
        The dense in-jit optimizer update then IS the lazy
        per-touched-row update, and ``_host_embed_scatter_back`` writes
        the rows home in place."""
        rep = self.machine.replicated()
        params_in = _copy_params_tree(params_in)
        batch_in = dict(batch)
        if opt_in is not None:
            opt_in = _copy_state_tree(opt_in)
        # pass 1 — table-INDEPENDENT host work (unique, remap, bucket):
        # runs while the previous step's async scatter-back is still in
        # flight, hiding this host cost behind the device step
        nproc = jax.process_count()
        preps = []
        for opn, info in self._host_embed.items():
            key = info["input_key"]
            idx = self._host_idx.get(key)
            if idx is None:
                idx = np.asarray(jax.device_get(batch[key]))
            if nproc > 1:
                # the compact row space must be GLOBAL (grads for the
                # gathered buffer psum across processes): union every
                # host's local uniques via a fixed-size id allgather
                from jax.experimental import multihost_utils
                local = np.unique(idx)
                pad_ids = np.full((info["u_max"],), -1, np.int64)
                pad_ids[:local.size] = local
                all_ids = np.asarray(
                    multihost_utils.process_allgather(pad_ids))
                uniq = np.unique(all_ids[all_ids >= 0])
                inv = np.searchsorted(uniq, idx)
            else:
                uniq, inv = np.unique(idx, return_inverse=True)
            n = int(uniq.size)
            b = 8
            while b < n:
                b <<= 1
            u = min(info["u_max"], max(b, info.get("u_hwm", 0)))
            if opt_in is not None:
                # training step: grow the monotone bucket and account
                # wire traffic.  Eval/predict (opt_in None) still sizes
                # THIS call's pad correctly but must not inflate the
                # train bucket (extra retrace) or the per-train-step
                # telemetry.
                info["u_hwm"] = u
                info["uniq_rows_total"] = info.get("uniq_rows_total", 0) + n
                info["uniq_rows_steps"] = info.get("uniq_rows_steps", 0) + 1
            deg = info.get("batch_degree")
            if deg is None:
                # fixed after compile; the consumer scan inside
                # _input_batch_degree is O(ops) and this runs per table
                # per step on the Python hot path
                deg = info["batch_degree"] = \
                    self._input_batch_degree(info["input"])
            batch_in[key] = self._place_batch(
                inv.reshape(idx.shape).astype(np.int32), deg)
            preps.append((opn, info, uniq, n, u))
        # read barrier: the previous step's rows must be home before the
        # tables are gathered
        self._he_join()
        ctxs = []
        for opn, info, uniq, n, u in preps:
            wn = info["weight"]
            table = params_in[opn][wn]
            uniq_p = np.zeros((u,), np.int64)
            uniq_p[:n] = uniq

            def gather(shard):
                """(u, D) buffer of the compact rows.  Multi-process:
                each host fills the rows IT owns and an allgather-sum
                assembles the full buffer (every compact id has exactly
                one owner, so the sum is exact) — the per-host gather +
                DCN exchange of the reference's multi-node CPU
                embeddings (run_summit.sh)."""
                if nproc == 1:
                    return np.ascontiguousarray(shard[uniq_p])
                from jax.experimental import multihost_utils
                lo, hi = info["row_lo"], info["row_hi"]
                part = np.zeros((u,) + shard.shape[1:], shard.dtype)
                own = (uniq_p >= lo) & (uniq_p < hi)
                part[own] = shard[uniq_p[own] - lo]
                return np.ascontiguousarray(np.asarray(
                    multihost_utils.process_allgather(part))
                    .sum(0, dtype=shard.dtype))

            params_in[opn][wn] = jax.device_put(gather(table), rep)
            slots = []
            if opt_in is not None:
                for k, v in opt_in.items():
                    full = (v.get(opn, {}).get(wn)
                            if isinstance(v, dict) else None)
                    if full is not None and \
                            getattr(full, "shape", None) == table.shape:
                        v[opn][wn] = jax.device_put(
                            gather(np.asarray(full)), rep)
                        slots.append((k, full))
            ctxs.append({"op": opn, "weight": wn, "table": table,
                         "uniq": uniq, "n": n, "slots": slots,
                         "row_lo": info["row_lo"],
                         "row_hi": info["row_hi"],
                         "multi": nproc > 1})
        return params_in, opt_in, batch_in, ctxs

    def _host_embed_scatter_back(self, new_params, new_opt, ctxs):
        """Swap the host tables back into the returned trees and write
        the step's updated rows home ASYNCHRONOUSLY.  The step's row
        arrays are device futures, so forcing them (np.asarray) blocks
        until the step completes; doing that on a worker thread lets
        ``update()`` return at dispatch time, so the training loop's
        host-side work for the next batch (data prep, set_batch, and
        swap-in pass 1: unique/remap/bucket) overlaps the device step —
        the overlap Legion's dataflow gives the reference's CPU
        embedding tasks for free (embedding.cc:18-77).  ``_he_join()``
        is the read barrier (swap-in pass 2, sync, weight accessors,
        checkpoint)."""
        step_params, step_opt = new_params, new_opt
        new_params = _copy_params_tree(new_params)
        if new_opt is not None:
            new_opt = _copy_state_tree(new_opt)
        for ctx in ctxs:
            opn, wn = ctx["op"], ctx["weight"]
            new_params[opn][wn] = ctx["table"]
            for k, full in ctx["slots"]:
                new_opt[k][opn][wn] = full
        if self._he_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._he_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ff-host-embed")
        self._he_join()  # at most one step in flight
        self._he_pending = self._he_pool.submit(
            self._he_write_rows, step_params, step_opt, ctxs)
        # decode's device-table cache invalidates; drop it NOW so the
        # full replicated device tables don't sit in HBM through a
        # training run between generate calls
        self._he_version += 1
        self._he_dev_cache = None
        if os.environ.get("FF_HE_SYNC_SCATTER"):
            # measurement knob: serialize the scatter-back with the step
            # (bench A/Bs this to report the async overlap's actual win)
            self._he_join()
        return new_params, new_opt

    @staticmethod
    def _he_write_rows(step_params, step_opt, ctxs):
        """Worker: force the updated row arrays and scatter them into
        the host tables (and optimizer-state arrays) in place.  In a
        multi-process run each host writes ONLY the rows it owns — the
        updated buffer is replicated, so no communication is needed and
        the lazy-row update stays local."""
        for ctx in ctxs:
            opn, wn, n = ctx["op"], ctx["weight"], ctx["n"]
            uniq, table = ctx["uniq"], ctx["table"]
            if ctx.get("multi"):
                sel = (uniq >= ctx["row_lo"]) & (uniq < ctx["row_hi"])
                dst = uniq[sel] - ctx["row_lo"]
            else:
                sel, dst = slice(None), uniq
            rows = np.asarray(step_params[opn][wn])
            table[dst] = rows[:n][sel].astype(table.dtype)
            for k, full in ctx["slots"]:
                srows = np.asarray(step_opt[k][opn][wn])
                full[dst] = srows[:n][sel].astype(full.dtype)

    def _he_join(self):
        """Read barrier for the async scatter-back: wait for the
        in-flight row write (if any) and re-raise worker exceptions.
        Must run before any host-table read or write."""
        f = self._he_pending
        if f is not None:
            self._he_pending = None
            f.result()

    def _he_info(self, op_name: str, weight_name: str):
        """Row-range sharding info when ``(op, weight)`` is a host table
        sharded across processes, else None."""
        info = self._host_embed.get(op_name)
        if (info and info["weight"] == weight_name
                and jax.process_count() > 1):
            return info
        return None

    @staticmethod
    def _he_assemble_full(info, shard: np.ndarray) -> np.ndarray:
        """Assemble the FULL table from this host's row-range shard via
        a process allgather (shards pad to the common per-host size)."""
        from jax.experimental import multihost_utils
        per = info["rows_per"]
        pad = np.zeros((per,) + shard.shape[1:], shard.dtype)
        pad[:shard.shape[0]] = shard
        allp = np.asarray(multihost_utils.process_allgather(pad))
        return np.ascontiguousarray(
            allp.reshape((-1,) + shard.shape[1:])[:info["num_entries"]])

    def _offload_put(self, tree, to_host: bool):
        """Move host-offloaded weights between pinned-host and device
        memory (params-shaped tree; missing entries are left alone)."""
        if not self._offload:
            return tree
        tree = {k: (dict(v) if isinstance(v, dict) else v)
                for k, v in tree.items()}
        for (opn, wn), (host_sh, dev_sh) in self._offload.items():
            if opn in tree and isinstance(tree[opn], dict) and wn in tree[opn]:
                tree[opn][wn] = jax.device_put(
                    tree[opn][wn], host_sh if to_host else dev_sh)
        return tree

    def _offload_put_state(self, state, to_host: bool):
        """Same as ``_offload_put`` for optimizer state: each value is a
        params-shaped subtree ("v"/"m"), scalars pass through."""
        if not self._offload or state is None:
            return state
        return {k: self._offload_put(v, to_host) if isinstance(v, dict) else v
                for k, v in state.items()}

    def init_layers(self, seed: Optional[int] = None) -> None:
        """Make the parameters (one jitted init, compiled or fetched
        here), the ops' statistics and the optimizer's state."""
        assert self._compiled, "call compile() first"
        with _ff_phase(self._telemetry, "init_layers"):
            self._init_layers_impl(seed)

    def _init_layers_impl(self, seed: Optional[int]) -> None:
        seed = self.config.seed if seed is None else seed
        key = jax.random.key(seed)
        shardings = self._param_spec_tree()

        ops_with_weights = [op for op in self.ops if op.weights
                            and op.name not in self._host_embed]
        pack = self._pipe_pack()

        import zlib

        def init_fn(key):
            params = {}
            buf = (jnp.zeros((pack["ring"], pack["width"]), jnp.float32)
                   if pack else None)
            for op in ops_with_weights:
                p = {}
                for w in op.weights:
                    # Deterministic per-(op, weight) stream: same graph →
                    # same init regardless of strategy or process history.
                    salt = zlib.crc32(f"{op.name}/{w.name}".encode())
                    v = w.initializer(jax.random.fold_in(key, salt),
                                      w.dims, jnp.float32)
                    if pack and op.name in pack["entries"]:
                        buf = self._pack_write(
                            buf, pack["entries"][op.name][w.name], v)
                    else:
                        p[w.name] = v
                if p:
                    params[op.name] = p
            if pack:
                params["_pipe"] = {"buffer": buf}
            return params

        # Offloaded weights are initialized on device (the SPMD partitioner
        # rejects host-placement annotations inside this jit) and streamed
        # to pinned-host memory right after.
        init_shardings = {opn: {wn: (self._offload[(opn, wn)][1]
                                     if (opn, wn) in self._offload else sh)
                                for wn, sh in ws.items()}
                          for opn, ws in shardings.items()
                          if opn not in self._host_embed}
        self._params = jax.jit(init_fn, out_shardings=init_shardings)(key)
        self._params = self._offload_put(self._params, True)
        # Row-sparse host tables: initialized on the host CPU backend
        # (same threefry streams → bit-identical to a device init) and
        # kept as numpy so per-step row scatter-updates are in-place.
        for opn, info in self._host_embed.items():
            op = next(o for o in self.ops if o.name == opn)
            w = op.weights[0]
            salt = zlib.crc32(f"{op.name}/{w.name}".encode())
            cpu0 = jax.local_devices(backend="cpu")[0]
            with jax.default_device(cpu0):
                hkey = jax.device_put(key, cpu0)
                v = np.array(w.initializer(jax.random.fold_in(hkey, salt),
                                           w.dims, jnp.float32))
            if jax.process_count() > 1:
                # every host computes the same full init (one threefry
                # stream) and keeps only its OWNED row range
                v = v[info["row_lo"]:info["row_hi"]].copy()
            self._params.setdefault(opn, {})[w.name] = v
        self._stats = {}
        for op in self.ops:
            st = op.init_stats()
            if st:
                self._stats[op.name] = jax.device_put(
                    st, self.machine.replicated())
        # Optimizer state mirrors the params pytree and inherits each
        # param's sharding (momentum/moment buffers live with their shard).
        if self.optimizer is not None:
            specs = {opn: {wn: sh.spec for wn, sh in ws.items()}
                     for opn, ws in shardings.items()}
            multi = self.machine.num_devices > 1
            # Host-offloaded leaves take the plain update (their streaming
            # device_put pairs don't model Pallas aliasing); every other
            # leaf keeps the fused path.
            nonfused = set(self._offload)
            nonfused |= {(opn, info["weight"])
                         for opn, info in self._host_embed.items()}
            zero_specs = (self._zero_state_specs()
                          if self.config.zero_optimizer and multi else None)
            if self.config.zero_optimizer and multi:
                # ZeRO-1 eligibility is structural (leading dim unsharded
                # and divisible over the free mesh axes) — report which
                # state actually sharded so a silently-replicated slot is
                # never mistaken for a sharded one.  Pipeline-packed,
                # host-offloaded, and host-sparse weights are accounted
                # as their own categories: packed stage state is sharded
                # ~1/ring by the pipe buffer itself, and host-resident
                # state never occupies device HBM at all.
                eligible = zero_specs or {}
                packed = set(pack["entries"]) if pack else set()
                cats = {"packed(1/ring)": 0, "host": 0}
                skipped = []
                n_total = 0
                for op in self.ops:
                    for w in op.weights:
                        k = (op.name, w.name)
                        n_total += 1
                        if op.param_key in packed:
                            cats["packed(1/ring)"] += 1
                        elif k in self._offload or op.name in self._host_embed:
                            cats["host"] += 1
                        elif k not in eligible:
                            skipped.append(k)
                extras = ", ".join(f"{n} {c}" for c, n in cats.items() if n)
                print(f"flexflow_tpu: ZeRO-1 optimizer-state sharding: "
                      f"{len(eligible)}/{n_total} weights sharded"
                      + (f" (+{extras})" if extras else "")
                      + (f"; replicated (ineligible): "
                         f"{', '.join('/'.join(k) for k in skipped[:8])}"
                         + ("..." if len(skipped) > 8 else "")
                         if skipped else ""))
            if zero_specs:
                # state spec != param spec breaks the fused kernels'
                # same-spec shard_map; those leaves take the plain update
                nonfused |= set(zero_specs)
            self.optimizer.set_mesh(self.machine.mesh if multi else None,
                                    specs, nonfused_paths=nonfused)
            self.optimizer.zero_specs = zero_specs
        self._opt_state = (self._init_opt_state()
                           if self.optimizer is not None else None)
        self._step_count = 0

    def _zero_state_specs(self):
        """ZeRO-1 layout: shard each parameter's OPTIMIZER STATE over the
        mesh axes the parameter itself does not occupy (momentum/moments
        of replicated weights drop to ~1/N per device; the update's
        gather/scatter comes out of GSPMD).  Only leaves whose leading
        dim is unsharded and divisible participate; offloaded leaves are
        host-resident already.  Returns {(op, weight): PartitionSpec}."""
        out = {}
        mesh = self.machine.mesh
        for op in self.ops:
            if not op.weights or op.name not in self._params:
                continue
            for w in op.weights:
                if (op.name, w.name) in self._offload \
                        or op.name in self._host_embed:
                    continue
                arr = self._params[op.name].get(w.name)
                if arr is None:
                    continue
                spec = arr.sharding.spec
                used = set()
                for e in spec:
                    if e is None:
                        continue
                    used.update(e if isinstance(e, tuple) else (e,))
                free = [a for a in mesh.axis_names if a not in used]
                if not free:
                    continue
                n_free = 1
                for a in free:
                    n_free *= mesh.shape[a]
                dim0 = (spec[0] if len(spec) > 0 else None)
                if dim0 is not None or arr.shape[0] % n_free != 0:
                    continue
                entries = list(spec) + [None] * (arr.ndim - len(spec))
                entries[0] = tuple(free) if len(free) > 1 else free[0]
                while entries and entries[-1] is None:
                    entries.pop()
                out[(op.name, w.name)] = PartitionSpec(*entries)
        return out

    def _init_opt_state(self):
        params = self._params
        if self._offload or self._host_embed:
            params = {opn: (dict(ws) if isinstance(ws, dict) else ws)
                      for opn, ws in params.items()}
        if self._offload:
            # zeros_like cannot materialize a pinned-host buffer (jax
            # builds arrays from callbacks in default device memory
            # only), so every stateful optimizer would crash at init on
            # an offloaded weight.  Hand init_state a device-memory
            # stand-in of the same shape/dtype/layout; the created
            # state streams to pinned host right below, exactly like
            # the weights do.
            for (opn, wn), (host_sh, dev_sh) in self._offload.items():
                ws = params.get(opn)
                if isinstance(ws, dict) and wn in ws:
                    leaf = ws[wn]
                    # allocate shard-wise directly — a device_put of a
                    # full single-device zeros buffer could OOM device 0
                    # for exactly the weights offload exists to hold
                    ws[wn] = jnp.zeros(leaf.shape, leaf.dtype,
                                       device=dev_sh)
        if self._host_embed:
            # Host-resident tables stay OUT of init_state (zeros_like
            # would allocate a table-sized device buffer); their state
            # lives host-side as numpy, scatter-updated per step.
            tables = {}
            for opn, info in self._host_embed.items():
                wn = info["weight"]
                d = params[opn]
                tables[(opn, wn)] = d.pop(wn)
                if not d:
                    params.pop(opn)
            state = self.optimizer.init_state(params)
            for v in state.values():
                if isinstance(v, dict):
                    for (opn, wn), tbl in tables.items():
                        v.setdefault(opn, {})[wn] = np.zeros(tbl.shape,
                                                             np.float32)
        else:
            state = self.optimizer.init_state(params)
        # pin offloaded entries' state to host so every step sees
        # consistent memory kinds
        state = self._offload_put_state(state, True)
        zero_specs = getattr(self.optimizer, "zero_specs", None)
        if zero_specs:
            mesh = self.machine.mesh
            state = {
                k: ({opn: {wn: (jax.device_put(
                        a, NamedSharding(mesh, zero_specs[(opn, wn)]))
                        if (opn, wn) in zero_specs else a)
                     for wn, a in ws.items()}
                     for opn, ws in v.items()}
                    if isinstance(v, dict) else v)
                for k, v in state.items()}
        # A leaf the optimizer made from scratch (optax's step count) is
        # uncommitted, on one device; the step hands it back committed
        # to the mesh.  Place it now as the step will.
        rep = self.machine.replicated()
        return jax.tree.map(
            lambda a: jax.device_put(a, rep)
            if isinstance(a, jax.Array) and not a.committed else a, state)

    # ------------------------------------------------------------------
    # forward-graph evaluation (inside jit)
    # ------------------------------------------------------------------
    def _run_graph(self, params, stats, batch, training: bool, rng,
                   counters: Optional[Dict[str, jax.Array]] = None,
                   losses: Optional[Dict[str, jax.Array]] = None):
        """``counters``: a dict the ops' per-step scalars are summed into
        (``Op.COUNTERS``); ``losses``: a dict the ops' terms of the
        objective are summed into (``FwdCtx.add_loss``).  The train step
        passes both."""
        env: Dict[int, jax.Array] = {}
        multi = self.machine.num_devices > 1
        cdtype = self.compute_dtype
        for t in self.input_tensors:
            x = batch[f"in_{t.guid}"]
            if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != cdtype:
                # Activations run in compute_dtype (bfloat16 on the MXU for
                # benchmarks); params stay float32 and ops cast per-use.
                with jax.named_scope("ff.input_cast"):
                    x = x.astype(cdtype)
            if multi:
                deg = self._input_batch_degree(t)
                if deg > 1:
                    x = jax.lax.with_sharding_constraint(
                        x, self.machine.batch_sharding(deg))
            env[t.guid] = x
        for t, val in self._constants.values():
            fill_dtype = jnp.int32 if "int" in t.dtype else cdtype
            env[t.guid] = jnp.full(t.dims, val, fill_dtype)
        ctx = FwdCtx(training=training, rng=rng, stats_in=stats,
                     stats_out={} if training else None, counters=counters,
                     losses=losses)
        plan = getattr(self, "_pipeline_plan", None)
        use_pipe = (plan is not None and multi and plan["degree"] > 1)
        head_ids = ({id(op) for op in plan["head"]}
                    if use_pipe and plan.get("head") else set())

        def constrain(op, ys):
            # the op's output partition; its dims' roles pick the class of
            # mesh axis each may take (parallel/mesh.py)
            if not multi:
                return ys
            cpc = op.constraint_pc()
            return [self.machine.constraint(y, cpc, dim_roles(op, y.ndim))
                    for y in ys]

        i = 0
        while i < len(self.ops):
            if use_pipe and i == plan["i0"]:
                # Heterogeneous head first: host-placed row-sparse
                # embeddings may sit anywhere in op order (DLRM builds
                # its bottom MLP before the tables) but their gathered
                # rows must be in env before the ring packs stage 0's
                # input bundle.
                for hop in plan["head"]:
                    if hop.output.guid not in env:
                        hxs = [env[t.guid] for t in hop.inputs]
                        with jax.named_scope(_op_scope(hop)):
                            hys = hop.forward(
                                params.get(hop.param_key, {}), hxs, ctx)
                        for t, y in zip(hop.outputs, constrain(hop, hys)):
                            env[t.guid] = y
                # Pipelined segment: GPipe microbatch schedule over the
                # pipe mesh axes (parallel/pipeline.py), replacing the
                # sequential op walk for ops[i0:i1].
                y = self._run_pipeline_segment(params, env, ctx)
                env[plan["seg_out"].guid] = y
                i = plan["i1"]
                continue
            op = self.ops[i]
            if id(op) in head_ids and op.output.guid in env:
                i += 1  # head op already ran at segment entry
                continue
            xs = [env[t.guid] for t in op.inputs]
            pvals = params.get(op.param_key, {})
            # One scope an op names both phases: under value_and_grad its
            # forward instructions carry jvp(ff.op...) and its backward
            # ones transpose(jvp(ff.op...)) (runtime/profiling.step_scopes).
            with jax.named_scope(_op_scope(op)):
                if training and self.config.remat and op.weights \
                        and not op.init_stats():
                    # Rematerialization: drop this op's internal
                    # activations from the residual set and recompute them
                    # in backward — FLOPs for HBM, the standard TPU memory
                    # lever.  Stateful ops (running stats) stay un-remat'ed.
                    ys = jax.checkpoint(
                        lambda p_, xs_, op_=op: op_.forward(p_, list(xs_),
                                                            ctx)
                    )(pvals, tuple(xs))
                else:
                    ys = op.forward(pvals, xs, ctx)
            for t, y in zip(op.outputs, constrain(op, ys)):
                env[t.guid] = y
            i += 1
        new_stats = dict(stats)
        if training and ctx.stats_out:
            new_stats.update(ctx.stats_out)
        return env, new_stats

    def _input_batch_degree(self, t: Tensor) -> int:
        plan = getattr(self, "_pipeline_plan", None)
        if plan is not None and t.guid in plan["seg_in_guids"]:
            return plan["dp_degree"]
        for op in self.ops:
            if t in op.inputs:
                if op.name in self._host_embed:
                    # host-placed row-sparse embedding: its pc is the
                    # host sentinel (degree 1 = replicated), but a
                    # replicated batch leaf cannot be assembled from
                    # per-host local shards in a multi-process run —
                    # shard the indices with the table OUTPUT's consumer
                    # dp degree instead (the lookup into the replicated
                    # gathered-row buffer distributes over batch)
                    out = op.output
                    if plan is not None and out.guid in plan["seg_in_guids"]:
                        # hetero head feeding the pipeline ring: segment
                        # ops carry no-split placeholder pcs, so the
                        # plan's dp degree is the batch sharding
                        return plan["dp_degree"]
                    for o2 in self.ops:
                        if out in o2.inputs \
                                and o2.name not in self._host_embed:
                            return o2.pc.dims[0]
                    return max(1, jax.process_count())
                return op.pc.dims[0]
        return 1

    # ------------------------------------------------------------------
    # the fused SPMD train step
    # ------------------------------------------------------------------
    def _build_train_step(self):
        loss_of, metrics_of = self._loss_head()
        base_key = jax.random.key(self.config.seed + 7919)
        opt = self.optimizer

        mkeys = self._metric_keys()

        accum = max(1, int(self.config.grad_accum_steps))

        # The guard needs the isfinite entries even without FF_HEALTH.
        guard_on = self._nonfinite_guard is not None
        track_health = self._health is not None or guard_on

        def health_metrics(loss, grads):
            # Device-side isfinite reduction over the loss and the
            # global grad-norm, folded into the metric vector — fetched
            # by the existing drain, no extra dispatches.
            gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads))
            gnorm = jnp.sqrt(gsq)
            vec = jnp.zeros((len(mkeys),), jnp.float32)
            vec = vec.at[mkeys.index("nonfinite_loss")].set(
                1.0 - jnp.isfinite(loss).astype(jnp.float32))
            vec = vec.at[mkeys.index("nonfinite_grad")].set(
                1.0 - jnp.isfinite(gnorm).astype(jnp.float32))
            vec = vec.at[mkeys.index("grad_norm")].set(
                jnp.where(jnp.isfinite(gnorm), gnorm, 0.0))
            return vec

        def objective(loss, terms):
            # the final tensor's loss plus the terms the ops added from
            # inside the graph (FwdCtx.add_loss); with none, the loss
            for name in sorted(terms):
                loss = loss + terms[name]
            return loss

        def micro_metrics(loss, read, labels, counts):
            msum = metrics_of(read, labels)
            msum.update(counts)
            msum["loss"] = loss
            msum["steps"] = 1.0
            # On-device metric accumulation: one small vector rides along
            # and is fetched once per drain — the analogue of the
            # reference's future-chain metric fold (model.cc:1145-1167)
            # without a host round-trip per step.
            return jnp.stack([jnp.float32(msum.get(k, 0.0)) for k in mkeys])

        def guard_finalize(params, stats, opt_state, new_params, new_stats,
                           new_opt, mvec, macc):
            # Non-finite step guard (runtime/resilience.py): when this
            # step's loss or grad-norm was non-finite, select the
            # PRE-step params/stats/opt-state back — a functional
            # in-jit select, so it is donation-safe (no host reference
            # to the donated input buffers) and the restore is bitwise.
            # The skipped step contributes only its health entries plus
            # skipped_steps=1 to the metric vector (steps stays 0), so
            # window means cover applied steps only; consec_skipped is
            # a run length, reset by any good step.
            from .observability.health import HEALTH_METRIC_KEYS
            bad = (mvec[mkeys.index("nonfinite_loss")]
                   + mvec[mkeys.index("nonfinite_grad")]) > 0

            def sel(old, new):
                return jax.tree.map(lambda o, n: jnp.where(bad, o, n),
                                    old, new)

            hmask = jnp.zeros((len(mkeys),), jnp.float32)
            for k in HEALTH_METRIC_KEYS:
                hmask = hmask.at[mkeys.index(k)].set(1.0)
            skip_vec = (mvec * hmask).at[
                mkeys.index("skipped_steps")].set(1.0)
            out = macc + jnp.where(bad, skip_vec, mvec)
            ci = mkeys.index("consec_skipped")
            out = out.at[ci].set(jnp.where(bad, macc[ci] + 1.0, 0.0))
            return (sel(params, new_params), sel(stats, new_stats),
                    sel(opt_state, new_opt), out)

        def finish(params, stats, opt_state, hparams, grads, new_stats, mvec,
                   macc):
            with jax.named_scope("ff.optimizer"):
                new_params, new_opt = opt.apply(params, grads, opt_state,
                                                hparams)
            if guard_on:
                with jax.named_scope("ff.guard"):
                    return guard_finalize(params, stats, opt_state,
                                          new_params, new_stats, new_opt,
                                          mvec, macc)
            with jax.named_scope("ff.metrics"):
                return new_params, new_stats, new_opt, macc + mvec

        def step(params, stats, opt_state, hparams, batch, step_idx, macc):
            rng = jax.random.fold_in(base_key, step_idx)
            labels = batch["label"]

            def loss_fn(p):
                counts, terms = {}, {}
                env, new_stats = self._run_graph(p, stats, batch, True, rng,
                                                 counts, terms)
                loss, read = loss_of(env, labels)
                return objective(loss, terms), (read, new_stats, counts)

            (loss, (read, new_stats, counts)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            with jax.named_scope("ff.metrics"):
                mvec = micro_metrics(loss, read, labels, counts)
                if track_health:
                    mvec = mvec + health_metrics(loss, grads)
            return finish(params, stats, opt_state, hparams, grads,
                          new_stats, mvec, macc)

        def step_accum(params, stats, opt_state, hparams, batch, step_idx,
                       macc):
            # Gradient accumulation: K micro-batches through a lax.scan
            # (one micro's activations live at a time), grads averaged,
            # ONE optimizer apply — numerically the full-batch step for
            # linear-in-loss grads (BatchNorm normalizes per micro, and
            # dropout draws per-micro masks, as everywhere else).
            rng = jax.random.fold_in(base_key, step_idx)
            br = {k: v.reshape((accum, v.shape[0] // accum) + v.shape[1:])
                  for k, v in batch.items()}
            g0 = jax.tree.map(jnp.zeros_like, params)
            m0 = jnp.zeros((len(mkeys),), jnp.float32)

            def body(carry, idx):
                g_acc, mv_acc, stats_c = carry
                mb = {k: v[idx] for k, v in br.items()}
                mlabels = mb["label"]

                def loss_fn(p):
                    counts, terms = {}, {}
                    env, new_stats = self._run_graph(
                        p, stats_c, mb, True, jax.random.fold_in(rng, idx),
                        counts, terms)
                    loss, read = loss_of(env, mlabels)
                    return objective(loss, terms), (read, new_stats, counts)

                (loss, (read, new_stats, counts)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                g_acc = jax.tree.map(lambda a, b: a + b / accum, g_acc, g)
                with jax.named_scope("ff.metrics"):
                    mv_acc = mv_acc + micro_metrics(loss, read, mlabels,
                                                    counts)
                return (g_acc, mv_acc, new_stats), None

            (grads, mvec, new_stats), _ = jax.lax.scan(
                body, (g0, m0, stats), jnp.arange(accum))
            # per-STEP metric semantics: counts sum across micros; the
            # loss entry is the mean micro loss; "steps" is one step
            fix = jnp.ones((len(mkeys),), jnp.float32)
            for name in ("loss", "steps"):
                if name in mkeys:
                    fix = fix.at[mkeys.index(name)].set(1.0 / accum)
            with jax.named_scope("ff.metrics"):
                mvec = mvec * fix
                if track_health:
                    # accumulated grads; the mean micro loss rides mvec and
                    # is NaN iff any micro's loss was
                    mvec = mvec + health_metrics(
                        mvec[mkeys.index("loss")], grads)
            return finish(params, stats, opt_state, hparams, grads,
                          new_stats, mvec, macc)

        step_fn = step if accum == 1 else step_accum
        fn = jax.jit(step_fn, donate_argnums=(0, 1, 2, 6))
        if self._memplane is not None:
            fn = self._memplane.wrap("train_step", fn)
        return fn

    def _build_eval_step(self):
        loss_of, metrics_of = self._loss_head()
        probs_t = self.final_tensor()

        def estep(params, stats, batch):
            env, _ = self._run_graph(params, stats, batch, False, None)
            labels = batch["label"]
            loss, read = loss_of(env, labels)
            with jax.named_scope("ff.metrics"):
                msum = metrics_of(read, labels)
            msum["loss"] = loss
            # the caller gets the probabilities, so here the trailing
            # Softmax runs whatever the metrics read
            return msum, env[probs_t.guid]

        fn = jax.jit(estep)
        if self._memplane is not None:
            fn = self._memplane.wrap("eval_step", fn)
        return fn

    # ------------------------------------------------------------------
    # driver API (reference: forward/zero_gradients/backward/update —
    # staged here, fused execution at update())
    # ------------------------------------------------------------------
    def set_batch(self, inputs: Dict[Tensor, Any], labels: Any) -> None:
        batch: Dict[str, Any] = {}
        he_keys = {info["input_key"] for info in self._host_embed.values()}
        for t, arr in inputs.items():
            key = f"in_{t.guid}"
            if key in he_keys:
                if not isinstance(arr, jax.Array):
                    # keep a host copy: the sparse gather uniques these
                    # indices on host per step without a device round-trip
                    self._host_idx[key] = np.asarray(arr)
                else:
                    # device-array batch: drop any stale host copy so
                    # swap-in falls back to device_get of THIS batch
                    self._host_idx.pop(key, None)
            batch[f"in_{t.guid}"] = self._place_batch(arr, self._input_batch_degree(t))
        deg = getattr(self.ops[-1], "pc", ParallelConfig(dims=(1,))).dims[0] \
            if self.ops else 1
        batch["label"] = self._place_batch(labels, deg)
        self._batch = batch

    def _place_batch(self, arr, degree: int):
        if isinstance(arr, jax.Array) and arr.committed:
            return arr
        arr = np.asarray(arr)
        if jax.process_count() > 1:
            # Multi-host: ``arr`` is this host's local shard of the global
            # batch (parallel/distributed.py, host_local_batch).
            from .parallel.distributed import host_local_batch
            return host_local_batch(self.machine, arr, degree)
        return jax.device_put(arr, self.machine.batch_sharding(degree))

    def forward(self) -> None:
        self._staged = True

    def zero_gradients(self) -> None:
        """No-op: gradients are functional values, freshly computed per
        step (the reference zeroes its accumulation regions,
        model.cc:1109-1132)."""

    def backward(self) -> None:
        self._staged = True

    def _metric_keys(self) -> List[str]:
        keys = ["train_all", "train_correct", "cce_loss", "sparse_cce_loss",
                "mse_loss", "rmse_loss", "mae_loss", "loss", "steps"]
        if self._health is not None or self._nonfinite_guard is not None:
            # Health entries ride the same on-device vector (non-finite
            # loss/grad counts + summed grad norm) so detection costs
            # zero extra dispatches; the drain pops them before
            # PerfMetrics sees the dict.  The guard needs them even
            # when FF_HEALTH is off — its skip decision keys off them.
            from .observability.health import HEALTH_METRIC_KEYS
            keys += list(HEALTH_METRIC_KEYS)
        if self._nonfinite_guard is not None:
            keys += list(self._nonfinite_guard.METRIC_KEYS)
        return keys + self._op_counter_keys()

    def _fresh_metric_acc(self, consec_skipped: float = 0.0) -> jax.Array:
        """An empty metric accumulator, committed and replicated over the
        model's mesh, which is how the train step hands it back: the
        first call of a step function so has the signature of every later
        one, and the step is traced, lowered and compiled once.  The
        guard's run length survives a reset."""
        keys = self._metric_keys()
        acc = jnp.zeros((len(keys),), jnp.float32,
                        device=self.machine.replicated())
        if consec_skipped:
            acc = acc.at[keys.index("consec_skipped")].set(
                float(consec_skipped))
        return acc

    def _op_counter_keys(self) -> List[str]:
        """The ops' own per-step scalars (``Op.COUNTERS``), which ride the
        metric vector and leave it at the drain for
        ``runtime.profiling.counters()``."""
        return sorted({k for op in self.ops for k in op.COUNTERS})

    def update(self) -> None:
        # The step choke point fires on the GLOBAL step index, so an
        # exact trigger is resume-aware: after a restore past it, the
        # fault never re-fires.
        if self._chaos is not None:
            self._chaos.fire("step", index=self._step_count, model=self)
        # The log's form of this span is stepstats' post-hoc "step"
        # record; _stepstats is non-None only under telemetry.
        with _ff_span(None, "update"):
            if self._stepstats is not None:
                return self._stepstats.timed_update(self._update_impl)
            self._update_impl()

    @staticmethod
    @contextlib.contextmanager
    def _bypass_compile_cache():
        """The persistent compilation cache and a mid-training re-compile
        don't mix: an executable deserialized from the on-disk cache can
        mis-alias donated buffers when it replaces a live step function
        (intermittent NaN params / heap corruption on the CPU backend),
        and a crash mid-write leaves a truncated entry that kills every
        later swap.  Hot-swap rebuilds compile fresh instead — the cache
        stays on for cold-start compiles, where it is safe and earns its
        keep."""
        old = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", old)

    def _update_impl(self) -> None:
        assert self._batch is not None, "no batch loaded: call a DataLoader first"
        if self._train_step_fn is not None:
            return self._run_step(contextlib.nullcontext())
        # first update() after a (re)build: build, trace and compile
        with _ff_phase(self._telemetry, "step_build"):
            compile_ctx = contextlib.nullcontext()
            self._train_step_fn = self._build_train_step()
            if self._fresh_jit:
                compile_ctx = self._bypass_compile_cache()
                self._fresh_jit = False
            self._run_step(compile_ctx)

    def _run_step(self, compile_ctx) -> None:
        tel = self._telemetry
        with _ff_span(tel, "update.prepare"):
            args, he_ctxs = self._step_args()
        # the first call traces and compiles; later calls enqueue
        with compile_ctx, _ff_step_enqueue(tel):
            new_params, self._stats, new_opt, self._metric_acc = \
                self._train_step_fn(*args)
        with _ff_span(tel, "update.finish"):
            if he_ctxs:
                new_params, new_opt = self._host_embed_scatter_back(
                    new_params, new_opt, he_ctxs)
            self._params = self._offload_put(new_params, True)
            self._opt_state = self._offload_put_state(new_opt, True)
        self._step_count += 1
        self._staged = False

    def _step_args(self):
        """The arguments of this step's call of the jitted step, and the
        host-embedding contexts to scatter back after it."""
        if self._opt_state is None:
            self._opt_state = self._init_opt_state()
        if self._metric_acc is None:
            guard = self._nonfinite_guard
            # re-seed the run length a reset_metrics discarded
            self._metric_acc = self._fresh_metric_acc(
                guard.consec if guard is not None else 0)
        hp = self.optimizer.hparams()
        # Host-offloaded weights stream on-chip for the step and back
        # after (eager device_put at the jit boundary: the reference's
        # CPU-resident tables likewise live in host memory between
        # iterations; the step itself computes on the accelerator).
        params_in = self._offload_put(self._params, False)
        opt_in = self._offload_put_state(self._opt_state, False)
        batch_in, he_ctxs = self._batch, None
        if self._host_embed:
            params_in, opt_in, batch_in, he_ctxs = \
                self._host_embed_swap_in(params_in, opt_in, self._batch)
        return (params_in, self._stats, opt_in, hp, batch_in,
                jnp.uint32(self._step_count), self._metric_acc), he_ctxs

    def train_iteration(self) -> None:
        """Convenience: forward+backward+update in one fused call."""
        self.forward()
        self.zero_gradients()
        self.backward()
        self.update()

    def train_step_hlo(self) -> str:
        """StableHLO text of the train step as traced for the staged
        batch — the program the next ``update()`` runs.  Trace only:
        nothing is compiled or executed.  A Pallas kernel compiled for
        the TPU appears as a ``tpu_custom_call``; an interpreted one
        leaves no custom call behind."""
        assert self._batch is not None, "no batch loaded"
        if self._host_embed:
            raise ValueError("train_step_hlo: row-sparse host tables are "
                             "swapped in per step; not supported")
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
        # the memory plane wraps the jitted step; lower the jit itself
        fn = getattr(self._train_step_fn, "fn", self._train_step_fn)
        if self._opt_state is None:
            self._opt_state = self._init_opt_state()
        macc = self._metric_acc if self._metric_acc is not None else \
            self._fresh_metric_acc()
        return fn.lower(
            self._offload_put(self._params, False), self._stats,
            self._offload_put_state(self._opt_state, False),
            self.optimizer.hparams(), self._batch,
            jnp.uint32(self._step_count), macc).as_text()

    def placement(self) -> Dict[str, Any]:
        """Where the training state lives: every parameter
        (``"op/weight"``) and staged batch array (``"batch/key"``) as
        the live array, so a caller can read ``.sharding`` and
        ``.addressable_shards``.  Row-sparse host tables are numpy."""
        out = {f"{opn}/{wn}": a
               for opn, ws in (self._params or {}).items()
               for wn, a in ws.items()}
        out.update({f"batch/{k}": a
                    for k, a in (self._batch or {}).items()})
        return out

    def _eval_inputs(self):
        params_in = self._offload_put(self._params, False)
        batch_in = self._batch
        if self._host_embed:
            params_in, _, batch_in, _ = self._host_embed_swap_in(
                params_in, None, self._batch)
        return params_in, batch_in

    def eval_batch(self) -> Dict[str, float]:
        if self._eval_step_fn is None:
            self._eval_step_fn = self._build_eval_step()
        params_in, batch_in = self._eval_inputs()
        msum, _ = self._eval_step_fn(params_in, self._stats, batch_in)
        # one device fetch for the whole metric dict, split on host —
        # per-key float(v) would round-trip to the device once per metric
        msum = jax.device_get(msum)
        return {k: float(v) for k, v in msum.items()}

    def predict_batch(self) -> np.ndarray:
        """Final-op outputs (probabilities) for the staged batch."""
        if self._eval_step_fn is None:
            self._eval_step_fn = self._build_eval_step()
        params_in, batch_in = self._eval_inputs()
        _, probs = self._eval_step_fn(params_in, self._stats, batch_in)
        return np.asarray(probs)

    def logits_batch(self) -> jax.Array:
        """What the loss reads for the staged batch, on the device and in
        the compute dtype: the logits where a trailing Softmax fuses with
        the loss, else the final tensor.  A forward pass of the graph as
        the train step runs it (no dropout); it trains nothing."""
        if self._logits_fn is None:
            read_t = self._loss_input_tensor()
            self._logits_fn = jax.jit(
                lambda params, stats, batch: self._run_graph(
                    params, stats, batch, False, None)[0][read_t.guid])
        params_in, batch_in = self._eval_inputs()
        return self._logits_fn(params_in, self._stats, batch_in)

    # ------------------------------------------------------------------
    # autoregressive generation (beyond the reference, which is
    # training-only: kv-cached decoding as one jitted lax.scan —
    # static shapes, no per-token retrace)
    # ------------------------------------------------------------------
    def _run_graph_decode(self, params, caches, batch, pos, ctx,
                          pre_env=None, skip=(), block_tables=None):
        env: Dict[int, jax.Array] = dict(pre_env) if pre_env else {}
        cdtype = self.compute_dtype
        for t in self.input_tensors:
            if t.guid in env:
                continue
            key = f"in_{t.guid}"
            if key not in batch:
                raise ValueError(
                    f"generate: graph input {t.name or t.guid!r} was not "
                    f"fed — pass it via extra_inputs")
            x = batch[key]
            if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != cdtype:
                x = x.astype(cdtype)
            env[t.guid] = x
        for t, val in self._constants.values():
            if t.guid not in env:
                fill_dtype = jnp.int32 if "int" in t.dtype else cdtype
                env[t.guid] = jnp.full(t.dims, val, fill_dtype)
        new_caches = {}
        for op in self.ops:
            if op.name in skip:
                continue
            xs = [env[t.guid] for t in op.inputs]
            if block_tables is not None and hasattr(op, "decode_paged"):
                # paged serving path: the op's cache rows are pool
                # blocks, addressed through the per-slot block tables
                ys, c = op.decode_paged(params.get(op.param_key, {}), xs,
                                        caches.get(op.name), pos,
                                        block_tables, ctx)
            else:
                ys, c = op.decode(params.get(op.param_key, {}), xs,
                                  caches.get(op.name), pos, ctx)
            new_caches[op.name] = c
            for t, y in zip(op.outputs, ys):
                env[t.guid] = y
        return env, new_caches

    def _decode_params(self):
        """Params tree for decoding: a pipelined model's packed stage
        weights unpack to per-op entries (the decode runner walks ops
        sequentially, not the GPipe ring), and host-resident embedding
        tables move to device ONCE per table version — generated ids
        are data-dependent, so the row-sparse pre-gather is impossible
        and feeding the numpy table into jit would re-upload the whole
        table every generate call.  Cached until a train step or restore
        replaces ``_params`` / bumps the table version."""
        # read barrier: decode reads host-resident tables the async
        # scatter-back may still be writing
        self._he_join()
        tree = self._params
        if self._pipe_pack() is not None:
            cached = getattr(self, "_dp_cache", None)
            if cached is not None and cached[0] is self._params:
                tree = cached[1]
            else:
                from .runtime.checkpoint import _unpack_tree
                tree = _unpack_tree(self, self._params)
                self._dp_cache = (self._params, tree)
        if self._host_embed:
            # keyed on the SOURCE TREE OBJECT (kept alive in the cache —
            # a raw id() could be recycled and false-hit, which in a
            # multi-process run would even diverge per rank around the
            # assemble collective) plus the table version
            cached = getattr(self, "_he_dev_cache", None)
            if (cached is None or cached[0] is not tree
                    or cached[1] != self._he_version):
                src = tree
                rep = self.machine.replicated()
                tree = {k: (dict(v) if isinstance(v, dict) else v)
                        for k, v in tree.items()}
                for opn, info in self._host_embed.items():
                    wn = info["weight"]
                    shard = tree[opn][wn]
                    if not isinstance(shard, np.ndarray):
                        continue
                    full = (self._he_assemble_full(info, shard)
                            if jax.process_count() > 1 else shard)
                    tree[opn][wn] = jax.device_put(
                        np.ascontiguousarray(full), rep)
                self._he_dev_cache = (src, self._he_version, tree)
            tree = self._he_dev_cache[2]
        return tree

    # ------------------------------------------------------------------
    # decode entry points — shared by generate()/beam_search() and the
    # serving engine (flexflow_tpu/serving/), which composes them into
    # its own jitted prefill/step functions over a slot-based kv pool
    # ------------------------------------------------------------------
    def resolve_decode_inputs(self, tokens_input: Optional[Tensor] = None,
                              positions_input: Optional[Tensor] = None):
        """Resolve the (tokens, positions) graph inputs fed one token at
        a time during decoding.  Explicit ``is None`` tests throughout: a
        falsy-but-valid Tensor handle must never be silently replaced by
        the default."""
        tok_t = tokens_input if tokens_input is not None \
            else self.input_tensors[0]
        pos_t = positions_input
        if pos_t is None and tokens_input is None \
                and len(self.input_tensors) > 1:
            # transformer layout (tokens, positions) — only guessed when
            # the tokens input was also defaulted
            pos_t = self.input_tensors[1]
        return tok_t, pos_t

    def init_decode_caches(self, batch_size: int, max_len: int, skip=()):
        """Fresh decode-cache pytree: one entry per op, ``batch_size``
        rows, ``max_len`` sequence positions (trace-safe)."""
        return {op.name: op.init_cache(batch_size, max_len,
                                       self.compute_dtype)
                for op in self.ops if op.name not in skip}

    def pageable_decode(self, skip=()) -> bool:
        """True when every cache-carrying op has a paged decode path —
        the serving engine's gate for block-paged KV (decoder-only
        transformers qualify; LSTM/seq2seq stacks fall back dense)."""
        from .ops.base import Op
        return all(type(op).init_cache is Op.init_cache
                   or hasattr(op, "init_paged_cache")
                   for op in self.ops if op.name not in skip)

    def init_paged_decode_caches(self, num_blocks: int, block_size: int,
                                 skip=()):
        """Fresh block-pool cache pytree: cache-carrying ops get
        ``(num_blocks, H, block_size, D)`` pools (block 0 is the garbage
        sink, serving/kvpool.py); stateless ops get None."""
        from .ops.base import Op
        out = {}
        for op in self.ops:
            if op.name in skip:
                continue
            if type(op).init_cache is Op.init_cache:
                out[op.name] = None
            elif hasattr(op, "init_paged_cache"):
                out[op.name] = op.init_paged_cache(num_blocks, block_size,
                                                   self.compute_dtype)
            else:
                raise ValueError(
                    f"paged decode: op {op.name!r} "
                    f"({type(op).__name__}) carries a decode cache but "
                    f"has no paged path — serve it with FF_SERVE_PAGED=off")
        return out

    def decode_step(self, params, stats, caches, cur, pos, tok_t, pos_t,
                    pre_env=None, skip=(), block_tables=None):
        """One single-token decode step: feed token ids ``cur`` (B,)
        int32 at position ``pos`` and return (probs (B, V) float32, new
        caches).  ``pos`` is a scalar, or a per-row (B,) vector when the
        rows sit at DIFFERENT sequence positions — the serving engine's
        continuous batch, where each slot carries its own write offset
        and causal-mask length.  Trace-safe: generate()/beam_search()
        call this inside their jitted scans, the serving engine inside
        its jitted prefill/step functions."""
        B = cur.shape[0]
        batch = {f"in_{tok_t.guid}": cur[:, None]}
        if pos_t is not None:
            p = pos if jnp.ndim(pos) else jnp.full((B,), pos, jnp.int32)
            batch[f"in_{pos_t.guid}"] = p[:, None]
        ctx = FwdCtx(training=False, rng=jax.random.key(self.config.seed),
                     stats_in=stats)
        env, caches = self._run_graph_decode(params, caches, batch, pos,
                                             ctx, pre_env=pre_env,
                                             skip=skip,
                                             block_tables=block_tables)
        probs = env[self.final_tensor().guid][:, -1, :].astype(jnp.float32)
        return probs, caches

    def _check_position_table(self, pos_t, s_max: int) -> None:
        """jnp.take clamps OOB position lookups under jit — catch an
        overlong request instead of degrading silently."""
        if pos_t is None:
            return
        # the scan runs P+N-1 steps over positions 0..P+N-2, so the
        # largest index used is s_max-2 — a table of s_max-1 entries is
        # exactly enough
        for op in self.ops:
            if isinstance(op, Embedding) and op.inputs[0] is pos_t \
                    and s_max - 1 > op.num_entries:
                raise ValueError(
                    f"decode: prompt + max_new_tokens = {s_max} needs "
                    f"{s_max - 1} positions but the position table has "
                    f"only {op.num_entries} entries")

    def _static_decode_ops(self, extra_guids):
        """Ops reachable from the FIXED extra inputs alone (a seq2seq
        encoder): run once before the decode scan, not once per token."""
        avail = set(extra_guids)
        avail.update(t.guid for t, _ in self._constants.values())
        static_ops = []
        if extra_guids:
            for op in self.ops:
                if op.inputs and all(t.guid in avail for t in op.inputs):
                    static_ops.append(op)
                    avail.update(t.guid for t in op.outputs)
        return static_ops, frozenset(op.name for op in static_ops)

    def _prefill_static(self, params, stats, extra, extra_guids,
                        static_ops, repeat: int = 1):
        cdtype = self.compute_dtype
        env = {}
        for g in extra_guids:
            x = extra[f"in_{g}"]
            env[g] = jnp.repeat(x, repeat, axis=0) if repeat > 1 else x
        for t, val in self._constants.values():
            fdt = jnp.int32 if "int" in t.dtype else cdtype
            env[t.guid] = jnp.full(t.dims, val, fdt)
        ctx = FwdCtx(training=False, rng=jax.random.key(self.config.seed),
                     stats_in=stats)
        for op in static_ops:
            xs = [env[t.guid] for t in op.inputs]
            ys = op.forward(params.get(op.param_key, {}), xs, ctx)
            for t, y in zip(op.outputs, ys):
                env[t.guid] = y
        return env

    def generate(self, prompt_tokens, max_new_tokens: int, *,
                 tokens_input: Optional[Tensor] = None,
                 positions_input: Optional[Tensor] = None,
                 extra_inputs: Optional[Dict[Tensor, Any]] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 seed: int = 0) -> np.ndarray:
        """Generate ``max_new_tokens`` continuations for a (B, P) int32
        prompt with kv-cached greedy (temperature=0) or sampled
        decoding.  The whole prefill+decode loop is ONE jitted
        ``lax.scan`` over P+N-1 single-token steps — each attention op
        carries a (B, H, P+N, head_dim) cache written in place.

        Sampling knobs (active only with temperature > 0): ``top_k``
        keeps the k most likely tokens; ``top_p`` keeps the smallest
        nucleus of tokens whose probabilities sum to >= p (the most
        likely token always survives); both may combine.

        ``tokens_input``/``positions_input`` default to the model's
        first/second graph inputs (the ``build_transformer`` layout).
        ``extra_inputs`` maps further graph inputs to FIXED full arrays
        fed every step — e.g. the source sentence of a seq2seq model
        (its encoder ops re-run per step; the decoder LSTMs carry their
        state in the decode cache).
        """
        assert self._compiled, "call compile() first"
        toks = jnp.asarray(prompt_tokens, jnp.int32)
        B, P = toks.shape
        N = int(max_new_tokens)
        if N <= 0:
            return np.zeros((B, 0), np.int32)
        tok_t, pos_t = self.resolve_decode_inputs(tokens_input,
                                                  positions_input)
        s_max = P + N
        self._check_position_table(pos_t, s_max)
        sampled = float(temperature) > 0.0
        # bad knob values fail loudly even when greedy ignores them ...
        if top_k is not None and int(top_k) < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if top_p is not None and not 0.0 < float(top_p) <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        # ... then normalize to trace constants: inactive knobs don't
        # fork the compile cache
        t_k = int(top_k) if sampled and top_k is not None else None
        t_p = float(top_p) if sampled and top_p is not None else None

        extra_guids = {t.guid for t in (extra_inputs or {})}
        static_ops, static_names = self._static_decode_ops(extra_guids)

        def step(params, stats, pre_env, temp, carry, inp):
            caches, tok, pos, key = carry
            feed_tok, use_feed = inp
            cur = jnp.where(use_feed, feed_tok, tok)          # (B,)
            probs, caches = self.decode_step(
                params, stats, caches, cur, pos, tok_t, pos_t,
                pre_env=pre_env, skip=static_names)           # (B, V)
            if sampled:
                logits = jnp.log(probs + 1e-9)
                if t_k is not None or t_p is not None:
                    srt = jnp.sort(probs, axis=-1)[:, ::-1]       # desc
                    if t_k is not None:
                        kth = srt[:, min(t_k, srt.shape[1]) - 1][:, None]
                        logits = jnp.where(probs >= kth, logits, -jnp.inf)
                    if t_p is not None:
                        csum = jnp.cumsum(srt, axis=-1)
                        # smallest prefix with mass >= p; cutoff = that
                        # prefix's lowest prob (top token always
                        # survives).  Clamp: with p=1.0 a float32 row
                        # summing just under 1.0 would index past V
                        keep_n = jnp.minimum(jnp.sum(csum < t_p, axis=-1),
                                             srt.shape[1] - 1)
                        cutoff = jnp.take_along_axis(
                            srt, keep_n[:, None], axis=-1)
                        logits = jnp.where(probs >= cutoff, logits,
                                           -jnp.inf)
                key, k = jax.random.split(key)
                nxt = jax.random.categorical(k, logits / temp, axis=-1)
            else:
                nxt = jnp.argmax(probs, axis=-1)
            nxt = nxt.astype(jnp.int32)
            return (caches, nxt, pos + 1, key), nxt

        extra = {f"in_{t.guid}": jnp.asarray(v)
                 for t, v in (extra_inputs or {}).items()}
        cache = getattr(self, "_gen_cache", None)
        if cache is None:
            cache = self._gen_cache = {}
        # seed/temperature are runtime ARGUMENTS (key0/temp below), not
        # trace constants — new seeds reuse the compiled scan
        ckey = (B, P, N, sampled, t_k, t_p, tok_t.guid,
                pos_t.guid if pos_t is not None else None,
                tuple(sorted((k, v.shape) for k, v in extra.items())))
        run = cache.get(ckey)
        if run is None:
            def run(params, stats, extra, feed, use, key0, temp):
                pre_env = self._prefill_static(params, stats, extra,
                                               extra_guids, static_ops)
                caches0 = self.init_decode_caches(B, s_max,
                                                  skip=static_names)
                carry0 = (caches0, jnp.zeros((B,), jnp.int32),
                          jnp.zeros((), jnp.int32), key0)
                _, outs = jax.lax.scan(
                    lambda c, i: step(params, stats, pre_env, temp, c, i),
                    carry0, (feed, use))
                return outs                                   # (P+N-1, B)

            run = jax.jit(run)
            if self._memplane is not None:
                run = self._memplane.wrap(f"generate:{B}x{P}x{N}", run)
            cache[ckey] = run

        feed = jnp.concatenate(
            [toks.T, jnp.zeros((N - 1, B), jnp.int32)]) if N > 1 else toks.T
        use = jnp.concatenate([jnp.ones((P,), bool),
                               jnp.zeros((N - 1,), bool)])
        outs = run(self._decode_params(), self._stats, extra, feed, use,
                   jax.random.key(seed),
                   jnp.asarray(float(temperature), jnp.float32))
        return np.asarray(outs[P - 1:].T)                     # (B, N)

    def beam_search(self, prompt_tokens, max_new_tokens: int, *,
                    beam_size: int = 4,
                    tokens_input: Optional[Tensor] = None,
                    positions_input: Optional[Tensor] = None,
                    extra_inputs: Optional[Dict[Tensor, Any]] = None,
                    eos_id: Optional[int] = None,
                    length_penalty: float = 0.0):
        """Beam-search decoding: returns (sequences (B, K, N) int32,
        scores (B, K) float32 — summed token log-probs, best first).

        Beams ride the batch dim (B*K rows through the same kv-cached
        decode graph as ``generate``); at each step candidate scores
        expand to (B, K*V), the top K survive, and every cache leaf is
        gathered by the surviving beams' parent indices — all inside one
        jitted ``lax.scan``.  A finished beam (``eos_id`` emitted) is
        frozen by forcing its next-token distribution to eos at
        log-prob 0.  ``length_penalty`` alpha > 0 re-ranks the final
        beams by the GNMT normalization score/((5+len)/6)^alpha (len =
        tokens up to and including eos); the returned scores stay raw
        log-prob sums.
        """
        assert self._compiled, "call compile() first"
        toks = jnp.asarray(prompt_tokens, jnp.int32)
        B, P = toks.shape
        N = int(max_new_tokens)
        K = int(beam_size)
        if N <= 0:
            return (np.zeros((B, K, 0), np.int32),
                    np.zeros((B, K), np.float32))
        tok_t, pos_t = self.resolve_decode_inputs(tokens_input,
                                                  positions_input)
        s_max = P + N
        self._check_position_table(pos_t, s_max)
        BK = B * K

        extra_guids = {t.guid for t in (extra_inputs or {})}
        static_ops, static_names = self._static_decode_ops(extra_guids)

        def step(params, stats, pre_env, carry, inp):
            caches, buf, scores, last, pos = carry
            feed_tok, use_feed, do_expand = inp           # (B,), scalars
            cur = jnp.where(use_feed,
                            jnp.repeat(feed_tok, K), last)    # (BK,)
            probs, caches = self.decode_step(
                params, stats, caches, cur, pos, tok_t, pos_t,
                pre_env=pre_env, skip=static_names)
            logp = jnp.log(probs + 1e-30)                  # (BK, V)
            V = logp.shape[-1]
            if eos_id is not None:
                # freeze on the token at THIS position (cur) — the carry
                # `last` is one token stale at the first expand step
                fin = (cur == eos_id)[:, None]
                frozen = jnp.full((1, V), -jnp.inf).at[0, eos_id].set(0.0)
                logp = jnp.where(fin, frozen, logp)

            def expand(args):
                caches, buf, scores, _ = args
                total = scores.reshape(B, K, 1) + logp.reshape(B, K, V)
                top, idx = jax.lax.top_k(total.reshape(B, K * V), K)
                parent = idx // V                          # (B, K)
                token = (idx % V).astype(jnp.int32)
                flat = (parent + jnp.arange(B)[:, None] * K).reshape(-1)
                caches = jax.tree.map(lambda c: c[flat], caches)
                buf = buf[flat]
                widx = jnp.clip(pos - (P - 1), 0, N - 1)
                buf = jax.lax.dynamic_update_slice(
                    buf, token.reshape(BK, 1), (0, widx))
                return caches, buf, top, token.reshape(-1)

            def passthrough(args):
                caches, buf, scores, _ = args
                return caches, buf, scores, cur

            caches, buf, scores, last = jax.lax.cond(
                do_expand, expand, passthrough, (caches, buf, scores, cur))
            return (caches, buf, scores, last, pos + 1), None

        extra = {f"in_{t.guid}": jnp.asarray(v)
                 for t, v in (extra_inputs or {}).items()}
        cache = getattr(self, "_gen_cache", None)
        if cache is None:
            cache = self._gen_cache = {}
        ckey = ("beam", B, P, N, K, eos_id, tok_t.guid,
                pos_t.guid if pos_t is not None else None,
                tuple(sorted((k, v.shape) for k, v in extra.items())))
        run = cache.get(ckey)
        if run is None:
            def run(params, stats, extra, feed, use):
                pre_env = self._prefill_static(params, stats, extra,
                                               extra_guids, static_ops,
                                               repeat=K)
                caches0 = self.init_decode_caches(BK, s_max,
                                                  skip=static_names)
                # beams 1..K-1 start at -inf so the first free step
                # expands from beam 0 alone
                scores0 = jnp.tile(
                    jnp.concatenate([jnp.zeros((1,)),
                                     jnp.full((K - 1,), -jnp.inf)])[None],
                    (B, 1)).astype(jnp.float32)
                carry0 = (caches0, jnp.zeros((BK, N), jnp.int32), scores0,
                          jnp.zeros((BK,), jnp.int32),
                          jnp.zeros((), jnp.int32))
                # T = P+N-1 steps: positions 0..P-2 feed the prompt;
                # positions P-1..P+N-2 expand (N beam updates)
                (caches, buf, scores, last, _), _ = jax.lax.scan(
                    lambda c, i: step(params, stats, pre_env, c, i),
                    carry0, (feed, use, do_exp))
                return buf.reshape(B, K, N), scores

            run = jax.jit(run)
            if self._memplane is not None:
                run = self._memplane.wrap(
                    f"beam_search:{B}x{P}x{N}x{K}", run)
            cache[ckey] = run

        feed = jnp.concatenate(
            [toks.T, jnp.zeros((N - 1, B), jnp.int32)]) if N > 1 else toks.T
        use = jnp.concatenate([jnp.ones((P,), bool),
                               jnp.zeros((N - 1,), bool)])
        do_exp = jnp.concatenate([jnp.zeros((P - 1,), bool),
                                  jnp.ones((N,), bool)])
        seqs, scores = run(self._decode_params(), self._stats, extra, feed, use)
        seqs, scores = np.asarray(seqs), np.asarray(scores)
        if length_penalty > 0.0 and eos_id is not None:
            # without an eos all lens == N and the re-rank is a no-op
            hits = seqs == eos_id                          # (B, K, N)
            lens = np.where(hits.any(-1),
                            hits.argmax(-1) + 1, N).astype(np.float64)
            norm = scores / (((5.0 + lens) / 6.0) ** length_penalty)
            order = np.argsort(-norm, axis=1, kind="stable")  # best first
            seqs = np.take_along_axis(seqs, order[:, :, None], axis=1)
            scores = np.take_along_axis(scores, order, axis=1)
        return seqs, scores

    # ------------------------------------------------------------------
    # metrics (reference: UPDATE_METRICS_TASK fold, model.cc:1145-1167)
    # ------------------------------------------------------------------
    def reset_metrics(self) -> None:
        if self._nonfinite_guard is not None and self._metric_acc is not None:
            # Guard entries (skip counts, consec run length) ride the
            # accumulator — drain before discarding so narration and
            # escalation can't be dropped by an epoch-boundary reset.
            self._drain_metrics()
        self.current_metrics.reset()
        self.last_loss = None
        self._metric_acc = None

    def _drain_metrics(self) -> None:
        if self._metric_acc is not None:
            with _ff_span(self._telemetry, "metric_drain"):
                self._fold_metrics()

    def _fold_metrics(self) -> None:
        """The drain's work: one small transfer and the fold of its
        totals into the host-side metrics."""
        vec = jax.device_get(self._metric_acc)  # single small transfer
        if self._stepstats is not None:
            self._stepstats.on_drain()  # a sync point: the rate's interval
        totals = dict(zip(self._metric_keys(), [float(v) for v in vec]))
        _ff_count({k: totals.pop(k) for k in self._op_counter_keys()})
        steps = totals.pop("steps", 0.0)
        loss_sum = totals.pop("loss", None)
        if steps > 0 and loss_sum is not None:
            self.last_loss = loss_sum / steps  # mean loss since last drain
        guard = self._nonfinite_guard
        guard_vals = None
        if guard is not None:
            guard_vals = {k: totals.pop(k, 0.0) for k in guard.METRIC_KEYS}
        if self._health is not None:
            from .observability.health import HEALTH_METRIC_KEYS
            health_vals = {k: totals.pop(k) for k in
                           HEALTH_METRIC_KEYS if k in totals}
            self._health.on_drain(health_vals, steps, self._step_count)
        elif guard is not None:
            # Health entries rode the vector only for the guard's
            # skip decision; pop so they don't leak into PerfMetrics.
            from .observability.health import HEALTH_METRIC_KEYS
            for k in HEALTH_METRIC_KEYS:
                totals.pop(k, None)
        self.current_metrics.update(totals)
        self._metric_acc = jnp.zeros_like(self._metric_acc)
        if guard_vals is not None:
            consec = guard_vals.get("consec_skipped", 0.0)
            if consec > 0:
                # consec_skipped is a run length, not a window sum:
                # carry it through the accumulator reset so a NaN
                # streak spanning drains still escalates.
                ci = self._metric_keys().index("consec_skipped")
                self._metric_acc = self._metric_acc.at[ci].set(consec)
            # Last: on_drain may raise NonFiniteEscalationError and
            # the window's totals are already folded in above.
            guard.on_drain(guard_vals.get("skipped_steps", 0.0),
                           consec, steps, self._step_count)

    def get_metrics(self) -> PerfMetrics:
        self._drain_metrics()
        return self.current_metrics

    def print_metrics(self) -> None:
        self.get_metrics().print()

    def sync(self) -> None:
        """Block until all dispatched device work completes (the analogue
        of the reference's execution fence + timing future): every
        output of the last step is ready when this returns."""
        if self._chaos is not None:
            self._chaos.fire("sync", model=self)
        with _ff_span(self._telemetry, "sync"):
            self._he_join()
            jax.block_until_ready((self._params, self._stats,
                                   self._opt_state, self._metric_acc))

    # ------------------------------------------------------------------
    # weight access (reference: Parameter::set_weights/get_weights,
    # src/runtime/model.cu:260-370)
    # ------------------------------------------------------------------
    # ------------------------------------------------------------------
    # checkpoint / profiling (runtime/checkpoint.py, runtime/profiling.py)
    # ------------------------------------------------------------------
    def save(self, path: str) -> None:
        """Save full training state (params/stats/optimizer/step)."""
        from .runtime.checkpoint import save_checkpoint
        save_checkpoint(self, path)

    def load(self, path: str) -> None:
        """Restore state saved by ``save``, re-sharded onto this mesh."""
        from .runtime.checkpoint import load_checkpoint
        load_checkpoint(self, path)

    def print_op_profile(self) -> None:
        """Per-op fwd/bwd ms (reference --profiling printouts)."""
        from .runtime.profiling import print_op_profile
        print_op_profile(self)

    def print_layers(self) -> None:
        """Per-op metadata dump (reference: FFModel::print_layers,
        src/runtime/model.cc — op type, output dims, weights, placement)."""
        strategies = self.get_strategies() if self._compiled else {}
        for i, op in enumerate(self.ops):
            pc = strategies.get(op.name)
            pcs = f" pc={list(pc.dims)}" if pc is not None else ""
            print(f"layer[{i}] {op.name} ({op._type}) "
                  f"out={op.output.dims}{pcs}")
            for w in op.weights:
                print(f"   weight {w.name}: {w.dims}")

    def _pack_entry(self, op_name: str, weight_name: str):
        pack = self._pipe_pack()
        if pack and op_name in pack["entries"]:
            return pack["entries"][op_name].get(weight_name)
        return None

    def get_parameter(self, op_name: str, weight_name: str = "kernel") -> np.ndarray:
        """Fetch a weight as numpy (reference: Parameter::get_weights).

        Multi-process NOTE: for a row-range-sharded host-resident
        embedding table this assembles the FULL table via a process
        allgather — a COLLECTIVE, so every process must call it in the
        same order (a rank-0-only call deadlocks, like any collective).
        """
        self._he_join()
        e = self._pack_entry(op_name, weight_name)
        if e is not None:
            # Slice the slot row on device first — fetching the whole
            # (ring, width) buffer per accessor call would move the
            # entire packed segment for one weight.
            _, off, shape, n = e
            row = self._params["_pipe"]["buffer"][e[0], off:off + n]
            return np.asarray(row).reshape(shape)
        w = self._params[op_name][weight_name]
        if isinstance(w, np.ndarray):
            info = self._he_info(op_name, weight_name)
            if info is not None:
                # row-range-sharded across processes: return the FULL
                # table (single-process accessor semantics)
                return self._he_assemble_full(info, w)
            # host-resident table: np.asarray would alias the live
            # array the scatter-back mutates in place — copy, matching
            # the device leaves (device_get always materializes fresh)
            return w.copy()
        return np.asarray(w)

    def set_parameter(self, op_name: str, weight_name: str, value: np.ndarray) -> None:
        self._he_join()
        e = self._pack_entry(op_name, weight_name)
        if e is not None:
            cur = self._params["_pipe"]["buffer"]
            new = self._pack_write(jnp.asarray(cur), e,
                                   jnp.asarray(value, jnp.float32))
            self._params["_pipe"]["buffer"] = jax.device_put(new, cur.sharding)
            # in-place rebind keeps id(self._params): the identity-keyed
            # decode caches would otherwise serve the pre-set weight
            self._dp_cache = None
            self._he_dev_cache = None
            return
        cur = self._params[op_name][weight_name]
        if isinstance(cur, np.ndarray):  # row-sparse host-resident table
            info = self._he_info(op_name, weight_name)
            if info is not None:  # full table in, own row range kept
                value = np.asarray(value)[info["row_lo"]:info["row_hi"]]
            self._params[op_name][weight_name] = np.asarray(
                value, dtype=cur.dtype).reshape(cur.shape).copy()
            self._he_version += 1
            self._he_dev_cache = None
            return
        self._params[op_name][weight_name] = jax.device_put(
            jnp.asarray(value, dtype=cur.dtype), cur.sharding)

    def get_strategies(self) -> Dict[str, ParallelConfig]:
        return self._all_strategies()
