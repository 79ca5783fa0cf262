"""Operator base class.

TPU-native analogue of the reference ``Op`` abstract class
(reference: include/model.h:190-231).  The reference contract is 8 Legion
methods (create_output_and_partition / create_weights / init / forward /
backward / measure_compute_time ...); here an op is a *pure function* plus
shape/partition metadata:

  * construction performs shape inference and declares weights
    (≈ create_weights + create_output_and_partition),
  * ``forward`` is a jit-traceable function of (weights, inputs) — the
    backward pass comes from ``jax.grad``, so no hand-written backward,
  * ``weight_partition_dims`` maps each weight dim to the output-config
    dim it shards with (compile lowers this to NamedShardings — the
    analogue of create_weights' region partitioning),
  * the simulator costs ops by compiling+timing ``forward`` on sub-shapes
    (≈ measure_compute_time).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax

from ..tensor import Parameter, Tensor


@dataclasses.dataclass
class FwdCtx:
    """Per-call context threaded through op forwards inside jit."""

    training: bool = False
    rng: Optional[jax.Array] = None  # folded per-op by guid before use
    stats_in: Optional[Dict[str, Dict[str, jax.Array]]] = None
    stats_out: Optional[Dict[str, Dict[str, jax.Array]]] = None
    # Scalars an op adds to under the names of its ``COUNTERS``; the train
    # step sums them into its metric vector.  None outside a train step.
    counters: Optional[Dict[str, jax.Array]] = None
    # Scalars an op adds to the step's objective, by name (``add_loss``):
    # the train step differentiates the final tensor's loss plus their
    # sum.  None outside a train step, and an op then computes no term.
    losses: Optional[Dict[str, jax.Array]] = None

    def add_loss(self, name: str, value: jax.Array) -> None:
        """Add ``value`` to the objective under ``name`` (terms of one
        name are summed).  A term is also a counter of that name where
        the op lists it in ``COUNTERS``, so the drain carries it out."""
        self.losses[name] = self.losses.get(name, 0.0) + value
        if self.counters is not None:
            self.counters[name] = self.counters.get(name, 0.0) \
                + jax.lax.stop_gradient(value)

    def op_rng(self, op: "Op") -> jax.Array:
        assert self.rng is not None, "op requires an RNG but none was provided"
        return jax.random.fold_in(self.rng, op.guid)


class Op:
    """Graph node: inputs → outputs with optional weights/state."""

    _type: str = "Op"
    # names of the per-step scalars this op adds to ``FwdCtx.counters``
    COUNTERS: Sequence[str] = ()

    def __init__(self, model, inputs: Sequence[Tensor], name: Optional[str] = None):
        self.model = model
        self.guid = model._next_op_guid()
        # Reference auto-names ops "<Type>_<guid>" (src/runtime/model.cc:142-144)
        # unless the _v2 named API supplies one; strategy files bind by name.
        self.name = name if name else f"{self._type}_{self.guid}"
        self.inputs: List[Tensor] = list(inputs)
        self.weights: List[Parameter] = []
        self.outputs: List[Tensor] = []
        self.profiling = False
        # Weight sharing (reference: NMT SharedVariable nmt/rnn.h:37-51 and
        # the FFModel ops' weight_sharing argument): when set, this op has
        # no weights of its own and reads the owner op's parameters.
        self.share_from: Optional["Op"] = None

    @property
    def param_key(self) -> str:
        """Key into the params pytree: the owning op's name."""
        return self.share_from.name if self.share_from is not None else self.name

    # -- graph construction ------------------------------------------------
    def _add_output(self, dims, dtype="float32") -> Tensor:
        t = Tensor(dims=tuple(dims), dtype=dtype, owner_op=self, owner_idx=len(self.outputs))
        self.outputs.append(t)
        return t

    def _add_weight(self, name, dims, initializer, partition_dims=None, dtype="float32") -> Parameter:
        p = Parameter(name=name, dims=tuple(dims), dtype=dtype,
                      initializer=initializer, owner_op=self,
                      partition_dims=partition_dims)
        self.weights.append(p)
        return p

    @property
    def output(self) -> Tensor:
        return self.outputs[0]

    # -- execution ---------------------------------------------------------
    def forward(self, params: Dict[str, jax.Array], xs: List[jax.Array], ctx: FwdCtx) -> List[jax.Array]:
        raise NotImplementedError

    # -- autoregressive decoding (FFModel.generate) ------------------------
    def init_cache(self, batch_size: int, max_len: int, dtype):
        """Decode-cache pytree for kv-cached generation; None for
        stateless ops."""
        return None

    def decode(self, params, xs: List[jax.Array], cache, pos, ctx: FwdCtx):
        """One-token decode step at sequence position ``pos`` (scalar
        int array).  ``xs`` carry a single time step (B, 1, ...).
        Returns (ys, new_cache).  Default: stateless forward."""
        return self.forward(params, xs, ctx), cache

    def constraint_pc(self):
        """ParallelConfig used to place this op's OUTPUT activations.
        Defaults to the op's own config; ops whose config dims carry
        non-layout meaning (e.g. the pipeline degree) override this."""
        return self.pc

    def batch_only_pc(self):
        """A ``constraint_pc`` for ops whose other config dims place
        weights, not outputs (an expert, head or width degree whose
        shards give partial sums): the output is batch-sharded only."""
        from ..config import ParallelConfig

        return ParallelConfig(dims=(self.pc.dims[0],)
                              + (1,) * (self.output.num_dims - 1))

    def _config_dim_bound(self, i: int) -> Optional[int]:
        """The size config dim ``i``'s degree must divide (None: no
        bound).  Ops whose config dims carry non-size meaning override
        this (PipelineMLP's dim 1 is the pipe degree, bounded by
        num_stages rather than the feature width)."""
        return self.output.dims[i] if i < self.output.num_dims else None

    def legalize_pc(self, pc):
        """Clamp a proposed config to one this op can execute — used by
        compile() and by BOTH search paths before costing a candidate.
        Each dim's degree must divide the op's bound for that dim (the
        reference simply asserts; we degrade to the largest legal
        degree)."""
        import math

        from ..config import ParallelConfig

        dims = list(pc.dims)
        changed = False
        for i, d in enumerate(dims):
            bound = self._config_dim_bound(i)
            if bound is not None and bound % d != 0:
                dims[i] = math.gcd(d, bound)
                changed = True
        if not changed:
            return pc
        npc = ParallelConfig(pc.device_type, tuple(dims),
                             memory_types=pc.memory_types)
        return npc.with_device_ids(tuple(range(npc.num_parts())))

    # -- stats (non-trainable state, e.g. batchnorm running moments) -------
    def init_stats(self) -> Dict[str, jax.Array]:
        return {}

    # -- cost model hooks (used by the simulator) --------------------------
    def flops_per_sample(self) -> float:
        """Analytic forward FLOPs per sample; simulator fallback when a
        measured timing is unavailable."""
        return 0.0

    # -- tiling hooks (simulator comm model; analogue of the reference's
    # get_output_tensor_shape / get_input_tensor_shape, model.cc:333-380) --
    def _grid_coord(self, pc, part_idx):
        coord = []
        rem = part_idx
        for d in reversed(pc.dims):
            coord.append(rem % d)
            rem //= d
        return tuple(reversed(coord))

    def output_tile(self, pc, part_idx, output_idx: int = 0):
        """Per-dim (lo, hi) inclusive ranges of this part's output tile."""
        dims = self.outputs[output_idx].dims
        coord = self._grid_coord(pc, part_idx)
        out = []
        for i, size in enumerate(dims):
            deg = pc.dims[i] if i < len(pc.dims) else 1
            c = coord[i] if i < len(coord) else 0
            tile = size // deg
            out.append((c * tile, (c + 1) * tile - 1))
        return out

    def input_ranges(self, j: int, pc, part_idx):
        """Per-dim (lo, hi) ranges of input ``j`` this part reads.

        Default: proportional mapping when ranks match (a dim of the
        output maps onto the same dim of the input, scaled — this yields
        conv-style halos approximately); otherwise only the batch dim is
        tiled and the rest is read fully."""
        in_dims = self.inputs[j].dims
        out_dims = self.outputs[0].dims
        tile = self.output_tile(pc, part_idx)
        rng = []
        if len(in_dims) == len(out_dims):
            for i, isz in enumerate(in_dims):
                osz = out_dims[i]
                lo, hi = tile[i]
                if isz == osz:
                    rng.append((lo, hi))
                else:
                    rng.append((lo * isz // osz,
                                min(isz - 1, -((-(hi + 1) * isz) // osz) - 1)))
        else:
            b_lo, b_hi = tile[0]
            rng.append((b_lo * in_dims[0] // out_dims[0],
                        (b_hi + 1) * in_dims[0] // out_dims[0] - 1))
            for isz in in_dims[1:]:
                rng.append((0, isz - 1))
        return rng

    def weight_tile(self, pc, w_idx: int, part_idx):
        """Per-dim ranges of weight ``w_idx`` held by this part — full
        range for replicated dims, the part's slice for sharded dims."""
        w = self.weights[w_idx]
        coord = self._grid_coord(pc, part_idx)
        out = []
        for i, size in enumerate(w.dims):
            pd = w.partition_dims[i]
            if pd is None or pd >= len(pc.dims) or pc.dims[pd] == 1:
                out.append((0, size - 1))
            else:
                deg = pc.dims[pd]
                c = coord[pd]
                tile = size // deg
                out.append((c * tile, (c + 1) * tile - 1))
        return out

    def __repr__(self):
        ins = ",".join(str(t.dims) for t in self.inputs)
        outs = ",".join(str(t.dims) for t in self.outputs)
        return f"{self._type}({self.name}: {ins} -> {outs})"
