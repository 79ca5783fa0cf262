"""Mixture-of-Experts operator with expert parallelism.

The reference has no MoE (SURVEY §2.3 "absent in reference"), but its
SOAP abstraction — partition any tensor dim of any op — is exactly the
hook expert parallelism needs: this op makes the EXPERT dim an explicit
partitionable axis the same way PipelineMLP exposes the operator dim
(ops/pipeline.py).  ``ParallelConfig`` dim 1 is the EXPERT-parallel
degree: expert weights shard over it, and XLA GSPMD emits the
token all_to_all (dispatch) + all_to_all (combine) pair over those mesh
axes from the sharding annotations alone — the TPU-native equivalent of
hand-written NCCL alltoall in GPU MoE stacks.

Routing is Switch-style top-1 with a capacity limit: per token,
``argmax(softmax(x @ router))`` picks the expert; tokens beyond
``capacity = ceil(tokens/E · capacity_factor)`` are dropped (output 0 —
callers add the residual).  Dispatch/combine are dense one-hot einsums:
static shapes, MXU-friendly, deterministic under any sharding — so
strategies change placement, not results.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional

import jax
import jax.numpy as jnp

from .base import FwdCtx, Op
from ..initializers import DefaultWeightInitializer, ZeroInitializer


class _ExpertDim:
    """Config semantics of an op whose dim 1 is the EXPERT-parallel
    degree over its ``num_experts`` (mirrors PipelineMLP's non-layout
    dim 1)."""

    def _config_dim_bound(self, i: int):
        """Config dim 1 is the EXPERT-parallel degree: legal iff it
        divides ``num_experts`` — not the tensor dim the base size check
        would compare against."""
        if i == 1:
            return self.num_experts
        return super()._config_dim_bound(i)

    # the expert degree places weights, not outputs
    constraint_pc = Op.batch_only_pc


class ExpertMLP(_ExpertDim, Op):
    _type = "ExpertMLP"

    def __init__(self, model, input_tensor, num_experts: int,
                 hidden_size: int, capacity_factor: float = 1.25,
                 activation: str = "relu", name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        dims = input_tensor.dims
        d = dims[-1]
        self.num_experts = int(num_experts)
        self.hidden_size = int(hidden_size)
        self.capacity_factor = float(capacity_factor)
        self.activation = activation
        e, h = self.num_experts, self.hidden_size
        # expert (leading) dim partitions over config dim 1 — the
        # expert-parallel degree; the router stays replicated.
        self._add_weight("router", (d, e), DefaultWeightInitializer())
        self._add_weight("w_in", (e, d, h), DefaultWeightInitializer(),
                         partition_dims=(1, None, None))
        self._add_weight("b_in", (e, h), ZeroInitializer(),
                         partition_dims=(1, None))
        self._add_weight("w_out", (e, h, d), DefaultWeightInitializer(),
                         partition_dims=(1, None, None))
        self._add_weight("b_out", (e, d), ZeroInitializer(),
                         partition_dims=(1, None))
        self._add_output(dims, input_tensor.dtype)

    def _ep_axes(self):
        pc = getattr(self, "pc", None)
        machine = self.model.machine
        if (pc is None or len(pc.dims) < 2 or pc.dims[1] <= 1
                or machine is None or machine.num_devices <= 1):
            return None
        try:
            groups = machine.axes_for_degrees([pc.dims[0], pc.dims[1]])
        except ValueError:
            return None
        return groups[1] or None

    def capacity(self, tokens: int) -> int:
        return max(1, math.ceil(tokens / self.num_experts
                                * self.capacity_factor))

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        x = xs[0]
        shape = x.shape
        d = shape[-1]
        dt = x.dtype
        s = 1
        for dim in shape[:-1]:
            s *= dim
        xf = x.reshape(s, d)
        e = params["w_in"].shape[0]
        cap = self.capacity(s)

        # Router in f32: top-1 gate per token (Switch).
        logits = jnp.dot(xf.astype(jnp.float32),
                         params["router"].astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)            # (S, E)
        expert_idx = jnp.argmax(gates, axis=-1)            # (S,)
        gate = jnp.max(gates, axis=-1)                     # (S,)
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)
        # position of each token in its expert's queue (capacity cut)
        pos = jnp.cumsum(onehot, axis=0) * onehot          # 1-based
        keep = (pos > 0) & (pos <= cap)
        pos_idx = jnp.clip(pos - 1.0, 0, cap - 1).astype(jnp.int32)
        slot = jax.nn.one_hot(jnp.max(pos_idx, axis=-1), cap,
                              dtype=jnp.float32)           # (S, C)
        disp = (onehot * keep).astype(jnp.float32)[:, :, None] \
            * slot[:, None, :]                             # (S, E, C)

        cons = self._expert_constraint
        expert_in = cons(jnp.einsum("sec,sd->ecd", disp,
                                    xf.astype(jnp.float32)))
        hmid = jnp.einsum("ecd,edh->ech", expert_in.astype(dt),
                          params["w_in"].astype(dt))
        hmid = hmid + params["b_in"].astype(hmid.dtype)[:, None, :]
        if self.activation == "relu":
            hmid = jax.nn.relu(hmid)
        elif self.activation == "gelu":
            hmid = jax.nn.gelu(hmid)
        hmid = cons(hmid)
        y_e = jnp.einsum("ech,ehd->ecd", hmid, params["w_out"].astype(dt))
        y_e = y_e + params["b_out"].astype(y_e.dtype)[:, None, :]
        y_e = cons(y_e)
        comb = disp * gate[:, None, None]                  # (S, E, C)
        y = jnp.einsum("sec,ecd->sd", comb,
                       y_e.astype(jnp.float32)).astype(dt)
        return [y.reshape(shape)]

    def decode(self, params, xs, cache, pos, ctx):
        """Dropless single-step routing: at decode only B tokens route
        per step, so the training-time capacity cut (which zeroes
        overflow tokens) would silently corrupt generations — compute
        every token's CHOSEN expert exactly instead.  Matches forward
        bit-for-bit whenever forward's capacity drops nothing."""
        x = xs[0]
        shape = x.shape
        d = shape[-1]
        dt = x.dtype
        s = 1
        for dim in shape[:-1]:
            s *= dim
        xf = x.reshape(s, d)
        e = params["w_in"].shape[0]
        logits = jnp.dot(xf.astype(jnp.float32),
                         params["router"].astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)
        gate = jnp.max(gates, axis=-1)                     # (S,)
        onehot = jax.nn.one_hot(jnp.argmax(gates, axis=-1), e,
                                dtype=jnp.float32)         # (S, E)
        h = jnp.einsum("sd,edh->seh", xf.astype(dt), params["w_in"].astype(dt))
        h = h + params["b_in"].astype(h.dtype)[None, :, :]
        if self.activation == "relu":
            h = jax.nn.relu(h)
        elif self.activation == "gelu":
            h = jax.nn.gelu(h)
        y_e = jnp.einsum("seh,ehd->sed", h, params["w_out"].astype(dt))
        y_e = y_e + params["b_out"].astype(y_e.dtype)[None, :, :]
        y = jnp.einsum("se,sed->sd", onehot * gate[:, None],
                       y_e.astype(jnp.float32)).astype(dt)
        return [y.reshape(shape)], cache

    def _expert_constraint(self, a):
        """Pin the expert dim of (E, C, ...) intermediates to the ep mesh
        axes so GSPMD places per-expert compute on its shard (and emits
        the all_to_all at the dispatch/combine einsums)."""
        axes = self._ep_axes()
        if axes is None:
            return a
        from jax.sharding import NamedSharding, PartitionSpec

        spec = PartitionSpec(axes if len(axes) > 1 else axes[0],
                             *([None] * (a.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(self.model.machine.mesh, spec))

    def flops_per_sample(self):
        dims = self.output.dims
        d = dims[-1]
        tokens_per_sample = 1
        for dim in dims[1:-1]:
            tokens_per_sample *= dim
        h = self.hidden_size
        # router + one expert's in+out projections per token (capacity
        # overhead included)
        return tokens_per_sample * (
            2.0 * d * self.num_experts
            + self.capacity_factor * 4.0 * d * h)


# ---------------------------------------------------------------------------
# Routed experts as one chip of an expert-parallel deployment holds them
# ---------------------------------------------------------------------------

def _ceil_div(a, b):
    return -(-a // b)


def router_scores(x, router, scoring: str = "softmax"):
    """Every expert's affinity for every token: the softmax of ``x @
    router`` over the experts (or, with ``scoring="sigmoid"``, each
    logit's sigmoid: DeepSeek-V3, arXiv:2412.19437, 2.1.2), product and
    softmax in float32 at the highest matmul precision whatever ``x``'s
    dtype (an affinity decides which experts a token gets)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def route(scores, *, top_k: int, n_group: int, topk_group: int, first: int,
          held: int, budget: int, tile_rows: int, select_bias=None,
          norm_topk_prob: bool = False):
    """Group-limited top-k routing, this chip's part of it, under a device
    budget.  ``scores`` is (tokens, all experts), float32 affinities.

    1. Group-limited greedy (DeepSeek-V2, arXiv:2405.04434, 2.1.2): a
       group's score is its largest affinity, the ``topk_group`` best
       groups stay, and a token's ``top_k`` experts are the largest
       affinities among their experts.  With ``select_bias`` (all
       experts,) the choice is by ``scores + select_bias`` and the
       weights are the chosen experts' own ``scores`` (DeepSeek-V3's
       ``noaux_tc``; the bias takes no gradient); with
       ``norm_topk_prob`` a token's weights are divided by their sum
       over all its ``top_k`` choices, those on absent experts too.
    2. Of the (token, expert) assignments that land on the held experts
       ``first .. first + held``, the ``budget`` with the largest
       affinity are kept (ties: lower token, then lower expert); the rest
       are dropped (device-level token dropping, 2.2.4).  Where fewer
       were made, the remainder is padding.
    3. The kept rows are sorted by expert, then token, and each expert's
       group is padded to whole tiles of ``tile_rows`` rows, at least
       one: ``ceil(budget / tile_rows) + held`` tiles hold any division.

    Every shape follows from the arguments; nothing depends on how many
    assignments were made or how they divide among the experts.  Returns
    a dict: ``row_token`` (M,) the token each buffer row reads (``tokens``,
    past the last, for padding), ``row_weight`` (M,) its affinity (0 for
    padding; differentiable in ``scores``), ``slot_row`` (tokens, top_k)
    the buffer row of each of a token's choices (M, past the last, where
    it is not here or was dropped), ``tile_group`` (M / tile_rows,) the
    expert of each tile, and the counters ``made``, ``kept``, ``sizes``
    (held,)."""
    t, e = scores.shape
    if n_group > 1:
        group_score = scores.reshape(t, n_group, e // n_group).max(axis=-1)
        _, best = jax.lax.top_k(group_score, topk_group)
        in_best = jax.nn.one_hot(best, n_group, dtype=jnp.int32).sum(axis=1)
        scores = jnp.where(jnp.repeat(in_best > 0, e // n_group, axis=1),
                           scores, 0.0)
    if select_bias is None:
        vals, idx = jax.lax.top_k(scores, top_k)           # (t, top_k)
    else:
        _, idx = jax.lax.top_k(
            jax.lax.stop_gradient(scores + select_bias), top_k)
        vals = jnp.take_along_axis(scores, idx, axis=1)
    if norm_topk_prob:
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    local = idx - first
    here = (local >= 0) & (local < held)
    # top_k prefers the lower index among equals: within a token the
    # slots of equal affinity stand by expert, so a lower flat index is a
    # lower token, then a lower expert
    cand = jnp.where(here, vals, -1.0).reshape(-1)
    kept_s, flat = jax.lax.top_k(cand, budget)
    last_s, last_flat = jax.lax.stop_gradient(kept_s[-1]), flat[-1]
    valid = kept_s >= 0.0
    expert = jnp.where(valid, local.reshape(-1)[flat], held)
    token = flat // top_k
    order = jnp.argsort(expert * t + token)
    expert, token = expert[order], token[order]
    kept_s = jnp.where(valid, kept_s, 0.0)[order]

    sizes = jax.nn.one_hot(expert, held, dtype=jnp.int32).sum(axis=0)
    tiles = jnp.maximum(1, _ceil_div(sizes, tile_rows))
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    row_start = jnp.cumsum(sizes) - sizes
    num_tiles = _ceil_div(budget, tile_rows) + held
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(num_tiles), side="right"),
        held - 1).astype(jnp.int32)
    p = jnp.arange(num_tiles * tile_rows)
    g = tile_group[p // tile_rows]
    rank = p - tile_start[g] * tile_rows
    filled = rank < sizes[g]
    src = jnp.clip(row_start[g] + rank, 0, budget - 1)
    # The same layout read the other way, by token: a choice is kept if
    # it stands before the budget's last one in top_k's order, and its row
    # is its expert's first row plus the kept choices of that expert at
    # earlier tokens (the rows of a group stand by token).
    order_of = jax.lax.stop_gradient(cand)
    kept_slot = (here.reshape(-1) & (
        (order_of > last_s) | ((order_of == last_s)
                               & (jnp.arange(t * top_k) <= last_flat))
    )).reshape(t, top_k)
    by_expert = jnp.sum(jax.nn.one_hot(jnp.where(kept_slot, local, held),
                                       held, dtype=jnp.int32), axis=1)
    first_row = tile_start * tile_rows + jnp.cumsum(by_expert, axis=0) \
        - by_expert                                        # (t, held)
    slot_row = jnp.where(
        kept_slot, jnp.take_along_axis(first_row, jnp.clip(local, 0, held - 1),
                                       axis=1), num_tiles * tile_rows)
    return {"row_token": jnp.where(filled, token[src], t),
            "row_weight": jnp.where(filled, kept_s[src], 0.0),
            "slot_row": slot_row, "tile_group": tile_group,
            "made": jnp.sum(here), "kept": jnp.sum(valid), "sizes": sizes}


def _take_rows(src, idx):
    """src[idx] along axis 0, zeros where idx is past the last row."""
    return jnp.take(src, idx, axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def spread_rows(x, row_token, slot_row):
    """The buffer's rows from the tokens': row p is ``x[row_token[p]]``
    (zeros for padding).  Its transpose sums a token's rows back, which
    ``collect_rows`` does as a gather by ``slot_row``, where JAX's own
    transpose would scatter-add row by row."""
    return _take_rows(x, row_token)


@jax.custom_vjp
def collect_rows(rows, row_token, slot_row):
    """The tokens' sums of their buffer rows: token t gets the sum over
    its choices j of ``rows[slot_row[t, j]]`` (nothing for a choice that
    is not here), accumulated in float32.  Transpose: ``spread_rows``.
    A gather a choice, added up: on the v5e 1.6 ms at 8192 x 6 x 5120
    where one gather of all choices and a sum took 3.8 (PERF.md, PR 32)."""
    acc = jnp.zeros((slot_row.shape[0], rows.shape[1]), jnp.float32)
    for j in range(slot_row.shape[1]):
        acc = acc + _take_rows(rows, slot_row[:, j]).astype(jnp.float32)
    return acc.astype(rows.dtype)


spread_rows.defvjp(
    lambda x, row_token, slot_row: (spread_rows(x, row_token, slot_row),
                                    (row_token, slot_row)),
    lambda res, g: (collect_rows(g, *res), None, None))
collect_rows.defvjp(
    lambda rows, row_token, slot_row: (collect_rows(rows, row_token, slot_row),
                                       (row_token, slot_row)),
    lambda res, g: (spread_rows(g, *res), None, None))


class RoutedExperts(_ExpertDim, Op):
    """The routed-experts layer of a DeepSeek-class decoder as one chip of
    an expert-parallel deployment runs it: a router over all
    ``n_routed_experts`` (float32 softmax, group-limited top-k, affinities
    not renormalised and scaled by ``routed_scaling_factor``), the
    ``experts_held`` experts from ``first_expert`` on, each a SiLU-gated
    MLP of ``expert_width``, and ``n_shared_experts`` shared experts
    computed as one gated MLP of their summed width:

        y = scaling * sum_i s_i E_i(x) + Shared(x)

    over the assignments that land on the held experts and survive the
    device budget (``route``, ``budget``).  What the absent experts would add is left
    out: with fewer held than routed the output is this chip's partial
    sum.  The experts' products run as one grouped product over the
    budget's buffer (kernels/grouped_matmul.py), whatever its fill.

    Config dim 1 is the expert-parallel degree over the held experts, as
    ``ExpertMLP`` declares it; the output is placed by the batch degree.
    Counters (``ctx.counters``, summed into the step's metric vector):
    assignments made and kept, tokens, and the held experts' largest
    load over their mean load."""

    _type = "RoutedExperts"
    COUNTERS = ("moe_assignments_made", "moe_assignments_kept", "moe_tokens",
                "moe_load_max_over_mean", "moe_layers")

    def __init__(self, model, input_tensor, n_routed_experts: int,
                 num_experts_per_tok: int, expert_width: int,
                 experts_held: Optional[int] = None, first_expert: int = 0,
                 n_group: int = 1, topk_group: int = 1,
                 routed_scaling_factor: float = 1.0,
                 n_shared_experts: int = 0, capacity_factor: float = 1.0,
                 tile_rows: int = 128, scoring: str = "softmax",
                 select_bias: bool = False, norm_topk_prob: bool = False,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        from ..initializers import StackedGlorotUniform

        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"{self.name}: unknown scoring {scoring!r}")
        self.scoring = scoring
        self.select_bias = bool(select_bias)
        self.norm_topk_prob = bool(norm_topk_prob)

        dims = input_tensor.dims
        d = dims[-1]
        self.n_routed = int(n_routed_experts)
        self.top_k = int(num_experts_per_tok)
        self.hidden_size = int(expert_width)
        self.held = int(experts_held or n_routed_experts)
        self.first = int(first_expert)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.scaling = float(routed_scaling_factor)
        self.n_shared = int(n_shared_experts)
        self.capacity_factor = float(capacity_factor)
        self.tile_rows = int(tile_rows)
        # None: the Pallas kernels on a TPU, XLA elsewhere; a test names
        # "pallas_interpret" or "xla"
        self.impl: Optional[str] = None
        if self.n_routed % self.n_group or \
                self.first + self.held > self.n_routed:
            raise ValueError(f"{self.name}: {self.n_routed} experts in "
                             f"{self.n_group} groups, held {self.first}.."
                             f"{self.first + self.held}")
        w, init = self.hidden_size, StackedGlorotUniform()
        self._add_weight("router", (d, self.n_routed), init)
        for wname, shape in (("w_gate", (self.held, d, w)),
                             ("w_up", (self.held, d, w)),
                             ("w_down", (self.held, w, d))):
            self._add_weight(wname, shape, init,
                             partition_dims=(1, None, None))
        if self.n_shared:
            sw = self.n_shared * w
            self._add_weight("shared_gate", (d, sw), init)
            self._add_weight("shared_up", (d, sw), init)
            self._add_weight("shared_down", (sw, d), init)
        self._add_output(dims, input_tensor.dtype)

    num_experts = property(lambda self: self.held)

    def cost_key(self) -> str:
        return (f"e{self.held}of{self.n_routed}k{self.top_k}"
                f"w{self.hidden_size}s{self.n_shared}")

    def init_stats(self):
        """The selection bias, where the router has one: a buffer that
        no gradient touches (its balancing update is not implemented:
        the buffer stays as it starts, zeros)."""
        if not self.select_bias:
            return {}
        return {"select_bias": jnp.zeros((self.n_routed,), jnp.float32)}

    def budget(self, tokens: int) -> int:
        """Rows of the device's buffer: ``capacity_factor`` times the
        assignments an even router would send these experts (the paper
        trains at 1.0), and no more than a token's choices allow."""
        return min(tokens * min(self.top_k, self.held), math.ceil(
            tokens * self.top_k * self.held / self.n_routed
            * self.capacity_factor))

    def _grouped_impl(self) -> str:
        if self.impl is not None:
            return self.impl
        platform = self.model.machine.devices[0].platform
        return "pallas" if platform == "tpu" else "xla"

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        from ..kernels.grouped_matmul import grouped_matmul
        from .linear import gated_mlp

        x = xs[0]
        shape, dt = x.shape, x.dtype
        xf = x.reshape(-1, shape[-1])
        tokens = xf.shape[0]
        # a softmax router is called as it always was: the benchmark's
        # stand-in for a lower precision (logit_check.lower_router) takes
        # the two arguments
        how = {} if self.scoring == "softmax" else {"scoring": self.scoring}
        bias = ctx.stats_in[self.name]["select_bias"] \
            if self.select_bias else None
        with jax.named_scope("ff.moe.route"):
            r = route(router_scores(xf, params["router"], **how),
                      top_k=self.top_k, n_group=self.n_group,
                      topk_group=self.topk_group, first=self.first,
                      held=self.held, budget=self.budget(tokens),
                      tile_rows=self.tile_rows, select_bias=bias,
                      norm_topk_prob=self.norm_topk_prob)
        if ctx.counters is not None:
            sizes = r["sizes"].astype(jnp.float32)
            for name, value in zip(self.COUNTERS, (
                    r["made"], r["kept"], tokens,
                    jnp.max(sizes) / jnp.maximum(jnp.mean(sizes), 1e-9), 1)):
                ctx.counters[name] = ctx.counters.get(name, 0.0) \
                    + jnp.float32(value)
        product = functools.partial(grouped_matmul, tile_group=r["tile_group"],
                                    tile_m=self.tile_rows,
                                    impl=self._grouped_impl())
        with jax.named_scope("ff.moe.dispatch"):
            rows = spread_rows(xf, r["row_token"], r["slot_row"])
        with jax.named_scope("ff.moe.experts"):
            mid = jax.nn.silu(product(rows, params["w_gate"])) \
                * product(rows, params["w_up"])
            y_rows = product(mid, params["w_down"])
        with jax.named_scope("ff.moe.combine"):
            weight = (r["row_weight"] * self.scaling)[:, None]
            y = collect_rows((y_rows.astype(jnp.float32) * weight).astype(dt),
                             r["row_token"], r["slot_row"])
        if self.n_shared:
            with jax.named_scope("ff.moe.shared"):
                y = y + gated_mlp(xf, params["shared_gate"],
                                  params["shared_up"], params["shared_down"])
        return [y.reshape(shape)]

    def decode(self, params, xs, cache, pos, ctx):
        raise NotImplementedError(
            f"{self.name}: RoutedExperts has no decode path (a budget over "
            f"one step's tokens is not the training rule)")

    def flops_per_sample(self):
        dims = self.output.dims
        d, w = dims[-1], self.hidden_size
        tokens = 1
        for dim in dims[1:-1]:
            tokens *= dim
        routed = self.top_k * self.held / self.n_routed
        return tokens * (2.0 * d * self.n_routed
                         + (routed + self.n_shared) * 6.0 * d * w)
