"""Embedding operator.

Reference: src/ops/embedding.cu (custom gather/scatter kernels, SUM/AVG
aggregation, embedding.cu:173-220) + CPU task variants (embedding.cc:18-77)
that let DLRM keep huge tables in host zero-copy memory.

TPU-native: the forward is a ``jnp.take`` gather, which XLA lowers to a
dynamic-gather that runs on-chip.  The table's gradient takes one of two
forms, which one rule on what the op can see picks (``_table_grad_rule``;
``grad_impl_used`` records it, no flag selects it):

  * ``scatter_add``: the gather's transpose as autodiff gives it, XLA's
    scatter-add.
  * ``one_hot_product``: ``dW = onehot(ids)^T @ dY`` on the MXU, the
    backward of a ``custom_vjp`` (``_lookup_product_grad``).  A one-hot is
    exact in bf16 and the sums are float32 (``preferred_element_type``),
    so these are the scatter-add's numbers in another order of addition.
    XLA fuses the ``iota == ids`` compare into the product's operand
    (nothing tokens x rows wide is written) and the optimizer's update
    into its output, as for any dense weight gradient.

Why two.  XLA:TPU's scatter-add has a fast path and a slow one, and which
it takes follows from the width of a row and the number of rows added,
not from the table's rows.  On the slow path it costs 1.8 us a row *of
the table* however few rows are added (12800 rows of 5120: 23 ms for
2048, 4096 or 8192 rows added; PR 32's expert combine, into 8192 rows of
5120, took the same 15 ms a layer).  The product runs at 175-187 TFLOP/s
(some 92 % of the v5e's peak) at every shape.  Measured on a v5e (PERF.md
section 6, PR 33; device ms a call, float32 table, bf16 cotangent):

    table rows x width, rows added      scatter-add   product
    12800 x 5120,  8192  (DeepSeek-V2)      24.42       5.88   slow path
    12800 x 5120,  2048                     22.67       1.44   slow path
    51200 x 5120,  8192                     91.52      23.51   slow path
     2048 x 5120,  8192                      5.64       0.94   slow path
    12800 x 5120,  1024                      1.03       0.73
    12800 x 4096,  8192                      2.08       4.61
    32000 x 4096,  8192                      3.06      11.51
    12800 x 1024,  8192                      0.48       1.15
    50257 x 1024,  4096  (GPT-2 tok_embed)   1.00       2.25
     1024 x 1024,  4096  (GPT-2 pos_embed)   0.12       0.05

so the product where the cotangent is bf16, a row is wider than
``_FAST_SCATTER_WIDTH`` and at least ``_SLOW_SCATTER_TOKENS`` rows are
added; the scatter-add everywhere else, for a float32 cotangent (a bf16
one-hot product would round it) and under SUM / AVG.  A small table's
product wins by hundredths of a millisecond alone (the last line) and
cost GPT-2's step 0.7 ms when it replaced the scatter-add there (XLA
laid out the rest of the step otherwise): the rule leaves it alone.

Large tables shard their *embedding dim* along the output channel config
dim (riding ICI); the product contracts over the tokens, so under data
parallelism the partitioner adds the all-reduce it adds for any dense
weight gradient.  The reference's CPU placement maps to host-offload: a
config with ``device_type=CPU`` pins the table to host memory via
``jax.device_put`` with a host-memory-kind sharding (DLRM path); the
row-sparse host path hands ``forward`` a compacted table, whose gradient
either form gives.

Input is (B, num_indices) int32; aggregation SUM or AVG over the
``num_indices`` dim, exactly the reference semantics.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .base import FwdCtx, Op
from ..initializers import GlorotUniform


class AggrMode:
    NONE = "none"
    SUM = "sum"
    AVG = "avg"


# Where XLA:TPU's scatter-add leaves its fast path, as measured on a v5e
# (module docstring): rows wider than _FAST_SCATTER_WIDTH, at least
# _SLOW_SCATTER_TOKENS of them.  Move only with a chip measurement.
_FAST_SCATTER_WIDTH = 4096
_SLOW_SCATTER_TOKENS = 2048


def _table_grad_rule(tokens: int, width: int, dtype, aggr: str
                     ) -> Tuple[str, str]:
    """(form, why) of the table's gradient where ``tokens`` rows of
    ``width`` are looked up and leave the op as ``dtype``."""
    if aggr != AggrMode.NONE:
        return "scatter_add", f"aggregation {aggr} keeps autodiff's gradient"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "scatter_add", (f"a {jnp.dtype(dtype).name} cotangent: a bf16 "
                               "one-hot product would round it")
    if width <= _FAST_SCATTER_WIDTH or tokens < _SLOW_SCATTER_TOKENS:
        return "scatter_add", (
            f"{tokens} rows of {width}: XLA's scatter-add keeps its fast "
            f"path up to width {_FAST_SCATTER_WIDTH} and under "
            f"{_SLOW_SCATTER_TOKENS} rows")
    return "one_hot_product", (
        f"{tokens} bf16 rows of {width}: XLA's scatter-add would take "
        "its slow path, a pass a row of the table")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lookup_product_grad(table, idx, dtype):
    """``table[idx]`` as ``dtype``, the table's gradient a one-hot product.
    The cast is in here so that the backward sees the cotangent in
    ``dtype`` and not its float32 copy."""
    with jax.named_scope("ff.embed.lookup"):
        return jnp.take(table, idx, axis=0).astype(dtype)


def _lookup_fwd(table, idx, dtype):
    # of the table the backward reads the shape and dtype alone
    return _lookup_product_grad(table, idx, dtype), (table, idx)


def _lookup_bwd(dtype, res, g):
    table, idx = res
    rows = table.shape[0]
    with jax.named_scope("ff.embed.grad"):
        ids = idx.reshape(-1)
        ids = jnp.where(ids < 0, ids + rows, ids)  # as jnp.take wraps
        onehot = ids[:, None] == lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        dw = lax.dot_general(
            onehot.astype(g.dtype), g.reshape(ids.shape[0], -1),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return dw.astype(table.dtype), np.zeros(idx.shape, jax.dtypes.float0)


_lookup_product_grad.defvjp(_lookup_fwd, _lookup_bwd)


class Embedding(Op):
    _type = "Embedding"

    def __init__(self, model, input_tensor, num_entries: int, out_dim: int,
                 aggr: str = AggrMode.SUM, kernel_initializer=None,
                 share_with=None, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        self.num_entries = num_entries
        self.out_dim = out_dim
        self.aggr = aggr
        # which form the table's gradient takes, by what the op can see;
        # forward() reads it anew from what it is handed
        self.grad_impl_used = _table_grad_rule(
            math.prod(input_tensor.dims), out_dim, model.compute_dtype, aggr)
        batch = input_tensor.dims[0]
        if aggr == AggrMode.NONE:
            if len(input_tensor.dims) != 2 or input_tensor.dims[1] != 1:
                # keep the sequence dim
                self._add_output(input_tensor.dims + (out_dim,), "float32")
            else:
                self._add_output((batch, out_dim), "float32")
        else:
            self._add_output((batch, out_dim), "float32")
        if share_with is not None:
            share_with = share_with.share_from or share_with  # resolve chains
            if not isinstance(share_with, Embedding) or \
                    (share_with.num_entries, share_with.out_dim) != (num_entries, out_dim):
                raise ValueError("share_with must be an Embedding of identical shape")
            self.share_from = share_with
        else:
            self._add_weight("weight", (num_entries, out_dim),
                             kernel_initializer or GlorotUniform(),
                             partition_dims=(None, len(self.output.dims) - 1))

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        idx = xs[0].astype(jnp.int32)
        table = params["weight"]
        cdtype = self.model.compute_dtype
        self.grad_impl_used = _table_grad_rule(
            idx.size, table.shape[1], cdtype, self.aggr)
        # (B, I, D) or (B, D) when idx is (B,)
        if self.grad_impl_used[0] == "one_hot_product":
            emb = _lookup_product_grad(table, idx, cdtype)
        else:  # autodiff's scatter-add, under the lookup's scope
            with jax.named_scope("ff.embed.lookup"):
                emb = jnp.take(table, idx, axis=0)
        if self.aggr == AggrMode.SUM and emb.ndim == 3:
            emb = jnp.sum(emb, axis=1)
        elif self.aggr == AggrMode.AVG and emb.ndim == 3:
            emb = jnp.mean(emb, axis=1)
        elif self.aggr == AggrMode.NONE and emb.ndim == 3 and self.output.num_dims == 2:
            emb = emb[:, 0, :]
        return [emb.astype(cdtype)]

    def flops_per_sample(self):
        n_idx = self.inputs[0].dims[1] if len(self.inputs[0].dims) > 1 else 1
        return float(n_idx * self.out_dim)


class TiedHead(Op):
    """The output head of a model whose head is its token embedding's
    table, tied: ``logits = x E^T`` over (..., E), ``E`` (rows, E) the
    table of ``embedding`` (an ``Embedding`` with a table of its own).
    The op holds no weight: it reads the embedding's parameters
    (``share_from``), so the table's gradient is the sum of the lookup's
    and the head's.  The product is ``linear.project``'s, under the scope
    ``ff.tied_head`` (outside ``ff.embed.``, which means the lookup and its
    gradient).  Only the batch splits: the table is the embedding's, laid
    out as its strategy says."""

    _type = "TiedHead"

    def __init__(self, model, input_tensor, embedding: Embedding,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        owner = embedding.share_from or embedding
        if not isinstance(owner, Embedding) \
                or owner.out_dim != input_tensor.dims[-1]:
            raise ValueError(f"{self.name}: ties a head of width "
                             f"{input_tensor.dims[-1]} to {owner!r}")
        self.share_from = owner
        self.num_entries = owner.num_entries
        self._add_output(input_tensor.dims[:-1] + (owner.num_entries,),
                         input_tensor.dtype)

    def _config_dim_bound(self, i: int):
        return super()._config_dim_bound(i) if i == 0 else 1

    constraint_pc = Op.batch_only_pc

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        from .linear import project

        with jax.named_scope("ff.tied_head"):
            return [project(xs[0], params["weight"].T)]

    def flops_per_sample(self):
        tokens = math.prod(self.output.dims[1:-1])
        return 2.0 * tokens * self.inputs[0].dims[-1] * self.num_entries
