"""Linear (dense) operator — the tensor-parallel workhorse.

Reference: src/ops/linear.cu (864 LoC: 3 cuBLAS GEMMs + replica tensors).
The reference implements tensor parallelism by replicating the input per
out-channel shard and summing input-gradient replicas with a dedicated
``backward2`` launch (linear.cu:594-621,683-703; create_linear_replica
model.cc:791-846).

TPU-native: one ``jnp.dot`` with the weight sharded on its out-channel dim
along the same mesh axes as the output's channel dim.  XLA GSPMD derives
the forward all-gather/identity and the backward ``psum`` of the input
gradient automatically — the entire replica machinery reduces to a
sharding annotation.  MXU accumulation in float32 via
``preferred_element_type`` for bf16 activations.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from .base import FwdCtx, Op
from .conv2d import ActiMode, apply_activation
from ..initializers import DefaultBiasInitializer, DefaultWeightInitializer


class Linear(Op):
    _type = "Dense"

    def __init__(self, model, input_tensor, out_dim: int,
                 activation: str = ActiMode.NONE, use_bias: bool = True,
                 kernel_initializer=None, bias_initializer=None,
                 share_with=None, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        in_dim = input_tensor.dims[-1]
        lead = input_tensor.dims[:-1]
        self.activation = activation
        self.use_bias = use_bias
        self._add_output(lead + (out_dim,), input_tensor.dtype)
        out_cfg_dim = len(lead + (out_dim,)) - 1  # channel dim of the output
        if share_with is not None:
            # resolve chains: sharing with an already-shared op means
            # sharing with its owner
            sw = share_with.share_from or share_with
            if not isinstance(sw, Linear) or sw.use_bias != use_bias or \
                    sw.weights[0].dims != (in_dim, out_dim):
                raise ValueError("share_with must be a Dense of identical shape")
            self.share_from = sw
            return
        self._add_weight("kernel", (in_dim, out_dim),
                         kernel_initializer or DefaultWeightInitializer(),
                         partition_dims=(None, out_cfg_dim))
        if use_bias:
            self._add_weight("bias", (out_dim,),
                             bias_initializer or DefaultBiasInitializer(),
                             partition_dims=(out_cfg_dim,))

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        x = xs[0]
        kernel = params["kernel"].astype(x.dtype)
        y = jnp.dot(x, kernel,
                    preferred_element_type=jnp.float32 if x.dtype == jnp.bfloat16 else None)
        y = y.astype(x.dtype)
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        return [apply_activation(y, self.activation)]

    def flops_per_sample(self):
        in_dim = self.inputs[0].dims[-1]
        out_dim = self.output.dims[-1]
        return 2.0 * in_dim * out_dim

    def input_ranges(self, j, pc, part_idx):
        """Every out-channel shard reads the FULL input feature dim (the
        reference replicates the input per c-shard, linear.cu:174-185)."""
        rng = super().input_ranges(j, pc, part_idx)
        in_dims = self.inputs[0].dims
        rng[-1] = (0, in_dims[-1] - 1)
        return rng


def project(x, w):
    """x @ w with w cast to x's dtype; float32 accumulation for bfloat16
    operands, the result in x's dtype.  No bias."""
    acc = jnp.float32 if x.dtype == jnp.bfloat16 else None
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=acc).astype(x.dtype)


def gated_mlp(x, w_gate, w_up, w_down):
    """(silu(x W_gate) * (x W_up)) W_down: the SiLU-gated MLP of the
    decoders after GPT-2 (Shazeer 2020, "GLU Variants")."""
    return project(jax.nn.silu(project(x, w_gate)) * project(x, w_up), w_down)


class GatedMLP(Op):
    """A SiLU-gated MLP of width ``width`` with no bias: one op, so that
    a strategy splits the width of all three matrices together.  Config
    dim ``last`` is that split: ``w_gate`` and ``w_up`` shard their
    columns over it and ``w_down`` its rows, each shard's product is a
    partial sum of the output, and the output itself is placed by the
    batch degree alone (``constraint_pc``)."""

    _type = "GatedMLP"

    def __init__(self, model, input_tensor, width: int,
                 kernel_initializer=None, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        dims = input_tensor.dims
        d = dims[-1]
        self.width = int(width)
        self._add_output(dims, input_tensor.dtype)
        init = kernel_initializer or DefaultWeightInitializer()
        split = len(dims) - 1
        self._add_weight("w_gate", (d, self.width), init,
                         partition_dims=(None, split))
        self._add_weight("w_up", (d, self.width), init,
                         partition_dims=(None, split))
        self._add_weight("w_down", (self.width, d), init,
                         partition_dims=(split, None))

    def _config_dim_bound(self, i: int):
        if i == self.output.num_dims - 1:
            return self.width
        return super()._config_dim_bound(i)

    constraint_pc = Op.batch_only_pc

    def cost_key(self) -> str:
        return f"w{self.width}"

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        return [gated_mlp(xs[0], params["w_gate"], params["w_up"],
                          params["w_down"])]

    def flops_per_sample(self):
        tokens = 1
        for dim in self.output.dims[1:-1]:
            tokens *= dim
        return 6.0 * tokens * self.output.dims[-1] * self.width

    def input_ranges(self, j, pc, part_idx):
        """A width shard reads the whole input feature dim."""
        rng = super().input_ranges(j, pc, part_idx)
        rng[-1] = (0, self.inputs[0].dims[-1] - 1)
        return rng
