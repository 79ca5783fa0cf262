"""Flat / Softmax / Concat / Dropout / RMSNorm / element-wise operators.

Reference files: src/ops/flat.cu (cross-rank partition copy),
src/ops/softmax.cu (cudnnSoftmaxForward ACCURATE), src/ops/concat.cu,
src/ops/dropout.cu (cudnnDropout with reserve space),
src/ops/element_unary.cu, src/ops/element_binary.cu, src/ops/mse_loss.cu.

All are single jnp expressions here — XLA fuses them into neighbouring
matmuls/convs, which is precisely why the reference's hand-written copy
and activation kernels have no TPU counterpart.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .base import FwdCtx, Op


class Flat(Op):
    """(B, H, W, C) → (B, H*W*C).  Reference: src/ops/flat.cu:96 uses a
    cross-dimensionality Legion partition; here it is a reshape, and the
    4D→2D partition transition (model.cc:571-606) is GSPMD resharding.
    Note the element order is HWC (NHWC-native), not the reference's CHW —
    a layout choice, not a semantic one."""

    _type = "Flat"

    def __init__(self, model, input_tensor, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        n = input_tensor.dims[0]
        flat = 1
        for d in input_tensor.dims[1:]:
            flat *= d
        self._add_output((n, flat), input_tensor.dtype)

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        return [xs[0].reshape(xs[0].shape[0], -1)]


class Softmax(Op):
    """Reference: src/ops/softmax.cu:166 (CUDNN_SOFTMAX_ACCURATE — i.e. the
    max-subtracted stable form, which is jax.nn.softmax).  When a CE loss
    follows, the executor feeds the loss from this op's *input* so the
    fused log-softmax path is used (see losses.py), and the metrics read
    that input too where they are functions of the logits
    (``FFModel._loss_head``).  As the graph's last op it then runs only
    where its output is used: in ``eval``/``predict`` and decoding, which
    return it, and in a train step whose metrics need the probabilities
    themselves (the error metrics).  Otherwise XLA drops it from the
    train step."""

    _type = "Softmax"

    def __init__(self, model, input_tensor, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        self._add_output(input_tensor.dims, input_tensor.dtype)

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        return [jax.nn.softmax(xs[0].astype(jnp.float32), axis=-1).astype(xs[0].dtype)]


class Concat(Op):
    """Reference: src/ops/concat.cu (custom copy kernels, variable #inputs,
    axis in NCHW order).  ``axis`` here is in native (NHWC) order — the
    model-builder converts reference-style channel axes."""

    _type = "Concat"

    def __init__(self, model, input_tensors, axis: int, name: Optional[str] = None):
        super().__init__(model, list(input_tensors), name)
        self.axis = axis
        base = list(input_tensors[0].dims)
        base[axis] = sum(t.dims[axis] for t in input_tensors)
        for t in input_tensors[1:]:
            for d in range(len(base)):
                if d != axis and t.dims[d] != base[d]:
                    raise ValueError(f"concat shape mismatch at dim {d}: {t.dims} vs {base}")
        self._add_output(tuple(base), input_tensors[0].dtype)

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        return [jnp.concatenate(xs, axis=self.axis)]

    def input_ranges(self, j, pc, part_idx):
        """Output tile ranges shifted by the input's offset along the
        concat axis, clipped to that input's extent."""
        tile = self.output_tile(pc, part_idx)
        off = sum(t.dims[self.axis] for t in self.inputs[:j])
        in_dims = self.inputs[j].dims
        rng = []
        for i, (lo, hi) in enumerate(tile):
            if i == self.axis:
                lo, hi = lo - off, hi - off
                lo, hi = max(0, lo), min(in_dims[i] - 1, hi)
            rng.append((lo, hi))
        return rng


class Dropout(Op):
    """Reference: src/ops/dropout.cu (cudnnDropout, seeded reserve space).
    Pure-functional: the mask derives from the per-step RNG folded with the
    op guid; identity when not training."""

    _type = "Dropout"

    def __init__(self, model, input_tensor, rate: float, seed: int = 0,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        self.rate = float(rate)
        self.seed = seed
        self._add_output(input_tensor.dims, input_tensor.dtype)

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        x = xs[0]
        if not ctx.training or self.rate <= 0.0:
            return [x]
        keep = 1.0 - self.rate
        rng = jax.random.fold_in(ctx.op_rng(self), self.seed)
        mask = jax.random.bernoulli(rng, p=keep, shape=x.shape)
        return [jnp.where(mask, x / keep, 0).astype(x.dtype)]


_UNARY = {
    "exp": jnp.exp,
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "elu": jax.nn.elu,
    "identity": lambda x: x,
}


class ElementUnary(Op):
    """Reference: src/ops/element_unary.cu (cudnnActivation or custom
    kernels; graph API FFModel::exp/relu/... element_unary.cu:19-50)."""

    _type = "ElementUnary"

    def __init__(self, model, input_tensor, op_name: str, name: Optional[str] = None):
        if op_name not in _UNARY:
            raise ValueError(f"unknown unary op {op_name}")
        super().__init__(model, [input_tensor], name)
        self.op_name = op_name
        self._add_output(input_tensor.dims, input_tensor.dtype)

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        return [_UNARY[self.op_name](xs[0])]


_BINARY = {
    "add": jnp.add,
    "subtract": jnp.subtract,
    "multiply": jnp.multiply,
    "divide": jnp.divide,
}


class ElementBinary(Op):
    """Reference: src/ops/element_binary.cu (add/sub/mul/div kernels,
    include/model.h:436-479)."""

    _type = "ElementBinary"

    def __init__(self, model, x, y, op_name: str, name: Optional[str] = None):
        if op_name not in _BINARY:
            raise ValueError(f"unknown binary op {op_name}")
        if x.dims != y.dims:
            raise ValueError(f"element binary shape mismatch: {x.dims} vs {y.dims}")
        super().__init__(model, [x, y], name)
        self.op_name = op_name
        self._add_output(x.dims, x.dtype)

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        return [_BINARY[self.op_name](xs[0], xs[1])]


class BatchNorm(Op):
    """Reference: src/ops/batch_norm.cu (cudnnBatchNorm spatial mode, scale
    and bias params, optional fused relu).  Batch statistics at train time;
    running moments kept as non-trainable stats for eval, updated with the
    reference cuDNN default momentum 0.1 semantics."""

    _type = "BatchNorm"
    MOMENTUM = 0.1
    EPS = 1e-5

    def __init__(self, model, input_tensor, relu: bool = True, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        self.relu = relu
        c = input_tensor.dims[-1]
        self._add_output(input_tensor.dims, input_tensor.dtype)
        from ..initializers import ConstantInitializer, ZeroInitializer

        cdim = len(input_tensor.dims) - 1
        self._add_weight("scale", (c,), ConstantInitializer(1.0), partition_dims=(cdim,))
        self._add_weight("bias", (c,), ZeroInitializer(), partition_dims=(cdim,))

    def init_stats(self):
        c = self.inputs[0].dims[-1]
        return {"mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        x = xs[0]
        axes = tuple(range(x.ndim - 1))
        xf = x.astype(jnp.float32)
        if ctx.training:
            mean = jnp.mean(xf, axis=axes)
            var = jnp.var(xf, axis=axes)
            if ctx.stats_out is not None:
                old = ctx.stats_in[self.name]
                m = BatchNorm.MOMENTUM
                ctx.stats_out[self.name] = {
                    "mean": (1 - m) * old["mean"] + m * mean,
                    "var": (1 - m) * old["var"] + m * var,
                }
        else:
            st = ctx.stats_in[self.name]
            mean, var = st["mean"], st["var"]
        inv = jax.lax.rsqrt(var + BatchNorm.EPS)
        y = (xf - mean) * inv * params["scale"] + params["bias"]
        y = y.astype(x.dtype)
        if self.relu:
            y = jax.nn.relu(y)
        return [y]


def rms_norm(x, scale, eps: float):
    """x / sqrt(mean(x^2) + eps) * scale over the last dim, computed in
    float32 and returned in x's dtype."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (xf * inv * scale.astype(jnp.float32)).astype(x.dtype)


class RMSNorm(Op):
    """Root-mean-square normalisation over the last dim with a learned
    scale and no shift (Zhang & Sennrich 2019): what the decoders after
    GPT-2 use where it has LayerNorm.  No reference counterpart."""

    _type = "RMSNorm"

    def __init__(self, model, input_tensor, eps: float = 1e-6,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        from ..initializers import ConstantInitializer

        self.eps = eps
        dims = input_tensor.dims
        self._add_output(dims, input_tensor.dtype)
        self._add_weight("scale", (dims[-1],), ConstantInitializer(1.0),
                         partition_dims=(len(dims) - 1,))

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        return [rms_norm(xs[0], params["scale"], self.eps)]

    def flops_per_sample(self):
        return 4.0 * float(np.prod(self.output.dims[1:]))


class MSELoss(Op):
    """Legacy MSE-loss op (reference: src/ops/mse_loss.cu — pre-``Loss``
    refactor path).  Produces the scalar mean-squared-error of its two
    inputs; kept for API parity."""

    _type = "MSELoss"

    def __init__(self, model, logit, label, reduction: str = "average",
                 name: Optional[str] = None):
        super().__init__(model, [logit, label], name)
        self.reduction = reduction
        self._add_output((1,), "float32")

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        diff = xs[0].astype(jnp.float32) - xs[1].astype(jnp.float32)
        sq = jnp.sum(diff * diff)
        if self.reduction == "average":
            sq = sq / xs[0].shape[0]
        return [sq.reshape(1)]
