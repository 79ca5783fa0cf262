"""Conv2D / Pool2D operators (NHWC, MXU-native).

Reference: src/ops/conv_2d.cu (1040 LoC of cuDNN host/launcher code) and
src/ops/pool_2d.cu.  Shape formula matches conv_2d.cu:100-101:
``out = 1 + (in + 2*pad - kernel) / stride``.

TPU-native design notes:
  * activations are NHWC so channels ride the 128-lane dim; kernels are
    HWIO — the layouts XLA:TPU tiles directly onto the MXU without
    relayout.
  * convolution lowers to a single ``lax.conv_general_dilated``; bias and
    activation fuse into it at the XLA level (no separate kernels as in
    the cuDNN path).
  * an image stem is computed space-to-depth (``Conv2D.impl_used``).  A
    strided convolution over three channels gives the MXU a contraction
    three deep per tap in the forward pass and an output three rows high
    per tap in the weight gradient, and the stride keeps XLA from folding
    neighbouring taps together cheaply.  Moving each stride x stride block
    of pixels into the channels makes it, exactly, a stride-1 convolution
    of ``ceil(k / s)`` taps a side over ``s * s * cin`` channels: AlexNet's
    11x11 stride 4 over 3 becomes a 3x3 over 48, the same products and
    sums plus 19 % multiplications by the zeros that pad the kernel to
    12x12.  The rule is the shape's (``_space_to_depth_rule``): one group,
    a square stride above 1, a kernel larger than it, at most 4 input
    channels.  The stored kernel stays ``(kh, kw, cin, cout)``; it is cut
    the same way inside ``forward`` (23 k elements), so its gradient flows
    back through the reshape and is the small convolution's.  The image
    is rearranged by a strided convolution with a one-hot kernel: one
    pass that takes the padding with it (``Conv2D._space_to_depth``).
  * float32 accumulation is requested via ``preferred_element_type`` when
    activations are bfloat16.
  * spatial (H/W) partitioning — the reference's "attribute" parallelism
    with implicit Legion halo copies (conv_2d.cu:173-211) — is expressed by
    sharding H/W mesh axes; XLA GSPMD emits the halo-exchange
    collective-permutes over ICI.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .base import FwdCtx, Op
from ..initializers import DefaultBiasInitializer, DefaultWeightInitializer


class ActiMode:
    NONE = "none"
    RELU = "relu"
    SIGMOID = "sigmoid"
    TANH = "tanh"
    GELU = "gelu"


def apply_activation(x, activation: Optional[str]):
    if not activation or activation == ActiMode.NONE:
        return x
    if activation == ActiMode.RELU:
        return jax.nn.relu(x)
    if activation == ActiMode.SIGMOID:
        return jax.nn.sigmoid(x)
    if activation == ActiMode.TANH:
        return jnp.tanh(x)
    if activation == ActiMode.GELU:
        return jax.nn.gelu(x)
    raise ValueError(f"unknown activation {activation}")


# An image stem: fewer input channels than one sublane tile holds, let
# alone 128 lanes.  Widen only with a chip measurement (PERF.md).
_STEM_CHANNELS = 4


def _space_to_depth_rule(kernel, stride, cin, groups) -> Tuple[str, str]:
    """(form, why) for a convolution of this shape: ``space_to_depth``
    where the direct form starves the MXU, else ``direct``."""
    (kh, kw), (sh, sw) = kernel, stride
    if groups != 1:
        return "direct", f"{groups} groups"
    if sh != sw or sh == 1:
        return "direct", f"stride {sh}x{sw} is not a square stride above 1"
    if min(kh, kw) <= sh:
        return "direct", f"kernel {kh}x{kw} is no larger than stride {sh}"
    if cin > _STEM_CHANNELS:
        return "direct", f"{cin} input channels fill the MXU's contraction"
    return "space_to_depth", (
        f"{kh}x{kw} stride {sh} over {cin} channels as "
        f"{-(-kh // sh)}x{-(-kw // sh)} stride 1 over {sh * sh * cin}")


class Conv2D(Op):
    _type = "Conv2D"

    def __init__(self, model, input_tensor, out_channels: int,
                 kernel_h: int, kernel_w: int, stride_h: int, stride_w: int,
                 padding_h: int, padding_w: int, activation: str = ActiMode.NONE,
                 use_bias: bool = True, groups: int = 1,
                 kernel_initializer=None, bias_initializer=None,
                 share_with=None, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        n, h, w, cin = input_tensor.dims
        self.kernel = (kernel_h, kernel_w)
        self.stride = (stride_h, stride_w)
        self.padding = (padding_h, padding_w)
        self.activation = activation
        self.use_bias = use_bias
        self.groups = groups
        # which form forward() computes, decided by the shape alone
        self.impl_used = _space_to_depth_rule(
            self.kernel, self.stride, cin, groups)
        out_h = 1 + (h + 2 * padding_h - kernel_h) // stride_h
        out_w = 1 + (w + 2 * padding_w - kernel_w) // stride_w
        self._add_output((n, out_h, out_w, out_channels), input_tensor.dtype)
        if share_with is not None:
            sw = share_with.share_from or share_with  # resolve chains
            kshape = (kernel_h, kernel_w, cin // groups, out_channels)
            if not isinstance(sw, Conv2D) or sw.use_bias != use_bias or \
                    sw.weights[0].dims != kshape:
                raise ValueError("share_with must be a Conv2D of identical shape")
            self.share_from = sw
            return
        # Kernel replicated across sample/spatial parts (the reference
        # replicates it and aggregates grad replicas, model.cc:763-787;
        # here GSPMD psums the gradient); out-channel dim shards with the
        # output channel config dim (index 3, NHWC).
        self._add_weight(
            "kernel", (kernel_h, kernel_w, cin // groups, out_channels),
            kernel_initializer or DefaultWeightInitializer(),
            partition_dims=(None, None, None, 3))
        if use_bias:
            self._add_weight("bias", (out_channels,),
                             bias_initializer or DefaultBiasInitializer(),
                             partition_dims=(3,))

    def _space_to_depth(self, x, kernel):
        """``x`` and ``kernel`` with each stride x stride block of pixels
        moved into the channels, ``(bh, bw, cin)`` minor: the stride-1
        ``VALID`` convolution of the two is the strided one.

        ``x`` is rearranged by a strided convolution with a one-hot
        kernel, which is exact and takes the padding with it.  As pad,
        reshape and transpose XLA:TPU, which keeps the batch on the
        lanes here, runs three passes over the image that cost more
        than the rearranged convolution saves (PERF.md, PR 31)."""
        s = self.stride[0]
        cin, cout = kernel.shape[2:]
        depth = s * s * cin
        _, oh, ow, _ = self.output.dims
        taps, x_pad, k_pad = [], [], []
        for size, k, p, out in zip(x.shape[1:3], self.kernel, self.padding,
                                   (oh, ow)):
            kb = -(-k // s)
            taps.append(kb)
            # negative where the trailing rows reach no window: cropped
            x_pad.append((p, (out - 1 + kb) * s - size - p))
            k_pad.append((0, kb * s - k, 0))
        one_hot = jnp.eye(depth, dtype=x.dtype).reshape(s, s, cin, depth)
        x = lax.conv_general_dilated(
            x, one_hot, window_strides=(s, s), padding=x_pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        th, tw = taps
        kernel = lax.pad(kernel, jnp.zeros((), kernel.dtype),
                         k_pad + [(0, 0, 0), (0, 0, 0)])
        kernel = kernel.reshape(th, s, tw, s, cin, cout)
        kernel = kernel.transpose(0, 2, 1, 3, 4, 5)
        return x, kernel.reshape(th, tw, depth, cout)

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        x = xs[0]
        kernel = params["kernel"].astype(x.dtype)
        ph, pw = self.padding
        stride, padding = self.stride, ((ph, ph), (pw, pw))
        if self.impl_used[0] == "space_to_depth":
            x, kernel = self._space_to_depth(x, kernel)
            stride, padding = (1, 1), "VALID"
        # No explicit f32 upcast: the MXU accumulates bf16 convs in f32
        # internally, and a preferred_element_type≠input dtype breaks the
        # conv transpose (wgrad) rule under jax.grad.
        y = lax.conv_general_dilated(
            x, kernel,
            window_strides=stride,
            padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=self.groups,
        )
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        return [apply_activation(y, self.activation)]

    def flops_per_sample(self):
        _, oh, ow, oc = self.output.dims
        kh, kw = self.kernel
        cin = self.inputs[0].dims[3]
        return 2.0 * oh * ow * oc * kh * kw * (cin // self.groups)

    def input_ranges(self, j, pc, part_idx):
        """Exact conv input rectangle incl. halo for an output tile
        (the reference's implicit Legion halo, conv_2d.cu:173-211)."""
        n, ih, iw, cin = self.inputs[0].dims
        (n_lo, n_hi), (oh_lo, oh_hi), (ow_lo, ow_hi), _ = \
            self.output_tile(pc, part_idx)
        sh, sw = self.stride
        ph, pw = self.padding
        kh, kw = self.kernel
        h_lo = max(0, oh_lo * sh - ph)
        h_hi = min(ih - 1, oh_hi * sh - ph + kh - 1)
        w_lo = max(0, ow_lo * sw - pw)
        w_hi = min(iw - 1, ow_hi * sw - pw + kw - 1)
        return [(n_lo, n_hi), (h_lo, h_hi), (w_lo, w_hi), (0, cin - 1)]


class PoolType:
    MAX = "max"
    AVG = "avg"


class Pool2D(Op):
    _type = "Pool2D"

    def __init__(self, model, input_tensor, kernel_h: int, kernel_w: int,
                 stride_h: int, stride_w: int, padding_h: int, padding_w: int,
                 pool_type: str = PoolType.MAX, activation: str = ActiMode.NONE,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        n, h, w, c = input_tensor.dims
        self.kernel = (kernel_h, kernel_w)
        self.stride = (stride_h, stride_w)
        self.padding = (padding_h, padding_w)
        self.pool_type = pool_type
        self.activation = activation
        out_h = 1 + (h + 2 * padding_h - kernel_h) // stride_h
        out_w = 1 + (w + 2 * padding_w - kernel_w) // stride_w
        self._add_output((n, out_h, out_w, c), input_tensor.dtype)

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        x = xs[0]
        kh, kw = self.kernel
        sh, sw = self.stride
        ph, pw = self.padding
        dims = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        pads = ((0, 0), (ph, ph), (pw, pw), (0, 0))
        if self.pool_type == PoolType.MAX:
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
            y = lax.reduce_window(x, init, lax.max, dims, strides, pads)
        else:
            # Average with padding excluded from the divisor, matching
            # cuDNN's CUDNN_POOLING_AVERAGE_COUNT_EXCLUDE_PADDING used by
            # the reference pool op.
            s = lax.reduce_window(x.astype(jnp.float32), 0.0, lax.add, dims, strides, pads)
            ones = jnp.ones(x.shape[1:3], jnp.float32)[None, :, :, None]
            cnt = lax.reduce_window(ones, 0.0, lax.add, (1, kh, kw, 1), strides,
                                    ((0, 0), (ph, ph), (pw, pw), (0, 0)))
            y = (s / cnt).astype(x.dtype)
        return [apply_activation(y, self.activation)]

    def flops_per_sample(self):
        _, oh, ow, c = self.output.dims
        return float(oh * ow * c * self.kernel[0] * self.kernel[1])
