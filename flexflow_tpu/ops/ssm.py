"""State-space and memory mixers of a hybrid decoder: Mamba's selective
state-space layer (Gu and Dao, arXiv:2312.00752) and SambaY's gated memory
unit (arXiv:2507.06607), which reads the gated scan output of one Mamba
layer in later layers.

Mamba, over (B, S, E), with ``d_inner`` channels, a state of ``d_state`` a
channel, a causal depthwise convolution of ``d_conv`` taps and ``dt_rank``
inputs to the step size:

    [u, z] = h W_in
    u = silu(conv_causal(u) + b_conv)                    depthwise, a channel
    [delta, B, C] = u W_x
    Delta = softplus(delta W_dt + b_dt),  A = -exp(A_log)
    s_t = exp(Delta_t A) s_{t-1} + (Delta_t u_t) B_t,  y_t = s_t C_t + D u_t
    g = y * silu(z),  out = g W_out

with a bias on the convolution and on ``W_dt`` alone.  ``memory=True``
gives ``g`` as a second output, the memory a gated memory unit reads:

    out = (m * silu(h W_1)) W_2

The scan is ``kernels/ssm_scan.py``: its Pallas kernels on a TPU where they
take the shape, XLA's chunked form elsewhere (``Mamba.impl_used`` says
which and why).  Neither op splits anything but the batch: a sequence
split would cut the scan (and the memory, which is a function of it), and
a width split would part the scan's channels from the convolution's.  The
degrees a strategy gives their other config dims are clamped to 1
(``Op.legalize_pc``, which compile and every search call), and the output
is placed by the batch degree alone.
"""

from __future__ import annotations

import math
import warnings
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from .base import FwdCtx, Op
from ..initializers import (ConstantInitializer, DefaultWeightInitializer,
                            Initializer, UniformInitializer)


class StateDecayInit(Initializer):
    """Mamba's ``A_log``: ``log(1 .. d_state)`` in every channel, so that
    the states decay at rates 1 .. d_state per unit of ``Delta``."""

    def __call__(self, key, shape, dtype=jnp.float32):
        rates = jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(rates), shape).astype(dtype)


class StepBiasInit(Initializer):
    """Mamba's ``b_dt``: the inverse softplus of a step drawn log-uniform
    in [dt_min, dt_max] a channel (floored at 1e-4), so that a seeded
    layer's ``Delta`` spreads as a trained one's does."""

    def __init__(self, dt_min: float = 1e-3, dt_max: float = 1e-1):
        self.lo, self.hi = math.log(dt_min), math.log(dt_max)

    def __call__(self, key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        self.lo, self.hi))
        dt = jnp.maximum(dt, 1e-4)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def causal_conv(u, w, b):
    """Depthwise causal convolution along axis 1 of u (B, S, D) with taps
    w (K, D) and bias b (D,), float32: out_t = b + sum_k w_k u_{t-K+1+k}."""
    k = w.shape[0]
    s = u.shape[1]
    up = jnp.pad(u.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    out = b.astype(jnp.float32)
    for j in range(k):
        out = out + w[j].astype(jnp.float32) * up[:, j:j + s]
    return out


def _batch_only_bound(op, i: int):
    """Config dims past the batch stay whole (module docstring)."""
    if i == 0:
        return Op._config_dim_bound(op, i)
    return 1


class Mamba(Op):
    """Mamba's mixer (module docstring).  Device scopes: ``ff.ssm.in_proj``,
    ``ff.ssm.conv``, ``ff.ssm.dt`` (``W_x``, ``W_dt``, the softplus and
    ``A``), ``ff.ssm.gate`` (``D u`` and the z gate) and
    ``ff.ssm.out_proj``; the scan outside them, its kernels under
    ``ff.kernel.ssm_scan_fwd`` / ``_bwd``.  Counts ``ssm_dt_mean``, the
    mean of ``Delta`` over its tokens and channels (the per-token decay of
    a state of rate a is ``exp(-Delta a)``; ``profiling.counters()`` gives
    the mean over such layers and drained steps)."""

    _type = "Mamba"
    COUNTERS = ("ssm_dt_mean", "ssm_layers")
    # None: the kernels on a TPU where they take the shape, else XLA's
    # chunked form.  A test sets "pallas_interpret", "pallas" or "xla".
    impl: Optional[str] = None
    impl_used: Optional[Tuple[str, str]] = None

    def __init__(self, model, input_tensor, d_inner: int, d_state: int,
                 d_conv: int, dt_rank: int, memory: bool = False,
                 kernel_initializer=None, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        b, s, e = input_tensor.dims
        self.d_inner, self.d_state = int(d_inner), int(d_state)
        self.d_conv, self.dt_rank = int(d_conv), int(dt_rank)
        self._add_output((b, s, e), input_tensor.dtype)
        if memory:
            self._add_output((b, s, self.d_inner), input_tensor.dtype)
        init = kernel_initializer or DefaultWeightInitializer()
        di, n, r = self.d_inner, self.d_state, self.dt_rank
        conv = 1.0 / math.sqrt(self.d_conv)
        self._add_weight("in_proj", (e, 2 * di), init)
        self._add_weight("conv_weight", (self.d_conv, di),
                         UniformInitializer(0, -conv, conv))
        self._add_weight("conv_bias", (di,),
                         UniformInitializer(0, -conv, conv))
        self._add_weight("x_proj", (di, r + 2 * n), init)
        self._add_weight("dt_proj", (r, di),
                         UniformInitializer(0, -r ** -0.5, r ** -0.5))
        self._add_weight("dt_bias", (di,), StepBiasInit())
        self._add_weight("a_log", (di, n), StateDecayInit())
        self._add_weight("d", (di,), ConstantInitializer(1.0))
        self._add_weight("out_proj", (di, e), init)

    _config_dim_bound = _batch_only_bound
    constraint_pc = Op.batch_only_pc

    def cost_key(self) -> str:
        return (f"ssm{self.d_inner}n{self.d_state}c{self.d_conv}"
                f"r{self.dt_rank}")

    def _pick_impl(self, seq: int) -> Tuple[str, str]:
        from ..kernels.ssm_scan import unsupported_reason

        if self.impl is not None:
            if self.impl not in ("pallas", "pallas_interpret", "xla"):
                raise ValueError(f"{self.name}: unknown scan impl "
                                 f"{self.impl!r}")
            return self.impl, "set on the op"
        platform = self.model.machine.devices[0].platform
        if platform != "tpu":
            return "xla", f"platform is {platform}"
        why = unsupported_reason(seq, self.d_inner)
        if why is not None:
            warnings.warn(f"{self.name}: {why}; the scan runs as XLA's "
                          f"chunked form")
            return "xla", why
        return "pallas", "platform is tpu"

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        from ..kernels import ssm_scan
        from .linear import project

        h = xs[0]
        b, s, _ = h.shape
        di, n, r = self.d_inner, self.d_state, self.dt_rank
        with jax.named_scope("ff.ssm.in_proj"):
            uz = project(h, params["in_proj"])
            u, z = uz[..., :di], uz[..., di:]
        with jax.named_scope("ff.ssm.conv"):
            u = jax.nn.silu(causal_conv(u, params["conv_weight"],
                                        params["conv_bias"])).astype(h.dtype)
        with jax.named_scope("ff.ssm.dt"):
            dbc = project(u, params["x_proj"])
            delta = jax.nn.softplus(
                project(dbc[..., :r], params["dt_proj"]).astype(jnp.float32)
                + params["dt_bias"]).astype(h.dtype)
            bs = dbc[..., r:r + n].astype(jnp.float32)
            cs = dbc[..., r + n:].astype(jnp.float32)
            a = -jnp.exp(params["a_log"])
        impl, why = self._pick_impl(s)
        self.impl_used = (impl, why)
        if impl == "xla":
            with jax.named_scope("ff.ssm.scan"):
                y = ssm_scan.selective_scan_chunked(u, delta, a, bs, cs)
        else:
            y = ssm_scan.selective_scan(u, delta, a, bs, cs,
                                        interpret=impl == "pallas_interpret")
        with jax.named_scope("ff.ssm.gate"):
            g = ((y.astype(jnp.float32)
                  + params["d"] * u.astype(jnp.float32))
                 * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)
        with jax.named_scope("ff.ssm.out_proj"):
            out = project(g, params["out_proj"])
        if ctx.counters is not None:
            mean = jnp.mean(jax.lax.stop_gradient(delta).astype(jnp.float32))
            for key, value in zip(self.COUNTERS, (mean, jnp.float32(1))):
                ctx.counters[key] = ctx.counters.get(key, 0.0) + value
        return [out, g][:len(self.outputs)]

    def flops_per_sample(self):
        """The projections and the convolution's multiply-adds."""
        _, s, e = self.output.dims
        di, n, r = self.d_inner, self.d_state, self.dt_rank
        return 2.0 * s * (e * 2 * di + di * (r + 2 * n) + r * di + di * e
                          + self.d_conv * di)

    def vector_cost_per_sample(self):
        """Element operations a sample of the scan's forward on the VPU
        and EUP, what the MXU's FLOPs leave out: a token, channel and
        state's exponential, its argument, the decay's product, the
        input's product and sum, and the output's product and sum."""
        return 7.0 * self.output.dims[1] * self.d_inner * self.d_state


class GatedMemoryUnit(Op):
    """SambaY's gated memory unit (arXiv:2507.06607): ``out = (m *
    silu(h W_1)) W_2`` over (B, S, E), ``m`` (B, S, d_inner) a Mamba
    layer's gated scan output; no bias.  Device scopes ``ff.gmu.in_proj``,
    ``ff.gmu.gate``, ``ff.gmu.out_proj``."""

    _type = "GatedMemoryUnit"

    def __init__(self, model, input_tensor, memory, kernel_initializer=None,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor, memory], name)
        b, s, e = input_tensor.dims
        if memory.dims[:2] != (b, s):
            raise ValueError(f"{self.name}: memory {memory.dims} for input "
                             f"{input_tensor.dims}")
        self.d_inner = memory.dims[-1]
        self._add_output((b, s, e), input_tensor.dtype)
        init = kernel_initializer or DefaultWeightInitializer()
        self._add_weight("in_proj", (e, self.d_inner), init)
        self._add_weight("out_proj", (self.d_inner, e), init)

    _config_dim_bound = _batch_only_bound
    constraint_pc = Op.batch_only_pc

    def cost_key(self) -> str:
        return f"gmu{self.d_inner}"

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        from .linear import project

        h, m = xs
        with jax.named_scope("ff.gmu.in_proj"):
            gate = project(h, params["in_proj"])
        with jax.named_scope("ff.gmu.gate"):
            gm = (m.astype(jnp.float32)
                  * jax.nn.silu(gate.astype(jnp.float32))).astype(h.dtype)
        with jax.named_scope("ff.gmu.out_proj"):
            return [project(gm, params["out_proj"])]

    def flops_per_sample(self):
        _, s, e = self.output.dims
        return 4.0 * s * e * self.d_inner
