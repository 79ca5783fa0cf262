"""Mamba's selective scan (Gu and Dao, arXiv:2312.00752, algorithm 2) as
Pallas TPU kernels, forward and backward, with a custom VJP:

    s_t = exp(delta_t * A) * s_{t-1} + (delta_t * x_t) B_t,   s_{-1} = 0
    y_t = s_t C_t

for x and delta (batch, T, D), A (D, N), B and C (batch, T, N): a state of
N a channel, (D, N) a sequence, that decays by a rate of its own a channel
and a state and takes in B_t and gives out through C_t.  The skip ``D * x``
and the gate are the caller's (ops/ssm.py).

Memory.  The states of every token, (T, D, N), are never written: at 8192
tokens, 5120 channels and 16 states that is 2.7 GB of float32 a layer.  A
kernel holds a block of channels' state in registers and walks the tokens
of a chunk one at a time; the forward writes ``y`` and the state at each
chunk's start, (T / chunk, N, D) float32, and the backward walks the
chunks last first, recomputes a chunk's states from its start into VMEM
and then runs the adjoint recurrence back through it.

Layout.  A token's channels are laid out (8, D / 8), channel ``r * D / 8
+ l`` at sublane ``r`` and lane ``l`` (a reshape of (T, D)), so that a
token's x, delta and y are whole vregs and a state is N of them: every
operation of the recurrence is elementwise between full vregs.  B and C,
one number a token and a state, come in SMEM, (T * 2N,) a chunk at a
time, and multiply a vreg as scalars.  Two kernels, each a ``pallas_call``
named as its scope: ``ssm_scan_fwd`` and ``ssm_scan_bwd``, on one grid,
(batch, lane blocks, chunks) with the chunks innermost, so that the state,
and backward its adjoint and dA, stay in VMEM across a block's chunks.

The backward's sums over channels, dB_t = sum_d g_t B-part and dC_t =
sum_d s_t dy_t, are products a token, a state and a vreg that go to VMEM
scratch.  After a chunk, loads strided over the sublanes sum each product
over its 8 sublanes into one row of lanes (a load strided by 4 or 2
sublanes is one load; by 8 it is two and a merge), and one product of
those rows with a row of ones on the MXU, at ``Precision.HIGHEST``, sums
each over its lanes into one lane-dense row, which the caller sums over
the lane blocks.  The MXU takes those rows as its weights, six bfloat16
passes over them, one row a token and product: 4096 a chunk at a state
of 16 (``tiling()``'s ``lane_sum_rows``).

Arithmetic in float32 throughout; x, delta, y, dy and their gradients in
the caller's dtype in HBM.  Work: one exponential a token, channel and
state forward, two backward (the recomputed chunk's and the adjoint's),
and the products and sums of the recurrence, on the VPU and EUP; nothing
here uses the MXU but the lane sums.

The kernels compile through Mosaic and run on a TPU only;
``interpret=True`` runs the same bodies in the Pallas interpreter, for
tests.  ``selective_scan_chunked`` is XLA's form for other platforms (a
scan over chunks whose body autodiff recomputes, so that it too keeps a
state a chunk), ``selective_scan_reference`` the plain sequential scan.
``unsupported_reason`` is the rule a caller picks the path by and
``tiling()`` what the kernels do with a shape.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNELS = ("ssm_scan_fwd", "ssm_scan_bwd")
SUBLANES = 8
# Lanes of a channel block (8 x 128 = 1024 channels): a state of 16 is 16
# vregs, held in registers across the tokens.
_LANES = 128
# Tokens a chunk: the backward holds a chunk's states and its dB / dC
# products in VMEM, (chunk + 1) x N and chunk x 2N vregs of a block.
_CHUNK = 128
# Tokens a step of the loops over a chunk's tokens (Mosaic unrolls a
# loop wholly or not at all, so these are a Python loop in its body).
_UNROLL = 8
_VMEM_LIMIT = 100 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))   # a @ b^T


def _lanes(width: int) -> int:
    """Lanes of a block of a (8, width) token: 128, or all of a narrower
    or unaligned width (for the interpreter)."""
    return _LANES if width % _LANES == 0 else width


def unsupported_reason(seq: int, d_inner: int) -> Optional[str]:
    """Why the compiled kernels cannot take this shape, or None."""
    if d_inner % (SUBLANES * _LANES):
        return (f"{d_inner} channels: the kernels lay a token out (8, "
                f"channels / 8) in blocks of {_LANES} lanes")
    if seq % _CHUNK:
        return f"{seq} tokens are not whole chunks of {_CHUNK}"
    return None


def tiling(seq: int, d_inner: int, d_state: int) -> Dict[str, int]:
    """What the kernels do with one sequence of this shape: the lanes and
    channels of a block, the tokens of a chunk, the grid's steps, the
    chunk-start states the forward writes for the backward (bytes), the
    VMEM the backward's scratch takes, and the rows of lanes a chunk of
    the backward pushes through the MXU for the lane sums of dB / dC."""
    width = d_inner // SUBLANES
    lanes = _lanes(width)
    vreg = SUBLANES * lanes * 4
    chunk = _CHUNK
    products = chunk * 2 * d_state
    return dict(
        chunk=chunk, lanes=lanes, channels_per_block=SUBLANES * lanes,
        grid_steps=(width // lanes) * (seq // chunk),
        saved_state_bytes=(seq // chunk) * d_state * d_inner * 4,
        bwd_scratch_bytes=vreg * ((chunk + 1) * d_state + products
                                  + products // 4 + d_state),
        lane_sum_rows=products)


# ---------------------------------------------------------------------------
# The plain scan and XLA's chunked form
# ---------------------------------------------------------------------------

def _step(a, s, inp):
    """One token of the recurrence over a (batch, D, N) state."""
    x, dt, b, c = inp
    s = jnp.exp(dt[..., None] * a) * s + (dt * x)[..., None] * b[:, None, :]
    return s, jnp.einsum("bdn,bn->bd", s, c)


def _time_first(*arrays):
    return tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in arrays)


def selective_scan_reference(x, delta, a, b, c):
    """The recurrence one token at a time (``lax.scan``), float32; y in
    x's dtype."""
    bsz, _, d = x.shape
    s0 = jnp.zeros((bsz, d, a.shape[1]), jnp.float32)
    _, y = lax.scan(functools.partial(_step, a.astype(jnp.float32)), s0,
                    _time_first(x, delta, b, c))
    return jnp.moveaxis(y, 0, 1).astype(x.dtype)


def selective_scan_chunked(x, delta, a, b, c, chunk: int = _CHUNK):
    """XLA's form: a scan over chunks of a scan over tokens, the chunk's
    body under ``jax.checkpoint``, so that autodiff keeps the state at
    each chunk's start and recomputes a chunk's states backward."""
    bsz, t, d = x.shape
    chunk = chunk if t % chunk == 0 else t
    af = a.astype(jnp.float32)

    @jax.checkpoint
    def body(s, inp):
        return lax.scan(functools.partial(_step, af), s, inp)

    def chunks(v):
        return v.reshape((t // chunk, chunk) + v.shape[1:])
    s0 = jnp.zeros((bsz, d, a.shape[1]), jnp.float32)
    _, y = lax.scan(body, s0, tuple(chunks(v) for v in
                                    _time_first(x, delta, b, c)))
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _loop(chunk, body, carry):
    """``body(t, carry)`` for t in 0 .. chunk-1, ``_UNROLL`` tokens a step
    of a ``fori_loop``."""
    def group(g, carry):
        for k in range(_UNROLL):
            carry = body(g * _UNROLL + k, carry)
        return carry
    return lax.fori_loop(0, chunk // _UNROLL, group, carry)


def _fwd_kernel(x_ref, dt_ref, a_ref, bc_ref, y_ref, h_ref, s_scr, *,
                chunk, n_state):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_scr[...] = jnp.zeros_like(s_scr)

    h_ref[...] = s_scr[...]

    def step(t, s):
        dt = dt_ref[t].astype(jnp.float32)
        dx = dt * x_ref[t].astype(jnp.float32)
        base = t * 2 * n_state
        y = jnp.zeros_like(dt)
        out = []
        for n in range(n_state):
            sn = jnp.exp(dt * a_ref[n]) * s[n] + dx * bc_ref[base + n]
            y = y + sn * bc_ref[base + n_state + n]
            out.append(sn)
        y_ref[t] = y.astype(y_ref.dtype)
        return tuple(out)

    s = _loop(chunk, step, tuple(s_scr[n] for n in range(n_state)))
    for n in range(n_state):
        s_scr[n] = s[n]


def _bwd_kernel(x_ref, dt_ref, a_ref, bc_ref, dy_ref, h_ref,
                dx_ref, ddt_ref, da_ref, dbc_ref, s_scr, p_scr, h_scr, ds_scr,
                *, chunk, n_state):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    s_scr[0] = h_ref[...]

    def forward(t, s):
        dt = dt_ref[t].astype(jnp.float32)
        dx = dt * x_ref[t].astype(jnp.float32)
        base = t * 2 * n_state
        out = []
        for n in range(n_state):
            sn = jnp.exp(dt * a_ref[n]) * s[n] + dx * bc_ref[base + n]
            s_scr[t + 1, n] = sn
            out.append(sn)
        return tuple(out)

    _loop(chunk, forward, tuple(h_ref[n] for n in range(n_state)))

    def backward(i, ds):
        t = chunk - 1 - i
        dt = dt_ref[t].astype(jnp.float32)
        x = x_ref[t].astype(jnp.float32)
        dy = dy_ref[t].astype(jnp.float32)
        dx = dt * x
        base = t * 2 * n_state
        ddx = jnp.zeros_like(dt)
        ddt = jnp.zeros_like(dt)
        out = []
        for n in range(n_state):
            a = a_ref[n]
            g = ds[n] + dy * bc_ref[base + n_state + n]     # dL / ds_t
            p_scr[t, pl.ds(n * SUBLANES, SUBLANES)] = g * dx  # -> dB_t
            p_scr[t, pl.ds((n_state + n) * SUBLANES, SUBLANES)] = (
                s_scr[t + 1, n] * dy)                           # -> dC_t
            ddx = ddx + g * bc_ref[base + n]
            decay = jnp.exp(dt * a)
            w = g * s_scr[t, n] * decay                     # dL / d(dt a)
            ddt = ddt + w * a
            da_ref[n] += w * dt
            out.append(g * decay)
        dx_ref[t] = (ddx * dt).astype(dx_ref.dtype)
        ddt_ref[t] = (ddt + ddx * x).astype(ddt_ref.dtype)
        return tuple(out)

    ds = _loop(chunk, backward, tuple(ds_scr[n] for n in range(n_state)))
    for n in range(n_state):
        ds_scr[n] = ds[n]
    # A token's products are rows 8k .. 8k+7 of p_scr[t].  Loads strided
    # by 4 at offsets 0-3 sum each product's two halves of 4 sublanes into
    # rows 2k and 2k+1 of h_scr[t]; loads strided by 2 add the halves.
    per = 2 * n_state
    parts = [p_scr[:, pl.ds(r, 2 * per, stride=4)] for r in range(4)]
    h_scr[...] = (parts[0] + parts[1]) + (parts[2] + parts[3])
    rows = (h_scr[:, pl.ds(0, per, stride=2)]
            + h_scr[:, pl.ds(1, per, stride=2)])
    sums = lax.dot_general(jnp.ones((SUBLANES, rows.shape[-1]), jnp.float32),
                           rows.reshape(chunk * per, -1), _NT,
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    dbc_ref[...] = sums[0:1]


def _specs(chunk, lanes, n_state, reverse=False, nc=None):
    """BlockSpecs of a token-blocked (batch, T, 8, width) array, of A's
    (N, 8, width) and of the (batch, T * 2N) SMEM row of B and C; the
    backward visits chunks last first."""
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    seq = pl.BlockSpec((None, chunk, SUBLANES, lanes),
                       lambda i, j, c: (i, at(c), 0, j))
    a = pl.BlockSpec((n_state, SUBLANES, lanes), lambda i, j, c: (0, 0, j))
    bc = pl.BlockSpec((None, chunk * 2 * n_state),
                      lambda i, j, c: (i, at(c)),
                      memory_space=pltpu.SMEM)
    state = pl.BlockSpec((None, None, n_state, SUBLANES, lanes),
                         lambda i, j, c: (i, at(c), 0, 0, j))
    return seq, a, bc, state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _fwd_call(x4, dt4, a3, bc, chunk, interpret):
    bsz, t, _, width = x4.shape
    n_state = a3.shape[0]
    lanes = _lanes(width)
    seq, a, bcs, state = _specs(chunk, lanes, n_state)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, n_state=n_state),
        grid=(bsz, width // lanes, t // chunk),
        in_specs=[seq, seq, a, bcs],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct(x4.shape, x4.dtype),
                   jax.ShapeDtypeStruct((bsz, t // chunk, n_state, SUBLANES,
                                         width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n_state, SUBLANES, lanes),
                                   jnp.float32)],
        interpret=interpret,
        name=KERNELS[0],
        compiler_params=_PARAMS,
    )(x4, dt4, a3, bc)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _bwd_call(x4, dt4, a3, bc, dy4, h, chunk, interpret):
    bsz, t, _, width = x4.shape
    n_state = a3.shape[0]
    lanes = _lanes(width)
    nc = t // chunk
    seq, a, bcs, state = _specs(chunk, lanes, n_state, reverse=True, nc=nc)
    row = chunk * 2 * n_state
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, n_state=n_state),
        grid=(bsz, width // lanes, nc),
        in_specs=[seq, seq, a, bcs, seq, state],
        out_specs=[seq, seq,
                   pl.BlockSpec((None, n_state, SUBLANES, lanes),
                                lambda i, j, c: (i, 0, 0, j)),
                   pl.BlockSpec((None, None, 1, row),
                                lambda i, j, c: (i, j, 0, nc - 1 - c))],
        out_shape=[jax.ShapeDtypeStruct(x4.shape, x4.dtype),
                   jax.ShapeDtypeStruct(dt4.shape, dt4.dtype),
                   jax.ShapeDtypeStruct((bsz, n_state, SUBLANES, width),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((bsz, width // lanes, 1, t * row
                                         // chunk), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((chunk + 1, n_state, SUBLANES, lanes), jnp.float32),
            pltpu.VMEM((chunk, 2 * n_state * SUBLANES, lanes), jnp.float32),
            pltpu.VMEM((chunk, 4 * n_state, lanes), jnp.float32),
            pltpu.VMEM((n_state, SUBLANES, lanes), jnp.float32)],
        interpret=interpret,
        name=KERNELS[1],
        compiler_params=_PARAMS,
    )(x4, dt4, a3, bc, dy4, h)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _tokens(v):
    """(batch, T, D) -> (batch, T, 8, D / 8)."""
    return v.reshape(v.shape[:2] + (SUBLANES, v.shape[2] // SUBLANES))


def _channels(v):
    return v.reshape(v.shape[:2] + (-1,))


def selective_scan(x, delta, a, b, c, chunk: Optional[int] = None,
                   interpret: bool = False):
    """y (batch, T, D) in x's dtype of x and delta (batch, T, D), a (D, N)
    and b, c (batch, T, N), by the kernels.  ``chunk``: the tokens a
    chunk, a multiple of 8 that divides T; by default 128, or the whole of
    a shorter sequence."""
    t = x.shape[1]
    if chunk is None:
        chunk = _CHUNK if t % _CHUNK == 0 or t > _CHUNK else t
    if chunk % _UNROLL or t % chunk:
        raise ValueError(f"selective_scan: {t} tokens in chunks of {chunk}")
    return _selective_scan(x, delta, a, b, c, chunk, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _selective_scan(x, delta, a, b, c, chunk, interpret):
    return _scan_fwd(x, delta, a, b, c, chunk, interpret)[0]


def _operands(x, delta, a, b, c):
    """The kernels' operands: x and delta laid out (batch, T, 8, D / 8)
    in x's dtype, A (N, 8, D / 8) and B and C as one float32 row a
    sequence, (batch, T * 2N)."""
    d, n_state = a.shape
    a3 = a.astype(jnp.float32).T.reshape(n_state, SUBLANES, d // SUBLANES)
    bc = jnp.concatenate([b, c], axis=-1).astype(jnp.float32)
    return (_tokens(x), _tokens(delta.astype(x.dtype)), a3,
            bc.reshape(bc.shape[0], -1))


def _scan_fwd(x, delta, a, b, c, chunk, interpret):
    with jax.named_scope("ff.kernel." + KERNELS[0]):
        y4, h = _fwd_call(*_operands(x, delta, a, b, c), chunk, interpret)
    return _channels(y4), (x, delta, a, b, c, h)


def _scan_bwd(chunk, interpret, res, dy):
    x, delta, a, b, c, h = res
    bsz, t, _ = x.shape
    n_state = a.shape[1]
    with jax.named_scope("ff.kernel." + KERNELS[1]):
        dx4, ddt4, da3, dbc = _bwd_call(
            *_operands(x, delta, a, b, c), _tokens(dy.astype(x.dtype)), h,
            chunk, interpret)
        da = jnp.sum(da3, axis=0).reshape(n_state, -1).T
        dbc = jnp.sum(dbc.reshape(bsz, -1, t, 2 * n_state), axis=1)
    return (_channels(dx4), _channels(ddt4).astype(delta.dtype),
            da.astype(a.dtype), dbc[..., :n_state].astype(b.dtype),
            dbc[..., n_state:].astype(c.dtype))


_selective_scan.defvjp(_scan_fwd, _scan_bwd)
