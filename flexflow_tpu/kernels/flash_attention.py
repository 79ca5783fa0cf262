"""Blockwise (flash) attention as a Pallas TPU kernel, with custom VJP.

The reference has no attention op (it predates transformers; its only
long-sequence mechanism is the NMT LSTM chunking, nmt/rnn.h:21-23).  On
TPU, attention is *the* hot op for long-context models, so the framework
provides a first-class fused kernel: online-softmax forward that never
materializes the (S, S) score matrix in HBM, and a recompute-based
backward.  The kernel also returns the per-row logsumexp, which is what
lets ring attention (parallel/sequence.py) merge partial results across
sequence shards.

Layout: (batch, heads, seq, head_dim), f32 or bf16 in / f32 accumulate.
Grid is (batch*heads, q_blocks, k_blocks) with the k dimension innermost
so the accumulator lives in VMEM scratch across the k sweep.

The kernels compile through Mosaic and run on a TPU only.  Callers pick
the implementation by platform (ops/attention.py); ``interpret=True``
runs the same kernel bodies in the Pallas interpreter on any backend and
is something a test asks for by name, never a default.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Lane width of the VPU; m/l scratch rows are replicated across it.
_LANES = 128


def _largest_block(seq: int, cap: int) -> Optional[int]:
    """The whole sequence when it fits under ``cap``; otherwise its
    largest divisor that is <= ``cap`` and a multiple of 8 rows; None
    when it has none."""
    if seq <= cap:
        return seq
    for b in range(cap // 8 * 8, 0, -8):
        if seq % b == 0:
            return b
    return None


def unsupported_reason(seq_q: int, seq_k: int,
                       block_q: Optional[int] = None,
                       block_k: Optional[int] = None) -> Optional[str]:
    """Why the kernel cannot tile this shape, or None when it can.

    A block must divide its sequence (the grid has no remainder step).
    Mosaic takes a block equal to the whole dimension whatever its
    length, and otherwise wants whole 8-row sublane groups: on the v5e
    with libtpu 0.0.34 blocks of 100 (= the sequence), 40 and 200 rows
    compile and match the reference in f32 and bf16 (chip run, PR 21).
    That leaves out a sequence longer than 512 with no divisor that is
    a multiple of 8 (1009, 1018) — the caller's cue for the XLA path."""
    for name, seq, want in (("q", seq_q, block_q), ("k", seq_k, block_k)):
        if _largest_block(seq, want or 512) is None:
            return (f"flash_attention: {name} sequence length {seq} has no "
                    f"divisor <= {want or 512} that is a multiple of 8 rows")
    return None


def _block_sizes(seq_q: int, seq_k: int,
                 block_q: Optional[int] = None, block_k: Optional[int] = None):
    """(block_q, block_k) the kernels tile the two sequences with;
    ValueError naming the sequence ``unsupported_reason`` rejects."""
    why = unsupported_reason(seq_q, seq_k, block_q, block_k)
    if why is not None:
        raise ValueError(why)
    return (_largest_block(seq_q, block_q or 512),
            _largest_block(seq_k, block_k or 512))


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_sc, m_sc, l_sc, *, scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = (qi * block_q + rows) >= (ki * block_k + cols)
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[:, :1]                       # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                      # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)             # (bq, 1)
        l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    if causal:
        # Skip blocks whose every (q, k) pair has k > q.
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        m = m_sc[:, :1]
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _flash_forward(q, k, v, scale, causal, block_q, block_k, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, d)

    grid = (bh, sq // bq, sk // bk)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, qi, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, qi, ki: (bh_, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh_, qi, ki: (bh_, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )
    with jax.named_scope("ff.kernel.flash_fwd"):
        out, lse = call(qr, kr, vr)
    return (out.reshape(b, h, sq, d), lse[:, :, 0].reshape(b, h, sq))


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_sc, dv_sc,
                     *, scale, causal, block_q, block_k):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]                     # (bq, 1)
        delta = delta_ref[0][:, :1]                 # (bq, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = (qi * block_q + rows) >= (ki * block_k + cols)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                        # (bq, bk)
        # dv += p^T @ do
        dv_sc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        # dp = do @ v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        # dk += ds^T @ q
        dk_sc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    if causal:
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_body)
    else:
        _body()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_sc, *, scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = (qi * block_q + rows) >= (ki * block_k + cols)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_sc[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    if causal:
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _flash_backward(scale, causal, block_q, block_k, interpret, res, grads):
    q, k, v, out, lse = res
    do, _ = grads
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    bh = b * h

    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, sk, d)
    vr = v.reshape(bh, sk, d)
    dor = do.reshape(bh, sq, d)
    lser = jnp.broadcast_to(lse.reshape(bh, sq, 1), (bh, sq, _LANES))
    deltar = jnp.broadcast_to(delta.reshape(bh, sq, 1), (bh, sq, _LANES))

    common_specs = [
        pl.BlockSpec((1, bq, d), lambda bh_, a, qi: (bh_, qi, 0)),      # q
        pl.BlockSpec((1, bk, d), lambda bh_, a, qi: (bh_, a, 0)),       # k
        pl.BlockSpec((1, bk, d), lambda bh_, a, qi: (bh_, a, 0)),       # v
        pl.BlockSpec((1, bq, d), lambda bh_, a, qi: (bh_, qi, 0)),      # do
        pl.BlockSpec((1, bq, _LANES), lambda bh_, a, qi: (bh_, qi, 0)),  # lse
        pl.BlockSpec((1, bq, _LANES), lambda bh_, a, qi: (bh_, qi, 0)),  # delta
    ]
    dkv_call = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(bh, sk // bk, sq // bq),
        in_specs=common_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh_, ki, qi: (bh_, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, ki, qi: (bh_, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )
    with jax.named_scope("ff.kernel.flash_dkv"):
        dk, dv = dkv_call(qr, kr, vr, dor, lser, deltar)

    dq_call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(bh, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, qi, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, qi, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh_, qi, ki: (bh_, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh_, qi, ki: (bh_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )
    with jax.named_scope("ff.kernel.flash_dq"):
        dq = dq_call(qr, kr, vr, dor, lser, deltar)

    return (dq.reshape(b, h, sq, d),
            dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    return _flash_forward(q, k, v, scale, causal, block_q, block_k, interpret)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, scale, causal, block_q, block_k,
                              interpret)
    return (out, lse), (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _flash_backward)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    return_lse: bool = False,
                    interpret: bool = False):
    """Fused attention: softmax(q k^T * scale [+ causal mask]) v.

    Args are (B, H, S, D).  Returns the output, plus the per-row
    logsumexp (B, H, S) when ``return_lse`` — ring attention uses the
    lse to merge shard-local partials.  Raises ValueError for a sequence
    length the kernel cannot tile (``unsupported_reason``).
    ``interpret=True`` runs the kernel in the Pallas interpreter (any
    backend, for tests); the default compiles it for the TPU.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    out, lse = _flash(q, k, v, scale, causal, block_q, block_k, interpret)
    if return_lse:
        return out, lse
    return out


def mha_reference(q, k, v, *, causal: bool = False, scale: Optional[float] = None):
    """Unfused reference attention (numerics oracle for tests)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
