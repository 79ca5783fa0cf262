"""The learned index's scores (ops/dsa.py) as Pallas TPU kernels.

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])

for the queries ``q`` (B, T, heads, d), the one key a position ``k``
(B, T, d) and the head weights ``w`` (B, T, heads).  What XLA's form
writes to HBM and reads back a block of queries at a time, the (heads x
queries, keys) product, lives here a tile at a time in VMEM: a kernel
computes one head's product of a (queries, keys) tile, applies the relu
and sums over the heads there, and writes ``I`` alone forward and ``dq``,
``dk``, ``dw`` backward.  Two kernels, each a ``pallas_call`` named as its
scope, on one grid, (batch, q blocks, k blocks) with k innermost; a q
block holds every head's queries, (block_q, heads * d), a head a lane
slice of it, and stays while the keys go by:

* ``dsa_index_fwd`` holds a head's product transposed, keys down the
  sublanes and queries along the lanes: ``w`` is one value a (query,
  head), so a head's weights are a lane-dense (1, block_q) row that a
  sublane broadcast spreads over the keys (PERF.md section 6, PR 26).
  ``w`` enters as (B, heads, T) and the tile of ``I`` is turned once
  before it is written, whatever the number of heads.
* ``dsa_index_bwd`` needs no weight a score.  With ``m_j = [q_j . k >
  0]`` and ``gm_j = m_j * g`` (queries down the sublanes, as ``g`` lies),

      dq_j = w_j * (gm_j @ k)        dk = sum_j gm_j^T @ (w_j * q_j)
      dw_j = rowsum(gm_j * (q_j k^T)) = <q_j, gm_j @ k>

  so a head and a tile take three products (the scores again, ``gm_j @
  k``, ``gm_j^T @ qw_j``), one compare and one select a score, and the
  weights come in once a q block: ``qw = w * q`` before its first k block
  and the scaling of ``dq`` and ``dw`` after its last, in VMEM.  ``gm_j``
  streams through the MXU as the left operand of both products, turned
  for the second, against the stationary ``k`` and ``qw_j``: with the
  scores held transposed one product had ``gm_j`` stationary for 128 rows
  a tile and the kernel took a third as long again (PERF.md section 6,
  PR 36).  ``dq`` accumulates over the k blocks of a q block in its
  output block and ``dk`` over the whole grid of a batch element in its,
  (T, d), both in VMEM.

Arithmetic.  Forward: float32 operands at ``Precision.HIGHEST`` (each
operand as three bfloat16 terms, six products, float32 accumulation;
Mosaic's ``contract_precision<fp32>``), the relu, the weights and the head
sum in float32: a score decides which keys a query gets.  Backward: ``q``,
``k``, ``w * q`` and ``gm`` in ``grad_dtype`` (the step's bfloat16), one
pass, float32 accumulation; the relu's mask from the scores recomputed at
that precision; what is elementwise in float32 (the v5e's VPU computes no
bfloat16).

The queries' rotary turn.  With ``rope=(cos, sin)`` the queries come as
the projection made them, each head's r/2 rotary pairs set apart (first
elements, second elements, the rest), and a kernel turns a head's
(block_q, d) by two lane rotations, once a q block: forward before the
block's first keys, into VMEM scratch and, in ``grad_dtype``, into a
second output for the backward pass, which turns ``dq`` back after the
block's last keys (turned a head and a tile inside the loop over heads,
the turn's latency stood before each head's product and the kernel took
22.7 ms where it takes 20.3).  The passes that run once a q block are
``fori_loop``s over the heads: as Python loops they made Mosaic's
compile ten times as long and a start from the compile cache 8 s longer.
In XLA the turn and its gradient were a dozen relayouts of the (T, heads,
d) queries, 268 MB each, between the projection and the kernels (PERF.md
section 6, PR 36); here the queries reach HBM as the projection writes
them, (T, heads * d) row-major, and ``dq`` as the weight gradient's
product reads it.

Causal.  A key block wholly above a query block runs no body, fetches
nothing and, forward, writes ``NEG_INF``; a block on the diagonal is
computed whole, so above the diagonal ``I`` holds ``NEG_INF`` or a score,
as ``ops/dsa.py`` allows (``select_topk`` and ``index_kl`` read the causal
entries alone, and ``index_kl`` leaves a gradient that is zero above it).

The kernels compile through Mosaic and run on a TPU only;
``interpret=True`` runs the same bodies in the Pallas interpreter, for
tests.  ``unsupported_reason`` is the rule a caller picks the path by and
``tiling()`` what the kernels do with a shape.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
KERNELS = ("dsa_index_fwd", "dsa_index_bwd")

# The most queries, and keys, of a tile (sweep on the v5e, PERF.md
# section 6, PR 36): the stationary operand of a product is used for
# block_q or block_k rows, so the larger the better up to what VMEM
# holds, and 1024 keys run more of the square above the diagonal.
_MAX_BLOCK = 512
# Bytes a query of a q block takes in VMEM, an element of (heads * d):
# forward the float32 block twice (Pallas double-buffers), the turned one
# once and the one it keeps for the backward pass, in the gradient's
# dtype, twice; backward that block twice, ``w * q`` once and the float32
# gradient twice.  The gradient's dtype counts as bfloat16's two bytes.
_BYTES_PER_ELEMENT = {"dsa_index_fwd": 2 * 4 + 4 + 2 * 2,
                      "dsa_index_bwd": 2 * 2 + 2 + 2 * 4}
# VMEM a call may use: the v5e has 128 MiB, Mosaic's default scope is 16.
_VMEM_LIMIT = 100 * 2 ** 20
# What the blocks that grow with the shape may take of it; the tile's own
# float32 temporaries take the rest.
_VMEM_BLOCKS = 72 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a^T @ b
_HIGHEST = jax.lax.Precision.HIGHEST


def _vmem_bytes(kernel: str, seq: int, heads: int, dim: int, bq: int) -> int:
    """The blocks of a call that grow with the shape: the q block and what
    is as large, and ``dk`` whole, twice."""
    return bq * heads * dim * _BYTES_PER_ELEMENT[kernel] + 2 * seq * dim * 4


def _blocks(kernel: str, seq: int, heads: int, dim: int,
            block_q: Optional[int] = None, block_k: Optional[int] = None
            ) -> Optional[Tuple[int, int]]:
    """(block_q, block_k) of a kernel: for each the largest divisor of
    ``seq`` that is a multiple of the 128 lanes (a tile of ``I`` lies
    along lanes either way round) and at most ``_MAX_BLOCK`` (or
    ``block_q`` / ``block_k``, a test's), the q block small enough for
    VMEM; None where there is none."""
    def largest(cap, fits=lambda b: True):
        return next((b for b in range(min(cap, seq) // 128 * 128, 0, -128)
                     if seq % b == 0 and fits(b)), None)
    bq = largest(block_q or _MAX_BLOCK, lambda b: _vmem_bytes(
        kernel, seq, heads, dim, b) <= _VMEM_BLOCKS)
    bk = largest(block_k or _MAX_BLOCK)
    return None if bq is None or bk is None else (bq, bk)


def unsupported_reason(seq: int, heads: int, dim: int,
                       block_q: Optional[int] = None,
                       block_k: Optional[int] = None) -> Optional[str]:
    """Why the kernels cannot take this shape, or None when they can: a
    head's width must be whole lanes (a head is a lane slice of the q
    block), the sequence needs a divisor that is a multiple of 128, and
    the smallest q block must fit in VMEM."""
    if dim % 128:
        return (f"dsa_index: index head width {dim} is not a multiple of "
                f"the 128 lanes")
    if seq % 128:
        return (f"dsa_index: sequence length {seq} has no divisor that is a "
                f"multiple of 128")
    for kernel in KERNELS:
        if _blocks(kernel, seq, heads, dim, block_q, block_k) is None:
            need = _vmem_bytes(kernel, seq, heads, dim, 128)
            return (f"dsa_index: {kernel}'s blocks take {need >> 20} MiB at "
                    f"sequence {seq} and {heads} heads of {dim}, over the "
                    f"{_VMEM_BLOCKS >> 20} MiB of VMEM kept for them")
    return None


def _block_sizes(kernel, seq, heads, dim, block_q=None, block_k=None):
    why = unsupported_reason(seq, heads, dim, block_q, block_k)
    if why is not None:
        raise ValueError(why)
    return _blocks(kernel, seq, heads, dim, block_q, block_k)


def _runs(qi, ki, block_q: int, block_k: int):
    """Does tile (qi, ki) hold a pair with k <= q?"""
    return ki * block_k <= qi * block_q + block_q - 1


def _last_k(qi, block_q: int, block_k: int):
    """The last k block a q block visits."""
    return (qi * block_q + block_q - 1) // block_k


def tiling(seq: int, heads: int, dim: int, block_q: Optional[int] = None,
           block_k: Optional[int] = None) -> Dict[str, Dict[str, int]]:
    """What each kernel does with one batch element of this shape: its
    blocks, the steps its grid has, those of them that run a body (the
    rest lie wholly above the diagonal; forward they write ``NEG_INF``)
    and the products a body step makes (one a head forward; the scores
    again, ``dq`` and ``dk`` backward)."""
    out = {}
    for kernel, products in zip(KERNELS, (1, 3)):
        bq, bk = _block_sizes(kernel, seq, heads, dim, block_q, block_k)
        nq, nk = seq // bq, seq // bk
        out[kernel] = dict(
            block_q=bq, block_k=bk, grid_steps=nq * nk,
            body_steps=sum(1 for qi in range(nq) for ki in range(nk)
                           if _runs(qi, ki, bq, bk)),
            products_per_step=products * heads)
    return out


def _k_of(bq: int, bk: int):
    """The k block a step names: above the diagonal the one already
    held, so that it fetches nothing."""
    return lambda qi, ki: jnp.minimum(ki, _last_k(qi, bq, bk))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _lanes(j, dim: int):
    """Head ``j``'s lanes of a (block_q, heads * d) block."""
    return pl.ds(pl.multiple_of(j * dim, dim), dim)


def rope_tables(cos, sin, dim: int):
    """(3, T, d) float32 from ``cos``, ``sin`` (T, r/2), for a head whose
    lanes hold the r/2 rotary pairs' first elements, then their second
    ones, then what is not turned: the factors of a lane's own value, of
    the value r/2 lanes to its right and of the one r/2 to its left,
    ``[cos cos 1]``, ``[-sin 0 0]`` and ``[0 sin 0]``."""
    cos, sin = jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)
    t, half = cos.shape
    fill = lambda value, n: jnp.full((t, n), value, jnp.float32)
    return jnp.stack([
        jnp.concatenate([cos, cos, fill(1.0, dim - 2 * half)], axis=1),
        jnp.concatenate([-sin, fill(0.0, dim - half)], axis=1),
        jnp.concatenate([fill(0.0, half), sin, fill(0.0, dim - 2 * half)],
                        axis=1)])


def _turn(x, rope_ref, half: Optional[int]):
    """The rotary turn of one head's (block_q, d) float32 in VMEM: the
    pairs ``(a, b)`` to ``(a cos - b sin, a sin + b cos)`` by two lane
    rotations; ``x`` as it is where the caller turned it (no tables)."""
    if half is None:
        return x
    dim = x.shape[1]
    return (x * rope_ref[0] + pltpu.roll(x, dim - half, 1) * rope_ref[1]
            + pltpu.roll(x, half, 1) * rope_ref[2])


def _turn_back(dy, rope_ref, half: Optional[int]):
    """The transpose of ``_turn``: the gradient of what it was given."""
    if half is None:
        return dy
    dim = dy.shape[1]
    return (dy * rope_ref[0] + pltpu.roll(dy * rope_ref[1], half, 1)
            + pltpu.roll(dy * rope_ref[2], dim - half, 1))


def _rope_spec(half, bq: int, dim: int):
    """The tables' block of a q block, the last input where there is one."""
    return [] if half is None else [
        pl.BlockSpec((3, bq, dim), lambda b_, qi, ki: (0, qi, 0))]


# Each call below is jitted on its own, as the flash kernels' (a call site
# otherwise traces and lowers the kernel anew); the scopes are the call
# sites', so that a cached trace holds no name.

# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, w_ref, *rest, heads, dim, block_q, block_k,
                half):
    if half is None:         # the queries came turned: no tables, no copy
        (o_ref, qt_ref, acc_sc), rope_ref, qs_ref = rest, None, q_ref
    else:
        rope_ref, o_ref, qt_ref, acc_sc, qs_ref = rest
    qi, ki = pl.program_id(1), pl.program_id(2)
    runs = _runs(qi, ki, block_q, block_k)

    @pl.when(ki == 0)
    def _turn_and_keep():
        def head(j, carry):
            lanes = _lanes(j, dim)
            q = _turn(q_ref[:, lanes], rope_ref, half)
            if half is not None:
                qs_ref[:, lanes] = q
            qt_ref[:, lanes] = q.astype(qt_ref.dtype)
            return carry
        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(runs)
    def _body():
        k = k_ref[...]                                   # (keys, d)
        acc_sc[...] = jnp.zeros_like(acc_sc)

        def head(j, carry):
            st = jax.lax.dot_general(                    # (keys, queries)
                k, qs_ref[:, _lanes(j, dim)], _NT, precision=_HIGHEST,
                preferred_element_type=jnp.float32)
            acc_sc[...] += w_ref[pl.ds(j, 1), :] * jnp.maximum(st, 0.0)
            return carry
        jax.lax.fori_loop(0, heads, head, 0)
        o_ref[...] = acc_sc[...].T

    @pl.when(jnp.logical_not(runs))
    def _above():
        o_ref[...] = jnp.full_like(o_ref, NEG_INF)


@functools.partial(jax.jit, static_argnames=("heads", "bq", "bk", "half",
                                             "keep", "interpret"))
def _fwd_call(q2, k, wt, rope, heads, bq, bk, half, keep, interpret):
    """``I`` (B, T, T) float32 and the turned queries (B, T, heads * d) in
    the dtype ``keep``, of q2 (B, T, heads * d), k (B, T, d), wt (B,
    heads, T), float32, and the rotary tables (3, T, d) or None."""
    b, t, dim = k.shape
    k_of = _k_of(bq, bk)
    rows = pl.BlockSpec((None, bq, heads * dim),
                        lambda b_, qi, ki: (b_, qi, 0))
    turned = [] if half is None else [
        pltpu.VMEM((bq, heads * dim), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, dim=dim, block_q=bq,
                          block_k=bk, half=half),
        grid=(b, t // bq, t // bk),
        in_specs=[
            rows,
            pl.BlockSpec((None, bk, dim),
                         lambda b_, qi, ki: (b_, k_of(qi, ki), 0)),
            pl.BlockSpec((None, heads, bq), lambda b_, qi, ki: (b_, 0, qi)),
        ] + _rope_spec(half, bq, dim),
        out_specs=[pl.BlockSpec((None, bq, bk),
                                lambda b_, qi, ki: (b_, qi, ki)), rows],
        out_shape=[jax.ShapeDtypeStruct((b, t, t), jnp.float32),
                   jax.ShapeDtypeStruct(q2.shape, keep)],
        scratch_shapes=[pltpu.VMEM((bk, bq), jnp.float32)] + turned,
        interpret=interpret,
        name=KERNELS[0],
        compiler_params=_PARAMS,
    )(q2, k, wt, *([] if half is None else [rope]))


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, kt_ref, w_ref, g_ref, *rest, heads, dim,
                block_q, block_k, half):
    rope_ref = None if half is None else rest[0]
    dq_ref, dk_ref, dw_ref, qw_sc = rest[-4:]
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    f32 = jnp.float32

    def head_is(j):
        """(block_q, heads) bool: the lanes of head ``j``'s weight."""
        return jax.lax.broadcasted_iota(jnp.int32, w_ref.shape, 1) == j

    def weight(j):
        """Head ``j``'s weights, one a query: a (block_q, 1) column."""
        return jnp.sum(jnp.where(head_is(j), w_ref[...], 0.0), axis=1,
                       keepdims=True)

    @pl.when(jnp.logical_and(qi == 0, ki == 0))
    def _init_dk():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(ki == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

        def head(j, carry):
            lanes = _lanes(j, dim)
            qw_sc[:, lanes] = (q_ref[:, lanes].astype(f32)
                               * weight(j)).astype(qw_sc.dtype)
            return carry
        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(_runs(qi, ki, block_q, block_k))
    def _body():
        k, kt = k_ref[...], kt_ref[...]              # (keys, d), (d, keys)

        def head(j, dk):
            lanes = _lanes(j, dim)
            s = jax.lax.dot_general(q_ref[:, lanes], kt, _NN,
                                    preferred_element_type=f32)
            gm = jnp.where(s > 0.0, g_ref[...], 0.0).astype(k.dtype)
            dq_ref[:, lanes] += jax.lax.dot_general(
                gm, k, _NN, preferred_element_type=f32)
            return dk + jax.lax.dot_general(gm, qw_sc[:, lanes], _TN,
                                            preferred_element_type=f32)
        dk = jax.lax.fori_loop(0, heads, head,
                               jnp.zeros((block_k, dim), f32))
        rows = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        dk_ref[rows, :] += dk

    @pl.when(ki == nk - 1)
    def _finish():
        def head(j, dw):
            lanes = _lanes(j, dim)
            raw = dq_ref[:, lanes]                   # gm_j @ k
            dq_ref[:, lanes] = _turn_back(raw * weight(j), rope_ref, half)
            return jnp.where(head_is(j), jnp.sum(
                raw * q_ref[:, lanes].astype(f32), axis=1, keepdims=True), dw)
        dw_ref[...] = jax.lax.fori_loop(0, heads, head,
                                        jnp.zeros(dw_ref.shape, f32))


@functools.partial(jax.jit, static_argnames=("heads", "bq", "bk", "half",
                                             "interpret"))
def _bwd_call(qt, k, w, g, rope, heads, bq, bk, half, interpret):
    """dq (B, T, heads * d), the gradient of the queries before their
    turn, dk (B, T, d) and dw (B, T, heads), float32, of the turned
    queries qt and k in the gradient's dtype, w (B, T, heads), the
    scores' gradient g (B, T, T) and the tables or None, float32."""
    b, t, dim = k.shape
    k_of = _k_of(bq, bk)
    rows = lambda width: pl.BlockSpec((None, bq, width),
                                      lambda b_, qi, ki: (b_, qi, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, dim=dim, block_q=bq,
                          block_k=bk, half=half),
        grid=(b, t // bq, t // bk),
        in_specs=[
            rows(heads * dim),
            pl.BlockSpec((None, bk, dim),
                         lambda b_, qi, ki: (b_, k_of(qi, ki), 0)),
            pl.BlockSpec((None, dim, bk),
                         lambda b_, qi, ki: (b_, 0, k_of(qi, ki))),
            rows(heads),
            pl.BlockSpec((None, bq, bk),
                         lambda b_, qi, ki: (b_, qi, k_of(qi, ki))),
        ] + _rope_spec(half, bq, dim),
        out_specs=[
            rows(heads * dim),
            pl.BlockSpec((None, t, dim), lambda b_, qi, ki: (b_, 0, 0)),
            rows(heads),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, heads * dim), jnp.float32),
            jax.ShapeDtypeStruct((b, t, dim), jnp.float32),
            jax.ShapeDtypeStruct((b, t, heads), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, heads * dim), qt.dtype)],
        interpret=interpret,
        name=KERNELS[1],
        compiler_params=_PARAMS,
    )(qt, k, jnp.swapaxes(k, 1, 2), w, g, *([] if half is None else [rope]))


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def index_scores(q, k, w, rope=None, grad_dtype=jnp.float32,
                 block_q: Optional[int] = None, block_k: Optional[int] = None,
                 interpret: bool = False):
    """``I`` (B, T, T) float32 of q (B, T, heads, d), k (B, T, d) and w
    (B, T, heads), all float32; above the diagonal ``NEG_INF`` or a
    score.  ``rope``: None for queries that come turned, or ``(cos,
    sin)``, (T, r/2) each, for queries whose heads hold the r/2 rotary
    pairs' first elements, then their second ones, then the rest, not
    turned yet: the kernels turn them, and ``dq`` is the gradient of what
    came.  ``grad_dtype``: what the backward products take their operands
    in.  ``block_q`` / ``block_k``: a test's cap on the blocks.
    ValueError for a shape ``unsupported_reason`` rejects."""
    return _index_fwd(q, k, w, rope, grad_dtype, block_q, block_k,
                      interpret)[0]


def _tables(rope, dim: int):
    """(r/2, tables) of ``rope``, (None, None) of none."""
    if rope is None:
        return None, None
    return rope[0].shape[-1], rope_tables(*rope, dim)


def _index_fwd(q, k, w, rope, grad_dtype, block_q, block_k, interpret):
    b, t, h, d = q.shape
    bq, bk = _block_sizes(KERNELS[0], t, h, d, block_q, block_k)
    half, tables = _tables(rope, d)
    with jax.named_scope("ff.kernel." + KERNELS[0]):
        scores, qt = _fwd_call(
            q.reshape(b, t, h * d), k, jnp.swapaxes(w, 1, 2), tables, h, bq,
            bk, half, jnp.dtype(grad_dtype), interpret)
    return scores, (qt, k, w, rope)


def _index_bwd(grad_dtype, block_q, block_k, interpret, res, g):
    qt, k, w, rope = res
    b, t, h = w.shape
    d = k.shape[-1]
    bq, bk = _block_sizes(KERNELS[1], t, h, d, block_q, block_k)
    half, tables = _tables(rope, d)
    with jax.named_scope("ff.kernel." + KERNELS[1]):
        dq, dk, dw = _bwd_call(qt, k.astype(grad_dtype), w, g, tables, h, bq,
                               bk, half, interpret)
    return dq.reshape(b, t, h, d), dk, dw, None


index_scores.defvjp(_index_fwd, _index_bwd)
