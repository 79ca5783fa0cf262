"""Fused optimizer-update Pallas kernels.

TPU-native analogue of the reference's hand-written update kernels
(reference: src/runtime/optimizer_kernel.cu:23-40 sgd_update,
:206-225 adam_update).  Semantics match the reference exactly:

  SGD:  g' = g + wd*w;  m = momentum*m + g';
        w -= lr * (g' + momentum*m)   (nesterov)
        w -= lr * m                   (momentum)
        w -= lr * g'                  (plain)
  Adam: g' = g + wd*w;  m = b1*m + (1-b1)*g';  v = b2*v + (1-b2)*g'^2;
        w -= alpha_t * m / (sqrt(v) + eps)
  (alpha_t folds the bias correction, as the reference precomputes
   alpha_t = alpha * sqrt(1-b2^t)/(1-b1^t), optimizer.cc:128-136.)

Each parameter is flattened, padded to a (rows, 128) layout, and the
kernel runs a 1-D grid of row-blocks with all operands aliased in-place.
XLA fuses unrolled elementwise updates well already, so the win here is
bounded — the point is parity of the "native kernel" path and the
in-place aliasing (no param-sized temporaries at peak memory).

The kernels compile through Mosaic for a TPU; ``interpret=True`` runs
them in the Pallas interpreter on any backend.  The caller decides
(``FFModel.compile`` by the machine's platform, and says so).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS = 8  # f32 sublane tile


def _row_block(n: int) -> int:
    """Row count of the padded (rows, 128) layout's grid block."""
    rows = -(-n // _LANES)
    if rows <= 512:
        return -(-rows // _ROWS) * _ROWS
    return 512


def _to_tiles(x: jax.Array):
    """Flatten to (rows, 128) with zero padding; return array + original size.

    rows is a multiple of the grid row-block so the 1-D grid divides evenly."""
    n = x.size
    bq = _row_block(n)
    rows = -(-(-(-n // _LANES)) // bq) * bq
    flat = jnp.zeros((rows * _LANES,), dtype=x.dtype).at[:n].set(x.reshape(-1))
    return flat.reshape(rows, _LANES), n


def _from_tiles(t: jax.Array, n: int, shape, dtype):
    return t.reshape(-1)[:n].reshape(shape).astype(dtype)


def _sgd_kernel(hp_ref, w_ref, g_ref, m_ref, w_out, m_out, *, momentum, nesterov):
    lr = hp_ref[0]
    wd = hp_ref[1]
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) + wd * w
    if momentum > 0.0:
        m = momentum * m_ref[:].astype(jnp.float32) + g
        m_out[:] = m.astype(m_out.dtype)
        upd = g + momentum * m if nesterov else m
    else:
        m_out[:] = m_ref[:]
        upd = g
    w_out[:] = (w - lr * upd).astype(w_out.dtype)


def fused_sgd_update(w, g, m, lr, wd=0.0, momentum=0.0, nesterov=False,
                     interpret=False):
    """One fused SGD step on a single parameter; returns (w_new, m_new)."""
    wt, n = _to_tiles(w)
    gt, _ = _to_tiles(g)
    mt, _ = _to_tiles(m)
    rows = wt.shape[0]
    bq = _row_block(n)
    hp = jnp.stack([jnp.asarray(lr, jnp.float32), jnp.asarray(wd, jnp.float32)])
    w2, m2 = pl.pallas_call(
        functools.partial(_sgd_kernel, momentum=float(momentum), nesterov=bool(nesterov)),
        grid=(rows // bq,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(wt.shape, wt.dtype),
            jax.ShapeDtypeStruct(mt.shape, mt.dtype),
        ],
        input_output_aliases={1: 0, 3: 1},
        interpret=interpret,
        name="fused_sgd",
    )(hp, wt, gt, mt)
    return (_from_tiles(w2, n, w.shape, w.dtype),
            _from_tiles(m2, n, m.shape, m.dtype))


def _adam_kernel(hp_ref, w_ref, g_ref, m_ref, v_ref, w_out, m_out, v_out,
                 *, beta1, beta2):
    alpha_t = hp_ref[0]
    wd = hp_ref[1]
    eps = hp_ref[2]
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) + wd * w
    m = beta1 * m_ref[:].astype(jnp.float32) + (1.0 - beta1) * g
    v = beta2 * v_ref[:].astype(jnp.float32) + (1.0 - beta2) * g * g
    m_out[:] = m.astype(m_out.dtype)
    v_out[:] = v.astype(v_out.dtype)
    w_out[:] = (w - alpha_t * m / (jnp.sqrt(v) + eps)).astype(w_out.dtype)


def fused_adam_update(w, g, m, v, alpha_t, wd=0.0, beta1=0.9, beta2=0.999,
                      eps=1e-8, interpret=False):
    """One fused Adam step; ``alpha_t`` carries the bias correction.

    Returns (w_new, m_new, v_new)."""
    wt, n = _to_tiles(w)
    gt, _ = _to_tiles(g)
    mt, _ = _to_tiles(m)
    vt, _ = _to_tiles(v)
    rows = wt.shape[0]
    bq = _row_block(n)
    hp = jnp.stack([jnp.asarray(alpha_t, jnp.float32),
                    jnp.asarray(wd, jnp.float32),
                    jnp.asarray(eps, jnp.float32)])
    w2, m2, v2 = pl.pallas_call(
        functools.partial(_adam_kernel, beta1=float(beta1), beta2=float(beta2)),
        grid=(rows // bq,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((bq, _LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(wt.shape, wt.dtype),
            jax.ShapeDtypeStruct(mt.shape, mt.dtype),
            jax.ShapeDtypeStruct(vt.shape, vt.dtype),
        ],
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
        name="fused_adam",
    )(hp, wt, gt, mt, vt)
    return (_from_tiles(w2, n, w.shape, w.dtype),
            _from_tiles(m2, n, m.shape, m.dtype),
            _from_tiles(v2, n, v.shape, v.dtype))
