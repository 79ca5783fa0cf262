"""Grouped matrix product over sorted rows, as Pallas TPU kernels.

A routed-experts layer (ops/moe.py, ``RoutedExperts``) sorts the rows it
keeps by expert and pads each expert's group to whole row tiles, so that
every tile of ``tile_m`` rows belongs to exactly one expert.  What it
then needs is ``y[tile t] = x[tile t] @ w[tile_group[t]]`` and the two
gradients of that.  Three kernels, each a ``pallas_call`` named as its
scope:

* ``gmm``    y = x @ w[g]: grid (column tiles, row tiles), rows innermost,
  so that a weight block is fetched once a group and a column tile (the
  rows are sorted: consecutive tiles of one group name the block already
  held), the whole contraction in one block;
* ``gmm_t``  dx = dy @ w[g]^T: the same kernel, contracting the
  weight's last dimension;
* ``tgmm``   dw[g] = sum over the group's tiles of x^T dy: grid (k tiles,
  n tiles, row tiles), rows innermost, an f32 accumulator in VMEM that is
  zeroed at a group's first tile and written at its last.

The weights are read in the dtype they are stored in (float32 parameters)
and cast to the rows' dtype a block at a time in VMEM: a layer whose
experts see a few hundred rows each is bound by the weights' bytes, and a
cast outside the kernel would read them once more and write them again.
Products accumulate in float32.

**The work is a function of the shapes alone.**  ``tile_group`` arrives by
scalar prefetch and only picks which weight block a tile reads: no tile
is skipped, whatever the groups' sizes.  Every group must own at least one
tile (``tgmm`` writes a group's block only when it visits it), and the
tiles of a group are consecutive.

``impl="xla"`` is the same mathematics as one gather of the tiles' weight
blocks and a batched product, for backends without Mosaic (the CPU
tests); ``"pallas_interpret"`` runs the kernel bodies in the Pallas
interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM a call may use: the v5e has 128 MiB, Mosaic's default scope is 16.
_VMEM_LIMIT = 64 * 2 ** 20
# What a weight block may take of it: two f32 buffers and the cast copy.
_WEIGHT_BLOCK_BYTES = 40 * 2 ** 20

_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _tile(dim: int, *wanted: int) -> int:
    """The first of ``wanted`` that divides ``dim``, else all of it."""
    return next((t for t in wanted if dim % t == 0), dim)


def _column_tile(contract: int, out: int, w_bytes: int, x_bytes: int) -> int:
    """Columns of a weight block that holds the whole contraction: the
    widest of 512, 256, 128 whose two buffers and cast copy fit."""
    fits = [t for t in (512, 256, 128)
            if contract * t * (2 * w_bytes + x_bytes) <= _WEIGHT_BLOCK_BYTES]
    return _tile(out, *fits)


def _gmm_kernel(tg_ref, x_ref, w_ref, o_ref, *, dims):
    x = x_ref[...]
    o_ref[...] = jax.lax.dot_general(
        x, w_ref[...].astype(x.dtype), dims,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "transpose_rhs",
                                             "interpret"))
def _gmm_call(x, w, tile_group, tile_m, transpose_rhs, interpret):
    """(M, out) = x (M, contract) times each tile's weight: w is
    (G, contract, out), or (G, out, contract) with ``transpose_rhs``."""
    m, contract = x.shape
    out = w.shape[1] if transpose_rhs else w.shape[2]
    tn = _column_tile(contract, out, w.dtype.itemsize, x.dtype.itemsize)
    if transpose_rhs:
        w_spec = pl.BlockSpec((None, tn, contract),
                              lambda n, t, tg: (tg[t], n, 0))
    else:
        w_spec = pl.BlockSpec((None, contract, tn),
                              lambda n, t, tg: (tg[t], 0, n))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, dims=_NT if transpose_rhs else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(out // tn, m // tile_m),
            in_specs=[pl.BlockSpec((tile_m, contract),
                                   lambda n, t, tg: (t, 0)), w_spec],
            out_specs=pl.BlockSpec((tile_m, tn), lambda n, t, tg: (t, n))),
        out_shape=jax.ShapeDtypeStruct((m, out), x.dtype),
        interpret=interpret,
        name="gmm_t" if transpose_rhs else "gmm",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(tile_group, x, w)


def _tgmm_kernel(tg_ref, x_ref, dy_ref, o_ref, acc_sc):
    t = pl.program_id(2)
    last_t = pl.num_programs(2) - 1
    group = tg_ref[t]
    first = jnp.logical_or(t == 0, tg_ref[jnp.maximum(t - 1, 0)] != group)
    last = jnp.logical_or(t == last_t,
                          tg_ref[jnp.minimum(t + 1, last_t)] != group)

    @pl.when(first)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    acc_sc[...] += jax.lax.dot_general(x_ref[...], dy_ref[...], _TN,
                                       preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finish():
        o_ref[...] = acc_sc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "groups", "dtype",
                                             "interpret"))
def _tgmm_call(x, dy, tile_group, tile_m, groups, dtype, interpret):
    """(G, K, N): for each group the sum over its tiles of x^T dy."""
    m, k = x.shape
    n = dy.shape[1]
    tk, tn = _tile(k, 1024, 512, 256, 128), _tile(n, 512, 256, 128)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k // tk, n // tn, m // tile_m),
            in_specs=[
                pl.BlockSpec((tile_m, tk), lambda ki, ni, t, tg: (t, ki)),
                pl.BlockSpec((tile_m, tn), lambda ki, ni, t, tg: (t, ni))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda ki, ni, t, tg: (tg[t], ki, ni)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        interpret=interpret,
        name="tgmm",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(tile_group, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(x, w, tile_group, tile_m, interpret):
    with jax.named_scope("ff.kernel.gmm"):
        return _gmm_call(x, w, tile_group, tile_m, False, interpret)


def _gmm_fwd(x, w, tile_group, tile_m, interpret):
    return _gmm(x, w, tile_group, tile_m, interpret), (x, w, tile_group)


def _gmm_bwd(tile_m, interpret, res, dy):
    x, w, tile_group = res
    with jax.named_scope("ff.kernel.gmm_t"):
        dx = _gmm_call(dy, w, tile_group, tile_m, True, interpret)
    with jax.named_scope("ff.kernel.tgmm"):
        dw = _tgmm_call(x, dy, tile_group, tile_m, w.shape[0], w.dtype,
                        interpret)
    return dx, dw, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul_xla(x, w, tile_group, tile_m):
    """The same product as one gather of each tile's weights and a
    batched product; differentiated by JAX."""
    tiles = x.reshape(-1, tile_m, x.shape[1])
    y = jnp.einsum("tmk,tkn->tmn", tiles, w[tile_group].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype).reshape(x.shape[0], -1)


def grouped_matmul(x, w, tile_group, *, tile_m: int, impl: str = "pallas"):
    """``y[t*tile_m:(t+1)*tile_m] = x[...] @ w[tile_group[t]]``.

    x is (M, K) with M a multiple of ``tile_m``, w (G, K, N) in its
    stored dtype, ``tile_group`` (M / tile_m,) int32, sorted, every group
    in it at least once.  Returns (M, N) in x's dtype; differentiable in
    x and w.  ``impl`` is ``pallas``, ``pallas_interpret`` or ``xla``."""
    if x.shape[0] % tile_m or tile_group.shape != (x.shape[0] // tile_m,):
        raise ValueError(f"grouped_matmul: {x.shape[0]} rows, tile_m "
                         f"{tile_m}, tile_group {tile_group.shape}")
    if impl == "xla":
        return grouped_matmul_xla(x, w, tile_group, tile_m)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"grouped_matmul: unknown impl {impl!r}")
    return _gmm(x, w, tile_group, tile_m, impl == "pallas_interpret")
