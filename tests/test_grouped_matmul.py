"""kernels/grouped_matmul.py on the CPU: the three kernels in the Pallas
interpreter against the batched product XLA differentiates, for groups of
uneven sizes, an empty group and trailing tiles; the dtypes of what comes
back; and what the kernels refuse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels.grouped_matmul import grouped_matmul

TILE = 8
# ten tiles: group 1 is empty but owns a tile of zero rows, the last two
# tiles are the trailing ones a full budget leaves to the last group
TILE_GROUP = jnp.array([0, 0, 0, 1, 2, 2, 3, 3, 3, 3], jnp.int32)


def _operands(dtype, k=64, n=48):
    ks = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(ks[0], (10 * TILE, k), jnp.float32)
    x = x.at[3 * TILE:4 * TILE].set(0.0).at[8 * TILE:].set(0.0)
    w = jax.random.normal(ks[1], (4, k, n), jnp.float32) / np.sqrt(k)
    cot = jax.random.normal(ks[2], (10 * TILE, n), jnp.float32)
    return x.astype(dtype), w, cot


def _graded(impl, x, w, cot):
    def f(x, w):
        y = grouped_matmul(x, w, TILE_GROUP, tile_m=TILE, impl=impl)
        return jnp.sum(y.astype(jnp.float32) * cot), y
    return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(x, w)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_kernels_match_the_batched_product(dtype, tol):
    x, w, cot = _operands(dtype)
    (_, y), (dx, dw) = _graded("pallas_interpret", x, w, cot)
    (_, y_ref), (dx_ref, dw_ref) = _graded("xla", x, w, cot)
    # rows in x's dtype, the weights' gradient in the weights'
    assert y.dtype == dx.dtype == dtype and dw.dtype == jnp.float32
    assert y.shape == (10 * TILE, 48) and dw.shape == w.shape
    for name, a, r in (("y", y, y_ref), ("dx", dx, dx_ref),
                       ("dw", dw, dw_ref)):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.abs(a - r).max() <= tol * np.abs(r).max(), name
    # the hand count: tile 4 is group 2's
    want = np.asarray(x[4 * TILE:5 * TILE], np.float32) @ np.asarray(w[2])
    np.testing.assert_allclose(np.asarray(y[4 * TILE:5 * TILE], np.float32),
                               want, rtol=10 * tol, atol=10 * tol)
    # an empty group's gradient is written, and is zero
    assert not np.asarray(dw[1]).any()


def test_refuses_rows_that_are_not_whole_tiles():
    x, w, _ = _operands(jnp.float32)
    with pytest.raises(ValueError, match="tile_m"):
        grouped_matmul(x[:-1], w, TILE_GROUP, tile_m=TILE, impl="xla")
    with pytest.raises(ValueError, match="tile_group"):
        grouped_matmul(x, w, TILE_GROUP[:-1], tile_m=TILE, impl="xla")
    with pytest.raises(ValueError, match="unknown impl"):
        grouped_matmul(x, w, TILE_GROUP, tile_m=TILE, impl="triton")
