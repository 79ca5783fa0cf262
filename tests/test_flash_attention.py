"""kernels/flash_attention.py on the CPU, in the Pallas interpreter: the
numbers against the unfused reference, the operand and accumulator
dtypes of every matmul, the layout of the row statistics between the
calls, and the causal blocks each kernel visits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels.flash_attention import (KERNELS, NEG_INF,
                                                  flash_attention,
                                                  mha_reference, tiling)


def _inputs(sq, sk, dtype, d=64, b=1, h=2, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (b, h, sq, d), jnp.float32).astype(dtype)
    k, v = (jax.random.normal(kk, (b, h, sk, d), jnp.float32).astype(dtype)
            for kk in ks[1:3])
    return q, k, v, jax.random.normal(ks[3], (b, h, sq, d), jnp.float32)


def _top_left_reference(q, k, v, *, causal):
    """The kernel's causal mask starts at the top-left corner (key j is
    seen by query i >= j) whatever the two lengths; ``mha_reference``
    agrees where they are equal and is the oracle there."""
    if not causal or q.shape[2] == k.shape[2]:
        return mha_reference(q, k, v, causal=causal)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, NEG_INF)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                      v.astype(jnp.float32)).astype(q.dtype)


def _graded(attention, w, causal):
    def f(q, k, v):
        o = attention(q, k, v, causal=causal)
        return jnp.sum(o.astype(jnp.float32) * w), o
    return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)


def _flash(block, **fixed):
    return lambda *a, **kw: flash_attention(*a, block_q=block, block_k=block,
                                            interpret=True, **fixed, **kw)


# several blocks a sequence, head 64; (seq_q, seq_k, causal)
CASES = [(256, 256, True), (256, 256, False), (128, 384, False),
         (384, 128, False), (128, 384, True), (384, 128, True)]


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 1e-5)])
@pytest.mark.parametrize("sq,sk,causal", CASES)
def test_output_and_gradients_match_reference(sq, sk, causal, dtype, tol):
    q, k, v, w = _inputs(sq, sk, dtype)
    (_, o), g = _graded(_flash(128), w, causal)(q, k, v)
    (_, o_ref), g_ref = _graded(_top_left_reference, w, causal)(q, k, v)
    assert o.dtype == dtype and all(x.dtype == dtype for x in g)
    for name, a, r in zip(("out", "dq", "dk", "dv"), (o,) + tuple(g),
                          (o_ref,) + tuple(g_ref)):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.isfinite(a).all(), name
        assert np.abs(a - r).max() <= tol * np.abs(r).max(), name


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 1e-5)])
@pytest.mark.parametrize("sq,sk,causal", [(256, 256, True), (128, 384, False)])
def test_return_lse_is_the_rows_logsumexp(sq, sk, causal, dtype, tol):
    q, k, v, _ = _inputs(sq, sk, dtype, b=2)
    _, lse = _flash(128, return_lse=True)(q, k, v, causal=causal)
    assert lse.shape == (2, 2, sq) and lse.dtype == jnp.float32
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / 8.0
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, NEG_INF)
    want = jax.nn.logsumexp(s, axis=-1)
    assert np.abs(np.asarray(lse - want)).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 1e-5)])
def test_diagonal_bands_match_reference(dtype, tol):
    # the blocks the module picks: one of 1024 a kernel, cut into 2, 4
    # and 8 bands on the diagonal
    plan = tiling(1024, 1024, 64, causal=True)
    assert {k: (t["block_q"], t["diagonal_bands"]) for k, t in plan.items()} \
        == {"flash_fwd": (1024, 2), "flash_dq": (1024, 4),
            "flash_dkv": (1024, 8)}
    q, k, v, w = _inputs(1024, 1024, dtype, h=1)
    flash = lambda *a, **kw: flash_attention(*a, interpret=True, **kw)
    (_, o), g = _graded(flash, w, True)(q, k, v)
    (_, o_ref), g_ref = _graded(mha_reference, w, True)(q, k, v)
    for name, a, r in zip(("out", "dq", "dk", "dv"), (o,) + tuple(g),
                          (o_ref,) + tuple(g_ref)):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.abs(a - r).max() <= tol * np.abs(r).max(), name


def test_scale_that_is_no_power_of_two_stays_on_the_scores():
    # head 32: 1 / sqrt(32) cannot be folded into a bf16 query exactly
    q, k, v, w = _inputs(256, 256, jnp.float32, d=32)
    (_, o), g = _graded(_flash(128), w, True)(q, k, v)
    (_, o_ref), g_ref = _graded(mha_reference, w, True)(q, k, v)
    for a, r in zip((o,) + tuple(g), (o_ref,) + tuple(g_ref)):
        assert np.abs(np.asarray(a - r)).max() <= 1e-5 * np.abs(r).max()


# ---------------------------------------------------------------------------
# what the traced kernels hold
# ---------------------------------------------------------------------------

def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub)


def _pallas_calls(dtype, sq=256, sk=256, block=128):
    """{kernel name: pallas_call equation} of one forward and backward."""
    q, k, v, w = _inputs(sq, sk, dtype)
    grad = jax.grad(lambda *a: jnp.sum(
        _flash(block)(*a, causal=True).astype(jnp.float32) * w),
        argnums=(0, 1, 2))
    calls = {e.params["name"]: e
             for e in _walk(jax.make_jaxpr(grad)(q, k, v).jaxpr)
             if e.primitive.name == "pallas_call"}
    assert set(calls) == set(KERNELS)
    return calls


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("kernel,dots", zip(KERNELS, (2, 3, 4)))
def test_matmuls_take_the_input_dtype_and_accumulate_in_f32(kernel, dots,
                                                            dtype):
    call = _pallas_calls(dtype)[kernel]
    # a masked and a plain body each (blocks of 128: one band)
    found = [e for e in _walk(call.params["jaxpr"])
             if e.primitive.name == "dot_general"]
    assert len(found) == 2 * dots
    for e in found:
        assert [v.aval.dtype for v in e.invars] == [dtype, dtype]
        assert e.params["preferred_element_type"] == jnp.float32
        assert e.outvars[0].aval.dtype == jnp.float32


def test_row_statistics_cross_hbm_one_value_a_row():
    sq, bh, d = 256, 2, 64
    calls = _pallas_calls(jnp.bfloat16, sq=sq)
    for kernel, call in calls.items():
        stats = [v.aval for v in list(call.invars) + list(call.outvars)
                 if v.aval.dtype == jnp.float32]
        # lse out of the forward; lse and delta into each backward kernel
        assert len(stats) == (1 if kernel == "flash_fwd" else 2), kernel
        assert all(a.size == bh * sq for a in stats), (kernel, stats)
        rest = [v.aval for v in list(call.invars) + list(call.outvars)
                if v.aval.dtype != jnp.float32]
        assert all(a.shape == (bh, sq, d) for a in rest), (kernel, rest)


@pytest.mark.parametrize("sq,sk", [(1024, 1024), (512, 4096), (200, 200)])
def test_body_steps_are_the_blocks_that_touch_the_lower_triangle(sq, sk):
    lower = np.tril(np.ones((sq, sk), bool))
    for d in (64, 128):
        plan = tiling(sq, sk, d, causal=True)
        assert set(plan) == set(KERNELS)
        for kernel, t in plan.items():
            bq, bk = t["block_q"], t["block_k"]
            assert sq % bq == 0 and sk % bk == 0
            blocks = lower.reshape(sq // bq, bq, sk // bk, bk).any(axis=(1, 3))
            assert t["grid_steps"] == blocks.size, kernel
            assert t["body_steps"] == int(blocks.sum()), kernel
            full = tiling(sq, sk, d, causal=False)[kernel]
            assert full["body_steps"] == full["grid_steps"]


def test_tiling_follows_a_block_override_and_the_shape_alone():
    t = tiling(1024, 1024, 64, causal=True, block_q=128, block_k=256)
    assert all((k["block_q"], k["block_k"]) == (128, 256) for k in t.values())
    assert t["flash_fwd"]["grid_steps"] == 32
    assert t["flash_fwd"]["body_steps"] == sum(
        1 for qi in range(8) for ki in range(4) if ki * 256 <= qi * 128 + 127)
    assert tiling(1024, 1024, 64, True) == tiling(1024, 1024, 64, True)


# latent attention's heads: query and key 192 wide (128 without position,
# 64 rotary), value 128, and a scale that is neither 1/sqrt(d) nor a
# power of two; several blocks a sequence, a diagonal block in bands
@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 1e-5)])
@pytest.mark.parametrize("block", [128, 256])
def test_query_key_head_wider_than_value_head(block, dtype, tol):
    ks = jax.random.split(jax.random.key(3), 4)
    q, k = (jax.random.normal(kk, (2, 2, 256, 192), jnp.float32).astype(dtype)
            for kk in ks[:2])
    v = jax.random.normal(ks[2], (2, 2, 256, 128), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[3], (2, 2, 256, 128), jnp.float32)
    scale = 192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2

    def graded(attention):
        def f(q, k, v):
            o = attention(q, k, v, causal=True, scale=scale)
            return jnp.sum(o.astype(jnp.float32) * w), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, o), g = graded(_flash(block))(q, k, v)
    (_, o_ref), g_ref = graded(mha_reference)(q, k, v)
    assert o.shape == (2, 2, 256, 128) and o.dtype == dtype
    for name, got, want in zip(("out", "dq", "dk", "dv"), (o,) + g,
                               (o_ref,) + g_ref):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        a, r = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.abs(a - r).max() <= tol * np.abs(r).max(), name
