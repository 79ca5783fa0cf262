"""The index's scores as Pallas kernels (kernels/dsa_index.py) against the
``jax.numpy`` form of ops/dsa.py, in the Pallas interpreter on the CPU: the
scores, their three gradients in float32 and in bfloat16, the selection
taken from them, the rule that picks the path and what it records on the
op, and what ``tiling()`` says of a shape."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.kernels import dsa_index
from flexflow_tpu.ops import dsa
from flexflow_tpu.ops.attention import LatentAttention
from flexflow_tpu.ops.base import FwdCtx

# (batch, T, heads, d, block_q, block_k): one block; several key blocks a
# query block, ragged against the diagonal; a batch of two with a key block
# wider than a query block
SHAPES = [(1, 128, 2, 128, None, None),
          (1, 512, 3, 128, 256, 128),
          (2, 256, 2, 128, 128, 256)]
IDS = ["one-block", "k-blocks-a-q-block", "batch-2"]


def _rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.linalg.norm(b.ravel()))


def _operands(b, t, h, d, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, d), jnp.float32)
    w = jax.random.normal(ks[2], (b, t, h), jnp.float32)
    g = jnp.tril(jax.random.normal(ks[3], (b, t, t), jnp.float32))
    return q, k, w, g


def _kernel(q, k, w, grad_dtype, bq, bk, rope=None):
    return dsa_index.index_scores(q, k, w, rope, grad_dtype, bq, bk, True)


def _grads(f, q, k, w, g):
    """The gradients of ``f``'s causal entries under the cotangent ``g``
    (lower-triangular, as ``index_kl`` leaves it)."""
    return jax.grad(lambda *a: jnp.sum(jnp.tril(f(*a)) * g), (0, 1, 2))(q, k, w)


@pytest.mark.parametrize("b,t,h,d,bq,bk", SHAPES, ids=IDS)
def test_scores_equal_the_reference_on_the_causal_entries(b, t, h, d, bq, bk):
    """Within float32 summation order; above the diagonal ``NEG_INF`` or a
    score, never anything else."""
    q, k, w, _ = _operands(b, t, h, d)
    with jax.default_matmul_precision("highest"):
        got = _kernel(q, k, w, jnp.float32, bq, bk)
        want = jnp.einsum("bqh,bqhk->bqk", w, jax.nn.relu(
            jnp.einsum("bqhd,bkd->bqhk", q, k)))
        blocks = dsa.index_scores(q, k, w)
    assert got.shape == (b, t, t) and got.dtype == jnp.float32
    causal = np.tril(np.ones((t, t), bool))
    assert _rel(jnp.where(causal, got, 0.0), jnp.where(causal, want, 0.0)) <= 1e-5
    assert _rel(jnp.where(causal, got, 0.0), jnp.where(causal, blocks, 0.0)) <= 1e-5
    above = np.asarray(got)[:, ~causal]
    assert (np.isclose(above, np.asarray(want)[:, ~causal], rtol=1e-4, atol=1e-4)
            | (above == dsa_index.NEG_INF)).all()


@pytest.mark.parametrize("b,t,h,d,bq,bk", SHAPES, ids=IDS)
def test_float32_gradients_equal_the_reference(b, t, h, d, bq, bk):
    q, k, w, g = _operands(b, t, h, d, seed=1)
    with jax.default_matmul_precision("highest"):
        got = _grads(lambda *a: _kernel(*a, jnp.float32, bq, bk), q, k, w, g)
        want = _grads(lambda *a: dsa.index_scores(*a, jnp.float32), q, k, w, g)
    for name, x, y in zip(("dq", "dk", "dw"), got, want):
        assert x.shape == y.shape and x.dtype == jnp.float32, name
        assert _rel(x, y) <= 1e-5, name


@pytest.mark.parametrize("b,t,h,d,bq,bk", SHAPES, ids=IDS)
def test_bfloat16_gradients_equal_todays_to_bfloat16_rounding(b, t, h, d, bq,
                                                              bk):
    """``grad_dtype`` bfloat16, the step's: one pass over bfloat16 ``q``,
    ``k`` and ``ds``, float32 accumulation, against the ``jax.numpy``
    form's bfloat16 blocks and against the float32 gradient."""
    q, k, w, g = _operands(b, t, h, d, seed=2)
    got = _grads(lambda *a: _kernel(*a, jnp.bfloat16, bq, bk), q, k, w, g)
    todays = _grads(lambda *a: dsa.index_scores(*a, jnp.bfloat16), q, k, w, g)
    with jax.default_matmul_precision("highest"):
        exact = _grads(lambda *a: dsa.index_scores(*a, jnp.float32), q, k, w, g)
    for name, x, y, z in zip(("dq", "dk", "dw"), got, todays, exact):
        assert x.dtype == jnp.float32, name
        assert _rel(x, y) <= 8e-3, name          # two bfloat16 roundings
        assert _rel(x, z) <= _rel(y, z) * 1.5 + 1e-3, name


@pytest.mark.parametrize("grad_dtype,tol", [(jnp.float32, 1e-5),
                                            (jnp.bfloat16, 8e-3)])
def test_the_kernels_turn_the_queries_as_the_reference_does(grad_dtype, tol):
    """Queries that come with their rotary pairs set apart and not turned:
    the kernels turn them in VMEM and hand back the gradient of what came,
    as ``turn_halves`` and autodiff do for the ``jax.numpy`` form."""
    b, t, h, d, r = 2, 256, 3, 128, 64
    q, k, w, g = _operands(b, t, h, d, seed=4)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * (1e4 ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r))[None, :]
    rope = (jnp.cos(ang), jnp.sin(ang))
    with jax.default_matmul_precision("highest"):
        got = _kernel(q, k, w, grad_dtype, 128, 128, rope)
        want = dsa.index_scores(q, k, w, q_rope=rope)
        assert _rel(jnp.tril(got), jnp.tril(want)) <= 1e-5
        assert _rel(jnp.tril(want), jnp.tril(dsa.index_scores(q, k, w))) > 0.1
    grads = _grads(lambda *a: _kernel(*a, grad_dtype, 128, 128, rope),
                   q, k, w, g)
    with jax.default_matmul_precision("highest"):
        wants = _grads(lambda *a: dsa.index_scores(
            *a, grad_dtype, q_rope=rope), q, k, w, g)
    for name, x, y in zip(("dq", "dk", "dw"), grads, wants):
        assert _rel(x, y) <= tol, name


def test_selection_from_the_kernels_scores_is_the_references():
    """A seeded case without near-ties: ``select_topk`` keeps the same
    keys whichever form made the scores."""
    q, k, w, _ = _operands(1, 256, 4, 128, seed=3)
    w = jnp.abs(w) + 0.1     # a query's best scores are not its zeros
    with jax.default_matmul_precision("highest"):
        got = _kernel(q, k, w, jnp.float32, 128, 128)
        want = dsa.index_scores(q, k, w)
    causal = np.tril(np.ones((256, 256), bool))
    ranked = np.sort(np.where(causal, np.asarray(want)[0], -np.inf), axis=-1)
    gaps = ranked[64:, -16] - ranked[64:, -17]   # 16th against 17th largest
    assert gaps.min() > 1e-4                     # no near-tie at the cut
    np.testing.assert_array_equal(np.asarray(dsa.select_topk(got, 16)),
                                  np.asarray(dsa.select_topk(want, 16)))


@pytest.mark.parametrize("seq,heads,dim,bq,bk,nq,nk", [
    (8192, 64, 128, None, None, 16, 16),     # the cell's
    (512, 3, 128, 256, 128, 2, 4),
    (256, 2, 128, 128, 256, 2, 1)])
def test_tiling_counts_the_causal_blocks(seq, heads, dim, bq, bk, nq, nk):
    """Body steps are the (q block, k block) pairs that hold a pair with
    k <= q, counted by hand; the backward kernel makes three products a
    head where the forward makes one."""
    tiles = dsa_index.tiling(seq, heads, dim, bq, bk)
    assert set(tiles) == set(dsa_index.KERNELS)
    block_q, block_k = seq // nq, seq // nk
    by_hand = sum(1 for qi in range(nq) for ki in range(nk)
                  if ki * block_k <= qi * block_q + block_q - 1)
    for name, products in zip(dsa_index.KERNELS, (1, 3)):
        assert tiles[name] == dict(block_q=block_q, block_k=block_k,
                                   grid_steps=nq * nk, body_steps=by_hand,
                                   products_per_step=products * heads), name
    if bq is None:       # the cell: 0.53 of the square, under XLA's 0.5625
        assert by_hand == 136 and by_hand / (nq * nk) < 0.5625


@pytest.mark.parametrize("seq,heads,dim,word", [
    (32, 4, 16, "128 lanes"),                # the tests' small model
    (8192, 64, 192, "128 lanes"),
    (1000, 4, 128, "no divisor"),
    (65536, 64, 128, "VMEM")])
def test_the_rule_refuses_what_the_kernels_cannot_tile(seq, heads, dim, word):
    why = dsa_index.unsupported_reason(seq, heads, dim)
    assert why is not None and word in why
    with pytest.raises(ValueError, match=word):
        dsa_index.tiling(seq, heads, dim)
    assert dsa_index.unsupported_reason(8192, 64, 128) is None


def _op(seq, hidden, index, impl):
    m = ff.FFModel(ff.FFConfig())
    x = m.create_tensor((1, seq, hidden), nchw=False)
    op = LatentAttention(m, x, 2, q_lora_rank=24, kv_lora_rank=16,
                         qk_nope_head_dim=16, qk_rope_head_dim=8,
                         v_head_dim=16, rope_theta=8e7, eps=1e-5,
                         index=index)
    op.impl = impl
    keys = jax.random.split(jax.random.key(0), len(op.weights))
    params = {w.name: 0.3 * jax.random.normal(k, w.dims, jnp.float32)
              for w, k in zip(op.weights, keys)}
    return op, params, jax.random.normal(jax.random.key(1), (1, seq, hidden))


def test_the_op_takes_the_kernels_where_the_shape_tiles_and_says_so():
    """256 tokens under an index of 2 heads of 128: with the core in the
    interpreter the index's scores are the kernels', the output the XLA
    path's, and the op records both choices."""
    out = {}
    for impl in ("pallas_interpret", "xla"):
        op, params, x = _op(256, 64, (2, 128, 32), impl)
        with jax.default_matmul_precision("highest"):
            out[impl] = op.forward(params, [x], FwdCtx())[0]
        assert op.impl_used == (impl, "set on the op")
        assert op.index_impl_used == (impl, "set on the op")
    assert _rel(out["pallas_interpret"], out["xla"]) <= 1e-4


def test_the_ops_index_and_its_weights_gradient_are_the_xla_paths():
    """The kernels' path projects with ``W^I_q``'s rotary pairs set apart
    and leaves the turn to the kernels: the scores and the gradient of
    every index weight equal the XLA path's, which turns in XLA."""
    out = {}
    for impl in ("pallas_interpret", "xla"):
        op, params, x = _op(256, 64, (2, 128, 32), impl)
        c_q = jax.random.normal(jax.random.key(2), (1, 256, 24))
        g = jnp.tril(jax.random.normal(jax.random.key(3), (1, 256, 256)))
        with jax.default_matmul_precision("highest"):
            out[impl] = jax.value_and_grad(lambda p: jnp.sum(jnp.tril(
                op._index_scores(p, x, c_q, impl)) * g))(params)
    (a, ga), (b, gb) = out["pallas_interpret"], out["xla"]
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    index_weights = [n for n in ga if n.startswith("wi_")]
    assert len(index_weights) == 5
    for name in index_weights:
        assert _rel(ga[name], gb[name]) <= 1e-5, name


def test_the_op_falls_back_where_the_rule_refuses_and_says_why():
    """The tests' 32-token shape: the core takes its kernel, the index's
    scores XLA's blocks, and the record holds the rule's reason."""
    op, params, x = _op(32, 64, (4, 16, 8), "pallas_interpret")
    op.forward(params, [x], FwdCtx())
    assert op.impl_used[0] == "pallas_interpret"
    impl, why = op.index_impl_used
    assert impl == "xla" and why == dsa_index.unsupported_reason(32, 4, 16)
    assert "128 lanes" in why
