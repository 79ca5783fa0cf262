"""The cross-entropy from logits (losses.py ``neg_log_prob``): the loss
and the metric's CCE sum against the formula they replaced, kept here as
the plain reference (``log_softmax`` of an f32 copy folded to two
dimensions, then a gather of one value a row); the gradient; what the
traced program holds; and a head split along its classes."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.losses import Loss, neg_log_prob
from flexflow_tpu.metrics import LOG_MIN_VALUE, Metrics, MetricsType
from flexflow_tpu.models.transformer import build_transformer

SPARSE, DENSE = "sparse_categorical_crossentropy", "categorical_crossentropy"
SHAPES = {"BC": (16, 12), "BTC": (4, 6, 12)}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


# ---------------------------------------------------------------------------
# the parent's formulas (losses.py and metrics.py before the helper)
# ---------------------------------------------------------------------------

def parent_rows(preds, labels):
    """One -log p[label] a row, (rows, 1)."""
    preds = preds.astype(jnp.float32).reshape(-1, preds.shape[-1])
    labels = labels.reshape(preds.shape[0]).astype(jnp.int32)
    logp = jax.nn.log_softmax(preds, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)


def parent_sparse(preds, labels):
    nll = parent_rows(preds, labels)
    return jnp.sum(nll) / nll.shape[0]


def parent_dense(preds, labels):
    preds = preds.astype(jnp.float32).reshape(-1, preds.shape[-1])
    labels = labels.reshape(preds.shape)
    logp = jax.nn.log_softmax(preds, axis=-1)
    return jnp.sum(-labels.astype(jnp.float32) * logp) / preds.shape[0]


def parent_mse(preds, labels):
    preds = preds.astype(jnp.float32).reshape(-1, preds.shape[-1])
    diff = preds - labels.reshape(preds.shape).astype(jnp.float32)
    return 0.5 * jnp.sum(diff * diff) / preds.shape[0]


def parent_metric_sum(preds, labels, sparse):
    nlogp = jnp.minimum(
        -jax.nn.log_softmax(preds.astype(jnp.float32), axis=-1),
        -math.log(LOG_MIN_VALUE))
    if sparse:
        labels = labels.reshape(preds.shape[:-1]).astype(jnp.int32)
        return jnp.sum(jnp.take_along_axis(nlogp, labels[..., None], axis=-1))
    labels = labels.reshape(preds.shape).astype(jnp.float32)
    return jnp.sum(jnp.where(labels > 0.0, labels * nlogp, 0.0))


def _logits(shape, dtype, seed=0, scale=3.0):
    rng = np.random.default_rng(seed)
    x = scale * rng.standard_normal(shape).astype(np.float32)
    return (jnp.asarray(x).astype(dtype),
            jnp.asarray(rng.integers(0, shape[-1], shape[:-1]), jnp.int32))


def _one_hot(labels, classes):
    return jnp.asarray(np.eye(classes, dtype=np.float32)[np.asarray(labels)])


# ---------------------------------------------------------------------------
# values
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_sparse_cce_is_the_parents(dtype, shape, seed):
    """On f32 logits bit for bit, primitive by primitive (inside one
    `jit` XLA's CPU fusions choose the order of the last sum over the
    rows, for either formula); on bf16 logits every exponent and sum is
    still f32, so the two agree as closely."""
    x, y = _logits(SHAPES[shape], DTYPES[dtype], seed)
    loss = Loss(SPARSE)
    got, want = loss(x, y), parent_sparse(x, y)
    assert got.dtype == jnp.float32 and got.shape == ()
    if dtype == "f32":
        assert float(got) == float(want)
    assert float(got) == pytest.approx(float(want), abs=1e-6)
    # a row's value is the parent's to the bit, compiled or not
    for rows in (neg_log_prob, jax.jit(neg_log_prob)):
        np.testing.assert_array_equal(
            np.asarray(rows(x, y)).reshape(-1),
            np.asarray(parent_rows(x, y)).reshape(-1))
    assert float(jax.jit(loss)(x, y)) == pytest.approx(
        float(jax.jit(parent_sparse)(x, y)), rel=1e-6)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_gradient_is_softmax_minus_onehot_over_the_rows(dtype, shape):
    x, y = _logits(SHAPES[shape], DTYPES[dtype])
    grad = jax.jit(jax.grad(Loss(SPARSE)))(x, y)
    assert grad.dtype == x.dtype and grad.shape == x.shape
    rows, classes = math.prod(x.shape[:-1]), x.shape[-1]
    want = (jax.nn.softmax(x.astype(jnp.float32), axis=-1)
            - _one_hot(y, classes)) / rows
    tol = dict(atol=1e-7, rtol=0 if dtype == "f32" else 2 ** -8)  # half a bf16 step
    np.testing.assert_allclose(np.asarray(grad, np.float32), want, **tol)
    np.testing.assert_allclose(
        np.asarray(grad, np.float32),
        np.asarray(jax.grad(parent_sparse)(x, y), np.float32), **tol)


@pytest.mark.parametrize("given", ["B", "B1", "BT", "BT1", "flat"])
def test_labels_in_every_shape_the_loaders_give(given):
    shape = SHAPES["BC" if given in ("B", "B1") else "BTC"]
    x, y = _logits(shape, jnp.float32)
    as_given = {"B": y, "B1": y[:, None], "BT": y, "BT1": y[..., None],
                "flat": y.reshape(-1)}[given]
    for dtype in (jnp.int32, jnp.int64, jnp.float32):  # Keras hands floats
        assert float(Loss(SPARSE)(x, as_given.astype(dtype))) == \
            float(parent_sparse(x, y))


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_dense_cce_keeps_its_value_and_gradient(dtype, shape):
    """gradient: softmax * sum(labels) - labels, over the rows; the
    labels need not be one-hot (label smoothing, soft targets)."""
    x, y = _logits(SHAPES[shape], DTYPES[dtype])
    classes = x.shape[-1]
    soft = 0.9 * _one_hot(y, classes) + 0.1 / classes
    rows = math.prod(x.shape[:-1])
    for labels in (_one_hot(y, classes), 2.0 * soft):
        got, want = Loss(DENSE)(x, labels), parent_dense(x, labels)
        if dtype == "f32":
            assert float(got) == float(want)
        assert float(got) == pytest.approx(float(want), abs=1e-6)
        grad = jax.grad(Loss(DENSE))(x, labels)
        assert grad.dtype == x.dtype
        p = jax.nn.softmax(x.astype(jnp.float32), axis=-1)
        np.testing.assert_allclose(
            np.asarray(grad, np.float32),
            (p * labels.sum(-1, keepdims=True) - labels) / rows,
            atol=1e-6, rtol=0 if dtype == "f32" else 2 ** -8)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_mse_is_untouched(shape):
    x, y = _logits(SHAPES[shape], jnp.float32)
    target = _one_hot(y, x.shape[-1])
    assert float(Loss("mean_squared_error")(x, target)) == \
        float(parent_mse(x, target))
    np.testing.assert_allclose(
        jax.grad(Loss("mse"))(x, target),
        (x - target) / math.prod(x.shape[:-1]), atol=1e-7)
    # a vector of outputs is a batch of scalars, as it was
    v, t = x.reshape(-1), target.reshape(-1)
    assert float(Loss("mse")(v, t)) == float(0.5 * jnp.sum((v - t) ** 2) / v.size)


def test_a_row_of_equal_logits_and_a_far_label():
    """log(C) for a flat row; a label 200 under the maximum reads 200,
    not infinity, and its gradient is finite."""
    flat = jnp.zeros((2, 8), jnp.float32)
    assert float(Loss(SPARSE)(flat, jnp.array([0, 7]))) == \
        pytest.approx(math.log(8), rel=1e-6)
    far = jnp.array([[200.0, 0.0, 0.0, 0.0]], jnp.float32)
    assert float(Loss(SPARSE)(far, jnp.array([1]))) == pytest.approx(200.0)
    assert np.isfinite(jax.grad(Loss(SPARSE))(far, jnp.array([1]))).all()


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("loss_type", [SPARSE, DENSE])
def test_metric_cce_sum_is_the_parents_clamp_included(loss_type, dtype, shape):
    sparse = loss_type == SPARSE
    asked = (MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY if sparse
             else MetricsType.CATEGORICAL_CROSSENTROPY)
    x, y = _logits(SHAPES[shape], DTYPES[dtype])
    flat = np.array(x, np.float32).reshape(-1, x.shape[-1])
    lab = np.array(y).reshape(-1)
    flat[2, 5], lab[2] = -60.0, 5            # p < LOG_MIN_VALUE: the clamp
    assert math.exp(-60.0) < LOG_MIN_VALUE
    x = jnp.asarray(flat.reshape(x.shape)).astype(x.dtype)
    y = jnp.asarray(lab.reshape(y.shape))
    labels = y if sparse else _one_hot(y, x.shape[-1])
    m = Metrics(loss_type, [asked])
    key = "sparse_cce_loss" if sparse else "cce_loss"
    got = m.compute(x, labels, from_logits=True)[key]
    want = parent_metric_sum(x, labels, sparse)
    if dtype == "f32":
        assert float(got) == float(want)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    one = m.compute(x.reshape(-1, x.shape[-1])[2:3],
                    labels.reshape((-1,) + labels.shape[y.ndim:])[2:3],
                    from_logits=True)[key]
    assert float(one) == pytest.approx(-math.log(LOG_MIN_VALUE), rel=1e-6)


# ---------------------------------------------------------------------------
# what the traced program holds
# ---------------------------------------------------------------------------

def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _loss(x, y):
    return Loss(SPARSE)(x, y)


def _metric(x, y):
    m = Metrics(SPARSE, [MetricsType.ACCURACY,
                         MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
    return m.compute(x, y, from_logits=True)["sparse_cce_loss"]


@pytest.mark.parametrize("grad", [False, True], ids=["value", "gradient"])
@pytest.mark.parametrize("what", ["loss", "metric", "parent"])
def test_no_log_softmax_and_no_gather_over_the_classes(what, grad):
    """bf16 (4, 16, 50) logits: no call of `log_softmax`, and no gather
    or scatter-add whose operand is f32 and as wide as the classes (what
    made the TPU write 823 MB twice for GPT-2's 4096 labels).  The
    parent's formula, traced the same way, holds both."""
    shape = (4, 16, 50)
    fn = {"loss": _loss, "metric": _metric, "parent": parent_sparse}[what]
    if grad:
        fn = jax.grad(fn)
    eqns = list(_equations(jax.make_jaxpr(fn)(
        jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape[:-1], jnp.int32)).jaxpr))
    calls = [e for e in eqns if e.params.get("name") == "log_softmax"]
    moved = [e for e in eqns
             if e.primitive.name in ("gather", "scatter-add", "scatter_add")
             and e.invars[0].aval.dtype == jnp.float32
             and e.invars[0].aval.shape[-1:] == shape[-1:]]
    if what == "parent":
        assert calls and moved, (calls, moved)
        return
    assert not calls and not moved, (calls, moved)
    # nor is the tensor folded: nothing class-wide has another shape
    wide = {v.aval.shape for e in eqns for v in e.outvars
            if getattr(v.aval, "shape", ())[-1:] == shape[-1:]}
    assert wide <= {shape}, wide


# ---------------------------------------------------------------------------
# a head split along its classes
# ---------------------------------------------------------------------------

VOCAB = 256


def _lm(strategies, chips):
    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32",
                      strategies=dict(strategies))
    cfg.parse_args(["-ll:tpu", str(chips)])
    m = ff.FFModel(cfg)
    tok, pos, _ = build_transformer(m, 4, seq_length=8, num_layers=1,
                                    embed_dim=32, num_heads=4,
                                    vocab_size=VOCAB)
    m.compile(ff.SGDOptimizer(m, lr=0.05), SPARSE, [MetricsType.ACCURACY])
    m.init_layers(seed=5)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, VOCAB, (4, 8), dtype=np.int32)
    m.set_batch({tok: toks,
                 pos: np.broadcast_to(np.arange(8, dtype=np.int32), (4, 8))},
                np.roll(toks, -1, axis=1))
    return m


def _train(m, steps=4):
    losses = []
    for _ in range(steps):
        m.train_iteration()
        m.sync()
        m.get_metrics()
        losses.append(m.last_loss)
    return losses


def _gathers_under_the_loss(m):
    """Result types of the all-gathers of the compiled train step whose
    `op_name` lies under `ff.loss` and that are as wide as the classes."""
    step = getattr(m._train_step_fn, "fn", m._train_step_fn)
    text = step.lower(*m._step_args()[0]).compile().as_text()
    found = re.findall(
        r"= ([a-z0-9]+\[[\d,]*\])\S* all-gather(?:-start)?\(.*"
        r"op_name=\"[^\"]*ff\.loss[^\"]*\"", text)
    return [t for t in found
            if VOCAB in map(int, re.findall(r"\d+", t.split("[")[1]))]


def test_a_class_split_head_gathers_no_classes_for_the_loss(devices):
    split = {"lm_head": ff.ParallelConfig(dims=(1, 1, 4))}
    one, four = _lm({}, 1), _lm(split, 4)
    spec = four._params["lm_head"]["kernel"].sharding.spec
    assert len(spec) >= 2 and spec[1] is not None, spec
    want, got = _train(one), _train(four)
    assert _gathers_under_the_loss(four) == []
    assert want[-1] < want[0]
    np.testing.assert_allclose(got, want, rtol=2e-5)
    np.testing.assert_allclose(four.get_parameter("lm_head", "kernel"),
                               one.get_parameter("lm_head", "kernel"),
                               rtol=2e-4, atol=2e-6)
