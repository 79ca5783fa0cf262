"""The embedding's table gradient (ops/embedding.py): which form the rule
picks, that the one-hot product gives the scatter-add's numbers, that the
float32 path is autodiff's bit for bit, that whole models train the same
whichever form runs, data parallelism, and that the compiled step holds
no scatter under the embedding when the product engages."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.models.transformer import (build_deepseek_v2,
                                             build_transformer)
from flexflow_tpu.ops import embedding
from flexflow_tpu.ops.embedding import AggrMode, _table_grad_rule
from flexflow_tpu.ops.base import FwdCtx
from flexflow_tpu.runtime import profiling
from tests.test_step_scopes import _step_hlo

# DeepSeek-V2's blocks at width 64, 128 rows of vocabulary
with open(os.path.join(os.path.dirname(__file__), "benchmark", "data",
                       "deepseek-v2-tiny.json")) as _f:
    SMALL = json.load(_f)["builder_kwargs"]


def run_op(op, params, *xs):
    return op.forward(params, list(xs), FwdCtx(training=False, rng=None))[0]


W, T = embedding._FAST_SCATTER_WIDTH, embedding._SLOW_SCATTER_TOKENS
# (rows, width, ids' shape, dtype, aggregation) -> does the product engage
RULE = {
    "deepseek-v2-tok_embed": (12800, 5120, (2, 4096), "bfloat16", "none", True),
    "gpt2-tok_embed": (50257, 1024, (4, 1024), "bfloat16", "none", False),
    "gpt2-pos_embed": (1024, 1024, (4, 1024), "bfloat16", "none", False),
    "deepseek-v2-whole-vocabulary": (102400, 5120, (2, 4096), "bfloat16",
                                     "none", True),
    "wide-few-rows-added": (12800, 5120, (1, T - 1), "bfloat16", "none",
                            False),
    "wide-at-the-rows-added": (12800, 5120, (T,), "bfloat16", "none", True),
    "at-the-fast-width": (12800, W, (2, 4096), "bfloat16", "none", False),
    "over-the-fast-width": (12800, W + 128, (2, 4096), "bfloat16", "none",
                            True),
    "ids-Bx1": (64, 5120, (T, 1), "bfloat16", "none", True),
    "float32-deepseek-v2": (12800, 5120, (2, 4096), "float32", "none", False),
    "float32-gpt2": (50257, 1024, (4, 1024), "float32", "none", False),
    "dlrm-sum": (1000000, 64, (128, 100), "bfloat16", "sum", False),
    "wide-sum": (64, 5120, (64, 64), "bfloat16", "sum", False),
    "wide-avg": (64, 5120, (64, 64), "bfloat16", "avg", False),
}


@pytest.mark.parametrize("case", RULE)
def test_table_grad_rule(case):
    rows, width, ids_shape, dtype, aggr, engages = RULE[case]
    form, why = _table_grad_rule(int(np.prod(ids_shape)), width,
                                 jnp.dtype(dtype), aggr)
    assert form == ("one_hot_product" if engages else "scatter_add"), why
    assert why
    # the op records what the rule says of its own shape and dtype: the
    # table's rows decide nothing
    for n in (rows, 7):
        op = _embedding_op(n, width, ids_shape, dtype, aggr)
        assert op.grad_impl_used == (form, why)


def _embedding_op(rows, width, ids_shape, dtype, aggr=AggrMode.NONE):
    m = ff.FFModel(ff.FFConfig(batch_size=ids_shape[0], compute_dtype=dtype))
    inp = m.create_tensor(ids_shape, dtype=ff.DataType.INT32, nchw=False)
    m.embedding(inp, rows, width, aggr=aggr)
    return m.ops[0]


def _autodiff(op, table, ids, weight):
    """The op as it was before it had a backward of its own."""
    def f(t):
        emb = jnp.take(t, ids.astype(jnp.int32), axis=0)
        if emb.ndim == 3 and op.aggr == AggrMode.SUM:
            emb = emb.sum(1)
        elif emb.ndim == 3 and op.aggr == AggrMode.AVG:
            emb = emb.mean(1)
        elif emb.ndim == 3 and op.output.num_dims == 2:
            emb = emb[:, 0, :]
        y = emb.astype(op.model.compute_dtype)
        return jnp.sum(y.astype(jnp.float32) * weight), y
    (_, y), g = jax.value_and_grad(f, has_aux=True)(table)
    return y, g


def _graded(op, table, ids, weight):
    def f(t):
        y = run_op(op, {"weight": t}, ids)
        return jnp.sum(y.astype(jnp.float32) * weight), y
    (_, y), g = jax.value_and_grad(f, has_aux=True)(table)
    return y, g


@pytest.mark.parametrize("ids_shape", [(T,), (T, 1), (8, T // 8)],
                         ids=["B", "Bx1", "BxS"])
def test_product_is_the_scatter_adds_gradient(ids_shape):
    """bf16 compute, rows wide enough that the rule engages, ids that
    repeat (and one that wraps, one out of range): the product's sums are
    the scatter-add's in another order."""
    width = W + 128
    op = _embedding_op(40, width, ids_shape, "bfloat16")
    rng = np.random.default_rng(5)
    table = jnp.asarray(rng.standard_normal((40, width), dtype=np.float32))
    ids = rng.integers(0, 40, ids_shape).astype(np.int32)
    ids.reshape(-1)[:3] = (7, -1, 40)   # a repeat's seed, row 39, dropped
    ids = jnp.asarray(ids)
    weight = jnp.asarray(rng.standard_normal(
        tuple(op.output.dims), dtype=np.float32))
    y, g = _graded(op, table, ids, weight)
    assert op.grad_impl_used[0] == "one_hot_product"
    want_y, want_g = _autodiff(op, table, ids, weight)
    assert y.dtype == jnp.bfloat16 and y.shape == tuple(op.output.dims)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want_y, np.float32))
    assert g.dtype == jnp.float32 and g.shape == (40, width)
    assert np.unique(np.asarray(ids), return_counts=True)[1].max() > 1
    assert float(jnp.abs(want_g).max()) > 1
    np.testing.assert_allclose(g, want_g, rtol=1e-6, atol=1e-6)
    prims = {e.primitive.name for e in jax.make_jaxpr(
        lambda t: _graded(op, t, ids, weight)[1])(table).eqns}
    assert "dot_general" in prims and not prims & {"scatter-add",
                                                   "scatter_add"}


@pytest.mark.parametrize("case", [
    ("float32", (96,), AggrMode.NONE), ("float32", (96, 1), AggrMode.NONE),
    ("float32", (8, 12), AggrMode.NONE), ("float32", (8, 12), AggrMode.SUM),
    ("float32", (8, 12), AggrMode.AVG), ("bfloat16", (8, 12), AggrMode.SUM),
    ("bfloat16", (8, 12), AggrMode.AVG), ("bfloat16", (96, 1), AggrMode.NONE),
    ("bfloat16", (8, 12), AggrMode.NONE)],
    ids=lambda c: f"{c[0]}-{'x'.join(map(str, c[1]))}-{c[2]}")
def test_scatter_add_path_is_autodiffs_bit_for_bit(case):
    dtype, ids_shape, aggr = case
    op = _embedding_op(40, 256, ids_shape, dtype, aggr)
    rng = np.random.default_rng(6)
    table = jnp.asarray(rng.standard_normal((40, 256), dtype=np.float32))
    ids = jnp.asarray(rng.integers(0, 40, ids_shape).astype(np.int32))
    weight = jnp.asarray(rng.standard_normal(
        tuple(op.output.dims), dtype=np.float32))
    y, g = jax.jit(lambda t: _graded(op, t, ids, weight))(table)
    assert op.grad_impl_used[0] == "scatter_add"
    want_y, want_g = jax.jit(lambda t: _autodiff(op, t, ids, weight))(table)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want_y, np.float32))
    np.testing.assert_array_equal(g, want_g)


def test_the_product_takes_the_rows_of_the_table_it_is_handed():
    """The host path hands forward() a compacted table: the gradient has
    its rows, not `num_entries`."""
    op = _embedding_op(1000, W + 128, (T,), "bfloat16")
    compact = jnp.ones((16, W + 128), jnp.float32)
    ids = jnp.arange(T, dtype=jnp.int32) % 16
    g = jax.grad(lambda t: run_op(op, {"weight": t}, ids)
                 .astype(jnp.float32).sum())(compact)
    assert op.grad_impl_used[0] == "one_hot_product"
    np.testing.assert_array_equal(g, np.full((16, W + 128), T / 16))


# ---------------------------------------------------------------------------
# whole models, the rule forced each way
# ---------------------------------------------------------------------------

def _force(monkeypatch, form):
    monkeypatch.setattr(embedding, "_table_grad_rule",
                        lambda tokens, width, dtype, aggr:
                        (form, "the test's"))


def _bf16_model(devices):
    cfg = ff.FFConfig()
    cfg.parse_args(["-b", "4", "-ll:tpu", str(devices), "--bf16"])
    return ff.FFModel(cfg)


def _deepseek(devices=1):
    m = _bf16_model(devices)
    tok, _ = build_deepseek_v2(m, 4, **SMALL)
    m.compile(ff.SGDOptimizer(m, lr=0.05), "sparse_categorical_crossentropy",
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=3)
    toks = np.random.default_rng(2).integers(
        0, 20, (4, SMALL["seq_length"]), dtype=np.int32)  # ids repeat
    m.set_batch({tok: toks}, np.roll(toks, -1, axis=1))
    return m


def _gpt():
    m = _bf16_model(1)
    build_transformer(m, 4, seq_length=32, num_layers=2, embed_dim=64,
                      num_heads=4, vocab_size=128)
    m.compile(ff.AdamOptimizer(m, alpha=1e-3),
              "sparse_categorical_crossentropy", [ff.MetricsType.ACCURACY])
    m.init_layers(seed=0)
    toks = np.random.default_rng(0).integers(0, 20, (4, 32), dtype=np.int32)
    pos = np.broadcast_to(np.arange(32, dtype=np.int32), (4, 32))
    m.set_batch(dict(zip(m.input_tensors, (toks, pos))),
                np.roll(toks, -1, axis=1))
    return m


MODELS = {"deepseek_v2": _deepseek, "transformer": _gpt}


def _losses(m, n=3):
    """`n` steps' losses, the step compiled without XLA's excess precision.
    With it (the default) XLA:CPU drops the rounding of a bf16 value that is
    widened again in the same fusion, so which of the embedding's cotangents
    are really bf16 depends on what consumes them: the two forms then differ
    by that rounding (2^-9 of a row), not by the order of their sums."""
    m._train_step_fn = m._build_train_step()
    step = getattr(m._train_step_fn, "fn", m._train_step_fn)
    m._train_step_fn = step.lower(*m._step_args()[0]).compile(
        compiler_options={"xla_allow_excess_precision": False})
    out = []
    for _ in range(n):
        m.train_iteration()
        m.sync()
        m.get_metrics()
        out.append(m.last_loss)
    return out


def _tables(m):
    return {key: np.asarray(a) for key, a in m.placement().items()
            if "embed" in key}


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_three_steps_are_the_same_in_either_form(devices, monkeypatch, kind):
    got = {}
    for form in ("one_hot_product", "scatter_add"):
        _force(monkeypatch, form)
        m = MODELS[kind]()
        got[form] = _losses(m), _tables(m)
        embeds = [op for op in m.ops if op._type == "Embedding"]
        assert embeds and all(op.grad_impl_used[0] == form for op in embeds)
    (la, ta), (lb, tb) = got.values()
    assert la[-1] < la[0]
    np.testing.assert_allclose(la, lb, rtol=1e-6)
    assert ta.keys() == tb.keys() and ta
    for key in ta:
        np.testing.assert_allclose(ta[key], tb[key], rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("n", [2, 4])
def test_data_parallel_product_equals_one_device(devices, monkeypatch, n):
    """The product contracts over the tokens the batch shards: the
    partitioner's all-reduce makes it the one-device gradient."""
    _force(monkeypatch, "one_hot_product")
    one = _deepseek(1)
    many = _deepseek(n)
    assert next(op for op in many.ops if op.name == "tok_embed").pc.dims[0] == n
    l1, lm = _losses(one, 1), _losses(many, 1)
    np.testing.assert_allclose(lm, l1, rtol=1e-5)
    t1, tm = _tables(one), _tables(many)
    for key in t1:
        np.testing.assert_allclose(tm[key], t1[key], rtol=2e-4, atol=2e-6)


# ---------------------------------------------------------------------------
# what the compiled step holds
# ---------------------------------------------------------------------------

def _under_tok_embed(text):
    """The instructions of an optimized HLO module whose `op_name` lies
    under `tok_embed`'s scope, fused ones included."""
    return [ln for ln in text.splitlines()
            if re.search(r'op_name="[^"]*ff\.op\.embedding\.tok_embed', ln)]


def test_no_scatter_under_tok_embed_when_the_product_engages(devices,
                                                             monkeypatch):
    _force(monkeypatch, "one_hot_product")
    m = _deepseek()
    _losses(m, 1)
    text = _step_hlo(m)
    lines = _under_tok_embed(text)
    assert any("ff.embed.grad" in ln for ln in lines)
    assert any("ff.embed.lookup" in ln for ln in lines)
    assert not [ln for ln in lines if re.search(r"\bscatter\(", ln)], lines
    assert [ln for ln in lines
            if "ff.embed.grad" in ln and re.search(r"\b(dot|convolution)\(",
                                                   ln)]
    # and the scope map names both spans, each in its phase
    spans = {(e.get("span"), e["phase"])
             for e in profiling.parse_hlo_scopes(text).values()}
    assert ("ff.embed.grad", "bwd") in spans
    assert ("ff.embed.lookup", "fwd") in spans


def test_a_scatter_under_tok_embed_when_it_does_not(devices):
    """At width 64 the rule leaves autodiff's scatter-add, whose scope is
    the lookup's, in the backward phase."""
    m = _deepseek()
    _losses(m, 1)
    assert next(op for op in m.ops
                if op.name == "tok_embed").grad_impl_used[0] == "scatter_add"
    text = _step_hlo(m)
    lines = _under_tok_embed(text)
    assert [ln for ln in lines if "ff.embed.lookup" in ln
            and re.search(r"\bscatter\(", ln)], lines
    assert not any("ff.embed.grad" in ln for ln in lines)
    spans = {(e.get("span"), e["phase"])
             for e in profiling.parse_hlo_scopes(text).values()}
    assert {("ff.embed.lookup", "fwd"), ("ff.embed.lookup", "bwd")} <= spans
