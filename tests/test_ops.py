"""Per-op numerics vs. independent references (torch CPU / numpy).

The reference validates ops only end-to-end (SURVEY.md §4); here each op is
unit-tested against torch.nn.functional (layout-converted NCHW↔NHWC) or
closed-form numpy.
"""

import numpy as np
import pytest
import torch
import torch.nn.functional as F

import jax
import jax.numpy as jnp

import flexflow_tpu as ff
from flexflow_tpu.ops.base import FwdCtx


def run_op(op, params, *xs, training=False, rng=None):
    ctx = FwdCtx(training=training, rng=rng,
                 stats_in={op.name: op.init_stats()} if op.init_stats() else {},
                 stats_out={} if training else None)
    return op.forward(params, list(xs), ctx)[0]


def make_model(batch=4):
    return ff.FFModel(ff.FFConfig(batch_size=batch, workers_per_node=1))


def test_conv2d_matches_torch():
    m = make_model()
    inp = m.create_tensor((4, 3, 16, 16))  # reference NCHW order
    out = m.conv2d(inp, 8, 3, 3, 2, 2, 1, 1)
    op = m.ops[0]
    assert out.dims == (4, 8, 8, 8)  # NHWC: (N, H', W', C)

    rng = np.random.default_rng(0)
    x_nchw = rng.standard_normal((4, 3, 16, 16), dtype=np.float32)
    k_hwio = rng.standard_normal((3, 3, 3, 8), dtype=np.float32)
    b = rng.standard_normal((8,), dtype=np.float32)

    y = run_op(op, {"kernel": jnp.asarray(k_hwio), "bias": jnp.asarray(b)},
               jnp.asarray(x_nchw.transpose(0, 2, 3, 1)))
    y_ref = F.conv2d(torch.from_numpy(x_nchw),
                     torch.from_numpy(k_hwio.transpose(3, 2, 0, 1)),
                     torch.from_numpy(b), stride=2, padding=1)
    np.testing.assert_allclose(np.asarray(y).transpose(0, 3, 1, 2),
                               y_ref.numpy(), rtol=2e-5, atol=2e-5)


def _conv_op(kernel, stride, padding, image, cin=3, cout=8, batch=2, **kw):
    m = make_model(batch)
    m.conv2d(m.create_tensor((batch, cin, *image)), cout, *kernel, *stride,
             *padding, **kw)
    return m.ops[0]


def _direct_conv(op, params, x):
    """The op as one strided convolution of the stored kernel."""
    (ph, pw) = op.padding
    y = jax.lax.conv_general_dilated(
        x, params["kernel"].astype(x.dtype), op.stride,
        ((ph, ph), (pw, pw)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=op.groups)
    if op.use_bias:
        y = y + params["bias"].astype(y.dtype)
    return ff.ops.conv2d.apply_activation(y, op.activation)


# kernel, stride, padding, image (h, w), the rest of conv2d's arguments
STEMS = {
    "alexnet-11/4/2-229": ((11, 11), (4, 4), (2, 2), (229, 229), {}),
    "resnet-7/2/3-224": ((7, 7), (2, 2), (3, 3), (224, 224), {}),
    "inception-3/2/0-299": ((3, 3), (2, 2), (0, 0), (299, 299), {}),
    "kernel-multiple-of-stride-8/4/2": ((8, 8), (4, 4), (2, 2), (61, 61), {}),
    "trailing-rows-cropped-8/4/0-15": ((8, 8), (4, 4), (0, 0), (15, 15), {}),
    "non-square-11/4/2-67x45": ((11, 11), (4, 4), (2, 2), (67, 45), {}),
    "no-bias": ((11, 11), (4, 4), (2, 2), (67, 67), {"use_bias": False}),
    "relu": ((11, 11), (4, 4), (2, 2), (67, 67),
             {"activation": ff.ActiMode.RELU}),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", STEMS)
def test_conv2d_space_to_depth_matches_direct(case, dtype):
    """An image stem is computed space-to-depth (ops/conv2d.py): output,
    kernel gradient and bias gradient are the direct convolution's of
    the stored kernel.  f32 to 1e-4 of the largest magnitude; bf16 as
    close to the f32 answer as the direct form in bf16 comes."""
    kernel, stride, padding, image, kw = STEMS[case]
    op = _conv_op(kernel, stride, padding, image, **kw)
    assert op.impl_used[0] == "space_to_depth", op.impl_used
    rng = np.random.default_rng(11)
    x32 = jnp.asarray(rng.standard_normal((2, *image, 3), dtype=np.float32))
    params = {"kernel": jnp.asarray(
        rng.standard_normal((*kernel, 3, 8), dtype=np.float32))}
    if op.use_bias:
        params["bias"] = jnp.asarray(rng.standard_normal(8, dtype=np.float32))
    weight = jnp.asarray(
        rng.standard_normal(op.output.dims, dtype=np.float32))

    def graded(forward, dt):
        def f(params):
            y = forward(params, x32.astype(dt)).astype(jnp.float32)
            return jnp.sum(y * weight), y
        (_, y), grads = jax.value_and_grad(f, has_aux=True)(params)
        return {"out": y, **grads}

    def worst(got, want):
        return {k: float(jnp.abs(got[k] - want[k]).max()
                         / jnp.abs(want[k]).max()) for k in want}

    oracle = graded(lambda p, x: _direct_conv(op, p, x), jnp.float32)
    got = graded(lambda p, x: run_op(op, p, x), dtype)
    assert got["out"].shape == tuple(op.output.dims)
    assert got["kernel"].shape == (*kernel, 3, 8)  # the stored layout's
    if dtype == "float32":
        bound = dict.fromkeys(oracle, 1e-4)
    else:
        direct = worst(graded(lambda p, x: _direct_conv(op, p, x), dtype),
                       oracle)
        bound = {k: 2 * v + 1e-3 for k, v in direct.items()}
    err = worst(got, oracle)
    assert all(err[k] <= bound[k] for k in err), (err, bound)


# kernel, stride, cin, groups -> does the rule take it
RULE = {
    "alexnet-stem": ((11, 11), (4, 4), 3, 1, True),
    "resnet-stem": ((7, 7), (2, 2), 3, 1, True),
    "inception-stem": ((3, 3), (2, 2), 3, 1, True),
    "one-channel": ((5, 5), (3, 3), 1, 1, True),
    "four-channels": ((5, 3), (2, 2), 4, 1, True),
    "stride-1": ((11, 11), (1, 1), 3, 1, False),
    "stride-not-square": ((5, 5), (2, 4), 3, 1, False),
    "groups": ((3, 3), (2, 2), 4, 2, False),
    "64-channels": ((3, 3), (2, 2), 64, 1, False),
    "5-channels": ((3, 3), (2, 2), 5, 1, False),
    "kernel-is-the-stride": ((2, 2), (2, 2), 3, 1, False),
    "1x1-stride-2": ((1, 1), (2, 2), 3, 1, False),
    "1x3-stride-2": ((1, 3), (2, 2), 3, 1, False),
}


@pytest.mark.parametrize("case", RULE)
def test_conv2d_space_to_depth_rule(case):
    """The rule is the shape's; a shape it leaves alone traces to the one
    convolution the op always was."""
    kernel, stride, cin, groups, engages = RULE[case]
    op = _conv_op(kernel, stride, (1, 1), (19, 19), cin=cin, groups=groups)
    form, why = op.impl_used
    assert form == ("space_to_depth" if engages else "direct"), why
    assert why
    params = {"kernel": jnp.ones((*kernel, cin // groups, 8)),
              "bias": jnp.ones(8)}
    x = jnp.ones((2, 19, 19, cin))
    prims = [str(e.primitive) for e in jax.make_jaxpr(
        lambda p, x: run_op(op, p, x))(params, x).eqns]
    convs = prims.count("conv_general_dilated")
    if engages:
        assert convs == 2  # the rearrangement of x, and the convolution
    else:
        assert convs == 1
        assert not {"pad", "reshape", "transpose"} & set(prims), prims
    np.testing.assert_allclose(run_op(op, params, x),
                               _direct_conv(op, params, x), rtol=1e-5)


def test_conv2d_shape_formula():
    # out = 1 + (in + 2p - k)/s  (reference conv_2d.cu:100-101)
    m = make_model()
    inp = m.create_tensor((4, 3, 229, 229))
    t = m.conv2d(inp, 64, 11, 11, 4, 4, 2, 2)
    assert t.dims == (4, 56, 56, 64)


def test_pool2d_max_matches_torch():
    m = make_model()
    inp = m.create_tensor((2, 4, 13, 13))
    out = m.pool2d(inp, 3, 3, 2, 2, 0, 0)
    assert out.dims == (2, 6, 6, 4)
    x = np.random.default_rng(1).standard_normal((2, 4, 13, 13), dtype=np.float32)
    y = run_op(m.ops[0], {}, jnp.asarray(x.transpose(0, 2, 3, 1)))
    y_ref = F.max_pool2d(torch.from_numpy(x), 3, 2)
    np.testing.assert_allclose(np.asarray(y).transpose(0, 3, 1, 2), y_ref.numpy(),
                               rtol=1e-6, atol=1e-6)


def test_pool2d_avg_excludes_padding():
    m = make_model()
    inp = m.create_tensor((1, 1, 4, 4))
    m.pool2d(inp, 3, 3, 2, 2, 1, 1, pool_type=ff.PoolType.AVG)
    x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
    y = run_op(m.ops[0], {}, jnp.asarray(x.transpose(0, 2, 3, 1)))
    y_ref = F.avg_pool2d(torch.from_numpy(x), 3, 2, padding=1,
                         count_include_pad=False)
    np.testing.assert_allclose(np.asarray(y).transpose(0, 3, 1, 2), y_ref.numpy(),
                               rtol=1e-6, atol=1e-6)


def test_linear_matches_numpy():
    m = make_model()
    inp = m.create_tensor((4, 32))
    out = m.dense(inp, 16, activation=ff.ActiMode.RELU)
    assert out.dims == (4, 16)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((4, 32), dtype=np.float32)
    w = rng.standard_normal((32, 16), dtype=np.float32)
    b = rng.standard_normal((16,), dtype=np.float32)
    y = run_op(m.ops[0], {"kernel": jnp.asarray(w), "bias": jnp.asarray(b)}, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), np.maximum(x @ w + b, 0), rtol=1e-5, atol=1e-5)


def test_embedding_sum_avg():
    m = make_model()
    inp = m.create_tensor((3, 5), dtype=ff.DataType.INT32, nchw=False)
    m.embedding(inp, num_entries=20, out_dim=6, aggr=ff.AggrMode.SUM)
    table = np.random.default_rng(3).standard_normal((20, 6), dtype=np.float32)
    idx = np.array([[0, 1, 2, 3, 4], [5, 5, 5, 5, 5], [19, 0, 19, 0, 1]], np.int32)
    y = run_op(m.ops[0], {"weight": jnp.asarray(table)}, jnp.asarray(idx))
    np.testing.assert_allclose(np.asarray(y), table[idx].sum(1), rtol=1e-6, atol=1e-6)

    m2 = make_model()
    inp2 = m2.create_tensor((3, 5), dtype=ff.DataType.INT32, nchw=False)
    m2.embedding(inp2, 20, 6, aggr=ff.AggrMode.AVG)
    y2 = run_op(m2.ops[0], {"weight": jnp.asarray(table)}, jnp.asarray(idx))
    np.testing.assert_allclose(np.asarray(y2), table[idx].mean(1), rtol=1e-6, atol=1e-6)


def test_flat_softmax_concat_elementwise():
    m = make_model()
    inp = m.create_tensor((2, 3, 4, 4))
    t = m.flat(inp)
    assert t.dims == (2, 48)

    x = np.random.default_rng(4).standard_normal((2, 4, 4, 3), dtype=np.float32)
    y = run_op(m.ops[0], {}, jnp.asarray(x))
    assert y.shape == (2, 48)

    # softmax
    sm = make_model()
    si = sm.create_tensor((2, 10), nchw=False)
    sm.softmax(si)
    logits = np.random.default_rng(5).standard_normal((2, 10), dtype=np.float32)
    p = run_op(sm.ops[0], {}, jnp.asarray(logits))
    np.testing.assert_allclose(np.asarray(p), F.softmax(torch.from_numpy(logits), -1).numpy(),
                               rtol=1e-5, atol=1e-6)

    # concat channel axis: reference axis=1 (NCHW) → native 3
    cm = make_model()
    a = cm.create_tensor((2, 3, 4, 4))
    b = cm.create_tensor((2, 5, 4, 4))
    out = cm.concat([a, b], axis=1)
    assert out.dims == (2, 4, 4, 8)

    # element binary
    em = make_model()
    u = em.create_tensor((2, 6), nchw=False)
    v = em.create_tensor((2, 6), nchw=False)
    em.add(u, v)
    xu = np.ones((2, 6), np.float32)
    xv = np.full((2, 6), 2.0, np.float32)
    y = run_op(em.ops[0], {}, jnp.asarray(xu), jnp.asarray(xv))
    np.testing.assert_allclose(np.asarray(y), xu + xv)


def test_batchnorm_train_matches_torch():
    m = make_model()
    inp = m.create_tensor((4, 3, 8, 8))
    m.batch_norm(inp, relu=True)
    op = m.ops[0]
    x = np.random.default_rng(6).standard_normal((4, 3, 8, 8), dtype=np.float32)
    scale = np.array([1.5, 0.5, 2.0], np.float32)
    bias = np.array([0.1, -0.2, 0.0], np.float32)
    y = run_op(op, {"scale": jnp.asarray(scale), "bias": jnp.asarray(bias)},
               jnp.asarray(x.transpose(0, 2, 3, 1)), training=True)
    bn = F.batch_norm(torch.from_numpy(x), None, None,
                      torch.from_numpy(scale), torch.from_numpy(bias),
                      training=True, eps=1e-5)
    np.testing.assert_allclose(np.asarray(y).transpose(0, 3, 1, 2),
                               F.relu(bn).numpy(), rtol=1e-4, atol=1e-4)


def test_dropout_train_and_eval():
    m = make_model()
    inp = m.create_tensor((8, 100), nchw=False)
    m.dropout(inp, rate=0.5)
    op = m.ops[0]
    x = jnp.ones((8, 100))
    y_eval = run_op(op, {}, x, training=False)
    np.testing.assert_allclose(np.asarray(y_eval), np.ones((8, 100)))
    y_tr = run_op(op, {}, x, training=True, rng=jax.random.key(0))
    arr = np.asarray(y_tr)
    assert set(np.unique(arr)).issubset({0.0, 2.0})
    assert 0.3 < (arr == 0).mean() < 0.7
