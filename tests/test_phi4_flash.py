"""Phi-4-mini-flash-reasoning on the training path
(models/transformer.build_phi4_flash: Mamba layers, window and full
attention, a gated memory unit and cross-attention that read one layer's
memory and keys, the head tied to the embedding's table) against its plain
reference (benchmark/reference/phi-4-mini-flash-reasoning.py) at a small
size on the CPU: logits, loss and every parameter's gradient; each
mechanism shown to matter; the selective scan's Pallas kernels in the
interpreter and XLA's chunked form against the sequential scan; the
published layout; the parameters of the cut; the step-size counter; the
cost model's price."""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.kernels import ssm_scan
from flexflow_tpu.models.transformer import (build_phi4_flash,
                                             phi4_flash_layer_kinds)
from flexflow_tpu.ops.attention import GroupedQueryAttention
from flexflow_tpu.ops.base import FwdCtx
from flexflow_tpu.ops.embedding import Embedding, TiedHead
from flexflow_tpu.ops.ssm import GatedMemoryUnit, Mamba

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# hidden 64: Mamba of 128 channels, a state of 4, dt_rank 4; 8 query heads
# over 4 key/value heads of 8; a window of 8 keys in 64 tokens; the cut's
# six layers (Mamba, window, memory, full, GMU, cross) of the published 32
SMALL = dict(seq_length=64, hidden_size=64, num_hidden_layers=32,
             layers=[0, 1, 16, 17, 18, 19], intermediate_size=96,
             num_attention_heads=8, num_key_value_heads=4, sliding_window=8,
             d_state=4, vocab_size=128)
BATCH = 2


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_phi4_flash", os.path.join(
            REPO, "benchmark", "reference", "phi-4-mini-flash-reasoning.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


@pytest.fixture(autouse=True)
def _own_counters(monkeypatch):
    """The program's counters are process-wide, and other files' tests
    read ratios of them (a worker runs several files in one process):
    what these tests count is put back."""
    from flexflow_tpu.runtime import profiling
    monkeypatch.setattr(profiling, "_counters", dict(profiling._counters))


def _model(dtype="float32", seed=3, impl=None):
    cfg = ff.FFConfig()
    cfg.parse_args(["-b", str(BATCH), "-ll:tpu", "1"]
                   + (["--bf16"] if dtype == "bfloat16" else []))
    m = ff.FFModel(cfg)
    (tok, _) = build_phi4_flash(m, BATCH, **SMALL)
    if impl is not None:
        for op in m.ops:
            if isinstance(op, (Mamba, GroupedQueryAttention)):
                op.impl = impl
    m.compile(ff.SGDOptimizer(m, lr=0.01), "sparse_categorical_crossentropy",
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=seed)
    (toks,), labels = ref.make_batch(jax.random.key(11), BATCH, **SMALL)
    m.set_batch({tok: np.asarray(toks)}, np.asarray(labels))
    return m, (toks,), labels


def _params(m):
    out = {}
    for key, a in m.placement().items():
        op, _, w = key.partition("/")
        if op != "batch":
            out.setdefault(op, {})[w] = jnp.asarray(np.asarray(a))
    return out


def _program_grads(m):
    """The gradient of the program's loss for the staged batch, of every
    parameter, as the train step computes it (its graph, its loss)."""
    loss_of, _ = m._loss_head()

    def loss(p):
        env, _ = m._run_graph(p, m._stats, m._batch, True,
                              jax.random.key(0), {}, {})
        return loss_of(env, m._batch["label"])[0]
    return jax.tree.map(np.asarray, jax.grad(loss)(m._params))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.fixture(scope="module")
def program():
    """The float32 program's parameters, logits and gradients, and its
    model, its first step not taken yet."""
    m, inputs, labels = _model()
    return dict(params=_params(m), inputs=inputs, labels=labels,
                logits=np.asarray(m.logits_batch(), np.float32),
                grads=_program_grads(m), model=m)


# Tolerances, as norm-wise relative errors.  float32: the same mathematics
# in the same precision in another order: 1e-6 to 6e-6 measured on the
# logits and the gradients (the scan's b_dt and A_log gradients are sums
# over every token, channel and state); the limits give that some twenty
# times of room.  bfloat16 compute: every operand of every product and
# every activation between ops is rounded to 8 bits, and the scan reads x
# and Delta in bf16; four seeds (3-6) read 0.0217-0.0233 on the logits,
# 1.9e-4 to 4.3e-4 on the loss and 0.054-0.070 on the worst gradient (a
# Mamba layer's or the first LayerNorms'); the limits give some two times
# of room, and the logits' fails 8-bit operands, which read 0.229-0.244
# on the same seeds (below).
@pytest.mark.parametrize("dtype,tol_logits,tol_loss,tol_grad", [
    ("float32", 1e-4, 1e-5, 1e-4), ("bfloat16", 0.05, 1e-3, 0.15)])
def test_program_matches_reference(devices, program, dtype, tol_logits,
                                   tol_loss, tol_grad):
    if dtype == "float32":
        m, inputs, labels = (program[k] for k in ("model", "inputs",
                                                  "labels"))
        logits, got = program["logits"], program["grads"]
    else:
        m, inputs, labels = _model(dtype=dtype)
        logits, got = m.logits_batch(), _program_grads(m)
    before = _params(m)
    want_loss, want_grads = jax.value_and_grad(ref.loss)(
        before, inputs, labels, **SMALL)
    want_logits = ref.logits(before, inputs, **SMALL)
    assert _rel(logits.astype(jnp.float32), want_logits) <= tol_logits
    worst = {f"{op}/{w}": _rel(got[op][w], want_grads[op][w])
             for op, ws in before.items() for w in ws}
    # the table, 13 norms of 2, two Mamba layers of 9, window and full
    # attention of 4, the GMU's 2, cross-attention's 2 (no W_k, W_v), six
    # MLPs of 3; the head has no weight of its own
    assert len(worst) == 1 + 26 + 2 * 9 + 2 * 4 + 2 + 2 + 6 * 3
    assert not {k: v for k, v in worst.items() if not v <= tol_grad}, worst
    # the train step itself: its first loss is the reference's
    m.train_iteration()
    m.sync()
    m.get_metrics()
    assert abs(m.last_loss - float(want_loss)) <= tol_loss * float(
        want_loss)


def _mamba_without(what):
    """The reference's Mamba layer with the convolution (its taps one
    identity tap), D or the z gate left out."""
    layer = ref._mamba.__wrapped__

    def mamba(x, norm, p, eps):
        if what == "no_conv":
            taps = jnp.zeros_like(p["conv_weight"]).at[-1].set(1.0)
            p = dict(p, conv_weight=taps)
        if what == "no_d":
            p = dict(p, d=jnp.zeros_like(p["d"]))
        if what != "no_z_gate":
            return layer(x, norm, p, eps)
        h = ref._layer_norm(x, norm, eps)
        d = p["out_proj"].shape[0]
        rank, n = p["dt_proj"].shape[0], p["a_log"].shape[1]
        u = (h @ p["in_proj"])[..., :d]
        s = u.shape[1]
        padded = jnp.pad(u, ((0, 0), (3, 0), (0, 0)))
        u = ref._silu(sum(p["conv_weight"][j] * padded[:, j:j + s]
                          for j in range(4)) + p["conv_bias"])
        dbc = u @ p["x_proj"]
        delta = ref._softplus(dbc[..., :rank] @ p["dt_proj"]
                              + p["dt_bias"])
        g = ref.scan(u, delta, -jnp.exp(p["a_log"]), dbc[..., rank:rank + n],
                     dbc[..., rank + n:]) + p["d"] * u
        return x + g @ p["out_proj"], g
    return jax.jit(mamba, static_argnames="eps")


def _patch(monkeypatch, what, params):
    """Leave one mechanism out of the reference, or get it wrong."""
    if what == "gmu_reads_h":
        # the unit gates its own projection, and not the memory
        def gmu(x, norm, p, m, eps):
            g = ref._layer_norm(x, norm, eps) @ p["in_proj"]
            return x + (g * ref._silu(g)) @ p["out_proj"]
        monkeypatch.setattr(ref, "_gmu", jax.jit(gmu, static_argnames="eps"))
    elif what == "cross_own_kv":
        # cross-attention makes k and v of its own input with the full
        # layer's weights, in place of reading those the full layer made
        attention, full = ref._attention, params["full_3"]

        def own(x, norm, p, kv, **kw):
            if kv is not None:
                h = ref._layer_norm(x, norm, kw["eps"])
                kv = (h @ full["w_k"], h @ full["w_v"])
            return attention(x, norm, p, kv, **kw)
        monkeypatch.setattr(ref, "_attention", own)
    elif what == "window_full":
        attention = ref._attention
        monkeypatch.setattr(ref, "_attention", lambda *a, **kw: attention(
            *a, **dict(kw, window=None)))
    else:
        monkeypatch.setattr(ref, "_mamba", _mamba_without(what))


@pytest.mark.parametrize("what", [
    "gmu_reads_h", "cross_own_kv", "no_conv", "no_d", "no_z_gate",
    "untied_head", "window_full"])
def test_each_mechanism_matters(program, monkeypatch, what):
    """With one mechanism left out of the reference, the comparison the
    test above makes fails by far: the logits, or for the tied head (whose
    forward is the same either way) the table's gradient, which is the
    lookup's and the head's summed."""
    p, inputs, labels = program["params"], program["inputs"], \
        program["labels"]
    if what == "untied_head":
        # the head reads a copy of the table that no gradient reaches
        head = ref._head
        monkeypatch.setattr(ref, "_head", lambda x, norm, t, eps: head(
            x, norm, jax.lax.stop_gradient(t), eps=eps))
        want = jax.grad(ref.loss)(p, inputs, labels, **SMALL)
        error = _rel(program["grads"]["tok_embed"]["weight"],
                     want["tok_embed"]["weight"])
    else:
        _patch(monkeypatch, what, p)
        error = _rel(program["logits"], ref.logits(p, inputs, **SMALL))
    assert error > 0.01, (what, error)


def test_bfloat16_limit_fails_8bit_operands(devices, monkeypatch):
    """What `benchmark/logit_check.py --lower operands` does on the chip:
    every projection's operands cut to 8 bits, the Mamba layer's, the
    GMU's and the tied head's among them."""
    from benchmark import logit_check

    m, inputs, _ = _model(dtype="bfloat16")
    want = ref.logits(_params(m), inputs, **SMALL)
    stated = _rel(m.logits_batch().astype(jnp.float32), want)
    logit_check.LOWER["operands"](monkeypatch.setattr)
    m._logits_fn = None                   # trace the forward pass again
    lowered = _rel(m.logits_batch().astype(jnp.float32), want)
    assert stated <= 0.05 < 0.1 < lowered, (stated, lowered)


# ---------------------------------------------------------------------------
# the selective scan: kernels, XLA's chunked form, the sequential scan
# ---------------------------------------------------------------------------

def _scan_inputs(seed, t, d, n, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (2, t, d)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (2, t, d)) - 2) \
        .astype(dtype)
    a = -jnp.arange(1, n + 1, dtype=jnp.float32)[None] \
        * jax.random.uniform(ks[2], (d, 1), minval=0.5, maxval=1.5)
    b = jax.random.normal(ks[3], (2, t, n))
    c = jax.random.normal(ks[4], (2, t, n))
    w = jax.random.normal(ks[5], (2, t, d))
    return (x, delta, a, b, c), w


@pytest.mark.parametrize("form,t,d,n,chunk", [
    ("kernels", 48, 2048, 4, 16),   # three chunks, two blocks of 128 lanes
    ("chunked", 48, 2048, 4, 16),
    ("chunked", 64, 128, 4, 16),    # four chunks
    ("chunked", 32, 1024, 16, 8),   # the published state, four chunks
    ("kernels", 32, 1024, 16, 8),
])
def test_scan_against_the_sequential_scan(form, t, d, n, chunk):
    """y and the gradients of x, Delta, A, B and C, the kernels in the
    Pallas interpreter and XLA's chunked form each against the scan one
    token at a time, at lengths that span several chunks and widths that
    span several channel blocks."""
    args, w = _scan_inputs(t + d + n, t, d, n)
    if form == "kernels":
        fn = lambda *v: ssm_scan.selective_scan(*v, chunk, True)
    else:
        fn = lambda *v: ssm_scan.selective_scan_chunked(*v, chunk)
    loss = lambda f: lambda *v: jnp.sum(f(*v) * w)
    got, got_g = jax.value_and_grad(loss(fn), argnums=range(5))(*args)
    want, want_g = jax.value_and_grad(
        loss(ssm_scan.selective_scan_reference), argnums=range(5))(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert _rel(fn(*args), ssm_scan.selective_scan_reference(*args)) < 1e-5
    for name, g, gw in zip("x delta a b c".split(), got_g, want_g):
        assert _rel(g, gw) < 1e-5, name


def test_scan_kernels_keep_the_caller_s_dtypes():
    """bf16 x and Delta in, y and their gradients out in bf16; A, B and C
    keep theirs; the arithmetic inside is float32."""
    args, w = _scan_inputs(1, 32, 128, 2, jnp.bfloat16)
    fn = lambda *v: ssm_scan.selective_scan(*v, 16, True)
    y = fn(*args)
    assert y.dtype == jnp.bfloat16
    grads = jax.grad(lambda *v: jnp.sum(fn(*v).astype(jnp.float32) * w),
                     argnums=range(5))(*args)
    assert [g.dtype for g in grads] == [a.dtype for a in args]
    want = ssm_scan.selective_scan_reference(*args)
    assert _rel(y.astype(jnp.float32), want.astype(jnp.float32)) < 1e-2


def test_scan_backward_sums_every_bit_of_the_products():
    """dB and dC to the last bit of a float32: A = 0 and Delta = 1, B one
    at the first token and C at the last, the cotangent a sign, so that
    dB_t = sum_d dy_{T-1} x_t and dC_t = sum_d x_0 dy_t; x is zero but in
    8 channels a token, spread over the sublanes, lanes and both blocks of
    1024, where it holds values such as 1 + 2^-9 + 2^-17 + 2^-20 with
    either sign.  Every product and every partial sum of eight is a
    float32 (multiples of 2^-20 under 16), so the kernels in the
    interpreter (two chunks) must give the float64 sums to 2e-7 of the
    products' magnitude; the two leading bfloat16 terms of each product
    alone miss them by some 1e-6."""
    t, d, n = 16, 2048, 4
    rng = np.random.default_rng(43)
    values = np.array([1 + 2 ** -9 + 2 ** -17 + 2 ** -20,
                       1.5 + 2 ** -10 + 2 ** -18 + 2 ** -19,
                       1.75 + 2 ** -9 + 2 ** -17 + 2 ** -20,
                       0.5 + 2 ** -10 + 2 ** -18 + 2 ** -20,
                       1.25 + 2 ** -8 + 2 ** -16 + 2 ** -19,
                       0.75 + 2 ** -11 + 2 ** -19 + 2 ** -20])
    x = np.zeros((1, t, d))
    for i in range(t):
        x[0, i, rng.choice(d, 8, replace=False)] = (
            rng.choice(values, 8) * np.where(rng.random(8) < 0.75, 1, -1))
    dy = np.where(rng.random((1, t, d)) < 0.5, 1.0, -1.0)
    b = np.zeros((1, t, n))
    b[:, 0] = 1
    c = np.zeros((1, t, n))
    c[:, -1] = 1
    args = [jnp.asarray(v, jnp.float32) for v in
            (x, np.ones((1, t, d)), np.zeros((d, n)), b, c)]
    _, vjp = jax.vjp(lambda *v: ssm_scan.selective_scan(*v, 8, True), *args)
    _, _, _, db, dc = vjp(jnp.asarray(dy, jnp.float32))
    for got, products in ((db, dy[0, -1] * x[0]), (dc, x[0, 0] * dy[0])):
        got = np.asarray(got, np.float64)[0]
        err = np.abs(got - products.sum(-1)[:, None])
        assert (err <= 2e-7 * np.abs(products).sum(-1)[:, None]).all()


def test_scan_tiling_and_refusals():
    """The cell's shape: blocks of 1024 channels, chunks of 128 tokens, 5
    x 64 grid steps; the chunk-start states a layer keeps are T / 128 x
    5120 x 16 float32, where every token's would be 2.7 GB; a chunk of the
    backward pushes one row of lanes a token and product through the MXU
    for the lane sums of dB / dC, 128 x 32, and not the product's 8."""
    tile = ssm_scan.tiling(8192, 5120, 16)
    assert (tile["lanes"], tile["channels_per_block"], tile["chunk"]) == (
        128, 1024, 128)
    assert tile["grid_steps"] == 5 * 64
    assert tile["saved_state_bytes"] == 64 * 5120 * 16 * 4
    assert tile["saved_state_bytes"] * 128 == 8192 * 5120 * 16 * 4
    assert tile["bwd_scratch_bytes"] < 64 * 2 ** 20
    assert tile["lane_sum_rows"] == 128 * 2 * 16 == 4096
    assert ssm_scan.unsupported_reason(8192, 5120) is None
    assert "channels" in ssm_scan.unsupported_reason(8192, 2560)
    assert "chunks" in ssm_scan.unsupported_reason(8200, 5120)


def test_the_op_s_kernels_and_xla_s_form_agree(devices):
    """The model with its Mamba ops and attention on the kernels in the
    Pallas interpreter (a block of 16 lanes: 128 channels) computes the
    reference's logits, as with XLA's forms."""
    m, inputs, labels = _model(impl="pallas_interpret")
    want = ref.logits(_params(m), inputs, **SMALL)
    assert _rel(m.logits_batch(), want) < 1e-4
    assert {op.impl_used for op in m.ops if isinstance(op, Mamba)} == {
        ("pallas_interpret", "set on the op")}


# ---------------------------------------------------------------------------
# the layout, the cut, the counter, the price
# ---------------------------------------------------------------------------

def test_builder_defaults_are_the_published_layout():
    """Over 32 layers with mb_per_layer 2: 9 Mamba layers (the last of
    them the memory), 8 window, 1 full, 7 GMU and 7 cross-attention
    layers, the reference's layout; every GMU reads the memory layer's
    second output and every cross layer the full layer's k and v; the head
    is tied to the token embedding."""
    kinds = phi4_flash_layer_kinds()
    assert kinds == ref.layout()
    assert [kinds.count(k) for k in ("mamba", "memory", "window", "full",
                                     "gmu", "cross")] == [8, 1, 8, 1, 7, 7]
    assert kinds[16:20] == ["memory", "full", "gmu", "cross"]
    m = ff.FFModel(ff.FFConfig())
    build_phi4_flash(m, 1, seq_length=64, hidden_size=64,
                     intermediate_size=96, num_attention_heads=8,
                     num_key_value_heads=4, d_state=4, vocab_size=128)
    mamba = [op for op in m.ops if isinstance(op, Mamba)]
    assert len(mamba) == 9 and [len(op.outputs) for op in mamba] == \
        [1] * 8 + [2]
    memory = mamba[-1].outputs[1]
    gmus = [op for op in m.ops if isinstance(op, GatedMemoryUnit)]
    assert len(gmus) == 7 and all(op.inputs[1] is memory for op in gmus)
    attn = [op for op in m.ops if isinstance(op, GroupedQueryAttention)]
    full = [op for op in attn if op.return_kv]
    assert len(full) == 1 and full[0].window is None
    cross = [op for op in attn if op.cross]
    assert len(cross) == 7 and all(
        op.inputs[1:] == full[0].outputs[1:] and not op.weights[1:2]
        or {w.name for w in op.weights} == {"w_q", "w_o"} for op in cross)
    assert [op.window for op in attn if not op.cross and not op.return_kv] \
        == [512] * 8
    assert {op.rotary_dim for op in attn} == {0}
    head = m.ops[-2]
    assert isinstance(head, TiedHead) and not head.weights
    assert isinstance(head.share_from, Embedding) \
        and head.share_from.name == "tok_embed"
    # the head reads every row of the table: no row-sparse host table
    assert not m._sparse_embed_structural_ok(head.share_from)
    with pytest.raises(ValueError, match="memory"):
        build_phi4_flash(ff.FFModel(ff.FFConfig()), 1, seq_length=64,
                         hidden_size=64, layers=[0, 18], d_state=4)
    with pytest.raises(ValueError, match="full"):
        build_phi4_flash(ff.FFModel(ff.FFConfig()), 1, seq_length=64,
                         hidden_size=64, layers=[16, 19, 17], d_state=4)


def test_parameters_of_the_cut():
    """The cell's graph at its published widths (built, not initialised)
    holds 697,072,640 parameters, as benchmark/phi4_flash.py counts and
    the configuration file states; the tied head holds none."""
    from benchmark import phi4_flash

    kw = dict(seq_length=8192, hidden_size=2560, num_hidden_layers=32,
              layers=[0, 1, 16, 17, 18, 19], intermediate_size=10240,
              num_attention_heads=40, num_key_value_heads=20,
              sliding_window=512, d_state=16, d_conv=4, expand=2,
              dt_rank=160, vocab_size=25008)
    m = ff.FFModel(ff.FFConfig())
    build_phi4_flash(m, 1, **kw)
    held = sum(math.prod(w.dims) for op in m.ops for w in op.weights)
    assert held == phi4_flash.parameters(**kw) == 697072640
    assert math.ceil(2560 / 16) == 160


def test_the_step_size_counter(devices, program):
    """A Mamba layer counts the mean of Delta over its tokens and
    channels; the drain gives the mean over the layers and steps, which
    with Mamba's initialisation lies in b_dt's range, [1e-3, 1e-1], give
    or take what W_dt adds."""
    m = program["model"]
    op = next(o for o in m.ops if isinstance(o, Mamba))
    x = jax.random.normal(jax.random.key(2), (2, 64, 64))
    counts = {}
    op.forward(program["params"][op.name], [x], FwdCtx(counters=counts))
    assert counts["ssm_layers"] == 1.0
    assert 1e-3 < float(counts["ssm_dt_mean"]) < 0.2
    m.train_iteration()
    m.sync()
    m.get_metrics()
    from flexflow_tpu.runtime import profiling
    assert 1e-3 < profiling.counters()["ssm_dt_mean"] < 0.2


def test_search_prices_the_new_work(devices):
    """The cost model prices every op of the graph; the Mamba op's price
    holds its scan's element work on the VPU besides its projections,
    and a split of anything but the batch is clamped away."""
    from flexflow_tpu.simulator.cost_model import CostModel
    from flexflow_tpu.simulator.machine import TPUMachineModel
    from flexflow_tpu.simulator.simulator import Simulator

    m = ff.FFModel(ff.FFConfig())
    build_phi4_flash(m, 2, **SMALL)
    cm = CostModel(TPUMachineModel.calibrated(num_devices=4), measure=False)
    one = ff.ParallelConfig(dims=(1, 1, 1))
    for op in m.ops:
        assert cm.op_time(op, one, "forward") > 0, op
    mamba = next(op for op in m.ops if isinstance(op, Mamba))
    assert mamba.vector_cost_per_sample() == pytest.approx(
        7 * 64 * 128 * 4)
    keep = cm.machine.vpu_ops_per_s
    cm2 = CostModel(TPUMachineModel.calibrated(num_devices=4,
                                               vpu_ops_per_s=keep / 10),
                    measure=False)
    assert cm2.op_time(mamba, one, "forward") > cm.op_time(mamba, one,
                                                           "forward")
    for op in m.ops:
        if isinstance(op, (Mamba, GatedMemoryUnit, TiedHead)):
            assert op.legalize_pc(ff.ParallelConfig(dims=(2, 2, 2))).dims \
                == (2, 1, 1)
    sim = Simulator(TPUMachineModel.calibrated(num_devices=4))
    dp = {op.name: ff.ParallelConfig(dims=(2,) + (1,) * (
        op.output.num_dims - 1)) for op in m.ops}
    assert 0 < sim.simulate_runtime(m, dp) < float("inf")
    assert sim.memory_per_device(m, dp)
