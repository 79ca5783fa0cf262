"""MiMo-V2-Flash on the training path (models/transformer.build_mimo_v2_flash:
grouped-query attention with rotary positions on part of the head and a
scaled value, window layers with a learned sink among full layers,
sigmoid-routed experts without a shared expert) against its plain reference
(benchmark/reference/mimo-v2-flash.py) at a small size on the CPU: logits,
loss and every parameter's gradient in float32 and bfloat16 compute; the op
against the reference's layer with the sink, the value scale and the partial
rotary each shown to matter; the flash kernels with groups and a sink in the
Pallas interpreter against XLA's core; the chip's shares against the uncut
layers; the published patterns; the sink's counter; the cost model's price."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.kernels.flash_attention import (flash_attention,
                                                 kernel_names, tiling)
from flexflow_tpu.models.transformer import (build_mimo_v2_flash,
                                             mimo_v2_layer_pattern)
from flexflow_tpu.ops.attention import GroupedQueryAttention, attention_core
from flexflow_tpu.ops.base import FwdCtx
from flexflow_tpu.ops.moe import RoutedExperts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# hidden 64; full layers 8 query heads over 2 key/value heads, window layers
# 8 over 4, heads 24 | 16 with rotary on 8 (int(0.334 * 24)), a window of 8
# keys in 64 tokens; 16 experts, top-4, no shared expert, layer 0 dense;
# this chip holds experts 4..7
SMALL = dict(seq_length=64, hidden_size=64, num_hidden_layers=4,
             hybrid_layer_pattern=[0, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1],
             intermediate_size=96, moe_intermediate_size=32,
             num_attention_heads=8, num_key_value_heads=2, head_dim=24,
             v_head_dim=16, swa_num_attention_heads=8,
             swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16,
             partial_rotary_factor=0.334, rope_theta=5e6, swa_rope_theta=1e4,
             sliding_window=8, attention_value_scale=0.707,
             layernorm_epsilon=1e-5, n_routed_experts=16,
             num_experts_per_tok=4, vocab_size=128, experts_held=4,
             first_expert=4, tile_rows=8)
BATCH = 2


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_mimo_v2", os.path.join(REPO, "benchmark", "reference",
                                          "mimo-v2-flash.py"))
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


def _model(kw=SMALL, dtype="float32", lr=1.0, seed=3):
    cfg = ff.FFConfig()
    cfg.parse_args(["-b", str(BATCH), "-ll:tpu", "1"]
                   + (["--bf16"] if dtype == "bfloat16" else []))
    m = ff.FFModel(cfg)
    (tok, _) = build_mimo_v2_flash(m, BATCH, **kw)
    m.compile(ff.SGDOptimizer(m, lr=lr), "sparse_categorical_crossentropy",
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=seed)
    (toks,), labels = ref.make_batch(jax.random.key(11), BATCH, **kw)
    m.set_batch({tok: np.asarray(toks)}, np.asarray(labels))
    return m, (toks,), labels


def _params(m):
    out = {}
    for key, a in m.placement().items():
        op, _, w = key.partition("/")
        if op != "batch":
            out.setdefault(op, {})[w] = jnp.asarray(np.asarray(a))
    return out


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def _step(m):
    m.train_iteration()
    m.sync()
    m.get_metrics()
    return m.last_loss


# Tolerances, as norm-wise relative errors.  float32: both sides compute the
# same mathematics in the same precision in another order (an expert choice
# would have to tie to 1e-7 to move); a gradient is read back as a
# difference of parameters at lr 1: 1e-4 is a hundred times what was
# measured (5e-7 on the logits).  bfloat16 compute: every operand of every
# product is rounded to 8 bits and the float32 router reads activations
# that were, so some tokens' experts differ from the reference's, and each
# such token's rows move wholesale.  Five seeds read 0.020-0.037 on the
# logits (this one 0.037), 2.2e-5 to 8.9e-4 on the loss (this one 8.9e-4)
# and 0.16-0.29 on the worst gradient, always a router's (this one 0.29;
# 0.10-0.17 on every other weight); the limits give those some two times of
# room.  The logits' limit fails 8-bit operands (below), which read
# 0.128-0.145 on the same five seeds.
@pytest.mark.parametrize("dtype,tol_logits,tol_loss,tol_grad", [
    ("float32", 1e-4, 1e-5, 1e-4), ("bfloat16", 0.08, 3e-3, 0.5)])
def test_program_matches_reference(devices, dtype, tol_logits, tol_loss,
                                   tol_grad):
    m, inputs, labels = _model(dtype=dtype)
    before = _params(m)
    want_loss, want_grads = jax.value_and_grad(ref.loss)(
        before, inputs, labels, **SMALL)
    want_logits = ref.logits(before, inputs, **SMALL)
    assert _rel(m.logits_batch().astype(jnp.float32),
                want_logits) <= tol_logits
    got_loss = _step(m)
    assert abs(got_loss - float(want_loss)) <= tol_loss * float(want_loss)
    after = _params(m)
    worst = {f"{op}/{w}": _rel(np.asarray(before[op][w])
                               - np.asarray(after[op][w]),   # lr is 1
                               want_grads[op][w])
             for op, ws in before.items() for w in ws}
    # embedding, 9 norms, 2 full attentions of 4, 2 window ones of 5 (the
    # sink), the dense MLP's 3, three expert layers of 4, the head
    assert len(worst) == 1 + 9 + 2 * 4 + 2 * 5 + 3 + 3 * 4 + 1
    assert not {k: v for k, v in worst.items() if not v <= tol_grad}, worst
    # the sink learns: zero at first, a gradient of its own
    assert float(jnp.abs(want_grads["attn_1"]["sink"]).max()) > 0


def test_bfloat16_limit_fails_8bit_operands(devices, monkeypatch):
    """What `benchmark/logit_check.py --lower operands` does on the chip."""
    from benchmark import logit_check

    m, inputs, _ = _model(dtype="bfloat16")
    want = ref.logits(_params(m), inputs, **SMALL)
    stated = _rel(m.logits_batch().astype(jnp.float32), want)
    logit_check.LOWER["operands"](monkeypatch.setattr)
    m._logits_fn = None                   # trace the forward pass again
    lowered = _rel(m.logits_batch().astype(jnp.float32), want)
    assert stated <= 0.08 < 0.1 < lowered, (stated, lowered)


# ---------------------------------------------------------------------------
# the op against the reference's layer, and what each mechanism adds
# ---------------------------------------------------------------------------

def _bare_op(cls, *args, **kw):
    m = ff.FFModel(ff.FFConfig())
    x = m.create_tensor((2, 64, 64), nchw=False)
    op = cls(m, x, *args, **kw)
    op.impl = "xla"
    return op


def _random(op, seed):
    keys = jax.random.split(jax.random.key(seed), len(op.weights))
    return {w.name: (1.0 if w.name == "sink" else 0.3)
            * jax.random.normal(k, w.dims, jnp.float32)
            for w, k in zip(op.weights, keys)}


WINDOW = dict(head_dim=24, v_head_dim=16, rotary_dim=8,
              rope_theta=1e4, window=8, sink=True, value_scale=0.707)


def _ref_cfg(heads=8, kv_heads=4, rotary=8, sink=True, value_scale=0.707,
             window=8, theta=1e4):
    return (heads, kv_heads, 24, 16, rotary, theta, window, sink,
            value_scale, 1e-5)


@pytest.mark.parametrize("left_out", [
    None, dict(sink=False), dict(value_scale=1.0), dict(rotary=24)])
def test_the_op_against_the_reference_and_each_mechanism(left_out):
    """A window layer with a sink, 8 query heads over 4 key/value heads:
    the output and every weight's gradient, the sink's among them, within
    1e-5 of the reference's layer; with the sink, the value scale or the
    partial rotary left out of the reference, the same comparison fails
    by far."""
    op = _bare_op(GroupedQueryAttention, 8, 4, **WINDOW)
    p = _random(op, 0)
    x = jax.random.normal(jax.random.key(1), (2, 64, 64), jnp.float32)
    g = jax.random.normal(jax.random.key(2), (2, 64, 64), jnp.float32)
    ones = {"scale": jnp.ones((64,))}
    cfg = _ref_cfg(**(left_out or {}))
    with jax.default_matmul_precision("highest"):
        h = ref._rms_norm(x, ones["scale"], 1e-5)
        got, got_g = jax.value_and_grad(
            lambda p: jnp.sum(op.forward(p, [h], FwdCtx())[0] * g))(p)
        want, want_g = jax.value_and_grad(
            lambda p: jnp.sum((ref._attention(x, ones, p, cfg=cfg) - x)
                              * g))(p)
        out = op.forward(p, [h], FwdCtx())[0]
        want_out = ref._attention(x, ones, p, cfg=cfg) - x
    errors = [_rel(out, want_out)] + [_rel(got_g[w], want_g[w]) for w in p]
    if left_out is None:
        assert max(errors) <= 1e-5, errors
        assert float(jnp.abs(got_g["sink"]).max()) > 0
    else:
        assert errors[0] > 0.03, (left_out, errors)


def _qkv(seed, heads, kv_heads, seq=64, d=24, dv=16):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (2, heads, seq, d))
    k = jax.random.normal(ks[1], (2, kv_heads, seq, d))
    v = jax.random.normal(ks[2], (2, kv_heads, seq, dv))
    w = jax.random.normal(ks[3], (2, heads, seq, dv))
    return q, k, v, w, jax.random.normal(ks[4], (heads,))


@pytest.mark.parametrize("kv_heads,window,sink,blocks,seq", [
    # the cases before the grids were banded keep their ids
    pytest.param(2, None, False, (16, 16), 64, id="2-None-False-blocks0"),
    pytest.param(2, 8, True, (16, 16), 64, id="2-8-True-blocks1"),
    pytest.param(1, 8, True, (32, 16), 64, id="1-8-True-blocks2"),
    pytest.param(4, 40, True, (16, 32), 64, id="4-40-True-blocks3"),
    pytest.param(8, None, True, (16, 16), 64, id="8-None-True-blocks4"),
    (8, 1, True, (None, None), 64), (1, 16, False, (16, 16), 64),
    (2, 24, True, (16, 16), 64), (4, 12, False, (32, 16), 64),
    (1, 100, True, (None, None), 64), (2, 128, True, (None, None), 512),
    (4, 200, False, (128, 128), 512), (8, 300, True, (256, 256), 512),
    (1, 513, False, (None, None), 1024), (2, 128, False, (128, 256), 512)])
def test_flash_kernels_with_groups_and_a_sink(kv_heads, window, sink, blocks,
                                              seq):
    """The three kernels in the Pallas interpreter, 8 query heads over
    fewer key/value heads (and as many), with and without a sink: the
    output, the rows' log-sum-exp and the gradients of q, k, v and the
    sink against XLA's form of the same core.  Under a window the banded
    grids and the band's tiles: window 1, windows narrower than a block,
    as wide as one, not dividing one, wider than two and longer than the
    sequence; the rule's blocks (no caps), capped blocks whose tiles are
    cells of 128, and unequal caps (square blocks under the smaller)."""
    names = kernel_names(window, sunk=sink)
    assert ("_sink_" in names[0]) == sink      # the forward's body differs
    assert names[1:] == kernel_names(window)[1:]
    assert set(tiling(seq, seq, 24, True, *blocks, window=window)) \
        == set(kernel_names(window))
    q, k, v, w, b = _qkv(kv_heads, 8, kv_heads, seq)
    b = b if sink else None
    scale = 24 ** -0.5

    def kernel(q, k, v, b):
        out, lse = flash_attention(q, k, v, causal=True, scale=scale,
                                   return_lse=True, interpret=True,
                                   window=window, sink=b,
                                   block_q=blocks[0], block_k=blocks[1])
        return jnp.sum(out * w) + 0.0 * jnp.sum(lse), lse

    def xla(q, k, v, b):
        out, lse = attention_core(q, k, v, "xla", scale, window=window,
                                  sink=b)
        return jnp.sum(out * w), lse
    argnums = (0, 1, 2, 3) if sink else (0, 1, 2)
    with jax.default_matmul_precision("highest"):
        ((a, lse_a), ga), ((c, lse_c), gc) = (
            jax.value_and_grad(f, argnums, has_aux=True)(q, k, v, b)
            for f in (kernel, xla))
    assert float(a) == pytest.approx(float(c), rel=1e-5)
    assert _rel(lse_a, lse_c) <= 1e-6
    for x, y in zip(ga, gc):
        assert _rel(x, y) <= 1e-5


def test_a_group_is_read_through_the_index_maps(devices):
    """No copy of k or v a query head reaches the kernels: the jaxpr of
    the grouped call gives each kernel k and v at their own heads."""
    q, k, v, _, b = _qkv(0, 8, 2)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v, b: jnp.sum(
        flash_attention(q, k, v, causal=True, interpret=True, window=8,
                        sink=b)), argnums=(0, 1, 2, 3)))(q, k, v, b)
    calls = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name in ("jit", "pjit")]
    shapes = [tuple(x.aval.shape) for e in calls for x in e.invars]
    assert (4, 64, 24) in shapes and (4, 64, 16) in shapes
    assert (16, 64, 24) in shapes                      # q, a row a head
    assert not any(s[0] == 16 and s[-1] == 24 and s != (16, 64, 24)
                   for s in shapes)


# ---------------------------------------------------------------------------
# the chip's shares
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["full", "window"])
def test_head_shares_add_up_to_the_uncut_attention(kind):
    """Four chips of two query heads each against the reference's eight
    over two key/value heads (groups of four): each share holds the one
    key/value head its queries read, whole, and the partial outputs add
    up."""
    sizes = WINDOW if kind == "window" else dict(
        WINDOW, window=None, sink=False, rope_theta=5e6)
    whole = _bare_op(GroupedQueryAttention, 8, 2, **sizes)
    p = _random(whole, 3)
    x = jax.random.normal(jax.random.key(4), (2, 64, 64), jnp.float32)
    ones = {"scale": jnp.ones((64,))}
    cfg = _ref_cfg(kv_heads=2, sink=kind == "window",
                   window=8 if kind == "window" else None,
                   theta=sizes["rope_theta"])
    with jax.default_matmul_precision("highest"):
        want = ref._attention(x, ones, p, cfg=cfg) - x
        h = ref._rms_norm(x, ones["scale"], 1e-5)
        got = 0.0
        for first in (0, 2, 4, 6):
            kv = first // 4
            share = dict(p, w_q=p["w_q"][:, first * 24:(first + 2) * 24],
                         w_k=p["w_k"][:, kv * 24:(kv + 1) * 24],
                         w_v=p["w_v"][:, kv * 16:(kv + 1) * 16],
                         w_o=p["w_o"][first * 16:(first + 2) * 16])
            if kind == "window":
                share["sink"] = p["sink"][first:first + 2]
            op = _bare_op(GroupedQueryAttention, 2, 1, **sizes)
            got = got + op.forward(share, [h], FwdCtx())[0]
    assert _rel(got, want) <= 1e-5


def test_expert_shares_add_up_to_the_uncut_layer():
    """32 chips of one expert each, a budget that drops nothing: sigmoid
    scores, a token's weights normalised over all its choices wherever
    they live, no shared expert; the routed parts add up to the layer,
    the residual counted once."""
    kw = dict(n_shared_experts=0, capacity_factor=16.0, tile_rows=8,
              scoring="sigmoid", select_bias=True, norm_topk_prob=True)
    whole = _bare_op(RoutedExperts, 32, 4, 32, **kw)
    p = _random(whole, 5)
    x = jax.random.normal(jax.random.key(6), (2, 64, 64), jnp.float32)
    ones = {"scale": jnp.ones((64,))}
    with jax.default_matmul_precision("highest"):
        want = ref._expert_mlp(x, ones, p,
                               cfg=(4, 0, 32, 16.0, 1.0, 1e-5)) - x
        h = ref._rms_norm(x, ones["scale"], 1e-5)
        got, kept = 0.0, 0.0
        for first in range(32):
            op = _bare_op(RoutedExperts, 32, 4, 32, experts_held=1,
                          first_expert=first, **kw)
            share = dict(p, **{w: p[w][first:first + 1]
                               for w in ("w_gate", "w_up", "w_down")})
            counts = {}
            ctx = FwdCtx(counters=counts,
                         stats_in={op.name: op.init_stats()})
            got = got + op.forward(share, [h], ctx)[0]
            assert counts["moe_assignments_kept"] \
                == counts["moe_assignments_made"]
            kept = kept + counts["moe_assignments_kept"]
    assert float(kept) == 2 * 64 * 4          # every token's four choices
    assert _rel(got, want) <= 1e-5


# ---------------------------------------------------------------------------
# the builder's defaults, the sink's counter, the price
# ---------------------------------------------------------------------------

def test_builder_defaults_are_the_published_model():
    """The published hybrid_layer_pattern (9 full layers of 48: 0, 5, 11,
    ..., 47) and moe_layer_freq (layer 0 dense), as the configuration file
    holds them, and the default build's heads, windows, sinks and
    experts."""
    pattern = mimo_v2_layer_pattern()
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mimo-v2-flash.json")) as f:
        published = json.load(f)["published"]
    assert pattern == published["hybrid_layer_pattern"] \
        == ref.layer_pattern()
    assert [i for i, t in enumerate(pattern) if t == 0] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47]
    m = ff.FFModel(ff.FFConfig())
    build_mimo_v2_flash(m, 1, seq_length=256)
    attn = [op for op in m.ops if isinstance(op, GroupedQueryAttention)]
    assert [int(op.window is not None) for op in attn] == pattern
    for op in attn:
        window = op.window is not None
        assert (op.num_heads, op.num_kv_heads) == (
            (64, 8) if window else (64, 4))
        assert (op.head_dim, op.v_dim, op.rotary_dim) == (192, 128, 64)
        assert op.sink == window and op.value_scale == 0.707
        assert op.window in (None, 128)
    experts = [op for op in m.ops if isinstance(op, RoutedExperts)]
    assert len(experts) == 47 == published["moe_layer_freq"].count(1)
    assert {(op.n_routed, op.top_k, op.held, op.n_shared, op.scoring,
             op.norm_topk_prob, op.select_bias) for op in experts} == {
        (256, 8, 256, 0, "sigmoid", True, True)}
    assert [op.name for op in m.ops if op.name.startswith("mlp_")] == \
        ["mlp_0"]


def test_the_sink_share_is_the_mass_the_sink_takes(devices):
    """The counter a window layer adds is the mean over its heads and
    queries of exp(b - lse); the drain gives the mean over the window
    layers and steps, near 1 / (1 + keys) with a sink of zero."""
    op = _bare_op(GroupedQueryAttention, 8, 4, **WINDOW)
    p = _random(op, 7)
    x = jax.random.normal(jax.random.key(8), (2, 64, 64), jnp.float32)
    counts = {}
    op.forward(p, [x], FwdCtx(counters=counts))
    assert counts["swa_layers"] == 1.0
    _, lse = attention_core(
        *(t.transpose(0, 2, 1, 3) for t in _turned(op, p, x)), "xla",
        24 ** -0.5, window=8, sink=p["sink"])
    want = jnp.mean(jnp.exp(p["sink"][None, :, None] - lse))
    assert float(counts["swa_sink_share"]) == pytest.approx(float(want),
                                                            rel=1e-5)
    m, _, _ = _model()
    _step(m)
    from flexflow_tpu.runtime import profiling
    share = profiling.counters()["swa_sink_share"]
    assert 1 / (1 + 8) / 2 < share < 1 / 2        # 8 keys a query at most


def _turned(op, p, x):
    """q, k, v (B, S, heads, d) as the op makes them."""
    from flexflow_tpu.ops.attention import rotate_pairs
    b, s, _ = x.shape
    turn = lambda t: jnp.concatenate(
        [rotate_pairs(t[..., :8], op.inv_freq), t[..., 8:]], axis=-1)
    return (turn((x @ p["w_q"]).reshape(b, s, 8, 24)),
            turn((x @ p["w_k"]).reshape(b, s, 4, 24)),
            (x @ (0.707 * p["w_v"])).reshape(b, s, 4, 16))


def test_search_prices_the_new_work(devices):
    """The cost model prices a window layer under a full one of the same
    heads, and a head split divides the query and output projections and
    the core but not the key/value projections, which every share does
    whole."""
    from flexflow_tpu.simulator.cost_model import CostModel
    from flexflow_tpu.simulator.machine import TPUMachineModel

    sizes = WINDOW
    window = _bare_op(GroupedQueryAttention, 8, 4, **sizes)
    full = _bare_op(GroupedQueryAttention, 8, 4,
                    **dict(sizes, window=None, sink=False))
    assert window.flops_per_sample() < full.flops_per_sample()
    assert window.unsplit_cost_per_sample()[0] == \
        full.unsplit_cost_per_sample()[0] == 2.0 * 64 * 64 * 4 * (24 + 16)
    assert window.cost_key() != full.cost_key()
    cm = CostModel(TPUMachineModel.calibrated(num_devices=4), measure=False)
    one = ff.ParallelConfig(dims=(1, 1, 1))
    heads = ff.ParallelConfig(dims=(1, 1, 4))
    for op in (window, full):
        assert 0 < cm.op_time(op, heads, "forward") \
            < cm.op_time(op, one, "forward")
        assert op.legalize_pc(ff.ParallelConfig(dims=(1, 2, 8))).dims == \
            (1, 1, 8)


def _trained(devices, heads=1):
    """Two steps at batch 4 on ``devices``; with ``heads`` the attention
    ops split their query heads over that many devices, the rest of the
    graph data parallel."""
    cfg = ff.FFConfig()
    cfg.parse_args(["-b", "4", "-ll:tpu", str(devices)])
    m = ff.FFModel(cfg)
    (tok, _) = build_mimo_v2_flash(m, 4, **SMALL)
    for i in range(SMALL["num_hidden_layers"]):
        if heads > 1:
            cfg.strategies[f"attn_{i}"] = ff.ParallelConfig(
                dims=(1, 1, heads))
    m.compile(ff.SGDOptimizer(m, lr=0.05), "sparse_categorical_crossentropy",
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=3)
    (toks,), labels = ref.make_batch(jax.random.key(11), 4, **SMALL)
    m.set_batch({tok: np.asarray(toks)}, np.asarray(labels))
    return [_step(m) for _ in range(2)], _params(m), m


@pytest.mark.parametrize("heads", [1, 4])
def test_four_devices_equal_one(devices, heads):
    """Data parallelism over four devices, and the attention's query
    heads split four ways (a key/value head repeated over the shards of
    its group on full layers, whole groups on window layers), train as
    one device does."""
    one, p1, _ = _trained(1)
    four, p4, m = _trained(4, heads)
    attn = [op for op in m.ops if isinstance(op, GroupedQueryAttention)]
    assert {op.pc.dims[2] if len(op.pc.dims) > 2 else 1
            for op in attn} == {heads}
    np.testing.assert_allclose(four, one, rtol=1e-5)
    for op, ws in p1.items():
        for w in ws:
            np.testing.assert_allclose(p4[op][w], ws[w], rtol=2e-4,
                                       atol=2e-6)
