"""DeepSeek-V2 on the training path (models/transformer.build_deepseek_v2:
RMSNorm, latent attention with YaRN rotary positions, gated MLPs, routed
experts under a device budget) against its plain reference
(benchmark/reference/deepseek-v2.py) at a small size on the CPU: loss,
logits and every parameter's gradient in float32 and in bfloat16 compute,
the chip's share against the uncut layer, the budget, the rotary
frequencies, data parallelism, and the benchmark's formulas against hand
counts."""

import functools
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from benchmark import deepseek_v2 as formulas
from benchmark import logit_check
from flexflow_tpu.models.transformer import build_deepseek_v2
from flexflow_tpu.ops.attention import (LatentAttention, yarn_inv_freq,
                                        yarn_mscale)
from flexflow_tpu.ops.base import FwdCtx
from flexflow_tpu.ops.moe import RoutedExperts, route

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YARN = dict(factor=40, original_max_position_embeddings=4096, beta_fast=32,
            beta_slow=1, mscale=0.707, mscale_all_dim=0.707, type="yarn")
# hidden 64, 4 heads of 16 | 8 | 16, 16 experts in 4 groups, top-3 of 2
# groups, 2 shared, 1 + 2 layers; this chip holds experts 4..7
SMALL = dict(seq_length=16, hidden_size=64, num_hidden_layers=3,
             first_k_dense_replace=1, intermediate_size=96,
             moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, rope_theta=10000, rope_scaling=YARN,
             rms_norm_eps=1e-6, n_routed_experts=16, num_experts_per_tok=3,
             n_group=4, topk_group=2, routed_scaling_factor=16.0,
             n_shared_experts=2, vocab_size=128, experts_held=4,
             first_expert=4, tile_rows=8)
BATCH = 4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_deepseek_v2",
        os.path.join(REPO, "benchmark", "reference", "deepseek-v2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


def _model(kw=SMALL, dtype="float32", devices=1, lr=1.0, seed=3):
    cfg = ff.FFConfig()
    cfg.parse_args(["-b", str(BATCH), "-ll:tpu", str(devices)]
                   + (["--bf16"] if dtype == "bfloat16" else []))
    m = ff.FFModel(cfg)
    (tok, _) = build_deepseek_v2(m, BATCH, **kw)
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


# Tolerances, as norm-wise relative errors.  float32: both sides compute
# the same mathematics in the same precision in another order, and a
# gradient is read back as a difference of parameters (one rounding of
# the parameter, 6e-8 of it): 1e-4 is a hundred times what was measured.
# bfloat16 compute: every operand of every product is rounded to 8 bits
# (2^-9 = 2e-3 relative a rounding), some tens of roundings deep in three
# layers, and an affinity that moves by that much can change a token's
# experts or the budget's last rows, which moves that token's rows
# wholesale (at 64 tokens one such token is 1.6 % of them, and an expert's
# weight is 16 s): the logits read 3.4e-2 and the worst gradient 8e-2;
# 8e-2 and 2.5e-1 leave room for another seed and fail a lower precision
# (below) or a dropped term (an expert, a norm, the rotary part: each
# moves some gradient by all of itself).
@pytest.mark.parametrize("dtype,tol_logits,tol_grad", [
    ("float32", 1e-4, 1e-4), ("bfloat16", 8e-2, 2.5e-1)])
def test_program_matches_reference(devices, dtype, tol_logits, tol_grad):
    m, inputs, labels = _model(dtype=dtype)
    before = _params(m)
    want_loss, want_grads = jax.value_and_grad(ref.loss)(
        before, inputs, labels, **SMALL)
    want_logits = ref.logits(before, inputs, **SMALL)
    assert _rel(m.logits_batch().astype(jnp.float32),
                want_logits) <= tol_logits
    m.train_iteration()
    m.sync()
    m.get_metrics()
    assert abs(m.last_loss - float(want_loss)) <= tol_logits * float(want_loss)
    after = _params(m)
    worst = {f"{op}/{w}": _rel(np.asarray(before[op][w])
                               - np.asarray(after[op][w]),   # lr is 1
                               want_grads[op][w])
             for op, ws in before.items() for w in ws}
    assert len(worst) == 7 + 3 * 7 + 3 + 2 * 7 + 2   # every parameter
    assert not {k: v for k, v in worst.items() if not v <= tol_grad}, worst


# The on-chip logit comparison (benchmark/logit_check.py) has one limit,
# the norm-wise relative error of all logits; at the small size the
# limit of the test above has to tell the stated precision (3.4e-2) from
# the next one down (a bfloat16 router 1.8e-1, 8-bit operands 3.5e-1).
LOGIT_LIMIT = 8e-2


@pytest.mark.parametrize("lower", ["router", "operands"])
def test_logit_limit_fails_a_lower_precision(devices, monkeypatch, lower):
    m, inputs, _ = _model(dtype="bfloat16")
    want = ref.logits(_params(m), inputs, **SMALL)
    stated = _rel(m.logits_batch().astype(jnp.float32), want)
    logit_check.LOWER[lower](monkeypatch.setattr)
    m._logits_fn = None                   # trace the forward pass again
    lowered = _rel(m.logits_batch().astype(jnp.float32), want)
    assert stated <= LOGIT_LIMIT < lowered, (stated, lowered)


# ---------------------------------------------------------------------------
# the chip's share
# ---------------------------------------------------------------------------

def _bare_op(cls, *args, **kw):
    m = ff.FFModel(ff.FFConfig())
    x = m.create_tensor((2, 16, 64), nchw=False)
    op = cls(m, x, *args, **kw)
    op.impl = "xla"
    return op


def _random(op, seed):
    keys = jax.random.split(jax.random.key(seed), len(op.weights))
    return {w.name: 0.3 * jax.random.normal(k, w.dims, jnp.float32)
            for w, k in zip(op.weights, keys)}


def test_head_shares_add_up_to_the_uncut_attention():
    sizes = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16, rope_scaling=YARN)
    whole = _bare_op(LatentAttention, 4, **sizes)
    p = _random(whole, 0)
    x = jax.random.normal(jax.random.key(1), (2, 16, 64), jnp.float32)
    ones = {"scale": jnp.ones((64,))}
    cfg = (4, 16, 16, 8, 16, 1e-6, 10000.0,
           tuple(sorted((k, v) for k, v in YARN.items() if k != "type")))
    with jax.default_matmul_precision("highest"):
        want = ref._attention(x, ones, p, cfg=cfg) - x
        h = ref._rms_norm(x, ones["scale"], 1e-6)
        got = 0.0
        for first in (0, 2):             # two chips, two heads each
            cols = lambda w, d: w.reshape(w.shape[0], 4, d)[
                :, first:first + 2].reshape(w.shape[0], 2 * d)
            share = dict(p, w_uq=cols(p["w_uq"], 24), w_ukv=cols(p["w_ukv"], 32),
                         w_o=p["w_o"][first * 16:(first + 2) * 16])
            op = _bare_op(LatentAttention, 2, **sizes)
            got = got + op.forward(share, [h], FwdCtx())[0]
    assert _rel(got, want) <= 1e-5


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of four experts each, a budget that drops nothing: the
    routed parts add up, the shared experts (every chip computes them
    alike) counted once."""
    kw = dict(n_group=4, topk_group=2, routed_scaling_factor=16.0,
              n_shared_experts=2, capacity_factor=16.0, tile_rows=8)
    whole = _bare_op(RoutedExperts, 16, 3, 32, **kw)
    p = _random(whole, 2)
    x = jax.random.normal(jax.random.key(3), (2, 16, 64), jnp.float32)
    ones = {"scale": jnp.ones((64,))}
    with jax.default_matmul_precision("highest"):
        want = ref._expert_mlp(x, ones, p,
                               cfg=(3, 4, 2, 0, 16, 16.0, 16.0, 1e-6)) - x
        h = ref._rms_norm(x, ones["scale"], 1e-6)
        shared = ref._gated(h, p["shared_gate"], p["shared_up"],
                            p["shared_down"])
        got = shared
        for first in (0, 4, 8, 12):
            op = _bare_op(RoutedExperts, 16, 3, 32, experts_held=4,
                          first_expert=first, **kw)
            share = dict(p, **{w: p[w][first:first + 4]
                               for w in ("w_gate", "w_up", "w_down")})
            counts = {}
            got = got + op.forward(share, [h], FwdCtx(counters=counts))[0] \
                - shared
            assert counts["moe_assignments_kept"] \
                == counts["moe_assignments_made"]
    assert _rel(got, want) <= 1e-5


# ---------------------------------------------------------------------------
# the device budget
# ---------------------------------------------------------------------------

def _scores(seed, tokens=64, experts=16):
    return jax.nn.softmax(2.0 * jax.random.normal(
        jax.random.key(seed), (tokens, experts), jnp.float32), axis=-1)


def _kept_by_route(s, budget, **kw):
    r = route(s, top_k=3, n_group=4, topk_group=2, first=4, held=4,
              budget=budget, tile_rows=8)
    tokens = s.shape[0]
    kept = np.zeros((tokens, 4), bool)
    tile = np.asarray(r["tile_group"])
    rows = np.asarray(r["row_token"])
    for row, tok in enumerate(rows):
        if tok < tokens:                   # not padding
            assert not kept[tok, tile[row // 8]]
            kept[tok, tile[row // 8]] = True
    # the same layout by token: each choice's row reads that token
    slots = np.asarray(r["slot_row"])
    assert slots.shape == (tokens, 3)
    here = slots < len(rows)
    assert here.sum() == kept.sum()
    assert (rows[slots[here]] == np.nonzero(here)[0]).all()
    return r, kept


@pytest.mark.parametrize("capacity,drops", [(0.5, True), (0.75, True),
                                            (3.0, False)])
def test_budget_keeps_the_largest_affinities(capacity, drops):
    s = _scores(5)
    budget = ref.device_budget(64, 3, 4, 16, capacity)
    r, kept = _kept_by_route(s, budget)
    want = np.asarray(ref.kept_assignments(s, 3, 4, 2, 4, 4, capacity))
    np.testing.assert_array_equal(kept, want)
    made, n_kept = int(r["made"]), int(r["kept"])
    assert n_kept == kept.sum() == min(made, budget)
    assert (made > budget) == drops
    affinity = np.asarray(s)[:, 4:8]
    if drops:        # what went has no more affinity than what stayed
        _, kept_all = _kept_by_route(s, 64 * 3)
        dropped = kept_all & ~kept
        assert dropped.any()
        assert affinity[dropped].max() <= affinity[kept].min()
    else:            # the rest of the buffer is padding and weighs nothing
        filled = np.asarray(r["row_token"]) < 64
        assert filled.sum() == made < budget
        assert not np.asarray(r["row_weight"])[~filled].any()
    # every tile has one expert, the experts' tiles stand in order, and
    # each expert owns at least one
    tiles = np.asarray(r["tile_group"])
    assert (np.diff(tiles) >= 0).all() and set(tiles) == {0, 1, 2, 3}
    assert len(tiles) == -(-budget // 8) + 4


def test_budget_breaks_ties_by_token_then_expert():
    s = jnp.full((8, 16), 1.0 / 16)       # every affinity equal
    r, kept = _kept_by_route(s, 5)
    want = np.asarray(ref.kept_assignments(s, 3, 4, 2, 4, 4, 5 / 6))
    assert ref.device_budget(8, 3, 4, 16, 5 / 6) == 5
    np.testing.assert_array_equal(kept, want)
    # groups 0 and 1 win, a token's three experts are 0, 1, 2: none here
    assert not kept.any()
    s = s.at[:, 4:7].set(0.2)             # now experts 4, 5, 6 of group 1
    r, kept = _kept_by_route(s, 5)
    np.testing.assert_array_equal(
        kept, np.asarray(ref.kept_assignments(s, 3, 4, 2, 4, 4, 5 / 6)))
    assert kept[0, :3].all() and kept[1, :2].all() and kept.sum() == 5


# The combine as it was until the compaction: a gather of every token a
# choice, added up in float32 in top_k's order.  The compacted form must
# give the same numbers, element for element, forward and backward.
def _by_choice(rows, slot_row):
    acc = jnp.zeros((slot_row.shape[0], rows.shape[1]), jnp.float32)
    for j in range(slot_row.shape[1]):
        acc = acc + jnp.take(rows, slot_row[:, j], axis=0, mode="fill",
                             fill_value=0).astype(jnp.float32)
    return acc.astype(rows.dtype)


@jax.custom_vjp
def _spread_by_choice(x, row_token, slot_row):
    return jnp.take(x, row_token, axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def _collect_by_choice(rows, row_token, slot_row):
    return _by_choice(rows, slot_row)


_spread_by_choice.defvjp(
    lambda x, *index: (_spread_by_choice(x, *index), index),
    lambda index, g: (_collect_by_choice(g, *index), None, None))
_collect_by_choice.defvjp(
    lambda rows, *index: (_collect_by_choice(rows, *index), index),
    lambda index, g: (_spread_by_choice(g, *index), None, None))


def _logits(seed, tokens=64, experts=16):
    return 2.0 * jax.random.normal(jax.random.key(seed), (tokens, experts),
                                   jnp.float32)


def _all_on_held(tokens, per_token):
    """Softmax scores under which the first ``tokens`` of 64 put
    ``per_token`` of their three choices on experts 4..7 (group 1) and
    the other two on experts 0 and 1 (group 0), and the rest choose none
    of 4..7."""
    z = _logits(9)
    z = z.at[:, 4:8].add(-20.0)
    z = z.at[:tokens, 4:4 + per_token].add(30.0)
    z = z.at[:tokens, :3 - per_token].add(25.0)
    return jax.nn.softmax(z, axis=-1)


# (routing keywords, scores, budget): softmax with groups (DeepSeek-V2)
# and sigmoid with a selection bias and normalised weights (dots3,
# MiMo-V2), under a budget that drops and one that pads; the first 16
# tokens putting all three choices here under a budget of 48, so that
# the rank-3 prefix (48 // 3 rows) is exactly full; 48 tokens of one
# choice each under the same budget, so that the rank-1 prefix is full.
SOFTMAX = dict(n_group=4, topk_group=2)
SIGMOID = dict(n_group=1, topk_group=1, norm_topk_prob=True)
ROUTES = {
    "softmax-groups-drops": (SOFTMAX, lambda: _scores(5), 24),
    "softmax-groups-pads": (SOFTMAX, lambda: _scores(5), 144),
    "sigmoid-bias-norm-drops": (
        SIGMOID, lambda: jax.nn.sigmoid(_logits(6)), 24),
    "sigmoid-bias-norm-pads": (
        SIGMOID, lambda: jax.nn.sigmoid(_logits(6)), 144),
    "full-top-rank": (SOFTMAX, lambda: _all_on_held(16, 3), 48),
    "full-first-rank": (SOFTMAX, lambda: _all_on_held(48, 1), 48),
}


def _route_case(which, **over):
    kw, scores, budget = ROUTES[which]
    kw = dict(kw, top_k=3, first=4, held=4, budget=budget, tile_rows=8)
    if "norm_topk_prob" in kw:
        kw["select_bias"] = 0.3 * jax.random.normal(jax.random.key(8), (16,))
    return scores(), dict(kw, **over)


@pytest.mark.parametrize("which", sorted(ROUTES))
def test_compact_combine_is_a_gather_a_choice(which):
    """The combine over ``route``'s compaction against a gather of every
    token a choice, and the transposes of the dispatch and the combine
    through ``jax.vjp`` in the tokens and the scores: equal, element for
    element, in float32 and bfloat16."""
    from flexflow_tpu.ops.moe import collect_rows, spread_rows
    s, kw = _route_case(which)
    r = route(s, **kw)
    kept = np.asarray(r["slot_row"]) < len(r["row_token"])
    spans = [block.shape for block in r["compact_rows"]]
    if which == "full-top-rank":         # 48 // 3 places hold three choices
        assert (kept.sum(axis=1) == np.repeat([3, 0], [16, 48])).all()
        assert spans == [(16, 3), (8, 2), (24, 1)]
    if which == "full-first-rank":       # as many places as the budget
        assert (kept.sum(axis=1) == np.repeat([1, 0], [48, 16])).all()
        assert spans[-1] == (24, 1) and sum(n for n, _ in spans) == 48
    if which.endswith("drops"):
        assert int(r["made"]) > int(r["kept"]) == kw["budget"]
    if which.endswith("pads"):
        assert int(r["made"]) == int(r["kept"]) < kw["budget"]

    def layer(spread, collect, compact, x, scores):
        r = route(scores, **kw)
        index = (r["row_token"], r["compact_rows"], r["token_position"]) \
            if compact else (r["row_token"], r["slot_row"])
        rows = jnp.tanh(spread(x, *index).astype(jnp.float32))
        return collect((rows * r["row_weight"][:, None]).astype(x.dtype),
                       *index)

    for dtype in (jnp.float32, jnp.bfloat16):
        x = jax.random.normal(jax.random.key(1), (64, 32), dtype)
        g = jax.random.normal(jax.random.key(2), (64, 32), dtype)
        rows = jax.random.normal(jax.random.key(3), (len(r["row_token"]), 32),
                                 dtype)
        np.testing.assert_array_equal(
            collect_rows(rows, r["row_token"], r["compact_rows"],
                         r["token_position"]),
            _by_choice(rows, r["slot_row"]))
        got, got_vjp = jax.vjp(
            lambda x, s: layer(spread_rows, collect_rows, True, x, s), x, s)
        want, want_vjp = jax.vjp(
            lambda x, s: layer(_spread_by_choice, _collect_by_choice, False,
                               x, s), x, s)
        np.testing.assert_array_equal(got, want)
        for a, b in zip(got_vjp(g), want_vjp(g)):
            np.testing.assert_array_equal(a, b)
        assert float(jnp.abs(got_vjp(g)[1]).max()) > 0
    # neither the dispatch nor the combine, nor their transposes, scatters
    index = (r["row_token"], r["compact_rows"], r["token_position"])
    text = jax.jit(jax.grad(lambda x: jnp.sum(collect_rows(
        jnp.tanh(spread_rows(x, *index)), *index)))).lower(x).as_text()
    assert "gather" in text and "scatter" not in text


def _ties(budget):
    s = jnp.full((8, 16), 1.0 / 16).at[:, 4:7].set(0.2)
    return s, dict(SOFTMAX, top_k=3, first=4, held=4, budget=budget,
                   tile_rows=8)


BOUND_ROUTES = {
    **{which: functools.partial(_route_case, which) for which in ROUTES},
    "fewer-ranks-than-choices": functools.partial(
        _route_case, "softmax-groups-drops", budget=2),
    "one-expert-held": functools.partial(
        _route_case, "sigmoid-bias-norm-pads", held=1, first=5),
    "all-choices-here-drops": lambda: (
        jax.nn.softmax(_logits(4).at[:, 4:8].add(10.0), axis=-1),
        dict(SOFTMAX, top_k=3, first=4, held=4, budget=48, tile_rows=8)),
    "ties-5": functools.partial(_ties, 5),
    "ties-24": functools.partial(_ties, 24)}


@pytest.mark.parametrize("which", sorted(BOUND_ROUTES))
def test_compaction_holds_every_kept_choice_once(which):
    """Every kept (token, choice) of ``slot_row`` stands once in the
    compact blocks, in its token's row and in top_k's order among the
    token's kept choices, the rest of the row past the buffer; the blocks
    stand place after place, the one of the places ``budget // (r + 1)``
    to ``budget // r`` (at most ``tokens``) r wide, so no token with r
    kept choices stands past the first ``budget // r`` places."""
    s, kw = BOUND_ROUTES[which]()
    r = route(s, **kw)
    t, m, budget = s.shape[0], len(r["row_token"]), kw["budget"]
    slots = np.asarray(r["slot_row"])
    position = np.asarray(r["token_position"])
    assert sorted(position) == list(range(t))
    order = np.argsort(position)
    ranks = min(3, kw["held"], budget)
    ends = [min(t, budget // rank) for rank in range(1, ranks + 1)] + [0]
    want = [(ends[rank - 1] - ends[rank], rank)
            for rank in range(ranks, 0, -1) if ends[rank] < ends[rank - 1]]
    assert [block.shape for block in r["compact_rows"]] == want
    got = [None] * t
    place = 0
    for block in map(np.asarray, r["compact_rows"]):
        for row in block:
            kept = [v for v in row if v < m]
            assert list(row) == kept + [m] * (len(row) - len(kept))
            got[order[place]] = kept
            place += 1
    assert place == ends[0]
    for tok in range(t):
        assert (got[tok] or []) == [row for row in slots[tok] if row < m]
    assert sum(map(len, filter(None, got))) == int(r["kept"]) <= budget
    assert int(r["with_choice"]) == sum(1 for rows in got if rows)


@pytest.mark.parametrize("capacity", [0.25, 3.0])
def test_tokens_with_choice_share_is_counted(monkeypatch, capacity):
    """``moe_tokens_with_choice_share`` as the metric drain folds what the
    layers counted: over two calls, the share of their tokens with a kept
    choice here, from ``slot_row`` in numpy."""
    from flexflow_tpu.ops.moe import router_scores
    from flexflow_tpu.runtime import profiling
    monkeypatch.setattr(profiling, "_counters", {})
    op = _bare_op(RoutedExperts, 16, 3, 32, n_group=4, topk_group=2,
                  experts_held=4, first_expert=4, capacity_factor=capacity,
                  tile_rows=8)
    assert "moe_tokens_with_choice" in op.COUNTERS
    with_choice = []
    for seed in (0, 1):
        p = _random(op, seed)
        x = jax.random.normal(jax.random.key(seed + 7), (2, 16, 64))
        counts = {}
        op.forward(p, [x], FwdCtx(counters=counts))
        profiling.count({k: float(v) for k, v in counts.items()})
        r = route(router_scores(x.reshape(-1, 64), p["router"]), top_k=3,
                  n_group=4, topk_group=2, first=4, held=4,
                  budget=op.budget(32), tile_rows=8)
        slots = np.asarray(r["slot_row"])
        with_choice.append((slots < len(r["row_token"])).any(axis=1))
    want = np.concatenate(with_choice).mean()
    assert 0 < want < 1
    got = profiling.counters()
    assert got["moe_tokens_with_choice_share"] == pytest.approx(want)
    assert (got["moe_dropped_share"] > 0) == (capacity < 1)


def test_padding_adds_nothing_and_shapes_ignore_the_seed():
    kw = dict(n_group=4, topk_group=2, routed_scaling_factor=16.0,
              n_shared_experts=2, experts_held=4, first_expert=4,
              capacity_factor=3.0, tile_rows=8)
    op = _bare_op(RoutedExperts, 16, 3, 32, **kw)
    jaxprs = []
    for seed in (0, 1):
        p = _random(op, seed)
        x = jax.random.normal(jax.random.key(seed + 7), (2, 16, 64))
        counts = {}
        ones = jnp.ones((64,))
        with jax.default_matmul_precision("highest"):
            h = ref._rms_norm(x, ones, 1e-6)
            got = op.forward(p, [h], FwdCtx(counters=counts))[0]
            want = ref._expert_mlp(
                x, {"scale": ones}, p,
                cfg=(3, 4, 2, 4, 4, 3.0, 16.0, 1e-6)) - x
        assert counts["moe_assignments_made"] < op.budget(32)
        assert _rel(got, want) <= 1e-5
        jaxprs.append(str(jax.make_jaxpr(
            lambda p, x: op.forward(p, [x], FwdCtx())[0])(p, x)))
    assert jaxprs[0] == jaxprs[1]


# ---------------------------------------------------------------------------
# rotary frequencies
# ---------------------------------------------------------------------------

def test_yarn_frequencies_and_scale_against_hand_values():
    f = yarn_inv_freq(64, 10000.0, **YARN)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    # correction dims: 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47,
    # and 22.51 for one turn: the ramp runs from dim 10 to dim 23
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-12)
    np.testing.assert_allclose(f[15], plain[15] * (8 / 13 + 5 / 13 / 40),
                               rtol=1e-12)
    np.testing.assert_allclose(f, ref.yarn_frequencies(
        64, 10000.0, **{k: v for k, v in YARN.items() if k != "type"}),
        rtol=1e-12)
    m = yarn_mscale(40, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1) \
        == pytest.approx(1.26080, abs=1e-5)
    op = _bare_op(LatentAttention, 2, q_lora_rank=24, kv_lora_rank=16,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                  rope_scaling=YARN)
    assert op.softmax_scale == pytest.approx(192 ** -0.5 * 1.26080 ** 2,
                                             rel=1e-5)
    assert op.rope_scale == 1.0
    assert yarn_inv_freq(64, 10000.0) == pytest.approx(plain)


# ---------------------------------------------------------------------------
# data parallelism, the search, the formulas
# ---------------------------------------------------------------------------

def _trained(devices):
    m, _, _ = _model(devices=devices, lr=0.05)
    losses = []
    for _ in range(2):
        m.train_iteration()
        m.sync()
        m.get_metrics()
        losses.append(m.last_loss)
    return losses, _params(m), m


def test_four_devices_equal_one(devices):
    one, p1, _ = _trained(1)
    four, p4, m = _trained(4)
    assert {op.pc.dims[0] for op in m.ops} == {4}
    np.testing.assert_allclose(four, one, rtol=1e-5)
    for op, ws in p1.items():
        for w in ws:
            np.testing.assert_allclose(p4[op][w], ws[w], rtol=2e-4, atol=2e-6)


def test_search_prices_the_new_ops(devices):
    from flexflow_tpu.simulator.cost_model import CostModel
    from flexflow_tpu.simulator.machine import TPUMachineModel
    from flexflow_tpu.simulator.search import splittable_dims

    _, _, m = _trained(4)
    by_type = {op._type: op for op in m.ops}
    assert splittable_dims(by_type["LatentAttention"]) == (0, 2)
    assert splittable_dims(by_type["RoutedExperts"]) == (0, 1)
    assert splittable_dims(by_type["GatedMLP"]) == (0, 2)
    cm = CostModel(TPUMachineModel.calibrated(num_devices=4), measure=False)
    for kind, dims in (("LatentAttention", (2, 1, 2)),
                       ("RoutedExperts", (2, 2, 1)), ("GatedMLP", (2, 1, 2)),
                       ("RMSNorm", (4, 1, 1))):
        op = by_type[kind]
        pc = op.legalize_pc(ff.ParallelConfig(dims=dims))
        assert pc.dims == dims
        whole = cm.op_time(op, ff.ParallelConfig(dims=(1, 1, 1)), "forward")
        assert 0 < cm.op_time(op, pc, "forward") < whole
    # a head or expert degree that does not divide what is held is clamped
    assert by_type["LatentAttention"].legalize_pc(
        ff.ParallelConfig(dims=(1, 2, 8))).dims == (1, 1, 4)
    assert by_type["RoutedExperts"].legalize_pc(
        ff.ParallelConfig(dims=(1, 8, 1))).dims == (1, 4, 1)


def test_formulas_against_hand_counts():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "deepseek-v2.json")) as f:
        config = json.load(f)
    kw = config["builder_kwargs"]
    attn = (5120 * 1536 + 1536 * 8 * 192 + 5120 * 576 + 512 * 8 * 256
            + 8 * 128 * 5120)
    block = attn + 1536 + 512 + 2 * 5120
    expert = 3 * 5120 * 1536
    layer = block + 5120 * 160 + 2 * expert + 10 * expert
    assert formulas.parameters(**kw) == (
        block + 3 * 5120 * 12288) + 4 * layer + 2 * 12800 * 5120 + 5120 \
        == 1552942080 == config["deployment"]["parameters"]
    per_token = (12800 * 5120 + 5 * attn + 3 * 5120 * 12288
                 + 4 * (5120 * 160 + 2 * expert + 0.375 * expert))
    assert formulas.matmul_params_per_token(**kw) == per_token == 579010560
    attention = 4096 * 4096 * 8 * (192 + 128)
    assert formulas.train_flops(**kw) == pytest.approx(
        6 * per_token * 4096 + 3 * attention * 5)
    flops, nbytes = formulas.mla_attention_train(batch=2, **kw)
    assert flops == pytest.approx(3 * attention * 5 * 2)
    assert nbytes == 2 * 4096 * 8 * 6 * 320 * 2 * 5
    flops, nbytes = formulas.routed_experts_train(batch=2, **kw)
    rows = 3072 + 10 * 128        # the budget, each group padded to a tile
    assert flops == pytest.approx(4 * 6 * rows * expert)
    assert nbytes == 4 * (3 * 10 * expert * 4
                          + rows * 4 * (5120 + 1536) * 2)
    # the program's own count agrees with the formula's
    m, _, _ = _model()
    small = sum(int(np.prod(w.dims)) for op in m.ops for w in op.weights)
    assert small == formulas.parameters(**SMALL)
    # the configuration keeps every published width
    for key, value in config["published"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
