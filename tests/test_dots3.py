"""dots3-note-prev on the training path (models/transformer.build_dots3:
latent attention under a learned index on the full layers and within a
window on the others, head-wise gates, rescaled latents, sigmoid-routed
experts with a selection bias, and the index's KL term as a loss term from
inside the graph) against its plain reference
(benchmark/reference/dots3-note-prev.py) at a small size on the CPU: the
whole objective, logits and every parameter's gradient in float32 and in
bfloat16 compute, the chip's shares against the uncut layers, the window
and the selected kernels against dense masks, the two gradient-isolation
rules, and the steps of the models that take none of this."""

import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.kernels.flash_attention import flash_attention, tiling
from flexflow_tpu.models.transformer import (build_deepseek_v2, build_dots3,
                                             build_transformer)
from flexflow_tpu.ops import dsa, moe
from flexflow_tpu.ops.attention import LatentAttention
from flexflow_tpu.ops.base import FwdCtx
from flexflow_tpu.ops.moe import RoutedExperts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# hidden 64; full layers: 2 heads of 16 | 8 | 16 under an index of 4 heads of
# 16 that keeps 8 keys; a window layer of 2 heads of 24 | 8 | 16 and 5 keys;
# 16 experts, top-4, 1 shared, 1 + 2 layers; this chip holds experts 4..7
SMALL = dict(seq_length=32, hidden_size=64, num_hidden_layers=3,
             layer_types=["full_attention", "full_attention",
                          "sliding_attention"],
             first_k_dense_replace=1, intermediate_size=96,
             moe_intermediate_size=32, num_attention_heads=2, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, rope_theta=8e7, index_n_heads=4,
             index_head_dim=16, index_topk=8, swa_num_attention_heads=2,
             swa_q_lora_rank=24, swa_kv_lora_rank=24, swa_qk_nope_head_dim=24,
             swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=5e4,
             sliding_window_size=5, rms_norm_eps=1e-5, n_routed_experts=16,
             num_experts_per_tok=4, n_shared_experts=1, vocab_size=128,
             experts_held=4, first_expert=4, tile_rows=8)
BATCH = 2


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_dots3", os.path.join(REPO, "benchmark", "reference",
                                        "dots3-note-prev.py"))
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
    (tok, _) = build_dots3(m, BATCH, **kw)
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


# Tolerances, as norm-wise relative errors.  float32: both sides compute
# the same mathematics in the same precision in another order (the
# selection is the same set: a score would have to tie to 1e-7 to move
# it), and a gradient is read back as a difference of parameters: 1e-4 is
# a hundred times what was measured (7e-7 on the logits).  bfloat16
# compute: every operand of every product is rounded to 8 bits, and the
# float32 index and router read activations that were: at 32 tokens, 8
# keys a query and 4 experts a token, a score moved by 2^-9 changes some
# queries' keys and some tokens' experts, and each such change moves that
# token's rows wholesale, in the logits, the loss (through the KL term's
# support too) and the gradients.  Four seeds read 0.09-0.22 on the logits
# (this one 0.094), 1.6e-3 to 4.8e-3 on the loss and 0.36-0.63 on the
# worst gradient (this one 0.36); the limits are this seed's readings with
# the other seeds' room.  They fail 8-bit operands (below: 0.40-0.53 on
# the logits) and a dropped branch (1.0 on its gradients); they cannot
# tell a bfloat16 index or router from the stated one, which moves no more
# choices than the bfloat16 activations already do: the float32 limits do
# that (below).
@pytest.mark.parametrize("dtype,tol_logits,tol_loss,tol_grad", [
    ("float32", 1e-4, 1e-5, 1e-4), ("bfloat16", 0.25, 1e-2, 0.6)])
def test_program_matches_reference(devices, dtype, tol_logits, tol_loss,
                                   tol_grad):
    m, inputs, labels = _model(dtype=dtype)
    before = _params(m)
    want_loss, want_grads = jax.value_and_grad(ref.loss)(
        before, inputs, labels, **SMALL)
    lm, index_term = ref.loss_terms(before, inputs, labels, **SMALL)
    assert float(index_term) > 0.1          # the objective has its term
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
    # embedding, 7 norms, 2 full attentions of 13, a window one of 8, the
    # dense MLP's 3, two expert layers of 7, the head
    assert len(worst) == 1 + 7 + 2 * 13 + 8 + 3 + 2 * 7 + 1
    assert not {k: v for k, v in worst.items() if not v <= tol_grad}, worst
    # the term left with the drain, as a counter: the mean over the layers
    from flexflow_tpu.runtime import profiling
    assert profiling.counters()["dsa_index_kl"] > 0


def _bf16_index(patch):
    """The index's scores from bfloat16 operands at the default precision."""
    real = dsa.index_scores

    def index_scores(q, k, w, *a):
        cut = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
        return real(cut(q), cut(k), cut(w), *a)
    patch(dsa, "index_scores", index_scores)
    patch(dsa, "_HIGHEST", None)


def _bf16_router(patch):
    def router_scores(x, router, scoring="softmax"):
        bf16 = jnp.bfloat16
        logits = jnp.dot(x.astype(bf16), router.astype(bf16)).astype(bf16)
        act = jax.nn.sigmoid if scoring == "sigmoid" else jax.nn.softmax
        return act(logits).astype(jnp.float32)
    patch(moe, "router_scores", router_scores)


# A lower precision where a choice is made fails the float32 limit on the
# logits: on seed 5 a bfloat16 index moves some queries' keys (0.11, where
# the stated precision reads 7e-7) and a bfloat16 router some tokens'
# experts (0.02).  The index's own values never reach the logits, only its
# choices do, so a seed on which no choice moves reads 0 (seed 3).
@pytest.mark.parametrize("lower", [_bf16_index, _bf16_router])
def test_float32_limit_fails_a_bfloat16_index_or_router(devices, monkeypatch,
                                                        lower):
    m, inputs, _ = _model(seed=5)
    want = ref.logits(_params(m), inputs, **SMALL)
    stated = _rel(m.logits_batch(), want)
    lower(monkeypatch.setattr)
    m._logits_fn = None                   # trace the forward pass again
    lowered = _rel(m.logits_batch(), want)
    assert stated <= 1e-4 < 1e-2 < lowered, (stated, lowered)


def test_bfloat16_limit_fails_8bit_operands(devices, monkeypatch):
    """What `benchmark/logit_check.py --lower operands` does on the chip."""
    from benchmark import logit_check

    m, inputs, _ = _model(dtype="bfloat16")
    want = ref.logits(_params(m), inputs, **SMALL)
    stated = _rel(m.logits_batch().astype(jnp.float32), want)
    logit_check.LOWER["operands"](monkeypatch.setattr)
    m._logits_fn = None
    lowered = _rel(m.logits_batch().astype(jnp.float32), want)
    assert stated <= 0.25 < 0.35 < lowered, (stated, lowered)


# ---------------------------------------------------------------------------
# the two gradient-isolation rules, and the step without a term
# ---------------------------------------------------------------------------

INDEX_WEIGHTS = ("wi_q", "wi_k", "wi_k_scale", "wi_k_bias", "wi_w")


def test_loss_terms_reach_their_own_parameters_alone(devices):
    """The index's parameters get no gradient from the language-model
    loss, and nothing else gets one from the index's objective: by the
    reference's two terms differentiated apart, and by the program's step
    against their sum (the test above)."""
    m, inputs, labels = _model()
    p = _params(m)
    g_lm = jax.grad(lambda p: ref.loss_terms(p, inputs, labels, **SMALL)[0])(p)
    g_ix = jax.grad(lambda p: ref.loss_terms(p, inputs, labels, **SMALL)[1])(p)
    for op, ws in p.items():
        for w in ws:
            own = op in ("attn_0", "attn_1") and w in INDEX_WEIGHTS
            lm, ix = (float(jnp.abs(g[op][w]).max()) for g in (g_lm, g_ix))
            assert (lm == 0.0) == own, (op, w, lm)
            assert (ix > 0.0) == own, (op, w, ix)
    # the program: a step whose only objective is the index's term moves
    # the index's parameters alone.  Labels that cost nothing are not to
    # be had, so the op is asked directly.
    op = next(o for o in m.ops if o.name == "attn_1")
    x = jax.random.normal(jax.random.key(5), (BATCH, 32, 64), jnp.float32)

    def term(params):
        terms = {}
        op.forward(params, [x], FwdCtx(training=True, losses=terms))
        return terms["dsa_index_kl"]

    def output(params):
        return jnp.sum(jnp.square(op.forward(params, [x], FwdCtx())[0]))
    g_term, g_out = jax.grad(term)(p["attn_1"]), jax.grad(output)(p["attn_1"])
    for w in p["attn_1"]:
        assert (float(jnp.abs(g_term[w]).max()) > 0) == (w in INDEX_WEIGHTS)
        assert (float(jnp.abs(g_out[w]).max()) == 0) == (w in INDEX_WEIGHTS)


def test_the_reported_loss_is_the_objective(devices):
    m, inputs, labels = _model()
    lm, index_term = ref.loss_terms(_params(m), inputs, labels, **SMALL)
    assert _step(m) == pytest.approx(float(lm) + float(index_term), rel=1e-5)
    assert "dsa_index_kl" in m._metric_keys()


# The steps of the models that take no loss term and none of the new
# options lower to the text they lowered to at the parent commit:
# sha256 of `train_step_hlo()` at the sizes below, computed there with
# this function.  A later PR that means to change one of these programs
# computes the digest anew and says so.  PR 38 did: the metric
# accumulator of the first call is committed to the mesh, so these are
# the digests its parent's tree gave after its first step (its second
# signature, the one every steady step ran), where before they were those
# of the signature that only the first step ran.  PERF.md section 6,
# PR 38, has the command that computed them there and what it printed.
STEP_DIGESTS = {
    "deepseek-v2/float32":
    "fbe75e0af80c273c6e229a4821b2e9b83e11254e85a06d305e981f658dc9c805",
    "deepseek-v2/bfloat16":
    "02c6cfdf8a4e81269e34c7bfc7d246dd737950764fb3a7612f11bf3cce02c030",
    "gpt2/bfloat16":
    "53ef7a97cb3ddb0f6c1e5141bec7bc3dc10e89e11d5cd3ee40218cec8c843628"}


DSV2_SMALL = dict(
    seq_length=16, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, intermediate_size=96, moe_intermediate_size=32,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000,
    rope_scaling=dict(factor=40, original_max_position_embeddings=4096,
                      beta_fast=32, beta_slow=1, mscale=0.707,
                      mscale_all_dim=0.707, type="yarn"),
    rms_norm_eps=1e-6, n_routed_experts=16, num_experts_per_tok=3, n_group=4,
    topk_group=2, routed_scaling_factor=16.0, n_shared_experts=2,
    vocab_size=128, experts_held=4, first_expert=4, tile_rows=8)


def _step_digest(which):
    name, dtype = which.split("/")
    cfg = ff.FFConfig()
    cfg.parse_args(["-b", "4", "-ll:tpu", "1"]
                   + (["--bf16"] if dtype == "bfloat16" else []))
    m = ff.FFModel(cfg)
    zeros = np.zeros((4, 16), np.int32)
    if name == "gpt2":
        tok, pos, _ = build_transformer(m, 4, seq_length=16, num_layers=2,
                                        embed_dim=32, num_heads=2,
                                        vocab_size=64)
        opt, batch = ff.AdamOptimizer(m, alpha=1e-4), {tok: zeros, pos: zeros}
    else:
        tok, _ = build_deepseek_v2(m, 4, **DSV2_SMALL)
        opt, batch = ff.SGDOptimizer(m, lr=0.01), {tok: zeros}
    m.compile(opt, "sparse_categorical_crossentropy",
              [ff.MetricsType.ACCURACY])
    m.init_layers(seed=3)
    m.set_batch(batch, zeros)
    return hashlib.sha256(m.train_step_hlo().encode()).hexdigest()


@pytest.mark.parametrize("which", sorted(STEP_DIGESTS))
def test_other_models_steps_are_the_programs_they_were(devices, which):
    assert _step_digest(which) == STEP_DIGESTS[which]


# ---------------------------------------------------------------------------
# the chip's shares
# ---------------------------------------------------------------------------

def _bare_op(cls, *args, **kw):
    m = ff.FFModel(ff.FFConfig())
    x = m.create_tensor((2, 32, 64), nchw=False)
    op = cls(m, x, *args, **kw)
    op.impl = "xla"
    return op


def _random(op, seed):
    keys = jax.random.split(jax.random.key(seed), len(op.weights))
    return {w.name: 0.3 * jax.random.normal(k, w.dims, jnp.float32)
            for w, k in zip(op.weights, keys)}


@pytest.mark.parametrize("kind", ["full", "window"])
def test_head_shares_add_up_to_the_uncut_attention(kind):
    """Two chips of two heads each against the reference's four: the
    partial outputs add up, and on a full layer every share computes the
    same selection (the index is whole on each)."""
    sizes = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16, rope_theta=8e7,
                 eps=1e-5, gate="headwise", latent_rescale=True)
    sizes.update(dict(index=(4, 16, 8)) if kind == "full"
                 else dict(window=5))
    whole = _bare_op(LatentAttention, 4, **sizes)
    p = _random(whole, 0)
    x = jax.random.normal(jax.random.key(1), (2, 32, 64), jnp.float32)
    ones = {"scale": jnp.ones((64,))}
    cfg = (4, 24, 16, 16, 8, 16, 1e-5, 8e7, sizes.get("window"), True,
           sizes.get("index"))
    with jax.default_matmul_precision("highest"):
        want = ref._attention(x, ones, p, cfg=cfg)[0] - x
        h = ref._rms_norm(x, ones["scale"], 1e-5)
        got, kept = 0.0, []
        for first in (0, 2):             # two chips, two heads each
            cols = lambda w, d: w.reshape(w.shape[0], 4, d)[
                :, first:first + 2].reshape(w.shape[0], 2 * d)
            share = dict(p, w_uq=cols(p["w_uq"], 24),
                         w_ukv=cols(p["w_ukv"], 32),
                         w_gate=p["w_gate"][:, first:first + 2],
                         w_o=p["w_o"][first * 16:(first + 2) * 16])
            op = _bare_op(LatentAttention, 2, **sizes)
            got = got + op.forward(share, [h], FwdCtx())[0]
            if kind == "full":
                c_q = ref._rms_norm(h @ p["w_dq"], p["q_norm"], 1e-5) \
                    * (64 / 24) ** 0.5
                kept.append(np.asarray(dsa.select_topk(
                    op._index_scores(share, h, c_q), 8)))
    assert _rel(got, want) <= 1e-5
    if kind == "full":
        np.testing.assert_array_equal(kept[0], kept[1])
        scores = ref.index_scores(h, c_q, p, 4, 16, 8, 8e7, 1e-5)
        np.testing.assert_array_equal(
            kept[0], np.asarray(ref.selected_keys(scores, 8)))
        assert (kept[0].sum(-1) == np.minimum(np.arange(32) + 1, 8)).all()


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of four experts each, a budget that drops nothing:
    sigmoid scores, a token's weights normalised over all its choices
    wherever they live; the routed parts add up, the shared expert (every
    chip computes it alike) counted once."""
    kw = dict(n_shared_experts=1, capacity_factor=16.0, tile_rows=8,
              scoring="sigmoid", select_bias=True, norm_topk_prob=True)
    whole = _bare_op(RoutedExperts, 16, 4, 32, **kw)
    p = _random(whole, 2)
    x = jax.random.normal(jax.random.key(3), (2, 32, 64), jnp.float32)
    ones = {"scale": jnp.ones((64,))}
    with jax.default_matmul_precision("highest"):
        want = ref._expert_mlp(x, ones, p,
                               cfg=(4, 0, 16, 16.0, 1.0, 1e-5)) - x
        h = ref._rms_norm(x, ones["scale"], 1e-5)
        shared = ref._gated(h, p["shared_gate"], p["shared_up"],
                            p["shared_down"])
        got = shared
        for first in (0, 4, 8, 12):
            op = _bare_op(RoutedExperts, 16, 4, 32, experts_held=4,
                          first_expert=first, **kw)
            share = dict(p, **{w: p[w][first:first + 4]
                               for w in ("w_gate", "w_up", "w_down")})
            counts = {}
            ctx = FwdCtx(counters=counts,
                         stats_in={op.name: op.init_stats()})
            got = got + op.forward(share, [h], ctx)[0] - shared
            assert counts["moe_assignments_kept"] \
                == counts["moe_assignments_made"]
    assert _rel(got, want) <= 1e-5


def test_selection_bias_chooses_and_does_not_weigh():
    """A bias moves which experts a token gets and not what they weigh:
    with a large bias on expert 5 every token takes it, at its own score
    over the sum of the chosen scores."""
    s = jax.nn.sigmoid(jax.random.normal(jax.random.key(0), (16, 8)))
    bias = jnp.zeros((8,)).at[5].set(10.0)
    r = moe.route(s, top_k=2, n_group=1, topk_group=1, first=4, held=4,
                  budget=32, tile_rows=8, select_bias=bias,
                  norm_topk_prob=True)
    tokens = np.asarray(r["row_token"])
    weight = np.asarray(r["row_weight"])
    expert = 4 + np.repeat(np.asarray(r["tile_group"]), 8)
    other = np.asarray(jnp.max(s.at[:, 5].set(-1.0), axis=-1))
    for row in np.nonzero((tokens < 16) & (expert == 5))[0]:
        t = tokens[row]
        assert weight[row] == pytest.approx(
            float(s[t, 5]) / (float(s[t, 5]) + other[t]), rel=1e-5)
    assert ((tokens < 16) & (expert == 5)).sum() == 16


# ---------------------------------------------------------------------------
# the kernels: a window and a selection against dense masks
# ---------------------------------------------------------------------------

def _dense(q, k, v, keep, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _compare(how, keep, seq=64, d=32, dv=16, **blocks):
    ks = jax.random.split(jax.random.key(seq), 4)
    q, k = (jax.random.normal(kk, (2, 2, seq, d)) for kk in ks[:2])
    v, w = (jax.random.normal(kk, (2, 2, seq, dv)) for kk in ks[2:])
    scale = d ** -0.5
    kernel = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, scale=scale, interpret=True, **how,
        **blocks) * w)
    dense = lambda q, k, v: jnp.sum(_dense(q, k, v, keep, scale) * w)
    (a, ga), (b, gb) = (jax.value_and_grad(f, (0, 1, 2))(q, k, v)
                        for f in (kernel, dense))
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    for x, y in zip(ga, gb):
        assert float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(y))) <= 1e-5


@pytest.mark.parametrize("window,block_q,block_k", [
    (9, 16, 16), (17, 16, 16), (5, 32, 16), (40, 16, 32), (100, 16, 16)])
def test_window_kernel_against_a_dense_mask(window, block_q, block_k):
    """Bands narrower and wider than a block, unequal blocks, and a window
    longer than the sequence (plain causal)."""
    t = jnp.arange(64)
    keep = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - window)
    _compare(dict(window=window), keep[None, None], block_q=block_q,
             block_k=block_k)
    steps = tiling(64, 64, 32, True, block_q, block_k, window=window)
    assert set(steps) == {"flash_win_fwd", "flash_win_dq", "flash_win_dkv"}
    # the blocks that run are those that hold a pair of the band
    blocks = np.asarray(keep).reshape(64 // block_q, block_q,
                                      64 // block_k, block_k)
    assert steps["flash_win_fwd"]["body_steps"] \
        == int(blocks.any(axis=(1, 3)).sum())


@pytest.mark.parametrize("topk,block_q,block_k", [
    (8, 16, 16), (20, 32, 16), (64, 16, 16), (100, 16, 16)])
def test_selected_kernel_against_a_dense_mask(topk, block_q, block_k):
    """A selection of fewer keys than a block holds (queries with no key
    in some block), of more, and at a length under topk, where it is plain
    causal attention."""
    scores = jax.random.normal(jax.random.key(topk), (2, 64, 64))
    keep = dsa.select_topk(scores, topk)
    assert (np.asarray(keep).sum(-1)
            == np.minimum(np.arange(64) + 1, topk)).all()
    select = jnp.swapaxes(keep, 1, 2).astype(jnp.bfloat16)
    _compare(dict(select=select), keep[:, None], block_q=block_q,
             block_k=block_k)
    if topk >= 64:
        t = jnp.arange(64)
        np.testing.assert_array_equal(
            np.asarray(keep[0]), np.asarray(t[None, :] <= t[:, None]))


def test_selection_breaks_ties_by_the_lower_key():
    scores = jnp.zeros((1, 16, 16)).at[0, :, 3].set(1.0)
    keep = np.asarray(dsa.select_topk(scores, 4))[0]
    for t in range(16):
        want = {s for s in range(min(t + 1, 3))} | ({3} if t >= 3 else set())
        if t >= 4:
            want = {0, 1, 2, 3}
        assert set(np.nonzero(keep[t])[0]) == want, t
    np.testing.assert_array_equal(
        keep, np.asarray(ref.selected_keys(scores, 4))[0])


def test_index_scores_in_blocks_equal_the_whole(devices):
    """The scores and their gradient, reduced over the heads a block of
    queries at a time within super blocks that leave out the keys above
    them, equal the (heads, T, T) form they never hold."""
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (2, 32, 4, 16))
    k = jax.random.normal(ks[1], (2, 32, 16))
    w = jax.random.normal(ks[2], (2, 32, 4))
    g = jnp.tril(jax.random.normal(ks[3], (2, 32, 32)))
    causal = jnp.tril(jnp.ones((32, 32), bool))

    def whole(q, k, w):
        s = jax.nn.relu(jnp.einsum("bqhd,bkd->bqhk", q, k))
        return jnp.sum(jnp.where(causal, jnp.einsum("bqh,bqhk->bqk", w, s),
                                 0.0) * g)

    def blocks(q, k, w):
        return jnp.sum(jnp.where(causal, dsa.index_scores(q, k, w, jnp.float32, 4, 16),
                                 0.0) * g)
    with jax.default_matmul_precision("highest"):
        (a, ga), (b, gb) = (jax.value_and_grad(f, (0, 1, 2))(q, k, w)
                            for f in (blocks, whole))
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    for x, y in zip(ga, gb):
        assert _rel(x, y) <= 1e-5


def test_search_prices_the_new_work(devices):
    """The cost model prices a full layer above a window layer of the same
    widths and both above neither option, and the index's work does not
    shrink with the head degree (every share computes it whole)."""
    from flexflow_tpu.simulator.cost_model import CostModel
    from flexflow_tpu.simulator.machine import TPUMachineModel

    sizes = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16)
    plain = _bare_op(LatentAttention, 4, **sizes)
    window = _bare_op(LatentAttention, 4, window=5, **sizes)
    full = _bare_op(LatentAttention, 4, index=(4, 16, 8), **sizes)
    assert window.flops_per_sample() < plain.flops_per_sample()
    assert full.flops_per_sample() < plain.flops_per_sample()
    assert full.unsplit_cost_per_sample()[0] > 0 == \
        window.unsplit_cost_per_sample()[0]
    assert len({op.cost_key() for op in (plain, window, full)}) == 3
    cm = CostModel(TPUMachineModel.calibrated(num_devices=4), measure=False)
    one = ff.ParallelConfig(dims=(1, 1, 1))
    heads = ff.ParallelConfig(dims=(1, 1, 4))
    for op in (plain, window, full):
        assert 0 < cm.op_time(op, heads, "forward") \
            <= cm.op_time(op, one, "forward")
