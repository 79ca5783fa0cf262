"""Transformer LM: attention op, LayerNorm, and seq-parallel strategies.

Covers the long-context path end to end: the MultiHeadAttention op under
pure data parallelism must match the same graph under a hybrid
(dp × sp) sequence-parallel strategy, and the model must train.
"""

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.config import ParallelConfig
from flexflow_tpu.models.transformer import build_transformer

B, S, E, HEADS, V = 8, 32, 32, 4, 64


def _build(cfg):
    m = ff.FFModel(cfg)
    tok, pos, out = build_transformer(m, cfg.batch_size, seq_length=S,
                                      num_layers=2, embed_dim=E,
                                      num_heads=HEADS, vocab_size=V)
    m.compile(ff.SGDOptimizer(lr=0.1),
              ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
              [ff.MetricsType.ACCURACY])
    return m, tok, pos


def _batch(rng):
    toks = rng.integers(0, V, size=(B, S)).astype(np.int32)
    pos = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S)).copy()
    labels = np.roll(toks, -1, axis=1).astype(np.int32)
    return toks, pos, labels


def test_transformer_dp_vs_seq_parallel_same_forward(devices):
    rng = np.random.default_rng(0)
    toks, pos_arr, labels = _batch(rng)

    outs = {}
    for mode, strat in (("dp", None), ("sp", (2, 4, 1))):
        cfg = ff.FFConfig(batch_size=B, compute_dtype="float32")
        if strat is not None:
            for i in range(2):
                cfg.strategies[f"attn_{i}"] = ParallelConfig(
                    dims=strat, device_ids=tuple(range(8)))
        m, tok, pos = _build(cfg)
        m.init_layers(seed=0)
        if strat is not None:
            attn = next(op for op in m.ops if op.name == "attn_0")
            assert attn.pc.dims == strat
        m.set_batch({tok: toks, pos: pos_arr}, labels)
        m.eval_batch()
        _, probs = m._eval_step_fn(m._params, m._stats, m._batch)
        outs[mode] = np.asarray(probs)
    np.testing.assert_allclose(outs["dp"], outs["sp"], atol=2e-4)


def test_transformer_trains(devices):
    cfg = ff.FFConfig(batch_size=B, compute_dtype="float32")
    for i in range(2):
        cfg.strategies[f"attn_{i}"] = ParallelConfig(
            dims=(2, 4, 1), device_ids=tuple(range(8)))
    m, tok, pos = _build(cfg)
    m.init_layers(seed=1)
    rng = np.random.default_rng(1)
    toks, pos_arr, _ = _batch(rng)
    labels = np.broadcast_to(np.arange(S, dtype=np.int32) % V, (B, S)).copy()

    losses = []
    for _ in range(30):
        m.set_batch({tok: toks, pos: pos_arr}, labels)
        m.train_iteration()
        m.sync()
        m.get_metrics()
        losses.append(m.last_loss)
        m.reset_metrics()
    assert losses[-1] < losses[0] * 0.5, losses


@pytest.mark.slow
def test_transformer_4d_example(devices):
    """dp x sp x tp x ep in one graph (examples/transformer_4d.py)."""
    from examples.transformer_4d import top_level_task

    tokens_s = top_level_task([], seq=16, layers=2, dim=32, heads=4,
                              vocab=64, iters=2)
    assert tokens_s > 0


def test_generate_matches_full_forward_oracle(devices):
    """kv-cached jitted generate() == iterative full-forward argmax
    (the cache path and the training forward are numerically the same
    computation)."""
    import jax.numpy as jnp

    from flexflow_tpu.models.transformer import build_transformer

    S, V, B, P, N = 16, 50, 4, 5, 6
    cfg = ff.FFConfig(batch_size=B)
    m = ff.FFModel(cfg)
    tok, pos, _ = build_transformer(m, B, seq_length=S, num_layers=2,
                                    embed_dim=32, num_heads=4, vocab_size=V)
    m.compile(ff.SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
              ["accuracy"])
    m.init_layers(seed=11)

    rng = np.random.default_rng(3)
    prompt = rng.integers(0, V, size=(B, P)).astype(np.int32)
    out = m.generate(prompt, N)
    assert out.shape == (B, N)

    seq = prompt.copy()
    for _ in range(N):
        L = seq.shape[1]
        toks_full = np.zeros((B, S), np.int32)
        toks_full[:, :L] = seq
        posa = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S)).copy()
        env, _ = m._run_graph(m._params, m._stats,
                              {f"in_{tok.guid}": jnp.asarray(toks_full),
                               f"in_{pos.guid}": jnp.asarray(posa)},
                              False, None)
        probs = np.asarray(env[m.final_tensor().guid])
        nxt = probs[:, L - 1, :].argmax(-1).astype(np.int32)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, seq[:, P:])

    # sampled decoding: right shape/range, deterministic per seed
    s1 = m.generate(prompt, N, temperature=0.8, seed=5)
    s2 = m.generate(prompt, N, temperature=0.8, seed=5)
    np.testing.assert_array_equal(s1, s2)
    assert s1.shape == (B, N) and (s1 >= 0).all() and (s1 < V).all()


@pytest.mark.slow
def test_beam_search(devices):
    """beam_size=1 equals greedy generate; with K=V and N=2 the beam is
    exhaustive-optimal (verified by enumerating all V^2 continuations);
    eos freezing stops a finished beam's score."""
    import itertools

    import jax.numpy as jnp

    from flexflow_tpu.models.transformer import build_transformer

    S2, V2, B2, P2 = 12, 6, 3, 4
    cfg = ff.FFConfig(batch_size=B2)
    m = ff.FFModel(cfg)
    tok, pos, _ = build_transformer(m, B2, seq_length=S2, num_layers=2,
                                    embed_dim=16, num_heads=2,
                                    vocab_size=V2)
    m.compile(ff.SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
              ["accuracy"])
    m.init_layers(seed=21)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, V2, size=(B2, P2)).astype(np.int32)

    g = m.generate(prompt, 3)
    seqs1, _ = m.beam_search(prompt, 3, beam_size=1)
    np.testing.assert_array_equal(seqs1[:, 0, :], g)

    N = 2
    seqs, scores = m.beam_search(prompt, N, beam_size=V2)
    assert (np.diff(scores, axis=1) <= 1e-6).all()  # best first

    def seq_logp(row, cont):
        seq = np.concatenate([prompt[row], np.asarray(cont, np.int32)])
        lp = 0.0
        for i, t in enumerate(cont):
            L = P2 + i
            tf = np.zeros((B2, S2), np.int32)
            tf[:, :len(seq)] = seq
            posa = np.broadcast_to(np.arange(S2, dtype=np.int32),
                                   (B2, S2)).copy()
            env, _ = m._run_graph(m._params, m._stats,
                                  {f"in_{tok.guid}": jnp.asarray(tf),
                                   f"in_{pos.guid}": jnp.asarray(posa)},
                                  False, None)
            p = np.asarray(env[m.final_tensor().guid])[row, L - 1, t]
            lp += np.log(p + 1e-30)
        return lp

    for row in range(B2):
        best = max(itertools.product(range(V2), repeat=N),
                   key=lambda c: seq_logp(row, c))
        assert tuple(seqs[row, 0, :].tolist()) == best
        np.testing.assert_allclose(scores[row, 0], seq_logp(row, best),
                                   rtol=1e-4, atol=1e-4)

    # eos freezing: a finished FINITE-score beam keeps emitting eos
    # (score -inf beams are fillers when every candidate is impossible
    # — their suffixes are arbitrary top_k tie-breaks)
    eos = int(seqs[0, 0, 0])
    seqs_e, scores_e = m.beam_search(prompt, 4, beam_size=2, eos_id=eos)
    checked = 0
    for row in range(B2):
        for k in range(2):
            if not np.isfinite(scores_e[row, k]):
                continue
            s = seqs_e[row, k].tolist()
            if eos in s:
                i = s.index(eos)
                assert all(t == eos for t in s[i:]), s
                checked += 1
    assert checked > 0


@pytest.mark.slow
def test_generate_on_sharded_model(devices):
    """generate/beam_search on a model trained over the 8-device mesh
    with head-TP attention: the decode jit consumes the sharded params
    directly (GSPMD computation-follows-data), no gather/resave step."""
    from flexflow_tpu.models.transformer import build_transformer
    from flexflow_tpu.parallel.mesh import Machine

    import jax

    B2, S2, V2 = 8, 16, 50
    cfg = ff.FFConfig(batch_size=B2, workers_per_node=8)
    for i in range(2):
        cfg.strategies[f"attn_{i}"] = ff.ParallelConfig(dims=(2, 1, 4))
    m = ff.FFModel(cfg)
    tok, pos, _ = build_transformer(m, B2, seq_length=S2, num_layers=2,
                                    embed_dim=32, num_heads=4,
                                    vocab_size=V2)
    m.compile(ff.SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
              ["accuracy"], machine=Machine(jax.devices()))
    m.init_layers(seed=11)
    rng = np.random.default_rng(3)
    toks = rng.integers(0, V2, size=(B2, S2)).astype(np.int32)
    posa = np.broadcast_to(np.arange(S2, dtype=np.int32), (B2, S2)).copy()
    m.set_batch({tok: toks, pos: posa},
                np.roll(toks, -1, 1).astype(np.int32))
    m.train_iteration()
    m.sync()

    prompt = rng.integers(0, V2, size=(B2, 5)).astype(np.int32)
    out = m.generate(prompt, 4)
    assert out.shape == (B2, 4)
    seqs, scores = m.beam_search(prompt, 3, beam_size=2)
    assert seqs.shape == (B2, 2, 3)
    assert (np.diff(scores, axis=1) <= 1e-6).all()


@pytest.mark.slow
def test_beam_length_penalty_reranks(devices):
    """length_penalty re-ranks finished-short vs long beams by the GNMT
    normalization; raw scores stay untouched sums."""
    from flexflow_tpu.models.transformer import build_transformer

    S2, V2, B2, P2 = 12, 6, 2, 3
    cfg = ff.FFConfig(batch_size=B2)
    m = ff.FFModel(cfg)
    tok, pos, _ = build_transformer(m, B2, seq_length=S2, num_layers=1,
                                    embed_dim=16, num_heads=2,
                                    vocab_size=V2)
    m.compile(ff.SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
              ["accuracy"])
    m.init_layers(seed=3)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, V2, size=(B2, P2)).astype(np.int32)

    s0, sc0 = m.beam_search(prompt, 4, beam_size=3, eos_id=0)
    s1, sc1 = m.beam_search(prompt, 4, beam_size=3, eos_id=0,
                            length_penalty=1.0)
    # same beam SET per row, possibly re-ordered; normalized order holds
    for row in range(B2):
        assert {tuple(x) for x in s0[row]} == {tuple(x) for x in s1[row]}
        fin = np.isfinite(sc1[row])
        lens = np.where((s1[row] == 0).any(-1),
                        (s1[row] == 0).argmax(-1) + 1, 4)
        norm = sc1[row] / (((5.0 + lens) / 6.0) ** 1.0)
        assert (np.diff(norm[fin]) <= 1e-6).all()


@pytest.mark.slow
def test_generate_bfloat16(devices):
    """The bench's decode config: kv caches and activations in bf16
    (argmax over f32-cast probs keeps token selection stable)."""
    from flexflow_tpu.models.transformer import build_transformer

    cfg = ff.FFConfig(batch_size=4, compute_dtype="bfloat16")
    m = ff.FFModel(cfg)
    tok, pos, _ = build_transformer(m, 4, seq_length=16, num_layers=2,
                                    embed_dim=32, num_heads=4,
                                    vocab_size=50)
    m.compile(ff.SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
              ["accuracy"])
    m.init_layers(seed=2)
    prompt = np.random.default_rng(0).integers(
        0, 50, size=(4, 1)).astype(np.int32)
    out = m.generate(prompt, 8)
    assert out.shape == (4, 8) and (out >= 0).all() and (out < 50).all()


@pytest.mark.slow
def test_generate_top_k_top_p(devices):
    """top_k=1 sampling equals greedy for any temperature; top_p keeps
    sampled tokens inside the nucleus (checked against per-step
    full-forward distributions)."""
    from flexflow_tpu.models.transformer import build_transformer

    cfg = ff.FFConfig(batch_size=4)
    m = ff.FFModel(cfg)
    tok, pos, _ = build_transformer(m, 4, seq_length=16, num_layers=2,
                                    embed_dim=32, num_heads=4,
                                    vocab_size=20)
    m.compile(ff.SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
              ["accuracy"])
    m.init_layers(seed=9)
    prompt = np.random.default_rng(5).integers(
        0, 20, size=(4, 3)).astype(np.int32)

    greedy = m.generate(prompt, 6)
    k1 = m.generate(prompt, 6, temperature=1.7, top_k=1, seed=3)
    np.testing.assert_array_equal(k1, greedy)

    # nucleus: every sampled token must be at least as probable as the
    # nucleus cutoff of its step's distribution
    p = 0.5
    out = m.generate(prompt, 6, temperature=1.0, top_p=p, seed=11)
    import jax.numpy as jnp

    seq = prompt.copy()
    for i in range(6):
        L = seq.shape[1]
        tf = np.zeros((4, 16), np.int32)
        tf[:, :L] = seq
        posa = np.broadcast_to(np.arange(16, dtype=np.int32),
                               (4, 16)).copy()
        env, _ = m._run_graph(m._params, m._stats,
                              {f"in_{tok.guid}": jnp.asarray(tf),
                               f"in_{pos.guid}": jnp.asarray(posa)},
                              False, None)
        probs = np.asarray(env[m.final_tensor().guid])[:, L - 1, :]
        for row in range(4):
            srt = np.sort(probs[row])[::-1]
            keep_n = int((np.cumsum(srt) < p).sum())
            cutoff = srt[keep_n]
            assert probs[row, out[row, i]] >= cutoff - 1e-7
        seq = np.concatenate([seq, out[:, i:i + 1]], axis=1)


@pytest.mark.slow
def test_generate_compile_cache_reuse(devices):
    """New seeds/temperatures reuse the compiled decode scan (seed and
    temp are runtime arguments, not trace constants)."""
    from flexflow_tpu.models.transformer import build_transformer

    cfg = ff.FFConfig(batch_size=4)
    m = ff.FFModel(cfg)
    tok, pos, _ = build_transformer(m, 4, seq_length=16, num_layers=1,
                                    embed_dim=16, num_heads=2,
                                    vocab_size=20)
    m.compile(ff.SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
              ["accuracy"])
    m.init_layers(seed=1)
    prompt = np.random.default_rng(0).integers(
        0, 20, size=(4, 2)).astype(np.int32)
    for seed in range(3):
        m.generate(prompt, 3, temperature=0.7 + 0.1 * seed, seed=seed)
    assert len(m._gen_cache) == 1  # one sampled-scan executable
    m.generate(prompt, 3)          # greedy variant adds exactly one more
    assert len(m._gen_cache) == 2


# ---------------------------------------------------------------------------
# build_transformer is build_decoder with GPT-2's parts: the graph it built
# before it took its parts as arguments (PR 31's builder, copied below) and
# the one it builds now are the same program
# ---------------------------------------------------------------------------

def _build_transformer_pr31(m, batch_size, seq_length, num_layers, embed_dim,
                            num_heads, vocab_size, mlp_ratio=4, moe_every=0,
                            num_experts=8):
    from flexflow_tpu.ops.embedding import AggrMode

    tok = m.create_tensor((batch_size, seq_length), name="tokens",
                          dtype="int32", nchw=False)
    pos = m.create_tensor((batch_size, seq_length), name="positions",
                          dtype="int32", nchw=False)
    x = m.embedding(tok, vocab_size, embed_dim, aggr=AggrMode.NONE,
                    name="tok_embed")
    p = m.embedding(pos, seq_length, embed_dim, aggr=AggrMode.NONE,
                    name="pos_embed")
    x = m.add(x, p, name="embed_add")
    for i in range(num_layers):
        h = m.layer_norm(x, name=f"ln1_{i}")
        h = m.multihead_attention(h, num_heads=num_heads, causal=True,
                                  dropout=0.0, name=f"attn_{i}")
        x = m.add(x, h, name=f"res_attn_{i}")
        h = m.layer_norm(x, name=f"ln2_{i}")
        if moe_every and (i + 1) % moe_every == 0:
            h = m.expert_mlp(h, num_experts=num_experts,
                             hidden_size=embed_dim * mlp_ratio,
                             activation="gelu", name=f"moe_{i}")
        else:
            h = m.dense(h, embed_dim * mlp_ratio, activation="gelu",
                        name=f"mlp_up_{i}")
            h = m.dense(h, embed_dim, name=f"mlp_down_{i}")
        x = m.add(x, h, name=f"res_mlp_{i}")
    x = m.layer_norm(x, name="ln_f")
    return tok, pos, m.softmax(m.dense(x, vocab_size, name="lm_head"),
                               name="softmax")


@pytest.mark.parametrize("moe_every", [0, 2])
def test_gpt2_graph_is_the_one_it_was(devices, moe_every):
    sizes = dict(seq_length=S, num_layers=2, embed_dim=E, num_heads=HEADS,
                 vocab_size=V, moe_every=moe_every, num_experts=4)
    models = []
    for builder in (_build_transformer_pr31, build_transformer):
        cfg = ff.FFConfig(batch_size=B, compute_dtype="bfloat16")
        m = ff.FFModel(cfg)
        tok, pos, _ = builder(m, B, **sizes)
        m.compile(ff.AdamOptimizer(m, alpha=1e-4),
                  ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.ACCURACY])
        m.init_layers(seed=0)
        toks, pos_arr, labels = _batch(np.random.default_rng(0))
        m.set_batch({tok: toks, pos: pos_arr}, labels)
        models.append(m)
    was, now = models
    assert [(op._type, op.name) for op in now.ops] \
        == [(op._type, op.name) for op in was.ops]
    params = lambda m: sorted(k for k in m.placement()
                              if not k.startswith("batch/"))
    assert params(now) == params(was)
    assert now._metric_keys() == was._metric_keys()
    assert now.train_step_hlo() == was.train_step_hlo()
