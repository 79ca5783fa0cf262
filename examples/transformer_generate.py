"""Train-then-generate: a decoder transformer learns a deterministic
token pattern, then FFModel.generate() continues prompts with kv-cached
jitted decoding (beyond the training-only reference; the decode loop is
one lax.scan with static shapes — no per-token retraces).

Run: python examples/transformer_generate.py [-b 16] [--iterations 150]
"""

import sys

try:
    import flexflow_tpu  # noqa: F401  (pip-installed)
except ImportError:  # source checkout without `pip install -e .`
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import time

import numpy as np

import flexflow_tpu as ff
from flexflow_tpu.models.transformer import build_transformer
from flexflow_tpu.utils.compile_cache import enable_compile_cache


def cyclic_batch(batch_size, seq, vocab, seed):
    """Next token = (token + 1) mod vocab — trivially learnable."""
    rng = np.random.default_rng(seed)
    start = rng.integers(0, vocab, size=(batch_size, 1))
    toks = (start + np.arange(seq)) % vocab
    toks = toks.astype(np.int32)
    posa = np.broadcast_to(np.arange(seq, dtype=np.int32),
                           (batch_size, seq)).copy()
    labels = ((toks + 1) % vocab).astype(np.int32)
    return toks, posa, labels


def top_level_task(argv=None, seq=32, vocab=32, iterations=150):
    cfg = ff.FFConfig(batch_size=16)
    cfg.parse_args(argv)
    enable_compile_cache()
    if cfg.iterations > 0:  # --iterations (parse_args consumes the flag)
        iterations = cfg.iterations

    model = ff.FFModel(cfg)
    tok, pos, _ = build_transformer(model, cfg.batch_size, seq_length=seq,
                                    num_layers=2, embed_dim=64,
                                    num_heads=4, vocab_size=vocab)
    model.compile(ff.AdamOptimizer(model, alpha=3e-3),
                  ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.ACCURACY])
    model.init_layers(seed=1)

    for it in range(iterations):
        toks, posa, labels = cyclic_batch(cfg.batch_size, seq, vocab, it)
        model.set_batch({tok: toks, pos: posa}, labels)
        model.train_iteration()
    model.sync()
    pm = model.get_metrics()
    print(f"train accuracy {pm.accuracy:.1f}%")

    # Prompt with the first 4 tokens of fresh cyclic rows; the model must
    # continue the +1 pattern.
    toks, _, _ = cyclic_batch(cfg.batch_size, seq, vocab, 10_000)
    prompt, want = toks[:, :4], toks[:, 4:12]
    t0 = time.perf_counter()
    out = model.generate(prompt, 8)
    dt = time.perf_counter() - t0
    acc = (out == want).mean() * 100.0
    print(f"generate: {out.shape[1]} tokens x {out.shape[0]} rows "
          f"in {dt:.2f}s, continuation accuracy {acc:.1f}%")
    print(f"  prompt {prompt[0].tolist()} -> {out[0].tolist()}")
    assert acc >= 90.0, f"continuation accuracy {acc:.1f}% < 90%"
    return acc


if __name__ == "__main__":
    top_level_task()
