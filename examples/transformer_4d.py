"""Decoder-only transformer LM under composed 4-D parallelism:
data x sequence (ring attention) x tensor (head/TP dense) x expert (MoE).

The reference predates transformers; this example exercises the
TPU-first capabilities layered on its SOAP machinery — every axis is
just a per-op ParallelConfig, so the same strategy files/search apply.

    python examples/transformer_4d.py -b 16 --seq 64 [--bf16]
"""

import sys
import time

try:
    import flexflow_tpu  # noqa: F401  (pip-installed)
except ImportError:  # source checkout without `pip install -e .`
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

import flexflow_tpu as ff
from flexflow_tpu.models.transformer import build_transformer
from flexflow_tpu.utils.compile_cache import enable_compile_cache


def top_level_task(argv=None, seq=64, layers=4, dim=128, heads=8,
                   vocab=1024, iters=6):
    cfg = ff.FFConfig(batch_size=16)
    argv = cfg.parse_args(argv)
    enable_compile_cache()
    for i, a in enumerate(list(argv or [])):
        if a == "--seq":
            seq = int(argv[i + 1])

    import jax

    nd = len(jax.devices())
    dp = max(1, nd // 4)
    sp = min(4, nd // dp)
    # attention: dp x sp (ring); MLP dense: dp x TP on features;
    # MoE blocks: dp x ep on the expert dim
    for i in range(layers):
        cfg.strategies[f"attn_{i}"] = ff.ParallelConfig(dims=(dp, sp, 1))
        cfg.strategies[f"mlp_up_{i}"] = ff.ParallelConfig(dims=(dp, 1, sp))
        cfg.strategies[f"mlp_down_{i}"] = ff.ParallelConfig(dims=(nd, 1, 1))
        cfg.strategies[f"moe_{i}"] = ff.ParallelConfig(dims=(dp, sp))

    model = ff.FFModel(cfg)
    tok, pos, _ = build_transformer(model, cfg.batch_size, seq_length=seq,
                                    num_layers=layers, embed_dim=dim,
                                    num_heads=heads, vocab_size=vocab,
                                    moe_every=2, num_experts=2 * max(2, sp))
    model.compile(ff.AdamOptimizer(model, alpha=1e-3),
                  ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.ACCURACY])
    model.init_layers()

    from flexflow_tpu.models.transformer import synthetic_lm_batch

    toks, posa, labels = synthetic_lm_batch(cfg.batch_size, seq, vocab)
    model.set_batch({tok: toks, pos: posa}, labels)
    model.train_iteration()
    model.sync()

    t0 = time.perf_counter()
    for _ in range(iters):
        model.train_iteration()
    model.sync()
    dt = time.perf_counter() - t0
    tokens_s = iters * cfg.batch_size * seq / dt
    print(f"4D parallel transformer: dp{dp} x sp{sp} over {nd} devices, "
          f"MoE every 2nd block — ELAPSED TIME = {dt:.4f}s, "
          f"THROUGHPUT = {tokens_s:.0f} tokens/s")
    return tokens_s


if __name__ == "__main__":
    top_level_task()
