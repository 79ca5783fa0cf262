"""Train-then-serve: a decoder transformer learns a deterministic token
pattern, then an ``InferenceEngine`` serves a burst of concurrent
mixed-length requests through the continuous-batching loop + stdlib
HTTP front end — and every greedy output is checked bitwise against a
one-shot ``FFModel.generate()`` of the same prompt (the transparency
contract, docs/serving.md).

Run: python examples/transformer_serve.py [-b 16] [--iterations 150]
"""

import sys

try:
    import flexflow_tpu  # noqa: F401  (pip-installed)
except ImportError:  # source checkout without `pip install -e .`
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import json
import threading
import time
import urllib.request

import numpy as np

import flexflow_tpu as ff
from flexflow_tpu.models.transformer import build_transformer
from flexflow_tpu.serving import InferenceEngine, ServingAPI
from flexflow_tpu.utils.compile_cache import enable_compile_cache


def cyclic_batch(batch_size, seq, vocab, seed):
    """Next token = (token + 1) mod vocab — trivially learnable."""
    rng = np.random.default_rng(seed)
    start = rng.integers(0, vocab, size=(batch_size, 1))
    toks = ((start + np.arange(seq)) % vocab).astype(np.int32)
    posa = np.broadcast_to(np.arange(seq, dtype=np.int32),
                           (batch_size, seq)).copy()
    labels = ((toks + 1) % vocab).astype(np.int32)
    return toks, posa, labels


def top_level_task(argv=None, seq=32, vocab=32, iterations=150):
    cfg = ff.FFConfig(batch_size=16)
    cfg.parse_args(argv)
    enable_compile_cache()
    if cfg.iterations > 0:
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

    # 8 concurrent requests, mixed prompt/output lengths, fired over HTTP
    # at an ephemeral port; the single engine loop batches them all.
    rng = np.random.default_rng(7)
    toks, _, _ = cyclic_batch(8, seq, vocab, 10_000)
    reqs = [(toks[i, :int(rng.integers(3, 9))],
             int(rng.integers(6, 13))) for i in range(8)]
    results = [None] * len(reqs)

    engine = InferenceEngine(model, max_batch=4, max_seq=seq,
                             max_new_tokens=16)
    t0 = time.perf_counter()
    with engine, ServingAPI(engine, port=0) as api:
        print(f"serving on {api.url}")

        def fire(i):
            prompt, n = reqs[i]
            body = json.dumps({"prompt": prompt.tolist(),
                               "max_new_tokens": n}).encode()
            r = urllib.request.Request(
                f"{api.url}/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(r, timeout=300) as resp:
                results[i] = json.loads(resp.read())

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
            time.sleep(0.01)        # staggered arrivals
        for t in threads:
            t.join()
        stats = engine.stats()
    wall = time.perf_counter() - t0

    matches = 0
    for (prompt, n), r in zip(reqs, results):
        want = model.generate(prompt[None], n)[0]
        got = np.asarray(r["tokens"], np.int32)
        matches += bool(np.array_equal(got, want))
    ttfts = sorted(r["ttft_s"] for r in results)
    print(f"served {len(reqs)} requests in {wall:.2f}s · "
          f"occupancy {stats['mean_occupancy']:.2f} · "
          f"TTFT max {ttfts[-1] * 1e3:.0f}ms · "
          f"greedy match {matches}/{len(reqs)} vs generate()")
    print(f"  prompt {reqs[0][0].tolist()} -> {results[0]['tokens']}")
    assert matches == len(reqs), "continuous batch diverged from generate()"
    assert stats["mean_occupancy"] > 1.0, stats
    return matches


if __name__ == "__main__":
    top_level_task()
