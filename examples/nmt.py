"""NMT LSTM seq2seq training example (reference: nmt/nmt.cc:31-84).

Reference defaults: bs=64/worker, 2 layers, seq 20, hidden=embed=2048,
vocab 20k; times 10 iterations and prints wall-clock.

    python examples/nmt.py -b 64 --bf16 [--seq 20 --hidden 2048 --vocab 20480]
                                        [--translate]
"""

import sys
import time

try:
    import flexflow_tpu  # noqa: F401  (pip-installed)
except ImportError:  # source checkout without `pip install -e .`
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import flexflow_tpu as ff
from flexflow_tpu.models.nmt import build_nmt, synthetic_batch
from flexflow_tpu.utils.compile_cache import enable_compile_cache


def main(argv=None):
    cfg = ff.FFConfig(batch_size=64)
    rest = cfg.parse_args(argv)
    enable_compile_cache()
    seq, hidden, embed, vocab, layers, iters = 20, 2048, 2048, 20 * 1024, 2, 10
    translate = False
    i = 0
    while i < len(rest):
        if rest[i] == "--seq":
            i += 1; seq = int(rest[i])
        elif rest[i] == "--hidden":
            i += 1; hidden = int(rest[i])
        elif rest[i] == "--embed":
            i += 1; embed = int(rest[i])
        elif rest[i] == "--vocab":
            i += 1; vocab = int(rest[i])
        elif rest[i] == "--layers":
            i += 1; layers = int(rest[i])
        elif rest[i] == "--iters":
            i += 1; iters = int(rest[i])
        elif rest[i] == "--translate":
            translate = True
        i += 1

    model = ff.FFModel(cfg)
    src, dst, _ = build_nmt(model, cfg.batch_size, seq_length=seq,
                            num_layers=layers, hidden_size=hidden,
                            embed_size=embed, vocab_size=vocab)
    model.compile(ff.SGDOptimizer(model, lr=0.1),
                  ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
    model.init_layers()
    s, d, labels = synthetic_batch(cfg.batch_size, seq, vocab)
    model.set_batch({src: s, dst: d}, labels)
    model.train_iteration()
    model.sync()
    model.reset_metrics()

    ts_start = time.perf_counter()
    for _ in range(iters):
        model.forward()
        model.backward()
        model.update()
    model.sync()
    run_time = time.perf_counter() - ts_start
    tokens = iters * cfg.batch_size * seq
    print(f"time = {run_time:.4f}s ({tokens / run_time:.0f} tokens/s, "
          f"{iters * cfg.batch_size / run_time:.1f} samples/s)")

    if translate:
        # greedy seq2seq decoding demo (beyond the training-only
        # reference): encode the source batch once, step the decoder
        from flexflow_tpu.models.nmt import greedy_translate

        t0 = time.perf_counter()
        out = greedy_translate(model, src, dst, s, seq, bos_id=1)
        dt = time.perf_counter() - t0
        print(f"translate: {out.shape[0]}x{out.shape[1]} tokens in "
              f"{dt:.2f}s; first row: {out[0, :10].tolist()}...")
    return run_time


if __name__ == "__main__":
    main()
