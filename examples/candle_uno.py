"""CANDLE-UNO training example (reference: examples/cpp/candle_uno/
candle_uno.cc — cancer drug-response regression).

    python examples/candle_uno.py -e 1 -b 64 [--bf16]

Multi-input MLP with per-feature encoder towers, MSE loss; synthetic
feature data (the reference's default mode when no CANDLE data dir is
given). Prints the reference's ELAPSED TIME / THROUGHPUT line.
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
from flexflow_tpu.models.candle_uno import (DEFAULT_FEATURE_SHAPES,
                                            DEFAULT_INPUT_FEATURES,
                                            build_candle_uno)
from flexflow_tpu.utils.compile_cache import enable_compile_cache


def synthetic_batch(batch_size, input_features, feature_shapes, seed=0):
    rng = np.random.default_rng(seed)
    xs = {}
    for name, fea_type in sorted(input_features.items()):
        dim = feature_shapes[fea_type]
        xs[name] = rng.standard_normal((batch_size, dim), dtype=np.float32)
    labels = rng.standard_normal((batch_size, 1), dtype=np.float32)
    return xs, labels


def main(argv=None):
    cfg = ff.FFConfig()
    cfg.parse_args(argv)
    enable_compile_cache()
    print(f"batchSize({cfg.batch_size}) workersPerNodes({cfg.workers_per_node}) "
          f"numNodes({cfg.num_nodes})")

    # Reference uses smaller encoder towers when run without data; keep
    # the published architecture (3×1000 towers + 3×1000 trunk).
    model = ff.FFModel(cfg)
    inputs, _ = build_candle_uno(model, cfg.batch_size)
    model.compile(ff.SGDOptimizer(model, lr=0.001),
                  ff.LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
                  [ff.MetricsType.MEAN_SQUARED_ERROR,
                   ff.MetricsType.ROOT_MEAN_SQUARED_ERROR])
    model.init_layers()

    xs, labels = synthetic_batch(cfg.batch_size, DEFAULT_INPUT_FEATURES,
                                 DEFAULT_FEATURE_SHAPES)
    batch = {inputs[name]: arr for name, arr in xs.items()}

    model.set_batch(batch, labels)
    model.train_iteration()  # warmup/compile
    model.sync()

    iterations = 32
    ts_start = time.perf_counter()
    for _ in range(cfg.epochs):
        model.reset_metrics()
        for _ in range(iterations):
            model.train_iteration()
    model.sync()
    run_time = time.perf_counter() - ts_start
    model.print_metrics()
    num_samples = iterations * cfg.epochs * cfg.batch_size
    print(f"ELAPSED TIME = {run_time:.4f}s, THROUGHPUT = "
          f"{num_samples / run_time:.2f} samples/s")
    return num_samples / run_time


if __name__ == "__main__":
    main()
