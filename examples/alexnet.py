"""AlexNet training example — mirrors examples/cpp/AlexNet/alexnet.cc.

Usage (reference-style flags accepted):
    python examples/alexnet.py -e 2 -b 256 --lr 0.001 -ll:tpu 1 [--bf16]
Prints the reference's benchmark line:
    ELAPSED TIME = %.4fs, THROUGHPUT = %.2f samples/s
"""

import sys
import time

try:
    import flexflow_tpu  # noqa: F401  (pip-installed)
except ImportError:  # source checkout without `pip install -e .`
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import flexflow_tpu as ff
from flexflow_tpu.models.alexnet import build_alexnet
from flexflow_tpu.utils.compile_cache import enable_compile_cache


def main(argv=None):
    cfg = ff.FFConfig()
    cfg.parse_args(argv)
    enable_compile_cache()
    print(f"batchSize({cfg.batch_size}) workersPerNodes({cfg.workers_per_node}) "
          f"numNodes({cfg.num_nodes})")
    model = ff.FFModel(cfg)
    inp, _ = build_alexnet(model, cfg.batch_size)
    optimizer = ff.SGDOptimizer(model, lr=0.001)
    model.compile(optimizer, ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.ACCURACY,
                   ff.MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
    data_loader = ff.DataLoader.synthetic(model, inp, num_samples=cfg.batch_size * 4)
    model.init_layers()

    # Warmup (compile) — the analogue of the reference's epoch-0 trace
    # capture; XLA compiles the fused step once here.
    data_loader.next_batch(model)
    model.train_iteration()
    model.sync()
    model.reset_metrics()

    ts_start = time.perf_counter()
    num_samples = 0
    for epoch in range(cfg.epochs):
        data_loader.reset()
        model.reset_metrics()
        # --iterations N caps the per-epoch loop (reference parse_args
        # has the same flag); default derives from the dataset size.
        iterations = data_loader.num_samples // cfg.batch_size
        if cfg.iterations > 0:
            iterations = min(iterations, cfg.iterations)
        for it in range(iterations):
            if cfg.dataset_path == "":
                if it == 0 and epoch == 0:
                    data_loader.next_batch(model)
            else:
                data_loader.next_batch(model)
            model.forward()
            model.zero_gradients()
            model.backward()
            model.update()
            num_samples += cfg.batch_size
    model.sync()
    run_time = time.perf_counter() - ts_start
    model.print_metrics()
    print(f"ELAPSED TIME = {run_time:.4f}s, THROUGHPUT = "
          f"{num_samples / run_time:.2f} samples/s")

    if model._telemetry is not None:
        # Telemetry runs double as the observability acceptance fixture:
        # round-trip a checkpoint so the trace carries save/restore spans.
        import os
        import tempfile

        ckpt = os.path.join(tempfile.mkdtemp(prefix="ff_alexnet_"),
                            "ckpt.npz")
        model.save(ckpt)
        model.load(ckpt)
        os.remove(ckpt)
    return num_samples / run_time


if __name__ == "__main__":
    main()
