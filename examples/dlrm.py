"""DLRM training example (reference: examples/cpp/DLRM, run_random.sh).

    python examples/dlrm.py -e 1 -b 256 --bf16 \
        [--arch-embedding-size 1000000-1000000-...] [--arch-sparse-feature-size 64] \
        [--host-embeddings] [--pipeline S [--pipeline-microbatches M]]
"""

import sys
import time

try:
    import flexflow_tpu  # noqa: F401  (pip-installed)
except ImportError:  # source checkout without `pip install -e .`
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import flexflow_tpu as ff
from flexflow_tpu.models.dlrm import build_dlrm, synthetic_batch
from flexflow_tpu.utils.compile_cache import enable_compile_cache


def main(argv=None):
    cfg = ff.FFConfig()
    rest = cfg.parse_args(argv)
    enable_compile_cache()
    # reference DLRM flags (dlrm.cc parse_input_args)
    emb_sizes = [1000000] * 8
    sparse_dim = 64
    bag = 1
    mlp_bot = [64, 512, 512, 64]
    mlp_top = [576, 1024, 1024, 1024, 1]
    host_embeddings = False
    pipeline_stages = 0
    pipeline_microbatches = 4
    i = 0
    while i < len(rest):
        if rest[i] == "--arch-embedding-size":
            i += 1
            emb_sizes = [int(v) for v in rest[i].split("-")]
        elif rest[i] == "--arch-sparse-feature-size":
            i += 1
            sparse_dim = int(rest[i])
        elif rest[i] == "--embedding-bag-size":
            i += 1
            bag = int(rest[i])
        elif rest[i] == "--arch-mlp-bot":
            i += 1
            mlp_bot = [int(v) for v in rest[i].split("-")]
        elif rest[i] == "--arch-mlp-top":
            i += 1
            mlp_top = [int(v) for v in rest[i].split("-")]
        elif rest[i] == "--host-embeddings":
            host_embeddings = True
        elif rest[i] == "--pipeline":
            i += 1
            pipeline_stages = int(rest[i])
        elif rest[i] == "--pipeline-microbatches":
            i += 1
            pipeline_microbatches = int(rest[i])
        i += 1

    if host_embeddings:
        # Reference DLRM's hetero placement (dlrm_strategy_hetero.cc puts
        # the 8x1M-row tables in host zero-copy memory): tables become
        # host-resident and ROW-SPARSE — per step only the batch's unique
        # rows move host<->device.  Applied after flag parsing so it
        # covers the final table count regardless of flag order.
        from flexflow_tpu.config import DeviceType
        for j in range(len(emb_sizes)):
            cfg.strategies[f"embedding{j}"] = ff.ParallelConfig(
                DeviceType.CPU, (1, 1), (0,))

    print(f"batchSize({cfg.batch_size}) workersPerNodes({cfg.workers_per_node}) "
          f"numNodes({cfg.num_nodes})")
    model = ff.FFModel(cfg)
    sparse_in, dense_in, _ = build_dlrm(
        model, cfg.batch_size, embedding_sizes=emb_sizes,
        embedding_bag_size=bag, sparse_feature_size=sparse_dim,
        mlp_bot=mlp_bot, mlp_top=mlp_top)
    if pipeline_stages > 1:
        # hetero compose: host-placed tables lift out of the ring as a
        # head; the MLP/interaction stack pipelines (ADR-002 schedule)
        model.set_pipeline(num_stages=pipeline_stages,
                           num_microbatches=pipeline_microbatches)
    model.compile(ff.SGDOptimizer(model, lr=0.01),
                  ff.LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
                  [ff.MetricsType.ACCURACY, ff.MetricsType.MEAN_SQUARED_ERROR])
    model.init_layers()
    if model._host_embed:
        u = sum(info["u_max"] for info in model._host_embed.values())
        total = sum(emb_sizes)
        print(f"host-sparse embeddings: {len(model._host_embed)} tables "
              f"({total:,} rows host-resident), <= {u} rows/step on the "
              f"wire worst-case (adaptive bucket sizes to the observed "
              f"unique counts)")

    sparse, dense, labels = synthetic_batch(cfg.batch_size, emb_sizes, bag, mlp_bot[0])
    inputs = {t: a for t, a in zip(sparse_in, sparse)}
    inputs[dense_in] = dense

    # warmup (reference dlrm.cc:144-150 runs warmup iterations before timing)
    model.set_batch(inputs, labels)
    model.train_iteration()
    model.sync()
    model.reset_metrics()

    iterations = 64
    ts_start = time.perf_counter()
    for epoch in range(cfg.epochs):
        model.reset_metrics()
        for _ in range(iterations):
            model.train_iteration()
    model.sync()
    run_time = time.perf_counter() - ts_start
    model.print_metrics()
    num_samples = iterations * cfg.batch_size * cfg.epochs
    print(f"ELAPSED TIME = {run_time:.4f}s, THROUGHPUT = "
          f"{num_samples / run_time:.2f} samples/s")
    return num_samples / run_time


if __name__ == "__main__":
    main()
