"""ResNet-50 training example (reference: examples/cpp/ResNet).

    python examples/resnet.py -e 1 -b 64 --bf16
"""

import sys

try:
    import flexflow_tpu  # noqa: F401  (pip-installed)
except ImportError:  # source checkout without `pip install -e .`
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import flexflow_tpu as ff
from flexflow_tpu.models.resnet import build_resnet50
from flexflow_tpu.utils.compile_cache import enable_compile_cache
from examples.common import train_and_report


def main(argv=None):
    cfg = ff.FFConfig()
    cfg.parse_args(argv)
    enable_compile_cache()
    print(f"batchSize({cfg.batch_size}) workersPerNodes({cfg.workers_per_node}) "
          f"numNodes({cfg.num_nodes})")
    model = ff.FFModel(cfg)
    inp, _ = build_resnet50(model, cfg.batch_size)
    model.compile(ff.SGDOptimizer(model, lr=0.001),
                  ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.ACCURACY,
                   ff.MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
    dl = ff.DataLoader.synthetic(model, inp, num_samples=cfg.batch_size * 2)
    model.init_layers()
    return train_and_report(model, dl, cfg)


if __name__ == "__main__":
    main()
