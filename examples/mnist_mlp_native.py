"""MNIST MLP via the native core API with numpy-attached data
(reference: examples/python/native/mnist_mlp_attach.py — full dataset
attached into zero-copy memory via Tensor::attach_raw_ptr, then scattered
per batch; here the DataLoader holds the host-resident numpy arrays and
feeds sharded device batches, the TPU analogue of that ZC path).

    python examples/mnist_mlp_native.py -e 2 -b 64
"""

import sys

try:
    import flexflow_tpu  # noqa: F401  (pip-installed)
except ImportError:  # source checkout without `pip install -e .`
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

import flexflow_tpu as ff
from flexflow_tpu.keras.datasets import mnist
from flexflow_tpu.utils.compile_cache import enable_compile_cache


def top_level_task(argv=None, num_samples=4096):
    cfg = ff.FFConfig()
    cfg.parse_args(argv)
    enable_compile_cache()
    (x_train, y_train), _ = mnist.load_data()
    x_train = x_train[:num_samples].reshape(-1, 784).astype(np.float32) / 255.0
    y_train = y_train[:num_samples].astype(np.int32).reshape(-1, 1)

    model = ff.FFModel(cfg)
    inp = model.create_tensor((cfg.batch_size, 784), name="input", nchw=False)
    t = model.dense(inp, 512, activation=ff.ActiMode.RELU, name="dense1")
    t = model.dense(t, 512, activation=ff.ActiMode.RELU, name="dense2")
    t = model.dense(t, 10, name="dense3")
    model.softmax(t, name="softmax")
    model.compile(ff.SGDOptimizer(model, lr=0.01),
                  ff.LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.MetricsType.ACCURACY])
    # attach the full host-resident dataset once; per-iteration batches
    # are sliced+sharded from it
    dl = ff.DataLoader(model, {inp: x_train}, y_train)
    model.init_layers()

    for epoch in range(cfg.epochs):
        dl.reset()
        model.reset_metrics()
        for _ in range(dl.num_batches()):
            dl.next_batch(model)
            model.train_iteration()
        model.sync()
        pm = model.get_metrics()
        print(f"epoch {epoch}: {pm.to_string()}")
    acc = pm.accuracy
    assert acc >= 60.0, f"accuracy {acc:.2f}% below 60%"
    return acc


if __name__ == "__main__":
    top_level_task()
