"""PCA-net graph test (reference: tests/PCA/pca.cc).

Reproduces the reference's graph shape: principal-component inputs
normalized with element-binary ops ((pcvec-pcmin)/(pcmax-pcmin)), five
parallel towers of dense layers whose tanh activation is built from
scalar graph ops (2/(1+exp(-2x)) - 1) using ``create_constant`` tensors,
concatenated into one output — then trained a few steps with MSE to
verify the composed graph is differentiable end to end.

    python examples/pca.py -b 32
"""

import sys

try:
    import flexflow_tpu  # noqa: F401  (pip-installed)
except ImportError:  # source checkout without `pip install -e .`
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

import flexflow_tpu as ff
from flexflow_tpu.utils.compile_cache import enable_compile_cache

NPCS = 5
NN_SHL = [10, 10, 10, 10, 10, 1]


def build_pca(model: ff.FFModel, batch_size: int):
    pcvec = model.create_tensor((batch_size, NPCS), name="pcvec", nchw=False)
    pcmax = model.create_tensor((batch_size, NPCS), name="pcmax", nchw=False)
    pcmin = model.create_tensor((batch_size, NPCS), name="pcmin", nchw=False)
    sb = {i: model.create_tensor((batch_size, NN_SHL[i]), name=f"sb{i}",
                                 nchw=False)
          for i in range(1, 6)}

    pcvec_n = model.divide(model.subtract(pcvec, pcmin),
                           model.subtract(pcmax, pcmin))
    outputs = []
    for pc in range(1, NPCS + 1):
        s = pcvec_n
        for i in range(1, 6):
            s = model.dense(s, NN_SHL[i], name=f"pc{pc}_dense{i}")
            one = model.create_constant((batch_size, NN_SHL[i]), 1.0)
            two = model.create_constant((batch_size, NN_SHL[i]), 2.0)
            minus_two = model.create_constant((batch_size, NN_SHL[i]), -2.0)
            s = model.add(s, sb[i])
            # tanh from scratch: 2/(1+exp(-2x)) - 1
            s = model.add(one, model.exp(model.multiply(minus_two, s)))
            s = model.subtract(model.divide(two, s), one)
        outputs.append(s)
    out = model.concat(outputs, axis=1, name="outlayer")
    inputs = {"pcvec": pcvec, "pcmax": pcmax, "pcmin": pcmin,
              **{f"sb{i}": sb[i] for i in range(1, 6)}}
    return inputs, out


def main(argv=None):
    cfg = ff.FFConfig()
    cfg.parse_args(argv)
    enable_compile_cache()
    model = ff.FFModel(cfg)
    inputs, out = build_pca(model, cfg.batch_size)
    model.compile(ff.SGDOptimizer(model, lr=0.05),
                  ff.LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
                  [ff.MetricsType.MEAN_SQUARED_ERROR])
    model.init_layers()

    rng = np.random.default_rng(0)
    b = cfg.batch_size
    x = rng.standard_normal((b, NPCS), dtype=np.float32)
    batch = {
        inputs["pcvec"]: x,
        inputs["pcmax"]: np.full((b, NPCS), 3.0, np.float32),
        inputs["pcmin"]: np.full((b, NPCS), -3.0, np.float32),
    }
    for i in range(1, 6):
        batch[inputs[f"sb{i}"]] = np.zeros((b, NN_SHL[i]), np.float32)
    labels = np.tanh(x)  # learnable smooth target

    model.set_batch(batch, labels)
    losses = []
    for _ in range(30):
        model.train_iteration()
        pm = model.get_metrics()
        losses.append(pm.mse_loss / max(1, pm.train_all))
        model.reset_metrics()
    model.sync()
    print(f"mse first={losses[0]:.5f} last={losses[-1]:.5f}")
    assert losses[-1] < losses[0], "PCA net did not learn"
    return losses


if __name__ == "__main__":
    main()
