"""AlexNet as examples/cpp/AlexNet/alexnet.cc builds it, in plain
`jax.numpy` and float32: no kernels, no sharding, matrix products at
the highest precision.  The benchmark compares the loss the program's
first step reports with `loss()` on the same parameters and batch.

Layout as the program keeps it: images NHWC, kernels HWIO, the
flattened features in H, W, C order.  No dropout and no local response
normalisation: alexnet.cc has neither.
"""

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 256  # samples per call of loss(); the harness averages chunks


def make_batch(key, batch_size, height=229, width=229, num_classes=10, **_):
    """One synthetic batch from the key: ((images,), labels)."""
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (batch_size, height, width, 3), jnp.float32)
    y = jax.random.randint(ky, (batch_size, 1), 0, num_classes, jnp.int32)
    return (x,), y


def _conv(x, p, stride, pad):
    y = lax.conv_general_dilated(
        x, p["kernel"], (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + p["bias"])


def _pool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), "VALID")


@jax.jit
def _loss(params, x, labels):
    x = _pool(_conv(x, params["conv1"], 4, 2))
    x = _pool(_conv(x, params["conv2"], 1, 2))
    x = _conv(x, params["conv3"], 1, 1)
    x = _conv(x, params["conv4"], 1, 1)
    x = _pool(_conv(x, params["conv5"], 1, 1))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["kernel"] + params["fc1"]["bias"])
    x = jax.nn.relu(x @ params["fc2"]["kernel"] + params["fc2"]["bias"])
    logits = x @ params["fc3"]["kernel"] + params["fc3"]["bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels.reshape(-1, 1), axis=-1)
    return jnp.mean(nll)


def loss(params, inputs, labels, **_):
    """Mean sparse cross-entropy of `params` ({op: {weight: array}}) on
    one chunk of the batch, float32 throughout."""
    with jax.default_matmul_precision("highest"):
        return _loss(params, inputs[0].astype(jnp.float32), labels)
