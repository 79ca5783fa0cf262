"""Weight initializers.

TPU-native analogue of the reference initializer suite
(reference: include/initializer.h:26-100, src/runtime/initializer_kernel.cu).
The reference runs one Legion task per weight partition with curand; here
each initializer is a pure function of a jax PRNG key, evaluated inside the
jitted, sharded ``init_params`` so every device materializes only its own
shard (no host round-trip).
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


class Initializer:
    def __call__(self, key: jax.Array, shape: Tuple[int, ...], dtype=jnp.float32) -> jax.Array:
        raise NotImplementedError


class GlorotUniform(Initializer):
    """Glorot/Xavier uniform: U(-s, s), s = sqrt(6/(fan_in+fan_out)).

    Fan computation follows the reference's per-op conventions
    (initializer_kernel.cu GlorotUniform::init_task): for conv kernels
    (h, w, cin, cout here; NHWC-native) fan_in = h*w*cin,
    fan_out = h*w*cout; for dense (cin, cout) fan_in = cin, fan_out = cout.
    """

    def __init__(self, seed: int = 0):
        self.seed = seed

    @staticmethod
    def _fans(shape: Sequence[int]) -> Tuple[float, float]:
        if len(shape) == 4:  # (kh, kw, cin, cout)
            rf = shape[0] * shape[1]
            return float(rf * shape[2]), float(rf * shape[3])
        if len(shape) == 2:  # (cin, cout)
            return float(shape[0]), float(shape[1])
        if len(shape) == 1:
            return float(shape[0]), float(shape[0])
        # fall back to matrix-like split
        recept = 1
        for d in shape[1:-1]:
            recept *= d
        return float(shape[0] * recept), float(shape[-1] * recept)

    def __call__(self, key, shape, dtype=jnp.float32):
        fan_in, fan_out = self._fans(shape)
        scale = math.sqrt(6.0 / max(1.0, fan_in + fan_out))
        return jax.random.uniform(key, shape, dtype, minval=-scale, maxval=scale)


class StackedGlorotUniform(GlorotUniform):
    """Glorot uniform for a stack of matrices (..., fan_in, fan_out): each
    matrix of the stack is drawn as a dense kernel of its own shape would
    be (an expert's weights do not shrink with the number of experts)."""

    @staticmethod
    def _fans(shape: Sequence[int]) -> Tuple[float, float]:
        return float(shape[-2]), float(shape[-1])


class ZeroInitializer(Initializer):
    def __call__(self, key, shape, dtype=jnp.float32):
        return jnp.zeros(shape, dtype)


class ConstantInitializer(Initializer):
    def __init__(self, value: float):
        self.value = value

    def __call__(self, key, shape, dtype=jnp.float32):
        return jnp.full(shape, self.value, dtype)


class UniformInitializer(Initializer):
    def __init__(self, seed: int = 0, min_val: float = 0.0, max_val: float = 1.0):
        self.seed = seed
        self.min_val = min_val
        self.max_val = max_val

    def __call__(self, key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, minval=self.min_val, maxval=self.max_val)


class NormInitializer(Initializer):
    def __init__(self, seed: int = 0, mean: float = 0.0, stddev: float = 1.0):
        self.seed = seed
        self.mean = mean
        self.stddev = stddev

    def __call__(self, key, shape, dtype=jnp.float32):
        return self.mean + self.stddev * jax.random.normal(key, shape, dtype)


DefaultWeightInitializer = GlorotUniform
DefaultBiasInitializer = ZeroInitializer
