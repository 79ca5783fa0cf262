"""Training metrics.

TPU-native analogue of the reference metrics layer (reference:
src/metrics_functions/metrics_functions.{cc,cu}, include/metrics_functions.h).

The reference accumulates a device-side ``PerfMetrics`` struct with atomics
per partition, then folds per-part futures on the CPU
(src/runtime/model.cc:1145-1167).  Here per-batch sums are computed inside
the jitted step (XLA reduces across the mesh — the analogue of the future
fold), returned as a small dict of scalars, and accumulated on host in a
``PerfMetrics`` whose ``print`` mirrors PerfMetrics::print
(metrics_functions.cc:44-70).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from .losses import neg_log_prob

LOG_MIN_VALUE = 1e-20


class MetricsType:
    ACCURACY = "accuracy"
    CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
    SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
    MEAN_SQUARED_ERROR = "mean_squared_error"
    ROOT_MEAN_SQUARED_ERROR = "root_mean_squared_error"
    MEAN_ABSOLUTE_ERROR = "mean_absolute_error"


@dataclasses.dataclass
class PerfMetrics:
    """Host-side running totals (reference: include/metrics_functions.h:25-39)."""

    train_all: int = 0
    train_correct: int = 0
    cce_loss: float = 0.0
    sparse_cce_loss: float = 0.0
    mse_loss: float = 0.0
    rmse_loss: float = 0.0
    mae_loss: float = 0.0

    def update(self, one: Dict[str, float]) -> None:
        self.train_all += int(one.get("train_all", 0))
        self.train_correct += int(one.get("train_correct", 0))
        self.cce_loss += float(one.get("cce_loss", 0.0))
        self.sparse_cce_loss += float(one.get("sparse_cce_loss", 0.0))
        self.mse_loss += float(one.get("mse_loss", 0.0))
        self.rmse_loss += float(one.get("rmse_loss", 0.0))
        self.mae_loss += float(one.get("mae_loss", 0.0))

    def reset(self) -> None:
        self.__init__()

    @property
    def accuracy(self) -> float:
        return self.train_correct * 100.0 / max(1, self.train_all)

    def to_string(self) -> str:
        out = "[Metrics]"
        if self.train_all > 0:
            out += (f" accuracy: {self.accuracy:.6f}% "
                    f"({self.train_correct} / {self.train_all})")
        if self.cce_loss > 0:
            out += f" categorical_crossentropy: {self.cce_loss / max(1, self.train_all):.6f}"
        if self.sparse_cce_loss > 0:
            out += (" sparse_categorical_crossentropy: "
                    f"{self.sparse_cce_loss / max(1, self.train_all):.6f}")
        if self.mse_loss > 0:
            out += f" mean_squared_error: {self.mse_loss / max(1, self.train_all):.6f}"
        if self.rmse_loss > 0:
            out += f" root_mean_squared_error: {self.rmse_loss / max(1, self.train_all):.6f}"
        if self.mae_loss > 0:
            out += f" mean_absolute_error: {self.mae_loss / max(1, self.train_all):.6f}"
        return out

    def print(self) -> None:
        print(self.to_string())


# What is a function of the logits alone: the arg-max of a softmax is the
# arg-max of its logits, and a cross-entropy reads the log-softmax.  The
# error metrics below need the probabilities themselves.
_FROM_LOGITS = frozenset({
    MetricsType.ACCURACY,
    MetricsType.CATEGORICAL_CROSSENTROPY,
    MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY,
})


class Metrics:
    """Jit-side per-batch metric sums (reference compute kernels:
    metrics_functions.cu:57-175).

    ``compute(preds, labels)`` reads the model's final activation: the
    trailing Softmax's output, or the raw final tensor where the graph
    has no softmax.  ``compute(preds, labels, from_logits=True)`` reads
    the Softmax's *input*, the tensor a cross-entropy loss reads
    (``Loss.wants_logits``), and gives the same sums without the
    probabilities; the step takes that path whenever ``logits_suffice``
    (``FFModel._loss_head``), so that a train step computes no softmax
    for a metric.  ``labels`` is int (B,)/(B,1) [or (B,T)] when
    ``sparse`` else one-hot/regression targets of ``preds``' shape.
    Sequence outputs (B,T,C) count per token; ``preds`` is read in the
    dtype and shape it has (no f32 copy, no fold of the leading
    dimensions) except where a sum over classes needs f32."""

    def __init__(self, loss_type: str, metrics: Sequence[str]):
        self.metrics = list(metrics)
        self.sparse = "sparse" in loss_type
        self.loss_type = loss_type

    @property
    def logits_suffice(self) -> bool:
        """Every metric asked for is a function of the logits."""
        return _FROM_LOGITS.issuperset(self.metrics)

    def compute(self, preds: jax.Array, labels: jax.Array,
                from_logits: bool = False) -> Dict[str, jax.Array]:
        """The batch's sums.  ``preds`` is the final activation, or with
        ``from_logits`` the trailing Softmax's input (``logits_suffice``
        must hold then)."""
        assert not from_logits or self.logits_suffice, self.metrics
        rows, num_classes = math.prod(preds.shape[:-1]), preds.shape[-1]
        out: Dict[str, jax.Array] = {"train_all": jnp.int32(rows)}
        m = self.metrics
        if self.sparse:
            labels = labels.reshape(preds.shape[:-1]).astype(jnp.int32)
        else:
            labels = labels.reshape(preds.shape).astype(jnp.float32)

        if MetricsType.ACCURACY in m:
            if not self.sparse and num_classes == 1:
                # accuracy is meaningless for 1 output; reference returns
                # 100% (metrics_functions.cu:121-126)
                out["train_correct"] = jnp.int32(rows)
            else:
                # first maximal index, of logits and probabilities alike
                true = labels if self.sparse else jnp.argmax(labels, axis=-1)
                out["train_correct"] = jnp.sum(
                    jnp.argmax(preds, axis=-1) == true, dtype=jnp.int32)

        if self.sparse:
            cce, cce_sum = (MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY,
                            "sparse_cce_loss")
        else:
            cce, cce_sum = MetricsType.CATEGORICAL_CROSSENTROPY, "cce_loss"
        if cce in m:
            # -log(max(p, LOG_MIN_VALUE)), from whichever tensor is read;
            # where labels are sparse, of each row's label alone
            if from_logits:
                nlogp = jnp.minimum(
                    neg_log_prob(preds, labels if self.sparse else None),
                    -math.log(LOG_MIN_VALUE))
            else:
                nlogp = -jnp.log(jnp.maximum(preds.astype(jnp.float32),
                                             LOG_MIN_VALUE))
                if self.sparse:
                    nlogp = jnp.take_along_axis(nlogp, labels[..., None],
                                                axis=-1)
            picked = nlogp if self.sparse else \
                jnp.where(labels > 0.0, labels * nlogp, 0.0)
            out[cce_sum] = jnp.sum(picked)

        if (MetricsType.MEAN_SQUARED_ERROR in m
                or MetricsType.ROOT_MEAN_SQUARED_ERROR in m
                or MetricsType.MEAN_ABSOLUTE_ERROR in m):
            target = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32) \
                if self.sparse else labels
            diff = preds.astype(jnp.float32) - target
            mse = jnp.sum(diff * diff, axis=-1)
            if MetricsType.MEAN_SQUARED_ERROR in m:
                out["mse_loss"] = jnp.sum(mse)
            if MetricsType.ROOT_MEAN_SQUARED_ERROR in m:
                out["rmse_loss"] = jnp.sum(jnp.sqrt(mse))
            if MetricsType.MEAN_ABSOLUTE_ERROR in m:
                out["mae_loss"] = jnp.sum(jnp.abs(diff))
        return out
