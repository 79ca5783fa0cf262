"""Loss functions with reference-exact gradient semantics.

TPU-native analogue of the reference loss layer
(reference: src/loss_functions/loss_functions.cu, include/loss_functions.h).

The reference computes the loss *gradient* directly at the softmax output
region and scales by ``1/batch_size`` (loss_functions.cu:141-150):
  * sparse CCE: grad = probs; probs[label] -= 1   (× 1/B)
  * CCE / MSE-avg: grad = logit - label           (× 1/B)

Here losses are scalar-valued pure functions differentiated by ``jax.grad``
— chosen so the autodiff gradient is *identical* to the reference kernels:
  * sparse/dense CCE is computed from the **pre-softmax** activations
    (the fused softmax+CE form: d/dlogits = (probs-onehot)/B — exactly
    the reference's fused pair of softmax-forward + CE-backward), by
    ``neg_log_prob``: a row's log-sum-exp and its label's logit, read
    from the logits in the dtype, shape and layout the last op wrote
    them in, f32 inside the reductions only.  Nothing as wide as the
    classes is written out, and nothing is gathered from.
  * MSE-avg uses 0.5·mean over samples of the squared error, whose gradient
    is (logit-label)/B.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


class LossType:
    CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
    SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
    MEAN_SQUARED_ERROR_AVG_REDUCE = "mean_squared_error"


def _canon(loss_type: str) -> str:
    aliases = {
        "categorical_crossentropy": LossType.CATEGORICAL_CROSSENTROPY,
        "sparse_categorical_crossentropy": LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        "mean_squared_error": LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        "mse": LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
    }
    if loss_type not in aliases:
        raise ValueError(f"Unrecognized loss type: {loss_type}")
    return aliases[loss_type]


def neg_log_prob(logits: jax.Array,
                 labels: Optional[jax.Array] = None) -> jax.Array:
    """``-log(softmax(logits))`` over the last axis, in f32: of each
    row's label where int ``labels`` of shape ``logits.shape[:-1]`` are
    given (one value a row), else of every class.

    The one cross-entropy from logits of the repo (the loss and the
    metrics).  ``logits`` is read as it is: no cast, fold or relayout of
    the tensor; ``x - max``, the exponent and the sums are f32, element
    for element what ``jax.nn.log_softmax`` computes.  The label's logit
    is a masked sum over the classes, not a gather: it fuses into the
    pass that sums the exponents, needs no buffer of the classes' width,
    and splits with a class axis that is sharded.  The maximum takes no
    gradient; d/dlogits is ``softmax - onehot`` in the logits' dtype."""
    top = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits.astype(jnp.float32) - top.astype(jnp.float32)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
    if labels is None:
        return lse - shifted
    classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
    picked = jnp.sum(jnp.where(classes == labels[..., None], shifted, 0.0),
                     axis=-1)
    return lse[..., 0] - picked


class Loss:
    """Scalar loss over (pre-softmax logits, labels).

    ``wants_logits`` tells the executor whether to feed the *input* of a
    trailing Softmax op (the fused, numerically-stable TPU path) instead of
    its output.
    """

    def __init__(self, loss_type: str):
        self.loss_type = _canon(loss_type)

    @property
    def wants_logits(self) -> bool:
        return self.loss_type in (
            LossType.CATEGORICAL_CROSSENTROPY,
            LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        )

    def __call__(self, preds: jax.Array, labels: jax.Array) -> jax.Array:
        """preds: (B, C) logits for CE losses, final outputs for MSE —
        or (B, T, C) for sequence models (NMT), reduced per-token.
        labels: (B,)/(B,1) [or (B,T)] int for sparse CE; matching shape
        otherwise."""
        # a vector of outputs is a batch of scalars
        rows = math.prod(preds.shape[:-1] or preds.shape)
        if self.loss_type == LossType.SPARSE_CATEGORICAL_CROSSENTROPY:
            labels = labels.reshape(preds.shape[:-1]).astype(jnp.int32)
            # the rows' values as one vector (16 KB for GPT-2's 4096), so
            # that they are summed in the order they always were
            return jnp.sum(neg_log_prob(preds, labels).reshape(rows)) / rows
        labels = labels.reshape(preds.shape).astype(jnp.float32)
        if self.loss_type == LossType.CATEGORICAL_CROSSENTROPY:
            return jnp.sum(labels * neg_log_prob(preds)) / rows
        # MSE avg-reduce: grad must be (pred-label)/B per element
        diff = preds.astype(jnp.float32) - labels
        return 0.5 * jnp.sum(diff * diff) / rows
