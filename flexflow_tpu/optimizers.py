"""Optimizers with reference-exact update semantics.

TPU-native analogue of the reference optimizer layer
(reference: src/runtime/optimizer.cc, src/runtime/optimizer_kernel.cu,
include/optimizer.h).  The reference runs one Legion task per parameter
which (a) sums the ``num_replicas`` stacked gradient copies and (b) applies
the update on the parameter's home GPU.  Here step (a) is subsumed by
GSPMD: gradients of replicated/sharded params come out of ``jax.grad``
already summed across the mesh (XLA inserts the ``psum``/reduce-scatter
collectives over ICI), so only the update math remains — implemented as
pure functions over the parameter pytree, jitted and sharded with it.

Time-varying scalars (lr, Adam's alpha_t) are threaded as traced arguments
so epoch advancement never retriggers XLA compilation.

Update formulas match the reference kernels exactly:
  * SGD  (optimizer_kernel.cu:23-40, pytorch-style):
        gt = g + wd*w
        if momentum: v = momentum*v + gt; gt = nesterov ? gt + momentum*v : v
        w -= lr * gt
  * Adam (optimizer_kernel.cu:206-225 + alpha_t schedule in
    AdamOptimizer::next_epoch, src/runtime/optimizer.cc):
        gt = g + wd*w
        m = b1*m + (1-b1)*gt ; v = b2*v + (1-b2)*gt^2
        w -= alpha_t * m / (sqrt(v) + eps),
        alpha_t = alpha * sqrt(1-b2^t) / (1-b1^t)
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
OptState = Dict[str, Any]
HParams = Dict[str, Any]


class Optimizer:
    """Base optimizer. State is a pytree mirroring the params pytree."""

    # Fused-kernel routing (kernels/fused_optimizer.py): the Pallas call
    # is not GSPMD-partitionable, so on a multi-device machine each
    # parameter's update runs inside a per-leaf shard_map with the
    # param's own PartitionSpec — every chip fuses-updates exactly its
    # local shard (the moral twin of the reference running
    # optimizer_kernel.cu on the parameter's home GPU,
    # optimizer.cc:74-101).  FFModel.init_layers installs mesh + specs.
    mesh = None
    param_specs = None
    nonfused_paths: frozenset = frozenset()
    zero_specs = None  # ZeRO-1: {(op, weight): PartitionSpec} for STATE
    # True runs the fused kernels in the Pallas interpreter; set by
    # FFModel.compile when the machine is not a TPU.
    fused_interpret = False

    def set_mesh(self, mesh, param_specs, nonfused_paths=()) -> None:
        """``nonfused_paths``: (op_name, weight_name) leaves that must
        take the plain jnp update (host-offloaded weights stream through
        device_put pairs the Pallas aliasing path doesn't model)."""
        self.mesh = mesh
        self.param_specs = param_specs
        self.nonfused_paths = frozenset(nonfused_paths)

    def _leaf_fused(self, path) -> bool:
        try:
            key = tuple(p.key for p in path)
        except AttributeError:
            return True
        return key not in self.nonfused_paths

    def _constrain_state(self, tree):
        """Pin a params-shaped state subtree to the ZeRO-1 shardings so
        the computed state stays sharded between steps (not
        materialized replicated and resharded on re-entry)."""
        return self._pin_zero_leaves(tree, lambda key: self.zero_specs[key])

    def _constrain_params(self, tree):
        """Pin the new parameters whose state ZeRO-1 shards to their own
        specs: the partitioner would hand them back sharded as the state
        they were computed from, and the next step would then receive
        them placed otherwise than the first did (a second program)."""
        return self._pin_zero_leaves(
            tree, lambda key: self.param_specs[key[0]][key[1]])

    def _pin_zero_leaves(self, tree, spec_of):
        if not self.zero_specs or self.mesh is None:
            return tree
        from jax.sharding import NamedSharding
        from jax.tree_util import tree_map_with_path

        def f(path, x):
            try:
                key = tuple(p.key for p in path)
            except AttributeError:
                return x
            if key not in self.zero_specs:
                return x
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec_of(key)))

        return tree_map_with_path(f, tree)

    def _spec_for_path(self, path):
        """PartitionSpec for a params-tree key path (PartitionSpec is a
        tuple subclass, hence a pytree NODE — specs can't ride tree.map
        and are looked up by path instead)."""
        node = self.param_specs
        if node is None:
            return None
        try:
            for p in path:
                node = node[p.key]
        except (KeyError, TypeError, AttributeError):
            return None
        return node

    def _shardwise(self, fn, spec, n_in, n_out):
        """Wrap a per-parameter fused update ``fn(hp, *operands)`` to run
        per-shard when the machine is a real mesh; identity wrapper on a
        single device.  ``hp`` is a replicated scalar vector."""
        if self.mesh is None or self.mesh.devices.size <= 1 or spec is None:
            return fn
        from jax import shard_map
        from jax.sharding import PartitionSpec

        scalar = PartitionSpec()
        in_specs = tuple([scalar] + [spec] * n_in)
        out_specs = tuple([spec] * n_out) if n_out > 1 else spec
        return shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def init_state(self, params: Params) -> OptState:
        raise NotImplementedError

    def hparams(self) -> HParams:
        """Current dynamic scalars, passed into the jitted step each call."""
        raise NotImplementedError

    def apply(self, params: Params, grads: Params, state: OptState,
              hparams: HParams) -> Tuple[Params, OptState]:
        raise NotImplementedError

    def next_epoch(self) -> None:
        """Per-epoch hook (reference Optimizer::next_epoch): Adam advances
        its bias-correction schedule here; SGD has no epoch state."""


def _unzip(tree, n):
    is_tup = lambda t: isinstance(t, tuple)
    return tuple(jax.tree.map(lambda t, i=i: t[i], tree, is_leaf=is_tup) for i in range(n))


class SGDOptimizer(Optimizer):
    def __init__(self, model=None, lr: float = 0.01, momentum: float = 0.0,
                 nesterov: bool = False, weight_decay: float = 0.0):
        self.lr = float(lr)
        self.momentum = float(momentum)
        self.nesterov = bool(nesterov)
        self.weight_decay = float(weight_decay)
        # Set by FFModel.compile from FFConfig.fused_optimizer: route the
        # update through the Pallas kernels (kernels/fused_optimizer.py,
        # the analogue of the reference's optimizer_kernel.cu).  On a
        # mesh each leaf updates per-shard via Optimizer._shardwise.
        self.fused = False

    def init_state(self, params):
        if self.momentum > 0.0:
            return {"v": jax.tree.map(jnp.zeros_like, params)}
        return {}

    def hparams(self):
        return {"lr": jnp.float32(self.lr)}

    def apply(self, params, grads, state, hparams):
        lr = hparams["lr"]
        wd, mom = self.weight_decay, self.momentum

        if self.fused:
            from jax.tree_util import tree_map_with_path

            from .kernels.fused_optimizer import fused_sgd_update

            if mom > 0.0:
                def fupd(path, w, g, v):
                    if not self._leaf_fused(path):
                        gt = g + wd * w
                        vn = v * mom + gt
                        step = gt + mom * vn if self.nesterov else vn
                        return w - lr * step.astype(w.dtype), vn
                    def body(hp, w, g, v):
                        return fused_sgd_update(
                            w, g, v, hp, wd, mom, self.nesterov,
                            interpret=self.fused_interpret)
                    return self._shardwise(body, self._spec_for_path(path),
                                           3, 2)(lr, w, g, v)

                out = tree_map_with_path(fupd, params, grads, state["v"])
                new_params, new_v = _unzip(out, 2)
                return (self._constrain_params(new_params),
                        {"v": self._constrain_state(new_v)})

            def fupd_plain(path, w, g):
                if not self._leaf_fused(path):
                    return w - lr * (g + wd * w).astype(w.dtype)
                def body(hp, w, g):
                    # momentum buffer unused: the kernel passes it through
                    return fused_sgd_update(
                        w, g, g, hp, wd, 0.0, False,
                        interpret=self.fused_interpret)[0]
                return self._shardwise(body, self._spec_for_path(path),
                                       2, 1)(lr, w, g)

            return tree_map_with_path(fupd_plain, params, grads), {}

        if mom > 0.0:
            def upd(w, g, v):
                gt = g + wd * w
                v = v * mom + gt
                step = gt + mom * v if self.nesterov else v
                return w - lr * step.astype(w.dtype), v

            out = jax.tree.map(upd, params, grads, state["v"])
            new_params, new_v = _unzip(out, 2)
            return (self._constrain_params(new_params),
                    {"v": self._constrain_state(new_v)})

        def upd_plain(w, g):
            return w - lr * (g + wd * w).astype(w.dtype)

        return jax.tree.map(upd_plain, params, grads), {}


class AdamOptimizer(Optimizer):
    def __init__(self, model=None, alpha: float = 0.001, beta1: float = 0.9,
                 beta2: float = 0.999, weight_decay: float = 0.0, epsilon: float = 1e-8):
        self.alpha = float(alpha)
        self.beta1 = float(beta1)
        self.beta2 = float(beta2)
        self.weight_decay = float(weight_decay)
        self.epsilon = float(epsilon)
        # Bias-correction schedule mirroring the reference's
        # alpha_t/beta1_t/beta2_t fields (include/optimizer.h).
        self.beta1_t = 1.0
        self.beta2_t = 1.0
        self.alpha_t = self.alpha
        self.fused = False  # see SGDOptimizer.fused

    def next_epoch(self):
        self.beta1_t *= self.beta1
        self.beta2_t *= self.beta2
        self.alpha_t = self.alpha * (1.0 - self.beta2_t) ** 0.5 / (1.0 - self.beta1_t)

    def init_state(self, params):
        return {
            "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params),
        }

    def hparams(self):
        return {"alpha_t": jnp.float32(self.alpha_t)}

    def apply(self, params, grads, state, hparams):
        alpha_t = hparams["alpha_t"]
        wd, b1, b2, eps = self.weight_decay, self.beta1, self.beta2, self.epsilon

        if self.fused:
            from jax.tree_util import tree_map_with_path

            from .kernels.fused_optimizer import fused_adam_update

            def fupd(path, w, g, m, v):
                if not self._leaf_fused(path):
                    gt = (g + wd * w).astype(jnp.float32)
                    mt = b1 * m + (1.0 - b1) * gt
                    vt = b2 * v + (1.0 - b2) * gt * gt
                    wt = (w - alpha_t * mt / (jnp.sqrt(vt) + eps)).astype(w.dtype)
                    return wt, mt, vt
                def body(hp, w, g, m, v):
                    return fused_adam_update(
                        w, g, m, v, hp, wd, b1, b2, eps,
                        interpret=self.fused_interpret)
                return self._shardwise(body, self._spec_for_path(path),
                                       4, 3)(alpha_t, w, g, m, v)

            out = tree_map_with_path(fupd, params, grads, state["m"],
                                     state["v"])
            new_params, new_m, new_v = _unzip(out, 3)
            return (self._constrain_params(new_params),
                    {"m": self._constrain_state(new_m),
                     "v": self._constrain_state(new_v)})

        def upd(w, g, m, v):
            gt = (g + wd * w).astype(jnp.float32)
            mt = b1 * m + (1.0 - b1) * gt
            vt = b2 * v + (1.0 - b2) * gt * gt
            return (w - alpha_t * mt / (jnp.sqrt(vt) + eps)).astype(w.dtype), mt, vt

        out = jax.tree.map(upd, params, grads, state["m"], state["v"])
        new_params, new_m, new_v = _unzip(out, 3)
        return (self._constrain_params(new_params),
                {"m": self._constrain_state(new_m),
                 "v": self._constrain_state(new_v)})


class OptaxOptimizer(Optimizer):
    """Adapter: run any optax ``GradientTransformation`` as the model
    optimizer (beyond the reference, which ships exactly SGD and Adam —
    this opens the whole JAX optimizer ecosystem: adamw, lion, lamb,
    schedules, gradient clipping chains, ...).

    The optax state rides the fused train step and checkpoints like the
    built-in slots.  The ``--fused-optimizer`` Pallas route, ZeRO-1
    state sharding, and host-offload state streaming apply only to the
    built-in SGD/Adam and are silently inert here.

        import optax
        model.compile(ff.OptaxOptimizer(optax.adamw(3e-4)), ...)
    """

    def __init__(self, tx=None, model=None):
        # tolerate the reference-style (model, ...) calling convention:
        # OptaxOptimizer(model, tx) and OptaxOptimizer(tx) both work
        if tx is not None and hasattr(tx, "ops") and model is not None:
            tx, model = model, tx
        if tx is None or hasattr(tx, "ops") \
                or not (hasattr(tx, "init") and hasattr(tx, "update")):
            # the .ops check rejects an FFModel passed alone (it has an
            # unrelated .update method)
            raise ValueError("OptaxOptimizer needs an optax "
                             "GradientTransformation")
        self.tx = tx
        self.fused = False

    def init_state(self, params):
        # leaves tx.init makes from scratch (step counters) come back
        # uncommitted, on one device: whoever holds the state places them
        return {"optax": self.tx.init(params)}

    def hparams(self):
        return {}

    def apply(self, params, grads, state, hparams):
        import optax

        updates, new_state = self.tx.update(grads, state["optax"], params)
        return optax.apply_updates(params, updates), {"optax": new_state}
