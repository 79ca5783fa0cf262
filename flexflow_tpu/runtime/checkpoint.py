"""Checkpoint / resume.

The reference has NO model checkpointing (SURVEY §5.4): the only
persisted artifacts are strategy files, and weights move only through
``Parameter::set_weights/get_weights`` (src/runtime/model.cu:260-370).
A TPU-native training framework needs real checkpoint/resume, so this
module adds it as a first-class subsystem on orbax:

  * full training state — params, batchnorm stats, optimizer slots,
    step counter — saved as a sharded pytree (multi-host safe: each
    host writes its own shards),
  * restore re-applies the model's NamedShardings so a checkpoint
    taken on one mesh reloads onto another (same global shapes),
  * ``CheckpointManager`` adds rotation + interval policies for
    long-running jobs.

Falls back to a plain ``.npz`` (fully-replicated) format when orbax is
unavailable — also the interchange format for weight import/export.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax
import numpy as np

from .profiling import phase


def _unpack_tree(model, tree: Dict[str, Any]) -> Dict[str, Any]:
    """Canonicalize a params-shaped tree: expand a pipelined model's
    packed ``_pipe`` stage-weight buffer into per-op arrays, and
    assemble row-range-sharded host-resident embedding tables (and
    their table-shaped optimizer state) into FULL arrays — so
    checkpoints are layout-portable (pipeline <-> plain, different
    stage splits, meshes, or process counts)."""
    pack = model._pipe_pack() if hasattr(model, "_pipe_pack") else None
    if pack and "_pipe" in tree:
        buf = tree["_pipe"]["buffer"]  # device: multi-host shards stay put
        rows = {}  # slice each ring row once, not once per weight
        out = {k: v for k, v in tree.items() if k != "_pipe"}
        for opn, ws in pack["entries"].items():
            d = dict(out.get(opn, {}))
            for wn, e in ws.items():
                row = rows.get(e[0])
                if row is None:
                    row = rows[e[0]] = buf[e[0]]
                d[wn] = model._pack_read(row, e)
            out[opn] = d
        tree = out
    for opn, info in getattr(model, "_host_embed", {}).items():
        wn = info["weight"]
        shard = tree.get(opn, {}).get(wn)
        if (model._he_info(opn, wn) is not None
                and isinstance(shard, np.ndarray)
                and shard.shape[0] == info["row_hi"] - info["row_lo"]):
            tree = {k: (dict(v) if k == opn else v) for k, v in tree.items()}
            tree[opn][wn] = model._he_assemble_full(info, shard)
    return tree


def _repack_tree(model, canonical: Dict[str, Any], like: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of _unpack_tree: fold per-op arrays of packed ops back
    into the model's ``_pipe`` buffer, placed with the LIKE leaf's
    sharding (params vs ZeRO-sharded optimizer slots differ), and slice
    canonical FULL host-embedding tables back to this process's owned
    row range."""
    for opn, info in getattr(model, "_host_embed", {}).items():
        wn = info["weight"]
        full = canonical.get(opn, {}).get(wn) \
            if isinstance(canonical, dict) else None
        if (model._he_info(opn, wn) is not None and full is not None
                and np.asarray(full).shape[0] == info["num_entries"]):
            canonical = {k: (dict(v) if k == opn else v)
                         for k, v in canonical.items()}
            canonical[opn][wn] = np.ascontiguousarray(
                np.asarray(full)[info["row_lo"]:info["row_hi"]])
    pack = model._pipe_pack() if hasattr(model, "_pipe_pack") else None
    if not pack or not isinstance(like, dict) or "_pipe" not in like:
        return canonical
    like_buf = like["_pipe"]["buffer"]
    packed = [(entries[wn], a)
              for opn, ws in canonical.items()
              if (entries := pack["entries"].get(opn))
              for wn, a in ws.items()]
    out = {opn: ws for opn, ws in canonical.items()
           if opn not in pack["entries"]}
    pipe = {k: v for k, v in like["_pipe"].items() if k != "buffer"}
    if all(getattr(a, "is_fully_addressable", True) for _, a in packed):
        # Assemble on host, place with ONE transfer — per-weight
        # .at[].set would copy the whole buffer once per weight.
        buf = np.zeros(like_buf.shape,
                       jax.dtypes.canonicalize_dtype(like_buf.dtype))
        for entry, a in packed:
            type(model)._pack_write_host(buf, entry, a)
        pipe["buffer"] = jax.device_put(buf, like_buf.sharding)
    else:
        # Multi-host restore hands back sharded device arrays a host
        # can't materialize — stay on device (slower: one buffer copy
        # per weight).
        import jax.numpy as jnp

        buf = jnp.zeros(like_buf.shape, like_buf.dtype)
        for entry, a in packed:
            buf = type(model)._pack_write(buf, entry,
                                          jnp.asarray(a, like_buf.dtype))
        pipe["buffer"] = jax.device_put(buf, like_buf.sharding)
    out["_pipe"] = pipe
    return out


def _map_slot_dicts(v, f):
    """Apply f to each params-shaped dict NODE inside an optimizer slot
    (optax states nest them inside NamedTuples/tuples)."""
    if isinstance(v, dict):
        return f(v)
    if isinstance(v, tuple):
        vals = [_map_slot_dicts(x, f) for x in v]
        return type(v)(*vals) if hasattr(v, "_fields") else type(v)(vals)
    if isinstance(v, list):
        return [_map_slot_dicts(x, f) for x in v]
    return v


def _map_slot_dicts2(v, like, f):
    """Two-tree variant: descend v and like in parallel (same outer
    structure; the dict nodes may differ — canonical vs packed)."""
    if isinstance(v, dict):
        return f(v, like)
    if isinstance(v, tuple):
        vals = [_map_slot_dicts2(x, l, f) for x, l in zip(v, like)]
        return type(v)(*vals) if hasattr(v, "_fields") else type(v)(vals)
    if isinstance(v, list):
        return [_map_slot_dicts2(x, l, f) for x, l in zip(v, like)]
    return v


def _tree_from_model(model) -> Dict[str, Any]:
    unpack = lambda d: _unpack_tree(model, d)
    state = {"params": unpack(model._params),
             "stats": model._stats,
             "step": np.full((), model._step_count, np.int64)}
    if model._opt_state is not None:
        state["opt_state"] = {k: _map_slot_dicts(v, unpack)
                              for k, v in model._opt_state.items()}
    return state


def _apply_tree(model, state: Dict[str, Any]) -> None:
    model._params = _repack_tree(model, state["params"], model._params)
    model._stats = state.get("stats", model._stats)
    model._step_count = int(state.get("step", 0))
    if "opt_state" in state and state["opt_state"]:
        cur = model._opt_state or {}
        repack = lambda d, like: _repack_tree(model, d, like)
        model._opt_state = {
            k: (_map_slot_dicts2(v, cur[k], repack) if k in cur
                else _map_slot_dicts(v, lambda d: _repack_tree(
                    model, d, None)))
            for k, v in state["opt_state"].items()}


def save_checkpoint(model, path: str, force: bool = True) -> None:
    """Write the model's full training state to ``path`` (a directory)."""
    from ..observability.health import write_heartbeat

    # no-op unless FF_HEARTBEAT_PATH is set: a wedged save gets named
    # by the external watchdog
    write_heartbeat("checkpoint_save",
                    step=getattr(model, "_step_count", 0))
    tel = getattr(model, "_telemetry", None)
    with phase(tel, "checkpoint_save", path=path,
               step=getattr(model, "_step_count", 0)):
        _save_checkpoint_impl(model, path, force)
    if tel is not None:
        tel.flush()


def _save_checkpoint_impl(model, path: str, force: bool = True) -> None:
    from .resilience import with_ckpt_retries

    # read barrier: an async host-table scatter-back may be in flight
    getattr(model, "_he_join", lambda: None)()
    if path.endswith(".npz"):
        with_ckpt_retries(lambda: _save_npz(model, path),
                          model=model, site="ckpt_save", path=path)
        return
    try:
        import orbax.checkpoint as ocp
    except ImportError:
        with_ckpt_retries(lambda: _save_npz(model, path + ".npz"),
                          model=model, site="ckpt_save", path=path + ".npz")
        return
    path = os.path.abspath(path)

    def _do():
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(path, _tree_from_model(model), force=force)

    # Retried on OSError (resilience.py): orbax writes into a temp dir
    # and finalizes atomically, so a failed attempt leaves no partial
    # checkpoint for the retry to trip over.
    with_ckpt_retries(_do, model=model, site="ckpt_save", path=path)


def load_checkpoint(model, path: str) -> None:
    """Restore training state saved by save_checkpoint, re-sharded onto
    the model's current mesh."""
    from ..observability.health import write_heartbeat

    write_heartbeat("checkpoint_restore")
    tel = getattr(model, "_telemetry", None)
    with phase(tel, "checkpoint_restore", path=path):
        _load_checkpoint_impl(model, path)
    if tel is not None:
        tel.flush()


def _load_checkpoint_impl(model, path: str) -> None:
    from .resilience import with_ckpt_retries

    # an in-flight scatter-back would race the restored tables
    getattr(model, "_he_join", lambda: None)()
    if os.path.isfile(path) or path.endswith(".npz"):
        with_ckpt_retries(lambda: _load_npz(model, path),
                          model=model, site="ckpt_restore", path=path)
        return
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    template = _tree_from_model(model)
    targets = jax.tree.map(
        lambda x: ocp.utils.to_shape_dtype_struct(x) if hasattr(x, "shape") else x,
        template)

    def _do():
        with ocp.StandardCheckpointer() as ckptr:
            return ckptr.restore(path, targets)

    state = with_ckpt_retries(_do, model=model, site="ckpt_restore",
                              path=path)
    _apply_tree(model, state)


def _flatten(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}/"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}{i}/"))
    else:
        out[prefix[:-1]] = np.asarray(tree)
    return out


def _save_npz(model, path: str) -> None:
    flat = _flatten(_tree_from_model(model))
    final = path if path.endswith(".npz") else path + ".npz"
    # Atomic: a crash mid-write must never corrupt the ONLY checkpoint.
    # Sibling temp (same filesystem, so os.replace is a rename) keyed by
    # pid so concurrent writers can't collide on the temp name.
    tmp = f"{final}.tmp-{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            np.savez(f, **flat)
        os.replace(tmp, final)
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _convert_legacy_pipe(model, data) -> Dict[str, np.ndarray]:
    """v0.3.x pipelined checkpoints stored the packed per-stage weight
    buffer verbatim (``.../_pipe/buffer``); the layout-portable format
    stores per-op arrays.  Expand legacy entries on load using the
    model's current pack layout — or fail with a message that names the
    problem instead of an opaque KeyError from the rebuild."""
    out = {k: data[k] for k in data.files}
    legacy = [k for k in out if k.endswith("_pipe/buffer")]
    if not legacy:
        return out
    pack = model._pipe_pack() if hasattr(model, "_pipe_pack") else None
    if not pack:
        raise ValueError(
            "checkpoint predates the layout-portable format (packed "
            "_pipe buffer) and the current model is not pipelined with "
            "a matching stage split — re-save it from a v0.3.x run or "
            "compile with the original pipeline plan to convert it")
    for k in legacy:
        prefix = k[:-len("_pipe/buffer")]
        buf = out.pop(k)
        try:
            for opn, ws in pack["entries"].items():
                for wn, e in ws.items():
                    out[f"{prefix}{opn}/{wn}"] = _pack_read_host(buf, e)
        except Exception as exc:
            raise ValueError(
                f"legacy packed checkpoint entry {k!r} does not match "
                f"the current pipeline pack layout ({exc}) — compile "
                "with the original stage split to convert it") from exc
    # drop any remaining legacy _pipe metadata keys
    return {k: v for k, v in out.items() if "/_pipe/" not in k}


def _pack_read_host(buf, entry):
    row = buf[entry[0]]
    _, off, shape, n = entry
    return np.asarray(row[off:off + n]).reshape(shape)


def _load_npz(model, path: str) -> None:
    data = np.load(path if path.endswith(".npz") else path + ".npz",
                   allow_pickle=False)
    data = _convert_legacy_pipe(model, data)

    def rebuild(template, prefix=""):
        if isinstance(template, dict):
            return {k: rebuild(v, f"{prefix}{k}/") for k, v in template.items()}
        if isinstance(template, (list, tuple)):
            vals = [rebuild(v, f"{prefix}{i}/") for i, v in enumerate(template)]
            if hasattr(template, "_fields"):  # NamedTuple (optax states)
                return type(template)(*vals)
            return type(template)(vals)
        return data[prefix[:-1]]

    state = rebuild(_tree_from_model(model))
    place_state(model, state)


def place_state(model, state: Dict[str, Any]) -> None:
    """Re-place a canonical (host-side, layout-portable) state tree with
    the model's CURRENT shardings and apply it.  Shared by the ``.npz``
    restore path and ``FFModel.recompile`` — after a strategy hot-swap
    the live training state must move onto the new mesh/sharding layout
    exactly the way a cross-mesh restore would."""
    spec_tree = model._param_spec_tree()

    he = getattr(model, "_host_embed", {})

    def place_params_like(tree, zero_specs=None):
        placed = {}
        for opn, ws in tree.items():
            shards = spec_tree.get(opn, {})
            placed[opn] = {}
            for wn, a in ws.items():
                if opn in he and he[opn]["weight"] == wn:
                    # row-sparse host table: stays host-side numpy
                    # (np.array: a writable copy — scatter-updates are
                    # in-place)
                    placed[opn][wn] = np.array(a)
                    continue
                sh = shards.get(wn)
                if zero_specs and (opn, wn) in zero_specs:
                    from jax.sharding import NamedSharding
                    sh = NamedSharding(model.machine.mesh,
                                       zero_specs[(opn, wn)])
                placed[opn][wn] = jax.device_put(a, sh) if sh else a
        return placed

    state["params"] = place_params_like(state["params"])
    if state.get("stats"):
        # replicated, as init_layers places them and the step returns them
        state["stats"] = jax.device_put(state["stats"],
                                        model.machine.replicated())
    if "opt_state" in state and isinstance(state["opt_state"], dict):
        # optimizer slots re-take their param's sharding — or the ZeRO-1
        # layout when the optimizer carries zero_specs; non-dict slots
        # (optax NamedTuple states) re-place replicated on the mesh so
        # the restored step doesn't mix host numpy with mesh arrays
        zs = getattr(model.optimizer, "zero_specs", None) \
            if model.optimizer is not None else None

        def place_other(v, key):
            # non-dict (optax NamedTuple) slots: take each leaf's
            # sharding from a state TEMPLATE freshly initialized over the
            # placed parameters, so param-shaped moments come back
            # sharded like their params (blanket replication would
            # gather model-parallel slots); a leaf made from scratch (a
            # step count) is replicated.  Every leaf ends committed to
            # the mesh, as the step hands it back.
            rep = model.machine.replicated()
            if model.optimizer is not None:
                try:
                    tmpl = model.optimizer.init_state(
                        state["params"]).get(key)
                    return jax.tree.map(
                        lambda a, t: jax.device_put(
                            a, t.sharding if getattr(t, "committed", False)
                            else rep),
                        v, tmpl)
                except Exception:
                    pass  # structure mismatch — replicate below
            return jax.tree.map(lambda a: jax.device_put(a, rep), v)

        state["opt_state"] = {
            k: (place_params_like(v, zs) if isinstance(v, dict)
                else place_other(v, k))
            for k, v in state["opt_state"].items()}
    _apply_tree(model, state)


class CheckpointManager:
    """Rotation + interval policy (orbax CheckpointManager wrapper)."""

    def __init__(self, directory: str, max_to_keep: int = 3,
                 save_interval_steps: int = 1):
        import orbax.checkpoint as ocp

        self.directory = os.path.abspath(directory)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps))

    def save(self, model, step: Optional[int] = None,
             force: bool = False) -> bool:
        import orbax.checkpoint as ocp

        from .resilience import with_ckpt_retries

        step = model._step_count if step is None else step
        if not force and not self._mgr.should_save(step):
            return False  # skip the tree build (and any pipe unpack)
        # force bypasses the interval policy — preemption/failure saves
        # must land regardless of save_interval_steps.
        return with_ckpt_retries(
            lambda: self._mgr.save(
                step, args=ocp.args.StandardSave(_tree_from_model(model)),
                force=force),
            model=model, site="ckpt_save", path=self.directory)

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def restore_latest(self, model) -> Optional[int]:
        import orbax.checkpoint as ocp

        from .resilience import with_ckpt_retries

        step = self._mgr.latest_step()
        if step is None:
            return None
        template = _tree_from_model(model)
        targets = jax.tree.map(
            lambda x: ocp.utils.to_shape_dtype_struct(x) if hasattr(x, "shape") else x,
            template)
        state = with_ckpt_retries(
            lambda: self._mgr.restore(
                step, args=ocp.args.StandardRestore(targets)),
            model=model, site="ckpt_restore", path=self.directory)
        _apply_tree(model, state)
        return step

    def wait_until_finished(self):
        self._mgr.wait_until_finished()

    def close(self):
        self._mgr.close()
