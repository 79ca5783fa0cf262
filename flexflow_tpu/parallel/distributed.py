"""Distributed multi-host runtime: the ICI/DCN communication backend.

The reference scales multi-node through Legion control replication +
GASNet, with a sharding functor splitting task points across nodes by
sample dim (reference: src/runtime/model.cc:1345-1370, README.md:18).
The TPU-native backend replaces that stack with JAX multi-controller
SPMD:

  * every host runs the same program (`jax.distributed.initialize`
    wires the coordination service — the GASNet analogue),
  * a **hybrid mesh** puts the slow DCN (inter-slice network) on the
    leading mesh axis and the fast ICI torus on the trailing axes, so
    batch-dim (data-parallel) sharding rides DCN while tensor/seq/spatial
    partitions ride ICI — the layout the reference approximates with its
    intra-node vs inter-node bandwidth model (simulator.cu:27-29),
  * per-host input feeding assembles a global batch from each host's
    local shard (`jax.make_array_from_process_local_data` — the analogue
    of the per-node dataloader scatter, model.cc:1361-1370).

Single-process runs degrade gracefully: initialize() is a no-op and the
hybrid mesh collapses to the plain prime-factored Machine mesh.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding

from .mesh import Machine, hybrid_axis_layout

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids: Optional[Sequence[int]] = None) -> None:
    """Bring up the multi-controller runtime (≈ Legion+GASNet startup).

    On TPU pods the args auto-detect from the metadata server; on other
    platforms they come from the caller or the standard env vars
    (COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID).  Safe to call in
    single-process runs — it no-ops when there is nothing to coordinate.
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("NUM_PROCESSES"):
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and os.environ.get("PROCESS_ID"):
        process_id = int(os.environ["PROCESS_ID"])
    if num_processes is not None and num_processes <= 1:
        return
    # IMPORTANT: nothing here may touch the XLA backend (jax.devices,
    # jax.default_backend, ...) — jax.distributed.initialize must run
    # before backend init or it refuses outright.
    if coordinator_address is None and num_processes is None:
        # If the XLA backend is ALREADY up we may query it without side
        # effects: a non-TPU backend with no coordinator info is a plain
        # single-process run — return rather than let the bare initialize
        # raise "must be called before any JAX calls" for a case that
        # needs no coordination at all.
        try:
            from jax._src import xla_bridge
            backend_up = xla_bridge.backends_are_initialized()
        except Exception:
            backend_up = False
        if backend_up and jax.default_backend() != "tpu":
            return
        # TPU pods autodetect everything from the metadata server; on any
        # other backend the bare call raises ValueError immediately →
        # single-process.  RuntimeError ("must be called before any JAX
        # calls") propagates: on a pod, swallowing it would silently turn
        # N hosts into N independent single-process runs.
        try:
            jax.distributed.initialize()
        except ValueError:
            return
    else:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id,
                                   local_device_ids=local_device_ids)
    _initialized = True


def shutdown() -> None:
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def is_coordinator() -> bool:
    return jax.process_index() == 0


def hybrid_machine(dcn_degree: Optional[int] = None,
                   devices: Optional[Sequence] = None) -> Machine:
    """Build a Machine whose mesh separates DCN from ICI.

    ``dcn_degree`` defaults to the number of processes (one slice per
    host group).  The DCN axis is the leading mesh axis named ``dcn``;
    the per-slice device count is prime-factored into ICI axes
    ``m0, m1, ...`` exactly like the single-slice Machine
    (``mesh.hybrid_axis_layout`` describes both).  An op output's sample
    dim claims axes first and only it may take ``dcn`` by rule
    (``mesh.assign_axes``), so the gradient all-reduce is the only
    DCN-crossing collective, matching how the reference maps sample-dim
    parallelism across nodes (DataParallelShardingFunctor,
    model.cc:1361-1370).
    """
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    if dcn_degree is None:
        dcn_degree = jax.process_count()
    if dcn_degree <= 1 or n % dcn_degree != 0:
        return Machine(devices)
    names, shape = hybrid_axis_layout(n, dcn_degree)
    # Host-major device order: contiguous blocks per process so the dcn
    # axis cuts exactly on host boundaries.
    order = sorted(range(n), key=lambda i: (
        getattr(devices[i], "process_index", 0), getattr(devices[i], "id", i)))
    dev_array = np.array([devices[i] for i in order]).reshape(shape)
    return Machine(mesh=Mesh(dev_array, names))


def host_local_batch(machine: Machine, local_arr: np.ndarray, degree: int):
    """Assemble the global batch array from this host's local shard.

    Every host holds ``global_batch / process_count`` samples; the result
    is a global jax.Array sharded over the batch axes of ``machine``.
    Single-process: equivalent to a device_put with the batch sharding.
    """
    sharding: NamedSharding = machine.batch_sharding(degree)
    if jax.process_count() == 1:
        return jax.device_put(local_arr, sharding)
    return jax.make_array_from_process_local_data(sharding, local_arr)
