"""GPipe-style pipeline parallelism over a mesh axis.

The reference achieves pipeline-ish parallelism by pinning ops to specific
GPUs and letting Legion overlap their execution (the NMT per-op GPU lists,
nmt/nmt.cc:269-308; SURVEY.md §2.3 'Pipeline-ish / operator placement').
The TPU-native equivalent is SPMD microbatch pipelining: each device along
a ``pipe`` mesh axis holds ONE stage's weights; activations flow stage to
stage via ``lax.ppermute`` while a ``lax.scan`` ticks through
microbatches, filling and draining the bubble.  Backward follows from
autodiff (the transpose of ppermute is the reverse permute; scan
transposes to the reversed schedule).

Constraint: every stage maps (mb, d) -> (mb, d) with the same activation
shape (transformer-block style), so the ring buffer has one static shape.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec


def sequential_stages(stage_fn: Callable, stage_params, x):
    """Reference semantics: apply the P stacked stages in order (the
    single-device fallback, and the per-device body when one device holds
    several consecutive stages)."""
    def body(h, p):
        return stage_fn(p, h), None

    h, _ = lax.scan(body, x, stage_params)
    return h


def gpipe_spmd(stage_fn: Callable, params_local, x_local, axis_name,
               ring_size: int, num_microbatches: int):
    """Run inside shard_map: one call per device along the pipe axis.

    ``params_local``: this device's slice of the stacked stage weights
    (leading dim = stages-per-device, consecutive stages).
    ``x_local``: (B, d) microbatch source, identical on every stage.
    Returns (B, d): the last stage's outputs, replicated to all stages.
    """
    P = ring_size
    M = num_microbatches
    B, *rest = x_local.shape
    assert B % M == 0, f"batch {B} not divisible by {M} microbatches"
    mb = B // M
    mbs = x_local.reshape((M, mb) + tuple(rest))
    s = lax.axis_index(axis_name)

    perm = [(i, (i + 1) % P) for i in range(P)]
    T = M + P - 1
    carry0 = jnp.zeros((mb,) + tuple(rest), x_local.dtype)
    outbuf0 = jnp.zeros((M, mb) + tuple(rest), x_local.dtype)

    def tick(state, t):
        carry, outbuf = state
        x_t = lax.dynamic_index_in_dim(mbs, jnp.clip(t, 0, M - 1), 0,
                                       keepdims=False)
        inp = jnp.where(s == 0, x_t, carry)
        y = sequential_stages(stage_fn, params_local, inp)
        # last stage banks its result once the pipe is full
        widx = jnp.clip(t - (P - 1), 0, M - 1)
        prev = lax.dynamic_index_in_dim(outbuf, widx, 0, keepdims=False)
        bank = jnp.where(jnp.logical_and(s == P - 1, t >= P - 1), y, prev)
        outbuf = lax.dynamic_update_index_in_dim(outbuf, bank, widx, 0)
        return (lax.ppermute(y, axis_name, perm), outbuf), None

    (_, outbuf), _ = lax.scan(tick, (carry0, outbuf0), jnp.arange(T))
    # replicate the last stage's outputs to every stage
    mask = (s == P - 1).astype(jnp.float32)
    out = lax.psum(outbuf.astype(jnp.float32) * mask, axis_name)
    return out.astype(x_local.dtype).reshape((B,) + tuple(rest))


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh: Mesh,
                   pipe_axes: Union[str, Sequence[str]],
                   num_microbatches: int,
                   batch_axes: Optional[Union[str, Sequence[str]]] = None):
    """Pipeline ``stage_fn`` over ``pipe_axes`` of ``mesh``.

    ``stage_params``: pytree whose leaves have a leading stage dim P
    (sharded over the pipe axes).  ``x``: (B, d) global activations
    (optionally batch-sharded over ``batch_axes``).  Composes dp×pp: the
    batch axes shard B while each pipe-axis slice runs its own pipeline.
    """
    pipe_axes = ((pipe_axes,) if isinstance(pipe_axes, str)
                 else tuple(pipe_axes))
    if batch_axes:
        batch_axes = ((batch_axes,) if isinstance(batch_axes, str)
                      else tuple(batch_axes))
    axis_name = pipe_axes[0] if len(pipe_axes) == 1 else pipe_axes
    ring = 1
    for a in pipe_axes:
        ring *= mesh.shape[a]
    num_stages = jax.tree.leaves(stage_params)[0].shape[0]
    assert num_stages % ring == 0, \
        f"{num_stages} stages not divisible over {ring} pipe devices"

    bspec = batch_axes if batch_axes else None
    x_spec = PartitionSpec(bspec, None)
    p_spec = jax.tree.map(lambda _: PartitionSpec(pipe_axes), stage_params)
    extra = _unused_axes(mesh, set(pipe_axes) | set(batch_axes or ()))

    @partial(shard_map, mesh=mesh, in_specs=(p_spec, x_spec),
             out_specs=x_spec, check_vma=False)
    def run(pl, xl):
        y = gpipe_spmd(stage_fn, pl, xl, axis_name, ring,
                       num_microbatches)
        return _replica_correct(y, mesh, extra)

    return run(stage_params, x)


def _unused_axes(mesh: Mesh, used) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a not in used)


def _replica_correct(y, mesh: Mesh, extra: Tuple[str, ...]):
    """Identity on the forward value, gradient-correct on the backward.

    When the pipeline occupies only a subset of the mesh axes, the
    computation is replicated over the unused axes; shard_map's transpose
    then psums replicated-input cotangents over ALL mesh axes, counting
    each replica's (identical, full) contribution once per replica.
    Emitting ``psum(y / R)`` over the unused axes leaves the forward value
    unchanged (R identical copies of y/R) while scaling each replica's
    cotangent to dout/R, so the transpose's psum reconstructs the true
    gradient exactly once.
    """
    if not extra:
        return y
    r = 1
    for a in extra:
        r *= mesh.shape[a]
    ax = extra if len(extra) > 1 else extra[0]
    return lax.psum(y / r, ax)


# ----------------------------------------------------------------------
# Heterogeneous pipelines: arbitrary per-stage subgraphs
# ----------------------------------------------------------------------
#
# The reference pipelines HETEROGENEOUS ops by pinning each op to a GPU
# list (nmt/nmt.cc:269-308 assigns encoder ops to one set of GPUs and
# decoder ops to another; the mapper places every point task accordingly,
# src/mapper/mapper.cc:33-146).  The TPU-native equivalent below keeps
# the SPMD single-program constraint: inside a shard_map over the pipe
# axis every device runs ``lax.switch`` on its own stage index, so device
# group s executes ONLY stage s's subgraph — placement by branch, the
# moral twin of the reference's placement by mapper.  Activations cross
# stage boundaries as flattened buffers padded to the largest boundary
# size so the ppermute ring keeps one static shape; the wire payload is
# trimmed to the largest real inter-stage boundary and the unused wrap
# hop is dropped (see ring_shift in gpipe_hetero_spmd).


def _flat_pad(y: jax.Array, pad: int, dtype) -> jax.Array:
    flat = y.reshape(y.shape[0], -1).astype(dtype)
    if flat.shape[1] < pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad - flat.shape[1])))
    return flat


def _unflat(h: jax.Array, shape: Tuple[int, ...], dtype) -> jax.Array:
    n = int(np.prod(shape)) if shape else 1
    return h[:, :n].reshape((h.shape[0],) + tuple(shape)).astype(dtype)


def gpipe_hetero_spmd(stage_fns: Sequence[Callable], params, x_local,
                      axis_name, ring_size: int, num_microbatches: int,
                      in_shapes: Sequence[Tuple[int, ...]],
                      out_shapes: Sequence[Tuple[int, ...]],
                      dtype, remat: bool = False) -> jax.Array:
    """GPipe schedule for per-stage heterogeneous functions.

    Runs inside shard_map over the pipe axis.  ``stage_fns[s]`` maps a
    (mb,)+in_shapes[s] microbatch to (mb,)+out_shapes[s]; every function
    receives the full ``params`` tree and closes over only what it needs
    (autodiff flows through the switch branches).  ``x_local``: this
    device's (B, flat) batch of flattened stage-0 inputs.
    """
    P = ring_size
    M = num_microbatches
    B = x_local.shape[0]
    assert B % M == 0, f"local batch {B} not divisible by {M} microbatches"
    mb = B // M
    pad = x_local.shape[1]
    mbs = x_local.reshape(M, mb, pad)
    s = lax.axis_index(axis_name)

    def make_branch(i):
        def raw(p, h, micro_idx):
            y = stage_fns[i](p, _unflat(h, in_shapes[i], dtype), micro_idx)
            return _flat_pad(y, pad, dtype)
        if remat:
            # Rematerialized ring: grad-of-scan keeps only the boundary
            # carries as residuals and recomputes each stage's interior
            # in backward — the memory lever that lets M grow and shrink
            # the fill/drain bubble fraction (P-1)/(M+P-1).  See
            # docs/ADR-002-pipeline-schedule.md for why this dominates a
            # literal 1F1B schedule under XLA's lockstep scan semantics.
            # prevent_cse=False: the scan's loop structure already rules
            # out the CSE remat guards against, and the default barriers
            # would block fusion inside the (M+P-1)-tick hot loop
            raw = jax.checkpoint(raw, prevent_cse=False)

        def branch(h, micro_idx):
            return raw(params, h, micro_idx)
        return branch

    branches = [make_branch(i) for i in range(P)]

    perm = [(i, (i + 1) % P) for i in range(P)]
    # Boundary byte budget: the compute buffers pad to the largest
    # boundary INCLUDING the stage-0 input and final output, but the only
    # data that ever crosses the wire is an inter-stage boundary.  Trim
    # the ppermute payload to the largest REAL hop (conv front stages
    # feeding a small dense head make this much smaller than pad) and
    # drop the unused wrap hop (P-1 -> 0; slot 0 reads the microbatch
    # feed instead).  Kept as ONE collective — per-hop-sized ppermutes
    # break shard_map's transpose sharding inference under jax.grad.
    n_hop = [max(1, int(np.prod(sh)) if sh else 1) for sh in out_shapes]
    n_wire = max(n_hop[:P - 1]) if P > 1 else pad
    trim = P > 1 and n_wire < pad

    def ring_shift(y):
        if not trim:
            return lax.ppermute(y, axis_name, perm)
        r = lax.ppermute(y[:, :n_wire], axis_name,
                         [(i, i + 1) for i in range(P - 1)])
        return jnp.pad(r, ((0, 0), (0, pad - n_wire)))

    T = M + P - 1
    carry0 = jnp.zeros((mb, pad), dtype)
    outbuf0 = jnp.zeros((M, mb, pad), dtype)

    def tick(state, t):
        carry, outbuf = state
        x_t = lax.dynamic_index_in_dim(mbs, jnp.clip(t, 0, M - 1), 0,
                                       keepdims=False)
        inp = jnp.where(s == 0, x_t, carry)
        # this device's current microbatch index (stage s sees mb t-s);
        # stochastic ops fold it into their RNG for per-microbatch draws
        micro_idx = jnp.clip(t - s, 0, M - 1)
        y = lax.switch(s, branches, inp, micro_idx)
        widx = jnp.clip(t - (P - 1), 0, M - 1)
        prev = lax.dynamic_index_in_dim(outbuf, widx, 0, keepdims=False)
        bank = jnp.where(jnp.logical_and(s == P - 1, t >= P - 1), y, prev)
        outbuf = lax.dynamic_update_index_in_dim(outbuf, bank, widx, 0)
        return (ring_shift(y), outbuf), None

    (_, outbuf), _ = lax.scan(tick, (carry0, outbuf0), jnp.arange(T))
    mask = (s == P - 1).astype(jnp.float32)
    out = lax.psum(outbuf.astype(jnp.float32) * mask, axis_name)
    n_out = int(np.prod(out_shapes[P - 1]))
    return out.astype(dtype).reshape(B, pad)[:, :n_out]


def pipeline_graph_apply(stage_fns: Sequence[Callable], params, x,
                         mesh: Mesh,
                         pipe_axes: Union[str, Sequence[str]],
                         num_microbatches: int,
                         in_shapes: Sequence[Tuple[int, ...]],
                         out_shapes: Sequence[Tuple[int, ...]],
                         batch_axes: Optional[Union[str, Sequence[str]]] = None,
                         param_specs=None, remat: bool = False):
    """Pipeline a chain of heterogeneous stage functions over ``pipe_axes``.

    ``stage_fns[s](params, h, micro_idx)`` consumes/produces per-sample
    shapes ``in_shapes[s]`` / ``out_shapes[s]`` (out_shapes[s] ==
    in_shapes[s+1]); ``micro_idx`` is the microbatch index for stochastic
    ops' RNG streams.  When the ring is smaller than ``len(stage_fns)``,
    consecutive stages are composed onto one device.  ``x``:
    (B,)+in_shapes[0] global input, optionally batch-sharded over
    ``batch_axes`` (dp×pp composition).  Returns (B,)+out_shapes[-1].

    ``param_specs``: optional PartitionSpec tree matching ``params``.
    Default replicates every leaf; the caller passes pipe-axis-sharded
    specs for stage-local weights (FFModel packs each ring slot's stage
    weights into a (ring, W) buffer sharded here, so an S-slot pipeline
    stores ~1/S of the model per device — the analogue of the reference
    mapper placing each op's weights only on its assigned GPUs,
    src/mapper/mapper.cc:33-146).  Stage fns read their slot's slice of
    the local view; shard_map's transpose keeps sharded-leaf cotangents
    local, so each device only ever materializes its own slot's grads.
    """
    pipe_axes = ((pipe_axes,) if isinstance(pipe_axes, str)
                 else tuple(pipe_axes))
    if batch_axes:
        batch_axes = ((batch_axes,) if isinstance(batch_axes, str)
                      else tuple(batch_axes))
    axis_name = pipe_axes[0] if len(pipe_axes) == 1 else pipe_axes
    ring = 1
    for a in pipe_axes:
        ring *= mesh.shape[a]
    S = len(stage_fns)
    assert S % ring == 0, f"{S} stages not divisible over {ring} pipe devices"
    k = S // ring

    # Group consecutive stages onto each ring slot.
    def compose(lo, hi):
        def fn(p, h, micro_idx):
            for i in range(lo, hi):
                h = stage_fns[i](p, h, micro_idx)
            return h
        return fn

    ring_fns = [compose(r * k, (r + 1) * k) for r in range(ring)]
    ring_in = [tuple(in_shapes[r * k]) for r in range(ring)]
    ring_out = [tuple(out_shapes[(r + 1) * k - 1]) for r in range(ring)]

    dtype = x.dtype
    boundary = ring_in + [ring_out[-1]]
    pad = max(int(np.prod(sh)) if sh else 1 for sh in boundary)
    xf = _flat_pad(x, pad, dtype)

    bspec = (batch_axes[0] if len(batch_axes) == 1 else batch_axes) \
        if batch_axes else None
    x_spec = PartitionSpec(bspec, None)
    p_spec = (param_specs if param_specs is not None
              else jax.tree.map(lambda _: PartitionSpec(), params))
    extra = _unused_axes(mesh, set(pipe_axes) | set(batch_axes or ()))

    @partial(shard_map, mesh=mesh, in_specs=(p_spec, x_spec),
             out_specs=x_spec, check_vma=False)
    def run(pl, xl):
        y = gpipe_hetero_spmd(ring_fns, pl, xl, axis_name, ring,
                              num_microbatches, ring_in, ring_out, dtype,
                              remat=remat)
        return _replica_correct(y, mesh, extra)

    out_flat = run(params, xf)
    B = x.shape[0]
    return out_flat.reshape((B,) + tuple(out_shapes[-1]))


