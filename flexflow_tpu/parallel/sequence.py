"""Sequence / context parallelism: ring attention and Ulysses all-to-all.

The reference has no sequence parallelism (SURVEY §5.7: its only
long-sequence mechanism is the NMT LSTM chunking, nmt/rnn.h:21-23); the
SOAP abstraction of partitioning any tensor dim is the hook, and this
module is the TPU realization: the sequence dim of an attention op's
ParallelConfig maps to a mesh axis, and attention runs as

  * **ring attention** — K/V shards rotate around the mesh axis with
    `lax.ppermute` (one ICI hop per step), each step folding a blockwise
    softmax partial into a running (out, logsumexp) pair — memory per
    chip stays O(S_local²) while the attention span is the full sequence;
  * **Ulysses all-to-all** — `lax.all_to_all` re-shards seq→heads, runs
    dense local attention, and re-shards back; cheaper at moderate S
    when heads divide the axis.

Both are pure jax and differentiable (ppermute/all_to_all have
transpose rules; the flash kernel carries a custom VJP), so the same
`jax.grad` training path the rest of the framework uses works unchanged.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..kernels.flash_attention import flash_attention, NEG_INF

_EMPTY_THRESH = NEG_INF / 2  # lse below this means "row saw no keys yet"


def _merge_partials(o1, lse1, o2, lse2):
    """Fold two normalized blockwise-softmax partials (out, lse) into one.

    o_i are already normalized over their own key blocks; the exact merge
    is a logsumexp-weighted average.  Rows that saw no keys carry
    lse <= NEG_INF/2 and contribute weight 0.
    """
    e1 = jnp.where(lse1 <= _EMPTY_THRESH, 0.0, 1.0)
    e2 = jnp.where(lse2 <= _EMPTY_THRESH, 0.0, 1.0)
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(m <= _EMPTY_THRESH, 0.0, m)
    a1 = e1 * jnp.exp(jnp.minimum(lse1 - m_safe, 0.0))
    a2 = e2 * jnp.exp(jnp.minimum(lse2 - m_safe, 0.0))
    denom = a1 + a2
    denom_safe = jnp.where(denom == 0.0, 1.0, denom)
    o = (o1 * a1[..., None] + o2 * a2[..., None]) / denom_safe[..., None]
    lse = jnp.where(denom == 0.0, NEG_INF, m_safe + jnp.log(denom_safe))
    return o, lse


def blockwise_attention(q, k, v, *, scale: Optional[float] = None,
                        causal: bool = False, q_offset=0, k_offset=0):
    """Local attention over one (q-block, k-block) pair returning
    (normalized out, lse).  Offsets give the blocks' absolute sequence
    positions so a causal mask works across shards; they may be traced.

    This is the XLA path — the caller picks between it and the Pallas
    flash kernel (ops/attention.py decides by platform and shape).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        q_pos = q_offset + jnp.arange(sq)
        k_pos = k_offset + jnp.arange(sk)
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                         # (B,H,Sq)
    empty = m <= _EMPTY_THRESH
    m_safe = jnp.where(empty, 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where((s <= _EMPTY_THRESH), 0.0, p) if causal else p
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf) / l_safe[..., None]
    lse = jnp.where(l == 0.0, NEG_INF, m_safe + jnp.log(l_safe))
    return out.astype(q.dtype), lse


def ring_attention(q, k, v, axis_name: str, *, causal: bool = False,
                   scale: Optional[float] = None,
                   use_flash: bool = False, interpret: bool = False):
    """Ring attention over sequence shards.  Call inside shard_map.

    q, k, v: (B, H, S_local, D), the local shard of a sequence split
    along ``axis_name``.  Each of the ``n`` steps attends the local q
    block against the currently-held K/V block, then rotates K/V one hop
    around the ring (lax.ppermute over ICI), merging the normalized
    partials by logsumexp.  Numerically identical to full attention over
    the gathered sequence.  ``use_flash`` runs each block pair through
    the Pallas kernel (``interpret`` as in ``flash_attention``) instead
    of ``blockwise_attention``; the caller chooses.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    flash = partial(flash_attention, scale=scale, return_lse=True,
                    interpret=interpret)
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(qb, kb, vb, step):
        """Attention of the local q block vs the block held at ``step``
        (which originated on device (idx - step) mod n)."""
        src = (idx - step) % n
        if not causal:
            if use_flash:
                return flash(qb, kb, vb)
            return blockwise_attention(qb, kb, vb, scale=scale)
        if use_flash:
            if step == 0:
                # Diagonal block: positions align, plain causal flash.
                return flash(qb, kb, vb, causal=True)
            # step >= 1: block is strictly earlier (full attention) when
            # src < idx, i.e. idx >= step; otherwise fully masked.
            def full(_):
                return flash(qb, kb, vb)

            def masked(_):
                return (jnp.zeros_like(qb),
                        jnp.full((b, h, s_loc), NEG_INF, jnp.float32))

            return jax.lax.cond(idx >= step, full, masked, None)
        return blockwise_attention(qb, kb, vb, scale=scale, causal=True,
                                   q_offset=idx * s_loc, k_offset=src * s_loc)

    o, lse = local(q, k, v, 0)
    kv = (k, v)
    for step in range(1, n):
        kv = jax.lax.ppermute(kv, axis_name, perm)
        o_s, lse_s = local(q, kv[0], kv[1], step)
        o, lse = _merge_partials(o, lse, o_s, lse_s)
    return o


def ulysses_attention(q, k, v, axis_name: str, *, causal: bool = False,
                      scale: Optional[float] = None,
                      use_flash: bool = False, interpret: bool = False):
    """DeepSpeed-Ulysses-style sequence parallelism.  Call inside shard_map.

    q, k, v: (B, H, S_local, D) sequence shards.  all_to_all re-shards to
    (B, H_local, S, D) head shards, local attention runs over the full
    sequence, and the inverse all_to_all restores sequence sharding.
    Requires H divisible by the axis size.
    """
    # seq-sharded → head-sharded: split heads, concat seq.
    qh = jax.lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    kh = jax.lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    vh = jax.lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    if use_flash:
        oh = flash_attention(qh, kh, vh, scale=scale, causal=causal,
                             interpret=interpret)
    else:
        oh, _ = blockwise_attention(qh, kh, vh, scale=scale, causal=causal)
    return jax.lax.all_to_all(oh, axis_name, split_axis=2, concat_axis=1, tiled=True)


def sequence_parallel_attention(q, k, v, mesh: Mesh, seq_axes, *,
                                batch_axes=None, causal: bool = False,
                                scale: Optional[float] = None,
                                mode: str = "ring",
                                use_flash: bool = False,
                                interpret: bool = False):
    """Run ring/Ulysses attention over global (B, H, S, D) arrays.

    Wraps shard_map over ``mesh``: sequence dim sharded by ``seq_axes``
    (a mesh-axis name or tuple of them), batch dim by ``batch_axes``.
    This is the entry the MultiHeadAttention op uses when its
    ParallelConfig splits the sequence dim.
    """
    seq_axes = (seq_axes,) if isinstance(seq_axes, str) else tuple(seq_axes)
    if batch_axes:
        batch_axes = ((batch_axes,) if isinstance(batch_axes, str)
                      else tuple(batch_axes))
    # A fused axis tuple acts as one flattened ring: ppermute/axis_index/
    # psum all accept axis-name tuples (row-major flattened index).
    axis_name = seq_axes[0] if len(seq_axes) == 1 else seq_axes
    bspec = batch_axes if batch_axes else None
    spec = PartitionSpec(bspec, None, seq_axes, None)
    fn = ring_attention if mode == "ring" else ulysses_attention

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def run(ql, kl, vl):
        return fn(ql, kl, vl, axis_name, causal=causal, scale=scale,
                  use_flash=use_flash, interpret=interpret)

    return run(q, k, v)
