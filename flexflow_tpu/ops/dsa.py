"""Learned sparse attention's index (the "DSA indexer" of DeepSeek-V3.2-Exp's
report and its public inference code), for training: a small index scores
every earlier key for every query, the ``topk`` best keys are the ones the
main attention's softmax runs over, and the index learns from a KL term
against the main attention's own distribution over those keys.

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])          (index_scores)
    S_t     = the min(topk, t + 1) keys s <= t of largest I[t, s],
              ties to the lower s                            (select_topk)
    L_I     = mean_t KL(p_t || softmax_{s in S_t} I[t, s])   (index_kl)

with ``p_t`` the main attention's probabilities over ``S_t`` summed over
the heads held and normalised to 1, a constant.  Nothing here is ever
held as (heads, T, T): the scores are reduced over the index's heads a
block of queries at a time, forward and backward, and so is the target.
The scores in float32 at the highest matmul precision (a score decides
which keys a query gets, as a router's affinity decides its experts),
their gradient in the step's compute dtype at the default one.

What runs where.  ``select_topk``, ``index_kl`` and ``masked_attention``
are plain ``jax.numpy`` on every platform.  ``index_scores`` has two
forms, and its caller says which (``impl``, as the attention core's):
the Pallas kernels of ``kernels/dsa_index.py`` on a TPU where the shape
tiles, which hold a tile's (heads x queries, keys) product in VMEM and
write ``I`` alone (``"pallas"``; ``"pallas_interpret"`` for a test; they
also take the queries' rotary turn, so that nothing as large as the
queries passes through HBM between the projection and them), and
the ``jax.numpy`` form below elsewhere (``"xla"``: the CPU, the tests'
32-token shapes), which is also the kernels' reference: a Python loop
over blocks of queries that XLA computes through HBM, held in turn by
``optimization_barrier``.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp

NEG_INF = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


def _blocks(t: int, block_q: int, super_q: int):
    """[(lo, hi, keys)]: the blocks of queries lo..hi, each with the keys
    it sees.  A super block's queries see the keys up to its own last one
    only, so the causal half of the work is left out a super block at a
    time; a block is what is held at once as (heads, block, keys)."""
    sup = super_q if t % super_q == 0 else t
    blk = block_q if sup % block_q == 0 else sup
    return [(lo, lo + blk, (lo // sup + 1) * sup) for lo in range(0, t, blk)]


def _in_turn(x, after):
    """``x``, not to be used before ``after`` is there: the blocks are
    independent, and a scheduler that started them all at once would hold
    every block's (heads, block, keys) product at the same time."""
    if after is None:
        return x
    return jax.lax.optimization_barrier((x, after))[0]


def _head_scores(qb, kb, precision, dtype=jnp.float32):
    """relu's argument, (B, n, heads, keys): every index head of a block
    of queries against the keys."""
    b, n, h, d = qb.shape
    s = jnp.einsum("bqd,bkd->bqk", qb.reshape(b, n * h, d), kb,
                   precision=precision, preferred_element_type=dtype)
    return s.reshape(b, n, h, kb.shape[1])


def pairs_apart(x, rope: int):
    """``x`` (..., d) with the first ``rope`` of its last dimension, r/2
    adjacent pairs, as the pairs' first elements and then their second
    ones: the layout ``turn_halves`` and the kernels turn."""
    pairs = x[..., :rope].reshape(x.shape[:-1] + (rope // 2, 2))
    return jnp.concatenate([pairs[..., 0], pairs[..., 1], x[..., rope:]],
                           axis=-1)


def turn_halves(q, cos, sin):
    """The rotary turn of ``q`` (B, T, heads, d) whose heads hold the r/2
    rotary pairs' first elements, then their second ones, then what is
    not turned; ``cos``, ``sin`` (T, r/2).  What ``rotate_pairs`` makes
    of adjacent pairs, value for value."""
    half = cos.shape[-1]
    a, b = q[..., :half], q[..., half:2 * half]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * c - b * s, a * s + b * c, q[..., 2 * half:]],
                           axis=-1)


def index_scores(q, k, w, grad_dtype=jnp.float32, block_q: int = 256,
                 super_q: int = 1024, impl: str = "xla", q_rope=None):
    """``I`` (B, T, T) float32 from the index's queries ``q`` (B, T,
    heads, d), its one key a position ``k`` (B, T, d) and the head
    weights ``w`` (B, T, heads), all float32.  Entries above the diagonal
    hold ``NEG_INF`` or a score: ``select_topk`` and ``index_kl`` read
    the causal ones alone.  ``grad_dtype``: what the backward pass holds
    its (heads, queries, keys) products in, the step's compute dtype.
    ``impl``: ``"xla"``, this module's form in blocks of ``block_q``
    queries within super blocks of ``super_q``; ``"pallas"`` or
    ``"pallas_interpret"``, the kernels, whose blocks are their own
    rule's (``kernels.dsa_index.tiling``).  ``q_rope``: None for queries
    that come turned, or ``(cos, sin)`` for queries laid out as
    ``turn_halves`` takes them and not turned yet; the kernels then turn
    them a q block at a time in VMEM, and their gradient back."""
    if impl == "xla":
        if q_rope is not None:
            q = turn_halves(q, *q_rope)
        return _index_scores_xla(q, k, w, grad_dtype, block_q, super_q)
    from ..kernels import dsa_index
    return dsa_index.index_scores(q, k, w, q_rope, grad_dtype, None, None,
                                  impl == "pallas_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _index_scores_xla(q, k, w, grad_dtype, block_q, super_q):
    """The blocks are a Python loop, not a ``lax.map``: a while loop's
    time is counted twice in a device trace (its own event and its
    body's)."""
    return _index_scores_fwd(q, k, w, grad_dtype, block_q, super_q)[0]


def _index_scores_fwd(q, k, w, grad_dtype, block_q, super_q):
    t = q.shape[1]
    rows, last = [], None
    for lo, hi, keys in _blocks(t, block_q, super_q):
        qb = _in_turn(q[:, lo:hi], last)
        s = jax.nn.relu(_head_scores(qb, k[:, :keys], _HIGHEST))
        last = jnp.sum(w[:, lo:hi, :, None] * s, axis=2)       # (B, n, keys)
        rows.append(jnp.pad(last, ((0, 0), (0, 0), (0, t - keys)),
                            constant_values=NEG_INF))
    return jnp.concatenate(rows, axis=1), (q, k, w)


def _index_scores_bwd(grad_dtype, block_q, super_q, res, g):
    q, k, w = res
    t = q.shape[1]
    dk = jnp.zeros(k.shape, jnp.float32)
    kg = k.astype(grad_dtype)
    dqs, dws = [], []
    for lo, hi, keys in _blocks(t, block_q, super_q):
        qb = _in_turn(q[:, lo:hi], dk if dqs else None).astype(grad_dtype)
        kb = kg[:, :keys]
        gb = g[:, lo:hi, None, :keys].astype(grad_dtype)       # (B, n, 1, keys)
        b, n, h, d = qb.shape
        s = _head_scores(qb, kb, None, grad_dtype)
        dws.append(jnp.sum((gb * jax.nn.relu(s)).astype(jnp.float32),
                           axis=-1))
        ds = jnp.where(s > 0, gb * w[:, lo:hi, :, None].astype(grad_dtype),
                       jnp.zeros((), grad_dtype)).reshape(b, n * h, keys)
        dqs.append(jnp.einsum("bqk,bkd->bqd", ds, kb,
                              preferred_element_type=jnp.float32
                              ).reshape(b, n, h, d))
        dk = dk.at[:, :keys].add(jnp.einsum(
            "bqk,bqd->bkd", ds, qb.reshape(b, n * h, d),
            preferred_element_type=jnp.float32))
    return jnp.concatenate(dqs, axis=1), dk, jnp.concatenate(dws, axis=1)


_index_scores_xla.defvjp(_index_scores_fwd, _index_scores_bwd)


def causal_mask(t: int):
    """(T, T) bool, queries down the rows: k <= q."""
    return jax.lax.broadcasted_iota(jnp.int32, (t, t), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)


def _order_keys(x):
    """float32 -> uint32 whose unsigned order is the floats' order
    (-inf lowest)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(bits, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def kth_largest_key(u, k: int):
    """The ``k``-th largest of each row of ``u`` (..., n) uint32: the
    largest value v with ``count(u >= v) >= k``, built two bits a pass
    from the top (16 passes that each count three candidates in one read
    of ``u``), where a sort of the rows took eight times as long on the
    v5e (PERF.md, PR 35)."""
    ans = jnp.zeros(u.shape[:-1] + (1,), jnp.uint32)
    for bit in range(30, -1, -2):
        digit = jnp.zeros_like(ans)
        for j in (1, 2, 3):
            cand = ans | jnp.uint32(j << bit)
            enough = jnp.sum((u >= cand).astype(jnp.int32), axis=-1,
                             keepdims=True) >= k
            digit = digit + enough.astype(jnp.uint32)
        ans = ans | (digit << bit)
    return ans


def select_topk(scores, topk: int):
    """``S`` (B, T, T) bool, queries down the rows: of the keys s <= t of
    query t, the ``min(topk, t + 1)`` of largest ``scores[t, s]``; among
    equal scores the lower s.  No gradient.  Exact: the row's ``topk``-th
    largest score is the threshold, every key above it is in, and of the
    keys equal to it the first ones that fill the count (where no tie
    straddles a threshold, as with real scores, those are all of them and
    the running count is never taken)."""
    t = scores.shape[-1]
    causal = causal_mask(t)
    if t <= topk:
        return jnp.broadcast_to(causal, scores.shape)
    x = jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf)
    u = _order_keys(x)
    kth = kth_largest_key(u, topk)
    above = u > kth
    equal = (u == kth) & causal
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    n_equal = jnp.sum(equal, axis=-1, keepdims=True)
    return (above & causal) | jax.lax.cond(
        jnp.any(n_equal > room),
        lambda: equal & (jnp.cumsum(equal, axis=-1) <= room),
        lambda: equal)


def masked_attention(q, k, v, keep, scale: float):
    """XLA's form of attention under a mask, for a platform without the
    kernel: q, k (B, H, T, d), v (B, H, T, dv), ``keep`` (B | 1, T, T)
    bool, queries down the rows.  Scores and softmax in float32, held
    whole: for small shapes.  Returns the output and the rows' log-sum-
    exp (B, H, T)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(keep[:, None], s, NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def index_kl(scores, keep, q, k, lse, scale: float, block_q: int = 512):
    """``L_I``: the mean over queries of ``KL(p_t || softmax_{S_t} I_t)``.

    ``scores`` (B, T, T) is ``I``; ``keep`` (B, T, T) bool is ``S``; the
    target ``p_t`` is computed here, a block of queries at a time, from
    the main attention's own operands: ``q``, ``k`` (B, H, T, d) as the
    core took them, its rows' log-sum-exp ``lse`` (B, H, T) and its
    ``scale``: ``exp(q . k * scale - lse)`` over the kept keys, summed
    over the heads and normalised to 1.  A constant: only ``scores``
    takes a gradient, ``(softmax_S(I) - p) / (B T)``, which the forward
    pass leaves behind for the backward one."""
    return _index_kl_fwd(scores, keep, q, k, lse, scale, block_q)[0]


def _index_kl_fwd(scores, keep, q, k, lse, scale, block_q):
    b, t, _ = scores.shape
    blk = block_q if t % block_q == 0 else t
    kl, grads = 0.0, []
    for lo in range(0, t, blk):
        ib, sb = scores[:, lo:lo + blk], keep[:, lo:lo + blk]  # (B, n, T)
        qb = _in_turn(q[:, :, lo:lo + blk], grads[-1] if grads else None)
        s = jnp.einsum("bhqd,bhkd->bqhk", qb, k,
                       preferred_element_type=jnp.float32) * scale
        lb = jnp.swapaxes(lse[:, :, lo:lo + blk], 1, 2)        # (B, n, H)
        p = jnp.where(sb[:, :, None, :], jnp.exp(s - lb[..., None]), 0.0)
        p = jnp.sum(p, axis=2)                                  # over heads
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        logits = jnp.where(sb, ib, NEG_INF)
        logq = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        kl = kl + jnp.sum(jnp.where(
            p > 0, p * (jnp.log(jnp.maximum(p, 1e-30)) - logq), 0.0))
        grads.append(jnp.where(sb, jnp.exp(logq) - p, 0.0) / (b * t))
    return kl / (b * t), jnp.concatenate(grads, axis=1)


def _index_kl_bwd(scale, block_q, grad, g):
    return (g * grad, None, None, None, None)


index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)
