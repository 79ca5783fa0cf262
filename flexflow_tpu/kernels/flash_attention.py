"""Blockwise (flash) attention as a Pallas TPU kernel, with custom VJP.

The reference has no attention op (it predates transformers; its only
long-sequence mechanism is the NMT LSTM chunking, nmt/rnn.h:21-23).  On
TPU, attention is *the* hot op for long-context models, so the framework
provides a first-class fused kernel: online-softmax forward that never
materializes the (S, S) score matrix in HBM, and a recompute-based
backward.  The kernel also returns the per-row logsumexp, which is what
lets ring attention (parallel/sequence.py) merge partial results across
sequence shards.

Layout: (batch, heads, seq, head_dim) in and out; the value head may be
narrower or wider than the query/key head.  Three kernels, each a
``pallas_call`` named as its scope: ``flash_fwd`` (grid batch*heads x
q blocks x k blocks, k innermost), ``flash_dq`` (the same grid) and
``flash_dkv`` (batch*heads x k blocks x q blocks, q innermost), so each
accumulator lives in VMEM scratch across its inner sweep.

Dtypes.  Every matmul takes its operands in the dtype the caller gave
(bf16 operands for a bf16 model, f32 for an f32 one; ``p`` and ``ds``
are cast to the dtype of the operand they meet) and accumulates in f32.
Scores, running max and sum, ``exp``, ``lse``, ``delta`` and the
accumulators are f32.  (Mosaic feeds the MXU an f32 operand in one bf16
pass, so on the chip an f32 call's error is a bf16 call's: PERF.md
section 6, PR 26.)

Orientation.  All three kernels hold the scores transposed, keys down
the sublanes and queries along the lanes: ``(k q^T)``, (block_k,
block_q).  What there is one of per query (running max and sum, ``lse``,
``delta``) is then a lane-dense (1, block_q) row: a reduction over keys
is elementwise between vregs, a broadcast is a sublane broadcast, and no
kernel needs a (block_q, 1) column or a cross-lane reduce.  The forward
and ``dq`` accumulate transposed too, (d, block_q), and turn the result
once a q block.

Row statistics.  ``lse`` leaves the forward, and ``lse`` and ``delta``
enter the backward kernels, one f32 a row in HBM, shaped (batch*heads,
q blocks, 1, block_q) so that a kernel's block is whole in its two minor
dimensions whatever block_q is.

Causal.  A block wholly above the diagonal runs no body and its index
maps name a block already held, so it fetches nothing; a block wholly
under it skips the mask; a block on the diagonal of square blocks is cut
into bands of keys that leave out the queries before them (``_tiles``).
A scale that is a power of two goes on the query, where it is exact.

Window and selection.  With ``window`` a query sees its own key and the
``window - 1`` before it: a band under the diagonal.  The grids are
banded (``_grid``): the forward's and ``dq``'s inner axis walks the k
blocks a q block's band touches, ``dkv``'s the q blocks that see a k
block, and a step past a band the sequence clips runs no body and names
a block already held.  The blocks are square, so whether a block needs a
mask and which of its pairs are in the band is a function of its offset
from the diagonal alone: each offset runs its own branch over tiles of
whole cells that hold band pairs (``_band_tiles``, ``_cell``).  With
``select`` (batch, seq_k, seq_q), the pairs a query keeps as non-zero
entries, keys down the rows as the scores are held, every block under
the diagonal runs and masks by its block of
``select`` alone (the selection is causal already); a query with no kept
key in a block leaves rows that the next block with one wipes
(``alpha`` is 0), and its own key's block comes last.  Both variants
carry their own kernel names (``flash_win_*``, ``flash_sel_*``), so that
a trace tells the three cores apart.

Groups and a sink.  k and v may hold fewer heads than q (grouped-query
attention): query head ``j`` reads key/value head ``j // group``, through
the index maps alone, so no kernel is given a copy of k or v per query
head; ``dkv`` writes each query head's share in f32 and the shares of a
group are summed outside it.  A sink is a learned logit ``b_h`` a query
head that takes mass in every row's softmax and adds no value: the
forward starts a row's running max at ``b_h`` and its sum at 1, so
``lse`` holds it, and the backward kernels, which read ``lse``, need
nothing more; its gradient, ``-sum_t exp(b_h - lse_t) delta_t``, is a
reduction outside them.  A call with a sink carries ``_sink`` in its
forward kernel's name (``flash_win_sink_fwd``); its backward kernels are
the plain ones.

Blocks are a function of (seq_q, seq_k, head_dim) and, under a window,
of the window (``_block_sizes``, chosen by sweeps on the v5e), the bands
of the kernel; ``tiling()`` says what each kernel does with a shape.

The kernels compile through Mosaic and run on a TPU only.  Callers pick
the implementation by platform (ops/attention.py); ``interpret=True``
runs the same kernel bodies in the Pallas interpreter on any backend and
is something a test asks for by name, never a default.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
# The kernels' names under a window and under a selection: the same
# three bodies, told apart in a trace.
WINDOW_KERNELS = tuple(k.replace("flash_", "flash_win_") for k in KERNELS)
SELECT_KERNELS = tuple(k.replace("flash_", "flash_sel_") for k in KERNELS)


def kernel_names(window=None, selected=False, sunk=False):
    """The names of the forward, dq and dkv kernels of a call; a sink
    changes the forward's body alone, and its name."""
    names = SELECT_KERNELS if selected else (
        WINDOW_KERNELS if window is not None else KERNELS)
    if sunk:      # flash_win_fwd -> flash_win_sink_fwd
        names = ("_sink_".join(names[0].rsplit("_", 1)),) + names[1:]
    return names
# A sequence of up to this many rows can be one block whatever its
# length; a longer one is cut into divisors that are multiples of 8.
_WHOLE = 512
# Keys in a band of a diagonal block (``_tiles``), a multiple of the 128
# lanes because a band's first key is also its first query column.  The
# fewer matmuls a kernel has, the dearer a band's fixed cost against the
# masked work it leaves out (sweep on the v5e, PERF.md section 6, PR 26).
_DIAGONAL_BAND = {"flash_fwd": 512, "flash_dq": 256, "flash_dkv": 128}
# Rows and keys of a block under a window.  The band's tiles compute about
# the same pairs whatever the block, so a larger block saves grid steps,
# each of a fixed cost; but the tiles are unrolled, a branch an offset
# from the diagonal and a tile a cell of keys, and every start of a
# process traces and lowers them again, whether the compile cache then
# holds the program or not.  At a window of 128 a layer ran in 3.01 ms at
# 256 rows, 2.47 at 512 and 2.13 at 2048, against 5.12 with the causal
# grid, and 2048 made every start of that model some 6 s longer (sweep
# and starts on the v5e: PERF.md, the window cores).
_WINDOW_BLOCK = 256

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _max_block(head_dim: int) -> int:
    """Rows of the largest block.  One grid step a (batch, head) up to
    sequence 1024 beat every finer grid in all three kernels at head 64
    and 128 (PERF.md section 6, PR 26); a wider head halves it to keep
    the operand blocks and the (block_k, block_q) f32 tiles in VMEM."""
    return 1024 if head_dim <= 128 else 512


def _largest_block(seq: int, cap: int) -> Optional[int]:
    """The whole sequence when it has at most ``_WHOLE`` rows and fits
    under ``cap``; otherwise its largest divisor that is <= ``cap`` and
    a multiple of 8 rows; None when it has none."""
    if seq <= min(cap, _WHOLE):
        return seq
    for b in range(min(cap, seq) // 8 * 8, 0, -8):
        if seq % b == 0:
            return b
    return None


def unsupported_reason(seq_q: int, seq_k: int,
                       block_q: Optional[int] = None,
                       block_k: Optional[int] = None) -> Optional[str]:
    """Why the kernel cannot tile this shape, or None when it can.

    A block must divide its sequence (the grid has no remainder step).
    Mosaic takes a block equal to the whole dimension whatever its
    length, and otherwise wants whole 8-row sublane groups: on the v5e
    with libtpu 0.0.34 blocks of 100 (= the sequence), 40 and 200 rows
    compile and match the reference in f32 and bf16 (chip run, PR 21).
    That leaves out a sequence longer than 512 with no divisor that is
    a multiple of 8 (1009, 1018) — the caller's cue for the XLA path."""
    for name, seq, want in (("q", seq_q, block_q), ("k", seq_k, block_k)):
        if _largest_block(seq, want or seq) is None:
            return (f"flash_attention: {name} sequence length {seq} has no "
                    f"divisor{f' <= {want}' if want else ''} that is a "
                    f"multiple of 8 rows")
    return None


def _block_sizes(seq_q: int, seq_k: int, head_dim: int,
                 block_q: Optional[int] = None, block_k: Optional[int] = None,
                 window: Optional[int] = None) -> Tuple[int, int]:
    """(block_q, block_k) all three kernels tile the two sequences with:
    a function of the shape and the window (``block_q`` / ``block_k``
    are a test's or a sweep's cap; whether the call is causal did not
    move the best choice in any kernel, so it is not asked).  A window's
    call is causal self-attention and its blocks are square, under the
    smaller cap.  ValueError naming the sequence ``unsupported_reason``
    rejects."""
    why = unsupported_reason(seq_q, seq_k, block_q, block_k)
    if why is not None:
        raise ValueError(why)
    if window is not None:
        block = _largest_block(seq_q, min(block_q or _WINDOW_BLOCK,
                                          block_k or _WINDOW_BLOCK))
        return block, block
    cap = _max_block(head_dim)
    return (_largest_block(seq_q, block_q or cap),
            _largest_block(seq_k, block_k or cap))


def _max(a, b):
    """The larger of two Python ints, or of traced scalars."""
    return max(a, b) if isinstance(a, int) else jnp.maximum(a, b)


def _min(a, b):
    """The smaller of two Python ints, or of traced scalars."""
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


def _runs(qi, ki, block_q: int, block_k: int, window=None):
    """Causal: does block (qi, ki) hold a pair with k <= q (and, under a
    window, with k > q - window)?  Python ints or traced scalars."""
    runs = qi * block_q + block_q - 1 >= ki * block_k
    if window is not None:
        runs = runs & (ki * block_k + block_k - 1 > qi * block_q - window)
    return runs


def _on_diagonal(qi, ki, block_q: int, block_k: int):
    """Causal: does block (qi, ki) hold a pair with k > q, so that it
    needs the mask?  A block that does not, runs."""
    return ki * block_k + block_k - 1 > qi * block_q


def tiling(seq_q: int, seq_k: int, head_dim: int, causal: bool,
           block_q: Optional[int] = None, block_k: Optional[int] = None,
           window: Optional[int] = None, selected: bool = False
           ) -> Dict[str, Dict[str, int]]:
    """What each kernel does with one (batch, head) of this shape: its
    blocks, the steps its grid has and those of them that run a body
    (the rest lie wholly above the causal diagonal, or past a band the
    sequence clips, and fetch nothing), and the bands a block on the
    diagonal is cut into (``_tiles``; one under a selection, whose blocks
    are masked whole; under a window the band's tiles of the diagonal
    block, ``_band_tiles``).  Under a window also the pairs the running
    tiles compute and the band's own pairs.  A group or a sink moves none
    of it (a sink's kernels are named as ``kernel_names`` says)."""
    bq, bk = _block_sizes(seq_q, seq_k, head_dim, block_q, block_k, window)
    nq, nk = seq_q // bq, seq_k // bk
    runs = [(qi, ki) for qi in range(nq) for ki in range(nk)
            if not causal or _runs(qi, ki, bq, bk, window)]
    inner_k, inner_q = _grid(seq_q, seq_k, bq, bk, window)
    plans = {}
    for kernel, name in zip(KERNELS, kernel_names(window, selected)):
        if window is not None:
            bands = len(_band_tiles(0, 0, 0, bk, window))
        else:
            bands = 1 if selected else len(_tiles(kernel, causal, 0, 0, bq,
                                                  bk))
        plans[name] = dict(
            block_q=bq, block_k=bk,
            grid_steps=nk * inner_q if kernel == "flash_dkv" else nq * inner_k,
            body_steps=len(runs), diagonal_bands=bands)
        if window is not None:
            plans[name].update(
                pairs_computed=sum(
                    (ks.stop - ks.start) * (qs.stop - qs.start)
                    for qi, ki in runs
                    for ks, qs, _ in _band_tiles(qi, ki, ki - qi, bk, window)),
                band_pairs=sum(min(q + 1, window) for q in range(seq_q)))
    return plans


def _folds(scale: float) -> bool:
    """A power of two scales a bf16 or f32 query exactly, so it goes on
    the (block_q, d) query and not on the (block_k, block_q) scores."""
    return math.frexp(scale)[0] == 0.5


def _scores_t(q, k, scale: float):
    """(k q^T) * scale, (bk, bq) in f32: the scores transposed, keys
    down the sublanes and queries along the lanes, so that what there is
    one of a query (max, sum, lse, delta) is a lane-dense (1, bq) row."""
    if _folds(scale):
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    st = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
    return st if _folds(scale) else st * scale


def _dscores_t(q, k, v, do, lse, delta, scale, mask_at, window=None,
               keep=None):
    """p^T and (p * (dp - delta))^T of one block, (bk, bq) in f32; the
    scale of ds is left to the caller's accumulator."""
    st = _causal_mask(_scores_t(q, k, scale), mask_at, window)
    if keep is not None:
        st = jnp.where(keep, st, NEG_INF)
    pt = jnp.exp(st - lse)
    dpt = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    return pt, pt * (dpt - delta)


def _causal_mask(st, mask_at, window=None):
    """Transposed scores with every pair k > q (and, under a window,
    k <= q - window) at NEG_INF; ``mask_at`` is the (q, k) position of
    the tile's first pair, or None for a tile that needs no mask."""
    if mask_at is None:
        return st
    qs = mask_at[0] + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    ks = mask_at[1] + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
    keep = qs >= ks
    if window is not None:
        keep = keep & (ks > qs - window)
    return jnp.where(keep, st, NEG_INF)


def _kept(sel_ref, ks, qs):
    """The pairs of a tile its block of ``select`` keeps, (keys,
    queries) bool; compared in f32 (the v5e compares no bf16)."""
    return sel_ref[ks, qs].astype(jnp.float32) > 0.0


def _tiles(kernel: str, masked: bool, qi, ki, block_q: int, block_k: int):
    """[(k rows, q columns, mask_at)] that cover what block (qi, ki) has
    to compute: two slices of the block and what ``_causal_mask`` takes.
    A masked block of square blocks starts on the diagonal (qi == ki),
    so which of its pairs are masked is known here: it is cut into bands
    of ``_DIAGONAL_BAND`` keys, and a band leaves out the queries before
    its first key."""
    if not masked:
        return [(slice(0, block_k), slice(0, block_q), None)]
    band = _DIAGONAL_BAND[kernel]
    if block_q != block_k or block_k % band:
        band = block_k                      # one band: the whole block
    return [(slice(lo, lo + band), slice(lo, block_q),
             (qi * block_q + lo, ki * block_k + lo))
            for lo in range(0, block_k, band)]


def _cell(block: int, window: int) -> int:
    """Keys and query columns of a cell of a window's block: the widest
    128 lanes times a power of two that divides the block and is no wider
    than the window (128 at least), so that a tile computes at most about
    twice the band's pairs and a wide band takes few tiles; a block not
    of whole 128s is one cell."""
    if block % 128:
        return block
    cell = 128
    while 2 * cell <= window and block % (2 * cell) == 0:
        cell *= 2
    return cell


def _band_tiles(qi, ki, offset: int, block: int, window: int):
    """Window: the tiles (as ``_tiles``) that cover the band's pairs in
    block (qi, ki) = (qi, qi + offset).  Which pairs those are depends on
    the offset alone: a pair of local query x and key y is in the band
    where ``offset * block <= x - y < offset * block + window``.  The block
    is cut into bands of ``_cell`` keys, each over the query columns, in
    whole cells, that hold its band pairs; a tile wholly inside the band
    needs no mask."""
    cell = _cell(block, window)
    lo, hi = offset * block, offset * block + window - 1
    tiles = []
    for y in range(0, block, cell):
        first = max(0, y + lo) // cell * cell
        end = min(block, -(-(y + cell + hi) // cell) * cell)
        if first >= end:
            continue
        inside = first - (y + cell - 1) >= lo and end - 1 - y <= hi
        tiles.append((slice(y, y + cell), slice(first, end), None if inside
                      else (qi * block + first, ki * block + y)))
    return tiles


def _when_causal(kernel: str, causal: bool, qi, ki, block_q: int,
                 block_k: int, body, window=None, selected: bool = False):
    """Run ``body(tiles)`` as block (qi, ki) needs it: not at all above
    the diagonal, masked on it, plain below it or without ``causal``.  A
    selection masks every block itself, so its blocks run plain.  Under a
    window a block runs the band's tiles (``_band_tiles``), one branch an
    offset from the diagonal, and none outside the band."""
    if not causal:
        body(_tiles(kernel, False, qi, ki, block_q, block_k))
        return
    if selected:
        pl.when(_runs(qi, ki, block_q, block_k))(
            lambda: body(_tiles(kernel, False, qi, ki, block_q, block_k)))
        return
    if window is not None:
        # the offsets ki - qi of the blocks that hold band pairs, from the
        # band's lower edge to the diagonal
        for offset in range(-((window + block_k - 2) // block_k), 1):
            pl.when(ki - qi == offset)(lambda offset=offset: body(
                _band_tiles(qi, ki, offset, block_k, window)))
        return
    diag = _on_diagonal(qi, ki, block_q, block_k)
    pl.when(jnp.logical_and(diag, _runs(qi, ki, block_q, block_k)))(
        lambda: body(_tiles(kernel, True, qi, ki, block_q, block_k)))
    pl.when(jnp.logical_not(diag))(
        lambda: body(_tiles(kernel, False, qi, ki, block_q, block_k)))


def _last_k(qi, block_q: int, block_k: int):
    """The last k block a causal q block visits."""
    return (qi * block_q + block_q - 1) // block_k


def _first_q(ki, block_q: int, block_k: int):
    """The first q block a causal k block visits."""
    return (ki * block_k) // block_q


def _band_first_k(qi, block_q: int, block_k: int, window: int):
    """Window: the first k block with a key of q block ``qi``'s band."""
    return _max(qi * block_q - window + 1, 0) // block_k


def _band_last_q(ki, block_q: int, block_k: int, window: int, nq):
    """Window: the last q block with a query that sees a key of k block
    ``ki``."""
    return _min((ki * block_k + block_k + window - 2) // block_q, nq - 1)


def _grid(seq_q: int, seq_k: int, block_q: int, block_k: int, window=None
          ) -> Tuple[int, int]:
    """The grids' inner axes: the k blocks a q block visits (forward,
    dq) and the q blocks a k block visits (dkv).  Every block; under a
    window the band's: the most k blocks a q block's band touches and the
    most q blocks that see a k block (a block whose band the sequence
    clips has fewer, and runs no body on the steps it has over)."""
    nq, nk = seq_q // block_q, seq_k // block_k
    if window is None:
        return nk, nq
    return (max(_last_k(qi, block_q, block_k)
                - _band_first_k(qi, block_q, block_k, window) + 1
                for qi in range(nq)),
            max(_band_last_q(ki, block_q, block_k, window, nq)
                - _first_q(ki, block_q, block_k) + 1 for ki in range(nk)))


def _k_of(causal: bool, block_q: int, block_k: int, window=None):
    """The k block a step (qi, ki) of a (batch*heads, q, k) grid names: a
    step above the causal diagonal names a block that is held or will be.
    Under a window the grid is banded: step ``ki`` names the band's
    ``ki``-th block, and a step past the band its last, which is held."""
    def k_of(qi, ki):
        if window is not None:
            return _min(_band_first_k(qi, block_q, block_k, window) + ki,
                        _last_k(qi, block_q, block_k))
        if causal:
            ki = jnp.minimum(ki, _last_k(qi, block_q, block_k))
        return ki
    return k_of


def _q_of(causal: bool, block_q: int, block_k: int, nq: int, window=None):
    """The q block a step (ki, qi) of a (batch*heads, k, q) grid names: a
    skipped step names the first block that runs, or the last.  Under a
    window the grid is banded and walks back from the last q block that
    sees block ``ki``: a step past the first names the first, which is
    held."""
    def q_of(ki, qi):
        if window is not None:
            return _max(_band_last_q(ki, block_q, block_k, window, nq) - qi,
                        _first_q(ki, block_q, block_k))
        if causal:
            qi = jnp.minimum(jnp.maximum(qi, _first_q(ki, block_q, block_k)),
                             nq - 1)
        return qi
    return q_of


def _kv_map(causal: bool, block_q: int, block_k: int, window=None,
            group: int = 1):
    """Index map of a k or v block on a (batch*heads, q, k) grid: query
    head row ``bh`` reads key/value row ``bh // group``."""
    k_of = _k_of(causal, block_q, block_k, window)
    if group > 1:
        return lambda bh_, qi, ki: (bh_ // group, k_of(qi, ki), 0)
    return lambda bh_, qi, ki: (bh_, k_of(qi, ki), 0)


def _stats_rows(x, block_q: int):
    """(bh, seq_q) row statistics as (bh, q blocks, 1, block_q): one
    value a row in HBM, and a kernel's block is whole in its two minor
    dimensions whatever block_q is."""
    return x.reshape(x.shape[0], -1, 1, block_q)


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# Each call below is jitted on its own: a model's step calls a kernel once
# a layer, and without it every call site traces the kernel body and
# lowers it to Mosaic again (5 s of a 24-layer step's lowering against
# 1 s, PERF.md section 6, PR 26).  The scopes are the call sites', so
# that a cached trace holds no name.
_STATIC = dict(static_argnames=("scale", "causal", "bq", "bk", "interpret",
                                "window", "group"))

# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, block_q, block_k, window=None,
                selected=False, sunk=False):
    q_ref, k_ref, v_ref, *refs = refs
    sel_ref = refs.pop(0) if selected else None
    sink_ref = refs.pop(0) if sunk else None
    o_ref, lse_ref, acc_sc, m_sc, l_sc = refs
    qi = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    ki = step if window is None else _band_first_k(
        qi, block_q, block_k, window) + step

    @pl.when(step == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        if sunk:      # the sink's exp(b - max) is 1 at a max of b
            m_sc[:] = sink_ref[...]
            l_sc[:] = jnp.ones_like(l_sc)
        else:
            m_sc[:] = jnp.full_like(m_sc, NEG_INF)
            l_sc[:] = jnp.zeros_like(l_sc)

    def _body(tiles):
        for ks, qs, mask_at in tiles:
            st = _causal_mask(                       # (keys, queries)
                _scores_t(q_ref[0, qs, :], k_ref[0, ks, :], scale), mask_at,
                window)
            if selected:
                st = jnp.where(_kept(sel_ref, ks, qs), st, NEG_INF)
            v = v_ref[0, ks, :]
            m_prev = m_sc[:, qs]                     # (1, queries)
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[:, qs] = alpha * l_sc[:, qs] + jnp.sum(pt, axis=0,
                                                        keepdims=True)
            # acc^T (d, queries) += v^T p^T: the small operand is turned
            acc_sc[:, qs] = acc_sc[:, qs] * alpha + jax.lax.dot_general(
                v, pt.astype(v.dtype), _TN,
                preferred_element_type=jnp.float32)
            m_sc[:, qs] = m_new

    _when_causal("flash_fwd", causal, qi, ki, block_q, block_k, _body, window,
                 selected)

    @pl.when(step == steps - 1)
    def _finish():
        l = l_sc[...]
        empty = l == 0.0
        l_safe = jnp.where(empty, 1.0, l)
        o_ref[0] = (acc_sc[...] / l_safe).T.astype(o_ref.dtype)
        lse_ref[...] = jnp.where(empty, NEG_INF, m_sc[...] + jnp.log(l_safe))


def _sel_spec(sel, bh: int, bq: int, bk: int, q_of, k_of):
    """Block (bk, bq) of ``select`` (batch, seq_k, seq_q) for a step whose
    grid indices after batch*heads are (a, b): the heads of a batch read
    the same block."""
    heads = bh // sel.shape[0]
    return pl.BlockSpec((None, bk, bq), lambda bh_, a, b: (
        bh_ // heads, k_of(a, b), q_of(a, b)))


@functools.partial(jax.jit, **_STATIC)
def _fwd_call(qr, kr, vr, scale, causal, bq, bk, interpret, window=None,
              sel=None, group=1, sink=None):
    """o (bh, sq, dv) and lse (bh, sq) of q (bh, sq, d), k (bh / group,
    sk, d) and v (bh / group, sk, dv) operands; ``sink`` (bh, 1, bq), a
    row's sink logit along the lanes."""
    bh, sq, d = qr.shape
    sk, dv = vr.shape[1:]
    k_of = _k_of(causal, bq, bk, window)
    cols = lambda width: pl.BlockSpec((1, bk, width),
                                      _kv_map(causal, bq, bk, window, group))
    extra = [] if sel is None else [_sel_spec(
        sel, bh, bq, bk, lambda qi, ki: qi, k_of)]
    if sink is not None:
        extra.append(pl.BlockSpec((None, 1, bq),
                                  lambda bh_, qi, ki: (bh_, 0, 0)))

    call = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, window=window,
                          selected=sel is not None, sunk=sink is not None),
        grid=(bh, sq // bq, _grid(sq, sk, bq, bk, window)[0]),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh_, qi, ki: (bh_, qi, 0)),
            cols(d), cols(dv),
        ] + extra,
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((None, None, 1, bq),
                         lambda bh_, qi, ki: (bh_, qi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), qr.dtype),
            jax.ShapeDtypeStruct((bh, sq // bq, 1, bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dv, bq), jnp.float32),
            pltpu.VMEM((1, bq), jnp.float32),
            pltpu.VMEM((1, bq), jnp.float32),
        ],
        interpret=interpret,
        name=kernel_names(window, sel is not None, sink is not None)[0],
        compiler_params=_SEMANTICS,
    )
    out, lse = call(qr, kr, vr, *[x for x in (sel, sink) if x is not None])
    return out, lse.reshape(bh, sq)


def _variant(window, sel, group=1, sink=None):
    """What a jitted call takes beside the plain causal call's arguments:
    nothing for that call, so that its trace is the one it was; a group
    or a sink only where there is one."""
    more = {} if window is None and sel is None else dict(window=window,
                                                          sel=sel)
    if group > 1:
        more["group"] = group
    if sink is not None:
        more["sink"] = sink
    return more


def _flash_forward(q, k, v, sel, sink, scale, causal, block_q, block_k,
                   interpret, window):
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    sk, dv = v.shape[2:]
    bq, bk = _block_sizes(sq, sk, d, block_q, block_k, window)
    if sink is not None:
        sink = jnp.broadcast_to(sink[None, :, None, None],
                                (b, h, 1, bq)).reshape(b * h, 1, bq)
    more = _variant(window, sel, h // hkv, sink)
    with jax.named_scope("ff.kernel." + kernel_names(
            window, sel is not None, sink is not None)[0]):
        out, lse = _fwd_call(q.reshape(b * h, sq, d),
                             k.reshape(b * hkv, sk, d),
                             v.reshape(b * hkv, sk, dv), scale, causal, bq,
                             bk, interpret, **more)
    return out.reshape(b, h, sq, dv), lse.reshape(b, h, sq)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dkdv_kernel(*refs, scale, causal, block_q, block_k, window=None,
                     selected=False):
    if selected:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sel_ref,
         dk_ref, dv_ref, dk_sc, dv_sc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_sc, dv_sc) = refs
    ki = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    # a window's blocks are square: as many q blocks as k blocks
    qi = step if window is None else _band_last_q(
        ki, block_q, block_k, window, pl.num_programs(1)) - step

    @pl.when(step == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _body(tiles):
        for ks, qs, mask_at in tiles:
            q, do = q_ref[0, qs, :], do_ref[0, qs, :]
            pt, dst = _dscores_t(
                q, k_ref[0, ks, :], v_ref[0, ks, :], do, lse_ref[:, qs],
                delta_ref[:, qs], scale, mask_at, window,
                _kept(sel_ref, ks, qs) if selected else None)
            dv_sc[ks, :] += jax.lax.dot_general(
                pt.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)
            dk_sc[ks, :] += jax.lax.dot_general(
                dst.astype(q.dtype), q, _NN,
                preferred_element_type=jnp.float32)

    _when_causal("flash_dkv", causal, qi, ki, block_q, block_k, _body, window,
                 selected)

    @pl.when(step == steps - 1)
    def _finish():
        dk_ref[0] = (dk_sc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, window=None,
                   selected=False):
    if selected:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sel_ref,
         dq_ref, dq_sc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_sc) = refs
    qi = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    ki = step if window is None else _band_first_k(
        qi, block_q, block_k, window) + step

    @pl.when(step == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def _body(tiles):
        for ks, qs, mask_at in tiles:
            k = k_ref[0, ks, :]
            dst = _dscores_t(
                q_ref[0, qs, :], k, v_ref[0, ks, :], do_ref[0, qs, :],
                lse_ref[:, qs], delta_ref[:, qs], scale, mask_at, window,
                _kept(sel_ref, ks, qs) if selected else None)[1]
            # dq^T (d, queries) += k^T ds^T
            dq_sc[:, qs] += jax.lax.dot_general(
                k, dst.astype(k.dtype), _TN,
                preferred_element_type=jnp.float32)

    _when_causal("flash_dq", causal, qi, ki, block_q, block_k, _body, window,
                 selected)

    @pl.when(step == steps - 1)
    def _finish():
        dq_ref[0] = (dq_sc[...] * scale).T.astype(dq_ref.dtype)


@functools.partial(jax.jit, **_STATIC)
def _dkv_call(qr, kr, vr, dor, lse, delta, scale, causal, bq, bk, interpret,
              window=None, sel=None, group=1):
    """dk (bh, sk, d) and dv (bh, sk, dv), each query head's share (in
    f32 where a group of them shares a key/value row, so that the caller
    sums them before rounding); k and v are (bh / group, sk, .); ``lse``
    and ``delta`` are (bh, sq)."""
    bh, sq, d = qr.shape
    sk, dv = vr.shape[1:]
    nq = sq // bq
    q_of = _q_of(causal, bq, bk, nq, window)

    def rows(width):
        return pl.BlockSpec((1, bq, width),
                            lambda bh_, ki, qi: (bh_, q_of(ki, qi), 0))

    def cols(width):
        return pl.BlockSpec((1, bk, width), lambda bh_, ki, qi: (bh_, ki, 0))

    def kv_cols(width):
        if group == 1:
            return cols(width)
        return pl.BlockSpec((1, bk, width),
                            lambda bh_, ki, qi: (bh_ // group, ki, 0))

    stat = pl.BlockSpec((None, None, 1, bq),
                        lambda bh_, ki, qi: (bh_, q_of(ki, qi), 0, 0))
    extra = [] if sel is None else [_sel_spec(
        sel, bh, bq, bk, q_of, lambda ki, qi: ki)]
    call = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, window=window,
                          selected=sel is not None),
        grid=(bh, sk // bk, _grid(sq, sk, bq, bk, window)[1]),
        in_specs=[rows(d), kv_cols(d), kv_cols(dv), rows(dv), stat,
                  stat] + extra,
        out_specs=[cols(d), cols(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d),
                                 kr.dtype if group == 1 else jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, dv),
                                 vr.dtype if group == 1 else jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        interpret=interpret,
        name=kernel_names(window, sel is not None)[2],
        compiler_params=_SEMANTICS,
    )
    return call(qr, kr, vr, dor, _stats_rows(lse, bq), _stats_rows(delta, bq),
                *([] if sel is None else [sel]))


@functools.partial(jax.jit, **_STATIC)
def _dq_call(qr, kr, vr, dor, lse, delta, scale, causal, bq, bk, interpret,
             window=None, sel=None, group=1):
    """dq (bh, sq, d); k and v are (bh / group, sk, .); ``lse`` and
    ``delta`` are (bh, sq)."""
    bh, sq, d = qr.shape
    sk, dv = vr.shape[1:]
    k_of = _k_of(causal, bq, bk, window)

    def rows(width):
        return pl.BlockSpec((1, bq, width), lambda bh_, qi, ki: (bh_, qi, 0))

    def cols(width):
        return pl.BlockSpec((1, bk, width),
                            _kv_map(causal, bq, bk, window, group))

    stat = pl.BlockSpec((None, None, 1, bq),
                        lambda bh_, qi, ki: (bh_, qi, 0, 0))
    extra = [] if sel is None else [_sel_spec(
        sel, bh, bq, bk, lambda qi, ki: qi, k_of)]
    call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, window=window,
                          selected=sel is not None),
        grid=(bh, sq // bq, _grid(sq, sk, bq, bk, window)[0]),
        in_specs=[rows(d), cols(d), cols(dv), rows(dv), stat, stat] + extra,
        out_specs=rows(d),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), qr.dtype),
        scratch_shapes=[pltpu.VMEM((d, bq), jnp.float32)],
        interpret=interpret,
        name=kernel_names(window, sel is not None)[1],
        compiler_params=_SEMANTICS,
    )
    return call(qr, kr, vr, dor, _stats_rows(lse, bq), _stats_rows(delta, bq),
                *([] if sel is None else [sel]))


def _flash_backward(scale, causal, block_q, block_k, interpret, window, res,
                    grads):
    q, k, v, sel, sink, out, lse = res
    do, _ = grads
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    sk, dv = v.shape[2:]
    bq, bk = _block_sizes(sq, sk, d, block_q, block_k, window)
    bh = b * h

    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    args = (q.reshape(bh, sq, d), k.reshape(b * hkv, sk, d),
            v.reshape(b * hkv, sk, dv), do.reshape(bh, sq, dv),
            lse.reshape(bh, sq), delta.reshape(bh, sq),
            scale, causal, bq, bk, interpret)
    more = _variant(window, sel, h // hkv)
    _, dq_name, dkv_name = kernel_names(window, sel is not None)
    with jax.named_scope("ff.kernel." + dkv_name):
        dk, dv_ = _dkv_call(*args, **more)
    with jax.named_scope("ff.kernel." + dq_name):
        dq = _dq_call(*args, **more)
    if h > hkv:       # each query head's share, summed over its group
        dk, dv_ = (x.reshape(b, hkv, h // hkv, sk, -1).sum(2).astype(y.dtype)
                   for x, y in ((dk, k), (dv_, v)))
    dsink = None
    if sink is not None:
        dsink = -jnp.sum(jnp.exp(sink[None, :, None] - lse) * delta,
                         axis=(0, 2))
    return (dq.reshape(b, h, sq, d),
            dk.reshape(b, hkv, sk, d),
            dv_.reshape(b, hkv, sk, dv), None, dsink)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, sel, sink, scale, causal, block_q, block_k, interpret,
           window):
    return _flash_forward(q, k, v, sel, sink, scale, causal, block_q,
                          block_k, interpret, window)


def _flash_fwd(q, k, v, sel, sink, scale, causal, block_q, block_k,
               interpret, window):
    out, lse = _flash_forward(q, k, v, sel, sink, scale, causal, block_q,
                              block_k, interpret, window)
    return (out, lse), (q, k, v, sel, sink, out, lse)


_flash.defvjp(_flash_fwd, _flash_backward)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    return_lse: bool = False,
                    interpret: bool = False,
                    window: Optional[int] = None,
                    select=None, sink=None):
    """Fused attention: softmax(q k^T * scale [+ causal mask]) v.

    q is (B, H, S, D); k is (B, Hkv, S, D) and v (B, Hkv, S, Dv), where
    Hkv divides H and query head ``j`` reads key/value head ``j // (H /
    Hkv)`` (grouped-query attention; Hkv = H is plain multi-head); the
    output has v's width, which need not be D (192 | 128).  ``sink``
    (H,), a learned logit a query head, takes mass in each row's softmax
    and adds no value: ``p_tj = exp(s_tj) / (exp(sink) + sum_j'
    exp(s_tj'))``; it takes a gradient, and ``lse`` then holds it.  ``scale``
    defaults to 1/sqrt(D).  Returns the output, plus the per-row
    logsumexp (B, H, S) when ``return_lse`` — ring attention uses the
    lse to merge shard-local partials.  Raises ValueError for a sequence
    length the kernel cannot tile (``unsupported_reason``).
    ``interpret=True`` runs the kernel in the Pallas interpreter (any
    backend, for tests); the default compiles it for the TPU.

    Causal self-attention only: ``window`` keeps, of the keys a query
    may see, its own and the ``window - 1`` before it; ``select``
    (B, S, S), keys down the rows and queries along the columns, keeps
    the pairs that are non-zero (of any float dtype; it must be causal
    itself and keep at least one key a query, and takes no gradient).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if (window is not None or select is not None) and not (
            causal and q.shape[2] == k.shape[2]):
        raise ValueError("flash_attention: a window or a selection is for "
                         "causal self-attention")
    if window is not None and select is not None:
        raise ValueError("flash_attention: a window or a selection, not both")
    if k.shape[1] != v.shape[1] or q.shape[1] % k.shape[1]:
        raise ValueError(f"flash_attention: {q.shape[1]} query heads over "
                         f"{k.shape[1]} key and {v.shape[1]} value heads")
    if sink is not None:
        sink = sink.astype(jnp.float32)
    out, lse = _flash(q, k, v, select, sink, scale, causal, block_q, block_k,
                      interpret, window)
    if return_lse:
        return out, lse
    return out


def mha_reference(q, k, v, *, causal: bool = False, scale: Optional[float] = None):
    """Unfused reference attention (numerics oracle for tests)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
