"""Multi-head attention + LayerNorm ops — the long-context path.

The reference predates transformers and has no attention op (SURVEY §5.7);
its SOAP abstraction (partition any output dim, include/config.h:42-51) is
what these ops extend to the sequence dim.  A MultiHeadAttention output is
(B, S, E); a ParallelConfig of (dp, sp, 1) lowers to:

  * sp == 1: one attention call per chip, GSPMD handling dp like any
    other op;
  * sp > 1: ring attention over the mesh axes assigned to the sequence
    dim (parallel/sequence.py) — K/V rotate over ICI via ppermute and
    per-chip memory stays O(S/sp · S/sp) instead of O(S²).

Either way the per-chip attention is the Pallas flash kernel
(kernels/flash_attention.py) on a TPU when the kernel can tile the
shape, and XLA's ``blockwise_attention`` otherwise; the op records which
ran and why in ``impl_used`` (``MultiHeadAttention._pick_impl``).

The decoders' attention ops (``LatentAttention``, ``GroupedQueryAttention``)
take their causal core, plain, banded, selected or with a sink, from one
function, ``attention_core``.
"""

from __future__ import annotations

import math
import warnings
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .base import FwdCtx, Op
from ..initializers import ConstantInitializer, DefaultWeightInitializer, ZeroInitializer


class LayerNorm(Op):
    """Normalize over the last dim with learned scale/shift."""

    _type = "LayerNorm"

    def __init__(self, model, input_tensor, eps: float = 1e-5,
                 elementwise_affine: bool = True, name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        self.eps = eps
        self.affine = elementwise_affine
        dims = input_tensor.dims
        self._add_output(dims, input_tensor.dtype)
        if elementwise_affine:
            feat_cfg_dim = len(dims) - 1
            self._add_weight("scale", (dims[-1],), ConstantInitializer(1.0),
                             partition_dims=(feat_cfg_dim,))
            self._add_weight("bias", (dims[-1],), ZeroInitializer(),
                             partition_dims=(feat_cfg_dim,))

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        x = xs[0]
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        if self.affine:
            y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        return [y.astype(x.dtype)]

    def flops_per_sample(self):
        import numpy as np
        return 8.0 * float(np.prod(self.output.dims[1:]))


class _PicksAttentionImpl:
    """How an attention op chooses its per-chip core (the Pallas flash
    kernel or XLA's ``blockwise_attention``) and records the choice."""

    # None: chosen by platform and shape at trace time (_pick_impl).
    # A test sets "pallas_interpret" (the kernel in the Pallas
    # interpreter, any backend), "pallas" or "xla" by name.
    impl: Optional[str] = None
    # (impl, why) of the last trace — what actually ran.
    impl_used: Optional[Tuple[str, str]] = None

    def _pick_impl(self, seq_q: int, seq_k: int) -> Tuple[str, str]:
        """(impl, why) for one chip's (seq_q, seq_k) attention block:
        the Pallas kernel on a TPU when it can tile the shape, else the
        XLA path — and on a TPU that is worth a warning, because the
        shape is then running without the kernel written for it."""
        if self.impl is not None:
            if self.impl not in ("pallas", "pallas_interpret", "xla"):
                raise ValueError(f"{self.name}: unknown attention impl "
                                 f"{self.impl!r}")
            return self.impl, "set on the op"
        from ..kernels.flash_attention import unsupported_reason

        platform = self.model.machine.devices[0].platform
        if platform != "tpu":
            return "xla", f"platform is {platform}"
        why = unsupported_reason(seq_q, seq_k)
        if why is not None:
            warnings.warn(f"{self.name}: {why}; using XLA attention")
            return "xla", why
        return "pallas", "platform is tpu"


class MultiHeadAttention(_PicksAttentionImpl, Op):
    """Scaled-dot-product multi-head attention with QKV/output projections.

    query/key/value: (B, Sq, E) / (B, Sk, E) / (B, Sk, E).  Output
    (B, Sq, E).  ``causal`` adds the autoregressive mask (requires
    Sq == Sk).  Sequence parallelism kicks in when the op's
    ParallelConfig splits dim 1 — see module docstring.
    """

    _type = "MultiHeadAttention"

    def __init__(self, model, query, key, value, embed_dim: int,
                 num_heads: int, causal: bool = False,
                 dropout: float = 0.0, use_bias: bool = False,
                 kernel_initializer=None, seq_parallel_mode: str = "ring",
                 name: Optional[str] = None):
        super().__init__(model, [query, key, value], name)
        assert embed_dim % num_heads == 0, "embed_dim must divide by num_heads"
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.causal = causal
        self.dropout = dropout
        self.use_bias = use_bias
        self.seq_parallel_mode = seq_parallel_mode
        b, sq, _ = query.dims
        self._add_output((b, sq, embed_dim), query.dtype)
        init = kernel_initializer or DefaultWeightInitializer()
        for wname, in_dim in (("wq", query.dims[-1]), ("wk", key.dims[-1]),
                              ("wv", value.dims[-1])):
            self._add_weight(wname, (in_dim, embed_dim), init,
                             partition_dims=(None, 2))
        self._add_weight("wo", (embed_dim, embed_dim), init,
                         partition_dims=(None, 2))
        if use_bias:
            for bname in ("bq", "bk", "bv", "bo"):
                self._add_weight(bname, (embed_dim,), ZeroInitializer(),
                                 partition_dims=(2,))

    # -- helpers -----------------------------------------------------------
    def _proj(self, params, x, w, b):
        acc = jnp.float32 if x.dtype == jnp.bfloat16 else None
        y = jnp.dot(x, params[w].astype(x.dtype), preferred_element_type=acc)
        y = y.astype(x.dtype)
        if self.use_bias:
            y = y + params[b].astype(y.dtype)
        return y

    def _config_dim_bound(self, i: int):
        """The feature split (dim 2) is head-parallel tensor parallelism:
        the degree must divide num_heads so each shard holds whole
        heads (the reshape to (B, S, H, D) then stays aligned)."""
        if i == 2:
            return self.num_heads
        return super()._config_dim_bound(i)

    def _seq_degree(self) -> int:
        pc = getattr(self, "pc", None)
        if pc is None or len(pc.dims) < 2:
            return 1
        return pc.dims[1]

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        q_in, k_in, v_in = xs
        B, Sq, _ = q_in.shape
        H, D = self.num_heads, self.head_dim

        q = self._proj(params, q_in, "wq", "bq")
        k = self._proj(params, k_in, "wk", "bk")
        v = self._proj(params, v_in, "wv", "bv")
        # (B, S, E) -> (B, H, S, D)
        split = lambda t: t.reshape(t.shape[0], t.shape[1], H, D).transpose(0, 2, 1, 3)
        qh, kh, vh = split(q), split(k), split(v)
        scale = 1.0 / math.sqrt(D)

        sp = self._seq_degree()
        machine = self.model.machine
        Sk = k_in.shape[1]
        seq_par = sp > 1 and machine.num_devices > 1 and Sq == Sk
        if seq_par and self.seq_parallel_mode == "ring":
            impl, why = self._pick_impl(Sq // sp, Sk // sp)
        else:  # ulysses re-shards to whole sequences per chip
            impl, why = self._pick_impl(Sq, Sk)
        self.impl_used = (impl, why)
        use_flash = impl != "xla"
        interpret = impl == "pallas_interpret"
        if seq_par:
            from ..parallel.sequence import sequence_parallel_attention
            degrees = list(self.pc.dims) + [1] * (3 - len(self.pc.dims))
            groups = machine.axes_for_degrees(degrees[:3])
            batch_axes = groups[0] if groups[0] else None
            seq_axes = groups[1]
            oh = sequence_parallel_attention(
                qh, kh, vh, machine.mesh, seq_axes, batch_axes=batch_axes,
                causal=self.causal, scale=scale, mode=self.seq_parallel_mode,
                use_flash=use_flash, interpret=interpret)
        elif use_flash:
            from ..kernels.flash_attention import flash_attention
            oh = flash_attention(qh, kh, vh, causal=self.causal, scale=scale,
                                 interpret=interpret)
        else:
            from ..parallel.sequence import blockwise_attention
            oh, _ = blockwise_attention(qh, kh, vh, causal=self.causal,
                                        scale=scale)
        out = oh.transpose(0, 2, 1, 3).reshape(B, Sq, self.embed_dim)
        if self.dropout > 0.0 and ctx.training:
            keep = 1.0 - self.dropout
            mask = jax.random.bernoulli(ctx.op_rng(self), keep, out.shape)
            out = jnp.where(mask, out / keep, 0.0).astype(out.dtype)
        return [self._proj(params, out, "wo", "bo")]

    def init_cache(self, batch_size: int, max_len: int, dtype):
        shp = (batch_size, self.num_heads, max_len, self.head_dim)
        return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}

    def decode(self, params, xs, cache, pos, ctx):
        """kv-cached single-token attention: append this step's k/v at
        ``pos``, attend q over the cache prefix (static shapes — the
        future positions are masked, not sliced).  Full-sequence or
        non-causal calls (an encoder re-run per step, or cross-attention
        with a single-token q over full-sequence k/v) are stateless —
        fall back to forward."""
        from jax import lax

        q_in, k_in, v_in = xs
        if q_in.shape[1] != 1 or k_in.shape[1] != 1:
            # full-sequence pass (an encoder re-run, or cross-attention
            # q over full k/v) — stateless, forward is correct
            return self.forward(params, xs, ctx), cache
        if not self.causal:
            # a 1-token non-causal self-attention step would silently
            # attend only itself; no valid cache semantics exist for it
            raise ValueError(
                f"generate: op {self.name!r} is non-causal single-token "
                f"self-attention — not decodable")
        B, S1, _ = q_in.shape
        H, D = self.num_heads, self.head_dim
        q = self._proj(params, q_in, "wq", "bq")
        k = self._proj(params, k_in, "wk", "bk")
        v = self._proj(params, v_in, "wv", "bv")
        split = lambda t: t.reshape(B, S1, H, D).transpose(0, 2, 1, 3)
        qh, kh, vh = split(q), split(k), split(v)            # (B, H, 1, D)
        if jnp.ndim(pos):
            # per-row positions (the serving engine's continuous batch):
            # each row scatters its k/v into its own slot offset and
            # masks by its own prefix length — rows of the SAME batch
            # sit at different sequence positions mid-flight
            rows = jnp.arange(B)
            ck = cache["k"].at[rows, :, pos, :].set(
                kh[:, :, 0, :].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, :, pos, :].set(
                vh[:, :, 0, :].astype(cache["v"].dtype))
            pos_b = pos[:, None, None, None]
        else:
            ck = lax.dynamic_update_slice(
                cache["k"], kh.astype(cache["k"].dtype), (0, 0, pos, 0))
            cv = lax.dynamic_update_slice(
                cache["v"], vh.astype(cache["v"].dtype), (0, 0, pos, 0))
            pos_b = pos
        scale = 1.0 / math.sqrt(D)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                            ck.astype(jnp.float32)) * scale
        valid = jnp.arange(ck.shape[2])[None, None, None, :] <= pos_b
        scores = jnp.where(valid, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs,
                         cv.astype(jnp.float32)).astype(q_in.dtype)
        out = out.transpose(0, 2, 1, 3).reshape(B, S1, self.embed_dim)
        return [self._proj(params, out, "wo", "bo")], {"k": ck, "v": cv}

    def init_paged_cache(self, num_blocks: int, block_size: int, dtype):
        """Block-pool k/v storage shared by every slot: block id indexes
        dim 0, so a slot's cache is whatever its block table names.
        Block 0 is the garbage sink (serving/kvpool.py) — idle lanes
        write and read it, masked."""
        shp = (num_blocks, self.num_heads, block_size, self.head_dim)
        return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}

    def decode_paged(self, params, xs, cache, pos, tables, ctx):
        """Single-token attention over a paged cache: scatter this
        step's k/v into the block named by the row's table at
        ``pos // block_size``, gather the W blocks of the table window
        and attend over W*block_size positions (W is the static window
        bucket the engine picked; positions past ``pos`` are masked with
        the same -1e30 as the dense path, so softmax contributions are
        exactly zero and greedy outputs stay bitwise-equal).

        ``tables``: (B, W) int32 block ids; ``pos``: (B,) or scalar."""
        q_in, k_in, v_in = xs
        if q_in.shape[1] != 1 or k_in.shape[1] != 1:
            raise ValueError(
                f"decode_paged: op {self.name!r} got a full-sequence "
                f"input; paged decode is single-token only")
        if not self.causal:
            raise ValueError(
                f"decode_paged: op {self.name!r} is non-causal — "
                f"not decodable")
        B, S1, _ = q_in.shape
        H, D = self.num_heads, self.head_dim
        bs = cache["k"].shape[2]
        W = tables.shape[1]
        pos_v = pos if jnp.ndim(pos) else jnp.full((B,), pos, jnp.int32)
        q = self._proj(params, q_in, "wq", "bq")
        k = self._proj(params, k_in, "wk", "bk")
        v = self._proj(params, v_in, "wv", "bv")
        split = lambda t: t.reshape(B, S1, H, D).transpose(0, 2, 1, 3)
        qh, kh, vh = split(q), split(k), split(v)            # (B, H, 1, D)
        rows = jnp.arange(B)
        bidx = tables[rows, pos_v // bs]                     # (B,)
        roff = pos_v % bs
        ck = cache["k"].at[bidx, :, roff, :].set(
            kh[:, :, 0, :].astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, :, roff, :].set(
            vh[:, :, 0, :].astype(cache["v"].dtype))
        # window gather: (B, W, H, bs, D) -> (B, H, W*bs, D); table order
        # is logical-block order, so the flat axis is position order
        gk = ck[tables].transpose(0, 2, 1, 3, 4).reshape(B, H, W * bs, D)
        gv = cv[tables].transpose(0, 2, 1, 3, 4).reshape(B, H, W * bs, D)
        scale = 1.0 / math.sqrt(D)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                            gk.astype(jnp.float32)) * scale
        valid = jnp.arange(W * bs)[None, None, None, :] \
            <= pos_v[:, None, None, None]
        scores = jnp.where(valid, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs,
                         gv.astype(jnp.float32)).astype(q_in.dtype)
        out = out.transpose(0, 2, 1, 3).reshape(B, S1, self.embed_dim)
        return [self._proj(params, out, "wo", "bo")], {"k": ck, "v": cv}

    def flops_per_sample(self):
        _, sq, e = self.output.dims
        sk = self.inputs[1].dims[1]
        proj = 2.0 * sq * e * e * 4
        attn = 2.0 * self.num_heads * sq * sk * self.head_dim * 2
        return proj + attn

    def input_ranges(self, j, pc, part_idx):
        # K/V travel the full ring: a seq shard reads every other shard's
        # K/V exactly once, so its effective input range is the full seq.
        rng = super().input_ranges(j, pc, part_idx)
        if j in (1, 2):
            in_dims = self.inputs[j].dims
            rng[1] = (0, in_dims[1] - 1)
        return rng


# ---------------------------------------------------------------------------
# Rotary positions with YaRN's frequency blend, and latent attention
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor: 0.1 * mscale * ln(factor) + 1
    (1 where the context is not extended)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float = 10000.0, factor: float = 1.0,
                  original_max_position_embeddings: int = 4096,
                  beta_fast: float = 32.0, beta_slow: float = 1.0, **_):
    """The ``dim // 2`` rotary frequencies under YaRN (Peng et al. 2023):
    ``theta^(-2i/dim)`` for the dimensions that turn more than
    ``beta_fast`` times over the original context, that over ``factor``
    for those that turn fewer than ``beta_slow`` times, and a linear ramp
    between the two correction dims.  float64 numpy; ``factor`` 1 gives
    plain rotary frequencies."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra

    def correction_dim(turns):
        return dim * math.log(original_max_position_embeddings
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def _rotary_angles(s: int, inv_freq):
    """(s, r/2) float32: ``position * inv_freq[i]``."""
    return jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]


def rotate_pairs(x, inv_freq, scale: float = 1.0):
    """Rotary position embedding of x (B, S, ..., d) along axis 1, on
    adjacent pairs (x[2i], x[2i+1]) by the angle ``position * inv_freq[i]``.
    The result holds the first elements of the rotated pairs, then the
    second: a fixed permutation of the pairs' layout, which a dot product
    of two vectors rotated here does not see."""
    s = x.shape[1]
    ang = _rotary_angles(s, inv_freq)
    shape = (1, s) + (1,) * (x.ndim - 3) + (ang.shape[-1],)
    cos = (jnp.cos(ang) * scale).reshape(shape)
    sin = (jnp.sin(ang) * scale).reshape(shape)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


def attention_core(qh, kh, vh, impl: str, scale: float, window=None,
                   select=None, sink=None):
    """The per-chip core of causal self-attention, and the one place that
    chooses between the flash kernels and XLA's forms, by ``impl`` as
    ``_PicksAttentionImpl._pick_impl`` gives it.

    ``qh`` (B, H, S, d); ``kh`` (B, Hkv, S, d) and ``vh`` (B, Hkv, S, dv),
    Hkv dividing H: query head ``j`` reads key/value head ``j // (H /
    Hkv)`` (XLA's forms are given each key/value head repeated over its
    group; the kernels read it through their index maps).  ``window``:
    query t sees keys ``t - window + 1 .. t``; ``select``: the pairs kept,
    bool with the queries down the rows for XLA, of a float dtype with the
    keys down the rows for the kernels (as ``flash_attention`` takes it);
    ``sink`` (H,): a logit a head that takes mass in every row's softmax
    and adds no value.  Returns the heads' outputs (B, H, S, dv) and the
    rows' log-sum-exp (B, H, S), the sink's mass in it."""
    if impl == "xla":
        group = qh.shape[1] // kh.shape[1]
        if group > 1:
            kh, vh = (jnp.repeat(t, group, axis=1) for t in (kh, vh))
        if window is None and select is None and sink is None:
            from ..parallel.sequence import blockwise_attention
            return blockwise_attention(qh, kh, vh, causal=True, scale=scale)
        from .dsa import causal_mask, masked_attention
        keep = select
        if keep is None:
            s = qh.shape[2]
            if window is None:
                keep = causal_mask(s)[None]
            else:
                t = jnp.arange(s)
                keep = (causal_mask(s)
                        & (t[None, :] > t[:, None] - window))[None]
        return masked_attention(qh, kh, vh, keep, scale, sink)
    from ..kernels.flash_attention import flash_attention
    return flash_attention(qh, kh, vh, causal=True, scale=scale,
                           return_lse=True,
                           interpret=impl == "pallas_interpret",
                           window=window, select=select, sink=sink)


class LatentAttention(_PicksAttentionImpl, Op):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, section
    2.1), causal self-attention over (B, S, E) for training:

        c_q = norm(x W_DQ);  [q_nope | q_pe] = c_q W_UQ        per head
        [c_kv | k_pe] = x W_DKV;  [k_nope | v] = norm(c_kv) W_UKV  per head
        score = (q_nope . k_nope + rot(q_pe) . rot(k_pe)) * scale
        out = concat_h(softmax(score) v) W_O

    with RMSNorms on the two latents, ``k_pe`` one vector shared by every
    head, rotary positions on adjacent pairs with YaRN's frequencies
    (``rope_scaling``), and ``scale = (nope + rope)^-0.5 * m^2``, ``m``
    YaRN's mscale of ``mscale_all_dim``.  No bias.

    ``num_heads`` is the heads this op holds: with fewer than the model
    has, ``W_UQ``, ``W_UKV`` carry those heads' columns, ``W_O`` their
    rows, and the output is that share's partial sum.  Config dim 2 is
    head parallelism over the held heads (the up-projections' columns and
    ``W_O``'s rows shard over it; the down-projections and their norms
    are replicated); the output is placed by the batch degree alone.
    The core is the flash kernel at query/key head ``nope + rope`` and
    value head ``v_head_dim`` on a TPU, XLA's blockwise attention
    elsewhere (``impl_used``).

    Four options, all off by default (DeepSeek-V2 takes none):

    * ``window``: query t sees keys ``t - window + 1 .. t`` (the flash
      kernel's band; a dense mask in XLA elsewhere).
    * ``gate="headwise"`` (Qiu et al., arXiv:2505.06708): ``g =
      sigmoid(x W_g)``, one scalar a held head, multiplied onto that
      head's attention output before ``W_O``.
    * ``latent_rescale`` (LongCat-Flash's scale-corrected latent
      attention, arXiv:2509.01322): the normed latents ``c_q`` and
      ``c_kv`` times ``sqrt(hidden / rank)``.
    * ``index=(heads, head_dim, topk)``: learned sparse attention
      (``ops/dsa.py``).  ``q^I = c_q W^I_q``, ``k^I = LayerNorm(x
      W^I_k)``, the rotary turn on the first ``qk_rope_head_dim`` of
      each, ``w = x W^I_w * heads^-0.5 * head_dim^-0.5``; the main
      softmax runs over the ``topk`` keys of largest index score alone
      (the flash kernel under a selection), and the index's objective
      ``L_I`` goes to the step's objective as the term ``dsa_index_kl``
      (``FwdCtx.add_loss``).  The index reads ``x`` and ``c_q`` under
      ``stop_gradient`` and the selection passes no gradient, so
      ``L_I`` reaches the index's parameters alone and nothing else
      does.  The index is whole whatever share of the heads is held:
      every share computes the same selection.
    """

    _type = "LatentAttention"

    def __init__(self, model, input_tensor, num_heads: int, q_lora_rank: int,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 rope_theta: float = 10000.0, rope_scaling=None,
                 eps: float = 1e-6, kernel_initializer=None,
                 window: Optional[int] = None, gate: Optional[str] = None,
                 latent_rescale: bool = False, index=None,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor], name)
        if gate not in (None, "headwise"):
            raise ValueError(f"{self.name}: unknown gate {gate!r}")
        if window is not None and index is not None:
            raise ValueError(f"{self.name}: a window layer has no index")
        self.window = None if window is None else int(window)
        self.gate = gate
        self.index = None if index is None else tuple(int(v) for v in index)
        b, s, e = input_tensor.dims
        self.num_heads = int(num_heads)
        self.q_lora_rank, self.kv_lora_rank = int(q_lora_rank), int(kv_lora_rank)
        self.nope, self.rope, self.v_dim = (int(qk_nope_head_dim),
                                            int(qk_rope_head_dim),
                                            int(v_head_dim))
        self.eps = eps
        scaling = dict(rope_scaling or {})
        factor = float(scaling.get("factor", 1.0))
        self.inv_freq = yarn_inv_freq(self.rope, rope_theta, **scaling)
        self.rope_scale = (yarn_mscale(factor, scaling.get("mscale", 1.0))
                           / yarn_mscale(factor,
                                         scaling.get("mscale_all_dim", 0.0)))
        self.softmax_scale = (self.nope + self.rope) ** -0.5 * yarn_mscale(
            factor, scaling.get("mscale_all_dim", 0.0)) ** 2
        self._add_output((b, s, e), input_tensor.dtype)
        init = kernel_initializer or DefaultWeightInitializer()
        h = self.num_heads
        self._add_weight("w_dq", (e, self.q_lora_rank), init)
        self._add_weight("q_norm", (self.q_lora_rank,),
                         ConstantInitializer(1.0))
        self._add_weight("w_uq", (self.q_lora_rank,
                                  h * (self.nope + self.rope)), init,
                         partition_dims=(None, 2))
        self._add_weight("w_dkv", (e, self.kv_lora_rank + self.rope), init)
        self._add_weight("kv_norm", (self.kv_lora_rank,),
                         ConstantInitializer(1.0))
        self._add_weight("w_ukv", (self.kv_lora_rank,
                                   h * (self.nope + self.v_dim)), init,
                         partition_dims=(None, 2))
        self._add_weight("w_o", (h * self.v_dim, e), init,
                         partition_dims=(2, None))
        if gate is not None:
            self._add_weight("w_gate", (e, h), init, partition_dims=(None, 2))
        self.q_rescale = self.kv_rescale = None
        if latent_rescale:
            self.q_rescale = math.sqrt(e / self.q_lora_rank)
            self.kv_rescale = math.sqrt(e / self.kv_lora_rank)
        if self.index is not None:
            ih, idim, _ = self.index
            if idim < self.rope:
                raise ValueError(f"{self.name}: index head {idim} is "
                                 f"narrower than the rotary part {self.rope}")
            self._add_weight("wi_q", (self.q_lora_rank, ih * idim), init)
            self._add_weight("wi_k", (e, idim), init)
            self._add_weight("wi_k_scale", (idim,), ConstantInitializer(1.0))
            self._add_weight("wi_k_bias", (idim,), ZeroInitializer())
            self._add_weight("wi_w", (e, ih), init)
            # the index's objective, summed over layers, and the layers
            self.COUNTERS = ("dsa_index_kl", "dsa_layers")

    def _config_dim_bound(self, i: int):
        if i == 1:
            return 1   # no sequence parallelism: the ring is MHA's
        if i == 2:
            return self.num_heads
        return super()._config_dim_bound(i)

    constraint_pc = Op.batch_only_pc

    def cost_key(self) -> str:
        key = (f"mla{self.num_heads}q{self.q_lora_rank}kv{self.kv_lora_rank}"
               f"d{self.nope}.{self.rope}.{self.v_dim}")
        if self.window is not None:
            key += f"w{self.window}"
        if self.gate is not None:
            key += "g"
        if self.index is not None:
            key += "i{}.{}.{}".format(*self.index)
        return key

    # (impl, why) of the index's scores at the last trace, beside the
    # core's ``impl_used``.
    index_impl_used: Optional[Tuple[str, str]] = None

    def _pick_index_impl(self, impl: str, why: str, seq: int
                         ) -> Tuple[str, str]:
        """(impl, why) of the index's scores, given the core's: the
        kernels where the core runs its own and they can take the shape
        (``kernels.dsa_index.unsupported_reason``), else ``ops/dsa.py``'s
        ``jax.numpy`` form; on a TPU that is worth a warning."""
        if impl == "xla":
            return impl, why
        from ..kernels.dsa_index import unsupported_reason
        refused = unsupported_reason(seq, *self.index[:2])
        if refused is None:
            return impl, why
        if self.impl is None:
            warnings.warn(f"{self.name}: {refused}; the index's scores "
                          f"run as XLA's blocks")
        return "xla", refused

    def _index_scores(self, params, x, c_q, impl="xla"):
        """``I`` (B, S, S) float32 of the index, from the op's input and
        the query latent, both constants to it; ``impl`` as
        ``dsa.index_scores`` takes it.  Its XLA form is given the queries
        turned; the kernels turn them themselves, in VMEM, and are given
        what ``W^I_q`` with each head's rotary pairs set apart makes (a
        permutation of 33 MB of weights and of their gradient, where the
        turn and its gradient were a dozen passes over the 268 MB of
        queries: PERF.md section 6, PR 36)."""
        from .dsa import index_scores, pairs_apart

        ih, idim, _ = self.index
        b, s, _ = x.shape
        f32 = jnp.float32
        hi = jax.lax.Precision.HIGHEST
        grad_dtype = x.dtype
        x = jax.lax.stop_gradient(x).astype(f32)
        c_q = jax.lax.stop_gradient(c_q).astype(f32)
        turn = lambda t: jnp.concatenate(
            [rotate_pairs(t[..., :self.rope], self.inv_freq),
             t[..., self.rope:]], axis=-1)
        wi_q, q_rope = params["wi_q"].astype(f32), None
        if impl != "xla":
            wi_q = pairs_apart(wi_q.reshape(-1, ih, idim),
                               self.rope).reshape(wi_q.shape)
            ang = _rotary_angles(s, self.inv_freq)
            q_rope = (jnp.cos(ang), jnp.sin(ang))
        qi = jnp.dot(c_q, wi_q, precision=hi).reshape(b, s, ih, idim)
        if q_rope is None:
            qi = turn(qi)
        ki = jnp.dot(x, params["wi_k"].astype(f32), precision=hi)
        mean = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
        ki = (ki - mean) * jax.lax.rsqrt(var + self.eps) \
            * params["wi_k_scale"].astype(f32) \
            + params["wi_k_bias"].astype(f32)
        w = jnp.dot(x, params["wi_w"].astype(f32), precision=hi) \
            * (ih ** -0.5 * idim ** -0.5)
        return index_scores(qi, turn(ki[:, :, None, :])[:, :, 0], w,
                            grad_dtype, 256, 1024, impl, q_rope)

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        from .linear import project
        from .misc import rms_norm

        x = xs[0]
        b, s, _ = x.shape
        h, dn, dr, dv = self.num_heads, self.nope, self.rope, self.v_dim
        with jax.named_scope("ff.mla.q_proj"):
            # the rescale goes on the norm's float32 scale, where it is
            # exact: as a bf16 constant sqrt(10) is 0.2 % short, on every
            # latent alike
            c_q = rms_norm(project(x, params["w_dq"]),
                           params["q_norm"] if self.q_rescale is None
                           else params["q_norm"] * self.q_rescale, self.eps)
            q = project(c_q, params["w_uq"]).reshape(b, s, h, dn + dr)
        with jax.named_scope("ff.mla.kv_proj"):
            ckv = project(x, params["w_dkv"])
            c_kv = rms_norm(ckv[..., :self.kv_lora_rank],
                            params["kv_norm"] if self.kv_rescale is None
                            else params["kv_norm"] * self.kv_rescale,
                            self.eps)
            kv = project(c_kv, params["w_ukv"]).reshape(b, s, h, dn + dv)
        with jax.named_scope("ff.mla.rope"):
            q_pe = rotate_pairs(q[..., dn:], self.inv_freq, self.rope_scale)
            k_pe = rotate_pairs(ckv[..., self.kv_lora_rank:], self.inv_freq,
                                self.rope_scale)
            k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, dr))
            heads = lambda t: t.transpose(0, 2, 1, 3)     # (B, H, S, d)
            qh = heads(jnp.concatenate([q[..., :dn], q_pe], axis=-1))
            kh = heads(jnp.concatenate([kv[..., :dn], k_pe], axis=-1))
            vh = heads(kv[..., dn:])
        impl, why = self._pick_impl(s, s)
        self.impl_used = (impl, why)
        if self.index is None:
            oh, _ = attention_core(qh, kh, vh, impl, self.softmax_scale,
                                   window=self.window)
        else:
            from .dsa import index_kl, select_topk
            self.index_impl_used = self._pick_index_impl(impl, why, s)
            with jax.named_scope("ff.dsa.index"):
                scores = self._index_scores(params, x, c_q,
                                            self.index_impl_used[0])
            with jax.named_scope("ff.dsa.select"):
                keep = select_topk(scores, self.index[2])
                # keys down the rows, as the kernels hold the scores
                sel = keep if impl == "xla" else jnp.swapaxes(
                    keep, 1, 2).astype(jnp.bfloat16)
            oh, lse = attention_core(qh, kh, vh, impl, self.softmax_scale,
                                     select=sel)
            if ctx.losses is not None:
                with jax.named_scope("ff.dsa.loss"):
                    sg = jax.lax.stop_gradient
                    ctx.add_loss("dsa_index_kl", index_kl(
                        scores, keep, sg(qh), sg(kh), sg(lse),
                        self.softmax_scale))
                if ctx.counters is not None:
                    ctx.counters["dsa_layers"] = ctx.counters.get(
                        "dsa_layers", 0.0) + jnp.float32(1)
        if self.gate is not None:
            with jax.named_scope("ff.mla.gate"):
                g = jax.nn.sigmoid(project(x, params["w_gate"])
                                   .astype(jnp.float32))       # (B, S, H)
                oh = (oh.astype(jnp.float32)
                      * g.transpose(0, 2, 1)[..., None]).astype(oh.dtype)
        with jax.named_scope("ff.mla.o_proj"):
            out = project(oh.transpose(0, 2, 1, 3).reshape(b, s, h * dv),
                          params["w_o"])
        return [out]

    def decode(self, params, xs, cache, pos, ctx):
        raise NotImplementedError(
            f"{self.name}: LatentAttention has no decode path (a cache "
            f"would hold the latent and the shared rotary key, not heads)")

    def flops_per_sample(self):
        _, s, e = self.output.dims
        h, dn, dr, dv = self.num_heads, self.nope, self.rope, self.v_dim
        proj = 2.0 * s * (e * self.q_lora_rank
                          + self.q_lora_rank * h * (dn + dr)
                          + e * (self.kv_lora_rank + dr)
                          + self.kv_lora_rank * h * (dn + dv)
                          + h * dv * e)
        # the pairs the core is given: the window's band, the selection,
        # or all of them (what a masked kernel computes is not asked)
        keys = min(s, self.window) if self.window is not None else s
        proj += 2.0 * s * e * h * (self.gate is not None)
        if self.index is not None:
            keys = min(s, self.index[2])     # the index: unsplit_cost_...
        return proj + 2.0 * h * s * keys * (dn + dr + dv)

    def unsplit_cost_per_sample(self):
        """(FLOPs, bytes) a sample of what every head share does whole,
        for the cost model: the index's projections and its scores over
        the causal pairs, in MXU passes (float32 at the highest precision
        is six bf16 passes), and the selection's traffic over the (S, S)
        scores: written, read by the top-k, the mask and the KL term,
        and the mask read by the core forward and backward."""
        if self.index is None:
            return 0.0, 0.0
        _, s, e = self.output.dims
        ih, idim, _ = self.index
        flops = 6.0 * (2.0 * s * (self.q_lora_rank * ih * idim
                                  + e * (idim + ih)) + ih * s * s * idim)
        return flops, float(s) * s * (4 * 4 + 2 * 4)


class GroupedQueryAttention(_PicksAttentionImpl, Op):
    """Grouped-query attention (Ainslie et al., arXiv:2305.13245), causal
    self-attention over (B, S, E) for training:

        q = x W_q,  k = x W_k,  v = value_scale * x W_v         per head
        rotary positions on the first ``rotary_dim`` of each q and k head
        score = q_t . k_s * head_dim^-0.5, query head i reading key/value
                head i // (num_heads / num_kv_heads)
        out = concat_i(softmax_s(score) v) W_O

    on adjacent pairs at ``rope_theta`` (``rotate_pairs``), no bias.
    ``num_heads`` query heads and ``num_kv_heads`` key/value heads are held
    here, the query heads in whole groups of ``num_heads / num_kv_heads``;
    where fewer query heads than a group are held, ``num_kv_heads`` is 1,
    the one key/value head they read, held on every chip of its group.
    ``W_O`` carries the held heads' rows and the output is this share's
    partial sum.

    ``rotary_dim`` 0 leaves the heads unturned (no positions: a model
    whose other layers carry them).  ``return_kv``: the op gives its k and
    v, as the projections make them ((B, S, Hkv * d) and (B, S, Hkv *
    dv)), as its second and third outputs, for later layers to read.
    ``kv=(k, v)``: cross-attention, the op's queries against those of
    another such op, with no key or value projection of its own (the
    gradients of k and v flow back into the layer that made them).

    ``window``: query t sees keys ``t - window + 1 .. t``.  ``sink``: a
    learned logit ``b_i`` a held head (zero at first) in the softmax's
    denominator, with no value: ``p_ts = exp(s_ts) / (exp(b_i) + sum_s'
    exp(s_ts'))``.  A window layer with a sink counts ``swa_sink_share``,
    the mass the sink takes (the mean over its heads and queries of
    ``exp(b_i - lse_t)``; ``profiling.counters()`` gives the mean over
    such layers and drained steps).  The core is ``attention_core``: the
    flash kernels on a TPU (k and v read a group at a time through their
    index maps), XLA's forms elsewhere (``impl_used``).

    Config dim 2 is head parallelism over the held query heads: ``W_q``'s
    columns, ``W_O``'s rows and the sinks shard over it, ``W_k`` and
    ``W_v`` are whole on every shard, so that a shard that holds whole
    groups or part of one reads the key/value heads its queries need; the
    cost model prices those two projections as work every shard does
    whole (``unsplit_cost_per_sample``).  The output is placed by the
    batch degree alone.
    """

    _type = "GroupedQueryAttention"

    def __init__(self, model, input_tensor, num_heads: int, num_kv_heads: int,
                 head_dim: int, v_head_dim: int,
                 rotary_dim: Optional[int] = None, rope_theta: float = 10000.0,
                 window: Optional[int] = None, sink: bool = False,
                 value_scale: float = 1.0, kernel_initializer=None,
                 return_kv: bool = False, kv=None,
                 name: Optional[str] = None):
        super().__init__(model, [input_tensor] + list(kv or ()), name)
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        if self.num_kv_heads < 1 or self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.name}: {num_heads} query heads held "
                             f"over {num_kv_heads} key/value heads")
        self.head_dim, self.v_dim = int(head_dim), int(v_head_dim)
        self.rotary_dim = self.head_dim if rotary_dim is None \
            else int(rotary_dim)
        if self.rotary_dim % 2 or not 0 <= self.rotary_dim <= self.head_dim:
            raise ValueError(f"{self.name}: rotary dims {rotary_dim} of a "
                             f"head of {head_dim}")
        self.inv_freq = yarn_inv_freq(self.rotary_dim, rope_theta)
        self.window = None if window is None else int(window)
        self.sink = bool(sink)
        self.value_scale = float(value_scale)
        self.softmax_scale = self.head_dim ** -0.5
        self.return_kv, self.cross = bool(return_kv), kv is not None
        b, s, e = input_tensor.dims
        h, hkv = self.num_heads, self.num_kv_heads
        widths = (hkv * self.head_dim, hkv * self.v_dim)
        if self.cross and tuple(t.dims for t in kv) != tuple(
                (b, s, w) for w in widths):
            raise ValueError(f"{self.name}: keys and values "
                             f"{[t.dims for t in kv]} for {hkv} heads of "
                             f"{self.head_dim} | {self.v_dim}")
        self._add_output((b, s, e), input_tensor.dtype)
        if self.return_kv:
            for w in widths:
                self._add_output((b, s, w), input_tensor.dtype)
        init = kernel_initializer or DefaultWeightInitializer()
        self._add_weight("w_q", (e, h * self.head_dim), init,
                         partition_dims=(None, 2))
        if not self.cross:
            self._add_weight("w_k", (e, widths[0]), init)
            self._add_weight("w_v", (e, widths[1]), init)
        self._add_weight("w_o", (h * self.v_dim, e), init,
                         partition_dims=(2, None))
        if self.sink:
            self._add_weight("sink", (h,), ZeroInitializer(),
                             partition_dims=(2,))
            if self.window is not None:
                self.COUNTERS = ("swa_sink_share", "swa_layers")

    def _config_dim_bound(self, i: int):
        if i == 1:
            return 1   # no sequence parallelism: the ring is MHA's
        if i == 2:
            return self.num_heads
        return super()._config_dim_bound(i)

    constraint_pc = Op.batch_only_pc

    def cost_key(self) -> str:
        key = (f"gqa{self.num_heads}.{self.num_kv_heads}"
               f"d{self.head_dim}.{self.v_dim}r{self.rotary_dim}")
        if self.window is not None:
            key += f"w{self.window}"
        return key + ("s" if self.sink else "") + ("x" if self.cross else "")

    def forward(self, params, xs: List[jax.Array], ctx: FwdCtx):
        from .linear import project

        x = xs[0]
        b, s, _ = x.shape
        h, hkv, d, dv = self.num_heads, self.num_kv_heads, self.head_dim, \
            self.v_dim
        with jax.named_scope("ff.gqa.q_proj"):
            q = project(x, params["w_q"]).reshape(b, s, h, d)
        if self.cross:
            k2, v2 = xs[1], xs[2]
            k, v = k2.reshape(b, s, hkv, d), v2.reshape(b, s, hkv, dv)
        else:
            with jax.named_scope("ff.gqa.kv_proj"):
                k2 = project(x, params["w_k"])
                k = k2.reshape(b, s, hkv, d)
                # v's scale is linear: it goes on the float32 weight,
                # which is rounded once either way
                w_v = params["w_v"] if self.value_scale == 1.0 \
                    else params["w_v"] * self.value_scale
                v2 = project(x, w_v)
                v = v2.reshape(b, s, hkv, dv)
        with jax.named_scope("ff.gqa.rope"):
            r = self.rotary_dim
            turn = (lambda t: jnp.concatenate(
                [rotate_pairs(t[..., :r], self.inv_freq), t[..., r:]],
                axis=-1)) if r else (lambda t: t)
            heads = lambda t: t.transpose(0, 2, 1, 3)     # (B, H, S, d)
            qh, kh, vh = heads(turn(q)), heads(turn(k)), heads(v)
        impl, why = self._pick_impl(s, s)
        self.impl_used = (impl, why)
        sink = params["sink"] if self.sink else None
        oh, lse = attention_core(qh, kh, vh, impl, self.softmax_scale,
                                 window=self.window, sink=sink)
        if "swa_layers" in self.COUNTERS and ctx.counters is not None:
            sg = jax.lax.stop_gradient
            share = jnp.mean(jnp.exp(
                sg(sink).astype(jnp.float32)[None, :, None] - sg(lse)))
            for name, value in zip(self.COUNTERS, (share, jnp.float32(1))):
                ctx.counters[name] = ctx.counters.get(name, 0.0) + value
        with jax.named_scope("ff.gqa.o_proj"):
            out = project(oh.transpose(0, 2, 1, 3).reshape(b, s, h * dv),
                          params["w_o"])
        return [out, k2, v2] if self.return_kv else [out]

    def decode(self, params, xs, cache, pos, ctx):
        raise NotImplementedError(
            f"{self.name}: GroupedQueryAttention has no decode path (a "
            f"window layer's cache would hold its last keys and a sink)")

    def flops_per_sample(self):
        """What head parallelism divides: the query and output
        projections and the core over the pairs it is given (the
        window's band, or the causal square as the other ops count it)."""
        _, s, e = self.output.dims
        h, d, dv = self.num_heads, self.head_dim, self.v_dim
        keys = min(s, self.window) if self.window is not None else s
        return 2.0 * s * e * h * (d + dv) + 2.0 * h * s * keys * (d + dv)

    def unsplit_cost_per_sample(self):
        """(FLOPs, bytes) a sample of what every head share does whole:
        the key and value projections, and their heads written in
        bfloat16 (none for cross-attention, which reads another layer's)."""
        if self.cross:
            return 0.0, 0.0
        _, s, e = self.output.dims
        width = self.num_kv_heads * (self.head_dim + self.v_dim)
        return 2.0 * s * e * width, 2.0 * s * width
