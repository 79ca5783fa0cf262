"""DeepSeek-V2 (arXiv:2405.04434; deepseek-ai/DeepSeek-V2 config.json) for
training, as one chip of a deployment that divides each layer over several
chips holds it, in plain `jax.numpy` and float32: token embedding, pre-norm
blocks of multi-head latent attention and a SiLU-gated MLP (the first
`first_k_dense_replace` layers) or routed experts with shared experts (the
rest), a final RMSNorm, an untied head, mean next-token cross-entropy.  No
kernels, no cache, scores materialised a head at a time, the experts a loop
with a mask, matrix products at the highest precision.  Written from the
layer equations (ISSUE 32; the paper's sections 2.1 and 2.2), not from the
program's ops.

The equations, `h = norm(x)`, every norm an RMSNorm with eps `rms_norm_eps`
and a learned scale, no bias anywhere:

    latent attention   c_q = norm(h W_DQ);  [q_nope | q_pe] = c_q W_UQ per head
                       [c_kv | k_pe] = h W_DKV;  [k_nope | v] = norm(c_kv) W_UKV
                       q_pe, k_pe rotated on adjacent pairs, YaRN frequencies;
                       k_pe one vector shared by all heads
                       score = (q_nope.k_nope + q_pe.k_pe) (nope+rope)^-0.5 m^2
                       out = concat_h(causal_softmax(score) v) W_O
    gated MLP          (silu(h W_gate) * (h W_up)) W_down
    routed experts     s = softmax(h W_g) over all experts, float32
                       a group's score is its largest s; the topk_group best
                       groups stay; the top_k largest s among their experts
                       y = scaling * sum_i s_i E_i(h) + Shared(h)
    model              x += attn(norm x); x += mlp(norm x); logits = norm(x) W

Departures from the published model, as the configuration file lists them:

* **The chip's share.**  `num_attention_heads` is the heads held (W_UQ, W_UKV
  carry their columns, W_O their rows: the attention output is this share's
  partial sum); `experts_held` of `n_routed_experts` experts from
  `first_expert` on are here, the router keeps its full width, and what
  the absent experts would add is left out; `vocab_size` is the rows of
  the vocabulary held, and tokens, logits and loss are over those.  Partial
  sums go on to the next layer as they are.
* **The device budget** (the paper's 2.2.4, capacity factor 1.0): of the
  (token, held expert) assignments the router makes over the step's tokens,
  the `ceil(tokens * top_k * held / n_routed)` (`device_budget`) with the
  largest `s` are kept (ties: lower token, then lower expert) and the rest
  add nothing.  The
  paper's exemption of a tenth of the sequences is not kept.  The budget
  is over all the step's tokens, so `loss` takes the whole batch at once.
* No balance losses; the program's default initialisers.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 30  # the whole batch a call: the budget is over a step's tokens


def make_batch(key, batch_size, seq_length=4096, vocab_size=102400, **_):
    """One synthetic batch from the key: ((tokens,), labels), token ids
    uniform over the vocabulary rows held, labels the next token, the
    last wrapping round."""
    toks = jax.random.randint(key, (batch_size, seq_length), 0, vocab_size,
                              jnp.int32)
    return (toks,), jnp.roll(toks, -1, axis=1)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def yarn_frequencies(dim, theta, factor, original_max_position_embeddings,
                     beta_fast, beta_slow, **_):
    """The dim/2 rotary frequencies: theta^(-2i/dim) blended with that over
    `factor` by a linear ramp between the correction dims."""
    def correction_dim(turns):
        return dim * math.log(original_max_position_embeddings
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    keep = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return plain / factor * (1.0 - keep) + plain * keep


def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rotate(x, freqs, magnitude):
    """Adjacent pairs (x[2i], x[2i+1]) of the last dim turned by position *
    freqs[i]; x is (batch, seq, ..., dim), position along axis 1."""
    s = x.shape[1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (-1,))
    cos, sin = jnp.cos(ang) * magnitude, jnp.sin(ang) * magnitude
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _attention(x, norm, p, cfg):
    (heads, kv_rank, nope, rope, v_dim, eps, theta, scaling) = cfg
    scaling = dict(scaling)
    b, s, _ = x.shape
    h = _rms_norm(x, norm["scale"], eps)
    q = (_rms_norm(h @ p["w_dq"], p["q_norm"], eps) @ p["w_uq"]).reshape(
        b, s, heads, nope + rope)
    down = h @ p["w_dkv"]
    kv = (_rms_norm(down[..., :kv_rank], p["kv_norm"], eps)
          @ p["w_ukv"]).reshape(b, s, heads, nope + v_dim)
    freqs = yarn_frequencies(rope, theta, **scaling)
    magnitude = mscale(scaling["factor"], scaling["mscale"]) \
        / mscale(scaling["factor"], scaling["mscale_all_dim"])
    q_pe = _rotate(q[..., nope:], freqs, magnitude)
    k_pe = _rotate(down[..., kv_rank:], freqs, magnitude)    # (b, s, rope)
    scale = (nope + rope) ** -0.5 \
        * mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    causal = jnp.tril(jnp.ones((s, s), bool))
    outs = []
    for i in range(heads):
        score = (jnp.einsum("bqd,bkd->bqk", q[:, :, i, :nope],
                            kv[:, :, i, :nope])
                 + jnp.einsum("bqd,bkd->bqk", q_pe[:, :, i], k_pe)) * scale
        probs = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bqk,bkd->bqd", probs, kv[:, :, i, nope:]))
    return x + jnp.concatenate(outs, axis=-1) @ p["w_o"]


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_mlp(x, norm, p, eps):
    return x + _gated(_rms_norm(x, norm["scale"], eps), p["w_gate"],
                      p["w_up"], p["w_down"])


def device_budget(tokens, top_k, held, routed, capacity_factor=1.0):
    """Rows a device keeps: `capacity_factor` times the assignments an even
    router would send its experts, and no more than the tokens can make."""
    return min(tokens * min(top_k, held),
               math.ceil(tokens * top_k * held / routed * capacity_factor))


def kept_assignments(s, top_k, n_group, topk_group, first, held,
                     capacity_factor=1.0):
    """(tokens, held) bool: the assignments of the router's choice that
    land on the held experts and survive the device budget; `s` is
    (tokens, all experts)."""
    t, e = s.shape
    group_score = s.reshape(t, n_group, e // n_group).max(axis=-1)
    best_groups = jnp.argsort(-group_score, axis=-1, stable=True)[
        :, :topk_group]
    group_of = jnp.arange(e) // (e // n_group)
    allowed = (group_of[None, :, None] == best_groups[:, None, :]).any(-1)
    chosen_ids = jnp.argsort(-jnp.where(allowed, s, 0.0), axis=-1,
                             stable=True)[:, :top_k]
    chosen = (jnp.arange(e)[None, :, None] == chosen_ids[:, None, :]).any(-1)
    here = chosen[:, first:first + held]
    budget = device_budget(t, top_k, held, e, capacity_factor)
    # descending affinity; among equals the lower token, then the lower
    # expert, which is the order of the flattened (token, expert) matrix
    affinity = jnp.where(here, s[:, first:first + held], -1.0).reshape(-1)
    rank = jnp.argsort(jnp.argsort(-affinity, stable=True), stable=True)
    return ((rank < budget) & (affinity >= 0.0)).reshape(t, held)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _expert_mlp(x, norm, p, cfg):
    top_k, n_group, topk_group, first, held, capacity, scaling, eps = cfg
    shape = x.shape
    h = _rms_norm(x, norm["scale"], eps).reshape(-1, shape[-1])
    s = jax.nn.softmax(h @ p["router"], axis=-1)
    keep = kept_assignments(s, top_k, n_group, topk_group, first, held,
                            capacity)
    y = jnp.zeros_like(h)
    for i in range(held):
        weight = jnp.where(keep[:, i], s[:, first + i], 0.0)[:, None]
        y = y + weight * _gated(h, p["w_gate"][i], p["w_up"][i],
                                p["w_down"][i])
    y = scaling * y
    if "shared_gate" in p:
        y = y + _gated(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return x + y.reshape(shape)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, eps):
    return _rms_norm(x, norm["scale"], eps) @ head["kernel"]


@jax.jit
def _mean_nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.mean(-jnp.take_along_axis(logp, labels[..., None], axis=-1))


def logits(params, inputs, num_hidden_layers=60, first_k_dense_replace=1,
           num_attention_heads=128, kv_lora_rank=512, qk_nope_head_dim=128,
           qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000.0,
           rope_scaling=None, rms_norm_eps=1e-6, n_routed_experts=160,
           num_experts_per_tok=6, n_group=8, topk_group=3,
           routed_scaling_factor=16.0, experts_held=None, first_expert=0,
           capacity_factor=1.0, **_):
    """(batch, seq, vocabulary held) logits of `params` ({op: {weight:
    array}}) on the whole batch, float32 throughout, a layer at a time."""
    (toks,) = inputs
    attn_cfg = (num_attention_heads, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, rms_norm_eps, rope_theta,
                tuple(sorted((k, v) for k, v in (rope_scaling or {}).items()
                             if k != "type")))
    moe_cfg = (num_experts_per_tok, n_group, topk_group, first_expert,
               experts_held or n_routed_experts, capacity_factor,
               routed_scaling_factor, rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["tok_embed"]["weight"], toks, axis=0)
        for i in range(num_hidden_layers):
            x = _attention(x, params[f"ln1_{i}"], params[f"attn_{i}"],
                           cfg=attn_cfg)
            if i < first_k_dense_replace:
                x = _dense_mlp(x, params[f"ln2_{i}"], params[f"mlp_{i}"],
                               eps=rms_norm_eps)
            else:
                x = _expert_mlp(x, params[f"ln2_{i}"], params[f"moe_{i}"],
                                cfg=moe_cfg)
        return _head(x, params["ln_f"], params["lm_head"], eps=rms_norm_eps)


def loss(params, inputs, labels, **kw):
    """Mean next-token cross-entropy over the vocabulary held, on the
    whole batch (the budget is over a step's tokens)."""
    return _mean_nll(logits(params, inputs, **kw), labels)
