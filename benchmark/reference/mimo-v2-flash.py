"""MiMo-V2-Flash's language model (XiaomiMiMo/MiMo-V2-Flash config.json) for
training, as one chip of a deployment that divides each layer over several
chips holds it, in plain `jax.numpy` and float32: token embedding, pre-norm
blocks of grouped-query attention (over all earlier keys on the full layers,
over a window with a learned sink on the others) and a SiLU-gated MLP
(`moe_layer_freq` 0) or routed experts (1), a final RMSNorm, an untied head,
and the mean next-token cross-entropy.  No kernels, no cache; dense masks;
scores held a block of queries at a time, every head at once, so that a
layer fits beside the model on one chip at 8192 tokens (all of a full
layer's would be 4.3 GB); matrix products at the highest precision.
Written from the layer equations of the public config, not from the
program's ops.

The equations, `h = norm(x)`, every norm an RMSNorm with eps
`layernorm_epsilon` and a learned scale, no bias anywhere:

    attention      q = h W_q, k = h W_k, v = value_scale * h W_v, per head
                   the first `rotary_dim` of each q and k head turned on
                   adjacent pairs by position * theta^(-2i/rotary_dim)
                   score_ts = q_t . k_s * head_dim^-0.5, query head i
                   reading key/value head i // group
                   p_ts = exp(score_ts) / (exp(b_i) + sum_{s' in keys(t)}
                   exp(score_ts')) with the sink b_i on window layers, and
                   without the exp(b_i) on full layers
                   out = concat_i(sum_s p_ts v_s) W_O
    keys(t)        full layer: s <= t; window layer: t-window+1 <= s <= t
    gated MLP      (silu(h W_gate) * (h W_up)) W_down
    routed experts s = sigmoid(h W_r), float32; the top_k largest of s + b
                   (b the selection bias: zeros, never updated here)
                   weight_i = s_i / sum of the chosen s (norm_topk_prob)
                   y = sum_i weight_i E_i(h)    (no shared expert, scaling 1)
    model          x += attn(norm x); x += mlp(norm x); logits = norm(x) W
    objective      mean_t -log softmax(logits)[label]

Departures from the published model, as the configuration file lists them
under `assumed`: rotary on adjacent pairs (a fixed permutation of W_q's and
W_k's columns against the published rotate-half); the value scale on v
(linear: the same as on the core's output); `attention_chunk_size` and the
multi-token-prediction layers are not built; and

* **The chip's share.**  `num_attention_heads`, `num_key_value_heads` and
  their `swa_` twins are the heads held (W_q and the sinks carry the held
  query heads, W_k and W_v the key/value heads they read, W_O their rows:
  the attention output is this share's partial sum), the group their
  ratio; `experts_held` of `n_routed_experts` experts from `first_expert`
  on are here, the router keeps its full width and a token's weights are
  normalised over all its choices, and what the absent experts would add is
  left out; `vocab_size` is the rows of the vocabulary held.
* **The device budget** (capacity factor 1.0 a device): of the (token, held
  expert) assignments the router makes over the step's tokens, the
  `ceil(tokens * top_k * held / n_routed)` with the largest weight are kept
  (ties: lower token, then lower expert) and the rest add nothing.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 30  # the whole batch a call: the budget is over a step's tokens
QUERY_BLOCK = 512  # queries whose scores are held at once, every head


def make_batch(key, batch_size, seq_length=8192, vocab_size=152576, **_):
    """One synthetic batch from the key: ((tokens,), labels), token ids
    uniform over the vocabulary rows held, labels the next token, the
    last wrapping round."""
    toks = jax.random.randint(key, (batch_size, seq_length), 0, vocab_size,
                              jnp.int32)
    return (toks,), jnp.roll(toks, -1, axis=1)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rotate(x, theta):
    """Adjacent pairs (x[2i], x[2i+1]) of the last dim turned by position *
    theta^(-2i/dim); x is (batch, seq, heads, dim), position along axis 1."""
    s, dim = x.shape[1], x.shape[-1]
    freqs = theta ** (-2.0 * np.arange(dim // 2, dtype=np.float64) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attend(q, k, v, window, sink, scale):
    """Causal attention of q (b, s, heads, d) over k (b, s, kv heads, d)
    and v (b, s, kv heads, dv), query head i reading key/value head
    i // (heads / kv heads), a block of queries at a time; keys s <= t,
    and s > t - window where `window` is given; `sink` (heads,) in each
    row's denominator, or None.  Returns (b, s, heads, dv)."""
    b, s, heads, _ = q.shape
    group = heads // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)

    def rows(args):
        qb, first = args                        # (b, block, heads, d)
        t = first + jnp.arange(block)
        keep = keys[None, :] <= t[:, None]
        if window is not None:
            keep = keep & (keys[None, :] > t[:, None] - window)
        score = jnp.where(keep, jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale,
                          -jnp.inf)
        top = jnp.max(score, axis=-1, keepdims=True)
        if sink is not None:
            top = jnp.maximum(top, sink[None, :, None, None])
        e = jnp.exp(score - top)
        total = jnp.sum(e, axis=-1, keepdims=True)
        if sink is not None:
            total = total + jnp.exp(sink[None, :, None, None] - top)
        return jnp.einsum("bhqk,bkhd->bqhd", e / total, v)
    blocks = jnp.moveaxis(q.reshape((b, s // block, block) + q.shape[2:]),
                          1, 0)
    out = jax.lax.map(rows, (blocks, jnp.arange(0, s, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, heads, v.shape[-1])


@functools.partial(jax.jit, static_argnames=("cfg",))
def _attention(x, norm, p, cfg):
    """x + attention(norm x)."""
    heads, kv_heads, d, dv, rotary, theta, window, sink, value_scale, eps = \
        cfg
    b, s, _ = x.shape
    h = _rms_norm(x, norm["scale"], eps)
    q = (h @ p["w_q"]).reshape(b, s, heads, d)
    k = (h @ p["w_k"]).reshape(b, s, kv_heads, d)
    v = value_scale * (h @ p["w_v"]).reshape(b, s, kv_heads, dv)
    turn = lambda a: jnp.concatenate([_rotate(a[..., :rotary], theta),
                                      a[..., rotary:]], axis=-1)
    out = attend(turn(q), turn(k), v, window, p["sink"] if sink else None,
                 d ** -0.5)
    return x + out.reshape(b, s, heads * dv) @ p["w_o"]


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


def kept_weights(s, bias, top_k, first, held, capacity_factor=1.0):
    """(tokens, held) float: the weight of each assignment that lands on
    the held experts and survives the device budget, 0 elsewhere; `s` is
    (tokens, all experts), the sigmoid scores."""
    t, e = s.shape
    chosen_ids = jnp.argsort(-(s + bias), axis=-1, stable=True)[:, :top_k]
    chosen = (jnp.arange(e)[None, :, None] == chosen_ids[:, None, :]).any(-1)
    weight = jnp.where(chosen, s, 0.0)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    here = chosen[:, first:first + held]
    budget = device_budget(t, top_k, held, e, capacity_factor)
    # descending weight; among equals the lower token, then the lower
    # expert, which is the order of the flattened (token, expert) matrix
    mine = jnp.where(here, weight[:, first:first + held], -1.0).reshape(-1)
    rank = jnp.argsort(jnp.argsort(-jax.lax.stop_gradient(mine), stable=True),
                       stable=True)
    keep = ((rank < budget) & (mine >= 0.0)).reshape(t, held)
    return jnp.where(keep, weight[:, first:first + held], 0.0)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _expert_mlp(x, norm, p, cfg):
    top_k, first, held, capacity, scaling, eps = cfg
    shape = x.shape
    h = _rms_norm(x, norm["scale"], eps).reshape(-1, shape[-1])
    s = jax.nn.sigmoid(h @ p["router"])
    weight = kept_weights(s, jnp.zeros((s.shape[1],)), top_k, first, held,
                          capacity)
    y = jnp.zeros_like(h)
    for i in range(held):
        y = y + weight[:, i, None] * _gated(h, p["w_gate"][i], p["w_up"][i],
                                            p["w_down"][i])
    return x + (scaling * y).reshape(shape)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, eps):
    return _rms_norm(x, norm["scale"], eps) @ head["kernel"]


@jax.jit
def _mean_nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.mean(-jnp.take_along_axis(logp, labels[..., None], axis=-1))


def layer_pattern(num_hidden_layers=48):
    """The published hybrid_layer_pattern: layer 0 and every layer i with
    i % 6 == 5 are full (0), the rest window layers (1)."""
    return [0 if i == 0 or i % 6 == 5 else 1 for i in range(num_hidden_layers)]


def _forward(params, inputs, num_hidden_layers=48, hybrid_layer_pattern=None,
             moe_layer_freq=None, num_attention_heads=64,
             num_key_value_heads=4, head_dim=192, v_head_dim=128,
             swa_num_attention_heads=64, swa_num_key_value_heads=8,
             swa_head_dim=192, swa_v_head_dim=128, partial_rotary_factor=0.334,
             rope_theta=5e6, swa_rope_theta=1e4, sliding_window=128,
             attention_value_scale=0.707, add_swa_attention_sink_bias=True,
             add_full_attention_sink_bias=False, layernorm_epsilon=1e-5,
             n_routed_experts=256, num_experts_per_tok=8,
             routed_scaling_factor=None, experts_held=None, first_expert=0,
             capacity_factor=1.0, **_):
    """Logits of `params` ({op: {weight: array}}) on the whole batch,
    float32 throughout, a layer at a time."""
    (toks,) = inputs
    pattern = hybrid_layer_pattern or layer_pattern(num_hidden_layers)
    moe = moe_layer_freq or [0] + [1] * (num_hidden_layers - 1)
    eps = layernorm_epsilon
    full = (num_attention_heads, num_key_value_heads, head_dim, v_head_dim,
            int(partial_rotary_factor * head_dim), float(rope_theta), None,
            bool(add_full_attention_sink_bias), attention_value_scale, eps)
    swa = (swa_num_attention_heads, swa_num_key_value_heads, swa_head_dim,
           swa_v_head_dim, int(partial_rotary_factor * swa_head_dim),
           float(swa_rope_theta), sliding_window,
           bool(add_swa_attention_sink_bias), attention_value_scale, eps)
    moe_cfg = (num_experts_per_tok, first_expert,
               experts_held or n_routed_experts, capacity_factor,
               routed_scaling_factor or 1.0, eps)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["tok_embed"]["weight"], toks, axis=0)
        for i in range(num_hidden_layers):
            x = _attention(x, params[f"ln1_{i}"], params[f"attn_{i}"],
                           cfg=swa if pattern[i] else full)
            if moe[i]:
                x = _expert_mlp(x, params[f"ln2_{i}"], params[f"moe_{i}"],
                                cfg=moe_cfg)
            else:
                x = _dense_mlp(x, params[f"ln2_{i}"], params[f"mlp_{i}"],
                               eps=eps)
        return _head(x, params["ln_f"], params["lm_head"], eps=eps)


def logits(params, inputs, **kw):
    """(batch, seq, vocabulary held) logits on the whole batch."""
    return _forward(params, inputs, **kw)


def loss(params, inputs, labels, **kw):
    """Mean next-token cross-entropy over the vocabulary held."""
    return _mean_nll(_forward(params, inputs, **kw), labels)
