"""dots3-note-prev's language model (dots-studio/dots3-note-prev config.json)
for training, as one chip of a deployment that divides each layer over
several chips holds it, in plain `jax.numpy` and float32: token embedding,
pre-norm blocks of latent attention (under a learned index on the full
layers, within a window on the others, both with a head-wise gate) and a
SiLU-gated MLP (the first `first_k_dense_replace` layers) or routed experts
with a shared expert (the rest), a final RMSNorm, an untied head, and the
objective: mean next-token cross-entropy plus the index's KL term of every
full layer.  No kernels, no cache; dense masks and `lax.top_k`; scores
materialised a head at a time and the index a block of queries at a time,
so that it fits one chip at 8192 tokens; matrix products at the highest
precision.  Written from the layer equations (ISSUE 35 and the public
descriptions it names), not from the program's ops.

The equations, `h = norm(x)`, every norm an RMSNorm with eps `rms_norm_eps`
and a learned scale unless said otherwise, no bias anywhere but the index
key's LayerNorm:

    latent attention   c_q = norm(h W_DQ) r_q;  [q_nope | q_pe] = c_q W_UQ
                       [c_kv | k_pe] = h W_DKV;  [k_nope | v] = norm(c_kv) r_kv W_UKV
                       r = sqrt(hidden / rank) (the rescale), q_pe, k_pe
                       rotated on adjacent pairs, k_pe shared by all heads
                       score = (q_nope.k_nope + q_pe.k_pe) (nope+rope)^-0.5
                       g = sigmoid(h W_g), one scalar a head
                       out = concat_h(g_h softmax_{s in keys(t)}(score) v) W_O
    keys(t)            window layer: s in t-window+1 .. t
                       full layer:   S_t, the min(topk, t+1) keys s <= t of
                       largest I[t, s] (ties: the lower s)
    index              q^I = c_q W^I_q (n heads of d_I); k^I = LayerNorm(h W^I_k)
                       the rotary turn on the first `rope` of each
                       w = h W^I_w n^-0.5 d_I^-0.5
                       I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])
                       L_I = mean_t KL(p_t || softmax_{s in S_t} I[t, s]),
                       p_t = sum over the heads held of the main attention's
                       probabilities, normalised to 1, a constant; the index
                       reads h and c_q as constants
    gated MLP          (silu(h W_gate) * (h W_up)) W_down
    routed experts     s = sigmoid(h W_r), float32; the top_k largest of s + b
                       (b the selection bias: zeros, never updated here)
                       weight_i = s_i / sum of the chosen s, times scaling
                       y = sum_i weight_i E_i(h) + Shared(h)
    model              x += attn(norm x); x += mlp(norm x); logits = norm(x) W
    objective          mean_t -log softmax(logits)[label] + sum over full
                       layers of L_I

Departures from the published model, as the configuration file lists them
under `assumed`: the rescale's reading; the window as 513 keys with the
query's own; the index's Hadamard turn and FP8 storage left out; the
selection bias's balancing update left out; weight 1 on L_I; and

* **The chip's share.**  `num_attention_heads` and `swa_num_attention_heads`
  are the heads held (W_UQ, W_UKV, W_g carry their columns, W_O their rows:
  the attention output is this share's partial sum; the index is whole);
  `experts_held` of `n_routed_experts` experts from `first_expert` on are
  here, the router keeps its full width and a token's weights are
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
INDEX_BLOCK = 256  # queries whose index scores are held at once, by head


def make_batch(key, batch_size, seq_length=8192, vocab_size=152064, **_):
    """One synthetic batch from the key: ((tokens,), labels), token ids
    uniform over the vocabulary rows held, labels the next token, the
    last wrapping round."""
    toks = jax.random.randint(key, (batch_size, seq_length), 0, vocab_size,
                              jnp.int32)
    return (toks,), jnp.roll(toks, -1, axis=1)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rotate(x, theta):
    """Adjacent pairs (x[2i], x[2i+1]) of the last dim turned by position *
    theta^(-2i/dim); x is (batch, seq, ..., dim), position along axis 1."""
    s, dim = x.shape[1], x.shape[-1]
    freqs = theta ** (-2.0 * np.arange(dim // 2, dtype=np.float64) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def index_scores(h, c_q, p, n_heads, head_dim, rope, theta, eps):
    """I (batch, seq, seq): every query's score for every key, reduced
    over the index's heads a block of queries at a time."""
    b, s, _ = h.shape
    q = (c_q @ p["wi_q"]).reshape(b, s, n_heads, head_dim)
    k = _layer_norm(h @ p["wi_k"], p["wi_k_scale"], p["wi_k_bias"], eps)
    q = jnp.concatenate([_rotate(q[..., :rope], theta), q[..., rope:]], -1)
    k = jnp.concatenate([_rotate(k[..., :rope], theta), k[..., rope:]], -1)
    w = (h @ p["wi_w"]) * n_heads ** -0.5 * head_dim ** -0.5
    block = INDEX_BLOCK if s % INDEX_BLOCK == 0 else s

    def rows(args):
        qb, wb = args                       # (b, block, heads, d), (b, block, heads)
        return jnp.einsum("bqh,bqhk->bqk", wb, jax.nn.relu(
            jnp.einsum("bqhd,bkd->bqhk", qb, k)))
    split = lambda a: jnp.moveaxis(
        a.reshape((b, s // block, block) + a.shape[2:]), 1, 0)
    out = jax.lax.map(rows, (split(q), split(w)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, s)


def selected_keys(scores, topk):
    """(batch, seq, seq) bool: S_t by `lax.top_k` over the keys s <= t
    (of equal scores it takes the lower s), as a dense mask."""
    b, s, _ = scores.shape
    causal = jnp.tril(jnp.ones((s, s), bool))
    if s <= topk:
        return jnp.broadcast_to(causal, scores.shape)
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    block = INDEX_BLOCK if s % INDEX_BLOCK == 0 else s

    def rows(ib):                           # (b, block, topk) key indices
        hit = jnp.zeros((b, block, s), bool)
        return hit.at[jnp.arange(b)[:, None, None],
                      jnp.arange(block)[None, :, None], ib].set(True)
    out = jax.lax.map(rows, jnp.moveaxis(
        idx.reshape(b, s // block, block, topk), 1, 0))
    # a query with fewer earlier keys than topk drew the rest from above
    # the diagonal
    return jnp.moveaxis(out, 0, 1).reshape(b, s, s) & causal


@functools.partial(jax.jit, static_argnames=("cfg",))
def _attention(x, norm, p, cfg):
    """x + attention(norm x), and the layer's L_I (0 on a window layer)."""
    (heads, q_rank, kv_rank, nope, rope, v_dim, eps, theta, window, rescale,
     index) = cfg
    b, s, e = x.shape
    h = _rms_norm(x, norm["scale"], eps)
    c_q = _rms_norm(h @ p["w_dq"], p["q_norm"], eps)
    down = h @ p["w_dkv"]
    c_kv = _rms_norm(down[..., :kv_rank], p["kv_norm"], eps)
    if rescale:
        c_q = c_q * math.sqrt(e / q_rank)
        c_kv = c_kv * math.sqrt(e / kv_rank)
    q = (c_q @ p["w_uq"]).reshape(b, s, heads, nope + rope)
    kv = (c_kv @ p["w_ukv"]).reshape(b, s, heads, nope + v_dim)
    q_pe = _rotate(q[..., nope:], theta)
    k_pe = _rotate(down[..., kv_rank:], theta)               # (b, s, rope)
    t = jnp.arange(s)
    keep = (t[None, :] <= t[:, None])[None]
    if window is not None:
        keep = keep & (t[None, :] > t[:, None] - window)[None]
    if index is not None:
        n_heads, head_dim, topk = index
        const = jax.lax.stop_gradient
        scores = index_scores(const(h), const(c_q), p, n_heads, head_dim,
                              rope, theta, eps)
        keep = selected_keys(const(scores), topk)
    scale = (nope + rope) ** -0.5
    gate = jax.nn.sigmoid(h @ p["w_gate"])                   # (b, s, heads)
    outs, target = [], 0.0
    for i in range(heads):
        score = (jnp.einsum("bqd,bkd->bqk", q[:, :, i, :nope],
                            kv[:, :, i, :nope])
                 + jnp.einsum("bqd,bkd->bqk", q_pe[:, :, i], k_pe)) * scale
        probs = jax.nn.softmax(jnp.where(keep, score, -jnp.inf), axis=-1)
        target = target + probs
        outs.append(gate[:, :, i, None]
                    * jnp.einsum("bqk,bkd->bqd", probs, kv[:, :, i, nope:]))
    y = x + jnp.concatenate(outs, axis=-1) @ p["w_o"]
    if index is None:
        return y, 0.0
    target = jax.lax.stop_gradient(target / heads)   # each head's sums to 1
    logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    kl = jnp.where(target > 0, target * (jnp.log(jnp.where(
        target > 0, target, 1.0)) - jnp.where(keep, logq, 0.0)), 0.0)
    return y, jnp.mean(jnp.sum(kl, axis=-1))


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


def _forward(params, inputs, num_hidden_layers=46, layer_types=None,
             first_k_dense_replace=1, num_attention_heads=128,
             q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128,
             qk_rope_head_dim=64, v_head_dim=128, rope_theta=8e7,
             index_n_heads=64, index_head_dim=128, index_topk=2048,
             swa_num_attention_heads=64, swa_q_lora_rank=1024,
             swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
             swa_qk_rope_head_dim=64, swa_v_head_dim=128, swa_rope_theta=5e4,
             sliding_window_size=513, apply_mla_qkv_lora_rescale=True,
             rms_norm_eps=1e-5, n_routed_experts=256, num_experts_per_tok=8,
             routed_scaling_factor=1.0, experts_held=None, first_expert=0,
             capacity_factor=1.0, **_):
    """(logits, sum over full layers of L_I) of `params` ({op: {weight:
    array}}) on the whole batch, float32 throughout, a layer at a time."""
    (toks,) = inputs
    if layer_types is None:
        layer_types = ["full_attention"] + [
            "sliding_attention" if i % 4 else "full_attention"
            for i in range(num_hidden_layers - 1)]
    full = (num_attention_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
            qk_rope_head_dim, v_head_dim, rms_norm_eps, float(rope_theta),
            None, bool(apply_mla_qkv_lora_rescale),
            (index_n_heads, index_head_dim, index_topk))
    swa = (swa_num_attention_heads, swa_q_lora_rank, swa_kv_lora_rank,
           swa_qk_nope_head_dim, swa_qk_rope_head_dim, swa_v_head_dim,
           rms_norm_eps, float(swa_rope_theta), sliding_window_size,
           bool(apply_mla_qkv_lora_rescale), None)
    moe_cfg = (num_experts_per_tok, first_expert,
               experts_held or n_routed_experts, capacity_factor,
               routed_scaling_factor, rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["tok_embed"]["weight"], toks, axis=0)
        index_loss = 0.0
        for i in range(num_hidden_layers):
            x, term = _attention(
                x, params[f"ln1_{i}"], params[f"attn_{i}"],
                cfg=full if layer_types[i] == "full_attention" else swa)
            index_loss = index_loss + term
            if i < first_k_dense_replace:
                x = _dense_mlp(x, params[f"ln2_{i}"], params[f"mlp_{i}"],
                               eps=rms_norm_eps)
            else:
                x = _expert_mlp(x, params[f"ln2_{i}"], params[f"moe_{i}"],
                                cfg=moe_cfg)
        return (_head(x, params["ln_f"], params["lm_head"], eps=rms_norm_eps),
                index_loss)


def logits(params, inputs, **kw):
    """(batch, seq, vocabulary held) logits on the whole batch."""
    return _forward(params, inputs, **kw)[0]


def loss_terms(params, inputs, labels, **kw):
    """(mean next-token cross-entropy over the vocabulary held, the sum
    over the full layers of L_I), on the whole batch."""
    out, index_loss = _forward(params, inputs, **kw)
    return _mean_nll(out, labels), index_loss


def loss(params, inputs, labels, **kw):
    """The objective: the language-model loss plus every full layer's
    index term, weight 1."""
    lm, index_loss = loss_terms(params, inputs, labels, **kw)
    return lm + index_loss
