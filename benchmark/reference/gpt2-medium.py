"""GPT-2 (Radford et al. 2019; openai-community/gpt2-medium) as
`models/transformer.py` builds it, in plain `jax.numpy` and float32:
learned token and position embeddings, pre-LayerNorm blocks of causal
multi-head attention and a biased GELU MLP, a final
LayerNorm and an output head, then mean next-token cross-entropy.  No
kernels, no cache, scores materialised, matrix products at the highest
precision.

Departures from the published model, as the configuration file lists
them: the head is not tied to the token embedding and has a bias; the
attention projections have a bias only where the parameters hold one
(`build_transformer` gives them none); dropout is 0.  GELU is the tanh
approximation (`gelu_new`).
"""

import functools

import jax
import jax.numpy as jnp

CHUNK = 2  # sequences per call of loss(); the harness averages chunks


def make_batch(key, batch_size, seq_length=1024, vocab_size=50257, **_):
    """One synthetic batch from the key: ((tokens, positions), labels),
    labels the next token, the last wrapping round as
    `synthetic_lm_batch` has it."""
    toks = jax.random.randint(key, (batch_size, seq_length), 0, vocab_size,
                              jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(seq_length, dtype=jnp.int32),
                           (batch_size, seq_length))
    return (toks, pos), jnp.roll(toks, -1, axis=1)


def _layer_norm(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


@functools.partial(jax.jit, static_argnames="num_heads")
def _block(x, ln1, attn, ln2, up, down, num_heads):
    b, s, e = x.shape
    h = _layer_norm(x, ln1)
    q, k, v = (h @ attn["w" + n] + attn.get("b" + n, 0.0) for n in "qkv")
    heads = lambda t: t.reshape(b, s, num_heads, -1).transpose(0, 2, 1, 3)
    q, k, v = heads(q), heads(k), heads(v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, e)
    x = x + o @ attn["wo"] + attn.get("bo", 0.0)
    h = _layer_norm(x, ln2)
    h = jax.nn.gelu(h @ up["kernel"] + up["bias"], approximate=True)
    return x + h @ down["kernel"] + down["bias"]


@jax.jit
def _embed(tok_w, pos_w, toks, pos):
    return jnp.take(tok_w, toks, axis=0) + jnp.take(pos_w, pos, axis=0)


@jax.jit
def _head_loss(x, ln_f, head, labels):
    logits = _layer_norm(x, ln_f) @ head["kernel"] + head["bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return jnp.mean(nll)


def loss(params, inputs, labels, num_layers=24, num_heads=16, **_):
    """Mean next-token cross-entropy of `params` ({op: {weight: array}})
    on one chunk of sequences, float32 throughout."""
    toks, pos = inputs
    with jax.default_matmul_precision("highest"):
        x = _embed(params["tok_embed"]["weight"],
                   params["pos_embed"]["weight"], toks, pos)
        for i in range(num_layers):
            x = _block(x, params[f"ln1_{i}"], params[f"attn_{i}"],
                       params[f"ln2_{i}"], params[f"mlp_up_{i}"],
                       params[f"mlp_down_{i}"], num_heads=num_heads)
        return _head_loss(x, params["ln_f"], params["lm_head"], labels)
