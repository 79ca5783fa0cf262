"""Phi-4-mini-flash-reasoning's language model (microsoft/Phi-4-mini-flash-
reasoning config.json; the SambaY decoder-hybrid-decoder of arXiv:2507.06607)
for training, in plain `jax.numpy` and float32: token embedding, pre-norm
blocks whose mixer is a Mamba layer, window or full attention, a gated
memory unit or cross-attention by the layer's index, SiLU-gated MLPs, a
final LayerNorm, the head tied to the embedding's table, and the mean
next-token cross-entropy.  No kernels, no cache: the scan is a sequential
`lax.scan` over the tokens; attention's scores are held a block of queries
at a time, every head at once, so that a layer fits on one chip at 8192
tokens; matrix products at the highest precision.  Written from the layer
equations, not from the program's ops.

The equations, `h = LN(x)`, every LayerNorm with eps `layer_norm_eps`, a
learned scale and a bias; N the published depth, i a layer's published
index:

    layout      i < N/2: Mamba where i % mb_per_layer == 0, else window
                attention; i = N/2: Mamba, its g kept as the memory m;
                i = N/2 + 1: full attention, its k and v kept; i > N/2 + 1:
                a gated memory unit where i % mb_per_layer == 0, else
                cross-attention
    Mamba       [u, z] = h W_in;  u = silu(conv(u) + b_conv), conv causal
                and depthwise over d_conv taps: sum_j w_j u_{t-d_conv+1+j}
                [delta, B, C] = u W_x;  Delta = softplus(delta W_dt + b_dt)
                A = -exp(A_log);  s_t = exp(Delta_t A) s_{t-1} + Delta_t
                u_t B_t, s_{-1} = 0;  y_t = s_t C_t + D u_t
                g = y silu(z);  out = g W_out
    attention   q = h W_q, k = h W_k, v = h W_v, heads of hidden / heads,
                query head j reading key/value head j // group; no
                positional encoding; score_ts = q_t . k_s head_dim^-0.5
                over s <= t (full), t - window < s <= t (window);
                out = concat_j(softmax_s(score) v) W_O
    cross       q = h W_q against the kept k and v, causal, then W_O
    GMU         out = (m silu(h W_1)) W_2
    MLP         (silu(h W_gate) (h W_up)) W_down, no bias
    model       x += mixer(LN1 x); x += MLP(LN2 x); logits = LN_f(x) E^T
    objective   mean_t -log softmax(logits)[label]

Departures from the published model, as the configuration file lists them
under `assumed`: softmax attention where SambaY also describes a
differential variant (neither the config nor its description names it);
the Mamba sizes (d_state 16, d_conv 4, expand 2, dt_rank ceil(hidden / 16))
and biases (convolution and W_dt alone) are the Mamba family's defaults;
the memory is read after the z gate; and

* **The chip's share.**  `layers` are the published indices built, in
  order; `vocab_size` is the rows of the vocabulary held, ids are drawn
  from them, and the logits and the loss are over them.
"""

import functools
import math

import jax
import jax.numpy as jnp

CHUNK = 1 << 30  # the whole batch a call
QUERY_BLOCK = 512  # queries whose scores are held at once, every head


def make_batch(key, batch_size, seq_length=8192, vocab_size=200064, **_):
    """One synthetic batch from the key: ((tokens,), labels), token ids
    uniform over the vocabulary rows held, labels the next token, the
    last wrapping round."""
    toks = jax.random.randint(key, (batch_size, seq_length), 0, vocab_size,
                              jnp.int32)
    return (toks,), jnp.roll(toks, -1, axis=1)


def layout(num_hidden_layers=32, mb_per_layer=2):
    """The mixer of each published layer (module docstring)."""
    half = num_hidden_layers // 2
    out = []
    for i in range(num_hidden_layers):
        if i == half:
            out.append("memory")
        elif i == half + 1:
            out.append("full")
        elif i < half:
            out.append("mamba" if i % mb_per_layer == 0 else "window")
        else:
            out.append("gmu" if i % mb_per_layer == 0 else "cross")
    return out


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def scan(u, delta, a, b, c):
    """y_t = s_t C_t of the recurrence, one token at a time: u and delta
    (batch, seq, d), a (d, n), b and c (batch, seq, n)."""
    def step(s, inp):
        u_t, d_t, b_t, c_t = inp
        s = jnp.exp(d_t[:, :, None] * a[None]) * s \
            + (d_t * u_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)
    s0 = jnp.zeros((u.shape[0], u.shape[2], a.shape[1]), jnp.float32)
    first = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, s0, (first(u), first(delta), first(b),
                                   first(c)))
    return jnp.moveaxis(y, 0, 1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _mamba(x, norm, p, eps):
    """(x + out, g) of a Mamba layer."""
    h = _layer_norm(x, norm, eps)
    d = p["out_proj"].shape[0]
    rank = p["dt_proj"].shape[0]
    n = p["a_log"].shape[1]
    uz = h @ p["in_proj"]
    u, z = uz[..., :d], uz[..., d:]
    taps = p["conv_weight"].shape[0]
    s = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(p["conv_weight"][j] * padded[:, j:j + s] for j in range(taps))
    u = _silu(conv + p["conv_bias"])
    dbc = u @ p["x_proj"]
    delta = _softplus(dbc[..., :rank] @ p["dt_proj"] + p["dt_bias"])
    y = scan(u, delta, -jnp.exp(p["a_log"]), dbc[..., rank:rank + n],
             dbc[..., rank + n:]) + p["d"] * u
    g = y * _silu(z)
    return x + g @ p["out_proj"], g


def attend(q, k, v, window):
    """Causal attention of q (b, s, heads, d) over k and v (b, s, kv
    heads, d), query head j reading key/value head j // group, a block of
    queries at a time; keys s <= t, and s > t - window where `window` is
    given.  Returns (b, s, heads, d)."""
    b, s, heads, dim = q.shape
    group = heads // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)

    def rows(args):
        qb, first = args
        t = first + jnp.arange(block)
        keep = keys[None, :] <= t[:, None]
        if window is not None:
            keep = keep & (keys[None, :] > t[:, None] - window)
        score = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(dim)
        score = jnp.where(keep, score, -jnp.inf)
        p = jnp.exp(score - jnp.max(score, axis=-1, keepdims=True))
        return jnp.einsum("bhqk,bkhd->bqhd",
                          p / jnp.sum(p, axis=-1, keepdims=True), v)
    blocks = jnp.moveaxis(q.reshape((b, s // block, block) + q.shape[2:]),
                          1, 0)
    out = jax.lax.map(rows, (blocks, jnp.arange(0, s, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, heads, dim)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "window",
                                             "eps"))
def _attention(x, norm, p, kv, heads, kv_heads, window, eps):
    """(x + out, k, v) of an attention layer; `kv` the kept (k, v) for
    cross-attention, else None."""
    h = _layer_norm(x, norm, eps)
    b, s, e = x.shape
    dim = e // heads
    q = (h @ p["w_q"]).reshape(b, s, heads, dim)
    k, v = kv if kv is not None else (h @ p["w_k"], h @ p["w_v"])
    out = attend(q, k.reshape(b, s, kv_heads, dim),
                 v.reshape(b, s, kv_heads, dim), window)
    return x + out.reshape(b, s, e) @ p["w_o"], k, v


@functools.partial(jax.jit, static_argnames=("eps",))
def _gmu(x, norm, p, m, eps):
    h = _layer_norm(x, norm, eps)
    return x + (m * _silu(h @ p["in_proj"])) @ p["out_proj"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _mlp(x, norm, p, eps):
    h = _layer_norm(x, norm, eps)
    return x + (_silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, table, eps):
    return _layer_norm(x, norm, eps) @ table.T


@jax.jit
def _mean_nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.mean(-jnp.take_along_axis(logp, labels[..., None], axis=-1))


def _forward(params, inputs, num_hidden_layers=32, layers=None,
             num_attention_heads=40, num_key_value_heads=20,
             sliding_window=512, mb_per_layer=2, layer_norm_eps=1e-5, **_):
    """Logits of `params` ({op: {weight: array}}) on the whole batch,
    float32 throughout, a layer at a time."""
    (toks,) = inputs
    kinds = layout(num_hidden_layers, mb_per_layer)
    kept = [kinds[i] for i in (layers if layers is not None
                               else range(num_hidden_layers))]
    eps = layer_norm_eps
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["tok_embed"]["weight"], toks, axis=0)
        memory, kv = None, None
        for i, kind in enumerate(kept):
            norm, p = params[f"ln1_{i}"], params[f"{kind}_{i}"]
            if kind in ("mamba", "memory"):
                x, g = _mamba(x, norm, p, eps=eps)
                if kind == "memory":
                    memory = g
            elif kind == "gmu":
                x = _gmu(x, norm, p, memory, eps=eps)
            else:
                x, k, v = _attention(
                    x, norm, p, kv if kind == "cross" else None,
                    heads=num_attention_heads, kv_heads=num_key_value_heads,
                    window=sliding_window if kind == "window" else None,
                    eps=eps)
                if kind == "full":
                    kv = (k, v)
            x = _mlp(x, params[f"ln2_{i}"], params[f"mlp_{i}"], eps=eps)
        return _head(x, params["ln_f"], params["tok_embed"]["weight"],
                     eps=eps)


def logits(params, inputs, **kw):
    """(batch, seq, vocabulary held) logits on the whole batch."""
    return _forward(params, inputs, **kw)


def loss(params, inputs, labels, **kw):
    """Mean next-token cross-entropy over the vocabulary held."""
    return _mean_nll(_forward(params, inputs, **kw), labels)
