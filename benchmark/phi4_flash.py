"""Operations, bytes and parameters of Phi-4-mini-flash-reasoning's language
model as `flexflow_tpu.models.transformer.build_phi4_flash` builds the layers
it is given, from the configuration's `builder_kwargs` alone (`flops.py` says
how the formulas are found and what they count).

A multiply-add is two operations; training is three times forward.  Full and
cross-attention are counted over the causal square as `mimo_v2` counts a
full layer (S x S pairs a head, half of them masked: what the flash kernels
are given), window attention over the band's pairs alone
(`dots3.kept_pairs`).  The head is the token embedding's table, tied: its
product is counted once, the lookup not at all.
"""

import math

from .dots3 import kept_pairs

ACT_BYTES = 2    # activations in bfloat16
F32_BYTES = 4    # the scan's state, B, C, A and their gradients
# Tokens between the states the scan's forward keeps for its backward
# (kernels/ssm_scan.py), one (d_inner, d_state) float32 state a chunk.
SCAN_CHUNK = 128


def layer_kinds(num_hidden_layers=32, layers=None, mb_per_layer=2, **_):
    """The kinds of the layers built, in order: SambaY's layout over the
    published depth (``mamba``, ``window`` in the self-decoder; the
    ``memory`` Mamba layer and the ``full`` attention layer at N/2 and N/2
    + 1; ``gmu``, ``cross`` in the cross-decoder), read at ``layers``."""
    half = num_hidden_layers // 2

    def kind(i):
        if i == half:
            return "memory"
        if i == half + 1:
            return "full"
        even = i % mb_per_layer == 0
        if i < half:
            return "mamba" if even else "window"
        return "gmu" if even else "cross"
    return [kind(i) for i in (range(num_hidden_layers) if layers is None
                              else layers)]


def _sizes(hidden_size=2560, intermediate_size=10240, num_attention_heads=40,
           num_key_value_heads=20, d_state=16, d_conv=4, expand=2,
           dt_rank=None, vocab_size=200064, **kw):
    d = hidden_size
    di = expand * d
    rank = dt_rank or math.ceil(d / 16)
    head = d // num_attention_heads
    kinds = layer_kinds(**kw)
    q_o = 2 * d * num_attention_heads * head
    kv = 2 * d * num_key_value_heads * head
    return {
        "d": d, "d_inner": di, "d_state": d_state,
        "heads": num_attention_heads,
        "head_dim": head, "kinds": kinds, "vocab": vocab_size,
        "mamba_layers": kinds.count("mamba") + kinds.count("memory"),
        # matrices by element: W_in, W_x, W_dt, W_out
        "mamba_matmul": d * 2 * di + di * (rank + 2 * d_state) + rank * di
        + di * d,
        # the convolution's taps and bias, b_dt, A_log, D
        "mamba_other": d_conv * di + di + di + di * d_state + di,
        "attention": q_o + kv, "cross": q_o, "gmu": 2 * d * di,
        "mlp": 3 * d * intermediate_size}


def _mixer(z, kind, which):
    if kind in ("mamba", "memory"):
        return z["mamba_" + which] if which != "params" else \
            z["mamba_matmul"] + z["mamba_other"]
    if which == "other":
        return 0
    return {"window": z["attention"], "full": z["attention"],
            "cross": z["cross"], "gmu": z["gmu"]}[kind]


def parameters(**kw):
    """Parameters the built layers hold: the tied table once, and in each
    layer its mixer, its MLP and two LayerNorms (scale and bias); the
    final LayerNorm."""
    z = _sizes(**kw)
    total = z["vocab"] * z["d"] + 2 * z["d"]
    for kind in z["kinds"]:
        total += _mixer(z, kind, "params") + z["mlp"] + 4 * z["d"]
    return total


def matmul_params_per_token(**kw):
    """Matrix elements a token is multiplied with in a forward pass: the
    tied head, each layer's mixer matrices (the convolution's four taps a
    channel are not a product) and its MLP."""
    z = _sizes(**kw)
    return z["vocab"] * z["d"] + sum(_mixer(z, k, "matmul") + z["mlp"]
                                     for k in z["kinds"])


def causal_attention_forward(seq_length=8192, **kw):
    """FLOPs per sample and full or cross layer of causal attention's two
    products, forward: 2 x S x S x heads x head each, half masked."""
    z = _sizes(**kw)
    return float(seq_length) * seq_length * z["heads"] * 2 * z["head_dim"]


def window_attention_forward(seq_length=8192, sliding_window=512, **kw):
    """FLOPs per sample and window layer of attention's two products over
    the window's pairs, forward."""
    z = _sizes(**kw)
    return 2.0 * kept_pairs(seq_length, sliding_window) * z["heads"] \
        * 2 * z["head_dim"]


def train_flops(seq_length=8192, **kw):
    """FLOPs per sample (one sequence) of one training step: 6 x the
    matrix elements a token passes through, plus three times the forward
    count of the full and cross layers' attention over the causal square
    and of the window layers' over the band.  The scan (``ssm_scan_train``:
    no MXU work), the convolution, norms, gates, softmaxes and whatever a
    kernel recomputes or masks are not counted."""
    z = _sizes(**kw)
    causal = sum(k in ("full", "cross") for k in z["kinds"])
    return 6.0 * matmul_params_per_token(**kw) * seq_length \
        + 3.0 * causal * causal_attention_forward(seq_length, **kw) \
        + 3.0 * z["kinds"].count("window") * window_attention_forward(
            seq_length, **kw)


# Element operations the scan needs a (token, channel, state), forward:
# Delta a, its exponential, the decay times the state, Delta u times B,
# their sum, the state times C and the sum over the states.  Backward, the
# adjoint: dy C and the carried adjoint summed, its products for dB, dC,
# d(Delta u) and their sums, Delta a and its exponential again, the
# adjoint times the state before and the decay, for dDelta and dA and
# their sums, and the adjoint carried back.  What the kernels recompute
# (the forward's states a chunk again) is not counted.
SCAN_OPS_FWD = 7
SCAN_OPS_BWD = 15


def ssm_scan_train(batch, seq_length=8192, **kw):
    """(operations, bytes) per step of the selective scans, forward and
    backward, over the Mamba layers built.

    Bytes, a layer and a sequence, at the dtypes the op gives the scan
    (kernels/ssm_scan.py): forward x and Delta read and y written (bf16,
    T x d_inner each), B and C read (f32, T x 2 d_state), A read, and the
    state at each chunk's start written (f32, T / 128 x d_inner x
    d_state); backward x, Delta and dy read and dx and dDelta written
    (bf16), B, C, A and the chunk states read, dB, dC and dA written (f32).

    Operations: ``SCAN_OPS_FWD`` + ``SCAN_OPS_BWD`` element operations a
    token, channel and state (22 x 8192 x 5120 x 16 = 14.8 G a layer).
    None of them is a product on the MXU: they run on the VPU and the EUP
    (the exponentials, two a token, channel and state), whose peak is not
    the bf16 peak the roofline divides by.  At that peak they are 0.08 ms
    a layer, so the share this formula gives is the bytes' share: 0.717
    GB a layer at 8192 tokens, 0.88 ms at 819 GB/s.  What bounds
    the kernels is the VPU: PERF.md gives their element operations a
    second as measured on the v5e, beside this share."""
    z = _sizes(**kw)
    t, di, n = seq_length, z["d_inner"], z["d_state"]
    act = t * di * ACT_BYTES
    bc = t * 2 * n * F32_BYTES
    a = di * n * F32_BYTES
    states = (t // SCAN_CHUNK) * di * n * F32_BYTES
    nbytes = (3 * act + bc + a + states) + (5 * act + 2 * bc + 2 * a
                                            + states)
    ops = (SCAN_OPS_FWD + SCAN_OPS_BWD) * float(t) * di * n
    layers = z["mamba_layers"] * batch
    return ops * layers, float(nbytes) * layers
