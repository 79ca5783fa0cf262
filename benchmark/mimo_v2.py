"""Operations, bytes and parameters of MiMo-V2-Flash's language model as
`flexflow_tpu.models.transformer.build_mimo_v2_flash` builds one chip's share
of it, from the configuration's `builder_kwargs` alone (`flops.py` says how
the formulas are found and what they count).

A multiply-add is two operations; training is three times forward.  A full
layer's attention is counted over the causal square as `deepseek_v2` counts
it (S x S pairs a head, half of them masked: what the flash kernels are
given), a window layer's over the band's pairs alone (`dots3.kept_pairs`).
The routed experts are counted at the device budget's rows.
"""

from .dots3 import kept_pairs

ACT_BYTES = 2    # activations in bfloat16


def layer_kinds(num_hidden_layers=48, hybrid_layer_pattern=None,
                moe_layer_freq=None, **_):
    """(hybrid_layer_pattern, moe_layer_freq): the published patterns
    where none are given (layer 0 and every layer i with i % 6 == 5 full,
    layer 0 dense)."""
    pattern = hybrid_layer_pattern or [
        0 if i == 0 or i % 6 == 5 else 1 for i in range(num_hidden_layers)]
    moe = moe_layer_freq or [0] + [1] * (num_hidden_layers - 1)
    assert len(pattern) == len(moe) == num_hidden_layers
    return list(pattern), list(moe)


def _sizes(hidden_size=4096, intermediate_size=16384,
           moe_intermediate_size=2048, num_attention_heads=64,
           num_key_value_heads=4, head_dim=192, v_head_dim=128,
           swa_num_attention_heads=64, swa_num_key_value_heads=8,
           swa_head_dim=192, swa_v_head_dim=128, sliding_window=128,
           add_swa_attention_sink_bias=True,
           add_full_attention_sink_bias=False, n_routed_experts=256,
           num_experts_per_tok=8, vocab_size=152576, experts_held=None,
           capacity_factor=1.0, **kw):
    d = hidden_size
    pattern, moe = layer_kinds(**kw)
    held = experts_held or n_routed_experts

    def attention(h, kv, qk, v):
        """W_q, W_k, W_v, W_O by element."""
        return d * h * qk + d * kv * qk + d * kv * v + h * v * d
    return {
        "d": d, "pattern": pattern, "moe": moe,
        "full_layers": pattern.count(0), "window_layers": pattern.count(1),
        "dense_layers": moe.count(0), "expert_layers": moe.count(1),
        "full": (num_attention_heads, num_key_value_heads, head_dim,
                 v_head_dim),
        "swa": (swa_num_attention_heads, swa_num_key_value_heads,
                swa_head_dim, swa_v_head_dim),
        "window": sliding_window,
        "full_attention": attention(num_attention_heads, num_key_value_heads,
                                    head_dim, v_head_dim),
        "swa_attention": attention(swa_num_attention_heads,
                                   swa_num_key_value_heads, swa_head_dim,
                                   swa_v_head_dim),
        # a sink a held query head, where the layer's kind has one
        "full_sinks": num_attention_heads * bool(add_full_attention_sink_bias),
        "swa_sinks": swa_num_attention_heads
        * bool(add_swa_attention_sink_bias),
        "dense_mlp": 3 * d * intermediate_size,
        "router": d * n_routed_experts,
        "expert": 3 * d * moe_intermediate_size, "held": held,
        "routed_share": num_experts_per_tok * held / n_routed_experts
        * capacity_factor,
        "vocab": vocab_size}


def parameters(**kw):
    """Parameters this chip holds: embedding and head, and in each layer
    the attention of its kind with its sinks, the two block norms and the
    dense MLP or the router and the held experts; the final norm.  The
    selection bias is a buffer, not a parameter."""
    z = _sizes(**kw)
    total = 2 * z["vocab"] * z["d"] + z["d"]
    for kind, moe in zip(z["pattern"], z["moe"]):
        total += 2 * z["d"] + (z["swa_attention"] + z["swa_sinks"] if kind
                               else z["full_attention"] + z["full_sinks"])
        total += z["router"] + z["held"] * z["expert"] if moe \
            else z["dense_mlp"]
    return total


def matmul_params_per_token(**kw):
    """Matrix elements a token is multiplied with in a forward pass: the
    head, each layer's attention projections, the dense MLP or the router
    and the budget's share of a routed expert."""
    z = _sizes(**kw)
    return (z["vocab"] * z["d"] + z["full_layers"] * z["full_attention"]
            + z["window_layers"] * z["swa_attention"]
            + z["dense_layers"] * z["dense_mlp"]
            + z["expert_layers"] * (z["router"]
                                    + z["routed_share"] * z["expert"]))


def full_attention_forward(seq_length=8192, **kw):
    """FLOPs per sample and full layer of causal attention's two products,
    forward: QK^T at the query/key head and PV at the value head,
    2 x S x S x heads x width each, half of it masked."""
    heads, _, qk, v = _sizes(**kw)["full"]
    return float(seq_length) * seq_length * heads * (qk + v)


def window_attention_forward(seq_length=8192, **kw):
    """FLOPs per sample and window layer of attention's two products over
    the window's pairs, forward."""
    z = _sizes(**kw)
    heads, _, qk, v = z["swa"]
    return 2.0 * kept_pairs(seq_length, z["window"]) * heads * (qk + v)


def train_flops(seq_length=8192, **kw):
    """FLOPs per sample (one sequence) of one training step: 6 x the
    matrix elements a token passes through, plus three times the forward
    count of the full layers' attention over the causal square and of the
    window layers' over the band.  Lookups, norms, rotary positions, SiLU,
    the softmaxes, the sinks, the routing and whatever a kernel recomputes
    or masks beyond that are not counted."""
    z = _sizes(**kw)
    return 6.0 * matmul_params_per_token(**kw) * seq_length \
        + 3.0 * z["full_layers"] * full_attention_forward(seq_length, **kw) \
        + 3.0 * z["window_layers"] * window_attention_forward(seq_length,
                                                              **kw)


def _core_bytes(seq_length, heads, kv_heads, qk, v):
    """q and k read forward and backward and their gradients written,
    (3 x heads + 3 x kv_heads) x qk a token; v likewise; o written forward
    and read backward with do: 3 x heads x v.  Key/value heads are counted
    once, however many query heads read them; the row statistics are left
    out."""
    return seq_length * (3 * (heads + kv_heads) * qk
                         + 3 * (heads + kv_heads) * v) * ACT_BYTES


def full_attention_train(batch, seq_length=8192, **kw):
    """(FLOPs, bytes) per step of the full layers' attention core, forward
    and backward: what the flash kernels are given (the causal square)."""
    z = _sizes(**kw)
    flops = 3.0 * full_attention_forward(seq_length, **kw)
    nbytes = _core_bytes(seq_length, *z["full"])
    return flops * batch * z["full_layers"], \
        float(nbytes) * batch * z["full_layers"]


def window_attention_train(batch, seq_length=8192, **kw):
    """(FLOPs, bytes) per step of the window layers' attention core,
    forward and backward, over the band's pairs alone."""
    z = _sizes(**kw)
    flops = 3.0 * window_attention_forward(seq_length, **kw)
    nbytes = _core_bytes(seq_length, *z["swa"])
    return flops * batch * z["window_layers"], \
        float(nbytes) * batch * z["window_layers"]
