"""Operations, bytes and parameters of DeepSeek-V2 as
`flexflow_tpu.models.transformer.build_deepseek_v2` builds one chip's share
of it, from the configuration's `builder_kwargs` alone (`flops.py` says how
the formulas are found and what they count).

A multiply-add is two operations; training is three times forward.  The
routed experts are counted at the device budget's rows, whatever the
router sent: `ceil(tokens * top_k * held / routed * capacity_factor)`.
"""

import math

ACT_BYTES = 2    # activations in bfloat16
PARAM_BYTES = 4  # parameters and their gradients in float32


def _sizes(hidden_size=5120, num_hidden_layers=60, first_k_dense_replace=1,
           intermediate_size=12288, moe_intermediate_size=1536,
           num_attention_heads=128, q_lora_rank=1536, kv_lora_rank=512,
           qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
           n_routed_experts=160, num_experts_per_tok=6, n_shared_experts=2,
           vocab_size=102400, experts_held=None, capacity_factor=1.0,
           tile_rows=128, **_):
    d, h = hidden_size, num_attention_heads
    held = experts_held or n_routed_experts
    return {
        "d": d, "heads": h, "qk": qk_nope_head_dim + qk_rope_head_dim,
        "v": v_head_dim, "width": moe_intermediate_size, "held": held,
        "dense_layers": first_k_dense_replace,
        "expert_layers": num_hidden_layers - first_k_dense_replace,
        "layers": num_hidden_layers, "tile_rows": tile_rows,
        # rows of the budget a token, an expert layer
        "routed_share": num_experts_per_tok * held / n_routed_experts
        * capacity_factor,
        "budget": lambda tokens: min(
            tokens * min(num_experts_per_tok, held), math.ceil(
                tokens * num_experts_per_tok * held / n_routed_experts
                * capacity_factor)),
        # matrices, by element
        "attention": d * q_lora_rank
        + q_lora_rank * h * (qk_nope_head_dim + qk_rope_head_dim)
        + d * (kv_lora_rank + qk_rope_head_dim)
        + kv_lora_rank * h * (qk_nope_head_dim + v_head_dim)
        + h * v_head_dim * d,
        "attention_norms": q_lora_rank + kv_lora_rank,
        "dense_mlp": 3 * d * intermediate_size,
        "shared": 3 * d * n_shared_experts * moe_intermediate_size,
        "router": d * n_routed_experts,
        "expert": 3 * d * moe_intermediate_size,
        "vocab": vocab_size}


def parameters(**kw):
    """Parameters this chip holds: embedding and head, and in each layer
    the attention with its two latent norms, the two block norms and the
    dense MLP or the router, the shared experts and the held experts; the
    final norm."""
    z = _sizes(**kw)
    block = z["attention"] + z["attention_norms"] + 2 * z["d"]
    return (2 * z["vocab"] * z["d"] + z["d"]
            + z["dense_layers"] * (block + z["dense_mlp"])
            + z["expert_layers"] * (block + z["router"] + z["shared"]
                                    + z["held"] * z["expert"]))


def matmul_params_per_token(**kw):
    """Matrix elements a token is multiplied with in a forward pass: the
    head, each layer's attention projections, the dense MLP or the router,
    the shared experts and the budget's share of a routed expert."""
    z = _sizes(**kw)
    return (z["vocab"] * z["d"] + z["layers"] * z["attention"]
            + z["dense_layers"] * z["dense_mlp"]
            + z["expert_layers"] * (z["router"] + z["shared"]
                                    + z["routed_share"] * z["expert"]))


def mla_attention_forward(seq_length=4096, **kw):
    """FLOPs per sample and layer of causal latent attention's two
    products, forward: QK^T at the query/key head and PV at the value
    head, 2*S*S*heads*width each, half of it masked."""
    z = _sizes(**kw)
    return float(seq_length) * seq_length * z["heads"] * (z["qk"] + z["v"])


def train_flops(seq_length=4096, **kw):
    """FLOPs per sample (one sequence) of one training step: 6 x the
    matrix elements a token passes through, plus causal attention forward
    and twice that backward.  Lookups, norms, rotary positions, SiLU, the
    softmaxes, the routing and the flash kernels' recomputation are not
    counted."""
    z = _sizes(**kw)
    return 6.0 * matmul_params_per_token(**kw) * seq_length \
        + 3.0 * mla_attention_forward(seq_length, **kw) * z["layers"]


def mla_attention_train(batch, seq_length=4096, **kw):
    """(FLOPs, bytes) per step of the attention core, forward and backward,
    over all layers: what the flash kernels are given to do.  Forward reads
    q, k (query/key head) and v and writes o (value head); backward reads
    q, k, v, o, do and writes dq, dk, dv; the row statistics are left out."""
    z = _sizes(**kw)
    flops = 3.0 * mla_attention_forward(seq_length, **kw) * z["layers"] \
        * batch
    per_token_head = (2 + 4) * z["qk"] + (2 + 4) * z["v"]
    return flops, float(batch * seq_length * z["heads"] * per_token_head
                        * ACT_BYTES * z["layers"])


def routed_experts_train(batch, seq_length=4096, **kw):
    """(FLOPs, bytes) per step of the held experts' products, forward and
    backward, over the expert layers.  Rows: the budget's buffer, each
    expert's group padded to the row tile (`ceil(budget / tile) + held`
    tiles, which the grouped product runs over whatever their fill).
    Bytes: the held experts' three matrices read as stored (float32) in
    the forward pass and again for the input gradient, their gradient
    written once; each row's input, output and the two products it keeps
    for the backward pass, written and read once each way."""
    z = _sizes(**kw)
    budget = z["budget"](batch * seq_length)
    rows = (-(-budget // z["tile_rows"]) + z["held"]) * z["tile_rows"]
    weights = z["held"] * z["expert"]
    flops = 3.0 * 2.0 * rows * z["expert"]
    nbytes = 3.0 * weights * PARAM_BYTES \
        + rows * 4 * (z["d"] + z["width"]) * ACT_BYTES
    return flops * z["expert_layers"], nbytes * z["expert_layers"]
