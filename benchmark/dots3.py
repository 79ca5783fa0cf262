"""Operations, bytes and parameters of dots3-note-prev's language model as
`flexflow_tpu.models.transformer.build_dots3` builds one chip's share of it,
from the configuration's `builder_kwargs` alone (`flops.py` says how the
formulas are found and what they count).

A multiply-add is two operations; training is three times forward.  The
work counted is what the mathematics needs: attention over the pairs a
query keeps (the index's `index_topk` keys, the window's 513), not what a
masked dense kernel computes; the index's scores over the causal pairs,
once, whatever the passes a float32 product takes on the MXU.  The routed
experts are counted at the device budget's rows.
"""

ACT_BYTES = 2    # activations in bfloat16
PARAM_BYTES = 4  # parameters and their gradients in float32
F32_BYTES = 4    # the index's operands, scores and their gradient


def layer_kinds(num_hidden_layers=46, layer_types=None, **_):
    """The published pattern where none is given: a full layer, then
    periods of full, window, window, window."""
    if layer_types is None:
        layer_types = ["full_attention"] + [
            "sliding_attention" if i % 4 else "full_attention"
            for i in range(num_hidden_layers - 1)]
    assert len(layer_types) == num_hidden_layers
    return list(layer_types)


def kept_pairs(seq_length, keys):
    """(query, key) pairs when query t keeps min(keys, t + 1) keys."""
    full = min(keys, seq_length)
    return full * (full + 1) // 2 + (seq_length - full) * keys


def _sizes(hidden_size=5120, first_k_dense_replace=1, intermediate_size=13824,
           moe_intermediate_size=1536, num_attention_heads=128,
           q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128,
           qk_rope_head_dim=64, v_head_dim=128, index_n_heads=64,
           index_head_dim=128, index_topk=2048, swa_num_attention_heads=64,
           swa_q_lora_rank=1024, swa_kv_lora_rank=1024,
           swa_qk_nope_head_dim=192, swa_qk_rope_head_dim=64,
           swa_v_head_dim=128, sliding_window_size=513, n_routed_experts=256,
           num_experts_per_tok=8, n_shared_experts=1, vocab_size=152064,
           experts_held=None, capacity_factor=1.0, tile_rows=128, **kw):
    d = hidden_size
    kinds = layer_kinds(**kw)
    held = experts_held or n_routed_experts

    def attention(h, q_rank, kv_rank, nope, rope, v):
        """Matrices of a latent attention with its gate, by element."""
        return (d * q_rank + q_rank * h * (nope + rope) + d * (kv_rank + rope)
                + kv_rank * h * (nope + v) + h * v * d + d * h)
    index = (q_lora_rank * index_n_heads * index_head_dim
             + d * index_head_dim + d * index_n_heads)
    return {
        "d": d, "kinds": kinds, "layers": len(kinds),
        "full_layers": kinds.count("full_attention"),
        "window_layers": kinds.count("sliding_attention"),
        "dense_layers": first_k_dense_replace,
        "expert_layers": len(kinds) - first_k_dense_replace,
        "heads": num_attention_heads,
        "qk": qk_nope_head_dim + qk_rope_head_dim, "v": v_head_dim,
        "swa_heads": swa_num_attention_heads,
        "swa_qk": swa_qk_nope_head_dim + swa_qk_rope_head_dim,
        "swa_v": swa_v_head_dim, "window": sliding_window_size,
        "index_heads": index_n_heads, "index_dim": index_head_dim,
        "topk": index_topk, "index": index,
        # matrices, by element; the vectors (norm scales, the index key's
        # LayerNorm) beside them
        "full": attention(num_attention_heads, q_lora_rank, kv_lora_rank,
                          qk_nope_head_dim, qk_rope_head_dim, v_head_dim)
        + index,
        "full_vectors": q_lora_rank + kv_lora_rank + 2 * index_head_dim,
        "swa": attention(swa_num_attention_heads, swa_q_lora_rank,
                         swa_kv_lora_rank, swa_qk_nope_head_dim,
                         swa_qk_rope_head_dim, swa_v_head_dim),
        "swa_vectors": swa_q_lora_rank + swa_kv_lora_rank,
        "dense_mlp": 3 * d * intermediate_size,
        "shared": 3 * d * n_shared_experts * moe_intermediate_size,
        "router": d * n_routed_experts,
        "expert": 3 * d * moe_intermediate_size, "held": held,
        "routed_share": num_experts_per_tok * held / n_routed_experts
        * capacity_factor,
        "vocab": vocab_size}


def parameters(**kw):
    """Parameters this chip holds: embedding and head, and in each layer
    the attention of its kind (the index whole on a full layer) with its
    vectors, the two block norms and the dense MLP or the router, the
    shared expert and the held experts; the final norm.  The selection
    bias is a buffer, not a parameter."""
    z = _sizes(**kw)
    total = 2 * z["vocab"] * z["d"] + z["d"]
    for i, kind in enumerate(z["kinds"]):
        total += 2 * z["d"] + (z["full"] + z["full_vectors"]
                               if kind == "full_attention"
                               else z["swa"] + z["swa_vectors"])
        total += z["dense_mlp"] if i < z["dense_layers"] else (
            z["router"] + z["shared"] + z["held"] * z["expert"])
    return total


def matmul_params_per_token(**kw):
    """Matrix elements a token is multiplied with in a forward pass: the
    head, each layer's attention projections (gate and index projections
    among them), the dense MLP or the router, the shared expert and the
    budget's share of a routed expert."""
    z = _sizes(**kw)
    return (z["vocab"] * z["d"] + z["full_layers"] * z["full"]
            + z["window_layers"] * z["swa"]
            + z["dense_layers"] * z["dense_mlp"]
            + z["expert_layers"] * (z["router"] + z["shared"]
                                    + z["routed_share"] * z["expert"]))


def selected_attention_forward(seq_length=8192, **kw):
    """FLOPs per sample and full layer of the main attention's two
    products over the selected pairs, forward."""
    z = _sizes(**kw)
    return 2.0 * kept_pairs(seq_length, z["topk"]) * z["heads"] \
        * (z["qk"] + z["v"])


def window_attention_forward(seq_length=8192, **kw):
    """FLOPs per sample and window layer of attention's two products over
    the window's pairs, forward."""
    z = _sizes(**kw)
    return 2.0 * kept_pairs(seq_length, z["window"]) * z["swa_heads"] \
        * (z["swa_qk"] + z["swa_v"])


def index_scores_forward(seq_length=8192, **kw):
    """FLOPs per sample and full layer of the index's scores over the
    causal pairs, forward: every index head's product with the key."""
    z = _sizes(**kw)
    return 2.0 * kept_pairs(seq_length, seq_length) * z["index_heads"] \
        * z["index_dim"]


def train_flops(seq_length=8192, **kw):
    """FLOPs per sample (one sequence) of one training step: 6 x the
    matrix elements a token passes through, plus three times the forward
    count of the index's scores, of the main attention over the selected
    pairs and of the window layers' attention over theirs.  Lookups,
    norms, rotary positions, SiLU, the softmaxes, the top-k, the routing,
    the KL term and whatever a kernel recomputes or masks are not
    counted."""
    z = _sizes(**kw)
    return 6.0 * matmul_params_per_token(**kw) * seq_length \
        + 3.0 * z["full_layers"] * (index_scores_forward(seq_length, **kw)
                                    + selected_attention_forward(seq_length,
                                                                 **kw)) \
        + 3.0 * z["window_layers"] * window_attention_forward(seq_length, **kw)


def index_train(batch, seq_length=8192, **kw):
    """(FLOPs, bytes) per step under `ff.dsa.index`, forward and backward,
    over the full layers: the index's three projections and its scores.
    Bytes: the index's queries (float32) written and read forward, read
    and their gradient written backward; the scores written once and
    their gradient read once (float32, seq x seq); the projections'
    inputs and weights are small beside them and left out."""
    z = _sizes(**kw)
    flops = 3.0 * (2.0 * seq_length * z["index"]
                   + index_scores_forward(seq_length, **kw))
    nbytes = (4 * seq_length * z["index_heads"] * z["index_dim"]
              + 2 * seq_length * seq_length) * F32_BYTES
    return flops * batch * z["full_layers"], \
        float(nbytes) * batch * z["full_layers"]


def _core_bytes(seq_length, heads, qk, v):
    """q, k, v read and o written forward; q, k, v, o, do read and dq, dk,
    dv written backward; the row statistics are left out."""
    return seq_length * heads * ((2 + 4) * qk + (2 + 4) * v) * ACT_BYTES


def selected_attention_train(batch, seq_length=8192, **kw):
    """(FLOPs, bytes) per step of the main attention's core on the full
    layers, forward and backward, over the selected pairs.  Bytes: the
    core's operands as `_core_bytes`, and the selection (bfloat16, seq x
    seq) read by each of the three kernels."""
    z = _sizes(**kw)
    flops = 3.0 * selected_attention_forward(seq_length, **kw)
    nbytes = _core_bytes(seq_length, z["heads"], z["qk"], z["v"]) \
        + 3 * seq_length * seq_length * ACT_BYTES
    return flops * batch * z["full_layers"], \
        float(nbytes) * batch * z["full_layers"]


def window_attention_train(batch, seq_length=8192, **kw):
    """(FLOPs, bytes) per step of the window layers' attention core,
    forward and backward, over the window's pairs."""
    z = _sizes(**kw)
    flops = 3.0 * window_attention_forward(seq_length, **kw)
    nbytes = _core_bytes(seq_length, z["swa_heads"], z["swa_qk"], z["swa_v"])
    return flops * batch * z["window_layers"], \
        float(nbytes) * batch * z["window_layers"]
