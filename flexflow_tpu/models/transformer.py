"""Decoder-only transformer LM — the long-context flagship.

The reference predates transformers; this model exists to exercise the
TPU-first capabilities the framework adds on top of the reference's
feature set: fused flash attention (pallas), ring-attention sequence
parallelism, and hybrid dp×sp×tp shardings of one op graph.  The graph is
built through the same FFModel op vocabulary as every reference model
(embedding/dense/layer_norm/multihead_attention/element add), so the
strategy machinery (SOAP configs, MCMC search, protobuf export) applies
to it unchanged.
"""

from __future__ import annotations

from ..model import FFModel
from ..ops.embedding import AggrMode


def build_decoder(ff: FFModel, batch_size: int, seq_length: int,
                  num_layers: int, embed_dim: int, vocab_size: int, *,
                  norm, attention, mlp, head, learned_positions: bool):
    """A pre-norm decoder-only LM from its block's parts; returns the
    input tensors and the softmax output, ``(tokens[, positions], out)``.

    ``norm(ff, x, name)`` is the normalisation (before attention as
    ``ln1_<i>``, before the MLP as ``ln2_<i>``, before the head as
    ``ln_f``); ``attention(ff, h, i)`` and ``mlp(ff, h, i)`` build layer
    ``i``'s two residual branches, so a model may change either by layer
    (a leading dense MLP, then experts); ``head(ff, x)`` gives the logits
    (an untied dense layer today: no op shares an embedding's table with
    a dense kernel).  With ``learned_positions`` a second input carries
    0..S-1 per row into a position table added to the token embedding;
    without, positions are the attention's own business (rotary)."""
    inputs = tuple(
        ff.create_tensor((batch_size, seq_length), name=name, dtype="int32",
                         nchw=False)
        for name in ("tokens", "positions")[:1 + learned_positions])
    x = ff.embedding(inputs[0], vocab_size, embed_dim, aggr=AggrMode.NONE,
                     name="tok_embed")
    if learned_positions:
        p = ff.embedding(inputs[1], seq_length, embed_dim,
                         aggr=AggrMode.NONE, name="pos_embed")
        x = ff.add(x, p, name="embed_add")
    for i in range(num_layers):
        h = attention(ff, norm(ff, x, f"ln1_{i}"), i)
        x = ff.add(x, h, name=f"res_attn_{i}")
        h = mlp(ff, norm(ff, x, f"ln2_{i}"), i)
        x = ff.add(x, h, name=f"res_mlp_{i}")
    logits = head(ff, norm(ff, x, "ln_f"))
    return inputs + (ff.softmax(logits, name="softmax"),)


def build_transformer(ff: FFModel, batch_size: int, seq_length: int = 256,
                      num_layers: int = 4, embed_dim: int = 512,
                      num_heads: int = 8, mlp_ratio: int = 4,
                      vocab_size: int = 32000, dropout: float = 0.0,
                      moe_every: int = 0, num_experts: int = 8):
    """The GPT-2-style decoder: learned positions, LayerNorm, one head
    count, a biased GELU MLP (or, every ``moe_every`` layers, a Switch
    ``ExpertMLP``), an untied biased head.
    Returns (tokens_tensor, positions_tensor, softmax_output).

    tokens/positions: (B, S) int32 — positions are 0..S-1 per row (the
    dataloader supplies them; synthetic mode generates arange).  Labels
    are next-token ids, shape (B, S) int32.
    """
    def attention(ff, h, i):
        return ff.multihead_attention(h, num_heads=num_heads, causal=True,
                                      dropout=dropout, name=f"attn_{i}")

    def mlp(ff, h, i):
        if moe_every and (i + 1) % moe_every == 0:
            # MoE block (Switch): expert-parallel FFN in place of the
            # dense MLP; dropped tokens ride the residual
            return ff.expert_mlp(h, num_experts=num_experts,
                                 hidden_size=embed_dim * mlp_ratio,
                                 activation="gelu", name=f"moe_{i}")
        h = ff.dense(h, embed_dim * mlp_ratio, activation="gelu",
                     name=f"mlp_up_{i}")
        return ff.dense(h, embed_dim, name=f"mlp_down_{i}")

    return build_decoder(
        ff, batch_size, seq_length, num_layers, embed_dim, vocab_size,
        norm=lambda ff, x, name: ff.layer_norm(x, name=name),
        attention=attention, mlp=mlp,
        head=lambda ff, x: ff.dense(x, vocab_size, name="lm_head"),
        learned_positions=True)


def build_deepseek_v2(ff: FFModel, batch_size: int, seq_length: int = 4096,
                      hidden_size: int = 5120, num_hidden_layers: int = 60,
                      first_k_dense_replace: int = 1,
                      intermediate_size: int = 12288,
                      moe_intermediate_size: int = 1536,
                      num_attention_heads: int = 128,
                      q_lora_rank: int = 1536, kv_lora_rank: int = 512,
                      qk_nope_head_dim: int = 128, qk_rope_head_dim: int = 64,
                      v_head_dim: int = 128, rope_theta: float = 10000.0,
                      rope_scaling=None, rms_norm_eps: float = 1e-6,
                      n_routed_experts: int = 160,
                      num_experts_per_tok: int = 6, n_group: int = 8,
                      topk_group: int = 3, routed_scaling_factor: float = 16.0,
                      n_shared_experts: int = 2, vocab_size: int = 102400,
                      experts_held=None, first_expert: int = 0,
                      capacity_factor: float = 1.0, tile_rows: int = 128):
    """DeepSeek-V2 (arXiv:2405.04434; the keys are those of the model's
    public config.json) for training, as one chip of a deployment that
    divides each layer holds it: RMSNorm, latent attention with YaRN
    rotary positions over the ``num_attention_heads`` heads held here,
    ``first_k_dense_replace`` dense gated MLPs and then routed experts
    (``experts_held`` of ``n_routed_experts`` from ``first_expert`` on,
    with the shared experts) under the device budget of
    ``capacity_factor``, an untied head
    without bias over the ``vocab_size`` rows held here.  The defaults
    are the whole published model.  Returns (tokens_tensor,
    softmax_output); labels are next-token ids, (B, S) int32."""
    def attention(ff, h, i):
        return ff.latent_attention(
            h, num_attention_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, rope_scaling=rope_scaling,
            eps=rms_norm_eps, name=f"attn_{i}")

    def mlp(ff, h, i):
        if i < first_k_dense_replace:
            return ff.gated_mlp(h, intermediate_size, name=f"mlp_{i}")
        return ff.routed_experts(
            h, n_routed_experts, num_experts_per_tok, moe_intermediate_size,
            experts_held=experts_held, first_expert=first_expert,
            n_group=n_group, topk_group=topk_group,
            routed_scaling_factor=routed_scaling_factor,
            n_shared_experts=n_shared_experts,
            capacity_factor=capacity_factor, tile_rows=tile_rows,
            name=f"moe_{i}")

    return build_decoder(
        ff, batch_size, seq_length, num_hidden_layers, hidden_size,
        vocab_size,
        norm=lambda ff, x, name: ff.rms_norm(x, eps=rms_norm_eps, name=name),
        attention=attention, mlp=mlp,
        head=lambda ff, x: ff.dense(x, vocab_size, use_bias=False,
                                    name="lm_head"),
        learned_positions=False)


def build_dots3(ff: FFModel, batch_size: int, seq_length: int = 8192,
                hidden_size: int = 5120, num_hidden_layers: int = 46,
                layer_types=None, first_k_dense_replace: int = 1,
                intermediate_size: int = 13824,
                moe_intermediate_size: int = 1536,
                num_attention_heads: int = 128, q_lora_rank: int = 1024,
                kv_lora_rank: int = 512, qk_nope_head_dim: int = 128,
                qk_rope_head_dim: int = 64, v_head_dim: int = 128,
                rope_theta: float = 8e7, index_n_heads: int = 64,
                index_head_dim: int = 128, index_topk: int = 2048,
                attention_gate_type: str = "headwise",
                swa_num_attention_heads: int = 64, swa_q_lora_rank: int = 1024,
                swa_kv_lora_rank: int = 1024, swa_qk_nope_head_dim: int = 192,
                swa_qk_rope_head_dim: int = 64, swa_v_head_dim: int = 128,
                swa_rope_theta: float = 5e4, sliding_window_size: int = 513,
                swa_attention_gate_type: str = "headwise",
                apply_mla_qkv_lora_rescale: bool = True,
                rms_norm_eps: float = 1e-5, n_routed_experts: int = 256,
                num_experts_per_tok: int = 8,
                routed_scaling_factor: float = 1.0, n_shared_experts: int = 1,
                scoring_func: str = "sigmoid", norm_topk_prob: bool = True,
                vocab_size: int = 152064, experts_held=None,
                first_expert: int = 0, capacity_factor: float = 1.0,
                tile_rows: int = 128):
    """dots3-note-prev's language model (the keys are those of its public
    config.json) for training, as one chip of a deployment that divides
    each layer holds it: RMSNorm; by ``layer_types``, latent attention
    under a learned index (``full_attention``: the ``index_topk`` keys of
    largest index score, ``ops/dsa.py``) or latent attention of the
    ``swa_`` widths within a window (``sliding_attention``), both with a
    head-wise output gate and rescaled latents, over the heads held here;
    ``first_k_dense_replace`` dense gated MLPs and then routed experts
    (sigmoid scores, a selection bias, weights renormalised over the
    chosen; ``experts_held`` of ``n_routed_experts`` from ``first_expert``
    on, with the shared expert) under the device budget; an untied head
    without bias over the ``vocab_size`` rows held here.  The defaults
    are the whole published model, ``layer_types`` its published pattern
    (a full layer, then a period of full, window, window, window).
    Returns (tokens_tensor, softmax_output); labels are next-token ids."""
    if layer_types is None:
        layer_types = ["full_attention"] + [
            "sliding_attention" if i % 4 else "full_attention"
            for i in range(num_hidden_layers - 1)]
    if len(layer_types) != num_hidden_layers or set(layer_types) - {
            "full_attention", "sliding_attention"}:
        raise ValueError(f"build_dots3: {num_hidden_layers} layers, "
                         f"layer_types {layer_types}")

    def attention(ff, h, i):
        if layer_types[i] == "sliding_attention":
            return ff.latent_attention(
                h, swa_num_attention_heads, q_lora_rank=swa_q_lora_rank,
                kv_lora_rank=swa_kv_lora_rank,
                qk_nope_head_dim=swa_qk_nope_head_dim,
                qk_rope_head_dim=swa_qk_rope_head_dim,
                v_head_dim=swa_v_head_dim, rope_theta=swa_rope_theta,
                eps=rms_norm_eps, window=sliding_window_size,
                gate=swa_attention_gate_type,
                latent_rescale=apply_mla_qkv_lora_rescale, name=f"attn_{i}")
        return ff.latent_attention(
            h, num_attention_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, eps=rms_norm_eps,
            gate=attention_gate_type,
            latent_rescale=apply_mla_qkv_lora_rescale,
            index=(index_n_heads, index_head_dim, index_topk),
            name=f"attn_{i}")

    def mlp(ff, h, i):
        if i < first_k_dense_replace:
            return ff.gated_mlp(h, intermediate_size, name=f"mlp_{i}")
        return ff.routed_experts(
            h, n_routed_experts, num_experts_per_tok, moe_intermediate_size,
            experts_held=experts_held, first_expert=first_expert,
            routed_scaling_factor=routed_scaling_factor,
            n_shared_experts=n_shared_experts,
            capacity_factor=capacity_factor, tile_rows=tile_rows,
            scoring=scoring_func, select_bias=True,
            norm_topk_prob=norm_topk_prob, name=f"moe_{i}")

    return build_decoder(
        ff, batch_size, seq_length, num_hidden_layers, hidden_size,
        vocab_size,
        norm=lambda ff, x, name: ff.rms_norm(x, eps=rms_norm_eps, name=name),
        attention=attention, mlp=mlp,
        head=lambda ff, x: ff.dense(x, vocab_size, use_bias=False,
                                    name="lm_head"),
        learned_positions=False)


def synthetic_lm_batch(batch_size: int, seq_length: int, vocab_size: int,
                       seed: int = 0):
    """(tokens, positions, next-token labels) for a synthetic LM step —
    the one recipe shared by the example, the bench, and the dryrun."""
    import numpy as np

    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab_size,
                        size=(batch_size, seq_length)).astype(np.int32)
    posa = np.broadcast_to(np.arange(seq_length, dtype=np.int32),
                           (batch_size, seq_length)).copy()
    labels = np.roll(toks, -1, axis=1).astype(np.int32)
    return toks, posa, labels
