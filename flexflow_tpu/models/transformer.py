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
    (a leading dense MLP, then experts), and may keep what one layer makes
    for later ones to read; ``head(ff, x)`` gives the logits (a dense
    layer, or ``ff.tied_head`` over the table of the embedding op
    ``tok_embed``).  With ``learned_positions`` a second input carries
    0..S-1 per row into a position table added to the token embedding;
    without, positions are the layers' own business (rotary attention,
    or a scan)."""
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


def mimo_v2_layer_pattern(num_hidden_layers: int = 48):
    """MiMo-V2-Flash's published ``hybrid_layer_pattern`` (0 a full layer,
    1 a window layer) over ``num_hidden_layers``: layer 0 and every layer
    ``i`` with ``i % 6 == 5`` are full, the rest window layers."""
    return [0 if i == 0 or i % 6 == 5 else 1 for i in range(num_hidden_layers)]


def build_mimo_v2_flash(ff: FFModel, batch_size: int, seq_length: int = 8192,
                        hidden_size: int = 4096, num_hidden_layers: int = 48,
                        hybrid_layer_pattern=None, moe_layer_freq=None,
                        intermediate_size: int = 16384,
                        moe_intermediate_size: int = 2048,
                        num_attention_heads: int = 64,
                        num_key_value_heads: int = 4, head_dim: int = 192,
                        v_head_dim: int = 128,
                        swa_num_attention_heads: int = 64,
                        swa_num_key_value_heads: int = 8,
                        swa_head_dim: int = 192, swa_v_head_dim: int = 128,
                        partial_rotary_factor: float = 0.334,
                        rope_theta: float = 5e6, swa_rope_theta: float = 1e4,
                        sliding_window: int = 128,
                        attention_value_scale: float = 0.707,
                        add_swa_attention_sink_bias: bool = True,
                        add_full_attention_sink_bias: bool = False,
                        layernorm_epsilon: float = 1e-5,
                        n_routed_experts: int = 256,
                        num_experts_per_tok: int = 8, n_group: int = 1,
                        topk_group: int = 1, routed_scaling_factor=None,
                        scoring_func: str = "sigmoid",
                        norm_topk_prob: bool = True,
                        vocab_size: int = 152576, experts_held=None,
                        first_expert: int = 0, capacity_factor: float = 1.0,
                        tile_rows: int = 128):
    """MiMo-V2-Flash's language model (the keys are those of its public
    config.json) for training, as one chip of a deployment that divides
    each layer holds it: RMSNorm; by ``hybrid_layer_pattern``, grouped-
    query attention over all keys (0) or over a window of
    ``sliding_window`` keys with a learned sink a head (1), each with its
    own head counts (``num_*``, ``swa_num_*``: the heads held here, the
    model's group their ratio) and rotary theta, rotary positions on
    ``partial_rotary_factor`` of the query/key head, v scaled by
    ``attention_value_scale``; by ``moe_layer_freq``, a dense gated MLP
    (0) or routed experts (1: sigmoid scores, a selection bias, weights
    renormalised over the chosen, no shared expert; ``experts_held`` of
    ``n_routed_experts`` from ``first_expert`` on) under the device
    budget; an untied head without bias over the ``vocab_size`` rows held
    here.  The defaults are the whole published model, the patterns its
    published ones (``mimo_v2_layer_pattern``; layer 0 dense).  Returns
    (tokens_tensor, softmax_output); labels are next-token ids."""
    if hybrid_layer_pattern is None:
        hybrid_layer_pattern = mimo_v2_layer_pattern(num_hidden_layers)
    if moe_layer_freq is None:
        moe_layer_freq = [0] + [1] * (num_hidden_layers - 1)
    for key, pattern in (("hybrid_layer_pattern", hybrid_layer_pattern),
                         ("moe_layer_freq", moe_layer_freq)):
        if len(pattern) != num_hidden_layers or set(pattern) - {0, 1}:
            raise ValueError(f"build_mimo_v2_flash: {num_hidden_layers} "
                             f"layers, {key} {pattern}")

    def attention(ff, h, i):
        window = hybrid_layer_pattern[i] == 1
        heads, kv_heads, d, dv, theta = (
            (swa_num_attention_heads, swa_num_key_value_heads, swa_head_dim,
             swa_v_head_dim, swa_rope_theta) if window else
            (num_attention_heads, num_key_value_heads, head_dim, v_head_dim,
             rope_theta))
        return ff.grouped_query_attention(
            h, heads, kv_heads, head_dim=d,
            v_head_dim=dv, rotary_dim=int(partial_rotary_factor * d),
            rope_theta=theta, window=sliding_window if window else None,
            sink=add_swa_attention_sink_bias if window
            else add_full_attention_sink_bias,
            value_scale=attention_value_scale, name=f"attn_{i}")

    def mlp(ff, h, i):
        if not moe_layer_freq[i]:
            return ff.gated_mlp(h, intermediate_size, name=f"mlp_{i}")
        return ff.routed_experts(
            h, n_routed_experts, num_experts_per_tok, moe_intermediate_size,
            experts_held=experts_held, first_expert=first_expert,
            n_group=n_group, topk_group=topk_group,
            routed_scaling_factor=routed_scaling_factor or 1.0,
            n_shared_experts=0, capacity_factor=capacity_factor,
            tile_rows=tile_rows, scoring=scoring_func, select_bias=True,
            norm_topk_prob=norm_topk_prob, name=f"moe_{i}")

    return build_decoder(
        ff, batch_size, seq_length, num_hidden_layers, hidden_size,
        vocab_size,
        norm=lambda ff, x, name: ff.rms_norm(x, eps=layernorm_epsilon,
                                             name=name),
        attention=attention, mlp=mlp,
        head=lambda ff, x: ff.dense(x, vocab_size, use_bias=False,
                                    name="lm_head"),
        learned_positions=False)


def phi4_flash_layer_kinds(num_hidden_layers: int = 32,
                           mb_per_layer: int = 2):
    """The mixer of each of ``num_hidden_layers`` layers in SambaY's
    decoder-hybrid-decoder (arXiv:2507.06607), as Phi-4-mini-flash's
    config reads: in the self-decoder (i < N/2) a Mamba layer where
    ``i % mb_per_layer == 0`` and window attention elsewhere; layer N/2 a
    Mamba layer whose gated scan output is the memory; layer N/2 + 1 full
    attention whose keys and values are kept; in the cross-decoder a gated
    memory unit where ``i % mb_per_layer == 0`` and cross-attention
    elsewhere.  Kinds: ``mamba``, ``window``, ``memory``, ``full``,
    ``gmu``, ``cross``."""
    half = num_hidden_layers // 2
    kinds = []
    for i in range(num_hidden_layers):
        even = i % mb_per_layer == 0
        if i < half:
            kinds.append("mamba" if even else "window")
        elif i == half:
            kinds.append("memory")
        elif i == half + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if even else "cross")
    return kinds


def build_phi4_flash(ff: FFModel, batch_size: int, seq_length: int = 8192,
                     hidden_size: int = 2560, num_hidden_layers: int = 32,
                     layers=None, intermediate_size: int = 10240,
                     num_attention_heads: int = 40,
                     num_key_value_heads: int = 20,
                     sliding_window: int = 512, mb_per_layer: int = 2,
                     layer_norm_eps: float = 1e-5, d_state: int = 16,
                     d_conv: int = 4, expand: int = 2, dt_rank=None,
                     vocab_size: int = 200064, tie_word_embeddings=True):
    """Phi-4-mini-flash-reasoning's language model (the keys are those of
    its public config.json; the Mamba sizes the Mamba family's defaults)
    for training: LayerNorm with a bias, by ``phi4_flash_layer_kinds``
    Mamba layers (``ops/ssm.py``; d_inner ``expand * hidden``, dt_rank
    ``ceil(hidden / 16)``), grouped-query attention within
    ``sliding_window`` keys or over all of them, gated memory units that
    read the memory layer's gated scan output, cross-attention that reads
    the full layer's keys and values; no positional encoding (the Mamba
    layers carry position); SiLU-gated MLPs without bias; the head tied to
    the token embedding's table, over the ``vocab_size`` rows held here.
    ``layers``: the published layer indices built, in order (the defaults
    are the whole model); a kept gated memory unit or cross-attention
    layer needs the memory and full layers kept before it.  Returns
    (tokens_tensor, softmax_output); labels are next-token ids."""
    import math

    if not tie_word_embeddings:
        raise ValueError("build_phi4_flash: the head is the token "
                         "embedding's table (tie_word_embeddings)")
    kinds = phi4_flash_layer_kinds(num_hidden_layers, mb_per_layer)
    layers = list(range(num_hidden_layers)) if layers is None \
        else [int(i) for i in layers]
    built = [kinds[i] for i in layers]
    for reader, maker in (("gmu", "memory"), ("cross", "full")):
        if reader in built and (maker not in built or built.index(maker)
                                > built.index(reader)):
            raise ValueError(f"build_phi4_flash: layers {layers} read the "
                             f"{maker} layer's output without building it "
                             f"first")
    d_inner = expand * hidden_size
    dt_rank = dt_rank or math.ceil(hidden_size / 16)
    head_dim = hidden_size // num_attention_heads
    kept = {}

    def mixer(ff, h, i):
        kind, name = built[i], f"{built[i]}_{i}"
        if kind in ("mamba", "memory"):
            out = ff.mamba(h, d_inner, d_state, d_conv, dt_rank,
                           memory=kind == "memory", name=name)
            if kind == "memory":
                out, kept["memory"] = out
            return out
        if kind == "gmu":
            return ff.gated_memory_unit(h, kept["memory"], name=name)
        sizes = dict(head_dim=head_dim, v_head_dim=head_dim, rotary_dim=0,
                     window=sliding_window if kind == "window" else None,
                     return_kv=kind == "full",
                     kv=kept.get("kv") if kind == "cross" else None)
        out = ff.grouped_query_attention(h, num_attention_heads,
                                         num_key_value_heads, name=name,
                                         **sizes)
        if kind == "full":
            out, *kept["kv"] = out
        return out

    def head(ff, x):
        embed = next(op for op in ff.ops if op.name == "tok_embed")
        return ff.tied_head(x, embed.output, name="lm_head")

    return build_decoder(
        ff, batch_size, seq_length, len(layers), hidden_size, vocab_size,
        norm=lambda ff, x, name: ff.layer_norm(x, eps=layer_norm_eps,
                                               name=name),
        attention=mixer,
        mlp=lambda ff, h, i: ff.gated_mlp(h, intermediate_size,
                                          name=f"mlp_{i}"),
        head=head, learned_positions=False)


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
