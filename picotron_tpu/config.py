"""Typed configuration for picotron-tpu.

One explicit config object threaded through the whole program — this replaces
both of the reference's config channels: the JSON file
(ref: template/base_config.json:1-52) and the shadow environment-variable
channel (`FLASH_ATTEN` / `CONTEXT_PARALLEL` / `DEVICE` / `DTYPE`, ref:
train.py:65-77, model.py:127-158, context_parallel.py:10-12), which SURVEY.md
§5 flags as a design wart.

The JSON schema is compatible with the reference's: a reference config.json
loads unchanged (unknown keys are ignored; the `environment` section is
irrelevant on TPU). Model hyperparameters resolve from a built-in preset
registry instead of a network `AutoConfig` fetch (ref: create_config.py:51-55)
— TPU pods frequently run with zero egress, so presets are first-class and
explicit overrides always win.
"""

from __future__ import annotations

import dataclasses
import json
import math
from dataclasses import dataclass, field
from typing import Any, NamedTuple, Optional


# ---------------------------------------------------------------------------
# Model preset registry (replaces network AutoConfig lookup).
# Hyperparameters are the public ones for each model family.
# ---------------------------------------------------------------------------

MODEL_PRESETS: dict[str, dict[str, Any]] = {
    # SmolLM family (Llama architecture)
    "HuggingFaceTB/SmolLM-135M": dict(
        vocab_size=49152, hidden_size=576, intermediate_size=1536,
        num_hidden_layers=30, num_attention_heads=9, num_key_value_heads=3,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
    ),
    "HuggingFaceTB/SmolLM-360M": dict(
        vocab_size=49152, hidden_size=960, intermediate_size=2560,
        num_hidden_layers=32, num_attention_heads=15, num_key_value_heads=5,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
    ),
    "HuggingFaceTB/SmolLM-1.7B": dict(
        vocab_size=49152, hidden_size=2048, intermediate_size=8192,
        num_hidden_layers=24, num_attention_heads=32, num_key_value_heads=32,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
    ),
    # Llama-2
    "meta-llama/Llama-2-7b-hf": dict(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=32,
        max_position_embeddings=4096, rope_theta=10000.0, rms_norm_eps=1e-5,
    ),
    "meta-llama/Llama-2-13b-hf": dict(
        vocab_size=32000, hidden_size=5120, intermediate_size=13824,
        num_hidden_layers=40, num_attention_heads=40, num_key_value_heads=40,
        max_position_embeddings=4096, rope_theta=10000.0, rms_norm_eps=1e-5,
    ),
    "meta-llama/Llama-2-70b-hf": dict(
        vocab_size=32000, hidden_size=8192, intermediate_size=28672,
        num_hidden_layers=80, num_attention_heads=64, num_key_value_heads=8,
        max_position_embeddings=4096, rope_theta=10000.0, rms_norm_eps=1e-5,
    ),
    # Llama-3
    "meta-llama/Meta-Llama-3-8B": dict(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=8192, rope_theta=500000.0, rms_norm_eps=1e-5,
    ),
    # Llama-3.1/3.2 (llama3-type RoPE scaling for 128k context)
    "meta-llama/Llama-3.1-8B": dict(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=131072, rope_theta=500000.0,
        rms_norm_eps=1e-5,
        rope_scaling=dict(rope_type="llama3", factor=8.0,
                          low_freq_factor=1.0, high_freq_factor=4.0,
                          original_max_position_embeddings=8192),
    ),
    "meta-llama/Llama-3.1-70B": dict(
        vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_hidden_layers=80, num_attention_heads=64, num_key_value_heads=8,
        max_position_embeddings=131072, rope_theta=500000.0,
        rms_norm_eps=1e-5,
        rope_scaling=dict(rope_type="llama3", factor=8.0,
                          low_freq_factor=1.0, high_freq_factor=4.0,
                          original_max_position_embeddings=8192),
    ),
    "meta-llama/Llama-3.2-1B": dict(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192,
        num_hidden_layers=16, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=131072, rope_theta=500000.0,
        rms_norm_eps=1e-5, tie_word_embeddings=True,
        rope_scaling=dict(rope_type="llama3", factor=32.0,
                          low_freq_factor=1.0, high_freq_factor=4.0,
                          original_max_position_embeddings=8192),
    ),
    "meta-llama/Llama-3.2-3B": dict(
        vocab_size=128256, hidden_size=3072, intermediate_size=8192,
        num_hidden_layers=28, num_attention_heads=24, num_key_value_heads=8,
        max_position_embeddings=131072, rope_theta=500000.0,
        rms_norm_eps=1e-5, tie_word_embeddings=True,
        rope_scaling=dict(rope_type="llama3", factor=32.0,
                          low_freq_factor=1.0, high_freq_factor=4.0,
                          original_max_position_embeddings=8192),
    ),
    # TinyLlama
    "TinyLlama/TinyLlama-1.1B-Chat-v1.0": dict(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=22, num_attention_heads=32, num_key_value_heads=4,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
    ),
    # Qwen2 family (Llama-like + attention qkv bias; small ones tie the
    # LM head to the embedding)
    "Qwen/Qwen2-0.5B": dict(
        vocab_size=151936, hidden_size=896, intermediate_size=4864,
        num_hidden_layers=24, num_attention_heads=14, num_key_value_heads=2,
        max_position_embeddings=32768, rope_theta=1e6, rms_norm_eps=1e-6,
        attention_bias=True, tie_word_embeddings=True,
    ),
    "Qwen/Qwen2-1.5B": dict(
        vocab_size=151936, hidden_size=1536, intermediate_size=8960,
        num_hidden_layers=28, num_attention_heads=12, num_key_value_heads=2,
        max_position_embeddings=32768, rope_theta=1e6, rms_norm_eps=1e-6,
        attention_bias=True, tie_word_embeddings=True,
    ),
    "Qwen/Qwen2-7B": dict(
        vocab_size=152064, hidden_size=3584, intermediate_size=18944,
        num_hidden_layers=28, num_attention_heads=28, num_key_value_heads=4,
        max_position_embeddings=32768, rope_theta=1e6, rms_norm_eps=1e-6,
        attention_bias=True,
    ),
    # Mixtral (MoE family; beyond the reference's dense-only coverage)
    "mistralai/Mixtral-8x7B-v0.1": dict(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=32768, rope_theta=1e6, rms_norm_eps=1e-5,
        num_experts=8, num_experts_per_token=2,
    ),
    "mistralai/Mixtral-8x22B-v0.1": dict(
        vocab_size=32768, hidden_size=6144, intermediate_size=16384,
        num_hidden_layers=56, num_attention_heads=48, num_key_value_heads=8,
        max_position_embeddings=65536, rope_theta=1e6, rms_norm_eps=1e-5,
        num_experts=8, num_experts_per_token=2,
    ),
    # OLMoE (fine-grained MoE: 64 experts of width 1024, 8 a token whose
    # gates are the raw softmax probabilities, QK-norm from model_type
    # olmoe, plain multi-head attention, no shared expert; trained
    # dropless). router_aux_coef is the source's router_aux_loss_coef;
    # the z-loss coefficient is the OLMoE paper's (arXiv:2409.02060).
    "allenai/OLMoE-1B-7B-0125-Instruct": dict(
        vocab_size=50304, hidden_size=2048, intermediate_size=1024,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=16,
        max_position_embeddings=4096, rope_theta=10000.0, rms_norm_eps=1e-5,
        num_experts=64, num_experts_per_token=8, moe_intermediate_size=1024,
        norm_topk_prob=False, qk_norm=True,
        router_aux_coef=0.01, router_z_coef=0.001,
    ),
    # Mellum 2 (JetBrains; model_type mellum): sparse experts in every
    # layer (64 of width 896, 8 a token, renormalised gates, no shared
    # expert), GQA 32:4 with a head_dim of its own (128, not hidden /
    # heads = 72), three sliding-window layers (window 1024, plain RoPE)
    # to one full layer (YaRN x16 over 8192 positions), untied head. The
    # dense intermediate_size (7168) is unused: every mlp_layer_types
    # entry is sparse.
    "JetBrains/Mellum2-12B-A2.5B-Instruct": dict(
        vocab_size=98304, hidden_size=2304, intermediate_size=7168,
        num_hidden_layers=28, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, max_position_embeddings=131072, rope_theta=500000.0,
        rms_norm_eps=1e-6,
        num_experts=64, num_experts_per_token=8, moe_intermediate_size=896,
        norm_topk_prob=True,
        layer_types=("sliding_attention", "sliding_attention",
                     "sliding_attention", "full_attention") * 7,
        sliding_window=1024,
        rope_parameters=dict(
            full_attention=dict(
                rope_type="yarn", rope_theta=500000.0, factor=16.0,
                original_max_position_embeddings=8192, beta_fast=32.0,
                beta_slow=1.0, attention_factor=1.2772588722239782),
            sliding_attention=dict(rope_type="default",
                                   rope_theta=500000.0)),
    ),
    # openPangu-Ultra-MoE-718B (model_type pangu_ultra_moe): latent
    # attention (MLA: q through a rank-1536 bottleneck, K and V through one
    # rank-512 latent + 64 shared RoPE dimensions), 3 leading dense layers
    # (SwiGLU 18,432) before 58 expert layers (256 routed experts of width
    # 2,048, 8 a token by sigmoid scores renormalised and scaled 2.5, + 1
    # shared expert), four RMSNorms a layer (sandwich), untied head. The
    # multi-token-prediction layer (num_nextn_predict_layers 1) drafts and
    # is not built: a served token does not pass through it.
    "FreedomIntelligence/openPangu-Ultra-MoE-718B": dict(
        vocab_size=153600, hidden_size=7680, intermediate_size=18432,
        num_hidden_layers=61, num_attention_heads=128,
        num_key_value_heads=128, max_position_embeddings=131072,
        rope_theta=25600000.0, rms_norm_eps=1e-5,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        first_k_dense_replace=3, num_experts=256, num_experts_per_token=8,
        moe_intermediate_size=2048, n_shared_experts=1,
        norm_topk_prob=True, moe_scoring="sigmoid",
        routed_scaling_factor=2.5, sandwich_norm=True,
        router_aux_coef=0.0,
    ),
    # K-EXAONE-236B-A23B (LG AI Research; model_type exaone_moe): 48 layers
    # in the pattern (sliding, sliding, sliding, full) x 12 at window 128,
    # GQA 64:8 at head_dim 128, layer 0 dense (SwiGLU 18,432) and 47 expert
    # layers (128 routed experts of width 2,048, 8 a token by sigmoid scores
    # renormalised and scaled 2.5, no groups, + 1 shared expert), untied
    # 153,600-row head. The pattern is cut where the stacks are: the dense
    # stack is (S,), the expert stack (S, S, F, S) x 11 + (S, S, F), scanned
    # over its own whole periods with the three left over run after the scan
    # (`pattern_of`). Built: all of the above through forward(), generate()
    # and ServeEngine. Assumed, with no key in config.json (the family's
    # modelling code): RMSNorm over each head of q and k (qk_norm 'head'),
    # no rotation on full layers (rope_type 'none'; the one published law,
    # theta 1e6 unscaled, is the sliding layers'), two RMSNorms a layer, no
    # selection bias in the router. Not built: the multi-token-prediction
    # layer (num_nextn_predict_layers 1), which drafts; a served token does
    # not pass through it.
    "LGAI-EXAONE/K-EXAONE-236B-A23B": dict(
        vocab_size=153600, hidden_size=6144, intermediate_size=18432,
        num_hidden_layers=48, num_attention_heads=64, num_key_value_heads=8,
        head_dim=128, max_position_embeddings=262144, rope_theta=1e6,
        rms_norm_eps=1e-5,
        layer_types=("sliding_attention", "sliding_attention",
                     "sliding_attention", "full_attention") * 12,
        sliding_window=128, qk_norm="head",
        rope_parameters=dict(
            sliding_attention=dict(rope_type="default", rope_theta=1e6),
            full_attention=dict(rope_type="none")),
        first_k_dense_replace=1, num_experts=128, num_experts_per_token=8,
        moe_intermediate_size=2048, n_shared_experts=1,
        norm_topk_prob=True, moe_scoring="sigmoid",
        routed_scaling_factor=2.5, router_aux_coef=0.0,
    ),
    # EvaByte (6.5B, byte-level; model_type evabyte, attention_class eva):
    # 32 layers, h 4096, 32:32 heads x 128, SwiGLU 11,008, no bias, a
    # vocabulary of 320 rows (bytes and a few specials), an untied head of
    # num_pred_heads 8 x 320 rows (head j at position t scores byte
    # t + 1 + j), RMSNorm with 1 + w (norm_add_unit_offset), residual adds
    # and logits in float32, RoPE theta 1e5 unscaled over 32,768 positions.
    # Attention is EVA (ops/eva.py): positions fall into windows of
    # window_size 2,048 and chunks of chunk_size 16; a query sees the keys
    # of its own window one by one and every CLOSED window through one
    # summary (a pooled key, a pooled value) a chunk, under one softmax.
    # Built: forward() (plain attention, AD, one device), generate() and
    # ServeEngine, whose paged cache holds window blocks (recycled at every
    # window boundary) and summary blocks in one table a slot
    # (serve/paged_cache.py EvaPagedCache); the served byte is head 0's.
    # Assumed, with no key in config.json (benchmark/reference_evabyte.py
    # repeats the list): (a) a chunk's pooled key is sum_j softmax_j(s k_j .
    # mu) k_j and its pooled value sum_j softmax_j(s k_j . phi) v_j, s =
    # head_dim^-1/2, one learned vector mu and one phi a head and layer
    # (eva_mu, eva_phi); (b) keys are pooled after rotation; (c) a window's
    # summaries are seen from the next window on; (d) the one scale s on
    # both kinds of score, no count term on a summary's; (e) mu, phi drawn
    # unit normal clamped to [-1, 1]; (f) pre-norm, two norms a layer; (g)
    # head j predicts byte t + 1 + j. Not built: training (no loss over the
    # 8 heads, no banded kernel with summary keys: ROADMAP M9),
    # self-speculative decoding from heads 1-7 (ROADMAP M8), tp / pp / cp /
    # ep, the fleet.
    "EvaByte/EvaByte": dict(
        vocab_size=320, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=32, num_attention_heads=32,
        num_key_value_heads=32, head_dim=128,
        max_position_embeddings=32768, rope_theta=100000.0,
        rms_norm_eps=1e-5, attention_class="eva", window_size=2048,
        chunk_size=16, num_pred_heads=8, norm_add_unit_offset=True,
        fp32_skip_add=True,
    ),
    # LongCat-Flash-Omni's language model (Meituan; 560B-A27B): 28 layers,
    # each TWO (latent attention, dense SwiGLU 12,288) pairs and an expert
    # branch that starts after the first attention and lands after the
    # second MLP (`shortcut_moe`; the block is written out at
    # models/llama.py `_shortcut_layer`): MLA at openPangu's ranks with 64
    # heads, each attention with its own weights and its own cache row
    # (`attention_sublayers`), the query and key/value latents scaled after
    # their norms (mla_scale_q_lora / mla_scale_kv_lora); a softmax router
    # 768 wide, 512 routed experts of width 2,048 and 256 zero-compute
    # experts (`zero_experts`: a pick returns the token itself times its
    # gate), 12 a token chosen by score + a selection bias
    # (`moe_selection_bias`) and weighed by the score x 6, not renormalised;
    # no shared expert, untied 131,072-row head. Built: forward(), generate()
    # and ServeEngine, on one device; training is refused by name. Assumed,
    # with no key in config.json (the released modelling code;
    # benchmark/reference_longcat.py repeats the list): the two scales are
    # sqrt(hidden / rank) and act after the latents' norms, c cached after
    # its scale; norm_topk_prob false; rotate-half RoPE. Not built: the
    # audio and vision encoders and the codec decoder.
    "meituan-longcat/LongCat-Flash-Omni": dict(
        vocab_size=131072, hidden_size=6144, intermediate_size=12288,
        num_hidden_layers=28, num_attention_heads=64, num_key_value_heads=64,
        max_position_embeddings=131072, rope_theta=1e7, rms_norm_eps=1e-5,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        mla_scale_q_lora=True, mla_scale_kv_lora=True, shortcut_moe=True,
        num_experts=512, num_experts_per_token=12, moe_intermediate_size=2048,
        zero_experts=256, moe_selection_bias=True, norm_topk_prob=False,
        routed_scaling_factor=6.0, router_aux_coef=0.0,
    ),
    # Qwen3-Next-80B-A3B-Instruct
    # (https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json).
    # 48 layers in periods of (L, L, L, F): three Gated DeltaNet mixers
    # (ops/gated_delta.py: 16 key heads and 32 value heads of 128, a causal
    # depthwise convolution of 4 over q, k and v, a float32 state of
    # 128 x 128 a value head carried by the gated delta rule, an RMSNorm of
    # each head's output gated by silu(z)) to one gated softmax attention
    # (16 heads over 2 KV heads of 256, per-head QK-norm, RoPE on the first
    # 64 of a head's 256 dimensions, the output times sigmoid of a gate that
    # comes out of q's projection); in every layer top-10 of 512 experts of
    # width 512 (softmax, renormalised) beside one shared expert whose
    # output is scaled by sigmoid of a 1-wide projection of the token; 1 + w
    # norms; untied 151,936-row head. Built: forward(), generate() and
    # ServeEngine, on one device. Assumed, with no key in config.json (the
    # released modelling code; benchmark/reference_qwen3_next.py repeats the
    # list): the attention's output gate and where it comes from, the order
    # of [q | k | v | z] and [b | a] inside their projections, the plain
    # weight of the mixer's output norm, A_log = log U(0, 16) and dt_bias
    # the inverse softplus of a step log-uniform in [0.001, 0.1] at init.
    # Not built: the multi-token-prediction module.
    "Qwen/Qwen3-Next-80B-A3B-Instruct": dict(
        vocab_size=151936, hidden_size=2048, intermediate_size=5120,
        num_hidden_layers=48, num_attention_heads=16, num_key_value_heads=2,
        head_dim=256, max_position_embeddings=262144, rope_theta=1e7,
        rms_norm_eps=1e-6, partial_rotary_factor=0.25,
        layer_types=("linear_attention", "linear_attention",
                     "linear_attention", "full_attention") * 12,
        linear_conv_kernel_dim=4, linear_key_head_dim=128,
        linear_num_key_heads=16, linear_num_value_heads=32,
        linear_value_head_dim=128, qk_norm="head", attn_output_gate=True,
        norm_add_unit_offset=True,
        num_experts=512, num_experts_per_token=10, moe_intermediate_size=512,
        n_shared_experts=1, shared_expert_gate=True, norm_topk_prob=True,
        router_aux_coef=0.0,
    ),
    # AI21-Jamba2-3B
    # (https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json).
    # 28 layers in periods of 14: layer i is a softmax attention where
    # i % attn_layer_period == attn_layer_offset (layers 7 and 21: 20 heads
    # of 128 over ONE K/V head, no rotation and no position term of any
    # kind), every other layer a Mamba-1 mixer (ops/selective_scan.py:
    # d_inner = 2 x 2560, a causal depthwise convolution of 4 with bias, an
    # input-dependent step dt through a 160-wide bottleneck, B and C of 16,
    # each of the three through an RMSNorm of its own, a float32 state of
    # [5120, 16] a sequence carried by the diagonal selective scan, D u
    # beside it, the output times silu(z)); a dense gated MLP of 8192 in
    # every layer (num_experts 1); tied 65,536-row head. Built: forward(),
    # generate() and ServeEngine, on one device. Assumed, with no key in
    # config.json (the released Jamba modelling code;
    # benchmark/reference_jamba.py repeats the list): the layer order from
    # period and offset, head_dim = hidden / heads, A_log = log(1..16) a
    # channel and D = 1 at init, the step's bias the inverse softplus of a
    # step log-uniform in [0.001, 0.1] (Gu and Dao's Mamba initialiser).
    "ai21labs/AI21-Jamba2-3B": dict(
        vocab_size=65536, hidden_size=2560, intermediate_size=8192,
        num_hidden_layers=28, num_attention_heads=20, num_key_value_heads=1,
        max_position_embeddings=262144, rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        layer_types=(("mamba",) * 7 + ("full_attention",)
                     + ("mamba",) * 6) * 2,
        rope_parameters=dict(full_attention=dict(rope_type="none")),
        mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160,
        mamba_conv_bias=True, mamba_proj_bias=False,
    ),
    # Kimi-Linear-48B-A3B-Instruct
    # (https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json,
    # model_type kimi_linear). 27 layers in periods of (K, K, K, F), the last
    # period short: a Kimi Delta Attention mixer (ops/kda.py: 32 heads of
    # 128 x 128, three causal depthwise convolutions of 4 over q, k and v,
    # the delta rule with a decay a CHANNEL of the key, from a low-rank
    # projection 2304 -> 128 -> 4096 with a bias a channel, a write strength
    # a head, a float32 state of 128 x 128 a head, an RMSNorm of each head's
    # output times a low-rank SIGMOID gate) three times to one latent
    # attention (MLA: no query bottleneck, a latent of 512 and 64 shared
    # dimensions that are NOT rotated, mla_use_nope: no position term of any
    # kind, the mixers order the sequence); layer 1 a dense gated MLP of
    # 9216, the other 26 top-8 of 256 experts of width 1024 (sigmoid scores
    # chosen with a bias a column, renormalised, scaled by 2.446) beside one
    # shared expert; untied 163,840-row head. Built: forward(), generate()
    # and ServeEngine, on one device. Assumed, with no key in config.json
    # (the released modelling code; benchmark/reference_kimi_linear.py
    # repeats the list): no bias in any projection or convolution, SiLU
    # behind the convolutions, l2-normalised q and k (eps 1e-6, q scaled by
    # d_k^-0.5), the width of the two bottlenecks (the head's 128), A_log =
    # log U(1, 16) a head and dt_bias the inverse softplus of a step
    # log-uniform in [0.001, 0.1] a channel at init, head_dim 72 used by
    # neither mixer.
    "moonshotai/Kimi-Linear-48B-A3B-Instruct": dict(
        vocab_size=163840, hidden_size=2304, intermediate_size=9216,
        num_hidden_layers=27, num_attention_heads=32, num_key_value_heads=32,
        max_position_embeddings=1048576, rope_theta=10000.0,
        rms_norm_eps=1e-5,
        layer_types=("kda", "kda", "kda", "full_attention") * 6
        + ("kda", "kda", "full_attention"),
        linear_conv_kernel_dim=4, linear_key_head_dim=128,
        linear_num_key_heads=32, linear_num_value_heads=32,
        linear_value_head_dim=128,
        q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, mla_use_nope=True,
        first_k_dense_replace=1, num_experts=256, num_experts_per_token=8,
        moe_intermediate_size=1024, n_shared_experts=1, norm_topk_prob=True,
        moe_scoring="sigmoid", routed_scaling_factor=2.446,
        moe_selection_bias=True, router_aux_coef=0.0,
    ),
    # NVIDIA-Nemotron-3-Super-120B-A12B
    # (https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json,
    # model_type nemotron_h). 88 layers that are ONE sublayer each behind one
    # norm, x + f(norm(x)), a letter of hybrid_override_pattern a layer: 40
    # `M`, a Mamba-2 mixer (ops/ssd.py: 128 heads of 64 channels, a float32
    # state of 64 x 128 a head decayed by one scalar a head and step, B and C
    # of 8 groups, a causal depthwise convolution of 4 with bias over [x | B
    # | C], the output gated BEFORE a norm over each group's 1,024 channels);
    # 8 `*`, an attention of 32 query heads over 2 K/V heads of 128 with no
    # rotation and no position term of any kind; 40 `E`, LatentMoE: sigmoid
    # scores over 512 experts, the 22 largest of score + bias, renormalised
    # and scaled by 5, the experts NOT gated (relu(W1 l)^2 through W2) on a
    # latent of 1,024 (W_dn, W_up around them), beside one shared expert of
    # 5,376 on the full width; untied 131,072-row head. Built: forward(),
    # generate() and ServeEngine, on one device. Assumed, with no key in
    # config.json (benchmark/reference_nemotron_h.py repeats the list): the
    # split order [z | x B C | dt], the gate before the grouped norm, no
    # clamp on the step, A_log = log U(1, 16) a head and dt_bias the inverse
    # softplus of a step log-uniform in [time_step_min, time_step_max] at
    # init, D = 1, the shared expert on the full width and the routed ones
    # on the latent (the published parameter count decides it), no drafting
    # module (num_nextn_predict_layers 1: ROADMAP M8).
    "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B": dict(
        vocab_size=131072, hidden_size=4096, intermediate_size=2688,
        num_hidden_layers=88, num_attention_heads=32, num_key_value_heads=2,
        head_dim=128, max_position_embeddings=262144, rope_theta=10000.0,
        rms_norm_eps=1e-5,
        layer_types=tuple(
            {"M": "mamba2", "*": "full_attention", "E": "experts"}[c]
            for c in "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
        rope_parameters=dict(full_attention=dict(rope_type="none")),
        hidden_act="relu2",
        mamba_num_heads=128, mamba_head_dim=64, n_groups=8,
        ssm_state_size=128, mamba_d_conv=4, mamba_conv_bias=True,
        num_experts=512, num_experts_per_token=22,
        moe_intermediate_size=2688, moe_latent_size=1024, n_shared_experts=1,
        moe_shared_expert_intermediate_size=5376, norm_topk_prob=True,
        moe_scoring="sigmoid", routed_scaling_factor=5.0,
        moe_selection_bias=True, router_aux_coef=0.0,
    ),
    # Tiny debug model for tests / CI
    "picotron-tpu/debug-tiny": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
    ),
    # Tiny Qwen2-style debug model (qkv bias + tied embeddings)
    "picotron-tpu/debug-tiny-qwen": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
        attention_bias=True, tie_word_embeddings=True,
    ),
    # Tiny MoE debug model (8 experts, top-2)
    "picotron-tpu/debug-tiny-moe": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
        num_experts=8, num_experts_per_token=2,
    ),
    # Tiny OLMoE-shaped debug model (16 experts, top-4 un-renormalised
    # gates, QK-norm, untied head)
    "picotron-tpu/debug-tiny-olmoe": dict(
        vocab_size=256, hidden_size=64, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
        num_experts=16, num_experts_per_token=4, moe_intermediate_size=32,
        norm_topk_prob=False, qk_norm=True,
        router_aux_coef=0.01, router_z_coef=0.001,
    ),
    # Tiny Mellum2-shaped debug model: two periods of (S, S, S, F),
    # head_dim (32) unequal to hidden / heads (16), 8 experts 2 a token,
    # window 8, YaRN over an original length (16) shorter than the tests'
    # sequences. Served with block_size 4 so that rings wrap.
    "picotron-tpu/debug-tiny-mellum2": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, max_position_embeddings=2048, rope_theta=10000.0,
        rms_norm_eps=1e-6,
        num_experts=8, num_experts_per_token=2, moe_intermediate_size=32,
        norm_topk_prob=True,
        layer_types=("sliding_attention", "sliding_attention",
                     "sliding_attention", "full_attention") * 2,
        sliding_window=8,
        rope_parameters=dict(
            full_attention=dict(
                rope_type="yarn", rope_theta=10000.0, factor=4.0,
                original_max_position_embeddings=16, beta_fast=32.0,
                beta_slow=1.0, attention_factor=1.1386294361119891),
            sliding_attention=dict(rope_type="default",
                                   rope_theta=10000.0)),
    ),
    # Tiny openPangu-Ultra-MoE-shaped debug model: 1 dense + 3 expert
    # layers, MLA (nope 16 / rope 8 / v 16, q rank 24, latent 32), 16
    # routed experts 2 a token + 1 shared, sigmoid scores scaled 2.5,
    # sandwich norms. Served with block_size 4.
    "picotron-tpu/debug-tiny-pangu-moe": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        first_k_dense_replace=1, num_experts=16, num_experts_per_token=2,
        moe_intermediate_size=32, n_shared_experts=1, norm_topk_prob=True,
        moe_scoring="sigmoid", routed_scaling_factor=2.5,
        sandwich_norm=True, router_aux_coef=0.0,
    ),
    # Tiny K-EXAONE-shaped debug model: a dense sliding layer, then
    # S, S, F, S expert layers (to `pattern_of` a period (S, S, F) of the
    # expert stack's own slice and one layer left over), window 8,
    # head_dim (32) unequal to hidden / heads, per-head QK-norm, unrotated
    # full layer, 16 routed experts 2 a token + 1 shared, sigmoid scores
    # scaled 2.5. Served with block_size 4 and a prefill chunk longer than
    # the window.
    "picotron-tpu/debug-tiny-exaone-moe": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, max_position_embeddings=2048, rope_theta=10000.0,
        rms_norm_eps=1e-5,
        layer_types=("sliding_attention", "sliding_attention",
                     "sliding_attention", "full_attention",
                     "sliding_attention"),
        sliding_window=8, qk_norm="head",
        rope_parameters=dict(
            sliding_attention=dict(rope_type="default", rope_theta=10000.0),
            full_attention=dict(rope_type="none")),
        first_k_dense_replace=1, num_experts=16, num_experts_per_token=2,
        moe_intermediate_size=32, n_shared_experts=1, norm_topk_prob=True,
        moe_scoring="sigmoid", routed_scaling_factor=2.5,
        router_aux_coef=0.0,
    ),
    # Tiny EvaByte-shaped debug model: EVA attention at window 32 and chunk
    # 4 (8 summaries a window: two blocks of 4), 2 heads of 16, 3 prediction
    # heads over the 320-row vocabulary, 1 + w norms, float32 residual
    # stream. Served with block_size 4 and a prefill chunk of 8 or 16.
    "picotron-tpu/debug-tiny-evabyte": dict(
        vocab_size=320, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rope_theta=100000.0,
        rms_norm_eps=1e-5, attention_class="eva", window_size=32,
        chunk_size=4, num_pred_heads=3, norm_add_unit_offset=True,
        fp32_skip_add=True,
    ),
    # Tiny LongCat-shaped debug model: 3 shortcut layers (6 attention
    # sublayers), MLA as the tiny Pangu model's with both scales, a router
    # 24 wide (16 routed + 8 zero-compute experts) 4 a token by score +
    # bias, gates x 6 un-renormalised. Served with block_size 4.
    "picotron-tpu/debug-tiny-longcat": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        mla_scale_q_lora=True, mla_scale_kv_lora=True, shortcut_moe=True,
        num_experts=16, num_experts_per_token=4, moe_intermediate_size=32,
        zero_experts=8, moe_selection_bias=True, norm_topk_prob=False,
        routed_scaling_factor=6.0, router_aux_coef=0.0,
    ),
    # Tiny Qwen3-Next-shaped debug model: two periods of (L, L, L, F), the
    # mixer at 2 key heads and 4 value heads of 8, the gated attention at 4
    # heads over 2 KV heads of 16 with 4 rotated dimensions, 16 experts 2 a
    # token + a gated shared expert. Served with block_size 4.
    "picotron-tpu/debug-tiny-qwen3-next": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=2048, rope_theta=10000.0,
        rms_norm_eps=1e-6, partial_rotary_factor=0.25,
        layer_types=("linear_attention", "linear_attention",
                     "linear_attention", "full_attention") * 2,
        linear_conv_kernel_dim=4, linear_key_head_dim=8,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_value_head_dim=8, qk_norm="head", attn_output_gate=True,
        norm_add_unit_offset=True,
        num_experts=16, num_experts_per_token=2, moe_intermediate_size=32,
        n_shared_experts=1, shared_expert_gate=True, norm_topk_prob=True,
        router_aux_coef=0.0,
    ),
    # Tiny Jamba-shaped debug model: two periods of (3 mixers, 1 attention,
    # 2 mixers), d_inner 128 over a state of 4 and a step rank of 3, the
    # attention at 4 heads over ONE K/V head of 16, unrotated; dense MLPs,
    # tied head. Served with block_size 4.
    "picotron-tpu/debug-tiny-jamba": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=12, num_attention_heads=4, num_key_value_heads=1,
        max_position_embeddings=2048, rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        layer_types=(("mamba",) * 3 + ("full_attention",)
                     + ("mamba",) * 2) * 2,
        rope_parameters=dict(full_attention=dict(rope_type="none")),
        mamba_d_state=4, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=3,
        mamba_conv_bias=True, mamba_proj_bias=False,
    ),
    # Tiny Kimi-Linear-shaped debug model: two periods of (K, K, K, F), the
    # first layer dense (the expert stack's own slice is then a period (K, K,
    # F, K) and three layers left over, as the benchmark's 12-layer cut), the
    # mixer at 4 heads of 8 x 8, the latent attention at 4 heads without a
    # query bottleneck and without rotation, 16 experts 2 a token + 1 shared,
    # sigmoid scores with a selection bias. Served with block_size 4.
    "picotron-tpu/debug-tiny-kimi-linear": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
        layer_types=("kda", "kda", "kda", "full_attention") * 2,
        linear_conv_kernel_dim=4, linear_key_head_dim=8,
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_value_head_dim=8,
        q_lora_rank=0, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True,
        first_k_dense_replace=1, num_experts=16, num_experts_per_token=2,
        moe_intermediate_size=32, n_shared_experts=1, norm_topk_prob=True,
        moe_scoring="sigmoid", routed_scaling_factor=2.446,
        moe_selection_bias=True, router_aux_coef=0.0,
    ),
    # Tiny Nemotron-H-shaped debug model: layers of one sublayer each, two
    # periods of (*, E, M, E, M) and (*, E) left over (12 layers: 3
    # attentions, 5 expert layers, 4 mixers); the mixer at 8 heads of 16
    # channels in 2 groups with a state of 32 (a tail of 3 x 256 numbers);
    # 16 non-gated experts 4 a token on a latent of 32 beside one shared
    # expert of 48, sigmoid scores with a selection bias, scaled by 5.
    # Served with block_size 4.
    "picotron-tpu/debug-tiny-nemotron-h": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=12, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
        layer_types=("full_attention", "experts", "mamba2", "experts",
                     "mamba2") * 2 + ("full_attention", "experts"),
        rope_parameters=dict(full_attention=dict(rope_type="none")),
        hidden_act="relu2",
        mamba_num_heads=8, mamba_head_dim=16, n_groups=2, ssm_state_size=32,
        mamba_d_conv=4, mamba_conv_bias=True,
        num_experts=16, num_experts_per_token=4, moe_intermediate_size=24,
        moe_latent_size=32, n_shared_experts=1,
        moe_shared_expert_intermediate_size=48, norm_topk_prob=True,
        moe_scoring="sigmoid", routed_scaling_factor=5.0,
        moe_selection_bias=True, router_aux_coef=0.0,
    ),
}

# Aliases so shorthand names in configs resolve too.
_PRESET_ALIASES = {
    "SmolLM-135M": "HuggingFaceTB/SmolLM-135M",
    "SmolLM-360M": "HuggingFaceTB/SmolLM-360M",
    "HuggingFaceTB/SmolLM-360M-Instruct": "HuggingFaceTB/SmolLM-360M",
    "SmolLM-1.7B": "HuggingFaceTB/SmolLM-1.7B",
    "HuggingFaceTB/SmolLM-1.7B-Instruct": "HuggingFaceTB/SmolLM-1.7B",
    "Llama-2-7B": "meta-llama/Llama-2-7b-hf",
    "Llama-2-13B": "meta-llama/Llama-2-13b-hf",
    "Llama-2-70B": "meta-llama/Llama-2-70b-hf",
    "Llama-3-8B": "meta-llama/Meta-Llama-3-8B",
    "Llama-3.1-8B": "meta-llama/Llama-3.1-8B",
    "Llama-3.1-70B": "meta-llama/Llama-3.1-70B",
    "Mixtral-8x22B": "mistralai/Mixtral-8x22B-v0.1",
    "Llama-3.2-1B": "meta-llama/Llama-3.2-1B",
    "Llama-3.2-3B": "meta-llama/Llama-3.2-3B",
    "TinyLlama-1.1B": "TinyLlama/TinyLlama-1.1B-Chat-v1.0",
    "Mixtral-8x7B": "mistralai/Mixtral-8x7B-v0.1",
    "Qwen2-0.5B": "Qwen/Qwen2-0.5B",
    "Qwen2-1.5B": "Qwen/Qwen2-1.5B",
    "Qwen2-7B": "Qwen/Qwen2-7B",
    "debug-tiny": "picotron-tpu/debug-tiny",
    "debug-tiny-qwen": "picotron-tpu/debug-tiny-qwen",
    "debug-tiny-moe": "picotron-tpu/debug-tiny-moe",
    "OLMoE-1B-7B": "allenai/OLMoE-1B-7B-0125-Instruct",
    "debug-tiny-olmoe": "picotron-tpu/debug-tiny-olmoe",
    "Mellum2-12B-A2.5B": "JetBrains/Mellum2-12B-A2.5B-Instruct",
    "debug-tiny-mellum2": "picotron-tpu/debug-tiny-mellum2",
    "openPangu-Ultra-MoE-718B": "FreedomIntelligence/openPangu-Ultra-MoE-718B",
    "debug-tiny-pangu-moe": "picotron-tpu/debug-tiny-pangu-moe",
    "K-EXAONE-236B-A23B": "LGAI-EXAONE/K-EXAONE-236B-A23B",
    "debug-tiny-exaone-moe": "picotron-tpu/debug-tiny-exaone-moe",
    "EvaByte": "EvaByte/EvaByte",
    "debug-tiny-evabyte": "picotron-tpu/debug-tiny-evabyte",
    "LongCat-Flash-Omni": "meituan-longcat/LongCat-Flash-Omni",
    "debug-tiny-longcat": "picotron-tpu/debug-tiny-longcat",
    "Qwen3-Next-80B-A3B-Instruct": "Qwen/Qwen3-Next-80B-A3B-Instruct",
    "debug-tiny-qwen3-next": "picotron-tpu/debug-tiny-qwen3-next",
    "AI21-Jamba2-3B": "ai21labs/AI21-Jamba2-3B",
    "debug-tiny-jamba": "picotron-tpu/debug-tiny-jamba",
    "Kimi-Linear-48B-A3B-Instruct": "moonshotai/Kimi-Linear-48B-A3B-Instruct",
    "debug-tiny-kimi-linear": "picotron-tpu/debug-tiny-kimi-linear",
    "NVIDIA-Nemotron-3-Super-120B-A12B":
        "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B",
    "debug-tiny-nemotron-h": "picotron-tpu/debug-tiny-nemotron-h",
}


def resolve_preset(name: str) -> dict[str, Any]:
    key = _PRESET_ALIASES.get(name, name)
    if key in MODEL_PRESETS:
        return dict(MODEL_PRESETS[key])
    raise KeyError(
        f"Unknown model preset {name!r}. Known presets: "
        f"{sorted(MODEL_PRESETS) + sorted(_PRESET_ALIASES)}. "
        "Pass explicit hyperparameters in the `model` config section instead."
    )


def resolve_hf_name(name: str) -> str:
    """Canonical HF hub id for a preset shorthand ('SmolLM-1.7B' ->
    'HuggingFaceTB/SmolLM-1.7B'); unknown names pass through unchanged."""
    return _PRESET_ALIASES.get(name, name)


# Nemotron-H's config.json: what the reader below does with each key. A key
# in neither set is REFUSED by name: `_filter_kwargs` ignores unknown keys on
# load, which is right for a dumped config of an older run and wrong for a
# published architecture, where a key this reader does not know (as
# `moe_latent_size` once was) would silently build another model (experts on
# the full width, 450 B parameters for 120 B).
_NEMOTRON_H_READ = frozenset((
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "num_attention_heads", "num_key_value_heads",
    "head_dim", "max_position_embeddings", "rope_theta", "layer_norm_epsilon",
    "norm_eps", "tie_word_embeddings", "mamba_num_heads", "mamba_head_dim",
    "n_groups", "ssm_state_size", "conv_kernel", "use_conv_bias", "expand",
    "mlp_hidden_act", "mamba_hidden_act", "n_routed_experts",
    "num_experts_per_tok", "moe_intermediate_size", "moe_latent_size",
    "moe_shared_expert_intermediate_size", "n_shared_experts",
    "norm_topk_prob", "routed_scaling_factor", "n_group", "topk_group",
    "attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias",
    "residual_in_fp32", "sliding_window", "intermediate_size"))
# read by nothing: the SSD chunk (an implementation's own), the rotation keys
# (the modelling code applies none: the attention is position-free), the
# initialiser's ranges, kernel switches, the drafting module (not built:
# ROADMAP M8) and a checkpoint's bookkeeping
_NEMOTRON_H_UNUSED = frozenset((
    "model_type", "chunk_size", "partial_rotary_factor", "num_logits_to_keep",
    "rescale_prenorm_residual", "time_step_floor", "time_step_max",
    "time_step_min", "time_step_limit", "use_mamba_kernels",
    "moe_shared_expert_overlap", "mtp_hybrid_override_pattern",
    "num_nextn_predict_layers", "initializer_range", "torch_dtype", "dtype",
    "architectures", "transformers_version", "bos_token_id", "eos_token_id",
    "pad_token_id", "use_cache", "attention_dropout", "hidden_dropout",
    "auto_map", "rope_scaling", "mamba_ssm_cache_dtype"))


def _nemotron_h_kwargs(hf: dict[str, Any]) -> dict[str, Any]:
    """ModelConfig kwargs of a `nemotron_h` config.json: layers that are ONE
    sublayer each, a letter of `hybrid_override_pattern` a layer (`M` a
    Mamba-2 mixer, `*` a softmax attention without rotation, `E` a LatentMoE
    expert block; `-`, a dense MLP layer, is not built), the mixer's and the
    experts' sizes under the HF names. A key the reader does not know is
    refused by name (the comment at `_NEMOTRON_H_READ`)."""
    unknown = sorted(set(hf) - _NEMOTRON_H_READ - _NEMOTRON_H_UNUSED)
    if unknown:
        raise ValueError(
            f"nemotron_h: config key(s) {unknown} are not known to this "
            f"reader; an unknown key of a published architecture is refused, "
            f"not ignored (it may change what a layer is)")
    letters = {"M": SSD, "*": "full_attention", "E": MOE}
    pattern = hf["hybrid_override_pattern"]
    bad = sorted(set(pattern) - set(letters))
    if bad:
        raise ValueError(
            f"nemotron_h: hybrid_override_pattern holds {bad}; built are "
            f"'M' (Mamba-2 mixer), '*' (attention) and 'E' (experts); a "
            f"dense MLP layer ('-') is not")
    if len(pattern) != hf["num_hidden_layers"]:
        raise ValueError(
            f"nemotron_h: hybrid_override_pattern names {len(pattern)} "
            f"layers, num_hidden_layers is {hf['num_hidden_layers']}")
    for key, want in (("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
                      ("n_group", 1), ("topk_group", 1),
                      ("attention_bias", False), ("mlp_bias", False),
                      ("use_bias", False), ("mamba_proj_bias", False),
                      ("residual_in_fp32", False), ("sliding_window", None)):
        if hf.get(key, want) != want:
            raise ValueError(
                f"nemotron_h with {key} = {hf[key]!r}: only {want!r} is built")
    heads, p = int(hf["mamba_num_heads"]), int(hf["mamba_head_dim"])
    if "expand" in hf and int(hf["expand"]) * hf["hidden_size"] != heads * p:
        raise ValueError(
            f"nemotron_h: expand x hidden_size ({hf['expand']} x "
            f"{hf['hidden_size']}) is not mamba_num_heads x mamba_head_dim "
            f"({heads} x {p})")
    n_heads = hf["num_attention_heads"]
    out: dict[str, Any] = {
        "vocab_size": hf["vocab_size"], "hidden_size": hf["hidden_size"],
        # a dense MLP layer's width: no layer of a built pattern reads it
        "intermediate_size": hf.get("intermediate_size",
                                    hf.get("moe_intermediate_size", 0)),
        "num_hidden_layers": hf["num_hidden_layers"],
        "num_attention_heads": n_heads,
        "num_key_value_heads": hf.get("num_key_value_heads", n_heads),
        "head_dim": hf.get("head_dim", hf["hidden_size"] // n_heads),
        "max_position_embeddings": hf.get("max_position_embeddings", 2048),
        "rope_theta": float(hf.get("rope_theta", 10000.0)),
        "rms_norm_eps": float(hf.get("layer_norm_epsilon",
                                     hf.get("norm_eps", 1e-5))),
        "tie_word_embeddings": bool(hf.get("tie_word_embeddings", False)),
        "layer_types": tuple(letters[c] for c in pattern),
        # position-free attention: the mixers order the sequence
        "rope_parameters": {"full_attention": {"rope_type": "none"}},
        "hidden_act": "relu2",
        "mamba_num_heads": heads, "mamba_head_dim": p,
        "n_groups": int(hf["n_groups"]),
        "ssm_state_size": int(hf["ssm_state_size"]),
        "mamba_d_conv": int(hf["conv_kernel"]),
        "mamba_conv_bias": bool(hf.get("use_conv_bias", True)),
    }
    if "E" in pattern:
        shared = int(hf.get("n_shared_experts", 0))
        if shared not in (0, 1):
            raise ValueError(
                f"nemotron_h with n_shared_experts = {shared}: one shared "
                f"expert of moe_shared_expert_intermediate_size, or none")
        out.update({
            "num_experts": int(hf["n_routed_experts"]),
            "num_experts_per_token": int(hf["num_experts_per_tok"]),
            "moe_intermediate_size": int(hf["moe_intermediate_size"]),
            "moe_latent_size": int(hf.get("moe_latent_size") or 0),
            "n_shared_experts": shared,
            "moe_shared_expert_intermediate_size": int(
                hf["moe_shared_expert_intermediate_size"]) if shared else 0,
            "norm_topk_prob": bool(hf.get("norm_topk_prob", True)),
            "routed_scaling_factor": float(
                hf.get("routed_scaling_factor", 1.0)),
            "moe_scoring": "sigmoid", "moe_selection_bias": True,
            "router_aux_coef": 0.0,
        })
    return out


def model_config_from_hf_json(path_or_dict) -> dict[str, Any]:
    """ModelConfig kwargs from a local HF `config.json` — the OFFLINE
    equivalent of the reference's network AutoConfig fetch
    (ref: create_config.py:51-55): any Llama/Qwen2/Mixtral/OLMoE/Mellum/
    Pangu-Ultra-MoE/EXAONE-MoE/EvaByte/LongCat-Flash/Qwen3-Next/Jamba/Kimi-Linear/Nemotron-H-family model outside the preset registry
    resolves from its config file instead of hand-typed hyperparameters.
    Pass a path or an already-parsed dict."""
    if isinstance(path_or_dict, dict):
        hf = path_or_dict
    else:
        with open(path_or_dict) as f:
            hf = json.load(f)

    # LongCat-Flash's config.json is told by its own keys where a copy
    # carries no model_type (the catalog's rows do not)
    mtype = hf.get("model_type") or (
        "longcat_flash" if "zero_expert_num" in hf else "llama")
    supported = ("llama", "mistral", "mixtral", "qwen2", "olmoe", "mellum",
                 "pangu_ultra_moe", "exaone_moe", "evabyte", "longcat_flash",
                 "qwen3_next", "jamba", "kimi_linear", "nemotron_h")
    if mtype not in supported:
        raise ValueError(
            f"model_type {mtype!r} is not a supported architecture family "
            f"({supported}); the model layer (models/llama.py) implements "
            "the Llama lineage")

    if mtype == "jamba":
        # the dense member of the family (num_experts 1: every feed-forward
        # the gated MLP, whatever expert_layer_period says); the MoE siblings
        # put experts in every expert_layer_period-th layer beside dense
        # MLPs in the others, which no stack of this tree holds
        if int(hf.get("num_experts", 1)) > 1:
            raise ValueError(
                f"jamba with num_experts = {hf['num_experts']}: experts in "
                f"every expert_layer_period-th layer beside dense MLPs in "
                f"the others are not built (num_experts must be 1: the "
                f"dense Jamba)")
        hf = {k: v for k, v in hf.items()
              if k not in ("num_experts", "num_experts_per_tok")}
    if mtype == "nemotron_h":
        return _nemotron_h_kwargs(hf)
    if mtype == "longcat_flash":
        # its names for the depth, the two widths and the experts a token
        hf = {**hf, "num_hidden_layers": hf["num_layers"],
              "intermediate_size": hf["ffn_hidden_size"],
              "moe_intermediate_size": hf["expert_ffn_hidden_size"],
              "num_experts_per_tok": hf["moe_topk"]}
    heads = hf["num_attention_heads"]
    out: dict[str, Any] = {
        "vocab_size": hf["vocab_size"],
        "hidden_size": hf["hidden_size"],
        "intermediate_size": hf["intermediate_size"],
        "num_hidden_layers": hf["num_hidden_layers"],
        "num_attention_heads": heads,
        "num_key_value_heads": hf.get("num_key_value_heads", heads),
        # (Kimi-Linear publishes its length as model_max_length)
        "max_position_embeddings": hf.get(
            "max_position_embeddings", hf.get("model_max_length", 2048)),
        "rope_theta": float(hf.get("rope_theta", 10000.0)),
        "rms_norm_eps": float(hf.get("rms_norm_eps", 1e-5)),
        "tie_word_embeddings": bool(hf.get("tie_word_embeddings", False)),
        # Qwen2 carries qkv bias as attention_bias=absent + model_type;
        # Llama exposes the flag directly
        "attention_bias": bool(hf.get("attention_bias",
                                      mtype == "qwen2")),
    }
    act = hf.get("hidden_act", "silu")
    if act in ("silu", "swish"):
        out["hidden_act"] = "silu"
    elif act == "gelu":
        # transformers' ACT2FN "gelu" is the EXACT erf GELU — mapping it
        # to the tanh approximation would silently drift logits vs the HF
        # reference (code review r4)
        out["hidden_act"] = "gelu"
    elif act in ("gelu_new", "gelu_pytorch_tanh"):
        out["hidden_act"] = "gelu_tanh"
    else:
        raise ValueError(
            f"hidden_act {act!r} unsupported (silu/gelu gated MLPs only)")
    if hf.get("rope_scaling"):
        out["rope_scaling"] = dict(hf["rope_scaling"])
    # Mixtral spells the expert count num_local_experts, OLMoE num_experts,
    # the DeepSeek-V3 lineage (Pangu Ultra MoE) n_routed_experts
    n_experts = (hf.get("num_local_experts") or hf.get("num_experts")
                 or hf.get("n_routed_experts"))
    if n_experts:
        out["num_experts"] = n_experts
        out["num_experts_per_token"] = hf.get(
            "num_experts_per_tok", hf.get("num_experts_per_token", 2))
        # Mixtral always renormalizes its k gates and has no key for it;
        # OLMoE publishes the key (false)
        out["norm_topk_prob"] = bool(hf.get("norm_topk_prob", True))
        if "router_aux_loss_coef" in hf:
            out["router_aux_coef"] = float(hf["router_aux_loss_coef"])
    if "head_dim" in hf:
        out["head_dim"] = hf["head_dim"]
    if hf.get("moe_intermediate_size"):
        out["moe_intermediate_size"] = hf["moe_intermediate_size"]
    mlp_kinds = tuple(hf.get("mlp_layer_types") or ())
    if "dense" in mlp_kinds:
        # the layer tree is two stacks, the leading dense layers and the
        # expert layers after them (first_k_dense_replace); a dense layer
        # anywhere else has no stack to live in
        lead = mlp_kinds.index("sparse") if "sparse" in mlp_kinds else 0
        if not lead or "dense" in mlp_kinds[lead:]:
            raise ValueError(
                "mlp_layer_types holds a 'dense' entry behind a 'sparse' "
                "one (or no 'sparse' entry at all): dense MLP layers are "
                "supported at the head of the stack only, before every "
                "expert layer (first_k_dense_replace)")
        out["first_k_dense_replace"] = lead
    if mtype == "pangu_ultra_moe":
        # MLA's widths, the leading dense layers, the shared expert and
        # the router's law. config.json has routed_scaling_factor and
        # norm_topk_prob and no scoring_func / n_group / topk_method key:
        # sigmoid scores, no selection bias, no expert groups (the
        # family's modelling code, the DeepSeek-V3 convention those two
        # keys come from). sandwich_norm: four RMSNorms a layer.
        for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                    "qk_rope_head_dim", "v_head_dim"):
            out[key] = int(hf[key])
        out["first_k_dense_replace"] = int(hf.get("first_k_dense_replace", 0))
        out["n_shared_experts"] = int(hf.get("n_shared_experts", 0))
        out["moe_scoring"] = "sigmoid"
        out["routed_scaling_factor"] = float(
            hf.get("routed_scaling_factor", 1.0))
        out["sandwich_norm"] = bool(hf.get("sandwich_norm", False))
        out["router_aux_coef"] = 0.0
    if hf.get("layer_types"):
        # layer_types decides which layers slide (use_sliding_window /
        # max_window_layers are read as "layer_types decides")
        out["layer_types"] = tuple(hf["layer_types"])
        if "sliding_attention" in out["layer_types"]:
            out["sliding_window"] = int(hf["sliding_window"])
    if hf.get("rope_parameters"):
        rp = hf["rope_parameters"]
        if "rope_type" in rp or "rope_theta" in rp:
            # one law for every layer, the newer spelling of
            # rope_theta + rope_scaling
            out["rope_theta"] = float(rp.get("rope_theta", out["rope_theta"]))
            if rp.get("rope_type", "default") != "default":
                out["rope_scaling"] = {k: v for k, v in rp.items()
                                       if k != "rope_theta"}
        else:
            out["rope_parameters"] = rp
            out["rope_theta"] = float(next(iter(rp.values())).get(
                "rope_theta", out["rope_theta"]))
    if mtype == "exaone_moe":
        # K-EXAONE: a dense layer before the expert layers
        # (first_k_dense_replace, or the leading 'dense' entries of
        # mlp_layer_types above), a shared expert (num_shared_experts), the
        # router's law by its own keys (scoring_func, routed_scaling_factor;
        # n_group / topk_group 1: no expert groups, and groups are not
        # built), sliding_windows a per-layer restatement of layer_types +
        # sliding_window. config.json has no key for two things the
        # family's modelling code does, both `assumed` where a benchmark
        # configuration states them: RMSNorm over each head of q and k
        # (qk_norm 'head'), and no rotation on the full-attention layers
        # (the one published law is the sliding layers').
        if int(hf.get("n_group", 1)) != 1 or int(hf.get("topk_group", 1)) != 1:
            raise ValueError(
                "exaone_moe with n_group / topk_group != 1: routing within "
                "expert groups is not built (the router picks the k largest "
                "of all its scores)")
        if "first_k_dense_replace" in hf:
            out["first_k_dense_replace"] = int(hf["first_k_dense_replace"])
        out["n_shared_experts"] = int(hf.get("num_shared_experts", 0))
        out["moe_scoring"] = hf.get("scoring_func", "softmax")
        out["routed_scaling_factor"] = float(
            hf.get("routed_scaling_factor", 1.0))
        out["router_aux_coef"] = 0.0
        out["qk_norm"] = "head"
        kinds = out.get("layer_types")
        if kinds:
            windows = hf.get("sliding_windows")
            if windows is not None and [int(w) for w in windows] != [
                    out["sliding_window"] if k == "sliding_attention" else 0
                    for k in kinds]:
                raise ValueError(
                    "sliding_windows disagrees with layer_types / "
                    "sliding_window: one window for every sliding layer "
                    "and 0 on a full layer is what is built")
            theta = out["rope_theta"]
            out.pop("rope_scaling", None)
            out["rope_parameters"] = {
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": theta},
                "full_attention": {"rope_type": "none"}}
    if mtype == "evabyte":
        # EvaByte: EVA attention by its own keys (attention_class eva,
        # window_size, chunk_size), the head of num_pred_heads x vocab_size
        # rows, RMSNorm with 1 + w, float32 residual adds. What config.json
        # has no key for (the pooling law, when a window's summaries are
        # seen) is `assumed` where a benchmark configuration states it.
        if hf.get("attention_class", "eva") != "eva":
            raise ValueError(
                f"evabyte with attention_class "
                f"{hf.get('attention_class')!r}: only 'eva' is built")
        out["attention_class"] = "eva"
        out["window_size"] = int(hf["window_size"])
        out["chunk_size"] = int(hf["chunk_size"])
        out["num_pred_heads"] = int(hf.get("num_pred_heads", 1))
        out["norm_add_unit_offset"] = bool(
            hf.get("norm_add_unit_offset", False))
        out["fp32_skip_add"] = bool(hf.get("fp32_skip_add", False))
    if mtype == "longcat_flash":
        # LongCat-Flash: MLA's widths and its two latent scales (booleans;
        # the factors are the modelling code's, `assumed` where a benchmark
        # configuration states them), the double layer with its shortcut
        # expert branch (every layer; no key), the zero-compute experts
        # beside the routed ones in one softmax router, a selection bias
        # (e_score_correction_bias, a buffer of the checkpoint), gates
        # scaled and not renormalised (no norm_topk_prob key: false)
        if hf.get("attention_method", "MLA") != "MLA":
            raise ValueError(
                f"longcat_flash with attention_method "
                f"{hf.get('attention_method')!r}: only 'MLA' is built")
        if hf.get("zero_expert_type", "identity") != "identity":
            raise ValueError(
                f"longcat_flash with zero_expert_type "
                f"{hf.get('zero_expert_type')!r}: only 'identity' is built")
        for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                    "qk_rope_head_dim", "v_head_dim"):
            out[key] = int(hf[key])
        out["mla_scale_q_lora"] = bool(hf.get("mla_scale_q_lora", False))
        out["mla_scale_kv_lora"] = bool(hf.get("mla_scale_kv_lora", False))
        out["shortcut_moe"] = True
        out["zero_experts"] = int(hf.get("zero_expert_num", 0))
        out["moe_selection_bias"] = True
        out["norm_topk_prob"] = bool(hf.get("norm_topk_prob", False))
        out["routed_scaling_factor"] = float(
            hf.get("routed_scaling_factor", 1.0))
        out["router_aux_coef"] = 0.0
    if mtype == "qwen3_next":
        # Qwen3-Next: the pattern by full_attention_interval (layer i is
        # full attention when (i + 1) % interval == 0, a Gated DeltaNet
        # mixer otherwise), the mixer's sizes by its own keys, the rotated
        # share of a head, experts in every layer (decoder_sparse_step 1,
        # mlp_only_layers empty: anything else is not built), the shared
        # expert by its width. config.json has no key for four things the
        # family's modelling code does, each `assumed` where a benchmark
        # configuration states it: per-head QK-norm, 1 + w norms, the
        # attention's output gate, the shared expert's sigmoid gate.
        if int(hf.get("decoder_sparse_step", 1)) != 1 or hf.get(
                "mlp_only_layers"):
            raise ValueError(
                "qwen3_next with decoder_sparse_step != 1 or mlp_only_layers"
                ": dense MLP layers among the expert layers are not built "
                "(every layer holds the experts)")
        every = int(hf["full_attention_interval"])
        out["layer_types"] = tuple(
            "full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(out["num_hidden_layers"]))
        for key in ("linear_conv_kernel_dim", "linear_key_head_dim",
                    "linear_num_key_heads", "linear_num_value_heads",
                    "linear_value_head_dim"):
            out[key] = int(hf[key])
        out["partial_rotary_factor"] = float(
            hf.get("partial_rotary_factor", 1.0))
        shared, width = (int(hf.get("shared_expert_intermediate_size", 0)),
                         int(hf["moe_intermediate_size"]))
        if shared % width:
            raise ValueError(
                f"qwen3_next: shared_expert_intermediate_size ({shared}) is "
                f"not a whole number of experts of moe_intermediate_size "
                f"({width})")
        out["n_shared_experts"] = shared // width
        out["shared_expert_gate"] = shared > 0
        out["qk_norm"] = "head"
        out["attn_output_gate"] = True
        out["norm_add_unit_offset"] = True
        out["router_aux_coef"] = 0.0
    if mtype == "jamba":
        # Jamba: layer i is a softmax attention where i % attn_layer_period
        # == attn_layer_offset, a Mamba mixer otherwise (the released
        # modelling code's rule; config.json has the two keys and no list),
        # the mixer's sizes by its own keys; the attentions carry no
        # position term of any kind (the mixers order the sequence)
        every, at = int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
        out["layer_types"] = tuple(
            "full_attention" if i % every == at else SSM
            for i in range(out["num_hidden_layers"]))
        out["rope_parameters"] = {"full_attention": {"rope_type": "none"}}
        for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand"):
            out[key] = int(hf[key])
        rank = hf.get("mamba_dt_rank", "auto")
        out["mamba_dt_rank"] = (-(-out["hidden_size"] // 16) if rank == "auto"
                                else int(rank))
        out["mamba_conv_bias"] = bool(hf.get("mamba_conv_bias", True))
        out["mamba_proj_bias"] = bool(hf.get("mamba_proj_bias", False))
    if mtype == "kimi_linear":
        # Kimi-Linear: the layer order from linear_attn_config's two
        # 1-indexed lists (an entry beyond num_hidden_layers names no layer
        # of a model cut in depth), the mixer's heads and convolution by the
        # same group (one head count and one width for q, k and v), MLA's
        # widths with q_lora_rank null (no query bottleneck) and
        # mla_use_nope (neither q_pe nor k_pe is rotated), the leading dense
        # layer, the router's law by its own keys (use_grouped_topk with ONE
        # group is no grouping; more are not built) and a selection bias
        # (e_score_correction_bias, a buffer of the checkpoint)
        lin = hf["linear_attn_config"]
        n = out["num_hidden_layers"]
        kda_at, full_at = set(lin["kda_layers"]), set(lin["full_attn_layers"])
        if kda_at & full_at or not set(range(1, n + 1)) <= kda_at | full_at:
            raise ValueError(
                f"kimi_linear: linear_attn_config's kda_layers and "
                f"full_attn_layers must name each of the layers 1..{n} once")
        out["layer_types"] = tuple(
            KDA if i in kda_at else "full_attention" for i in range(1, n + 1))
        out["linear_conv_kernel_dim"] = int(lin["short_conv_kernel_size"])
        out["linear_num_key_heads"] = int(lin["num_heads"])
        out["linear_num_value_heads"] = int(lin["num_heads"])
        out["linear_key_head_dim"] = int(lin["head_dim"])
        out["linear_value_head_dim"] = int(lin["head_dim"])
        if (int(hf.get("num_expert_group", 1)) != 1
                or int(hf.get("topk_group", 1)) != 1):
            raise ValueError(
                "kimi_linear with num_expert_group / topk_group != 1: "
                "routing within expert groups is not built (the router picks "
                "the k largest of all its scores)")
        if int(hf.get("moe_layer_freq", 1)) != 1:
            raise ValueError(
                "kimi_linear with moe_layer_freq != 1: dense MLP layers "
                "among the expert layers are not built (every layer behind "
                "first_k_dense_replace holds the experts)")
        for key in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                    "v_head_dim"):
            out[key] = int(hf[key])
        out["q_lora_rank"] = int(hf.get("q_lora_rank") or 0)
        out["mla_use_nope"] = bool(hf.get("mla_use_nope", False))
        out["first_k_dense_replace"] = int(hf.get("first_k_dense_replace", 0))
        out["n_shared_experts"] = int(hf.get("num_shared_experts", 0))
        out["moe_scoring"] = hf.get("moe_router_activation_func", "sigmoid")
        out["norm_topk_prob"] = bool(hf.get("moe_renormalize", True))
        out["routed_scaling_factor"] = float(
            hf.get("routed_scaling_factor", 1.0))
        out["moe_selection_bias"] = True
        out["router_aux_coef"] = 0.0
        # published, and used by neither mixer (the default hidden / heads)
        out.pop("head_dim", None)
    if mtype == "olmoe":
        # config.json has no key for either: OLMoE's intermediate_size IS
        # the width of one expert, and its attention normalizes q and k
        # (modeling_olmoe.py)
        out["moe_intermediate_size"] = hf["intermediate_size"]
        out["qk_norm"] = True
        if hf.get("clip_qkv") is not None:
            raise ValueError("olmoe with clip_qkv set is not supported")
    return out


# ---------------------------------------------------------------------------
# Config sections — mirror the reference JSON sections one-to-one.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DistributedConfig:
    """4D parallel layout (ref: template/base_config.json:2-10)."""

    tp_size: int = 1
    cp_size: int = 1
    pp_size: int = 1
    dp_size: int = 1
    pp_engine: str = "1f1b"  # "1f1b" | "afab"
    # Sequence layout across cp shards: "zigzag" gives each shard one early
    # and one late chunk so causal attention work is balanced around the ring
    # (the reference splits contiguously and carries the known imbalance +
    # a zigzag TODO, ref: data.py:105-109, tests/test_dataloader.py:136).
    # "contiguous" reproduces the reference layout.
    cp_layout: str = "zigzag"
    # Context-parallel attention schedule: "" derives the flavor from
    # model.attn_impl (back-compat: "ring"/"ulysses"/"mesh" there select
    # directly, anything else defaults to the ring). "mesh" is the 2D
    # schedule (ops/mesh_attention.py): cp factors into cp_x x cp_y, an
    # Ulysses-style head scatter runs within cp_y subgroups and a K/V
    # ring over the cp_x rows — same per-hop volume as the ring but only
    # cp_x-1 hops, head divisibility required only by cp_y.
    cp_flavor: str = ""  # "" | "ring" | "ulysses" | "mesh"
    # Mesh-flavor factorization "XxY" (e.g. "2x4": cp_x=2 ring rows,
    # cp_y=4 head-scatter columns); X*Y must equal cp_size. "" picks the
    # most-square feasible factorization (resolved_cp_mesh); the planner
    # enumerates all feasible ones against the ICI cost model.
    cp_mesh: str = ""
    # Expert parallelism: shards MoE expert banks over a dedicated mesh
    # axis; acts as an additional data axis for non-expert computation
    # (batch over the fused ('dp','ep') axes). Requires a MoE model
    # (model.num_experts > 0) when > 1.
    ep_size: int = 1
    # Megatron-style sequence parallelism over the tp axis (the reference
    # leaves this as a TODO, ref: utils.py:66): between blocks the residual
    # stream / norms are sharded [*, S/tp, H] and the TP entry/exit
    # collectives become all_gather / reduce_scatter (same bytes as the
    # psum they replace; tp x less pipeline boundary traffic). Memory: the
    # tp x shrink applies only to the norm/residual tensors BETWEEN g and
    # f — measured ~5% of total activation memory with remat off and ~0
    # under the dots remat policies, whose saved dot outputs sit after the
    # gather and stay full-sequence (tools/memcheck.py --override, PERF.md
    # round 4). Use it for the boundary traffic, not as a memory lever.
    sequence_parallel: bool = False
    # ZeRO-1 optimizer-state sharding (beyond the reference): shards the
    # Adam moments over 'dp' in addition to their param's tp/pp/ep
    # sharding. GSPMD turns the sharding annotation into the per-shard
    # update + all-gather schedule, cutting resident moment memory by
    # ~dp_size — measured on SmolLM-1.7B dp8: 13.5 -> 1.69 GiB/device of
    # moments, 20.25 -> 8.44 GiB/device total state (tools/memcheck.py
    # --override distributed.zero1=true; PERF.md round 4).
    zero1: bool = False
    # Multi-slice topology: number of TPU slices joined over DCN (the
    # data-center network). 1 = single slice, everything on ICI. When > 1
    # the slice granules are absorbed into the DCN-tolerant axes (dp first,
    # then pp — mesh._split_axes_over_dcn), so only their collectives
    # cross the cut.
    slices: int = 1
    # Accepted for reference-JSON compatibility; ignored (XLA picks transport).
    backend: str = "jax"
    use_cpu: bool = False

    @property
    def world_size(self) -> int:
        return (self.tp_size * self.cp_size * self.pp_size * self.dp_size
                * self.ep_size)

    def validate(self) -> None:
        for name in ("tp_size", "cp_size", "pp_size", "dp_size", "ep_size"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, got {getattr(self, name)}")
        if self.pp_engine not in ("1f1b", "afab"):
            raise ValueError(f"pp_engine must be '1f1b' or 'afab', got {self.pp_engine!r}")
        if self.cp_layout not in ("zigzag", "contiguous"):
            raise ValueError(
                f"cp_layout must be 'zigzag' or 'contiguous', got {self.cp_layout!r}")
        if self.cp_flavor not in ("", "ring", "ulysses", "mesh"):
            raise ValueError(
                f"cp_flavor must be one of ring/ulysses/mesh (or empty to "
                f"derive from model.attn_impl), got {self.cp_flavor!r}")
        if self.cp_flavor and self.cp_size == 1:
            raise ValueError(
                f"cp_flavor={self.cp_flavor!r} requires cp_size > 1 (it "
                "names a context-parallel schedule)")
        if self.cp_mesh:
            cp_x, cp_y = parse_cp_mesh(self.cp_mesh)
            if cp_x * cp_y != self.cp_size:
                raise ValueError(
                    f"cp_mesh '{self.cp_mesh}' must factor the cp degree: "
                    f"{cp_x} * {cp_y} != cp_size ({self.cp_size})")
        if self.slices < 1:
            raise ValueError(f"slices must be >= 1, got {self.slices}")
        if self.slices > 1:
            # Mirror mesh._split_axes_over_dcn: the slice count must divide
            # dp*pp so ep/cp/tp collectives stay on ICI.
            if self.slices > self.dp_size * self.pp_size or (
                    self.dp_size * self.pp_size) % self.slices != 0:
                raise ValueError(
                    f"slices ({self.slices}) must divide dp*pp "
                    f"({self.dp_size}*{self.pp_size}="
                    f"{self.dp_size * self.pp_size}) — ep/cp/tp collectives "
                    "must stay on ICI. Rebalance the layout so dp*pp "
                    "absorbs the slice count.")


def _parse_mesh2(spec: str, field: str) -> tuple[int, int]:
    parts = spec.lower().split("x")
    try:
        m_x, m_y = (int(p) for p in parts)
    except (TypeError, ValueError):
        raise ValueError(
            f"{field} must be 'XxY' (two positive integers, e.g. '2x4'), "
            f"got {spec!r}") from None
    if m_x < 1 or m_y < 1:
        raise ValueError(f"{field} factors must be >= 1, got {spec!r}")
    return m_x, m_y


def parse_cp_mesh(spec: str) -> tuple[int, int]:
    """'XxY' -> (cp_x, cp_y), with a field-naming error (not a bare int
    crash) on malformed input."""
    return _parse_mesh2(spec, "cp_mesh")


GDN = "linear_attention"  # the kind of a layer that is a Gated DeltaNet mixer
SSM = "mamba"  # the kind of a layer that is a Mamba-1 selective-scan mixer
KDA = "kda"  # the kind of a layer that is a Kimi Delta Attention mixer
# the kind of a layer that is a Mamba-2 (SSD) mixer ALONE, with no MLP behind
# it (a model whose layers are one sublayer each: `ModelConfig.single_sublayer`)
SSD = "mamba2"
# the kind of a layer that is an expert block ALONE, with no mixer before it
MOE = "experts"
# the kinds whose mixer carries a state a SEQUENCE, not a row a position
RECURRENT = (GDN, SSM, KDA, SSD)


class Block(NamedTuple):
    """What one decoder block is made of: the one description that the
    training forward (`models.llama.decoder_layer`) and the cached decode
    forward (`generate._decode_layers`) both read. A block is a mixer
    FOLLOWED BY an MLP, each behind a norm of its own, unless it is `alone`:
    then a layer is ONE sublayer behind ONE norm, `x + f(norm(x))`, and the
    layer's kind (`Stack.kinds`) says which: the softmax attention
    ("full_attention"), a Mamba-2 mixer ("mamba2") or the experts
    ("experts"); `attn` and `mlp` then say what those are made of."""

    # "gqa": q/k/v per head | "mla": latent attention | "eva": q/k/v per
    # head over the open window's keys and a summary a chunk of the rest.
    # A layer whose kind is "linear_attention" (`Stack.kinds`) runs a Gated
    # DeltaNet mixer in this attention's place (ops/gated_delta.py), over a
    # recurrent state and not over cached positions; one whose kind is
    # "mamba" a Mamba-1 mixer (ops/selective_scan.py), one whose kind is
    # "kda" a Kimi Delta Attention mixer (ops/kda.py), likewise
    attn: str
    # "dense": gated MLP | "experts": routed (+ shared) experts |
    # "shortcut": the layer is TWO (attention, dense gated MLP) pairs, and
    # routed experts read the first pair's normed MLP input and land on the
    # residual stream after the second pair's MLP (every per-pair leaf of
    # the stack has a sublayer axis behind the layer axis)
    mlp: str
    sandwich: bool  # RMSNorms on the attention's and the MLP's outputs too
    alone: bool = False  # a layer is ONE sublayer, named by its kind

    @property
    def attentions(self) -> int:
        """Attention sublayers a layer, each with its own cache row."""
        return 2 if self.mlp == "shortcut" else 1


def pattern_of(kinds: tuple) -> tuple:
    """(period, whole, rest) of a run of layer kinds: the shortest `period`
    with kinds[i] == period[i % len(period)] for every i, how many `whole`
    periods the run holds, and the `rest` after them (a head of the period,
    shorter than it). A layer scan runs the whole periods, one an
    iteration with each layer's kind static in the body, and the rest is
    run after it, outside the scan."""
    for p in range(1, len(kinds) + 1):
        if all(kinds[i] == kinds[i % p] for i in range(p, len(kinds))):
            return kinds[:p], len(kinds) // p, kinds[len(kinds) // p * p:]
    return (), 0, ()


class Stack(NamedTuple):
    """One stack of the layer tree (`ModelConfig.stacks`): layers of one
    kind of block, stacked on a leading axis under one params key. It knows
    its own slice of the model's layer pattern: the dense stack of a model
    whose pattern is (S, S, S, F) x n is (S,), and its expert stack starts
    one layer into the pattern."""

    name: str     # params key: "layers" | "dense_layers"
    layers: int
    block: Block
    kinds: tuple  # the attention kind of each layer: its slice of layer_kinds


@dataclass(frozen=True)
class ModelConfig:
    """Llama-family architecture hyperparameters.

    Resolved from a preset by name with explicit overrides on top
    (ref: create_config.py:51-63 does the same via AutoConfig + overrides).
    """

    name: str = "picotron-tpu/debug-tiny"
    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 128
    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    max_position_embeddings: int = 2048
    rope_theta: float = 10000.0
    # HF-style rope_scaling config (Llama-3.1/3.2's {"rope_type": "llama3",
    # "factor": 8.0, ...} or {"rope_type": "linear", "factor": N}); None =
    # unscaled. Stored internally as a sorted (key, value) tuple so the
    # frozen config stays hashable (generation jits with the config as a
    # static argument); pass a plain dict, __post_init__ normalizes.
    rope_scaling: Optional[Any] = None
    # Size of one attention head. None = hidden_size // num_attention_heads
    # (the Llama convention; resolved in __post_init__). A model that
    # publishes the key (Mellum2: 128 at hidden 2304 / 32 heads) has q and
    # o projections of hidden x (heads * head_dim).
    head_dim: Optional[int] = None
    # The published per-layer attention kinds, "full_attention" or
    # "sliding_attention" a layer, or "linear_attention" (a Gated DeltaNet
    # mixer in the attention's place) or "mamba" (a Mamba-1 selective-scan
    # mixer there) or "kda" (a Kimi Delta Attention mixer there, beside
    # LATENT full attentions); None = every layer full. The layer scan runs
    # over whole periods of the pattern (`layer_period`). A sliding layer's
    # position i sees j with 0 <= i - j < sliding_window.
    layer_types: Optional[tuple] = None
    sliding_window: Optional[int] = None
    # RoPE law a layer kind: {"full_attention": {rope_type, rope_theta,
    # ...}, "sliding_attention": {...}} (the published key). None = one law
    # for every layer, from rope_theta + rope_scaling. A kind whose
    # rope_type is "none" is not rotated at all (the EXAONE hybrids' full
    # layers): its tables are cos 1, sin 0. Stored like rope_scaling, as
    # sorted tuples.
    rope_parameters: Optional[Any] = None
    rms_norm_eps: float = 1e-5
    # Qwen2-style architecture variants: bias on the q/k/v projections, and
    # an LM head tied to the embedding matrix (logits = h @ embedding.T; no
    # separate lm_head parameter — the Llama family unties, ref:
    # checkpoint.py:88-91 force-creates lm_head).
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # Gated-MLP activation: "silu" (Llama/Qwen/Mixtral SwiGLU), "gelu"
    # (EXACT erf GELU — transformers' "gelu"), or "gelu_tanh" (the tanh
    # approximation — transformers' "gelu_pytorch_tanh"/"gelu_new",
    # the Gemma-style GeGLU) — widens the --from-hf-config long tail
    # beyond pure-SwiGLU families. "relu2": NOT gated, down(relu(up x)^2),
    # two matrices an MLP (the Nemotron-H experts; `mlp_gated`).
    hidden_act: str = "silu"
    dtype: str = "bfloat16"  # compute/activation dtype; master params are fp32
    # Attention implementation: "auto" picks flash on TPU / reference on CPU;
    # CP > 1 always routes through the ring (ref: model.py:148-158 dispatch).
    attn_impl: str = "auto"  # "auto" | "flash" | "reference" | "ring"
    # Mixture-of-experts (beyond the reference, SURVEY §2.2 marks EP absent):
    # num_experts = 0 keeps the dense SwiGLU MLP; > 0 replaces every MLP with
    # a top-k-routed expert bank (Mixtral-style: softmax over the top-k
    # router logits) plus a load-balancing aux loss. Experts shard over the
    # 'ep' mesh axis. With ep = 1 the dispatch is dropless (assignments
    # permuted into expert order, grouped matmuls over the ragged group
    # sizes: nothing padded, nothing dropped); with ep > 1 it is
    # capacity-bounded (GShard-style, `capacity_factor`), because the
    # all_to_all needs fixed shapes.
    num_experts: int = 0
    num_experts_per_token: int = 2
    moe_intermediate_size: Optional[int] = None  # default: intermediate_size
    capacity_factor: float = 1.25
    # Renormalize the k chosen gates to sum to 1 (Mixtral's rule). False
    # keeps the raw softmax probabilities (OLMoE's published key).
    norm_topk_prob: bool = True
    # RMSNorm on the q and k projections before RoPE, in one of two forms.
    # True: over the WHOLE projected vector before the head split (OLMoE;
    # learned weights q_norm [n_q*d], k_norm [n_kv*d] per layer); the norm
    # runs over channels tensor parallelism splits, so tp > 1 is refused
    # (Config.validate). "head": over each head's head_dim numbers, one
    # weight vector [d] for all the heads of q and one for k (the EXAONE
    # family's and Qwen3's form).
    qk_norm: Any = False
    router_aux_coef: float = 0.01
    # Router z-loss coefficient (ST-MoE eq. 5; 1e-3 there). 0 disables.
    router_z_coef: float = 0.0
    # Compute router aux statistics (balance f/P, z-loss mean) over the
    # GLOBAL batch via pmean over the data axes — layout-exact losses
    # (identical for any dp/cp/ep factorization). False = per-device
    # statistics (cheaper by two [E]-sized pmeans per layer, differs across
    # layouts by O(shard variance)).
    router_aux_global: bool = True
    # Latent attention (MLA; kv_lora_rank > 0): q through a low-rank
    # bottleneck with its own RMSNorm (q_lora_rank), K and V of every head
    # expanded from ONE normed latent of kv_lora_rank numbers a token, plus
    # qk_rope_head_dim rotated dimensions shared by all heads; a head's
    # query and key are qk_nope_head_dim + qk_rope_head_dim wide, its value
    # v_head_dim. A cache holds the latent and the rotated dimensions and
    # nothing per head (ops/mla.py). The published keys.
    # q_lora_rank 0 beside kv_lora_rank > 0: no query bottleneck, q comes
    # out of one projection (`q_b` [hidden, heads x (nope + rope)]).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Neither the queries' nor the keys' qk_rope_head_dim shared dimensions
    # are rotated and no RoPE table is built (the published key; the model
    # orders its sequence by recurrent mixers).
    mla_use_nope: bool = False
    # The first k layers keep the dense gated MLP of `intermediate_size`;
    # the layers after them have the experts (the published key). The layer
    # tree is then two stacks, `dense_layers` and `layers` (`stacks`).
    first_k_dense_replace: int = 0
    # Experts every token passes through, beside the routed ones: one gated
    # MLP of n_shared_experts x the expert width, gate 1.
    n_shared_experts: int = 0
    # The share of the experts this device holds (serving one chip of an
    # expert-parallel group, with no exchange): the router scores
    # `router_experts` experts (0 = num_experts: every expert is here) and
    # the banks hold `num_experts` of them from index `expert_first` on. A
    # pick that lands elsewhere adds nothing here.
    router_experts: int = 0
    expert_first: int = 0
    # The router's scoring law: "softmax" over all router logits, or
    # "sigmoid" of each (DeepSeek-V3 lineage); the k largest scores are
    # renormalised (norm_topk_prob) and scaled by routed_scaling_factor.
    moe_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    # Four RMSNorms a layer: the attention's and the MLP's OUTPUTS are
    # normed too before they join the residual stream (Pangu Ultra).
    sandwich_norm: bool = False
    # Latent attention's two latents are scaled after their norms, the
    # query's by sqrt(hidden_size / q_lora_rank) and the key/value's by
    # sqrt(hidden_size / kv_lora_rank) (the published keys, booleans; the
    # cache holds c after its scale).
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # A layer is two (attention, dense MLP of `intermediate_size`) pairs and
    # a shortcut-connected expert branch (`Block.mlp == "shortcut"`).
    shortcut_moe: bool = False
    # Zero-compute experts: the router's LAST `zero_experts` columns, behind
    # those of the routed experts; a pick of one returns the token itself
    # times its gate (the published zero_expert_type 'identity'), is always
    # on the token's own device, and reads no bank.
    zero_experts: int = 0
    # The router chooses its k experts by score + a bias a column
    # (`router_bias`, zeros at init) and weighs them by the score alone.
    moe_selection_bias: bool = False
    # The attention's law: "softmax" over every (or a band of) earlier
    # key, or "eva" (the published key `attention_class`; ops/eva.py):
    # positions fall into windows of `window_size` and chunks of
    # `chunk_size`, a query sees the keys of its own window and one learned
    # summary (eva_mu, eva_phi: a pooled key and value) a chunk of every
    # closed window, under one softmax. Not `layer_types` /
    # `sliding_window`: this window is block-aligned (it resets, it does
    # not slide) and the same in every layer.
    attention_class: str = "softmax"
    window_size: int = 0
    chunk_size: int = 0
    # RMSNorm scales by 1 + w (the published key; w is drawn 0).
    norm_add_unit_offset: bool = False
    # The residual stream is float32: the blocks' outputs are added to it
    # in float32 and the final norm and the head read it so (the published
    # keys fp32_skip_add / fp32_logits; the program's logits are float32
    # for every model).
    fp32_skip_add: bool = False
    # The head holds num_pred_heads x vocab_size rows: head j at position t
    # scores token t + 1 + j. forward() returns all of them; a served token
    # is head 0's (heads 1.. draft, which is not built: ROADMAP M8).
    num_pred_heads: int = 1
    # The share of a head's dimensions RoPE rotates, the leading ones (the
    # published key; 1.0: all of them). `rope_dim`.
    partial_rotary_factor: float = 1.0
    # The softmax attention's output is scaled by sigmoid of a gate a
    # dimension before the output projection; the gate comes out of q's
    # projection, which is twice as wide: each head's 2 x head_dim outputs
    # are its query, then its gate (Qwen3-Next).
    attn_output_gate: bool = False
    # The Gated DeltaNet mixer of a "linear_attention" layer
    # (ops/gated_delta.py), the published keys: the kernel of the causal
    # depthwise convolution over [q | k | v], the key heads and their
    # width (q's too), the value heads and theirs; value heads are a whole
    # multiple of key heads, each key head serving that many. A "kda"
    # layer's Kimi Delta Attention mixer (ops/kda.py) reads the same five
    # (key heads = value heads; its two low-rank projections, the decay's
    # and the output gate's, pass through linear_value_head_dim numbers).
    linear_conv_kernel_dim: int = 0
    linear_key_head_dim: int = 0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_value_head_dim: int = 0
    # The shared expert's output is scaled by sigmoid of a 1-wide
    # projection of the token (`shared_out_gate`); false: gate 1.
    shared_expert_gate: bool = False
    # The Mamba-1 mixer of a "mamba" layer (ops/selective_scan.py), the
    # published keys: the state's width a channel, the kernel of the causal
    # depthwise convolution, d_inner over hidden_size (`ssm_inner`), the
    # width of the step's bottleneck, whether the convolution has a bias
    # and whether the in and out projections have one (not built).
    mamba_d_state: int = 0
    mamba_d_conv: int = 0
    mamba_expand: int = 0
    mamba_dt_rank: int = 0
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # The Mamba-2 (SSD) mixer of a "mamba2" layer (ops/ssd.py), the published
    # keys: heads of `mamba_head_dim` channels (d_inner = heads x head_dim:
    # `ssd_inner`), each with a float32 state [head_dim, ssm_state_size]
    # decayed by ONE scalar a head and step; B and C come a GROUP of heads
    # (`n_groups`); the convolution's kernel and bias are `mamba_d_conv` /
    # `mamba_conv_bias`, over [x | B | C] (`ssd_channels`).
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    n_groups: int = 0
    ssm_state_size: int = 0
    # LatentMoE: the routed experts read and write a latent of this width,
    # between a down projection of the token and an up projection of their
    # weighted sum (W_dn [hidden, latent], W_up [latent, hidden]); the router
    # and the shared expert stay on the full width. 0: the experts are
    # hidden_size wide.
    moe_latent_size: int = 0
    # The width of the one shared expert where the model publishes it (0:
    # n_shared_experts x the routed experts' width).
    moe_shared_expert_intermediate_size: int = 0
    # Accepted for reference compat (ref uses them to pick CUDA kernels).
    use_flash_attention: bool = True
    use_fused_adam: bool = True

    def __post_init__(self):
        rs = self.rope_scaling
        if isinstance(rs, dict):
            rs = tuple(sorted(rs.items()))
        elif isinstance(rs, (list, tuple)) and rs:
            # JSON round-trip (to_json_dict -> config_from_dict) turns the
            # tuple of pairs into nested lists — re-normalize so the frozen
            # config stays hashable
            rs = tuple(sorted(tuple(pair) for pair in rs))
        object.__setattr__(self, "rope_scaling", rs or None)
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim",
                self.hidden_size // self.num_attention_heads)
        if self.layer_types is not None:
            lt = tuple(self.layer_types)
            # every layer full is the model without the key
            object.__setattr__(
                self, "layer_types",
                lt if set(lt) - {"full_attention"} else None)
        rp = self.rope_parameters
        if rp:
            # dict (or its JSON round trip as nested pair lists) -> sorted
            # tuples of pairs, hashable
            rp = tuple(sorted(
                (kind, tuple(sorted(dict(law).items())))
                for kind, law in dict(rp).items()))
        object.__setattr__(self, "rope_parameters", rp or None)

    @property
    def rope_scaling_dict(self) -> Optional[dict]:
        return dict(self.rope_scaling) if self.rope_scaling else None

    def rope_law(self, kind: str) -> tuple:
        """(theta, HF-style scaling dict or None) of a layer kind: its
        section of `rope_parameters`, else the model's one law."""
        if self.rope_parameters:
            law = dict(dict(self.rope_parameters)[kind])
            theta = float(law.pop("rope_theta", self.rope_theta))
            scaled = law.get("rope_type", "default") != "default"
            return theta, (law if scaled else None)
        return self.rope_theta, self.rope_scaling_dict

    @property
    def layer_kinds(self) -> tuple:
        """The attention kind of each layer, as published."""
        return self.layer_types or (
            ("full_attention",) * self.num_hidden_layers)

    @property
    def layer_period(self) -> tuple:
        """The shortest period of `layer_kinds` (`pattern_of`).
        ("full_attention",) for a model of one kind. A stack scans the
        periods of its own slice (`Stack.kinds`), not this."""
        return pattern_of(self.layer_kinds)[0]

    @property
    def expert_ffn_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def eva(self) -> bool:
        return self.attention_class == "eva"

    @property
    def rope_dim(self) -> int:
        """The rotated width of a head: all of it, its leading
        partial_rotary_factor share, or MLA's shared qk_rope_head_dim."""
        if self.mla:
            return self.qk_rope_head_dim
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def gdn(self) -> bool:
        """Whether some layer is a Gated DeltaNet mixer."""
        return GDN in self.layer_kinds

    @property
    def ssm(self) -> bool:
        """Whether some layer is a Mamba-1 selective-scan mixer."""
        return SSM in self.layer_kinds

    @property
    def kda(self) -> bool:
        """Whether some layer is a Kimi Delta Attention mixer."""
        return KDA in self.layer_kinds

    @property
    def ssd(self) -> bool:
        """Whether some layer is a Mamba-2 (SSD) mixer."""
        return SSD in self.layer_kinds

    @property
    def single_sublayer(self) -> bool:
        """Whether a layer is ONE sublayer behind one norm (a mixer alone or
        an MLP alone: `Block.alone`), which a layer kind of "mamba2" or
        "experts" says of the whole model."""
        return SSD in self.layer_kinds or MOE in self.layer_kinds

    @property
    def mlp_gated(self) -> bool:
        """Whether an MLP is gated, act(gate x) * (up x), three matrices; a
        "relu2" MLP is down(relu(up x)^2), two."""
        return self.hidden_act != "relu2"

    @property
    def ssd_inner(self) -> int:
        """d_inner of a Mamba-2 mixer: heads x head_dim."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def ssd_channels(self) -> int:
        """The channels a Mamba-2 mixer's convolution runs over: [x | B | C]."""
        return self.ssd_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def expert_in_size(self) -> int:
        """The width a routed expert reads and writes: the latent's, or the
        residual stream's."""
        return self.moe_latent_size or self.hidden_size

    @property
    def shared_ffn_size(self) -> int:
        """The shared expert's width (0: none)."""
        return (self.moe_shared_expert_intermediate_size
                or self.n_shared_experts * self.expert_ffn_size)

    @property
    def ssm_inner(self) -> int:
        """d_inner: the channels of a Mamba mixer, each with a state of
        mamba_d_state."""
        return self.mamba_expand * self.hidden_size

    @property
    def recurrent_layers(self) -> int:
        """The layers whose mixer carries a state a sequence (a Gated
        DeltaNet, a Mamba, a Kimi Delta Attention or a Mamba-2 mixer): a
        model with some is cached in a state pool beside the attentions' K/V
        (or their latents)."""
        return sum(k in RECURRENT for k in self.layer_kinds)

    @property
    def recurrent(self) -> bool:
        return self.recurrent_layers > 0

    @property
    def gdn_channels(self) -> int:
        """The channels the mixer's convolution runs over: [q | k | v]."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def router_width(self) -> int:
        """The router's columns: every routed expert of the model, held
        here or not, then the zero-compute experts."""
        return (self.router_experts or self.num_experts) + self.zero_experts

    @property
    def mla_scales(self) -> tuple:
        """(query latent's, key/value latent's) scale after its norm."""
        return (
            (self.hidden_size / self.q_lora_rank) ** 0.5
            if self.mla_scale_q_lora else 1.0,
            (self.hidden_size / self.kv_lora_rank) ** 0.5
            if self.mla_scale_kv_lora else 1.0)

    @property
    def attention_sublayers(self) -> int:
        """Attention sublayers of the model, one cache row each: the
        leading axis of a latent cache, or the layer axis of a hybrid's K/V
        pool (a recurrent mixer has none, nor has a layer that is the
        experts alone)."""
        return (sum(st.layers * st.block.attentions for st in self.stacks)
                - self.recurrent_layers - self.layer_kinds.count(MOE))

    @property
    def stacks(self) -> tuple:
        """The layer tree's stacks in order, a `Stack` (params key, layers,
        Block, the layers' kinds) each: what `models.llama.run_stacks` and
        `generate._decode_layers` scan, one stack after the other. One
        stack, `layers`, for a model of one kind of block; the leading
        dense layers of a model with first_k_dense_replace are
        `dense_layers`. The pattern of `layer_types` is cut where the
        stacks are: each stack carries its own slice."""
        attn = ("mla" if self.mla else "eva" if self.eva else "gqa")
        n, k = self.num_hidden_layers, self.first_k_dense_replace
        kinds = self.layer_kinds
        if self.single_sublayer:
            return (Stack("layers", n, Block(attn, "experts", False, True),
                          kinds),)
        if not self.num_experts:
            return (Stack("layers", n,
                          Block(attn, "dense", self.sandwich_norm), kinds),)
        if self.shortcut_moe:
            return (Stack("layers", n, Block(attn, "shortcut", False), kinds),)
        out = (Stack("layers", n - k,
                     Block(attn, "experts", self.sandwich_norm), kinds[k:]),)
        if k:
            out = (Stack("dense_layers", k,
                         Block(attn, "dense", self.sandwich_norm),
                         kinds[:k]),) + out
        return out

    def _validate_single_sublayer(self, sizes: tuple) -> None:
        """A model whose layers are ONE sublayer each (kinds 'mamba2',
        'experts', 'full_attention'): what is built beside them."""
        kinds = set(self.layer_types)
        if kinds - {"full_attention", SSD, MOE}:
            raise ValueError(
                f"layers that are one sublayer each are 'mamba2', 'experts' "
                f"and 'full_attention': {sorted(kinds - {'full_attention', SSD, MOE})} "
                f"beside them are not built")
        if self.ssd:
            if min(sizes) < 1 or self.mamba_d_conv < 2 or (
                    self.mamba_num_heads % self.n_groups):
                raise ValueError(
                    f"layer_types holds mamba2 layers: mamba_num_heads, "
                    f"mamba_head_dim, n_groups and ssm_state_size must be "
                    f">= 1, mamba_d_conv >= 2 and the heads a whole number a "
                    f"group, got {sizes}, mamba_d_conv {self.mamba_d_conv}")
            if (self.mamba_d_conv - 1) * self.ssd_channels % 128:
                raise ValueError(
                    f"layer_types holds mamba2 layers: the convolution's "
                    f"tail, (mamba_d_conv - 1) x (d_inner + 2 x n_groups x "
                    f"ssm_state_size) = {(self.mamba_d_conv - 1) * self.ssd_channels} "
                    f"numbers, must be whole rows of 128 lanes")
        elif any(sizes):
            raise ValueError(
                "mamba_num_heads / mamba_head_dim / n_groups / ssm_state_size "
                "are a mamba2 layer's: no layer of layer_types is one")
        if (MOE in kinds) != bool(self.num_experts):
            raise ValueError(
                "layer_types holds 'experts' layers exactly when num_experts "
                "> 0")
        if (self.mla or self.eva or self.sandwich_norm or self.shortcut_moe
                or self.first_k_dense_replace or self.zero_experts
                or self.attention_bias or self.qk_norm
                or self.attn_output_gate or self.norm_add_unit_offset
                or self.fp32_skip_add or self.num_pred_heads > 1
                or self.shared_expert_gate or self.mamba_proj_bias
                or self.partial_rotary_factor != 1.0):
            raise ValueError(
                "layers that are one sublayer each are built with plain "
                "q/k/v heads, one norm a layer and experts with at most one "
                "ungated shared expert: latent attention, attention_class "
                "'eva', sandwich_norm, shortcut_moe, first_k_dense_replace, "
                "zero_experts, attention_bias, qk_norm, attn_output_gate, "
                "norm_add_unit_offset, fp32_skip_add, num_pred_heads > 1, "
                "shared_expert_gate, mamba_proj_bias and "
                "partial_rotary_factor < 1 must be unset")
        if self.moe_latent_size < 0 or (
                self.moe_shared_expert_intermediate_size < 0):
            raise ValueError(
                "moe_latent_size and moe_shared_expert_intermediate_size "
                "must be >= 0")

    def validate(self) -> None:
        if self.attn_impl not in ("auto", "flash", "reference", "ring",
                                  "ulysses", "mesh"):
            raise ValueError(
                f"attn_impl must be one of auto/flash/reference/ring/"
                f"ulysses/mesh, got {self.attn_impl!r}"
            )
        if (self.head_dim == self.hidden_size // self.num_attention_heads
                and self.hidden_size % self.num_attention_heads != 0):
            # a head_dim of its own (published key) lifts the convention
            raise ValueError("hidden_size must be divisible by num_attention_heads")
        if self.num_attention_heads % self.num_key_value_heads != 0:
            raise ValueError("num_attention_heads must be divisible by num_key_value_heads")
        if self.head_dim % 2 != 0:
            raise ValueError("head_dim must be even for RoPE")
        if self.layer_types is not None:
            if len(self.layer_types) != self.num_hidden_layers:
                raise ValueError(
                    f"layer_types names {len(self.layer_types)} layers, "
                    f"num_hidden_layers is {self.num_hidden_layers}")
            bad = set(self.layer_types) - {"full_attention",
                                           "sliding_attention", *RECURRENT,
                                           MOE}
            if bad:
                raise ValueError(
                    f"layer_types entries must be 'full_attention', "
                    f"'sliding_attention', 'linear_attention', 'mamba', "
                    f"'kda', 'mamba2' or 'experts', got {sorted(bad)}")
            if "sliding_attention" in self.layer_types and (
                    not self.sliding_window or self.sliding_window < 1):
                raise ValueError(
                    "layer_types holds sliding_attention layers: "
                    "sliding_window must be a positive number of positions")
        sizes = (self.linear_conv_kernel_dim, self.linear_key_head_dim,
                 self.linear_num_key_heads, self.linear_num_value_heads,
                 self.linear_value_head_dim)
        if self.gdn:
            if min(sizes) < 1 or (self.linear_num_value_heads
                                  % self.linear_num_key_heads):
                raise ValueError(
                    f"layer_types holds linear_attention layers: "
                    f"linear_conv_kernel_dim, linear_key_head_dim, "
                    f"linear_num_key_heads, linear_num_value_heads and "
                    f"linear_value_head_dim must be >= 1 and the value "
                    f"heads a whole multiple of the key heads, got {sizes}")
            if ("sliding_attention" in self.layer_types or self.mla
                    or self.eva or self.first_k_dense_replace
                    or self.shortcut_moe or self.sandwich_norm
                    or self.attention_bias or self.rope_parameters):
                raise ValueError(
                    "linear_attention layers are built beside full "
                    "attention layers of q/k/v heads in one stack with two "
                    "norms a layer and one RoPE law: sliding_attention "
                    "layers, latent attention, attention_class 'eva', "
                    "first_k_dense_replace, shortcut_moe, sandwich_norm, "
                    "attention_bias and rope_parameters must be unset")
        elif self.kda:
            if min(sizes) < 1 or (self.linear_num_value_heads
                                  != self.linear_num_key_heads):
                raise ValueError(
                    f"layer_types holds kda layers: linear_conv_kernel_dim, "
                    f"linear_key_head_dim, linear_num_key_heads, "
                    f"linear_num_value_heads and linear_value_head_dim must "
                    f"be >= 1 and the value heads as many as the key heads, "
                    f"got {sizes}")
            if (set(self.layer_types) - {"full_attention", KDA}
                    or "full_attention" not in self.layer_types
                    or not self.mla or self.eva or self.shortcut_moe
                    or self.sandwich_norm or self.rope_parameters
                    or self.mla_scale_q_lora or self.mla_scale_kv_lora):
                raise ValueError(
                    "kda layers are built beside full layers of latent "
                    "attention (kv_lora_rank > 0, one at least) with two "
                    "norms a layer: sliding_attention, linear_attention and "
                    "mamba layers, attention_class 'eva', shortcut_moe, "
                    "sandwich_norm, rope_parameters and mla_scale_q_lora / "
                    "mla_scale_kv_lora must be unset")
        elif any(sizes):
            raise ValueError(
                "linear_conv_kernel_dim / linear_key_head_dim / "
                "linear_num_key_heads / linear_num_value_heads / "
                "linear_value_head_dim are a linear_attention layer's (or a "
                "kda layer's): set layer_types with them, or none of them")
        sizes = (self.mamba_d_state, self.mamba_d_conv, self.mamba_expand,
                 self.mamba_dt_rank)
        if self.ssm:
            if min(sizes) < 1 or self.mamba_d_conv < 2:
                raise ValueError(
                    f"layer_types holds mamba layers: mamba_d_state, "
                    f"mamba_expand and mamba_dt_rank must be >= 1 and "
                    f"mamba_d_conv >= 2, got {sizes}")
            if self.ssm_inner % 128:
                raise ValueError(
                    f"layer_types holds mamba layers: d_inner = mamba_expand "
                    f"x hidden_size ({self.ssm_inner}) must be a whole number "
                    f"of 128-lane rows (a sequence's convolution tail is "
                    f"held in rows of 128)")
            if self.mamba_proj_bias:
                raise ValueError(
                    "mamba_proj_bias: a bias on the mixer's in and out "
                    "projections is not built (must be false)")
            if ("full_attention" not in self.layer_types
                    or set(self.layer_types) - {"full_attention", SSM}
                    or self.mla or self.eva or self.num_experts
                    or self.sandwich_norm or self.attention_bias
                    or self.qk_norm or self.attn_output_gate
                    or self.norm_add_unit_offset or self.fp32_skip_add
                    or self.num_pred_heads > 1):
                raise ValueError(
                    "mamba layers are built beside full attention layers "
                    "of plain q/k/v heads (one at least) in one stack of "
                    "dense MLPs with two norms a layer: sliding_attention "
                    "and linear_attention layers, latent attention, "
                    "attention_class 'eva', experts, sandwich_norm, "
                    "attention_bias, qk_norm, attn_output_gate, "
                    "norm_add_unit_offset, fp32_skip_add and num_pred_heads "
                    "> 1 must be unset")
        elif any(sizes) and not (self.ssd and sizes == (
                0, self.mamba_d_conv, 0, 0)):
            raise ValueError(
                "mamba_d_state / mamba_d_conv / mamba_expand / mamba_dt_rank "
                "are a mamba layer's: set layer_types with them, or none of "
                "them (a mamba2 layer reads mamba_d_conv alone)")
        sizes = (self.mamba_num_heads, self.mamba_head_dim, self.n_groups,
                 self.ssm_state_size)
        if self.single_sublayer:
            self._validate_single_sublayer(sizes)
        elif any(sizes) or self.moe_latent_size or (
                self.moe_shared_expert_intermediate_size):
            raise ValueError(
                "mamba_num_heads / mamba_head_dim / n_groups / ssm_state_size "
                "/ moe_latent_size / moe_shared_expert_intermediate_size "
                "describe a model whose layers are one sublayer each: set "
                "layer_types of 'mamba2' / 'experts' / 'full_attention' with "
                "them, or none of them")
        if not 0.0 < self.partial_rotary_factor <= 1.0 or (
                self.rope_dim % 2):
            raise ValueError(
                f"partial_rotary_factor ({self.partial_rotary_factor}) must "
                f"lie in (0, 1] and leave an even number of rotated "
                f"dimensions of head_dim ({self.head_dim})")
        if (self.partial_rotary_factor != 1.0 or self.attn_output_gate) and (
                self.mla or self.eva or "sliding_attention" in self.layer_kinds
                or self.rope_parameters):
            raise ValueError(
                "partial_rotary_factor < 1 and attn_output_gate are built "
                "for full attention layers of q/k/v heads under one RoPE "
                "law: latent attention, attention_class 'eva', "
                "sliding_attention layers and rope_parameters must be unset")
        if self.attn_output_gate and self.qk_norm is True:
            raise ValueError(
                "attn_output_gate splits q's projection a head: qk_norm "
                "must be 'head' or false, not true (the whole vector)")
        if (self.norm_add_unit_offset and self.qk_norm == "head"
                and not self.attn_output_gate):
            raise ValueError(
                "norm_add_unit_offset with qk_norm 'head' is built in the "
                "gated attention only (attn_output_gate): the head norms "
                "scale by 1 + w there")
        if self.shared_expert_gate and not self.n_shared_experts:
            raise ValueError(
                "shared_expert_gate needs n_shared_experts > 0")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(
                f"qk_norm must be false, true (over the whole projected "
                f"vector) or 'head' (over each head), got {self.qk_norm!r}")
        if self.rope_parameters:
            # a recurrent mixer has no positions to rotate: no section
            missing = (set(self.layer_kinds) - set(RECURRENT) - {MOE}
                       - set(dict(self.rope_parameters)))
            if missing:
                raise ValueError(
                    f"rope_parameters has no section for {sorted(missing)}")
        if self.hidden_act not in ("silu", "gelu", "gelu_tanh", "relu2"):
            raise ValueError(
                f"hidden_act must be 'silu', 'gelu', 'gelu_tanh' or 'relu2', "
                f"got {self.hidden_act!r}")
        if not self.mlp_gated and not self.single_sublayer:
            raise ValueError(
                "hidden_act 'relu2' (a non-gated MLP of two matrices) is "
                "built for the experts of a model whose layers are one "
                "sublayer each (layer_types of 'mamba2' / 'experts' / "
                "'full_attention'); a dense gated MLP has three")
        if self.mla:
            for key in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"):
                if getattr(self, key) < 1:
                    raise ValueError(
                        f"kv_lora_rank > 0 (latent attention) needs "
                        f"{key} >= 1, got {getattr(self, key)}")
            if self.q_lora_rank < 0:
                raise ValueError(
                    f"q_lora_rank must be >= 1, or 0 for no query "
                    f"bottleneck, got {self.q_lora_rank}")
            if self.qk_rope_head_dim % 2 and not self.mla_use_nope:
                raise ValueError("qk_rope_head_dim must be even for RoPE")
            if (self.attention_bias or self.qk_norm or self.rope_parameters
                    or (self.layer_types is not None and not self.kda)):
                raise ValueError(
                    "latent attention (kv_lora_rank > 0) has no qkv bias, "
                    "no whole-vector QK-norm, no sliding-window layers and "
                    "one RoPE law: attention_bias / qk_norm / rope_parameters "
                    "must be unset, and layer_types too unless its other "
                    "layers are kda mixers")
        elif (self.q_lora_rank or self.qk_nope_head_dim
                or self.qk_rope_head_dim or self.v_head_dim
                or self.mla_use_nope):
            raise ValueError(
                "q_lora_rank / qk_nope_head_dim / qk_rope_head_dim / "
                "v_head_dim / mla_use_nope are latent attention's (MLA): set "
                "kv_lora_rank > 0 with them, or none of them")
        if self.attention_class not in ("softmax", "eva"):
            raise ValueError(
                f"attention_class must be 'softmax' or 'eva', got "
                f"{self.attention_class!r}")
        if self.eva:
            w, c = self.window_size, self.chunk_size
            if c < 1 or w < c or w % c:
                raise ValueError(
                    f"attention_class 'eva' needs chunk_size >= 1 and a "
                    f"window_size that is a whole number of chunks, got "
                    f"window_size {w}, chunk_size {c}")
            if (self.mla or self.layer_types is not None or self.num_experts
                    or self.attention_bias or self.qk_norm
                    or self.sandwich_norm or self.rope_parameters):
                raise ValueError(
                    "attention_class 'eva' is built with plain q/k/v "
                    "heads, one RoPE law and a dense MLP: latent attention, "
                    "layer_types, experts, attention_bias, qk_norm, "
                    "sandwich_norm and rope_parameters must be unset")
        elif self.window_size or self.chunk_size:
            raise ValueError(
                "window_size / chunk_size are attention_class 'eva''s: set "
                "it with them, or neither")
        if (self.norm_add_unit_offset or self.fp32_skip_add) and (
                self.mla or self.sandwich_norm or self.shortcut_moe
                or self.first_k_dense_replace or self.qk_norm is True
                or (self.fp32_skip_add and (self.num_experts
                                            or self.qk_norm))):
            raise ValueError(
                "norm_add_unit_offset / fp32_skip_add are built for a block "
                "of q/k/v heads with two norms a layer, the float32 stream "
                "with a dense MLP and no QK-norm: latent attention, "
                "sandwich_norm, shortcut_moe, first_k_dense_replace and "
                "whole-vector qk_norm must be unset, and with fp32_skip_add "
                "experts and qk_norm too")
        if self.num_pred_heads < 1:
            raise ValueError(
                f"num_pred_heads must be >= 1, got {self.num_pred_heads}")
        if self.num_pred_heads > 1 and self.tie_word_embeddings:
            raise ValueError(
                "num_pred_heads > 1 needs an untied head (the head holds "
                "num_pred_heads x vocab_size rows)")
        if self.first_k_dense_replace:
            if not self.num_experts:
                raise ValueError(
                    "first_k_dense_replace > 0 needs num_experts > 0: a "
                    "model without experts is dense in every layer")
            if not 0 < self.first_k_dense_replace < self.num_hidden_layers:
                raise ValueError(
                    f"first_k_dense_replace ({self.first_k_dense_replace}) "
                    f"must leave at least one expert layer of "
                    f"num_hidden_layers ({self.num_hidden_layers})")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_scoring must be 'softmax' or 'sigmoid', got "
                f"{self.moe_scoring!r}")
        if (self.n_shared_experts or self.router_experts
                or self.expert_first) and not self.num_experts:
            raise ValueError(
                "n_shared_experts / router_experts / expert_first describe "
                "an expert layer: they need num_experts > 0")
        if self.n_shared_experts < 0 or self.expert_first < 0:
            raise ValueError(
                "n_shared_experts and expert_first must be >= 0")
        if self.num_experts and (
                self.expert_first + self.num_experts
                > self.router_width - self.zero_experts):
            raise ValueError(
                f"the experts held here ({self.num_experts} from index "
                f"{self.expert_first}) do not lie inside the router's "
                f"{self.router_width - self.zero_experts} routed experts "
                f"(router_experts)")
        if (self.zero_experts or self.moe_selection_bias
                or self.shortcut_moe) and not self.num_experts:
            raise ValueError(
                "zero_experts / moe_selection_bias / shortcut_moe describe "
                "an expert branch: they need num_experts > 0")
        if self.zero_experts < 0:
            raise ValueError("zero_experts must be >= 0")
        if self.shortcut_moe and (
                not self.mla or self.first_k_dense_replace
                or self.sandwich_norm or self.n_shared_experts):
            raise ValueError(
                "shortcut_moe is built as two (latent attention, dense MLP) "
                "pairs a layer with two norms a pair and no shared expert: "
                "it needs kv_lora_rank > 0, and first_k_dense_replace, "
                "sandwich_norm and n_shared_experts unset")
        if (self.mla_scale_q_lora or self.mla_scale_kv_lora) and not self.mla:
            raise ValueError(
                "mla_scale_q_lora / mla_scale_kv_lora are latent "
                "attention's: they need kv_lora_rank > 0")
        if self.num_experts and self.num_experts_per_token > self.router_width:
            raise ValueError(
                f"num_experts_per_token ({self.num_experts_per_token}) "
                f"exceeds the router's width ({self.router_width})")


@dataclass(frozen=True)
class TrainingConfig:
    """(ref: template/base_config.json:20-29)."""

    seed: int = 42
    learning_rate: float = 3e-4
    # LR schedule: "constant" (the reference's behavior, ref: train.py:209
    # builds a bare AdamW), "cosine" (linear warmup -> cosine decay to
    # lr * lr_min_ratio over total_train_steps), or "linear" (warmup ->
    # linear decay). Warmup counts from step 0 even on resume — the
    # schedule reads the restored optimizer step count.
    lr_schedule: str = "constant"
    lr_warmup_steps: int = 0
    lr_min_ratio: float = 0.1
    weight_decay: float = 0.0
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    # "float32" | "bfloat16": bf16 halves Adam-moment memory (update math
    # stays fp32) — the knob that fits SmolLM-1.7B's optimizer on one v5e.
    adam_moments_dtype: str = "float32"
    # ZeRO-Offload-style optimizer-state offload (beyond the reference,
    # whose CUDA path keeps everything in GPU memory): the fp32 master
    # params and both Adam moments live permanently in pinned HOST memory;
    # the device keeps only a bf16 compute copy of the params plus the fp32
    # gradient accumulator. The update streams leaf-by-leaf through the
    # device (host->device DMA, fused AdamW, device->host write-back), so
    # per-step PCIe traffic is params+moments each way — amortize it with
    # gradient_accumulation_steps >= ~16. This is the lever that fits
    # full-depth SmolLM-1.7B (fp32 master + grads + moments ~21 GB) on one
    # 15.75 GB v5e chip with NO numerics compromise: the master update math
    # is identical to the on-device path; only per-microbatch grads are
    # bf16 (they accumulate in fp32, the standard mixed-precision
    # arrangement).
    optimizer_offload: bool = False
    grad_clip_norm: float = 0.0  # 0 disables clipping
    total_train_steps: int = 200
    seq_length: int = 1024
    micro_batch_size: int = 1
    gradient_accumulation_steps: int = 1
    num_samples: Optional[int] = None
    max_tokens: Optional[int] = None
    # Periodic validation: every eval_frequency training steps, run
    # eval_steps batches from the eval source (dataset.eval_split for HF
    # datasets; a disjoint-seed synthetic stream otherwise) and log
    # val_loss. 0 disables (the reference has no eval loop).
    eval_frequency: int = 0
    eval_steps: int = 8
    # Stream the LM-head cross-entropy over vocab chunks of this many
    # columns: the [tokens, vocab] logits never materialize (neither as a
    # forward tensor nor a saved backward residual — chunks recompute),
    # trading one extra chunk matmul in backward for ~tokens*vocab*2 bytes
    # of peak HBM. 0 disables (fused single-matmul CE). Must divide
    # vocab_size / tp_size or it silently falls back to fused.
    ce_chunk_size: int = 0
    # Gradient rematerialization for long-context / big-model memory savings.
    remat: bool = True
    # "full" recomputes everything in backward (max memory savings);
    # "dots" saves matmul outputs and recomputes only elementwise ops —
    # usually within a few % of no-remat speed at a fraction of the memory;
    # "dots_attn" saves only the attention-side dots and recomputes the MLP
    # (~2.6x less activation HBM than "dots" for ~+7% step FLOPs — the
    # memory/speed midpoint that pairs with optimizer_offload);
    # "dots_norms" additionally saves RMSNorm outputs (~2 activations/layer
    # more HBM, less backward recompute).
    remat_policy: str = "dots"
    # Gradient engine of the microbatch loop and of the 1F1B tick's
    # backward unit: "ad" differentiates each microbatch (each tick's
    # layer block) and tree-adds into the fp32 accumulator (one whole-tree
    # temp write + one whole-tree add per microbatch — measured 26 ms of
    # serialized roofline HBM traffic per microbatch at SmolLM-1.7B,
    # PERF.md r5); "fused" runs the manual backward layer scan
    # (parallel/fused_bwd.py) that accumulates each layer's dW in-scan,
    # eliminating both passes. "auto" picks "fused" whenever it is
    # supported and gradients accumulate: under remat dots_attn, any
    # single-pipeline-stage layout (dp/tp/SP/cp ring|ulysses/ep/MoE) over
    # more than one microbatch, and at pp > 1 the spmd executor's 1F1B
    # engine over the same axes (PR 63; MoE only where the layers split
    # evenly over the stages); AFAB and the MPMD executor run "ad". One
    # predicate says
    # which: parallel/fused_bwd.py resolved_grad_engine (see the README
    # eligibility matrix). Numerics match the AD engine (pinned by
    # tests/test_fused_bwd.py and tests/test_pp_engines.py).
    grad_engine: str = "auto"


@dataclass(frozen=True)
class DatasetConfig:
    """(ref: template/base_config.json:30-35). `synthetic` replaces network
    datasets in tests/benchmarks (deterministic PRNG token stream)."""

    name: str = "synthetic"
    subset_name: Optional[str] = None
    tokenizer_name: Optional[str] = None
    num_workers: int = 0
    num_proc: int = 1
    split: str = "train"
    # HF split for the validation loader (training.eval_frequency > 0);
    # None with a synthetic source uses a disjoint seed stream.
    eval_split: Optional[str] = None
    text_column: str = "text"


@dataclass(frozen=True)
class CheckpointConfig:
    """(ref: template/base_config.json:36-40)."""

    save_dir: str = "ckpt"
    save_frequency: int = 0  # 0 disables periodic saving
    # Async Orbax save (SURVEY §5): save() stages device->host copies and
    # returns; the disk write overlaps subsequent training steps. The
    # trainer waits for durability at exit. False = blocking saves (the
    # reference's behavior, ref: checkpoint.py:246-260).
    async_save: bool = True
    load_path: str = ""
    # Resume from the newest durable checkpoint in save_dir when no
    # load_path is given (no-op when save_dir holds none). This is the
    # in-process half of preemption recovery: the reference's scheduler
    # resubmits failed jobs (ref: submit_slurm_jobs.py:157-172) but each
    # resubmission restarts from scratch unless resume is hand-configured;
    # with auto_resume a resubmitted/preempted job continues where its
    # last completed save left off — the standard arrangement for
    # preemptible TPU pods.
    auto_resume: bool = False
    # Optional HF safetensors dir to materialize initial weights from (the
    # reference's bootstrap reads safetensors but only as shape templates,
    # ref: checkpoint.py:93-101; we actually load the values).
    init_from_hf: str = ""
    # Retention GC (picotron_tpu/ckpt_integrity): after each durable
    # commit, prune step dirs beyond the keep_last newest. 0 disables
    # (keep everything — the pre-lineage behavior). keep_every
    # additionally pins steps divisible by it forever (sparse anchors
    # under an aggressive keep_last). The last *verified* checkpoint is
    # never deleted regardless of policy — keep_last=1 with a corrupt
    # newest step keeps the restore fallback alive.
    keep_last: int = 0
    keep_every: int = 0
    # Elastic resume (picotron_tpu/resilience/elastic.py): allow restore
    # into a mesh whose topology differs from the one the checkpoint was
    # saved under (e.g. dp=2 -> dp=4 after a fleet resize). Orbax reshards
    # the global arrays onto the new mesh; the restore validates that
    # global_batch_size is unchanged (the token-exact cursor / loss-parity
    # invariant) and books the restore under the `resize` goodput
    # category. False = a topology mismatch at restore time is a hard
    # error naming the tools/elastic_resize.py re-stamp that would fix it.
    elastic: bool = False


@dataclass(frozen=True)
class ResilienceConfig:
    """Runtime fault tolerance (picotron_tpu/resilience; beyond the
    reference, whose loop dies on the first NaN, hang, or preemption).
    See README "Fault tolerance" for the recovery matrix."""

    # Fault-injection spec, e.g. "sigterm@3,ckpt_io@2x2" (resilience/
    # chaos.py documents the grammar). Empty = no injection. The
    # PICOTRON_CHAOS env var, when set, overrides this field.
    chaos: str = ""
    # Response to a tripped divergence guard (non-finite loss/grads, loss
    # spike): "skip" drops the batch but keeps optimizer state (the
    # non-finite half runs inside the jitted step), "rollback" restores
    # the last durable checkpoint and skips past the poison data range,
    # "abort" exits EXIT_DIVERGED (76), "off" disables the guards — and
    # with them the per-step host sync they require; use "off" to recover
    # fully-async stepping when logging is sparse.
    guard_policy: str = "abort"
    # Rolling loss-spike detection: trip when the loss sits spike_zscore
    # standard deviations above the mean of the last spike_window healthy
    # steps. 0 disables (non-finite detection stays on).
    spike_zscore: float = 0.0
    spike_window: int = 32
    # Consecutive guard trips before escalating to abort regardless of
    # policy — a guard that keeps tripping is not recovering.
    max_guard_trips: int = 3
    # Retry-with-backoff policy for flaky I/O (checkpoint save/restore,
    # durability probes, dataset reads): total attempts and the
    # exponential-backoff delay bounds in seconds.
    retry_attempts: int = 3
    retry_base_delay: float = 0.5
    retry_max_delay: float = 30.0
    # Seconds without step-loop progress before the watchdog dumps all
    # thread stacks and exits EXIT_WATCHDOG (77) for a supervisor
    # restart. 0 disables. Armed only after the first step completes
    # (step 1 includes unbounded XLA compile time); set it well above a
    # normal step + checkpoint write.
    watchdog_timeout: float = 0.0

    def validate(self) -> None:
        if self.guard_policy not in ("off", "skip", "rollback", "abort"):
            raise ValueError(
                f"guard_policy must be off/skip/rollback/abort, got "
                f"{self.guard_policy!r}")
        if self.spike_zscore < 0:
            raise ValueError(
                f"spike_zscore must be >= 0, got {self.spike_zscore}")
        if self.spike_window < 4:
            # fewer points make the z-score statistically meaningless and
            # trip on ordinary early-training descent
            raise ValueError(
                f"spike_window must be >= 4, got {self.spike_window}")
        if self.max_guard_trips < 1:
            raise ValueError(
                f"max_guard_trips must be >= 1, got {self.max_guard_trips}")
        if self.retry_attempts < 1:
            raise ValueError(
                f"retry_attempts must be >= 1, got {self.retry_attempts}")
        if self.retry_base_delay < 0 or self.retry_max_delay < self.retry_base_delay:
            raise ValueError(
                f"retry delays must satisfy 0 <= base <= max, got "
                f"base={self.retry_base_delay} max={self.retry_max_delay}")
        if self.watchdog_timeout < 0:
            raise ValueError(
                f"watchdog_timeout must be >= 0, got {self.watchdog_timeout}")
        if self.chaos:
            # Parse errors at config load, not at step N mid-run.
            from picotron_tpu.resilience.chaos import parse_spec

            parse_spec(self.chaos)


@dataclass(frozen=True)
class ServeConfig:
    """Serving stack (picotron_tpu/serve): continuous batching + paged KV
    cache on the decode path. Sizing contract: per-slot capacity is
    max_model_len tokens (table width = ceil(max_model_len / block_size));
    the POOL is num_blocks fixed-size blocks shared by every slot — cache
    HBM scales with num_blocks, not decode_slots x max_model_len, which is
    the whole point (ragged request lengths stop stranding cache memory).
    Oversubscribe deliberately: the scheduler preempts youngest-first when
    the pool runs dry."""

    # In-flight decode batch width: the ONE static shape the decode step
    # is compiled for (slots are refilled mid-flight, never reshaped).
    decode_slots: int = 8
    # Tokens per physical cache block. Smaller = less fragmentation waste
    # per sequence (at most block_size - 1 slots), larger = smaller block
    # tables and fewer scatter indices.
    block_size: int = 16
    # Physical blocks in the shared pool. 0 = auto: decode_slots *
    # ceil(max_model_len / block_size) — the no-oversubscription worst
    # case (same HBM as a contiguous cache at max length). Set it
    # explicitly to actually bank the paged-cache memory win. A model with
    # latent attention (kv_lora_rank > 0) gets a latent pool of as many
    # blocks: its bytes follow the latent's width, not the heads'
    # (serve/paged_cache.py latent_row_width).
    num_blocks: int = 0
    # A model with sliding-window layers keeps a second pool for them:
    # a slot holds a fixed RING of at most
    # ceil((sliding_window + prefill_chunk) / block_size) + 1 blocks there
    # (serve/paged_cache.py ring_blocks_for), given at admission, while
    # its full-attention layers grow by the block in the pool above.
    # 0 = auto: decode_slots rings, so the window pool never refuses an
    # admission a free slot allows. Ignored by a model of full layers.
    num_window_blocks: int = 0
    # Prompt tokens prefilled per engine iteration; one chunk interleaves
    # with each decode step so a long prompt cannot stall in-flight
    # decodes. Also the prefill program's static shape (prompts pad to a
    # chunk multiple; padded positions are sentinel-dropped).
    prefill_chunk: int = 64
    # Per-sequence capacity (prompt + generated). 0 = the model's
    # max_position_embeddings.
    max_model_len: int = 0
    # Decode steps run INSIDE one dispatch (a lax.scan over the decode
    # step, with in-flight EOS forcing identical to generate.py's scan):
    # amortizes the per-dispatch host overhead over this many tokens per
    # slot. The scheduler only sees tokens every interval, so admission/
    # retirement latency quantizes to it and a request that hits EOS or
    # its budget mid-interval pays the leftover steps as padding — keep
    # it small (2-8) for interactive SLOs, 1 for exact per-token
    # scheduling.
    decode_interval: int = 4

    # --- fleet serving (serve/fleet.py): a FleetSupervisor fronting N
    # engine replicas with health/failover/drain — the robustness layer
    # in front of the single-engine stack ---
    # Engine replicas behind the supervisor. 1 = no fleet (the
    # single-engine paths are untouched). Each replica gets its own
    # device (round-robin over jax.devices()), its own KV pool, and the
    # SAME base sampling key, so failover re-dispatch is bit-identical.
    fleet_size: int = 1
    # Default admission deadline applied to requests that do not carry
    # their own: a request still queued once its wait exceeds this many
    # milliseconds is SHED (rejected, serve_shed event, booked to the
    # `shed` ledger category) instead of admitted late. 0 = no deadline.
    deadline_ms: float = 0.0
    # Grace budget for FleetSupervisor.drain(): how long (trace-clock
    # seconds) a draining engine may keep its residents before they are
    # forcibly re-dispatched onto the survivors and the engine retires
    # anyway.
    drain_grace_s: float = 5.0

    def validate(self) -> None:
        for name in ("decode_slots", "block_size", "prefill_chunk",
                     "decode_interval"):
            if getattr(self, name) < 1:
                raise ValueError(
                    f"serve.{name} must be >= 1, got {getattr(self, name)}")
        for name in ("num_blocks", "num_window_blocks"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"serve.{name} must be >= 0 (0 = auto), got "
                    f"{getattr(self, name)}")
        if self.max_model_len < 0:
            raise ValueError(
                f"serve.max_model_len must be >= 0 (0 = model limit), got "
                f"{self.max_model_len}")
        if self.fleet_size < 1:
            raise ValueError(
                f"serve.fleet_size must be >= 1, got {self.fleet_size}")
        if self.deadline_ms < 0:
            raise ValueError(
                f"serve.deadline_ms must be >= 0 (0 = no deadline), got "
                f"{self.deadline_ms}")
        if self.drain_grace_s < 0:
            raise ValueError(
                f"serve.drain_grace_s must be >= 0, got "
                f"{self.drain_grace_s}")


@dataclass(frozen=True)
class LoggingConfig:
    """(ref: template/base_config.json:41-45)."""

    use_wandb: bool = False
    project_name: str = "picotron-tpu"
    run_name: Optional[str] = None
    log_frequency: int = 1
    # jax.profiler trace capture (SURVEY.md §5: the reference has no
    # profiler story; on TPU xprof traces are how compute/collective
    # overlap is verified). None disables; a directory enables capture of
    # steps [profile_start_step, profile_start_step + profile_num_steps).
    profile_dir: Optional[str] = None
    profile_start_step: int = 3
    profile_num_steps: int = 3
    # Structured telemetry (picotron_tpu/telemetry): write the per-host
    # JSONL event stream — step-phase timings, goodput ledger, resilience
    # events, per-step metrics — to `telemetry.jsonl` next to the
    # checkpoints (telemetry_dir overrides the location). Append-mode, so
    # a supervised restart continues the same stream and
    # tools/telemetry_report.py can account replayed steps across
    # restarts. The stdout log line is unaffected either way (its format
    # is frozen; tools/extract_metrics.py parses it).
    telemetry_jsonl: bool = True
    telemetry_dir: Optional[str] = None
    # Size-capped JSONL rotation: when > 0, a telemetry.jsonl exceeding
    # this many MB is rotated once to `telemetry.jsonl.1` and a fresh
    # segment starts. Readers (tools/telemetry_report.py,
    # tools/extract_metrics.py) read `.1` then the live file, so
    # cross-restart replay accounting survives rotation. 0 = unbounded.
    telemetry_max_mb: float = 0.0
    # flightdeck span tracer (telemetry/flightdeck/tracer.py): a
    # directory enables span recording (train phases, MPMD schedule
    # ticks, serve request lifecycles, resilience instants) exported as
    # Chrome-trace/Perfetto JSON `trace.json` on close. None disables —
    # the disabled path allocates nothing.
    trace_dir: Optional[str] = None
    # flightdeck crash flight recorder (telemetry/flightdeck/flight.py):
    # ring of the last N steps' phase timings + metrics + spans, dumped
    # to `flightdeck_postmortem.json` on abnormal exits (watchdog 77,
    # divergence abort/rollback, preemption 75, unhandled exceptions).
    # 0 disables.
    flight_steps: int = 8
    # flightdeck drift sentinel (telemetry/flightdeck/sentinel.py):
    # online watch of rolling step time, sync share vs the cost model's
    # predicted exposed comm, and data-wait share. A quantity breaching
    # `sentinel_ratio` x baseline (and `sentinel_zscore` sigmas where
    # the window has variance) for `sentinel_patience` consecutive
    # steps emits ONE `sentinel_alert` event and auto-dumps the flight
    # recorder.
    sentinel: bool = False
    sentinel_window: int = 32
    sentinel_zscore: float = 4.0
    sentinel_ratio: float = 1.5
    sentinel_patience: int = 3

    def validate(self) -> None:
        if self.telemetry_max_mb < 0:
            raise ValueError(
                f"logging.telemetry_max_mb must be >= 0 (0 disables "
                f"rotation), got {self.telemetry_max_mb}")
        if self.flight_steps < 0:
            raise ValueError(
                f"logging.flight_steps must be >= 0 (0 disables the "
                f"flight recorder), got {self.flight_steps}")
        if self.sentinel_window < 4:
            raise ValueError(
                f"logging.sentinel_window must be >= 4, got "
                f"{self.sentinel_window}")
        if self.sentinel_ratio <= 1.0:
            raise ValueError(
                f"logging.sentinel_ratio must be > 1.0, got "
                f"{self.sentinel_ratio}")
        if self.sentinel_zscore <= 0.0:
            raise ValueError(
                f"logging.sentinel_zscore must be > 0, got "
                f"{self.sentinel_zscore}")
        if self.sentinel_patience < 1:
            raise ValueError(
                f"logging.sentinel_patience must be >= 1, got "
                f"{self.sentinel_patience}")


@dataclass(frozen=True)
class PipelineConfig:
    """Pipeline executor selection (picotron_tpu/parallel/mpmd.py).

    executor='spmd' is the reference twin: the whole pipeline is one jitted
    program over the full mesh and the schedule is a lockstep lax.scan over
    the 1f1b table — every device runs every tick, so an IDLE tick costs a
    full traced unit (PERF.md r4 measured the implied bubble at 7.0 ticks
    for pp=4). executor='mpmd' compiles one program per stage and drives
    them from the host-side schedule table; idle ticks cost ~0 host time
    (arxiv 2412.14374), which is what makes interleaved schedules
    profitable at all (see the PERF.md "Interleaved-PP rejection" note,
    which is scoped to the SPMD executor)."""

    # 'spmd' (lockstep scan twin, the default) or 'mpmd' (per-stage
    # programs + host-side schedule).
    executor: str = "spmd"
    # Schedule grammar: '1f1b' (one-forward-one-backward, depth-first
    # backward priority), 'gpipe' (all forwards then all backwards — the
    # AFAB dependency shape, useful as a debugging twin), 'interleaved'
    # (virtual stages: each device group owns `interleave` non-contiguous
    # layer chunks, shrinking the bubble to (pp-1)/v units). mpmd only for
    # anything but '1f1b'.
    schedule: str = "1f1b"
    # Virtual pipeline chunks per device group (v). 1 = plain schedules;
    # >= 2 requires schedule='interleaved' and executor='mpmd'.
    interleave: int = 1

    def validate(self) -> None:
        if self.executor not in ("spmd", "mpmd"):
            raise ValueError(
                f"pipeline.executor must be 'spmd' or 'mpmd', got "
                f"{self.executor!r}")
        if self.schedule not in ("1f1b", "gpipe", "interleaved"):
            raise ValueError(
                f"pipeline.schedule must be '1f1b', 'gpipe', or "
                f"'interleaved', got {self.schedule!r}")
        if self.interleave < 1:
            raise ValueError(
                f"pipeline.interleave must be >= 1, got {self.interleave}")


@dataclass(frozen=True)
class Config:
    distributed: DistributedConfig = field(default_factory=DistributedConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    training: TrainingConfig = field(default_factory=TrainingConfig)
    dataset: DatasetConfig = field(default_factory=DatasetConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    logging: LoggingConfig = field(default_factory=LoggingConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    pipeline: PipelineConfig = field(default_factory=PipelineConfig)

    # -- derived quantities (ref: data.py:17-20) --

    @property
    def global_batch_size(self) -> int:
        t = self.training
        return (t.micro_batch_size * t.gradient_accumulation_steps
                * self.distributed.dp_size * self.distributed.ep_size)

    @property
    def tokens_per_step(self) -> int:
        return self.global_batch_size * self.training.seq_length

    @property
    def seq_length_per_device(self) -> int:
        return self.training.seq_length // self.distributed.cp_size

    def validate(self) -> None:
        self.distributed.validate()
        self.model.validate()
        self.logging.validate()
        self.resilience.validate()
        self.serve.validate()
        self.pipeline.validate()
        if self.serve.max_model_len > self.model.max_position_embeddings:
            raise ValueError(
                f"serve.max_model_len ({self.serve.max_model_len}) exceeds "
                f"max_position_embeddings "
                f"({self.model.max_position_embeddings})")
        if self.serve.fleet_size > 1 and self.model.num_experts:
            # the fleet's bit-identical failover re-dispatch is pinned by
            # test for dense models only
            raise ValueError(
                "serve.fleet_size > 1 does not support MoE models "
                "(model.num_experts > 0): failover re-dispatch is pinned "
                "bit-identical for dense models only and has never been "
                "run or tested with an expert block; serve experts "
                "through one ServeEngine")
        if self.model.layer_types is not None:
            self._refuse_window_layers()
        self._refuse_new_blocks()
        d, m, t = self.distributed, self.model, self.training
        ck = self.checkpoint
        if ck.keep_last < 0 or ck.keep_every < 0:
            raise ValueError(
                f"checkpoint.keep_last/keep_every must be >= 0 (0 "
                f"disables), got keep_last={ck.keep_last} "
                f"keep_every={ck.keep_every}")
        if self.resilience.guard_policy == "skip" and t.optimizer_offload:
            # The in-jit skip selects the pre-update params/opt state,
            # but the offload update streams the host master in place —
            # there is no pre-update tree left to select.
            raise ValueError(
                "resilience.guard_policy='skip' is not supported with "
                "training.optimizer_offload (the streamed host-master "
                "update cannot be un-applied in-step); use 'rollback' "
                "or 'abort'")
        if m.num_attention_heads % d.tp_size != 0:
            raise ValueError("num_attention_heads must be divisible by tp_size")
        if m.num_key_value_heads % d.tp_size != 0:
            raise ValueError("num_key_value_heads must be divisible by tp_size")
        if m.vocab_size % d.tp_size != 0:
            raise ValueError("vocab_size must be divisible by tp_size")
        if (d.cp_flavor and m.attn_impl in ("ring", "ulysses", "mesh")
                and m.attn_impl != d.cp_flavor):
            raise ValueError(
                f"distributed.cp_flavor={d.cp_flavor!r} contradicts "
                f"model.attn_impl={m.attn_impl!r} — set one of them (or "
                "attn_impl='auto' and let cp_flavor pick the schedule)")
        flavor = resolved_cp_flavor(self)
        if flavor == "ulysses":
            if (m.num_attention_heads // d.tp_size) % d.cp_size != 0 or (
                    m.num_key_value_heads // d.tp_size) % d.cp_size != 0:
                raise ValueError(
                    "the ulysses cp flavor scatters the tp-local heads over "
                    "cp: num_attention_heads/tp and num_key_value_heads/tp "
                    f"must be divisible by cp_size ({d.cp_size}); use the "
                    "ring or mesh flavor for head counts that do not divide")
        if d.cp_mesh and flavor != "mesh":
            raise ValueError(
                f"cp_mesh={d.cp_mesh!r} only applies to the mesh cp flavor "
                f"(resolved flavor here: {flavor or 'none — cp_size is 1'}); "
                "set cp_flavor='mesh' or attn_impl='mesh'")
        if flavor == "mesh":
            cp_x, cp_y = resolved_cp_mesh(self)
            if cp_y > 1 and (
                    (m.num_attention_heads // d.tp_size) % cp_y != 0
                    or (m.num_key_value_heads // d.tp_size) % cp_y != 0):
                raise ValueError(
                    f"mesh cp flavor with cp_mesh {cp_x}x{cp_y} scatters "
                    f"the tp-local heads over the inner factor: "
                    "num_attention_heads/tp and num_key_value_heads/tp "
                    f"must be divisible by cp_y ({cp_y}); pick a smaller "
                    "cp_y (cp_y=1 degenerates to the ring schedule)")
        if d.ep_size > 1 and m.num_experts == 0:
            raise ValueError(
                "ep_size > 1 requires a mixture-of-experts model "
                "(model.num_experts > 0)")
        if m.num_experts:
            if m.num_experts % d.ep_size != 0:
                raise ValueError(
                    f"num_experts ({m.num_experts}) must be divisible by "
                    f"ep_size ({d.ep_size})")
            if not 1 <= m.num_experts_per_token <= m.num_experts:
                raise ValueError(
                    f"num_experts_per_token must be in [1, num_experts], "
                    f"got {m.num_experts_per_token} of {m.num_experts}")
            if m.expert_ffn_size % d.tp_size != 0:
                raise ValueError(
                    "expert ffn size must be divisible by tp_size")
        if m.qk_norm is True and d.tp_size > 1:
            raise ValueError(
                "model.qk_norm normalizes q and k over the whole projected "
                "vector, which tp_size > 1 splits across shards; a per-shard "
                "norm would be a different model, so the combination is "
                "refused (tp_size must be 1)")
        if t.remat_policy not in ("full", "dots", "dots_attn", "dots_lean",
                                  "dots_norms", "dots_offload"):
            raise ValueError(
                f"remat_policy must be 'full', 'dots', 'dots_attn', "
                f"'dots_lean', 'dots_norms', or 'dots_offload', got "
                f"{t.remat_policy!r}")
        if t.adam_moments_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"adam_moments_dtype must be 'float32' or 'bfloat16', got "
                f"{t.adam_moments_dtype!r}")
        if t.lr_schedule not in ("constant", "cosine", "linear"):
            raise ValueError(
                f"lr_schedule must be constant/cosine/linear, got "
                f"{t.lr_schedule!r}")
        if t.lr_warmup_steps < 0 or t.lr_warmup_steps > t.total_train_steps:
            raise ValueError(
                f"lr_warmup_steps must be in [0, total_train_steps], got "
                f"{t.lr_warmup_steps}")
        if not 0.0 <= t.lr_min_ratio <= 1.0:
            # a negative ratio would drive the decayed LR below zero and
            # silently ascend the loss late in training
            raise ValueError(
                f"lr_min_ratio must be in [0, 1], got {t.lr_min_ratio}")
        if t.ce_chunk_size < 0:
            raise ValueError(
                f"ce_chunk_size must be >= 0, got {t.ce_chunk_size}")
        if t.ce_chunk_size > 0:
            vshard = m.vocab_size // d.tp_size
            if t.ce_chunk_size >= vshard:
                # a chunk spanning the whole per-shard vocab IS the fused
                # path — the implementation would silently take it, and the
                # user set the knob precisely to avoid that memory (ADVICE
                # r3: the old check let any value >= vshard through)
                raise ValueError(
                    f"ce_chunk_size ({t.ce_chunk_size}) must be smaller "
                    f"than the per-tp-shard vocab (vocab_size/tp_size = "
                    f"{vshard}); at or above it chunking degenerates to "
                    f"the fused CE path")
            if vshard % t.ce_chunk_size != 0:
                # a non-dividing chunk would silently fall back to the
                # fused path — the user set the knob to AVOID that memory
                raise ValueError(
                    f"ce_chunk_size ({t.ce_chunk_size}) must divide the "
                    f"per-tp-shard vocab (vocab_size/tp_size = {vshard})")
        if t.grad_engine not in ("auto", "ad", "fused"):
            raise ValueError(
                f"grad_engine must be auto/ad/fused, got {t.grad_engine!r}")
        if t.grad_engine == "fused":
            from picotron_tpu.parallel.fused_bwd import fused_bwd_supported

            if not fused_bwd_supported(self):
                raise ValueError(
                    "grad_engine='fused' requires remat with "
                    "remat_policy='dots_attn' — the save set the manual "
                    "backward is derived from — and a layout that runs "
                    "it: a single pipeline stage (pp_size=1, where "
                    "dp/tp/sequence_parallel/cp (ring and ulysses)/ep/MoE "
                    "all compose), or pp_engine='1f1b' under the spmd "
                    "executor (MoE only where the layers split evenly "
                    "over the stages; see the README grad-engine "
                    "eligibility matrix); use 'auto' to fall back to the "
                    "AD engine automatically")
        if t.optimizer_offload:
            # zero1 COMPOSES with offload (r5): the host master/moments
            # shard over the fused data axes, each process streams 1/dp
            # of the state, and the update all-gathers the refreshed
            # bf16 params — dp x less host RAM and PCIe per process.
            if self.model.dtype != "bfloat16":
                raise ValueError(
                    "optimizer_offload requires model.dtype='bfloat16' "
                    "(the device-resident compute copy is the model's "
                    "compute dtype; an fp32 compute copy would duplicate "
                    "the master and save nothing)")
            if d.pp_size > 1 and d.pp_engine == "afab":
                # afab differentiates through the pipeline scan, so param
                # cotangents accumulate in the param dtype — bf16 under
                # offload, losing exactly the low bits the fp32 master
                # keeps. The 1f1b manual-VJP path accumulates its grads in
                # fp32 explicitly (pp.py g_zero) and is the supported
                # offload x pp combination (ADVICE r4).
                raise ValueError(
                    "optimizer_offload with pp_engine='afab' would "
                    "accumulate microbatch gradients in bf16 (the AD "
                    "path's cotangent dtype is the bf16 param dtype); "
                    "use pp_engine='1f1b' (the default), whose manual "
                    "VJP accumulates gradients in fp32")
        lg = self.logging
        if lg.profile_dir is not None:
            if lg.profile_start_step < 1:
                raise ValueError(
                    f"profile_start_step must be >= 1 (steps are 1-based), "
                    f"got {lg.profile_start_step}")
            if lg.profile_num_steps < 1:
                raise ValueError(
                    f"profile_num_steps must be >= 1, got "
                    f"{lg.profile_num_steps}")
        if t.seq_length < 1:
            raise ValueError(f"seq_length must be >= 1, got {t.seq_length}")
        if t.seq_length % d.cp_size != 0:
            raise ValueError("seq_length must be divisible by cp_size")
        if (d.sequence_parallel
                and t.seq_length % (d.cp_size * d.tp_size) != 0):
            raise ValueError(
                "sequence_parallel shards the cp-local sequence over tp: "
                "seq_length must be divisible by cp_size * tp_size "
                f"(= {d.cp_size * d.tp_size}), got {t.seq_length}")
        if (d.cp_size > 1 and d.cp_layout == "zigzag"
                and t.seq_length % (2 * d.cp_size) != 0):
            raise ValueError(
                f"zigzag cp_layout needs seq_length divisible by 2*cp_size "
                f"({2 * d.cp_size}); got {t.seq_length}. Use "
                f"cp_layout='contiguous' or adjust seq_length."
            )
        if t.seq_length > m.max_position_embeddings:
            # Same bound the reference applies by construction (ref:
            # train.py:159 sets seq_length == max_position_embeddings).
            raise ValueError(
                f"seq_length ({t.seq_length}) exceeds max_position_embeddings "
                f"({m.max_position_embeddings})"
            )
        if d.pp_size > m.num_hidden_layers:
            raise ValueError(
                f"pp_size ({d.pp_size}) cannot exceed num_hidden_layers ({m.num_hidden_layers})"
            )
        # num_hidden_layers % pp_size may be nonzero: the stacked layer axis
        # is padded with identity (all-zero) layers and the remainder goes to
        # early stages (ref: pipeline_parallel.py:42-51 distribute_layers);
        # see models.llama.pp_layer_placement.
        if t.eval_frequency < 0 or (t.eval_frequency > 0 and t.eval_steps < 1):
            raise ValueError(
                "eval_frequency must be >= 0 and eval_steps >= 1 when "
                f"eval is enabled, got {t.eval_frequency}/{t.eval_steps}")
        if t.gradient_accumulation_steps < 1:
            raise ValueError(
                f"gradient_accumulation_steps must be >= 1, got "
                f"{t.gradient_accumulation_steps}"
            )
        pl = self.pipeline
        if pl.executor == "spmd":
            if pl.schedule != "1f1b" or pl.interleave != 1:
                raise ValueError(
                    "the spmd executor only runs the lockstep 1f1b scan "
                    "(schedule='1f1b', interleave=1); alternative schedules "
                    "require pipeline.executor='mpmd', where an idle tick "
                    f"stops costing a full traced unit — got "
                    f"schedule={pl.schedule!r} interleave={pl.interleave}")
        else:  # mpmd
            if d.pp_size < 2:
                raise ValueError(
                    "pipeline.executor='mpmd' requires pp_size >= 2 (with "
                    "one stage there is nothing to schedule; the single "
                    "jitted program IS the spmd executor)")
            if t.optimizer_offload:
                raise ValueError(
                    "pipeline.executor='mpmd' does not compose with "
                    "training.optimizer_offload yet (the streamed host "
                    "update assumes the monolithic step program); use the "
                    "spmd executor for offload runs")
            if m.num_experts:
                raise ValueError(
                    "pipeline.executor='mpmd' does not support MoE models "
                    "yet (per-stage submeshes drop the 'ep' axis from the "
                    "stage programs, and nothing tests an expert block "
                    "there); use the spmd executor")
            if d.sequence_parallel:
                raise ValueError(
                    "pipeline.executor='mpmd' does not support "
                    "sequence_parallel yet (the sp grad sync runs over the "
                    "whole-mesh program); use the spmd executor")
            if d.pp_engine != "1f1b":
                raise ValueError(
                    "pipeline.executor='mpmd' drives the host schedule "
                    "table; set pp_engine='1f1b' (the afab engine is an "
                    "spmd-only differentiation strategy)")
        if pl.schedule == "interleaved":
            if pl.interleave < 2:
                raise ValueError(
                    "pipeline.schedule='interleaved' needs interleave >= 2 "
                    "(v=1 interleaving IS plain 1f1b); got "
                    f"{pl.interleave}")
            slots = -(-m.num_hidden_layers // d.pp_size)  # ceil
            if slots % pl.interleave != 0:
                raise ValueError(
                    f"pipeline.interleave ({pl.interleave}) must divide the "
                    f"per-stage layer slot count (ceil(num_hidden_layers / "
                    f"pp_size) = {slots}) so every virtual chunk is the "
                    f"same shape and compiles once")
        elif pl.interleave != 1:
            raise ValueError(
                f"pipeline.interleave > 1 requires "
                f"pipeline.schedule='interleaved', got "
                f"schedule={pl.schedule!r} interleave={pl.interleave}")

    def _refuse_window_layers(self) -> None:
        """Sliding-window layers, Gated DeltaNet mixers (linear_attention
        layers), Mamba mixers (mamba layers), Kimi Delta Attention mixers
        (kda layers) and layers that are one sublayer each (mamba2 and
        experts layers) run on the plain attention of
        `forward()`, on `generate()` and on `ServeEngine`. Every path
        that has no band, or that slices, shards or copies a stack whose
        layers are all alike, refuses the model by name (ROADMAP M4: the
        banded flash kernel for training; M9: the mixer's sharded
        layouts)."""
        d, m, t, sv = (self.distributed, self.model, self.training,
                       self.serve)
        kinds = " and ".join(sorted(set(m.layer_types) - {"full_attention"}))

        def refuse(what: str) -> None:
            raise ValueError(
                f"model.layer_types holds {kinds} layers, which "
                f"{what} does not implement (no band in its mask, no "
                f"recurrent state, one kind of layer a stack); they "
                f"run on attn_impl='reference', generate() and "
                f"ServeEngine only")

        if m.attn_impl in ("flash", "ring", "ulysses", "mesh"):
            refuse(f"attn_impl={m.attn_impl!r}")
        if d.cp_size > 1:
            refuse(f"context parallelism (cp_size={d.cp_size}: the "
                   f"ring / ulysses / mesh schedules)")
        if t.grad_engine == "fused":
            refuse("grad_engine='fused'")
        if d.pp_size > 1:
            refuse(f"pipeline parallelism (pp_size={d.pp_size}: a stage "
                   f"slice would have to carry its own slice of the "
                   f"pattern, as a stack does)")
        if d.tp_size > 1:
            refuse(f"tensor parallelism (tp_size={d.tp_size}: the two "
                   f"pools of a mixed cache, and a state pool, are not "
                   f"sharded)")
        if m.recurrent and d.ep_size > 1:
            refuse(f"expert parallelism (ep_size={d.ep_size}: the mixer's "
                   f"leaves have no sharding rule)")
        if sv.fleet_size > 1:
            refuse("serve.fleet_size > 1 "
                   "(one pool, one table a slot, no hand-over of a state)")

    def _refuse_new_blocks(self) -> None:
        """Latent attention (scaled or not), sandwich norms, a shared
        expert, sigmoid routing, a held share of the experts, a layer of two
        attentions with a shortcut-connected expert branch, zero-compute
        experts, a router selection bias, leading dense layers (with
        or without a layer pattern cut over the two stacks), per-head
        QK-norm, an unrotated layer kind, EVA attention, a head of several
        prediction heads, 1 + w norms, a float32 residual stream, a
        partly rotated head, a gated attention output and a gated shared
        expert run on
        the plain attention of `forward()` (and its AD), on `generate()`
        and on `ServeEngine`, on one device (EVA and several prediction
        heads have no training loss at all: `refuse_training`). Every
        path that has its own copy of the block, one
        head width for q, k and v, or a single `layers` stack refuses them
        by name (ROADMAP M5, M3, D6)."""
        d, m, t, sv = (self.distributed, self.model, self.training,
                       self.serve)
        what = [name for name, on in (
            ("per-head QK-norm (qk_norm='head')", m.qk_norm == "head"),
            ("a layer kind that is not rotated (rope_type 'none')",
             any(dict(law).get("rope_type") == "none"
                 for _, law in m.rope_parameters or ())),
            ("latent attention (kv_lora_rank > 0)", m.mla),
            ("latent attention without a query bottleneck (q_lora_rank 0)",
             m.mla and not m.q_lora_rank),
            ("an unrotated latent head (mla_use_nope)", m.mla_use_nope),
            ("first_k_dense_replace > 0", m.first_k_dense_replace > 0),
            ("sandwich_norm", m.sandwich_norm),
            ("n_shared_experts > 0", m.n_shared_experts > 0),
            ("moe_scoring='sigmoid'", m.moe_scoring != "softmax"),
            ("routed_scaling_factor != 1", m.routed_scaling_factor != 1.0),
            ("a held share of the experts (router_experts)",
             m.router_width - m.zero_experts != m.num_experts),
            ("a shortcut-connected expert branch beside two attentions a "
             "layer (shortcut_moe)", m.shortcut_moe),
            ("zero-compute experts (zero_experts)", m.zero_experts > 0),
            ("a router selection bias (moe_selection_bias)",
             m.moe_selection_bias),
            ("scaled latents (mla_scale_q_lora / mla_scale_kv_lora)",
             m.mla_scale_q_lora or m.mla_scale_kv_lora),
            ("attention_class 'eva' (window_size / chunk_size)", m.eva),
            ("num_pred_heads > 1", m.num_pred_heads > 1),
            ("norm_add_unit_offset", m.norm_add_unit_offset),
            ("fp32_skip_add", m.fp32_skip_add),
            ("partial_rotary_factor < 1", m.partial_rotary_factor != 1.0),
            ("a gated attention output (attn_output_gate)",
             m.attn_output_gate),
            ("a gated shared expert (shared_expert_gate)",
             m.shared_expert_gate),
            ("layers that are one sublayer each (mamba2 / experts layers)",
             m.single_sublayer),
            ("a non-gated MLP (hidden_act 'relu2')", not m.mlp_gated),
            ("experts on a latent (moe_latent_size)", m.moe_latent_size > 0),
        ) if on]
        if not what:
            return

        def refuse(path: str, why: str) -> None:
            raise ValueError(
                f"model has {', '.join(what)}, which {path} does not "
                f"implement ({why}); such a model runs on "
                f"attn_impl='reference' with grad_engine='ad', on "
                f"generate() and on ServeEngine, on one device")

        if m.attn_impl in ("flash", "ring", "ulysses", "mesh"):
            refuse(f"attn_impl={m.attn_impl!r}",
                   "the flash kernels and the cp schedules have one head "
                   "width for q, k and v and no latent form")
        if d.cp_size > 1:
            refuse(f"context parallelism (cp_size={d.cp_size})",
                   "the ring / ulysses / mesh schedules move K and V per "
                   "head")
        if t.grad_engine == "fused":
            refuse("grad_engine='fused'",
                   "the fused grad engine carries its own copy of the "
                   "block and one `layers` stack")
        if d.tp_size > 1:
            refuse(f"tensor parallelism (tp_size={d.tp_size})",
                   "a latent cache has one head and is not split by "
                   "heads; the new weights have no tp sharding rule")
        if d.pp_size > 1:
            refuse(f"pipeline parallelism (pp_size={d.pp_size})",
                   "a stage slices one `layers` stack")
        if d.ep_size > 1:
            refuse(f"expert parallelism (ep_size={d.ep_size})",
                   "the exchange across 'ep' routes by softmax gates over "
                   "every expert; a held share is served without exchange")
        if sv.fleet_size > 1:
            refuse("serve.fleet_size > 1",
                   "one K/V pool, never run with this block")

    def to_json_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def replace(self, **sections: Any) -> "Config":
        return dataclasses.replace(self, **sections)


def refuse_training(m: ModelConfig) -> None:
    """The training entry points (`models.llama.loss_sum_count`, which every
    train step differentiates, and `train.main`) refuse by name a model
    that has no training loss: EVA attention trains through a banded kernel
    with summary keys and its backward, which is not built (ROADMAP M9),
    a head of several prediction heads has no loss over them, and a router
    with zero-compute experts or a selection bias has no balance term (the
    bias is moved by the experts' load outside the loss, and a share of the
    load is meant to fall on the zero-compute experts), nor has the layer
    of two attentions a fused or pipelined form; a Mamba mixer's scan has
    no backward at training shapes (a [sequence, d_inner, d_state] float32
    history a layer, kept or recomputed: ROADMAP M9), nor has a Kimi Delta
    Attention mixer's chunked rule (its within-chunk decays are built block
    by block with a loop over the columns of each diagonal block, a form
    written for the served prefill: ROADMAP M9 (a)); a Mamba-2 mixer's
    chunked rule is plain matrix products, but a model of them is layers of
    one sublayer each with non-gated experts on a latent, for which no train
    step, sharding rule or router loss was ever run (ROADMAP M9 (c))."""
    what = [name for name, on in (
        ("mamba2 layers (layers of one sublayer each)", m.ssd),
        ("experts layers (an expert block alone a layer)",
         MOE in m.layer_kinds),
        ("kda layers (a delta rule with a decay a channel)", m.kda),
        ("mamba layers (a selective scan)", m.ssm),
        ("attention_class 'eva'", m.eva),
        ("num_pred_heads > 1", m.num_pred_heads > 1),
        ("a shortcut-connected expert branch (shortcut_moe)", m.shortcut_moe),
        ("zero-compute experts (zero_experts)", m.zero_experts > 0),
        ("a router selection bias (moe_selection_bias)",
         m.moe_selection_bias)) if on]
    if what:
        raise ValueError(
            f"model has {', '.join(what)}, which training does not "
            f"implement (no backward of the selective scan or of the "
            f"per-channel delta rule at training shapes, no train step over "
            f"layers of one sublayer each, no loss over "
            f"several prediction heads, no banded "
            f"attention kernel with summary keys and its backward, no router "
            f"loss over zero-compute experts and no update of a selection "
            f"bias); such a model runs on forward(), generate() and "
            f"ServeEngine")


def check_eva_serving(m: ModelConfig, sv: ServeConfig) -> None:
    """What `ServeEngine` needs of the serve settings to hold a model with
    attention_class 'eva' in its paged cache (serve/paged_cache.py
    EvaPagedCache): a prefill chunk never straddles a window and holds
    whole chunks, a window, a window's summaries and a prefill chunk are
    whole blocks."""
    w, c, bs, pc = m.window_size, m.chunk_size, sv.block_size, sv.prefill_chunk
    if w % pc or pc % c:
        raise ValueError(
            f"attention_class 'eva': serve.prefill_chunk ({pc}) must divide "
            f"window_size ({w}) and be a whole number of chunks of "
            f"chunk_size ({c}): a prefill chunk never straddles a window "
            f"and summarises whole chunks")
    if w % bs or (w // c) % bs or pc % bs:
        raise ValueError(
            f"attention_class 'eva': a window ({w} positions), a window's "
            f"summaries ({w // c}) and a prefill chunk ({pc}) must each be "
            f"a whole number of blocks of serve.block_size ({bs})")


def resolved_cp_flavor(cfg: "Config") -> str:
    """The context-parallel attention schedule this config runs:
    'ring' | 'ulysses' | 'mesh' when cp_size > 1, '' otherwise. The single
    dispatch key for parallel/api.py, parallel/fused_bwd.py, the
    collective-schedule audit and the cost model — distributed.cp_flavor
    wins, model.attn_impl names a flavor directly for back-compat, and the
    default is the ring (no head-divisibility constraint)."""
    d, m = cfg.distributed, cfg.model
    if d.cp_size <= 1:
        return ""
    if d.cp_flavor:
        return d.cp_flavor
    if m.attn_impl in ("ring", "ulysses", "mesh"):
        return m.attn_impl
    return "ring"


def resolved_cp_mesh(cfg: "Config") -> tuple[int, int]:
    """(cp_x, cp_y) for the mesh cp flavor. An explicit distributed.cp_mesh
    wins; otherwise the most-square FEASIBLE factorization (cp_y must
    divide the tp-local q and kv head counts), tie-broken toward the
    larger cp_y — one all_to_all over more (contiguous, innermost-ICI)
    devices is cheaper than an extra serial ring hop. The planner
    enumerates every feasible factorization against the topology-aware
    cost model instead of trusting this default."""
    d, m = cfg.distributed, cfg.model
    cp = d.cp_size
    if d.cp_mesh:
        return parse_cp_mesh(d.cp_mesh)
    hq = m.num_attention_heads // d.tp_size
    hkv = m.num_key_value_heads // d.tp_size
    feasible = [y for y in range(1, cp + 1)
                if cp % y == 0 and hq % y == 0 and hkv % y == 0]
    cp_y = min(feasible, key=lambda y: (abs(y - cp ** 0.5), -y))
    return cp // cp_y, cp_y


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------


def _filter_kwargs(cls: type, raw: dict[str, Any]) -> dict[str, Any]:
    # Unknown keys are ignored on load: a dumped config of a newer or older
    # run loads. The consequence for a READER of a published architecture is
    # the opposite rule: `model_config_from_hf_json` hands this function only
    # names it chose, so a key of the source it does not know never gets here
    # to be dropped, and the one reader whose family keeps growing keys that
    # change what a layer IS (`nemotron_h`: `_nemotron_h_kwargs`) refuses an
    # unknown key by name, so that a config with `moe_latent_size` cannot
    # silently build full-width experts.
    names = {f.name for f in dataclasses.fields(cls)}
    return {k: v for k, v in raw.items() if k in names}


# Options that were removed together with the code they selected, by
# section, with their former defaults. Unknown keys are ignored on load, so
# without this a config asking for a removed schedule would silently train
# the one that remains (or serve one token a step where it asked for
# drafts). A key carrying its former default loads (dumped configs of old
# runs do) and is dropped.
_RETIRED = {
    "distributed": {
        "tp_strategy": "megatron",
        "tp_sync": "sync",
        "tp_mesh": "",
        "dcn_axes": "dp,pp",
        "hier_dp_reduce": "auto",
    },
    "serve": {
        "speculator": "off",
        "draft_len": 3,
        "disagg": False,
        "prefill_slots": 0,
        "prefill_num_blocks": 0,
        "prefill_device": -1,
        "decode_device": -1,
    },
}


def _reject_retired(raw: dict[str, Any]) -> None:
    for section, retired in _RETIRED.items():
        given = raw.get(section) or {}
        for key, former in retired.items():
            if key in given and given[key] != former:
                raise ValueError(
                    f"{section}.{key}={given[key]!r}: this option was "
                    f"removed with the code it selected; only its former "
                    f"default {former!r} still loads. Delete the key.")


def config_from_dict(raw: dict[str, Any]) -> Config:
    """Build a Config from a (reference-schema-compatible) dict."""
    _reject_retired(raw)
    model_raw = dict(raw.get("model", {}))
    name = model_raw.get("name")
    if name:
        try:
            preset = resolve_preset(name)
        except KeyError:
            # Unknown name is only acceptable when the JSON itself carries the
            # architecture — otherwise a typo'd name would silently train the
            # tiny debug defaults.
            core = {"vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers"}
            if not core.issubset(model_raw):
                raise
            preset = {}
        # Explicit values in the JSON override the preset (ref:
        # create_config.py:56-63 same precedence for layer/head overrides).
        merged = {**preset, **{k: v for k, v in model_raw.items() if v is not None}}
    else:
        # No name: a partially-specified architecture would silently merge
        # with the tiny debug defaults — require the core fields, or nothing.
        core = {"vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers"}
        arch_keys = {k for k, v in model_raw.items() if v is not None} - {
            "dtype", "attn_impl", "use_flash_attention", "use_fused_adam"
        }
        if arch_keys and not core.issubset(arch_keys):
            raise ValueError(
                "model section specifies architecture fields without a `name`; "
                f"either set `name` to a preset or provide all of {sorted(core)}"
            )
        merged = model_raw
    # The reference allows `num_hidden_layers: null` meaning "use preset".
    merged = {k: v for k, v in merged.items() if v is not None}

    cfg = Config(
        distributed=DistributedConfig(**_filter_kwargs(DistributedConfig, raw.get("distributed", {}))),
        model=ModelConfig(**_filter_kwargs(ModelConfig, merged)),
        training=TrainingConfig(**_filter_kwargs(TrainingConfig, raw.get("training", {}))),
        dataset=DatasetConfig(**_filter_kwargs(DatasetConfig, raw.get("dataset", {}))),
        checkpoint=CheckpointConfig(**_filter_kwargs(CheckpointConfig, raw.get("checkpoint", {}))),
        logging=LoggingConfig(**_filter_kwargs(LoggingConfig, raw.get("logging", {}))),
        resilience=ResilienceConfig(**_filter_kwargs(ResilienceConfig, raw.get("resilience", {}))),
        serve=ServeConfig(**_filter_kwargs(ServeConfig, raw.get("serve", {}))),
        pipeline=PipelineConfig(**_filter_kwargs(PipelineConfig, raw.get("pipeline", {}))),
    )
    cfg.validate()
    return cfg


def load_config(path: str) -> Config:
    """Load a config JSON (reference schema compatible, ref: train.py:62)."""
    with open(path) as f:
        raw = json.load(f)
    return config_from_dict(raw)


def save_config(cfg: Config, path: str) -> None:
    with open(path, "w") as f:
        json.dump(cfg.to_json_dict(), f, indent=2)


def num_params(m: ModelConfig, active_only: bool = False,
               include_tied_head: bool = False) -> int:
    """Total parameter count (embedding + untied head counted separately,
    matching the reference's accounting in utils.py:50-79). For MoE,
    `active_only` counts the top-k experts a token actually visits — the N
    that belongs in the 6N FLOPs/token formula. `include_tied_head` counts
    the h*v head term even when tie_word_embeddings shares it with the
    embedding: the head MATMUL executes either way, so the FLOPs accounting
    (utils.flops_per_token) must include it or tied models would
    understate MFU by the head's share."""
    h, i, v, l = m.hidden_size, m.intermediate_size, m.vocab_size, m.num_hidden_layers
    kv = m.num_key_value_heads * m.head_dim
    if m.single_sublayer:
        # a layer is one sublayer and one norm: an attention (q, k, v, o), a
        # Mamba-2 mixer ([z | x B C | dt], the convolution and its bias,
        # dt_bias, A_log and D a head, the grouped norm, the output
        # projection) or the experts (router and its bias, the latent's two
        # projections, the routed experts of two matrices on the latent, the
        # shared expert of two on the full width)
        q = m.num_attention_heads * m.head_dim
        di, c = m.ssd_inner, m.ssd_channels
        mixer = (h * (di + c + m.mamba_num_heads) + c * m.mamba_d_conv
                 + (c if m.mamba_conv_bias else 0) + 3 * m.mamba_num_heads
                 + di + di * h)
        mats = 3 if m.mlp_gated else 2
        lat = m.expert_in_size
        experts = (h * m.router_width
                   + (m.router_width if m.moe_selection_bias else 0)
                   + (2 * h * lat if m.moe_latent_size else 0)
                   + (m.num_experts_per_token if active_only
                      else m.num_experts) * mats * lat * m.expert_ffn_size
                   + mats * h * m.shared_ffn_size)
        kinds = m.layer_kinds
        layers = (kinds.count("full_attention") * (2 * h * q + 2 * h * kv)
                  + kinds.count(SSD) * mixer + kinds.count(MOE) * experts
                  + l * h)
        head = (h * v if (not m.tie_word_embeddings or include_tied_head)
                else 0)
        return v * h + layers + h + head
    dense_ffn = 3 * h * i  # gate/up/down
    if m.num_experts:
        e_ffn = 3 * h * m.expert_ffn_size  # gate/up/down per expert
        n_ffn_experts = (m.num_experts_per_token if active_only
                         else m.num_experts)
        # router (+ its selection bias) + routed experts (those held here)
        # + shared experts
        ffn = (h * m.router_width + n_ffn_experts * e_ffn
               + m.n_shared_experts * e_ffn
               + (m.router_width if m.moe_selection_bias else 0)
               + (h if m.shared_expert_gate else 0))
    else:
        ffn = dense_ffn
    if m.mla:
        heads = m.num_attention_heads
        # q_a + its norm, then q_b; q_b alone (from h) without a bottleneck
        attn = (h * m.q_lora_rank + m.q_lora_rank
                + (m.q_lora_rank or h) * heads * (m.qk_nope_head_dim
                                                  + m.qk_rope_head_dim)
                + h * (m.kv_lora_rank + m.qk_rope_head_dim)
                + m.kv_lora_rank  # kv_a + its norm
                + m.kv_lora_rank * heads * (m.qk_nope_head_dim
                                            + m.v_head_dim)
                + heads * m.v_head_dim * h)
    else:
        q = m.num_attention_heads * m.head_dim
        attn = h * q + h * kv * 2 + q * h  # q, k/v, out projections
        if m.attn_output_gate:
            attn += h * q  # the gate's half of q's projection
        if m.attention_bias:
            attn += q + 2 * kv  # q/k/v biases
        if m.qk_norm == "head":
            attn += 2 * m.head_dim  # q_norm / k_norm, one vector for all heads
        elif m.qk_norm:
            attn += q + kv  # q_norm / k_norm weights
        if m.eva:
            attn += 2 * kv  # eva_mu / eva_phi: a pooling vector a KV head
    norms = (4 if m.sandwich_norm else 2) * h  # RMSNorm weights a layer
    if m.shortcut_moe:
        # two (attention, dense MLP, two norms) pairs beside the experts
        attn, ffn, norms = 2 * attn, ffn + 2 * dense_ffn, 2 * norms
    k = m.first_k_dense_replace
    layers = (l - k) * (attn + ffn + norms) + k * (attn + dense_ffn + norms)
    if m.gdn:
        # a Gated DeltaNet mixer in the attention's place: [q | k | v | z]
        # and [b | a], the convolution, A_log and dt_bias, the output norm
        # and the output projection
        hv, dv = m.linear_num_value_heads, m.linear_value_head_dim
        mixer = (h * (m.gdn_channels + hv * dv) + h * 2 * hv
                 + m.gdn_channels * m.linear_conv_kernel_dim + 2 * hv + dv
                 + hv * dv * h)
        layers += m.layer_kinds.count(GDN) * (mixer - attn)
    if m.kda:
        # a Kimi Delta Attention mixer in the attention's place: [q | k | v],
        # the three convolutions, the decay's two projections with dt_bias
        # and A_log, beta's, the output gate's two, the output norm and the
        # output projection
        hv, dv = m.linear_num_value_heads, m.linear_value_head_dim
        c, key = m.gdn_channels, hv * m.linear_key_head_dim
        mixer = (h * c + c * m.linear_conv_kernel_dim
                 + h * dv + dv * key + key + hv
                 + h * hv + h * dv + dv * hv * dv + dv + hv * dv * h)
        layers += m.layer_kinds.count(KDA) * (mixer - attn)
    if m.ssm:
        # a Mamba mixer in the attention's place: [u | z], the convolution
        # and its bias, [r | B | C] and their three norms, the step's
        # projection and bias, A_log, D and the output projection
        di, n, r = m.ssm_inner, m.mamba_d_state, m.mamba_dt_rank
        mixer = (h * 2 * di + di * m.mamba_d_conv
                 + (di if m.mamba_conv_bias else 0) + di * (r + 2 * n)
                 + (r + 2 * n) + r * di + di + di * n + di + di * h)
        layers += m.layer_kinds.count(SSM) * (mixer - attn)
    head = (h * v * m.num_pred_heads
            if (not m.tie_word_embeddings or include_tied_head) else 0)
    return v * h + layers + h + head  # embed + layers + final_norm (+ head)
