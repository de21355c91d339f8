"""Operations, bytes and parameters of serving one chip's share of
Kimi-Linear-48B-A3B-Instruct (`configs/kimi-linear-48b-a3b-12l-ep8.json`),
computed from shapes: what the configuration's cut is reckoned by, and what a
decode step and a prefill chunk have to do and to move.

- Nine of the twelve layers are Kimi Delta Attention mixers, whose state is
  not a row a position: a slot holds, a mixer, one float32 matrix of d_k x d_v
  a head (32 x 128 x 128 x 4 B = 2 MiB) and the three convolutions' last 3
  inputs in float32 (3 x 12,288 x 4 B = 144 KiB), whatever the length of its
  sequence.
- The recurrence's yardstick, WHATEVER implements it: a decode step must read
  and write the state of every live (slot, mixer) pair once; a prefill
  dispatch must read and write the state of every (row, mixer) pair once a
  chunk and spend 6 d_k d_v operations a token and head (the rule written
  token by token: the decay S' = Diag(e^g) S, r = S'^T k, S = S' + k u^T and
  o = S^T q are each one or two operations a state element; that the decay
  is a channel's and not a head's changes no count). WHAT IS NOT COUNTED:
  the exponentials (d_k a token and head in the rule; 16 x d_k a token and
  head in the chunked form's diagonal blocks), the chunked form's own
  products inside a sub-chunk (about as many operations again, in float32
  at six bfloat16 passes each), the convolutions and the two low-rank
  projections (under `kda_conv` / `kda_gate`, outside the recurrence's
  scopes). `readers/kda_roofline.py` divides the least time these need
  (`peaks.json`) by the device time under the scope `kda_state` (decode) or
  `kda_chunk` (prefill).
- The three full layers keep one latent row a position: 512 + 64 numbers, 2 B
  each, 1,152 B a layer and 3,456 B a position (stored 640 wide:
  `flops_pangu_moe.latent_block_bytes` with this cell's `model` block gives
  what the decode kernel reads).
- The held experts' grouped matmuls: a decode step reads the three matrices of
  every held expert that a live row chose (`flops_mellum2.decode_experts_bytes`
  with this cell's `model` block: 14.2 MB an expert); of a token's 8 picks
  over 256 columns 1 lands on the 32 held.

`m` is the configuration file's `model` block (the program's names).
"""

from __future__ import annotations

ITEM = 2   # bytes of a bfloat16 value
F32 = 4    # bytes of a float32 value
KDA = "kda"


def mixers(m: dict) -> int:
    return list(m["layer_types"]).count(KDA)


def full_layers(m: dict) -> int:
    return m["num_hidden_layers"] - mixers(m)


def conv_channels(m: dict) -> int:
    """[q | k | v]: what the three convolutions run over."""
    return (2 * m["linear_num_key_heads"] * m["linear_key_head_dim"]
            + m["linear_num_value_heads"] * m["linear_value_head_dim"])


def mixer_params(m: dict) -> int:
    """[q | k | v], the convolutions, the decay's low-rank projection with
    dt_bias and A_log, beta, the output gate's low-rank projection, the output
    norm and the output projection."""
    h, heads = m["hidden_size"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    c = conv_channels(m)
    return (h * c + c * m["linear_conv_kernel_dim"]
            + h * dv + dv * heads * dk + heads * dk + heads
            + h * heads + h * dv + dv * heads * dv + dv + heads * dv * h)


def attention_params(m: dict) -> int:
    """A latent attention without a query bottleneck."""
    h, heads, rank = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (h * heads * (dn + dr) + h * (rank + dr) + rank
            + rank * heads * (dn + dv) + heads * dv * h)


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def beside_params(m: dict) -> int:
    """What an expert layer holds beside its mixer and its routed experts: the
    router over every expert of the model and its selection bias, the shared
    expert, two norms."""
    h = m["hidden_size"]
    return (h * m["router_experts"] + m["router_experts"]
            + m["n_shared_experts"] * expert_params(m) + 2 * h)


def total_params(m: dict) -> int:
    h, dense = m["hidden_size"], m["first_k_dense_replace"]
    expert_layers = m["num_hidden_layers"] - dense
    return (mixers(m) * mixer_params(m) + full_layers(m) * attention_params(m)
            + dense * (dense_mlp_params(m) + 2 * h)
            + expert_layers * (beside_params(m) + m["num_experts"] * expert_params(m))
            + 2 * m["vocab_size"] * h + h)


def state_row_bytes(m: dict) -> int:
    """One slot's state and convolution tail of one mixer."""
    state = (m["linear_num_value_heads"] * m["linear_key_head_dim"]
             * m["linear_value_head_dim"] * F32)
    return state + (m["linear_conv_kernel_dim"] - 1) * conv_channels(m) * F32


def slot_state_bytes(m: dict) -> int:
    """... of every mixer: what a slot costs whatever its length."""
    return mixers(m) * state_row_bytes(m)


def position_latent_bytes(m: dict) -> int:
    """[c | k_r] of one cached position over the full layers."""
    return full_layers(m) * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * ITEM


def recurrence_ops(m: dict, tokens: float) -> float:
    """Operations of the delta rule over `tokens` tokens of ONE mixer."""
    return (tokens * m["linear_num_value_heads"] * 6 * m["linear_key_head_dim"]
            * m["linear_value_head_dim"])


def decode_state_least_seconds(m: dict, state_rows: float, peak: dict) -> float:
    """Least time of the state updates of decode steps: `state_rows` (slot,
    mixer) pairs summed over the steps, each row read and written once; the
    operations (6 d_k d_v a head and row) never bound it."""
    secs_bytes = 2 * state_rows * state_row_bytes(m) / peak["hbm_bytes_per_s"]
    secs_ops = recurrence_ops(m, state_rows) / peak["bf16_flops_per_s"]
    return max(secs_bytes, secs_ops)


def prefill_state_least_seconds(m: dict, state_rows: float, tokens: float, peak: dict) -> float:
    """Least time of the recurrence of prefill dispatches: `state_rows` (row,
    mixer) pairs read and written once a chunk, `tokens` real tokens through
    every mixer; the larger of the bytes' and the operations' time."""
    secs_bytes = 2 * state_rows * state_row_bytes(m) / peak["hbm_bytes_per_s"]
    secs_ops = mixers(m) * recurrence_ops(m, tokens) / peak["bf16_flops_per_s"]
    return max(secs_bytes, secs_ops)


def picks_expected(m: dict) -> dict:
    """Of a token's picks, under a router that is uniform over its columns:
    those that land on the experts held here, and elsewhere."""
    here = m["num_experts_per_token"] * m["num_experts"] / m["router_experts"]
    return dict(here=here, away=m["num_experts_per_token"] - here)


def banks_touched_expected(m: dict, live_rows: float) -> float:
    """Held experts at least one of `live_rows` rows chose, a layer, under a
    uniform router: E (1 - (1 - k/R)^rows)."""
    miss = 1.0 - m["num_experts_per_token"] / m["router_experts"]
    return m["num_experts"] * (1.0 - miss ** live_rows)


def weights_bytes_a_step(m: dict, banks_touched: float) -> dict:
    """What a decode step reads of the weights, by part, every layer."""
    dense = m["first_k_dense_replace"]
    expert_layers = m["num_hidden_layers"] - dense
    return dict(
        mixers=mixers(m) * mixer_params(m) * ITEM,
        attention=full_layers(m) * attention_params(m) * ITEM,
        dense=dense * (dense_mlp_params(m) + 2 * m["hidden_size"]) * ITEM,
        beside=expert_layers * beside_params(m) * ITEM,
        banks=expert_layers * banks_touched * expert_params(m) * ITEM,
        head=m["vocab_size"] * m["hidden_size"] * ITEM)
