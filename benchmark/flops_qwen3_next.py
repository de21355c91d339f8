"""Operations, bytes and parameters of serving one chip's share of
Qwen3-Next-80B-A3B-Instruct (`configs/qwen3-next-80b-a3b-12l-ep8.json`),
computed from shapes: what the configuration's cut is reckoned by, and what a
decode step and a prefill chunk have to do and to move.

- Nine of the twelve layers are Gated DeltaNet mixers, whose state is not a
  row a position: a slot holds, a mixer, one float32 matrix of d_k x d_v a
  value head (32 x 128 x 128 x 4 B = 2 MiB) and the convolution's last 3
  inputs in float32 (3 x 8,192 x 4 B = 96 KiB), whatever the length of its
  sequence.
- The recurrence's yardstick, WHATEVER implements it: a decode step must read
  and write the state of every live (slot, mixer) pair once; a prefill
  dispatch must read and write the state of every (row, mixer) pair once a
  chunk and spend 6 d_k d_v operations a token and value head (the rule
  written token by token: the decay S' = e^g S, r = S'^T k, S = S' + k u^T
  and o = S^T q are each one or two operations a state element). The chunked
  form the program runs spends about as many again inside its sub-chunks; a
  kernel that spends fewer is judged by the same count.
  `readers/gdn_roofline.py` divides the least time these need (`peaks.json`)
  by the device time under the scope `gdn_state`.
- The three full layers keep K and V a position: 2 x 2 heads x 256 x 2 B =
  2 KiB a layer, 6 KiB a position (`flops_mellum2.kv_block_bytes` with this
  cell's `model` block gives a block's 32 KiB a layer).
- The held experts' grouped matmuls: a decode step reads the three matrices of
  every held expert that a live row chose (`flops_mellum2.decode_experts_bytes`
  with this cell's `model` block: 6.3 MB an expert, the narrowest in the
  benchmark); of a token's 10 picks over 512 columns 1.25 land on the 64 held.

`m` is the configuration file's `model` block (the program's names).
"""

from __future__ import annotations

ITEM = 2   # bytes of a bfloat16 value
F32 = 4    # bytes of a float32 value
GDN = "linear_attention"


def mixers(m: dict) -> int:
    return list(m["layer_types"]).count(GDN)


def full_layers(m: dict) -> int:
    return m["num_hidden_layers"] - mixers(m)


def conv_channels(m: dict) -> int:
    """[q | k | v]: what the convolution runs over."""
    return (2 * m["linear_num_key_heads"] * m["linear_key_head_dim"]
            + m["linear_num_value_heads"] * m["linear_value_head_dim"])


def mixer_params(m: dict) -> int:
    h, hv, dv = m["hidden_size"], m["linear_num_value_heads"], m["linear_value_head_dim"]
    c = conv_channels(m)
    return (h * (c + hv * dv) + h * 2 * hv + c * m["linear_conv_kernel_dim"] + 2 * hv + dv
            + hv * dv * h)


def attention_params(m: dict) -> int:
    """A gated attention: q's projection holds query and gate."""
    h, d = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    return h * 2 * q + 2 * h * kv + q * h + 2 * d


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def beside_params(m: dict) -> int:
    """What every layer holds beside its mixer and its routed experts: the
    router over every expert of the model, the shared expert and its gate,
    two norms."""
    h = m["hidden_size"]
    return (h * m["router_experts"] + m["n_shared_experts"] * expert_params(m) + h + 2 * h)


def total_params(m: dict) -> int:
    per_layer = beside_params(m) + m["num_experts"] * expert_params(m)
    return (mixers(m) * mixer_params(m) + full_layers(m) * attention_params(m)
            + m["num_hidden_layers"] * per_layer
            + 2 * m["vocab_size"] * m["hidden_size"] + m["hidden_size"])


def state_row_bytes(m: dict) -> int:
    """One slot's state and convolution tail of one mixer."""
    state = (m["linear_num_value_heads"] * m["linear_key_head_dim"]
             * m["linear_value_head_dim"] * F32)
    return state + (m["linear_conv_kernel_dim"] - 1) * conv_channels(m) * F32


def slot_state_bytes(m: dict) -> int:
    """... of every mixer: what a slot costs whatever its length."""
    return mixers(m) * state_row_bytes(m)


def position_kv_bytes(m: dict) -> int:
    """K and V of one cached position over the full layers."""
    return full_layers(m) * 2 * m["num_key_value_heads"] * m["head_dim"] * ITEM


def recurrence_ops(m: dict, tokens: float) -> float:
    """Operations of the gated delta rule over `tokens` tokens of ONE mixer."""
    return (tokens * m["linear_num_value_heads"] * 6 * m["linear_key_head_dim"]
            * m["linear_value_head_dim"])


def decode_state_least_seconds(m: dict, state_rows: float, peak: dict) -> float:
    """Least time of the state updates of decode steps: `state_rows` (slot,
    mixer) pairs summed over the steps, each row read and written once; the
    operations (6 d_k d_v a value head and row) never bound it."""
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
    head = m["vocab_size"] * m["hidden_size"] * ITEM
    return dict(
        mixers=mixers(m) * mixer_params(m) * ITEM,
        attention=full_layers(m) * attention_params(m) * ITEM,
        beside=m["num_hidden_layers"] * beside_params(m) * ITEM,
        banks=m["num_hidden_layers"] * banks_touched * expert_params(m) * ITEM,
        head=head)
