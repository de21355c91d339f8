"""Operations, bytes and parameters of serving NVIDIA-Nemotron-3-Super-120B-A12B
as one chip of an 8-chip expert group (`configs/nemotron3-super-120b-a12b-22l-ep8.json`),
computed from shapes: what the configuration's size is reckoned by, and what a
decode step and a prefill chunk have to do and to move.

- A layer is ONE sublayer and one norm: a Mamba-2 mixer, an attention or the
  experts. A mixer's state is not a row a position: a slot holds, a mixer, one
  float32 matrix [P, N] a head (128 x 64 x 128 x 4 B = 4 MiB) and the
  convolution's last 3 inputs over [x | B | C] in float32 (3 x 10,240 x 4 B =
  120 KiB), whatever the length of its sequence: 4,317,184 B a (slot, mixer).
- The recurrence's yardstick, WHATEVER implements it. A decode step must read
  and write the state and the tail of every live (slot, mixer) pair once. A
  prefill dispatch must read and write them of every (row, mixer) pair once a
  chunk, must take each real token's x, B and C in and hand its y out (float32:
  they cross the memory bus between the matrix products on either side of the
  recurrence in any implementation), and spends `rule_ops_per_token` operations
  a token and mixer: an element of a head's state costs the decay's product,
  the outer product's, an add, and the contraction with C a product and an add
  (5 in all). In matrix products (the chunked form) the count is of the same
  size (6.6 M against 5.2 M a token and mixer at sub-chunks of 128), so the
  rule's own is the yardstick; a float32 product at the highest precision is
  six passes of the matrix unit, so a form in matrix products reads a sixth of
  the peak at best. `readers/ssd_roofline.py` divides the least time these need
  (`peaks.json`) by the device time under the scope `ssd_step` or `ssd_chunk`.
- The experts live on a latent: a routed expert is TWO matrices of
  moe_latent_size x moe_intermediate_size (not three of hidden_size x width:
  `flops_mellum2.decode_experts_bytes` would price a touched bank at six times
  its bytes), between two projections hidden <-> latent that every token
  passes; the shared expert is two matrices on the full width.
- The two attention layers keep K and V a position: 2 layers x 2 x 2 heads x
  128 x 2 B = 2,048 B a position.

`m` is the configuration file's `model` block (the program's names).
"""

from __future__ import annotations

ITEM = 2   # bytes of a bfloat16 value
F32 = 4    # bytes of a float32 value
MAMBA2, EXPERTS, ATTENTION = "mamba2", "experts", "full_attention"
# the recurrence an element of a head's state and token, each term by name
RULE_TERMS = dict(decay_times_state=1, dx_times_B=1, add=1, contraction_with_C=2)


def count(m: dict, kind: str) -> int:
    return list(m["layer_types"]).count(kind)


def d_inner(m: dict) -> int:
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def conv_channels(m: dict) -> int:
    return d_inner(m) + 2 * m["n_groups"] * m["ssm_state_size"]


def mixer_params(m: dict) -> int:
    """A Mamba-2 mixer without its layer's norm: W_in, the convolution and
    its bias, dt_bias, A_log and D, the grouped norm, W_out."""
    h, di, c, hm = m["hidden_size"], d_inner(m), conv_channels(m), m["mamba_num_heads"]
    return (h * (di + c + hm) + c * m["mamba_d_conv"] + (c if m["mamba_conv_bias"] else 0)
            + 3 * hm + di + di * h)


def attention_params(m: dict) -> int:
    h, d = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def expert_params(m: dict) -> int:
    """One routed expert: W1 [latent, F], W2 [F, latent]."""
    return 2 * m["moe_latent_size"] * m["moe_intermediate_size"]


def beside_experts_params(m: dict) -> int:
    """An expert layer outside its routed experts, without the layer's norm:
    the router and its bias, the latent's two projections, the shared expert."""
    h, r = m["hidden_size"], m["router_experts"] or m["num_experts"]
    return (h * r + r + 2 * h * m["moe_latent_size"]
            + 2 * h * m["moe_shared_expert_intermediate_size"])


def total_params(m: dict, held=None, active_only: bool = False) -> int:
    """The tree's count with `held` routed experts a layer (the model's own
    where None; `active_only`: the experts a token visits)."""
    e = m["num_experts_per_token"] if active_only else (
        m["num_experts"] if held is None else held)
    h = m["hidden_size"]
    return (count(m, MAMBA2) * mixer_params(m) + count(m, ATTENTION) * attention_params(m)
            + count(m, EXPERTS) * (beside_experts_params(m) + e * expert_params(m))
            + m["num_hidden_layers"] * h + 2 * m["vocab_size"] * h + h)


def state_bytes(m: dict) -> int:
    """One slot's recurrent state of one mixer."""
    return d_inner(m) * m["ssm_state_size"] * F32


def tail_bytes(m: dict) -> int:
    """One slot's convolution tail of one mixer."""
    return (m["mamba_d_conv"] - 1) * conv_channels(m) * F32


def state_row_bytes(m: dict) -> int:
    return state_bytes(m) + tail_bytes(m)


def slot_state_bytes(m: dict) -> int:
    """... of every mixer: what a slot costs whatever its length."""
    return count(m, MAMBA2) * state_row_bytes(m)


def position_kv_bytes(m: dict) -> int:
    """K and V of one cached position over the attention layers."""
    return count(m, ATTENTION) * 2 * m["num_key_value_heads"] * m["head_dim"] * ITEM


def rule_ops_per_token(m: dict) -> int:
    """Operations of the recurrence a token of ONE mixer (`RULE_TERMS`)."""
    return sum(RULE_TERMS.values()) * d_inner(m) * m["ssm_state_size"]


def token_stream_bytes(m: dict) -> int:
    """What one token brings to and takes from the recurrence of ONE mixer: x,
    B and C in, y out, float32."""
    return (conv_channels(m) + d_inner(m)) * F32


def decode_step_least_seconds(m: dict, state_rows: float, peak: dict) -> float:
    """Least time of the state updates of decode steps: `state_rows` (slot,
    mixer) pairs summed over the steps, each row's state and tail read and
    written once; the operations never bound it."""
    secs_bytes = 2 * state_rows * state_row_bytes(m) / peak["hbm_bytes_per_s"]
    secs_ops = state_rows * rule_ops_per_token(m) / peak["bf16_flops_per_s"]
    return max(secs_bytes, secs_ops)


def prefill_chunk_least_seconds(m: dict, state_rows: float, scan_tokens: float,
                                peak: dict) -> float:
    """Least time of the recurrence of prefill dispatches: `state_rows` (row,
    mixer) pairs read and written once a chunk, `scan_tokens` (real token,
    mixer) pairs through the rule; the larger of the bytes' and the
    operations' time."""
    secs_bytes = ((2 * state_rows * state_row_bytes(m) + scan_tokens * token_stream_bytes(m))
                  / peak["hbm_bytes_per_s"])
    secs_ops = scan_tokens * rule_ops_per_token(m) / peak["bf16_flops_per_s"]
    return max(secs_bytes, secs_ops)


def decode_experts_bytes(m: dict, touched: float, row_steps: float) -> float:
    """What decode steps must move for their routed experts: the TWO banks of
    every (expert, layer, step) a live row was routed to (`touched`, summed),
    and a latent row in and out for the picks that land here (`row_steps`
    live rows x top-k x the held share)."""
    here = m["num_experts_per_token"] * m["num_experts"] / (
        m["router_experts"] or m["num_experts"])
    return (touched * expert_params(m) * ITEM
            + row_steps * count(m, EXPERTS) * here * 2 * m["moe_latent_size"] * ITEM)


def least_seconds(n_bytes: float, peak: dict) -> float:
    return n_bytes / peak["hbm_bytes_per_s"]


def weights_bytes_a_step(m: dict) -> dict:
    """What a decode step reads of the weights whatever the router chose, by
    part (the routed banks are `decode_experts_bytes`')."""
    return dict(
        mixers=count(m, MAMBA2) * mixer_params(m) * ITEM,
        attention=count(m, ATTENTION) * attention_params(m) * ITEM,
        beside_experts=count(m, EXPERTS) * beside_experts_params(m) * ITEM,
        head=m["vocab_size"] * m["hidden_size"] * ITEM)
