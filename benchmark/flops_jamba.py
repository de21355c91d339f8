"""Operations, bytes and parameters of serving AI21-Jamba2-3B whole
(`configs/jamba2-3b.json`), computed from shapes: what the configuration's
size is reckoned by, and what a decode step and a prefill chunk have to do and
to move.

- 26 of the 28 layers are Mamba-1 mixers, whose state is not a row a position:
  a slot holds, a mixer, one float32 number a (channel, state) pair (5,120 x
  16 x 4 B = 320 KiB) and the convolution's last 3 inputs in float32 (3 x
  5,120 x 4 B = 60 KiB), whatever the length of its sequence: 389,120 B a
  (slot, mixer), 10,117,120 B a slot.
- The recurrence's yardstick, WHATEVER implements it. A decode step must read
  and write the state and the tail of every live (slot, mixer) pair once. A
  prefill dispatch must read and write them of every (row, mixer) pair once a
  chunk, must take each real token's convolved input u in and hand its y out
  (2 x 5,120 float32 a token and mixer: they cross the memory bus between the
  matrix products on either side of the recurrence in any implementation),
  and spends `scan_ops_per_token` operations a token and mixer: a (channel,
  state) pair costs one exp, three products (dt A, the decay times the state,
  (dt u) B), one add, and the contraction with C a product and an add (7 in
  all). The recurrence has no form in matrix products, so the operations are
  the vector unit's; held against the chip's published peak they never bound
  it, and the bytes do. `readers/ssm_roofline.py` divides the least time these
  need (`peaks.json`) by the device time under the scope `ssm_step` or
  `ssm_scan`.
- The two attention layers keep K and V a position: 2 layers x 2 x 1 head x
  128 x 2 B = 1,024 B a position.

`m` is the configuration file's `model` block (the program's names).
"""

from __future__ import annotations

ITEM = 2   # bytes of a bfloat16 value
F32 = 4    # bytes of a float32 value
MAMBA = "mamba"
# the recurrence a (channel, state) pair and token, each term by name
SCAN_TERMS = dict(exp=1, dt_times_A=1, decay_times_state=1, dtu_times_B=1, add=1,
                  contraction_with_C=2)


def mixers(m: dict) -> int:
    return list(m["layer_types"]).count(MAMBA)


def attention_layers(m: dict) -> int:
    return m["num_hidden_layers"] - mixers(m)


def d_inner(m: dict) -> int:
    return m["mamba_expand"] * m["hidden_size"]


def mixer_params(m: dict) -> int:
    h, di, n, r = m["hidden_size"], d_inner(m), m["mamba_d_state"], m["mamba_dt_rank"]
    return (h * 2 * di + di * m["mamba_d_conv"] + (di if m["mamba_conv_bias"] else 0)
            + di * (r + 2 * n) + (r + 2 * n) + r * di + di + di * n + di + di * h)


def attention_params(m: dict) -> int:
    h, d = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def beside_params(m: dict) -> int:
    """What every layer holds beside its mixer: the gated MLP, two norms."""
    return 3 * m["hidden_size"] * m["intermediate_size"] + 2 * m["hidden_size"]


def total_params(m: dict) -> int:
    """The tied embedding counted once."""
    return (mixers(m) * mixer_params(m) + attention_layers(m) * attention_params(m)
            + m["num_hidden_layers"] * beside_params(m)
            + m["vocab_size"] * m["hidden_size"] + m["hidden_size"])


def state_bytes(m: dict) -> int:
    """One slot's recurrent state of one mixer."""
    return d_inner(m) * m["mamba_d_state"] * F32


def tail_bytes(m: dict) -> int:
    """One slot's convolution tail of one mixer."""
    return (m["mamba_d_conv"] - 1) * d_inner(m) * F32


def state_row_bytes(m: dict) -> int:
    """One slot's state and convolution tail of one mixer."""
    return state_bytes(m) + tail_bytes(m)


def slot_state_bytes(m: dict) -> int:
    """... of every mixer: what a slot costs whatever its length."""
    return mixers(m) * state_row_bytes(m)


def position_kv_bytes(m: dict) -> int:
    """K and V of one cached position over the attention layers."""
    return attention_layers(m) * 2 * m["num_key_value_heads"] * m["head_dim"] * ITEM


def scan_ops_per_token(m: dict) -> int:
    """Operations of the recurrence a token of ONE mixer (`SCAN_TERMS`)."""
    return sum(SCAN_TERMS.values()) * d_inner(m) * m["mamba_d_state"]


def token_stream_bytes(m: dict) -> int:
    """What one token brings to and takes from the recurrence of ONE mixer:
    u in, y out, float32."""
    return 2 * d_inner(m) * F32


def decode_step_least_seconds(m: dict, state_rows: float, peak: dict) -> float:
    """Least time of the state updates of decode steps: `state_rows` (slot,
    mixer) pairs summed over the steps, each row's state and tail read and
    written once; the operations never bound it."""
    secs_bytes = 2 * state_rows * state_row_bytes(m) / peak["hbm_bytes_per_s"]
    secs_ops = state_rows * scan_ops_per_token(m) / peak["bf16_flops_per_s"]
    return max(secs_bytes, secs_ops)


def prefill_scan_least_seconds(m: dict, state_rows: float, scan_tokens: float,
                               peak: dict) -> float:
    """Least time of the recurrence of prefill dispatches: `state_rows` (row,
    mixer) pairs read and written once a chunk, `scan_tokens` (real token,
    mixer) pairs through the scan; the larger of the bytes' and the
    operations' time."""
    secs_bytes = ((2 * state_rows * state_row_bytes(m) + scan_tokens * token_stream_bytes(m))
                  / peak["hbm_bytes_per_s"])
    secs_ops = scan_tokens * scan_ops_per_token(m) / peak["bf16_flops_per_s"]
    return max(secs_bytes, secs_ops)


def weights_bytes_a_step(m: dict) -> dict:
    """What a decode step reads of the weights, by part (the tied embedding
    once, as the head)."""
    return dict(
        mixers=mixers(m) * mixer_params(m) * ITEM,
        attention=attention_layers(m) * attention_params(m) * ITEM,
        mlps=m["num_hidden_layers"] * beside_params(m) * ITEM,
        head=m["vocab_size"] * m["hidden_size"] * ITEM)
