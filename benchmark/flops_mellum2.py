"""Bytes the serving of a decoder with sparse experts and a paged cache with
sliding-window layers has to move, computed from shapes: what the new serve
metrics hold the decode program's device time against.

A decode step is bound by memory, not by operations (32 rows against 0.79 B
multiplying parameters: 50 GFLOP a step, a quarter of a millisecond at the
chip's peak, against 7 GB of weights to read), so the rooflines here are
bytes over the chip's memory bandwidth (`peaks.json`).

- The expert matmuls of one layer and step read the three banks (gate, up,
  down) of every expert that at least one live row was routed to, once; an
  expert no row chose is not read. The rows themselves (32 x 8 x 2304 values
  in, as many out) are under a thousandth of that and are counted too.
- The decode kernel reads, for a slot and layer, the K and V blocks that hold
  the positions it attends: every block below the length on a full layer, the
  blocks from position `length - window` on for a sliding layer.

`m` is a configuration file's `model` block.
"""

from __future__ import annotations

ITEM = 2  # bytes of a bfloat16 value


def expert_bytes(m: dict) -> int:
    """One expert's three matrices."""
    f = m.get("moe_intermediate_size") or m["intermediate_size"]
    return 3 * m["hidden_size"] * f * ITEM


def decode_experts_bytes(m: dict, touched: float, row_steps: float) -> float:
    """What the expert matmuls of decode steps read and write: `touched`
    (experts with at least one live row, summed over layers and steps) banks,
    and for `row_steps` (live rows summed over steps) the rows of the three
    grouped matmuls in every layer, k assignments a row: gate and up each read
    a hidden row and write a width, down reads a width and writes a hidden
    row."""
    f = m.get("moe_intermediate_size") or m["intermediate_size"]
    k, h = m["num_experts_per_token"], m["hidden_size"]
    rows = row_steps * m["num_hidden_layers"] * k * (3 * h + 3 * f) * ITEM
    return touched * expert_bytes(m) + rows


def kv_block_bytes(m: dict, block_size: int) -> int:
    """K and V of one block of one layer, every KV head."""
    d = m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]
    return 2 * m["num_key_value_heads"] * block_size * d * ITEM


def least_seconds(nbytes: float, peak: dict) -> float:
    return nbytes / peak["hbm_bytes_per_s"]


def weights_bytes_a_step(m: dict) -> int:
    """Every weight a decode step that touches every expert reads once:
    attention projections, routers, all experts, the head (for orientation;
    no metric divides by it)."""
    h = m["hidden_size"]
    d = m.get("head_dim") or h // m["num_attention_heads"]
    attn = 2 * h * m["num_attention_heads"] * d + 2 * h * m["num_key_value_heads"] * d
    layer = attn * ITEM + h * m["num_experts"] * ITEM + m["num_experts"] * expert_bytes(m)
    return m["num_hidden_layers"] * layer + h * m["vocab_size"] * ITEM
