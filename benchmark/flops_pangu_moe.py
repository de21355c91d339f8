"""Operations and bytes the serving of a decoder with latent attention (MLA)
and a held share of sparse experts has to do and to move, computed from
shapes: what the new serve metrics hold the decode program's device time
against.

- The latent decode step, absorbed (`picotron_tpu/ops/mla.py`): one query
  position against one cached position reads that position's `[c | k_r]` row
  ONCE for all heads, for key and value (`kv_lora_rank + qk_rope_head_dim`
  numbers: 576 x 2 bytes), and costs every head a dot product of the row with
  the absorbed query (2 x 576 operations) and a weighted sum of its latent
  (2 x 512). At 128 heads that is 278,528 operations for 1,152 bytes, 242
  operations a byte, where the v5e's ridge (`peaks.json`: 197e12 / 819e9) is
  240: the kernel sits on the ridge, so its roofline is the larger of the two
  least times. The pool's rows are stored 640 wide (whole 128-lane rows);
  the 64 numbers of padding are not state and are not counted, so the share
  of the roofline is under-, not overstated.
- The held banks: a decode step reads the three matrices of every held expert
  that at least one live row was routed to (`flops_mellum2.expert_bytes`, the
  same shapes), and the shared expert's three every step.

`m` is a configuration file's `model` block.
"""

from __future__ import annotations

ITEM = 2  # bytes of a bfloat16 value


def latent_row_values(m: dict) -> int:
    """Numbers of state a cached position of one layer: [c | k_r]."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def latent_block_bytes(m: dict, block_size: int) -> int:
    """The state of one block of one layer of the latent pool: no head axis."""
    return block_size * latent_row_values(m) * ITEM


def latent_decode_ops(m: dict, positions: float) -> float:
    """Operations of absorbed decode attention over `positions` (query, cached
    position) pairs: every head scores the row (2 x (rank + rope)) and sums its
    latent (2 x rank)."""
    return positions * m["num_attention_heads"] * 2 * (latent_row_values(m) + m["kv_lora_rank"])


def latent_decode_least_seconds(m: dict, blocks: float, block_size: int, peak: dict) -> float:
    """The least time the chip could take over `blocks` attended blocks: the
    larger of reading them and of the arithmetic on them."""
    return max(blocks * latent_block_bytes(m, block_size) / peak["hbm_bytes_per_s"],
               latent_decode_ops(m, blocks * block_size) / peak["bf16_flops_per_s"])


def per_head_cache_bytes(m: dict) -> int:
    """What a cached position of one layer would be as K and V per head."""
    return m["num_attention_heads"] * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                                       + m["v_head_dim"]) * ITEM


def expert_bytes(m: dict) -> int:
    """One expert's three matrices (a routed expert, and the shared one)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * ITEM


def held_banks_bytes(m: dict) -> int:
    """The banks of the experts held here, one layer."""
    return m["num_experts"] * expert_bytes(m)


def mla_weight_bytes(m: dict) -> int:
    """The five matrices of one layer's latent attention."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return ITEM * (h * m["q_lora_rank"] + m["q_lora_rank"] * heads * (dn + dr)
                   + h * (m["kv_lora_rank"] + dr) + m["kv_lora_rank"] * heads * (dn + dv)
                   + heads * dv * h)


def weights_bytes_a_step(m: dict, touched_a_layer: float) -> float:
    """Every weight a decode step reads once, `touched_a_layer` held experts
    touched in each expert layer: MLA's projections in every layer, the dense
    MLPs, the routers, the shared and the touched experts, the head slice (for
    orientation; no metric divides by it)."""
    h, k = m["hidden_size"], m["first_k_dense_replace"]
    n_exp = m["num_hidden_layers"] - k
    return (m["num_hidden_layers"] * mla_weight_bytes(m)
            + k * 3 * h * m["intermediate_size"] * ITEM
            + n_exp * (h * m["router_experts"] * ITEM
                       + (m["n_shared_experts"] + touched_a_layer) * expert_bytes(m))
            + h * m["vocab_size"] * ITEM)
