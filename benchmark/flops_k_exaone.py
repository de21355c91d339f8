"""Bytes and parameters of serving one chip's share of K-EXAONE-236B-A23B
(`configs/k-exaone-236b-a23b-5l-ep8.json`), computed from shapes: what the
configuration's cut is reckoned by, and what a decode step has to read.

A decode step is bound by memory (48 rows against 3.7 B multiplying
parameters), so what matters is bytes over the chip's bandwidth
(`peaks.json`). The cell adds no kernel: the decode kernel's and the grouped
experts' rooflines are `flops_mellum2`'s (`kv_block_bytes`,
`decode_experts_bytes`), which read this model's heads, widths and block from
its own `model` and `serve` blocks. Here:

- the parameters of the cut, a matrix at a time, as ISSUE 39 reckons them
  (`tests/test_flops_k_exaone.py` holds them against the built tree);
- what a decode step reads: every layer's attention projections, the dense
  layer's MLP, each expert layer's router, shared expert and the banks of the
  held experts its live rows chose, the head slice; and of the cache one full
  layer's blocks and, in the four sliding layers, the band's 9 at most;
- the state a cached position costs: 4,096 B in the full layer, nothing that
  grows in a sliding one (a ring of `ring_blocks` blocks a slot and layer).

`m` is a configuration file's `model` block.
"""

from __future__ import annotations

ITEM = 2  # bytes of a bfloat16 value
FULL, SLIDING = "full_attention", "sliding_attention"


def attention_params(m: dict) -> int:
    """q, k, v, o of one layer."""
    h, d = m["hidden_size"], m["head_dim"]
    return 2 * h * m["num_attention_heads"] * d + 2 * h * m["num_key_value_heads"] * d


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_params(m: dict) -> int:
    """One expert's three matrices (a routed expert, and the shared one)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_layer_params(m: dict) -> int:
    """Attention, the router over every expert of the model, the shared
    expert(s) and the banks held here."""
    return (attention_params(m) + m["hidden_size"] * m["router_experts"]
            + (m["n_shared_experts"] + m["num_experts"]) * expert_params(m))


def matrix_params(m: dict) -> int:
    """Every matrix of the cut: the leading dense layers, the expert layers,
    the embedding and the head slices (norm weights are `norm_params`)."""
    k = m["first_k_dense_replace"]
    return (k * (attention_params(m) + dense_mlp_params(m))
            + (m["num_hidden_layers"] - k) * expert_layer_params(m)
            + 2 * m["vocab_size"] * m["hidden_size"])


def norm_params(m: dict) -> int:
    """Two RMSNorms and the two per-head QK-norm vectors a layer, the final norm."""
    return (m["num_hidden_layers"] * (2 * m["hidden_size"] + 2 * m["head_dim"])
            + m["hidden_size"])


def kv_position_bytes(m: dict) -> int:
    """K and V of one cached position of one layer, every KV head."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * ITEM


def cache_bytes(m: dict, sv: dict, ring_blocks: int) -> dict:
    """The two pools at the serve settings: the full layers keep every
    position of every slot, a sliding layer a ring a slot."""
    kinds = m["layer_types"]
    blocks = sv["decode_slots"] * -(-sv["max_model_len"] // sv["block_size"])
    block = sv["block_size"] * kv_position_bytes(m)
    return dict(full=kinds.count(FULL) * blocks * block,
                window=kinds.count(SLIDING) * sv["decode_slots"] * ring_blocks * block)


def band_blocks(m: dict, length: int, block_size: int) -> int:
    """Blocks a sliding layer's decode step reads for a slot of `length`
    cached positions: from the block of position length - window on."""
    first = max(length - m["sliding_window"], 0) // block_size
    return -(-length // block_size) - first


def weights_bytes_a_step(m: dict, touched_a_layer: float) -> dict:
    """Every weight a decode step reads once, `touched_a_layer` held experts
    touched in each expert layer, by part (for orientation; no metric divides
    by it)."""
    k = m["first_k_dense_replace"]
    n_exp = m["num_hidden_layers"] - k
    return dict(
        attention=m["num_hidden_layers"] * attention_params(m) * ITEM,
        dense_mlp=k * dense_mlp_params(m) * ITEM,
        routers=n_exp * m["hidden_size"] * m["router_experts"] * ITEM,
        shared=n_exp * m["n_shared_experts"] * expert_params(m) * ITEM,
        banks=n_exp * touched_a_layer * expert_params(m) * ITEM,
        head=m["hidden_size"] * m["vocab_size"] * ITEM)


def banks_touched_expected(m: dict, rows: float) -> float:
    """Held banks a layer that at least one of `rows` live rows picks, were
    the router uniform: held x (1 - (1 - 1/R)^(k rows))."""
    r = m["router_experts"]
    return m["num_experts"] * (1.0 - (1.0 - 1.0 / r) ** (m["num_experts_per_token"] * rows))
