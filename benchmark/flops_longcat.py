"""Operations, bytes and parameters of serving one chip's share of
LongCat-Flash-Omni's language model (`configs/longcat-flash-omni-4l-ep32.json`),
computed from shapes: what the configuration's cut is reckoned by, and what a
decode step and a prefill chunk have to do and to move.

- A layer is TWO (latent attention, dense FFN) pairs and one expert branch, so
  the model has 2 x num_hidden_layers attention sublayers, each with its own row
  of the latent pool: a cached position costs `sublayers x (kv_lora_rank +
  qk_rope_head_dim)` numbers (8 x 576 x 2 B = 9,216 B of state, stored 8 x 640 x
  2 = 10,240 B).
- The latent decode step, absorbed, at 64 heads: one query against one cached
  position reads the position's row once for all heads (1,152 B) and costs 64 x
  2 x (576 + 512) = 139,264 operations, 121 operations a byte where the v5e's
  ridge is 240: at this model's heads the kernel is bound by MEMORY (openPangu's
  128 heads sit on the ridge). `flops_pangu_moe.latent_decode_least_seconds`,
  which `readers/latent_attention_roofline.py` calls with this cell's own
  `model` block, takes the larger of the two least times and so gives the bytes'.
- The latent prefill attention, expanded: a key position costs its expansion
  through Wkvb once a sublayer and chunk (2 x 512 x 64 x 256) and every query of
  the chunk a score and a value product with it (2 x 64 x (192 + 128)).
- The held experts' grouped matmuls: a decode step reads the three matrices of
  every held expert that a live row chose (`flops_mellum2.decode_experts_bytes`
  with this cell's `model` block: 75.5 MB an expert). Zero-compute experts read
  nothing and multiply nothing: a pick costs one scaled add of the token.
- The dense path: both FFNs of every layer (0.9 GB a layer) and both attentions'
  projections (0.36 GB a layer) are read whole by every step.

`m` is the configuration file's `model` block (the program's names).
"""

from __future__ import annotations

ITEM = 2  # bytes of a bfloat16 value


def sublayers(m: dict) -> int:
    """Attention sublayers, each a row of the latent pool: two a layer."""
    return 2 * m["num_hidden_layers"]


def latent_row_values(m: dict) -> int:
    """Numbers of state a cached position of one sublayer: [c | k_r]."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def position_state_bytes(m: dict) -> int:
    """The state one cached position costs over all sublayers."""
    return sublayers(m) * latent_row_values(m) * ITEM


def position_pool_bytes(m: dict) -> int:
    """... as the pool stores it: rows padded up to whole 128-lane rows."""
    return sublayers(m) * -(-latent_row_values(m) // 128) * 128 * ITEM


def latent_decode_ops(m: dict, positions: float) -> float:
    """Operations of absorbed decode attention over `positions` (query, cached
    position) pairs of ONE sublayer: every head scores the row and sums its
    latent."""
    return positions * m["num_attention_heads"] * 2 * (latent_row_values(m) + m["kv_lora_rank"])


def latent_decode_step(m: dict, lengths, peak: dict) -> dict:
    """A decode step over slots holding `lengths` cached positions, every
    sublayer: bytes, operations and the least seconds of each."""
    pos = float(sum(lengths)) * sublayers(m)
    nbytes, ops = pos * latent_row_values(m) * ITEM, latent_decode_ops(m, pos)
    return dict(bytes=nbytes, ops=ops, bytes_s=nbytes / peak["hbm_bytes_per_s"],
                ops_s=ops / peak["bf16_flops_per_s"])


def latent_prefill_ops(m: dict, chunk: int, keys: float) -> float:
    """Operations of expanded prefill attention of one row's chunk of `chunk`
    queries over `keys` key positions (cached and its own), every sublayer: the
    keys' expansion through Wkvb, then scores and values a (query, key) pair."""
    heads = m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    expand = keys * 2 * m["kv_lora_rank"] * heads * (dn + dv)
    attend = chunk * keys * 2 * heads * (dn + dr + dv)
    return sublayers(m) * (expand + attend)


def mla_params(m: dict) -> int:
    """One attention sublayer: five matrices and the two inner norms."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    ql, rank = m["q_lora_rank"], m["kv_lora_rank"]
    return (h * ql + ql * heads * (dn + dr) + h * (rank + dr) + rank * heads * (dn + dv)
            + heads * dv * h + ql + rank)


def dense_ffn_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def router_params(m: dict) -> int:
    """The router over the routed and the zero-compute experts, and its
    selection bias."""
    width = m["router_experts"] + m["zero_experts"]
    return m["hidden_size"] * width + width


def layer_params(m: dict) -> dict:
    """One layer by part: two attentions, two dense FFNs, the router, the held
    experts, four norms."""
    return dict(attention=2 * mla_params(m), dense_ffn=2 * dense_ffn_params(m),
                router=router_params(m), experts=m["num_experts"] * expert_params(m),
                norms=4 * m["hidden_size"])


def total_params(m: dict) -> int:
    """The cut's parameter tree: layers, embedding and head slices, final norm."""
    return (m["num_hidden_layers"] * sum(layer_params(m).values())
            + 2 * m["vocab_size"] * m["hidden_size"] + m["hidden_size"])


def picks_expected(m: dict) -> dict:
    """Of a token's picks under a uniform router: zero-compute, routed and
    held here, routed and held elsewhere."""
    k, r, z = m["num_experts_per_token"], m["router_experts"], m["zero_experts"]
    return dict(zero=k * z / (r + z), here=k * m["num_experts"] / (r + z),
                away=k * (r - m["num_experts"]) / (r + z))


def banks_touched_expected(m: dict, rows: float) -> float:
    """Held banks a layer that at least one of `rows` live rows picks, were
    the router uniform over its r + z columns."""
    width = m["router_experts"] + m["zero_experts"]
    return m["num_experts"] * (1.0 - (1.0 - 1.0 / width) ** (m["num_experts_per_token"] * rows))


def weights_bytes_a_step(m: dict, touched_a_layer: float) -> dict:
    """Every weight a decode step reads once, `touched_a_layer` held experts
    touched in each layer, by part (for orientation; no metric divides by it)."""
    n, p = m["num_hidden_layers"], layer_params(m)
    return dict(attention=n * p["attention"] * ITEM, dense_ffn=n * p["dense_ffn"] * ITEM,
                routers=n * p["router"] * ITEM,
                banks=n * touched_a_layer * expert_params(m) * ITEM,
                head=m["hidden_size"] * m["vocab_size"] * ITEM)
