"""Operations the arithmetic of a sparse-expert decoder requires, computed
from shapes: `flops.py`'s count for a model whose MLP is a router and
`num_experts_per_token` of `num_experts` experts of width
`moe_intermediate_size`.

A token passes through the attention projections, the router and exactly k
experts, so those are the multiplying parameters (`active`); the other
experts' weights multiply nothing for that token. The grouped matmuls are
counted with rows = tokens x k exactly: no capacity padding, no dropped
assignment, and the dispatch's permutations and the recomputed forward (remat,
the fused grad engine's re-run of the block) are not required operations.
"""

from __future__ import annotations

import flops


def expert_width(m: dict) -> int:
    return m.get("moe_intermediate_size") or m["intermediate_size"]


def active_multiplying_params(m: dict) -> int:
    """Parameters that sit in a matmul one token passes through once.
    `m` is the configuration file's `model` block."""
    h = m["hidden_size"]
    d = m.get("head_dim") or h // m["num_attention_heads"]
    q_out = m["num_attention_heads"] * d
    kv_out = m["num_key_value_heads"] * d
    attn = h * q_out + 2 * h * kv_out + q_out * h
    router = h * m["num_experts"]
    experts = m["num_experts_per_token"] * 3 * h * expert_width(m)
    return m["num_hidden_layers"] * (attn + router + experts) + h * m["vocab_size"]


def train_flops_per_token_active(m: dict, seq: int) -> float:
    """Forward + backward, per trained token: 6 per active multiplying
    parameter and 3 x the causal forward attention (`flops.py`'s rule)."""
    return (6.0 * active_multiplying_params(m)
            + 3.0 * flops.attention_flops_per_token_fwd(m, seq))


def grouped_matmul_flops(rows: int, k_in: int, n_out: int) -> float:
    """[rows, k_in] x one of [E, k_in, n_out] per row: every row through one
    expert's matrix, whatever the group sizes."""
    return 2.0 * rows * k_in * n_out


def grouped_matmul_bytes(rows: int, k_in: int, n_out: int, experts: int,
                         itemsize: int = 2) -> float:
    """The least one grouped matmul moves: the rows in, the whole bank once,
    the rows out. The two backward matmuls (dX: grad rows and the bank in,
    rows out; dW: both row operands in, the bank's gradient out) move the
    same three tensors."""
    return float(itemsize) * (rows * k_in + experts * k_in * n_out + rows * n_out)


def expert_block_least_seconds(m: dict, tokens: int, peak: dict) -> float:
    """Roofline of one layer's expert matmuls for one microbatch of `tokens`
    tokens, forward + backward: gate, up and down, each once forward and
    twice backward (dX, dW): nine grouped matmuls of rows = tokens x k."""
    rows = tokens * m["num_experts_per_token"]
    h, f, e = m["hidden_size"], expert_width(m), m["num_experts"]
    one = flops.least_seconds(grouped_matmul_flops(rows, h, f),
                              grouped_matmul_bytes(rows, h, f, e), peak)
    return 9.0 * one
