"""Operations the arithmetic requires, computed from shapes.

This is the benchmark's own count, kept here so that no later PR can move it.
It differs from `picotron_tpu.utils.flops_per_token` (the reference's
`6N + 12*L*h*s`) in two ways: attention is counted causal (a token attends
to the positions up to and including its own, (S + 1) / 2 on average, not
S), and only parameters that multiply are in N (no norms, no biases, no
embedding look-up; the head matmul runs whether or not it is tied). Recomputed
operations (remat, the flash backward's second pass over QK^T) are not
required operations and are not counted in `train_flops_per_token`.
"""

from __future__ import annotations


def multiplying_params(m: dict) -> int:
    """Parameters that sit in a matmul a token passes through once.
    `m` is the configuration file's `model` block."""
    h = m["hidden_size"]
    d = m.get("head_dim") or h // m["num_attention_heads"]
    q_out = m["num_attention_heads"] * d
    kv_out = m["num_key_value_heads"] * d
    attn = h * q_out + 2 * h * kv_out + q_out * h
    mlp = 3 * h * m["intermediate_size"]
    return m["num_hidden_layers"] * (attn + mlp) + h * m["vocab_size"]


def attention_flops_per_token_fwd(m: dict, seq: int) -> float:
    """Causal QK^T and PV, forward, per token, all layers: two matmuls of
    2 * d operations per (query, key) pair per head, over (seq + 1) / 2
    visible keys on average."""
    h = m["hidden_size"]
    d = m.get("head_dim") or h // m["num_attention_heads"]
    return (m["num_hidden_layers"] * m["num_attention_heads"]
            * 2 * 2 * d * (seq + 1) / 2)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward, per trained token: 6 per multiplying parameter
    (2 forward, 4 backward) and 3 x the forward attention (backward needs
    dV, dP, dS->dQ, dS->dK: twice the forward's two)."""
    return 6.0 * multiplying_params(m) + 3.0 * attention_flops_per_token_fwd(m, seq)


# Matmuls over the visible (query, key) pairs that each flash kernel's own
# contract needs: forward QK^T, PV; the dq kernel QK^T, dO V^T, dS K; the
# dkv kernel QK^T, dO V^T, P^T dO, dS^T Q. (The dq/dkv pair computes QK^T
# and dO V^T twice between them: a fused backward would need 5, not 7. That
# loss is the split's, and shows in the step time, not in this share.)
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call_flops(kind: str, batch: int, heads: int, seq: int, d: int) -> float:
    """Operations one causal flash-kernel call requires."""
    pairs = seq * (seq + 1) // 2
    return float(FLASH_MATMULS[kind]) * 2.0 * d * pairs * batch * heads


def flash_call_bytes(kind: str, batch: int, heads: int, kv_heads: int,
                     seq: int, d: int, itemsize: int = 2) -> float:
    """Bytes one call has to move at the least: each operand read once, each
    result written once (q, o, do, dq are [B, S, H, D]; k, v, dk, dv are
    [B, S, Hkv, D]; lse/delta rows are fp32 [B, H, S])."""
    qs = batch * seq * heads * d * itemsize
    ks = batch * seq * kv_heads * d * itemsize
    row = batch * heads * seq * 4
    if kind == "fwd":
        return qs + 2 * ks + qs + row              # q, k, v -> o, lse
    if kind == "dq":
        return 2 * qs + 2 * ks + 2 * row + qs      # q, do, k, v, lse, delta -> dq
    return 2 * qs + 2 * ks + 2 * row + 2 * ks      # ... -> dk, dv


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline: the larger of operations over peak rate and bytes over
    peak bandwidth."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
