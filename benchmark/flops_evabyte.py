"""Bytes and parameters of serving one pipeline stage of EvaByte
(`configs/evabyte-6.5b-8l.json`), computed from shapes: what the
configuration's cut is reckoned by, what a slot's cache holds at a length,
and what a decode step has to read.

A decode step is bound by memory (16 rows against 1.6 B multiplying
parameters), so what matters is bytes over the chip's bandwidth
(`peaks.json`). The cell adds no kernel: the decode kernel's roofline is
`flops_mellum2`'s (`kv_block_bytes` x the blocks the engine counts on its
`serve.decode.dispatch` span), which reads this model's heads and block from
its own `model` and `serve` blocks; with EVA attention that count is the
summary blocks of the closed windows plus the open window's blocks
(`blocks_read`), which is what the kernel reads. Here:

- the parameters of the cut, a matrix at a time, as ISSUE 43 reckons them
  (`tests/test_flops_evabyte.py` holds them against the built tree);
- a block's bytes, the blocks a slot HOLDS after n positions (window blocks,
  one window's worth at the most, and a summary row a complete chunk) and the
  blocks a query READS at a length, beside what full attention would;
- what a decode step reads: every layer's matrices, head 0's columns of the
  head, and the blocks of every live slot in every layer.

`m` is a configuration file's `model` block.
"""

from __future__ import annotations

ITEM = 2  # bytes of a bfloat16 value


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def attention_params(m: dict) -> int:
    """q, k, v, o of one layer."""
    h, d = m["hidden_size"], head_dim(m)
    return 2 * h * m["num_attention_heads"] * d + 2 * h * m["num_key_value_heads"] * d


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def pooling_params(m: dict) -> int:
    """mu and phi: a vector a KV head each."""
    return 2 * m["num_key_value_heads"] * head_dim(m)


def layer_params(m: dict) -> int:
    """The matrices, the two norms and the pooling vectors of one layer."""
    return attention_params(m) + mlp_params(m) + 2 * m["hidden_size"] + pooling_params(m)


def ends_params(m: dict) -> int:
    """Embedding, the head of num_pred_heads x vocab_size rows, the final norm."""
    return (1 + m["num_pred_heads"]) * m["vocab_size"] * m["hidden_size"] + m["hidden_size"]


def total_params(m: dict) -> int:
    return m["num_hidden_layers"] * layer_params(m) + ends_params(m)


def kv_position_bytes(m: dict) -> int:
    """K and V of one cached row of one layer, every KV head: a position's,
    and a chunk summary's, which has the same shape."""
    return 2 * m["num_key_value_heads"] * head_dim(m) * ITEM


def block_bytes(m: dict, block_size: int) -> int:
    """One block of the pool, every layer."""
    return m["num_hidden_layers"] * block_size * kv_position_bytes(m)


def blocks_held(m: dict, n: int, block_size: int) -> tuple:
    """(window blocks, summary blocks) a slot holds once n positions are
    written: what the positions fill up to one window's worth, then recycled
    in place; a summary row a complete chunk, which stays."""
    return (-(-min(n, m["window_size"]) // block_size),
            -(-(n // m["chunk_size"]) // block_size))


def blocks_read(m: dict, n: int, block_size: int) -> tuple:
    """(window blocks, summary blocks) a layer's attention reads for the query
    at position n - 1: what the open window's positions fill, and the
    summaries of the closed windows (none of its own window's)."""
    closed = (n - 1) // m["window_size"]
    return (-(-(n - closed * m["window_size"]) // block_size),
            -(-(closed * (m["window_size"] // m["chunk_size"])) // block_size))


def full_attention_blocks(n: int, block_size: int) -> int:
    return -(-n // block_size)


def decode_step_bytes(m: dict, lengths, block_size: int) -> dict:
    """Bytes one decode step reads at the least, by part: every layer's
    matrices once, head 0's columns of the head, and K and V of the blocks
    each live slot (`lengths`: positions written, the query's included) reads
    in every layer."""
    blocks = sum(sum(blocks_read(m, n, block_size)) for n in lengths)
    return dict(
        layers=m["num_hidden_layers"] * (attention_params(m) + mlp_params(m)) * ITEM,
        head=m["hidden_size"] * m["vocab_size"] * ITEM,
        cache=blocks * block_bytes(m, block_size))
