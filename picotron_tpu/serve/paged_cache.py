"""Block/paged KV cache for the serving decode path.

The offline `generate.KVCache` pays `batch x max_length` HBM for every
sequence — at serving batch sizes with ragged request lengths most of
that is stranded (a 40-token reply in a 4096-slot row wastes 99% of it).
The paged cache instead allocates fixed-size BLOCKS from one shared pool
and maps each decode slot's logical positions onto physical blocks
through a per-slot block table (the vLLM arrangement, kept deliberately
static-shaped for XLA):

- ``k``/``v``: ``[L, num_blocks, block_size, Hkv, D]`` — the pool.
  Persistent cache HBM scales with ``num_blocks`` actually provisioned,
  not with ``slots x max_length`` (pinned by the pool-accounting test).
- ``tables``: ``[B, max_blocks]`` int32, logical block -> physical block.
  ``num_blocks`` itself is the UNMAPPED sentinel: scatter writes at the
  sentinel drop (``mode="drop"``), gathers clamp into the pool and the
  clamped garbage is masked by the causal mask before anything reads it.

Writes use the same advanced-indexing scatter for decode (one token per
slot, each at its own position) and chunked prefill (a contiguous span of
one slot); positions < 0 (chunk padding) are routed to the sentinel. The
attention view gathers a slot's blocks back into logical order, so
`generate._cached_attention` runs on it unchanged — slot j of the
gathered view holds the token at position j, exactly like the contiguous
cache, which is what makes paged-vs-contiguous greedy parity a
structural property rather than a numerical accident.

`BlockPool` is the host-side allocator: free-list alloc/free with
all-or-nothing semantics and peak accounting, so the scheduler can make
admission/preemption decisions and tests can assert no block leaks
across a full trace.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp

from picotron_tpu.config import ModelConfig
from picotron_tpu.models.llama import compute_dtype
from picotron_tpu.telemetry.scopes import scope


class PagedKVCache(NamedTuple):
    """Pool-backed cache; same interface as `generate.KVCache`
    (num_layers / write / layer_view) so `generate._decode_layers` is
    cache-agnostic."""

    k: jnp.ndarray       # [L, num_blocks, block_size, Hkv, D]
    v: jnp.ndarray       # [L, num_blocks, block_size, Hkv, D]
    tables: jnp.ndarray  # [B, max_blocks] int32; num_blocks = unmapped

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @scope("kv_write")
    def write(self, li, k_new, v_new, q_pos) -> "PagedKVCache":
        """Scatter K/V [B, s, Hkv, D] into each token's (physical block,
        offset) slot of layer li. q_pos: [s] batch-shared or [B, s]
        per-slot global positions; positions < 0, positions beyond the
        table's capacity, and unmapped table entries all resolve to the
        out-of-bounds sentinel and are DROPPED by the scatter."""
        bs = self.block_size
        if q_pos.ndim == 1:
            q_pos = jnp.broadcast_to(q_pos[None, :],
                                     (k_new.shape[0], q_pos.shape[0]))
        blk = jnp.maximum(q_pos, 0) // bs                       # [B, s]
        idx = jnp.minimum(blk, self.tables.shape[1] - 1)
        phys = jnp.take_along_axis(self.tables, idx, axis=1)    # [B, s]
        ok = (q_pos >= 0) & (blk < self.tables.shape[1])
        phys = jnp.where(ok, phys, self.num_blocks)
        off = jnp.maximum(q_pos, 0) % bs
        k = self.k.at[li, phys, off].set(k_new, mode="drop")
        v = self.v.at[li, phys, off].set(v_new, mode="drop")
        return self._replace(k=k, v=v)

    def layer_view(self, li):
        """Gather layer li's blocks back into logical order:
        ([B, max_blocks * block_size, Hkv, D], same) — slot j holds the
        token at position j, identically to the contiguous cache, so the
        shared attention math applies unchanged. Unmapped table entries
        clamp to the last pool block; whatever stale K/V they surface sits
        beyond every live q position and is causally masked. This view is
        a per-layer TRANSIENT inside the layer scan (capacity-sized
        activation), not persistent cache memory."""
        kl = self.k[li]  # [num_blocks, block_size, Hkv, D]
        vl = self.v[li]
        b, mb = self.tables.shape
        shape = (b, mb * self.block_size) + kl.shape[2:]
        return (kl[self.tables].reshape(shape),
                vl[self.tables].reshape(shape))


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     num_slots: int, max_blocks: int) -> PagedKVCache:
    """Zeroed pool + all-unmapped tables. Pool memory is
    L * num_blocks * block_size * Hkv * D * 2 tensors — sized by the
    blocks provisioned, independent of num_slots * max_length."""
    shape = (cfg.num_hidden_layers, num_blocks, block_size,
             cfg.num_key_value_heads, cfg.head_dim)
    dt = compute_dtype(cfg)
    tables = jnp.full((num_slots, max_blocks), num_blocks, jnp.int32)
    return PagedKVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt), tables)


class BlockPool:
    """Host-side free-list allocator over the physical blocks.

    All-or-nothing `alloc(n)` (a partially-allocated sequence could never
    run and would strand blocks), LIFO reuse (freshly-freed blocks are the
    ones whose stale contents the causal mask already screens), and peak
    accounting for the pool-utilization telemetry."""

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))
        self.peak_in_use = 0

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_blocks - len(self._free)

    def alloc(self, n: int) -> Optional[list]:
        """n physical block ids, or None (and no state change) when the
        pool cannot cover all n."""
        if n < 0:
            raise ValueError(f"alloc count must be >= 0, got {n}")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return out

    def free(self, blocks) -> None:
        for b in blocks:
            if not 0 <= b < self.num_blocks:
                raise ValueError(f"freeing unknown block {b}")
            if b in self._free:
                raise ValueError(f"double free of block {b}")
            self._free.append(b)
