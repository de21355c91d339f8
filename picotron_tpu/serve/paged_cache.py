"""Block/paged KV cache for the serving decode path.

The offline `generate.KVCache` pays `batch x max_length` HBM for every
sequence — at serving batch sizes with ragged request lengths most of
that is stranded (a 40-token reply in a 4096-slot row wastes 99% of it).
The paged cache instead allocates fixed-size BLOCKS from one shared pool
and maps each decode slot's logical positions onto physical blocks
through a per-slot block table (the vLLM arrangement, kept deliberately
static-shaped for XLA):

- ``k``/``v``: ``[Hkv, L, num_blocks, block_size, D]`` — the pool, one
  ``[L, num_blocks, block_size, D]`` pool a KV head. Persistent cache HBM
  scales with ``num_blocks`` actually provisioned, not with
  ``slots x max_length`` (pinned by the pool-accounting test).
- ``tables``: ``[B, max_blocks]`` int32, logical block -> physical block.
  ``num_blocks`` itself is the UNMAPPED sentinel: scatter writes at the
  sentinel drop (``mode="drop"``), gathers clamp into the pool and the
  clamped garbage is masked by the causal mask before anything reads it;
  the decode kernel reads no entry past a slot's length at all.

Why the KV heads lead: the pool rides the layer scan's carry, and the
compiler gives a carried buffer ONE layout that `write`'s scatter and
`layer_view`'s gather must both accept — where they disagree it
re-lays-out the whole pool around the scatter in every layer. With the
heads behind the block (``[L, blocks, block, Hkv, D]``) the prefill
program carried V head-major for the P.V contraction and copied all of it
twice a layer: 58 copies of 1.88 GB a dispatch at Qwen2-1.5B's chat
settings, a third of the dispatch. Here `write` and `layer_view` are
`vmap`s over the head axis of a scatter and a gather on one head's pool,
so the head is a batching dimension of both (and the axis a tp mesh
shards: each device scatters and gathers its own heads, no collective),
the scatter's window is a row of ``D``, the gather's a whole
``[block_size, D]`` block (16 x 128: one bf16 tile) addressed by
(layer, block) straight out of the pool, and both serve programs compile
for a v5e with the pool in the default layout
``{4,3,2,1,0:T(8,128)(2,1)}`` from entry to exit: no pool-sized ``copy``
anywhere, no layer sliced out before the gather, the scatters in place on
the donated buffers. tests/test_chip_compile.py compiles both and counts.

Writes address a token's row the same way for decode (one token per
slot, each at its own position) and chunked prefill (a span of every
mid-prefill slot): `_slots_of`, with positions < 0 (chunk padding) routed
to the sentinel. The attention view gathers a slot's blocks back into logical
order, so `generate._cached_attention` runs on it unchanged — slot j of
the gathered view holds the token at position j, exactly like the
contiguous cache, which is what makes paged-vs-contiguous greedy parity a
structural property rather than a numerical accident.

`attend` is what the layer loop calls. A decode step (one query position
a slot) on a chip does not build that view: `ops/paged_attention.py`
reads the ``ceil((pos + 1) / block_size)`` blocks each slot holds straight
out of the pool through the table, the same mathematics with the scores
kept in float32. The view spans ``slots x max_blocks`` blocks whatever is
live (the whole pool's size where the pool is sized ``slots x
max_model_len``), and gathering it was 69% of the decode program's device
time at Qwen2-1.5B's chat settings, where the traffic holds a sixth of the
pool at the fullest (PERF.md, PR 32). Prefill chunks (more than one query
position a slot), shapes the kernel does not take and every CPU run keep
the view. Such a step WRITES in place too (`write`, PR 59): one kernel a
layer over both pools where they lie (`ops/paged_attention.py
paged_kv_write`), which reads each live slot's block into VMEM by the
strided DMA the attention kernel reads it with, puts the new row in, and
sends the block back, K and V and all the KV heads of a row in one round
of DMAs each way; an idle slot moves nothing. The scatter it stands in
for costs by its windows, one a (row, KV head), 92 ns each on a v5e
whatever they hold (47 us a call at EvaByte's 32 heads x 16 slots, four
calls a layer: a fifth of that cell's device time, PERF.md, PR 59), and
still writes the prefill chunks, every CPU run and a tp-sharded pool.

A model with sliding-window layers has two kinds of state
(`MixedPagedKVCache`): a full-attention layer needs every position of a
sequence, a sliding layer the last `sliding_window` only. Its full layers
keep the pool and tables above, with only those layers in the pool's layer
axis; its sliding layers keep a second, smaller pool in which a slot holds
a fixed RING of `ring_blocks_for(window, prefill_chunk, block_size)`
blocks, given at admission and never grown: position `p` lives in ring
entry `(p // block_size) % ring`, so a sequence overwrites what has left
every later query's band. The ring is sized by what prefill needs
(window + chunk), whichever is the larger: K-EXAONE's window of 128 under
a chunk of 256 is a ring of 25 blocks of which a decode step's band lies
in 9, and the decode kernel buffers those 9, not the ring. What a ring
entry holds now is known from the last position written
(`_ring_positions`), and a key is masked by that position, as a key past
the length is. Neither kind of layer builds a whole view at prefill:
`_tiled_attention` walks the keys in tiles of blocks under an online
softmax (a full layer as far as the longest row of the batch reaches, a
sliding layer over its ring), so a 16k-position row never has its
`[chunk, 16384]` scores in memory at once.

`BlockPool` is the host-side allocator: free-list alloc/free with
all-or-nothing semantics and peak accounting, so the scheduler can make
admission/preemption decisions and tests can assert no block leaks
across a full trace.

Which of these caches a model is served from is decided in ONE place,
`init_serve_cache`, and a cache's format is known here and nowhere else.
The serving engine (serve/engine.py) holds whatever that constructor gave
it and asks it: `pools` (what a serve program is handed, donated, and
hands back) and `of` (the cache inside the program, from those and the
dispatch's table rows), `table_specs` (each table's row width and unmapped
sentinel), `slot_rows` (a slot's host table rows from the blocks the
scheduler gave it and the slot's own index), `scheduler_args` (what the scheduler must know of the
format) and `prefill_counts` / `decode_counts` (what a dispatch's span
says it writes and reads). A new kind of cache is a class with those
answers and an arm of `init_serve_cache`; the engine does not change.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from picotron_tpu.config import (
    ModelConfig, ServeConfig, check_eva_serving,
)
from picotron_tpu.generate import _cached_attention, conv_through
from picotron_tpu.models.llama import compute_dtype, recurrent_start
from picotron_tpu.ops.eva import chunk_summaries, eva_summarise
from picotron_tpu.ops.gated_delta import (
    gated_delta_chunk_pooled, gated_delta_chunk_suits,
    gated_delta_kernel_suits, gated_delta_step_pooled, per_value_head,
)
from picotron_tpu.ops.kda import delta_rule, kda_chunk_pooled, kda_chunk_suits
from picotron_tpu.ops.mla import (
    TILE_KEYS, absorb_queries, latent_attention, values_from_latent,
)
from picotron_tpu.ops.paged_attention import (
    decode_kernel_suits, latent_decode_attention, latent_kernel_suits,
    kv_write_suits, latent_prefill_attention, latent_prefill_suits,
    latent_prefill_tile, paged_decode_attention, paged_kv_write,
)
from picotron_tpu.ops.selective_scan import (
    conv_kernel_suits, conv_step_pooled, scan_segment,
    selective_scan_chunk_pooled, selective_scan_step_pooled, ssm_chunk_suits,
    ssm_kernel_suits,
)
from picotron_tpu.ops.ssd import (
    ssd, ssd_chunk_pooled, ssd_chunk_suits, ssd_kernel_suits, ssd_step_pooled,
)
from picotron_tpu.serve.scheduler import blocks_for
from picotron_tpu.telemetry.scopes import scope


def _slots_of(tables, q_pos, rows: int, block_size: int, num_blocks: int,
              ring: bool = False):
    """(physical block [B, s], offset in it [B, s]) of the positions q_pos
    ([s] batch-shared or [B, s]) of `rows` table rows. Positions < 0,
    positions beyond the table's capacity and unmapped entries all resolve
    to the out-of-bounds sentinel `num_blocks`, which a scatter with
    `mode="drop"` drops. `ring`: logical block j lives at entry j % width,
    and no position is beyond the table."""
    width = tables.shape[1]
    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None, :], (rows, q_pos.shape[0]))
    blk = jnp.maximum(q_pos, 0) // block_size                   # [B, s]
    if ring:
        blk = blk % width
    phys = jnp.take_along_axis(tables, jnp.minimum(blk, width - 1), axis=1)
    phys = jnp.where((q_pos >= 0) & (blk < width), phys, num_blocks)
    return phys, jnp.maximum(q_pos, 0) % block_size


def _table_row(spec, blocks=(), first: int = 0):
    """A slot's host row of a table of `spec` (`table_specs`): `blocks`
    from entry `first` on, unmapped elsewhere."""
    width, unmapped = spec
    row = np.full((width,), unmapped, np.int32)
    row[first:first + len(blocks)] = blocks
    return row


class PagedKVCache(NamedTuple):
    """Pool-backed cache; same interface as `generate.KVCache`
    (num_layers / write / layer_view / attend) so
    `generate._decode_layers` is cache-agnostic."""

    k: jnp.ndarray       # [Hkv, L, num_blocks, block_size, D]
    v: jnp.ndarray       # [Hkv, L, num_blocks, block_size, D]
    tables: jnp.ndarray  # [B, max_blocks] int32; num_blocks = unmapped

    @property
    def num_layers(self) -> int:
        return self.k.shape[1]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[2]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @scope("kv_write")
    def write(self, li, k_new, v_new, q_pos,
              ring: bool = False) -> "PagedKVCache":
        """K/V [B, s, Hkv, D] into each token's (physical block, offset)
        row of layer li. q_pos: [s] batch-shared or [B, s] per-slot global
        positions; positions < 0, positions beyond the table's capacity,
        and unmapped table entries all resolve to the out-of-bounds
        sentinel and are DROPPED. `ring`: the table is a ring (a sliding
        layer's), logical block j at entry j % width, and no position is
        beyond it. One result, two forms, chosen from what the step's
        shapes and the backend say (`kv_write_suits`): a decode step on a
        chip is ONE kernel over both pools in place (`paged_kv_write`: a
        row's block comes into VMEM, takes the row, and goes back, one DMA
        each way for all its KV heads; a dropped row moves nothing);
        everything else scatters. No two rows of a decode step share a
        block: a slot writes into its own last block."""
        phys, off = _slots_of(self.tables, q_pos, k_new.shape[0],
                              self.block_size, self.num_blocks, ring)
        if kv_write_suits(k_new, self.k):
            k, v = paged_kv_write(self.k, self.v, li, k_new[:, 0], v_new[:, 0],
                                  phys[:, 0], off[:, 0])
            return self._replace(k=k, v=v)
        return self._scatter(li, k_new, v_new, phys, off)

    def _scatter(self, li, k_new, v_new, phys, off) -> "PagedKVCache":
        """K/V [B, s, Hkv, D] into rows `off` [B, s] of blocks `phys` [B, s]
        of layer li; a block outside the pool is dropped."""
        # the indices are batched over the heads with the pool: vmapped
        # over pool and rows alone, the head folds into the scatter's
        # window and the compiler carries the pool heads-minor again
        # (four pool copies a dispatch in both programs)
        hkv = self.k.shape[0]
        phys = jnp.broadcast_to(phys, (hkv,) + phys.shape)
        off = jnp.broadcast_to(off, (hkv,) + off.shape)
        put = jax.vmap(  # on one head's [L, num_blocks, block_size, D]
            lambda pool, new, ph, of: pool.at[li, ph, of].set(new, mode="drop"),
            in_axes=(0, 2, 0, 0))
        return self._replace(k=put(self.k, k_new, phys, off),
                             v=put(self.v, v_new, phys, off))

    def layer_view(self, li):
        """Gather layer li's blocks back into logical order:
        ([B, max_blocks * block_size, Hkv, D], same) — slot j holds the
        token at position j, identically to the contiguous cache, so the
        shared attention math applies unchanged. Unmapped table entries
        clamp to the last pool block; whatever stale K/V they surface sits
        beyond every live q position and is causally masked. This view is
        a per-layer TRANSIENT inside the layer scan (capacity-sized
        activation), not persistent cache memory."""
        hkv = self.k.shape[0]
        b, mb = self.tables.shape
        # tables batched over the heads for the same reason as in `write`
        tables = jnp.broadcast_to(self.tables, (hkv, b, mb))
        gather = jax.vmap(lambda pool, t: pool[li, t])

        def view(pool):
            g = gather(pool, tables)  # [Hkv, B, max_blocks, block_size, D]
            return g.reshape(hkv, b, mb * self.block_size, -1).transpose(
                1, 2, 0, 3)

        return view(self.k), view(self.v)

    def attend(self, li, q, q_pos):
        """Attention of q [B, s, Hq, D] at positions q_pos ([s] or
        [B, s]) over layer li's cached positions. One algorithm, two
        forms, chosen from what the step's shapes and the backend say
        (`decode_kernel_suits`): a decode step reads each slot's own
        blocks in place, everything else attends the gathered view.
        Slots at positions < 0 have length 0: the kernel reads nothing
        for them and returns zeros (discarded by the caller, finite for
        the layers after)."""
        if not decode_kernel_suits(q, self.k):
            return _cached_attention(q, *self.layer_view(li), q_pos)
        pos = jnp.broadcast_to(q_pos.reshape(-1), q.shape[:1])  # s == 1
        out = paged_decode_attention(q[:, 0], self.k, self.v, li,
                                     self.tables, jnp.maximum(pos + 1, 0))
        return out[:, None]

    # -- what the serving engine asks of its cache (the module docstring's
    # last paragraph). Everything below `pools` runs on the host and reads
    # shapes alone: the engine asks a cache of shapes.

    @classmethod
    def of(cls, pools, tables):
        """The cache inside a serve program: the pools it was handed and
        the table rows uploaded for this dispatch (a tuple each)."""
        return cls(*pools, *tables)

    @property
    def pools(self) -> tuple:
        return self.k, self.v

    @property
    def table_specs(self) -> tuple:
        """(entries of a row, the unmapped sentinel) of each table."""
        return ((self.tables.shape[1], self.num_blocks),)

    def scheduler_args(self, cfg: ModelConfig) -> dict:
        """`Scheduler`'s arguments beyond the pool of `num_blocks` blocks."""
        return {}

    def slot_rows(self, st, cfg: ModelConfig, slot: int) -> tuple:
        """Slot `slot`'s row of each table from the blocks its request
        holds (`st`: its `RequestState`, None for a free slot)."""
        return (_table_row(self.table_specs[0], st.blocks if st else ()),)

    def blocks_read(self, n: int, cfg: ModelConfig) -> int:
        """Blocks a layer's attention reads for a query at position n - 1:
        every block its n positions fill."""
        return blocks_for(n, self.block_size)

    def prefill_counts(self, spans, cfg: ModelConfig, rows=None) -> dict:
        """Further counts of a prefill dispatch's span. `spans`: (positions
        already cached, tokens of this chunk) a row with a request; `rows`:
        the rows of the rung that ran, those and its pad rows (None: no
        pad row)."""
        return {}

    def decode_counts(self, spans, cfg: ModelConfig) -> dict:
        """Counts of a decode dispatch's span. `spans`: (the position its
        first step writes, the tokens it has yet to emit in this dispatch)
        a slot. `kv_blocks`: the blocks the slots' cached positions fill at
        the dispatch's first token, which is what a decode step that
        attends in place reads a layer."""
        return dict(kv_blocks=sum(self.blocks_read(p + 1, cfg)
                                  for p, _ in spans))


# Blocks a tile of `_tiled_attention`: 32 blocks of 16 positions are 512
# keys, [rows, heads, chunk, 512] float32 scores a tile (0.54 GB on the
# 32-row rung at Mellum2's 32 heads and a 256-token chunk).
TILE_BLOCKS = 32


def ring_blocks_for(window: int, prefill_chunk: int, block_size: int) -> int:
    """Blocks in a slot's ring for its sliding layers. A prefill chunk
    writes its `prefill_chunk` positions before it attends, and its first
    query still needs the `window - 1` positions before it: the ring holds
    window + chunk positions, and one block more for the block edges."""
    return -(-(window + prefill_chunk) // block_size) + 1


def _ring_positions(last, n: int):
    """The position each of a ring's `n` slots holds once positions
    0 .. last have been written in order (last [B], -1: nothing yet):
    the newest position <= last that is congruent to the slot mod n.
    Negative: never written. [B, n]."""
    j = jnp.arange(n)[None, :]
    return last[:, None] - (last[:, None] - j) % n


def _tiled_attention(q, q_pos, n_tiles, fetch, hkv: int, window=None):
    """Causal attention of q [B, s, Hq, D] at positions q_pos [B, s] over
    keys fetched a tile at a time, under an online softmax: the
    mathematics of `generate._cached_attention` (scores and statistics in
    float32, P in the value dtype for PV, float32 accumulation) without
    the whole row of scores. `fetch(t)` -> (k [Hkv, B, T, D], v, kv_pos
    [B, T]): tile t's keys and the position each holds; a key is seen
    where 0 <= kv_pos <= q_pos (and q_pos - kv_pos < window on a sliding
    layer). `n_tiles` may be traced. Rows at q_pos < 0 (padding) attend
    as position 0 and are discarded by the caller; a row that sees no key
    returns zeros."""
    b, s, hq, d = q.shape

    def body(t, carry):
        m, l, acc = carry
        k, v, kp = fetch(t)
        qg = q.reshape(b, s, hkv, hq // hkv, d)
        sc = jnp.einsum("bshgd,hbtd->bhgst", qg, k,
                        preferred_element_type=jnp.float32) / (d ** 0.5)
        qp = jnp.maximum(q_pos, 0)[:, :, None]                 # [B, s, 1]
        seen = (kp[:, None, :] >= 0) & (kp[:, None, :] <= qp)  # [B, s, T]
        if window is not None:
            seen &= qp - kp[:, None, :] < window
        seen = seen[:, None, None]
        sc = jnp.where(seen, sc, -1e30)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(sc - m_new[..., None]), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgst,hbtd->bhgsd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    g = hq // hkv
    init = (jnp.full((b, hkv, g, s), -1e30, jnp.float32),
            jnp.zeros((b, hkv, g, s), jnp.float32),
            jnp.zeros((b, hkv, g, s, d), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_tiles, body, init)
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, hq, d).astype(q.dtype)


class MixedPagedKVCache(NamedTuple):
    """The serving cache of a model with sliding-window and full layers
    side by side. `generate._decode_layers` calls it with `window` (the
    layer's band; None on a full layer) and `ki` (the layer's ordinal
    among the model's layers of its kind, which is its index in its pool:
    a pool holds the layers of its kind of every stack of the layer tree,
    a leading dense layer's beside the expert layers', in model order)."""

    k: jnp.ndarray        # [Hkv, L_full, num_blocks, block_size, D]
    v: jnp.ndarray
    wk: jnp.ndarray       # [Hkv, L_window, num_window_blocks, block_size, D]
    wv: jnp.ndarray
    tables: jnp.ndarray   # [B, max_blocks]; num_blocks = unmapped
    wtables: jnp.ndarray  # [B, ring]; num_window_blocks = unmapped

    @property
    def num_layers(self) -> int:
        return self.k.shape[1] + self.wk.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    def _of(self, window) -> PagedKVCache:
        return (PagedKVCache(self.k, self.v, self.tables) if window is None
                else PagedKVCache(self.wk, self.wv, self.wtables))

    def write(self, li, k_new, v_new, q_pos, window=None,
              ki=None) -> "MixedPagedKVCache":
        c = self._of(window).write(ki, k_new, v_new, q_pos,
                                   ring=window is not None)
        return (self._replace(k=c.k, v=c.v) if window is None
                else self._replace(wk=c.k, wv=c.v))

    def attend(self, li, q, q_pos, window=None, ki=None):
        """Attention of q [B, s, Hq, D] over layer `ki` of the pool of its
        kind. A decode step on a chip reads the slot's blocks in place
        (the kernel, from the band's first block on a sliding layer);
        everything else walks the keys in tiles."""
        c = self._of(window)
        b, s = q.shape[:2]
        if q_pos.ndim == 1:
            q_pos = jnp.broadcast_to(q_pos[None, :], (b, s))
        with scope("attn_full" if window is None else "attn_window"):
            if decode_kernel_suits(q, c.k):
                out = paged_decode_attention(
                    q[:, 0], c.k, c.v, ki, c.tables,
                    jnp.maximum(q_pos[:, 0] + 1, 0), window=window)
                return out[:, None]
            return self._tiled(c, ki, q, q_pos, window)

    @staticmethod
    def _tiled(c: PagedKVCache, ki, q, q_pos, window):
        bs, width = c.block_size, c.tables.shape[1]
        tb = min(TILE_BLOCKS, width)
        tiles = -(-width // tb)
        hkv, b = c.k.shape[0], q.shape[0]
        # whole tiles: entries past the table read the unmapped sentinel,
        # which the gather clamps into the pool and the positions mask
        tables = jnp.pad(c.tables, ((0, 0), (0, tiles * tb - width)),
                         constant_values=c.num_blocks)
        last = jnp.max(q_pos, axis=1)                            # [B]
        if window is not None:
            held = _ring_positions(last, width * bs)             # [B, n]
            held = jnp.pad(held, ((0, 0), (0, (tiles * tb - width) * bs)),
                           constant_values=-1)

        def fetch(t):
            tbl = jax.lax.dynamic_slice_in_dim(tables, t * tb, tb, axis=1)
            tbl = jnp.broadcast_to(tbl, (hkv, b, tb))
            gather = jax.vmap(lambda pool, rows: pool[ki, rows])
            k = gather(c.k, tbl).reshape(hkv, b, tb * bs, -1)
            v = gather(c.v, tbl).reshape(hkv, b, tb * bs, -1)
            if window is None:
                kp = jnp.broadcast_to(
                    t * tb * bs + jnp.arange(tb * bs)[None, :], (b, tb * bs))
            else:
                kp = jax.lax.dynamic_slice_in_dim(held, t * tb * bs,
                                                  tb * bs, axis=1)
            return k, v, kp

        # a full layer's keys end at the batch's last position; a ring is
        # walked whole
        n_tiles = (tiles if window is not None else
                   jnp.clip(-(-(jnp.max(last) + 1) // (tb * bs)), 0, tiles))
        return _tiled_attention(q, q_pos, n_tiles, fetch, hkv, window)

    # -- what the serving engine asks (see `PagedKVCache`)

    @classmethod
    def of(cls, pools, tables):
        k, wk, v, wv = pools
        return cls(k, v, wk, wv, *tables)

    @property
    def pools(self) -> tuple:
        """Both pools' K, then both pools' V: the order the serve programs
        have taken them in since there were two. A compiled program is not
        indifferent to the order of its parameters (K-EXAONE's one-row
        prefill schedules 8 prefetches fewer under another; PR 46)."""
        return self.k, self.wk, self.v, self.wv

    @property
    def table_specs(self) -> tuple:
        return ((self.tables.shape[1], self.k.shape[2]),
                (self.wtables.shape[1], self.wk.shape[2]))

    def scheduler_args(self, cfg: ModelConfig) -> dict:
        ring, window_blocks = self.table_specs[1]
        return dict(window_pool=BlockPool(window_blocks), ring_blocks=ring)

    def slot_rows(self, st, cfg: ModelConfig, slot: int) -> tuple:
        full, ring = self.table_specs
        return (_table_row(full, st.blocks if st else ()),
                _table_row(ring, st.wblocks if st else ()))

    prefill_counts = PagedKVCache.prefill_counts

    def decode_counts(self, spans, cfg: ModelConfig) -> dict:
        """`kv_blocks`, and each summed over the layers of its kind, at
        the dispatch's first token: `kv_blocks_full` (the full layers read
        every block a slot's positions fill), `kv_blocks_window` (the
        sliding layers read from the block of position length - window
        on), `kv_blocks_banded` (their sum: what the step reads) and
        `kv_blocks_unwindowed` (what it would read were every layer
        full)."""
        bs = self.block_size
        n_full, n_win = self.k.shape[1], self.wk.shape[1]
        kv_blocks = band = 0
        for p, _ in spans:
            kv_blocks += blocks_for(p + 1, bs)
            band += (blocks_for(p + 1, bs)
                     - max(p + 1 - cfg.sliding_window, 0) // bs)
        return dict(kv_blocks=kv_blocks,
                    kv_blocks_full=n_full * kv_blocks,
                    kv_blocks_window=n_win * band,
                    kv_blocks_banded=n_full * kv_blocks + n_win * band,
                    kv_blocks_unwindowed=(n_full + n_win) * kv_blocks)


def init_mixed_cache(cfg: ModelConfig, num_blocks: int,
                     num_window_blocks: int, block_size: int,
                     num_slots: int, max_blocks: int,
                     ring_blocks: int) -> MixedPagedKVCache:
    """Zeroed pools + all-unmapped tables for a model with sliding and
    full layers: each pool's layer axis holds the layers of its kind, of
    every stack (`cfg.layer_kinds` is the whole model's)."""
    kinds = cfg.layer_kinds
    dt = compute_dtype(cfg)

    def pool(kind, n):
        return jnp.zeros((cfg.num_key_value_heads, kinds.count(kind), n,
                          block_size, cfg.head_dim), dt)

    return MixedPagedKVCache(
        pool("full_attention", num_blocks), pool("full_attention", num_blocks),
        pool("sliding_attention", num_window_blocks),
        pool("sliding_attention", num_window_blocks),
        jnp.full((num_slots, max_blocks), num_blocks, jnp.int32),
        jnp.full((num_slots, ring_blocks), num_window_blocks, jnp.int32))


def latent_row_width(cfg: ModelConfig) -> int:
    """Numbers a cached position of a latent pool: `[c | k_r]`
    (kv_lora_rank + qk_rope_head_dim) padded up to whole 128-lane rows
    (576 -> 640 at openPangu-Ultra's widths, 11%: a block is then ONE
    contiguous piece for the decode kernel's DMA and one matmul operand,
    and a 576-wide array would be laid out 640 wide in HBM anyway)."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


class LatentPagedCache(NamedTuple):
    """The serving cache of a model with latent attention (MLA,
    ops/mla.py): a third kind of state beside the K/V pool and the ring.
    One pool [L, num_blocks, block_size, W] with NO head axis: a position
    holds `[c | k_r | 0]`, c after its norm (and its scale, where the model
    has one) and k_r after its rotation, W = `latent_row_width`. L counts
    attention SUBLAYERS (`cfg.attention_sublayers`): the layers, but two a
    layer for a model whose layers hold two attentions, each with its own
    row of the pool. A position costs one block entry whatever L is:
    tables, sentinel and `BlockPool` as `PagedKVCache`'s.
    `generate._decode_layers` calls `write(li, ckr, q_pos)` and
    `attend(li, q_n, q_r, q_pos, kv_b, cfg)`, li the sublayer."""

    kv: jnp.ndarray      # [L, num_blocks, block_size, W]
    tables: jnp.ndarray  # [B, max_blocks] int32; num_blocks = unmapped

    @property
    def num_layers(self) -> int:
        return self.kv.shape[0]

    @property
    def num_blocks(self) -> int:
        return self.kv.shape[1]

    @property
    def block_size(self) -> int:
        return self.kv.shape[2]

    @scope("kv_write")
    def write(self, li, ckr_new, q_pos) -> "LatentPagedCache":
        """Scatter `[c | k_r]` [B, s, rank + rope] into each token's
        (physical block, offset) row of layer li, padded to the row's
        width; dropped where `PagedKVCache.write` drops."""
        phys, off = _slots_of(self.tables, q_pos, ckr_new.shape[0],
                              self.block_size, self.num_blocks)
        new = jnp.pad(ckr_new, ((0, 0), (0, 0),
                                (0, self.kv.shape[3] - ckr_new.shape[2])))
        return self._replace(
            kv=self.kv.at[li, phys, off].set(new, mode="drop"))

    @scope("attn_latent")
    def attend(self, li, q_n, q_r, q_pos, kv_b, cfg: ModelConfig):
        """Attention of q_n [B, s, heads, nope] / q_r [B, s, heads, rope]
        (rotated) over layer li's cached positions -> [B, s, heads, v]. On
        a chip a step reads the blocks a row holds in place: a prefill chunk
        expanded (`latent_prefill_attention`), a decode step absorbed
        (`latent_decode_attention`). Shapes neither kernel takes and every
        CPU run walk tiles (`ops/mla.py latent_attention`, either form)."""
        b, s = q_n.shape[:2]
        if q_pos.ndim == 1:
            q_pos = jnp.broadcast_to(q_pos[None, :], (b, s))
        if latent_prefill_suits(q_n, q_r, self.kv, kv_b, self.tables.shape[1]):
            return latent_prefill_attention(q_n, q_r, q_pos, self.kv, li,
                                            self.tables, kv_b)
        if latent_kernel_suits(s, self.kv, cfg.kv_lora_rank):
            q = jnp.concatenate(
                [absorb_queries(q_n[:, 0], kv_b, cfg), q_r[:, 0]], axis=-1)
            q = jnp.pad(q, ((0, 0), (0, 0), (0, self.kv.shape[3] - q.shape[-1])))
            o_lat = latent_decode_attention(
                q, self.kv, li, self.tables, jnp.maximum(q_pos[:, 0] + 1, 0),
                rank=cfg.kv_lora_rank, sm_scale=1.0 / (
                    cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** 0.5)
            return values_from_latent(o_lat, kv_b, cfg)[:, None]
        return self._tiled(li, q_n, q_r, q_pos, kv_b, cfg)

    def _tiled(self, li, q_n, q_r, q_pos, kv_b, cfg):
        bs, width = self.block_size, self.tables.shape[1]
        tb = min(max(TILE_KEYS // bs, 1), width)
        tiles = -(-width // tb)
        # whole tiles: entries past the table read the unmapped sentinel,
        # which the gather clamps into the pool and the positions mask
        tables = jnp.pad(self.tables, ((0, 0), (0, tiles * tb - width)),
                         constant_values=self.num_blocks)
        at = jnp.arange(tb * bs)

        def fetch(bi, t):
            tbl = jax.lax.dynamic_slice_in_dim(tables[bi], t * tb, tb)
            return (self.kv[li, tbl].reshape(tb * bs, -1), t * tb * bs + at)

        return latent_attention(q_n, q_r, q_pos, fetch, tiles, tb * bs,
                                kv_b, cfg)

    # -- what the serving engine asks (see `PagedKVCache`, whose answers
    # hold wherever one table maps one pool)

    @property
    def pools(self) -> tuple:
        return (self.kv,)

    of = classmethod(PagedKVCache.of.__func__)
    table_specs = PagedKVCache.table_specs
    scheduler_args = PagedKVCache.scheduler_args
    slot_rows = PagedKVCache.slot_rows

    def prefill_counts(self, spans, cfg: ModelConfig, rows=None) -> dict:
        """`latent_keys`: the key positions the rows' chunks may see (each
        row's cached positions and its chunk, rounded up to the
        attention's tile), summed over the pool's rows (`attn_sublayers`: the
        attention sublayers, each of which walks them): with the seconds of
        `latent_prefill_attention`'s events it gives the kernel's share of
        the matmul peak, at `2 keys rank heads (nope + v) + 2 s keys heads
        (nope + rope + v)` operations a row."""
        tile = latent_prefill_tile(self.block_size, self.tables.shape[1])
        return dict(self._sublayers(cfg), latent_keys=self.num_layers * sum(
            -(-(p + n) // tile) * tile for p, n in spans))

    def decode_counts(self, spans, cfg: ModelConfig) -> dict:
        """`kv_blocks`, and `latent_blocks`: the blocks of the latent pool
        the step's slots hold, summed over the pool's rows
        (`attn_sublayers`: one call of the latent kernel each), which is
        what the kernel reads."""
        kv_blocks = sum(blocks_for(p + 1, self.block_size) for p, _ in spans)
        return dict(self._sublayers(cfg), kv_blocks=kv_blocks,
                    latent_blocks=self.num_layers * kv_blocks)

    def _sublayers(self, cfg: ModelConfig) -> dict:
        """`attn_sublayers` on a dispatch's span, where the pool's rows are
        not the model's layers (two attentions a layer): what the counts
        beside it were summed over."""
        return ({} if self.num_layers == cfg.num_hidden_layers
                else dict(attn_sublayers=self.num_layers))


def init_latent_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                      num_slots: int, max_blocks: int) -> LatentPagedCache:
    """Zeroed latent pool + all-unmapped tables: `latent_row_width` numbers
    a position and attention sublayer, of which kv_lora_rank +
    qk_rope_head_dim are the state."""
    return LatentPagedCache(
        jnp.zeros((cfg.attention_sublayers, num_blocks, block_size,
                   latent_row_width(cfg)), compute_dtype(cfg)),
        jnp.full((num_slots, max_blocks), num_blocks, jnp.int32))


class ShardedPagedKVCache(PagedKVCache):
    """The pool of an engine whose mesh shards it over the KV heads
    (tp > 1 serving): the compiler does not partition a Pallas call, so
    every step attends the gathered view and writes by the scatter, both
    of which it does partition."""

    def attend(self, li, q, q_pos):
        return _cached_attention(q, *self.layer_view(li), q_pos)

    @scope("kv_write")
    def write(self, li, k_new, v_new, q_pos, ring: bool = False):
        return self._scatter(li, k_new, v_new, *_slots_of(
            self.tables, q_pos, k_new.shape[0], self.block_size,
            self.num_blocks, ring))


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     num_slots: int, max_blocks: int) -> PagedKVCache:
    """Zeroed pool + all-unmapped tables. Pool memory is
    L * num_blocks * block_size * Hkv * D * 2 tensors — sized by the
    blocks provisioned, independent of num_slots * max_length."""
    shape = (cfg.num_key_value_heads, cfg.num_hidden_layers, num_blocks,
             block_size, cfg.head_dim)
    dt = compute_dtype(cfg)
    tables = jnp.full((num_slots, max_blocks), num_blocks, jnp.int32)
    return PagedKVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt), tables)


# The decode kernel double-buffers a chunk of pages of K and of V for every
# KV head of a slot in VMEM: `pages_per_chunk` for a cache whose model has
# many KV heads is what fits this many bytes (64 pages, the kernel's own
# default, at up to 8 heads of 128 in bfloat16; 16 at EvaByte's 32).
KERNEL_VMEM_BYTES = 8 << 20


class EvaPagedCache(PagedKVCache):
    """The serving cache of a model with EVA attention (ops/eva.py): the
    pool and the tables of `PagedKVCache`, and a LAW for what a slot's
    table row holds. A summary row `(k~, v~)` has the shape of a K/V row,
    so both kinds live in the one pool, and a slot's table is two regions:

    - entries `[0, n_sum)`: summary blocks. The summary of chunk C (global
      index, `position // chunk_size`) is row `C`: entry `C // block_size`,
      offset `C % block_size`. A block is mapped once the chunk that
      starts it is complete, and stays.
    - entries `[n_sum, n_sum + window_size / block_size)`: the open
      window's blocks. Position `i` is row `i % window_size` of the
      region: a new window overwrites the rows of the one before it in
      place (the blocks are recycled, not returned), and a slot never
      holds more than one window of positions.

    `n_sum` is what the summaries of `max_model_len` positions fill (the
    width of the table less the window's entries). A chunk is summarised
    when its last position is written (`write`): by a prefill chunk from
    its own rows, by a decode step from the chunk's rows in the pool (a
    decode step whose positions end no chunk, in any slot, reads no chunk
    and pools nothing: the step's positions decide, `write`); a
    partial chunk is in no summary. So closing a window takes no work at
    all, on the host or on the device: its summaries are there, and what
    changes is what the next query may see. That is the rows below ONE
    length of a table whose entries are `[the closed windows' summary
    blocks | the open window's blocks]` (a window's summaries are whole
    blocks: `config.check_eva_serving`), which `attend` builds from the
    slot's row and hands, with that length, to the programs every other
    model's cache uses: the decode kernel on a chip (the entries below
    each slot's length, read in place), the tiled walk everywhere else.
    """

    def _summary_entries(self, cfg: ModelConfig) -> int:
        return self.tables.shape[1] - cfg.window_size // self.block_size

    def _window_rows(self, pos, cfg: ModelConfig):
        """The table-wide row of each position's K/V (-1 stays -1)."""
        first = self._summary_entries(cfg) * self.block_size
        return jnp.where(pos >= 0, first + pos % cfg.window_size, -1)

    def write(self, li, k_new, v_new, q_pos, mu, phi,
              cfg: ModelConfig) -> "EvaPagedCache":
        """K/V [B, s, Hkv, D] of positions q_pos into the open window's
        rows of layer li, then the summary of every chunk those positions
        complete into its summary row. s > 1 (a prefill chunk, which
        starts on a chunk boundary): the chunks of the segment itself,
        whole where their last position is a real one. s == 1 (a decode
        step): the chunk the position ends, from the rows the pool holds
        now, read and pooled only in a step where some slot's position
        ends a chunk (one test of the step's positions; every other step
        writes the row and nothing else). Dropped where
        `PagedKVCache.write` drops."""
        b, s = k_new.shape[:2]
        c = cfg.chunk_size
        if q_pos.ndim == 1:
            q_pos = jnp.broadcast_to(q_pos[None, :], (b, s))
        if s > 1:
            cache = self._write_blocks(li, k_new, v_new, q_pos, cfg)
            ks, vs = chunk_summaries(k_new, v_new, mu, phi, c)
            last = q_pos[:, c - 1::c]                           # [B, s // c]
        else:
            cache = PagedKVCache.write(self, li, k_new, v_new,
                                       self._window_rows(q_pos, cfg))
            last = jnp.where((q_pos + 1) % c == 0, q_pos, -1)   # [B, 1]
            # where the chunk's rows lie is worked out here and held here:
            # left to itself the compiler sinks the table's gather into the
            # branch below, the conditional becomes a reader of the table
            # that nothing hoists, and the table's other gathers (the rows
            # a query may see, `attend`: the same for every layer) then
            # stay in the layer loop, one a layer for one a step
            at = jnp.maximum(q_pos, c - 1) - (c - 1) + jnp.arange(c)[None, :]
            phys, off = jax.lax.optimization_barrier(_slots_of(
                cache.tables, self._window_rows(at, cfg),
                b, self.block_size, self.num_blocks))

            def summaries():
                hkv = self.k.shape[0]
                # clamped into the pool: an unmapped row's summary is dropped
                ph = jnp.broadcast_to(jnp.minimum(phys, self.num_blocks - 1),
                                      (hkv,) + phys.shape)
                of = jnp.broadcast_to(off, (hkv,) + off.shape)
                rows = jax.vmap(lambda pool, p, o: pool[li, p, o])

                def chunk_of(pool):  # [Hkv, B, c, D] -> [B, 1, c, Hkv, D]
                    return rows(pool, ph, of).transpose(1, 2, 0, 3)[:, None]

                return eva_summarise(chunk_of(cache.k), chunk_of(cache.v),
                                     mu, phi)

            # the pools are read inside the conditional and never written
            # or returned by it: what comes out is the two summary rows
            # (zeros from a step that closes nothing, which the write below
            # drops with every `last` at -1)
            ks, vs = jax.lax.cond(
                jnp.any(last >= 0), summaries,
                lambda: (jnp.zeros(k_new.shape, cache.k.dtype),
                         jnp.zeros(v_new.shape, cache.v.dtype)))
        return PagedKVCache.write(cache, li, ks, vs,
                                  jnp.where(last >= 0, last // c, -1))

    @scope("kv_write")
    def _write_blocks(self, li, k_new, v_new, q_pos,
                      cfg: ModelConfig) -> "EvaPagedCache":
        """A prefill chunk's K/V [B, s, Hkv, D] a BLOCK at a time: the
        chunk starts on a block boundary and is whole blocks long
        (`config.check_eva_serving`), so the scatter's window is a block
        of `[block_size, D]` and not a row of `D`. The scatter costs by its
        windows (70 ns each on a v5e), and with a KV head a query head a
        16-row chunk of 256 positions is 131,072 rows a tensor and layer,
        19 ms of a layer's 42; a block at a time it is 8,192. A block is
        written where its first position is a real one; what its rows past
        the chunk's last real position then hold (padding's K/V) lies
        beyond every query's length until the position that owns the row
        writes it."""
        b, s, hkv, d = k_new.shape
        bs = self.block_size
        rows = self._window_rows(q_pos[:, ::bs], cfg)            # [B, s / bs]
        phys, _ = _slots_of(self.tables, rows, b, bs, self.num_blocks)
        phys = jnp.broadcast_to(phys, (hkv,) + phys.shape)
        put = jax.vmap(  # on one head's [L, num_blocks, block_size, D]
            lambda pool, new, ph: pool.at[li, ph].set(
                new.reshape(b, s // bs, bs, d), mode="drop"),
            in_axes=(0, 2, 0))
        return self._replace(k=put(self.k, k_new, phys),
                             v=put(self.v, v_new, phys))

    def attend(self, li, q, q_pos, cfg: ModelConfig):
        """Attention of q [B, s, Hq, D] at positions q_pos (one window a
        row: a prefill chunk never straddles one) over what each may see
        of layer li."""
        b, s = q.shape[:2]
        w, bs, width = cfg.window_size, self.block_size, self.tables.shape[1]
        per_window = w // cfg.chunk_size           # summaries a window
        if q_pos.ndim == 1:
            q_pos = jnp.broadcast_to(q_pos[None, :], (b, s))
        window = jnp.max(jnp.maximum(q_pos, 0), axis=1) // w        # [B]
        closed = (window * (per_window // bs))[:, None]   # summary blocks seen
        # the table whose rows below one length are what a query sees: the
        # closed windows' summary blocks, then the open window's blocks
        e = jnp.arange(width)[None, :]
        src = jnp.where(e < closed, e, self._summary_entries(cfg) + e - closed)
        seen = jnp.where(
            src < width,
            jnp.take_along_axis(self.tables, jnp.minimum(src, width - 1), 1),
            self.num_blocks)
        at = jnp.where(q_pos >= 0,
                       window[:, None] * per_window + q_pos % w, -1)
        if decode_kernel_suits(q, self.k):
            hkv, _, _, _, d = self.k.shape
            page = hkv * bs * d * self.k.dtype.itemsize   # a block's K
            out = paged_decode_attention(
                q[:, 0], self.k, self.v, li, seen,
                jnp.maximum(at[:, 0] + 1, 0),
                pages_per_chunk=max(1, KERNEL_VMEM_BYTES // (4 * page)))
            return out[:, None]
        return MixedPagedKVCache._tiled(
            PagedKVCache(self.k, self.v, seen), li, q, at, None)

    # -- what the serving engine asks (see `PagedKVCache`)

    def scheduler_args(self, cfg: ModelConfig) -> dict:
        return dict(summary=(cfg.window_size, cfg.chunk_size))

    def slot_rows(self, st, cfg: ModelConfig, slot: int) -> tuple:
        """The summary blocks first, the open window's blocks after the
        summary region."""
        row = _table_row(self.table_specs[0], st.blocks if st else (),
                         first=self._summary_entries(cfg))
        if st:
            row[:len(st.sblocks)] = st.sblocks
        return (row,)

    def blocks_read(self, n: int, cfg: ModelConfig) -> int:
        """The closed windows' summary blocks and what the open window
        fills."""
        w, c = cfg.window_size, cfg.chunk_size
        closed = (n - 1) // w
        return (blocks_for(closed * (w // c), self.block_size)
                + blocks_for(n - closed * w, self.block_size))

    def prefill_counts(self, spans, cfg: ModelConfig, rows=None) -> dict:
        """`eva_summaries_written`: chunks the rows' positions complete, a
        summary row a layer each; `eva_windows_closed`: windows they
        complete."""
        w, c = cfg.window_size, cfg.chunk_size
        return dict(
            eva_summaries_written=self.num_layers * sum(
                (p + n) // c - p // c for p, n in spans),
            eva_windows_closed=sum((p + n) // w - p // w for p, n in spans))

    def decode_counts(self, spans, cfg: ModelConfig) -> dict:
        """`kv_blocks`, what the dispatch's positions complete
        (`prefill_counts`), and at its first token, over slots and layers:
        `eva_summary_blocks` + `eva_window_blocks` = `eva_blocks_read`,
        what the step's attention reads, and `eva_blocks_full_attention`,
        what full attention over the same lengths would. `eva_steps`: the
        dispatch's steps in which some slot still has a token to emit;
        `eva_steps_summarising`: those of them in which the program reads
        chunks and pools them (`write`), because some slot's position ends
        a chunk (the program advances every live slot a position a step,
        whatever the slot has left to emit)."""
        w, c, bs = cfg.window_size, cfg.chunk_size, self.block_size
        layers = self.num_layers
        summary = sum(blocks_for(p // w * (w // c), bs) for p, _ in spans)
        both = sum(self.blocks_read(p + 1, cfg) for p, _ in spans)
        steps = max((n for _, n in spans), default=0)
        return dict(
            kv_blocks=both, **self.prefill_counts(spans, cfg),
            eva_steps=steps,
            eva_steps_summarising=sum(
                any((p + j + 1) % c == 0 for p, _ in spans)
                for j in range(steps)),
            eva_summary_blocks=layers * summary,
            eva_window_blocks=layers * (both - summary),
            eva_blocks_read=layers * both,
            eva_blocks_full_attention=layers * sum(
                blocks_for(p + 1, bs) for p, _ in spans))


def eva_table_width(cfg: ModelConfig, max_len: int, block_size: int) -> int:
    """Entries of an `EvaPagedCache` table row: the summary blocks of
    max_len positions, then one window's blocks."""
    return (-(-(max_len // cfg.chunk_size) // block_size)
            + cfg.window_size // block_size)


def init_eva_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                   num_slots: int, max_len: int) -> EvaPagedCache:
    """Zeroed pool + all-unmapped tables of `eva_table_width` entries."""
    return EvaPagedCache(*init_paged_cache(
        cfg, num_blocks, block_size, num_slots,
        eva_table_width(cfg, max_len, block_size)))


def _finite(x):
    """x with zeros where it is not finite."""
    return jnp.where(jnp.isfinite(x), x, 0)


class HybridPagedCache(NamedTuple):
    """The serving cache of a model whose layers are recurrent mixers and
    full attentions side by side: two kinds of state, one of them not
    addressed by position. Described for Gated DeltaNet mixers (Qwen3-Next);
    a model of Mamba mixers (Jamba) is held the same way, with `state`
    [L_ssm, slots, d_state, d_inner] float32 (a channel's states down the
    sublanes: ops/selective_scan.py), the same one-column table, the same
    rule of a start from zeros at position 0 and of rows that write nothing,
    and `scan` where the other's recurrence is `recur`: a decode step on a
    chip ONE kernel over the pool in place, the live rows' states alone
    (`ops.selective_scan.selective_scan_step_pooled`; the convolution before
    it likewise over the tail pool, `conv_step_pooled`), and so is a prefill
    chunk (`selective_scan_chunk_pooled`: a row's state comes into VMEM a
    block of channels at a time, is carried in registers across the chunk's
    positions and goes back once; a rung's pad rows are skipped); the tiny
    test models and every CPU run gather the rows, scan token by token and
    scatter them back. The full layers keep the pool and
    tables of `PagedKVCache`, with only those layers in the pool's layer
    axis (the arrangement of `MixedPagedKVCache`'s full half; a layer's
    row is `ki`). The mixers keep a row a SLOT: `state` [L_gdn, slots, Hv,
    d_k, d_v] float32, the matrix the gated delta rule carries from token
    to token, and `tail` [L_gdn, slots, (kernel - 1) x channels], the
    convolution's last inputs as one row (`models.llama.gdn_mixer`);
    `stables` [B, 1] maps a row of the dispatch
    to its slot's row of both (`slots` itself: unmapped, the write drops).
    A state row is the slot's for good, so admission allocates nothing for
    it, and nothing on the host ever resets one: what a sequence carries
    into position 0 is zeros whatever the row holds (`state_of`, `tail_of`,
    the kernel's `fresh`), so a slot's next request, a request resumed after
    a preemption (its prefill starts again at 0) and a decode dispatch still
    in flight for a request that has left cannot leak into the sequence
    that follows. A row that holds no real position in a dispatch (a
    padding row, an idle slot) writes nothing (`put_state`, `put_tail`, the
    kernel's `live`). `generate._decode_layers` calls `write` / `attend` with
    `ki` on a full layer; on a mixer `tail_of(gi, q_pos)` before the
    convolution, `recur(gi, q, k, v, g, beta, q_pos)` for the recurrence and
    `put_tail(gi, tail, q_pos)` after. `recur` answers for the state, and on
    a chip the pool never leaves its place: a decode step is ONE kernel over
    it (`ops.gated_delta.gated_delta_step_pooled`: the live rows' matrices
    are read once and written once where they lie, an idle row costs
    nothing), and so is a prefill chunk (`gated_delta_chunk_pooled`: a row's
    state comes into VMEM once, stays there across the chunk's sub-chunks
    and goes back once; a rung's pad rows are skipped). The tiny test
    models and every CPU run gather the rows, run the plain rule and scatter
    them back (`state_of` -> `ops.gated_delta.gated_delta` -> `put_state`).
    The tail (96 KiB a row) moves through one product a mixer each way
    with the rows' one-hot map (`tail_of` / `put_tail`), whatever the rows;
    a Mamba mixer's, kept in its kernel's rows of lanes, by index.
    A hybrid pairs its state rows with ONE of two kinds of pool for its full
    layers: this K/V pool, or a latent pool (`HybridLatentPagedCache`, the
    sibling below, which takes this class's state half as it is and is where
    a Kimi Delta Attention mixer's rows live: `recur` takes a decay a channel
    of the key as it takes a decay a head)."""

    k: jnp.ndarray        # [Hkv, L_full, num_blocks, block_size, D]
    v: jnp.ndarray
    state: jnp.ndarray    # [L_gdn, slots, Hv, d_k, d_v] float32
    tail: jnp.ndarray     # [L_gdn, slots, (kernel - 1) x channels] float32
    tables: jnp.ndarray   # [B, max_blocks]; num_blocks = unmapped
    stables: jnp.ndarray  # [B, 1]; slots = unmapped

    @property
    def num_layers(self) -> int:
        return self.k.shape[1] + self.state.shape[0]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def _kv(self) -> PagedKVCache:
        return PagedKVCache(self.k, self.v, self.tables)

    def write(self, li, k_new, v_new, q_pos, window=None,
              ki=None) -> "HybridPagedCache":
        c = self._kv.write(ki, k_new, v_new, q_pos)
        return self._replace(k=c.k, v=c.v)

    def attend(self, li, q, q_pos, window=None, ki=None):
        """Attention of q [B, s, Hq, D] over full layer `ki`: a decode step
        on a chip reads the slot's blocks in place, everything else walks
        the keys in tiles as far as the batch's longest row reaches (a
        row's whole view would be `max_model_len` positions wide)."""
        c = self._kv
        b, s = q.shape[:2]
        if q_pos.ndim == 1:
            q_pos = jnp.broadcast_to(q_pos[None, :], (b, s))
        with scope("attn_full"):
            if decode_kernel_suits(q, c.k):
                out = paged_decode_attention(
                    q[:, 0], c.k, c.v, ki, c.tables,
                    jnp.maximum(q_pos[:, 0] + 1, 0))
                return out[:, None]
            return MixedPagedKVCache._tiled(c, ki, q, q_pos, None)

    def _carried(self, pool, gi, q_pos):
        """Mixer gi's rows of `pool` that the dispatch's rows carry into
        positions q_pos [B, s]: their slots', zeros where a row starts at
        position 0."""
        rows = jnp.minimum(self.stables[:, 0], pool.shape[1] - 1)  # unmapped: discarded
        fresh = q_pos[:, 0] == 0
        x = pool[gi, rows]
        return jnp.where(fresh.reshape((-1,) + (1,) * (x.ndim - 1)), 0, x)

    def _carry_on(self, pool, gi, x, q_pos):
        """`pool` with what the rows carry on in mixer gi's rows of their
        slots; dropped for a row without a real position and for an
        unmapped one."""
        rows = jnp.where(jnp.any(q_pos >= 0, axis=1), self.stables[:, 0],
                         pool.shape[1])
        return pool.at[gi, rows].set(x.astype(pool.dtype), mode="drop")

    def state_of(self, gi, q_pos):
        """The state [B, Hv, d_k, d_v] the rows carry into q_pos [B, s]."""
        return self._carried(self.state, gi, q_pos)

    def put_state(self, gi, state, q_pos) -> "HybridPagedCache":
        return self._replace(state=self._carry_on(self.state, gi, state, q_pos))

    # The tails' moves. Gathered and scattered by index the compiler walks a
    # mixer's rows one after the other (a dynamic-update-slice and a select
    # a slot, whatever is live: at 64 slots of 144 KiB 4.5 of a decode
    # step's 18.1 ms, twice the recurrence's own kernel; PERF.md section 6,
    # PR 57). So a mixer's whole plane [slots, W] goes through ONE product
    # with the rows' one-hot map [B, slots] each way (float32 at the highest
    # precision: a one times a value and zeros, which is the value to the
    # bit) and one select, whatever the rows. A product sums over every
    # slot, and 0 x nan is nan: what is not finite is zeroed on its way in,
    # so that one request's fault stays in its own row (its state keeps it).
    # A tail kept in a kernel's rows of 128 lanes (a Mamba mixer's, [slots,
    # rows, 128]: its decode step is `conv_step_pooled` in place, and only a
    # prefill chunk's few rows come here) is no matrix without a re-laying
    # of the whole pool (`tests/test_chip_compile.py` finds that copy), and
    # goes by index.
    def _hot(self, keep):
        """[B, slots] bool: row b's slot, for the rows of `keep` [B] that
        are mapped."""
        return (self.stables[:, :1] == jnp.arange(self.tail.shape[1])) & keep[:, None]

    def tail_of(self, gi, q_pos):
        """The tail [B, ...] the rows carry into q_pos: their slots', zeros
        where a row starts at position 0."""
        if self.tail.ndim != 3:
            return self._carried(self.tail, gi, q_pos)
        plane = jax.lax.dynamic_index_in_dim(self.tail, gi, 0, keepdims=False)
        return jnp.matmul(self._hot(q_pos[:, 0] != 0).astype(plane.dtype),
                          _finite(plane), precision=jax.lax.Precision.HIGHEST)

    def put_tail(self, gi, tail, q_pos) -> "HybridPagedCache":
        """The tail pool with what the rows carry on in mixer gi's rows of
        their slots; a row without a real position, or an unmapped one,
        writes nothing."""
        if self.tail.ndim != 3:
            return self._replace(tail=self._carry_on(self.tail, gi, tail, q_pos))
        hot = self._hot(jnp.any(q_pos >= 0, axis=1))
        plane = jax.lax.dynamic_index_in_dim(self.tail, gi, 0, keepdims=False)
        new = jnp.matmul(hot.T.astype(plane.dtype), _finite(tail.astype(plane.dtype)),
                         precision=jax.lax.Precision.HIGHEST)
        plane = jnp.where(jnp.any(hot, axis=0)[:, None], new, plane)
        return self._replace(tail=jax.lax.dynamic_update_index_in_dim(
            self.tail, plane, gi, 0))

    def recur(self, gi, q, k, v, g, beta, q_pos):
        """The gated delta rule over the segment (q, k [B, s, Hk, d_k], a
        row a KEY head; v [B, s, Hv, d_v]; g, beta [B, s, Hv]; q_pos [B, s])
        from mixer gi's state of the rows' slots -> (o [B, s, Hv, d_v], the
        cache with the state after it). A decode step and a
        prefill chunk that their kernels suit update the pool in place, the
        rows with a real position and a mapped slot alone; everything else
        gathers, runs the plain rule and scatters. g [B, s, Hv, d_k], a
        decay a CHANNEL (a Kimi Delta Attention mixer's): the decode step's
        kernel takes it as it takes the other, and a prefill chunk has a
        kernel of its own (`ops.kda.kda_chunk_pooled`, asked for by
        `kda_chunk_suits`; `g.ndim` tells the two rules apart); what it
        refuses gathers, runs `ops.kda.kda_chunked` and scatters."""
        where = (self.state, gi, self.stables[:, 0],
                 jnp.any(q_pos >= 0, axis=1), q_pos[:, 0] == 0)
        if gated_delta_kernel_suits(q.shape[1], self.state):
            q, k = (per_value_head(x[:, 0], v.shape[2]) for x in (q, k))
            o, state = gated_delta_step_pooled(
                q, k, v[:, 0], g[:, 0], beta[:, 0], *where)
            return o[:, None], self._replace(state=state)
        if g.ndim == beta.ndim and gated_delta_chunk_suits(
                q.shape[1], q.shape[2], self.state):
            o, state = gated_delta_chunk_pooled(q, k, v, g, beta, *where)
            return o, self._replace(state=state)
        if g.ndim == q.ndim and kda_chunk_suits(
                q.shape[1], q.shape[2], self.state):
            o, state = kda_chunk_pooled(q, k, v, g, beta, *where)
            return o, self._replace(state=state)
        o, state = delta_rule(q, k, v, g, beta, self.state_of(gi, q_pos))
        return o, self.put_state(gi, state, q_pos)

    def conv(self, gi, x, w, bias, n_valid, moves, q_pos):
        """`models.llama.mamba_mixer`'s convolution (x [B, s, d_inner]; w
        [d_inner, kernel]; q_pos [B, s]) from Mamba mixer gi's tail of the
        rows' slots -> (u [B, s, d_inner], the cache with the tail after
        it). A decode step that the kernel suits updates the tail pool in
        place, the rows with a token and a mapped slot alone; everything else
        gathers the rows' tails, convolves and scatters (`conv_through`)."""
        if conv_kernel_suits(x.shape[1], self.tail):
            with scope(moves):
                u, tail = conv_step_pooled(
                    x[:, 0], w, bias, self.tail, gi, self.stables[:, 0],
                    q_pos[:, 0] >= 0, q_pos[:, 0] == 0)
            return u[:, None], self._replace(tail=tail)
        return conv_through(self, gi, x, w, bias, n_valid, moves, q_pos)

    def scan(self, gi, u, dt, b, c, a, q_pos):
        """The selective scan over the segment (u, dt [B, s, d_inner]; b, c
        [B, s, d_state]; a [d_state, d_inner]; q_pos [B, s]) from Mamba mixer
        gi's state of the rows' slots -> (y [B, s, d_inner], the cache with
        the state after it). A decode step and a prefill chunk that their
        kernels suit update the pool in place, the rows with a real position
        and a mapped slot alone; everything else gathers, runs the plain
        rule and scatters."""
        real = q_pos >= 0
        if ssm_kernel_suits(u.shape[1], self.state):
            y, state = selective_scan_step_pooled(
                u[:, 0], dt[:, 0], b[:, 0], c[:, 0], a, self.state, gi,
                self.stables[:, 0], real[:, 0], q_pos[:, 0] == 0)
            return y[:, None], self._replace(state=state)
        if ssm_chunk_suits(u.shape[1], self.state):
            y, state = selective_scan_chunk_pooled(
                u, dt, b, c, a, self.state, gi, self.stables[:, 0],
                jnp.sum(real, axis=1, dtype=jnp.int32), q_pos[:, 0] == 0)
            # the kernel writes y where a real position is, and nowhere else
            return (jnp.where(real[..., None], y, 0.0),
                    self._replace(state=state))
        y, state = scan_segment(u, dt, b, c, a, self.state_of(gi, q_pos))
        return y, self.put_state(gi, state, q_pos)

    def ssd(self, gi, v, g, b, c, q_pos):
        """`models.llama.mamba2_mixer`'s recurrence over the segment (v [B,
        s, H, P]; g [B, s, H]; b, c [B, s, G, N]; q_pos [B, s]) from Mamba-2
        mixer gi's state [H, P, N] of the rows' slots -> (y [B, s, H, P], the
        cache with the state after it). A decode step and a prefill chunk
        that their kernels suit update the pool in place, the rows with a
        real position and a mapped slot alone (`ops.ssd.ssd_step_pooled`,
        `ssd_chunk_pooled`); everything else gathers, runs the plain rule and
        scatters. The convolution before it is `conv`, as a Mamba-1 mixer's."""
        where = (self.state, gi, self.stables[:, 0],
                 jnp.any(q_pos >= 0, axis=1), q_pos[:, 0] == 0)
        if ssd_kernel_suits(v.shape[1], self.state):
            y, state = ssd_step_pooled(v[:, 0], g[:, 0], b[:, 0], c[:, 0],
                                       *where)
            return y[:, None], self._replace(state=state)
        if ssd_chunk_suits(v.shape[1], b.shape[2], self.state):
            y, state = ssd_chunk_pooled(v, g, b, c, *where)
            return y, self._replace(state=state)
        y, state = ssd(v, g, b, c, self.state_of(gi, q_pos))
        return y, self.put_state(gi, state, q_pos)

    # -- what the serving engine asks (see `PagedKVCache`)

    @classmethod
    def of(cls, pools, tables):
        return cls(*pools, *tables)

    @property
    def pools(self) -> tuple:
        return self.k, self.v, self.state, self.tail

    @property
    def table_specs(self) -> tuple:
        return ((self.tables.shape[1], self.k.shape[2]),
                (1, self.state.shape[1]))

    scheduler_args = PagedKVCache.scheduler_args  # a state row is the slot's

    def slot_rows(self, st, cfg: ModelConfig, slot: int) -> tuple:
        full, state = self.table_specs
        return (_table_row(full, st.blocks if st else ()),
                _table_row(state, (slot,) if st else ()))

    blocks_read = PagedKVCache.blocks_read

    def state_row_bytes(self) -> int:
        """Bytes of one slot's state and tail of one mixer."""
        return sum(int(np.prod(x.shape[2:])) * x.dtype.itemsize
                   for x in (self.state, self.tail))

    def _state_counts(self, rows: int, resets: int) -> dict:
        """`state_rows`: (slot, mixer) pairs whose state and tail a dispatch
        reads and writes (every step of a decode dispatch again);
        `state_bytes`: their bytes, both ways; `state_resets`: those of
        them that start from zeros."""
        n = self.state.shape[0]
        return dict(state_rows=n * rows,
                    state_bytes=2 * n * rows * self.state_row_bytes(),
                    state_resets=n * resets)

    def _chunk_counts(self, spans, rows) -> dict:
        """The state's counts of a prefill dispatch and its rung's
        `chunk_rows_batch` / `chunk_rows_idle` (`prefill_counts`)."""
        batch = self.state.shape[0] * (len(spans) if rows is None else rows)
        real = self.state.shape[0] * sum(n > 0 for _, n in spans)
        return dict(
            **self._state_counts(len(spans), sum(p == 0 for p, _ in spans)),
            chunk_rows_batch=batch, chunk_rows_idle=batch - real)

    def _step_counts(self, spans) -> dict:
        """The state's counts a step of a decode dispatch and its batch's
        `state_rows_batch` / `state_rows_idle` (`decode_counts`)."""
        counts = self._state_counts(len(spans), 0)
        batch = self.state.shape[0] * self.stables.shape[0]
        return dict(**counts, state_rows_batch=batch,
                    state_rows_idle=batch - counts["state_rows"])

    def prefill_counts(self, spans, cfg: ModelConfig, rows=None) -> dict:
        """The state's counts of the dispatch, and what the prefill program's
        rung holds: `chunk_rows_batch` ((row, mixer) pairs, a row with a
        request or a pad row) and `chunk_rows_idle` (those of them without
        a real position, which the chunk's kernel skips); for a model of
        Mamba or Mamba-2 mixers `scan_tokens` too."""
        counts = self._chunk_counts(spans, rows)
        if cfg.ssm or cfg.ssd:
            # (position, mixer) pairs with a token: what a selective scan
            # runs over, one after the other, whatever the rung pads
            counts["scan_tokens"] = self.state.shape[0] * sum(
                n for _, n in spans)
        return counts

    def decode_counts(self, spans, cfg: ModelConfig) -> dict:
        """`kv_blocks` (what one full layer's kernel reads),
        `kv_blocks_banded` (summed over the full layers, the only ones that
        read any: the name `MixedPagedKVCache` gives the same sum), the
        state's counts a step of the dispatch, and beside `state_rows` what
        the decode program's batch holds: `state_rows_batch` ((row, mixer)
        pairs, a row a slot, live or idle) and `state_rows_idle` (those of
        them without a token, whose state a step leaves where it lies)."""
        kv = PagedKVCache.decode_counts(self, spans, cfg)["kv_blocks"]
        return dict(kv_blocks=kv, kv_blocks_banded=self.k.shape[1] * kv,
                    **self._step_counts(spans))


def init_hybrid_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                      num_slots: int, max_blocks: int) -> HybridPagedCache:
    """Zeroed pools + all-unmapped tables: the K/V pool over the full
    layers alone, a state row and a tail row a slot and mixer, shaped from
    the model's own start state (`models.llama.recurrent_start`)."""
    n_rec = cfg.recurrent_layers
    dt = compute_dtype(cfg)
    # (the layers that hold an attention: a layer's row is its ordinal among
    # them, and neither a mixer nor a layer that is the experts alone has one)
    shape = (cfg.num_key_value_heads, cfg.attention_sublayers,
             num_blocks, block_size, cfg.head_dim)
    state, tail = recurrent_start(cfg, num_slots)
    return HybridPagedCache(
        jnp.zeros(shape, dt), jnp.zeros(shape, dt),
        jnp.zeros((n_rec,) + state.shape, state.dtype),
        jnp.zeros((n_rec,) + tail.shape, tail.dtype),
        jnp.full((num_slots, max_blocks), num_blocks, jnp.int32),
        jnp.full((num_slots, 1), num_slots, jnp.int32))


class HybridLatentPagedCache(NamedTuple):
    """`HybridPagedCache` for a model whose full layers are LATENT
    attentions (Kimi-Linear: Kimi Delta Attention mixers beside MLA): the
    two kinds of pool a hybrid may pair its state rows with are a K/V pool
    (`HybridPagedCache`) and, here, a latent pool. The attention half is
    `LatentPagedCache`'s pool and table, `kv` [L_full, num_blocks,
    block_size, W] with only the full layers in its leading axis (a layer's
    row is `ki`), written and attended by that class's own methods (the
    decode step's `latent_decode_attention`, a prefill chunk's
    `latent_prefill_attention`, tiles off a chip). The state half is
    `HybridPagedCache`'s, field for field and method for method (`state`
    [L_kda, slots, H, d_k, d_v] float32, `tail` [L_kda, slots, (kernel - 1)
    x channels] float32, `stables`; a start from zeros at position 0, rows
    without a real position write nothing, `recur` answers for the state:
    a decode step on a chip is ONE kernel over the pool in place,
    `kda_step_pooled` in a trace, and so is a prefill chunk,
    `kda_chunk_pooled`). `generate._decode_layers` calls `write(li,
    ckr, q_pos, ki=)` / `attend(li, q_n, q_r, q_pos, kv_b, cfg, ki=)` on a
    full layer and `tail_of` / `recur` / `put_tail` on a mixer. A dispatch's
    span carries both halves' counts: `latent_blocks` / `latent_keys` over
    the full layers alone, `state_rows` / `state_bytes` / `state_resets`
    and the rung's `chunk_rows_*` / `state_rows_*` over the mixers."""

    kv: jnp.ndarray       # [L_full, num_blocks, block_size, W]
    state: jnp.ndarray    # [L_kda, slots, H, d_k, d_v] float32
    tail: jnp.ndarray     # [L_kda, slots, (kernel - 1) x channels] float32
    tables: jnp.ndarray   # [B, max_blocks]; num_blocks = unmapped
    stables: jnp.ndarray  # [B, 1]; slots = unmapped

    @property
    def num_layers(self) -> int:
        return self.kv.shape[0] + self.state.shape[0]

    @property
    def block_size(self) -> int:
        return self.kv.shape[2]

    @property
    def _latent(self) -> LatentPagedCache:
        return LatentPagedCache(self.kv, self.tables)

    def write(self, li, ckr_new, q_pos, ki=None) -> "HybridLatentPagedCache":
        return self._replace(kv=self._latent.write(ki, ckr_new, q_pos).kv)

    def attend(self, li, q_n, q_r, q_pos, kv_b, cfg: ModelConfig, ki=None):
        return self._latent.attend(ki, q_n, q_r, q_pos, kv_b, cfg)

    # the state half, as `HybridPagedCache` has it
    _carried = HybridPagedCache._carried
    _carry_on = HybridPagedCache._carry_on
    state_of = HybridPagedCache.state_of
    put_state = HybridPagedCache.put_state
    recur = HybridPagedCache.recur
    _hot = HybridPagedCache._hot
    tail_of = HybridPagedCache.tail_of
    put_tail = HybridPagedCache.put_tail
    state_row_bytes = HybridPagedCache.state_row_bytes
    _state_counts = HybridPagedCache._state_counts
    _chunk_counts = HybridPagedCache._chunk_counts
    _step_counts = HybridPagedCache._step_counts

    # -- what the serving engine asks (see `PagedKVCache`)

    of = classmethod(HybridPagedCache.of.__func__)

    @property
    def pools(self) -> tuple:
        return self.kv, self.state, self.tail

    @property
    def table_specs(self) -> tuple:
        return ((self.tables.shape[1], self.kv.shape[1]),
                (1, self.state.shape[1]))

    scheduler_args = PagedKVCache.scheduler_args
    slot_rows = HybridPagedCache.slot_rows

    def prefill_counts(self, spans, cfg: ModelConfig, rows=None) -> dict:
        """`LatentPagedCache`'s `latent_keys` over the full layers
        (`attn_sublayers` says how many) beside `HybridPagedCache`'s state
        and rung counts over the mixers."""
        return dict(self._latent.prefill_counts(spans, cfg),
                    **self._chunk_counts(spans, rows))

    def decode_counts(self, spans, cfg: ModelConfig) -> dict:
        """`LatentPagedCache`'s `kv_blocks` and `latent_blocks` (summed
        over the full layers, each one call of the latent kernel) beside
        `HybridPagedCache`'s state counts a step of the dispatch."""
        return dict(self._latent.decode_counts(spans, cfg),
                    **self._step_counts(spans))


def init_hybrid_latent_cache(cfg: ModelConfig, num_blocks: int,
                             block_size: int, num_slots: int,
                             max_blocks: int) -> HybridLatentPagedCache:
    """Zeroed pools + all-unmapped tables: the latent pool over the full
    layers alone (`cfg.attention_sublayers`), a state row and a tail row a
    slot and mixer."""
    latent = init_latent_cache(cfg, num_blocks, block_size, num_slots,
                               max_blocks)
    state, tail = recurrent_start(cfg, num_slots)
    n_rec = cfg.recurrent_layers
    return HybridLatentPagedCache(
        latent.kv, jnp.zeros((n_rec,) + state.shape, state.dtype),
        jnp.zeros((n_rec,) + tail.shape, tail.dtype), latent.tables,
        jnp.full((num_slots, 1), num_slots, jnp.int32))


def init_serve_cache(cfg: ModelConfig, scfg: ServeConfig, num_slots: int,
                     num_blocks: int, max_len: int, sharded: bool = False):
    """The cache a model is served from, zeroed and all-unmapped: `num_slots`
    slots of up to `max_len` positions over a pool of `num_blocks` blocks
    of `scfg.block_size`. The one place that reads a model's configuration
    for the kind of its serving cache: EVA's pool, a hybrid (recurrent
    mixers: state rows a slot beside a K/V pool, or beside a latent pool
    where the full layers are latent attentions), a latent pool, a K/V pool
    with a ring pool for sliding layers, or the plain K/V pool. `sharded`: a mesh shards the pool
    over its KV heads (tp > 1): attention then keeps the gathered view
    whatever the step, which the compiler partitions, and never the
    in-place kernel, which it does not."""
    bs = scfg.block_size
    max_blocks = blocks_for(max_len, bs)
    if cfg.eva:  # one pool, a table row of two regions
        check_eva_serving(cfg, scfg)
        return init_eva_cache(cfg, num_blocks, bs, num_slots, max_len)
    if cfg.recurrent:  # a state row a slot beside the full layers' pool,
        # which is a K/V pool or (latent attention) a latent pool
        if sharded:
            raise ValueError(
                "a model with linear_attention, kda or mamba layers is "
                "served from one device: the state pool is not sharded "
                "(tp = 1; mamba2 layers are held the same way)")
        init = init_hybrid_latent_cache if cfg.mla else init_hybrid_cache
        return init(cfg, num_blocks, bs, num_slots, max_blocks)
    if cfg.mla:  # one pool with no head axis, sized from the latent's width
        return init_latent_cache(cfg, num_blocks, bs, num_slots, max_blocks)
    if cfg.layer_types is not None:  # a second pool, a ring a slot
        if sharded:
            raise ValueError(
                "a model with sliding-window layers is served from "
                "one device: the two pools are not sharded (tp = 1)")
        ring = min(max_blocks, ring_blocks_for(
            cfg.sliding_window, scfg.prefill_chunk, bs))
        return init_mixed_cache(
            cfg, num_blocks, scfg.num_window_blocks or num_slots * ring, bs,
            num_slots, max_blocks, ring)
    cache = init_paged_cache(cfg, num_blocks, bs, num_slots, max_blocks)
    return ShardedPagedKVCache(*cache) if sharded else cache


class BlockPool:
    """Host-side free-list allocator over the physical blocks.

    All-or-nothing `alloc(n)` (a partially-allocated sequence could never
    run and would strand blocks), LIFO reuse (freshly-freed blocks are the
    ones whose stale contents the causal mask already screens), and peak
    accounting for the pool-utilization telemetry.

    Beside the list a byte a block says whether the block is free, so that
    `free` tells a double free without looking through the list: giving
    blocks back costs their number, whatever the pool holds."""

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))
        self._is_free = bytearray(b"\x01") * num_blocks
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
        for b in out:
            self._is_free[b] = 0
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return out

    def free(self, blocks) -> None:
        """Give `blocks` back, in the order given. An unknown block or one
        that is already free (from an earlier call or earlier in this
        one) raises ValueError and leaves the pool as it was."""
        blocks = list(blocks)
        is_free = self._is_free
        for i, b in enumerate(blocks):
            known = 0 <= b < self.num_blocks
            if not known or is_free[b]:
                for marked in blocks[:i]:
                    is_free[marked] = 0
                raise ValueError(f"double free of block {b}" if known
                                 else f"freeing unknown block {b}")
            is_free[b] = 1
        self._free.extend(blocks)
