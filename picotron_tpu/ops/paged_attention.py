"""Pallas paged attention for the serving decode step: one query position a
slot against the blocks that slot holds, read in place through the block
table.

`PagedKVCache.layer_view` + `generate._cached_attention` gather every slot's
whole `max_blocks * block_size` view out of the pool, live or idle, mapped or
not, and then attend to it: at the chat cell's settings 2 x 67 MB a layer
written and read again, 69% of the decode program's device time, of which the
traffic holds a sixth at the fullest (PERF.md section 6, PR 32). This kernel
reads the `ceil(length / block_size)` blocks of a slot and no other: the
sequence loop is inside the kernel, so a block past a slot's length costs
neither a grid step nor a DMA, and a slot of length 0 (idle, or mid-prefill)
reads nothing.

Design (after the kernel JAX ships,
`jax.experimental.pallas.ops.tpu.paged_attention`, from which the online
softmax over chunks of pages and the double buffer are taken):

- **The pool stays where it is**: `[Hkv, L, num_blocks, block_size, D]` is
  handed over whole, in HBM (`memory_space=ANY`), with the layer index as a
  prefetched scalar; a page is `pool[:, li, page]`, ONE strided DMA for all
  the KV heads of a block (`block_size x D` = one 4 KB bf16 tile a head).
  No reshape of the pool, no layer sliced out, no offset added to the table.
- **Grid (slots,)**, every KV head inside the program, grouped queries: a
  block is fetched once for all the query heads that read it.
- **Chunks of `pages_per_chunk` pages**, double-buffered in VMEM: while a
  chunk is attended the next one's DMAs are in flight. Only the pages below
  the slot's length are fetched (the DMA loops have dynamic trip counts), and
  a table entry is clamped into the pool before it addresses a read: the
  unmapped sentinel `num_blocks` is never a source.
- **Mathematics of `_cached_attention`**, at no lower precision: K/V as
  stored, scores `q k^T / sqrt(D)` accumulated and kept in float32, softmax
  statistics in float32, P cast to the value dtype for the PV matmul with
  float32 accumulation. Positions at or beyond the length are masked by
  position, and V's rows there are zeroed, so whatever a partly filled block
  or a stale buffer holds beyond the length (NaN included) cannot reach the
  output. A slot of length 0 returns zeros.
- **A sliding-window layer** (`window`) reads from the block that holds
  position `length - window` on, not from block 0, and its table is a RING:
  logical block `b` lives at table entry `b % max_blocks`
  (`serve/paged_cache.py`, the window pool). Positions before the band are
  masked by position, as those at or beyond the length are. With
  `window=None` the kernel is the one it was, instruction for instruction.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30
# Pages a chunk: one DMA a page and KV tensor, so a chunk of 64 pages of 16
# positions is 1,024 positions and 2 x 64 DMAs in flight. Swept on the chip
# at the chat cell's shapes over 4 .. 64 (PERF.md section 6, PR 32): the
# kernel is bound by issuing the DMAs (50-65 ns each), and what a larger
# chunk saves is the loop around them.
DEFAULT_PAGES_PER_CHUNK = 64


def compiled_kernels_available() -> bool:
    """`ops.flash_attention`'s answer (a TPU backend), asked at each call:
    the tests that compile for a described chip patch it there."""
    from picotron_tpu.ops.flash_attention import compiled_kernels_available
    return compiled_kernels_available()


def decode_kernel_suits(q, k_pool) -> bool:
    """Whether a step with queries q [B, s, Hq, D] against k_pool
    [Hkv, L, num_blocks, block_size, D] is one the compiled kernel takes:
    a decode step (one query position a slot), the head a whole number of
    128-lane rows, the block a whole number of sublane tiles of the pool's
    dtype (16 rows of bf16, 8 of float32), and a backend that compiles
    Pallas kernels. Everything else keeps the gathered view: prefill
    chunks (more than one query a slot), a pool that a tp mesh shards
    (`ShardedPagedKVCache` never asks), the tiny test models' heads and
    blocks, and every CPU run. The two other cache
    kinds answer for themselves: a model with sliding layers asks this for
    each of its two pools and walks tiles where the answer is no
    (`MixedPagedKVCache`); a latent cache asks `latent_kernel_suits` and
    walks tiles likewise (`LatentPagedCache`)."""
    block_size, d = k_pool.shape[3], k_pool.shape[4]
    sublanes = 8 * 4 // jnp.dtype(k_pool.dtype).itemsize
    return (q.shape[1] == 1 and d % 128 == 0 and block_size % sublanes == 0
            and compiled_kernels_available())


def latent_kernel_suits(s: int, pool, rank: int) -> bool:
    """`decode_kernel_suits` for a latent pool [L, num_blocks, block_size,
    W]: a decode step, the latent (the value: the first `rank` numbers of a
    key) and the padded key both whole 128-lane rows, the block whole
    sublane tiles, a backend that compiles Pallas kernels."""
    block_size, w = pool.shape[2], pool.shape[3]
    sublanes = 8 * 4 // jnp.dtype(pool.dtype).itemsize
    return (s == 1 and rank % 128 == 0 and w % 128 == 0
            and block_size % sublanes == 0 and compiled_kernels_available())


def _kernel(lengths_ref, tables_ref, li_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, *, sm_scale: float, pages_per_chunk: int,
            max_blocks: int, window: Optional[int]):
    b = pl.program_id(0)
    hkv, _, num_blocks, bs, d = k_hbm.shape
    chunk = pages_per_chunk * bs
    li = li_ref[0]
    length = lengths_ref[b]
    if window is None:
        n_pages = jnp.minimum(pl.cdiv(length, bs), max_blocks)
    else:
        # the band's first position and the logical block that holds it;
        # the pages read are that block .. the block of position length - 1
        lo = jnp.maximum(length - window, 0)
        first = lo // bs
        n_pages = jnp.minimum(pl.cdiv(length, bs) - first, max_blocks)
    n_chunks = pl.cdiv(n_pages, pages_per_chunk)

    def copies(c, buf, j):
        # the unmapped sentinel (num_blocks) never addresses a read; no page
        # below a live slot's length is unmapped, so the clamp changes none
        entry = c * pages_per_chunk + j
        if window is not None:
            entry = (first + entry) % max_blocks  # the ring
        page = jnp.minimum(tables_ref[b * max_blocks + entry],
                           num_blocks - 1)
        return (pltpu.make_async_copy(k_hbm.at[:, li, page],
                                      k_buf.at[buf, :, j], sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[:, li, page],
                                      v_buf.at[buf, :, j], sems.at[1, buf]))

    def pages_in(c):
        return jnp.minimum(n_pages - c * pages_per_chunk, pages_per_chunk)

    def each_copy(c, buf, act):
        def one(j, _):
            for cp in copies(c, buf, j):
                act(cp)
        lax.fori_loop(0, pages_in(c), one, None)

    def start(c, buf):
        each_copy(c, buf, lambda cp: cp.start())

    def wait(c, buf):
        each_copy(c, buf, lambda cp: cp.wait())

    @pl.when(n_chunks > 0)
    def _first():
        start(0, 0)

    def body(c, carry):
        buf = c % 2

        @pl.when(c + 1 < n_chunks)
        def _next():
            start(c + 1, 1 - buf)

        wait(c, buf)
        if window is None:
            live = length - c * chunk  # positions of this chunk below the length
            col = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) < live
            row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) < live
        else:
            # the chunk's element i holds position at0 + i: inside the band
            # where lo <= position < length
            at0 = first * bs + c * chunk
            col = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) + at0
            col = (col >= lo) & (col < length)
            row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) + at0
            row = (row >= lo) & (row < length)
        out = []
        for h in range(hkv):
            m_prev, l_prev, acc = carry[h]
            k = k_buf[buf, h].reshape(chunk, d)
            v = v_buf[buf, h].reshape(chunk, d)
            v = jnp.where(row, v, jnp.zeros_like(v))
            s = lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.where(col, s * sm_scale, _NEG_INF)       # [G, chunk] f32
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(col, jnp.exp(s - m_new), 0.0)
            acc = acc * alpha + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            out.append((m_new, l_new, acc))
        return tuple(out)

    g = q_ref.shape[1]
    init = tuple((jnp.full((g, 1), _NEG_INF, jnp.float32),
                  jnp.zeros((g, 1), jnp.float32),
                  jnp.zeros((g, d), jnp.float32)) for _ in range(hkv))
    final = lax.fori_loop(0, n_chunks, body, init)
    for h, (_, l, acc) in enumerate(final):
        # length 0: no chunk ran, acc = 0 and l = 0 -> zeros
        o_ref[h] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, li, tables, lengths, *,
                           window: Optional[int] = None,
                           pages_per_chunk: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Attention of one query position a slot over that slot's cached
    positions, read from the paged pool in place.

    q [B, Hq, D]; k_pool / v_pool [Hkv, L, num_blocks, block_size, D];
    li: the layer (a scalar, traced or not); tables [B, max_blocks] int32,
    logical block -> physical block, `num_blocks` = unmapped; lengths [B]
    int32: slot b attends positions 0 .. lengths[b] - 1 (0: nothing is
    read, the row is zeros). Returns [B, Hq, D] in q's dtype.

    `window`: a sliding layer. Slot b attends positions
    max(lengths[b] - window, 0) .. lengths[b] - 1, and `tables` is its ring
    in the window pool: logical block j at entry j % max_blocks. The ring
    has to hold the band (max_blocks * block_size >= window + block_size).

    `interpret=None` compiles the kernel on a TPU backend and runs the
    Pallas interpreter anywhere else (the CPU unit tests); the caller
    decides whether the shapes suit the compiled kernel
    (`decode_kernel_suits`)."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    b, hq, d = q.shape
    hkv, _, _, bs, _ = k_pool.shape
    max_blocks = tables.shape[1]
    if hq % hkv:
        raise ValueError(f"{hq} query heads do not group over {hkv} KV heads")
    if k_pool.shape != v_pool.shape or k_pool.shape[4] != d:
        raise ValueError(f"pools {k_pool.shape} / {v_pool.shape} do not "
                         f"match q {q.shape}")
    g = hq // hkv
    ppc = min(pages_per_chunk or DEFAULT_PAGES_PER_CHUNK, max_blocks)
    if window is not None:
        if max_blocks * bs < window + bs:
            raise ValueError(f"a ring of {max_blocks} blocks of {bs} cannot "
                             f"hold a band of {window} positions")
        # a band lies in at most this many blocks, whatever the ring holds
        # beside it for prefill (window 128 in a ring of 25 blocks of 16:
        # 9 pages a chunk, not 25 buffered and multiplied)
        ppc = min(ppc, -(-window // bs) + 1)
    kernel = functools.partial(_kernel, sm_scale=1.0 / d ** 0.5,
                               pages_per_chunk=ppc, max_blocks=max_blocks,
                               window=window)
    q_spec = pl.BlockSpec((None, hkv, g, d), lambda i, *_: (i, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # lengths, the tables (flat), the layer
            grid=(b,),
            in_specs=[q_spec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, hkv, ppc, bs, d), k_pool.dtype),
                pltpu.VMEM((2, hkv, ppc, bs, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),  # (K | V, buffer)
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(lengths.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
      jnp.asarray(li, jnp.int32).reshape(1), q.reshape(b, hkv, g, d),
      k_pool, v_pool)
    return out.reshape(b, hq, d)


# ---------------------------------------------------------------------------
# The latent cache's decode step (MLA absorbed, ops/mla.py): every query head
# of a slot against ONE row a cached position, the value being the first
# `rank` numbers of the key.
# ---------------------------------------------------------------------------


def _latent_kernel(lengths_ref, tables_ref, li_ref, q_ref, kv_hbm, o_ref,
                   kv_buf, sems, m_ref, l_ref, acc_ref, *, sm_scale: float,
                   pages_per_chunk: int, max_blocks: int, rank: int):
    b = pl.program_id(0)
    _, num_blocks, bs, w = kv_hbm.shape
    chunk = pages_per_chunk * bs
    li = li_ref[0]
    length = lengths_ref[b]
    n_pages = jnp.minimum(pl.cdiv(length, bs), max_blocks)
    n_chunks = pl.cdiv(n_pages, pages_per_chunk)

    def copy(c, buf, j):
        # ONE DMA a page: key and value are the same rows
        page = jnp.minimum(tables_ref[b * max_blocks + c * pages_per_chunk + j],
                           num_blocks - 1)
        return pltpu.make_async_copy(kv_hbm.at[li, page], kv_buf.at[buf, j],
                                     sems.at[buf])

    def each_copy(c, buf, act):
        n = jnp.minimum(n_pages - c * pages_per_chunk, pages_per_chunk)
        lax.fori_loop(0, n, lambda j, _: act(copy(c, buf, j)), None)

    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(n_chunks > 0)
    def _first():
        each_copy(0, 0, lambda cp: cp.start())

    def body(c, _):
        buf = c % 2

        @pl.when(c + 1 < n_chunks)
        def _next():
            each_copy(c + 1, 1 - buf, lambda cp: cp.start())

        each_copy(c, buf, lambda cp: cp.wait())
        live = length - c * chunk  # positions of this chunk below the length
        col = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) < live
        row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) < live
        kv = kv_buf[buf].reshape(chunk, w)
        s = lax.dot_general(q_ref[...], kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.where(col, s * sm_scale, _NEG_INF)            # [heads, chunk]
        v = kv[:, :rank]
        v = jnp.where(row, v, jnp.zeros_like(v))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(col, jnp.exp(s - m_new), 0.0)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new

    lax.fori_loop(0, n_chunks, body, None)
    l = l_ref[...]
    # length 0: no chunk ran, acc = 0 and l = 0 -> zeros
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def latent_decode_attention(q, pool, li, tables, lengths, *, rank: int,
                            sm_scale: float,
                            pages_per_chunk: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """Absorbed latent attention of one query position a slot over that
    slot's cached positions, read from the latent pool in place.

    q [B, heads, W]: a head's query carried into the latent space, `[q_n
    Wuk_h^T | q_r | 0]` (ops/mla.py), as wide as a pool row; pool [L,
    num_blocks, block_size, W]: `[c | k_r | 0]` a cached position, c after
    its norm and k_r after its rotation; li: the layer; tables [B,
    max_blocks] int32, `num_blocks` = unmapped; lengths [B] int32 (0:
    nothing is read, the row is zeros). Scores `q . row * sm_scale` in
    float32, softmax statistics in float32, P in the pool's dtype against
    the row's first `rank` numbers (the value) with float32 accumulation:
    the mathematics of `ops/mla.py latent_attention`, absorbed. A block is
    read ONCE, for key and value and for all the heads. Returns P c
    [B, heads, rank] in q's dtype.

    Grid (slots,), chunks of `pages_per_chunk` pages double-buffered, only
    the pages below a slot's length fetched, as `paged_decode_attention`.
    `interpret=None` compiles the kernel on a TPU backend and runs the
    Pallas interpreter anywhere else; the caller decides whether the shapes
    suit the compiled kernel (`latent_kernel_suits`)."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    b, heads, w = q.shape
    _, _, bs, wp = pool.shape
    max_blocks = tables.shape[1]
    if wp != w or rank > w:
        raise ValueError(f"pool {pool.shape} does not match q {q.shape} "
                         f"and rank {rank}")
    ppc = min(pages_per_chunk or DEFAULT_PAGES_PER_CHUNK, max_blocks)
    kernel = functools.partial(_latent_kernel, sm_scale=sm_scale,
                               pages_per_chunk=ppc, max_blocks=max_blocks,
                               rank=rank)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # lengths, the tables (flat), the layer
            grid=(b,),
            in_specs=[pl.BlockSpec((None, heads, w), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, heads, rank),
                                   lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ppc, bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, rank), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode_attention",
    )(lengths.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
      jnp.asarray(li, jnp.int32).reshape(1), q, pool)


# ---------------------------------------------------------------------------
# The latent cache's prefill chunk (MLA expanded, ops/mla.py): `s` queries a
# row against the blocks that row holds, read in place, a tile of keys at a
# time; a head's keys and values are built from the tile's latents in VMEM
# and its scores never leave the chip. At the END of this file: a Pallas
# program's compile-cache key holds the file and line of every frame above
# its call, so a line added above the two decode kernels would cost every
# serving cell a cold compile (PERF.md section 7, "Open since PR 42" (a)).
# ---------------------------------------------------------------------------

# Keys a tile, as `ops/mla.py TILE_KEYS`: the online softmax then rounds P
# at the same tile edges as the plain form the kernel is tested against.
PREFILL_TILE_KEYS = 512
# Heads a grid step: their `kv_b` columns ([g, rank, nope + v], 8 MB at
# g = 32), queries and float32 accumulators stay in VMEM while the row's
# tiles stream past, and a tile is fetched once a group. Swept on the chip
# at openPangu-Ultra's widths (PERF.md section 6, PR 44; us a tile of 256
# queries over 16,384 keys / over four rows of unequal length): 8 heads
# 216 / 270, 16 heads 206 / 250, 32 heads 199 / 242: a grid step costs
# some 20 us beside its tiles, so fewer and larger steps win.
PREFILL_HEAD_GROUP = 32
# Heads a trip of the loop inside a tile, unrolled: the scheduler overlaps
# one head's softmax with the next one's matmuls only inside a trip (16
# heads a step: 218 us a tile at 1, 209 at 2, 206 at 4, 204 at 8), and a
# trip's body is what the kernel's code and its lowering time grow with.
PREFILL_HEAD_UNROLL = 4
# Scoped VMEM the kernel may use (the default is 16 MiB of the chip's 128):
# at 32 heads the blocks, double-buffered, are 2 x (8 + 2 + 2 + 2) MB, the
# statistics and the accumulator 12 MB, the two tiles 1.3 MB, and the
# compiler's temporaries (a head's scores are 0.5 MB) ride on top.
PREFILL_VMEM_BYTES = 64 << 20
_LANES = 128


def _divisor(n: int, most: int) -> int:
    """The largest divisor of n that is at most `most`."""
    return next(d for d in range(min(n, most), 0, -1) if n % d == 0)


def latent_prefill_tile(block_size: int, max_blocks: int) -> int:
    """Keys a tile of a prefill chunk's walk over a latent pool whose rows
    hold `max_blocks` blocks: whole blocks, `PREFILL_TILE_KEYS` at most
    (the kernel's tile and `LatentPagedCache._tiled`'s)."""
    return min(max(PREFILL_TILE_KEYS // block_size, 1), max_blocks) * block_size


def latent_prefill_suits(q_n, q_r, pool, kv_b, max_blocks: int) -> bool:
    """Whether a step with queries q_n [B, s, heads, nope] / q_r [B, s,
    heads, rope] over a latent pool [L, num_blocks, block_size, W] with
    `kv_b` [rank, heads * (nope + v)] and tables of `max_blocks` entries a
    row is one `latent_prefill_attention` takes compiled: more than one
    query a row and a whole number of sublane tiles of them, the latent, a
    head's key, a head's value and a tile's keys whole 128-lane rows, the
    rotated part inside the row's last lanes, the block whole sublane
    tiles, a backend that compiles Pallas kernels. Everything
    else walks tiles in `ops/mla.py latent_attention`: every CPU run, the
    tiny test models, `generate()`'s dense cache (which has no block
    table and never asks)."""
    s, heads, dn = q_n.shape[1:]
    block_size, w = pool.shape[2], pool.shape[3]
    rank, dv = kv_b.shape[0], kv_b.shape[1] // heads - dn
    sublanes = 8 * 4 // jnp.dtype(pool.dtype).itemsize
    return (s > 1 and s % sublanes == 0 and block_size % sublanes == 0
            and rank % _LANES == 0 and w % _LANES == 0 and dn % _LANES == 0
            and dv > 0 and dv % _LANES == 0 and rank + q_r.shape[3] <= w
            and latent_prefill_tile(block_size, max_blocks) % _LANES == 0
            and q_n.dtype == pool.dtype and compiled_kernels_available())


def _latent_prefill_kernel(lengths_ref, tables_ref, li_ref, qn_ref, qr_ref,
                           qpos_ref, w_ref, kv_hbm, o_ref, kv_buf, sems,
                           m_ref, l_ref, acc_ref, *, sm_scale: float,
                           pages_per_tile: int, max_blocks: int, rank: int,
                           dn: int):
    b = pl.program_id(1)
    group, _, dv = o_ref.shape
    unroll = _divisor(group, PREFILL_HEAD_UNROLL)
    _, num_blocks, bs, w = kv_hbm.shape
    tile = pages_per_tile * bs
    li = li_ref[0]
    length = lengths_ref[b]
    n_pages = jnp.minimum(pl.cdiv(length, bs), max_blocks)
    n_tiles = pl.cdiv(n_pages, pages_per_tile)

    def copy(t, buf, j):
        # ONE DMA a page, straight out of the pool: [c | k_r | 0] of 16 keys
        page = jnp.minimum(tables_ref[b * max_blocks + t * pages_per_tile + j],
                           num_blocks - 1)
        return pltpu.make_async_copy(kv_hbm.at[li, page], kv_buf.at[buf, j],
                                     sems.at[buf])

    def each_copy(t, buf, act):
        n = jnp.minimum(n_pages - t * pages_per_tile, pages_per_tile)
        lax.fori_loop(0, n, lambda j, _: act(copy(t, buf, j)), None)

    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(n_tiles > 0)
    def _first():
        each_copy(0, 0, lambda cp: cp.start())

    # a padding query (position < 0) attends as position 0, as the plain form
    qp0 = jnp.maximum(qpos_ref[...], 0)                            # [s, 1]

    def body(t, _):
        buf = t % 2

        @pl.when(t + 1 < n_tiles)
        def _next():
            each_copy(t + 1, 1 - buf, lambda cp: cp.start())

        each_copy(t, buf, lambda cp: cp.wait())
        ckr = kv_buf[buf].reshape(tile, w)
        # rows at or beyond the length hold whatever the buffer or a partly
        # filled block held (NaN included): no key there is seen, and a
        # zeroed latent expands to a zero value, so 0 x it stays 0
        live = lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < length - t * tile
        ckr = jnp.where(live, ckr, jnp.zeros_like(ckr))
        c, kr = ckr[:, :rank], ckr[:, rank:]
        kpos = t * tile + lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        seen = kpos <= qp0                                         # [s, tile]

        def head(h, _):
            # head h's keys and values of this tile, rounded to the compute
            # dtype as `latent_attention` rounds them
            kv = lax.dot_general(
                c, w_ref[h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(c.dtype)
            sc = lax.dot_general(qn_ref[h], kv[:, :dn], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            sc = (sc + lax.dot_general(
                qr_ref[h], kr, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)) * sm_scale
            sc = jnp.where(seen, sc, _NEG_INF)
            # m and l ride 128 lanes wide: m the same in every lane, l a
            # partial sum a lane (key k in lane k % 128), summed once when
            # the row's tiles are done, so a tile costs ONE cross-lane
            # reduction a query (the max) and no lane broadcast
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # every query sees key 0, so from tile 0 on m is a real score
            # and an unseen key's exp(-1e30 - m) is exactly 0
            p = jnp.exp(sc - pltpu.repeat(m_new, tile // _LANES, axis=1))
            l_ref[h] = l_ref[h] * alpha + sum(
                p[:, j:j + _LANES] for j in range(0, tile, _LANES))
            acc_ref[h] = (acc_ref[h] * pltpu.repeat(alpha, dv // _LANES, axis=1)
                          + lax.dot_general(
                              p.astype(c.dtype), kv[:, dn:],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32))
            m_ref[h] = m_new

        def trip(i, _):
            for j in range(unroll):
                head(i * unroll + j, None)

        lax.fori_loop(0, group // unroll, trip, None)

    lax.fori_loop(0, n_tiles, body, None)

    def finish(h, _):
        l = jnp.sum(l_ref[h], axis=-1, keepdims=True)
        # length 0 (a padding row): no tile ran, acc = 0 and l = 0 -> zeros
        o_ref[h] = (acc_ref[h] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    lax.fori_loop(0, group, finish, None)


def latent_prefill_attention(q_n, q_r, q_pos, pool, li, tables, kv_b, *,
                             sm_scale: Optional[float] = None,
                             pages_per_tile: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Expanded latent attention of `s` query positions a row over that
    row's cached positions, read from the latent pool in place: the
    mathematics of `ops/mla.py latent_attention(absorbed=False)` over
    `LatentPagedCache`'s tiles, to the rounding.

    q_n [B, s, heads, nope], q_r [B, s, heads, rope] (rotated), q_pos
    [B, s] int32 (< 0: padding); pool [L, num_blocks, block_size, W],
    `[c | k_r | 0]` a cached position; li: the layer; tables [B,
    max_blocks] int32, `num_blocks` = unmapped; kv_b [rank, heads * (nope +
    v)]. A key at position k is seen by a query at position p where
    k <= max(p, 0). Row b walks the tiles of `pages_per_tile` pages up to
    its own last position (`max(q_pos[b]) + 1` keys) and no further, and
    fetches only the pages below it: an unmapped entry beyond a row's
    length is never read, and a row whose positions are all negative reads
    nothing and returns zeros.

    Grid (head groups, rows), the rows innermost so a group's `kv_b`
    columns are fetched once; inside a step the row's tiles, double
    buffered, one DMA a page, and inside a tile a loop over the group's
    heads. A tile's keys and values are expanded a head at a time in VMEM
    (`c @ kv_b_h`, rounded to the compute dtype), scores
    `(q_n . k + q_r . k_r) * sm_scale` and the softmax's statistics are
    float32, P is rounded to the compute dtype for PV, accumulation is
    float32: nothing a head wide or a score wide reaches HBM. Queries,
    weights and outputs go in and out head-major (one transpose each
    outside the kernel), so a head is a leading index inside it.
    `interpret=None` compiles on a TPU backend and runs the Pallas
    interpreter anywhere else; the caller decides whether the shapes suit
    the compiled kernel (`latent_prefill_suits`). Returns [B, s, heads, v]
    in q_n's dtype."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    return _latent_prefill_call(q_n, q_r, q_pos, pool, li, tables, kv_b,
                                sm_scale=sm_scale,
                                pages_per_tile=pages_per_tile,
                                interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "pages_per_tile", "interpret"))
def _latent_prefill_call(q_n, q_r, q_pos, pool, li, tables, kv_b, *,
                         sm_scale, pages_per_tile, interpret: bool):
    """`latent_prefill_attention`, jitted: a model's stacks call it with
    the same shapes, and a jitted function is traced and lowered once a
    program however many call it (on a TPU host Mosaic's layout passes run
    while the kernel is lowered, before the compile cache is asked: about
    a second a kernel at openPangu-Ultra's widths, every start)."""
    b, s, heads, dn = q_n.shape
    dr = q_r.shape[3]
    _, _, bs, w = pool.shape
    rank = kv_b.shape[0]
    dv = kv_b.shape[1] // heads - dn
    max_blocks = tables.shape[1]
    if rank + dr > w or kv_b.shape[1] != heads * (dn + dv) or dv <= 0:
        raise ValueError(f"pool {pool.shape} / kv_b {kv_b.shape} do not "
                         f"match q_n {q_n.shape} / q_r {q_r.shape}")
    if sm_scale is None:
        sm_scale = 1.0 / (dn + dr) ** 0.5
    g = _divisor(heads, PREFILL_HEAD_GROUP)
    ppt = min(pages_per_tile or max_blocks,
              latent_prefill_tile(bs, max_blocks) // bs)
    if ppt * bs % _LANES or dv % _LANES:
        raise ValueError(f"tiles of {ppt * bs} keys / values of {dv} are not "
                         f"whole rows of {_LANES} lanes")
    wr = w - rank  # [k_r | 0]: the lanes of a pool row behind the latent
    lengths = jnp.clip(jnp.max(q_pos, axis=1) + 1, 0, max_blocks * bs)
    kernel = functools.partial(
        _latent_prefill_kernel, sm_scale=sm_scale, pages_per_tile=ppt,
        max_blocks=max_blocks, rank=rank, dn=dn)

    def per_row(width):
        return pl.BlockSpec((None, g, s, width),
                            lambda hg, i, *_: (i, hg, 0, 0))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # lengths, the tables (flat), the layer
            grid=(heads // g, b),
            in_specs=[per_row(dn), per_row(wr),
                      pl.BlockSpec((None, s, 1), lambda hg, i, *_: (i, 0, 0)),
                      pl.BlockSpec((g, rank, dn + dv),
                                   lambda hg, i, *_: (hg, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=per_row(dv),
            scratch_shapes=[
                pltpu.VMEM((2, ppt, bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((g, s, _LANES), jnp.float32),
                pltpu.VMEM((g, s, _LANES), jnp.float32),
                pltpu.VMEM((g, s, dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, heads, s, dv), q_n.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=PREFILL_VMEM_BYTES),
        interpret=interpret,
        name="latent_prefill_attention",
    )(lengths.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
      jnp.asarray(li, jnp.int32).reshape(1),
      q_n.transpose(0, 2, 1, 3),
      jnp.pad(q_r, ((0, 0), (0, 0), (0, 0), (0, wr - dr))).transpose(
          0, 2, 1, 3),
      q_pos.astype(jnp.int32)[..., None],
      kv_b.astype(q_n.dtype).reshape(rank, heads, dn + dv).transpose(1, 0, 2),
      pool)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# The decode step's K/V write: one position a row into the row's own block of
# both pools, in place. At the END of this file for the reason given above
# the prefill kernel (the compile cache's keys hold the lines above a call).
# ---------------------------------------------------------------------------

# VMEM the write may hold: every row's tile of K and of V at once (EvaByte's
# 16 rows x 32 heads x 4 KB x 2 is 4 MB, K-EXAONE's 48 x 8 x 4 KB x 2 is 3).
KV_WRITE_VMEM_BYTES = 32 << 20


def _sublanes(dtype) -> int:
    """Rows of a sublane tile of `dtype`: 8 of float32, 16 of bfloat16."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def kv_write_suits(k_new, k_pool) -> bool:
    """Whether writing k_new [B, s, Hkv, D] into k_pool [Hkv, L, num_blocks,
    block_size, D] is a step `paged_kv_write` takes compiled: the shapes
    `decode_kernel_suits` asks for (one position a row, the head whole
    128-lane rows, the block whole sublane tiles of the pool's dtype, a
    backend that compiles Pallas kernels), and every row's tile of both
    pools in VMEM at once. Everything else keeps the scatter: prefill
    chunks, the tiny test models' heads and blocks, every CPU run, and a
    pool that a tp mesh shards (`ShardedPagedKVCache` never asks)."""
    b, _, hkv, d = k_new.shape
    tile = hkv * _sublanes(k_pool.dtype) * d * jnp.dtype(k_pool.dtype).itemsize
    return (decode_kernel_suits(k_new, k_pool)
            and 2 * b * tile <= KV_WRITE_VMEM_BYTES)


def _kv_write_kernel(li_ref, page_ref, off_ref, k_new, v_new, k_in, v_in,
                     k_out, v_out, order, k_buf, v_buf, sems):
    rows, hkv, sub, d = k_buf.shape
    li = li_ref[0]

    def note(r, n):  # the rows that are written, compacted into `order`
        @pl.when(page_ref[r] >= 0)
        def _():
            order[n] = r
        return n + (page_ref[r] >= 0).astype(jnp.int32)

    live = lax.fori_loop(0, rows, note, 0)

    def copies(r, back: bool):
        # the aligned sublane tile that holds the row, of every KV head: the
        # strided read the attention kernel makes of a page, `sub` rows of it
        at = pl.ds(pl.multiple_of(off_ref[r] // sub * sub, sub), sub)
        for i, (src, dst, buf) in enumerate(((k_in, k_out, k_buf),
                                             (v_in, v_out, v_buf))):
            if back:
                yield pltpu.make_async_copy(
                    buf.at[r], dst.at[:, li, page_ref[r], at], sems.at[1, i])
            else:
                yield pltpu.make_async_copy(
                    src.at[:, li, page_ref[r], at], buf.at[r], sems.at[0, i])

    def each_live(act):
        lax.fori_loop(0, live, lambda t, _: act(order[t]), None)

    def start(r, back):
        for cp in copies(r, back):
            cp.start()

    def wait(r, back):
        for cp in copies(r, back):
            cp.wait()

    def put(r):
        # every head at once: the new rows ride [B, Hkv, 1, D], a head a
        # leading index as in the tiles, so a row costs two selects, not two
        # a head (the unrolled form took 3 s to lower at EvaByte's 32 heads)
        here = lax.broadcasted_iota(jnp.int32, (hkv, sub, d), 1) == off_ref[r] % sub
        for new, buf in ((k_new, k_buf), (v_new, v_buf)):
            buf[r] = jnp.where(here, jnp.broadcast_to(new[r], (hkv, sub, d)), buf[r])
        start(r, True)

    # every read in flight before the first is waited for, every write-back
    # before the first of those: a row costs no DMA's latency of its own
    each_live(lambda r: start(r, False))
    each_live(lambda r: wait(r, False))
    each_live(put)
    each_live(lambda r: wait(r, True))


def paged_kv_write(k_pool, v_pool, li, k_new, v_new, phys, off, *,
                   interpret: Optional[bool] = None):
    """One new position a row into both pools, in place: what
    `pool.at[li, phys, off].set(new, mode="drop")` over the KV heads leaves,
    bit for bit (no arithmetic: the same rows in the same places).

    k_pool / v_pool [Hkv, L, num_blocks, block_size, D]; li: the layer (a
    scalar, traced or not); k_new / v_new [B, Hkv, D], cast to the pools'
    dtype as the scatter casts them; phys [B] int32: the physical block of
    each row's position, `num_blocks` (or anything outside the pool) =
    dropped; off [B] int32: the offset in it. NO TWO ROWS THAT ARE WRITTEN
    SHARE A BLOCK (a slot writes into its own last block; the scatter has
    the same premise for a (block, offset)): the unit moved is the aligned
    sublane tile that holds the row (a bfloat16 row is half a packed
    sublane: 16 rows, the whole block at `block_size` 16), read into VMEM,
    the row put at its offset by a select, and written back, the tile's
    other rows as they were read.

    One grid step; both pools handed over whole in HBM and aliased to the
    outputs; a row's tile is `pool[:, li, page, rows]`, one strided DMA for
    all its KV heads each way; all rows' reads are in flight together and
    all their write-backs. A dropped row issues no DMA: a call whose rows
    are all dropped (EvaByte's summary write in a step that closes no
    chunk) is a launch and nothing else. `interpret=None` compiles on a
    TPU backend and runs the Pallas interpreter anywhere else; the caller
    decides whether the shapes suit the compiled kernel (`kv_write_suits`).
    Returns (k_pool', v_pool')."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    if (k_pool.shape != v_pool.shape or k_new.shape != v_new.shape
            or k_new.shape[1:] != (k_pool.shape[0], k_pool.shape[4])):
        raise ValueError(f"pools {k_pool.shape} / {v_pool.shape} do not match "
                         f"rows {k_new.shape} / {v_new.shape}")
    return _kv_write_call(k_pool, v_pool, li, k_new, v_new, phys, off,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_write_call(k_pool, v_pool, li, k_new, v_new, phys, off, *,
                   interpret: bool):
    """`paged_kv_write`, jitted: a layer's K/V rows and EvaByte's summary
    rows call it with the same shapes, and a jitted function is traced and
    lowered once a program however many call it."""
    b, hkv, d = k_new.shape
    sub = _sublanes(k_pool.dtype)
    k_new = k_new.astype(k_pool.dtype)[:, :, None]
    v_new = v_new.astype(v_pool.dtype)[:, :, None]
    page = jnp.where((phys >= 0) & (phys < k_pool.shape[2]), phys, -1)

    def whole(x):
        return pl.BlockSpec(x.shape, lambda *_: (0,) * x.ndim,
                            memory_space=pltpu.VMEM)

    pool = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _kv_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # the layer, the rows' blocks, their offsets
            grid=(1,),
            in_specs=[whole(k_new), whole(v_new), pool, pool],
            out_specs=[pool, pool],
            scratch_shapes=[
                pltpu.SMEM((b,), jnp.int32),
                pltpu.VMEM((b, hkv, sub, d), k_pool.dtype),
                pltpu.VMEM((b, hkv, sub, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),  # (in | out, K | V)
            ]),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={5: 0, 6: 1},  # the pools, counted with the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=KV_WRITE_VMEM_BYTES + (16 << 20)),
        interpret=interpret,
        name="paged_kv_write",
    )(jnp.asarray(li, jnp.int32).reshape(1), page.astype(jnp.int32),
      off.astype(jnp.int32), k_new, v_new, k_pool, v_pool)
