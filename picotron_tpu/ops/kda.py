"""Kimi Delta Attention: the recurrence of a `kda` layer (Kimi-Linear). The
gated delta rule of `ops/gated_delta.py` with ONE difference: the decay is a
vector over the key's d_k channels, not a number a head. For a token with key
k and query q (L2-normalised, q scaled by d_k^-0.5), value v, decay g <= 0 a
CHANNEL and write strength beta in (0, 1), on a state S [d_k, d_v] float32:

    S' = Diag(exp(g)) S;   r = S'^T k;   S = S' + k (beta (v - r))^T;   o = S^T q

The step and the token-by-token scan are `gated_delta_step` /
`gated_delta_scan` themselves, which take g either way, and so is the decode
step's kernel over a serving cache's state pool
(`gated_delta_step_pooled`: one body, the decay a [d_k, H] tile a row where
the scalar gate is a word; `kda_step_pooled` in a trace). What changes is the
chunked form, `kda_chunked`, here, and its kernel over the same pool,
`kda_chunk_pooled`.

Inside a sub-chunk of c positions, with G the running sum of g (a vector a
position), every pair of positions j <= i meets through

    P(x)_ij = sum_d x_i[d] k_j[d] exp(G_i[d] - G_j[d])

(x = k for what a position reads of the earlier writes, x = q for what it
outputs): the decay no longer factors out of x k^T as one number a pair.
Written as (x_i exp(G_i)) . (k_j exp(-G_j)) it is one matrix product, and
exp(-G_j) overflows float32 88 units down a channel, a few dozen positions
at the decays a seeded (or a trained) model has. So a sub-chunk is cut into
blocks of `block` positions (16), and each pair of blocks is computed where
every exponent is <= 0:

- a block of rows I against the keys BEFORE it: both sides relative to the
  block's first position n = I block, (x_i exp(G_i - G_n)) . (k_j exp(G_n -
  G_j)): G falls, so G_i <= G_n <= G_j and both factors are at most 1 (an
  underflow is a product below 1e-38 either way). One matrix product a
  block of rows.
- a block against itself: pairwise, a column j of the block at a time,
  sum_d x_i[d] k_j[d] exp(min(G_i[d] - G_j[d], 0)), on the vector unit: 16
  x 16 x d_k exponentials a block, a quarter of the sub-chunk's pairs at
  c = 64.

From there on the mathematics is `gated_delta_chunked`'s: with A = beta
P(k) strictly below the diagonal, T = (I + A)^-1 is built from the diagonal
outwards by matrix products (where X inverts the diagonal blocks of m rows
and B is A's blocks beside them that complete blocks of 2m, X - X B X
inverts those: no power of A, no row waiting for the row above it), each
position writes W = T (beta v - (beta k exp(G)) S_0), outputs (q exp(G)) S_0
+ P(q) W, and the sub-chunk hands on Diag(exp(G_c)) S_0 + (k exp(G_c -
G))^T W. A position that carries no token is made inert by its caller (g = 0
and beta = 0). Everything is float32 and the matrix products are asked for at
the highest precision, as in `ops/gated_delta.py`.

`kda_chunk_pooled` is `kda_chunked` as ONE Pallas kernel over a serving
cache's state pool [L_kda, slots, H, d_k, d_v], in place: the served form of
a prefill chunk on a chip, built as `ops.gated_delta.gated_delta_chunk_pooled`
is (a row's state comes into VMEM a few heads at a time, stays there across
the chunk's sub-chunks and goes back once; a rung's pad rows and unmapped
rows move no byte and compute nothing; two heads' 64 x 64 matrices side by
side in one row of lanes). The same sub-chunk of 64, block of 16, float32 and
product precision: a block of rows against the keys before it is one product
a pair of heads, a block against itself 16 columns on the vector unit inside
the kernel, where the `jax.numpy` form leaves the compiler some 2,000 small
operations an execution of nine mixers (PERF.md section 6, PR 61).
`kda_chunk_suits` says which chunks take it; `forward()`, `generate()`, every
CPU run and the tiny test models keep `kda_chunked`.

`tests/test_kimi_linear.py` holds the chunked form and the kernel (in the
Pallas interpreter) to token by token on the fastest-decaying channels of the
seeded draw, from a non-zero state, with padding.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from picotron_tpu.ops.gated_delta import (
    _dot, gated_delta, gated_delta_step, work_first,
)
from picotron_tpu.ops.paged_attention import (
    _LANES, _divisor, compiled_kernels_available,
)

F32 = jnp.float32
CHUNK = 64  # positions a sub-chunk: one (I + A)^-1 of 64 x 64 a head
BLOCK = 16  # positions a block of it: 16 steps of a decay stay far from 88
CHUNK_PAIRS = 2  # pairs of heads whose products stand side by side in the kernel


def _pairs(x, k, gc, block: int):
    """P(x) of the module docstring for one sub-chunk: k, gc [..., c, d_k]
    (gc the running sum of g inside the sub-chunk), x [n, ..., c, d_k] (the
    rows of n kinds met with the same keys, k's own and q's: their
    exponentials are the same) -> [n, ..., c, c], zero above the diagonal; c
    a whole number of `block`s."""
    c, dk = k.shape[-2:]
    nb = c // block
    lead = k.shape[:-2]

    def blocks(a):
        return a.reshape(*a.shape[:-2], nb, block, dk)

    xb, kb, gb = blocks(x), blocks(k), blocks(gc)
    first = gb[..., :1, :]                                  # G_n, [..., nb, 1, d_k]
    # every key relative to each block's first position; min: a key at or
    # behind that position is masked below, and must not overflow before
    keys = k[..., None, :, :] * jnp.exp(
        jnp.minimum(first - gc[..., None, :, :], 0.0))      # [..., nb, c, d_k]
    before = jnp.einsum("n...ird,...ijd->n...irj", xb * jnp.exp(gb - first),
                        keys).reshape(-1, *lead, c, c)
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    before = jnp.where(j < i // block * block, before, 0.0)
    # a block against itself, a column at a time
    cols = [jnp.sum(xb * (kb[..., n:n + 1, :] * jnp.exp(
        jnp.minimum(gb - gb[..., n:n + 1, :], 0.0))), axis=-1)
        for n in range(block)]
    own = jnp.stack(cols, axis=-1)                          # [n, ..., nb, r, col]
    own = (own[..., :, :, None, :]
           * jnp.eye(nb, dtype=F32)[:, None, :, None]).reshape(-1, *lead, c, c)
    return before + jnp.where((j <= i) & (j >= i // block * block), own, 0.0)


def _unit_lower_inverse(a):
    """(I + a)^-1 for a [..., c, c] strictly lower triangular, c a power of
    two, by matrix products alone, from the diagonal outwards."""
    c = a.shape[-1]
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    x = jnp.broadcast_to(jnp.eye(c, dtype=F32), a.shape)
    for sh in range(c.bit_length() - 1):
        beside = (((i >> sh) & 1) == 1) & ((j >> sh) == (i >> sh) - 1)
        x = x - x @ jnp.where(beside, a, 0.0) @ x
    return x


def kda_chunked(q, k, v, g, beta, state, sub: int = CHUNK, block: int = BLOCK):
    """A segment in sub-chunks of `sub` positions (the module docstring).
    q, k, g [B, s, H, d_k]; v [B, s, H, d_v]; beta [B, s, H]; state [B, H,
    d_k, d_v] -> (o [B, s, H, d_v], state'). s need not be a multiple of
    `sub` (the segment is padded with inert positions); `sub` and `block`
    are powers of two."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = max(min(sub, 1 << (s - 1).bit_length()), block)
    pad = -s % c
    n = (s + pad) // c

    def split(x):  # [B, s, H, ...] -> [N, B, H, c, ...]
        x = jnp.pad(x.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]

    def one(st, xs):
        # everything of a sub-chunk inside the loop over them: the keys
        # rescaled a block of rows are `c / block` copies of k, held for one
        # sub-chunk at a time
        q_i, k_i, v_i, g_i, b_i = xs
        gc = jnp.cumsum(g_i, axis=-2)                         # [B, H, c, d_k]
        kb = k_i * b_i[..., None]
        kk, qk = _pairs(jnp.stack([k_i, q_i]), k_i, gc, block)
        solved = _unit_lower_inverse(jnp.where(j < i, b_i[..., None] * kk, 0.0))
        from_state = jnp.concatenate(
            [kb * jnp.exp(gc), q_i * jnp.exp(gc)], axis=-2) @ st
        v_new = solved @ (v_i * b_i[..., None] - from_state[..., :c, :])
        o = from_state[..., c:, :] + qk @ v_new
        last = gc[..., -1:, :]
        st = (st * jnp.swapaxes(jnp.exp(last), -1, -2)
              + jnp.swapaxes(k_i * jnp.exp(last - gc), -1, -2) @ v_new)
        return st, o

    with jax.default_matmul_precision("highest"):
        state, o = lax.scan(one, state.astype(F32),
                            tuple(split(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)           # [B, N, c, H, d_v]
    return o.reshape(b, n * c, h, dv)[:, :s], state


def kda(q, k, v, g, beta, state):
    """A segment from `state` in the plain form that suits its length: the
    rule itself for one position a row, else the chunked form. Shapes as
    `kda_chunked`'s."""
    if q.shape[1] == 1:
        o, state = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0], state)
        return o[:, None], state
    return kda_chunked(q, k, v, g, beta, state)


def delta_rule(q, k, v, g, beta, state):
    """A segment from `state` by the rule its decay asks for: `kda` where g
    comes a channel of the key ([B, s, H, d_k]), `gated_delta` where it is a
    number a head ([B, s, H]). What a cache that holds a state for either
    kind of mixer runs when no kernel takes the segment."""
    return (kda if g.ndim == q.ndim else gated_delta)(q, k, v, g, beta, state)


# ---------------------------------------------------------------------------
# A prefill chunk over a serving cache's state pool, in place.
# ---------------------------------------------------------------------------


def chunk_vmem_bytes(s: int, qk_width: int, v_width: int) -> int:
    """What `kda_chunk_pooled` asks of VMEM: a row's q, k, G, v and o whole,
    each in two buffers (the next row's come in and the last row's o goes
    out under a row's work), and room for the kernel's own values."""
    return 2 * 4 * s * (3 * qk_width + 2 * v_width) + 24 * 2**20


def kda_chunk_suits(s: int, heads: int, pool) -> bool:
    """Whether a segment of `s` positions a row, q, k and the decay over
    `heads` heads, over a state pool [L_kda, slots, H, d_k, d_v] is one
    `kda_chunk_pooled` takes compiled: whole sub-chunks of `CHUNK` positions
    (so never a decode step), a float32 state a row a head whose d_k and d_v
    are whole rows of 128 lanes, heads that come in pairs, a row's q, k, G, v
    and o small enough for VMEM (96 of a v5e's 128 MiB), a backend that
    compiles Pallas kernels (`ops.gated_delta.gated_delta_chunk_suits` is
    the scalar-gated rule's answer to the same question)."""
    h, dk, dv = pool.shape[2:]
    return (s > 1 and s % CHUNK == 0 and pool.dtype == F32 and heads == h
            and h % 2 == 0 and dk % _LANES == 0 and dv % _LANES == 0
            and chunk_vmem_bytes(s, h * dk, h * dv) <= 96 * 2**20
            and compiled_kernels_available())


def _chunk_kernel(gi_ref, slot_ref, fresh_ref, order_ref, n_ref, q_ref, k_ref,
                  gc_ref, v_ref, beta_ref, pool_in, pool_out, o_ref, s_buf,
                  sems):
    """Grid step t: the t-th row with work, whole: its heads in PAIRS (2p and
    2p + 1), `s_buf.shape[0] // 2` pairs a step of the loop, all the
    sub-chunks of each, laid out as `ops.gated_delta._chunk_kernel` lays them
    out: a matrix of 64 x 64 a head is held for both heads of a pair side by
    side, [64, 128], and the matrices of a loop step's pairs and sub-chunks
    are the leading axis of one array, so that products that do not wait for
    one another stand side by side. What is this rule's own is P(x) of the
    module docstring in place of (x k^T) decay: a block of 16 rows against
    the keys before it one product a pair of heads, relative to the block's
    first position; a block against itself a column at a time on the vector
    unit; and the decays of what meets the state a channel, not a number."""
    c, blk = CHUNK, BLOCK
    nb = c // blk
    t = pl.program_id(0)
    s, hv = beta_ref.shape
    dk, dv = s_buf.shape[1:]
    together, subs = s_buf.shape[0] // 2, s // c
    items = together * subs

    @pl.when(t >= n_ref[0])
    def _idle():  # a row without work: its o is zeros, nothing else moves
        o_ref[...] = jnp.zeros(o_ref.shape, F32)

    i = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    lane = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    first = lane < c                      # the first head's half
    j = jnp.where(first, lane, lane - c)
    both = lax.broadcasted_iota(jnp.int32, (2 * c, 2 * c), 0) < c
    both = both == (lax.broadcasted_iota(jnp.int32, (2 * c, 2 * c), 1) < c)
    head = lax.broadcasted_iota(jnp.int32, (s, hv), 1)
    eye, causal, strict = jnp.where(i == j, 1.0, 0.0), j <= i, j < i
    before = j < i // blk * blk           # a key of an earlier block
    # a block's rows apart, [items x nb, blk, 2c]: the column of each half
    # that stands n positions behind the block's first (these masks come
    # from iotas of their own shapes: a lane iota's mask is held once for
    # all its rows, and Mosaic refuses to slice 16 rows out of it)
    lane3 = lax.broadcasted_iota(jnp.int32, (items * nb, blk, 2 * c), 2)
    first3 = lane3 < c
    behind = (jnp.where(first3, lane3, lane3 - c)
              - lax.broadcasted_iota(jnp.int32, (items * nb, blk, 2 * c), 0)
              % nb * blk)
    first2 = lax.broadcasted_iota(jnp.int32, (blk, 2 * c), 1) < c
    # of blocks of 2, 4, ... 64 rows on the diagonal, the quarter under the
    # diagonal and left of it: what two inverted blocks of half the size lack
    beside = [(((i >> sh) & 1) == 1) & ((j >> sh) == (i >> sh) - 1)
              for sh in range(c.bit_length() - 1)]

    def pair(m):  # [m | m'] -> [[m, 0], [0, m']]: a product with it is a
        return jnp.where(both, jnp.concatenate([m, m], 1), 0.0)  # head's own

    def halves(m):  # [m | m'] -> [[m | 0], [0 | m']]
        return jnp.concatenate([jnp.where(first, m, 0.0),
                                jnp.where(first, 0.0, m)], 1)

    def some_pairs(step, _):
        b = order_ref[t]
        h0 = 2 * together * step
        place = (gi_ref[0], slot_ref[b], pl.ds(h0, 2 * together))
        fetch = pltpu.make_async_copy(pool_in.at[place], s_buf, sems.at[0])
        store = pltpu.make_async_copy(s_buf, pool_out.at[place], sems.at[1])
        carried = fresh_ref[b] == 0

        @pl.when(carried)
        def _():
            fetch.start()

        at_k = pl.ds(pl.multiple_of(h0 * dk, _LANES), 2 * together * dk)
        at_v = pl.ds(pl.multiple_of(h0 * dv, _LANES), 2 * together * dv)

        def of_heads(ref):  # [e][pairs x subs, c, d_k]: each pair's head e
            x = ref[:, at_k]
            return [jnp.concatenate(
                [x[:, (2 * u + e) * dk:(2 * u + e + 1) * dk].reshape(subs, c, dk)
                 for u in range(together)], 0) for e in range(2)]

        def column(h):  # head h's beta, a column a sub-chunk
            return jnp.sum(jnp.where(head == h, beta_ref[...], 0.0), axis=1,
                           keepdims=True).reshape(subs, c, 1)

        # what does not wait for the state: of every sub-chunk, (I + A)^-1
        # and P(q) of both heads of each pair
        k, q, gc = (of_heads(ref) for ref in (k_ref, q_ref, gc_ref))
        bt = [jnp.concatenate([column(h0 + 2 * u + e) for u in range(together)], 0)
              for e in range(2)]

        def blocks(x):  # [items, c, .] -> [items x nb, blk, .]
            return x.reshape(items * nb, blk, x.shape[2])

        # a block against itself, a column at a time: the column n of both
        # kinds (k's rows and q's) and both heads, over the heads' halves
        kb, qb, gb = ([blocks(x) for x in xs] for xs in (k, q, gc))
        own = [jnp.zeros((items * nb, blk, 2 * c), F32) for _ in range(2)]
        for n in range(blk):
            w = [kb[e][:, n:n + 1] * jnp.exp(jnp.minimum(
                gb[e] - gb[e][:, n:n + 1], 0.0)) for e in range(2)]
            for kind, xb in enumerate((kb, qb)):
                col = [jnp.sum(xb[e] * w[e], axis=2, keepdims=True)
                       for e in range(2)]
                own[kind] = jnp.where(behind == n,
                                      jnp.where(first3, col[0], col[1]),
                                      own[kind])
        # a block against the keys before it: both sides relative to the
        # block's first position, one product a pair of heads (the other
        # head's quarter of it is dropped)
        rows = [[jnp.zeros((items, blk, 2 * c), F32)] for _ in range(2)]
        for r in range(1, nb):
            at = slice(r * blk, (r + 1) * blk)
            start = [gc[e][:, r * blk:r * blk + 1] for e in range(2)]
            keys = jnp.concatenate(
                [k[e] * jnp.exp(jnp.minimum(start[e] - gc[e], 0.0))
                 for e in range(2)], 1)                     # [items, 2c, d_k]
            scale = [jnp.exp(gc[e][:, at] - start[e]) for e in range(2)]
            met = _dot(jnp.concatenate(
                [x[e][:, at] * scale[e] for e in range(2) for x in (k, q)], 1),
                keys, 2, 2)                                 # [items, 4 blk, 2c]
            for kind in range(2):
                rows[kind].append(jnp.where(
                    first2, met[:, kind * blk:(kind + 1) * blk],
                    met[:, (2 + kind) * blk:(3 + kind) * blk]))
        kk, qk = (jnp.where(before, jnp.concatenate(rows[kind], 1),
                            own[kind].reshape(items, c, 2 * c))
                  for kind in range(2))
        lower = jnp.where(strict, jnp.where(first, bt[0], bt[1]) * kk, 0.0)
        scores = jnp.where(causal, qk, 0.0)
        # (I + A)^-1 from the diagonal outwards: where X inverts the diagonal
        # blocks of m rows and B is A's blocks beside them that complete
        # blocks of 2m, X - X B X inverts those
        solved = eye - jnp.where(beside[0], lower, 0.0)
        for quarter in beside[1:]:
            solved = solved - _dot(
                _dot(solved, pair(jnp.where(quarter, lower, 0.0))), pair(solved))

        @pl.when(carried)
        def _():
            fetch.wait()

        @pl.when(jnp.logical_not(carried))
        def _():  # position 0: whatever the row holds, zeros
            s_buf[...] = jnp.zeros(s_buf.shape, F32)

        # `kda_chunked`'s `one`, a sub-chunk after the other; the leading
        # axis is the step's heads (or its pairs, the two heads' rows one
        # under the other)
        def a_head(x, n):  # [e][pairs x subs, ...] -> sub-chunk n's, a row a head
            return jnp.concatenate([x[e][u * subs + n][None]
                                    for u in range(together) for e in range(2)], 0)

        def a_pair(x):  # [heads, r, .] -> [pairs, 2 r, .]
            return x.reshape(together, 2 * x.shape[1], x.shape[2])

        state = s_buf[...]
        for n in range(subs):
            k_n, q_n, gc_n, bt_n = (a_head(x, n) for x in (k, q, gc, bt))
            last = gc_n[:, c - 1:]                          # [heads, 1, d_k]
            into = jnp.exp(gc_n)
            from_state = _dot(jnp.concatenate(
                [k_n * (bt_n * into), q_n * into], 1), state)
            v_n = v_ref[pl.ds(n * c, c), at_v]
            v_n = jnp.stack([v_n[:, e * dv:(e + 1) * dv]
                             for e in range(2 * together)])
            pick = lambda x: jnp.concatenate(  # noqa: E731
                [x[u * subs + n][None] for u in range(together)], 0)
            v_new = _dot(halves(pick(solved)),
                         a_pair(v_n * bt_n - from_state[:, :c]))
            kt = jnp.swapaxes(a_pair(k_n * jnp.exp(last - gc_n)), 1, 2)
            out = _dot(jnp.concatenate(
                [halves(pick(scores)), jnp.where(first[:1], kt, 0.0),
                 jnp.where(first[:1], 0.0, kt)], 1), v_new)
            o = from_state[:, c:] + out[:, :2 * c].reshape(2 * together, c, dv)
            o_ref[pl.ds(n * c, c), at_v] = jnp.concatenate(list(o), 1)
            # Diag(exp(G_c)): a channel's decay down the state's rows
            shrink = jnp.swapaxes(jnp.broadcast_to(
                jnp.exp(last), (2 * together, dv, dk)), 1, 2)
            state = shrink * state + out[:, 2 * c:].reshape(
                2 * together, dk, dv)
        s_buf[...] = state
        store.start()
        store.wait()

    @pl.when(t < n_ref[0])
    def _work():
        lax.fori_loop(0, hv // 2 // together, some_pairs, None)


def kda_chunk_pooled(q, k, v, g, beta, pool, gi, rows, live, fresh, *,
                     interpret: Optional[bool] = None):
    """`kda_chunked` for the batch's rows that hold a real position, on mixer
    `gi`'s rows of a state pool, in place: ONE Pallas kernel, built as
    `ops.gated_delta.gated_delta_chunk_pooled` is.

    q, k, g [B, s, H, d_k]; v [B, s, H, d_v]; beta [B, s, H]; pool [L_kda,
    slots, H, d_k, d_v], all float32; gi: the mixer (a scalar, traced or
    not); rows [B] int32: row b's slot, `slots` or more = unmapped; live [B]
    bool: the row holds a real position (a padded position INSIDE a live row
    is made inert by the caller); fresh [B] bool: it starts its sequence
    (the state it carries in is zeros, whatever the pool holds). No two rows
    with work share a slot. Returns (o [B, s, H, d_v], pool'): for a live,
    mapped row the chunked form's o and its state' at pool'[gi, rows[b]]; any
    other row's o is zeros, and nothing of the pool outside the worked rows'
    matrices of mixer gi is read or written.

    One grid step a row with work (`work_first`; a rung's pad rows and
    unmapped rows move no byte and compute nothing); the pool stays in HBM
    and is the kernel's output too; a row's state comes into VMEM `2 x
    CHUNK_PAIRS` heads at a time, stays there across the chunk's sub-chunks
    and goes back once. Inside, the module docstring's mathematics with
    `kda_chunked`'s sub-chunk of 64, block of 16 and float32 products at the
    highest precision: every exponent is <= 0. `interpret=None` compiles on a
    TPU backend and runs the Pallas interpreter anywhere else; the caller
    decides whether the shapes suit the compiled kernel (`kda_chunk_suits`)."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    if (pool.shape[2:] != q.shape[2:] + v.shape[3:] or pool.dtype != F32
            or q.shape[1] % CHUNK or q.shape[2] % 2 or g.shape != q.shape):
        raise ValueError(f"pool {pool.shape} {pool.dtype} does not match q "
                         f"{q.shape} / v {v.shape} / g {g.shape} in float32 "
                         f"sub-chunks of {CHUNK}, heads in pairs")
    return _chunk_pooled_call(q, k, v, g, beta, pool, gi, rows, live, fresh,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_pooled_call(q, k, v, g, beta, pool, gi, rows, live, fresh, *,
                       interpret: bool):
    """`kda_chunk_pooled`, jitted: a period's mixers call it with the same
    shapes, and a jitted function is traced and lowered once a program
    however many call it."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    work = live & (rows < pool.shape[1])
    order, n = work_first(work)
    # G, the running sum of g inside a sub-chunk, a vector a position
    gc = jnp.cumsum(g.reshape(b, s // CHUNK, CHUNK, h * dk), axis=2)

    def row(*block):  # of [B, ...]: the t-th row with work; the last, after
        return pl.BlockSpec(
            (None,) + block, lambda t, gi, slot, fresh, order, n:
            (order[jnp.minimum(t, jnp.maximum(n[0], 1) - 1)],)
            + (0,) * len(block))

    pool, o = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # the mixer, the rows' slots, their starts, the rows with work
            # first, their count
            num_scalar_prefetch=5,
            grid=(b,),
            in_specs=[row(s, h * dk), row(s, h * dk), row(s, h * dk),
                      row(s, h * dv), row(s, h),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec((None, s, h * dv),
                                    lambda t, gi, slot, fresh, order, n:
                                    (order[t], 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((2 * _divisor(h // 2, CHUNK_PAIRS), dk, dv), F32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, s, h * dv), F32)],
        input_output_aliases={10: 0},  # the pool, counted with the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=chunk_vmem_bytes(s, h * dk, h * dv)),
        interpret=interpret,
        name="kda_chunk_pooled",
    )(jnp.asarray(gi, jnp.int32).reshape(1),
      jnp.where(work, rows, 0).astype(jnp.int32), fresh.astype(jnp.int32),
      order, n.reshape(1), q.reshape(b, s, h * dk), k.reshape(b, s, h * dk),
      gc.reshape(b, s, h * dk), v.reshape(b, s, h * dv), beta, pool)
    return o.reshape(b, s, h, dv), pool
