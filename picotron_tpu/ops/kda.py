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
chunked form, `kda_chunked`, here.

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

`tests/test_kimi_linear.py` holds the chunked form to token by token on the
fastest-decaying channels of the seeded draw, from a non-zero state, with
padding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.ops.gated_delta import gated_delta, gated_delta_step

F32 = jnp.float32
CHUNK = 64  # positions a sub-chunk: one (I + A)^-1 of 64 x 64 a head
BLOCK = 16  # positions a block of it: 16 steps of a decay stay far from 88


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
