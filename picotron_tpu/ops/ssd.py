"""The recurrence of a Mamba-2 mixer (`mamba2` layers: Nemotron-H), state
space duality's: a matrix state a head, S [P, N] float32 (P the head's
channels, N the state's width), decayed by ONE scalar a head and step and
written by an outer product. For a token with the head's input x [P], step
d > 0, the layer's A < 0 a head, and B and C [N] of the head's GROUP (a
group of heads shares them):

    S = exp(d A) S + (d x) B^T;   y = S C     (+ D x, the mixer's to add)

It is the gated delta rule (ops/gated_delta.py) WITHOUT its delta term: key
B, value d x, query C, no write strength, no normalisation, nothing to
solve. The forms here take v = d x [.., H, P] and g = d A [.., H] (<= 0)
ready made, and b, c [.., G, N] a GROUP; the state is held [H, P, N], N along
the lanes: at Nemotron-H's 64 x 128 a head's matrix is eight whole (8, 128)
tiles, where the delta rule's [d_k, d_v] order would put 64 numbers in every
row of 128 lanes and a pool of them in twice its bytes.

- `ssd_step`: one token a row, the rule as written. The decode step's form
  and, under `lax.scan`, the token-by-token form of any segment (`ssd_scan`,
  what the chunked form is tested against).
- `ssd_chunked`: a segment in sub-chunks of `sub` positions. Inside one,
  with L the running sum of g, y_t = exp(L_t) S_0 C_t + sum_(s<=t) exp(L_t -
  L_s) (C_t . B_s) v_s: every exponent <= 0, C B^T ONE [sub, sub] product a
  GROUP, shared by its heads, and only the sub-chunks run one after the
  other. The form of `forward()`, `generate()` and every CPU run.
- `ssd_step_pooled`: `ssd_step` as ONE Pallas kernel over a serving cache's
  state pool [L, slots, H, P, N], in place: of each row of the batch that
  holds a token the slot's matrices come into VMEM once, a block of one
  group's heads at a time, and go back where they were; a row without a
  token moves no byte (`ops.gated_delta.gated_delta_step_pooled`'s
  arrangement). `ssd_kernel_suits` says which steps take it.
- `ssd_chunk_pooled`: `ssd_chunked` as ONE Pallas kernel over the same pool,
  in place: the served form of a prefill chunk on a chip. A grid step is a
  (row with work, group) pair: the group's heads' states come into VMEM
  once, stay there across the chunk's sub-chunks and go back once; the
  heads are walked in PAIRS, whose 2 x 64 channels fill the 128 lanes of
  every product. A rung's pad rows and unmapped rows move no byte and
  compute nothing. `ssd_chunk_suits` says which chunks take it.

A position that carries no token (chunk padding, an idle slot) is made inert
by its caller: g = 0 and v = 0 leave the state as it was. Everything is
float32 and every product is asked for at the highest precision, for
`ops.gated_delta`'s reason: the state lives for tens of thousands of tokens.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from picotron_tpu.ops.gated_delta import work_first
from picotron_tpu.ops.paged_attention import (
    _LANES, _divisor, compiled_kernels_available,
)

F32 = jnp.float32
SUB = 128  # positions a sub-chunk: C B^T is one [128, 128] product a group
# Heads a DMA of the decode step: 16 matrices of 64 x 128 float32 are 512 KiB,
# in and out and double-buffered 2 MiB of VMEM; a block lies inside one group.
STEP_HEAD_BLOCK = 16


def _per_head(x, heads: int):
    """b or c [..., G, N] a row a HEAD: each group serves heads / G heads."""
    return jnp.repeat(x, heads // x.shape[-2], axis=-2)


def ssd_step(v, g, b, c, state):
    """One token a row. v [R, H, P]; g [R, H]; b, c [R, G, N]; state [R, H, P,
    N], all float32 -> (y [R, H, P] WITHOUT the D x term, state')."""
    h = v.shape[1]
    state = (jnp.exp(g)[..., None, None] * state
             + v[..., :, None] * _per_head(b, h)[..., None, :])
    return jnp.sum(state * _per_head(c, h)[..., None, :], axis=-1), state


def ssd_scan(v, g, b, c, state):
    """A segment token by token. v [R, s, H, P]; g [R, s, H]; b, c [R, s, G,
    N]; state [R, H, P, N] -> (y [R, s, H, P], state')."""
    def one(s, xs):
        y, s = ssd_step(*xs, s)
        return s, y

    state, y = lax.scan(one, state.astype(F32), tuple(
        jnp.moveaxis(x.astype(F32), 1, 0) for x in (v, g, b, c)))
    return jnp.moveaxis(y, 0, 1), state


def ssd_chunked(v, g, b, c, state, sub: int = SUB):
    """A segment in sub-chunks of `sub` positions (the module docstring).
    Shapes as `ssd_scan`'s; s need not be a multiple of `sub` (the segment is
    padded with inert positions)."""
    r, s, h, p = v.shape
    q = min(sub, s)
    pad = -s % q
    n = (s + pad) // q

    def split(x):  # [R, s, ...] -> [n, R, q, ...]
        x = jnp.pad(x.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(r, n, q, *x.shape[2:]), 1, 0)

    v, g, b, c = (split(x) for x in (v, g, b, c))
    with jax.default_matmul_precision("highest"):
        lc = jnp.cumsum(g, axis=2)                              # [n, R, q, H]
        i, j = jnp.arange(q)[:, None], jnp.arange(q)[None, :]
        lh = jnp.moveaxis(lc, 3, 2)                             # [n, R, H, q]
        # exp(L_i - L_j) where j <= i; the other half would overflow
        decay = jnp.where(j <= i, jnp.exp(jnp.where(
            j <= i, lh[..., :, None] - lh[..., None, :], 0.0)), 0.0)
        cb = jnp.einsum("nrigk,nrjgk->nrgij", c, b)             # a GROUP's
        scores = decay * jnp.repeat(cb, h // cb.shape[2], axis=2)  # [n, R, H, q, q]
        within = jnp.einsum("nrhij,nrjhp->nrihp", scores, v)
        bh, ch = _per_head(b, h), _per_head(c, h)               # [n, R, q, H, N]

        def one(st, xs):
            v_i, lc_i, bh_i, ch_i, within_i = xs
            y = within_i + jnp.exp(lc_i)[..., None] * jnp.einsum(
                "rihk,rhpk->rihp", ch_i, st)
            last = lc_i[:, -1:]                                  # [R, 1, H]
            st = (jnp.exp(last[:, 0])[..., None, None] * st + jnp.einsum(
                "rihp,rihk->rhpk", v_i * jnp.exp(last - lc_i)[..., None], bh_i))
            return st, y

        state, y = lax.scan(one, state.astype(F32), (v, lc, bh, ch, within))
    return jnp.moveaxis(y, 0, 1).reshape(r, n * q, h, p)[:, :s], state


def ssd(v, g, b, c, state):
    """A segment from `state` in the plain form that suits its length: the
    rule itself for one position a row, else the chunked form. Shapes as
    `ssd_scan`'s."""
    if v.shape[1] == 1:
        y, state = ssd_step(v[:, 0], g[:, 0], b[:, 0], c[:, 0], state)
        return y[:, None], state
    return ssd_chunked(v, g, b, c, state)


# ---------------------------------------------------------------------------
# The decode step over a serving cache's state pool, in place.
# ---------------------------------------------------------------------------


def ssd_kernel_suits(s: int, pool) -> bool:
    """Whether a segment of `s` positions a row over a state pool [L, slots,
    H, P, N] is one `ssd_step_pooled` takes compiled: a decode step, a float32
    state of whole (8, 128) tiles a head, heads that fill rows of 128 lanes
    (the step's inputs and outputs are handed over [P, H] a row), a backend
    that compiles Pallas kernels."""
    return (s == 1 and pool.dtype == F32 and pool.shape[2] % _LANES == 0
            and pool.shape[3] % 8 == 0 and pool.shape[4] % _LANES == 0
            and compiled_kernels_available())


def _step_kernel(gi_ref, slot_ref, fresh_ref, decay_ref, vt_ref, b_ref, c_ref,
                 pool_in, pool_out, yt_ref, order, s_in, s_out, sems, *,
                 heads: int, per_group: int):
    rows, p, hv = vt_ref.shape
    blocks = hv // heads
    gi = gi_ref[0]
    yt_ref[...] = jnp.zeros(yt_ref.shape, yt_ref.dtype)

    # the rows with work, compacted: order[0 .. n) in the batch's order
    def note(r, n):
        @pl.when(slot_ref[r] >= 0)
        def _():
            order[n] = r
        return n + (slot_ref[r] >= 0).astype(jnp.int32)

    items = blocks * lax.fori_loop(0, rows, note, 0)

    # item t: block t % blocks of the heads of the (t // blocks)-th such row,
    # through buffer t % 2; its matrices are pool[gi, slot, the block's heads]
    def place(t):
        return (gi, slot_ref[order[t // blocks]],
                pl.ds((t % blocks) * heads, heads))

    def fetch(t):
        return pltpu.make_async_copy(pool_in.at[place(t)], s_in.at[t % 2],
                                     sems.at[0, t % 2])

    def store(t):
        return pltpu.make_async_copy(s_out.at[t % 2], pool_out.at[place(t)],
                                     sems.at[1, t % 2])

    @pl.when(items > 0)
    def _first():
        fetch(0).start()

    lane = lax.broadcasted_iota(jnp.int32, (p, hv), 1)

    def item(t, _):
        r = order[t // blocks]
        h0 = (t % blocks) * heads
        buf = t % 2

        @pl.when(t + 1 < items)
        def _next():
            fetch(t + 1).start()

        fetch(t).wait()

        @pl.when(fresh_ref[r] != 0)
        def _start():  # position 0: whatever the row holds, zeros
            s_in[buf] = jnp.zeros(s_in.shape[1:], F32)

        @pl.when(t >= 2)
        def _free():
            # item t - 2's matrices are on their way out of this buffer
            store(t - 2).wait()

        grp = h0 // per_group            # the block's heads share one B and C
        brow = b_ref[r, pl.ds(grp, 1), :]                       # [1, N]
        crow = c_ref[r, pl.ds(grp, 1), :]
        vt = vt_ref[r]                                          # [P, H]

        def head(j, o):
            h = h0 + j
            # head h's value as a column, P down the sublanes as the state's
            # rows are: one lane of the tile, the others zeros
            vc = jnp.sum(jnp.where(lane == h, vt, 0.0), axis=1, keepdims=True)
            # ssd_step, expression for expression
            s = decay_ref[r, h] * s_in[buf, j] + vc * brow
            s_out[buf, j] = s
            return jnp.where(lane == h,
                             jnp.sum(s * crow, axis=1, keepdims=True), o)

        o = lax.fori_loop(0, heads, head, jnp.zeros((p, hv), F32))
        yt_ref[r] = yt_ref[r] + o  # the block's lanes; zeros everywhere else
        store(t).start()

    lax.fori_loop(0, items, item, None)
    for back in (1, 2):  # the two stores still in flight, one a buffer
        @pl.when(items >= back)
        def _drain():
            store(items - back).wait()


def ssd_step_pooled(v, g, b, c, pool, gi, rows, live, fresh, *,
                    interpret: Optional[bool] = None):
    """`ssd_step` for the batch's rows that hold a token, on mixer `gi`'s rows
    of a state pool, in place.

    v [R, H, P]; g [R, H]; b, c [R, G, N]; pool [L, slots, H, P, N], all
    float32; gi: the mixer (a scalar, traced or not); rows [R] int32: row r's
    slot, `slots` or more = unmapped; live [R] bool: the row holds a token;
    fresh [R] bool: it starts its sequence (the state it carries in is zeros,
    whatever the pool holds). No two rows with work share a slot. Returns (y
    [R, H, P] without the D x term, pool'): for a live, mapped row `ssd_step`'s
    y and its state' at pool'[gi, rows[r]]; any other row's y is zeros, and
    every bit of the pool outside the worked rows' matrices of mixer gi is as
    it was: nothing there is read or written.

    One grid step; the pool is handed over whole in HBM and aliased to the
    output; the rows with work are walked in blocks of `STEP_HEAD_BLOCK` heads
    (the largest divisor of a group's heads up to it: a block's heads share
    one B and one C), one DMA in and one out a block, double-buffered both
    ways, so a matrix crosses the memory bus once each way. Inside: float32 on
    the vector unit alone, `ssd_step`'s expressions in its order; v is handed
    over [P, H] a row and a head's value is that tile's lane h as a column (P
    down the sublanes, as the state's rows are), y comes back the same way.
    The sum over N is the only place where the order of additions may differ
    from the plain form's. `interpret=None` compiles on a TPU backend and runs
    the Pallas interpreter anywhere else; the caller decides whether the
    shapes suit the compiled kernel (`ssd_kernel_suits`)."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    if (pool.shape[2:] != v.shape[1:] + b.shape[2:] or pool.dtype != F32
            or v.shape[1] % b.shape[1] or b.shape != c.shape):
        raise ValueError(f"pool {pool.shape} {pool.dtype} does not match v "
                         f"{v.shape} / b {b.shape} / c {c.shape} in float32")
    per_group = v.shape[1] // b.shape[1]
    return _step_pooled_call(
        v, g, b, c, pool, gi, rows, live, fresh,
        heads=_divisor(per_group, STEP_HEAD_BLOCK), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _step_pooled_call(v, g, b, c, pool, gi, rows, live, fresh, *, heads: int,
                      interpret: bool):
    """`ssd_step_pooled`, jitted: a period's mixers call it with the same
    shapes, and a jitted function is traced and lowered once a program
    however many call it."""
    r, hv, p = v.shape
    n = b.shape[-1]
    slot = jnp.where(live & (rows < pool.shape[1]), rows, -1)

    def whole(x):
        return pl.BlockSpec(x.shape, lambda *_: (0,) * x.ndim,
                            memory_space=pltpu.VMEM)

    vt = jnp.swapaxes(v, 1, 2)                                  # [R, P, H]
    pool, yt = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads,
                          per_group=hv // b.shape[1]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # the mixer, the rows' slots, their starts
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      whole(vt), whole(b), whole(c),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), whole(vt)],
            scratch_shapes=[
                pltpu.SMEM((r,), jnp.int32),
                pltpu.VMEM((2, heads, p, n), F32),
                pltpu.VMEM((2, heads, p, n), F32),
                pltpu.SemaphoreType.DMA((2, 2)),  # (in | out, buffer)
            ]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(vt.shape, F32)],
        input_output_aliases={7: 0},  # the pool, counted with the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(96 << 20, 4 * 4 * (vt.size + b.size)
                                     + 4 * 4 * heads * p * n + (8 << 20)))),
        interpret=interpret,
        name="ssd_step_pooled",
    )(jnp.asarray(gi, jnp.int32).reshape(1), slot.astype(jnp.int32),
      fresh.astype(jnp.int32), jnp.exp(g), vt, b, c, pool)
    return jnp.swapaxes(yt, 1, 2), pool


# ---------------------------------------------------------------------------
# A prefill chunk over a serving cache's state pool, in place.
# ---------------------------------------------------------------------------


def ssd_chunk_suits(s: int, groups: int, pool) -> bool:
    """Whether a segment of `s` positions a row, its B and C over `groups`
    groups, over a state pool [L, slots, H, P, N] is one `ssd_chunk_pooled`
    takes compiled: whole sub-chunks of `SUB` positions (so never a decode
    step), a float32 state whose pairs of heads fill 128 lanes (P = 64) and
    whose N is whole rows of lanes, groups of whole sublane tiles of heads, a
    backend that compiles Pallas kernels."""
    heads, p, n = pool.shape[2:]
    return (s > 1 and s % SUB == 0 and pool.dtype == F32 and 2 * p == _LANES
            and n % _LANES == 0 and heads % groups == 0
            and (heads // groups) % 8 == 0 and compiled_kernels_available())


def _mm(a, b, b_dim: int = 0):
    """a [m, k] with b over its dimension `b_dim` ([k, n], or [n, k] for 1),
    float32 at the precision the plain forms ask for."""
    return lax.dot_general(a, b, (((1,), (b_dim,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=F32)


def _chunk_kernel(gi_ref, slot_ref, fresh_ref, order_ref, n_ref, v_ref, b_ref,
                  c_ref, lc_ref, lr_ref, pool_in, pool_out, o_ref, s_buf, sems,
                  *, sub: int):
    """Grid step (t, g): group g's heads of the t-th row with work, all the
    chunk's sub-chunks of each PAIR of heads (2u and 2u + 1 of the group): a
    pair's states stand one under the other, [2 P, N], its values and outputs
    side by side along the lanes, [sub, 2 P]."""
    t, g = pl.program_id(0), pl.program_id(1)
    s = v_ref.shape[0]
    per, p, n = s_buf.shape
    q, subs = sub, s // sub

    @pl.when(t >= n_ref[0])
    def _idle():  # a row without work: its y is zeros, nothing else moves
        o_ref[...] = jnp.zeros(o_ref.shape, F32)

    @pl.when(t < n_ref[0])
    def _work():
        r = order_ref[t]
        place = (gi_ref[0], slot_ref[r], pl.ds(g * per, per))
        fetch = pltpu.make_async_copy(pool_in.at[place], s_buf, sems.at[0])
        store = pltpu.make_async_copy(s_buf, pool_out.at[place], sems.at[1])
        carried = fresh_ref[r] == 0

        @pl.when(carried)
        def _():
            fetch.start()

        # what does not wait for the state: C B^T of every sub-chunk, ONE
        # product the group's heads share
        i = lax.broadcasted_iota(jnp.int32, (q, q), 0)
        causal = lax.broadcasted_iota(jnp.int32, (q, q), 1) <= i
        cb = [_mm(c_ref[pl.ds(m * q, q), :], b_ref[pl.ds(m * q, q), :], 1)
              for m in range(subs)]
        head = lax.broadcasted_iota(jnp.int32, (q, lc_ref.shape[1]), 1)
        first = lax.broadcasted_iota(jnp.int32, (q, 2 * p), 1) < p
        upper = lax.broadcasted_iota(jnp.int32, (2 * p, n), 0) < p

        @pl.when(carried)
        def _():
            fetch.wait()

        @pl.when(jnp.logical_not(carried))
        def _():  # position 0: whatever the row holds, zeros
            s_buf[...] = jnp.zeros(s_buf.shape, F32)

        def pair(u, _):
            h0 = g * per + 2 * u
            lanes = pl.ds(pl.multiple_of(2 * u * p, 2 * p), 2 * p)
            st = s_buf[pl.ds(2 * u, 2)].reshape(2 * p, n)
            for m in range(subs):
                at = pl.ds(m * q, q)
                vp = v_ref[at, lanes]                            # [q, 2 P]
                # L of the pair's heads: a column a head for what scales a
                # position's row, a row of lanes for the decay matrix's columns
                lc = lc_ref[at, :]
                la_m = jnp.sum(jnp.where(head == h0, lc, 0.0), axis=1,
                               keepdims=True)                    # [q, 1]
                lb_m = jnp.sum(jnp.where(head == h0 + 1, lc, 0.0), axis=1,
                               keepdims=True)
                # exp(L_i - L_j) where j <= i; the other half would overflow
                da = jnp.where(causal, jnp.exp(jnp.where(
                    causal, la_m - lr_ref[2 * u, pl.ds(m, 1), :], 0.0)), 0.0)
                db = jnp.where(causal, jnp.exp(jnp.where(
                    causal, lb_m - lr_ref[2 * u + 1, pl.ds(m, 1), :], 0.0)), 0.0)
                within = (_mm(da * cb[m], jnp.where(first, vp, 0.0))
                          + _mm(db * cb[m], jnp.where(first, 0.0, vp)))
                from_state = _mm(c_ref[at, :], st, 1)            # [q, 2 P]
                o_ref[at, lanes] = within + from_state * jnp.exp(
                    jnp.where(first, la_m, lb_m))
                last_a, last_b = la_m[q - 1:], lb_m[q - 1:]      # [1, 1]
                w = jnp.exp(jnp.where(first, last_a - la_m, last_b - lb_m))
                st = (jnp.exp(jnp.where(upper, last_a, last_b)) * st
                      + _mm((vp * w).T, b_ref[at, :]))
            s_buf[pl.ds(2 * u, 2)] = st.reshape(2, p, n)

        lax.fori_loop(0, per // 2, pair, None)
        store.start()
        store.wait()


def ssd_chunk_pooled(v, g, b, c, pool, gi, rows, live, fresh, *,
                     sub: int = SUB, interpret: Optional[bool] = None):
    """`ssd_chunked` for the batch's rows that hold a real position, on mixer
    `gi`'s rows of a state pool, in place.

    v [R, s, H, P]; g [R, s, H]; b, c [R, s, G, N]; pool [L, slots, H, P, N],
    all float32; gi, rows, live, fresh as `ssd_step_pooled`'s (live: the row
    holds a real position; a padded position INSIDE a live row is made inert
    by the caller). s is whole sub-chunks of `sub`, a group's heads come in
    pairs. Returns (y [R, s, H, P], pool'): for a live, mapped row the chunked
    form's y and its state' at pool'[gi, rows[r]]; any other row's y is zeros,
    and nothing of the pool outside the worked rows' matrices of mixer gi is
    read or written."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    sub = min(sub, v.shape[1])
    if (pool.shape[2:] != v.shape[2:] + b.shape[3:] or pool.dtype != F32
            or v.shape[1] % sub or v.shape[2] % (2 * b.shape[2])
            or b.shape != c.shape):
        raise ValueError(f"pool {pool.shape} {pool.dtype} does not match v "
                         f"{v.shape} / b {b.shape} in float32 sub-chunks of "
                         f"{sub}, heads in pairs a group")
    return _chunk_pooled_call(v, g, b, c, pool, gi, rows, live, fresh,
                              sub=sub, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def _chunk_pooled_call(v, g, b, c, pool, gi, rows, live, fresh, *, sub: int,
                       interpret: bool):
    """`ssd_chunk_pooled`, jitted for `_step_pooled_call`'s reason."""
    r, s, hv, p = v.shape
    grp, n = b.shape[2:]
    per = hv // grp
    work = live & (rows < pool.shape[1])
    order, count = work_first(work)
    # L, the running sum of g inside a sub-chunk, a column a head and a row
    lc = jnp.cumsum(g.reshape(r, s // sub, sub, hv), axis=2).reshape(r, s, hv)
    lr = jnp.swapaxes(lc, 1, 2).reshape(r, hv, s // sub, sub)  # [R, H, subs, sub]

    def row(width, of_group=True):
        # of [R, s, width x groups]: the t-th row with work (the last, after),
        # group g's columns
        return pl.BlockSpec(
            (None, s, width), lambda t, g, gi, slot, fresh, order, n:
            (order[jnp.minimum(t, jnp.maximum(n[0], 1) - 1)], 0,
             g if of_group else 0))

    pool, y = pl.pallas_call(
        functools.partial(_chunk_kernel, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # the mixer, the rows' slots, their starts, the rows with work
            # first, their count
            num_scalar_prefetch=5,
            grid=(r, grp),
            in_specs=[row(per * p), row(n), row(n), row(hv, False),
                      pl.BlockSpec(
                          (None, per, s // sub, sub),
                          lambda t, g, gi, slot, fresh, order, n:
                          (order[jnp.minimum(t, jnp.maximum(n[0], 1) - 1)], g, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec((None, s, per * p),
                                    lambda t, g, gi, slot, fresh, order, n:
                                    (order[t], 0, g))],
            scratch_shapes=[pltpu.VMEM((per, p, n), F32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((r, s, hv * p), F32)],
        input_output_aliases={10: 0},  # the pool, counted with the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(96 << 20, 4 * 4 * s * (
                2 * per * p + 2 * n + hv + per) + (24 << 20)))),
        interpret=interpret,
        name="ssd_chunk_pooled",
    )(jnp.asarray(gi, jnp.int32).reshape(1),
      jnp.where(work, rows, 0).astype(jnp.int32), fresh.astype(jnp.int32),
      order, count.reshape(1), v.reshape(r, s, hv * p),
      b.reshape(r, s, grp * n), c.reshape(r, s, grp * n), lc, lr, pool)
    return y.reshape(r, s, hv, p), pool
