"""The selective scan of a Mamba-1 mixer (`mamba` layers: Jamba), whose
state is not a row a position but one vector of `d_state` numbers a channel,
S [d_inner, d_state] float32 a sequence, carried from token to token by a
DIAGONAL recurrence: no matrix product, an input-dependent step. For a token
with channel inputs u [d_inner], step dt [d_inner] > 0, B and C [d_state]
and the layer's A [d_inner, d_state] < 0 and D [d_inner]:

    S = exp(dt[:, None] A) S + (dt u)[:, None] B[None, :];   y = S C + D u

Every function here holds the state TRANSPOSED, [d_state, d_inner], and takes
A so: the 16 states of a channel lie down the sublanes and the 5,120 channels
along the lanes, where a [5120, 16] array would fill an eighth of every
(8, 128) tile it is stored in and a pool of them eight times its bytes.

- `selective_scan_step`: one token a row, the rule as written above. The
  decode step's form, and under `lax.scan` the token-by-token form of any
  segment (`selective_scan`): the form of `forward()`, of `generate()` and
  of every CPU run. The recurrence has no chunked form in matrix products
  (the decay differs a (position, channel, state) triple), so a segment costs
  its positions one after the other whatever runs it; what a kernel saves is
  the state's trip to HBM and back between them.
- `selective_scan_step_pooled`: `selective_scan_step` as ONE Pallas kernel
  over a serving cache's state pool [L_ssm, slots, d_state, d_inner], in
  place: the pool stays in HBM and is the kernel's output too, and of each
  row of the batch that holds a token the kernel brings the slot's state into
  VMEM once (320 KiB at Jamba2-3B's widths), updates it and writes it back
  where it was. A row without a token moves no byte either way: a decode
  step costs the LIVE rows' state, not the batch's
  (`ops.gated_delta.gated_delta_step_pooled` is the same arrangement for a
  matrix state; PERF.md section 6, PRs 52 and 55). `ssm_kernel_suits` says
  which steps take it.
- `conv_step_pooled`: the causal convolution of a decode step
  (`ops.gated_delta.causal_conv` at one position a row) as ONE Pallas kernel
  over the cache's TAIL pool [L_ssm, slots, (kernel - 1) x d_inner / 128, 128]
  (`tail_shape`), in place,
  the same arrangement: a live row's 60 KiB of tail in, the convolution with
  its bias and SiLU, the tail shifted by the new position and back where it
  was; a row without a token moves nothing (gathered and scattered by
  jax.numpy, the tails of all 128 slots of the batch cost a step more than
  the live rows' states: PERF.md section 6, PR 55).
- `selective_scan_chunk_pooled`: `selective_scan` as ONE Pallas kernel over
  the same pool, in place: the served form of a prefill chunk on a chip. The
  channels are independent, so the kernel walks (block of `CHUNK_CHANNELS`
  channels, row with a real position): the block's share of the row's state
  comes into VMEM once, is carried in registers from position to position
  (under `lax.scan` in jax.numpy it went to HBM and back 256 times a chunk,
  for every row of the rung: PERF.md section 6, PR 55) and goes back once; a
  row's positions are walked in groups of 8 as far as its last real one, and
  a rung's pad rows and unmapped rows move no byte and compute nothing.
  `ssm_chunk_suits` says which chunks take it; `forward()`, `generate()`,
  every CPU run and the tiny test models keep `selective_scan`.

A position that carries no token (chunk padding, an idle slot) is made inert
by its caller: dt = 0 leaves the state as it was (exp(0) = 1, nothing
written). Everything is float32. The causal convolution in front of the scan
is `ops.gated_delta.causal_conv`, with this mixer's bias.
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
_SUBLANES = 8


def selective_scan_step(u, dt, b, c, a, state):
    """One token a row. u, dt [R, Di]; b, c [R, N]; a [N, Di]; state [R, N,
    Di], all float32 -> (y [R, Di] WITHOUT the D u term, state')."""
    state = (jnp.exp(dt[:, None, :] * a) * state
             + (dt * u)[:, None, :] * b[:, :, None])
    return jnp.sum(state * c[:, :, None], axis=1), state


def selective_scan(u, dt, b, c, a, state):
    """A segment token by token. u, dt [R, s, Di]; b, c [R, s, N]; a [N, Di];
    state [R, N, Di] -> (y [R, s, Di] without the D u term, state')."""
    def one(s, xs):
        y, s = selective_scan_step(*xs, a, s)
        return s, y

    state, y = lax.scan(one, state.astype(F32), tuple(
        jnp.moveaxis(x.astype(F32), 1, 0) for x in (u, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state


def scan_segment(u, dt, b, c, a, state):
    """A segment from `state` in the plain form that suits its length: the
    rule itself for one position a row, else the scan. Shapes as
    `selective_scan`'s."""
    if u.shape[1] == 1:
        y, state = selective_scan_step(u[:, 0], dt[:, 0], b[:, 0], c[:, 0], a,
                                       state)
        return y[:, None], state
    return selective_scan(u, dt, b, c, a, state)


# ---------------------------------------------------------------------------
# The decode step over a serving cache's state pool, in place.
# ---------------------------------------------------------------------------


def ssm_kernel_suits(s: int, pool) -> bool:
    """Whether a segment of `s` positions a row over a state pool [L_ssm,
    slots, d_state, d_inner] is one `selective_scan_step_pooled` takes
    compiled: a decode step (one position a row), a float32 state of whole
    (8, 128) tiles, a backend that compiles Pallas kernels."""
    return (s == 1 and pool.dtype == F32 and pool.shape[2] % _SUBLANES == 0
            and pool.shape[3] % _LANES == 0 and compiled_kernels_available())


def _walk_live_rows(gi_ref, slot_ref, fresh_ref, pool_in, pool_out, order,
                    held_in, held_out, sems, update):
    """The body both decode kernels share: the rows with work (slot >= 0)
    compacted into `order`, then walked one after the other: what the pool
    holds for the row's slot of mixer gi comes into `held_in[t % 2]` by one
    DMA (the next row's is already on its way), is zeros where the row is
    fresh, `update(r, buf)` reads it and fills `held_out[buf]`, which goes
    back where it was by one DMA (waited for two rows later, when the buffer
    is needed again)."""
    gi = gi_ref[0]

    def note(r, n):
        @pl.when(slot_ref[r] >= 0)
        def _():
            order[n] = r
        return n + (slot_ref[r] >= 0).astype(jnp.int32)

    items = lax.fori_loop(0, slot_ref.shape[0], note, 0)

    def fetch(t):
        return pltpu.make_async_copy(
            pool_in.at[gi, slot_ref[order[t]]], held_in.at[t % 2],
            sems.at[0, t % 2])

    def store(t):
        return pltpu.make_async_copy(
            held_out.at[t % 2], pool_out.at[gi, slot_ref[order[t]]],
            sems.at[1, t % 2])

    @pl.when(items > 0)
    def _first():
        fetch(0).start()

    def item(t, _):
        r = order[t]
        buf = t % 2

        @pl.when(t + 1 < items)
        def _next():
            fetch(t + 1).start()

        fetch(t).wait()

        @pl.when(fresh_ref[r] != 0)
        def _start():  # position 0: whatever the row holds, zeros
            held_in[buf] = jnp.zeros(held_in.shape[1:], F32)

        @pl.when(t >= 2)
        def _free():
            # item t - 2's is on its way out of this buffer
            store(t - 2).wait()

        update(r, buf)
        store(t).start()

    lax.fori_loop(0, items, item, None)
    for back in (1, 2):  # the two stores still in flight, one a buffer
        @pl.when(items >= back)
        def _drain():
            store(items - back).wait()


def _step_kernel(gi_ref, slot_ref, fresh_ref, dt_ref, u_ref, bt_ref, ct_ref,
                 a_ref, pool_in, pool_out, y_ref, order, s_in, s_out, sems):
    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)
    lane = lax.broadcasted_iota(jnp.int32, bt_ref.shape, 1)
    a = a_ref[...]

    def update(r, buf):
        # row r's B and C as columns, d_state down the sublanes as the
        # state's rows are: one lane of the tile, the others zeros
        bc = jnp.sum(jnp.where(lane == r, bt_ref[...], 0.0), axis=1,
                     keepdims=True)
        cc = jnp.sum(jnp.where(lane == r, ct_ref[...], 0.0), axis=1,
                     keepdims=True)
        dt = dt_ref[pl.ds(r, 1), :]                           # [1, Di]
        u = u_ref[pl.ds(r, 1), :]
        # selective_scan_step, expression for expression
        s = jnp.exp(dt * a) * s_in[buf] + (dt * u) * bc
        s_out[buf] = s
        y_ref[pl.ds(r, 1), :] = jnp.sum(s * cc, axis=0, keepdims=True)

    _walk_live_rows(gi_ref, slot_ref, fresh_ref, pool_in, pool_out, order,
                    s_in, s_out, sems, update)


def selective_scan_step_pooled(u, dt, b, c, a, pool, gi, rows, live, fresh, *,
                               interpret: Optional[bool] = None):
    """`selective_scan_step` for the batch's rows that hold a token, on mixer
    `gi`'s rows of a state pool, in place.

    u, dt [R, Di]; b, c [R, N]; a [N, Di]; pool [L_ssm, slots, N, Di], all
    float32; gi: the mixer (a scalar, traced or not); rows [R] int32: row r's
    slot, `slots` or more = unmapped; live [R] bool: the row holds a token;
    fresh [R] bool: it starts its sequence (the state it carries in is zeros,
    whatever the pool holds). No two rows with work share a slot. Returns
    (y [R, Di] without the D u term, pool'): for a live, mapped row
    `selective_scan_step`'s y and its state' at pool'[gi, rows[r]]; any other
    row's y is zeros, and every bit of the pool outside the worked rows'
    states of mixer gi is as it was: nothing there is read or written.

    One grid step; the pool is handed over whole in HBM and aliased to the
    output; the rows with work are walked one state a DMA in and one out,
    double-buffered both ways, so a state crosses the memory bus once each
    way. Inside: float32 on the vector unit, the plain form's expressions in
    its order; B and C are handed over [N, R], and a row's is that tile's
    lane r as a column. The sum over d_state is the only place where the
    order of additions may differ from the plain form's. `interpret=None`
    compiles on a TPU backend and runs the Pallas interpreter anywhere else;
    the caller decides whether the shapes suit the compiled kernel
    (`ssm_kernel_suits`)."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    if pool.shape[2:] != a.shape or pool.dtype != F32:
        raise ValueError(f"pool {pool.shape} {pool.dtype} does not match "
                         f"A {a.shape} in float32")
    return _step_pooled_call(u, dt, b, c, a, pool, gi, rows, live, fresh,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pooled_call(u, dt, b, c, a, pool, gi, rows, live, fresh, *,
                      interpret: bool):
    """`selective_scan_step_pooled`, jitted: a period's mixers call it with
    the same shapes, and a jitted function is traced and lowered once a
    program however many call it."""
    r, di = u.shape
    n = a.shape[0]
    slot = jnp.where(live & (rows < pool.shape[1]), rows, -1)

    def whole(x):
        return pl.BlockSpec(x.shape, lambda *_: (0,) * x.ndim,
                            memory_space=pltpu.VMEM)

    bt, ct = b.T, c.T
    pool, y = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # the mixer, the rows' slots, their starts
            grid=(1,),
            in_specs=[whole(dt), whole(u), whole(bt), whole(ct), whole(a),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), whole(u)],
            scratch_shapes=[
                pltpu.SMEM((r,), jnp.int32),
                pltpu.VMEM((2, n, di), F32),
                pltpu.VMEM((2, n, di), F32),
                pltpu.SemaphoreType.DMA((2, 2)),  # (in | out, buffer)
            ]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(u.shape, F32)],
        input_output_aliases={8: 0},  # the pool, counted with the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # dt, u and y whole (R x Di float32 each, twice where the
            # pipeline double-buffers a block) beside the four state buffers
            vmem_limit_bytes=int(min(96 << 20, (8 * r * di + 6 * n * di) * 4
                                     + (8 << 20)))),
        interpret=interpret,
        name="selective_scan_step_pooled",
    )(jnp.asarray(gi, jnp.int32).reshape(1), slot.astype(jnp.int32),
      fresh.astype(jnp.int32), dt, u, bt, ct, a, pool)
    return y, pool


# ---------------------------------------------------------------------------
# The decode step's convolution over a serving cache's tail pool, in place.
# ---------------------------------------------------------------------------


def tail_shape(channels: int, kernel: int) -> tuple:
    """The shape of one sequence's convolution tail, the last kernel - 1
    inputs, position-major: rows of 128 lanes, [(kernel - 1) x channels / 128,
    128]. A slot's tail in a pool is then whole (8, 128) tiles that one DMA
    moves (a single row of a [slots, (kernel - 1) x channels] array is a slice
    of its tiles, which no DMA takes), and a position's channels are
    `channels / 128` whole rows."""
    return ((kernel - 1) * channels // _LANES, _LANES)


def conv_kernel_suits(s: int, pool) -> bool:
    """Whether the convolution of a segment of `s` positions a row over a
    tail pool [L_ssm, slots, (kernel - 1) x channels / 128, 128] is one
    `conv_step_pooled` takes compiled: a decode step, a float32 tail whose
    positions are whole sublane tiles, a backend that compiles Pallas
    kernels."""
    return (s == 1 and pool.dtype == F32 and pool.shape[3] == _LANES
            and pool.shape[2] % _SUBLANES == 0 and compiled_kernels_available())


def _conv_kernel(gi_ref, slot_ref, fresh_ref, x_ref, w_ref, bias_ref, pool_in,
                 pool_out, y_ref, order, t_in, t_out, sems):
    q = x_ref.shape[1]                  # rows of 128 lanes a position
    before = t_in.shape[1] // q         # kernel - 1
    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    def update(r, buf):
        x = x_ref[r]                                            # [q, 128]
        # causal_conv at one position, tap for tap in its order
        acc = w_ref[0] * t_in[buf, 0:q]
        for j in range(1, before):
            acc = acc + w_ref[j] * t_in[buf, j * q:(j + 1) * q]
        acc = acc + w_ref[before] * x + bias_ref[...]
        y_ref[r] = acc * jax.nn.sigmoid(acc)
        if before > 1:
            t_out[buf, 0:(before - 1) * q] = t_in[buf, q:before * q]
        t_out[buf, (before - 1) * q:before * q] = x

    _walk_live_rows(gi_ref, slot_ref, fresh_ref, pool_in, pool_out, order,
                    t_in, t_out, sems, update)


def conv_step_pooled(x, w, bias, pool, gi, rows, live, fresh, *,
                     interpret: Optional[bool] = None):
    """`ops.gated_delta.causal_conv` at one position a row for the batch's
    rows that hold a token, on mixer `gi`'s rows of a tail pool, in place.

    x [R, C] float32: the position's channels; w [C, K], w[:, K - 1] the
    current position's tap; bias [C] or None; pool [L_ssm, slots, (K - 1) x
    C / 128, 128] float32, position-major (`tail_shape`); gi, rows, live,
    fresh as `selective_scan_step_pooled`'s. Returns (y [R, C] float32 after
    the SiLU, pool'): for a live, mapped row the convolution over its tail
    (zeros where it is fresh) and the position, and its tail' = the last K - 1
    positions at pool'[gi, rows[r]]; any other row's y is zeros, and nothing
    of the pool outside the worked rows' tails of mixer gi is read or
    written."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    c, k = w.shape
    if pool.shape[2:] != tail_shape(c, k) or pool.dtype != F32 or x.shape[1] != c:
        raise ValueError(f"pool {pool.shape} {pool.dtype} does not match x "
                         f"{x.shape} and w {w.shape} in float32")
    if bias is None:
        bias = jnp.zeros((c,), F32)
    q = c // _LANES
    y, pool = _conv_pooled_call(
        x.astype(F32).reshape(-1, q, _LANES), w.astype(F32).T.reshape(k, q, _LANES),
        bias.astype(F32).reshape(q, _LANES), pool, gi, rows, live, fresh,
        interpret=interpret)
    return y.reshape(x.shape), pool


@functools.partial(jax.jit, static_argnames=("interpret",))
def _conv_pooled_call(x, wt, bias, pool, gi, rows, live, fresh, *,
                      interpret: bool):
    """`conv_step_pooled`, jitted for `_step_pooled_call`'s reason."""
    r = x.shape[0]
    slot = jnp.where(live & (rows < pool.shape[1]), rows, -1)

    def whole(a):
        return pl.BlockSpec(a.shape, lambda *_: (0,) * a.ndim,
                            memory_space=pltpu.VMEM)

    pool, y = pl.pallas_call(
        _conv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # the mixer, the rows' slots, their starts
            grid=(1,),
            in_specs=[whole(x), whole(wt), whole(bias),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), whole(x)],
            scratch_shapes=[
                pltpu.SMEM((r,), jnp.int32),
                pltpu.VMEM((2,) + pool.shape[2:], F32),
                pltpu.VMEM((2,) + pool.shape[2:], F32),
                pltpu.SemaphoreType.DMA((2, 2)),  # (in | out, buffer)
            ]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(x.shape, F32)],
        input_output_aliases={6: 0},  # the pool, counted with the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(96 << 20, 4 * 4 * x.size + (8 << 20)))),
        interpret=interpret,
        name="ssm_conv_step_pooled",
    )(jnp.asarray(gi, jnp.int32).reshape(1), slot.astype(jnp.int32),
      fresh.astype(jnp.int32), x, wt, bias, pool)
    return y, pool


# ---------------------------------------------------------------------------
# A prefill chunk over a serving cache's state pool, in place.
# ---------------------------------------------------------------------------

CHUNK_CHANNELS = 1280  # channels a block: a [16, 1280] state is 20 vector registers
CHUNK_GROUP = 8        # positions a load of dt and u and a store of y: one sublane tile


def ssm_chunk_suits(s: int, pool) -> bool:
    """Whether a segment of `s` positions a row over a state pool [L_ssm,
    slots, d_state, d_inner] is one `selective_scan_chunk_pooled` takes
    compiled: more than one position a row in whole groups of 8, a float32
    state of whole (8, 128) tiles, a backend that compiles Pallas kernels."""
    return (s > 1 and s % CHUNK_GROUP == 0 and pool.dtype == F32
            and pool.shape[2] % _SUBLANES == 0 and pool.shape[3] % _LANES == 0
            and compiled_kernels_available())


def _chunk_kernel(gi_ref, slot_ref, fresh_ref, order_ref, nvalid_ref, n_ref,
                  dt_ref, u_ref, bt_ref, ct_ref, a_ref, pool_in, pool_out,
                  y_ref, state, sems):
    d, t = pl.program_id(0), pl.program_id(1)
    s, width = dt_ref.shape
    g = CHUNK_GROUP

    @pl.when(t < n_ref[0])
    def _row():
        r = order_ref[t]
        place = (gi_ref[0], slot_ref[r], slice(None), pl.ds(d * width, width))
        fetch = pltpu.make_async_copy(pool_in.at[place], state, sems.at[0])
        fetch.start()
        fetch.wait()

        @pl.when(fresh_ref[r] != 0)
        def _start():  # position 0: whatever the row holds, zeros
            state[...] = jnp.zeros(state.shape, F32)

        a = a_ref[...]
        bt, ct = bt_ref[...], ct_ref[...]                     # [N, s]
        lane = lax.broadcasted_iota(jnp.int32, bt.shape, 1)
        sub = lax.broadcasted_iota(jnp.int32, (g, width), 0)

        def group(k, st):
            t0 = pl.multiple_of(k * g, g)
            dts, us = dt_ref[pl.ds(t0, g), :], u_ref[pl.ds(t0, g), :]
            ys = jnp.zeros((g, width), F32)
            for j in range(g):
                # position t0 + j's B and C as columns, d_state down the
                # sublanes as the state's rows are
                bc = jnp.sum(jnp.where(lane == t0 + j, bt, 0.0), axis=1,
                             keepdims=True)
                cc = jnp.sum(jnp.where(lane == t0 + j, ct, 0.0), axis=1,
                             keepdims=True)
                dt, u = dts[j:j + 1], us[j:j + 1]
                # selective_scan_step, expression for expression
                st = jnp.exp(dt * a) * st + (dt * u) * bc
                ys = jnp.where(sub == j, jnp.sum(st * cc, axis=0, keepdims=True), ys)
            y_ref[pl.ds(t0, g), :] = ys
            return st

        # as far as the row's last real position (the positions behind it in
        # its last group are inert: dt = 0)
        st = lax.fori_loop(0, pl.cdiv(nvalid_ref[r], g), group, state[...])
        state[...] = st
        store = pltpu.make_async_copy(state, pool_out.at[place], sems.at[1])
        store.start()
        store.wait()


def selective_scan_chunk_pooled(u, dt, b, c, a, pool, gi, rows, n_valid, fresh,
                                *, interpret: Optional[bool] = None):
    """`selective_scan` for the batch's rows that hold a real position, on
    mixer `gi`'s rows of a state pool, in place.

    u, dt [R, s, Di]; b, c [R, s, N]; a [N, Di]; pool [L_ssm, slots, N, Di],
    all float32; gi: the mixer; rows [R] int32: row r's slot, `slots` or more
    = unmapped; n_valid [R] int32: the row's real positions, a prefix of it
    (0: none; dt = 0 behind them, the caller's); fresh [R] bool: the row
    starts its sequence. No two rows with work share a slot. Returns (y [R, s,
    Di] without the D u term, pool'): for a mapped row with real positions
    `selective_scan`'s y at those positions and its state' at pool'[gi,
    rows[r]]; what y holds anywhere else is NOT defined (the caller masks it:
    `HybridPagedCache.scan`), and nothing of the pool outside the worked
    rows' states of mixer gi is read or written."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    if pool.shape[2:] != a.shape or pool.dtype != F32 or u.shape[1] % CHUNK_GROUP:
        raise ValueError(f"pool {pool.shape} {pool.dtype} does not match A "
                         f"{a.shape} in float32, positions in groups of "
                         f"{CHUNK_GROUP} ({u.shape[1]})")
    return _chunk_pooled_call(u, dt, b, c, a, pool, gi, rows, n_valid, fresh,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_pooled_call(u, dt, b, c, a, pool, gi, rows, n_valid, fresh, *,
                       interpret: bool):
    """`selective_scan_chunk_pooled`, jitted for `_step_pooled_call`'s reason."""
    r, s, di = u.shape
    n = a.shape[0]
    width = (CHUNK_CHANNELS if di % CHUNK_CHANNELS == 0
             else _LANES * _divisor(di // _LANES, CHUNK_CHANNELS // _LANES))
    work = (n_valid > 0) & (rows < pool.shape[1])
    order, count = work_first(work)

    def which(t, order, count):  # the t-th row with work; the last, after
        return order[jnp.minimum(t, jnp.maximum(count[0], 1) - 1)]

    def channels(d, t, gi, slot, fresh, order, nvalid, count):
        return which(t, order, count), 0, d

    def whole(d, t, gi, slot, fresh, order, nvalid, count):
        return which(t, order, count), 0, 0

    pool, y = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # the mixer, the rows' slots, their starts, the rows with work
            # first, the rows' real positions, the count of rows with work
            num_scalar_prefetch=6,
            # a block of channels, then the rows: the rows behind the last
            # one with work ask for the block they have, and move nothing
            grid=(di // width, r),
            in_specs=[pl.BlockSpec((None, s, width), channels),
                      pl.BlockSpec((None, s, width), channels),
                      pl.BlockSpec((None, n, s), whole),
                      pl.BlockSpec((None, n, s), whole),
                      pl.BlockSpec((n, width), lambda d, t, *_: (0, d)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec((None, s, width), channels)],
            scratch_shapes=[pltpu.VMEM((n, width), F32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(u.shape, F32)],
        input_output_aliases={11: 0},  # the pool, counted with the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(8 * s * width * 4 + (8 << 20))),
        interpret=interpret,
        name="selective_scan_chunk_pooled",
    )(jnp.asarray(gi, jnp.int32).reshape(1),
      jnp.where(work, rows, 0).astype(jnp.int32), fresh.astype(jnp.int32),
      order, jnp.where(work, n_valid, 0).astype(jnp.int32), count.reshape(1),
      dt, u, jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2), a, pool)
    return y, pool
