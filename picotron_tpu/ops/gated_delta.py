"""Gated DeltaNet: the recurrence of a `linear_attention` layer
(Qwen3-Next), whose state is not a row a position but one matrix a value
head, S [d_k, d_v] float32, carried from token to token by the gated delta
rule. For a token with key k and query q (L2-normalised, q scaled by
d_k^-0.5), value v, decay g <= 0 and write strength beta in (0, 1):

    S' = exp(g) S;   r = S'^T k;   S = S' + k (beta (v - r))^T;   o = S^T q

Five things live here, each a function of arrays alone:

- `causal_conv`: the depthwise convolution over the sequence that q, k and v
  pass through before the recurrence, with the `kernel - 1` positions before
  the segment carried in (a TAIL; zeros before position 0) and the tail
  after the segment's last real position handed back.
- `gated_delta_step`: one token a row, the rule as written above. The decode
  step's form, and under `lax.scan` the token-by-token form of any segment
  (`gated_delta_scan`, what the chunked form is tested against).
- `gated_delta_chunked`: a segment in sub-chunks of `sub` positions (64).
  Inside a sub-chunk the rule is solved for all its positions at once: with
  G the running sum of g, A_ij = beta_i (k_i . k_j) exp(G_i - G_j) for j < i,
  the rows u = (I + A)^-1 (beta v) and w = (I + A)^-1 (beta exp(G) k) are
  what each position writes given the state at the sub-chunk's start (one
  triangular solve of 64 x 64 a head, the rest matrix products), and only
  the sub-chunks run one after the other. Same mathematics as the step:
  tests/test_qwen3_next.py holds them together from a non-zero start state.
- `gated_delta_step_pooled`: `gated_delta_step` as ONE Pallas kernel over a
  serving cache's state pool [L_gdn, slots, H, d_k, d_v], in place: the pool
  stays in HBM and is the kernel's output too, and of each row of the batch
  that holds a token the kernel brings the slot's matrices into VMEM once,
  a block of heads at a time, updates them and writes them back where they
  were. A row without a token moves no byte either way (at the Qwen3-Next
  cell's 2.4 live rows of 16, gathering every row, the rule and the scatter
  back made 2.7 passes over ALL rows: PERF.md section 6, PR 52).
  `gated_delta_kernel_suits` says which steps take it; `gated_delta` is the
  plain forms' one entry (the step for one position, else the chunks).
- `gated_delta_chunk_pooled`: `gated_delta_chunked` as ONE Pallas kernel over
  the same pool, in place: the served form of a prefill chunk on a chip. A
  row's state comes into VMEM once, a few value heads at a time (in pairs
  beside one key head), stays there across the chunk's sub-chunks and goes
  back once; k k^T and q k^T are one product a key head, and (I + A)^-1
  is built from the diagonal outwards by matrix products alone: the inverse
  of blocks of 2 is I - A there, and where X inverts the diagonal blocks of
  m rows, X - X B X inverts those of 2m (B: A's blocks beside the diagonal
  that complete them), five times to 64. No power of A is ever formed, so
  nothing grows where a sub-chunk's keys are alike, and no row waits for the
  row above it as in a triangular solve. A rung's pad rows and unmapped rows
  move no byte and compute nothing. `gated_delta_chunk_suits` says which
  chunks take it (PERF.md section 6, PR 54); training, `forward()` under AD,
  every CPU run and the tiny test models keep `gated_delta_chunked`.

A position that carries no token (chunk padding, an idle slot) is made inert
by its caller: g = 0 and beta = 0 leave the state as it was. Everything is
float32; the matrix products are asked for at the highest precision, because
a float32 product on a TPU is otherwise rounded to bfloat16 on the way in,
and the state lives for tens of thousands of tokens.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from picotron_tpu.ops.paged_attention import (
    _LANES, _divisor, compiled_kernels_available,
)

F32 = jnp.float32
# Value heads a DMA: 8 matrices of 128 x 128 float32 are 512 KiB, in and out
# and double-buffered 2 MiB of VMEM, and the 8 heads' rows of v and o are one
# float32 sublane tile.
STEP_HEAD_BLOCK = 8


def causal_conv(x, tail, w, n_valid, bias=None):
    """Depthwise causal convolution over the sequence, then SiLU.

    x [B, s, C]: the segment's channels; tail [B, K - 1, C]: the channels of
    the K - 1 positions before it; w [C, K], w[:, K - 1] the current
    position's tap; n_valid [B]: the segment's real positions, a prefix of
    it; bias [C]: added before the SiLU (a Mamba mixer's; the Gated DeltaNet
    mixer's convolution has none). Returns (y [B, s, C] in x's dtype, the tail after the last real
    position [B, K - 1, C] in the tail's dtype: the old tail where
    n_valid is 0). At one position a row (a decode step) the new tail is
    one select between the two cases, a token or none; a longer segment
    takes K - 1 positions from each row's own n_valid by a slice a row
    (64 slots' slices were 0.9 of a 10.1 ms Kimi-Linear step: PERF.md
    section 6, PR 61)."""
    k = w.shape[-1]
    s = x.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(F32)
    y = sum(full[:, j:j + s].astype(F32) * wf[:, j] for j in range(k))
    if bias is not None:
        y = y + bias.astype(F32)
    if s == 1:
        # one position a row: a token or none, the two tails one select
        new_tail = jnp.where((n_valid >= 1)[:, None, None], full[:, 1:],
                             full[:, :-1])
    else:
        new_tail = jax.vmap(
            lambda f, n: lax.dynamic_slice_in_dim(f, n, k - 1, axis=0))(
                full, n_valid)
    return jax.nn.silu(y).astype(x.dtype), new_tail.astype(tail.dtype)


def l2_normalise(x, eps: float = 1e-6):
    """x / sqrt(sum x^2 + eps) over the last axis, float32."""
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_delta_step(q, k, v, g, beta, state):
    """One token a row. q, k [B, H, d_k]; v [B, H, d_v]; g, beta [B, H];
    state [B, H, d_k, d_v], all float32 -> (o [B, H, d_v], state'). g may
    also come a CHANNEL of the key, [B, H, d_k] (Kimi Delta Attention,
    ops/kda.py): the state's row d then decays by exp(g[d])."""
    s = state * (jnp.exp(g)[..., None] if g.ndim == k.ndim
                 else jnp.exp(g)[..., None, None])
    r = jnp.sum(s * k[..., :, None], axis=-2)
    s = s + k[..., :, None] * (beta[..., None] * (v - r))[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


def gated_delta_scan(q, k, v, g, beta, state):
    """A segment token by token. q, k [B, s, H, d_k]; v [B, s, H, d_v];
    g, beta [B, s, H] (g [B, s, H, d_k] where the decay is a channel's);
    state [B, H, d_k, d_v] -> (o [B, s, H, d_v], state')."""
    def one(s, xs):
        o, s = gated_delta_step(*xs, s)
        return s, o

    state, o = lax.scan(one, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def gated_delta_chunked(q, k, v, g, beta, state, sub: int = 64):
    """A segment in sub-chunks of `sub` positions (the module docstring).
    Shapes as `gated_delta_scan`'s; s need not be a multiple of `sub` (the
    segment is padded with inert positions)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(sub, s)
    pad = -s % c
    n = (s + pad) // c

    def split(x):  # [B, s, H, ...] -> [N, B, H, c, ...]
        x = jnp.pad(x.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (split(x) for x in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        gc = jnp.cumsum(g, axis=-1)                            # [N, B, H, c]
        i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
        # exp(G_i - G_j) where j <= i; the other half would overflow
        decay = jnp.where(j <= i, jnp.exp(jnp.where(
            j <= i, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
        kb = k * beta[..., None]
        a = jnp.where(j < i, jnp.einsum("...id,...jd->...ij", kb, k) * decay,
                      0.0)
        rhs = jnp.concatenate(
            [v * beta[..., None], kb * jnp.exp(gc)[..., None]], axis=-1)
        # (the finite series (I - A)(I + A^2)(I + A^4) ... is the same inverse
        # in matrix products alone and a quarter faster on the chip, but its
        # powers overflow float32 where a sub-chunk's keys are alike)
        sol = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(c, dtype=F32), rhs, lower=True, unit_diagonal=True)
        u, w = sol[..., :dv], sol[..., dv:]
        qk = jnp.einsum("...id,...jd->...ij", q, k) * decay

        def one(st, xs):
            q_i, k_i, u_i, w_i, qk_i, gc_i = xs
            v_new = u_i - jnp.einsum("bhcd,bhde->bhce", w_i, st)
            o = jnp.einsum("bhcd,bhde->bhce", q_i * jnp.exp(gc_i)[..., None],
                           st) + jnp.einsum("bhij,bhje->bhie", qk_i, v_new)
            last = gc_i[..., -1:]
            st = st * jnp.exp(last)[..., None] + jnp.einsum(
                "bhcd,bhce->bhde", k_i * jnp.exp(last - gc_i)[..., None],
                v_new)
            return st, o

        state, o = lax.scan(one, state.astype(F32), (q, k, u, w, qk, gc))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)       # [B, N, c, H, d_v]
    return o.reshape(b, n * c, h, dv)[:, :s], state


def per_value_head(x, heads: int):
    """q or k [..., Hk, d_k] as the rule's forms take them, a row a VALUE
    head: each key head serves heads / Hk value heads, side by side."""
    return jnp.repeat(x, heads // x.shape[-2], axis=-2)


def gated_delta(q, k, v, g, beta, state):
    """A segment from `state` in the plain form that suits its length: the
    rule itself for one position a row, else the chunked form. Shapes as
    `gated_delta_scan`'s, but q and k may come a row a KEY head (a mixer
    hands them over so: `per_value_head`)."""
    q, k = (per_value_head(x, v.shape[2]) for x in (q, k))
    if q.shape[1] == 1:
        o, state = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0], state)
        return o[:, None], state
    return gated_delta_chunked(q, k, v, g, beta, state)


# ---------------------------------------------------------------------------
# The decode step over a serving cache's state pool, in place.
# ---------------------------------------------------------------------------


def gated_delta_kernel_suits(s: int, pool) -> bool:
    """Whether a segment of `s` positions a row over a state pool [L_gdn,
    slots, H, d_k, d_v] is one `gated_delta_step_pooled` takes compiled: a
    decode step (one position a row), a float32 state whose d_k and d_v are
    whole rows of 128 lanes and whose heads are whole blocks of
    `STEP_HEAD_BLOCK`, a backend that compiles Pallas kernels.
    Everything else gathers its rows and takes the plain forms: prefill
    chunks, the tiny test models' heads, every CPU run
    (`ops.paged_attention.decode_kernel_suits` is the K/V pool's answer to
    the same question)."""
    return (s == 1 and pool.dtype == F32 and pool.shape[2] % STEP_HEAD_BLOCK == 0
            and pool.shape[3] % _LANES == 0 and pool.shape[4] % _LANES == 0
            and compiled_kernels_available())


def _step_kernel(gi_ref, slot_ref, fresh_ref, decay_ref, beta_ref, qt_ref,
                 kt_ref, v_ref, pool_in, pool_out, o_ref, order, s_in, s_out,
                 sems, *, heads: int, per_channel: bool = False):
    rows, hv, dv = v_ref.shape
    blocks = hv // heads
    gi = gi_ref[0]
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    # the rows with work, compacted: order[0 .. n) in the batch's order
    def note(b, n):
        @pl.when(slot_ref[b] >= 0)
        def _():
            order[n] = b
        return n + (slot_ref[b] >= 0).astype(jnp.int32)

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

    lane = lax.broadcasted_iota(jnp.int32, kt_ref.shape[1:], 1)
    sub = lax.broadcasted_iota(jnp.int32, (heads, dv), 0)

    def item(t, _):
        b = order[t // blocks]
        h0 = pl.multiple_of((t % blocks) * heads, heads)
        buf = t % 2

        @pl.when(t + 1 < items)
        def _next():
            fetch(t + 1).start()

        fetch(t).wait()

        @pl.when(fresh_ref[b] != 0)
        def _start():  # position 0: whatever the row holds, zeros
            s_in[buf] = jnp.zeros(s_in.shape[1:], F32)

        @pl.when(t >= 2)
        def _free():
            # item t - 2's matrices are on their way out of this buffer
            store(t - 2).wait()

        kt, qt = kt_ref[b], qt_ref[b]                      # [d_k, H]
        v = v_ref[b, pl.ds(h0, heads), :]                  # [heads, d_v]

        def head(j, o):
            h = h0 + j
            # head h's key and query as columns, d_k down the sublanes as
            # the state's rows are: one lane of the tile, the others zeros
            kc = jnp.sum(jnp.where(lane == h, kt, 0.0), axis=1, keepdims=True)
            qc = jnp.sum(jnp.where(lane == h, qt, 0.0), axis=1, keepdims=True)
            vr = jnp.sum(jnp.where(sub == j, v, 0.0), axis=0, keepdims=True)
            # gated_delta_step, expression for expression; a decay a
            # channel is handed over as the key is, [d_k, H] a row
            s = s_in[buf, j] * (
                jnp.sum(jnp.where(lane == h, decay_ref[b], 0.0), axis=1,
                        keepdims=True) if per_channel else decay_ref[b, h])
            r = jnp.sum(s * kc, axis=0, keepdims=True)
            s = s + kc * (beta_ref[b, h] * (vr - r))
            s_out[buf, j] = s
            return jnp.where(sub == j, jnp.sum(s * qc, axis=0, keepdims=True), o)

        o = lax.fori_loop(0, heads, head, jnp.zeros((heads, dv), F32))
        o_ref[b, pl.ds(h0, heads), :] = o
        store(t).start()

    lax.fori_loop(0, items, item, None)
    for back in (1, 2):  # the two stores still in flight, one a buffer
        @pl.when(items >= back)
        def _drain():
            store(items - back).wait()


def gated_delta_step_pooled(q, k, v, g, beta, pool, gi, rows, live, fresh, *,
                            interpret: Optional[bool] = None):
    """`gated_delta_step` for the batch's rows that hold a token, on mixer
    `gi`'s rows of a state pool, in place.

    q, k [B, H, d_k]; v [B, H, d_v]; g, beta [B, H] (g [B, H, d_k] where the
    decay is a channel's: the same body, the decay a tile [d_k, H] a row in
    VMEM where the scalar one is a word in SMEM, and the kernel's name
    `kda_step_pooled`); pool [L_gdn, slots, H,
    d_k, d_v], all float32; gi: the mixer (a scalar, traced or not); rows [B]
    int32: row b's slot, `slots` or more = unmapped; live [B] bool: the row
    holds a token; fresh [B] bool: it starts its sequence (the state it
    carries in is zeros, whatever the pool holds). No two rows with work
    share a slot. Returns (o [B, H, d_v], pool'): for a live, mapped row
    `gated_delta_step`'s o and its state' at pool'[gi, rows[b]]; any other
    row's o is zeros, and every bit of the pool outside the worked rows'
    matrices of mixer gi is as it was: nothing there is read or written.

    One grid step; the pool is handed over whole in HBM and aliased to the
    output; the rows with work are walked in blocks of `STEP_HEAD_BLOCK`
    heads (the largest divisor of H up to it), one DMA in and one out a
    block, double-buffered both ways, so a matrix crosses the memory bus
    once each way; the loops over (row, block) and over a block's heads are
    the kernel's own, so its body is one head's update whatever H is (the
    heads of a row unrolled read the same device time at 1-3 live rows, 8%
    less at 16, and cost every start 1.5 s a mixer of Mosaic's passes
    where this costs 0.6 s in all: PERF.md section 6, PR 52). Inside:
    float32 on the vector unit alone, `gated_delta_step`'s expressions in
    its order; q and k are handed over [d_k, H], and a head's key is that
    tile's lane h as a column (d_k down the sublanes, as the state's rows
    are). The two sums over d_k are
    the only place where the order of additions may differ from the plain
    form's (on a v5e they come out bit-equal: PERF.md section 6, PR 52).
    `interpret=None` compiles on a TPU backend and runs the Pallas
    interpreter anywhere else; the caller decides whether the shapes suit
    the compiled kernel (`gated_delta_kernel_suits`)."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    if pool.shape[2:] != q.shape[1:] + v.shape[2:] or pool.dtype != F32:
        raise ValueError(f"pool {pool.shape} {pool.dtype} does not match "
                         f"q {q.shape} / v {v.shape} in float32")
    return _step_pooled_call(
        q, k, v, g, beta, pool, gi, rows, live, fresh,
        heads=_divisor(q.shape[1], STEP_HEAD_BLOCK), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _step_pooled_call(q, k, v, g, beta, pool, gi, rows, live, fresh, *,
                      heads: int, interpret: bool):
    """`gated_delta_step_pooled`, jitted: a period's mixers call it with the
    same shapes, and a jitted function is traced and lowered once a program
    however many call it (Mosaic's passes run while the kernel is lowered,
    before the compile cache is asked: every start pays them)."""
    b, _, dk = q.shape
    dv = v.shape[-1]
    slot = jnp.where(live & (rows < pool.shape[1]), rows, -1)

    def whole(x):
        return pl.BlockSpec(x.shape, lambda *_: (0,) * x.ndim,
                            memory_space=pltpu.VMEM)

    qt, kt = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
    per_channel = g.ndim == 3
    decay = jnp.swapaxes(jnp.exp(g), 1, 2) if per_channel else jnp.exp(g)
    pool, o = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads, per_channel=per_channel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # the mixer, the rows' slots, their starts
            grid=(1,),
            in_specs=[whole(decay) if per_channel
                      else pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pltpu.SMEM),
                      whole(qt), whole(kt), whole(v),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), whole(v)],
            scratch_shapes=[
                pltpu.SMEM((b,), jnp.int32),
                pltpu.VMEM((2, heads, dk, dv), F32),
                pltpu.VMEM((2, heads, dk, dv), F32),
                pltpu.SemaphoreType.DMA((2, 2)),  # (in | out, buffer)
            ]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(v.shape, F32)],
        input_output_aliases={8: 0},  # the pool, counted with the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="kda_step_pooled" if per_channel else "gated_delta_step_pooled",
    )(jnp.asarray(gi, jnp.int32).reshape(1), slot.astype(jnp.int32),
      fresh.astype(jnp.int32), decay, beta, qt, kt, v, pool)
    return o, pool


# ---------------------------------------------------------------------------
# A prefill chunk over a serving cache's state pool, in place.
# ---------------------------------------------------------------------------

CHUNK_SUB = 64  # positions a sub-chunk: two value heads' 64 x 64 fill 128 lanes
CHUNK_PAIRS = 2  # pairs of value heads whose products stand side by side


def chunk_vmem_bytes(s: int, qk_width: int, v_width: int) -> int:
    """What `gated_delta_chunk_pooled` asks of VMEM: a row's q, k, v and o
    whole, each in two buffers (the next row's come in and the last row's o
    goes out under a row's work), and room for the kernel's own values."""
    return 2 * 4 * s * (2 * qk_width + 2 * v_width) + 16 * 2**20


def gated_delta_chunk_suits(s: int, key_heads: int, pool) -> bool:
    """Whether a segment of `s` positions a row, its q and k over `key_heads`
    heads, over a state pool [L_gdn, slots, H, d_k, d_v] is one
    `gated_delta_chunk_pooled` takes compiled: whole sub-chunks of
    `CHUNK_SUB` positions (so never a decode step), a float32 state whose
    d_k and d_v are whole rows of 128 lanes, value heads that come in pairs
    beside one key head, a row's q, k, v and o small enough for VMEM (96 of a
    v5e's 128 MiB), a backend that compiles Pallas kernels."""
    heads, dk, dv = pool.shape[2:]
    return (s > 1 and s % CHUNK_SUB == 0 and pool.dtype == F32
            and dk % _LANES == 0 and dv % _LANES == 0
            and heads % (2 * key_heads) == 0
            and chunk_vmem_bytes(s, key_heads * dk, heads * dv) <= 96 * 2**20
            and compiled_kernels_available())


def _dot(a, b, a_dim: int = 2, b_dim: int = 1):
    """A product a matrix of the leading axis, a [n, ., .] with b [n, ., .]
    over `a_dim` and `b_dim`, in float32, each at the precision the plain
    forms ask for (six bfloat16 passes; the same six by hand, the parts of
    `a` that meet one part of `b` stacked into one product, ran a quarter
    slower on a v5e, whose vector unit has no bfloat16: PERF.md section 6,
    PR 54)."""
    return lax.dot_general(a, b, (((a_dim,), (b_dim,)), ((0,), (0,))),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=F32)


def _chunk_kernel(gi_ref, slot_ref, fresh_ref, order_ref, n_ref, q_ref, k_ref,
                  v_ref, gc_ref, beta_ref, gr_ref, pool_in, pool_out, o_ref,
                  s_buf, sems):
    """Grid step t: the t-th row with work, whole: its value heads in PAIRS
    (2p and 2p + 1, beside one key head), `s_buf.shape[0] // 2` pairs a step
    of the loop, all the sub-chunks of each. A matrix of 64 x 64 a head is
    held for both heads of a pair side by side, [64, 128]: lanes 0-63 the
    first head's columns, 64-127 the second's; the matrices of a loop step's
    pairs and sub-chunks are the leading axis of one array, so that their
    products, none of which waits for another, stand side by side: a chain
    of them alone leaves the MXU idle most of the time."""
    c = CHUNK_SUB
    t = pl.program_id(0)
    s, hv = gc_ref.shape
    dv, dk = v_ref.shape[1] // hv, s_buf.shape[1]
    pairs_a_key = hv // q_ref.shape[1] // 2
    together, subs = s_buf.shape[0] // 2, s // c

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
        ps = [together * step + u for u in range(together)]
        place = (gi_ref[0], slot_ref[b], pl.ds(2 * ps[0], 2 * together))
        fetch = pltpu.make_async_copy(pool_in.at[place], s_buf, sems.at[0])
        store = pltpu.make_async_copy(s_buf, pool_out.at[place], sems.at[1])
        carried = fresh_ref[b] == 0

        @pl.when(carried)
        def _():
            fetch.start()

        def of_key(ref):  # [pairs x subs, c, d_k], a pair's key head's
            return jnp.concatenate([ref[:, p // pairs_a_key, :].reshape(subs, c, dk)
                                    for p in ps], 0)

        def column(ref, h):  # head h's entries, a column a sub-chunk
            return jnp.sum(jnp.where(head == h, ref[...], 0.0), axis=1,
                           keepdims=True).reshape(subs, c, 1)

        # what does not wait for the state: of every sub-chunk, (I + A)^-1
        # and q k^T decay of both heads
        k, q = of_key(k_ref), of_key(q_ref)
        gram = _dot(jnp.concatenate([k, q], 1), jnp.concatenate([k, k], 1),
                    2, 2)                     # [k k^T | k k^T], [q k^T | q k^T]
        gc = [column(gc_ref, 2 * p + e) for p in ps for e in range(2)]
        bt = [column(beta_ref, 2 * p + e) for p in ps for e in range(2)]

        def beside_each_other(cols):  # a pair's two columns, each over its half
            return jnp.concatenate([jnp.where(first, cols[2 * u], cols[2 * u + 1])
                                    for u in range(together)], 0)

        rows = jnp.concatenate([gr_ref[p][:, None, :] for p in ps], 0)
        # exp(G_i - G_j) where j <= i; the other half would overflow
        decay = jnp.where(causal, jnp.exp(jnp.where(
            causal, beside_each_other(gc) - rows, 0.0)), 0.0)
        lower = jnp.where(strict, beside_each_other(bt) * gram[:, :c] * decay, 0.0)
        scores = gram[:, c:] * decay
        # (I + A)^-1 from the diagonal outwards: where X inverts the diagonal
        # blocks of m rows and B is A's blocks beside them that complete
        # blocks of 2m, X - X B X inverts those
        solved = eye - jnp.where(beside[0], lower, 0.0)
        for blocks in beside[1:]:
            solved = solved - _dot(
                _dot(solved, pair(jnp.where(blocks, lower, 0.0))), pair(solved))

        @pl.when(carried)
        def _():
            fetch.wait()

        @pl.when(jnp.logical_not(carried))
        def _():  # position 0: whatever the row holds, zeros
            s_buf[...] = jnp.zeros(s_buf.shape, F32)

        # `gated_delta_chunked`'s `one`, a sub-chunk after the other, with
        # v_new = u - w S as T (beta v - (beta exp(G) k) S); the leading
        # axis is the step's heads (or its pairs, the two heads' rows one
        # under the other)
        def a_head(x, n):  # [pairs x subs, ...] -> sub-chunk n's, a row a head
            return jnp.concatenate([x[u * subs + n][None] for u in range(together)
                                    for _ in range(2)], 0)

        def a_pair(x):  # [heads, r, .] -> [pairs, 2 r, .]
            return x.reshape(together, 2 * x.shape[1], x.shape[2])

        state = s_buf[...]
        at_v = pl.ds(pl.multiple_of(2 * ps[0] * dv, _LANES), 2 * together * dv)
        for n in range(subs):
            k_n, q_n = a_head(k, n), a_head(q, n)
            gc_n = jnp.concatenate([x[n:n + 1] for x in gc], 0)  # [heads, c, 1]
            bt_n = jnp.concatenate([x[n:n + 1] for x in bt], 0)
            last = gc_n[:, c - 1:, :]
            from_state = _dot(jnp.concatenate(
                [k_n * (bt_n * jnp.exp(gc_n)), q_n * jnp.exp(gc_n)], 1), state)
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
            shrink = jnp.exp(jnp.broadcast_to(gc_n, (2 * together, c, dv)))
            state = shrink[:, c - 1:] * state + out[:, 2 * c:].reshape(
                2 * together, dk, dv)
        s_buf[...] = state
        store.start()
        store.wait()

    @pl.when(t < n_ref[0])
    def _work():
        lax.fori_loop(0, hv // 2 // together, some_pairs, None)


def work_first(work):
    """(order [B] int32, count): the rows of a batch with `work` [B] bool
    first, in the batch's order, then the others (a stable argsort of `not
    work`, without the sort), and how many have work. What a chunk kernel's
    grid walks."""
    b = work.shape[0]
    n = jnp.sum(work, dtype=jnp.int32)
    at = jnp.where(work, jnp.cumsum(work) - 1, n + jnp.cumsum(~work) - 1)
    ids = jnp.arange(b, dtype=jnp.int32)
    order = jnp.sum(jnp.where(at[None, :] == ids[:, None], ids[None, :], 0),
                    axis=1, dtype=jnp.int32)
    return order, n


def gated_delta_chunk_pooled(q, k, v, g, beta, pool, gi, rows, live, fresh, *,
                             interpret: Optional[bool] = None):
    """`gated_delta_chunked` for the batch's rows that hold a real position,
    on mixer `gi`'s rows of a state pool, in place.

    q, k [B, s, Hk, d_k] (a row a KEY head); v [B, s, Hv, d_v]; g, beta [B,
    s, Hv]; pool [L_gdn, slots, Hv, d_k, d_v], all float32; gi, rows, live,
    fresh as `gated_delta_step_pooled`'s (live: the row holds a real
    position; a padded position INSIDE a live row is made inert by the
    caller). Returns (o [B, s, Hv, d_v], pool'): for a live, mapped row the
    chunked form's o and its state' at pool'[gi, rows[b]]; any other row's o
    is zeros, and nothing of the pool outside the worked rows' matrices of
    mixer gi is read or written."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    if (pool.shape[2:] != v.shape[2:3] + q.shape[3:] + v.shape[3:]
            or pool.dtype != F32 or q.shape[1] % CHUNK_SUB
            or v.shape[2] % (2 * q.shape[2])):
        raise ValueError(f"pool {pool.shape} {pool.dtype} does not match q "
                         f"{q.shape} / v {v.shape} in float32 sub-chunks of "
                         f"{CHUNK_SUB}, value heads in pairs a key head")
    return _chunk_pooled_call(q, k, v, g, beta, pool, gi, rows, live, fresh,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_pooled_call(q, k, v, g, beta, pool, gi, rows, live, fresh, *,
                       interpret: bool):
    """`gated_delta_chunk_pooled`, jitted for `_step_pooled_call`'s reason."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    c, pairs = CHUNK_SUB, hv // 2
    work = live & (rows < pool.shape[1])
    order, n = work_first(work)
    # G, the running sum of g inside a sub-chunk: a column a head for what
    # scales a position's row, and a pair of heads' 2 x 64 side by side as
    # one row of lanes for the decay matrix's columns
    gc = jnp.cumsum(g.reshape(b, s // c, c, hv), axis=2)
    gr = jnp.moveaxis(gc.reshape(b, s // c, c, pairs, 2), 2, 4)
    gr = jnp.moveaxis(gr, 1, 2).reshape(b, pairs, s // c, 2 * c)

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
            in_specs=[row(s, hk, dk), row(s, hk, dk), row(s, hv * dv),
                      row(s, hv), row(s, hv), row(pairs, s // c, 2 * c),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec((None, s, hv * dv),
                                    lambda t, gi, slot, fresh, order, n:
                                    (order[t], 0, 0))],
            scratch_shapes=[pltpu.VMEM((2 * _divisor(pairs, CHUNK_PAIRS), dk, dv), F32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, s, hv * dv), F32)],
        input_output_aliases={11: 0},  # the pool, counted with the scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=chunk_vmem_bytes(s, hk * dk, hv * dv)),
        interpret=interpret,
        name="gated_delta_chunk_pooled",
    )(jnp.asarray(gi, jnp.int32).reshape(1),
      jnp.where(work, rows, 0).astype(jnp.int32), fresh.astype(jnp.int32),
      order, n.reshape(1), q, k,
      v.reshape(b, s, hv * dv), gc.reshape(b, s, hv), beta, gr, pool)
    return o.reshape(b, s, hv, dv), pool
