"""Pallas grouped gated MLP for the served experts: every row tile through the
ONE expert it belongs to, gate, up, activation and down in one kernel, the
expert's weights taken out of the model's whole stacks through prefetched
scalars.

The decode paths put few rows through many experts: a decode step of 32
slots has 256 assignments over 64 experts, of which the live slots touch 26
to 38; a 1,024-token prefill chunk has 128 rows an expert. The dense form
(every row x every expert) reads all 64 banks whatever is live and does
E / k times the operations; the compiler's `ragged-dot` costs about 35 us a
group however few rows it holds (PERF.md section 6, PRs 33 and 34). Here a
grid step is a (row tile, expert) pair that holds rows, and nothing else is
visited: an expert no live row chose is never read, and a group costs its
rows.

Design:

- **Groups are tile-aligned** in the expert-sorted buffer: expert `e`'s rows
  start at a multiple of the row tile `tm` (`group_tiles`), so a tile belongs
  to one expert, needs no mask, and is written once. The tiles that hold rows
  are the first `n_visits` of the buffer, in expert order; the buffer is
  sized for the worst case (`max_tiles`) and the steps past `n_visits` are
  skipped: their block indices repeat the last visit's, so they move no
  byte.
- **The banks stay whole**: `[L, E, H, F]` / `[L, E, F, H]` are handed over
  as they are, the layer and each tile's expert as prefetched scalars in the
  weights' index maps. No layer is sliced out, nothing is reshaped.
- **Grid (tiles, F tiles)**: a tile's rows stay in VMEM while the expert's
  weights stream by in `tf`-wide slices of the hidden dimension; the down
  projection accumulates over them in float32. `tf` is all of F where three
  double-buffered slices fit the budget (the widths served here), so two
  tiles of one expert in a row read its weights once.
- **Mathematics of `_dropless_experts`**, at no lower precision: operands as
  stored (bf16), each matmul accumulated in float32, the activation and the
  product in float32 (the `ragged-dot` form rounds gate and up to bf16
  first), one rounding to the operand dtype before the down projection and
  one of its float32 result.

Rows of a visited tile beyond its group's size are computed from whatever
the buffer holds there and are never read back; tiles that are not visited
are never written. The caller (`ops/moe.py _grouped_experts`) reads only the
rows of live assignments and selects zeros for every other.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from picotron_tpu.ops.paged_attention import compiled_kernels_available


# Bounds of the row tile: 16 rows are one sublane tile of bf16, the least a
# block may hold. Above 256 rows nothing is gained: on uniform groups the
# kernel runs at 70 / 80 / 94% of the chip's matmul peak with tiles of 128 /
# 256 / 512 rows, and the half tile a group wastes on average, in the kernel
# and in the gather that fills the buffer, takes it back (PERF.md section 6,
# PR 34: 4,096 tokens 46.3 ms at 256 against 48.3 at 512, 8,192 tokens 85.0
# against 85.3).
MIN_ROW_TILE, MAX_ROW_TILE = 16, 256
# VMEM the kernel may use, of the chip's 128 MiB (v5e), and the part of it
# the double-buffered weight slices may take.
VMEM_LIMIT_BYTES = 64 * 2**20
WEIGHTS_VMEM_BYTES = 40 * 2**20


def row_tile(rows: int, experts: int) -> int:
    """The row tile for `rows` assignments (N x k, static) over `experts`
    groups: the mean group's rows rounded up to a power of two, inside
    [MIN_ROW_TILE, MAX_ROW_TILE]. A decode step's 256 rows over 64 experts
    take 16-row tiles (a touched expert is one visit: its bank is read
    once), a 1,024-token chunk's 8,192 take 128, 4,096 tokens and more 256."""
    mean = max(-(-rows // experts), 1)
    return min(max(1 << (mean - 1).bit_length(), MIN_ROW_TILE), MAX_ROW_TILE)


def ffn_tile(h: int, f: int, itemsize: int) -> int:
    """The slice of the experts' hidden dimension F a grid step takes: the
    widest multiple of 128 lanes that divides F and whose three weight
    slices [H, tf] x 2, [tf, H], double-buffered, fit WEIGHTS_VMEM_BYTES
    (all of F at Mellum2's 2304 x 896 and OLMoE's 2048 x 1024; 512 of
    Mixtral's 4096 x 14336). An F that is no multiple of 128 (the tiny test
    models) is taken whole."""
    if f % 128:
        return f
    fits = [t for t in range(128, f + 1, 128)
            if f % t == 0 and 6 * h * t * itemsize <= WEIGHTS_VMEM_BYTES]
    return fits[-1] if fits else 128


def max_tiles(rows: int, experts: int, tm: int) -> int:
    """The most row tiles `rows` assignments over `experts` tile-aligned
    groups can fill: every group ends in at most one partly filled tile,
    and a tile holds at least one row."""
    return min(rows // tm + experts, rows)


def group_tiles(counts, tm: int, n_tiles: int):
    """The tile-aligned layout of groups of `counts` [E] rows in tiles of
    `tm`: (first_row [E] of each group in the buffer, tile_expert [n_tiles]:
    the expert of each tile, n_visits []: the tiles that hold rows, which
    are the buffer's first). A step past the last visit repeats it
    (`grouped_swiglu` skips its work; the repeated indices move nothing)."""
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n_visits = tile_end[-1]
    t = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                    jnp.maximum(n_visits - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, t, side="right"), counts.shape[0] - 1)
    return ((tile_end - tiles) * tm, tile_expert.astype(jnp.int32),
            n_visits.astype(jnp.int32))


def _kernel(tile_expert_ref, meta_ref, x_ref, *refs, act, n_f: int):
    del tile_expert_ref  # read by the index maps
    # three banks, or two where the experts are not gated
    *w_refs, wd_ref, o_ref, acc_ref = refs
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(t <= meta_ref[1])  # a tile that holds rows (or tile 0)
    def _visit():
        x = x_ref[...]
        dt = x.dtype  # the compute dtype: a weight stored wider is cast here
        g = jnp.dot(x, w_refs[0][...].astype(dt),
                    preferred_element_type=jnp.float32)
        if len(w_refs) == 2:
            u = jnp.dot(x, w_refs[1][...].astype(dt),
                        preferred_element_type=jnp.float32)
            mid = act(g) * u
        else:  # not gated: the one bank's product is the activation's input
            mid = act(g)
        y = jnp.dot(mid.astype(dt), wd_ref[...].astype(dt),
                    preferred_element_type=jnp.float32)
        if n_f == 1:
            o_ref[...] = y.astype(o_ref.dtype)
            return

        @pl.when(f == 0)
        def _first():
            acc_ref[...] = y

        @pl.when(f > 0)
        def _rest():
            acc_ref[...] += y

        @pl.when(f == n_f - 1)
        def _store():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_swiglu(xs, w_gate, w_up, w_down, tile_expert, n_visits, layer, *,
                   tm: int, act=jax.nn.silu,
                   interpret: Optional[bool] = None):
    """`act(x @ w_gate[l, e]) * (x @ w_up[l, e]) @ w_down[l, e]` for every
    row tile of xs with its expert `e = tile_expert[tile]` of layer `l`.

    xs [T * tm, H]: the expert-sorted, tile-aligned buffer (`group_tiles`);
    w_gate / w_up [L, E, H, F] and w_down [L, E, F, H]: the model's whole
    stacks; tile_expert [T] int32; n_visits []: only the first `n_visits`
    tiles are computed (tile 0 always is); layer: a scalar, traced or not.
    Returns [T * tm, H] in xs' dtype; tiles from `max(n_visits, 1)` on are
    NOT written. `w_gate` None: experts that are not gated, `act(x @
    w_up[l, e]) @ w_down[l, e]`, two banks read.

    `interpret=None` compiles the kernel on a TPU backend and runs the
    Pallas interpreter anywhere else (the CPU tests)."""
    if interpret is None:
        interpret = not compiled_kernels_available()
    m, h = xs.shape
    n_layers, e, _, f = w_up.shape
    n_tiles = tile_expert.shape[0]
    ins = (w_up,) if w_gate is None else (w_gate, w_up)
    if m != n_tiles * tm or any(w.shape != (n_layers, e, h, f) for w in ins) or (
            w_down.shape != (n_layers, e, f, h)):
        raise ValueError(f"rows {xs.shape} in {n_tiles} tiles of {tm} do not "
                         f"match banks {[w.shape for w in ins]} / "
                         f"{w_down.shape}")
    tf = ffn_tile(h, f, jnp.dtype(w_up.dtype).itemsize)
    n_f = f // tf
    last = jnp.maximum(jnp.asarray(n_visits, jnp.int32) - 1, 0)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32), last])

    # a step past the last visit keeps every block where the last visit left
    # it: same tile, same expert, the last F slice
    def rows(t, f, te, meta):
        return jnp.minimum(t, meta[1]), 0

    def up(t, f, te, meta):
        return meta[0], te[t], 0, jnp.where(t <= meta[1], f, n_f - 1)

    def down(t, f, te, meta):
        return meta[0], te[t], jnp.where(t <= meta[1], f, n_f - 1), 0

    return pl.pallas_call(
        functools.partial(_kernel, act=act, n_f=n_f),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # the tiles' experts; (layer, last visit)
            grid=(n_tiles, n_f),
            in_specs=[pl.BlockSpec((tm, h), rows),
                      *(pl.BlockSpec((None, None, h, tf), up) for _ in ins),
                      pl.BlockSpec((None, None, tf, h), down)],
            out_specs=pl.BlockSpec((tm, h), rows),
            scratch_shapes=[pltpu.VMEM((tm, h) if n_f > 1 else (8, 128),
                                       jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, h), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="grouped_experts",
    )(tile_expert, meta, xs, *ins, w_down)
