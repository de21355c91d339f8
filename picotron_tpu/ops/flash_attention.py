"""Pallas flash attention for TPU: blockwise causal attention with LSE export.

TPU-native replacement for the reference's imported flash-attn CUDA kernel
(ref: picotron/model.py:7,33-37,152-154 calls flash_attn_func; SURVEY.md §2.3
row 1 requires a first-class equivalent). Same contract as
`ops.attention.sdpa_attention` — including `return_lse` — so it slots into
`ParallelCtx.attn` directly and into the context-parallel ring as the
per-block kernel (ref: the CP ring's pure-torch blockwise math + TODOs
wishing for flash, context_parallel.py:22-23,112-155).

Design:
- Inputs [B, S, H, D] are viewed [B, H, S, D]; the KV dimension is a *grid
  dimension*, not a kernel-internal loop: grid (batch, q-head, q-block,
  kv-block) with online-softmax (m, l, acc) carries in VMEM scratch across
  the sequential kv dimension. Only one K/V block is VMEM-resident per step,
  so per-shard sequence length is bounded by HBM, not VMEM — the
  long-context regime CP exists for (16k+ per shard) compiles and runs.
- **GQA in the index map**: the K/V BlockSpecs map q-head h to kv-head
  h // (Hq // Hkv), so grouped heads never materialize (the reference
  repeat_interleaves K/V to full Hq first, model.py:142-143).
- **Masking by explicit positions**, not block indices: the causal mask is
  `q_pos >= kv_pos` on position vectors, so context-parallel shards (local
  index != global position) and the zigzag layout reuse the same kernel.
  Blocks that are entirely masked skip their matmuls via `pl.when`.
- **Custom VJP with Pallas backward kernels**: dq via a q-block-parallel
  kernel, dk/dv via a kv-block-parallel kernel, both recomputing P from the
  saved LSE (flash-attn 2's backward structure; no S x S materialization).
  The dkv grid is (batch, KV-head, kv-block): under GQA the group's query
  heads are accumulated *inside* the program (an inner sequential grid
  dimension), not materialized per-q-head and summed after.

Numerics: fp32 accumulation for scores/softmax/output regardless of input
dtype, matching sdpa_attention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Swept on v5e at seq 2048 (B3 H32 D64): 1024x1024 runs 4x faster than
# 256x256 — the kernel is VPU/overhead-bound, not MXU-bound, so fewer,
# larger programs win. VMEM (fp32 [BQ, BK] score block) caps growth: 2048^2
# exceeds the 16 MB scoped-vmem budget.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
_NEG_INF = -1e30


def compiled_kernels_available() -> bool:
    """True where `flash_attention(interpret=None)` builds the compiled
    Pallas kernels: on the TPU backend. Everywhere else the same call
    builds the jnp reference math (see `flash_attention`'s dispatch note);
    `parallel.api.attention_path` reports which one a config gets."""
    return jax.default_backend() == "tpu"


def _pick_block(s: int, preferred: int) -> int:
    b = min(preferred, s)
    while s % b != 0:
        b //= 2
    return max(b, 1)


def _kv_eff(qi, ki, bq: int, bk: int):
    """Clamp a kv-block index to the last block visible from q-block qi
    under contiguous causal positions (static_causal index maps): skipped
    tiles re-address the previous iteration's blocks, so Mosaic elides
    their DMAs entirely."""
    return jnp.minimum(ki, (qi * bq + bq - 1) // bk)


def _q_eff(qi, ki, bq: int, bk: int, num_q: int):
    """Clamp a q-block index to the first block that can see kv-block ki
    (the dkv kernel's mirror of _kv_eff). The upper clamp matters when
    sk > sq: the last kv blocks see no q block at all, and an unclamped
    index would address past the q array (code review r5)."""
    return jnp.minimum(jnp.maximum(qi, (ki * bk) // bq), num_q - 1)


def _static_block_classes(qi, ki, bq: int, bk: int):
    """(visible, full) block classes as integer functions of the program
    ids — the static_causal twin of the kernels' position-based
    `max(qpos) >= min(kpos)` / `min(qpos) >= max(kpos)` tests, shared by
    all three kernels so the class boundaries cannot desynchronize."""
    visible = qi * bq + bq - 1 >= ki * bk
    full = qi * bq >= ki * bk + bk - 1
    return visible, full


def _rot_tables(cos, sin, pos, dtype=jnp.float32):
    """Gather the half tables [maxS, d/2] at `pos` [1, S] and lay them out
    full-width for the in-kernel rotate-half:

        rot(x)     = x * C + roll(x, d/2) * S,   C = [cos|cos], S = [-sin|sin]
        rot_inv(y) = y * C + roll(y, d/2) * (-S)

    (roll moves the upper half down: roll(x)[: d/2] = x2, matching the HF
    rotate_half convention rot(x) = x*cos_full + [-x2|x1]*sin_full.)"""
    c = cos[pos[0]].astype(dtype)                    # [S, d/2]
    s = sin[pos[0]].astype(dtype)
    C = jnp.concatenate([c, c], axis=-1)[None]       # [1, S, d]
    S = jnp.concatenate([-s, s], axis=-1)[None]
    return C, S


def _rot(x, c_ref, s_ref, sign: float):
    """Rotate an [N, d] tile with full-width tables from `_rot_tables`;
    sign=+1 applies RoPE, sign=-1 its inverse (transpose). fp32 math, result
    cast back to x.dtype so the MXU stays on the bf16 path."""
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rolled = jnp.concatenate([xf[:, half:], xf[:, :half]], axis=-1)
    out = xf * c_ref[0] + rolled * (sign * s_ref[0])
    return out.astype(x.dtype)


def _out_struct(shape, dtype, *operands):
    """ShapeDtypeStruct whose `vma` is the union of the operands' varying
    mesh axes — required for pallas_call under shard_map(check_vma=True)
    (the CP ring runs this kernel on 'cp'-varying blocks)."""
    from picotron_tpu import compat

    vma = frozenset()
    for x in operands:
        vma = vma | compat.vma(x)
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, sm_scale: float, causal: bool, num_kv: int,
                fused_rope: bool, static_causal: bool = False,
                block_q: int = 0, block_k: int = 0):
    if fused_rope:
        (qpos_ref, kpos_ref, cq_ref, sq_ref, ck_ref, sk_ref,
         q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
         qrot_ref) = refs
    else:
        (qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_ref, l_ref, acc_ref) = refs
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if fused_rope:
            # q is constant across the sequential kv dim — rotate once per
            # q-block chain, not once per kv block (the rotation lands on
            # the VPU, this kernel's bottleneck unit).
            qrot_ref[...] = _rot(q_ref[0, 0], cq_ref, sq_ref, 1.0)

    qpos = qpos_ref[0]                                       # [BQ]
    kpos = kpos_ref[0]                                       # [BK]
    if static_causal:
        # Contiguous-positions fast path: the block classes are integer
        # functions of the program ids, and the index maps re-point every
        # skipped tile's kv-side blocks at the previous (visible) blocks,
        # so skipped programs trigger NO new DMAs — measured ~1.4 us per
        # skipped program otherwise, ~20% of the whole kernel at seq 16k
        # where nearly half the rectangular grid is below the causal
        # diagonal (PERF.md r5).
        qi = pl.program_id(2)
        visible, full = _static_block_classes(qi, ki, block_q, block_k)
    elif causal:
        # Three block classes: fully masked (skip entirely), fully visible
        # (no mask / no -inf guards — the common case, ~(num_kv-1)/2 of the
        # grid), and diagonal-straddling (masked path). Splitting the paths
        # removes 4+ VPU passes over [BQ, BK] from the common case; the
        # softmax VPU work, not the MXU matmuls, bounds this kernel at D=64.
        visible = jnp.max(qpos) >= jnp.min(kpos)
        full = jnp.min(qpos) >= jnp.max(kpos)
    else:
        visible = ki >= 0
        full = visible

    def _tile(masked: bool):
        # Matmuls keep the input dtype (bf16 on the fast MXU path) with fp32
        # accumulation via preferred_element_type; only the softmax math runs
        # in fp32. Casting inputs to fp32 before the dot would put the MXU in
        # fp32 mode (~8x slower on MXU).
        if fused_rope:
            q = qrot_ref[...]                                # [BQ, D]
            k_blk = _rot(k_ref[0, 0], ck_ref, sk_ref, 1.0)
        else:
            q = q_ref[0, 0]                                  # [BQ, D]
            k_blk = k_ref[0, 0]                              # [BK, D]
        v_blk = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [BQ, BK] fp32
        if sm_scale != 1.0:  # the public wrapper pre-scales q; this is the
            s = s * sm_scale  # fallback for direct _fwd/_bwd callers
        if masked:
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...][:, 0]                            # [BQ]
        l_prev = l_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)                      # exp(-inf-(-inf))
        alpha = jnp.where(m_prev <= _NEG_INF, 0.0, alpha)    # guarded to 0
        p = jnp.exp(s - m_new[:, None])
        if masked:
            # a fully-masked row has m_new = -inf; exp(-inf - -inf) = nan
            p = jnp.where(m_new[:, None] <= _NEG_INF, 0.0, p)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(full)
    def _compute_full():
        _tile(masked=False)

    if causal:
        @pl.when(visible & ~full)
        def _compute_masked():
            _tile(masked=True)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        m = m_ref[...][:, 0]
        l = l_ref[...][:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        # True -inf for fully-masked rows — the CP ring's LSE merge keys on
        # isinf, matching sdpa_attention's convention.
        lse = jnp.where(l == 0.0, -jnp.inf, m + jnp.log(l_safe))
        lse_ref[0, 0] = lse.astype(jnp.float32)[:, None]


def _fwd(q4, k4, v4, qpos, kpos, rope, sm_scale, causal, block_q, block_k,
         interpret, static_causal=False):
    """q4 [B,Hq,Sq,D]; k4/v4 [B,Hkv,Sk,D]; qpos [1,Sq]; kpos [1,Sk];
    rope = None or (cos, sin) half tables [maxS, D/2] applied in-kernel.
    static_causal: positions are known to be plain 0..S-1 — skipped tiles
    use program-id block classes and DMA-free index maps (_kv_eff)."""
    b, hq, sq, d = q4.shape
    hkv, sk = k4.shape[1], k4.shape[2]
    n_rep = hq // hkv
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    num_kv = sk // bk

    def keff(qi, ki):
        # last kv block any row of q-block qi can see; skipped tiles
        # re-load it (same block as the previous iteration -> no DMA)
        return _kv_eff(qi, ki, bq, bk) if static_causal else ki

    rope_args, rope_specs = [], []
    if rope is not None:
        cq, sq_t = _rot_tables(*rope, qpos)
        ck, sk_t = _rot_tables(*rope, kpos)
        rope_args = [cq, sq_t, ck, sk_t]
        rope_specs = [
            pl.BlockSpec((1, bq, d), lambda bi, hi, qi, ki: (0, qi, 0)),
            pl.BlockSpec((1, bq, d), lambda bi, hi, qi, ki: (0, qi, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bi, hi, qi, ki: (0, keff(qi, ki), 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bi, hi, qi, ki: (0, keff(qi, ki), 0)),
        ]

    grid = (b, hq, sq // bq, num_kv)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, num_kv=num_kv,
        fused_rope=rope is not None, static_causal=static_causal,
        block_q=bq, block_k=bk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq), lambda bi, hi, qi, ki: (0, qi)),  # qpos
            pl.BlockSpec((1, bk),
                         lambda bi, hi, qi, ki: (0, keff(qi, ki))),  # kpos
            *rope_specs,
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki, n_rep=n_rep:
                         (bi, hi // n_rep, keff(qi, ki), 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki, n_rep=n_rep:
                         (bi, hi // n_rep, keff(qi, ki), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            _out_struct((b, hq, sq, d), q4.dtype, q4, k4, v4, qpos, kpos,
                        *rope_args),
            _out_struct((b, hq, sq, 1), jnp.float32, q4, k4, v4, qpos, kpos,
                        *rope_args),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # m (broadcast over lanes)
            pltpu.VMEM((bq, 128), jnp.float32),   # l
            pltpu.VMEM((bq, d), jnp.float32),     # acc
        ] + ([pltpu.VMEM((bq, d), q4.dtype)]      # rotated q, reused per ki
             if rope is not None else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qpos, kpos, *rope_args, q4, k4, v4)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels (flash-attn 2 structure: recompute P from saved LSE)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(*refs, sm_scale: float, causal: bool, num_kv: int,
                   fused_rope: bool, static_causal: bool = False,
                   block_q: int = 0, block_k: int = 0):
    if fused_rope:
        (qpos_ref, kpos_ref, cq_ref, sq_ref, ck_ref, sk_ref,
         q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc_ref, qrot_ref) = refs
    else:
        (qpos_ref, kpos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
         delta_ref, dq_ref, dq_acc_ref) = refs
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)
        if fused_rope:
            qrot_ref[...] = _rot(q_ref[0, 0], cq_ref, sq_ref, 1.0)

    qpos = qpos_ref[0]
    kpos = kpos_ref[0]
    if static_causal:
        # program-id block classes + DMA-free skipped tiles (_kv_eff) —
        # see _fwd_kernel's static_causal note
        qi = pl.program_id(2)
        visible, full = _static_block_classes(qi, ki, block_q, block_k)
    elif causal:
        visible = jnp.max(qpos) >= jnp.min(kpos)
        full = jnp.min(qpos) >= jnp.max(kpos)
    else:
        visible = ki >= 0
        full = visible

    def _tile(masked: bool):
        # bf16 MXU matmuls with fp32 accumulation (see _fwd_kernel note).
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, :, 0]                            # [BQ]
        delta = delta_ref[0, 0, :, 0]                        # [BQ]
        v_blk = v_ref[0, 0]
        if fused_rope:
            q = qrot_ref[...]                                # [BQ, D]
            k_blk = _rot(k_ref[0, 0], ck_ref, sk_ref, 1.0)
        else:
            q = q_ref[0, 0]                                  # [BQ, D]
            k_blk = k_ref[0, 0]                              # [BK, D]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if sm_scale != 1.0:
            s = s * sm_scale
        if masked:
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if masked:
            p = jnp.where(lse[:, None] <= _NEG_INF, 0.0, p)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        if sm_scale != 1.0:
            ds = ds * sm_scale
        ds = ds.astype(k_blk.dtype)
        dq_acc_ref[...] = dq_acc_ref[...] + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(full)
    def _compute_full():
        _tile(masked=False)

    if causal:
        @pl.when(visible & ~full)
        def _compute_masked():
            _tile(masked=True)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        dq = dq_acc_ref[...]
        if fused_rope:
            # dq was accumulated w.r.t. the rotated q; map back through the
            # rotation's transpose (R^T = rotation with negated sin).
            dq = _rot(dq, cq_ref, sq_ref, -1.0)
        dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, sm_scale: float, causal: bool, num_inner: int,
                    fused_rope: bool, static_causal: bool = False,
                    block_q: int = 0, block_k: int = 0, num_q: int = 0):
    if fused_rope:
        (qpos_ref, kpos_ref, cq_ref, sq_ref, ck_ref, sk_ref,
         q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, krot_ref) = refs
    else:
        (qpos_ref, kpos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
         delta_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref) = refs
    # Inner sequential dim folds (group-head, q-block): the GQA group
    # accumulates into this kv-head's dk/dv inside the program.
    t = pl.program_id(3)

    @pl.when(t == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)
        if fused_rope:
            # k is constant across the inner (group-head x q-block) dim —
            # rotate once per kv-block chain.
            krot_ref[...] = _rot(k_ref[0, 0], ck_ref, sk_ref, 1.0)

    qpos = qpos_ref[0]
    kpos = kpos_ref[0]
    if static_causal:
        # program-id block classes + DMA-free skipped tiles (_q_eff) —
        # see _fwd_kernel's static_causal note
        ki = pl.program_id(2)
        qi = t % num_q
        visible, full = _static_block_classes(qi, ki, block_q, block_k)
    elif causal:
        visible = jnp.max(qpos) >= jnp.min(kpos)
        full = jnp.min(qpos) >= jnp.max(kpos)
    else:
        visible = t >= 0
        full = visible

    def _tile(masked: bool):
        # bf16 MXU matmuls with fp32 accumulation (see _fwd_kernel note).
        v_blk = v_ref[0, 0]
        do = do_ref[0, 0]
        if fused_rope:
            k_blk = krot_ref[...]                            # [BK, D]
            q_blk = _rot(q_ref[0, 0], cq_ref, sq_ref, 1.0)
        else:
            k_blk = k_ref[0, 0]                              # [BK, D]
            q_blk = q_ref[0, 0]                              # [BQ, D]
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [BQ, BK]
        if sm_scale != 1.0:
            s = s * sm_scale
        if masked:
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if masked:
            p = jnp.where(lse[:, None] <= _NEG_INF, 0.0, p)
        p_lo = p.astype(do.dtype)
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            p_lo, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        if sm_scale != 1.0:
            ds = ds * sm_scale
        ds = ds.astype(q_blk.dtype)
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(full)
    def _compute_full():
        _tile(masked=False)

    if causal:
        @pl.when(visible & ~full)
        def _compute_masked():
            _tile(masked=True)

    @pl.when(t == num_inner - 1)
    def _finalize():
        dk = dk_acc_ref[...]
        if fused_rope:
            dk = _rot(dk, ck_ref, sk_ref, -1.0)  # back through R^T
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _bwd(q4, k4, v4, o4, lse, do4, dlse, qpos, kpos, rope, sm_scale, causal,
         block_q, block_k, interpret, static_causal=False):
    b, hq, sq, d = q4.shape
    hkv, sk = k4.shape[1], k4.shape[2]
    n_rep = hq // hkv
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    num_q = sq // bq
    num_kv = sk // bk

    def keff(qi, ki):
        return _kv_eff(qi, ki, bq, bk) if static_causal else ki

    def qeff(qi, ki):
        return _q_eff(qi, ki, bq, bk, num_q) if static_causal else qi

    rope_args = []
    if rope is not None:
        cq, sq_t = _rot_tables(*rope, qpos)
        ck, sk_t = _rot_tables(*rope, kpos)
        rope_args = [cq, sq_t, ck, sk_t]

    def rope_specs(qmap, kmap):
        if rope is None:
            return []
        return [pl.BlockSpec((1, bq, d), qmap), pl.BlockSpec((1, bq, d), qmap),
                pl.BlockSpec((1, bk, d), kmap), pl.BlockSpec((1, bk, d), kmap)]

    # delta = rowsum(do * o) [B, Hq, Sq] (flash-attn 2's D term). The LSE
    # cotangent folds in here: dL/ds_ij = p_ij * (dp_ij - delta_i + dlse_i)
    # because dlse_i/ds_ij = p_ij — so shipping (delta - dlse) to the kernels
    # handles out- and lse-cotangents in one pass (the CP ring's LSE merge
    # differentiates through both).
    delta = jnp.sum(do4.astype(jnp.float32) * o4.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = delta - dlse.astype(jnp.float32)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          num_kv=num_kv, fused_rope=rope is not None,
                          static_causal=static_causal, block_q=bq,
                          block_k=bk),
        grid=(b, hq, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, bq), lambda bi, hi, qi, ki: (0, qi)),
            pl.BlockSpec((1, bk),
                         lambda bi, hi, qi, ki: (0, keff(qi, ki))),
            *rope_specs(lambda bi, hi, qi, ki: (0, qi, 0),
                        lambda bi, hi, qi, ki: (0, keff(qi, ki), 0)),
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki, n_rep=n_rep:
                         (bi, hi // n_rep, keff(qi, ki), 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki, n_rep=n_rep:
                         (bi, hi // n_rep, keff(qi, ki), 0)),
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=_out_struct((b, hq, sq, d), q4.dtype,
                              q4, k4, v4, do4, lse, delta, qpos, kpos,
                              *rope_args),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)]
        + ([pltpu.VMEM((bq, d), q4.dtype)] if rope is not None else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qpos, kpos, *rope_args, q4, k4, v4, do4, lse, delta)

    # dk/dv: one program per (batch, KV head, kv-block); the inner
    # sequential dim walks the group's query heads x q-blocks, accumulating
    # into scratch — GQA costs no extra memory traffic or post-hoc sum.
    num_inner = n_rep * num_q

    def qhead(hi, t):
        return hi * n_rep + t // num_q

    def qblk(t):
        return t % num_q

    def qbe(ki, t):
        return qeff(qblk(t), ki)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          num_inner=num_inner, fused_rope=rope is not None,
                          static_causal=static_causal, block_q=bq,
                          block_k=bk, num_q=num_q),
        grid=(b, hkv, num_kv, num_inner),
        in_specs=[
            pl.BlockSpec((1, bq), lambda bi, hi, ki, t: (0, qbe(ki, t))),
            pl.BlockSpec((1, bk), lambda bi, hi, ki, t: (0, ki)),
            *rope_specs(lambda bi, hi, ki, t: (0, qbe(ki, t), 0),
                        lambda bi, hi, ki, t: (0, ki, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, ki, t: (bi, qhead(hi, t),
                                                qbe(ki, t), 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, t: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, t: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, ki, t: (bi, qhead(hi, t),
                                                qbe(ki, t), 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda bi, hi, ki, t: (bi, qhead(hi, t),
                                                qbe(ki, t), 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda bi, hi, ki, t: (bi, qhead(hi, t),
                                                qbe(ki, t), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, t: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, t: (bi, hi, ki, 0)),
        ],
        out_shape=[
            _out_struct((b, hkv, sk, d), k4.dtype,
                        q4, k4, v4, do4, lse, delta, qpos, kpos, *rope_args),
            _out_struct((b, hkv, sk, d), v4.dtype,
                        q4, k4, v4, do4, lse, delta, qpos, kpos, *rope_args),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ] + ([pltpu.VMEM((bk, d), k4.dtype)]  # rotated k, reused per t
             if rope is not None else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qpos, kpos, *rope_args, q4, k4, v4, do4, lse, delta)

    return dq, dk.astype(k4.dtype), dv.astype(v4.dtype)


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_core(q4, k4, v4, qpos, kpos, rope, sm_scale, causal, block_q,
                block_k, interpret, static_causal):
    return _fwd(q4, k4, v4, qpos, kpos, rope, sm_scale, causal, block_q,
                block_k, interpret, static_causal)


def _flash_core_fwd(q4, k4, v4, qpos, kpos, rope, sm_scale, causal, block_q,
                    block_k, interpret, static_causal):
    out, lse = _fwd(q4, k4, v4, qpos, kpos, rope, sm_scale, causal, block_q,
                    block_k, interpret, static_causal)
    # Residuals carry the *named* values: under jax.checkpoint the "dots"
    # policy (models/llama.py remat_policy_for) saves attn_out/attn_lse, so
    # the backward pass reads them instead of re-running the forward kernel
    # (profiled at ~4% of step time as rematted_computation). The named
    # residual is the FLAT [B, S, H*D] view: saving the 4-D [B, S, H, 64]
    # form would tile the 64-wide minor dim to 128 lanes — a 2x HBM pad on
    # every saved attention output (PERF.md r4); the reshape back is free.
    b, s, h, dd = out.shape
    out_flat = checkpoint_name(out.reshape(b, s, h * dd), "attn_out")
    out = out_flat.reshape(b, s, h, dd)
    lse = checkpoint_name(lse, "attn_lse")
    return (out, lse), (q4, k4, v4, out_flat, lse, qpos, kpos, rope)


def _flash_core_bwd(sm_scale, causal, block_q, block_k, interpret,
                    static_causal, res, cts):
    q4, k4, v4, out_flat, lse, qpos, kpos, rope = res
    do4, dlse = cts
    out = out_flat.reshape(do4.shape)
    dq, dk, dv = _bwd(q4, k4, v4, out, lse, do4, dlse, qpos, kpos, rope,
                      sm_scale, causal, block_q, block_k, interpret,
                      static_causal)
    # rope tables get a zero cotangent (they are precomputed position
    # constants, never trained).
    drope = None if rope is None else jax.tree.map(jnp.zeros_like, rope)
    return dq, dk, dv, None, None, drope


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_positions: Optional[jnp.ndarray] = None,
    kv_positions: Optional[jnp.ndarray] = None,
    return_lse: bool = False,
    sm_scale: Optional[float] = None,
    rope: Optional[tuple] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
):
    """Drop-in flash counterpart of `sdpa_attention` (same shapes/semantics):
    q [B, Sq, Hq, D]; k/v [B, Sk, Hkv, D] (GQA unexpanded); optional global
    position vectors for CP shards. Returns out (and fp32 lse [B, Hq, Sq]).

    rope: optional (cos, sin) half tables [maxS, D/2] from ops.rope — when
    given, q/k arrive UNROTATED and rotate-half RoPE is applied inside the
    kernels at q_positions/kv_positions (replacing the reference's separate
    fused-rotary CUDA kernel, ref: model.py:8,136-137, and XLA's layout-heavy
    rotate-half, which profiled at ~7% of a train step).

    Backend dispatch: on TPU the Pallas kernels run compiled. On other
    backends (the simulated-mesh test platform) the mathematically identical
    jnp path runs instead — Pallas interpreter mode does not compose with
    shard_map's varying-axis checking, and tests/test_flash_attention.py
    pins kernel==jnp equivalence in interpreter mode directly. Pass
    `interpret=True` to force the Pallas interpreter (kernel unit tests).
    """
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if interpret is None and not compiled_kernels_available():
        from picotron_tpu.ops.attention import sdpa_attention
        from picotron_tpu.ops.rope import apply_rope

        if rope is not None:
            q = apply_rope(q, *rope, q_positions)
            k = apply_rope(k, *rope, kv_positions)
        return sdpa_attention(
            q, k, v, causal=causal, q_positions=q_positions,
            kv_positions=kv_positions, return_lse=return_lse,
            sm_scale=sm_scale)
    interpret = bool(interpret)
    # Contiguous-causal fast path: positions passed as None mean plain
    # 0..S-1, so block visibility is a static function of the program ids
    # and the kernels elide every below-diagonal tile's DMAs (PERF.md r5:
    # skipped programs measured ~1.4 us each — ~20% of the seq-16k
    # forward kernel). Callers with genuinely permuted layouts (the CP
    # ring/zigzag) pass explicit position arrays and keep the dynamic
    # masking path.
    static_causal = (causal and q_positions is None
                     and kv_positions is None)
    qpos = (q_positions if q_positions is not None else jnp.arange(sq))
    kpos = (kv_positions if kv_positions is not None else jnp.arange(sk))
    qpos = qpos.astype(jnp.int32).reshape(1, sq)
    kpos = kpos.astype(jnp.int32).reshape(1, sk)

    q4 = jnp.swapaxes(q, 1, 2)
    k4 = jnp.swapaxes(k, 1, 2)
    v4 = jnp.swapaxes(v, 1, 2)

    # Fold sm_scale into q once here instead of scaling the [BQ, BK] score
    # block inside every kernel program — one [B,H,S,D] multiply replaces
    # S/BK of them, and for the common d = 4^k the scale 2^-k is exact in
    # bf16. Differentiable, so dq picks up the factor through the VJP chain.
    out, lse = _flash_core(q4 * jnp.asarray(sm_scale, q4.dtype), k4, v4,
                           qpos, kpos, rope, 1.0, causal, block_q,
                           block_k, interpret, static_causal)
    out = jnp.swapaxes(out, 1, 2)
    if return_lse:
        # LSE is the *scaled-score* logsumexp, same convention as
        # sdpa_attention (which also applies sm_scale before the softmax).
        # Kernels carry it [B, Hq, Sq, 1] (TPU block-shape constraint);
        # drop the trailing dim at the boundary.
        return out, lse[..., 0]
    return out


def flash_attention_bwd_from_saved(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    out: jnp.ndarray,
    lse: jnp.ndarray,
    dout: jnp.ndarray,
    *,
    causal: bool = True,
    q_positions: Optional[jnp.ndarray] = None,
    kv_positions: Optional[jnp.ndarray] = None,
    sm_scale: Optional[float] = None,
    rope: Optional[tuple] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
):
    """(dq, dk, dv) from the forward's saved tensors — the manual-VJP entry
    for the fused grad engine (parallel/fused_bwd.py), which saves exactly
    (q, k, v, out, lse) per layer and never re-runs the forward kernel.

    Shapes follow the public `flash_attention`: q [B, Sq, Hq, D] UNROTATED
    and UNSCALED (as produced by qkv_proj — the "qkv_out" save set), out
    [B, Sq, Hq, D], lse [B, Hq, Sq] fp32 (the public return_lse form),
    dout like out. The sm_scale fold and the head-axis swaps happen here,
    mirroring `flash_attention`'s pre-kernel steps, so callers hold only
    the flat matmul-layout tensors. The LSE cotangent is zero by contract
    (training consumes `out` only).

    Contract: the gradients are computed FROM the passed (out, lse) — the
    probabilities are normalized by the saved lse, never a recomputed local
    one. Called on one K/V block of a larger attention with the block's
    positions and the GLOBAL (out, lse, dout), the result is that block's
    additive contribution to the global (dq, dk, dv) — the property the
    context-parallel backwards sum over (ring_attention_bwd_from_saved /
    ulysses_attention_bwd_from_saved). On non-TPU backends the identical
    math runs as plain jnp (ops.attention.sdpa_attention_bwd_from_saved),
    so CPU-mesh parity tests exercise the same structure as the kernels.
    """
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if interpret is None and not compiled_kernels_available():
        from picotron_tpu.ops.attention import sdpa_attention_bwd_from_saved
        from picotron_tpu.ops.rope import apply_rope

        if rope is None:
            return sdpa_attention_bwd_from_saved(
                q, k, v, out, lse, dout, causal=causal,
                q_positions=q_positions, kv_positions=kv_positions,
                sm_scale=sm_scale)
        # q/k arrive unrotated; grads map back through the rotation's
        # transpose — jax.vjp over apply_rope is that transpose exactly.
        (qr, kr), rot_vjp = jax.vjp(
            lambda q_, k_: (apply_rope(q_, *rope, q_positions),
                            apply_rope(k_, *rope, kv_positions)), q, k)
        dqr, dkr, dv = sdpa_attention_bwd_from_saved(
            qr, kr, v, out, lse, dout, causal=causal,
            q_positions=q_positions, kv_positions=kv_positions,
            sm_scale=sm_scale)
        dq, dk = rot_vjp((dqr, dkr))
        return dq, dk, dv
    interpret = bool(interpret)
    static_causal = (causal and q_positions is None
                     and kv_positions is None)
    qpos = (q_positions if q_positions is not None else jnp.arange(sq))
    kpos = (kv_positions if kv_positions is not None else jnp.arange(sk))
    qpos = qpos.astype(jnp.int32).reshape(1, sq)
    kpos = kpos.astype(jnp.int32).reshape(1, sk)
    scale = jnp.asarray(sm_scale, q.dtype)
    q4 = jnp.swapaxes(q, 1, 2) * scale
    k4 = jnp.swapaxes(k, 1, 2)
    v4 = jnp.swapaxes(v, 1, 2)
    o4 = jnp.swapaxes(out, 1, 2)
    do4 = jnp.swapaxes(dout, 1, 2)
    lse4 = lse[..., None]
    dq4, dk4, dv4 = _bwd(q4, k4, v4, o4, lse4, do4, jnp.zeros_like(lse4),
                         qpos, kpos, rope, 1.0, causal, block_q, block_k,
                         interpret, static_causal)
    # chain rule through the q * sm_scale fold
    dq = jnp.swapaxes(dq4, 1, 2) * scale
    return dq, jnp.swapaxes(dk4, 1, 2), jnp.swapaxes(dv4, 1, 2)
