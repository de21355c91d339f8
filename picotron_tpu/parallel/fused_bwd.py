"""Fused-accumulation grad engine: a manual VJP over the decoder-layer scan
that adds each layer's weight gradients into the fp32 accumulator IN-SCAN.

Why this exists (PERF.md r5): under gradient accumulation the AD path
materializes every microbatch's full stacked-layer grad tree (the backward
scan's ys output, ~6.5 GB fp32 at SmolLM-1.7B) and then runs whole-tree
`g_acc + grads` adds — measured at 26 ms per microbatch of pure serialized
HBM traffic between the backward and the next forward scan (1.7 s of a 36 s
step at grad-acc 64, all at roofline, none of it overlappable: TPU cores
run one op at a time, and the adds depend on the completed backward-scan
output buffer). This engine instead carries the fp32 accumulator through a
manual backward layer scan and updates one layer's slices per iteration
(`dynamic-update-slice(acc, acc[k] + dW_k)`), so the microbatch grad tree
never exists — the temp write AND the separate add pass disappear.

The backward mirrors exactly the `dots_attn` remat policy's save set
(models/llama.py remat_policy_for): the forward scan saves per layer the
layer input x plus the attention impl's residuals (q/k/v flat "qkv_out",
out flat "attn_out", and the saved softmax statistics "attn_lse"); the
backward recomputes the norms, the o-projection input, and the whole
MLP/MoE block, and reaches the attention backward through a
`*_bwd_from_saved` entry — never re-running the forward kernel. Segment
VJPs (`jax.vjp` over the same llama.py building blocks — qkv_proj,
_mlp_block/_moe_block, the ctx.f/g hooks) derive every other transpose, so
TP/SP/EP collectives and activation functions cannot diverge from the AD
engine; parity is pinned by tests/test_fused_bwd.py.

Per-axis structure (the north-star layouts; VERDICT r5):

- **TP / sequence parallelism**: the ctx.f/g hooks live inside the segment
  VJPs, so Megatron-SP's all-gather / reduce-scatter pair appears in both
  directions of the fused layer scan for free (forward as written;
  backward as JAX's transposes: tiled all_gather <-> psum_scatter). The
  residual stream and its saved layer inputs stay seq-sharded [B, S/tp, H];
  the saved q/k/v/out are the full-sequence post-gather tensors, exactly
  as under the AD engine's dots_attn policy.
- **Context parallelism**: both cp schedules save their per-block softmax
  statistics and re-enter the backward through a from-saved twin — the
  ring via `ring_attention_bwd_from_saved` (a second forward ppermute ring
  carrying dK/dV accumulators with their blocks; globally-normalized
  per-block grads from the merged LSE), Ulysses via
  `ulysses_attention_bwd_from_saved` (the same all_to_all pair in both
  directions around the flash backward kernel). RoPE for the ring is
  applied outside the ring exactly as in the forward wiring
  (parallel/api.py), with the rotation's transpose recovered by jax.vjp.
- **Data axes**: the in-scan accumulator is a purely per-device fp32
  tree — no collective touches it until the engine seam
  (api._data_axes_psum) reduces it ONCE over the data axes after the last
  microbatch, the same exit as the AD engine's.
- **MoE (Mixtral expert block)**: the expert MLP is recomputed in backward
  by a segment VJP over `_moe_block` — routing (router logits, top-k,
  slot cumsum) recomputes deterministically from the saved layer input,
  so the forward-scan save set stays exactly dots_attn's (no [E, C, H]
  dispatch buffers saved). The router aux loss re-folds inside the
  segment (`aux * count`, the loss_sum_count convention) so balance/z
  gradients flow with the same cotangent the AD engine sees; the capacity
  drop statistic rides the forward scan only (observability, no grad).

Eligibility (see `fused_bwd_supported`; `resolved_grad_engine` is the one
predicate every reader asks): every single-pipeline-stage layout —
dp/tp/SP/cp (ring and Ulysses)/ep/MoE — under remat with the dots_attn
policy, through `fused_micro_grads` in the microbatch loop; and since
PR 63 the 1F1B pipeline engine's tick (parallel/pp.py), whose backward
unit calls the same two scans (`layer_scans`) on its stage's slice and the
same head block (`head_grads`) in the last stage's branch, over the same
axes. AFAB, the MPMD executor, other remat policies and MoE over stages of
unequal depth keep the AD engine.
The reference gets in-place accumulation for free on every layout from
per-rank autograd hooks (ref: bucket.py:25-31 — an imperative luxury an
SPMD program has to earn back with scan structure); with the three axes
above, the SPMD port is no longer single-chip-only.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.config import Config
from picotron_tpu.models.llama import (
    ParallelCtx, _mlp_block, _moe_block, compute_dtype, head_weight,
    model_rope_tables, qkv_proj,
)
from picotron_tpu.ops.flash_attention import (
    flash_attention, flash_attention_bwd_from_saved,
)
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.telemetry.scopes import scope


def fused_bwd_supported(cfg: Config) -> bool:
    """True when the manual backward of this file covers the config: the
    old block in one `layers` stack, under remat with the dots_attn policy
    (the save set the manual backward is derived from), and a layout whose
    program calls it. At pp = 1 that is every layout (dp/tp/SP/cp
    ring|ulysses|mesh/ep/MoE: `fused_micro_grads` in the microbatch loop).
    At pp > 1 it is the spmd executor's 1F1B engine, whose tick's backward
    unit runs this file's two layer scans on its stage's slice
    (parallel/pp.py `pipeline_1f1b_grads`), over the same axes; MoE only
    where the layers split evenly over the stages (the scans do not mask a
    padded slot's router statistics). tests/test_pp_engines.py holds each
    against the AD tick leaf by leaf. AFAB differentiates through its scan
    and the MPMD executor has its own per-stage programs: both stay AD."""
    d, t = cfg.distributed, cfg.training
    m = cfg.model
    # one kind of block in one `layers` stack: latent and EVA attention,
    # sandwich and 1 + w norms, a float32 residual stream, a shared expert,
    # sigmoid routing and leading dense layers keep the AD engine
    # (Config.validate refuses an explicit 'fused' for them)
    plain = (len(m.stacks) == 1 and not m.stacks[0].block.sandwich
             and m.stacks[0].block.attn == "gqa"
             and not m.norm_add_unit_offset and not m.fp32_skip_add
             and not m.n_shared_experts
             and m.qk_norm != "head"
             and m.moe_scoring == "softmax"
             and m.routed_scaling_factor == 1.0
             and m.router_width == m.num_experts)
    layout = d.pp_size == 1 or (
        d.pp_engine == "1f1b" and cfg.pipeline.executor == "spmd"
        and not (m.num_experts and m.num_hidden_layers % d.pp_size))
    return (layout and plain
            and t.remat and t.remat_policy == "dots_attn")


def resolved_grad_engine(cfg: Config) -> str:
    """'fused' or 'ad': the backward unit the step's program holds, for the
    step (parallel/api.py `_device_grads`), the 1F1B tick (parallel/pp.py),
    `Config.validate` and the collectives audit
    (analysis/collectives.py) alike. `training.grad_engine: ad` is the AD
    engine everywhere; `fused` is refused by `Config.validate` where
    `fused_bwd_supported` is false; `auto` takes the manual backward
    wherever it is supported and gradients accumulate: under a pipeline
    (the schedule IS the accumulation) or over more than one microbatch.
    In a device trace the `dw_accum` scope is the sign that it engaged."""
    t = cfg.training
    if t.grad_engine == "ad" or not fused_bwd_supported(cfg):
        return "ad"
    if (t.grad_engine == "fused" or cfg.distributed.pp_size > 1
            or t.gradient_accumulation_steps > 1):
        return "fused"
    return "ad"


def _vary_like(x, ref):
    from picotron_tpu import compat
    from picotron_tpu.parallel.pp import _vary_over

    return _vary_over(x, set(compat.vma(ref)))


def _attn_paths(cfg: Config, ctx: ParallelCtx, cos, sin):
    """(attn_fwd, attn_bwd) closures for this config's attention schedule,
    mirroring parallel/api.py's dispatch exactly:

      attn_fwd(q, k, v) -> (out, lse)          q/k UNROTATED [B, S, H, D]
      attn_bwd(q, k, v, out, lse, dout) -> (dq, dk, dv)   same domains

    The lse is whatever statistic the schedule's `*_bwd_from_saved` twin
    consumes: the kernel LSE (cp=1), the globally merged ring LSE, or the
    inner-domain Ulysses LSE."""
    from picotron_tpu.config import resolved_cp_flavor, resolved_cp_mesh

    d, m = cfg.distributed, cfg.model
    pos = ctx.positions
    use_flash = m.attn_impl in ("auto", "flash", "ring", "ulysses", "mesh")
    cp_flavor = resolved_cp_flavor(cfg)

    if d.cp_size > 1 and cp_flavor == "ulysses":
        from picotron_tpu.ops.ulysses import (
            ulysses_attention, ulysses_attention_bwd_from_saved,
            ulysses_static_layout,
        )

        full_pos, seq_sort = ulysses_static_layout(cfg)
        uly_kw = dict(axis="cp", q_positions=pos, rope=(cos, sin),
                      seq_sort=seq_sort, full_positions=full_pos,
                      positions_static=True)

        def attn_fwd(q, k, v):
            return ulysses_attention(q, k, v, attn_fn=flash_attention,
                                     return_lse=True, **uly_kw)

        def attn_bwd(q, k, v, out, lse, dout):
            return ulysses_attention_bwd_from_saved(q, k, v, out, lse,
                                                    dout, **uly_kw)

        return attn_fwd, attn_bwd

    if d.cp_size > 1 and cp_flavor == "mesh":
        from picotron_tpu.ops.attention import (
            sdpa_attention, sdpa_attention_bwd_from_saved,
        )
        from picotron_tpu.ops.mesh_attention import (
            mesh_attention, mesh_attention_bwd_from_saved,
        )
        from picotron_tpu.ops.rope import apply_rope

        cp_mesh = resolved_cp_mesh(cfg)
        blockwise = partial(
            (flash_attention if use_flash else sdpa_attention),
            return_lse=True)
        block_bwd = (flash_attention_bwd_from_saved if use_flash
                     else sdpa_attention_bwd_from_saved)

        def rot_pair(q, k):
            # pre-rotation, same single-sourcing as the ring branch below
            return jax.vjp(
                lambda q_, k_: (apply_rope(q_, cos, sin, pos),
                                apply_rope(k_, cos, sin, pos)), q, k)

        def attn_fwd(q, k, v):
            (qr, kr), _ = rot_pair(q, k)
            return mesh_attention(qr, kr, v, axis="cp", cp_mesh=cp_mesh,
                                  q_positions=pos, attn_block=blockwise,
                                  return_lse=True)

        def attn_bwd(q, k, v, out, lse, dout):
            (qr, kr), rot_vjp = rot_pair(q, k)
            dqr, dkr, dv = mesh_attention_bwd_from_saved(
                qr, kr, v, out, lse, dout, axis="cp", cp_mesh=cp_mesh,
                q_positions=pos, block_bwd=block_bwd)
            dq, dk = rot_vjp((dqr, dkr))
            return dq, dk, dv

        return attn_fwd, attn_bwd

    if d.cp_size > 1:
        from picotron_tpu.ops.attention import (
            sdpa_attention, sdpa_attention_bwd_from_saved,
        )
        from picotron_tpu.ops.ring_attention import (
            ring_attention, ring_attention_bwd_from_saved,
        )
        from picotron_tpu.ops.rope import apply_rope

        blockwise = partial(
            (flash_attention if use_flash else sdpa_attention),
            return_lse=True)
        block_bwd = (flash_attention_bwd_from_saved if use_flash
                     else sdpa_attention_bwd_from_saved)

        def rot_pair(q, k):
            # K is rotated BEFORE entering the ring so each block travels
            # pre-rotated with its positions (same single-sourcing as the
            # forward wiring, parallel/api.py); jax.vjp over the rotation
            # is its exact transpose for the backward.
            return jax.vjp(
                lambda q_, k_: (apply_rope(q_, cos, sin, pos),
                                apply_rope(k_, cos, sin, pos)), q, k)

        def attn_fwd(q, k, v):
            (qr, kr), _ = rot_pair(q, k)
            return ring_attention(qr, kr, v, axis="cp", q_positions=pos,
                                  attn_block=blockwise, return_lse=True)

        def attn_bwd(q, k, v, out, lse, dout):
            (qr, kr), rot_vjp = rot_pair(q, k)
            dqr, dkr, dv = ring_attention_bwd_from_saved(
                qr, kr, v, out, lse, dout, axis="cp", q_positions=pos,
                block_bwd=block_bwd)
            dq, dk = rot_vjp((dqr, dkr))
            return dq, dk, dv

        return attn_fwd, attn_bwd

    if use_flash:
        def attn_fwd(q, k, v):
            return flash_attention(q, k, v, causal=True, rope=(cos, sin),
                                   q_positions=pos, kv_positions=pos,
                                   return_lse=True)

        def attn_bwd(q, k, v, out, lse, dout):
            return flash_attention_bwd_from_saved(
                q, k, v, out, lse, dout, causal=True, q_positions=pos,
                kv_positions=pos, rope=(cos, sin))

        return attn_fwd, attn_bwd

    from picotron_tpu.ops.attention import (
        sdpa_attention, sdpa_attention_bwd_from_saved,
    )
    from picotron_tpu.ops.rope import apply_rope

    def rot_pair(q, k):
        return jax.vjp(
            lambda q_, k_: (apply_rope(q_, cos, sin, pos),
                            apply_rope(k_, cos, sin, pos)), q, k)

    def attn_fwd(q, k, v):
        (qr, kr), _ = rot_pair(q, k)
        return sdpa_attention(qr, kr, v, causal=True, q_positions=pos,
                              kv_positions=pos, return_lse=True)

    def attn_bwd(q, k, v, out, lse, dout):
        (qr, kr), rot_vjp = rot_pair(q, k)
        dqr, dkr, dv = sdpa_attention_bwd_from_saved(
            qr, kr, v, out, lse, dout, causal=True, q_positions=pos,
            kv_positions=pos)
        dq, dk = rot_vjp((dqr, dkr))
        return dq, dk, dv

    return attn_fwd, attn_bwd


def layer_scans(cfg: Config, ctx: ParallelCtx, layers):
    """(forward, backward): the two layer scans of the manual backward over
    the stacked `layers` tree (a whole model's, or one pipeline stage's
    slice), shared by `fused_micro_grads` and the 1F1B tick's backward unit
    (parallel/pp.py).

      forward(x0) -> (xL, saved, aux_layers): the layer block, saving per
        layer exactly `dots_attn`'s set (the layer input, q/k/v and out
        flat, the softmax statistics); aux_layers [L, 3] is each layer's
        (router loss, drops, load), zeros for a dense model.
      backward(saved, dxL, g_layers, count_f) -> (dx0, g_layers'): the
        reverse scan; g_layers is the fp32 accumulator of the stack, and
        each iteration adds one layer's dW into its slices (scope
        `dw_accum`), so no gradient tree of the stack ever exists.
        count_f, the microbatch's token count, weights MoE's aux fold."""
    m = cfg.model
    eps = m.rms_norm_eps
    hd = m.head_dim
    moe = bool(m.num_experts)
    cos, sin = model_rope_tables(m)
    attn_fwd, attn_bwd = _attn_paths(cfg, ctx, cos, sin)
    # flatten by the tensor's OWN leading dims: under sequence parallelism
    # the residual stream is seq-sharded [B, S/tp, H] while the post-gather
    # q/k/v/out are full-sequence — reshaping those by x's dims would
    # silently fold tp x seq into the feature axis
    flat = lambda t: t.reshape(t.shape[0], t.shape[1], -1)  # noqa: E731

    def attn_bwd_flat(qf, kf, vf, outf, lse, doutf):
        r = lambda t: t.reshape(t.shape[0], t.shape[1], -1, hd)  # noqa: E731
        dq, dk, dv = attn_bwd(r(qf), r(kf), r(vf), r(outf), lse, r(doutf))
        return flat(dq), flat(dk), flat(dv)

    # optional leaves of the q/k/v segment: Qwen2's biases, OLMoE's QK-norm
    # weights (qkv_proj branches on their presence)
    qkv_opt_keys = [k for k in ("b_q", "b_k", "b_v", "q_norm", "k_norm")
                    if k in layers]

    moe_keys = (["router", "w_gate", "w_up", "w_down"] if moe
                else ["gate", "up", "down"])

    # Scopes (telemetry/scopes.py): the same names as the forward's in
    # models/llama.py, entered here because this engine calls the
    # building blocks below `_attention_block`. `attn_fwd` / `attn_bwd`
    # run OUTSIDE every scope: the flash kernels' events are named after
    # the innermost name-stack element at the call, and the benchmark's
    # `flash_roofline.train` finds them under the name the layer scan's
    # body gives them (tests/test_chip_compile.py).

    def fwd_body(x, lp):
        with scope("attention"):
            h1 = rms_norm(x, lp["input_norm"], eps)
            hf = ctx.f(h1)
            q, k, v = qkv_proj(hf, lp, hd, eps)
        out, lse = attn_fwd(q, k, v)
        with scope("attention"):
            outf = flat(out)
            a = x + ctx.g(outf @ lp["o"].astype(x.dtype))
        if moe:
            mo, aux = _moe_block(a, lp, m, ctx)
            y = a + mo
        else:
            y = a + _mlp_block(a, lp, m, ctx)
            aux = jnp.zeros(3, jnp.float32)
        return y, ((x, flat(q), flat(k), flat(v), outf, lse), aux)

    def forward(x0):
        xL, (saved, aux_layers) = lax.scan(fwd_body, x0, layers)
        return xL, saved, aux_layers

    def backward(saved, dxL, g_layers, count_f):
        def bwd_body(carry, xs):
            dy, gL = carry
            (x, qf, kf, vf, outf, lse), lp, idx = xs

            # MLP/MoE half: recompute a = x + o-proj (the dots_attn policy's
            # recompute set), derive the block's grads by segment VJP. For
            # MoE the routing recomputes deterministically and the aux-loss
            # fold (aux * count) rides the segment so balance/z grads flow.
            with scope("attention"):
                a = x + ctx.g(outf @ lp["o"].astype(x.dtype))

            if moe:
                def seg_mlp(a_, *ws):
                    lp2 = dict(lp)
                    lp2.update(zip(["post_norm"] + moe_keys, ws))
                    mo, aux2 = _moe_block(a_, lp2, m, ctx)
                    return a_ + mo, aux2[0] * count_f

                (_, fold_re), vjp_b = jax.vjp(
                    seg_mlp, a, lp["post_norm"], *[lp[k] for k in moe_keys])
                d_fold = _vary_like(jnp.ones((), jnp.float32), fold_re)
                da, d_post, *d_ws = vjp_b((dy, d_fold))
            else:
                def seg_mlp(a_, *ws):
                    lp2 = dict(lp)
                    lp2.update(zip(["post_norm"] + moe_keys, ws))
                    return a_ + _mlp_block(a_, lp2, m, ctx)

                _, vjp_b = jax.vjp(
                    seg_mlp, a, lp["post_norm"], *[lp[k] for k in moe_keys])
                da, d_post, *d_ws = vjp_b(dy)

            @scope("attention")
            def seg_o(x_, outf_, wo):
                return x_ + ctx.g(outf_ @ wo.astype(x_.dtype))

            _, vjp_o = jax.vjp(seg_o, x, outf, lp["o"])
            with scope("attention"):
                dx1, doutf, d_o = vjp_o(da)

            dqf, dkf, dvf = attn_bwd_flat(qf, kf, vf, outf, lse, doutf)

            @scope("attention")
            def seg_qkv(x_, w_in, wq, wk, wv, *bs):
                lpq = dict(lp)
                lpq.update(input_norm=w_in, q=wq, k=wk, v=wv,
                           **dict(zip(qkv_opt_keys, bs)))
                h1_ = rms_norm(x_, w_in, eps)
                hf_ = ctx.f(h1_)
                q_, k_, v_ = qkv_proj(hf_, lpq, hd, eps)
                return flat(q_), flat(k_), flat(v_)

            _, vjp_q = jax.vjp(seg_qkv, x, lp["input_norm"], lp["q"],
                               lp["k"], lp["v"],
                               *[lp[k] for k in qkv_opt_keys])
            with scope("attention"):
                dx2, d_in, d_q, d_k, d_v, *d_bs = vjp_q((dqf, dkf, dvf))

            gl = dict(input_norm=d_in, q=d_q, k=d_k, v=d_v, o=d_o,
                      post_norm=d_post,
                      **dict(zip(moe_keys, d_ws)),
                      **dict(zip(qkv_opt_keys, d_bs)))
            assert set(gl) == set(lp), (sorted(gl), sorted(lp))

            def acc(accl, g):
                cur = lax.dynamic_index_in_dim(accl, idx, 0, keepdims=False)
                return lax.dynamic_update_index_in_dim(
                    accl, cur + g.astype(accl.dtype), idx, 0)

            with scope("dw_accum"):
                gL = jax.tree.map(acc, gL, gl)
            return (dx1 + dx2, gL), None

        n_layers = jax.tree.leaves(layers)[0].shape[0]
        (dx0, g_layers), _ = lax.scan(
            bwd_body, (dxL, g_layers),
            (saved, layers, jnp.arange(n_layers)), reverse=True)
        return dx0, g_layers

    return forward, backward


def head_grads(xL, nonlayer, tgt, cfg: Config, ctx: ParallelCtx,
               weight=None):
    """The final norm, the head and the cross entropy of one microbatch,
    forward and backward in one place: (nll_sum, valid_count, dxL,
    g_nonlayer), where g_nonlayer is the gradient of `weight` x nll_sum in
    every leaf of `nonlayer` (the final norm and the head's matrix: the
    embedding's when tied). `weight` None is 1; the 1F1B tick passes 0 on a
    tick without a backward."""
    eps = cfg.model.rms_norm_eps

    @scope("head_ce")
    def head_fn(x, nl):
        xh = rms_norm(x, nl["final_norm"], eps)
        if ctx.head_ce is not None:
            total, count = ctx.head_ce(xh, head_weight(nl), tgt)
        else:
            from picotron_tpu.ops.losses import cross_entropy_sum_count

            logits = xh @ head_weight(nl).astype(xh.dtype)
            total, count = cross_entropy_sum_count(logits, tgt)
        return total, count

    (total, vjp_head, count) = jax.vjp(head_fn, xL, nonlayer, has_aux=True)
    cot = _vary_like(
        jnp.ones((), jnp.float32) if weight is None else weight, total)
    with scope("head_ce"):
        dxL, g_nonlayer = vjp_head(cot)
    return total, count, dxL, g_nonlayer


def fused_micro_grads(params, ids, tgt, g_acc, cfg: Config,
                      ctx: ParallelCtx):
    """One microbatch: returns (g_acc', nll_sum, valid_count, dropw) with
    grads accumulated into g_acc (layer leaves in-scan, non-layer leaves by
    one small add). Per-device semantics — runs inside the train step's
    shard_map body like the AD engine it replaces. Numerics match the AD
    engine: per-layer dW emerges in the bf16 param dtype from the same
    segment math before the fp32 accumulate. `dropw` is the token-weighted
    MoE capacity-drop sum (aux[1] * count, the loss_sum_count convention;
    0 for dense models)."""
    m = cfg.model
    forward, backward = layer_scans(cfg, ctx, params["layers"])

    # ---------------- forward ----------------
    with scope("embed"):
        x0, vjp_embed = jax.vjp(
            lambda e: (ctx.embed_lookup(e, ids)
                       if ctx.embed_lookup is not None
                       else e[ids]).astype(compute_dtype(m)),
            params["embedding"])

    xL, saved, aux_layers = forward(x0)
    aux_sum = jnp.sum(aux_layers, axis=0)  # [3]: (router loss, drops, load)

    # ---------------- head + CE ----------------
    nonlayer = {k: v for k, v in params.items() if k != "layers"}
    total, count, dxL, g_nonlayer = head_grads(xL, nonlayer, tgt, cfg, ctx)
    count_f = count.astype(jnp.float32)
    if m.num_experts:
        # the loss_sum_count fold: reported total = nll + (sum_l aux_l)*count
        # — the router-loss gradient flows per layer through the backward
        # scan's segment VJPs with cotangent 1.0 on the folded scalar.
        total = total + aux_sum[0] * count_f
        dropw = aux_sum[1:] * count_f
    else:
        dropw = total * jnp.zeros((2,), jnp.float32)

    # ---------------- backward layer scan ----------------
    dx0, g_layers = backward(saved, dxL, g_acc["layers"], count_f)

    # ---------------- embedding + non-layer accumulate ----------------
    with scope("embed"):
        (g_embed,) = vjp_embed(dx0)
    new_acc = {"layers": g_layers}
    with scope("head_ce"):  # with a tied head, the embedding's accumulation
        for k in g_acc:
            if k == "layers":
                continue
            g = g_nonlayer[k]
            if k == "embedding":
                g = g + g_embed if g is not None else g_embed
            new_acc[k] = g_acc[k] + g.astype(g_acc[k].dtype)
    return new_acc, total, count, dropw
