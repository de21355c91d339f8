"""The composed parallel train step — one SPMD program over the 5D mesh.

This is the TPU-native replacement for the reference's entire L4/L5 wiring
(apply_tensor_parallel -> PipelineParallel -> apply_context_parallel ->
DataParallelBucket -> train_step dispatch, ref: train.py:174-231):

- gradients: differentiate through `lax.pmean(loss, ('dp','cp'))` — the
  transpose machinery emits exactly the grad all-reduce over the fused cp_dp
  group that the reference implements with bucketed autograd hooks
  (ref: data_parallel.py:83, bucket.py:25-31). XLA's all-reduce combiner
  plays the role of the 25MB bucket manager, and its latency-hiding
  scheduler overlaps the reduction with remaining backward compute.
- the standard (on-device) optimizer update runs *outside* shard_map in
  plain GSPMD land, so optax transforms (incl. global-norm clipping) see
  global arrays and gradient-norm reductions span all shards
  automatically. Under `optimizer_offload` the update instead runs
  INSIDE the same shard_map body as the gradients (grads crossing the
  boundary as outputs cost a second full fp32 grad tree — PERF.md r4);
  there the hand-rolled streamed AdamW (optimizer.offload_adam_update)
  reproduces the optax math per shard, with an explicit per-leaf psum
  over each param's sharded axes for the global grad norm.
- one uniform code path for every (dp, pp, cp, tp) size — collectives over
  size-1 axes compile away, so there are no `if tp > 1` forks in the traced
  program (the reference dispatches between four wrapper stacks).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from picotron_tpu import compat
from picotron_tpu.config import (
    Config, resolved_cp_flavor, resolved_cp_mesh,
)
from picotron_tpu.mesh import MeshEnv
from picotron_tpu.models.llama import (
    ParallelCtx, init_params, loss_sum_count, pad_layers_for_pp,
)
from picotron_tpu.optimizer import (
    OffloadAdamState, global_grad_norm, make_optimizer,
    offload_adam_update,
)
from picotron_tpu.parallel.sharding import batch_spec, param_shardings, param_specs
from picotron_tpu.parallel.tp import (
    gather_logits,
    sp_gather_seq,
    sp_scatter_seq,
    tp_psum,
    vocab_parallel_ce_local_stats,
    vocab_parallel_ce_merge,
    vocab_parallel_ce_sum_count,
    vocab_parallel_embed,
)
from picotron_tpu.telemetry.scopes import scope
from picotron_tpu.train_step import TrainState, guard_nonfinite


def attention_path(cfg: Config) -> str:
    """What this config's attention is built from on the current backend —
    the fact the trainer's start-up line reports: 'pallas' (the compiled
    Pallas flash kernels, TPU only) or 'jnp' (the reference math).
    `attn_impl` 'auto' and the cp schedules take the kernels where they
    exist and the reference elsewhere (the CPU test meshes);
    'flash' names the kernels, so off-TPU it is an error rather than a
    quiet substitution."""
    from picotron_tpu.ops.flash_attention import compiled_kernels_available

    impl = cfg.model.attn_impl
    if impl == "reference":
        return "jnp"
    if compiled_kernels_available():
        return "pallas"
    if impl == "flash":
        raise ValueError(
            f"attn_impl='flash' asks for the compiled Pallas kernels, which "
            f"need the TPU backend; this process runs on "
            f"{jax.default_backend()!r}. Use attn_impl='auto' (kernels on "
            f"TPU, reference math elsewhere) or 'reference'.")
    return "jnp"


def make_parallel_ctx(cfg: Config) -> ParallelCtx:
    """Build the ParallelCtx used *inside* the shard_map body.

    Must be called under an active ('dp','pp','cp','tp') mesh context since
    positions use axis_index. Uniform across axis sizes: tp hooks and cp
    position arithmetic are identities when the axis has size 1.
    """
    d = cfg.distributed
    s_local = cfg.training.seq_length // d.cp_size
    idx = lax.axis_index("cp")
    if d.cp_size == 1:
        # contiguous 0..S-1 — encode as None (ParallelCtx's documented
        # meaning) so the flash kernels take the static-causal fast path
        # (program-id block classes + DMA-free skipped tiles; PERF.md r5)
        positions = None
    elif d.cp_layout == "zigzag":
        # Must mirror data.cp_sequence_permutation: shard r holds chunks
        # (r, 2cp-1-r) of 2cp chunks — its tokens' global positions.
        half = s_local // 2
        lo = idx * half
        hi = (2 * d.cp_size - 1 - idx) * half
        positions = jnp.concatenate([lo + jnp.arange(half),
                                     hi + jnp.arange(half)])
    else:
        positions = idx * s_local + jnp.arange(s_local)

    # Attention implementation dispatch (the reference routes via the
    # FLASH_ATTEN / CONTEXT_PARALLEL env vars, ref: model.py:148-158):
    # flash = the Pallas kernel on TPU (jnp twin elsewhere), reference = the
    # plain jnp softmax path, ring = require context parallelism.
    if cfg.model.attn_impl in ("ring", "ulysses", "mesh") and d.cp_size == 1:
        raise ValueError(
            f"attn_impl={cfg.model.attn_impl!r} requires cp_size > 1 (it is "
            "a context-parallel schedule; ref: context_parallel.py:10-12)"
        )
    attention_path(cfg)  # refuses attn_impl='flash' off-TPU
    use_flash = cfg.model.attn_impl in ("auto", "flash", "ring", "ulysses",
                                        "mesh")
    if use_flash:
        from picotron_tpu.ops.flash_attention import flash_attention as attn_fn
    else:
        from picotron_tpu.ops.attention import sdpa_attention as attn_fn

    cp_flavor = resolved_cp_flavor(cfg)
    if d.cp_size > 1 and cp_flavor == "ulysses":
        from picotron_tpu.ops.ulysses import (
            ulysses_attention, ulysses_static_layout,
        )

        # the gathered sequence's global positions are exactly the
        # dataloader's layout permutation (arange when contiguous) — known
        # at trace time, so no runtime position all_gather is needed, and a
        # static argsort restores a monotone sequence so the kernel's
        # causal fast paths fire. Derived by ulysses_static_layout — the
        # same source the fused grad engine's backward uses, so the two
        # sides cannot disagree about the gathered order.
        full_pos, seq_sort = ulysses_static_layout(cfg)

        def attn(q, k, v, pos, rope):
            # one all_to_all pair trades the seq shard for a head shard;
            # the flash kernel (fused RoPE, position-masked causal) then
            # runs full-sequence on this device's head subset (ops/ulysses)
            return ulysses_attention(q, k, v, axis="cp", q_positions=pos,
                                     attn_fn=attn_fn, rope=rope,
                                     seq_sort=seq_sort,
                                     full_positions=full_pos,
                                     # full_pos is built from the config
                                     # right here — a trace-time constant
                                     positions_static=True)
    elif d.cp_size > 1 and cp_flavor == "mesh":
        from picotron_tpu.ops.mesh_attention import mesh_attention
        from picotron_tpu.ops.rope import apply_rope

        cp_mesh = resolved_cp_mesh(cfg)
        blockwise = partial(attn_fn, return_lse=True)

        def attn(q, k, v, pos, rope):
            # same pre-rotation contract as the ring (rotation commutes
            # with the head split, so positions stay single-sourced here);
            # the 2D schedule factors cp into a cp_y head scatter and a
            # cp_x row ring (ops/mesh_attention.py)
            q = apply_rope(q, *rope, pos)
            k = apply_rope(k, *rope, pos)
            return mesh_attention(q, k, v, axis="cp", cp_mesh=cp_mesh,
                                  q_positions=pos, attn_block=blockwise)
    elif d.cp_size > 1:
        from picotron_tpu.ops.ring_attention import ring_attention
        from picotron_tpu.ops.rope import apply_rope

        blockwise = partial(attn_fn, return_lse=True)

        def attn(q, k, v, pos, rope):
            # positions are single-sourced here: RoPE and the ring's causal
            # masking must see the same sequence layout (zigzag ordering
            # changes `positions` in exactly one place). K/V are rotated
            # BEFORE entering the ring so each block travels pre-rotated
            # with its positions (ref: context_parallel.py:189-195).
            q = apply_rope(q, *rope, pos)
            k = apply_rope(k, *rope, pos)
            return ring_attention(q, k, v, axis="cp", q_positions=pos,
                                  attn_block=blockwise)
    elif use_flash:

        def attn(q, k, v, pos, rope):
            # RoPE fused into the Pallas kernels (rotation + un-rotation in
            # VMEM) — XLA's rotate-half concat/slice chain profiled at ~7%
            # of a train step.
            return attn_fn(q, k, v, causal=True, rope=rope,
                           q_positions=pos, kv_positions=pos)
    else:
        from picotron_tpu.ops.rope import apply_rope

        def attn(q, k, v, pos, rope):
            q = apply_rope(q, *rope, pos)
            k = apply_rope(k, *rope, pos)
            return attn_fn(q, k, v, causal=True,
                           q_positions=pos, kv_positions=pos)

    ce_chunk = cfg.training.ce_chunk_size
    ce = partial(vocab_parallel_ce_sum_count, axis="tp", chunk_size=ce_chunk)
    hooks = dict(
        g=tp_psum,
        embed_lookup=partial(vocab_parallel_embed, axis="tp"),
        head_ce=ce,
        # the split form lets the PP engines run the head matmul only on
        # the last stage (collective-free branch + tiny uniform merge)
        head_ce_local=partial(vocab_parallel_ce_local_stats, axis="tp",
                              chunk_size=ce_chunk),
        head_ce_merge=partial(vocab_parallel_ce_merge, axis="tp"),
    )
    if d.sequence_parallel:
        # Megatron-SP (parallel/tp.py): residual stream seq-sharded over tp,
        # f/g become all_gather / reduce-scatter. head_ce and the eval logits
        # path re-gather the sequence before the head matmul (a seq-sharded
        # hidden against a vocab-sharded head would yield diagonal blocks of
        # the logits, which cannot be assembled).
        hooks = dict(
            f=sp_gather_seq,
            g=sp_scatter_seq,
            embed_lookup=partial(vocab_parallel_embed, axis="tp",
                                 scatter_seq=True),
            head_ce=lambda x, head, tgt: ce(sp_gather_seq(x), head, tgt),
            seq_shard=d.tp_size,
            # all tp ranks compute the same aux from the gathered tokens;
            # pmean re-marks it tp-invariant for the loss fold
            moe_aux_sync=lambda a: lax.pmean(a, "tp"),
        )

    # Uneven-PP padding: mask the aux statistics of pad slots from the
    # STATIC placement rule (pp_layer_placement puts each stage's real
    # layers in its leading slots; remainder to early stages) rather than
    # sniffing router weights (ADVICE r3).
    L, pp = cfg.model.num_hidden_layers, d.pp_size
    layer_is_real = None
    if pp > 1 and L % pp != 0:
        def layer_is_real(n_slots):
            cnt = L // pp + (lax.axis_index("pp") < L % pp).astype(jnp.int32)
            return (jnp.arange(n_slots) < cnt).astype(jnp.float32)

    return ParallelCtx(
        attn=attn,
        gather_logits=partial(gather_logits, axis="tp"),
        positions=positions,
        layer_is_real=layer_is_real,
        moe_ep_axis="ep",
        # layout-exact router statistics: pmean f/P/z over the data axes so
        # the aux losses describe the global batch (config.router_aux_global)
        moe_stat_axes=(("dp", "ep", "cp")
                       if cfg.model.router_aux_global else None),
        remat=cfg.training.remat,
        remat_policy=cfg.training.remat_policy,
        **hooks,
    )


def _data_axes_psum(grads, cfg: Config):
    """Sum grads over the data axes. 'ep' is a data axis for every param
    EXCEPT the expert banks sharded over it — their per-device grads already
    integrate every peer's tokens via the dispatch all_to_all, so an ep psum
    would multiply them by ep_size.

    This is the one seam BOTH grad engines exit through (the AD and fused
    paths below, and the pp scan path)."""
    specs = param_specs(cfg)

    def red(g, spec):
        flat = [a for part in spec if part is not None
                for a in (part if isinstance(part, (tuple, list)) else (part,))]
        axes = ("dp", "cp") if "ep" in flat else ("dp", "ep", "cp")
        return lax.psum(g, axes)

    return jax.tree.map(red, grads, specs, is_leaf=lambda x: isinstance(x, P))


def _normalize_extras(dropw, count, cfg: Config) -> dict:
    """Turn the token-weighted observability sums [2] into per-layer means:
    dropw accumulates sum_micro(count_micro * sum_layers(stat)) for the
    capacity drop fraction and the busiest expert's load over the mean, so
    dividing by count_total * L gives the token-weighted mean per-layer
    value of each. Empty for dense models (no silent dict keys)."""
    if not cfg.model.num_experts:
        return {}
    mean = dropw / (count * cfg.model.num_hidden_layers)
    return {"moe_drop_frac": mean[0], "moe_load_max_over_mean": mean[1]}


def _device_grads(params, batch, cfg: Config):
    """Per-device grad computation: scan microbatches accumulating fp32
    NLL-sum grads and valid-token counts (ref: train.py:29-55 loop +
    require_backward_grad_sync gating), then one psum over the data axes and
    a single division — a per-shard token mean followed by an unweighted
    pmean would mis-weight shards whose IGNORE_INDEX counts differ.

    Returns (grads, loss, extras) — extras is a dict of normalized
    observability scalars ({"moe_drop_frac", "moe_load_max_over_mean"} for
    MoE runs, {} otherwise) that the step surfaces in its metrics."""
    from picotron_tpu.parallel.pp import _vary_over

    ctx = make_parallel_ctx(cfg)
    ids, tgt = batch  # [n_micro, mbs_local, s_local]
    # Differentiate w.r.t. params that VARY over every axis whose reduction
    # is written out below (the data axes in _data_axes_psum, 'pp' in
    # sync_pp_replicated_grads). AD of an axis-INVARIANT param ends in the
    # pvary-transpose psum, so each microbatch's grads would arrive already
    # summed over those axes — an all-reduce per microbatch — and the
    # explicit psum would then count them axis-size times (measured on JAX
    # 0.9.0: every grad exactly dp x, cp x, and the pp-replicated leaves
    # pp x, the single-device gradient; Adam's scale invariance hid it from
    # the loss-trajectory parity tests). Varying params give per-device
    # partials, reduced once per step. Values are unchanged — pcast only
    # retypes.
    reduced_axes = {"dp", "ep", "cp"}
    if cfg.distributed.pp_size > 1:
        reduced_axes.add("pp")
    params = jax.tree.map(lambda p: _vary_over(p, reduced_axes), params)

    if cfg.distributed.pp_size > 1:
        # The pipeline scan subsumes the microbatch loop: grad accumulation
        # across microbatches IS the schedule (ref: train.py:225-227
        # dispatches to the pipeline engines the same way).
        from picotron_tpu.parallel.pp import (
            pipeline_1f1b_grads, pipeline_loss_sum_count,
            sync_pp_replicated_grads,
        )

        if cfg.distributed.pp_engine == "1f1b":
            # Manual-VJP schedule: grads come out of the scan directly.
            grads, nll_total, count, dropw = pipeline_1f1b_grads(
                params, ids, tgt, cfg, ctx)
        else:  # "afab": differentiate through the forward scan

            def pp_nll(params):
                total, count, dropw = pipeline_loss_sum_count(
                    params, ids, tgt, cfg, ctx)
                return total, (count, dropw)

            (nll_total, (count, dropw)), grads = jax.value_and_grad(
                pp_nll, has_aux=True)(params)
        grads = sync_pp_replicated_grads(grads, param_specs(cfg))
        grads = _data_axes_psum(grads, cfg)
        nll_total = lax.psum(nll_total, ("dp", "ep", "cp"))
        dropw = lax.psum(dropw, ("dp", "ep", "cp"))
        count = jnp.maximum(lax.psum(count, ("dp", "ep", "cp")), 1)
        return _finish_grads(grads, nll_total, count, dropw, cfg)

    from picotron_tpu.parallel.fused_bwd import (
        fused_micro_grads, resolved_grad_engine,
    )

    use_fused = resolved_grad_engine(cfg) == "fused"

    def nll_sum(params, mb_ids, mb_tgt):
        total, count, extras = loss_sum_count(params, mb_ids, mb_tgt,
                                              cfg.model, ctx)
        return total, (count, extras.get("moe_obs_weighted",
                                         jnp.zeros((2,), jnp.float32)))

    def micro_step(carry, mb):
        g_acc, l_acc, c_acc, d_acc = carry
        mb_ids, mb_tgt = mb
        if use_fused:
            # manual backward layer scan accumulating dW in-scan: no
            # per-microbatch grad tree, no whole-tree adds (fused_bwd.py)
            g_acc, total, count, dropw = fused_micro_grads(
                params, mb_ids, mb_tgt, g_acc, cfg, ctx)
            return (g_acc, l_acc + total, c_acc + count,
                    d_acc + dropw), None
        (total, (count, dropw)), grads = jax.value_and_grad(
            nll_sum, has_aux=True)(params, mb_ids, mb_tgt)
        return (jax.tree.map(jnp.add, g_acc, grads), l_acc + total,
                c_acc + count, d_acc + dropw), None

    d = cfg.distributed
    if ids.shape[0] == 1 and not use_fused:
        # Single-microbatch fast path: differentiate directly — the
        # accumulation scan's fp32 zeros carry + per-microbatch grad temp
        # would hold TWO full grad trees for zero numerical effect
        # (add(0.0f32, bf16 g) is an exact promotion). At MoE scale the
        # double tree is the difference between fitting and OOM: the
        # Mixtral-8x7B single-chip row needs this path (PERF.md r5).
        # (An explicit grad_engine='fused' still takes the scan path —
        # silently swapping engines under the user would invalidate any
        # ga=1 A/B measurement; code review r5.)
        (nll_total, (count, dropw)), grads = jax.value_and_grad(
            nll_sum, has_aux=True)(params, ids[0], tgt[0])
        if (not cfg.training.optimizer_offload
                or d.dp_size * d.ep_size * d.cp_size > 1):
            # fp32 BEFORE the data-axes psum: under offload the bf16
            # params yield bf16 grads, and a multi-shard all-reduce in
            # bf16 would drop exactly the low bits the fp32 master keeps
            # (the accumulation path promotes via its fp32 carry; code
            # review r5). Single-shard offload keeps the bf16 tree — the
            # psum is an identity there and the streamed update casts
            # per slice, which is what lets Mixtral-1L fit.
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32), grads)
    else:
        # The accumulators vary inside the scan exactly as the (varying)
        # params they are grads of do, so the initial carry must carry the
        # same varying type.
        # fp32 accumulation regardless of the param dtype: with
        # optimizer_offload the params (hence per-microbatch grads) are
        # bf16; summing grad-acc microbatches in bf16 would lose exactly
        # the low bits the fp32 master exists to keep (jnp.add promotes
        # bf16 + fp32 -> fp32).
        zeros = jax.tree.map(
            lambda p: _vary_over(jnp.zeros(p.shape, jnp.float32),
                                 set(compat.vma(p))),
            params)
        init_carry = (zeros,) + compat.pcast(
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
             jnp.zeros((2,), jnp.float32)),
            ("dp", "ep", "cp"), to="varying")
        (grads, nll_total, count, dropw), _ = lax.scan(
            micro_step, init_carry, (ids, tgt))
    # gradient + loss sync over the fused data axes (the reference's cp_dp
    # group semantics: ref process_group_manager.py:22, utils.py:93-98)
    grads = _data_axes_psum(grads, cfg)
    nll_total = lax.psum(nll_total, ("dp", "ep", "cp"))
    dropw = lax.psum(dropw, ("dp", "ep", "cp"))
    count = jnp.maximum(lax.psum(count, ("dp", "ep", "cp")), 1)
    return _finish_grads(grads, nll_total, count, dropw, cfg)


def _finish_grads(grads, nll_total, count, dropw, cfg: Config):
    """Final token-mean normalization. Under optimizer_offload the grads are
    returned UN-divided with the 1/count scale riding in extras: the
    elementwise division would materialize a second 6.75 GB fp32 grad tree
    (it cannot fuse across the while-loop boundary into the streamed update
    scan) — measured as ~6 GB of "fragmentation" that OOMed full-depth
    SmolLM-1.7B. offload_adam_update folds the scale into its slice math
    instead."""
    extras = _normalize_extras(dropw, count, cfg)
    if cfg.training.optimizer_offload:
        extras["_grad_scale"] = 1.0 / count.astype(jnp.float32)
        return grads, nll_total / count, extras
    return (jax.tree.map(lambda g: g / count, grads), nll_total / count,
            extras)


def make_train_step(cfg: Config, menv: MeshEnv, inject_nan: bool = False):
    """Build the jitted (TrainState, batch) -> (TrainState, metrics) step
    over the mesh. batch = (input_ids, targets), each
    [n_micro, global_b, seq] sharded P(None, ('dp', 'ep'), 'cp').

    metrics is a dict with at least {"loss"}; MoE runs additionally carry
    {"moe_drop_frac"} (the capacity-drop observability scalar — VERDICT r2
    weak #4: drops used to be silent in training logs). With
    resilience.guard_policy != "off" it also carries {"grad_norm",
    "nonfinite"} — the divergence guard's inputs — and under policy
    "skip" a non-finite loss/grad step keeps params and optimizer state
    unchanged (train_step.guard_nonfinite; the step counter still
    advances).

    `inject_nan=True` poisons every step's gradients and loss — the
    chaos harness's nan_grad event (the driver routes only the injected
    steps through this variant). Injection must live inside the compiled
    step: it is the only way the in-jit skip path sees a genuinely
    non-finite gradient tree."""
    cfg.validate()
    if cfg.pipeline.executor == "mpmd":
        # Per-stage programs + host-side schedule (parallel/mpmd.py) —
        # same (state, batch) -> (state, metrics) contract, so callers
        # (train.py, chaos harness) never see the executor swap. Lazy
        # import: mpmd.py imports this module at its top level.
        from picotron_tpu.parallel.mpmd import make_mpmd_train_step

        return make_mpmd_train_step(cfg, menv, inject_nan=inject_nan)
    mesh = menv.mesh
    pspecs = param_specs(cfg)
    bspec = batch_spec()
    guards_on = cfg.resilience.guard_policy != "off"
    guard_skip = cfg.resilience.guard_policy == "skip"

    def _poison(grads, loss):
        nan = jnp.float32(jnp.nan)
        grads = jax.tree.map(lambda g: g + nan.astype(g.dtype), grads)
        return grads, loss + nan

    grad_fn = compat.shard_map(
        partial(_device_grads, cfg=cfg),
        mesh=mesh,
        in_specs=(pspecs, (bspec, bspec)),
        out_specs=(pspecs, P(), P()),  # P() prefixes the extras dict
    )

    if cfg.training.optimizer_offload:
        from picotron_tpu.models.llama import compute_dtype

        cdt = compute_dtype(cfg.model)
        transfer = offload_memory_kind(mesh) is not None

        # The update runs INSIDE the shard_map body, fused with the grad
        # computation: grads crossing the shard_map boundary as outputs
        # cost a SECOND full fp32 grad tree (the grad-accumulation while
        # carry cannot alias a boundary output — measured 6-7 GB of pure
        # waste at SmolLM-1.7B scale). Inside, every leaf is this device's
        # local shard and the host<->device moves are memory-space-only
        # transfers, so the same body is correct on any mesh (each process
        # streams exactly its own host-resident state shards).
        # ZeRO-1 composition (VERDICT r4 #3): the host master/moments
        # shard over the fused data axes; each process streams 1/dp of
        # the state and the update all-gathers the refreshed bf16 params
        # over dp at the end.
        z1_info = None
        mspecs = pspecs
        if cfg.distributed.zero1:
            abs_master = abstract_master(cfg)
            z1_info = offload_zero1_info(cfg, abs_master)
            sizes = _zero1_sizes(cfg)
            mspecs = jax.tree.map(
                lambda s, a: _zero1_spec(s, a.shape, sizes),
                pspecs, abs_master, is_leaf=lambda x: isinstance(x, P))

        def _device_step(params, batch, opt_state):
            grads, loss, extras = _device_grads(params, batch, cfg)
            if inject_nan:
                grads, loss = _poison(grads, loss)
            grad_scale = extras.pop("_grad_scale")
            if guards_on:
                # same observable the on-device path reports (optax
                # global_norm below): the norm of the token-mean gradient
                extras["grad_norm"] = (
                    global_grad_norm(grads, pspecs) * grad_scale)
            new_params, new_opt = offload_adam_update(
                grads, opt_state, cfg.training, cdt, transfer=transfer,
                clip_specs=pspecs, grad_scale=grad_scale,
                zero1_info=z1_info)
            return new_params, new_opt, loss, extras

        opt_specs = OffloadAdamState(count=P(), master=mspecs, mu=mspecs,
                                     nu=mspecs)
        # Under zero1 the refreshed bf16 params leave the shard_map still
        # sharded over the zero1 axes (out spec = mspecs); the GSPMD
        # constraint below re-gathers them to the full param layout — the
        # ZeRO-1 update all-gather, expressed as a resharding.
        fused = compat.shard_map(
            _device_step, mesh=mesh,
            in_specs=(pspecs, (bspec, bspec), opt_specs),
            out_specs=(mspecs, opt_specs, P(), P()))
        full_shardings = param_shardings(cfg, mesh)

        @partial(jax.jit, donate_argnums=(0,))
        def train_step(state: TrainState, batch):
            new_params, new_opt, loss, extras = fused(
                state.params, batch, state.opt_state)
            if cfg.distributed.zero1:
                new_params = jax.lax.with_sharding_constraint(
                    new_params, full_shardings)
            metrics = {"loss": loss, **extras}
            if guards_on:
                # Offload guards key on the (already psum'd) loss only:
                # 'skip' is rejected for offload at config time, and
                # rollback/abort both trigger off the loss.
                metrics["nonfinite"] = (
                    1.0 - jnp.isfinite(loss).astype(jnp.float32))
            return TrainState(new_params, new_opt, state.step + 1), metrics

        return train_step

    opt = make_optimizer(cfg.training)

    # The function's name is the program's module name (`jit_train_step`):
    # a device trace lists each executed step under it on the `XLA
    # Modules` line. Pinned by tests/test_scopes.py.
    @partial(jax.jit, donate_argnums=(0,))
    def train_step(state: TrainState, batch):
        grads, loss, extras = grad_fn(state.params, batch)
        if inject_nan:
            grads, loss = _poison(grads, loss)
        if guards_on:
            # One global norm covers the whole tree: any NaN/Inf leaf
            # poisons it, so non-finite detection is a single scalar
            # check instead of a per-leaf isfinite sweep. Surfaced as a
            # metric either way — grad-norm curves are standard
            # divergence forensics.
            gnorm = optax.global_norm(grads)
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            extras = {**extras, "grad_norm": gnorm,
                      "nonfinite": 1.0 - ok.astype(jnp.float32)}
        with scope("optimizer"):
            updates, opt_state = opt.update(grads, state.opt_state,
                                            state.params)
            new_params = optax.apply_updates(state.params, updates)
            if guards_on and guard_skip:
                new_params = guard_nonfinite(ok, new_params, state.params)
                opt_state = guard_nonfinite(ok, opt_state, state.opt_state)
        metrics = {"loss": loss, **extras}
        return TrainState(new_params, opt_state, state.step + 1), metrics

    return train_step


def make_eval_step(cfg: Config, menv: MeshEnv):
    """Jitted forward-only (params, batch) -> loss over the mesh — the
    validation half of the train step: same sharded loss computation
    (pipeline engines included, via the AFAB loss path), no grads, no
    optimizer, no donation (params are reused across eval batches)."""
    cfg.validate()
    pspecs = param_specs(cfg)
    bspec = batch_spec()

    def _device_loss(params, batch):
        ctx = make_parallel_ctx(cfg)
        ids, tgt = batch
        if cfg.distributed.pp_size > 1:
            from picotron_tpu.parallel.pp import pipeline_loss_sum_count

            total, count, _ = pipeline_loss_sum_count(params, ids, tgt,
                                                      cfg, ctx)
        else:
            def body(carry, mb):
                l_acc, c_acc = carry
                total, count, _ = loss_sum_count(params, mb[0], mb[1],
                                                 cfg.model, ctx)
                return (l_acc + total, c_acc + count), None

            init = compat.pcast(
                (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
                ("dp", "ep", "cp"), to="varying")
            (total, count), _ = lax.scan(body, init, (ids, tgt))
        total = lax.psum(total, ("dp", "ep", "cp"))
        count = jnp.maximum(lax.psum(count, ("dp", "ep", "cp")), 1)
        return total / count

    loss_fn_sharded = compat.shard_map(
        _device_loss, mesh=menv.mesh,
        in_specs=(pspecs, (bspec, bspec)), out_specs=P())
    return jax.jit(loss_fn_sharded)


def init_sharded_state(cfg: Config, menv: MeshEnv, key: jax.Array,
                       abstract: bool = False) -> TrainState:
    """Initialize params directly into their mesh shardings (each device
    materializes only its shard — the role of the reference's meta-device
    init + per-rank materialization, ref: checkpoint.py:15-102, minus the
    safetensors shape-template dance).

    `abstract=True` returns sharding-annotated ShapeDtypeStructs instead of
    real arrays — zero memory, same shardings — for AOT uses like
    tools/memcheck.py's compile-only analysis (materializing a 7B model's
    fp32 master + moments just to call .lower() would need ~84 GB of host
    RAM)."""
    cfg.validate()
    mesh = menv.mesh
    shardings = param_shardings(cfg, mesh)

    def init(key):
        # Pad the layer stack for uneven PP splits (identity zero-layers);
        # real layers keep exactly the single-device init values.
        return pad_layers_for_pp(init_params(cfg.model, key),
                                 cfg.model.num_hidden_layers,
                                 cfg.distributed.pp_size)

    if cfg.training.optimizer_offload:
        return _init_offload_state(cfg, menv, key, init, shardings, abstract)

    if abstract:
        params = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            jax.eval_shape(init, key), shardings)
    else:
        params = jax.jit(init, out_shardings=shardings)(key)
    opt = make_optimizer(cfg.training)
    # Optimizer moments must mirror the param shardings (Adam mu/nu live
    # wherever their param lives — the reference gets this implicitly from
    # per-rank optimizer instances, ref: train.py:209); scalar counters are
    # replicated. Without explicit out_shardings, jit can leave the whole
    # opt state on one device, which breaks the first step after a
    # checkpoint restore. Moment subtrees are recognized structurally (any
    # opt-state subtree with the params' treedef takes the params'
    # shardings leaf-for-leaf) — matching by leaf shape would collide for
    # same-shape/different-spec params like q [h, h] and o [h, h].
    replicated = NamedSharding(mesh, P())
    params_treedef = jax.tree.structure(params)
    param_leaf_shardings = [p.sharding for p in jax.tree.leaves(params)]

    if cfg.distributed.zero1:
        # ZeRO-1 (beyond the reference; SURVEY §2.2 marks ZeRO absent): the
        # Adam moments additionally shard over the data axes — GSPMD then
        # partitions the elementwise optimizer update per shard and inserts
        # the update all-gather, i.e. the ZeRO-1 schedule falls out of a
        # sharding annotation instead of a hand-written partitioner.
        sizes = _zero1_sizes(cfg)
        param_leaf_shardings = [
            NamedSharding(mesh, _zero1_spec(s.spec, p.shape, sizes))
            for p, s in zip(jax.tree.leaves(params), param_leaf_shardings)]

    def opt_subtree_shardings(subtree):
        if jax.tree.structure(subtree) == params_treedef:
            return jax.tree.unflatten(params_treedef, param_leaf_shardings)
        return jax.tree.map(lambda _: replicated, subtree)

    abstract_opt = jax.eval_shape(opt.init, params)
    opt_shardings = jax.tree.map(
        opt_subtree_shardings, abstract_opt,
        is_leaf=lambda x: jax.tree.structure(x) == params_treedef)
    if abstract:
        opt_state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract_opt, opt_shardings)
        step0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)
    else:
        opt_state = jax.jit(opt.init, out_shardings=opt_shardings)(params)
        step0 = jax.device_put(jnp.zeros((), jnp.int32), replicated)
    return TrainState(params=params, opt_state=opt_state, step=step0)


def offload_memory_kind(mesh) -> str | None:
    """'pinned_host' on TPU, None elsewhere. On the CPU backend "device"
    memory IS host RAM, and XLA:CPU's pinned_host plumbing cannot round-trip
    donated buffers through jit outputs — so the simulated-mesh tests run
    the offload code path placement-free (same math, same state layout)
    while real chips get genuine host placement."""
    return ("pinned_host"
            if mesh.devices.flat[0].platform == "tpu" else None)


def _init_offload_state(cfg: Config, menv: MeshEnv, key, init,
                        dev_shardings, abstract: bool) -> TrainState:
    """optimizer_offload state layout: fp32 master + Adam moments in pinned
    host memory (sharded exactly like their params), bf16 compute copy + an
    int32 step counter on device. See OffloadAdamState."""
    from picotron_tpu.models.llama import compute_dtype

    mesh = menv.mesh
    abs_master = abstract_master(cfg)
    host_shardings = _offload_host_shardings(cfg, mesh, abs_master)
    cdt = compute_dtype(cfg.model)
    mdt = (jnp.bfloat16 if cfg.training.adam_moments_dtype == "bfloat16"
           else jnp.float32)
    replicated = NamedSharding(mesh, P())

    if abstract:
        sds = lambda a, dt, s: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, dt, sharding=s)
        master = jax.tree.map(lambda a, s: sds(a, a.dtype, s),
                              abs_master, host_shardings)
        params = jax.tree.map(lambda a, s: sds(a, cdt, s),
                              abs_master, dev_shardings)
        mu = jax.tree.map(lambda a, s: sds(a, mdt, s),
                          abs_master, host_shardings)
        nu = jax.tree.map(lambda a, s: sds(a, mdt, s),
                          abs_master, host_shardings)
        count = jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)
        step0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)
    else:
        # Stage through device shardings and device_put to host OUTSIDE jit:
        # XLA's SPMD partitioner rejects host-memory-kind out_shardings on a
        # multi-device mesh ("side-effect HLO must have sharding"), while
        # plain device_put transfers (and device_put inside jit, which the
        # train step uses) partition fine.
        master_dev = jax.jit(init, out_shardings=dev_shardings)(key)
        params = jax.jit(
            lambda mp: jax.tree.map(lambda x: x.astype(cdt), mp),
            out_shardings=dev_shardings)(master_dev)
        master = jax.device_put(master_dev, host_shardings)
        zeros = jax.jit(
            lambda: jax.tree.map(lambda a: jnp.zeros(a.shape, mdt),
                                 abs_master),
            out_shardings=dev_shardings)
        mu = jax.device_put(zeros(), host_shardings)
        nu = jax.device_put(zeros(), host_shardings)
        count = jax.device_put(jnp.zeros((), jnp.int32), replicated)
        step0 = jax.device_put(jnp.zeros((), jnp.int32), replicated)
    opt_state = OffloadAdamState(count=count, master=master, mu=mu, nu=nu)
    return TrainState(params=params, opt_state=opt_state, step=step0)


def install_params(cfg: Config, menv: MeshEnv, state: TrainState,
                   params) -> TrainState:
    """Install externally produced fp32 params (HF import, params-only
    restore) into `state`, respecting the optimizer-state layout: under
    optimizer_offload they become the pinned-host master AND the bf16
    device compute copy; otherwise they simply replace state.params."""
    from picotron_tpu.models.llama import compute_dtype

    if not cfg.training.optimizer_offload:
        shardings = param_shardings(cfg, menv.mesh)
        return state._replace(
            params=jax.tree.map(jax.device_put, params, shardings))
    dev_shardings = param_shardings(cfg, menv.mesh)
    host_shardings = _offload_host_shardings(
        cfg, menv.mesh, jax.eval_shape(lambda t: t, params))
    master = jax.tree.map(
        lambda p, s: jax.device_put(jnp.asarray(p, jnp.float32), s),
        params, host_shardings)
    compute = jax.jit(
        lambda mp: jax.tree.map(
            lambda x: x.astype(compute_dtype(cfg.model)), mp),
        out_shardings=dev_shardings)(master)
    return state._replace(params=compute,
                          opt_state=state.opt_state._replace(master=master))


def _zero1_placement(spec: P, shape, data_axis_sizes: dict):
    """(dim, axes) of the ZeRO-1 shard extension for this leaf, or None
    when none qualifies: the first unsharded dimension divisible by the
    product of the applicable fused data axes ('dp','ep'). Axes the param
    already shards over (the ep of expert banks) are excluded, matching
    _data_axes_psum's view of which axes are data axes per leaf."""
    used = {a for part in spec if part is not None
            for a in (part if isinstance(part, (tuple, list)) else (part,))}
    axes = tuple(a for a in ("dp", "ep")
                 if data_axis_sizes.get(a, 1) > 1 and a not in used)
    if not axes:
        return None
    factor = 1
    for a in axes:
        factor *= data_axis_sizes[a]
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (entry, dim) in enumerate(zip(entries, shape)):
        if entry is None and dim % factor == 0:
            return i, axes
    return None


def _zero1_spec(spec: P, shape, data_axis_sizes: dict) -> P:
    """Extend a param's PartitionSpec per `_zero1_placement` (identity when
    no dimension qualifies — tiny tensors just stay replicated)."""
    place = _zero1_placement(spec, shape, data_axis_sizes)
    if place is None:
        return spec
    dim, axes = place
    entries = list(spec) + [None] * (len(shape) - len(spec))
    entries[dim] = axes if len(axes) > 1 else axes[0]
    return P(*entries)


def _zero1_sizes(cfg: Config) -> dict:
    return {"dp": cfg.distributed.dp_size, "ep": cfg.distributed.ep_size}


def abstract_master(cfg: Config):
    """ShapeDtypeStructs of the fp32 master param pytree — the single
    source of the param tree structure wherever specs must align with the
    real state leaf-for-leaf (zero1 placements, host shardings,
    checkpoint templates). Every consumer derives from here so the init
    expression cannot silently diverge between sites (code review r5)."""
    return jax.eval_shape(lambda: pad_layers_for_pp(
        init_params(cfg.model, jax.random.key(0)),
        cfg.model.num_hidden_layers, cfg.distributed.pp_size))


def offload_zero1_info(cfg: Config, abs_master) -> list | None:
    """Flattened-leaf-aligned list of (dim, axes, axis_sizes) ZeRO-1
    placements (None per leaf when unsharded) for the offload x zero1
    composition, or None when zero1 is off. Static — consumed at trace
    time by optimizer.offload_adam_update for the grad slice / param
    all-gather."""
    if not cfg.distributed.zero1:
        return None
    sizes = _zero1_sizes(cfg)
    specs = param_specs(cfg)
    s_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    a_leaves = jax.tree.leaves(abs_master)
    out = []
    for s, a in zip(s_leaves, a_leaves):
        place = _zero1_placement(s, a.shape, sizes)
        out.append(None if place is None else
                   (place[0], place[1],
                    tuple(sizes[ax] for ax in place[1])))
    return out


def _offload_host_shardings(cfg: Config, mesh, abs_master):
    """Host-memory shardings for the offload master/moments. Under zero1
    they additionally shard over the fused data axes (VERDICT r4 #3 —
    each process keeps and streams only 1/dp of the host state; the
    update all-gathers the refreshed bf16 params over dp afterwards)."""
    kind = offload_memory_kind(mesh)
    if not cfg.distributed.zero1:
        return param_shardings(cfg, mesh, memory_kind=kind)
    kw = {} if kind is None else {"memory_kind": kind}
    sizes = _zero1_sizes(cfg)
    return jax.tree.map(
        lambda spec, a: NamedSharding(
            mesh, _zero1_spec(spec, a.shape, sizes), **kw),
        param_specs(cfg), abs_master,
        is_leaf=lambda x: isinstance(x, P))
