"""Pipeline parallelism: microbatch pipelining over the 'pp' mesh axis.

TPU-native equivalent of the reference's pipeline stack
(ref: picotron/pipeline_parallel/pipeline_parallel.py +
pp_communications.py). The mapping:

- **Stage slicing** — the reference keeps a contiguous block of decoder
  layers per stage, embedding on the first stage, norm+head on the last
  (ref: pipeline_parallel.py:13-51). Here the stacked layer pytree is
  *sharded* over 'pp' on its leading layer axis (parallel/sharding.py), so
  inside shard_map each device's `params['layers']` IS its stage slice.
- **Activation transport** — the reference's batched isend/irecv pairs with
  hard cuda synchronization and `CUDA_DEVICE_MAX_CONNECTIONS=1` ordering
  (ref: pp_communications.py:8-46, base_job.slurm:53) become one
  `lax.ppermute` per pipeline tick; XLA orders and overlaps it.

Both engines share one stage unit (`_make_stage_fn`): at a given tick, stage
s applies its layer block to microbatch m, where stage 0 ingests `embed(m)`
(masked-uniform) and the last stage scores m against the targets in a
`lax.cond` branch by stage (the head matmul runs ONLY on the last stage —
see _make_stage_fn, which also states what such a branch may hold). AFAB
differentiates through the unit, scoring and all; 1F1B runs it WITHOUT the
scoring (`score=False`) as its forward unit, only where a later stage needs
the output, and its backward unit is written out by hand from the same
building blocks (pipeline_1f1b_grads).

**"afab"** (all-forward-all-backward, ref: pipeline_parallel.py:77-118):
one `lax.scan` over n_micro + pp - 1 ticks; at tick t stage s forwards
microbatch t - s. Differentiating through the scan yields the reverse
schedule with transposed ppermutes — the reference's manual
`torch.autograd.backward` choreography + grad send/recv is derived, not
written. Memory: scan AD stores per-tick residuals, i.e. O(n_micro) —
bounded by the tick-level `jax.checkpoint` (which honors the configured
remat policy) to one boundary activation per tick plus policy-saved values.

**"1f1b"** (ref: pipeline_parallel.py:122-215 warmup/steady/cooldown): a
synchronous schedule-table scan with *manual* VJP — no AD through the scan.
Microbatch m's forward runs at stage s on tick m + s; its backward at tick
m + 2(pp-1) - s — each steady-state tick executes one active forward AND
one active backward per stage, finishing in n_micro + 2(pp-1) ticks (see
pipeline_1f1b_grads for the schedule/memory analysis). On the last stage the
two are the same microbatch and the forward has no consumer, so that stage's
tick is the backward unit alone (which runs the forward once). In the first
and last pp-1 ticks a stage holds a microbatch for one unit only, or for
none, and runs only that: each unit sits in a `lax.cond` by whether its
schedule slot is live (PR 65), so a tick costs its slowest stage's live
units. Activation
cotangents ride a reverse ppermute; parameter gradients accumulate in the
scan carry, each leaf written where its gradient is produced and on the
stage that produces it (no per-tick gradient tree, no whole-tree add; the
layer block's backward is parallel/fused_bwd.py's manual scans where
`resolved_grad_engine` allows, jax.vjp of the block elsewhere);
live boundary inputs sit in a min(n_micro, 2(pp-1))-slot ring,
*independent of n_micro* (AFAB's live set grows with n_micro). 1f1b is the
default engine: ~AFAB speed with O(pp) instead of O(n_micro) boundary-
activation memory.

**Why no Megatron interleaved (virtual-stage) schedule UNDER THIS
EXECUTOR** (`pipeline.executor: spmd`, the default): with v chunks per
device the pipeline deepens to V = v*pp virtual stages, and the lockstep
scan runs n + 2(V-1) ticks, each holding a device's v forward + v backward
unit slots and ending in the two ppermutes every stage joins — so the
number of fill/drain ticks grows with V while per-tick cost grows with v,
making interleaving worse here (efficiency n/(n + 2(V-1)) vs this
schedule's n/(n + 2(pp-1)) with every tick at full price). Interleaving
wins on per-rank imperative runtimes because a rank moves on as soon as
its own slot is done; here a tick lasts as long as its slowest stage's live
units (PERF.md r4 measured ~one traced unit per idle tick when every slot
ran; since PR 65 a slot that holds no microbatch is skipped, which makes
the 2(pp-1) fill and drain ticks partial, not free: some stage is live in
each). Under the scan the remaining lever for bubble fraction is more
microbatches (n).

`pipeline.executor: mpmd` (parallel/mpmd.py) is the executor where that
premise does not hold: per-stage programs driven by a host-side schedule
table make idle ticks ~free, so the interleaved schedule is supported
there (and measured winning, PERF.md r10). This module stays the SPMD
reference twin the MPMD executor is parity-pinned against.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu import compat
from picotron_tpu.config import Config
from picotron_tpu.models.llama import (
    ParallelCtx, compute_dtype, embed, final_hidden, head_weight,
    model_rope_tables, remat_policy_for, run_layers,
)
from picotron_tpu.ops.losses import IGNORE_INDEX, cross_entropy_sum_count
from picotron_tpu.parallel.fused_bwd import (
    head_grads, layer_scans, resolved_grad_engine,
)
from picotron_tpu.telemetry.scopes import scope


def _vary_over(x, want):
    """Promote x to vary over the mesh axes in `want` (no-op for axes it
    already varies over). Sound in the safe direction only: it forgets
    replication knowledge, never asserts it."""
    have = compat.vma(x)
    missing = tuple(a for a in ("dp", "pp", "ep", "cp", "tp")
                    if a in want and a not in have)
    return compat.pcast(x, missing, to="varying") if missing else x


def _cast_varying_like(x, target):
    return _vary_over(x, set(compat.vma(target)))


def _boundary_axes(ctx) -> tuple:
    """Mesh axes the pipeline's activation boundary buffers vary over. A
    seq-sharded residual stream (sequence parallelism) is tp-VARYING; the
    nll/count scalars never are (head_ce psums over tp)."""
    return ("dp", "ep", "cp", "pp") + (("tp",) if ctx.seq_shard > 1 else ())


def _make_stage_fn(ids, tgt, m, ctx: ParallelCtx, cos, sin, s_idx, pp):
    """One stage-forward unit, shared by both engines.

    Returns stage_fn(params, x_buf, m_idx, valid, score=True) ->
    ((y, nll_sum), (count, dropw)): stage 0 consumes embed(ids[m_idx])
    (zero-masked when not `valid`), other stages consume the rotated-in
    activation `x_buf`; the last stage's nll_sum scores microbatch m_idx.
    Differentiable in params and x_buf ((count, dropw) is aux). With the
    static `score=False` it is embed -> run_layers -> y and returns y alone:
    1F1B's forward unit, which feeds the next stage and nothing else.

    The vocab-head scoring is gated with `lax.cond` on the stage index, not
    masked: a masked-uniform program would pay the full [B*S, H] x [H, V/tp]
    head matmul (and the fp32 exp over the logits) on EVERY stage every tick
    — at pp=4, tp=1 that is ~pp x redundant head FLOPs riding every tick
    (VERDICT r2 weak #2; the reference runs the head only on the last stage,
    ref: pipeline_parallel.py:53-63).

    The rule for a branch taken by stage — by a predicate on the stage
    index and the scan counter alone, which every device of one stage
    computes alike: `s_idx == pp - 1` and `s_idx == 0` here and in the 1F1B
    backward unit, `(s_idx == pp - 1) | ~f_on` round the 1F1B forward unit
    and `b_on` round its backward unit (f_on / b_on: the stage holds a
    microbatch for the unit at this tick) — NO COLLECTIVE OVER 'pp' IN IT,
    AND NO PPERMUTE OVER ANY AXIS. A collective whose replica group spans
    devices that take different branches leaves the in-branch members
    waiting on peers that never arrive (observed as a rendezvous deadlock
    on the CPU backend). An all-reduce, all-gather, reduce-scatter or
    all-to-all over another axis (tp, ep, cp) is allowed: every member of
    its replica group sits on the same stage and takes the same branch. A
    ppermute is not: the CPU backend's collective-permute is one
    rendezvous of EVERY device of the run whatever its pairs (observed,
    PR 38: dp 2 x pp 2 x cp 2 with ring attention's K/V ring in the 1F1B
    forward unit's branch, "expected 8 threads to join, 4 arrived"; not
    probed on the chip).
    Probe, `tools/pp_branch_probe.py` (a scan whose body has a cond on
    axis_index('pp') with a psum over 'tp' in one branch only and a
    ppermute over 'pp' after it, on a (pp 2, tp 2) mesh): right values on
    four CPU devices and on the four-chip v5e host (PR 38, JAX 0.9.0). The
    scoring cond's BACKWARD has held such a collective all along (until
    PR 63 the compiled four-chip step had a tp all-reduce in each branch of
    `transpose(jvp(head_ce))/cond`). 1F1B relies on the rule for a whole
    layer block in its forward unit and for its whole backward unit (the
    layer block forward and backward with their tp all-reduces, the head
    and the lookup in branches of their own inside it): pipeline_1f1b_grads.

    The scoring branch computes this tp shard's local softmax stats
    (vocab_parallel_ce_local_stats; zero FLOPs off the last stage) and the
    [B, S]-sized pmax/psum merge runs uniformly on every stage. Under
    sequence parallelism the engines keep r2's uniform masked scoring (SP
    already divides the head by tp; the rule above would allow its seq
    all_gather over tp in the branch — not done, no measured cell runs SP).
    The embed stays masked-uniform (its gather FLOPs are negligible).

    The token count needs no head output (it is just the non-ignored-target
    count) and is computed outside the cond because the MoE aux-loss fold
    weights by it on every stage.
    """
    dtype = compute_dtype(m)
    gated = ctx.head_ce_local is not None and ctx.seq_shard == 1

    def stage_fn(params, x_buf, m_idx, valid, score=True):
        mb_ids = lax.dynamic_index_in_dim(ids, m_idx, 0, keepdims=False)
        # Zero-mask invalid ingest so garbage never enters the pipe (all
        # bubble compute then runs on zeros, which every op here keeps
        # finite — no NaNs can poison the masked accumulators' grads).
        x0 = embed(params, mb_ids, m, ctx) * valid.astype(dtype)
        x_in = jnp.where(s_idx == 0, x0, x_buf)
        y, aux = run_layers(params["layers"], x_in, m, ctx, cos, sin)
        if not score:
            return y
        mb_tgt = lax.dynamic_index_in_dim(tgt, m_idx, 0, keepdims=False)
        count = jnp.sum(mb_tgt != IGNORE_INDEX)

        # Two rules keep collectives over 'pp' out of the BACKWARD cond's
        # branches as well (verified against the optimized HLO — violations
        # deadlock the CPU runtime's order-matched rendezvous):
        # 1. No lax.pcast inside a branch: pcast-to-varying transposes to a
        #    psum. The neutral branch instead anchors its constants on
        #    zero-weighted elements of exactly the arrays the scoring
        #    branch consumes — same varying type by construction, and the
        #    transpose of `* 0` is `* 0`.
        # 2. Every float array a branch consumes must ALREADY vary over the
        #    branch result's axes: consuming a pp-replicated param (head,
        #    final norm) inside the branch makes shard_map insert the
        #    pvary there implicitly, whose transpose is again an in-branch
        #    psum — so promote them out here, where the psum is uniform.
        y_vma = set(compat.vma(y))
        # the head weight source is lm_head, or the embedding when tied
        # (Qwen2-style) — promote whichever the scoring branch will read
        head_key = "lm_head" if "lm_head" in params else "embedding"
        head_v = _vary_over(params[head_key], y_vma)
        norm_v = _vary_over(params["final_norm"], y_vma)
        params_v = {**params, head_key: head_v, "final_norm": norm_v}

        def _anchor(args):
            y_sc, params_sc = args
            return (y_sc.ravel()[0].astype(jnp.float32)
                    + params_sc[head_key].ravel()[0].astype(jnp.float32)) * 0.0

        @scope("head_ce")
        def score_microbatch():
            if gated:
                # neutral branch merges to logz = log(tp_size) — finite
                # garbage (never inf/nan: a nan would poison the masked
                # accumulators' gradients through 0*nan), masked by the
                # contrib select below

                def score(args):
                    y_sc, params_sc = args
                    hf = final_hidden(params_sc, y_sc, m)
                    return ctx.head_ce_local(hf, head_weight(params_sc),
                                             mb_tgt)

                def no_score(args):
                    a = _anchor(args)
                    zero = jnp.zeros(mb_tgt.shape, jnp.float32) + a
                    return (zero, zero + 1.0, zero)  # max=0, sumexp=1, label=0

                stats = lax.cond(s_idx == pp - 1, score, no_score,
                                 (y, params_v))
                return ctx.head_ce_merge(stats, mb_tgt)
            if ctx.head_ce is not None:
                hf = final_hidden(params, y, m)
                return ctx.head_ce(hf, head_weight(params), mb_tgt)[0]
            # no TP head hook (plain unsharded head): the whole scoring is
            # already collective-free, so the cond can return the total

            def score_full(args):
                y_sc, params_sc = args
                hf = final_hidden(params_sc, y_sc, m)
                logits = hf @ head_weight(params_sc).astype(hf.dtype)
                return cross_entropy_sum_count(logits, mb_tgt)[0]

            return lax.cond(s_idx == pp - 1, score_full, _anchor,
                            (y, params_v))

        total = score_microbatch()
        # `contrib` is stage-additive: the CE sum counts only on the last
        # stage (masked HERE, so the engines accumulate on every active
        # tick), while each stage contributes its own layers' (pre-weighted)
        # MoE router loss, scaled by the microbatch token count
        # (llama.loss_sum_count's folding rule) — psum over 'pp' then
        # assembles the full total. dropw [2] is the same-scaled pair of
        # observability sums, capacity drops and busiest-expert load
        # (aux[1:] == 0 for dense models).
        contrib = jnp.where(s_idx == pp - 1, total, 0.0)
        if m.num_experts:
            contrib = contrib + aux[0] * count
        dropw = aux[1:] * count
        return (y, contrib), (count, dropw)

    return stage_fn


def pipeline_loss_sum_count(params, ids, tgt, cfg: Config, ctx: ParallelCtx):
    """AFAB engine: (nll_sum, valid_count, drop_weighted_sum) for the full
    microbatch stream, pipelined over 'pp'. Must run inside shard_map with
    'pp' (and 'dp','cp','tp') in scope; differentiate through it for
    gradients (the counts are non-differentiable pass-throughs).

    ids/tgt: [n_micro, mbs_local, s_local] (this device's dp/cp shard,
    replicated over pp — every stage sees the token stream, matching the
    reference's dataloader feeding all ranks, ref: pipeline_parallel.py:145-155).

    Outputs are replicated over 'pp' (psum-broadcast from the last stage).
    """
    m = cfg.model
    pp = lax.psum(1, "pp")
    s_idx = lax.axis_index("pp")
    n_micro, mbs, s_local = ids.shape
    n_ticks = n_micro + pp - 1

    cos, sin = model_rope_tables(m)
    dtype = compute_dtype(m)
    # Remat is applied at tick granularity below (so the policy governs what
    # the scan's AD saves per tick); disable the inner per-layer checkpoint
    # to avoid nesting two remat regions.
    ctx_inner = dataclasses.replace(ctx, remat=False)
    stage_fn = _make_stage_fn(ids, tgt, m, ctx_inner, cos, sin, s_idx, pp)
    fwd_perm = [(i, i + 1) for i in range(pp - 1)]

    def tick(carry, t):
        x_buf, nll_acc, cnt_acc, drop_acc = carry
        d = t - s_idx  # microbatch index this stage works on at tick t
        on = (d >= 0) & (d < n_micro)
        m_f = jnp.clip(d, 0, n_micro - 1)
        (y, contrib), (cnt, dropw) = stage_fn(params, x_buf, m_f, on)
        # contrib is pre-masked to the last stage's CE (+ this stage's MoE
        # aux) inside stage_fn — accumulate wherever the stage was active.
        # dropw is this stage's layers' contribution: every active tick.
        nll_acc = nll_acc + jnp.where(on, contrib, 0.0)
        cnt_acc = cnt_acc + jnp.where(on & (s_idx == pp - 1), cnt, 0)
        drop_acc = drop_acc + jnp.where(on, dropw, 0.0)
        with scope("pp_boundary"):
            y_next = lax.ppermute(y * on.astype(y.dtype), "pp", fwd_perm)
        return (y_next, nll_acc, cnt_acc, drop_acc), None

    body = tick
    if ctx.remat:
        body = jax.checkpoint(body, policy=remat_policy_for(ctx.remat_policy))

    # Boundary buffers carry the residual stream, which sequence parallelism
    # shards to s_local / seq_shard (tp x less ppermute traffic per tick).
    x0_buf = compat.pcast(
        jnp.zeros((mbs, s_local // ctx.seq_shard, m.hidden_size), dtype),
        _boundary_axes(ctx), to="varying")
    init = (x0_buf,) + compat.pcast(
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
         jnp.zeros((2,), jnp.float32)),
        ("dp", "ep", "cp", "pp"), to="varying")
    (x_last, nll_sum, cnt, dropw), _ = lax.scan(body, init,
                                                jnp.arange(n_ticks))

    # Broadcast the last stage's totals to every stage (masked elsewhere, so
    # psum == select; the drop sum is genuinely pp-partial — each stage
    # holds its own layers' share — and the same psum assembles it;
    # ref: utils.py:93-98 averages loss on the last PP stage then
    # broadcasts via the wandb-rank convention).
    nll_sum = lax.psum(nll_sum, "pp")
    cnt = lax.psum(cnt, "pp")
    dropw = lax.psum(dropw, "pp")
    return nll_sum, cnt, dropw


def pp_1f1b_ticks(n_micro: int, pp: int) -> int:
    """Tick count of the 1F1B schedule: n_micro + 2(pp-1). Exposed so tests
    can pin the schedule length (VERDICT r3: a tick-count assertion)."""
    return n_micro + 2 * (pp - 1)


def pp_1f1b_ring_slots(n_micro: int, pp: int) -> int:
    """Boundary-input ring size: min(n_micro, 2(pp-1)), at least 1."""
    return max(1, min(n_micro, 2 * (pp - 1)))


def units_in_branches(cfg: Config) -> bool:
    """Whether a pipeline tick's two units each sit in a branch taken only
    where the stage holds a microbatch for the unit: the 1F1B engine's tick
    (pipeline_1f1b_grads), unless cp > 1 — the layers then hold a ring of
    ppermutes, which may not sit in a branch (_make_stage_fn's rule), and
    both units run masked-uniform on every stage in every tick. AFAB is
    differentiated through and keeps its units whole (a differentiated cond
    makes both branches hand over residuals)."""
    d = cfg.distributed
    return d.pp_engine == "1f1b" and d.cp_size == 1


def pipeline_1f1b_grads(params, ids, tgt, cfg: Config, ctx: ParallelCtx):
    """1F1B engine: (grads, nll_sum, valid_count, drop_weighted_sum),
    pipelined over 'pp'.

    Unlike the AFAB engine this computes gradients *itself* (manual VJP per
    tick) — do not differentiate through it. Full-rate schedule (the
    synchronous analogue of ref: pipeline_parallel.py:122-215):

        forward  of microbatch m at stage s: tick m + s
        backward of microbatch m at stage s: tick m + 2(pp-1) - s

    Every steady-state tick runs ONE active forward and ONE active backward
    on every stage (warmup: stage s forwards 2(pp-1-s) microbatches before
    its first backward; cooldown mirrors it), completing in
    n_micro + 2(pp-1) ticks — within pp-1 ticks of AFAB's forward-pass
    length, vs the 2*n_micro + 2(pp-1) - 1 of the previous half-rate
    schedule, which idled every stage on alternating ticks and cost ~2x
    AFAB's pipeline FLOPs (VERDICT r2 weak #1).

    Memory: stage s holds up to min(n_micro, 2(pp-1-s)) boundary *inputs*
    live — the ring holds only [mbs, S_local, H] stage inputs (the backward
    unit recomputes the stage interior, honoring the remat policy), so the
    bound is 2x Megatron's per-stage pp-s activations but
    counts only boundary tensors, negligible against weights at realistic
    shapes. The 2x is fundamental to full rate: microbatch m's grad returns
    to stage s exactly 2(pp-1-s) ticks after its forward (one stage per
    tick each way), during which a full-rate stage forwards 2(pp-1-s) more
    microbatches. Halving the in-flight set requires halving the forward
    rate — the previous schedule — never a win on TPU, where HBM spent on
    2pp boundary buffers is cheap and idle MXU ticks are not.

    Ring-slot safety (R = min(n_micro, 2(pp-1)) slots, slot = m mod R):
    the load of microbatch m's input at tick m + 2(pp-1) - s happens before
    the store of microbatch m + R at tick m + R + s in tick order for every
    s > 0; at s = 0 with R = 2(pp-1) they land on the same tick, so the
    tick body LOADS the backward input before the forward unit stores. At
    the last stage backward and forward of the same microbatch share a tick
    (b == f) and the backward consumes the live x_buf directly, not the
    ring.

    What a tick runs: each of its two units only where the stage holds a
    microbatch for it at this tick (PR 65), the two ppermutes always. The
    forward unit's slot is live at stage s on ticks s .. s + n_micro - 1
    (`f_on`), the backward unit's on ticks 2(pp-1) - s .. 2(pp-1) - s +
    n_micro - 1 (`b_on`); each unit sits in a `lax.cond` on its predicate,
    which depends on the stage index and the scan counter alone, so every
    peer of a tp / ep / dp collective inside takes the same branch
    (_make_stage_fn's rule). A fill tick (t < pp-1: no stage holds a
    backward) therefore costs a forward unit, a drain tick (t >=
    n_micro + pp-1: no forward left) a backward unit, and the ticks between
    whatever the slowest stage's live units cost; a skipped unit added
    exact zeros before (its cotangents were zero), so the sums are the
    same. At pp 2 with 8 microbatches: tick 0 is stage 0's forward unit
    alone and tick 9 its backward unit alone, where each cost a whole tick.
    `units_in_branches` says where this holds: with cp > 1 the layers hold
    a ring of ppermutes, which the rule forbids in a branch, and both units
    run masked-uniform on every stage in every tick (on zeros off their
    slots, as everywhere until PR 65).

    The *backward unit* takes microbatch m_b through the
    stage a second time, forward then backward, and every weight gradient
    lands in the fp32 accumulator `g_acc` WHERE IT IS PRODUCED, on the
    stage that produces it (PR 63) — the tick builds no gradient tree and
    adds no tree. Its four parts, each the same mathematics (dW in the
    compute dtype, cast to fp32, added into the accumulator):

    1. Ingest, a branch by stage: stage 0 looks the microbatch up, the
       others take the saved boundary input.
    2. The layer block. Where `resolved_grad_engine` says 'fused'
       (parallel/fused_bwd.py: the old block under remat dots_attn, on
       any dp/tp/SP/cp/ep layout) it is that file's two layer scans:
       the forward with dots_attn's save set, then the reverse scan that
       carries `g_acc["layers"]` and updates one layer's slices an
       iteration (scope `dw_accum`), fed by the cotangent from the next
       stage (the head's dx on the last). Everywhere else (`grad_engine:
       ad`, another remat policy, MoE over stages of unequal depth) it is
       `jax.vjp` of `run_layers` and one add over the stack's leaves.
    3. The head, a branch by stage that only the last stage takes: final
       norm, head matmul, CE (its tp merge too) AND their backward
       (`fused_bwd.head_grads`), `acc + dW` for the norm and the head's
       matrix inside the branch, so the compiler folds the add into the dW
       matmul. Forward and backward share the branch, so nothing
       differentiates THROUGH a cond: no neutral-branch residuals (AD made
       the stage that never scores zero-fill a microbatch's fp32 logits
       every tick), and _make_stage_fn's two backward-branch rules, which
       AFAB still needs, do not arise. Its primal is where the engine reads
       the loss sum — every microbatch has exactly one backward on every
       stage; the token count and the MoE drop / load sums need no head.
    4. The embedding's rows, a branch by stage: scattered onto
       `g_acc["embedding"]` on stage 0. With a tied head the embedding is
       touched twice, once in each branch.

    A stage passes the accumulators it does not use through the other
    branch untouched, and a tick without a backward passes them all through
    the unit's idle branch, which returns zeros for the loss term, the drop
    sums and the cotangent it sends on; tests/test_chip_compile.py holds
    that the compiled tick neither copies nor fills nor adds an
    accumulator-shaped leaf outside a live branch. Every branch follows
    _make_stage_fn's rule: a tp all-reduce may sit in it, nothing over
    'pp', no ppermute.

    The *forward unit* is the layer block alone at microbatch m_f
    (`score=False`: no head, no merge collectives), and exists to feed the
    next stage. On the last stage m_f == m_b and nothing consumes y, so the
    unit's `lax.cond` takes its zeros branch there as well as off a forward
    tick: that stage computes each microbatch's forward once (a second
    forward of layers and head there is a third of the tick of the stage
    that sets the step).

    Grads of pp-replicated params (embedding / final norm / head) come out
    nonzero only on the stage that uses them — pass through
    sync_pp_replicated_grads like the AFAB path's.
    """
    m = cfg.model
    pp = lax.psum(1, "pp")
    s_idx = lax.axis_index("pp")
    n_micro, mbs, s_local = ids.shape
    n_ticks = pp_1f1b_ticks(n_micro, pp)
    ring_slots = pp_1f1b_ring_slots(n_micro, pp)

    cos, sin = model_rope_tables(m)
    dtype = compute_dtype(m)
    stage_fn = _make_stage_fn(ids, tgt, m, ctx, cos, sin, s_idx, pp)
    fwd_perm = [(i, i + 1) for i in range(pp - 1)]
    bwd_perm = [(i + 1, i) for i in range(pp - 1)]

    # The backward unit's layer block (docstring): the manual backward's two
    # scans, or jax.vjp of run_layers and an add over the stack's leaves.
    accumulate = resolved_grad_engine(cfg) == "fused"
    if accumulate:
        layers_fwd, layers_bwd = layer_scans(cfg, ctx, params["layers"])
    # the leaves the head's branch reads and accumulates into: the final
    # norm and the head's matrix (the embedding's, where tied)
    head_keys = ("final_norm",
                 "lm_head" if "lm_head" in params else "embedding")
    data_pp = {"dp", "ep", "cp", "pp"}
    on_boundary = set(_boundary_axes(ctx))
    gated = units_in_branches(cfg)

    def into(acc, g):
        """acc + g: a gradient in the compute dtype, cast to the fp32
        accumulator's dtype and varying type, added."""
        return acc + _cast_varying_like(g.astype(acc.dtype), acc)

    def tick(carry, t):
        ring, x_buf, g_buf, g_acc, nll_acc, cnt_acc, drop_acc = carry

        # ---- backward ring load FIRST: at stage 0 with a full ring the
        # slot being loaded is re-stored by this tick's forward unit ----
        db = t - 2 * (pp - 1) + s_idx
        b_on = (db >= 0) & (db < n_micro)
        m_b = jnp.clip(db, 0, n_micro - 1)
        x_ring = lax.dynamic_index_in_dim(ring, m_b % ring_slots, 0,
                                          keepdims=False)

        # ---- forward unit: microbatch m_f advances one stage ----
        # Layers only, and only where a later stage will read the result:
        # not on the last stage (docstring), not off a forward tick. Not
        # differentiated, so the pcast in the zeros branch transposes to
        # nothing.
        df = t - s_idx
        f_on = (df >= 0) & (df < n_micro)
        m_f = jnp.clip(df, 0, n_micro - 1)

        def layers_fwd_unit(p, xb):
            return stage_fn(p, xb, m_f, f_on, score=False)

        if gated:
            y = lax.cond(
                (s_idx == pp - 1) | ~f_on,
                lambda p, xb: _cast_varying_like(jnp.zeros_like(xb), xb),
                layers_fwd_unit, params, x_buf)
        else:
            y = layers_fwd_unit(params, x_buf)
        # Save this stage's *input* for the backward recompute. Guard the
        # store: on non-forward ticks m_f aliases a possibly-live slot.
        ring_new = lax.dynamic_update_index_in_dim(
            ring, x_buf, m_f % ring_slots, 0)
        ring = jnp.where(f_on, ring_new, ring)
        with scope("pp_boundary"):
            y_send = lax.ppermute(y * f_on.astype(y.dtype), "pp", fwd_perm)

        # ---- backward unit: microbatch m_b retreats one stage ----
        mb_ids = lax.dynamic_index_in_dim(ids, m_b, 0, keepdims=False)
        mb_tgt = lax.dynamic_index_in_dim(tgt, m_b, 0, keepdims=False)
        count = jnp.sum(mb_tgt != IGNORE_INDEX)

        def backward_unit(g_acc, x_saved, g_buf, live):
            """-> (g_acc, contrib, dropw, dx_in). `live` says whether the
            stage holds a backward this tick: True in the branch by b_on,
            the traced b_on where the unit runs masked-uniform."""
            # The loss's cotangent is 1 on EVERY stage that ran m_b (the CE
            # counts on the last stage only, each stage's MoE aux term on
            # its own). Masked-uniform it is 0 on a tick without a backward:
            # g_buf is zeros then too, every gradient below is linear in the
            # two, so nothing else needs a mask.
            g_nll = _vary_over(jnp.where(live, 1.0, 0.0), data_pp)

            # Ingest, in a branch by stage: stage 0 looks the microbatch up
            # (masked-uniform: zeroed off a backward tick, so that all
            # bubble compute runs on zeros, which every op here keeps
            # finite), every other stage takes its saved input — the last
            # one this tick's live x_buf, since there b(m) == f(m).
            def lookup(emb):
                x = embed({"embedding": emb}, mb_ids, m, ctx)
                return x if live is True else x * live.astype(dtype)

            x_in = lax.cond(
                s_idx == 0,
                lambda emb, xs: _vary_over(lookup(emb), on_boundary),
                lambda emb, xs: xs, params["embedding"], x_saved)

            # the layer block, forward
            def weighted(aux):
                # llama.loss_sum_count's folding rule: this stage's layers'
                # (pre-weighted) router loss scaled by the token count,
                # which joins the loss; and the same-scaled drop / load sums
                return ((aux[0] * count,) if m.num_experts else ()), \
                    aux[1:] * count

            if accumulate:
                x_out, saved, aux_layers = layers_fwd(x_in)
                fold, dropw = weighted(jnp.sum(aux_layers, axis=0))
            else:
                def block(lp, x):
                    y_, aux = run_layers(lp, x, m, ctx, cos, sin)
                    fold, dropw = weighted(aux)
                    return (y_,) + fold, dropw

                (x_out, *fold), vjp_block, dropw = jax.vjp(
                    block, params["layers"], x_in, has_aux=True)

            # The head, forward AND backward, in the branch the last stage
            # takes: final norm, head matmul, CE (its tp merge too) and
            # their gradients, the head's and the norm's landing in their
            # accumulators there. Every other stage passes its accumulators
            # through and hands the layer block the cotangent that arrived
            # from the next stage.
            def score(x, nl, acc):
                total, _, dx, g_nl = head_grads(x, nl, mb_tgt, cfg, ctx,
                                                g_nll)
                with scope("head_ce"):
                    acc = {k: into(acc[k], g_nl[k]) for k in acc}
                return (_vary_over(total, data_pp),
                        _vary_over(dx, on_boundary), acc)

            def no_score(x, nl, acc):
                return (_vary_over(jnp.zeros((), jnp.float32), data_pp),
                        g_buf, acc)

            contrib, dx_out, acc_head = lax.cond(
                s_idx == pp - 1, score, no_score, x_out,
                {k: params[k] for k in head_keys},
                {k: g_acc[k] for k in head_keys})
            g_acc = {**g_acc, **acc_head}

            # the layer block, backward: each layer's dW into the
            # accumulator inside the reverse scan, or AD's tree for the
            # stack and one add
            if fold:
                contrib = contrib + fold[0]
            if accumulate:
                # (the scan's aux fold is `aux * weight` with cotangent 1:
                # the token count, times the loss's cotangent on this tick)
                dx_in, g_layers = layers_bwd(saved, dx_out, g_acc["layers"],
                                             count * g_nll)
            else:
                g_lp, dx_in = vjp_block(
                    (dx_out,)
                    + tuple(_cast_varying_like(g_nll, f) for f in fold))
                g_layers = jax.tree.map(into, g_acc["layers"], g_lp)

            # the embedding's rows, scattered onto its accumulator on
            # stage 0
            def lookup_bwd(acc, dx):
                with scope("embed"):
                    _, vjp_lookup = jax.vjp(lookup, params["embedding"])
                    (g_emb,) = vjp_lookup(dx)
                    return into(acc, g_emb)

            g_emb_acc = lax.cond(s_idx == 0, lookup_bwd, lambda acc, dx: acc,
                                 g_acc["embedding"], dx_in)
            g_acc = {**g_acc, "layers": g_layers, "embedding": g_emb_acc}
            return (g_acc, _vary_over(contrib, data_pp),
                    _vary_over(dropw, data_pp), dx_in)

        def idle_unit(g_acc, x_saved, g_buf):
            """A tick without a backward: the accumulators as they came,
            and zeros typed as backward_unit's other results."""
            return (g_acc, _vary_over(jnp.zeros((), jnp.float32), data_pp),
                    _vary_over(jnp.zeros((2,), jnp.float32), data_pp),
                    _cast_varying_like(jnp.zeros_like(g_buf), g_buf))

        x_saved = jnp.where(s_idx == pp - 1, x_buf, x_ring)
        if gated:
            g_acc, contrib, dropw, dx_in = lax.cond(
                b_on, lambda *a: backward_unit(*a, True), idle_unit,
                g_acc, x_saved, g_buf)
        else:
            g_acc, contrib, dropw, dx_in = backward_unit(
                g_acc, x_saved, g_buf, b_on)

        # The loss is read where it is computed: the backward unit's
        # primal. Every microbatch has exactly one backward on every stage.
        nll_acc = nll_acc + jnp.where(b_on, contrib, 0.0)
        cnt_acc = cnt_acc + jnp.where(b_on & (s_idx == pp - 1), count, 0)
        drop_acc = drop_acc + jnp.where(b_on, dropw, 0.0)
        # Cotangents ride the reverse ring: stage 0's has no receiver.
        with scope("pp_boundary"):
            g_send = lax.ppermute(dx_in, "pp", bwd_perm)

        return (ring, y_send, g_send, g_acc, nll_acc, cnt_acc, drop_acc), None

    x0 = jnp.zeros((mbs, s_local // ctx.seq_shard, m.hidden_size), dtype)
    bufs = compat.pcast(
        (jnp.zeros((ring_slots,) + x0.shape, dtype), x0, x0),
        _boundary_axes(ctx), to="varying"
    ) + compat.pcast(
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
         jnp.zeros((2,), jnp.float32)),
        ("dp", "ep", "cp", "pp"), to="varying")
    # Each grad-accumulator leaf varies over the data axes and 'pp' plus
    # whatever its param already varies over (tp shardings) — what the
    # backward unit produces each tick, so the scan carry type is stable.
    # NOT over 'tp' for a tp-replicated param under sequence parallelism
    # (the norms): the gradient of a tp-invariant param arrives complete,
    # AD's pvary-transpose psum over tp having run inside the tick (the rule
    # parallel/api.py _device_grads states for the data axes). Until PR 63
    # these leaves were typed tp-varying here and a tp psum after the scan
    # (`sync_sp_partial_grads`, gone) summed them a second time: every
    # norm's gradient tp x too large under 1F1B + SP (AFAB and pp = 1 were
    # right), which is what set 1F1B's grad_norm 1% off AFAB's (PERF.md
    # section 7, PR 38 (f)).
    # fp32 accumulation regardless of the param dtype: with
    # optimizer_offload the params (and hence the per-tick dW) are bf16, and
    # summing n_micro bf16 grads in bf16 would lose the low bits the fp32
    # master exists to keep (jnp.add promotes bf16 + fp32 -> fp32).
    g_zero = jax.tree.map(
        lambda p: _vary_over(jnp.zeros(p.shape, jnp.float32),
                             data_pp | set(compat.vma(p))),
        params)
    init = (bufs[0], bufs[1], bufs[2], g_zero, bufs[3], bufs[4], bufs[5])
    (_, _, _, grads, nll_sum, cnt, dropw), _ = lax.scan(
        tick, init, jnp.arange(n_ticks))

    nll_sum = lax.psum(nll_sum, "pp")
    cnt = lax.psum(cnt, "pp")
    dropw = lax.psum(dropw, "pp")
    return grads, nll_sum, cnt, dropw


def sync_pp_replicated_grads(grads, specs):
    """psum over 'pp' the grads of params replicated across pipeline stages
    (embedding / final norm / lm_head): each is used by one stage, so its
    per-stage grads are disjoint and the sum assembles the true total.
    Layer params are sharded over 'pp' (leading axis) and need no collective.

    Only a grad that still VARIES over 'pp' is a per-stage partial. AD of a
    pp-invariant param already ends in the pvary-transpose psum, so such a
    grad arrives invariant and complete — a second psum would multiply it
    by the stage count (measured on JAX 0.9.0: embedding / final_norm /
    lm_head grads exactly pp x the single-device ones under both engines).
    """
    from jax.sharding import PartitionSpec as P

    def fix(g, spec):
        flat = []
        for part in spec:
            if isinstance(part, (tuple, list)):
                flat.extend(part)
            elif part is not None:
                flat.append(part)
        if "pp" in flat or "pp" not in compat.vma(g):
            return g
        return lax.psum(g, "pp")

    return jax.tree.map(fix, grads, specs,
                        is_leaf=lambda x: isinstance(x, P))
