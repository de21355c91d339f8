"""Optimizer: AdamW over fp32 master params.

The reference uses `torch.optim.AdamW(fused=True)` (ref: train.py:204-209) —
a CUDA kernel. On TPU, optax's adamw update is a handful of elementwise ops
that XLA fuses into one kernel per bucket automatically; no custom kernel is
needed (SURVEY.md §2.3 row `fused AdamW`).

`adam_moments_dtype: "bfloat16"` stores both Adam moments in bf16 (compute
still fp32): moment memory halves, which is what lets full-depth
SmolLM-1.7B's optimizer state fit a single 16G v5e chip. The reference has
no low-precision optimizer option; this is a TPU-memory-driven extension.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax

from picotron_tpu.config import TrainingConfig
from picotron_tpu.telemetry.scopes import scope


def scale_by_adam_low_moments(b1: float, b2: float, eps: float,
                              moments_dtype) -> optax.GradientTransformation:
    """scale_by_adam with BOTH moments stored in `moments_dtype` (optax's
    mu_dtype covers only the first moment). The update math runs in fp32;
    only the carried state is rounded."""

    def init(params):
        zeros = lambda p: jnp.zeros_like(p, dtype=moments_dtype)  # noqa: E731
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree.map(zeros, params),
            nu=jax.tree.map(zeros, params),
        )

    def update(updates, state, params=None):
        del params
        count = state.count + 1
        mu32 = jax.tree.map(
            lambda g, m: b1 * m.astype(jnp.float32)
            + (1 - b1) * g.astype(jnp.float32),
            updates, state.mu)
        nu32 = jax.tree.map(
            lambda g, n: b2 * n.astype(jnp.float32)
            + (1 - b2) * jnp.square(g.astype(jnp.float32)),
            updates, state.nu)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        out = jax.tree.map(
            lambda m, n: (m / c1) / (jnp.sqrt(n / c2) + eps), mu32, nu32)
        new_state = optax.ScaleByAdamState(
            count=count,
            mu=jax.tree.map(lambda m: m.astype(moments_dtype), mu32),
            nu=jax.tree.map(lambda n: n.astype(moments_dtype), nu32),
        )
        return out, new_state

    return optax.GradientTransformation(init, update)


def make_lr(t: TrainingConfig):
    """Learning-rate schedule (a float or an optax schedule fn). The
    reference trains at constant LR (ref: train.py:209); warmup + cosine /
    linear decay are the standard pretraining extensions. Schedules are a
    pure function of the optimizer step count, which lives in the restored
    optimizer state — resume continues the schedule where it left off."""
    if t.lr_schedule == "constant" and t.lr_warmup_steps == 0:
        return t.learning_rate
    peak, floor = t.learning_rate, t.learning_rate * t.lr_min_ratio
    decay_steps = max(1, t.total_train_steps - t.lr_warmup_steps)
    if t.lr_schedule == "cosine":
        decay = optax.cosine_decay_schedule(peak, decay_steps,
                                            alpha=t.lr_min_ratio)
    elif t.lr_schedule == "linear":
        decay = optax.linear_schedule(peak, floor, decay_steps)
    else:  # constant with warmup
        decay = optax.constant_schedule(peak)
    if t.lr_warmup_steps == 0:
        return decay
    return optax.join_schedules(
        [optax.linear_schedule(0.0, peak, t.lr_warmup_steps), decay],
        boundaries=[t.lr_warmup_steps])


# Minimum fp32-master bytes per streamed-update slice for axis-0 scanning
# to beat a whole-leaf transfer: ~16 MB slices already run ~4 GB/s on v5e
# (measured; the per-iteration latency floor dominates below that), and
# tiny leaves (norms) go whole-leaf through the barrier chain instead.
_OFFLOAD_MIN_SLICE_BYTES = 4 * 2 ** 20
# Target fp32-master bytes per ROW GROUP when streaming big-axis-0 leaves
# (embedding/lm_head): ~32 MB groups measured 4.0 GB/s via
# dynamic_slice_in_dim on the pinned-host buffer.
_OFFLOAD_ROW_GROUP_BYTES = 32 * 2 ** 20


class OffloadAdamState(NamedTuple):
    """Optimizer state for `training.optimizer_offload`: the fp32 master
    params and both Adam moments live in pinned HOST memory (their leaves
    carry `memory_kind='pinned_host'` shardings); only the step counter is a
    device scalar. TrainState.params is then the bf16 device compute copy —
    the master moves INTO the optimizer state, which is where it
    conceptually belongs (it exists only for the update)."""

    count: jnp.ndarray  # int32 scalar, device
    master: Any         # fp32 pytree, pinned_host
    mu: Any             # adam_moments_dtype pytree, pinned_host
    nu: Any             # adam_moments_dtype pytree, pinned_host


def _lr_at(t: TrainingConfig, count):
    lr = make_lr(t)
    return lr(count) if callable(lr) else jnp.asarray(lr, jnp.float32)


def global_grad_norm(grads, clip_specs):
    """Global grad norm under shard_map: per-leaf local sum-of-squares,
    psum'd over the mesh axes the leaf is SHARDED over (its PartitionSpec
    axes — distinct shards sum to the global total; replicated leaves need
    no collective and must not double-count). clip_specs None = local norm
    (outside shard_map / single device)."""
    total = jnp.zeros((), jnp.float32)
    if clip_specs is None:
        for g in jax.tree.leaves(grads):
            total += jnp.sum(jnp.square(g.astype(jnp.float32)))
        return jnp.sqrt(total)
    from jax.sharding import PartitionSpec as P

    g_leaves, treedef = jax.tree.flatten(grads)
    s_leaves = jax.tree.leaves(clip_specs,
                               is_leaf=lambda x: isinstance(x, P))
    for g, spec in zip(g_leaves, s_leaves):
        s = jnp.sum(jnp.square(g.astype(jnp.float32)))
        axes = tuple(a for part in spec if part is not None
                     for a in (part if isinstance(part, (tuple, list))
                               else (part,)))
        # scalar psums (one fp32 each): latency-only, per-leaf axis sets
        # differ so they cannot batch into one op
        total += lax.psum(s, axes) if axes else s  # shardcheck: ok
    return jnp.sqrt(total)


@scope("optimizer")
def offload_adam_update(grads, state: OffloadAdamState, t: TrainingConfig,
                        compute_dtype, *, transfer: bool = True,
                        clip_specs=None, grad_scale=None, zero1_info=None):
    """One AdamW step streamed through the device, leaf by leaf — written
    in PER-DEVICE terms so it runs INSIDE the train step's shard_map body:
    every operand is this device's local shard, and host<->device movement
    uses memory-space-only transfers (`jax.device_put(x, MemorySpace)`),
    which carry no resharding semantics. Fusing the update into the grad
    shard_map is load-bearing for memory: grads leaving a shard_map as
    outputs cost a SECOND full fp32 tree (the while-loop grad carry cannot
    alias a boundary output — measured 6-7 GB of waste at SmolLM-1.7B
    scale, PERF.md r4).

    grads: fp32 local grad shards (data-axis-psum'd, NOT yet divided).
    transfer False (CPU test meshes) runs the identical math without
    placement transfers. clip_specs: the params' PartitionSpec tree, for
    the cross-shard grad-norm psum (None = local norm). grad_scale (e.g.
    1/token_count) is folded into the per-slice math so the caller never
    materializes a divided copy of the grad tree. zero1_info (from
    api.offload_zero1_info): per-flattened-leaf (dim, axes, axis_sizes)
    ZeRO-1 placements — the host state arrives sharded over the fused
    data axes, so each process slices its shard out of the (replicated)
    grads, updates 1/dp of the state, and all-gathers the refreshed
    compute-dtype params back to full size at the end. The math per
    element is unchanged; zero1 changes WHICH process updates it.

    Returns (new_params_compute_dtype, new_state). The math is
    bit-identical to the on-device `scale_by_adam_low_moments` +
    `add_decayed_weights` + `scale_by_learning_rate` chain (and to
    optax.adamw for fp32 moments): offload changes WHERE state lives, not
    what the update computes."""
    b1, b2, eps = t.adam_beta1, t.adam_beta2, t.adam_eps
    wd = t.weight_decay
    mdt = jnp.bfloat16 if t.adam_moments_dtype == "bfloat16" else jnp.float32

    count = state.count + 1
    # optax evaluates the LR schedule at the PRE-increment count (the number
    # of updates already applied) while Adam's bias correction uses the
    # incremented count — mirror both exactly so the parity test holds.
    lr = _lr_at(t, state.count)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)

    # One combined scalar multiplier on g, applied inside the slice math:
    # the token-mean 1/count (grad_scale) and the global-norm clip. The
    # clip threshold compares against the SCALED grad norm — identical to
    # clipping after division, since ||s*g|| = s*||g||.
    scale = (jnp.asarray(1.0, jnp.float32) if grad_scale is None
             else jnp.asarray(grad_scale, jnp.float32))
    if t.grad_clip_norm > 0:
        gn = global_grad_norm(grads, clip_specs) * scale
        scale = scale * jnp.where(gn < t.grad_clip_norm, 1.0,
                                  t.grad_clip_norm / gn)

    if transfer:
        to_dev = lambda x: jax.device_put(x, jax.memory.Space.Device)  # noqa: E731
        to_host = lambda x: jax.device_put(x, jax.memory.Space.Host)  # noqa: E731
    else:
        to_dev = to_host = lambda x: x

    def math(p, m, n, g):
        g = g.astype(jnp.float32) * scale
        m2 = b1 * m + (1 - b1) * g
        n2 = b2 * n + (1 - b2) * jnp.square(g)
        upd = (m2 / c1) / (jnp.sqrt(n2 / c2) + eps) + wd * p
        return p - lr * upd, m2, n2

    def leaf_plain(g, p_h, m_h, n_h):
        p2, m2, n2 = math(p_h, m_h.astype(jnp.float32),
                          n_h.astype(jnp.float32), g)
        return (p2, m2.astype(mdt), n2.astype(mdt),
                p2.astype(compute_dtype))

    def leaf_whole(g, p_h, m_h, n_h, token):
        # Sequence this leaf's h2d DMAs after the previous leaf's update
        # compute: without the barrier XLA hoists every leaf's master +
        # moment transfers to the front of the update, and ~15 GB of fp32
        # state is live on device at once (measured: 17.6 GB peak, OOM).
        p_h, m_h, n_h, token = lax.optimization_barrier(
            (p_h, m_h, n_h, token))
        p = to_dev(p_h)
        m = to_dev(m_h).astype(jnp.float32)
        n = to_dev(n_h).astype(jnp.float32)
        p2, m2, n2 = math(p, m, n, g)
        token, p2 = lax.optimization_barrier((token, p2))
        return (to_host(p2),
                to_host(m2.astype(mdt)),
                to_host(n2.astype(mdt)),
                p2.astype(compute_dtype)), token

    def group_scanned(members, token):
        # Stream a GROUP of equal-depth stacked leaves through the device
        # one axis-0 slice (= one layer of each local stacked-tree shard)
        # at a time: lax.scan's per-iteration dynamic-slices read directly
        # from the pinned-host buffers (one h2d DMA per leaf per slice)
        # and the stacked outputs dynamic-update-slice back into
        # pinned-host results, so at most ~two layers' worth of fp32 state
        # is device-resident at any point. Fusing every same-depth leaf
        # into ONE scan (instead of one scan per leaf, r4) lets the DMA
        # engines pipeline all the leaves' slice transfers within an
        # iteration — leaf-serial scans measured 21 GB/s aggregate on the
        # 64 MB-slice MLP leaves vs 43 GB/s on the smaller qkv slices; the
        # fused scan keeps every engine fed (PERF.md r5). Slicing MUST be
        # each leaf's own leading axis: reshaping the host operand to fold
        # layers into bigger chunks drops the async-DMA fast path
        # (measured 4.8 -> 1.7 GB/s, PERF.md r4).
        def body(tok, xs):
            p2s, outs = [], []
            for p_sl, m_sl, n_sl, g_sl in xs:
                p = to_dev(p_sl)
                m = to_dev(m_sl).astype(jnp.float32)
                n = to_dev(n_sl).astype(jnp.float32)
                p2, m2, n2 = math(p, m, n, g_sl)
                p2s.append(p2)
                outs.append((m2, n2))
            # the token must DATA-DEPEND on the slice work — a pass-through
            # carry would be forwarded to the scan's init by the while-loop
            # simplifier, severing the inter-leaf ordering chain that
            # leaf_whole's barriers hang off (code review r4). Output-side
            # only: an input-side barrier too was measured ~10% slower
            # (it serializes the h2d against the previous iteration). One
            # barrier over the whole group: intra-group transfers stay
            # unordered (that is the parallelism), inter-iteration memory
            # stays bounded.
            bar = lax.optimization_barrier(tuple(p2s) + (tok,))
            p2s, tok = bar[:-1], bar[-1]
            return tok, tuple(
                (to_host(p2), to_host(m2.astype(mdt)),
                 to_host(n2.astype(mdt)), p2.astype(compute_dtype))
                for p2, (m2, n2) in zip(p2s, outs))

        xs = tuple((p_leaves[i], m_leaves[i], n_leaves[i], g_leaves[i])
                   for i in members)
        token, outs = lax.scan(body, token, xs)
        return outs, token

    def leaf_scanned_rows(g, p_h, m_h, n_h, token, group):
        # Row-group streaming for leaves whose axis 0 is a big vocab/
        # feature dim (embedding, lm_head): explicit dynamic_slice_in_dim
        # with a computed offset keeps the async host-DMA fast path
        # (measured 4.0 GB/s — a host RESHAPE to fold rows would drop it
        # to 1.7) while capping the device-resident transient at one
        # ~32 MB group instead of the whole 400 MB leaf chain.
        n = p_h.shape[0] // group

        def body(tok, i):
            def sl(x):
                return lax.dynamic_slice_in_dim(x, i * group, group, 0)

            p = to_dev(sl(p_h))
            m = to_dev(sl(m_h)).astype(jnp.float32)
            nn = to_dev(sl(n_h)).astype(jnp.float32)
            p2, m2, n2 = math(p, m, nn, sl(g))
            tok, p2 = lax.optimization_barrier((tok, p2))
            return tok, (to_host(p2),
                         to_host(m2.astype(mdt)),
                         to_host(n2.astype(mdt)),
                         p2.astype(compute_dtype))

        token, ys = lax.scan(body, token, jnp.arange(n))
        shape = p_h.shape
        out = tuple(y.reshape(shape) for y in ys)
        return out, token

    def row_group(p_h) -> int:
        """Group size for leaf_scanned_rows (0 = not applicable): a
        divisor of axis 0 whose group stays near _OFFLOAD_ROW_GROUP_BYTES.
        Searches below the target first, then up to 4x above it, so vocab
        sizes without a divisor right at the target still stream (e.g.
        49152/151936/128256 all do). A genuinely prime-ish axis 0 (GPT-2's
        50257) has no usable divisor and falls back to the whole-leaf
        path — acceptable: its transient is one leaf, and scan slices
        must be uniform."""
        shape = p_h.shape
        if len(shape) < 2 or shape[0] <= 1024:
            return 0
        row_bytes = p_h.nbytes // shape[0]
        target = max(1, _OFFLOAD_ROW_GROUP_BYTES // max(row_bytes, 1))
        gsz = min(target, shape[0])
        while gsz > 1 and shape[0] % gsz:
            gsz -= 1
        if gsz > 1 and gsz * row_bytes >= _OFFLOAD_MIN_SLICE_BYTES \
                and gsz < shape[0]:
            return gsz
        # nothing usable at-or-below the target: take the smallest divisor
        # above it (bounded, so the transient stays within ~4x the target)
        for cand in range(target + 1, min(4 * target, shape[0] - 1) + 1):
            if shape[0] % cand == 0:
                return cand
        return 0

    def scannable(p_h) -> bool:
        """Stream sliced along axis 0 (one slice per stacked layer of the
        LOCAL shard — inside shard_map the leading axis is always safe to
        slice)? Short enough to be a layer stack rather than a
        vocab/feature dim, big enough per slice for the DMA to run near
        peak."""
        shape = p_h.shape
        if len(shape) < 2 or not 2 <= shape[0] <= 1024:
            return False
        return p_h.nbytes // shape[0] >= _OFFLOAD_MIN_SLICE_BYTES

    # One ordering token PER VMA CLASS (the set of mesh axes a leaf varies
    # over inside shard_map): the optimization_barrier chain joins the
    # varying-axes type of everything it groups, so a single token would
    # leak e.g. the embedding's {tp} onto the replicated norms' outputs and
    # fail the out_specs vma check. Leaves of the same class (in practice:
    # all the big tp-sharded matrices) still chain — which is where the
    # DMA-hoisting memory bound matters; the off-class leaves are the KB-
    # sized norms. Outside shard_map every vma is empty and this is one
    # global token, exactly the old behavior.
    tokens: dict = {}

    def token_for(leaf):
        from picotron_tpu import compat

        key = compat.vma(leaf)
        if key not in tokens:
            tok = jnp.zeros((), jnp.float32)
            if key:  # only ever non-empty when the vma types exist
                tok = lax.pvary(tok, tuple(sorted(key)))
            tokens[key] = tok
        return key, tokens[key]

    g_leaves, treedef = jax.tree.flatten(grads)
    p_leaves = treedef.flatten_up_to(state.master)
    m_leaves = treedef.flatten_up_to(state.mu)
    n_leaves = treedef.flatten_up_to(state.nu)
    # ZeRO-1: slice each leaf's (replicated) grads down to this process's
    # state shard. The global-norm clip above already consumed the FULL
    # grad tree, so the clip scale is identical on every shard.
    if zero1_info is not None:
        def z1_slice(g, place):
            if place is None:
                return g
            dim, axes, sizes = place
            idx = jnp.zeros((), jnp.int32)
            for a, s in zip(axes, sizes):
                idx = idx * s + lax.axis_index(a)
            n_shards = 1
            for s in sizes:
                n_shards *= s
            shard = g.shape[dim] // n_shards
            return lax.dynamic_slice_in_dim(g, idx * shard, shard, dim)

        g_leaves = [z1_slice(g, pl)
                    for g, pl in zip(g_leaves, zero1_info)]
    # Squeeze leading unit dims so single-layer stacks still stream: a
    # 1-layer model's stacked expert bank is [1, E, H, I] — axis 0 of
    # size 1 would fall through to leaf_whole and put the entire
    # multi-GB master in flight at once (measured: the Mixtral-8x7B-1L
    # row OOM'd by 2.6 GB, PERF.md r5). Dropping the unit dim is a
    # layout-preserving view (unlike the dim-folding reshapes that kill
    # the async-DMA fast path), so the bank streams along its expert
    # axis; outputs reshape back below.
    lead1 = [p.ndim >= 3 and p.shape[0] == 1 for p in p_leaves]
    if transfer:
        sq = lambda t: t.reshape(t.shape[1:])  # noqa: E731
        p_leaves = [sq(p) if s else p for p, s in zip(p_leaves, lead1)]
        m_leaves = [sq(m) if s else m for m, s in zip(m_leaves, lead1)]
        n_leaves = [sq(n) if s else n for n, s in zip(n_leaves, lead1)]
        g_leaves = [sq(g) if s else g for g, s in zip(g_leaves, lead1)]
    # collect the scannable leaves into same-(vma, depth) groups so each
    # group streams as one fused scan (group_scanned)
    groups: dict = {}
    if transfer:
        for i, p_h in enumerate(p_leaves):
            if scannable(p_h):
                key, _ = token_for(p_h)
                groups.setdefault((key, p_h.shape[0]), []).append(i)
    out: list = [None] * len(g_leaves)
    for i, (g, p_h, m_h, n_h) in enumerate(
            zip(g_leaves, p_leaves, m_leaves, n_leaves)):
        if out[i] is not None:
            continue  # filled by an earlier member's fused group scan
        if not transfer:
            out[i] = leaf_plain(g, p_h, m_h, n_h)
            continue
        key, token = token_for(p_h)
        if scannable(p_h):
            members = groups[(key, p_h.shape[0])]
            os_, tokens[key] = group_scanned(members, token)
            for j, o in zip(members, os_):
                out[j] = o
        elif (grp := row_group(p_h)):
            o, tokens[key] = leaf_scanned_rows(g, p_h, m_h, n_h, token, grp)
            out[i] = o
        else:
            o, tokens[key] = leaf_whole(g, p_h, m_h, n_h, token)
            out[i] = o
    if transfer and any(lead1):
        out = [tuple(t.reshape((1,) + t.shape) for t in o) if s else o
               for o, s in zip(out, lead1)]
    # Under zero1 the compute-dtype params leave this function still
    # SHARDED over the zero1 axes (each process computed only its 1/dp);
    # the caller re-gathers them with a GSPMD sharding constraint outside
    # the shard_map — shard_map's varying-axes checker cannot statically
    # see that an all_gather of per-shard updates is replicated, while
    # the SPMD partitioner's resharding is invariant by construction.
    pick = lambda i: jax.tree.unflatten(  # noqa: E731
        treedef, [o[i] for o in out])
    new_state = OffloadAdamState(count=count, master=pick(0), mu=pick(1),
                                 nu=pick(2))
    return pick(3), new_state


def make_optimizer(t: TrainingConfig) -> optax.GradientTransformation:
    lr = make_lr(t)
    steps = [] if t.grad_clip_norm <= 0 else [optax.clip_by_global_norm(t.grad_clip_norm)]
    if t.adam_moments_dtype == "bfloat16":
        steps += [
            scale_by_adam_low_moments(t.adam_beta1, t.adam_beta2, t.adam_eps,
                                      jnp.bfloat16),
            optax.add_decayed_weights(t.weight_decay),
            optax.scale_by_learning_rate(lr),
        ]
    else:
        steps.append(
            optax.adamw(
                learning_rate=lr,
                b1=t.adam_beta1,
                b2=t.adam_beta2,
                eps=t.adam_eps,
                weight_decay=t.weight_decay,
            )
        )
    return optax.chain(*steps)
