"""Mixture-of-experts layer: top-k router + capacity-bounded dispatch +
expert parallelism over the 'ep' mesh axis.

Beyond the reference (SURVEY §2.2 marks EP/MoE absent) — designed TPU-first:

- **Static shapes** (GShard-style capacity; the path under ep > 1, whose
  all_to_all needs them): every expert processes exactly
  `capacity` token slots per device; overflow tokens are dropped from the
  expert path (their residual stream passes through unchanged — top-k
  combine just contributes 0), underflow slots compute on zeros. XLA sees
  one fixed [E, C, H] einsum program, no data-dependent shapes.
- **Routing**: scores over all router logits, softmax over them or the
  sigmoid of each (`scoring`; the DeepSeek-V3 lineage), the k largest
  chosen. `norm_topk_prob` (the published key) renormalizes the k gates to
  sum to 1 per token (Mixtral's rule, the default); false keeps the raw
  scores (OLMoE); `scale` (`routed_scaling_factor`) multiplies them. The
  load-balancing aux loss is the standard Switch/Mixtral
  `E * sum_e(frac_tokens_e * mean_router_prob_e)`.
- **A held share** (`held`: first index, count): the router scores every
  expert of the model, the banks hold `count` of them from `first` on (one
  chip of an expert-parallel group, served without its exchange). A pick
  that lands elsewhere keeps its gate's place in the renormalisation and
  adds nothing here: it joins the dead assignments' group behind the last
  held expert's, so no row is multiplied and no weight read on its account.
- **Dropless dispatch** (`capacity_factor=None`; what the model layer asks
  for whenever ep = 1): no capacity. Each
  assignment's slot within its expert plus the exclusive prefix of the
  group sizes is its row in an expert-sorted [N*k, H] buffer — one
  permutation in, three grouped matmuls over the ragged group sizes
  (`lax.ragged_dot`, which this chip's compiler lowers to its own grouped
  matmul kernel that walks only the rows present), one permutation out.
  No row of zeros is multiplied and no assignment can be dropped. The
  decode paths (`moe_mlp_served`) run the same mathematics through one
  Pallas kernel a layer (`ops/grouped_experts.py`): the compiler's kernel
  costs about 35 us a group however few rows it holds, which a decode
  step's 256 rows over 64 experts cannot pay.
- **Expert parallelism**: the expert bank [E, ...] is sharded over 'ep'
  (parallel/sharding.py). Dispatch builds per-device [E, C, H] slots, an
  `all_to_all` over 'ep' regroups them to [E/ep, ep*C, H] so each device
  runs only its experts over every device's slots, and a reverse
  `all_to_all` brings expert outputs home. With ep = 1 (or outside
  shard_map) both collectives are skipped and the math is identical.
- **TP composes**: the expert ffn dim is sharded over 'tp' like the dense
  MLP's; the caller's row-parallel exit hook psums the partial outputs.

The dispatch/combine uses scatter/gather by slot index (computed with one
[N*k, E] cumsum), not the [N, E, C] one-hot einsum of the original GShard —
the one-hot dispatch tensor is O(N*E*C) memory, which at train shapes
(N = 6k tokens) dwarfs the activations; slot scatter is O(N*k + E*C).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.ops.grouped_experts import (
    group_tiles, grouped_swiglu, max_tiles, row_tile,
)
from picotron_tpu.telemetry.scopes import scope


# The most bytes of one expert-sorted buffer of the served experts (rows x
# hidden): above it `moe_mlp_served` takes the tokens in blocks. Mellum2's
# largest prefill batch (8,192 tokens, 0.38 GB) stays one block.
MAX_SORTED_BYTES = 640 * 2**20


class Routing(NamedTuple):
    """Per-token routing decisions (all leading dim N = flattened tokens)."""

    expert_idx: jnp.ndarray   # [N, k] int32 — chosen expert per assignment
    gate: jnp.ndarray         # [N, k] fp32 — combine weight (top-k softmax)
    slot: jnp.ndarray         # [N, k] int32 — slot within the expert's
    #                           capacity buffer; >= capacity means dropped
    aux_loss: jnp.ndarray     # [] fp32 — load-balancing loss (unweighted)
    z_loss: jnp.ndarray       # [] fp32 — router z-loss (unweighted)
    counts: jnp.ndarray       # [E] int32 — assignments per expert (the
    #                           dropless dispatch's group sizes)
    zero_pick: Optional[jnp.ndarray] = None  # [N, k] bool — the pick fell
    #                           on a zero-compute expert (a router with some)


def topk_gates(logits, k: int, norm_topk_prob: bool,
               scoring: str = "softmax", scale: float = 1.0, bias=None):
    """(scores [N, E], chosen experts [N, k], gates [N, k]) of float32
    router logits: softmax over all E or the sigmoid of each (`scoring`),
    the k largest. Mixtral renormalizes the k selected scores to sum to 1;
    OLMoE (norm_topk_prob false) combines with the raw ones; the sigmoid
    law (DeepSeek-V3, Pangu Ultra MoE) renormalizes with 1e-20 under the
    sum and multiplies by `scale` (routed_scaling_factor). `bias` [E] (a
    selection bias: DeepSeek-V3's e_score_correction_bias, LongCat-Flash's):
    the k are the largest of score + bias, and their gates are made of the
    scores alone."""
    sigmoid = scoring == "sigmoid"
    probs = (jax.nn.sigmoid(logits) if sigmoid
             else jax.nn.softmax(logits, axis=-1))
    if bias is None:
        top_p, top_i = lax.top_k(probs, k)
    else:
        _, top_i = lax.top_k(probs + bias.astype(jnp.float32), k)
        top_p = jnp.take_along_axis(probs, top_i, axis=-1)
    gate = top_p
    if norm_topk_prob:
        total = jnp.sum(top_p, axis=-1, keepdims=True)
        gate = top_p / (total + 1e-20 if sigmoid else total)
    return probs, top_i, (gate * scale if scale != 1.0 else gate)


def route_topk(logits: jnp.ndarray, k: int,
               stat_axes: Optional[tuple] = None,
               norm_topk_prob: bool = True,
               live: Optional[jnp.ndarray] = None,
               scoring: str = "softmax", scale: float = 1.0,
               held: Optional[tuple] = None, bias=None,
               zero: int = 0) -> Routing:
    """Top-k routing with slots assigned in token order.

    `scoring`, `scale`, `bias`: the gates' law (`topk_gates`). `held`
    (first, count): the banks hold experts first .. first + count - 1 of the
    router's E. `expert_idx` is then an index into the banks, a pick that
    lands on an expert held elsewhere is a dead assignment as a dead row's
    are (below), and `counts` has count + 1 entries. `zero`: the router's
    last `zero` columns are zero-compute experts, behind the routed ones
    that `held` counts within. A pick of one is a dead assignment too (it
    lies in no held range, so it is in no group size and reads no bank);
    `zero_pick` says which picks they were, and the caller adds their
    gates times the token.

    `live` [N] bool (the serving programs: rows that carry a token): a row
    that is not live is assigned to no expert. Its k assignments go to a
    group of their own behind the last expert's (`counts` then has E + 1
    entries, the last the dead assignments), so they appear in no
    expert's group size, the grouped matmuls skip their rows, and no
    expert's weights are read on their account.

    logits: [N, E] fp32 router outputs. Slot assignment is deterministic in
    token order (first-come priority); the CALLER drops assignments whose
    slot lands beyond its capacity (moe_mlp's `keep = slot < cap`).

    `stat_axes` names mesh axes to pmean the aux statistics over (must be
    inside shard_map): the balance loss's f/P and the z-loss token mean then
    describe the GLOBAL batch, making the losses layout-exact — a per-device
    statistic differs across dp/cp/ep layouts by O(shard variance) (VERDICT
    r2 weak #4). None keeps per-device statistics.

    z-loss (ST-MoE, Zoph et al. 2022 eq. 5): mean(logsumexp(logits)^2) —
    penalizes router logit drift; returned unweighted, the caller applies
    its coefficient.
    """
    n, e = logits.shape
    logits = logits.astype(jnp.float32)
    probs, top_i, gate = topk_gates(logits, k, norm_topk_prob, scoring, scale,
                                    bias)
    picked = top_i  # in the router's numbering, for the balance statistic

    # slot_in_expert: for assignment (token t, choice j) -> how many earlier
    # assignments went to the same expert. Flatten [N, k] in token-major
    # order, one-hot over E, exclusive cumsum down the assignment axis.
    groups = e
    if held is not None and held != (0, e):
        first, groups = held
        here = (top_i >= first) & (top_i < first + groups)
        top_i = jnp.where(here, top_i - first, groups)
        live = here if live is None else here & live[:, None]
    elif live is not None:
        live = live[:, None]
    flat_e = top_i.reshape(-1)                                    # [N*k]
    if live is not None:
        top_i = jnp.where(live, top_i, groups)
        flat_e, groups = top_i.reshape(-1), groups + 1
    onehot = jax.nn.one_hot(flat_e, groups, dtype=jnp.int32)      # [N*k, E]
    prior = jnp.cumsum(onehot, axis=0) - onehot                   # exclusive
    slot = jnp.take_along_axis(prior, flat_e[:, None], axis=1)[:, 0]
    slot = slot.reshape(n, k)

    def stat_mean(v):
        return lax.pmean(v, stat_axes) if stat_axes else v

    # Load-balancing aux (Switch eq. 4 / Mixtral): E * sum_e f_e * P_e where
    # f_e = fraction of assignments routed to e, P_e = mean router prob.
    # Equal-sized token shards make pmean-of-means the exact global mean.
    f = stat_mean(
        jnp.mean(jax.nn.one_hot(picked, e, dtype=jnp.float32), axis=(0, 1)))
    # (with `live`, f and the z-loss count dead rows too: they are training
    # statistics, and the serving programs that pass `live` drop them)
    p = stat_mean(jnp.mean(probs, axis=0))
    aux = e * jnp.sum(f * p)

    z = jax.nn.logsumexp(logits, axis=-1)                         # [N]
    z_loss = stat_mean(jnp.mean(z * z))

    return Routing(top_i.astype(jnp.int32), gate, slot.astype(jnp.int32),
                   aux, z_loss, jnp.sum(onehot, axis=0),
                   picked >= e - zero if zero else None)


def add_zero_experts(out, flat, r: Routing, live=None):
    """`out` [N, H], the routed experts' part of the tokens' outputs, plus
    the zero-compute experts' part: the token itself times the summed gates
    of its picks of them (zeros, selected and not multiplied, for a row that
    is not `live`). Added in float32 and rounded once."""
    with scope("moe_zero"):
        gate = jnp.sum(jnp.where(r.zero_pick, r.gate, 0.0), axis=-1)
        term = gate[:, None] * flat.astype(jnp.float32)
        if live is not None:
            term = jnp.where(live[:, None], term, 0.0)
        return (out.astype(jnp.float32) + term).astype(out.dtype)


def _swiglu_experts(slots: jnp.ndarray, w_gate, w_up, w_down,
                    act=jax.nn.silu) -> jnp.ndarray:
    """Batched gated MLP over expert slots: [E_local, C', H] with weight
    banks [E_local, H, F] / [E_local, F, H]. bf16 MXU matmuls, fp32
    accumulation folded by XLA; mirrors the dense _mlp_block math (`act`
    is models.llama.mlp_act's choice — silu or gelu)."""
    dt = slots.dtype
    g = jnp.einsum("ech,ehf->ecf", slots, w_gate.astype(dt))
    u = jnp.einsum("ech,ehf->ecf", slots, w_up.astype(dt))
    return jnp.einsum("ecf,efh->ech", act(g) * u, w_down.astype(dt))


# The dropless dispatch's two permutations. `row` [N, k] is each
# assignment's row in the expert-sorted buffer and `inv` [N*k] its inverse
# (row r holds assignment inv[r], of token inv[r] // k). Each direction is
# written as a GATHER, and its transpose as the other one's gather instead
# of the scatter-add AD would emit: on this chip a row gather costs about a
# third of a row scatter (PERF.md, Findings PR 26).


@jax.custom_vjp
def _sorted_from_tokens(flat, row, inv):
    """flat [N, H] -> expert-sorted [N*k, H]: row r is its token's copy."""
    return flat[inv // row.shape[1]]


def _sorted_from_tokens_fwd(flat, row, inv):
    return flat[inv // row.shape[1]], row


def _sorted_from_tokens_bwd(row, d_sorted):
    with scope("moe_dispatch"):
        d_flat = jnp.sum(d_sorted[row].astype(jnp.float32), axis=1)
        return d_flat.astype(d_sorted.dtype), None, None


_sorted_from_tokens.defvjp(_sorted_from_tokens_fwd, _sorted_from_tokens_bwd)


@jax.custom_vjp
def _assignments_from_sorted(out_sorted, row, inv):
    """expert-sorted [N*k, H] -> [N, k, H]: each token's k expert outputs."""
    return out_sorted[row]


def _assignments_from_sorted_fwd(out_sorted, row, inv):
    return out_sorted[row], inv


def _assignments_from_sorted_bwd(inv, d_picked):
    with scope("moe_dispatch"):
        h = d_picked.shape[-1]
        return d_picked.reshape(-1, h)[inv], None, None


_assignments_from_sorted.defvjp(_assignments_from_sorted_fwd,
                                _assignments_from_sorted_bwd)


def _dropless_experts(flat, r: Routing, w_gate, w_up, w_down, act):
    """Every assignment through its expert, no capacity: flat [N, H] ->
    [N, H] (gates applied, summed over k). Requires the whole bank
    [E, H, F] / [E, F, H] on the device. `w_gate` None: the experts are not
    gated, down(act(up x)), two banks."""
    n, h = flat.shape
    k = r.expert_idx.shape[1]
    dt = flat.dtype
    with scope("moe_router"):
        # an assignment's row: its expert's first row (the exclusive prefix
        # of the group sizes) + its slot within the expert. Slots are in
        # token order, so the rows are a permutation of 0..N*k-1: no sort.
        offsets = jnp.cumsum(r.counts) - r.counts
        row = offsets[r.expert_idx] + r.slot                      # [N, k]
        inv = jnp.zeros((n * k,), jnp.int32).at[row.reshape(-1)].set(
            jnp.arange(n * k, dtype=jnp.int32), unique_indices=True)
    with scope("moe_dispatch"):
        xs = _sorted_from_tokens(flat, row, inv)                  # [N*k, H]
    with scope("moe_experts"):
        # the experts' group sizes: all of `counts`, less the dead rows'
        # group where the router was told of them (route_topk `live`): the
        # rows past the last group are selected away below
        sizes = r.counts[:w_up.shape[0]]
        u = lax.ragged_dot(xs, w_up.astype(dt), sizes)
        if w_gate is None:
            ys = lax.ragged_dot(act(u), w_down.astype(dt), sizes)
        else:
            g = lax.ragged_dot(xs, w_gate.astype(dt), sizes)
            ys = lax.ragged_dot(act(g) * u, w_down.astype(dt), sizes)
    with scope("moe_dispatch"):
        picked = _assignments_from_sorted(ys, row, inv)           # [N, k, H]
        if r.counts.shape[0] > w_up.shape[0]:
            # dead assignments (picks of experts held elsewhere, rows
            # without a token) lie past the last group, where the chip's
            # grouped matmul leaves whatever was there, not zeros (54 x the
            # block's output at 64 of 512 held: PERF.md section 6, PR 51)
            picked = jnp.where(
                (r.expert_idx < w_up.shape[0])[..., None], picked, 0)
        out = jnp.sum(picked.astype(jnp.float32) * r.gate[..., None], axis=1)
    return out.astype(dt)


def _grouped_experts(flat, r: Routing, live, w_gate, w_up, w_down, act,
                     layer):
    """`_dropless_experts` for the decode paths, through the grouped
    kernel (`ops/grouped_experts.py`): flat [N, H] -> ([N, H], the (row
    tile, expert) pairs the kernel visits). `r` routes the live rows' picks
    of held experts alone (`route_topk(live=..., held=...)`: every other
    assignment has expert index E); the banks are the stack's whole banks
    [L, E, H, F] / [L, E, F, H], of which `layer` (traced or not) is this
    layer: the kernel addresses its experts inside them, so no layer's bank
    is sliced out or copied, and an expert without rows is never read.

    The live assignments are permuted into expert order as above, except
    that an expert's rows start on a row tile (the tile is chosen from the
    static number of rows and experts, `row_tile`): a tile then belongs to
    one expert. A dead assignment has no place in the buffer and adds
    zeros, selected, not multiplied: what the buffer holds outside the
    live assignments' rows is never written and never read."""
    n, h = flat.shape
    k = r.expert_idx.shape[1]
    e = w_up.shape[1]
    dt = flat.dtype
    live = live[:, None] & (r.expert_idx < e)                      # [N, k]
    tm = row_tile(n * k, e)
    n_tiles = max_tiles(n * k, e, tm)
    with scope("moe_router"):
        first_row, tile_expert, visits = group_tiles(r.counts[:e], tm, n_tiles)
        # a dead assignment (expert index E) reads row 0 and is selected
        # away below; in the inverse it lands beyond the buffer and is dropped
        row = jnp.where(live,
                        first_row[jnp.minimum(r.expert_idx, e - 1)] + r.slot,
                        0)                                         # [N, k]
        at = jnp.arange(n * k, dtype=jnp.int32)
        inv = jnp.zeros((n_tiles * tm,), jnp.int32).at[
            jnp.where(live.reshape(-1), row.reshape(-1), n_tiles * tm + at)
        ].set(at, mode="drop", unique_indices=True)
    with scope("moe_dispatch"):
        xs = flat[inv // k]                                        # [T*tm, H]
    with scope("moe_experts"):
        ys = grouped_swiglu(xs, w_gate, w_up, w_down, tile_expert, visits,
                            layer, tm=tm, act=act)
    with scope("moe_dispatch"):
        picked = ys[row].astype(jnp.float32) * r.gate[..., None]  # [N, k, H]
        out = jnp.sum(jnp.where(live[..., None], picked, 0.0), axis=1)
    return out.astype(dt), visits


def _split_over_a_mesh(w) -> bool:
    """Whether the array a jitted program was handed lives on a mesh of
    more than one device (`generate.place_for_decode` with tp > 1 shards the
    banks on F): the compiler partitions `lax.ragged_dot`, and not a Pallas
    kernel."""
    return jax.typeof(w).sharding.mesh.size > 1


# jitted: the layers of a scanned period call it with the same shapes, so a
# program traces and lowers the block (and its Mosaic kernel) once, not once
# a layer: a second of every start of an engine with five programs
@functools.partial(jax.jit,
                   static_argnames=("top_k", "act", "norm_topk_prob",
                                    "scoring", "scale", "expert_first",
                                    "zero"))
def moe_mlp_served(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                   act, norm_topk_prob: bool, live, layer,
                   scoring: str = "softmax", scale: float = 1.0,
                   expert_first: int = 0, bias=None, zero: int = 0,
                   latent=None):
    """The routed experts of the decode paths (`generate`, the serve
    programs): `moe_mlp`'s router and dropless mathematics, no loss terms,
    and `live` [B, S] saying which rows carry a token (idle slots and chunk
    padding do not: they are routed nowhere and come out as zeros). The
    router scores every expert of the model (`router_w` [H, R]); the banks
    are the stack's whole banks [L, E, ...] of the E experts held on this
    device, `expert_first` .. `expert_first + E - 1` of the R (all of them
    where E = R), and `layer` this layer's index in them. `bias` [R]: the
    router's selection bias (`topk_gates`). `zero`: the router's last
    `zero` columns are zero-compute experts (the routed ones are the R -
    zero before them): a pick of one adds its gate times the token, here,
    whatever this device holds, and is in no group of the kernel. A pick that lands
    on an expert held elsewhere adds nothing here (a token whose picks are
    all elsewhere comes out as zeros, and its block output is the shared
    expert's alone), and nothing stands in for the absent devices. A
    token's experts depend on that token alone, so chunking a prompt
    differently changes nothing.

    One form at every number of rows (a batch whose expert-sorted buffer
    would pass MAX_SORTED_BYTES goes through in equal blocks of tokens):
    the live rows' assignments permuted
    into expert order and put through the grouped kernel
    (`_grouped_experts`), which visits the (row tile, expert) pairs that
    hold rows and reads no other expert. Only banks that a mesh splits
    (tp > 1) keep the compiler's grouped matmul, on this layer's slice of
    the stacks.

    Returns (out [B, S, H], counts [4] int32): the held experts at least
    one live row was routed to, which is what a step NEEDS of the expert
    banks; the (row tile, expert) pairs the kernel visited, which is what
    it read of them (an expert whose rows span two tiles is two visits);
    the live rows' picks that landed on held experts; and all their picks
    (rows x k). With `zero`, counts [6]: then the live rows' picks of
    zero-compute experts, and the live rows none of whose picks read a
    held bank (all of them zero-compute or held elsewhere).

    `latent` [B, S, W]: what the experts read in x's place (LatentMoE: the
    router scores the token, the experts a W-wide projection of it, banks
    [L, E, W, F] / [L, E, F, W]); the output is then W wide, and the caller's
    to project back. `w_gate` None: the experts are not gated, two banks."""
    b, s, _ = x.shape
    n, e = b * s, w_up.shape[1]
    into = x if latent is None else latent
    h = into.shape[-1]

    def block(args):
        tokens, flat, live = args
        with scope("moe_router"):
            logits = (tokens.astype(jnp.float32)
                      @ router_w.astype(jnp.float32))             # [N, R] fp32
            r = route_topk(logits, top_k, norm_topk_prob=norm_topk_prob,
                           live=live, scoring=scoring, scale=scale,
                           held=(expert_first, e), bias=bias, zero=zero)
            touched = jnp.sum(r.counts[:e] > 0).astype(jnp.int32)
            picks = [jnp.sum(r.counts[:e]), jnp.sum(live) * top_k]
            if zero:
                picks += [jnp.sum(r.zero_pick & live[:, None]),
                          jnp.sum(live & jnp.all(r.expert_idx >= e, axis=-1))]
            picks = jnp.stack(picks).astype(jnp.int32)
        if _split_over_a_mesh(w_up):
            out = _dropless_experts(
                flat, r, *(None if w is None else lax.dynamic_index_in_dim(
                    w, layer, 0, keepdims=False)
                    for w in (w_gate, w_up, w_down)), act)
            visits = touched  # the compiler's kernel walks a group once
        else:
            out, visits = _grouped_experts(flat, r, live, w_gate, w_up,
                                           w_down, act, layer)
        if zero:
            out = add_zero_experts(out, flat, r, live)
        return out, jnp.concatenate([jnp.stack([touched, visits]), picks])

    # a prefill batch whose expert-sorted buffer would pass
    # MAX_SORTED_BYTES goes through in equal blocks of tokens, one after
    # the other (16 rows of 1,024 tokens at openPangu-Ultra's 7,680 wide
    # rows are 2 x 1.9 GB otherwise); the counts are summed over the
    # blocks, an expert two blocks touch counted twice (only a decode
    # step's counts are read, and a decode step is one block)
    blocks = 1
    while (n * top_k * h * x.dtype.itemsize > blocks * MAX_SORTED_BYTES
           and n % (2 * blocks) == 0):
        blocks *= 2
    # (unrolled, not a `lax.map`: loop-invariant banks that ride a while
    # loop's operands were copied whole by the compiler, 3 x 1.9 GB)
    tokens = x.reshape(blocks, n // blocks, -1)
    flat, live = into.reshape(blocks, n // blocks, h), live.reshape(blocks, -1)
    outs = [block((tokens[j], flat[j], live[j])) for j in range(blocks)]
    return (jnp.concatenate([o for o, _ in outs]).reshape(b, s, h),
            sum(c for _, c in outs))


def moe_mlp(
    x: jnp.ndarray,
    router_w: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: Optional[float] = 1.25,
    act=jax.nn.silu,
    ep_axis: Optional[str] = None,
    router_aux_coef: float = 0.0,
    router_z_coef: float = 0.0,
    stat_axes: Optional[tuple] = None,
    norm_topk_prob: bool = True,
    scoring: str = "softmax",
    scale: float = 1.0,
    expert_first: int = 0,
    bias=None,
    zero: int = 0,
    latent=None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """MoE feed-forward. x: [B, S, H]; router_w: [H, E]; expert banks
    [E_local, H, F] / [E_local, F, H] (E_local = E/ep under expert
    parallelism — the bank arrives pre-sharded inside shard_map).

    Returns (out [B, S, H] — partial over tp like the dense down-proj,
    aux [] — the PRE-WEIGHTED router loss `aux_coef * balance +
    z_coef * z`, drop_frac [] — fraction of routing assignments dropped by
    the capacity bound, an observability scalar the train log reports;
    capacity drops are otherwise silent; without a capacity it is the
    share of the expert-sorted rows that no group of the grouped matmuls
    covers, 0 whenever the group sizes sum to N * k,
    load [] — the busiest expert's assignments over the mean expert's, on
    this device: 1 is a perfectly balanced router, E / k one that sends
    every token to the same k). `capacity_factor=None` takes the dropless
    grouped-matmul dispatch (`_dropless_experts`; ep = 1 only).
    `ep_axis` names the mesh axis for
    the all_to_all pair; None = no expert parallelism (single device, or
    ep = 1). `stat_axes` makes the router statistics global (route_topk).
    `scoring`, `scale`: the gates' law (`topk_gates`). A router wider than
    `num_experts` (router_w [H, R], R > E) is a held share: the banks are
    experts `expert_first` .. `expert_first + E - 1` of the R, dropless
    dispatch only, and a pick elsewhere adds nothing (`route_topk` held).
    `bias`, `zero`: a selection bias and zero-compute experts, as
    `moe_mlp_served` describes them (a held share in this sense: the
    router is wider than the banks). `latent` [B, S, W] and `w_gate` None: as
    `moe_mlp_served` describes them too (the experts read `latent`, the
    router x; the output is W wide; dropless dispatch only).

    Recompute contract: every op here is a deterministic function of
    (x, weights) — fp32 router logits, top_k, the slot cumsum, the
    capacity bound — so re-running this block on the same inputs
    reproduces the forward's routing bit-identically. Both remat (the AD
    engine under the dots/dots_attn policies) and the fused grad engine's
    backward segment VJP (parallel/fused_bwd.py) rely on that: they
    recompute the whole expert block from the saved layer input instead
    of saving the [E, C, H] dispatch buffers, and a nondeterministic
    tie-break here would silently diverge their gradients.
    """
    b, s, h = x.shape
    n = b * s
    e = num_experts
    ep = lax.psum(1, ep_axis) if ep_axis is not None else 1
    e_local = w_up.shape[0]
    assert e_local * ep == e, (e_local, ep, e)
    flat = x.reshape(n, h)
    with scope("moe_router"):
        logits = (flat.astype(jnp.float32)
                  @ router_w.astype(jnp.float32))                 # [N, E] fp32
        share = router_w.shape[1] != e
        r = route_topk(logits, top_k, stat_axes=stat_axes,
                       norm_topk_prob=norm_topk_prob, scoring=scoring,
                       scale=scale,
                       held=(expert_first, e) if share else None,
                       bias=bias, zero=zero)
        aux = router_aux_coef * r.aux_loss + router_z_coef * r.z_loss
        load = (jnp.max(r.counts[:e]).astype(jnp.float32)
                * (e / (n * top_k)))

    if capacity_factor is None:
        assert ep == 1 and e_local == e, "dropless dispatch needs ep = 1"
        if latent is not None:  # the experts' own input and width
            flat = latent.reshape(n, -1)
        out = _dropless_experts(flat, r, w_gate, w_up, w_down, act)
        if zero:
            out = add_zero_experts(out, flat, r)
        # a grouped matmul leaves the rows past its last group zero, so an
        # assignment is dropped exactly when the group sizes fall short
        # (`counts` includes a held share's picks elsewhere: not drops)
        drop_frac = ((n * top_k - jnp.sum(r.counts)).astype(jnp.float32)
                     / (n * top_k))
        return out.reshape(b, s, -1), aux, drop_frac, load
    assert not share, "a held share of the experts is dropless (ep = 1)"
    assert latent is None and w_gate is not None, (
        "experts on a latent, or without a gate, are dropless (ep = 1)")

    # Per-device capacity per expert, padded to a lane-friendly multiple.
    cap = int(capacity_factor * top_k * n / e) + 1
    cap = -(-cap // 8) * 8

    # ---- dispatch: scatter assignments into [E, cap, H] slot buffers ----
    with scope("moe_dispatch"):
        keep = r.slot < cap                                       # [N, k]
        drop_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
        eidx = r.expert_idx.reshape(-1)                           # [N*k]
        sidx = jnp.where(keep, r.slot, cap - 1).reshape(-1)
        kflat = keep.reshape(-1)
        tok = jnp.repeat(jnp.arange(n), top_k)                    # [N*k]
        buf = jnp.zeros((e, cap, h), x.dtype)
        buf = buf.at[eidx, sidx].add(
            flat[tok] * kflat[:, None].astype(x.dtype), mode="drop")

    # ---- expert parallelism: regroup slots so each device runs only its
    # local experts over every ep-peer's slots ----
    if ep_axis is not None and ep > 1:
        # [E, cap, H] -> split E into (ep, E_local) -> all_to_all: trade the
        # ep groups so this device holds [E_local, ep*cap, H].
        buf = buf.reshape(ep, e_local, cap, h)
        buf = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                             tiled=False)                         # [ep, El, cap, H]
        buf = jnp.moveaxis(buf, 0, 1).reshape(e_local, ep * cap, h)

    with scope("moe_experts"):
        out_slots = _swiglu_experts(buf, w_gate, w_up, w_down, act=act)

    if ep_axis is not None and ep > 1:
        out_slots = out_slots.reshape(e_local, ep, cap, h)
        out_slots = jnp.moveaxis(out_slots, 1, 0)                 # [ep, El, cap, H]
        out_slots = lax.all_to_all(out_slots, ep_axis, split_axis=0,
                                   concat_axis=0, tiled=False)
        out_slots = out_slots.reshape(e, cap, h)

    # ---- combine: gather each assignment's slot, weight by its gate.
    # tok is arange(n) repeated k times in order, so the "scatter-add back
    # to tokens" is just a dense sum over the k assignment column ----
    with scope("moe_dispatch"):
        picked = out_slots[eidx, sidx]                            # [N*k, H]
        w = (r.gate.reshape(-1) * kflat).astype(x.dtype)[:, None]
        out = (picked * w).reshape(n, top_k, h).sum(axis=1)
    return out.reshape(b, s, h), aux, drop_frac, load
