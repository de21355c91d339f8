"""MPMD pipeline executor: per-stage jitted programs + a host-side schedule.

The SPMD 1f1b engine (parallel/pp.py) runs the whole pipeline as ONE jitted
lockstep scan: every device steps through every tick, and until PR 65 ran
every tick's traced units whether its schedule slot was active or not, so an
IDLE tick cost a full forward+backward unit (PERF.md r4 measured 64.7 ms/tick
with an implied bubble of 7.0 ticks at pp=4; since PR 65 a tick costs its
slowest stage's live units, see `schedule_stats`). This module is the fix from "Scaling Deep Learning Training with MPMD
Pipeline Parallelism" (arxiv 2412.14374): compile one program per pipeline
stage (each tracing ONLY its own layer block) and drive them from a host-side
schedule table — an idle tick dispatches nothing and costs ~0, which is what
makes interleaved (and zero-bubble-style) schedules profitable at all.

Architecture (selected by `pipeline.executor: mpmd`; the SPMD scan stays as
the reference twin under `spmd`):

- **Schedule tables** (`build_schedule`) — a greedy dependency-driven tick
  simulator generalizing pp.py's closed-form 1f1b table (fwd of microbatch m
  at stage s on tick m+s, bwd on tick m+2(pp-1)-s — the greedy simulator
  with backward-priority reproduces exactly that makespan) to gpipe,
  interleaved (v virtual layer chunks per device group) and zero-bubble
  (ZB-H1-style split-backward, accounting only) schedules, and to the edge
  shapes (n_micro < pp, n_micro == 1, pp == 1 passthrough) the closed form
  never met.
- **Per-stage programs** — each virtual stage j (layer block j of V = pp*v)
  gets a forward and a backward `jit(shard_map)` over its device group's
  submesh (axes dp/ep/cp/tp — no 'pp' axis: stage identity is baked in, so
  the head matmul is traced only into the last stage's program and pp.py's
  lax.cond gating disappears). The backward recomputes the stage interior
  from the saved stage *input* under `jax.vjp` — the same manual-VJP math as
  the SPMD 1f1b engine, honoring the configured remat policy — and adds
  per-microbatch grads (psummed over the data axes) into a donated fp32
  accumulator, so every program is compile-once by construction
  (analysis/variants.py proves it).
- **Ring buffers** — boundary activations/cotangents move between stage
  submeshes via explicit `jax.device_put` (committed shardings end to end),
  so a step is `jax.transfer_guard("disallow")`-clean: nothing implicit
  crosses hosts or devices.
- **Finish program** — one jitted step-tail over the FULL mesh: concatenate
  the per-chunk layer grads back into the P('pp')-sharded global tree, sum
  multi-owner leaves (a tied embedding earns grads on both the first and the
  last stage), divide by the token count, and run the optax update + guard
  logic of the SPMD step, donating the TrainState.

Known costs, accepted for this revision and recorded in PERF.md: per-step
param re-slicing + chunk grads crossing to the full mesh replicate boundary
tensors over 'pp' (aliasing the chunk shards into the global arrays is a
future optimization), and per-microbatch grads pay their data-axes psum per
backward call instead of once per step (ga x more collective launches, each
1/ga the payload).
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from picotron_tpu import compat
from picotron_tpu.config import Config
from picotron_tpu.resilience import chaos, watchdog
from picotron_tpu.telemetry import bus as telemetry_bus
from picotron_tpu.telemetry.flightdeck.tracer import TID_PP_BASE
from picotron_tpu.telemetry.spans import span
from picotron_tpu.mesh import MeshEnv
from picotron_tpu.models.llama import (
    compute_dtype, embed, final_hidden, head_weight, model_rope_tables,
    pp_layer_placement, run_layers,
)
from picotron_tpu.optimizer import make_optimizer
from picotron_tpu.parallel.api import make_parallel_ctx
from picotron_tpu.parallel.pp import (
    _cast_varying_like, _vary_over, pp_1f1b_ticks, units_in_branches,
)
from picotron_tpu.parallel.sharding import batch_spec, param_shardings, param_specs
from picotron_tpu.train_step import TrainState, guard_nonfinite

# Submesh axes of one stage's device group: the full mesh minus 'pp'.
SUB_AXES = ("dp", "ep", "cp", "tp")

# Executable schedules ("zb" is accounting-only: the split-backward programs
# it needs are not built; config.validate() rejects it as a pipeline.schedule
# value, bench --pp-tick-sweep reports its tick accounting).
SCHEDULES = ("1f1b", "gpipe", "interleaved", "zb")

# Hook for per-stage tick timing (telemetry): when set, a sampled step calls
# it with ({group: [op_seconds, ...]}, python_step_index) after its schedule
# walk. train.py installs the telemetry emitter; sampling cadence comes from
# PICOTRON_PP_TICK_SAMPLE (0 = never; N = every Nth step), so the
# block_until_ready the timing needs never rides an unsampled step.
on_stage_times = None


# ---------------------------------------------------------------------------
# Schedule tables
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TickOp:
    """One scheduled unit: device group `group` runs `op` for microbatch
    `mb` of virtual stage `vstage` at host tick `tick`. Ops: "F" forward,
    "B" backward (combined), "BX"/"BW" the zero-bubble split (input-grad /
    weight-grad halves)."""

    tick: int
    group: int
    op: str
    mb: int
    vstage: int


def build_schedule(kind: str, n_micro: int, pp: int,
                   interleave: int = 1) -> list[TickOp]:
    """Greedy dependency-driven schedule table, sorted by (tick, group).

    Model: V = pp * interleave virtual stages; virtual stage j runs on
    device group j % pp (Megatron's round-robin chunk assignment); each
    group executes at most one op per tick and every op costs one tick.
    Dependencies: F(m, j) needs F(m, j-1); B(m, j) needs F(m, j) and
    B(m, j+1); the zero-bubble split relaxes the weight half — BX carries
    the B dependencies, BW needs only BX(m, j) and fills bubbles at the
    lowest priority (ZB-H1's observation).

    Priorities: "gpipe" runs any ready forward first (the AFAB dependency
    shape); everything else runs ready backwards first — which reproduces
    the canonical 1f1b warmup/steady/cooldown (stage s forwards pp-1-s
    extra microbatches before its first backward falls ready) and its
    2n + 2(pp-1) tick makespan, without hand-writing the three phases.
    Edge shapes fall out of the dependency rules: n_micro < pp and
    n_micro == 1 just drain early, pp == 1 degenerates to an alternating
    F/B stream (or all-F-then-all-B for gpipe) with zero bubble.
    """
    if kind not in SCHEDULES:
        raise ValueError(f"unknown schedule kind {kind!r}; one of {SCHEDULES}")
    if n_micro < 1 or pp < 1:
        raise ValueError(
            f"need n_micro >= 1 and pp >= 1, got {n_micro}/{pp}")
    v = interleave if kind == "interleaved" else 1
    if interleave != 1 and kind != "interleaved":
        raise ValueError(
            f"interleave={interleave} only applies to the 'interleaved' "
            f"schedule, got kind={kind!r}")
    if v < 1:
        raise ValueError(f"interleave must be >= 1, got {interleave}")
    V = pp * v
    split_b = kind == "zb"

    f_done: dict = {}   # (mb, vstage) -> first tick the result is usable
    b_done: dict = {}   # combined B, or BX under the zb split
    w_done: dict = {}   # BW under the zb split
    ops: list[TickOp] = []
    total = n_micro * V * (3 if split_b else 2)
    t = 0
    max_ticks = 8 * total + 16  # generous; greedy always progresses
    while len(ops) < total and t < max_ticks:
        for g in range(pp):
            stages = range(g, V, pp)
            ready_f = [(m, j) for j in stages for m in range(n_micro)
                       if (m, j) not in f_done
                       and (j == 0 or f_done.get((m, j - 1), t + 1) <= t)]
            ready_b = [(m, j) for j in stages for m in range(n_micro)
                       if (m, j) not in b_done
                       and f_done.get((m, j), t + 1) <= t
                       and (j == V - 1 or b_done.get((m, j + 1), t + 1) <= t)]
            ready_w = [(m, j) for j in stages for m in range(n_micro)
                       if split_b and (m, j) not in w_done
                       and b_done.get((m, j), t + 1) <= t]
            # F tie-break: deepest virtual stage first under interleaving
            # (advance in-flight microbatches to completion so backwards
            # fall ready early); plain schedules have one vstage per group.
            f_key = (lambda o: (-o[1], o[0])) if v > 1 else (
                lambda o: (o[0], o[1]))
            b_key = lambda o: (o[0], -o[1])  # noqa: E731 — FIFO microbatches
            pick = None
            if kind == "gpipe":
                if ready_f:
                    pick, kop = min(ready_f, key=f_key), "F"
                elif ready_b:
                    pick, kop = min(ready_b, key=b_key), "B"
            else:
                if ready_b:
                    pick, kop = min(ready_b, key=b_key), "BX" if split_b else "B"
                elif ready_f:
                    pick, kop = min(ready_f, key=f_key), "F"
                elif ready_w:
                    pick, kop = min(ready_w, key=b_key), "BW"
            if pick is None:
                continue
            m, j = pick
            ops.append(TickOp(tick=t, group=g, op=kop, mb=m, vstage=j))
            done = {"F": f_done, "B": b_done, "BX": b_done, "BW": w_done}[kop]
            done[(m, j)] = t + 1
        t += 1
    if len(ops) < total:
        raise RuntimeError(
            f"schedule simulator stalled at {len(ops)}/{total} ops "
            f"(kind={kind}, n={n_micro}, pp={pp}, v={interleave})")
    problems = lint_schedule(ops, n_micro, pp, interleave, kind=kind)
    if problems:
        raise ScheduleBufferError(
            f"schedule table fails the static lint (kind={kind}, "
            f"n={n_micro}, pp={pp}, v={interleave}): "
            f"{'; '.join(problems)}")
    return ops


def lint_schedule(table: list, n_micro: int, pp: int,
                  interleave: int = 1, kind: str = None) -> list[str]:
    """Static schedule-table lint: walk the table with the exact
    produce/consume rules `_run_schedule` applies at runtime and return
    every problem as a string — the `ScheduleBufferError` contract proven
    BEFORE any schedule runs, instead of after a wasted walk.

    Three rule families:

    - **consume-before-produce**: an op that pops an activation /
      cotangent / saved-input buffer no earlier op filled would KeyError
      mid-walk at runtime (a dependency-broken table);
    - **balanced produce/consume**: the end-of-walk live set must be
      empty per (vstage, mb) buffer key — leftovers are orphaned tensors
      some dispatched op produced and nothing consumed (a truncated
      table), exactly what the runtime assert at the end of
      `_run_schedule` reports today;
    - **bounded live set**: the peak number of saved stage inputs per
      virtual stage must not exceed the schedule's in-flight budget —
      n_micro for gpipe (all-forward-then-all-backward legitimately
      saves everything), min(n_micro, 2*pp*v) per vstage otherwise.
      The greedy backward-first simulator's warmup depth at early
      stages reaches 2*pp - 3 (measured across pp up to 16), so the
      bound tracks twice the pipeline depth, widened by the interleave
      factor. A table over budget would OOM activations on hardware
      even though it drains cleanly.

    The zb split's BX carries B's buffer rules and BW is buffer-neutral
    (weight-grad only). Exposed through `shardcheck --variants`
    (analysis/variants.py) so a schedule bug is a static finding."""
    V = pp * (interleave if interleave > 1 else 1)
    if V < 2:
        return []
    problems: list[str] = []
    names = {"x": "activation", "s": "saved-input", "g": "cotangent"}
    live: dict = {}            # ("x"|"s"|"g", vstage, mb) -> True
    peak_saved: dict = {}      # vstage -> peak live saved-inputs
    n_saved: dict = {}

    def produce(b, j, m):
        live[(b, j, m)] = True
        if b == "s":
            n_saved[j] = n_saved.get(j, 0) + 1
            peak_saved[j] = max(peak_saved.get(j, 0), n_saved[j])

    def consume(b, j, m, op):
        if not live.pop((b, j, m), None):
            problems.append(
                f"{op.op}@tick{op.tick} (vstage={op.vstage}, mb={op.mb}) "
                f"consumes {names[b]} (vstage={j}, mb={m}) never produced")
        elif b == "s":
            n_saved[j] -= 1

    for op in sorted(table, key=lambda o: (o.tick, o.group)):
        j, m = op.vstage, op.mb
        if op.op == "F":
            if j == 0:
                produce("x", j + 1, m)
            elif j == V - 1:
                consume("x", j, m, op)
                produce("s", j, m)
            else:
                consume("x", j, m, op)
                produce("s", j, m)
                produce("x", j + 1, m)
        elif op.op in ("B", "BX"):
            if j == V - 1:
                consume("s", j, m, op)
                produce("g", j - 1, m)
            elif j == 0:
                consume("g", j, m, op)
            else:
                consume("s", j, m, op)
                consume("g", j, m, op)
                produce("g", j - 1, m)
        # BW: weight-grad half, touches no boundary buffers
    leftover = sorted(live)
    if leftover:
        keys = "; ".join(f"{names[b]} (vstage={j}, mb={m})"
                         for b, j, m in leftover)
        problems.append(
            f"{len(leftover)} live boundary buffer(s) at end of walk — "
            f"produced but never consumed: {keys}")
    v = interleave if interleave > 1 else 1
    budget = n_micro if kind == "gpipe" else min(n_micro, 2 * pp * v)
    for j, peak in sorted(peak_saved.items()):
        if peak > budget:
            problems.append(
                f"vstage {j} holds {peak} saved inputs at peak, over the "
                f"schedule's in-flight budget of {budget} — the table "
                f"defers backwards past the {kind or 'schedule'} "
                f"in-flight depth (activation OOM on hardware)")
    return problems


def schedule_stats(kind: str, n_micro: int, pp: int,
                   interleave: int = 1, gated: bool = True) -> dict:
    """Tick accounting for a schedule, in full units (1 unit = one stage's
    forward + backward for one microbatch — a steady SPMD tick's cost).

    kind="spmd" prices the lockstep scan twin closed-form: n + 2(pp-1)
    ticks of two unit slots a stage, the forward unit (a quarter of a full
    unit) and the backward unit (three quarters: it runs the forward again,
    and each backward half costs about a forward — the ZB-H1 assumption
    below). `gated` (pp.units_in_branches: the 1F1B engine, no ring over cp)
    says each unit runs only where its stage holds a microbatch for it, so a
    tick costs its slowest stage's live units and the first and last pp-1
    ticks are partial; otherwise EVERY tick is a full unit on every device
    and bubble = 2(pp-1) units. `units_live` counts the slots that hold a
    microbatch a later unit needs (the last stage's forward unit never
    does: its backward unit runs that forward), `units_skipped` the slots
    whose unit does not run (none ungated: the rest run on zeros).
    MPMD schedules are priced off the simulated table: makespan
    ticks / ticks-per-unit, where a full unit spans 2v chunk-ops (3v under
    the zb split, whose halves each cost ~a forward). busy is always
    n_micro units; the bubble is the rest.
    """
    if kind == "spmd":
        ticks = pp_1f1b_ticks(n_micro, pp)
        # [tick][stage] -> (forward unit live, backward unit live)
        live = [[(s < pp - 1 and 0 <= t - s < n_micro,
                  0 <= t - 2 * (pp - 1) + s < n_micro) for s in range(pp)]
                for t in range(ticks)]
        units_live = sum(f + b for row in live for f, b in row)
        makespan = (sum(max(0.25 * f + 0.75 * b for f, b in row)
                        for row in live)
                    # (pp 1 has no fill or drain, and its one stage's tick is
                    # the backward unit alone: the full unit of this account)
                    if gated and pp > 1 else float(ticks))
        bubble = makespan - n_micro
        return {
            "kind": kind, "n_micro": n_micro, "pp": pp, "interleave": 1,
            "ticks": ticks, "makespan_units": makespan,
            "busy_units": float(n_micro), "bubble_units": bubble,
            "bubble_fraction": bubble / makespan if makespan else 0.0,
            "units_live": units_live,
            "units_skipped": 2 * pp * ticks - units_live if gated else 0,
        }
    table = build_schedule(kind, n_micro, pp, interleave)
    v = interleave if kind == "interleaved" else 1
    ticks = max(op.tick for op in table) + 1
    per_unit = (3 if kind == "zb" else 2) * v
    makespan = ticks / per_unit
    bubble = makespan - n_micro
    return {
        "kind": kind, "n_micro": n_micro, "pp": pp, "interleave": interleave,
        "ticks": ticks, "makespan_units": makespan,
        "busy_units": float(n_micro), "bubble_units": bubble,
        "bubble_fraction": bubble / makespan if makespan else 0.0,
    }


def pipeline_bubble_fraction(cfg: Config) -> float:
    """Static schedule-derived idle fraction of a step for this config (0.0
    when pp == 1) — what telemetry books under the 'pp_bubble' goodput
    category. For the SPMD executor this is the lockstep scan's accounting
    (partial fill and drain ticks where the engine gates its units, the
    full price elsewhere); for MPMD it comes off the simulated table."""
    pp = cfg.distributed.pp_size
    if pp <= 1:
        return 0.0
    n = cfg.training.gradient_accumulation_steps
    kind = ("spmd" if cfg.pipeline.executor == "spmd"
            else cfg.pipeline.schedule)
    return schedule_stats(kind, n, pp, cfg.pipeline.interleave,
                          units_in_branches(cfg))["bubble_fraction"]


# ---------------------------------------------------------------------------
# Stage decomposition
# ---------------------------------------------------------------------------


def _stage_blocks(cfg: Config) -> list[tuple[int, int, np.ndarray | None]]:
    """Per virtual stage j: (row_lo, row_hi, real_mask_or_None) into the
    padded global layer stack. Block j is the j-th contiguous chunk of
    padded rows; its real-slot mask comes from the same static placement
    rule as pp_layer_placement (group k's real layers fill the leading
    counts[k] of its `per` rows). For dense models the mask is only
    documentation — pad layers are exact identities with zero grads — but
    it keeps the chunk programs aligned with the SPMD layout."""
    L, pp = cfg.model.num_hidden_layers, cfg.distributed.pp_size
    v = cfg.pipeline.interleave
    padded, _ = pp_layer_placement(L, pp)
    per = padded // pp
    V = pp * v
    if padded % V != 0:
        raise ValueError(
            f"interleave {v} does not divide the per-stage slot count "
            f"{per} (padded stack {padded}, pp {pp})")
    Lv = padded // V
    counts = np.asarray([L // pp + (1 if k < L % pp else 0)
                         for k in range(pp)])
    blocks = []
    for j in range(V):
        rows = np.arange(j * Lv, (j + 1) * Lv)
        mask = (rows % per) < counts[rows // per]
        blocks.append((j * Lv, (j + 1) * Lv,
                       None if mask.all() else mask.astype(np.float32)))
    return blocks


def _stage_meshes(menv: MeshEnv) -> list[Mesh]:
    """One submesh per device group: the full mesh's pp=g slice, re-meshed
    over (dp, ep, cp, tp)."""
    dev = menv.mesh.devices  # (dp, pp, ep, cp, tp)
    return [Mesh(dev[:, g], SUB_AXES) for g in range(dev.shape[1])]


def _strip_pp(spec: P) -> P:
    return P(*[None if part == "pp" else part for part in spec])


def _chunk_param_specs(cfg: Config, j: int, V: int) -> dict:
    """PartitionSpec tree of virtual stage j's parameter chunk on its
    submesh: the layer-block slice (leading 'pp' dropped — the block lives
    whole on the group), plus the embedding on the first stage and the
    final norm + head on the last (the tied-embedding case puts the
    embedding on BOTH end stages; the finish program sums their grads)."""
    full = param_specs(cfg)
    layers = jax.tree.map(_strip_pp, full["layers"],
                          is_leaf=lambda x: isinstance(x, P))
    specs: dict = {"layers": layers}
    tied = "lm_head" not in full
    if j == 0:
        specs["embedding"] = full["embedding"]
    if j == V - 1:
        specs["final_norm"] = full["final_norm"]
        if tied:
            specs["embedding"] = full["embedding"]
        else:
            specs["lm_head"] = full["lm_head"]
    return specs


def _vary_over_data(params):
    """Params retyped to vary over the data axes, for differentiation: the
    vjp then yields this device's partial grads and `_sub_data_psum` is the
    one reduction. (AD of a data-invariant param ends in its own psum, and
    the explicit one would count the grads dp x — api._device_grads.)"""
    return jax.tree.map(lambda p: _vary_over(p, {"dp", "ep", "cp"}), params)


def _sub_data_psum(grads, cfg: Config):
    """Per-microbatch grad reduction over the submesh's data axes. No
    per-leaf exceptions: MoE (the expert-bank case _data_axes_psum special-
    cases) is rejected for the MPMD executor at config time."""
    return jax.tree.map(lambda g: lax.psum(g, ("dp", "ep", "cp")), grads)


def _accumulate(acc, g_params):
    return jax.tree.map(
        lambda a, g: jnp.add(a, _cast_varying_like(g.astype(jnp.float32), a)),
        acc, g_params)


# ---------------------------------------------------------------------------
# Per-stage programs
# ---------------------------------------------------------------------------


class _StagePrograms:
    """Compiled surface of one virtual stage: fwd / bwd / zeros jits plus
    the committed shardings its feeds must carry. Built once per train-step
    construction; every call site feeds identical abstract signatures, so
    each jit mints exactly one executable (proven by analysis/variants.py).
    """

    def __init__(self, cfg: Config, submesh: Mesh, j: int, V: int,
                 block, global_mesh: Mesh):
        lo, hi, mask = block
        m = cfg.model
        self.j, self.V = j, V
        self.first, self.last = j == 0, j == V - 1
        first, last = self.first, self.last
        pspecs = _chunk_param_specs(cfg, j, V)
        self.param_shardings = jax.tree.map(
            lambda s: NamedSharding(submesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P))
        xspec = P(("dp", "ep"), "cp", None)
        bspec = batch_spec()
        self.x_sharding = NamedSharding(submesh, xspec)
        self.batch_sharding = NamedSharding(submesh, bspec)
        self.scalar_sharding = NamedSharding(submesh, P())
        tied = "lm_head" not in param_specs(cfg)
        self.tied = tied

        def ctx_for():
            ctx = make_parallel_ctx(cfg)
            # The composed ctx's layer_is_real reads lax.axis_index('pp'),
            # which does not exist on the submesh — replace it with this
            # chunk's STATIC mask (None when every slot is real; dense pad
            # slots are exact identities either way).
            lir = (None if mask is None
                   else (lambda n_slots: jnp.asarray(mask)))
            return dataclasses.replace(ctx, layer_is_real=lir)

        def run_chunk(params, x):
            ctx = ctx_for()
            cos, sin = model_rope_tables(m)
            y, _ = run_layers(params["layers"], x, m, ctx, cos, sin)
            return y

        def embed_chunk(params, mb_ids):
            ctx = ctx_for()
            cos, sin = model_rope_tables(m)
            x = embed(params, mb_ids, m, ctx)
            y, _ = run_layers(params["layers"], x, m, ctx, cos, sin)
            return y

        def chunk_loss(params, x, mb_tgt):
            ctx = ctx_for()
            cos, sin = model_rope_tables(m)
            y, _ = run_layers(params["layers"], x, m, ctx, cos, sin)
            hf = final_hidden(params, y, m)
            total, count = ctx.head_ce(hf, head_weight(params), mb_tgt)
            return total, count

        sm = partial(compat.shard_map, mesh=submesh)
        P_ = P()

        if first:

            def fwd_body(params, ids, idx):
                mb = lax.dynamic_index_in_dim(ids, idx, 0, keepdims=False)
                return embed_chunk(params, mb)

            self.fwd = jax.jit(sm(fwd_body,
                                  in_specs=(pspecs, bspec, P_),
                                  out_specs=xspec))

            def bwd_body(params, ids, idx, g_in, acc):
                mb = lax.dynamic_index_in_dim(ids, idx, 0, keepdims=False)
                y, vjp_fn = jax.vjp(lambda p: embed_chunk(p, mb),
                                    _vary_over_data(params))
                (g_params,) = vjp_fn(_cast_varying_like(g_in, y))
                return _accumulate(acc, _sub_data_psum(g_params, cfg))

            self.bwd = jax.jit(
                sm(bwd_body,
                   in_specs=(pspecs, bspec, P_, xspec, pspecs),
                   out_specs=pspecs),
                donate_argnums=(4,))
        elif last:

            def fwd_body(params, x_in, tgt, idx, nll_acc, cnt_acc):
                mb_tgt = lax.dynamic_index_in_dim(tgt, idx, 0,
                                                  keepdims=False)
                total, count = chunk_loss(params, x_in, mb_tgt)
                total = lax.psum(total, ("dp", "ep", "cp"))
                count = lax.psum(count, ("dp", "ep", "cp"))
                return total, count, nll_acc + total, cnt_acc + count

            self.fwd = jax.jit(
                sm(fwd_body,
                   in_specs=(pspecs, xspec, bspec, P_, P_, P_),
                   out_specs=(P_, P_, P_, P_)),
                donate_argnums=(4, 5))

            def bwd_body(params, x_saved, tgt, idx, acc):
                mb_tgt = lax.dynamic_index_in_dim(tgt, idx, 0,
                                                  keepdims=False)

                def f(p, x):
                    total, _ = chunk_loss(p, x, mb_tgt)
                    return total
                total, vjp_fn = jax.vjp(f, _vary_over_data(params), x_saved)
                one = _vary_over(jnp.ones((), jnp.float32),
                                 set(compat.vma(total)))
                g_params, g_x = vjp_fn(one)
                return _accumulate(acc, _sub_data_psum(g_params, cfg)), g_x

            self.bwd = jax.jit(
                sm(bwd_body,
                   in_specs=(pspecs, xspec, bspec, P_, pspecs),
                   out_specs=(pspecs, xspec)),
                donate_argnums=(4,))
        else:

            def fwd_body(params, x_in):
                return run_chunk(params, x_in)

            self.fwd = jax.jit(sm(fwd_body,
                                  in_specs=(pspecs, xspec),
                                  out_specs=xspec))

            def bwd_body(params, x_saved, g_in, acc):
                y, vjp_fn = jax.vjp(run_chunk, _vary_over_data(params),
                                    x_saved)
                g_params, g_x = vjp_fn(_cast_varying_like(g_in, y))
                return _accumulate(acc, _sub_data_psum(g_params, cfg)), g_x

            self.bwd = jax.jit(
                sm(bwd_body,
                   in_specs=(pspecs, xspec, xspec, pspecs),
                   out_specs=(pspecs, xspec)),
                donate_argnums=(3,))

        # Grad-accumulator factory: fresh fp32 zeros each step (the previous
        # step's accumulators were donated into their last bwd call).
        abs_chunk = jax.tree.map(
            lambda s: None, pspecs, is_leaf=lambda x: isinstance(x, P))
        del abs_chunk  # structure documented via pspecs; zeros built below
        self._slicer = _make_slicer(cfg, lo, hi, first, last, tied)
        abs_params = _abstract_global_params(cfg)
        abs_chunk = jax.eval_shape(self._slicer, abs_params)
        self.abstract_params = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abs_chunk, self.param_shardings)
        self.zeros = jax.jit(
            lambda: jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), abs_chunk),
            out_shardings=self.param_shardings)

    def slice_params(self, global_params):
        """Chunk this stage's params off the global tree (a compile-once
        global-mesh jit) and commit them onto the stage submesh via an
        explicit device_put."""
        return jax.device_put(self._slicer(global_params),
                              self.param_shardings)


def _abstract_global_params(cfg: Config):
    from picotron_tpu.parallel.api import abstract_master

    return abstract_master(cfg)


def _make_slicer(cfg: Config, lo: int, hi: int, first: bool, last: bool,
                 tied: bool):
    def slicer(params):
        out = {"layers": jax.tree.map(
            lambda x: lax.slice_in_dim(x, lo, hi, axis=0),
            params["layers"])}
        if first or (last and tied):
            out["embedding"] = params["embedding"]
        if last:
            out["final_norm"] = params["final_norm"]
            if not tied:
                out["lm_head"] = params["lm_head"]
        return out
    return jax.jit(slicer)


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------


def _build_stages(cfg: Config, menv: MeshEnv):
    pp, v = cfg.distributed.pp_size, cfg.pipeline.interleave
    V = pp * v
    blocks = _stage_blocks(cfg)
    meshes = _stage_meshes(menv)
    return [_StagePrograms(cfg, meshes[j % pp], j, V, blocks[j], menv.mesh)
            for j in range(V)]


class ScheduleBufferError(RuntimeError):
    """The schedule walk finished with live boundary buffers: some
    dispatched op produced an activation/cotangent/saved-input that no
    later op consumed. Always a schedule-table bug (truncated table,
    broken dependency edge) — named so the diagnostic lists exactly
    which (vstage, mb) keys were orphaned instead of a bare assert."""


def _index_arrays(n_micro: int, sharding: NamedSharding):
    """The microbatch index feed, staged ONCE: n committed int32 scalars on
    the stage submesh. Re-minting them per step would be a host-to-device
    transfer inside the schedule walk (transfer_guard-dirty) for values
    that never change."""
    return [jax.device_put(np.int32(i), sharding) for i in range(n_micro)]


def _run_schedule(stages, table, chunk_params, accs, state_scalars,
                  ids_s, tgt_s, idx_first, idx_last, timings=None,
                  step=None):
    """Walk the schedule table in (tick, group) order, dispatching stage
    programs and moving boundary tensors with explicit device_put. Returns
    (accs, nll_acc, cnt_acc, per_microbatch_nll, per_microbatch_cnt).

    Mid-schedule fault surface: each dispatched op heartbeats the
    watchdog with the live (stage, tick, op, mb) — a stall inside the
    walk is reported as that op, not a bare stack dump — and calls the
    `schedule_tick` chaos point so a `#TICK` event can deliver a
    SIGTERM/hang at a named op. A SIGTERM landing mid-walk only sets the
    preemption flag (the handler runs no consequential Python), so the
    walk always drains to the step boundary: the emergency checkpoint
    the driver then writes only ever contains fully-accumulated state,
    never a half-walked schedule's partial grads."""
    V = len(stages)
    nll_acc, cnt_acc = state_scalars
    # Each op is one `pp.<stage>.<op>` span on its device group's lane
    # (telemetry/spans.py). With a span tracer installed each op is also
    # synced, like the sampled-timings path, so that span durations are
    # real tick times (an opt-in perturbation, same as
    # PICOTRON_PP_TICK_SAMPLE); without one the span times the dispatch.
    synced = (timings is not None
              or getattr(telemetry_bus.active(), "tracer", None) is not None)
    xbuf: dict = {}    # (vstage, mb) -> inbound activation
    xsave: dict = {}   # (vstage, mb) -> saved stage input for the backward
    gbuf: dict = {}    # (vstage, mb) -> inbound cotangent
    mb_nll: dict = {}
    mb_cnt: dict = {}
    for op in table:
        j, mb = op.vstage, op.mb
        st = stages[j]
        if watchdog.active():
            watchdog.touch(f"pp_schedule stage={j} tick={op.tick} "
                           f"op={op.op} mb={mb}", step)
        if step is not None:
            chaos.fire("schedule_tick", step=step,
                       tick=op.tick, stage=j, op=op.op, mb=mb)
        with span(f"pp.{j}.{op.op}", tid=TID_PP_BASE + op.group,
                  tick=op.tick, mb=mb, step=step) as sp:
            if op.op == "F":
                if st.first:
                    y = st.fwd(chunk_params[j], ids_s, idx_first[mb])
                    xbuf[(j + 1, mb)] = jax.device_put(
                        y, stages[j + 1].x_sharding)
                elif st.last:
                    x_in = xbuf.pop((j, mb))
                    xsave[(j, mb)] = x_in
                    nll_mb, cnt_mb, nll_acc, cnt_acc = st.fwd(
                        chunk_params[j], x_in, tgt_s, idx_last[mb],
                        nll_acc, cnt_acc)
                    mb_nll[mb], mb_cnt[mb] = nll_mb, cnt_mb
                else:
                    x_in = xbuf.pop((j, mb))
                    xsave[(j, mb)] = x_in
                    y = st.fwd(chunk_params[j], x_in)
                    xbuf[(j + 1, mb)] = jax.device_put(
                        y, stages[j + 1].x_sharding)
            elif op.op == "B":
                if st.last:
                    accs[j], g_x = st.bwd(chunk_params[j], xsave.pop((j, mb)),
                                          tgt_s, idx_last[mb], accs[j])
                    gbuf[(j - 1, mb)] = jax.device_put(
                        g_x, stages[j - 1].x_sharding)
                elif st.first:
                    accs[j] = st.bwd(chunk_params[j], ids_s, idx_first[mb],
                                     gbuf.pop((j, mb)), accs[j])
                else:
                    accs[j], g_x = st.bwd(chunk_params[j], xsave.pop((j, mb)),
                                          gbuf.pop((j, mb)), accs[j])
                    gbuf[(j - 1, mb)] = jax.device_put(
                        g_x, stages[j - 1].x_sharding)
            else:  # pragma: no cover — zb tables are accounting-only
                raise RuntimeError(
                    f"op {op.op!r} has no executable stage program")
            if synced:
                jax.block_until_ready(accs[j] if op.op == "B" else
                                      (nll_acc if st.last else
                                       xbuf.get((j + 1, mb))))
        if timings is not None:
            timings.setdefault(op.group, []).append(sp.secs)
    leftover = ([f"activation (vstage={j}, mb={m})" for j, m in sorted(xbuf)]
                + [f"cotangent (vstage={j}, mb={m})" for j, m in sorted(gbuf)]
                + [f"saved-input (vstage={j}, mb={m})"
                   for j, m in sorted(xsave)])
    if leftover:
        raise ScheduleBufferError(
            f"schedule walk left {len(leftover)} live boundary buffer(s) "
            f"— the table dispatched ops that produced tensors no later "
            f"op consumed (a truncated or dependency-broken table): "
            f"{'; '.join(leftover)}")
    return accs, nll_acc, cnt_acc, mb_nll, mb_cnt


def make_mpmd_train_step(cfg: Config, menv: MeshEnv,
                         inject_nan: bool = False):
    """Build the MPMD (state, batch) -> (state, metrics) step: a host
    function (NOT a jit) whose schedule walk dispatches the per-stage
    programs and whose tail runs the jitted global finish/update. Same
    contract as the SPMD `make_train_step` — train.py cannot tell them
    apart (that is the point of the executor knob)."""
    cfg.validate()
    if cfg.pipeline.executor != "mpmd":
        raise ValueError("make_mpmd_train_step needs pipeline.executor='mpmd'")
    n_micro = cfg.training.gradient_accumulation_steps
    pp, v = cfg.distributed.pp_size, cfg.pipeline.interleave
    table = build_schedule(cfg.pipeline.schedule, n_micro, pp, v)
    stages = _build_stages(cfg, menv)
    V = len(stages)

    ids_sharding = stages[0].batch_sharding
    tgt_sharding = stages[V - 1].batch_sharding
    idx_first = _index_arrays(n_micro, stages[0].scalar_sharding)
    idx_last = _index_arrays(n_micro, stages[V - 1].scalar_sharding)
    zero_scalars = jax.jit(
        lambda: (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        out_shardings=(stages[V - 1].scalar_sharding,
                       stages[V - 1].scalar_sharding))
    finish = _make_finish(cfg, menv, inject_nan)
    global_chunk_shardings = [
        jax.tree.map(lambda s: NamedSharding(menv.mesh, s),
                     _chunk_param_specs(cfg, j, V),
                     is_leaf=lambda x: isinstance(x, P))
        for j in range(V)]
    replicated = NamedSharding(menv.mesh, P())
    sample = int(os.environ.get("PICOTRON_PP_TICK_SAMPLE", "0") or 0)
    host_step = [0]

    def step(state: TrainState, batch):
        ids, tgt = batch
        chunk_params = [stages[j].slice_params(state.params)
                        for j in range(V)]
        accs = [stages[j].zeros() for j in range(V)]
        ids_s = jax.device_put(ids, ids_sharding)
        tgt_s = jax.device_put(tgt, tgt_sharding)
        host_step[0] += 1
        step_no = host_step[0]
        if chaos.controller().has_tick_events():
            # #TICK chaos keys on the TRAINING step number (identical on
            # every process / across resumes); resolve it exactly via a
            # host sync this path otherwise avoids. Without tick events
            # the process-local invocation index is plenty for the
            # watchdog's diagnostic beats.
            step_no = int(jax.device_get(state.step)) + 1
        timings = ({} if on_stage_times is not None and sample > 0
                   and host_step[0] % sample == 0 else None)
        accs, nll_acc, cnt_acc, _, _ = _run_schedule(
            stages, table, chunk_params, accs, zero_scalars(),
            ids_s, tgt_s, idx_first, idx_last, timings=timings,
            step=step_no)
        if timings is not None and on_stage_times is not None:
            on_stage_times(timings, host_step[0])
        grads = tuple(
            jax.device_put(accs[j], global_chunk_shardings[j])
            for j in range(V))
        nll_g = jax.device_put(nll_acc, replicated)
        cnt_g = jax.device_put(cnt_acc, replicated)
        return finish(state, grads, nll_g, cnt_g)

    return step


def mpmd_microbatch_losses(cfg: Config, menv: MeshEnv, params, batch):
    """Forward-only probe: per-microbatch (nll_sum, count) through the
    per-stage programs — what the parity tests pin against the SPMD twin's
    per-microbatch reference. Returns (nll[n_micro], count[n_micro]) as
    numpy arrays."""
    cfg.validate()
    n_micro = cfg.training.gradient_accumulation_steps
    pp, v = cfg.distributed.pp_size, cfg.pipeline.interleave
    table = [op for op in build_schedule(
        cfg.pipeline.schedule if cfg.pipeline.executor == "mpmd" else "1f1b",
        n_micro, pp, v) if op.op == "F"]
    stages = _build_stages(cfg, menv)
    V = len(stages)
    idx_first = _index_arrays(n_micro, stages[0].scalar_sharding)
    idx_last = _index_arrays(n_micro, stages[V - 1].scalar_sharding)
    ids, tgt = batch
    ids_s = jax.device_put(ids, stages[0].batch_sharding)
    tgt_s = jax.device_put(tgt, stages[V - 1].batch_sharding)
    chunk_params = [stages[j].slice_params(params) for j in range(V)]
    nll_acc, cnt_acc = jax.jit(
        lambda: (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        out_shardings=(stages[V - 1].scalar_sharding,
                       stages[V - 1].scalar_sharding))()
    xbuf: dict = {}
    mb_nll = [None] * n_micro
    mb_cnt = [None] * n_micro
    for op in table:
        j, mb = op.vstage, op.mb
        st = stages[j]
        if st.first:
            y = st.fwd(chunk_params[j], ids_s, idx_first[mb])
            xbuf[(j + 1, mb)] = jax.device_put(y, stages[j + 1].x_sharding)
        elif st.last:
            nll_mb, cnt_mb, nll_acc, cnt_acc = st.fwd(
                chunk_params[j], xbuf.pop((j, mb)), tgt_s, idx_last[mb],
                nll_acc, cnt_acc)
            mb_nll[mb], mb_cnt[mb] = nll_mb, cnt_mb
        else:
            y = st.fwd(chunk_params[j], xbuf.pop((j, mb)))
            xbuf[(j + 1, mb)] = jax.device_put(y, stages[j + 1].x_sharding)
    return (np.asarray([float(x) for x in mb_nll]),
            np.asarray([int(x) for x in mb_cnt]))


def _make_finish(cfg: Config, menv: MeshEnv, inject_nan: bool):
    """The jitted step tail on the FULL mesh: reassemble the global grad
    tree from the per-chunk accumulators, normalize by the token count, and
    run the same optax update + divergence-guard logic as the SPMD step
    (api.make_train_step's standard branch), donating the TrainState."""
    mesh = menv.mesh
    layer_shardings = param_shardings(cfg, mesh)["layers"]
    opt = make_optimizer(cfg.training)
    guards_on = cfg.resilience.guard_policy != "off"
    guard_skip = cfg.resilience.guard_policy == "skip"
    tied = cfg.model.tie_word_embeddings

    def _assemble(sh, *xs):
        # Rebuild the P('pp')-sharded layer stack by dynamic_update_slice
        # into a constrained zeros buffer, NOT jnp.concatenate: this XLA's
        # SPMD partitioner double-counts replicated inputs when a concat's
        # result is resharded along the concat axis (each dp replica's copy
        # lands as a contribution instead of a copy — values scale by
        # dp_size). DUS of a replicated update into a sharded operand
        # lowers correctly.
        rows = sum(x.shape[0] for x in xs)
        y = jax.lax.with_sharding_constraint(
            jnp.zeros((rows,) + xs[0].shape[1:], xs[0].dtype), sh)
        off = 0
        for x in xs:
            y = jax.lax.with_sharding_constraint(
                lax.dynamic_update_slice(y, x, (off,) + (0,) * (x.ndim - 1)),
                sh)
            off += x.shape[0]
        return y

    @partial(jax.jit, donate_argnums=(0,))
    def finish(state: TrainState, chunk_grads, nll_total, count):
        layers = jax.tree.map(_assemble, layer_shardings,
                              *[g["layers"] for g in chunk_grads])
        grads = {"layers": layers,
                 "final_norm": chunk_grads[-1]["final_norm"]}
        if tied:
            # the embedding earns grads on BOTH end stages (lookup on the
            # first, head matmul on the last) — disjoint contributions sum
            grads["embedding"] = (chunk_grads[0]["embedding"]
                                  + chunk_grads[-1]["embedding"])
        else:
            grads["embedding"] = chunk_grads[0]["embedding"]
            grads["lm_head"] = chunk_grads[-1]["lm_head"]
        count = jnp.maximum(count, 1)
        grads = jax.tree.map(lambda g: g / count, grads)
        loss = nll_total / count
        if inject_nan:
            nan = jnp.float32(jnp.nan)
            grads = jax.tree.map(lambda g: g + nan.astype(g.dtype), grads)
            loss = loss + nan
        metrics = {"loss": loss}
        if guards_on:
            gnorm = optax.global_norm(grads)
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            metrics["grad_norm"] = gnorm
            metrics["nonfinite"] = 1.0 - ok.astype(jnp.float32)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        if guards_on and guard_skip:
            new_params = guard_nonfinite(ok, new_params, state.params)
            opt_state = guard_nonfinite(ok, opt_state, state.opt_state)
        return TrainState(new_params, opt_state, state.step + 1), metrics

    return finish


# ---------------------------------------------------------------------------
# Variant-prover surface (analysis/variants.py / tools/shardcheck.py)
# ---------------------------------------------------------------------------


def mpmd_entry_feeds(cfg: Config, menv: MeshEnv) -> dict:
    """{entry_name: [abstract argument tuple per scheduled call]} for every
    per-stage program of this config's schedule — what the variant prover
    audits to certify each stage program compiles exactly once. Every feed
    is a committed ShapeDtypeStruct tree (shardings included), enumerated
    per call the schedule actually makes, so a stage whose calls disagree
    in abstract signature (a second executable) is caught, not assumed."""
    cfg.validate()
    n_micro = cfg.training.gradient_accumulation_steps
    pp, v = cfg.distributed.pp_size, cfg.pipeline.interleave
    table = build_schedule(cfg.pipeline.schedule, n_micro, pp, v)
    stages = _build_stages(cfg, menv)
    V = len(stages)
    m = cfg.model
    mbs = cfg.training.micro_batch_size
    d = cfg.distributed
    batch_shape = (n_micro, mbs * d.dp_size * d.ep_size,
                   cfg.training.seq_length)

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    feeds: dict[str, list] = {}
    for j in range(V):
        st = stages[j]
        p_abs = st.abstract_params
        acc_abs = jax.tree.map(
            lambda a, s: sds(a.shape, jnp.float32, s),
            p_abs, st.param_shardings)
        x_abs = sds((mbs * d.dp_size * d.ep_size,
                     cfg.training.seq_length, m.hidden_size),
                    compute_dtype(m), st.x_sharding)
        ids_abs = sds(batch_shape, jnp.int32, st.batch_sharding)
        idx_abs = sds((), jnp.int32, st.scalar_sharding)
        s_f32 = sds((), jnp.float32, st.scalar_sharding)
        s_i32 = sds((), jnp.int32, st.scalar_sharding)
        fkey, bkey = f"mpmd_stage{j}_fwd", f"mpmd_stage{j}_bwd"
        feeds[fkey], feeds[bkey] = [], []
        for op in table:
            if op.vstage != j:
                continue
            if op.op == "F":
                if st.first:
                    feeds[fkey].append((p_abs, ids_abs, idx_abs))
                elif st.last:
                    feeds[fkey].append(
                        (p_abs, x_abs, ids_abs, idx_abs, s_f32, s_i32))
                else:
                    feeds[fkey].append((p_abs, x_abs))
            else:
                if st.first:
                    feeds[bkey].append(
                        (p_abs, ids_abs, idx_abs, x_abs, acc_abs))
                elif st.last:
                    feeds[bkey].append(
                        (p_abs, x_abs, ids_abs, idx_abs, acc_abs))
                else:
                    feeds[bkey].append((p_abs, x_abs, x_abs, acc_abs))
    return feeds
