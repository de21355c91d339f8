"""Runner `train_step_moe`: the program's training step for a model with
sparse experts, as the trainer runs it, checked against `reference_moe.py`.

The set-up and the timed loop are `train_step.py`'s, unchanged (`Config` from
the configuration file's own blocks, `MeshEnv.from_config`,
`init_sharded_state`, `make_train_step`; chained donated steps, one wait at
the end); `train_step.py` imports `reference` by name, which has no experts,
so this model needs a runner of its own. What differs is `correct` and three
facts.

`correct`, after the window and after `device_report()`:

1. the first step's loss (cross-entropy + both auxiliary terms) against
   `reference_moe` on the same tokens and weights;
2. logits: the program's own forward (`models.llama.forward`, bf16, Pallas
   attention, the dropless dispatch, inside the cell's mesh) against
   `reference_moe` at 256 seeded positions of one sequence of the first batch,
   positions with a near-tie between the reference's k-th and (k+1)-th router
   probability left out: the distance at every kept position, and the share
   in the program's error of two faults a distance cannot see. Twice: at the
   initial weights (a balanced router, an expert block the size of bf16's
   rounding), and at the weights the run ends with (`state.params` after the
   last step: the router has collapsed onto few experts, so the grouped
   matmuls walk the skewed groups the window ran, and the block's output is
   no longer small);
3. every assignment inside a group of the grouped matmuls in every step
   (`moe_drop_frac` 0: the group sizes sum to tokens x k, so no row of the
   expert-sorted buffer lies past the last group), no non-finite loss, and
   the first batch's loss fallen by `MIN_LOSS_FALL` when it comes round again.

Facts returned beside `train_step`'s: `moe_load_max_over_mean` and
`moe_drop_frac` (one sample a step: the step's metrics; the metric
`moe_load_max_over_mean.train` reads the first through `fact_stat`),
`moe_shape` (for the expert roofline).
"""

from __future__ import annotations

import math
import time

import numpy as np

# Readings on the chip (`tools/tolerance_probe_moe.py` and this runner's own
# lines, PERF.md Findings PR 27; two sequences and two runs, 256 positions each).
#
# 1. The loss. With an untied head and the program's initialisation the first
# loss is ln(50,304) + 0.2 whatever the layer computes: the reference's loss
# moves by 2.5e-6 of itself with the k-th expert left out, 7e-6 with QK-norm
# skipped, 1.8e-5 with the gates renormalised, 1.4e-5 in float8, each with
# either sign. So this check proves the head, the cross-entropy and the size of
# the auxiliary terms (0.0287 of 11.02: either term missing moves it by 9e-4 or
# more, transformers' scale of the balance term by 6e-3) and little else; the
# layer is check 2's. The program sat 1.1e-6 and 7.1e-7 from the reference.
LOSS_RTOL = 1e-5

# 2. The logits, per compared position r: e_r = |program_r - reference_r|_2 /
# |reference_r|_2 over the vocabulary.
# (a) `correct` needs the largest e_r over the kept positions under
# LOGITS_RTOL. The program (bf16 activations and weights, bf16 logits) reads
# 3.25e-3 to 3.54e-3 at every kept position: rounding noise of one size. A
# reference with the gates renormalised reads 2.4e-2 to 3.2e-2 (its worst row 7
# times the tolerance), with matmul operands rounded to float8_e4m3fn, the
# nearest precision below the bfloat16 the configuration states, 2.5e-2 to
# 2.8e-2 (6 times), with QK-norm skipped 5.3e-3 to 5.8e-2 (13 times), with a
# non-causal mask up to 0.105 (23 times). Two wrong references are NOT outside
# it: the chip's default matmul precision (2.3e-3 to 2.5e-3, the same as
# operands rounded to bfloat16: that is the precision the program itself
# computes in, so it sits as close as the program does) and the k-th expert
# left out (2.2e-3 to 3.9e-3: with these weights the 8th expert carries 0.02 of
# a block whose whole output is a hundredth of the residual stream, the size of
# bf16's rounding). Hence (b).
LOGITS_ROWS = 256
LOGITS_RTOL = 4.5e-3
# A position is left out where the reference's k-th and (k+1)-th largest
# router probabilities differ by less than this in log: the program's router
# reads bf16 hidden states, and where it picks the other expert the position
# reads e_r 4.9e-3 to 5.5e-3 (another, equally valid, model there). Every such
# position seen had a gap under 0.005; the margin is twice that. It leaves out
# 48 to 60 of 256 positions (the median gap is 0.03).
TIE_MARGIN = 0.01
# of the LOGITS_ROWS positions at most this share may be left out
MAX_TIES = 0.5
# (b) The share of a fault in the program's error: with d_r = program_r -
# reference_r and c_r = (the reference with the fault) - reference_r,
# sum_r <d_r, c_r> / sum_r <c_r, c_r> over the kept positions. Rounding noise is
# not aligned with such a c, so a right program reads 0 (measured -0.0012 to
# +0.0001 for these two, +0.004 for float8 operands; +0.52 for bfloat16
# operands, which the program has);
# a program with the fault reads 1. It tells apart what a norm cannot: the
# missing k-th expert, and QK-norm at the positions where its effect is small.
FAULTS = {"expert k of k left out": dict(drop_last_expert=True),
          "QK-norm skipped": dict(skip_qk_norm=True)}
FAULT_SHARE = 0.25
# (c) The same comparison at the weights the run ends with (about 95 steps on
# the cycle of 4 batches: loss 0.2-0.4, the busiest expert at 3.0-5.3 times
# the mean over the compared positions, the k chosen experts holding 0.7 of the
# probability mass where they held 0.28). The block's output is no longer
# small, so a position where the program's router picks the other expert at a
# gap past TIE_MARGIN is far (3e-2 to 1e-1; none or one of some 235 kept
# positions in each of 8 runs), where at the initial weights it read 5e-3: the
# statistic is therefore the 95th percentile of e_r over the kept positions,
# not the largest. The program reads 5.92e-3 to 7.27e-3 (median
# 4.0e-3 to 4.5e-3; 9 runs and 4 probe sequences). The reference with float8
# operands reads 7.0e-2 to 7.4e-2 (4.7 times the tolerance; its nearest
# position 1.7e-2), with the k-th expert left out 8.5e-2 to 1.27e-1 (5.7
# times: outside by distance here, as it cannot be at the initial weights),
# with the gates renormalised 0.80 to 1.04, with QK-norm skipped 0.94 to
# 0.98, with a non-causal mask 0.69 to 0.72. The chip's default precision is
# again as near as the program (5.2e-3 to 5.5e-3). The fault shares read
# -0.0020 to +0.0060 and -0.0004 to +0.0007.
END_PERCENTILE = 95
LOGITS_RTOL_END = 1.5e-2

# 3. The update (`train_step.py`'s rule and reason: the batches are a cycle
# of 4, so step 5 sees step 1's batch after four updates). Measured fall 0.095.
MIN_LOSS_FALL = 0.02

SPANS = ("train.dispatch", "train.wait")


def program_logits_fn(cfg, menv):
    """jit of the program's forward inside the cell's mesh, as the step runs
    it (same `ParallelCtx`: Pallas attention, the cell's dispatch):
    (params, ids [b, s]) -> logits [b, s, V] in the compute dtype."""
    import jax
    from jax.sharding import PartitionSpec as P

    from picotron_tpu import compat
    from picotron_tpu.models.llama import forward
    from picotron_tpu.parallel.api import make_parallel_ctx
    from picotron_tpu.parallel.sharding import param_specs

    def on_device(params, ids):
        return forward(params, ids, cfg.model, make_parallel_ctx(cfg))

    return jax.jit(compat.shard_map(
        on_device, mesh=menv.mesh,
        in_specs=(param_specs(cfg), P(("dp", "ep"), "cp")),
        out_specs=P(("dp", "ep"), "cp", "tp")))


def row_errors(got, want) -> np.ndarray:
    """e_r of the module comment, for [rows, V] arrays."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)


def fault_share(got, want, wrong) -> float:
    """The module comment's 2 (b), for [rows, V] arrays; 0 where the fault
    changes nothing (a model without the mechanism)."""
    got, want, wrong = (np.asarray(a, np.float64) for a in (got, want, wrong))
    c = wrong - want
    return float(((got - want) * c).sum() / max((c * c).sum(), 1e-300))


def tie_rows(probs, k: int, margin: float) -> np.ndarray:
    """[rows] bool: in some layer the k-th and (k+1)-th largest of `probs`
    [L, rows, E] lie within `margin` in log."""
    top = -np.sort(-np.asarray(probs, np.float64), axis=-1)
    return (np.log(top[..., k - 1] / top[..., k]) < margin).any(axis=0)


def moe_counters(metrics):
    """A step's two expert counters, still on the device."""
    return metrics["moe_drop_frac"], metrics["moe_load_max_over_mean"]


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import reference_moe
    from picotron_tpu.config import config_from_dict
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.parallel.api import (attention_path, init_sharded_state,
                                           make_train_step)
    from picotron_tpu.parallel.sharding import param_shardings

    c = ctx.config
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "training")})
    t, m = cfg.training, c["model"]
    for k in reference_moe.SIZES:  # the reference reads the file, not the program's presets
        if not hasattr(cfg.model, k):
            raise SystemExit(f"train_step_moe: this program has no model.{k}")
        if getattr(cfg.model, k) != m[k]:
            raise SystemExit(f"train_step_moe: model.{k} differs between the file and the program")
    if cfg.model.num_hidden_layers % cfg.distributed.pp_size:
        raise SystemExit("train_step_moe: layers must divide evenly over pp stages")
    menv = MeshEnv.from_config(cfg)
    if menv.world_size != ctx.chips:
        raise SystemExit(f"train_step_moe: the layout has {menv.world_size} devices, "
                         f"the cell {ctx.chips}")
    key = jax.random.key(ctx.seed31(0))
    state = init_sharded_state(cfg, menv, key)
    step = make_train_step(cfg, menv)
    ctx.log(f"attention={attention_path(cfg)} grad_engine={t.grad_engine} "
            f"remat={t.remat_policy} ep={cfg.distributed.ep_size} "
            f"mesh={dict(menv.mesh.shape)}")

    # a fixed cycle of 4 distinct token batches, drawn on the device
    ga = t.gradient_accumulation_steps
    b_global = t.micro_batch_size * cfg.distributed.dp_size
    sharding = menv.batch_sharding()
    toks = jax.jit(
        lambda k: jax.random.randint(k, (4, ga, b_global, t.seq_length + 1), 0,
                                     cfg.model.vocab_size, jnp.int32))(
        jax.random.key(ctx.seed31(1)))
    batches = [(jax.device_put(toks[i, ..., :-1], sharding),
                jax.device_put(toks[i, ..., 1:], sharding)) for i in range(4)]
    first_ids, first_tgt = (np.asarray(toks[0, ..., :-1]), np.asarray(toks[0, ..., 1:]))
    del toks
    tokens_per_step = ga * b_global * t.seq_length

    # warm-up: 2 steps; the second, timed, sizes the window
    state, metrics = step(state, batches[0])
    first_loss = metrics["loss"]
    counters = [moe_counters(metrics)]
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    state, metrics = step(state, batches[1])
    jax.block_until_ready(metrics)
    step_s = time.perf_counter() - t0
    counters.append(moe_counters(metrics))
    n_steps = max(math.ceil(ctx.seconds / step_s), 3)  # to step 5, for `correct`
    i_next = 2

    # the window: N chained donated steps, one wait at the end
    ctx.window_starts()
    losses = []
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step(state, batches[i_next % 4])
        losses.append(metrics["loss"])
        counters.append(moe_counters(metrics))
        i_next += 1
    jax.block_until_ready(metrics)
    elapsed = time.perf_counter() - t0
    in_window = ctx.window_ends()

    layers_here = cfg.model.num_hidden_layers // cfg.distributed.pp_size
    facts = dict(
        tokens_per_s_per_chip=tokens_per_step * n_steps / elapsed / ctx.chips,
        tokens_per_step=tokens_per_step, model=m, seq=t.seq_length,
        attempted=n_steps, compiles_in_window=in_window["compiles"], spans=SPANS,
        # one fwd, one dq, one dkv call per layer this device runs, per microbatch
        flash_calls_per_step=ga * layers_here,
        flash_shape=dict(batch=t.micro_batch_size,
                         heads=cfg.model.num_attention_heads // cfg.distributed.tp_size,
                         kv_heads=max(cfg.model.num_key_value_heads
                                      // cfg.distributed.tp_size, 1),
                         seq=t.seq_length // cfg.distributed.cp_size,
                         d=cfg.model.head_dim),
        # one expert block per layer this device runs, per microbatch
        moe_shape=dict(tokens=t.micro_batch_size * t.seq_length // cfg.distributed.cp_size,
                       blocks_per_step=ga * layers_here),
    )

    if ctx.trace:
        # per-step walls, each ending in a wait, outside the trace
        walls = []
        for _ in range(min(n_steps, 6)):
            t0 = time.perf_counter()
            state, metrics = step(state, batches[i_next % 4])
            jax.block_until_ready(metrics)
            walls.append((time.perf_counter() - t0) * 1e3)
            losses.append(metrics["loss"])
            counters.append(moe_counters(metrics))
            i_next += 1
        facts["step_ms"] = walls
        # 3 steady steps under the profiler, chained as in the window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for k in range(3):
                with jax.profiler.StepTraceAnnotation("train_step", step_num=k):
                    with jax.profiler.TraceAnnotation("train.dispatch"):
                        state, metrics = step(state, batches[i_next % 4])
                losses.append(metrics["loss"])
                counters.append(moe_counters(metrics))
                i_next += 1
            with jax.profiler.TraceAnnotation("train.wait"):
                jax.block_until_ready(metrics)
        jax.profiler.stop_trace()
        facts["traced_steps"] = 3

    facts["device"] = ctx.device_report()  # before the reference adds its own peak

    # ---- correct: after the window, outside set-up
    t_check = time.perf_counter()
    losses = np.asarray(jax.device_get(losses), np.float64)
    counters = np.asarray(jax.device_get(counters), np.float64)  # [steps, 2]
    facts["moe_drop_frac"] = counters[:, 0].tolist()
    facts["moe_load_max_over_mean"] = counters[:, 1].tolist()
    facts["failed"] = int((~np.isfinite(losses)).sum())
    first_loss = float(first_loss)
    params_end = state.params  # the fp32 master the last step left
    del state, metrics, batches
    params0 = jax.jit(lambda k: init_params(cfg.model, k),
                      out_shardings=param_shardings(cfg, menv.mesh))(key)

    # the program's statistics are per microbatch: with more than one sequence
    # in a microbatch the reference's per-sequence terms would not be its own
    if t.micro_batch_size != 1:
        raise SystemExit("train_step_moe: the reference's auxiliary terms are per "
                         "sequence; the cell needs micro_batch_size 1")
    # 1. the first step's loss: the mean over its sequences (one a microbatch)
    rows = np.sort(np.random.default_rng(ctx.seed31(2)).choice(
        t.seq_length, size=min(LOGITS_ROWS, t.seq_length), replace=False))
    j_rows = jnp.asarray(rows)
    ref = jax.jit(lambda p, i, g, r: reference_moe.evaluate(p, i, g, r, m))
    wrong_refs = {name: jax.jit(lambda p, i, g, r, kw=kw: reference_moe.evaluate(
        p, i, g, r, m, **kw)["logits"]) for name, kw in FAULTS.items()}
    ref_loss = float(np.mean([float(ref(params0, jnp.asarray(first_ids[a, b]),
                                        jnp.asarray(first_tgt[a, b]), j_rows)["loss"])
                              for a in range(ga) for b in range(b_global)]))
    gap = abs(first_loss - ref_loss) / abs(ref_loss)

    # 2. logits at seeded positions of the step's first sequence, at the
    # initial weights and at the weights the run ended with
    program = program_logits_fn(cfg, menv)
    ids0, tgt0 = jnp.asarray(first_ids[0, 0]), jnp.asarray(first_tgt[0, 0])
    ids_b = jax.device_put(jnp.asarray(np.repeat(first_ids[0, :1], b_global, axis=0)),
                           menv.sharding(("dp", "ep"), "cp"))  # [b, s]: one row a data shard
    k, e = m["num_experts_per_token"], m["num_experts"]
    logits_ok, logits_notes = True, []
    for label, params, q, rtol in (("initial weights", params0, 100, LOGITS_RTOL),
                                   ("final weights", params_end, END_PERCENTILE,
                                    LOGITS_RTOL_END)):
        want, probs = jax.device_get([ref(params, ids0, tgt0, j_rows)[n]
                                      for n in ("logits", "probs")])
        got = np.asarray(program(params, ids_b)[0, rows].astype(jnp.float32))
        ties = tie_rows(probs, k, TIE_MARGIN)
        kept, errs = ~ties, row_errors(got, want)
        worst = float(np.percentile(errs[kept], q)) if kept.any() else float("inf")
        shares, far = {}, {}
        for name, fn in wrong_refs.items():
            wrong = jax.device_get(fn(params, ids0, tgt0, j_rows))
            shares[name] = fault_share(got[kept], want[kept], wrong[kept])
            far[name] = row_errors(wrong[kept], want[kept])
        logits_ok = (logits_ok and worst <= rtol and ties.mean() <= MAX_TIES
                     and all(abs(v) <= FAULT_SHARE for v in shares.values()))
        # the skew of the groups this forward walked, from the reference's own top k
        chosen = np.argsort(-np.asarray(probs), axis=-1)[..., :k]
        load = np.bincount(chosen.reshape(-1), minlength=e).max() * e / chosen.size
        logits_notes.append(
            f"logits, {label}, at {len(rows)} positions (busiest expert there {load:.2f} x "
            f"the mean): {int(ties.sum())} left out as router near-ties (log gap under "
            f"{TIE_MARGIN}; at most {MAX_TIES:.0%}); over the rest percentile {q} of the "
            f"relative distance is {worst:.3e} (tolerance {rtol}), the median "
            f"{float(np.median(errs[kept])) if kept.any() else float('nan'):.3e}, the largest "
            f"{float(errs[kept].max()) if kept.any() else float('nan'):.3e}, "
            f"{int((errs[kept] > rtol).sum())} beyond the tolerance; over the "
            f"left-out ones at most {float(errs[ties].max()) if ties.any() else 0.0:.3e}; share "
            f"of a fault in the program's error (at most {FAULT_SHARE}) and the fault's own "
            f"distance: " + ", ".join(
                f"{n} {shares[n]:+.4f} ({far[n].min():.2e} to {far[n].max():.2e})"
                for n in shares))

    # 3. steps 1 and 2 were the warm-up, so the window's third step is step 5
    fall = 1.0 - float(losses[2]) / first_loss
    drops = float(np.abs(counters[:, 0]).max())
    facts["correct"] = bool(
        gap <= LOSS_RTOL and logits_ok
        and drops == 0.0 and facts["failed"] == 0 and np.isfinite(first_loss)
        and fall >= MIN_LOSS_FALL)
    facts["notes"] = [
        f"step_s(warm-up)={step_s:.4f} steps={n_steps} elapsed={elapsed:.4f}",
        f"first-step loss {first_loss:.6f} reference {ref_loss:.6f} (with the auxiliary "
        f"terms) relative gap {gap:.2e} (tolerance {LOSS_RTOL}); the same "
        f"batch at step 5: {losses[2]:.6f}, a fall of {fall:.4f} (at least {MIN_LOSS_FALL}); "
        f"window loss {losses[0]:.4f} -> {losses[-1]:.4f}, {facts['failed']} non-finite of "
        f"{len(losses)}",
        *logits_notes,
        f"assignments outside every group: largest share in any of {len(counters)} steps "
        f"{drops:.3g}; busiest expert over the mean: {counters[:, 1].min():.3f} to "
        f"{counters[:, 1].max():.3f}; the checks took {time.perf_counter() - t_check:.1f} s",
    ]
    return facts
