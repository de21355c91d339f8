"""Runner `train_step`: the program's training step, as the trainer runs it.

`Config` from the configuration file's own blocks, `MeshEnv.from_config`,
`parallel.api.init_sharded_state`, `parallel.api.make_train_step`: what
`picotron_tpu.train` and `bench.py run_one` use, no side path. The timed loop
is `bench.py run_one`'s (chained donated steps, one wait at the end), copied.

Facts returned (`workloads/<cell>.json` maps metric names to these keys):
`tokens_per_s_per_chip`, `step_ms` (per-step walls, traced run only),
`tokens_per_step`, `model`, `seq`, `flash_calls_per_step`, and the common
ones (`attempted`, `failed`, `correct`, `compiles_in_window`, `device`).
"""

from __future__ import annotations

import math
import time

import numpy as np

# The program's first-step loss (bf16 activations, fp32 master, Pallas
# attention, mean over the step's tokens) against the plain reference
# (float32, `highest`) on the same tokens and the same initial weights.
# With the program's initialisation (unit-normal embedding, which a tied head
# multiplies by again) the first loss is in the hundreds or thousands, so the
# tolerance is relative. Measured on the chip at Qwen2-1.5B 12L, seq 4096
# (PERF.md, Findings PR 24): the program, which rounds activations to bf16,
# sits 4.1e-5 of the loss from the reference (0.059 of 1437.95); a reference
# with one layer dropped moves by 5.4e-3, with a non-causal mask by 3.0e-3, at
# the chip's default matmul precision by 5.2e-6. 2e-4 is five times the
# program's own distance and fifteen times under the nearest wrong model; a
# program that rounded to 8 bits instead of bf16's 8 + 8 would be some
# sixteen times farther out than it is, and fail.
LOSS_RTOL = 2e-4
# A cell's file may tighten it (`"loss_rtol"`, never loosen): with an untied
# head the first loss is about ln(vocab) + 0.17 whatever the layers compute,
# and only a much smaller distance separates a wrong model from a right one.

# The backward pass and the update: the batches are a cycle of 4, so step 5
# sees step 1's batch again after four updates, and its loss has to sit this
# share under step 1's. It catches an update that is skipped, has the wrong
# sign or does not reach the weights the forward pass reads; it cannot tell a
# gradient that is missing for some layers from a whole one. Measured on the
# chip (PERF.md, Findings PR 24): unseen random batches barely fall in the
# untied 7B cell (12.0965 at step 1, 12.0862 at step 3, 0.09%), a batch seen
# once before falls by 29% (step 3 -> step 7); in the tied 1.5B cell step 5
# sits 89% under step 1.
MIN_LOSS_FALL = 0.02

SPANS = ("train.dispatch", "train.wait")


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import reference
    from picotron_tpu.config import config_from_dict
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.parallel.api import (attention_path, init_sharded_state,
                                           make_train_step)
    from picotron_tpu.parallel.sharding import param_shardings

    c = ctx.config
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "training")})
    t, m = cfg.training, c["model"]
    for k in reference.SIZES:  # the reference reads the file, not the program's presets
        if getattr(cfg.model, k) != m[k]:
            raise SystemExit(f"train_step: model.{k} differs between the file and the program")
    if cfg.model.num_hidden_layers % cfg.distributed.pp_size:
        raise SystemExit("train_step: layers must divide evenly over pp stages")
    menv = MeshEnv.from_config(cfg)
    if menv.world_size != ctx.chips:
        raise SystemExit(f"train_step: the layout has {menv.world_size} devices, "
                         f"the cell {ctx.chips}")
    key = jax.random.key(ctx.seed31(0))
    state = init_sharded_state(cfg, menv, key)
    step = make_train_step(cfg, menv)
    ctx.log(f"attention={attention_path(cfg)} grad_engine={t.grad_engine} "
            f"remat={t.remat_policy} mesh={dict(menv.mesh.shape)}")

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
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    state, metrics = step(state, batches[1])
    jax.block_until_ready(metrics)
    step_s = time.perf_counter() - t0
    n_steps = max(math.ceil(ctx.seconds / step_s), 3)  # to step 5, for `correct`
    i_next = 2

    # the window: N chained donated steps, one wait at the end
    ctx.window_starts()
    losses = []
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step(state, batches[i_next % 4])
        losses.append(metrics["loss"])
        i_next += 1
    jax.block_until_ready(metrics)
    elapsed = time.perf_counter() - t0
    in_window = ctx.window_ends()

    facts = dict(
        tokens_per_s_per_chip=tokens_per_step * n_steps / elapsed / ctx.chips,
        tokens_per_step=tokens_per_step, model=m, seq=t.seq_length,
        attempted=n_steps, compiles_in_window=in_window["compiles"], spans=SPANS,
        # one fwd, one dq, one dkv call per layer this device runs, per microbatch
        flash_calls_per_step=ga * cfg.model.num_hidden_layers // cfg.distributed.pp_size,
        flash_shape=dict(batch=t.micro_batch_size,
                         heads=cfg.model.num_attention_heads // cfg.distributed.tp_size,
                         kv_heads=max(cfg.model.num_key_value_heads
                                      // cfg.distributed.tp_size, 1),
                         seq=t.seq_length // cfg.distributed.cp_size,
                         d=cfg.model.head_dim),
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
                i_next += 1
            with jax.profiler.TraceAnnotation("train.wait"):
                jax.block_until_ready(metrics)
        jax.profiler.stop_trace()
        facts["traced_steps"] = 3

    facts["device"] = ctx.device_report()  # before the reference adds its own peak

    # ---- correct: after the window, outside set-up
    losses = np.asarray(jax.device_get(losses), np.float64)
    facts["failed"] = int((~np.isfinite(losses)).sum())
    first_loss = float(first_loss)
    del state, metrics, batches
    params0 = jax.jit(lambda k: init_params(cfg.model, k),
                      out_shardings=param_shardings(cfg, menv.mesh))(key)
    ref = jax.jit(lambda p, i, g: reference.nll_sum(p, i, g, m)[0])
    total = 0.0
    for a in range(ga):
        for b in range(b_global):
            total += float(ref(params0, jnp.asarray(first_ids[a, b]),
                               jnp.asarray(first_tgt[a, b])))
    ref_loss = total / tokens_per_step
    gap = abs(first_loss - ref_loss) / abs(ref_loss)
    rtol = min(LOSS_RTOL, float(ctx.workload.get("loss_rtol", LOSS_RTOL)))
    # steps 1 and 2 were the warm-up, so the window's third step is step 5
    fall = 1.0 - float(losses[2]) / first_loss
    facts["correct"] = bool(gap <= rtol and facts["failed"] == 0
                            and np.isfinite(first_loss) and fall >= MIN_LOSS_FALL)
    facts["notes"] = [
        f"step_s(warm-up)={step_s:.4f} steps={n_steps} elapsed={elapsed:.4f}",
        f"first-step loss {first_loss:.6f} reference {ref_loss:.6f} relative gap {gap:.2e} "
        f"(tolerance {rtol}); the same batch at step 5: {losses[2]:.6f}, a fall of {fall:.4f} "
        f"(at least {MIN_LOSS_FALL}); window loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
        f"{facts['failed']} non-finite of {len(losses)}",
    ]
    return facts
