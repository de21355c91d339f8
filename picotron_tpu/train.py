"""Training driver — `python -m picotron_tpu.train --config cfg.json`.

Parity with the reference's train.py (ref: train.py:57-281), single-controller:
load config -> initialize the (possibly multi-host) runtime -> build mesh,
dataloader, sharded train state (fresh, HF-bootstrapped, or resumed) -> step
loop with per-step tokens/s / MFU / memory logging -> periodic checkpointing.

What disappears relative to the reference: torchrun rank choreography, the
rank-0 config/tokenizer broadcasts (ref: train.py:152-165, data.py:23-32),
device placement flags, and the env-var dispatch channel — one process per
host runs ordinary Python and every collective lives inside the jitted step.

What the reference's loop lacks entirely: runtime fault tolerance. The step
loop here is wired through picotron_tpu/resilience — SIGTERM/SIGINT land as
a finished step + emergency checkpoint + exit 75 (auto_resume recovers
losslessly), divergence guards answer NaN/spike steps with skip / rollback /
abort, checkpoint and dataset I/O retry with backoff, and a watchdog turns a
hung step or stalled producer into a stack dump + exit 77 instead of a
silently burning reservation. All of it is testable on CPU via the chaos
harness (PICOTRON_CHAOS / resilience.chaos; tools/chaos.py runs whole
fault-recovery scenarios). See README "Fault tolerance".

Observability: the loop reports through picotron_tpu/telemetry — the
frozen stdout line, a per-host telemetry.jsonl event stream (step-phase
timings, goodput/badput ledger, resilience events, exact compile time),
and a rollback-safe wandb adapter; tools/telemetry_report.py summarizes a
stream post-hoc. See README "Observability".
"""

from __future__ import annotations

import argparse
import os
import time

import jax

from picotron_tpu.checkpoint import CheckpointManager, load_hf_safetensors
from picotron_tpu.config import (
    Config, load_config, num_params, refuse_training,
)
from picotron_tpu.models.llama import pad_layers_for_pp
from picotron_tpu.data import MicroBatchDataLoader
from picotron_tpu.mesh import MeshEnv, multihost_initialize
from picotron_tpu.parallel.api import (
    attention_path, init_sharded_state, install_params, make_train_step,
    offload_memory_kind,
)
from picotron_tpu.resilience import (
    EXIT_DIVERGED, EXIT_PREEMPTED, DivergenceGuard, GuardAction,
    PreemptionHandler, Watchdog, chaos, elastic,
)
from picotron_tpu.telemetry import Telemetry, bus as telemetry_bus
from picotron_tpu.train_step import TrainState
from picotron_tpu.utils import (
    StepTimer, device_memory_gb, device_peak_flops, human_format,
    is_logging_host, log_print, mfu, require_platform,
    setup_compile_cache, training_log_line,
)


def build_state(cfg: Config, menv: MeshEnv, tel: Telemetry = None) \
        -> tuple[TrainState, int, int, dict, str]:
    """(state, start_step, trained_tokens, ckpt_meta, resumed_from) — fresh
    init, HF weights, or resume, in the reference's precedence (ref:
    train.py:174-215: materialize weights, then load_checkpoint overrides).
    `resumed_from` is the checkpoint directory the state came from ("" when
    fresh): with auto_resume and no explicit load_path, the newest durable
    checkpoint in save_dir wins — preemption recovery."""
    state = init_sharded_state(cfg, menv, jax.random.key(cfg.training.seed))

    if cfg.checkpoint.init_from_hf:
        params = load_hf_safetensors(cfg.checkpoint.init_from_hf, cfg.model)
        params = pad_layers_for_pp(params, cfg.model.num_hidden_layers,
                                   cfg.distributed.pp_size)
        # install_params respects the optimizer-offload layout (pinned-host
        # master + bf16 device copy) as well as the standard fp32 layout
        state = install_params(cfg, menv, state, params)
        log_print(f"initialized weights from {cfg.checkpoint.init_from_hf}")

    load_dir = cfg.checkpoint.load_path
    mgr = None
    if not load_dir and cfg.checkpoint.auto_resume:
        probe = CheckpointManager(cfg, menv)
        # Durable AND manifest-verified: a bit-flipped/truncated newest
        # checkpoint makes the probe (and restore below) walk down the
        # lineage to the last known-good step — emitting ckpt_corrupt —
        # instead of resuming silently wrong.
        if probe.latest_valid_step() is not None:
            load_dir = probe.directory
            mgr = probe  # same dir — reuse, don't build a second manager
            log_print(f"auto_resume: found checkpoints in {load_dir}")

    if load_dir:
        if mgr is None:
            mgr = CheckpointManager(cfg, menv, directory=load_dir)
        # An elastic restore across a topology change is booked under the
        # `resize` goodput category, not `restore`, so shrink/grow cost is
        # measured apart from plain resumes. The phase name must be chosen
        # before the phase opens, so probe the newest valid step's source
        # topology up front (cheap manifest read; restore re-checks it
        # authoritatively).
        phase_name = "restore"
        if cfg.checkpoint.elastic:
            probe_step = mgr.latest_valid_step()
            if probe_step is not None:
                saved = elastic.saved_topology(mgr._step_dir(probe_step))
                here = elastic.topology_from_distributed(cfg.distributed)
                if elastic.topology_mismatch(saved, here):
                    phase_name = "resize"
        if tel is not None:
            with tel.phases.phase(phase_name):
                state, meta = mgr.restore(state)
        else:
            state, meta = mgr.restore(state)
        tokens = meta.get("trained_tokens", 0)
        resize = meta.get("elastic_resize")
        if resize:
            if tel is not None:
                tel.emit("elastic_resize", step=int(state.step),
                         **{k: resize[k] for k in ("from", "to", "axes")})
            log_print(
                f"elastic resize: restored step {int(state.step)} saved "
                f"at [{elastic.describe_topology(resize['from'])}] into "
                f"[{elastic.describe_topology(resize['to'])}] "
                f"(axes: {', '.join(resize['axes'])}; global batch "
                f"{cfg.global_batch_size} unchanged)")
        log_print(f"resumed from {load_dir} at step "
                  f"{int(state.step)} ({human_format(tokens)} tokens)")
        return state, int(state.step), tokens, meta, load_dir
    return state, 0, 0, {}, ""


def _emergency_checkpoint(cfg, menv, ckpt_mgr, state, trained_tokens, dl,
                          saved_steps):
    """Preemption landed: make the in-flight progress durable inside the
    grace window. Builds a manager on the spot when periodic saving was
    off — an emergency save must not depend on save_frequency."""
    mgr = ckpt_mgr if ckpt_mgr is not None else CheckpointManager(cfg, menv)
    step = int(state.step)
    if step not in saved_steps:
        path = mgr.save(state, trained_tokens, dataloader_state=dl.state)
        saved_steps.add(step)
        log_print(f"emergency checkpoint -> {path}")
    mgr.wait_until_finished()
    return mgr


def _rollback(ckpt_mgr, state, dl, step, trained_tokens, why):
    """Divergence-guard rollback: restore the last known-good checkpoint
    (durable AND manifest-verified — a corrupt newest step is skipped
    down the lineage, ckpt_integrity) and reposition the dataloader to
    the cursor AFTER the poison batch, so the resumed steps skip the data
    range that tripped the guard. Returns the restored (state, step,
    trained_tokens); escalates to EXIT_DIVERGED when there is nothing
    valid to roll back to."""
    if ckpt_mgr is None or ckpt_mgr.latest_valid_step() is None:
        log_print(f"[guard {step:06d}] {why}; rollback requested but no "
                  f"valid checkpoint exists — aborting "
                  f"(exit {EXIT_DIVERGED})")
        raise SystemExit(EXIT_DIVERGED)
    skip_to = dl.state  # position after the poison batch
    ckpt_mgr.wait_until_finished()
    state, meta = ckpt_mgr.restore(state)
    restored = int(state.step)
    dl.reset(skip_to)
    tokens = int(meta.get("trained_tokens", 0))
    log_print(f"[guard {step:06d}] {why}; rolled back to step {restored} "
              f"(skipping poisoned data through "
              f"epoch {skip_to['epoch']} cursor {skip_to['cursor']}); "
              f"was {human_format(trained_tokens)} tokens, "
              f"now {human_format(tokens)}")
    return state, restored, tokens


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="picotron-tpu trainer")
    ap.add_argument("--config", required=True, help="config JSON path "
                    "(reference-schema compatible)")
    args = ap.parse_args(argv)

    cfg = load_config(args.config)
    refuse_training(cfg.model)  # a model with no training loss, by name
    if cfg.distributed.use_cpu:
        # The reference's --use_cpu path (gloo + FLASH_ATTEN=0, ref:
        # create_config.py:64-66): run the full parallel layout on simulated
        # host devices. Must happen before any backend-initializing jax call.
        # Under the multi-process launcher contract each process provisions
        # only its share of the world's devices (the 2-process integration
        # test runs exactly this path). launcher_contract() validates the
        # PICOTRON_* vars as a unit, so a stale partial contract fails here
        # rather than as a confusing mesh-oversubscription error.
        from picotron_tpu.mesh import force_host_device_count, launcher_contract

        contract = launcher_contract()
        n_proc = contract[1] if contract else 1
        world = cfg.distributed.world_size
        if world % n_proc != 0:
            raise ValueError(
                f"world_size {world} not divisible by "
                f"PICOTRON_NUM_PROCESSES={n_proc}")
        # exact under a multi-process contract: an inherited XLA_FLAGS count
        # would otherwise over-provision every process (code review r3)
        force_host_device_count(world // n_proc, exact=n_proc > 1)
        jax.config.update("jax_platforms", "cpu")
    setup_compile_cache()
    multihost_initialize()
    # The CPU is a fine place to train when somebody asked for it
    # (`use_cpu: true`, or JAX_PLATFORMS / jax_platforms naming cpu — the
    # tests' and tools' two spellings); JAX falling back to it because no
    # accelerator came up is refused.
    asked_cpu = "cpu" in (jax.config.jax_platforms or "").split(",")
    dev0 = require_platform("train", allow_cpu=asked_cpu)
    menv = MeshEnv.from_config(cfg)
    t = cfg.training

    # Fail-fast static pre-flight (tools/shardcheck.py is the full pass):
    # spec lint + donation/recompile hazards catch a mis-authored
    # PartitionSpec or a lost donation BEFORE any pod time is committed —
    # every one of these previously surfaced as a partitioner error or an
    # OOM at step 1 of a real run. Costs one extra abstract trace of the
    # step (seconds, vs the compile that follows anyway); set
    # PICOTRON_PREFLIGHT=0 to skip.
    from picotron_tpu.analysis import preflight

    if os.environ.get("PICOTRON_PREFLIGHT", "1") != "0":
        pre = preflight(cfg, menv)  # raises ShardcheckError with the report
        log_print(f"shardcheck preflight: ok "
                  f"({len(pre.warnings())} warning(s))")
        # An unproven jit entry is a perf smell the operator should see at
        # startup, with the fix named (analysis/variants.py).
        for f in pre.warnings():
            if f.check == "variants":
                log_print(f"shardcheck preflight WARNING: {f.render()}")
        ts = pre.info.get("variants", {}).get("train_step", {})
        if ts.get("proven"):
            log_print("shardflow: train step proven compile-once "
                      f"({ts['leaves']} abstract leaves, 1 signature)")
        if cfg.checkpoint.save_frequency > 0:
            # Same fail-fast contract for the checkpoint store: an
            # unwritable save_dir or a disk without headroom for one
            # checkpoint must die here, not at the first periodic save
            # hours in (picotron_tpu/ckpt_integrity.preflight).
            from picotron_tpu.ckpt_integrity import preflight_save_dir

            est = preflight_save_dir(cfg)  # raises RuntimeError w/ story
            log_print(f"checkpoint preflight: ok ({cfg.checkpoint.save_dir}"
                      f", ~{est / 1e9:.2f} GB/checkpoint)")
        if (cfg.distributed.world_size > 1 and dev0.platform == "tpu"
                and os.environ.get("PICOTRON_COST_PREFLIGHT", "1") != "0"):
            # Advisory layout check (analysis/cost_model + planner): pure
            # arithmetic, milliseconds even at pod scale; priced for the TPU
            # generation this process runs on, so it has nothing to say on
            # the CPU platform. Warn — never
            # fail — when the chosen layout is predicted >= 20% slower
            # than the planner's best at the same chip count, with the
            # overrides line that would close the gap. Threshold via
            # PICOTRON_COST_GAP (fraction); PICOTRON_COST_PREFLIGHT=0
            # disables.
            from picotron_tpu.analysis.cost_model import CostModel
            from picotron_tpu.analysis.planner import planner_gap

            cm = CostModel(dev0.device_kind)
            cur, best, gap = planner_gap(cfg, cm)
            gap_bar = float(os.environ.get("PICOTRON_COST_GAP", "0.2"))
            log_print(f"cost preflight [{cm.gen.name}]: predicted "
                      f"{cur.total_s * 1e3:.4g} ms/step "
                      f"({cur.exposed_comm_s * 1e3:.4g} ms exposed comm)")
            if best is not None and gap >= gap_bar:
                log_print(
                    f"cost preflight WARNING: this layout is predicted "
                    f"{gap * 100:.0f}% slower than the planner's best at "
                    f"{cfg.distributed.world_size} chips "
                    f"({best.label}, {best.cost.total_s * 1e3:.4g} "
                    f"ms/step). To adopt it: {best.overrides_line()}")
                if (cfg.pipeline.executor == "spmd"
                        and cfg.distributed.pp_size > 1):
                    # When just flipping the executor (same layout)
                    # closes a material share of the gap, say so — it is
                    # a one-knob change, vs the full relayout above.
                    import dataclasses as _dc

                    from picotron_tpu.config import PipelineConfig

                    try:
                        twin = _dc.replace(
                            cfg, pipeline=PipelineConfig(executor="mpmd"))
                        twin.validate()
                        closed = cur.total_s - cm.predict(twin).total_s
                        gap_s = cur.total_s - best.cost.total_s
                        if gap_s > 0 and closed >= 0.2 * gap_s:
                            log_print(
                                f"cost preflight: pipeline.executor=mpmd "
                                f"alone (same layout) is predicted to "
                                f"close {closed / gap_s * 100:.0f}% of "
                                f"that gap — --override "
                                f"pipeline.executor=mpmd")
                    except (ValueError, KeyError):
                        pass  # layout can't host mpmd (offload/sp/MoE)

    n_chips = menv.world_size
    n_params = num_params(cfg.model)
    # MFU needs a peak, and only a TPU in the table has one: on the CPU
    # platform the column is not computed (the frozen line prints 0.00%).
    peak = device_peak_flops(dev0) if dev0.platform == "tpu" else None
    offload = (offload_memory_kind(menv.mesh) or "unplaced"
               if t.optimizer_offload else "off")
    # which expert dispatch was built: the capacity path and its option exist
    # only across 'ep' (models/llama._moe_block selects on the mesh)
    experts = "" if not cfg.model.num_experts else (
        f" experts=capacity({cfg.model.capacity_factor})" if menv.ep > 1
        else " experts=dropless (capacity_factor has no effect at ep=1)")
    log_print(
        f"model {cfg.model.name}: {human_format(n_params)} params | "
        f"mesh dp={menv.dp} pp={menv.pp} ep={menv.ep} cp={menv.cp} tp={menv.tp} "
        f"({n_chips} chips, {dev0.device_kind}) | "
        f"global batch {cfg.global_batch_size} x seq {t.seq_length} = "
        f"{human_format(cfg.tokens_per_step)} tokens/step | "
        f"platform={dev0.platform} attention={attention_path(cfg)} "
        f"offload={offload}{experts}"
    )

    # Structured telemetry (picotron_tpu/telemetry; README
    # "Observability"): metrics registry + sinks (the frozen stdout line,
    # the per-host telemetry.jsonl next to the checkpoints, wandb), the
    # step-phase timer that doubles as the watchdog heartbeat source, the
    # goodput/badput ledger, and exact compile-time accounting. Installed
    # on the bus BEFORE the dataloader/state build so restore retries and
    # chaos events are captured from the first second.
    tel = telemetry_bus.install(Telemetry.from_config(cfg))
    if tel.jsonl_path:
        log_print(f"telemetry -> {tel.jsonl_path}")
    if tel.trace_path:
        log_print(f"flightdeck trace -> {tel.trace_path}")
    if cfg.distributed.pp_size > 1:
        # Book the analytic fill/drain share of every step into the
        # pp_bubble ledger category (both executors — the schedule table
        # implies the fraction either way), and let the MPMD executor's
        # sampled per-stage tick timings (PICOTRON_PP_TICK_SAMPLE) feed
        # the section/pp_stage* histograms the telemetry report reads.
        from picotron_tpu.parallel import mpmd

        tel.set_pp_bubble_fraction(mpmd.pipeline_bubble_fraction(cfg))
        log_print(f"pipeline: executor={cfg.pipeline.executor} "
                  f"schedule={cfg.pipeline.schedule} "
                  f"v={cfg.pipeline.interleave} — predicted bubble "
                  f"{tel.pp_bubble_fraction * 100:.1f}% of step wall")
        if cfg.pipeline.executor == "mpmd":
            def _stage_times(timings, _step, _tel=tel):
                for g, secs in sorted(timings.items()):
                    for s in secs:
                        _tel.observe_section(f"pp_stage{g}", s)

            mpmd.on_stage_times = _stage_times

    dl = MicroBatchDataLoader(cfg, menv)
    (state, start_step, trained_tokens, ckpt_meta,
     resumed_from) = build_state(cfg, menv, tel)
    tel.ledger.resume_from(start_step)
    if start_step > 0:
        # Fast-forward the dataloader so resume does not replay consumed
        # data (ADVICE r1). Checkpoints record the exact position; for ones
        # that predate that, derive it from the step count and the
        # tail-dropping epoch arithmetic.
        dl_state = ckpt_meta.get("dataloader")
        if dl_state is None:
            steps_per_epoch = max(1, len(dl.source) // cfg.global_batch_size)
            dl_state = {
                "epoch": start_step // steps_per_epoch,
                "cursor": (start_step % steps_per_epoch) * cfg.global_batch_size,
            }
        dl.set_state(dl_state)
    step_fn = make_train_step(cfg, menv)
    eval_batches = eval_fn = None
    if t.eval_frequency > 0:
        from picotron_tpu.data import build_eval_source
        from picotron_tpu.parallel.api import make_eval_step

        # Materialize a FIXED validation set once: every eval (and every
        # resumed run) scores the same batches, so the val_loss curve
        # reflects the model, not which slice of the split got sampled
        # (code review r3).
        eval_dl = MicroBatchDataLoader(cfg, menv,
                                       source=build_eval_source(cfg))
        eval_batches = [next(eval_dl) for _ in range(t.eval_steps)]
        eval_dl.close()
        eval_fn = make_eval_step(cfg, menv)
    ckpt_mgr = (CheckpointManager(cfg, menv)
                if cfg.checkpoint.save_frequency > 0 else None)

    wandb_run = None
    if cfg.logging.use_wandb and is_logging_host():
        try:
            import wandb
            wandb_run = wandb.init(project=cfg.logging.project_name,
                                   name=cfg.logging.run_name,
                                   config=cfg.to_json_dict())
            # The sink logs against a monotonic event counter with the
            # training step as a field (+ define_metric'd step axis):
            # wandb silently drops non-monotonic step= calls, which used
            # to erase every point after a guard rollback.
            tel.attach_wandb(wandb_run)
        except Exception as e:  # wandb optional; zero-egress pods have none
            log_print(f"wandb unavailable ({e}); continuing without")

    # Two stop conditions, whichever bites first: the step budget and the
    # token budget (ref: the config's max_tokens field).
    total_steps = t.total_train_steps
    if t.max_tokens is not None:
        remaining = max(0, t.max_tokens - trained_tokens)
        total_steps = min(total_steps,
                          start_step + -(-remaining // cfg.tokens_per_step))

    # Runtime resilience (picotron_tpu/resilience; README "Fault
    # tolerance"). Chaos installs LAST so the eval batches materialized
    # above cannot consume a data event meant for the training stream.
    rcfg = cfg.resilience
    ctrl = chaos.install(rcfg.chaos)
    if ctrl.active:
        log_print(f"chaos: {ctrl.describe()}")
    # The poisoned twin compiles lazily on first use; built only when the
    # chaos spec names a nan_grad event (injection must happen inside the
    # jitted step — see make_train_step).
    poison_step_fn = (make_train_step(cfg, menv, inject_nan=True)
                      if ctrl.has_nan_grad() else None)
    guard = (DivergenceGuard.from_config(rcfg)
             if rcfg.guard_policy != "off" else None)
    preempt = PreemptionHandler()
    watchdog = Watchdog(rcfg.watchdog_timeout)
    # One clock for liveness and timing: every phase entry below beats the
    # watchdog AND times the section for the goodput ledger.
    tel.attach_watchdog(watchdog)
    ph = tel.phases

    timer = StepTimer()
    last_logged_step = start_step
    # Steps whose checkpoint already exists in the SAVE directory: the loaded
    # step counts only when the resume source IS the save dir (explicit
    # load_path there, or auto_resume) — resuming from elsewhere must still
    # write a final save into save_dir.
    resumed_in_place = (
        resumed_from
        and os.path.abspath(resumed_from)
        == os.path.abspath(cfg.checkpoint.save_dir))
    saved_steps = {start_step} if resumed_in_place else set()
    prof = cfg.logging  # trace capture window (config.py LoggingConfig)
    tracing = False
    exit_code = None
    # A while loop, not a range: the rollback path rewinds `step` to the
    # restored checkpoint and the loop re-trains from there.
    step = start_step
    try:
        preempt.install()
        while step < total_steps:
            step += 1
            chaos.fire("step_begin", step=step)
            if (prof.profile_dir
                    and step - start_step == prof.profile_start_step):
                jax.profiler.start_trace(prof.profile_dir)
                tracing = True
            with ph.phase("data", step):
                batch = next(dl)
            with ph.phase("step", step):
                use_poison = (poison_step_fn is not None
                              and ctrl.poison_step(step))
                state, metrics = (poison_step_fn if use_poison
                                  else step_fn)(state, batch)
            trained_tokens += cfg.tokens_per_step
            if not watchdog.started:
                # Arm only after the first step completes: step 1 includes
                # XLA compilation, whose duration no sane timeout covers.
                watchdog.start()
            if (tracing and step - start_step
                    >= prof.profile_start_step + prof.profile_num_steps - 1):
                jax.block_until_ready(metrics)
                jax.profiler.stop_trace()
                tracing = False
                log_print(f"profiler trace -> {prof.profile_dir}")

            want_log = (step % cfg.logging.log_frequency == 0
                        or step == total_steps)
            fmetrics = None
            if guard is not None or want_log:
                with ph.phase("sync", step):
                    fmetrics = {k: float(v) for k, v in
                                jax.block_until_ready(metrics).items()}
            if guard is not None:
                action, why = guard.observe(
                    step, fmetrics["loss"],
                    grad_norm=fmetrics.get("grad_norm"),
                    nonfinite=fmetrics.get("nonfinite"))
                if action is not GuardAction.OK:
                    tel.emit("guard", action=action.value, step=step,
                             why=why)
                if action is GuardAction.ABORT:
                    log_print(f"[guard {step:06d}] {why}; aborting "
                              f"(exit {EXIT_DIVERGED})")
                    if tel.flight is not None:
                        tel.flight.dump("divergence_abort", step=step,
                                        why=why)
                    exit_code = EXIT_DIVERGED
                    break
                if action is GuardAction.SKIP:
                    if "spike" in why:
                        # Spikes are detected host-side AFTER the update
                        # applied; under 'skip' they can only be
                        # quarantined from the guard window.
                        log_print(f"[guard {step:06d}] {why}; quarantined "
                                  f"from the spike window (update already "
                                  f"applied — policy 'rollback' undoes it)")
                    else:
                        log_print(f"[guard {step:06d}] {why}; batch skipped "
                                  f"(update suppressed in-step, optimizer "
                                  f"state preserved)")
                elif action is GuardAction.ROLLBACK:
                    bad_step = step
                    if tel.flight is not None:
                        # Dump BEFORE restoring: the window still holds
                        # the diverging steps, and _rollback can itself
                        # exit (no valid checkpoint -> EXIT_DIVERGED).
                        tel.flight.dump("rollback", step=bad_step,
                                        why=why)
                    with ph.phase("rollback", step):
                        state, step, trained_tokens = _rollback(
                            ckpt_mgr, state, dl, step, trained_tokens, why)
                    # Steps (restored, bad_step] now re-run at-or-below
                    # the ledger's high-water mark -> booked as replay.
                    tel.emit("rollback", step=bad_step, restored=step,
                             why=why)
                    saved_steps.add(step)
                    last_logged_step = step
                    timer.lap()  # restart the throughput window
                    continue

            if want_log:
                loss = fmetrics.pop("loss")
                fmetrics.pop("nonfinite", None)  # guard plumbing, not a metric
                # Floor the wall-clock window: a ~0 s lap (resume-heavy
                # tests, clock quantization) must never print inf
                # tokens/s or inf MFU (mirrors PR 1's decode-timing guard).
                dt = max(timer.lap(), 1e-9)
                steps_in_window = step - last_logged_step
                last_logged_step = step
                tokens_per_sec = cfg.tokens_per_step * steps_in_window / dt
                mfu_frac = (mfu(tokens_per_sec, cfg.model, t.seq_length,
                                n_chips, peak) if peak else 0.0)
                mem_gb = device_memory_gb()
                line = training_log_line(
                    step, loss, tokens_per_sec, tokens_per_sec / n_chips,
                    mfu_frac, trained_tokens, mem_gb,
                    extras=fmetrics)
                # One record, every sink: stdout gets the preformatted
                # line byte-identically (the extract_metrics contract);
                # JSONL/wandb get the structured fields.
                tel.record_step(
                    step, line, loss=loss, tokens_per_sec=tokens_per_sec,
                    tokens_per_sec_per_chip=tokens_per_sec / n_chips,
                    mfu=mfu_frac, trained_tokens=trained_tokens,
                    memory_gb=mem_gb, **fmetrics)

            if eval_fn is not None and (step % t.eval_frequency == 0
                                        or step == total_steps):
                with ph.phase("eval", step):
                    # max(1, ...) guards the division alongside config.py's
                    # eval_steps >= 1 validation (defense in depth: a custom
                    # driver could hand-build a Config bypassing validate()).
                    val = (sum(float(eval_fn(state.params, b))
                               for b in eval_batches)
                           / max(1, len(eval_batches)))
                tel.record_eval(step, val,
                                f"[eval  {step:06d}] val_loss: {val:.4f} "
                                f"({t.eval_steps} batches)")

            if (ckpt_mgr is not None
                    and step % cfg.checkpoint.save_frequency == 0):
                with ph.phase("save", step):
                    path = ckpt_mgr.save(state, trained_tokens,
                                         dataloader_state=dl.state)
                saved_steps.add(step)
                log_print(f"saved checkpoint -> {path}")

            if preempt.triggered:
                # The in-flight step finished above; make it durable and
                # hand control back to the supervisor with the distinct
                # exit code auto_resume pairs with.
                with ph.phase("preempt-save", step):
                    ckpt_mgr = _emergency_checkpoint(
                        cfg, menv, ckpt_mgr, state, trained_tokens, dl,
                        saved_steps)
                tel.emit("preempted", step=step)
                if tel.flight is not None:
                    tel.flight.dump("preempted", step=step)
                log_print(f"preempted at step {step}; state is durable — "
                          f"exiting {EXIT_PREEMPTED} for auto_resume")
                exit_code = EXIT_PREEMPTED
                break

        if exit_code is None:
            # Final save, unless this run already wrote this exact step (a
            # resumed run whose budget was met trains zero steps; re-saving
            # the loaded step into its existing directory would make Orbax
            # fail an otherwise-clean exit). Tracked in-process so a stale
            # same-numbered checkpoint from an earlier run into the same
            # save_dir cannot suppress the save.
            if ckpt_mgr is not None and int(state.step) not in saved_steps:
                with ph.phase("save", int(state.step)):
                    ckpt_mgr.save(state, trained_tokens,
                                  dataloader_state=dl.state)
    except SystemExit:
        raise  # deliberate exits (rollback-without-ckpt) dumped above
    except BaseException as e:  # noqa: BLE001
        # Unhandled crash: leave the last-K-steps window next to the
        # checkpoints before the teardown below runs.
        if tel.flight is not None:
            tel.flight.dump("exception", step=step, error=repr(e))
        raise
    finally:
        # Always-run teardown: a mid-run crash must not leak the producer
        # thread, a half-written async checkpoint, an open trace, or a
        # dangling wandb run. Each step is fenced so one failing cleanup
        # cannot mask the original exception (or the other cleanups).
        watchdog.stop()
        preempt.uninstall()
        if tracing:
            try:
                jax.profiler.stop_trace()
                log_print(f"profiler trace -> {prof.profile_dir}")
            except Exception as e:  # noqa: BLE001
                log_print(f"profiler stop failed during shutdown: {e!r}")
        if ckpt_mgr is not None:
            # Async saves overlap training; the process must not exit
            # before the last one is durable.
            try:
                ckpt_mgr.wait_until_finished()
            except Exception as e:  # noqa: BLE001
                log_print(f"checkpoint finalization failed during "
                          f"shutdown: {e!r}")
        try:
            dl.close()
        except Exception as e:  # noqa: BLE001
            log_print(f"dataloader close failed during shutdown: {e!r}")
        # Writes the run_summary event (goodput ledger + metric snapshot),
        # closes the JSONL stream, finishes wandb (WandbSink.close), and
        # uninstalls the bus so a crashed run cannot leak a sink into the
        # next in-process run (tests).
        try:
            tel.close()
        except Exception as e:  # noqa: BLE001
            log_print(f"telemetry close failed during shutdown: {e!r}")
    if exit_code is not None:
        raise SystemExit(exit_code)
    log_print("training done")


if __name__ == "__main__":
    main()
