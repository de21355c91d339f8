#!/usr/bin/env python
"""Does the trainer actually survive the failure you fear? — scenario runs.

Each named scenario runs a short CPU training job under a chaos spec
(picotron_tpu/resilience/chaos.py), plays external supervisor (restart on
the resilience exit codes, with the fault disabled on restart — the way a
real resubmission does not re-live a preemption), and verifies recovery:
the run must reach EXIT 0 within the restart budget, its log must show the
resilience mechanism actually engaged, and the final checkpoint's step and
trained_tokens must MATCH a fault-free baseline run of the same config —
i.e. the failure cost retries/restarts, not training progress.

Scenarios (the runtime-failure matrix README "Fault tolerance" documents):

  sigterm       preemption mid-run -> emergency ckpt + exit 75 -> resume
  ckpt_io       transient checkpoint-write I/O errors -> absorbed by retry
  nan_skip      NaN gradients, guard_policy=skip -> batch dropped in-step
  nan_rollback  NaN gradients, guard_policy=rollback -> restore + skip data
  data_stall    stuck data producer -> watchdog exit 77 -> resume
  ckpt_corrupt_bitflip
                newest committed checkpoint bit-flipped on disk, then
                SIGKILL -> restart falls back to the prior verified step
                (manifest verification + lineage walk); ckpt_doctor must
                flag exactly the injected-corrupt step
  dp_resize     elastic scale-out: dp=2 run SIGKILLed mid-training,
                re-stamped to dp=1 offline (tools/elastic_resize.py),
                killed again, then restored into a dp=4 mesh via
                checkpoint.elastic — constant global batch throughout,
                final step/tokens AND the per-step loss trajectory must
                match the fault-free dp=2 baseline, and the resize
                seconds must land in the `resize` goodput category
  pp_resize     elastic pipeline resize: pp=2 MPMD run SIGKILLed,
                re-stamped to pp=1 offline (tools/elastic_resize.py
                --pp), killed again, then restored into a pp=2 MPMD
                mesh via checkpoint.elastic — same loss-parity /
                resize-booking bar as dp_resize, plus the PR-9 prover
                pins every rebuilt stage program compiles exactly once
  slice_lost    whole-slice loss on a 2-slice job: slice_lost@3 kills
                the pod with the lost slice named in the log, the store
                is re-stamped single-slice offline (tools/
                elastic_resize.py --slices 1), and the surviving chips
                finish at dp=1 via checkpoint.elastic — final
                step/tokens and per-step losses match the single-slice
                baseline, resize booked to the goodput ledger
  mpmd_sigterm  mid-schedule faults on the MPMD executor: SIGTERM at a
                named (stage, tick, op) drains the schedule walk to the
                step boundary (emergency ckpt, exit 75, zero replayed
                steps on resume); a forced mid-schedule hang is
                watchdog-reported naming the live (stage, tick, op)
  serve_engine_dead
                kill 1 of 2 serving replicas mid-burst (chaos
                engine_dead@REQ in the fleet dispatch loop): the
                survivor finishes EVERY request with tokens
                bit-identical to a fault-free single-engine oracle
                (temperature > 0 — the sampling-key fold is the
                mechanism), zero leaked blocks on the survivor pool, a
                serve_engine_dead postmortem, deterministic on repeat
  serve_overload
                burst a 1-slot engine with deadline'd requests: the
                shed set is a deterministic function of the trace
                (virtual clock), admitted requests' tokens match the
                no-deadline run bit-for-bit, every admitted queue wait
                respects the deadline, and the shed seconds land in
                the telemetry ledger's `shed` (badput) category

Usage:

  python tools/chaos.py --list
  python tools/chaos.py --scenario sigterm
  python tools/chaos.py --all          # exit 0 iff every scenario recovers

Long by design (each scenario is several full trainer subprocesses);
the test tier marks these `slow`.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
from dataclasses import dataclass, field
from typing import Callable, Optional

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from picotron_tpu.resilience import (  # noqa: E402
    EXIT_PREEMPTED, EXIT_WATCHDOG,
)

STEPS = 6  # total_train_steps for every scenario (fault lands mid-run)


@dataclass
class Scenario:
    chaos: str                      # resilience.chaos spec for the first run
    marker: str                     # log regex proving the mechanism engaged
    note: str                       # one-line human description
    expect_exits: tuple = ()        # nonzero exits the supervisor restarts on
    max_restarts: int = 0           # restart budget (0 = must recover in-run)
    overrides: dict = field(default_factory=dict)  # config section updates
    # Assertion over save_dir right after the FIRST trainer exit (the
    # faulted state, before any supervised restart repairs it) — returns
    # an error string or None. The corruption scenario inspects the
    # really-corrupted store with ckpt_doctor here.
    check_after_fault: Optional[Callable] = None


SCENARIOS: dict[str, Scenario] = {
    "sigterm": Scenario(
        chaos=f"sigterm@{STEPS // 2}",
        expect_exits=(EXIT_PREEMPTED,),
        max_restarts=2,
        marker=r"emergency checkpoint ->",
        note="preemption mid-run: finish step, emergency ckpt, exit "
             f"{EXIT_PREEMPTED}, auto_resume",
        check_after_fault=lambda save_dir: _postmortem_matches(
            save_dir, reason="preempted", fault_step=STEPS // 2),
    ),
    "ckpt_io": Scenario(
        # Two injected write failures at the step-2 save; the default
        # 3-attempt retry absorbs them with no restart.
        chaos="ckpt_io@2x2",
        marker=r"\[retry\] checkpoint save",
        note="transient checkpoint-write I/O errors absorbed by "
             "retry-with-backoff",
    ),
    "nan_skip": Scenario(
        chaos=f"nan_grad@{STEPS // 2}",
        overrides={"resilience": {"guard_policy": "skip"}},
        marker=r"batch skipped",
        note="NaN gradients dropped in-step (optimizer state preserved), "
             "run continues",
    ),
    "nan_rollback": Scenario(
        chaos=f"nan_grad@{STEPS - 2}",
        overrides={"resilience": {"guard_policy": "rollback"}},
        marker=r"rolled back to step",
        note="NaN gradients: restore last durable ckpt, skip the poison "
             "data range, re-train",
        check_after_fault=lambda save_dir: _postmortem_matches(
            save_dir, reason="rollback", fault_step=STEPS - 2),
    ),
    "data_stall": Scenario(
        # Producer sleeps far longer than the watchdog timeout; the
        # watchdog dumps stacks and exits for the supervisor to restart.
        chaos=f"data_stall@{STEPS // 2}~120",
        expect_exits=(EXIT_WATCHDOG,),
        max_restarts=2,
        overrides={"dataset": {"num_workers": 2},
                   "resilience": {"watchdog_timeout": 5.0}},
        marker=r"\[watchdog\] no progress",
        note="stalled data producer: watchdog stack-dump + exit "
             f"{EXIT_WATCHDOG}, supervisor restart, auto_resume",
        check_after_fault=lambda save_dir: _postmortem_matches(
            save_dir, reason="watchdog", fault_step=STEPS // 2),
    ),
    "ckpt_corrupt_bitflip": Scenario(
        # The step-4 periodic save commits (manifest written), a byte in
        # its largest array payload is flipped on disk, then SIGKILL at
        # step 5 — a hard crash with a poisoned newest checkpoint. The
        # restart must NOT trust "finalized": verification fails step 4,
        # the lineage walk falls back to the verified step-2 save, and
        # the re-trained run still lands on the baseline's exact final
        # step/tokens. Saves are synchronous here so the commit (and the
        # corruption riding it) is ordered strictly before the kill.
        chaos=f"ckpt_corrupt_bitflip@{STEPS - 2},kill@{STEPS - 1}",
        expect_exits=(-signal.SIGKILL,),
        max_restarts=2,
        overrides={"checkpoint": {"async_save": False}},
        marker=r"failed verification",
        note="newest committed checkpoint bit-flipped, then SIGKILL: "
             "restart verifies, falls back to the prior verified step, "
             "re-trains to the baseline's final step",
        check_after_fault=lambda save_dir: _doctor_flags_exactly(
            save_dir, corrupt_step=STEPS - 2),
    ),
}


def run_dp_resize(workdir: str, verbose: bool = False) -> bool:
    """Elastic scale-out scenario — three topologies, one training run.

    Doesn't fit the Scenario dataclass (every leg needs its own config),
    so it is a custom runner registered next to SCENARIOS:

      baseline  dp=2 mbs=2 ga=1, fault-free, steps 1-6
      leg 1     dp=2, SIGKILL at step-3 begin (save @2 committed first)
      re-stamp  tools/elastic_resize.py --dp 1 rewrites the store offline
      leg 2     dp=1 mbs=2 ga=2, elastic OFF (the re-stamped store now IS
                dp=1), SIGKILL at step-5 begin (save @4 committed first)
      leg 3     dp=4 mbs=1 ga=1, checkpoint.elastic=true — the runtime
                resize path restores the dp=1-stamped step 4 into a dp=4
                mesh, trains to completion

    Global batch is 4 in every leg (2x2x1 = 2x1x2 = 1x4x1), so the loss
    trajectory is the baseline's modulo fp32 reduction order — compared
    per-step with tight tolerances. The resize must be booked: `resize`
    seconds and an `elastic_resize` event in the telemetry stream."""
    import numpy as np

    fail = lambda msg: (print(f"[chaos-cli] dp_resize: FAIL — {msg}"),  # noqa: E731
                        False)[1]

    def leg_config(ckpt_dir: str, *, dp: int, mbs: int, ga: int,
                   chaos_spec: str = "", elastic: bool = False) -> dict:
        cfg = scenario_config(os.path.dirname(ckpt_dir), chaos_spec,
                              {"checkpoint": {"async_save": False}})
        cfg["distributed"]["dp_size"] = dp
        cfg["training"]["micro_batch_size"] = mbs
        cfg["training"]["gradient_accumulation_steps"] = ga
        cfg["checkpoint"]["save_dir"] = ckpt_dir
        if elastic:
            cfg["checkpoint"]["elastic"] = True
        return cfg

    def run_leg(cfg: dict, cfg_name: str, leg_dir: str) -> int:
        cfg_path = os.path.join(leg_dir, cfg_name)
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        return _run_trainer(cfg_path, os.path.join(leg_dir, "run.log"), {})

    def step_losses(jsonl_path: str) -> dict:
        losses = {}
        with open(jsonl_path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn final line of a killed leg
                if ev.get("kind") == "step" and "loss" in ev:
                    losses[ev["step"]] = ev["loss"]  # last wins (replay)
        return losses

    # Fault-free dp=2 baseline: the trajectory every leg must stay on.
    base_dir = os.path.join(workdir, "baseline")
    os.makedirs(base_dir, exist_ok=True)
    base_ckpt = os.path.join(base_dir, "ckpt")
    rc = run_leg(leg_config(base_ckpt, dp=2, mbs=2, ga=1),
                 "config.json", base_dir)
    if rc != 0:
        return fail(f"baseline run exited {rc}")
    base_meta = _final_meta(base_ckpt)

    fault_dir = os.path.join(workdir, "fault")
    os.makedirs(fault_dir, exist_ok=True)
    ckpt_dir = os.path.join(fault_dir, "ckpt")

    # Leg 1: dp=2, killed at step-3 begin; the sync save @2 is durable.
    rc = run_leg(leg_config(ckpt_dir, dp=2, mbs=2, ga=1,
                            chaos_spec=f"kill@{STEPS // 2}"),
                 "config_dp2.json", fault_dir)
    if rc != -signal.SIGKILL:
        return fail(f"leg 1 (dp=2) exited {rc}, expected "
                    f"{-signal.SIGKILL} (SIGKILL)")

    # Offline re-stamp: the store becomes a dp=1 checkpoint (constant
    # global batch -> mbs 2 x ga 2), manifest re-committed.
    resize_log = os.path.join(fault_dir, "resize.log")
    with open(resize_log, "ab") as log:
        rc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "elastic_resize.py"),
             ckpt_dir, "--dp", "1"],
            stdout=log, stderr=subprocess.STDOUT, timeout=120).returncode
    if rc != 0:
        return fail(f"tools/elastic_resize.py --dp 1 exited {rc} "
                    f"(see {resize_log})")

    # Leg 2: dp=1, elastic OFF — restoring the re-stamped store must need
    # no special config. Killed at step-5 begin; sync save @4 durable.
    rc = run_leg(leg_config(ckpt_dir, dp=1, mbs=2, ga=2,
                            chaos_spec=f"kill@{STEPS - 1}"),
                 "config_dp1.json", fault_dir)
    if rc != -signal.SIGKILL:
        return fail(f"leg 2 (dp=1) exited {rc}, expected "
                    f"{-signal.SIGKILL} (SIGKILL)")

    # Leg 3: dp=4 with checkpoint.elastic — the runtime resize path
    # restores the dp=1-stamped step 4 into a dp=4 mesh and finishes.
    rc = run_leg(leg_config(ckpt_dir, dp=4, mbs=1, ga=1, elastic=True),
                 "config_dp4.json", fault_dir)
    if rc != 0:
        return fail(f"leg 3 (dp=4, elastic) exited {rc}, expected 0")

    with open(os.path.join(fault_dir, "run.log")) as f:
        log_text = f.read()
    if verbose:
        print(log_text)
    if not re.search(r"elastic resize:", log_text):
        return fail("marker /elastic resize:/ absent from the leg-3 log")

    meta = _final_meta(ckpt_dir)
    for key in ("step", "trained_tokens"):
        if meta[key] != base_meta[key]:
            return fail(f"final {key} {meta[key]} != fault-free baseline "
                        f"{base_meta[key]}")

    # Loss-trajectory parity: same global batch, same data order -> the
    # only legitimate difference across dp=2/1/4 is fp32 reduction order.
    base_losses = step_losses(os.path.join(base_ckpt, "telemetry.jsonl"))
    fault_losses = step_losses(os.path.join(ckpt_dir, "telemetry.jsonl"))
    if set(fault_losses) != set(base_losses):
        return fail(f"step sets differ: fault {sorted(fault_losses)} vs "
                    f"baseline {sorted(base_losses)}")
    steps = sorted(base_losses)
    bl = np.array([base_losses[s] for s in steps])
    fl = np.array([fault_losses[s] for s in steps])
    if not np.allclose(fl, bl, rtol=1e-3, atol=1e-4):
        return fail(f"loss trajectory diverged from baseline: "
                    f"{list(zip(steps, fl.tolist(), bl.tolist()))}")

    # The resize must be booked, not just survived.
    import telemetry_report

    summary = telemetry_report.summarize(telemetry_report.load_events(
        os.path.join(ckpt_dir, "telemetry.jsonl")))
    if summary["categories"].get("resize", 0.0) <= 0.0:
        return fail(f"no `resize` seconds in the goodput categories "
                    f"({summary['categories']})")
    if not summary.get("resize", {}).get("events"):
        return fail("no elastic_resize event in the telemetry stream")

    print(f"[chaos-cli] dp_resize: OK — dp 2->1 (offline re-stamp) ->4 "
          f"(runtime elastic), final step {meta['step']} / "
          f"{meta['trained_tokens']} tokens and loss trajectory match "
          f"baseline; resize booked "
          f"{summary['categories']['resize']:.3f}s")
    return True


def run_pp_resize(workdir: str, verbose: bool = False) -> bool:
    """Elastic PIPELINE resize — the dp_resize story on the pp axis.

    pp does not enter the global batch (mbs x ga x dp x ep), so every leg
    keeps mbs=2 ga=2 dp=1 untouched; what changes is the stage layout:

      baseline  pp=2 MPMD (per-stage programs), fault-free, steps 1-6
      leg 1     pp=2 MPMD, SIGKILL at step-3 begin (sync save @2 durable)
      re-stamp  tools/elastic_resize.py --pp 1 rewrites the store offline
                (even split: debug-tiny's 4 layers pad identically at
                pp=1 and pp=2, so the stack is shared — metadata only)
      leg 2     pp=1, the plain SPMD executor (config forbids MPMD at
                pp=1), elastic OFF — the re-stamped store simply IS a
                pp=1 checkpoint. SIGKILL at step-5 begin (save @4)
      leg 3     pp=2 MPMD again, checkpoint.elastic=true — the runtime
                elastic path restores the pp=1-stamped step 4 into a
                pp=2 mesh; the executor rebuilds stage programs and the
                schedule table from config and trains to completion

    Same acceptance bar as dp_resize (per-step loss parity vs baseline,
    final step/tokens equal, resize seconds + event booked) plus the
    MPMD-specific pin: the PR-9 prover re-proves the rebuilt pp=2 stage
    programs compile exactly once after the resize."""
    import numpy as np

    fail = lambda msg: (print(f"[chaos-cli] pp_resize: FAIL — {msg}"),  # noqa: E731
                        False)[1]

    def leg_config(ckpt_dir: str, *, pp: int, chaos_spec: str = "",
                   elastic: bool = False) -> dict:
        cfg = scenario_config(os.path.dirname(ckpt_dir), chaos_spec,
                              {"checkpoint": {"async_save": False}})
        cfg["distributed"].update(dp_size=1, tp_size=1, pp_size=pp)
        cfg["training"]["micro_batch_size"] = 2
        cfg["training"]["gradient_accumulation_steps"] = 2
        if pp > 1:
            cfg["pipeline"] = {"executor": "mpmd"}
        cfg["checkpoint"]["save_dir"] = ckpt_dir
        if elastic:
            cfg["checkpoint"]["elastic"] = True
        return cfg

    def run_leg(cfg: dict, cfg_name: str, leg_dir: str) -> tuple[int, str]:
        cfg_path = os.path.join(leg_dir, cfg_name)
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        return (_run_trainer(cfg_path, os.path.join(leg_dir, "run.log"),
                             {}), cfg_path)

    # Fault-free pp=2 MPMD baseline: the trajectory every leg must hold.
    base_dir = os.path.join(workdir, "baseline")
    os.makedirs(base_dir, exist_ok=True)
    base_ckpt = os.path.join(base_dir, "ckpt")
    rc, _ = run_leg(leg_config(base_ckpt, pp=2), "config.json", base_dir)
    if rc != 0:
        return fail(f"baseline run (pp=2 mpmd) exited {rc}")
    base_meta = _final_meta(base_ckpt)

    fault_dir = os.path.join(workdir, "fault")
    os.makedirs(fault_dir, exist_ok=True)
    ckpt_dir = os.path.join(fault_dir, "ckpt")

    # Leg 1: pp=2 MPMD, killed at step-3 begin; the sync save @2 durable.
    rc, _ = run_leg(leg_config(ckpt_dir, pp=2,
                               chaos_spec=f"kill@{STEPS // 2}"),
                    "config_pp2.json", fault_dir)
    if rc != -signal.SIGKILL:
        return fail(f"leg 1 (pp=2) exited {rc}, expected "
                    f"{-signal.SIGKILL} (SIGKILL)")

    # Offline re-stamp: the store becomes a pp=1 checkpoint. Pure-pp, so
    # the batch plan is untouched; the tool verifies the padded layer
    # stacks match before mutating anything.
    resize_log = os.path.join(fault_dir, "resize.log")
    with open(resize_log, "ab") as log:
        rc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "elastic_resize.py"),
             ckpt_dir, "--pp", "1"],
            stdout=log, stderr=subprocess.STDOUT, timeout=120).returncode
    if rc != 0:
        return fail(f"tools/elastic_resize.py --pp 1 exited {rc} "
                    f"(see {resize_log})")

    # Leg 2: pp=1 (SPMD — the executor fence requires pp>=2 for MPMD),
    # elastic OFF: the re-stamped store needs no special config. Killed
    # at step-5 begin; sync save @4 durable.
    rc, _ = run_leg(leg_config(ckpt_dir, pp=1,
                               chaos_spec=f"kill@{STEPS - 1}"),
                    "config_pp1.json", fault_dir)
    if rc != -signal.SIGKILL:
        return fail(f"leg 2 (pp=1) exited {rc}, expected "
                    f"{-signal.SIGKILL} (SIGKILL)")

    # Leg 3: pp=2 MPMD with checkpoint.elastic — the runtime elastic path
    # restores the pp=1-stamped step 4 into a pp=2 mesh; stage programs
    # and the schedule table rebuild from config at startup.
    rc, cfg3_path = run_leg(leg_config(ckpt_dir, pp=2, elastic=True),
                            "config_pp2_elastic.json", fault_dir)
    if rc != 0:
        return fail(f"leg 3 (pp=2, elastic) exited {rc}, expected 0")

    with open(os.path.join(fault_dir, "run.log")) as f:
        log_text = f.read()
    if verbose:
        print(log_text)
    if not re.search(r"elastic resize:", log_text):
        return fail("marker /elastic resize:/ absent from the leg-3 log")

    meta = _final_meta(ckpt_dir)
    for key in ("step", "trained_tokens"):
        if meta[key] != base_meta[key]:
            return fail(f"final {key} {meta[key]} != fault-free baseline "
                        f"{base_meta[key]}")

    # Loss-trajectory parity: identical global batch and data order; the
    # only legitimate pp=2-MPMD / pp=1-SPMD difference is fp32 reduction
    # order (the parity bar test_mpmd pins much tighter per-executor).
    def step_losses(jsonl_path: str) -> dict:
        losses = {}
        with open(jsonl_path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn final line of a killed leg
                if ev.get("kind") == "step" and "loss" in ev:
                    losses[ev["step"]] = ev["loss"]  # last wins (replay)
        return losses

    base_losses = step_losses(os.path.join(base_ckpt, "telemetry.jsonl"))
    fault_losses = step_losses(os.path.join(ckpt_dir, "telemetry.jsonl"))
    if set(fault_losses) != set(base_losses):
        return fail(f"step sets differ: fault {sorted(fault_losses)} vs "
                    f"baseline {sorted(base_losses)}")
    steps = sorted(base_losses)
    bl = np.array([base_losses[s] for s in steps])
    fl = np.array([fault_losses[s] for s in steps])
    if not np.allclose(fl, bl, rtol=1e-3, atol=1e-4):
        return fail(f"loss trajectory diverged from baseline: "
                    f"{list(zip(steps, fl.tolist(), bl.tolist()))}")

    # The resize must be booked, not just survived.
    import telemetry_report

    summary = telemetry_report.summarize(telemetry_report.load_events(
        os.path.join(ckpt_dir, "telemetry.jsonl")))
    if summary["categories"].get("resize", 0.0) <= 0.0:
        return fail(f"no `resize` seconds in the goodput categories "
                    f"({summary['categories']})")
    if not summary.get("resize", {}).get("events"):
        return fail("no elastic_resize event in the telemetry stream")

    # Compile-once pin on the REBUILT stages: re-prove leg 3's config
    # (the post-resize pp=2 MPMD layout) in a fresh process — every stage
    # program must compile exactly once. 2 stages x fwd/bwd = 4 programs.
    prover = ("import json, sys\n"
              "from picotron_tpu.config import load_config\n"
              "from picotron_tpu.analysis.variants import "
              "prove_mpmd_stages\n"
              "rep = prove_mpmd_stages(load_config(sys.argv[1]))\n"
              "print('PROVE ' + json.dumps(rep.info['variants']))\n"
              "sys.exit(0 if rep.ok() else 1)\n")
    env = dict(os.environ)
    for k in ("PICOTRON_COORDINATOR", "PICOTRON_NUM_PROCESSES",
              "PICOTRON_PROCESS_ID"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    res = subprocess.run([sys.executable, "-c", prover, cfg3_path],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    lines = [ln for ln in res.stdout.splitlines()
             if ln.startswith("PROVE ")]
    if res.returncode != 0 or not lines:
        return fail(f"post-resize stage prover exited {res.returncode}: "
                    f"{res.stdout[-500:]}{res.stderr[-500:]}")
    variants = json.loads(lines[-1][len("PROVE "):])
    if not variants.get("proven") or variants.get("programs") != 4:
        return fail(f"post-resize stages not proven compile-once: "
                    f"{variants}")

    print(f"[chaos-cli] pp_resize: OK — pp 2->1 (offline re-stamp) ->2 "
          f"(runtime elastic, MPMD rebuild), final step {meta['step']} / "
          f"{meta['trained_tokens']} tokens and loss trajectory match "
          f"baseline; resize booked "
          f"{summary['categories']['resize']:.3f}s; "
          f"{variants['programs']} rebuilt stage programs proven "
          f"compile-once")
    return True


def run_mpmd_sigterm(workdir: str, verbose: bool = False) -> bool:
    """Mid-schedule fault hardening on the MPMD executor — two legs.

    SIGTERM leg: `sigterm@3#2` lands the signal INSIDE the schedule walk
    at a named (stage, tick, op) of step 3 — the hardest place to die,
    with boundary buffers live and gradients half-accumulated. The
    record-only preemption handler means the walk drains to the step
    boundary, the emergency checkpoint persists a CLEAN step-3 state,
    exit 75, and the supervised restart resumes with ZERO replayed steps
    (telemetry stream is the witness).

    Hang leg: `hang@4~120#1` wedges the walk at tick 1 of step 4 for far
    longer than the watchdog timeout. The per-op heartbeat means the
    watchdog names the live (stage, tick, op) in its report — not a bare
    stack dump — then exits 77 for the supervisor; the restart resumes
    from the last periodic save (steps ARE replayed here: the hang, by
    design, persists nothing) and finishes at the baseline's step."""
    fail = lambda msg: (print(f"[chaos-cli] mpmd_sigterm: FAIL — {msg}"),  # noqa: E731
                        False)[1]

    def leg_config(ckpt_dir: str, chaos_spec: str,
                   overrides: dict) -> dict:
        cfg = scenario_config(os.path.dirname(ckpt_dir), chaos_spec,
                              {"checkpoint": {"async_save": False},
                               **overrides})
        cfg["distributed"].update(dp_size=1, tp_size=1, pp_size=2)
        cfg["training"]["micro_batch_size"] = 2
        cfg["training"]["gradient_accumulation_steps"] = 2
        cfg["pipeline"] = {"executor": "mpmd"}
        cfg["checkpoint"]["save_dir"] = ckpt_dir
        return cfg

    def run_leg(cfg: dict, leg_dir: str, extra_env: dict) -> int:
        cfg_path = os.path.join(leg_dir, "config.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        return _run_trainer(cfg_path, os.path.join(leg_dir, "run.log"),
                            extra_env)

    # Fault-free pp=2 MPMD baseline.
    base_dir = os.path.join(workdir, "baseline")
    os.makedirs(base_dir, exist_ok=True)
    base_ckpt = os.path.join(base_dir, "ckpt")
    rc = run_leg(leg_config(base_ckpt, "", {}), base_dir, {})
    if rc != 0:
        return fail(f"baseline run (pp=2 mpmd) exited {rc}")
    base_meta = _final_meta(base_ckpt)

    # ---- SIGTERM mid-schedule ------------------------------------------
    st_dir = os.path.join(workdir, "sigterm")
    os.makedirs(st_dir, exist_ok=True)
    st_ckpt = os.path.join(st_dir, "ckpt")
    st_cfg = leg_config(st_ckpt, f"sigterm@{STEPS // 2}#2", {})
    rc = run_leg(st_cfg, st_dir, {})
    if rc != EXIT_PREEMPTED:
        return fail(f"sigterm leg exited {rc}, expected {EXIT_PREEMPTED}")
    # Restart with injection disabled — the resubmission does not re-live
    # the preemption.
    rc = run_leg(st_cfg, st_dir, {"PICOTRON_CHAOS": ""})
    if rc != 0:
        return fail(f"sigterm-leg restart exited {rc}, expected 0")

    with open(os.path.join(st_dir, "run.log")) as f:
        st_log = f.read()
    if verbose:
        print(st_log)
    # The fault must really have landed mid-schedule, at the named tick.
    if not re.search(r"firing sigterm at schedule_tick step "
                     rf"{STEPS // 2} \(stage=\d+ tick=2 op=\w+", st_log):
        return fail("no mid-schedule sigterm firing (schedule_tick with "
                    "stage/tick/op) in the sigterm-leg log")
    if not re.search(r"emergency checkpoint ->", st_log):
        return fail("marker /emergency checkpoint ->/ absent — the drain "
                    "to the step boundary did not persist durable state")

    meta = _final_meta(st_ckpt)
    for key in ("step", "trained_tokens"):
        if meta[key] != base_meta[key]:
            return fail(f"sigterm leg final {key} {meta[key]} != baseline "
                        f"{base_meta[key]}")

    # Lossless resume: the emergency checkpoint carried the full step-3
    # state, so NO step number appears twice in the telemetry stream.
    import telemetry_report

    summary = telemetry_report.summarize(telemetry_report.load_events(
        os.path.join(st_ckpt, "telemetry.jsonl")))
    st = summary.get("steps") or {}
    if st.get("count") != STEPS or st.get("max") != STEPS:
        return fail(f"sigterm leg trained steps {st}, expected "
                    f"count=max={STEPS}")
    if st.get("replayed"):
        return fail(f"sigterm leg replayed {st['replayed']} step(s) — the "
                    f"mid-schedule preemption was supposed to drain to "
                    f"the boundary and lose nothing")

    # ---- forced hang mid-schedule --------------------------------------
    hg_dir = os.path.join(workdir, "hang")
    os.makedirs(hg_dir, exist_ok=True)
    hg_ckpt = os.path.join(hg_dir, "ckpt")
    hg_cfg = leg_config(
        hg_ckpt, f"hang@{STEPS - 2}~120#1",
        {"resilience": {"watchdog_timeout": 5.0}})
    rc = run_leg(hg_cfg, hg_dir, {})
    if rc != EXIT_WATCHDOG:
        return fail(f"hang leg exited {rc}, expected {EXIT_WATCHDOG}")
    rc = run_leg(hg_cfg, hg_dir, {"PICOTRON_CHAOS": ""})
    if rc != 0:
        return fail(f"hang-leg restart exited {rc}, expected 0")

    with open(os.path.join(hg_dir, "run.log")) as f:
        hg_log = f.read()
    if verbose:
        print(hg_log)
    # The watchdog report must NAME the wedged op, not just dump stacks.
    m = re.search(r"\[watchdog\] no progress .* last "
                  r"phase='pp_schedule stage=\d+ tick=\d+ op=\w+ mb=\d+'",
                  hg_log)
    if not m:
        return fail("watchdog report does not name the live "
                    "(stage, tick, op) — /pp_schedule stage=/ phase "
                    "absent from the hang-leg log")
    meta = _final_meta(hg_ckpt)
    for key in ("step", "trained_tokens"):
        if meta[key] != base_meta[key]:
            return fail(f"hang leg final {key} {meta[key]} != baseline "
                        f"{base_meta[key]}")

    print(f"[chaos-cli] mpmd_sigterm: OK — mid-schedule SIGTERM drained "
          f"to the step boundary (exit {EXIT_PREEMPTED}, 0 replayed "
          f"steps) and mid-schedule hang was watchdog-named "
          f"({m.group(0).split('last ')[-1]}); both legs finished at "
          f"baseline step {base_meta['step']}")
    return True


def run_slice_lost(workdir: str, verbose: bool = False) -> bool:
    """Whole-slice loss on a 2-slice job — THE failure mode multi-slice
    adds over a single pod. Custom runner (per-leg configs + an offline
    CLI step), registered next to SCENARIOS:

      baseline  dp=2 tp=2, single slice, fault-free, steps 1-6
      leg 1     dp=2 tp=2 slices=2 (dp carries the slice granule) —
                slice_lost@3: SIGKILL with the slice named in the log;
                the sync save @2 is durable and records slices=2 in its
                manifest topology
      re-stamp  tools/elastic_resize.py --slices 1 rewrites the store as
                single-slice (placement metadata only; dp untouched)
      leg 2     dp=1 tp=2 (one surviving slice's worth of chips) with
                checkpoint.elastic=true: the dp 2->1 mismatch rides the
                runtime resize path at constant global batch, is booked
                to the `resize` goodput category, and trains to done

    Final step/tokens and the per-step loss trajectory must match the
    fault-free baseline at the rtol=1e-3 house tolerance."""
    import numpy as np

    from picotron_tpu.resilience import elastic

    fail = lambda msg: (print(f"[chaos-cli] slice_lost: FAIL — {msg}"),  # noqa: E731
                        False)[1]

    def leg_config(ckpt_dir: str, *, dp: int, mbs: int, ga: int,
                   slices: int = 1, chaos_spec: str = "",
                   elastic_on: bool = False) -> dict:
        cfg = scenario_config(os.path.dirname(ckpt_dir), chaos_spec,
                              {"checkpoint": {"async_save": False}})
        cfg["distributed"]["dp_size"] = dp
        cfg["distributed"]["slices"] = slices
        cfg["training"]["micro_batch_size"] = mbs
        cfg["training"]["gradient_accumulation_steps"] = ga
        cfg["checkpoint"]["save_dir"] = ckpt_dir
        if elastic_on:
            cfg["checkpoint"]["elastic"] = True
        return cfg

    def run_leg(cfg: dict, cfg_name: str, leg_dir: str) -> int:
        cfg_path = os.path.join(leg_dir, cfg_name)
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        return _run_trainer(cfg_path, os.path.join(leg_dir, "run.log"), {})

    def step_losses(jsonl_path: str) -> dict:
        losses = {}
        with open(jsonl_path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn final line of a killed leg
                if ev.get("kind") == "step" and "loss" in ev:
                    losses[ev["step"]] = ev["loss"]  # last wins (replay)
        return losses

    def newest_step_dir(ckpt_dir: str) -> str:
        steps = sorted(
            int(m.group(1)) for d in os.listdir(ckpt_dir)
            if (m := re.fullmatch(r"step_(\d+)", d))
            and os.path.isdir(os.path.join(ckpt_dir, d, "state")))
        return os.path.join(ckpt_dir, f"step_{steps[-1]:08d}")

    # Fault-free single-slice baseline: the trajectory to stay on.
    base_dir = os.path.join(workdir, "baseline")
    os.makedirs(base_dir, exist_ok=True)
    base_ckpt = os.path.join(base_dir, "ckpt")
    rc = run_leg(leg_config(base_ckpt, dp=2, mbs=2, ga=1),
                 "config.json", base_dir)
    if rc != 0:
        return fail(f"baseline run exited {rc}")
    base_meta = _final_meta(base_ckpt)

    fault_dir = os.path.join(workdir, "fault")
    os.makedirs(fault_dir, exist_ok=True)
    ckpt_dir = os.path.join(fault_dir, "ckpt")

    # Leg 1: 2-slice run, a whole slice lost at step-3 begin; the sync
    # save @2 is durable.
    rc = run_leg(leg_config(ckpt_dir, dp=2, mbs=2, ga=1, slices=2,
                            chaos_spec=f"slice_lost@{STEPS // 2}"),
                 "config_slices2.json", fault_dir)
    if rc != -signal.SIGKILL:
        return fail(f"leg 1 (slices=2) exited {rc}, expected "
                    f"{-signal.SIGKILL} (SIGKILL)")
    with open(os.path.join(fault_dir, "run.log")) as f:
        leg1_log = f.read()
    if "slice_lost: the slice hosting process" not in leg1_log:
        return fail("slice_lost firing (with the lost slice named) "
                    "absent from the leg-1 log")
    saved = elastic.saved_topology(newest_step_dir(ckpt_dir)) or {}
    if saved.get("slices") != 2:
        return fail(f"durable save records topology {saved}, expected "
                    f"slices=2 in its manifest")

    # Offline re-stamp: single-slice store (the survivors' shape).
    resize_log = os.path.join(fault_dir, "resize.log")
    with open(resize_log, "ab") as log:
        rc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "elastic_resize.py"),
             ckpt_dir, "--slices", "1"],
            stdout=log, stderr=subprocess.STDOUT, timeout=120).returncode
    if rc != 0:
        return fail(f"tools/elastic_resize.py --slices 1 exited {rc} "
                    f"(see {resize_log})")
    saved = elastic.saved_topology(newest_step_dir(ckpt_dir)) or {}
    if saved.get("slices", 1) != 1:
        return fail(f"re-stamped store still records {saved}")

    # Leg 2: one slice's worth of chips (dp=1), checkpoint.elastic — the
    # dp 2->1 mismatch reshards at restore time, booked as `resize`.
    rc = run_leg(leg_config(ckpt_dir, dp=1, mbs=2, ga=2, elastic_on=True),
                 "config_dp1.json", fault_dir)
    if rc != 0:
        return fail(f"leg 2 (dp=1, elastic) exited {rc}, expected 0")

    with open(os.path.join(fault_dir, "run.log")) as f:
        log_text = f.read()
    if verbose:
        print(log_text)
    if not re.search(r"elastic resize:", log_text):
        return fail("marker /elastic resize:/ absent from the leg-2 log")

    meta = _final_meta(ckpt_dir)
    for key in ("step", "trained_tokens"):
        if meta[key] != base_meta[key]:
            return fail(f"final {key} {meta[key]} != fault-free baseline "
                        f"{base_meta[key]}")

    base_losses = step_losses(os.path.join(base_ckpt, "telemetry.jsonl"))
    fault_losses = step_losses(os.path.join(ckpt_dir, "telemetry.jsonl"))
    if set(fault_losses) != set(base_losses):
        return fail(f"step sets differ: fault {sorted(fault_losses)} vs "
                    f"baseline {sorted(base_losses)}")
    steps = sorted(base_losses)
    bl = np.array([base_losses[s] for s in steps])
    fl = np.array([fault_losses[s] for s in steps])
    if not np.allclose(fl, bl, rtol=1e-3, atol=1e-4):
        return fail(f"loss trajectory diverged from baseline: "
                    f"{list(zip(steps, fl.tolist(), bl.tolist()))}")

    import telemetry_report

    summary = telemetry_report.summarize(telemetry_report.load_events(
        os.path.join(ckpt_dir, "telemetry.jsonl")))
    if summary["categories"].get("resize", 0.0) <= 0.0:
        return fail(f"no `resize` seconds in the goodput categories "
                    f"({summary['categories']})")
    if not summary.get("resize", {}).get("events"):
        return fail("no elastic_resize event in the telemetry stream")

    print(f"[chaos-cli] slice_lost: OK — 2-slice run lost a slice, "
          f"re-stamped --slices 1, finished at dp=1 via runtime elastic; "
          f"final step {meta['step']} / {meta['trained_tokens']} tokens "
          f"and loss trajectory match baseline; resize booked "
          f"{summary['categories']['resize']:.3f}s")
    return True


def _doctor_flags_exactly(save_dir: str, corrupt_step: int):
    """tools/ckpt_doctor.py over the faulted store must flag exactly the
    injected-corrupt step and pass the rest (the fsck half of the
    corruption acceptance criteria)."""
    import ckpt_doctor

    rows = ckpt_doctor.scan(save_dir)
    bad = [r["step"] for r in rows if r["verdict"] == "corrupt"]
    good = [r["step"] for r in rows
            if r["verdict"] in ("verified", "legacy")]
    if bad != [corrupt_step]:
        return (f"ckpt_doctor flagged corrupt steps {bad}, expected "
                f"exactly [{corrupt_step}] (rows: {rows})")
    if not good:
        return f"ckpt_doctor found no restorable step besides the corrupt one"
    return None


def _run_bench_fleet(leg_dir: str, extra_args: list,
                     telemetry: str | None = None) -> dict:
    """One `bench.py --serve --fleet` leg in a subprocess (2 simulated
    CPU devices, so replicas really live on distinct devices); returns
    the bench JSON row."""
    os.makedirs(leg_dir, exist_ok=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PICOTRON_PREFLIGHT"] = "0"
    env.pop("PICOTRON_CHAOS", None)  # the leg's --chaos is the only fault
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "bench.py"),
           "--serve", "--cpu", "--model", "debug-tiny", "--prompt-len", "16",
           "--max-new-tokens", "8", "--serve-slots", "3", "--block-size",
           "4", "--prefill-chunk", "4", "--serve-temperature", "0.7",
           "--serve-seed", "7"] + extra_args
    if telemetry:
        cmd += ["--telemetry", telemetry]
    log_path = os.path.join(leg_dir, "run.log")
    with open(log_path, "ab") as log:
        proc = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=log,
                              env=env, timeout=600)
    with open(log_path, "ab") as log:
        log.write(proc.stdout)
    if proc.returncode != 0:
        raise RuntimeError(f"bench fleet leg exited {proc.returncode} "
                           f"(log: {log_path})")
    for line in reversed(proc.stdout.decode().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"no JSON row in bench output (log: {log_path})")


def run_serve_engine_dead(workdir: str, verbose: bool = False) -> bool:
    """Engine failover under load — the serving half of the fault matrix.

    Oracle leg: fleet of 1, no faults, temperature 0.7. Fault leg: fleet
    of 2 with `engine_dead@2` fired in the dispatch loop — engine killed
    abruptly (state discarded wholesale) while requests are resident.
    The survivor must finish EVERY request with per-request token
    digests IDENTICAL to the oracle's (the (request id, token index)
    sampling-key fold makes re-dispatched continuations bit-exact at any
    temperature), show zero leaked blocks, and leave a
    serve_engine_dead flightdeck postmortem. A repeat of the fault leg
    must reproduce the digests exactly — recovery is deterministic, not
    merely successful."""
    fail = lambda msg: (print(f"[chaos-cli] serve_engine_dead: FAIL — "  # noqa: E731
                              f"{msg}"), False)[1]
    n_req = 8
    common = ["--requests", str(n_req)]

    oracle = _run_bench_fleet(os.path.join(workdir, "oracle"),
                              common + ["--fleet", "1"])
    tel_dir = os.path.join(workdir, "fault")
    fault = _run_bench_fleet(
        tel_dir, common + ["--fleet", "2", "--chaos", "engine_dead@2"],
        telemetry=os.path.join(tel_dir, "telemetry.jsonl"))
    if verbose:
        print(json.dumps(oracle), "\n", json.dumps(fault))

    if fault["engines_dead"] != 1:
        return fail(f"engines_dead {fault['engines_dead']} != 1 — the "
                    f"chaos kill did not land")
    if fault["completed"] != n_req or fault["shed"]:
        return fail(f"survivor finished {fault['completed']}/{n_req} "
                    f"(shed {fault['shed']}) — every request must "
                    f"complete on the surviving engine")
    if fault["redispatched"] < 1:
        return fail("no requests were re-dispatched — the engine died "
                    "with nothing in flight, so the scenario proved "
                    "nothing")
    if fault["request_digests"] != oracle["request_digests"]:
        bad = [k for k, v in oracle["request_digests"].items()
               if fault["request_digests"].get(k) != v]
        return fail(f"token parity broken after failover for request(s) "
                    f"{bad} — re-dispatched continuations must be "
                    f"bit-identical to the fault-free oracle")
    if fault["leaked_blocks"]:
        return fail(f"{fault['leaked_blocks']} leaked block(s) on "
                    f"survivor pools after the trace drained")

    pm_path = os.path.join(tel_dir, "flightdeck_postmortem.json")
    if not os.path.exists(pm_path):
        return fail(f"no flightdeck postmortem at {pm_path}")
    with open(pm_path) as f:
        pm = json.load(f)
    if pm.get("reason") != "serve_engine_dead":
        return fail(f"postmortem reason {pm.get('reason')!r} != "
                    f"'serve_engine_dead'")

    repeat = _run_bench_fleet(
        os.path.join(workdir, "repeat"),
        common + ["--fleet", "2", "--chaos", "engine_dead@2"])
    if repeat["request_digests"] != fault["request_digests"] \
            or repeat["redispatched"] != fault["redispatched"]:
        return fail("fault leg is not deterministic across repeats "
                    "(digests or redispatch count changed)")

    dead_engine = (pm.get("extra") or {}).get("engine")
    print(f"[chaos-cli] serve_engine_dead: OK — engine killed mid-burst "
          f"(postmortem engine {dead_engine}), survivor finished "
          f"{fault['completed']}/{n_req} requests bit-identical to the "
          f"single-engine oracle ({fault['redispatched']} re-dispatched), "
          f"0 leaked blocks, deterministic on repeat")
    return True


def run_serve_overload(workdir: str, verbose: bool = False) -> bool:
    """Deadline load shedding under a saturation burst.

    Both legs run a 1-slot engine on the same all-at-t=0 burst (10
    requests into one decode slot — a 10x overload). The no-deadline leg
    serves everything late; the deadline leg sheds the requests whose
    VIRTUAL-clock queue wait exceeds --deadline-ms. Pins: the shed set
    is non-empty and identical across repeats (the shed decision is a
    pure function of the trace), admitted requests' token digests match
    the no-deadline leg bit-for-bit (shedding neighbors must not perturb
    sampling), every admitted queue wait respects the deadline (the
    graceful-degradation SLO), and the shed seconds are booked to the
    telemetry ledger's `shed` category, rendered by telemetry_report."""
    fail = lambda msg: (print(f"[chaos-cli] serve_overload: FAIL — "  # noqa: E731
                              f"{msg}"), False)[1]
    n_req = 10
    deadline_ms = 6.0
    burst = ["--requests", str(n_req), "--serve-slots", "1",
             "--rate", "0"]

    unloaded = _run_bench_fleet(os.path.join(workdir, "no_deadline"),
                                burst + ["--fleet", "1"])
    tel_dir = os.path.join(workdir, "deadline")
    tel_path = os.path.join(tel_dir, "telemetry.jsonl")
    shedleg = _run_bench_fleet(
        tel_dir,
        burst + ["--fleet", "1", "--deadline-ms", str(deadline_ms)],
        telemetry=tel_path)
    if verbose:
        print(json.dumps(unloaded), "\n", json.dumps(shedleg))

    if not shedleg["shed"]:
        return fail("burst shed nothing — the overload never tripped "
                    "the deadline, scenario proves nothing")
    if shedleg["completed"] + shedleg["shed"] != n_req:
        return fail(f"completed {shedleg['completed']} + shed "
                    f"{shedleg['shed']} != {n_req} submitted")
    admitted = {k: v for k, v in shedleg["request_digests"].items()}
    mismatch = [k for k, v in admitted.items()
                if unloaded["request_digests"].get(k) != v]
    if mismatch:
        return fail(f"admitted request(s) {mismatch} decoded different "
                    f"tokens than the no-deadline leg — shedding "
                    f"neighbors must not perturb sampling")
    qw95 = shedleg["queue_wait_p95_ms"]
    if qw95 is None or qw95 > deadline_ms + 1e-6:
        return fail(f"admitted queue wait p95 {qw95} ms exceeds the "
                    f"{deadline_ms} ms deadline — admission let an "
                    f"expired request through")

    repeat = _run_bench_fleet(
        os.path.join(workdir, "repeat"),
        burst + ["--fleet", "1", "--deadline-ms", str(deadline_ms)])
    if repeat["shed_ids"] != shedleg["shed_ids"] \
            or repeat["request_digests"] != shedleg["request_digests"]:
        return fail(f"shed set not deterministic: {shedleg['shed_ids']} "
                    f"vs {repeat['shed_ids']} on repeat")

    import telemetry_report

    summary = telemetry_report.summarize(
        telemetry_report.load_events(tel_path))
    shed_s = (summary.get("categories") or {}).get("shed", 0.0)
    if not shed_s > 0.0:
        return fail("no seconds booked to the `shed` ledger category in "
                    "the telemetry stream")
    sv = summary.get("serving") or {}
    if sv.get("shed") != shedleg["shed"]:
        return fail(f"telemetry_report serving view shed {sv.get('shed')} "
                    f"!= bench row {shedleg['shed']}")
    if "shed" not in telemetry_report.render(summary):
        return fail("telemetry_report render does not show the shed row")

    print(f"[chaos-cli] serve_overload: OK — burst shed "
          f"{shedleg['shed']}/{n_req} deterministically "
          f"(ids {shedleg['shed_ids']}), admitted tokens bit-identical "
          f"to the no-deadline leg, queue wait p95 {qw95} ms <= "
          f"{deadline_ms} ms deadline, {round(shed_s, 4)}s booked to "
          f"`shed`")
    return True


def _postmortem_matches(save_dir: str, reason: str, fault_step: int):
    """The flightdeck flight recorder (telemetry/flightdeck/flight.py)
    must have left a postmortem dump next to the checkpoints whose
    reason and last recorded step match the injected fault — the
    abnormal-exit half of the flightdeck acceptance criteria."""
    path = os.path.join(save_dir, "flightdeck_postmortem.json")
    if not os.path.exists(path):
        return f"no flightdeck_postmortem.json under {save_dir}"
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return f"unreadable postmortem {path}: {e}"
    if doc.get("reason") != reason:
        return (f"postmortem reason {doc.get('reason')!r} != expected "
                f"{reason!r}")
    if doc.get("step") != fault_step:
        return (f"postmortem last recorded step {doc.get('step')!r} != "
                f"fault step {fault_step}")
    if not doc.get("steps"):
        return "postmortem carries an empty last-K-steps window"
    return None


def scenario_config(workdir: str, chaos_spec: str,
                    overrides: dict) -> dict:
    cfg = {
        "distributed": {"dp_size": 2, "tp_size": 2, "use_cpu": True},
        "model": {"name": "debug-tiny", "dtype": "float32"},
        "training": {"total_train_steps": STEPS, "seq_length": 32,
                     "micro_batch_size": 2,
                     "gradient_accumulation_steps": 1,
                     "remat": False, "seed": 5},
        "dataset": {"name": "synthetic", "num_workers": 0},
        "checkpoint": {"save_dir": os.path.join(workdir, "ckpt"),
                       "save_frequency": 2, "auto_resume": True},
        "logging": {"log_frequency": 1},
        "resilience": {"chaos": chaos_spec,
                       "retry_base_delay": 0.05, "retry_max_delay": 0.2},
    }
    for section, vals in overrides.items():
        cfg.setdefault(section, {}).update(vals)
    return cfg


def _run_trainer(cfg_path: str, log_path: str, extra_env: dict) -> int:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # trainer provisions its own device count
    for k in ("PICOTRON_COORDINATOR", "PICOTRON_NUM_PROCESSES",
              "PICOTRON_PROCESS_ID"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PICOTRON_PREFLIGHT"] = "0"  # scenario wall-time, not shardcheck's
    env.update(extra_env)
    with open(log_path, "ab") as log:
        return subprocess.run(
            [sys.executable, "-m", "picotron_tpu.train",
             "--config", cfg_path],
            stdout=log, stderr=subprocess.STDOUT, env=env,
            timeout=600).returncode


def _final_meta(save_dir: str) -> dict:
    """meta.json of the newest step dir that has a committed state dir.
    The runs verified here exited 0, so the last save is finalized."""
    steps = sorted(
        int(m.group(1)) for d in os.listdir(save_dir)
        if (m := re.fullmatch(r"step_(\d+)", d))
        and os.path.isdir(os.path.join(save_dir, d, "state")))
    if not steps:
        raise FileNotFoundError(f"no checkpoints under {save_dir}")
    with open(os.path.join(save_dir, f"step_{steps[-1]:08d}",
                           "meta.json")) as f:
        return json.load(f)


def run_scenario(name: str, workdir: str, verbose: bool = False) -> bool:
    sc = SCENARIOS[name]
    fail = lambda msg: (print(f"[chaos-cli] {name}: FAIL — {msg}"),  # noqa: E731
                        False)[1]

    # Fault-free baseline: what "no training progress lost" means.
    base_dir = os.path.join(workdir, "baseline")
    os.makedirs(base_dir, exist_ok=True)
    base_cfg = scenario_config(base_dir, "", sc.overrides)
    base_path = os.path.join(base_dir, "config.json")
    with open(base_path, "w") as f:
        json.dump(base_cfg, f)
    rc = _run_trainer(base_path, os.path.join(base_dir, "run.log"), {})
    if rc != 0:
        return fail(f"baseline run exited {rc}")
    base_meta = _final_meta(base_cfg["checkpoint"]["save_dir"])

    # Fault run under supervision.
    fault_dir = os.path.join(workdir, "fault")
    os.makedirs(fault_dir, exist_ok=True)
    cfg = scenario_config(fault_dir, sc.chaos, sc.overrides)
    cfg_path = os.path.join(fault_dir, "config.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    log_path = os.path.join(fault_dir, "run.log")
    exits = []
    for attempt in range(sc.max_restarts + 1):
        # Restarts disable injection via the env override — a resubmitted
        # job does not re-live the environmental fault.
        extra = {} if attempt == 0 else {"PICOTRON_CHAOS": ""}
        rc = _run_trainer(cfg_path, log_path, extra)
        exits.append(rc)
        if attempt == 0 and sc.check_after_fault is not None:
            # Inspect the faulted store BEFORE any restart repairs it
            # (e.g. ckpt_doctor over the really-corrupted lineage).
            err = sc.check_after_fault(cfg["checkpoint"]["save_dir"])
            if err:
                return fail(err)
        if rc == 0:
            break
        if rc not in sc.expect_exits:
            return fail(f"unexpected exit {rc} (allowed: 0 or "
                        f"{sc.expect_exits}); exits so far {exits}")
    if exits[-1] != 0:
        return fail(f"did not recover within {sc.max_restarts} restarts "
                    f"(exits {exits})")

    with open(log_path) as f:
        log_text = f.read()
    if verbose:
        print(log_text)
    if not re.search(sc.marker, log_text):
        return fail(f"recovery marker /{sc.marker}/ absent from {log_path}")
    meta = _final_meta(cfg["checkpoint"]["save_dir"])
    for key in ("step", "trained_tokens"):
        if meta[key] != base_meta[key]:
            return fail(f"final {key} {meta[key]} != fault-free baseline "
                        f"{base_meta[key]}")
    print(f"[chaos-cli] {name}: OK — exits {exits}, final step "
          f"{meta['step']} / {meta['trained_tokens']} tokens match "
          f"baseline")
    return True


# Scenarios with bespoke runners (multiple per-leg configs, offline CLI
# steps): registered next to the Scenario table so --list/--scenario/--all
# treat them uniformly.
CUSTOM_SCENARIOS: dict[str, tuple[Callable, str]] = {
    "dp_resize": (run_dp_resize,
                  "elastic scale-out: SIGKILL a dp=2 run, re-stamp to "
                  "dp=1 offline, SIGKILL again, finish at dp=4 via "
                  "checkpoint.elastic; loss-trajectory parity vs the "
                  "dp=2 baseline, resize seconds booked"),
    "pp_resize": (run_pp_resize,
                  "elastic pipeline resize: SIGKILL a pp=2 MPMD run, "
                  "re-stamp to pp=1 offline (--pp), SIGKILL again, "
                  "finish at pp=2 via checkpoint.elastic; loss parity "
                  "vs the pp=2 baseline, resize booked, rebuilt stage "
                  "programs proven compile-once"),
    "slice_lost": (run_slice_lost,
                   "whole-slice loss on a 2-slice job: slice_lost@3 "
                   "SIGKILLs with the slice named, "
                   "tools/elastic_resize.py --slices 1 re-stamps "
                   "the store, the survivors finish at dp=1 via "
                   "checkpoint.elastic; loss parity vs the single-slice "
                   "baseline, resize booked"),
    "mpmd_sigterm": (run_mpmd_sigterm,
                     "mid-schedule MPMD faults: SIGTERM at a named "
                     "(stage, tick, op) drains to the step boundary "
                     "(exit 75, zero replayed steps on resume); forced "
                     "hang is watchdog-reported naming the live op"),
    "serve_engine_dead": (run_serve_engine_dead,
                          "kill 1 of 2 serving replicas mid-burst: the "
                          "survivor finishes every request bit-identical "
                          "to the single-engine oracle (temp 0.7), zero "
                          "leaked blocks, serve_engine_dead postmortem, "
                          "deterministic on repeat"),
    "serve_overload": (run_serve_overload,
                       "deadline shedding under a 10x burst: "
                       "deterministic shed set, admitted tokens match "
                       "the no-deadline leg bit-for-bit, queue wait p95 "
                       "within the deadline, shed seconds booked to the "
                       "`shed` ledger category"),
}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="picotron-tpu fault-recovery scenario runner")
    ap.add_argument("--scenario", action="append", default=[],
                    choices=sorted(set(SCENARIOS) | set(CUSTOM_SCENARIOS)),
                    help="scenario to run (repeatable)")
    ap.add_argument("--all", action="store_true",
                    help="run every scenario")
    ap.add_argument("--list", action="store_true",
                    help="list scenarios and exit")
    ap.add_argument("--workdir", default=None,
                    help="scratch directory (default: a fresh tempdir)")
    ap.add_argument("--verbose", action="store_true",
                    help="print the fault run's log")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.list:
        for name, sc in SCENARIOS.items():
            print(f"{name:14s} chaos={sc.chaos!r:24s} {sc.note}")
        for name, (_fn, note) in CUSTOM_SCENARIOS.items():
            print(f"{name:14s} chaos={'custom':26s} {note}")
        return 0
    names = sorted(set(args.scenario)) if args.scenario else []
    if args.all:
        names = sorted(set(SCENARIOS) | set(CUSTOM_SCENARIOS))
    if not names:
        build_parser().error("pick --scenario NAME (repeatable), --all, "
                             "or --list")
    workdir = args.workdir
    if workdir is None:
        import tempfile

        workdir = tempfile.mkdtemp(prefix="picotron-chaos-")
    ok = True
    for name in names:
        sub = os.path.join(workdir, name)
        os.makedirs(sub, exist_ok=True)
        if name in CUSTOM_SCENARIOS:
            ok &= CUSTOM_SCENARIOS[name][0](sub, verbose=args.verbose)
        else:
            ok &= run_scenario(name, sub, verbose=args.verbose)
    print(f"[chaos-cli] {'all scenarios recovered' if ok else 'FAILURES'} "
          f"(workdir {workdir})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
