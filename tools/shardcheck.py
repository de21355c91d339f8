#!/usr/bin/env python
"""Will this config's SPMD program do what you think? — static audit, no TPU.

Runs the shardcheck analyzers (picotron_tpu/analysis) for one or more
configs by abstract evaluation on simulated host devices:

- spec lint: PartitionSpec pytree vs param pytree vs mesh, path-level errors
- collective-schedule audit: parse the lowered step's HLO — the grad
  all-reduce over the fused data axes must exist, pipeline ppermutes and
  expert all_to_alls must exist where the layout promises them, and no
  all-gather may exceed the replication byte budget
- donation + recompilation hazards: every TrainState buffer donated; the
  step's output avals identical to its inputs (anything else recompiles
  every step)
- jit-variant prover (--variants): statically enumerate the abstract
  signatures (shape/dtype/sharding/commitment) reaching each jit entry
  point — train step, serve prefill/decode — and prove compile-once
- source lint: no semi-private jax.core, no host callbacks in library
  code, no uncommitted jax.device_put

Usage:

  python tools/shardcheck.py --config runs/smollm17-dp8/config.json
  python tools/shardcheck.py --preset tiny-dense --preset tiny-moe-ep
  python tools/shardcheck.py --all-presets --verbose
  python tools/shardcheck.py --all-presets --variants --json

--json emits one machine-readable line per config for every subcommand
(findings + the per-check info dict); a config that cannot trace at all
on this JAX becomes a row with a "fatal" key instead of killing the
sweep.

Exit status 0 iff every config is green. The preset matrix covers the
layouts the test tier exercises (dense/MoE, pp>1, ep>1, offload on/off) on
at most 8 simulated devices, so the whole matrix runs on a laptop.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# (model, distributed kwargs, training kwargs[, pipeline kwargs]) tuples;
# every preset fits the 8 simulated host devices the test tier provisions.
PRESETS: dict[str, tuple] = {
    "tiny-1chip": ("debug-tiny", {}, {}),
    "tiny-dense": ("debug-tiny",
                   dict(dp_size=2, tp_size=2, cp_size=2),
                   dict(gradient_accumulation_steps=2)),
    "tiny-dense-pp": ("debug-tiny",
                      dict(pp_size=2, dp_size=2),
                      dict(gradient_accumulation_steps=2)),
    # the MPMD executor's per-stage programs (parallel/mpmd.py): the
    # --variants prover must certify each stage fwd/bwd jit compiles
    # exactly once across every call the schedule table makes
    "tiny-dense-pp-mpmd": ("debug-tiny",
                           dict(pp_size=2, dp_size=2),
                           dict(gradient_accumulation_steps=2),
                           dict(executor="mpmd")),
    "tiny-moe-ep": ("debug-tiny-moe",
                    dict(ep_size=2, dp_size=2),
                    dict(gradient_accumulation_steps=2)),
    "tiny-dense-offload": ("debug-tiny", {},
                           dict(gradient_accumulation_steps=2,
                                optimizer_offload=True)),
    "tiny-moe-offload": ("debug-tiny-moe", dict(ep_size=2),
                         dict(gradient_accumulation_steps=2,
                              optimizer_offload=True)),
    # the fused grad engine on its widened axes (parallel/fused_bwd.py):
    # the audit must see the same per-axis schedule the AD engine lowers —
    # SP all-gather/reduce-scatter pair, cp4 ring ppermute — from the
    # manual backward scan (collectives.py presence rules)
    "tiny-sp-fused": ("debug-tiny",
                      dict(dp_size=2, tp_size=2, sequence_parallel=True),
                      dict(gradient_accumulation_steps=2,
                           grad_engine="fused",
                           remat_policy="dots_attn")),
    "tiny-cp4-fused": ("debug-tiny", dict(dp_size=2, cp_size=4),
                       dict(gradient_accumulation_steps=2,
                            grad_engine="fused",
                            remat_policy="dots_attn")),
    # the mesh cp flavor's 2D schedule (ops/mesh_attention.py): the audit
    # must see the head-scatter all_to_all on the cp_y subgroup AND the
    # row ring ppermute on the cp_x rows — and no collective widened to
    # the full cp axis (collectives.py mesh presence rule)
    "tiny-cp4-mesh": ("debug-tiny",
                      dict(dp_size=2, cp_size=4, cp_flavor="mesh",
                           cp_mesh="2x2"),
                      dict(gradient_accumulation_steps=2)),
    "tiny-cp4-mesh-fused": ("debug-tiny",
                            dict(dp_size=2, cp_size=4, cp_flavor="mesh",
                                 cp_mesh="2x2"),
                            dict(gradient_accumulation_steps=2,
                                 grad_engine="fused",
                                 remat_policy="dots_attn")),
}


def preset_config(name: str):
    from picotron_tpu.config import (
        Config, DistributedConfig, ModelConfig, PipelineConfig,
        TrainingConfig, resolve_preset,
    )

    model, dist_kw, train_kw, *rest = PRESETS[name]
    pipe_kw = rest[0] if rest else {}
    cfg = Config(
        distributed=DistributedConfig(**dist_kw),
        model=ModelConfig(name=model, **resolve_preset(model)),
        training=TrainingConfig(seq_length=64, micro_batch_size=1,
                                **train_kw),
        pipeline=PipelineConfig(**pipe_kw),
    )
    cfg.validate()
    return cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="picotron-tpu static SPMD analysis (shardcheck)")
    ap.add_argument("--config", action="append", default=[],
                    help="config JSON path (repeatable)")
    ap.add_argument("--preset", action="append", default=[],
                    choices=sorted(PRESETS),
                    help="built-in tiny config (repeatable)")
    ap.add_argument("--all-presets", action="store_true",
                    help="run the full preset matrix (dense/MoE, pp>1, "
                         "ep>1, offload on/off)")
    ap.add_argument("--checks", default=None,
                    help="comma-separated subset of spec,source,"
                         "collectives,variants,donation,stability "
                         "(default: all)")
    ap.add_argument("--variants", action="store_true",
                    help="focus on the static jit-variant prover: abstract "
                         "signatures reaching each jit entry point, "
                         "compile-once proof (spec lint still runs first)")
    ap.add_argument("--budget-mb", type=float, default=None,
                    help="all-gather replication budget in MiB (default: "
                         "the largest param leaf / activation block)")
    ap.add_argument("--json", action="store_true",
                    help="one JSON line per config instead of the report")
    ap.add_argument("--verbose", action="store_true",
                    help="include info-level findings and summary tables")
    ap.add_argument("--cost", action="store_true",
                    help="price the traced collective schedule with the "
                         "ICI cost model and compare the config against "
                         "the layout planner's best at equal chip count "
                         "(picotron_tpu/analysis/cost_model.py)")
    ap.add_argument("--generation", default="v5e",
                    choices=["v4", "v5e", "v5p", "v6e"],
                    help="TPU generation for --cost (ICI bandwidth, "
                         "topology, HBM)")
    args = ap.parse_args(argv)

    names = list(args.preset) + (sorted(PRESETS) if args.all_presets
                                 else [])
    if not names and not args.config:
        ap.error("nothing to check: pass --config, --preset, or "
                 "--all-presets")

    from picotron_tpu.analysis import ALL_CHECKS, run_shardcheck
    from picotron_tpu.config import load_config

    if args.checks:
        checks = tuple(c.strip() for c in args.checks.split(","))
    elif args.variants:
        checks = ("spec", "variants")
    else:
        checks = ALL_CHECKS
    unknown = set(checks) - set(ALL_CHECKS)
    if unknown:
        ap.error(f"unknown checks {sorted(unknown)}; valid: {ALL_CHECKS}")
    budget = (int(args.budget_mb * 1024 * 1024)
              if args.budget_mb is not None else None)

    targets = [(f"preset:{n}", preset_config(n)) for n in names]
    targets += [(path, load_config(path)) for path in args.config]

    # Simulate the largest topology on host CPUs — must precede the first
    # backend-initializing jax call (same recipe as tools/memcheck.py).
    world = max(cfg.distributed.world_size for _, cfg in targets)
    from picotron_tpu.mesh import force_host_device_count

    if world > 1:
        force_host_device_count(world)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")

    cost_model = None
    if args.cost:
        from picotron_tpu.analysis.cost_model import CostModel

        cost_model = CostModel(args.generation)

    n_bad = 0
    for label, cfg in targets:
        try:
            rep = run_shardcheck(cfg, checks=checks, budget_bytes=budget,
                                 cost_model=cost_model)
        except Exception as e:  # a layout that fails to trace is one bad row
            n_bad += 1
            if args.json:
                print(json.dumps({
                    "config": label, "ok": False,
                    "fatal": f"{type(e).__name__}: {e}",
                }), flush=True)
            else:
                print(f"== {label} ==")
                print(f"FATAL {type(e).__name__}: {e}", flush=True)
            continue
        cost_row = None
        if cost_model is not None:
            from picotron_tpu.analysis.planner import planner_gap

            cur, best, gap = planner_gap(cfg, cost_model)
            cost_row = {
                "generation": cost_model.gen.name,
                "predicted_step_ms": round(cur.total_s * 1e3, 3),
                "exposed_comm_ms": round(cur.exposed_comm_s * 1e3, 3),
                "planner_best": best.label if best else None,
                "planner_best_step_ms": (round(best.cost.total_s * 1e3, 3)
                                         if best else None),
                "gap_vs_best_pct": round(gap * 100, 1),
            }
        n_bad += 0 if rep.ok() else 1
        if args.json:
            print(json.dumps({
                "config": label,
                "ok": rep.ok(),
                "errors": len(rep.errors()),
                "warnings": len(rep.warnings()),
                "findings": [f.render() for f in rep.findings
                             if f.severity != "info" or args.verbose],
                "info": rep.info,
                **({"cost": cost_row} if cost_row else {}),
            }), flush=True)
        else:
            print(f"== {label} ==")
            print(rep.render(verbose=args.verbose), flush=True)
            var = rep.info.get("variants")
            if var:
                for entry in ("train_step", "mpmd_stages", "serve"):
                    v = var.get(entry) or {}
                    if "proven" in v:
                        state = ("proven compile-once" if v["proven"]
                                 else "NOT proven")
                        detail = (f"{v['programs']} stage program(s)"
                                  if "programs" in v else
                                  f"{v.get('signatures', '?')} abstract "
                                  f"signature(s)")
                        print(f"variants[{v.get('entry', entry)}]: {state} "
                              f"({detail})", flush=True)
                lint = (var.get("mpmd_stages") or {}).get("schedule_lint")
                if lint:
                    state = ("statically proven"
                             if lint["proven"] else "FAILS the lint")
                    print(f"variants[schedule:{lint['kind']}]: table "
                          f"{state} ({lint['ops']} op(s) over "
                          f"{lint['ticks']} tick(s), "
                          f"{lint['problems']} problem(s))", flush=True)
            if cost_row:
                line = (f"cost[{cost_row['generation']}]: predicted step "
                        f"{cost_row['predicted_step_ms']} ms (exposed "
                        f"comm {cost_row['exposed_comm_ms']} ms)")
                if cost_row["planner_best"]:
                    line += (f"; planner best at equal chips: "
                             f"{cost_row['planner_best']} "
                             f"({cost_row['planner_best_step_ms']} ms, "
                             f"this config "
                             f"+{cost_row['gap_vs_best_pct']}%)")
                print(line, flush=True)
    if not args.json:
        status = "green" if n_bad == 0 else f"{n_bad} config(s) with errors"
        print(f"shardcheck: {len(targets)} config(s) checked — {status}")
    return 0 if n_bad == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
