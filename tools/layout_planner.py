#!/usr/bin/env python
"""Which layout is fastest? — rank 4D layouts by predicted time, on CPU.

Enumerates the dp×tp×pp×cp×ep×{sequence_parallel, zero1, offload} space
— and, wherever pp > 1, the pipeline executor/schedule space on top
({spmd-1f1b, mpmd-1f1b, mpmd-interleaved-vN}) — for a model + chip
count, prunes HBM non-fits, prices the survivors with the ICI-topology
cost model (picotron_tpu/analysis/cost_model.py), and prints a ranked
table with the predicted-fastest config as a ready-to-run overrides
line. No TPU needed — the model is calibrated against the
measured SWEEP/BENCH rows on disk (validate with --validate-sweep).

  python tools/layout_planner.py --chips 8 --model SmolLM-1.7B --seq 2048
  python tools/layout_planner.py --chips 64 --config runs/llama3-8b-4d-v5p64/config.json \
      --generation v5p --markdown
  python tools/layout_planner.py --chips 8 --model debug-tiny --seq 64 \
      --trace 3 --verify-hbm            # re-cost top-3 from traced HLO,
                                        # memcheck-verify the winner
  python tools/layout_planner.py --validate-sweep   # rank agreement vs
                                                    # SWEEP_r03–r04

--trace and --verify-hbm lower/compile on simulated host devices (the
memcheck recipe); expect minutes for multi-billion-parameter configs —
the analytic default answers in milliseconds.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def build_base_config(args):
    from picotron_tpu.config import (
        Config, ModelConfig, TrainingConfig, load_config, resolve_preset,
    )

    if args.config:
        cfg = load_config(args.config)
        if args.seq:
            cfg = cfg.replace(training=dataclasses.replace(
                cfg.training, seq_length=args.seq))
        return cfg
    preset = resolve_preset(args.model)
    seq = args.seq or 2048
    preset["max_position_embeddings"] = max(
        preset.get("max_position_embeddings", seq), seq)
    if args.layers:
        preset["num_hidden_layers"] = args.layers
    cfg = Config(
        model=ModelConfig(name=args.model, **preset),
        training=TrainingConfig(
            seq_length=seq, micro_batch_size=args.mbs,
            gradient_accumulation_steps=args.grad_acc),
    )
    cfg.validate()
    return cfg


def render_table(points, top, markdown=False):
    rows = []
    for i, p in enumerate(points[:top]):
        d = p.as_dict()
        rows.append((i + 1, d["layout"], d["predicted_step_ms"],
                     d["compute_ms"], d["exposed_comm_ms"],
                     d["bubble_ms"] + d["offload_ms"],
                     d.get("traced_comm_ms", ""),
                     d["hbm_est_gib"],
                     d.get("memcheck_gib", "")))
    hdr = ("rank", "layout", "step_ms", "compute_ms", "comm_ms",
           "bubble+io_ms", "traced_comm_ms", "hbm_est_gib", "memcheck_gib")
    if markdown:
        lines = ["| " + " | ".join(hdr) + " |",
                 "|" + "---|" * len(hdr)]
        lines += ["| " + " | ".join(str(c) for c in r) + " |"
                  for r in rows]
    else:
        w = [max(len(str(x)) for x in [h] + [r[i] for r in rows])
             for i, h in enumerate(hdr)]
        lines = ["  ".join(h.ljust(w[i]) for i, h in enumerate(hdr))]
        lines += ["  ".join(str(c).ljust(w[i]) for i, c in enumerate(r))
                  for r in rows]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="picotron-tpu automatic layout planner (CPU-only)")
    ap.add_argument("--chips", type=int, default=None,
                    help="slice size to plan for (required unless "
                         "--validate-sweep)")
    ap.add_argument("--model", default="SmolLM-1.7B",
                    help="model preset (ignored with --config)")
    ap.add_argument("--config", default=None,
                    help="plan around an existing config JSON (its model/"
                         "batch settings seed the search)")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None,
                    help="override the preset's depth")
    ap.add_argument("--mbs", type=int, default=1)
    ap.add_argument("--grad-acc", type=int, default=8,
                    help="grad-accum of the SEED point; the planner holds "
                         "the implied global batch constant across "
                         "layouts")
    ap.add_argument("--generation", default="v5e",
                    choices=["v4", "v5e", "v5p", "v6e"],
                    help="TPU generation: ICI topology, link bandwidth, "
                         "HBM capacity")
    ap.add_argument("--hbm-gib", type=float, default=None,
                    help="override the generation's per-chip HBM capacity")
    ap.add_argument("--no-flags", action="store_true",
                    help="search only the 5 parallel axes (skip sp/zero1/"
                         "offload toggles)")
    ap.add_argument("--top", type=int, default=10,
                    help="rows to print")
    ap.add_argument("--trace", type=int, default=0, metavar="K",
                    help="re-cost the top K points from their traced "
                         "collective schedules (lowers the step on "
                         "simulated host devices — slow for big models)")
    ap.add_argument("--verify-hbm", action="store_true",
                    help="memcheck-verify the winner (XLA compile-time "
                         "memory breakdown); walks down the ranking until "
                         "a point passes, so the proposal is never a "
                         "config memcheck rejects")
    ap.add_argument("--json", action="store_true",
                    help="one JSON line per ranked point")
    ap.add_argument("--markdown", action="store_true",
                    help="markdown table (PERF.md format)")
    ap.add_argument("--cp-crossover", action="store_true",
                    help="instead of planning, sweep cp degree and print "
                         "each cp flavor's predicted step time per ICI "
                         "generation, with the smallest cp degree where "
                         "the 2D mesh flavor wins (its crossover)")
    ap.add_argument("--cp-degrees", type=int, nargs="*", default=None,
                    metavar="CP", help="cp degrees to sweep with "
                         "--cp-crossover (default 2 4 8 16 32)")
    ap.add_argument("--validate-sweep", action="store_true",
                    help="score the cost model's rank agreement against "
                         "the measured SWEEP_r03-r04 rows instead of "
                         "planning")
    ap.add_argument("--fit", action="store_true",
                    help="with --validate-sweep: refit the calibration "
                         "constants from the rows first")
    args = ap.parse_args(argv)

    from picotron_tpu.analysis.cost_model import CostModel

    if args.validate_sweep:
        from picotron_tpu.analysis.calibration import (
            fit_calibration, load_measured_rows, rank_agreement,
        )

        points = load_measured_rows()
        if not points:
            print("no SWEEP_r*.jsonl rows found", file=sys.stderr)
            return 1
        model = CostModel(args.generation)
        if args.fit:
            model = CostModel(args.generation, fit_calibration(points))
        ra = rank_agreement(points, model)
        if args.json:
            print(json.dumps(ra))
        else:
            print(f"rank agreement vs measured sweeps "
                  f"({len(points)} rows):")
            for src, rho in ra["per_round"].items():
                print(f"  {src}: spearman {rho}")
            print(f"  pooled: {ra.get('pooled')}")
            for r in ra["rows"]:
                print(f"    {r['metric']:42s} measured "
                      f"{r['measured_tps_chip']:>9} predicted "
                      f"{r['predicted_tps_chip']:>9} tok/s/chip")
        return 0

    if args.cp_crossover:
        from picotron_tpu.analysis.cost_model import (
            GENERATIONS, cp_crossover, cp_crossover_table,
        )

        base = build_base_config(args)
        degrees = tuple(args.cp_degrees or (2, 4, 8, 16, 32))
        out = []
        for gen in GENERATIONS:
            m = CostModel(gen)
            out.append((gen, cp_crossover_table(m, base, degrees),
                        cp_crossover(m, base, degrees)))
        if args.json:
            for gen, rows, cross in out:
                print(json.dumps({"generation": gen, "rows": rows,
                                  "crossover_cp": cross}), flush=True)
            return 0
        print(f"cp-flavor crossover: {base.model.name} seq "
              f"{base.training.seq_length} (tp={base.distributed.tp_size},"
              f" '-' = flavor infeasible at that degree)")
        hdr = ("gen", "cp", "ring_ms", "ulysses_ms", "mesh_ms",
               "mesh_fact", "winner")
        print("  " + "  ".join(h.rjust(10) for h in hdr))
        for gen, rows, cross in out:
            for r in rows:
                cells = (gen, r["cp"], r["ring_ms"],
                         r.get("ulysses_ms") or "-",
                         r.get("mesh_ms") or "-",
                         r.get("mesh_factorization", "-"), r["winner"])
                print("  " + "  ".join(str(c).rjust(10) for c in cells))
        for gen, _, cross in out:
            print(f"predicted mesh crossover on {gen}: "
                  + (f"cp={cross}" if cross else
                     "never (within swept degrees)"))
        return 0

    if not args.chips:
        ap.error("--chips is required (or use --validate-sweep)")

    from picotron_tpu.analysis.planner import best_point, plan, reprice_traced

    base = build_base_config(args)
    model = CostModel(args.generation)
    cap = args.hbm_gib if args.hbm_gib is not None else model.gen.hbm_gib
    points = plan(base, args.chips, model, flags=not args.no_flags,
                  hbm_gib=cap)
    if not points:
        print(f"no layout of {base.model.name} fits {args.chips}x"
              f"{args.generation} ({cap} GiB HBM) — try --hbm-gib, more "
              f"chips, or a smaller micro-batch", file=sys.stderr)
        return 1

    needs_devices = args.trace > 0 or args.verify_hbm
    if needs_devices:
        from picotron_tpu.mesh import force_host_device_count

        force_host_device_count(args.chips)
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
    if args.trace > 0:
        points = reprice_traced(points, model, top_k=args.trace)
    winner = best_point(points, verify=args.verify_hbm, hbm_gib=cap,
                        model=model)
    if winner is None:
        print("every candidate failed HBM verification; relax --hbm-gib "
              "or shrink the model/batch", file=sys.stderr)
        return 1

    if args.json:
        for p in points[:args.top]:
            print(json.dumps(p.as_dict()), flush=True)
    else:
        n_all = len(points)
        print(f"layout planner: {base.model.name} seq "
              f"{base.training.seq_length} on {args.chips}x"
              f"{args.generation} — {n_all} HBM-feasible layouts, top "
              f"{min(args.top, n_all)}:")
        print(render_table(points, args.top, markdown=args.markdown))
        print()
        print(f"predicted fastest: {winner.label} "
              f"({winner.cost.as_dict()['predicted_step_ms']} ms/step, "
              f"{winner.cost.as_dict()['tokens_per_sec_per_chip']} "
              f"tok/s/chip)")
        print(f"  run it: {winner.overrides_line()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
