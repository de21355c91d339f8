#!/usr/bin/env python3
"""Run one cell as the builder's contract asks before a bound is set: two sets
of N runs, the same seeds in both sets, each run of a set with another seed,
all in one call; then a traced run. Prints each run's metrics and, per metric,
each set's spread: the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median. Run by hand
on the chip; writes `chiprun_out/sets.<cell>.json`.

    python3 benchmark/tools/measure_sets.py <cell> <seconds> [runs per set] [sets] [traced runs]

This process never touches JAX, so each run has the chip to itself.
"""
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    cell, seconds = sys.argv[1], sys.argv[2]
    n_runs = int(sys.argv[3]) if len(sys.argv) > 3 else 6
    n_sets = int(sys.argv[4]) if len(sys.argv) > 4 else 2
    n_traced = int(sys.argv[5]) if len(sys.argv) > 5 else 1
    SEEDS = [2147483659 + 7919 * i for i in range(n_runs)]  # large, as the driver's are

    def run(seed, trace):
        t0 = time.time()
        p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(seed),
                            "--seconds", seconds, "--trace", str(trace)], cwd=ROOT,
                           capture_output=True, text=True)
        notes = [ln for ln in p.stdout.splitlines() if ln.startswith("[benchmark]")]
        if p.returncode != 0:
            print(f"seed {seed} trace {trace}: exit {p.returncode}\n{p.stderr[-1500:]}", flush=True)
            return None, notes
        line = json.loads(p.stdout.strip().splitlines()[-1])
        line["wall_s"] = time.time() - t0
        return line, notes

    def spread(values):
        q1, _, q3 = statistics.quantiles(values, n=4)
        return (q3 - q1) / statistics.median(values)


    out = dict(cell=cell, seconds=seconds, seeds=SEEDS, sets=[], traced=[])
    for s in range(n_sets):
        rows = []
        for seed in SEEDS:
            line, notes = run(seed, 0)
            if line is None:
                continue
            rows.append(line)
            vals = {k: round(v["value"], 4) for k, v in line["metrics"].items()}
            print(f"set {s} seed {seed}: correct={line['correct']} attempted={line['attempted']} "
                  f"failed={line['failed']} wall={line['wall_s']:.0f}s {vals}", flush=True)
        out["sets"].append(rows)
        if len(rows) >= 2:
            for name in rows[0]["metrics"]:
                vs = [r["metrics"][name]["value"] for r in rows]
                print(f"set {s} {name}: median {statistics.median(vs):.6g} spread "
                      f"{100 * spread(vs):.3f}% of median (all runs); after the first run: "
                      f"median {statistics.median(vs[1:]):.6g}", flush=True)
    for k in range(n_traced):
        line, notes = run(SEEDS[k], 1)
        out["traced"].append(dict(line=line, notes=notes))
        print("\n".join(notes), flush=True)
        print("traced:", json.dumps(line)[:6000], flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"sets.{cell}.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
