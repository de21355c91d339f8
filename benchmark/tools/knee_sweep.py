#!/usr/bin/env python3
"""Find the knee of a serving cell once: run the cell at several offered
rates, one run each, and print each run's lines. Run by hand on the chip;
PERF.md records what it printed and the rate written into the cell's file.

    python3 benchmark/tools/knee_sweep.py <cell> <seconds> [shape_seed=<n>] [seeds=<a>,<b>,...] [control=<name>] <rate> [<rate> ...]

`shape_seed=<n>` draws another schedule (other arrival times and lengths) than
the cell's own, to see whether the knee rests on the one schedule. `seeds=`
runs each rate once a seed (default: 11). `control=int8` reads the control of
`correct` at the cell's own size: the runner then puts the reference with
weights of that lower precision in the program's place, and the result's
`correct` is the control's verdict, which has to be false (PERF.md).

A rate is sustained when its run ends `queue_at_end=drained` with `failed` 0
and the queue did not grow inside the window: the p90 of the queue wait over
the requests due in the second half of the window is no more than one engine
step (0.15 s) above that of the first half (the runner prints both). The knee
is the highest sustained rate.

Builds a checkout in miniature under `.bench_out/sweep/` (links to the program
and to this directory's files, one copy of the cell's file per rate, a
BENCHMARK.json that lists them) and runs `run.py` there, one process after the
other. This process never touches JAX, so each child has the chip to itself.
"""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def main() -> None:
    cell, seconds, rest = sys.argv[1], sys.argv[2], sys.argv[3:]
    opts = dict(a.split("=") for a in rest if "=" in a)
    shape_seed = int(opts["shape_seed"]) if "shape_seed" in opts else None
    seeds = opts.get("seeds", "11").split(",")
    rates = [float(r) for r in rest if "=" not in r]

    tree = os.path.join(ROOT, ".bench_out", "sweep")
    shutil.rmtree(tree, ignore_errors=True)
    os.makedirs(os.path.join(tree, "benchmark", "workloads"))
    os.symlink(os.path.join(ROOT, "picotron_tpu"), os.path.join(tree, "picotron_tpu"))
    for name in os.listdir(HERE):
        if name not in ("workloads", "__pycache__"):
            os.symlink(os.path.join(HERE, name), os.path.join(tree, "benchmark", name))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(HERE, "workloads", cell + ".json")) as f:
        base = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    names = []
    for r in rates:
        name = f"{cell}.r{r:g}"
        names.append(name)
        w = json.loads(json.dumps(base))
        w["name"] = name
        w["traffic"]["rate_per_s"] = r
        if shape_seed is not None:
            w["traffic"]["shape_seed"] = shape_seed
        if "control" in opts:
            w["control"] = opts["control"]
        with open(os.path.join(tree, "benchmark", "workloads", name + ".json"), "w") as f:
            json.dump(w, f)
        bench["workloads"].append(dict(entry, name=name))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if cell in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + names
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for r, name in zip(rates, names):
        for seed in seeds:
            print(f"=== offered rate {r:g} requests/s, seed {seed}", flush=True)
            p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", name, "--seed",
                                seed, "--seconds", seconds, "--trace", "0"], cwd=tree,
                               capture_output=True, text=True)
            lines = [ln for ln in p.stdout.splitlines() if ln.startswith(("[benchmark]", "{"))]
            print("\n".join(lines) if p.returncode == 0 else p.stderr[-2000:], flush=True)


if __name__ == "__main__":
    main()
