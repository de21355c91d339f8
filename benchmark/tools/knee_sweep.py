#!/usr/bin/env python3
"""Find the knee of a serving cell once: run the cell at several offered
rates, one run each, and print each run's lines. Run by hand on the chip;
PERF.md records what it printed and the rate written into the cell's file.

    python3 benchmark/tools/knee_sweep.py <cell> <seconds> [shape_seed=<n>] <rate> [<rate> ...]

`shape_seed=<n>` draws another schedule (other arrival times and lengths) than
the cell's own, to see whether the knee rests on the one schedule.

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
    shape_seed = None
    if rest[0].startswith("shape_seed="):
        shape_seed = int(rest.pop(0).split("=")[1])
    rates = [float(r) for r in rest]

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
        with open(os.path.join(tree, "benchmark", "workloads", name + ".json"), "w") as f:
            json.dump(w, f)
        bench["workloads"].append(dict(entry, name=name))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if cell in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + names
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for r, name in zip(rates, names):
        print(f"=== offered rate {r:g} requests/s", flush=True)
        p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", name, "--seed", "11",
                            "--seconds", seconds, "--trace", "0"], cwd=tree,
                           capture_output=True, text=True)
        lines = [ln for ln in p.stdout.splitlines() if ln.startswith(("[benchmark]", "{"))]
        print("\n".join(lines) if p.returncode == 0 else p.stderr[-2000:], flush=True)


if __name__ == "__main__":
    main()
