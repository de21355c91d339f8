#!/usr/bin/env python3
"""Run one cell with a quiet host and with a busy one, to see which of its
numbers depend on the host's timing. Run by hand on the chip.

    python3 benchmark/tools/host_noise.py <cell> <seconds> [quiet runs] [busy runs] [traced runs] [burners]

The busy runs share the host with `burners` processes that spin in Python
(they never touch JAX, so the run keeps the chip); the driver's check runs on
a host whose cores may be shared, the chip tool's does not. Prints each run's
result line and the runner's notes (a serving run's last completion, dispatch
counts and tails are there). This process never touches JAX either.
"""
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SPIN = "while True:\n    pass\n"


def run(cell, seconds, seed, trace):
    t0 = time.time()
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(seed),
                        "--seconds", seconds, "--trace", str(trace)], cwd=ROOT,
                       capture_output=True, text=True)
    if p.returncode != 0:
        print(f"seed {seed} trace {trace}: exit {p.returncode}\n{p.stderr[-1500:]}", flush=True)
        return
    lines = p.stdout.strip().splitlines()
    for ln in lines[:-1]:
        if ln.startswith("[benchmark]") and ("last_done" in ln or "harness" in ln or "setup_s" in ln):
            print("   ", ln[:600], flush=True)
    line = json.loads(lines[-1])
    line.pop("breakdown", None)
    print(f"    wall {time.time() - t0:.0f}s", json.dumps(line), flush=True)


def main() -> None:
    cell, seconds = sys.argv[1], sys.argv[2]
    n_quiet, n_busy, n_traced, n_burn = (int(sys.argv[i]) if len(sys.argv) > i else d
                                         for i, d in ((3, 3), (4, 3), (5, 1), (6, os.cpu_count())))
    seeds = [2147483659 + 7919 * i for i in range(max(n_quiet, n_busy, n_traced))]
    for k in range(n_quiet):
        print(f"quiet run {k} seed {seeds[k]}", flush=True)
        run(cell, seconds, seeds[k], 0)
    for k in range(n_traced):
        print(f"quiet traced run {k} seed {seeds[k]}", flush=True)
        run(cell, seconds, seeds[k], 1)
    burners = [subprocess.Popen([sys.executable, "-c", SPIN]) for _ in range(n_burn)]
    try:
        for k in range(n_busy):
            print(f"busy run {k} seed {seeds[k]} ({n_burn} burners)", flush=True)
            run(cell, seconds, seeds[k], 0)
    finally:
        for b in burners:
            b.kill()
        for b in burners:
            b.wait()


if __name__ == "__main__":
    main()
