#!/usr/bin/env python3
"""Run one cell as the builder's contract asks before a bound is set: two sets
of N runs, the same seeds in both sets, each run of a set with another seed,
all in one call; then a traced run. Prints each run's metrics and, per metric
and per scalar fact of the runner (a serving cell's `output_tokens_per_s` and
`tpot_ms_p90` among them), each set's spread: the distance between the first
and third quartile (`statistics.quantiles(values, n=4)`) as a share of the
median, over every run and with the run farthest from the median left out.
Then, per end-to-end metric, the smallest bound of 0.01, 0.015, 0.02, 0.03,
0.05, 0.1 (the contract's cap) that the driver's two rules allow: the mean of the sets' spreads without
their farthest runs at or under half of it (not too tight), and it at or under
eight times the widest spread of all runs (not too loose; 0.01 never is). Run
by hand on the chip; writes `chiprun_out/sets.<cell>.json`.

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
        stamps = os.path.join(ROOT, ".bench_out", cell + ".requests.jsonl")
        if os.path.exists(stamps):  # a serving cell's per-request stamps, kept a run
            with open(stamps) as f:
                line["requests"] = [json.loads(ln) for ln in f]
            os.remove(stamps)
        return line, notes

    def spread(values):
        q1, _, q3 = statistics.quantiles(values, n=4)
        return (q3 - q1) / statistics.median(values)

    def trimmed(values):  # the run farthest from the median left out, as the driver does
        med = statistics.median(values)
        far = max(range(len(values)), key=lambda i: abs(values[i] - med))
        return spread(values[:far] + values[far + 1:])

    out = dict(cell=cell, seconds=seconds, seeds=SEEDS, sets=[], traced=[], spreads={})
    for s in range(n_sets):
        rows = []
        for seed in SEEDS:
            line, notes = run(seed, 0)
            if line is None:
                continue
            line["notes"] = notes
            rows.append(line)
            vals = {k: round(v["value"], 4) for k, v in line["metrics"].items()}
            print(f"set {s} seed {seed}: correct={line['correct']} attempted={line['attempted']} "
                  f"failed={line['failed']} wall={line['wall_s']:.0f}s {vals}", flush=True)
            print("\n".join(n for n in notes if " p90 = rank" in n or "queue_at_end" in n),
                  flush=True)
        out["sets"].append(rows)
        if len(rows) < 4:
            continue
        series = {k: [r["metrics"][k]["value"] for r in rows] for k in rows[0]["metrics"]}
        facts = rows[0].get("facts", {})
        series.update({"fact:" + k: [r["facts"][k] for r in rows] for k in facts
                       if k not in series and len({r["facts"][k] for r in rows}) > 1})
        for name, vs in series.items():
            if statistics.median(vs) == 0:
                continue
            out["spreads"].setdefault(name, []).append(
                dict(median=statistics.median(vs), all=spread(vs), trimmed=trimmed(vs)))
            print(f"set {s} {name}: median {statistics.median(vs):.6g} spread "
                  f"{100 * spread(vs):.3f}% of median (all runs), {100 * trimmed(vs):.3f}% "
                  f"(farthest run left out); min {min(vs):.6g} max {max(vs):.6g}", flush=True)
    for name, sp in out["spreads"].items():
        if name.startswith("fact:") or name == "setup_s" or len(sp) < 2:
            continue
        tight = statistics.mean(x["trimmed"] for x in sp)
        widest = max(x["all"] for x in sp)
        bound = next((b for b in (0.01, 0.015, 0.02, 0.03, 0.05, 0.1) if tight <= b / 2), None)
        print(f"{name}: mean trimmed spread {100 * tight:.3f}%, widest spread {100 * widest:.3f}%: "
              f"smallest bound not too tight {bound}; not too loose: "
              f"{bound is not None and (bound <= 0.01 or bound <= 8 * widest)}; medians "
              f"{[round(x['median'], 3) for x in sp]} apart by "
              f"{100 * abs(sp[1]['median'] / sp[0]['median'] - 1):.3f}%", flush=True)
    for k in range(n_traced):
        line, notes = run(SEEDS[k], 1)
        out["traced"].append(dict(line=line, notes=notes))
        print("\n".join(notes), flush=True)
        print("traced:", json.dumps({k: v for k, v in (line or {}).items() if k != "requests"})[:6000],
              flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"sets.{cell}.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
