#!/usr/bin/env python3
"""A model of `serve_open_loop`'s loop over `ServeEngine.step`, on the CPU:
how far a serving cell's numbers move when dispatch times jitter.

    python3 benchmark/tools/loop_model.py <cell> [prefill_s] [decode_s] [runs]

The engine's step is: admit what was submitted; one prefill dispatch if any
slot is mid-prompt (every such slot advances one chunk, at one price whatever
their number); one decode dispatch if any slot decodes (`decode_interval`
tokens each). The model walks the cell's own schedule through that loop with
the two dispatch times given (defaults: the chat cell's, 0.85 s and 0.145 s,
from its traced run) and multiplies each by 1 + jitter x a normal draw. It
needs no chip and measures nothing: it says which numbers of a cell have
modes, and how far apart, given times measured elsewhere.
"""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "traffic"))


def walk(reqs, prefill_s, decode_s, jitter, rng, chunk, interval):
    """reqs: [(due_s, prompt tokens, max_new)]. Returns the run's numbers as
    the runner takes them: stamps at the return of each step."""
    pend = list(reqs)[::-1]
    t, slots, n_prefill, n_decode = 0.0, [], 0, 0
    due, first, n_first, done, out = {}, {}, {}, {}, {}
    while pend or slots:
        while pend and pend[-1][0] <= t:
            d, n_prompt, max_new = pend.pop()
            due[len(due)] = d
            slots.append(dict(id=len(due) - 1, left=n_prompt, gen=0, max=max_new))
        if not slots:
            t = pend[-1][0]
            continue
        dt = 0.0
        mid = [s for s in slots if s["left"] > 0]
        if mid:
            dt += prefill_s * (1 + jitter * rng.standard_normal())
            n_prefill += 1
            for s in mid:
                s["left"] -= min(chunk, s["left"])
                if s["left"] == 0:
                    s["gen"] = 1
        ready = [s for s in slots if s["left"] == 0 and 0 < s["gen"] < s["max"]]
        if ready:
            dt += decode_s * (1 + jitter * rng.standard_normal())
            n_decode += 1
            for s in ready:
                s["gen"] = min(s["gen"] + interval, s["max"])
        t += dt
        for s in list(slots):
            if s["gen"] > 0 and s["id"] not in first:
                first[s["id"]], n_first[s["id"]] = t, s["gen"]
            if s["left"] == 0 and s["gen"] >= s["max"]:
                done[s["id"]], out[s["id"]] = t, s["gen"]
                slots.remove(s)
    ttft = [(first[r] - due[r]) * 1e3 for r in due]
    tpot = [(done[r] - first[r]) / (out[r] - n_first[r]) * 1e3 for r in due if out[r] > n_first[r]]
    last = max(done.values())
    return dict(ttft_p90_ms=np.percentile(ttft, 90), tpot_p90_ms=np.percentile(tpot, 90),
                out_tok_s=sum(out.values()) / last, last_done_s=last,
                prefill_dispatches=n_prefill, decode_dispatches=n_decode)


def main() -> None:
    cell = sys.argv[1]
    prefill_s = float(sys.argv[2]) if len(sys.argv) > 2 else 0.85
    decode_s = float(sys.argv[3]) if len(sys.argv) > 3 else 0.145
    runs = int(sys.argv[4]) if len(sys.argv) > 4 else 40
    with open(os.path.join(HERE, "workloads", cell + ".json")) as f:
        w = json.load(f)
    with open(os.path.join(HERE, "configs", w["config"] + ".json")) as f:
        serve = json.load(f)["serve"]
    gen = __import__(w["traffic"]["generator"])
    reqs = [(t, len(p), m) for t, p, m in gen.make(w["traffic"], 1, 51.0, 1000)]
    print(f"{cell}: {len(reqs)} requests, {sum(r[2] for r in reqs)} output tokens")
    for jitter in (0.0005, 0.002, 0.01):
        rng = np.random.default_rng(0)
        rows = [walk(reqs, prefill_s, decode_s, jitter, rng, serve["prefill_chunk"],
                     serve["decode_interval"]) for _ in range(runs)]
        print(f"jitter {100 * jitter:g}% of a dispatch, {runs} runs:")
        for k in rows[0]:
            v = np.array([r[k] for r in rows], float)
            q1, q3 = np.percentile(v, [25, 75])
            print(f"  {k:20s} median {np.median(v):10.3f}  quartiles apart {100 * (q3 - q1) / np.median(v):6.3f}%"
                  f"  min {v.min():10.3f}  max {v.max():10.3f}")
    for scale in (0.98, 0.99, 1.0, 1.01, 1.02):
        r = walk(reqs, prefill_s * scale, decode_s * scale, 0.0, np.random.default_rng(0),
                 serve["prefill_chunk"], serve["decode_interval"])
        print(f"every dispatch x {scale}: ttft_p90_ms {r['ttft_p90_ms']:.1f} tpot_p90_ms "
              f"{r['tpot_p90_ms']:.2f} out_tok_s {r['out_tok_s']:.3f} "
              f"prefill dispatches {r['prefill_dispatches']}")


if __name__ == "__main__":
    main()
