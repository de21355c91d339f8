#!/usr/bin/env python3
"""A model of `serve_open_loop`'s loop over `ServeEngine.step`, on the CPU:
how far a serving cell's numbers move when dispatch times jitter.

    python3 benchmark/tools/loop_model.py <cell> [decode_s] [runs] [<rows>=<prefill_s> ...]

The engine's step is: admit what was submitted while a slot is free; one
prefill dispatch if any slot is mid-prompt (every such slot advances one
chunk; the batch is those slots padded to the next rung of the ladder 1, 4,
16, ..., decode_slots, and the dispatch costs by its rung); one decode
dispatch if any slot decodes (`decode_interval` tokens each). The model walks
the cell's own schedule through that loop with the dispatch times given
(defaults: 0.1106 s a decode dispatch and 0.0116 / 0.0688 / 0.268 / 0.5455 s a
prefill dispatch of 1 / 4 / 16 / 32 rows, PR 28's chip runs in PERF.md) and
multiplies each by 1 + jitter x a normal draw. It needs no chip and measures
nothing: it says which numbers of a cell have modes, and how far apart, given
times measured elsewhere.
"""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "traffic"))


PREFILL_S = {1: 0.0116, 4: 0.0688, 16: 0.268, 32: 0.5455}  # by rung; PR 28's chip runs


def rungs(num_slots):
    """The prefill program's row counts (a copy of `serve/engine.py prefill_rungs`):
    the powers of 4 below the slot count, then the slot count."""
    out, r = [], 1
    while r < num_slots:
        out.append(r)
        r *= 4
    return out + [num_slots]


def walk(reqs, prefill_s, decode_s, jitter, rng, chunk, interval, num_slots):
    """reqs: [(due_s, prompt tokens, max_new)]; prefill_s: seconds by rung.
    Returns the run's numbers as the runner takes them: stamps at the return
    of each step."""
    pend = list(reqs)[::-1]
    t, queue, slots, n_prefill, n_decode = 0.0, [], [], {}, 0
    due, first, n_first, done, out = {}, {}, {}, {}, {}
    while pend or queue or slots:
        while pend and pend[-1][0] <= t:
            d, n_prompt, max_new = pend.pop()
            due[len(due)] = d
            queue.append(dict(id=len(due) - 1, left=n_prompt, gen=0, max=max_new))
        while queue and len(slots) < num_slots:
            slots.append(queue.pop(0))
        if not slots:
            t = pend[-1][0]
            continue
        dt = 0.0
        mid = [s for s in slots if s["left"] > 0]
        if mid:
            rung = next(r for r in sorted(prefill_s) if r >= len(mid))
            dt += prefill_s[rung] * (1 + jitter * rng.standard_normal())
            n_prefill[rung] = n_prefill.get(rung, 0) + 1
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
    return dict(ttft_p90_ms=np.percentile(ttft, 90), tpot_p90_ms=np.percentile(tpot, 90) if tpot else float("nan"),
                out_tok_s=sum(out.values()) / last, last_done_s=last,
                prefill_dispatches=sum(n_prefill.values()), decode_dispatches=n_decode,
                **{f"prefill_dispatches_rung_{r}": n for r, n in sorted(n_prefill.items())})


def main() -> None:
    cell, rest = sys.argv[1], sys.argv[2:]
    prices = dict(a.split("=") for a in rest if "=" in a)
    rest = [a for a in rest if "=" not in a]
    decode_s = float(rest[0]) if rest else 0.1106
    runs = int(rest[1]) if len(rest) > 1 else 40
    with open(os.path.join(HERE, "workloads", cell + ".json")) as f:
        w = json.load(f)
    with open(os.path.join(HERE, "configs", w["config"] + ".json")) as f:
        serve = json.load(f)["serve"]
    ladder = rungs(serve["decode_slots"])
    prefill_s = {r: float(prices.get(str(r), PREFILL_S.get(r, 0))) for r in ladder}
    if not all(prefill_s.values()):
        raise SystemExit(f"loop_model: give a price for each rung of {ladder} as <rows>=<seconds>")
    gen = __import__(w["traffic"]["generator"])
    reqs = [(t, len(p), m) for t, p, m in gen.make(w["traffic"], 1, 51.0, 1000)]
    print(f"{cell}: {len(reqs)} requests, {sum(r[2] for r in reqs)} output tokens; a decode "
          f"dispatch {decode_s} s, a prefill dispatch by rung {prefill_s}")
    args = (serve["prefill_chunk"], serve["decode_interval"], serve["decode_slots"])
    for jitter in (0.0005, 0.002, 0.01):
        rng = np.random.default_rng(0)
        rows = [walk(reqs, prefill_s, decode_s, jitter, rng, *args) for _ in range(runs)]
        print(f"jitter {100 * jitter:g}% of a dispatch, {runs} runs:")
        for k in ("ttft_p90_ms", "tpot_p90_ms", "out_tok_s", "last_done_s",
                  "prefill_dispatches", "decode_dispatches"):
            v = np.array([r[k] for r in rows], float)
            q1, q3 = np.percentile(v, [25, 75])
            print(f"  {k:20s} median {np.median(v):10.3f}  quartiles apart {100 * (q3 - q1) / np.median(v):6.3f}%"
                  f"  min {v.min():10.3f}  max {v.max():10.3f}")
    for scale in (0.98, 0.99, 1.0, 1.01, 1.02):
        r = walk(reqs, {k: v * scale for k, v in prefill_s.items()}, decode_s * scale, 0.0,
                 np.random.default_rng(0), *args)
        by_rung = {k[len("prefill_dispatches_rung_"):]: v for k, v in r.items() if "rung" in k}
        print(f"every dispatch x {scale}: ttft_p90_ms {r['ttft_p90_ms']:.1f} tpot_p90_ms "
              f"{r['tpot_p90_ms']:.2f} out_tok_s {r['out_tok_s']:.3f} "
              f"prefill dispatches by rung {by_rung}")


if __name__ == "__main__":
    main()
