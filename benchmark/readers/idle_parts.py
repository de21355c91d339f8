"""Reader `idle_parts`: the first device's idle time in the traced window
(`device_idle.serve`) split by what the serving loop was doing, in % of the
window. `part` picks the one this metric reports:

- `inside_program`: gaps between operations inside an execution of a program
  (the device plane's `XLA Modules` line): the program's, not the loop's;
- `round_trip`: idle outside every execution and under a `*.dispatch`,
  `*.wait` or `serve.handoff` leaf of a `serve.step` span: launch, copy back
  and wake-up, which overlapping dispatches would hide and no tidying of the
  host's code removes;
- `starved`: idle outside every execution and under any other leaf of a
  `serve.step`, or under no leaf inside the step: work pending and nothing
  enqueued, the host's own share.

What is left (`outside_step`: under the harness's own spans, between steps)
is logged, so that the four sum to the window's idle time, and logged by
harness span (`facts["spans"]`): under `wait.arrival` the system is empty and
nothing is pending, which is no one's overhead. Each gap is split
by overlap: a gap that starts under a wait and ends under the emit gives each
its own part. The reader logs the starved part by leaf the same way, and the
engine's own count for the same steps (`starved_us` on the `serve.step`
spans: `picotron_tpu/serve/engine.py step_account`) beside it.

**The clock check.** All of this rests on the host plane and the device
plane sharing a clock, and they do not always: the first process to trace on a
fresh machine wrote a device plane about 1 ms early in three calls of three
(PERF.md section 6, PR 37). So the reader measures the offset. Every
`serve.decode.dispatch` / `serve.decode.wait` pair in the window is matched to
the `serve_decode` execution that overlaps it most (an execution lasts tens of
ms, so the match survives an offset of a few), and the order the program
guarantees bounds the offset from both sides: an execution starts after its
dispatch span starts and ends before its wait span ends. Where 0 lies between the bounds
nothing is moved; else the device plane is moved by the least that restores the
order, which reads the fastest launch (or wake) of the window as 0: what is
left of the error is under the fastest launch the other traces show (0.2-0.3
ms a dispatch, which the split then books as starved and not as round trip).
The reader logs the bounds, the offset it took and the median launch (dispatch
start -> execution start, where the device was idle until then) and wake
(execution end -> wait end) in ms. It reports NOTHING for every part where no
pair found its execution, or where no one offset holds the order for all but
1% of them: a trace whose clocks drift or jump is not an attribution.

No window, no device plane, no `serve.step` span -> nothing reported."""

import bisect

import numpy as np

import trace_reduce
import trace_scopes

ROUND_TRIP = ("serve.prefill.dispatch", "serve.prefill.wait", "serve.decode.dispatch",
              "serve.decode.wait", "serve.handoff")
OTHER_LEAVES = ("serve.admit", "serve.prefill.build", "serve.decode.build", "serve.decode.emit")
MAX_VIOLATIONS = 0.01
PARTS = ("inside_program", "round_trip", "starved", "outside_step")


def overlap(a, b):
    """The intersection of two sorted lists of disjoint (start, end)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def overlap_by_name(a, named):
    """{name: ns of `a` under the spans of that name}; `a` sorted and disjoint,
    `named` (name, start, end) sorted by start and disjoint: one sweep."""
    out, i = {}, 0
    for name, lo, hi in named:
        while i < len(a) and a[i][1] <= lo:
            i += 1
        j = i
        while j < len(a) and a[j][0] < hi:
            out[name] = out.get(name, 0.0) + min(a[j][1], hi) - max(a[j][0], lo)
            j += 1
    return out


def split(planes, win, dev, harness_spans=(), shift=0.0):
    """{part: ns}, {leaf: ns of the starved part} and {harness span: ns of the
    part outside every step} for one device, its plane moved by `shift` ns
    against the host's, or None where the trace lacks what the split needs."""
    ops = trace_scopes.device_lines(planes, trace_reduce.OPS_LINE).get(dev, [])
    steps = trace_scopes.annotations(planes, ["serve.step"], *win)
    if not ops or not steps:
        return None
    busy = trace_reduce.clip(trace_reduce.union(
        (s + shift, s + shift + d) for _, s, d, _ in ops), *win)
    gaps = trace_reduce.complement(busy, *win)
    runs = trace_reduce.clip(trace_reduce.union(
        (s + shift, s + shift + d) for _, s, d, _ in
        trace_scopes.device_lines(planes, trace_scopes.MODULES_LINE).get(dev, [])), *win)
    inside = overlap(gaps, runs)
    outside = overlap(gaps, trace_reduce.complement(runs, *win))
    stepped = trace_reduce.union((lo, hi) for _, lo, hi, _ in steps)
    in_step = overlap(outside, stepped)
    by_leaf = overlap_by_name(in_step, [a[:3] for a in trace_scopes.annotations(
        planes, ROUND_TRIP + OTHER_LEAVES, *win)])
    round_trip = sum(by_leaf.pop(n, 0.0) for n in ROUND_TRIP)
    starved = trace_reduce.total(in_step) - round_trip
    by_leaf["unspanned"] = starved - sum(by_leaf.values())
    out_step = overlap(outside, trace_reduce.complement(stepped, *win))
    by_harness = overlap_by_name(out_step, [a[:3] for a in trace_scopes.annotations(
        planes, [n for n in harness_spans if n != "engine.step"], *win)])
    by_harness["(around engine.step and between spans)"] = (
        trace_reduce.total(out_step) - sum(by_harness.values()))
    parts = dict(inside_program=trace_reduce.total(inside), round_trip=round_trip,
                 starved=starved, outside_step=trace_reduce.total(out_step))
    return parts, by_leaf, by_harness, trace_reduce.total(gaps), steps


def matched(planes, win, dev):
    """[(dispatch start, execution start, execution end, wait end)] in ns: every
    `serve.decode.dispatch` / `.wait` pair in the window with the `serve_decode`
    execution that overlaps it most, where one lies in it by more than half."""
    runs = sorted((s, s + d) for name, s, d, _ in trace_scopes.device_lines(
        planes, trace_scopes.MODULES_LINE).get(dev, [])
        if trace_scopes.program_name(name) == "serve_decode")
    starts = [s for s, _ in runs]
    out, dispatch_at = [], None
    for name, lo, hi, _ in trace_scopes.annotations(
            planes, ["serve.decode.dispatch", "serve.decode.wait"], *win):
        if name == "serve.decode.dispatch":
            dispatch_at = lo
        elif dispatch_at is not None:
            k = bisect.bisect_left(starts, dispatch_at)
            near = runs[max(k - 1, 0):k + 1]
            if near:
                s, e = max(near, key=lambda r: min(r[1], hi) - max(r[0], dispatch_at))
                if min(e, hi) - max(s, dispatch_at) > (e - s) / 2:
                    out.append((dispatch_at, s, e, hi))
            dispatch_at = None
    return out


def clock_offset(pairs):
    """(lo, hi, offset) in ns: the offsets of the device plane that hold the
    order for all but `MAX_VIOLATIONS` of `pairs` (half of them dropped from
    each bound) lie in [lo, hi]; the one taken is the nearest to 0, None where
    lo > hi."""
    spare = int(MAX_VIOLATIONS * len(pairs)) // 2
    lo = sorted(d0 - s for d0, s, _, _ in pairs)[len(pairs) - 1 - spare]
    hi = sorted(w1 - e for _, _, e, w1 in pairs)[spare]
    return lo, hi, (None if lo > hi else min(max(0.0, lo), hi))


def compute(facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    if not planes or win is None:
        return None
    dev = ctx.trace["first_device"]
    if not (trace_scopes.device_lines(planes, trace_reduce.OPS_LINE).get(dev)
            and trace_scopes.annotations(planes, ["serve.step"], *win)):
        return None
    pairs = matched(planes, win, dev)
    if not pairs:
        ctx.log("clock check: no serve.decode.dispatch / .wait pair in the window found its "
                "serve_decode execution: nothing to hold the two clocks against, no part of "
                "the idle time is reported")
        return None
    lo, hi, shift = clock_offset(pairs)
    said = (f"clock check: {len(pairs)} serve_decode executions held against their "
            f"serve.decode.dispatch / .wait spans; the order holds for device plane offsets "
            f"of {lo / 1e6:+.3f} to {hi / 1e6:+.3f} ms")
    if shift is None:
        ctx.log(said + ": no one offset holds it, the host plane and the device plane do not "
                "share a clock in this trace: no part of the idle time is reported")
        return None
    launch = [s + shift - d0 for d0, s, _, _ in pairs]
    ctx.log(said + (", 0 among them: nothing moved" if shift == 0.0 else
                    f": the device plane moved by {shift / 1e6:+.3f} ms")
            + f"; launch median {np.median(launch) / 1e6:.3f} ms, fastest "
            f"{min(launch) / 1e6:.3f}; wake median "
            f"{np.median([w1 - e - shift for _, _, e, w1 in pairs]) / 1e6:.3f} ms")
    parts, by_leaf, by_harness, idle, steps = split(
        planes, win, dev, facts.get("spans", ()), shift)
    width = win[1] - win[0]
    ctx.log(f"idle of device {dev}, {idle / 1e9:.4f} s of {width / 1e9:.4f} s, by part: " + ", ".join(
        f"{p} {parts[p] / 1e9:.4f} s ({100.0 * parts[p] / width:.2f}%)" for p in PARTS))
    ctx.log("the starved part by the program's leaf span, each gap split by overlap: " + ", ".join(
        f"{n} {s / 1e9:.4f} s" for n, s in sorted(by_leaf.items(), key=lambda x: -x[1])))
    ctx.log("the part outside every step by the harness's span (an empty system waits under "
            "wait.arrival): " + ", ".join(
                f"{n} {s / 1e9:.4f} s" for n, s in sorted(by_harness.items(), key=lambda x: -x[1])))
    counted = [c for *_, c in steps if "starved_us" in c]
    if counted:
        said = sum(float(c["starved_us"]) for c in counted) / 1e6
        ctx.log(f"the engine's own count over the same {len(counted)} steps: starved_us "
                f"{said:.4f} s of wall_us {sum(float(c['wall_us']) for c in counted) / 1e6:.4f} s; "
                f"the device plane's starved part {parts['starved'] / 1e9:.4f} s "
                f"({parts['starved'] / 1e9 / said:.3f} x the count)" if said else
                f"the engine counted no starved second over {len(counted)} steps")
    return {p: 100.0 * parts[p] / width for p in PARTS}


def read(params, facts, ctx):
    if not hasattr(ctx, "idle_parts"):
        ctx.idle_parts = compute(facts, ctx)  # one reduction and one log a run, three metrics read it
    return None if ctx.idle_parts is None else ctx.idle_parts[params["part"]]
