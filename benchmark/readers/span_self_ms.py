"""Reader `span_self_ms`: the median, over the program's spans named `parent`
inside the traced window, of the span's duration less the part its children
named in `minus` cover, in ms: a region's own host time, without the waits
for the device inside it. No such span -> nothing reported.

With `idle_gaps_by` (the names of the parent's leaf spans) it also logs the
first device's idle gaps by the program's leaf span that covers each: what
the host was doing while the device had nothing to do."""

import numpy as np

import trace_reduce
import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    if not planes or win is None:
        return None
    names = [params["parent"], *params["minus"]]
    spans = trace_scopes.annotations(planes, names, *win)
    parents = [a for a in spans if a[0] == params["parent"]]
    if not parents:
        return None
    own, i = [], 0
    kids = [a for a in spans if a[0] != params["parent"]]
    for _, lo, hi, _ in parents:
        inside = 0.0
        while i < len(kids) and kids[i][2] <= hi:
            if kids[i][1] >= lo:
                inside += kids[i][2] - kids[i][1]
            i += 1
        own.append((hi - lo) - inside)
    if params.get("idle_gaps_by"):
        _log_idle_gaps(planes, win, params["idle_gaps_by"], ctx)
    return float(np.median(own)) / 1e6


def _log_idle_gaps(planes, win, leaf_names, ctx):
    dev = ctx.trace["first_device"]
    ops = trace_scopes.device_lines(planes, trace_reduce.OPS_LINE).get(dev, [])
    busy = trace_reduce.clip(trace_reduce.union((s, s + d) for _, s, d, _ in ops), *win)
    gaps = trace_reduce.complement(busy, *win)
    leaves = [a[:3] for a in trace_scopes.annotations(planes, leaf_names, *win)]
    by = {}
    for gap, name in zip(gaps, trace_reduce.attribute(gaps, leaves)):
        by[name or "(between spans)"] = by.get(name or "(between spans)", 0.0) + (gap[1] - gap[0]) / 1e9
    ctx.log(f"idle gaps of device {dev} by the program's leaf span ({len(gaps)} gaps, "
            f"{sum(by.values()):.4f} s): " + ", ".join(
                f"{n} {s:.4f} s" for n, s in sorted(by.items(), key=lambda x: -x[1])))
