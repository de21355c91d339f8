"""Reader `program_ms`: the median device duration, in ms, of one jitted
program's executions inside the traced window on the first device. The
executions are the events of the device plane's `XLA Modules` line under the
program's name (`trace_scopes.py`); `per_fact` divides by a number the runner
holds (a decode dispatch covers `decode_interval` tokens). A program the
trace does not hold under that name -> nothing reported.

It also logs every program's executions in the window beside the window's
busy seconds, so that the two can be checked against each other, and with
`log_scopes` the self seconds under those named scopes of the program (the
breakdown by region of PERF.md section 5)."""

import numpy as np

import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    if not planes or win is None:
        return None
    dev = ctx.trace["first_device"]
    by = trace_scopes.programs(planes, *win).get(dev, {})
    if by and not getattr(ctx, "programs_logged", False):
        ctx.programs_logged = True  # one line a run, whatever number of metrics read it
        total = sum(sum(ds) for ds in by.values()) / 1e9
        ctx.log("programs in the window, device %d: " % dev + "; ".join(
            f"{n} x{len(ds)} {sum(ds) / 1e9:.4f} s" for n, ds in sorted(by.items()))
            + f"; sum {total:.4f} s of {ctx.trace['per_device'][dev]['busy_s']:.4f} s busy")
    if params.get("log_scopes"):
        words = trace_scopes.scope_seconds(planes, *win).get(dev, {})
        ctx.log(f"scopes on device {dev}, self seconds in the window: " + ", ".join(
            f"{w} {words.get(w, 0.0):.4f}" for w in params["log_scopes"]))
    durs = by.get(params["program"])
    if not durs:
        return None
    return float(np.median(durs)) / 1e6 / float(facts.get(params.get("per_fact"), 1) or 1)
