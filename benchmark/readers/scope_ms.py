"""Reader `scope_ms`: the self time of the device operations under one named
scope of the program (`picotron_tpu/telemetry/scopes.py`; found as a word of
the operation's name stack, `trace_scopes.py`) inside the traced window.

`unit` is `ms_per_step` (over the runner's `traced_steps`) or `window_share`
(% of the device's traced window). `device` is `first` or `max`: the device
on which the scope takes longest, so that a region only one pipeline stage
runs (the head on the last) is seen. No operation under the scope, or a
trace without name stacks -> nothing reported. `also_log` names further scopes
whose self seconds on the same device are logged beside it (the breakdown by
region of PERF.md section 5; an operation under nested scopes counts under
each, so the list does not add up to the busy time)."""

import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    if not planes or win is None:
        return None
    words = trace_scopes.scope_seconds(planes, *win)
    by_dev = {d: w.get(params["scope"], 0.0) for d, w in words.items()}
    by_dev = {d: s for d, s in by_dev.items() if s > 0.0 and d in ctx.trace["per_device"]}
    if not by_dev:
        return None
    first = ctx.trace["first_device"]
    dev = max(by_dev, key=by_dev.get) if params.get("device") == "max" else first
    if dev not in by_dev:
        return None
    ctx.log(f"scope {params['scope']}: " + ", ".join(
        f"device {d} {s:.4f} s" for d, s in sorted(by_dev.items())) + f"; read on {dev}")
    if params.get("also_log"):
        ctx.log(f"other scopes on device {dev}, self seconds in the window: " + ", ".join(
            f"{w} {words[dev].get(w, 0.0):.4f}" for w in params["also_log"]))
    if params.get("unit") == "window_share":
        return 100.0 * by_dev[dev] / ctx.trace["per_device"][dev]["window_s"]
    steps = facts.get("traced_steps")
    return by_dev[dev] * 1e3 / steps if steps else None
