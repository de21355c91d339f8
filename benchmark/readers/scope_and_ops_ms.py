"""Reader `scope_and_ops_ms`: as `scope_ms` in `ms_per_step`, for a region
part of whose work the compiler runs in kernels of its own that carry no
name stack: the self time, inside the traced window, of the device operations
that are under the named scope `scope` OR whose event name matches `ops`
(this installation lowers `lax.ragged_dot` to `ragged-dot-none.N` and
`ragged-dot-metadata.N` custom calls whose `tf_op` is just that name).

`device` is `first` or `max`; `also_log` names further scopes whose self
seconds on the same device are logged beside it. No operation found, or a
trace without the window -> nothing reported."""

import re

import trace_reduce
import trace_scopes


def seconds_by_device(planes, win, scope: str, ops: str):
    """{device: (self seconds under `scope` or named like `ops`, calls of the
    named ones)} over the operations that overlap the window."""
    pat = re.compile(ops)
    out = {}
    for dev, events in trace_scopes.device_lines(planes, trace_reduce.OPS_LINE).items():
        keyed = []
        for name, s, d, st in events:
            if not (s + d > win[0] and s < win[1]):
                continue
            named = bool(pat.search(trace_reduce.short_name(name)[0]))
            inside = scope in trace_scopes.scope_words(st.get("tf_op", ""))
            keyed.append((("named" if named else "scope" if inside else "other"), s, d))
        secs, calls = trace_reduce.self_times(keyed)
        total = secs.get("named", 0.0) + secs.get("scope", 0.0)
        if total > 0.0:
            out[dev] = (total, calls.get("named", 0))
    return out


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    steps = facts.get("traced_steps")
    if not planes or win is None or not steps:
        return None
    by_dev = {d: v for d, v in seconds_by_device(
        planes, win, params["scope"], params["ops"]).items()
        if d in ctx.trace["per_device"]}
    if not by_dev:
        return None
    first = ctx.trace["first_device"]
    dev = (max(by_dev, key=lambda d: by_dev[d][0]) if params.get("device") == "max"
           else first)
    if dev not in by_dev:
        return None
    ctx.log(f"scope {params['scope']} + ops {params['ops']!r}: " + ", ".join(
        f"device {d} {s:.4f} s ({n} named calls)" for d, (s, n) in sorted(by_dev.items()))
        + f"; read on {dev}")
    if params.get("also_log"):
        words = trace_scopes.scope_seconds(planes, *win)
        ctx.log(f"other scopes on device {dev}, self seconds in the window: " + ", ".join(
            f"{w} {words[dev].get(w, 0.0):.4f}" for w in params["also_log"]))
    return by_dev[dev][0] * 1e3 / steps
