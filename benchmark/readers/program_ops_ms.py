"""Reader `program_ops_ms`: device ms a decode token of one region of ONE
jitted program: the self time, inside the traced window on the first device,
of the operations that run during an execution of `program` (the device
plane's `XLA Modules` line) and that are under every named scope of `scopes`
(words of the operation's name stack) OR whose event name matches `ops` (a
kernel of the compiler's own, which carries no name stack: `ragged-dot-*`),
over the program's executions x the runner's `per_fact` (a decode dispatch
covers `decode_interval` tokens). `scope_ms` and `scope_and_ops_ms` sum over
every program of the window and divide by training steps; a serving window
holds two programs, and the same scopes are in both.

`seconds_in_program` is what the roofline readers of the serve cells divide
their bytes by. No such program, scope or operation in the trace (a program
from before the scope existed) -> nothing reported."""

import re

import trace_reduce
import trace_scopes


def seconds_in_program(planes, win, dev: int, program: str, scopes=(), ops=None):
    """(self seconds of the matching operations inside executions of
    `program` that lie in the window, those executions, matching events)."""
    runs = sorted((s, s + d) for name, s, d, _ in
                  trace_scopes.device_lines(planes, trace_scopes.MODULES_LINE).get(dev, [])
                  if trace_scopes.program_name(name) == program
                  and s >= win[0] and s + d <= win[1])
    if not runs:
        return 0.0, 0, 0
    pat = re.compile(ops) if ops else None
    want = set(scopes)
    keyed, i = [], 0
    events = sorted(trace_scopes.device_lines(planes, trace_reduce.OPS_LINE).get(dev, []),
                    key=lambda e: e[1])
    for name, s, d, st in events:
        while i < len(runs) and runs[i][1] <= s:
            i += 1
        if i == len(runs):
            break
        if s < runs[i][0]:
            continue
        named = bool(pat and pat.search(trace_reduce.short_name(name)[0]))
        inside = bool(want) and want <= trace_scopes.scope_words(st.get("tf_op", ""))
        keyed.append(("hit" if named or inside else "other", s, d))
    secs, calls = trace_reduce.self_times(keyed)
    return secs.get("hit", 0.0), len(runs), calls.get("hit", 0)


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    if not planes or win is None:
        return None
    secs, runs, hits = seconds_in_program(
        planes, win, ctx.trace["first_device"], params["program"],
        params.get("scopes", ()), params.get("ops"))
    if not runs or not hits:
        return None
    per = float(facts.get(params.get("per_fact"), 1) or 1)
    ctx.log(f"{params['program']}: {hits} operations under {list(params.get('scopes', ()))} "
            f"or named like {params.get('ops')!r} in {runs} executions, {secs:.4f} s")
    return secs * 1e3 / (runs * per)
