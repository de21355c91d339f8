"""Reader `program_any_scope_ms`: `program_ops_ms` summed over several regions
of one program, each named by ONE scope (`any_scope`: the regions are disjoint,
a scope is never entered inside another of the list): device ms a decode token
of the operations under any of them, inside the program's executions. A scope
that is not in the trace adds nothing; none of them there -> nothing reported."""

import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    if not planes or win is None:
        return None
    one = ctx.load_module("readers", "program_ops_ms").seconds_in_program
    total, runs, hits = 0.0, 0, 0
    for s in params["any_scope"]:
        secs, runs, n = one(planes, win, ctx.trace["first_device"], params["program"], (s,))
        total, hits = total + secs, hits + n
    if not runs or not hits:
        return None
    per = float(facts.get(params.get("per_fact"), 1) or 1)
    ctx.log(f"{params['program']} under any of {params['any_scope']}: {total:.4f} s in "
            f"{hits} operations of {runs} executions")
    return total / (runs * per) * 1e3
