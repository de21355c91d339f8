"""Reader `program_union_scopes_ms`: `program_ops_ms` for a region that several
scopes name BETWEEN them: device ms a decode token of the operations that run
inside executions of `program` and carry ANY of the scopes of `any_scope` as a
word of their name stack, each operation counted once however many of the names
it carries. `program_any_scope_ms` sums scope by scope and needs them disjoint;
here they are not: a jitted block that is lowered once (ops/moe.py
moe_mlp_served) gives some of its operations the names of its call site and
others only those entered inside it, so that an operation of the expert branch
reads `scmoe_branch/.../moe_router/...` or just `moe_router/...`. No such
program or no operation under any of the names -> nothing reported."""

import trace_reduce
import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    if not planes or win is None:
        return None
    dev, program, want = ctx.trace["first_device"], params["program"], set(params["any_scope"])
    runs = sorted((s, s + d) for name, s, d, _ in
                  trace_scopes.device_lines(planes, trace_scopes.MODULES_LINE).get(dev, [])
                  if trace_scopes.program_name(name) == program
                  and s >= win[0] and s + d <= win[1])
    keyed, i = [], 0
    events = sorted(trace_scopes.device_lines(planes, trace_reduce.OPS_LINE).get(dev, []),
                    key=lambda e: e[1])
    for _, s, d, st in events:
        while i < len(runs) and runs[i][1] <= s:
            i += 1
        if i == len(runs):
            break
        if s >= runs[i][0]:
            inside = bool(want & trace_scopes.scope_words(st.get("tf_op", "")))
            keyed.append(("hit" if inside else "other", s, d))
    secs, calls = trace_reduce.self_times(keyed)
    if not runs or not calls.get("hit"):
        return None
    per = float(facts.get(params.get("per_fact"), 1) or 1)
    ctx.log(f"{program} under any of {sorted(want)}: {secs['hit']:.4f} s in "
            f"{calls['hit']} operations of {len(runs)} executions")
    return secs["hit"] * 1e3 / (len(runs) * per)
