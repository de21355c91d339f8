"""Reader `decode_experts_roofline`: for the expert matmuls of the decode
steps in the traced window, the least time the chip could take to move their
bytes (`flops_mellum2.decode_experts_bytes`: the three banks of every expert
that a live row was routed to, which the decode program counts and the engine
puts on its `serve.decode.wait` span as `experts_touched`, plus the rows) over
the device time `program_ops_ms` sums for them inside `serve_decode` (the
`moe_experts` scope and the compiler's `ragged-dot-*` kernels), in %. A decode
step is bound by memory: the bound is bytes over the chip's bandwidth.
Nothing counted or nothing found -> nothing reported."""

import flops_mellum2
import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    m = ctx.config.get("model", {})
    if not planes or win is None or not m.get("num_experts"):
        return None
    waits = trace_scopes.annotations(planes, [params["span"]], *win)
    touched = sum(float(c.get("experts_touched", 0)) for *_, c in waits)
    steps = trace_scopes.annotations(planes, [params["dispatch_span"]], *win)
    row_steps = sum(float(c.get("active", 0)) * float(c.get("interval", 0))
                    for *_, c in steps)
    if not touched:
        return None
    secs, runs, hits = ctx.load_module("readers", "program_ops_ms").seconds_in_program(
        planes, win, ctx.trace["first_device"], params["program"],
        params.get("scopes", ()), params.get("ops"))
    if not hits or secs <= 0:
        return None
    least = flops_mellum2.least_seconds(
        flops_mellum2.decode_experts_bytes(m, touched, row_steps), ctx.peak)
    ctx.log(f"decode_experts_roofline: {touched:.0f} experts touched in {len(waits)} dispatches "
            f"({runs} executions in the window), least {least:.4f} s over {secs:.4f} s spent")
    return 100.0 * least / secs
