"""Reader `latent_experts_roofline`: `decode_experts_roofline` for experts
that live on a LATENT (`moe_latent_size`) and are not gated: for the expert
matmuls of the decode steps in the traced window, the least time the chip
could take to move their bytes (`flops_nemotron_h.decode_experts_bytes`: the
TWO banks of moe_latent_size x moe_intermediate_size of every expert that a
live row was routed to, which the decode program counts and the engine puts on
its `serve.decode.wait` span as `experts_touched`, plus the latent rows) over
the device time `program_ops_ms` sums for them inside `serve_decode` (the
`moe_experts` scope), in %. A decode step is bound by memory. A model without
a latent, nothing counted or nothing found -> nothing reported."""

import flops_nemotron_h
import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    m = ctx.config.get("model", {})
    if not planes or win is None or not m.get("num_experts") or not m.get("moe_latent_size"):
        return None
    waits = trace_scopes.annotations(planes, [params["span"]], *win)
    touched = sum(float(c.get("experts_touched", 0)) for *_, c in waits)
    steps = trace_scopes.annotations(planes, [params["dispatch_span"]], *win)
    row_steps = sum(float(c.get("active", 0)) * float(c.get("interval", 0))
                    for *_, c in steps)
    if not touched:
        return None
    secs, runs, hits = ctx.load_module("readers", "program_ops_ms").seconds_in_program(
        planes, win, ctx.trace["first_device"], params["program"], params["scopes"])
    if not hits or secs <= 0:
        return None
    least = flops_nemotron_h.least_seconds(
        flops_nemotron_h.decode_experts_bytes(m, touched, row_steps), ctx.peak)
    ctx.log(f"latent_experts_roofline: {touched:.0f} experts touched in {len(waits)} dispatches "
            f"({runs} executions in the window), least {least:.4f} s over {secs:.4f} s spent")
    return 100.0 * least / secs
