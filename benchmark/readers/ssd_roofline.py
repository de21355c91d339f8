"""Reader `ssd_roofline`: for the Mamba-2 state work of ONE serve program in
the traced window, the least time the chip could take (`flops_nemotron_h.py`,
over `peaks.json`) over the device time of the operations under the
recurrence's scope inside that program's executions
(`program_ops_ms.seconds_in_program`), in %.

The work is counted from the configuration's shapes and from the engine's own
counts on the program's dispatch spans, never from what implements it
(`ssm_roofline`'s arrangement): `program` `serve_decode`, scope `ssd_step`:
every step of a dispatch reads and writes the state and the tail of each of its
`state_rows` (slot, mixer) pairs once (x the span's `interval`); `program`
`serve_prefill`, scope `ssd_chunk`: a dispatch reads and writes its
`state_rows` once, takes x, B and C in and hands y out for each of its
`scan_tokens` (real token, mixer) pairs, and spends 5 operations an element of
a head's state on each. No such scope, span or count (a program from before
PR 62, a model without Mamba-2 mixers) -> nothing reported."""

import flops_nemotron_h
import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    m = ctx.config.get("model")
    if (not planes or win is None or not m
            or flops_nemotron_h.MAMBA2 not in (m.get("layer_types") or ())):
        return None
    secs, runs, hits = ctx.load_module("readers", "program_ops_ms").seconds_in_program(
        planes, win, ctx.trace["first_device"], params["program"], params["scopes"])
    spans = [c for *_, c in trace_scopes.annotations(planes, [params["span"]], *win)
             if "state_rows" in c]
    if not secs or not hits or not spans:
        return None
    if params["program"] == "serve_decode":
        rows = sum(float(c["state_rows"]) * float(c.get("interval", 0)) for c in spans)
        least = flops_nemotron_h.decode_step_least_seconds(m, rows, ctx.peak)
        what = f"{rows:.0f} state rows over the steps"
    else:
        rows = sum(float(c["state_rows"]) for c in spans)
        tokens = sum(float(c.get("scan_tokens", 0)) for c in spans)
        least = flops_nemotron_h.prefill_chunk_least_seconds(m, rows, tokens, ctx.peak)
        what = f"{rows:.0f} state rows, {tokens:.0f} (token, mixer) pairs"
    ctx.log(f"ssd_roofline {params['program']}: {what} in {len(spans)} dispatches "
            f"({runs} executions), least {least:.4f} s over {secs:.4f} s under "
            f"{list(params['scopes'])}")
    return 100.0 * least / secs
