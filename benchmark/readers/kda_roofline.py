"""Reader `kda_roofline`: for the Kimi Delta Attention state work of ONE serve
program in the traced window, the least time the chip could take
(`flops_kimi_linear.py`, over `peaks.json`) over the device time of the
operations under the recurrence's scope inside that program's executions
(`program_ops_ms.seconds_in_program`), in %: `kda_state` in `serve_decode`,
`kda_chunk` in `serve_prefill`.

The work is counted from the configuration's shapes and from the engine's own
counts on the program's dispatch spans, never from what implements it:
`program` `serve_decode`: every step of a dispatch reads and writes the state
of each of its `state_rows` (slot, mixer) pairs once (x the span's `interval`);
`program` `serve_prefill`: a dispatch reads and writes its `state_rows` once and
spends 6 d_k d_v operations a head on each of its `tokens` in every mixer. The
spans are taken inside the window as the executions are; the engine runs one
decode dispatch ahead, so the two sets may differ by a dispatch at an edge. No
such scope, span or count, or a configuration without kda layers (a program
from before PR 57) -> nothing reported."""

import flops_kimi_linear
import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    m = ctx.config.get("model")
    if not planes or win is None or not m or flops_kimi_linear.KDA not in (
            m.get("layer_types") or ()):
        return None
    secs, runs, hits = ctx.load_module("readers", "program_ops_ms").seconds_in_program(
        planes, win, ctx.trace["first_device"], params["program"], params["scopes"])
    spans = [c for *_, c in trace_scopes.annotations(planes, [params["span"]], *win)
             if "state_rows" in c]
    if not secs or not hits or not spans:
        return None
    if params["program"] == "serve_decode":
        rows = sum(float(c["state_rows"]) * float(c.get("interval", 0)) for c in spans)
        least = flops_kimi_linear.decode_state_least_seconds(m, rows, ctx.peak)
        what = f"{rows:.0f} state rows over the steps"
    else:
        rows = sum(float(c["state_rows"]) for c in spans)
        tokens = sum(float(c.get("tokens", 0)) for c in spans)
        least = flops_kimi_linear.prefill_state_least_seconds(m, rows, tokens, ctx.peak)
        what = f"{rows:.0f} state rows, {tokens:.0f} tokens"
    ctx.log(f"kda_roofline {params['program']}: {what} in {len(spans)} dispatches "
            f"({runs} executions), least {least:.4f} s over {secs:.4f} s under "
            f"{list(params['scopes'])}")
    return 100.0 * least / secs
