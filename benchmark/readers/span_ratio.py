"""Reader `span_ratio`: over the program's spans of one name inside the
traced window (`picotron_tpu/telemetry/spans.py`: `TraceAnnotation`s whose
keyword arguments are counts), the sum of one count over the sum of another,
in %. No such span, or a zero denominator -> nothing reported."""

import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    if not planes or win is None:
        return None
    spans = trace_scopes.annotations(planes, [params["span"]], *win)
    num = sum(float(c.get(params["num"], 0)) for *_, c in spans)
    den = sum(float(c.get(params["den"], 0)) for *_, c in spans)
    if not den:
        return None
    ctx.log(f"{params['span']}: {len(spans)} spans, {params['num']} {num:.0f} "
            f"of {params['den']} {den:.0f}")
    return 100.0 * num / den
