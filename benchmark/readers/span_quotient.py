"""Reader `span_quotient`: over the program's spans of one name inside the
traced window (`picotron_tpu/telemetry/spans.py`: `TraceAnnotation`s whose
keyword arguments are counts), the sum of one count over the sum of another,
or over the number of spans where `den` is not given (a mean a span); a plain
quotient, where `span_ratio` gives a share in %. No such span, a span without
the count (a program from before it), or a zero denominator -> nothing
reported."""

import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    if not planes or win is None:
        return None
    spans = [c for *_, c in trace_scopes.annotations(planes, [params["span"]], *win)
             if params["num"] in c]
    num = sum(float(c[params["num"]]) for c in spans)
    den = (sum(float(c.get(params["den"], 0)) for c in spans) if "den" in params
           else float(len(spans)))
    if not den:
        return None
    ctx.log(f"{params['span']}: {len(spans)} spans, {params['num']} {num:.0f} over "
            f"{params.get('den', 'spans')} {den:.0f}")
    return num / den
