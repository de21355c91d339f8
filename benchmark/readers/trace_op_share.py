"""Reader `trace_op_share`: self time of the first device's operations whose
name matches `pattern` (a regular expression), over the traced window, in %.
Nothing matched -> nothing reported."""

import re


def read(params, facts, ctx):
    pat = re.compile(params["pattern"])
    secs = sum(s for n, s in ctx.trace["op_seconds"].items() if pat.search(n))
    win = ctx.trace["per_device"][ctx.trace["first_device"]]["window_s"]
    return 100.0 * secs / win if secs > 0 and win > 0 else None
