"""Reader `flash_roofline`: for the flash-attention kernel calls in the traced
steps, the least time the chip could take (`flops.py` operations and bytes per
call over `peaks.json`, the larger of the two bounds) over the summed device
time of the kernels' events, in %. The kernels' events are those whose name
matches `pattern`; each layer and microbatch makes one call of each of
`kinds` (`fwd`, `dq`, `dkv`), so the trace has to hold exactly
len(kinds) x `flash_calls_per_step` x traced steps of them, or the pattern
has caught something else and nothing is reported."""

import re

import flops


def read(params, facts, ctx):
    shape = facts.get("flash_shape")
    if not shape or not facts.get("traced_steps"):
        return None
    pat = re.compile(params["pattern"])
    names = [n for n in ctx.trace["op_seconds"] if pat.search(n)]
    calls = sum(ctx.trace["op_calls"][n] for n in names)
    per_kind = facts["flash_calls_per_step"] * facts["traced_steps"]
    if calls != per_kind * len(params["kinds"]):
        ctx.log(f"flash_roofline: {calls} events match {params['pattern']!r}, the step's "
                f"shape says {per_kind * len(params['kinds'])}: not reported")
        return None
    spent = sum(ctx.trace["op_seconds"][n] for n in names)
    least = sum(per_kind * flops.least_seconds(
        flops.flash_call_flops(k, shape["batch"], shape["heads"], shape["seq"], shape["d"]),
        flops.flash_call_bytes(k, shape["batch"], shape["heads"], shape["kv_heads"],
                               shape["seq"], shape["d"]),
        ctx.peak) for k in params["kinds"])
    return 100.0 * least / spent if spent > 0 else None
