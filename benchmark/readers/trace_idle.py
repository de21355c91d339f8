"""Reader `trace_idle`: 1 - (union of device-op intervals) / traced window on
the first device, in %."""


def read(params, facts, ctx):
    d = ctx.trace["per_device"][ctx.trace["first_device"]]
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"]) if d["window_s"] > 0 else None
