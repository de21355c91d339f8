"""Reader `phase_mean`: the mean `secs` of the engine's `phase` telemetry
events of one phase, in ms: the sum over the run by the number of events, so
that a few long ones weigh what they cost (`phase_secs` reads the median, which
leaves them out). It logs the sum, the count, the median and the longest."""

import numpy as np


def read(params, facts, ctx):
    secs = [s for p, s in facts.get("phases", ()) if p == params["phase"] and s is not None]
    if not secs:
        return None
    ctx.log(f"phase {params['phase']}: {sum(secs):.4f} s over {len(secs)} events, "
            f"median {float(np.median(secs)) * 1e3:.4f} ms, longest {max(secs) * 1e3:.3f} ms")
    return sum(secs) / len(secs) * 1e3
