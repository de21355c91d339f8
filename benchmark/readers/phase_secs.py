"""Reader `phase_secs`: the median `secs` of the engine's `phase` telemetry
events of one phase, in ms; `per_fact` divides by a number the runner holds (a
decode dispatch covers `decode_interval` tokens)."""

import numpy as np


def read(params, facts, ctx):
    secs = [s for p, s in facts.get("phases", ()) if p == params["phase"] and s is not None]
    if not secs:
        return None
    return float(np.median(secs)) * 1e3 / float(facts.get(params.get("per_fact"), 1) or 1)
