"""Reader `fact_stat`: a statistic (`median`, or `pNN`) of a list of samples
the runner holds, times `scale`."""

import numpy as np


def read(params, facts, ctx):
    xs = facts.get(params["key"])
    if xs is None or len(xs) == 0:
        return None
    stat = params.get("stat", "median")
    q = 50.0 if stat == "median" else float(stat.lstrip("p"))
    return float(np.percentile(np.asarray(xs, np.float64), q)) * params.get("scale", 1.0)
