"""Reader `fact`: one number the runner already holds, times `scale`."""


def read(params, facts, ctx):
    v = facts.get(params["key"])
    return None if v is None else float(v) * params.get("scale", 1.0)
