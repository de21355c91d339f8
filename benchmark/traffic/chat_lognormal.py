"""Generator `chat_lognormal`: independent chat users of one replica.

Arrivals are Poisson at `rate_per_s`; prompt and output lengths are lognormal
with the given medians and sigmas, clipped to the given ranges; prompt tokens
are uniform over the vocabulary; nothing is shared between prompts.

What the seed changes and what it does not: the arrival times and each
arrival's (prompt, output) lengths are drawn from the cell's own `shape_seed`,
so every run of the cell offers the same work at the same instants; `--seed`
draws the prompts' tokens (and, in the runner, the weights).

make(params, seed, seconds, vocab) -> [(due_s, [tokens], max_new_tokens)],
sorted by due time; only requests due inside `seconds`.
"""

from __future__ import annotations

import numpy as np


def _lengths(rng, n, spec):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def make(params: dict, seed: int, seconds: float, vocab: int):
    shape = np.random.default_rng(int(params["shape_seed"]))
    # gaps first, in blocks, so that a longer window extends the same arrivals
    rate = float(params["rate_per_s"])
    gaps = shape.exponential(1.0 / rate, size=int(rate * 60) + 64)
    due = np.cumsum(gaps)
    due = due[due < seconds]
    n = len(due)
    shape = np.random.default_rng(int(params["shape_seed"]) + 1)
    prompt_len = _lengths(shape, n, params["prompt_tokens"])
    output_len = _lengths(shape, n, params["output_tokens"])
    rng = np.random.default_rng([int(seed), 7])
    out = []
    for i, t in enumerate(due):
        prompt = rng.integers(0, vocab, size=int(prompt_len[i])).tolist()
        out.append((float(t), prompt, int(output_len[i])))
    return out
