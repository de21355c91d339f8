"""Generator `code_mixed`: users of a coding assistant on one replica, short
questions and long requests (open files, a slice of a repository) in one
queue.

Arrivals are Poisson at `rate_per_s`. Each arrival belongs to one of the
`classes` (drawn with the classes' `share`s), and its prompt length is
lognormal with that class's median and sigma, clipped to its range; output
lengths are lognormal with one law for every class; prompt tokens are uniform
over the vocabulary; nothing is shared between prompts.

What the seed changes and what it does not (as `chat_lognormal`): the arrival
times, each arrival's class and its (prompt, output) lengths are drawn from
the cell's own `shape_seed`, so every run of the cell offers the same work at
the same instants; `--seed` draws the prompts' tokens (and, in the runner,
the weights).

make(params, seed, seconds, vocab) -> [(due_s, [tokens], max_new_tokens)],
sorted by due time; only requests due inside `seconds`.
"""

from __future__ import annotations

import numpy as np


def _lengths(rng, n, spec):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def shape(params: dict, seconds: float):
    """(due [n], class index [n], prompt length [n], output length [n]): the
    cell's fixed schedule, without the tokens."""
    rng = np.random.default_rng(int(params["shape_seed"]))
    # gaps first, in blocks, so that a longer window extends the same arrivals
    rate = float(params["rate_per_s"])
    due = np.cumsum(rng.exponential(1.0 / rate, size=int(rate * 60) + 64))
    due = due[due < seconds]
    n = len(due)
    rng = np.random.default_rng(int(params["shape_seed"]) + 1)
    classes = params["classes"]
    shares = np.asarray([c["share"] for c in classes], np.float64)
    which = rng.choice(len(classes), size=n, p=shares / shares.sum())
    by_class = [_lengths(rng, n, c["prompt_tokens"]) for c in classes]
    prompt_len = np.choose(which, by_class)
    return due, which, prompt_len, _lengths(rng, n, params["output_tokens"])


def make(params: dict, seed: int, seconds: float, vocab: int):
    due, _, prompt_len, output_len = shape(params, seconds)
    rng = np.random.default_rng([int(seed), 7])
    return [(float(t), rng.integers(0, vocab, size=int(prompt_len[i])).tolist(),
             int(output_len[i])) for i, t in enumerate(due)]
