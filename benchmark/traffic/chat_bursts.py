"""Generator `chat_bursts`: chat users of one replica whose requests come in
bursts (BurstGPT, arXiv:2401.17644: the gaps between arrivals at a served
model are far from exponential; a few seconds of many requests, then quiet).

`chat_lognormal`'s lengths and seeds under another arrival law: the gaps
between arrivals are gamma-distributed with mean `1 / rate_per_s` and
coefficient of variation `arrival_cv` (shape `1 / cv^2`, scale `cv^2 /
rate_per_s`; `cv` 1 is `chat_lognormal`'s Poisson process, `cv` 3 puts most
gaps far under the mean and a few at many times it). Prompt and output lengths
are lognormal with the given medians and sigmas, clipped to the given ranges;
prompt tokens are uniform over the vocabulary; nothing is shared between
prompts.

The prompt's law is read from `prompt_tokens`, or where the cell's runner
wants its traffic in classes (`serve_reference`'s `pick` reads
`classes[0].prompt_tokens.max`), from the one class of `classes`.

What the seed changes and what it does not (as `chat_lognormal`): the gaps are
drawn first from the cell's own `shape_seed`, the (prompt, output) lengths from
`shape_seed + 1`, so every run of the cell offers the same work at the same
instants; `--seed` draws the prompts' tokens (and, in the runner, the weights).

make(params, seed, seconds, vocab) -> [(due_s, [tokens], max_new_tokens)],
sorted by due time; only requests due inside `seconds`.
"""

from __future__ import annotations

import numpy as np


def _lengths(rng, n, spec):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _prompt_spec(params: dict) -> dict:
    if "prompt_tokens" in params:
        return params["prompt_tokens"]
    (only,) = params["classes"]  # one class: this generator has one prompt law
    return only["prompt_tokens"]


def shape(params: dict, seconds: float):
    """(due [n], prompt length [n], output length [n]): the cell's fixed
    schedule, without the tokens."""
    rng = np.random.default_rng(int(params["shape_seed"]))
    rate, cv = float(params["rate_per_s"]), float(params["arrival_cv"])
    # gaps first, in blocks, so that a longer window extends the same arrivals
    gaps = rng.gamma(1.0 / cv ** 2, cv ** 2 / rate, size=int(rate * 60) + 64)
    due = np.cumsum(gaps)
    while due[-1] < seconds:  # a long quiet spell: draw on
        more = rng.gamma(1.0 / cv ** 2, cv ** 2 / rate, size=len(gaps))
        due = np.concatenate([due, due[-1] + np.cumsum(more)])
    due = due[due < seconds]
    rng = np.random.default_rng(int(params["shape_seed"]) + 1)
    n = len(due)
    return due, _lengths(rng, n, _prompt_spec(params)), _lengths(rng, n, params["output_tokens"])


def make(params: dict, seed: int, seconds: float, vocab: int):
    due, prompt_len, output_len = shape(params, seconds)
    rng = np.random.default_rng([int(seed), 7])
    return [(float(t), rng.integers(0, vocab, size=int(prompt_len[i])).tolist(),
             int(output_len[i])) for i, t in enumerate(due)]
