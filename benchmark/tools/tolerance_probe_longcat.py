#!/usr/bin/env python3
"""What the numbers `correct` compares in the LongCat-Flash-Omni serving cell
(`longcat-flash-omni-4l-ep32.agent-turns`, runner `serve_reference`) move by
when the model is wrong in the ways its limits have to catch, at the cell's
widths on the chip: the plain reference (`reference_longcat.py`) against itself
with (a) the zero-compute experts' term dropped, (b) the key/value latent's
scale missing, (c) the query latent's scale missing, (d) a layer's second
attention reading the first one's cached rows (two attentions sharing one cache
row), (e) the selection bias left out (a no-op while the bias is zeros, as it
is under seeded weights: listed so that the reading says so), (f) every matrix
rounded to int8 and back (the nearest precision below the bfloat16 weights with
float32 accumulation the configuration states). Each control stands in the
program's place: the token it puts first at each position and the logit it
gives that token are read by the runner's own `compare`, against the exact
reference, beside the cell's `limits`. (a)-(d) and (f) each have to come out NOT
correct by at least one of them. (The program's own readings are printed by
every run of the cell; PERF.md section 6, PR 47, records both.)

Two sequences from the cell's own traffic, the first request of the long class
and the first of the short class, each with `outputs` forced tokens behind its
prompt; compared at the forced tokens' positions, where a served token would
be. Run by hand on the chip, which it insists on.

    python3 benchmark/tools/tolerance_probe_longcat.py [seed] [outputs] [controls, e.g. adf]

`controls`: the letters of the controls to run (default all six); every control
compiles the reference's layer anew.
"""
import importlib
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from picotron_tpu.config import config_from_dict  # noqa: E402
from picotron_tpu.models.llama import init_params  # noqa: E402
from picotron_tpu.utils import require_platform  # noqa: E402

CELL = "longcat-flash-omni-4l-ep32.agent-turns"
CONTROLS = [("(a) the zero-compute term dropped", "no_zero_term"),
            ("(b) the key/value latent's scale missing", "no_kv_scale"),
            ("(c) the query latent's scale missing", "no_q_scale"),
            ("(d) the second attention reading the first one's cache row", "shared_cache_row"),
            ("(e) the selection bias left out", "no_selection_bias")]


def load(kind, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> None:
    require_platform("tolerance_probe_longcat", allow_cpu=False)
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 1
    n_out = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    wanted = sys.argv[3] if len(sys.argv) > 3 else "abcdef"
    mellum = load("runners", "serve_mellum2")  # `reference_logits`, `compare`: the runner's own
    with open(os.path.join(HERE, "workloads", CELL + ".json")) as f:
        w = json.load(f)
    with open(os.path.join(HERE, "configs", w["config"] + ".json")) as f:
        c = json.load(f)
    reference = importlib.import_module(w["reference"])
    limits = w["limits"]
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")})
    pub = {k: c[k] for k in reference.KEYS}
    print(f"device {jax.devices()[0].device_kind}; configuration {w['config']}, seed {seed}, "
          f"{n_out} forced tokens a sequence; limits {limits}", flush=True)

    def weights(key):  # as the runner draws them
        p = init_params(cfg.model, key)
        p = dict(p, embedding=p["embedding"] * c["initializer_range"])
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)

    seed31 = int(np.random.SeedSequence([seed, 0]).generate_state(1)[0] >> 1)
    params = jax.jit(weights)(jax.random.key(seed31))
    requests = load("traffic", w["traffic"]["generator"]).make(
        w["traffic"], seed, 51.0, cfg.model.vocab_size)
    short_max = w["traffic"]["classes"][0]["prompt_tokens"]["max"]
    prompts = [next(p for _, p, _ in requests if len(p) > short_max),
               next(p for _, p, _ in requests if len(p) <= short_max)]
    rng = np.random.default_rng(seed)
    forced = [rng.integers(0, cfg.model.vocab_size, size=n_out).tolist() for _ in prompts]

    def logits(p, faults):
        """[sequence][n_out, V] at the forced tokens' positions. The runner's
        helper, with the probe's faults passed through."""
        class Faulty:  # `reference_logits` calls reference.logits_at(params, ids, rows, pub)
            @staticmethod
            def logits_at(*a):
                return reference.logits_at(*a, **faults)
        return [mellum.reference_logits(Faulty, p, pr, f, pub) for pr, f in zip(prompts, forced)]

    exact = logits(params, {})
    print("sequences: prompts of " + ", ".join(str(len(p)) for p in prompts)
          + f" tokens; the exact reference's top logit there: median "
          f"{np.median(np.concatenate([e.max(-1) for e in exact])):.3f}", flush=True)

    def verdict(name, ctl):
        tie, errs = 0.0, []
        for e, x in zip(exact, ctl):
            first = x.argmax(-1)
            got = mellum.compare(x[np.arange(len(first)), first], first, e)
            tie, errs = max(tie, got["tie"]), errs + [got["err"]]
        errs = np.concatenate(errs)
        read = dict(tie=tie, logit_err_mean=float(errs.mean()), logit_err_max=float(errs.max()))
        over = [k for k in limits if read[k] > limits[k]]
        print(f"{name}: " + "; ".join(f"{k} {read[k]:.5f} (limit {limits[k]})" for k in read)
              + f"; the errors' median {np.median(errs):.5f}, p90 {np.percentile(errs, 90):.5f}, "
                f"p99 {np.percentile(errs, 99):.5f}"
              + f" -> {'NOT correct, by ' + ', '.join(over) if over else 'passes as correct'}",
              flush=True)

    verdict("the exact reference in the program's place", exact)
    for name, fault in CONTROLS:
        if name[1] in wanted:
            verdict(name, logits(params, {fault: True}))
    if "f" not in wanted:
        return
    # (f) last, a leaf at a time, so that no second copy of the weights is ever held
    for n in reference.MATRICES:
        params["layers"][n] = reference.rounded_to(params, 8, only=(n,))["layers"][n]
    for n in ("embedding", "lm_head"):
        params[n] = reference.rounded_to(params, 8, only=(n,))[n]
    verdict("(f) every matrix rounded to int8 and back", logits(params, {}))


if __name__ == "__main__":
    main()
