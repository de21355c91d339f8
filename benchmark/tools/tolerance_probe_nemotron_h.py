#!/usr/bin/env python3
"""What the numbers `correct` compares in the Nemotron-H serving cell
(`nemotron3-super-120b-a12b-22l-ep8.agent-ctx`, runner `serve_reference_reuse`)
move by when the model is wrong in the ways its limits have to catch, at the
cell's widths on the chip: the plain reference (`reference_nemotron_h.py`)
against itself with (a) the recurrent state rounded to bfloat16 after every
token, (b) the convolution's tail dropped at every prefill-chunk boundary, (c) a
state not reset between two requests of one slot (the recurrence starts from the
state the sequence's own first chunk leaves, in every mixer), (d) D x left out, (e) B and C
taken from the wrong group (a head reads the next group's), (f) the gated norm
over all 8,192 channels instead of 8 groups, (g) the gate after the norm, (h)
relu in place of relu^2, (j) the routed scaling factor 5 left out, (k) the
latent's up projection skipped (the experts' sum left in the stream's first
1,024 columns), (l) the selection bias ignored (a no-op while the bias is
seeded zero: it has to read as the exact reference does, and is held in float32
on the CPU), (m) the middle mixer skipped, (i) every matrix rounded to int8 and
back (the nearest precision below the bfloat16 weights with float32
accumulation the configuration states). Each control stands in the program's
place: the token it puts first at each position and the logit it gives that
token are read by the runner's own `compare`, against the exact reference,
beside the cell's `limits`, and the readings of the runner's reuse phase
(`runners/serve_reference_reuse.py`) beside the limits of the cell's `reuse`.
Each has to come out NOT correct by at least one of the six. One more run is a
WITNESS and has to pass: (w) the reference with every activation rounded to
bfloat16 where a bfloat16 program holds one (`bf16_acts`), which reads what the
program reads if the program's distance is rounding and nothing else. (The
program's own readings are printed by every run of the cell; PERF.md section 6,
PR 62, records both.)

Two sequences from the cell's own traffic, the longest prompt (cut to
`longest` tokens, default 16,000: every control runs the reference over it
anew) and the first one, each with `outputs` forced tokens behind its prompt;
compared at the forced tokens' positions, where a served token would be. The
reuse phase's readings from four sequences of its shape (`prompt_tokens` +
`output_tokens`, tokens from the seed): the logits as above, the first mixer's
carried state against the exact reference's (`state_err`) and the share of it a
bfloat16 holds. Also printed: what exp(d A) keeps a step over the seeded heads.
Run by hand on the chip, which it insists on.

    python3 benchmark/tools/tolerance_probe_nemotron_h.py [seed] [outputs] [controls, e.g. adi] [longest]

`controls`: the letters of the controls to run (default all thirteen and the
witness, w); every control compiles the reference's layers anew.
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

CELL = "nemotron3-super-120b-a12b-22l-ep8.agent-ctx"
CONTROLS = [("(a) the state rounded to bfloat16 after every token", "bf16_state"),
            ("(b) the convolution's tail dropped at every chunk boundary", "tail_dropped"),
            ("(c) a state not reset between two requests of one slot", "state_kept"),
            ("(d) D x left out", "no_d_skip"),
            ("(e) B and C taken from the wrong group", "wrong_group"),
            ("(f) the norm over all channels instead of the groups", "norm_ungrouped"),
            ("(g) the gate after the norm", "gate_after_norm"),
            ("(h) relu in place of relu^2", "relu_not_squared"),
            ("(j) the routed scaling factor left out", "no_scale"),
            ("(k) the latent's up projection skipped", "no_latent_up"),
            ("(l) the selection bias ignored", "bias_ignored"),
            ("(m) the middle mixer skipped", "mixer_skipped"),
            ("(w) WITNESS, has to pass: activations rounded to bfloat16", "bf16_acts")]


def load(kind, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> None:
    require_platform("tolerance_probe_nemotron_h", allow_cpu=False)
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 1
    n_out = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    wanted = sys.argv[3] if len(sys.argv) > 3 else "abcdefghijklmw"
    longest = int(sys.argv[4]) if len(sys.argv) > 4 else 16000
    mellum = load("runners", "serve_mellum2")  # `reference_logits`, `compare`: the runner's own
    with open(os.path.join(HERE, "workloads", CELL + ".json")) as f:
        w = json.load(f)
    with open(os.path.join(HERE, "configs", w["config"] + ".json")) as f:
        c = json.load(f)
    reference = importlib.import_module(w["reference"])
    reuse = w["reuse"]
    limits = {**w["limits"], **reuse["limits"]}
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
    prompts = [max((p for _, p, _ in requests), key=len)[:longest], requests[0][1]]
    # what exp(d A) keeps a step, over the seeded heads, d = softplus(dt_bias)
    lay = params["layers"]
    kept = np.exp(-np.asarray(jax.nn.softplus(lay["ssd_dt_bias"].astype(jnp.float32)))
                  * np.exp(np.asarray(lay["ssd_A_log"].astype(jnp.float32))))
    print("exp(d A) a step at the 5th, 25th, 50th, 75th, 95th percentile of "
          f"{kept.size} (mixer, head) pairs: "
          + ", ".join(f"{q:.4f}" for q in np.quantile(kept, [0.05, 0.25, 0.5, 0.75, 0.95])),
          flush=True)
    rng = np.random.default_rng(seed)
    forced = [rng.integers(0, cfg.model.vocab_size, size=n_out).tolist() for _ in prompts]
    # the reuse phase's shape: short sequences
    short = [rng.integers(0, cfg.model.vocab_size, size=reuse["prompt_tokens"]).tolist()
             for _ in range(4)]
    short_forced = [rng.integers(0, cfg.model.vocab_size, size=reuse["output_tokens"]).tolist()
                    for _ in short]

    def logits(p, faults):
        """([sequence][n_out, V] at the forced tokens' positions, the same for
        the reuse phase's sequences, the first mixer's carried state of each of
        those). The runner's helper, with the probe's faults passed through."""
        class Faulty:  # `reference_logits` calls reference.logits_at(params, ids, rows, pub)
            @staticmethod
            def logits_at(*a):
                return reference.logits_at(*a, **faults)
        return ([mellum.reference_logits(Faulty, p, pr, f, pub) for pr, f in zip(prompts, forced)],
                [mellum.reference_logits(Faulty, p, pr, f, pub)
                 for pr, f in zip(short, short_forced)],
                [np.asarray(reference.first_state(p, jnp.asarray(pr + f[:-1], jnp.int32), pub,
                                                  **faults), np.float64)
                 for pr, f in zip(short, short_forced)])

    exact = logits(params, {})
    print("sequences: prompts of " + ", ".join(str(len(p)) for p in prompts)
          + f" tokens; the exact reference's top logit there: median "
          f"{np.median(np.concatenate([e.max(-1) for e in exact[0]])):.3f}; the reuse phase's: "
          f"4 of {reuse['prompt_tokens']} + {reuse['output_tokens']}", flush=True)

    def against(exact, ctl):
        tie, errs = 0.0, []
        for e, x in zip(exact, ctl):
            first = x.argmax(-1)
            got = mellum.compare(x[np.arange(len(first)), first], first, e)
            tie, errs = max(tie, got["tie"]), errs + [got["err"]]
        return tie, np.concatenate(errs)

    def verdict(name, ctl):
        tie, errs = against(exact[0], ctl[0])
        read = dict(tie=tie, logit_err_mean=float(errs.mean()), logit_err_max=float(errs.max()))
        state = np.concatenate([x.ravel() for x in ctl[2]]).astype(np.float32)
        read.update(
            reuse_logit_err_mean=float(against(exact[1], ctl[1])[1].mean()),
            state_err=max(float(np.linalg.norm(x - e) / np.linalg.norm(e))
                          for e, x in zip(exact[2], ctl[2])),
            state_bf16_share=float(np.mean((state.view(np.uint32) & 0xFFFF) == 0)))
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
    if "i" not in wanted:
        return
    # (i) last, a leaf at a time, so that no second copy of the weights is ever held
    for n in reference.MATRICES:
        params["layers"][n] = reference.rounded_to(params, 8, only=(n,), donate=True)["layers"][n]
    for n in ("embedding", "lm_head"):
        params[n] = reference.rounded_to(params, 8, only=(n,), donate=True)[n]
    verdict("(i) every matrix rounded to int8 and back", logits(params, {}))


if __name__ == "__main__":
    main()
