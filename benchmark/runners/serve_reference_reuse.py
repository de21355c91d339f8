"""Runner `serve_reference_reuse`: `serve_reference`'s run as it is (the open
loop, the clock, the facts, `correct` over the timed run's logits), and behind
it a second, short phase for a model whose cache holds a STATE a slot beside
the rows a position: what the timed run's logits cannot tell from the
program's own bfloat16 distance is read where it shows.

Why a phase of its own (PERF.md section 6, PR 51): the timed traffic compares
logits 1,024 or more positions into a sequence, where a state left by the
slot's last request has decayed away and an attention over thousands of keys
adds little to the stream; both faults read inside the program's own distance
there, and twenty times it 48 positions into a sequence. And a state kept in a
lower precision is, to any comparison of outputs, one more bfloat16 rounding
among the activations': it shows in the state's own bits and nowhere else.

The phase, after the first engine is closed: the same weights from the seed, a
second engine from the same `serve` block (the compiled programs are the first
one's), and two rounds of `decode_slots` requests, so that every slot is used
twice: round one prompts of `first_prompt_tokens`, round two prompts of
`prompt_tokens` with `output_tokens` each (one more where the last would not
fall on a decode dispatch's last step), tokens from the seed. The cell's file
names it:

    "reuse": {"first_prompt_tokens": 300, "prompt_tokens": 48, "output_tokens": 17,
              "state_pool": "state",    the cache's field that holds the state a slot
              "limits": {...}}

Three readings, each with its limit, all part of `correct`:

- `reuse_logit_err_mean`: round two's served tokens under teacher forcing
  through the reference, by `serve_mellum2.compare`, the mean error as
  `logit_err_mean` has it: a slot's second request must start from a zero
  state, and a short context is where the attention's output (and its gate)
  weighs most.
- `state_err`: the first layer's rows of the state pool when round two has
  ended, against the state the reference's token-by-token rule carries out of
  the same tokens (`first_state`; the first layer reads the embedding alone, so
  the layers' accumulated distance is not in it): the largest |difference| /
  |reference| over the slots.
- `state_bf16_share`: the share of the whole state pool's elements that a
  bfloat16 holds exactly (the low 16 bits of the float32 are zero): about 2^-16
  of a float32 state, all of one kept or rounded in bfloat16.

A builder's sweep (`control=skip`) skips the phase with the reference.
"""

from __future__ import annotations

import importlib

import numpy as np


def rounds(engine, first, second, n_out: int) -> tuple:
    """Serve `first` (prompts, a few tokens each), then `second` (`n_out`
    tokens each) on an idle engine. Returns (results by request id for the
    second round, the slot each of them decoded in)."""
    slot_of = {}

    def drain():
        while engine.sched.has_work():
            engine.step(0.0)
            for i, st in enumerate(engine.sched.slots):
                if st is not None:
                    slot_of[st.req.id] = i

    n0 = len(engine.results)
    for i, p in enumerate(first):
        engine.submit(p, engine.scfg.decode_interval, req_id=1_000_000 + i)
    drain()
    ids = [2_000_000 + i for i in range(len(second))]
    for rid, p in zip(ids, second):
        engine.submit(p, n_out, req_id=rid)
    drain()
    by_id = {r["id"]: r for r in engine.results[n0:]}
    return {rid: by_id[rid] for rid in ids}, {rid: slot_of[rid] for rid in ids}


def bf16_share(pool) -> float:
    """The share of a float32 array's elements whose low 16 bits are zero."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(pool, jnp.uint32)
    return float(jnp.mean((bits & 0xFFFF) == 0))


def read(engine, reference, mellum, params, pub, spec: dict, seed: int, vocab: int) -> tuple:
    """The phase on a fresh engine -> (readings, a note)."""
    import jax.numpy as jnp

    rng = np.random.default_rng([seed, 51])
    n = engine.num_slots
    first = [rng.integers(0, vocab, size=spec["first_prompt_tokens"]).tolist() for _ in range(n)]
    second = [rng.integers(0, vocab, size=spec["prompt_tokens"]).tolist() for _ in range(n)]
    # a dispatch runs all its steps, and a step past a request's last token still moves
    # the slot's state (nobody reads it again: the slot's next request starts from zeros);
    # so that the state read below is the one after the tokens served, the last token
    # falls on a dispatch's last step
    n_out = spec["output_tokens"]
    n_out += -(n_out - 1) % engine.scfg.decode_interval
    results, slot_of = rounds(engine, first, second, n_out)
    pool = engine._kv[type(engine.cache)._fields.index(spec["state_pool"])]
    share = bf16_share(pool)
    rows = np.asarray(pool[0], np.float64)  # the first mixer's: [slots, Hv, dk, dv]
    engine.close()
    errs, state_err = [], 0.0
    for (rid, res), prompt in zip(results.items(), second):
        logits = mellum.reference_logits(reference, params, prompt, res["tokens"], pub)
        errs.append(mellum.compare(res["logits"], res["tokens"], logits)["err"])
        # what the slot has consumed: the prompt and every token but the last one served
        seen = jnp.asarray(prompt + res["tokens"][:-1], jnp.int32)
        want = np.asarray(reference.first_state(params, seen, pub), np.float64)
        got = rows[slot_of[rid]]
        state_err = max(state_err, float(np.linalg.norm(got - want) / np.linalg.norm(want)))
    errs = np.concatenate(errs)
    readings = dict(reuse_logit_err_mean=float(errs.mean()), state_err=state_err,
                    state_bf16_share=share)
    note = (f"reuse phase: {n} slots used twice (prompts of {spec['first_prompt_tokens']}, then "
            f"{spec['prompt_tokens']} tokens with {n_out} served, slots "
            f"{sorted(set(slot_of.values()))}), {len(errs)} served tokens: "
            + "; ".join(f"{k} {v:.5f} (limit {spec['limits'][k]})" for k, v in readings.items())
            + f"; the errors' median {np.median(errs):.5f}, max {errs.max():.5f}")
    return readings, note


def run(ctx) -> dict:
    facts = ctx.load_module("runners", "serve_reference").run(ctx)
    w, c = ctx.workload, ctx.config
    if w.get("control") == "skip":
        return facts

    import jax
    import jax.numpy as jnp

    from picotron_tpu.config import config_from_dict
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.serve import ServeEngine

    mellum = ctx.load_module("runners", "serve_mellum2")
    reference = importlib.import_module(w["reference"])
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve") if k in c})
    pub = {k: c[k] for k in reference.KEYS}

    def weights(key):  # as `serve_reference` draws them
        p = init_params(cfg.model, key)
        p = dict(p, embedding=p["embedding"] * c["initializer_range"])
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)

    params = jax.jit(weights)(jax.random.key(ctx.seed31(0)))
    engine = ServeEngine(params, cfg.model, cfg.serve)
    readings, note = read(engine, reference, mellum, params, pub, w["reuse"], ctx.seed,
                          cfg.model.vocab_size)
    facts.update(readings)
    facts["notes"].append(note)
    facts["correct"] = bool(facts["correct"]
                            and all(readings[k] <= v for k, v in w["reuse"]["limits"].items()))
    return facts
