"""Runner `serve_reference`: one `ServeEngine` serving a configuration whose
cell's file names its own reference, load offered on a schedule. The fourth
copy of the open loop and the last one a new configuration should need: what
the three before it (`serve_open_loop`, `serve_mellum2`, `serve_pangu_moe`)
name inside `run` is read from the cell's file here (PERF.md section 7 (d)):

    "reference": "reference_k_exaone"   the module beside `run.py` that holds the plain
                                        reference: `KEYS` (the keys it reads from the
                                        configuration's file), `as_program(pub)` (the same
                                        keys under the program's names, for the check
                                        below) and `logits_at(params, ids, rows, pub)`
    "pools": {"pool_fill": "pool", "window_pool_fill": "wpool"}
                                        fact -> the engine's attribute that holds a
                                        `BlockPool`: each pool's fullest is a fact, and
                                        none may hold a block after the drain
    "limits": {"tie": ..., "logit_err_mean": ..., "logit_err_max": ...}
    "picks": 12                         completed requests a run whose tokens are compared

The loop, the clock and the facts are `serve_open_loop`'s by way of
`serve_pangu_moe`; imported as they are: `serve_open_loop`'s helpers and
`serve_mellum2`'s `pick`, `reference_logits` and `compare`. bf16 weights from
the seed, one engine from the configuration's `serve` block, warmed with two
short requests; a request is submitted when it is due on the trace clock and
timed from when it was due; what a client would see is read by the harness
after each `engine.step` returns. Further facts where the model has experts:
`experts_touched_share`, `picks_here_share`, `rows_per_bank`; `rows_live` (live
slots a decode dispatch); `model`.

Before a weight is drawn the file's published keys are checked against the
model the program built: a program from before this configuration drops the
keys it does not know and would serve another model; it fails here at once.

`correct`, of what the timed run produced: `picks` completed requests (the
longest, and the others drawn from the seed, at least one of them of the
first class) under teacher forcing through the reference (float32, no cache,
every token through every held expert), a layer at a time, the sequence padded
to a power of two. Three comparisons, each with its limit (the cell's
`limits`; the readings behind each, the program's and the controls', are in
PERF.md section 6 under the PR that added the cell):

- `tie`: every served token is the reference's first or within 16 bfloat16
  steps (2^-8 of the top logit each) of it (`serve_mellum2.compare`).
- `logit_err_mean`: the mean over the compared tokens of |the float32 logit the
  engine chose the token at - the reference's logit of that token at that
  position| / max(|the reference's top logit|, 1): what moves when every token
  is computed wrongly by a little (int8 weights, a missing expert or norm).
- `logit_err_max`: the largest such error: a single token computed wrongly by
  much (a band missing on positions only long requests reach).

Also `correct`: no compile inside the window, no block of any pool still held
after the drain, nothing failed. A builder's sweep of rates or serve settings
(`tools/knee_sweep.py ... control=skip`, never the cell's own file) skips the
reference, and such a run cannot come out as correct.
"""

from __future__ import annotations

import contextlib
import json
import os
import time

import numpy as np


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    # the open-loop runner's helpers, as they are (it touches its own reference
    # only inside its `run`)
    base = ctx.load_module("runners", "serve_open_loop")
    # ... and the Mellum2 runner's comparison of logits: `pick`, `reference_logits`,
    # `compare` (its tie band of 16 bfloat16 steps)
    mellum = ctx.load_module("runners", "serve_mellum2")
    _Collect, _p90, _around_p90 = base._Collect, base._p90, base._around_p90
    SPANS, ID0, TRACE_S = base.SPANS, base.ID0, base.TRACE_S

    import importlib

    from picotron_tpu.config import config_from_dict
    from picotron_tpu.generate import place_for_decode
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.serve import ServeEngine
    from picotron_tpu.telemetry import Telemetry

    c, w = ctx.config, ctx.workload
    m = c["model"]
    reference = importlib.import_module(w["reference"])  # beside run.py, which is on the path
    LIMITS, PICKS = w["limits"], int(w["picks"])
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve") if k in c})
    mcfg, scfg, tp = cfg.model, cfg.serve, cfg.distributed.tp_size
    # the reference reads the file's published keys, not the program's config
    pub = {k: c[k] for k in reference.KEYS}
    for k, v in reference.as_program(pub).items():
        # a program from before this model has no such field: it fails here, at once
        if getattr(mcfg, k, None) != v:
            raise SystemExit(f"serve_reference: {k} differs between the file's published keys "
                             f"({v!r}) and the program's model ({getattr(mcfg, k, None)!r})")
    if tp != ctx.chips:
        raise SystemExit(f"serve_reference: tp {tp} but the cell has {ctx.chips} chip(s)")

    def weights(key):
        p = init_params(mcfg, key)
        if "initializer_range" in c:
            # the embedding at a trained model's standard deviation: the program draws
            # it unit normal, which drowns what the layers add to the residual stream,
            # so that a fault in a layer would hardly show in the logits
            p = dict(p, embedding=p["embedding"] * c["initializer_range"])
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)

    params = jax.jit(weights)(jax.random.key(ctx.seed31(0)))
    if tp > 1:
        params = place_for_decode(params, mcfg, tp=tp, devices=ctx.devices)
    sink = _Collect()
    engine = ServeEngine(params, mcfg, scfg, telemetry=Telemetry(sinks=[sink]))

    requests = ctx.load_module("traffic", w["traffic"]["generator"]).make(
        w["traffic"], ctx.seed, ctx.seconds, mcfg.vocab_size)
    # (due_s, prompt tokens, max_new), sorted by due time

    # warm-up: the prefill and the decode program, nothing else
    for _ in range(2):
        engine.submit(list(range(1, 41)), 2 * scfg.decode_interval)
    while engine.sched.has_work():
        engine.step(0.0)
    n_warm = len(engine.results)
    stats0 = dict(engine.stats)
    sink.phases.clear()

    trace_at = max(ctx.seconds - TRACE_S, 0.0)
    tracing = False
    traced = not ctx.trace
    span = contextlib.nullcontext
    window_cm = None

    limit = ctx.seconds + float(w["drain_limit_s"])
    pending = list(requests)
    pending.reverse()  # pop() from the end = earliest due
    first_seen, n_at_first, done_at, lag = {}, {}, {}, {}
    due_of = {}
    n_results = n_warm
    in_step = 0.0  # seconds inside `engine.step`, for the note that splits a run's wall

    ctx.window_starts()
    t0 = time.perf_counter()
    while pending or engine.sched.has_work():
        now = time.perf_counter() - t0
        if now > limit:
            break
        if not traced and not tracing and now >= trace_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)
            tracing, span = True, jax.profiler.TraceAnnotation
            window_cm = jax.profiler.TraceAnnotation("bench.window")
            window_cm.__enter__()
            now = time.perf_counter() - t0
        elif tracing and now >= trace_at + TRACE_S:
            window_cm.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing, traced, span = False, True, contextlib.nullcontext
            now = time.perf_counter() - t0
        if pending and pending[-1][0] <= now:
            with span("submit"):
                while pending and pending[-1][0] <= now:
                    due, prompt, max_new = pending.pop()
                    rid = ID0 + len(due_of)
                    due_of[rid] = due
                    lag[rid] = now - due
                    engine.submit(prompt, max_new, req_id=rid, arrival=due)
        if not engine.sched.has_work():
            with span("wait.arrival"):
                time.sleep(min(max(pending[-1][0] - now, 0.0), 0.001))
            continue
        with span("engine.step"):
            engine.step(now)
        with span("observe"):
            t_after = time.perf_counter() - t0
            in_step += t_after - now
            for st in engine.sched.slots:
                if st is not None and st.generated and st.req.id not in first_seen:
                    first_seen[st.req.id] = t_after
                    n_at_first[st.req.id] = len(st.generated)
            for res in engine.results[n_results:]:
                if res["id"] not in first_seen:
                    first_seen[res["id"]] = t_after
                    n_at_first[res["id"]] = res["output_tokens"]
                done_at[res["id"]] = t_after
            n_results = len(engine.results)
    wall_end = time.perf_counter() - t0
    if tracing:
        window_cm.__exit__(None, None, None)
        jax.profiler.stop_trace()
    in_window = ctx.window_ends()

    results = {r["id"]: r for r in engine.results[n_warm:]}
    attempted = len(requests)
    completed = [rid for rid in due_of if rid in results]
    failed = attempted - len(completed)
    out_tokens = sum(results[r]["output_tokens"] for r in completed)
    last_done = max((done_at[r] for r in completed), default=wall_end)
    ttft_of = {r: (first_seen[r] - due_of[r]) * 1e3 for r in completed}
    ttft = list(ttft_of.values())
    per_token_of = {r: (done_at[r] - due_of[r]) / results[r]["output_tokens"] * 1e3
                    for r in completed}
    tpot = [(done_at[r] - first_seen[r]) / (results[r]["output_tokens"] - n_at_first[r]) * 1e3
            for r in completed if results[r]["output_tokens"] > n_at_first[r]]
    steps = engine.stats["decode_steps"] - stats0["decode_steps"]
    facts = dict(
        attempted=attempted, failed=failed, compiles_in_window=in_window["compiles"],
        spans=SPANS,
        # over the wall from t = 0 to the last completion (to the limit, where
        # something was left unfinished), drain included
        output_tokens_per_s=out_tokens / (wall_end if failed else last_done),
        latency_per_token_ms_p90=_p90(list(per_token_of.values())),
        ttft_ms_p90=_p90(ttft), tpot_ms_p90=_p90(tpot),
        queue_wait_ms=[results[r]["queue_wait_s"] * 1e3 for r in completed],
        slot_occupancy=((engine.stats["occupancy_sum"] - stats0["occupancy_sum"])
                        / max(steps, 1)),
        # live slots a decode dispatch: what amortises the weights a step reads
        rows_live=((engine.stats["occupancy_sum"] - stats0["occupancy_sum"])
                   * scfg.decode_slots / max(steps, 1)),
        model=m,
        phases=list(sink.phases), decode_interval=scfg.decode_interval,
        device=ctx.device_report(),
    )
    pools = {fact: getattr(engine, attr) for fact, attr in w["pools"].items()}
    for fact, pool in pools.items():
        facts[fact] = pool.peak_in_use / pool.num_blocks
    since = {k: engine.stats[k] - stats0[k] for k in
             ("experts_touched", "expert_slots", "picks_here", "picks_all")}
    if mcfg.num_experts:
        facts.update(
            experts_touched_share=since["experts_touched"] / max(since["expert_slots"], 1),
            picks_here_share=since["picks_here"] / max(since["picks_all"], 1),
            # rows a touched bank multiplies a step: a deployment's are its chips' x that
            rows_per_bank=since["picks_here"] / max(since["experts_touched"], 1))
    eng_ttft = [results[r]["ttft_s"] * 1e3 for r in completed if results[r]["ttft_s"] is not None]
    eng_tpot = [results[r]["tpot_s"] * 1e3 for r in completed if results[r]["tpot_s"] is not None]
    thirds = [[ttft_of[r] for r in completed
               if k * ctx.seconds / 3 <= due_of[r] < (k + 1) * ctx.seconds / 3] for k in range(3)]
    halves = [[results[r]["queue_wait_s"] * 1e3 for r in completed
               if k * ctx.seconds / 2 <= due_of[r] < (k + 1) * ctx.seconds / 2] for k in range(2)]
    prompts = {ID0 + i: r[1] for i, r in enumerate(requests)}
    prompt_len = {r: len(p) for r, p in prompts.items()}
    chunks = {r: -(-n // scfg.prefill_chunk) for r, n in prompt_len.items()}
    os.makedirs(ctx.out_dir, exist_ok=True)
    with open(os.path.join(ctx.out_dir, ctx.cell["name"] + ".requests.jsonl"), "w") as f:
        for r in sorted(due_of):
            f.write(json.dumps(dict(
                id=r, due_s=due_of[r], submitted_s=due_of[r] + lag[r],
                first_token_seen_s=first_seen.get(r), done_s=done_at.get(r),
                prompt_tokens=prompt_len[r], chunks=chunks[r],
                output_tokens=results[r]["output_tokens"] if r in results else None,
                queue_wait_s=results[r]["queue_wait_s"] if r in results else None)) + "\n")
    notes = [
        "queue wait p90 of the requests due in each half of the window (the knee sweep's rule "
        "reads this): " + ", ".join(f"{_p90(x):.1f} ms (n={len(x)})" if x else "-" for x in halves),
        "ttft p50 of the requests due in each third of the window (a queue that grows shows "
        "here): " + ", ".join(f"{np.median(x):.0f} ms (n={len(x)})" if x else "-" for x in thirds),
        f"requests={attempted} completed={len(completed)} shed={len(engine.shed_results)} "
        f"output_tokens={out_tokens} last_done={last_done:.3f}s wall={wall_end:.3f}s "
        f"queue_at_end={'grew' if failed else 'drained'} "
        f"preemptions={engine.sched.n_preempted}",
        f"harness latency a token p50/p90 "
        f"{np.median(list(per_token_of.values())) if completed else None}/"
        f"{facts['latency_per_token_ms_p90']} ms, ttft p50/p90 "
        f"{np.median(ttft) if ttft else None}/{_p90(ttft)} ms, tpot p50/p90 "
        f"{np.median(tpot) if tpot else None}/{_p90(tpot)} ms; engine's own stamps ttft p90 "
        f"{_p90(eng_ttft)} tpot p90 {_p90(eng_tpot)} ms; gen lag p90 "
        f"{_p90([x * 1e3 for x in lag.values()])} ms; occupancy {facts['slot_occupancy']:.3f} "
        f"({facts['rows_live']:.1f} live slots a decode dispatch); "
        + "; ".join(f"{fact} blocks {pool.peak_in_use} of {pool.num_blocks} at the fullest"
                    for fact, pool in pools.items())
        + f"; decode steps {steps}, prefill chunks "
        f"{engine.stats['prefill_chunks'] - stats0['prefill_chunks']}",
    ]
    if mcfg.num_experts:
        notes.append(
            f"held experts touched a decode step and layer "
            f"{mcfg.num_experts * facts['experts_touched_share']:.1f} of {mcfg.num_experts}, "
            f"{100 * facts['picks_here_share']:.2f}% of the picks landed here, "
            f"{facts['rows_per_bank']:.2f} rows a touched bank and step")
    dec = [secs * 1e3 for phase, secs in sink.phases if phase == "decode" and secs is not None]
    if dec:
        # a run that reads slow: is it the dispatch + wait (device, runtime) or the host between?
        notes.append(f"where the wall went: {len(dec)} decode dispatches, median "
                     f"{np.median(dec):.3f} mean {np.mean(dec):.3f} ms each by the engine's clock "
                     f"({sum(dec) / 1e3:.3f} s); {in_step:.3f} s inside engine.step of "
                     f"{wall_end:.3f} s of loop")
    if completed:
        def describe(r):
            return (f"due {due_of[r]:.3f} s, lag {lag[r] * 1e3:.0f} ms, {prompt_len[r]} prompt "
                    f"tokens in {chunks[r]} chunks, {results[r]['output_tokens']} out")

        notes.append(_around_p90("latency a token", per_token_of, describe))
        notes.append(_around_p90("ttft", ttft_of, describe))

    # ---- correct: PICKS completed requests (the longest, the others from the seed) under
    # teacher forcing with the plain reference, logits compared; no leaked block in any pool
    drained = not engine.sched.has_work()
    leaked = {fact: pool.in_use if drained else 0 for fact, pool in pools.items()}
    donate = engine.donate
    engine.close()  # the program's state goes before the reference runs
    del engine
    picks = mellum.pick(completed, prompt_len, results, ctx.seed, w)[:PICKS]
    if w.get("control") == "skip":
        # a builder's sweep of rates or serve settings (never the cell's own file): the
        # reference is not run, and the run cannot come out as correct
        picks = []
    ok = len(picks) > 0 and not any(leaked.values())
    read = dict(tie=0.0, logit_err_mean=0.0, logit_err_max=0.0)
    if picks:
        errs = []
        for r in picks:
            logits = mellum.reference_logits(reference, params, prompts[r],
                                             results[r]["tokens"], pub)
            got = mellum.compare(results[r]["logits"], results[r]["tokens"], logits)
            ok = ok and bool(np.isfinite(logits).all())
            errs.append(got["err"])
            read["tie"] = max(read["tie"], got["tie"])
        errs = np.concatenate(errs)
        read.update(logit_err_mean=float(errs.mean()), logit_err_max=float(errs.max()))
        ok = ok and all(read[k] <= LIMITS[k] for k in LIMITS)
        spread_of_errs = (f"; the errors' median {np.median(errs):.5f}, p90 "
                          f"{np.percentile(errs, 90):.5f}, p99 {np.percentile(errs, 99):.5f}")
    notes.append(
        f"teacher forcing on requests {picks} (the longest first; prompts "
        f"{[prompt_len[r] for r in picks]}, {sum(len(results[r]['tokens']) for r in picks)} "
        f"served tokens): " + "; ".join(f"{k} {read[k]:.5f} (limit {LIMITS[k]})" for k in LIMITS)
        + (spread_of_errs if picks else "")
        + f"; leaked blocks {leaked} (limit 0 each); donation {donate}")
    facts["correct"] = bool(ok)
    facts["notes"] = notes
    return facts
