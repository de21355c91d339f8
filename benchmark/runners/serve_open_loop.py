"""Runner `serve_open_loop`: one `ServeEngine`, load offered on a schedule.

bf16 weights from the seed, one engine from the configuration's `serve`
block, warmed with two short requests. The loop is `ServeEngine.run`'s,
copied, so that the harness owns the clock: a request is submitted when it is
due on the trace clock and timed from when it was due, whatever the engine
was doing then. What a client would see is read by the harness itself, after
each `engine.step` returns: a request's first token is there once that step
has returned (a streaming front end could send it then), and it is done
when the step that retired it has returned. The engine's own per-request
stamps are printed beside these, not reported.

One record a request (id, due, submitted, first token seen, done, prompt
tokens, chunks, output tokens, the engine's queue wait) goes to
`<out_dir>/<cell>.requests.jsonl` after the window, from the stamps the loop
takes anyway, and the requests at and next to the percentile are printed: a
spread between runs can then be traced to the requests that moved. No metric
reads the file.

Facts (`workloads/<cell>.json` maps metric names to these keys):
`latency_per_token_ms_p90` (the 90th percentile over the requests of due time
-> done, over the request's output tokens: what a client waits a token it is
sent, queue, prefill and decode all in), `output_tokens_per_s`, `ttft_ms_p90`,
`tpot_ms_p90`, `queue_wait_ms` (a
list), `slot_occupancy`, `pool_fill` (the most blocks of the KV pool in use
at once, over the blocks it has), `phases` (the engine's `phase` telemetry
events), `decode_interval`, and the common ones.
"""

from __future__ import annotations

import contextlib
import json
import os
import time

import numpy as np

TIE_STEPS = 8  # bf16 steps (2^-8 of the top logit) that still count as a tie:
# chip_smoke.py's band. A served token passes if, under teacher forcing with
# the plain float32 reference, its logit is within that of the top logit.
SPANS = ("wait.arrival", "submit", "engine.step", "observe")
ID0 = 1000  # request ids of the measured set start here (warm-up uses 0, 1)
TRACE_S = 10.0  # a traced run profiles the last 10 s of the window


class _Collect:
    """A telemetry sink that keeps the engine's `phase` events in memory."""

    def __init__(self) -> None:
        self.phases = []

    def emit(self, event: dict) -> None:
        if event.get("kind") == "phase":
            self.phases.append((event.get("phase"), event.get("secs")))

    def close(self) -> None:
        pass


def _p90(values):
    return float(np.percentile(np.asarray(values, np.float64), 90)) if len(values) else None


def _around_p90(what: str, value_of: dict, describe) -> str:
    """The requests whose values `np.percentile(..., 90)` interpolates between,
    and one neighbour on each side, as `id value (what describe says)`."""
    order = sorted(value_of, key=value_of.get)
    rank = 0.9 * (len(order) - 1)
    lo, hi = int(np.floor(rank)), int(np.ceil(rank))
    near = range(max(lo - 1, 0), min(hi + 2, len(order)))
    return (f"{what} p90 = rank {rank:.1f} of {len(order)} (from 0, ascending; {len(order) - 1 - hi} "
            "beyond it): " + "; ".join(
                f"{'*' if lo <= i <= hi else ''}{order[i]} {value_of[order[i]]:.1f} ms "
                f"({describe(order[i])})" for i in near))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import reference
    from picotron_tpu.config import config_from_dict
    from picotron_tpu.generate import place_for_decode
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.serve import ServeEngine
    from picotron_tpu.telemetry import Telemetry

    c, w = ctx.config, ctx.workload
    m = c["model"]
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve") if k in c})
    mcfg, scfg, tp = cfg.model, cfg.serve, cfg.distributed.tp_size
    for k in reference.SIZES:  # the reference reads the file, not the program's presets
        if getattr(mcfg, k) != m[k]:
            raise SystemExit(f"serve_open_loop: model.{k} differs between the file and the program")
    if tp != ctx.chips:
        raise SystemExit(f"serve_open_loop: tp {tp} but the cell has {ctx.chips} chip(s)")

    def weights(key):
        p = init_params(mcfg, key)
        if "initializer_range" in c:
            # the embedding at the published standard deviation: the program draws it
            # unit normal, and through a tied head a model with such an embedding only
            # repeats its input token, which no fault short of a crash can change
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
        pool_fill=engine.pool.peak_in_use / engine.pool.num_blocks,
        phases=list(sink.phases), decode_interval=scfg.decode_interval,
        device=ctx.device_report(),
    )
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
        f"{_p90([x * 1e3 for x in lag.values()])} ms; occupancy {facts['slot_occupancy']:.3f}; "
        f"pool blocks {engine.pool.peak_in_use} of {engine.pool.num_blocks} at the fullest; "
        f"decode steps {steps}, prefill chunks "
        f"{engine.stats['prefill_chunks'] - stats0['prefill_chunks']}",
    ]
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

    # ---- correct: four completed requests (the longest, three from the seed) under teacher forcing
    # with the plain reference, logits compared; no leaked block
    leaked = engine.pool.in_use if not engine.sched.has_work() else 0
    pad_to, donate = engine.max_len, engine.donate
    engine.close()  # the program's state goes before the reference runs
    del engine
    rng = np.random.default_rng(ctx.seed)
    # the longest finished request and three more drawn from the seed
    longest = max(completed, key=lambda r: prompt_len[r] + results[r]["output_tokens"],
                  default=None)
    picks = [longest] * bool(completed) + [
        r for r in (completed[i] for i in rng.permutation(len(completed))) if r != longest][:3]
    worst = 0.0
    ok = len(picks) > 0 and leaked == 0
    logit_of = {}  # the reference's logits a request (host memory), read again by a control
    if picks:
        # one shape for every run (the longest sequence and output the engine
        # admits), so that the reference compiles once and is cached after
        n_rows = max(len(results[r]["tokens"]) for r in results)
        n_rows = -(-n_rows // 128) * 128
        ref = jax.jit(lambda p, ids, rows: reference.logits_at(p, ids, rows, m))

        def ref_logits(p, r):
            ids, n = list(prompts[r]) + results[r]["tokens"], len(results[r]["tokens"])
            rows = np.arange(len(prompts[r]) - 1, len(ids) - 1)
            rows = np.concatenate([rows, np.full(n_rows - n, rows[-1])])
            return np.asarray(ref(p, jnp.asarray(ids + [0] * (pad_to - len(ids)), jnp.int32),
                                  jnp.asarray(rows, jnp.int32)), np.float32)[:n]

        def worst_gap(logits, toks):  # of `toks` below the reference's best, in tie bands
            top = logits.max(axis=-1)
            gap = top - logits[np.arange(len(toks)), np.asarray(toks)]
            return float((gap / (TIE_STEPS * 2.0 ** -8 * np.maximum(np.abs(top), 1.0))).max())

        for r in picks:
            logits = logit_of[r] = ref_logits(params, r)
            worst = max(worst, worst_gap(logits, results[r]["tokens"]))
            ok = ok and bool(np.isfinite(logits).all())
        ok = ok and worst <= 1.0
    notes.append(f"teacher forcing on requests {picks} (the longest first; "
                 f"{sum(len(results[r]['tokens']) for r in picks)} served tokens): worst gap to "
                 f"the top logit {worst:.3f} of the tie band (limit 1.000); leaked blocks "
                 f"{leaked} (limit 0); donation {donate}")
    if w.get("control"):
        # the control in the program's place (tools/knee_sweep.py control=...; no cell of
        # BENCHMARK.json sets it): the reference with lower-precision weights, at each
        # position of the same prompts and tokens; the token it puts first is read like
        # a served one, and `correct` is then the control's verdict
        bits = reference.CONTROLS[w["control"]]
        lower = jax.jit(lambda p: reference.rounded_to(p, bits))(params)
        first = {r: ref_logits(lower, r).argmax(axis=-1) for r in picks}
        ctl = max(worst_gap(logit_of[r], first[r]) for r in picks)

        def not_first(toks):  # tokens other than the reference's first, over the sample
            return sum(int((np.asarray(toks[r]) != logit_of[r].argmax(axis=-1)).sum())
                       for r in picks)

        notes.append(f"control {w['control']} in the program's place: worst gap to the top logit "
                     f"{ctl:.3f} of the tie band (limit 1.000; the program itself read {worst:.3f}); "
                     f"tokens that are not the reference's first: control {not_first(first)}, "
                     f"program {not_first({r: results[r]['tokens'] for r in picks})}")
        ok = ok and ctl <= 1.0
    facts["correct"] = bool(ok)
    facts["notes"] = notes
    return facts
