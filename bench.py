#!/usr/bin/env python
"""Benchmark harness: train-step throughput + MFU on real hardware.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} (plus
auxiliary fields). `vs_baseline` compares achieved MFU against the driver's
north-star bar of 40% MFU (BASELINE.json; the reference reports ~50% MFU for
SmolLM-1.7B on 8xH100 and 38% for Llama-2-7B on 64xH100, ref: README.md:7).

Defaults are sized for a single TPU chip: a depth-reduced SmolLM-1.7B, seq
2048, bf16 compute over fp32 master params. On a multi-chip host it
data-parallelizes over all local chips automatically.

`--sweep` runs the breadth matrix instead (BASELINE.md asks for tokens/s/chip
+ MFU across configurations; DP x TP x PP x CP needs chips this host lacks,
so the single-chip axes are model size / depth, sequence length, and batch):
one JSON line per config, headline config last.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

# (model, layers [None = preset depth], seq, mbs, extra-kwargs) — ordered so
# the headline metric is the LAST line, keeping `python bench.py --sweep |
# tail -1` compatible with the single-run output.
OFFLOAD_24L = dict(grad_acc=64, remat_policy="dots_attn", optimizer_offload=True)
SWEEP = [
    ("SmolLM-360M", None, 2048, 6, {}),   # full-depth model, no reduction
    ("SmolLM-1.7B", 8, 4096, 2, {}),
    ("SmolLM-1.7B", 4, 16384, 1, {}),     # long-context: blocked-KV flash
    ("SmolLM-1.7B", 8, 2048, 5, {}),      # depth-reduced peak-MFU config
    # Llama-2-7B per-layer anchor (the reference's second published
    # figure is 38% at 7B on 64xH100, ref README.md:7): 4 of 32 layers
    # fit one chip with offload; per-layer work (h=4096, 11008-wide MLP,
    # MHA 32:32) is identical to the full model
    ("Llama-2-7B", 4, 4096, 2,
     dict(grad_acc=16, remat_policy="dots_attn", optimizer_offload=True)),
    # MoE on hardware (the reference has no MoE): Mixtral-8x7B's full
    # 1.41 B-param expert bank at 1 layer — GShard capacity dispatch +
    # the streamed update over the [E, H, I] banks (ep=1 on one chip)
    ("Mixtral-8x7B", 1, 2048, 2,
     dict(grad_acc=64, remat_policy="dots", optimizer_offload=True)),
    # FULL depth at seq 4096 — long context + optimizer offload compose
    # (row-group update streaming keeps the embedding/lm_head transients
    # off the peak; PERF.md r4)
    ("SmolLM-1.7B", None, 4096, 1, OFFLOAD_24L),
    # headline: the FULL 24-layer model on one chip — fp32 master + Adam
    # moments live in pinned host memory (optimizer_offload), the fused
    # grad engine accumulates dW in-scan (PERF.md r5), and grad-acc 43
    # amortizes the PCIe round trip (mbs 3 x 43 x 2048 = 264k tokens/step
    # ~= SmolLM's real ~2M-token global batch at the reference's 8-GPU
    # scale; mbs 3 fits because the fused engine never materializes the
    # per-microbatch grad tree). Beats the reference's full-depth ~50%
    # bar (ref: README.md:7).
    ("SmolLM-1.7B", None, 2048, 3, dict(OFFLOAD_24L, grad_acc=43)),
]


def require_backend(force_cpu: bool) -> None:
    """Bring the backend up, or fail with one line. This is a measurement
    path: it runs on a TPU, or on the CPU only when `--cpu` asked for a
    structural smoke run. `JAX_PLATFORMS=cpu` in the environment is not
    consent, and neither is JAX falling back to the CPU because no
    accelerator came up — both would print a rate under a device metric's
    name."""
    from picotron_tpu.utils import require_platform, setup_compile_cache

    if force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    setup_compile_cache()
    dev = require_platform("bench", allow_cpu=force_cpu)
    if dev.platform != "tpu" and not force_cpu:
        raise SystemExit(
            f"bench: platform {dev.platform!r} is not a TPU; the peak table "
            f"and the kernels are TPU-only (rerun with --cpu for a "
            f"structural smoke run)")


def peak_flops_or_none():
    """Per-chip peak FLOP/s of the TPU under the bench; None on the `--cpu`
    structural path, where a fraction of peak is not computed."""
    from picotron_tpu.utils import device_peak_flops

    dev = jax.devices()[0]
    return device_peak_flops(dev) if dev.platform == "tpu" else None


def bench_config(model: str, layers, seq: int, mbs: int, *,
                 grad_acc: int = 1, remat: bool = True,
                 remat_policy: str = "dots",
                 adam_moments_dtype: str = "bfloat16", ce_chunk: int = 0,
                 optimizer_offload: bool = False, n_chips: int = None):
    """The exact Config a bench invocation trains — shared by the timed run
    and `--shardcheck` so the static audit can never drift from what the
    benchmark measures."""
    from picotron_tpu.config import (
        Config, DistributedConfig, ModelConfig, TrainingConfig, resolve_preset,
    )

    n_chips = n_chips if n_chips is not None else len(jax.devices())
    preset = resolve_preset(model)
    preset["max_position_embeddings"] = max(
        preset.get("max_position_embeddings", seq), seq
    )
    if layers:
        preset["num_hidden_layers"] = layers
    cfg = Config(
        distributed=DistributedConfig(dp_size=n_chips),
        model=ModelConfig(name=model, **preset),
        training=TrainingConfig(
            seq_length=seq,
            micro_batch_size=mbs,
            gradient_accumulation_steps=grad_acc,
            remat=remat,
            remat_policy=remat_policy,
            adam_moments_dtype=adam_moments_dtype,
            ce_chunk_size=ce_chunk,
            optimizer_offload=optimizer_offload,
        ),
    )
    cfg.validate()
    return cfg


def run_one(model: str, layers, seq: int, mbs: int, *, grad_acc: int = 1,
            steps: int = 8, warmup: int = 2, remat: bool = True,
            remat_policy: str = "dots", adam_moments_dtype: str = "bfloat16",
            ce_chunk: int = 0, optimizer_offload: bool = False,
            profile: str | None = None,
            profile_steps: int | None = None,
            telemetry: str | None = None,
            trace: str | None = None) -> dict:
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.parallel.api import init_sharded_state, make_train_step
    from picotron_tpu.telemetry import Histogram, JsonlSink
    from picotron_tpu.telemetry.flightdeck import SpanTracer
    from picotron_tpu.utils import flops_per_token, mfu

    n_chips = len(jax.devices())
    cfg = bench_config(model, layers, seq, mbs, grad_acc=grad_acc,
                       remat=remat, remat_policy=remat_policy,
                       adam_moments_dtype=adam_moments_dtype,
                       ce_chunk=ce_chunk,
                       optimizer_offload=optimizer_offload,
                       n_chips=n_chips)

    menv = MeshEnv.from_config(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    step = make_train_step(cfg, menv)

    b_global = mbs * n_chips
    toks = jax.random.randint(
        jax.random.key(1), (grad_acc, b_global, seq + 1),
        0, cfg.model.vocab_size,
    )
    sharding = menv.batch_sharding()
    batch = (jax.device_put(toks[..., :-1], sharding),
             jax.device_put(toks[..., 1:], sharding))

    for _ in range(max(warmup, 1)):  # >=1 so compile stays out of the timing
        state, metrics = step(state, batch)
    float(metrics["loss"])  # drain the warmup chain before the clock starts

    # Time N chained steps, fetching ONLY the final loss. The data dependency
    # (loss_N needs state_{N-1} needs ... state_0) forces every step to have
    # executed before the fetch returns, with no host round-trip per step.
    # (On this installation jax.block_until_ready(metrics) ends the same
    # donated chain at the same instant — 0.9066 s vs 0.9073 s for 10 steps,
    # nothing left running after either; PERF.md "Bring-up". The fetch stays
    # because the loss is reported anyway.)
    # --profile-steps N caps the capture window to the LAST N timed steps:
    # a full-window capture at the mbs-3 headline OOMs the chip (the fused
    # scan's in-flight slices + xprof's device trace buffers, PERF.md r5);
    # a single-step window is the documented capture config that fits.
    cap = steps if profile_steps is None else min(profile_steps, steps)
    if profile and cap >= steps:
        jax.profiler.start_trace(profile)
    t0 = time.perf_counter()
    for i in range(steps):
        if profile and cap < steps and i == steps - cap:
            float(metrics["loss"])  # drain the chain before the window
            jax.profiler.start_trace(profile)
        state, metrics = step(state, batch)
    final_loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    if profile:
        jax.profiler.stop_trace()

    tokens_per_step = b_global * grad_acc * seq
    tokens_per_sec = tokens_per_step * steps / dt
    peak = peak_flops_or_none()
    mfu_frac = (mfu(tokens_per_sec, cfg.model, seq, n_chips, peak)
                if peak else None)

    # Per-step distribution: the chained timing above is the headline mean
    # (no per-step host round-trip), but a mean hides stragglers — a second
    # pass times each step individually with a value fetch and reports
    # p50/p95 from the histogram registry. The per-step sync adds the
    # host<->device transport latency to every sample, so p50 can sit a
    # touch above the chained mean; the p95/p50 RATIO is the straggler
    # signal. `telemetry` (``--telemetry FILE``) additionally writes every
    # sample to the JSONL sink (one bench_step event per step +
    # bench_summary), the same stream tools/telemetry_report.py reads.
    hist = Histogram()
    sink = JsonlSink(telemetry) if telemetry else None
    # ``--trace FILE``: record one flightdeck span per timed step and
    # export the Chrome-trace JSON; the span-recording cost rides inside
    # these samples, so p50 here vs the chained mean above IS the
    # enabled-path overhead (plus the per-span microbench below, which
    # measures the tracer call in isolation).
    tracer = SpanTracer() if trace else None
    for i in range(steps):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        float(metrics["loss"])  # value fetch: the step must have executed
        dt_i = time.perf_counter() - t0
        hist.observe(dt_i)
        if tracer is not None:
            tracer.complete("step", start_s=t0, dur_s=dt_i, i=i)
        if sink is not None:
            sink.emit({"ts": time.time(), "kind": "bench_step", "i": i,
                       "secs": round(dt_i, 6),
                       "tokens_per_sec": round(tokens_per_step / dt_i, 1)})

    layer_tag = f"-{cfg.model.num_hidden_layers}L"
    row = {
        "metric": f"mfu_{model.split('/')[-1]}{layer_tag}_seq{seq}",
        # None = not computed: no peak for this device (the --cpu path)
        "value": round(mfu_frac, 4) if peak else None,
        "unit": "fraction_of_peak",
        "vs_baseline": round(mfu_frac / 0.40, 4) if peak else None,
        "tokens_per_sec_per_chip": round(tokens_per_sec / n_chips, 1),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "n_chips": n_chips,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "peak_flops_per_chip": peak,
        "flops_per_token": flops_per_token(cfg.model, seq),
        "loss": final_loss,
        # NOTE: the bench feeds the SAME random batch every step (pure perf
        # harness) — `loss` trends toward memorization and says nothing
        # about model quality; see tests/test_train_e2e.py for real training.
        "loss_is_fixed_batch_memorization": True,
        "step_time_ms_mean": round(dt / steps * 1e3, 2),
        "step_time_ms_p50": round(hist.p50 * 1e3, 2),
        "step_time_ms_p95": round(hist.p95 * 1e3, 2),
    }
    if tracer is not None:
        # Per-span cost measured in isolation (a train step records a
        # handful of spans: 3-4 phases + any MPMD ticks): this is the
        # number PERF.md documents as the enabled-path overhead.
        probe = SpanTracer()
        n_probe = 10_000
        t0 = time.perf_counter()
        for i in range(n_probe):
            probe.complete("probe", start_s=t0, dur_s=1e-6, i=i)
        span_cost_us = (time.perf_counter() - t0) / n_probe * 1e6
        tracer.export(trace)
        row["trace"] = trace
        row["trace_events"] = len(tracer)
        row["trace_span_cost_us"] = round(span_cost_us, 3)
    if sink is not None:
        sink.emit({"ts": time.time(), "kind": "bench_summary", **row})
        sink.close()
    return row


def run_decode(model: str, layers, prompt_len: int, max_new: int,
               batch: int, steps: int = 3, tp: int = 1) -> dict:
    """Generation throughput on the chip (the reference is training-only,
    ref: README.md:2 — this is the beyond-parity feature's number): one
    JSON line with steady-state decode tokens/s as the headline value plus
    the prefill rate. The prefill/decode split comes from differencing a
    max_new=1 run (prefill + one sample) against the full run — the two
    phases live inside one jitted program, so there is no boundary to
    time directly.

    tp > 1 re-places the params into the training TP shardings over tp
    chips (`place_for_decode`; pure GSPMD decode, XLA shards the KV cache
    and inserts the collectives) — the 7B-scale decode arrangement. The
    Llama-2-7B per-layer decode anchor is the 4L proxy:
    `bench.py --decode --model Llama-2-7B --layers 4` (single chip bf16;
    add --tp 2 on a multi-chip host for the sharded path)."""
    import numpy as np

    from picotron_tpu.config import ModelConfig, resolve_preset
    from picotron_tpu.generate import generate, place_for_decode
    from picotron_tpu.models.llama import init_params

    preset = resolve_preset(model)
    preset["max_position_embeddings"] = max(
        preset.get("max_position_embeddings", 0), prompt_len + max_new)
    if layers:
        preset["num_hidden_layers"] = layers
    mcfg = ModelConfig(name=model, **preset)
    params = jax.jit(
        lambda k: jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                               init_params(mcfg, k)))(jax.random.key(0))
    params = place_for_decode(params, mcfg, tp=tp)
    prompts = jax.random.randint(jax.random.key(1), (batch, prompt_len),
                                 0, mcfg.vocab_size)

    def timed(n_new: int) -> float:
        np.asarray(generate(params, mcfg, prompts, n_new))  # compile
        best = float("inf")
        for _ in range(steps):
            t0 = time.perf_counter()
            np.asarray(generate(params, mcfg, prompts, n_new))
            best = min(best, time.perf_counter() - t0)
        return best

    t_prefill = timed(1)
    t_full = timed(max_new)
    if t_full <= t_prefill:
        # Timing jitter on a loaded host can make the differenced decode
        # time <= 0 (tiny models, max_new close to 2). One re-measure
        # absorbs a transient stall; a repeat means the measurement is
        # genuinely degenerate and must fail loudly — an inf/negative
        # decode tok/s must never be recorded (ADVICE r5).
        t_prefill = timed(1)
        t_full = timed(max_new)
    if t_full <= t_prefill:
        raise RuntimeError(
            f"decode timing degenerate: full run ({t_full * 1e3:.3f} ms for "
            f"{max_new} tokens) was not slower than the prefill-only run "
            f"({t_prefill * 1e3:.3f} ms) — increase --max-new-tokens or "
            f"re-run on an idle host; refusing to report a nonsensical "
            f"decode rate")
    dt = max(t_full - t_prefill, 1e-9)
    decode_tps = batch * (max_new - 1) / dt
    tp_tag = f"-tp{tp}" if tp > 1 else ""
    return {
        "metric": f"decode_{model.split('/')[-1]}"
                  f"-{mcfg.num_hidden_layers}L{tp_tag}",
        "tp": tp,
        "value": round(decode_tps, 1),
        "unit": "decode_tokens_per_sec",
        "prefill_tokens_per_sec": round(batch * prompt_len / t_prefill, 1),
        "batch": batch,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new,
        "decode_ms_per_token_per_seq": round(dt / (max_new - 1) * 1e3, 2),
        "device_kind": jax.devices()[0].device_kind,
    }


def make_serve_trace(n_requests: int, rate: float, prompt_len: int,
                     max_new: int, vocab: int, seed: int = 0) -> list:
    """Synthetic arrival trace: mixed prompt lengths in
    [prompt_len/8, prompt_len], mixed output budgets in
    [max_new/8, max_new] (wide spread — real traffic is heavy-tailed,
    and the spread is precisely what continuous batching monetizes),
    Poisson arrivals at `rate` req/s (rate <= 0 = everything arrives at
    t=0 — the saturation/throughput trace; a finite rate exercises
    queue_wait under load). Deterministic per seed, so serve and
    baseline always score the same workload."""
    import numpy as np

    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    for _ in range(n_requests):
        plen = int(rng.integers(max(prompt_len // 8, 1), prompt_len + 1))
        olen = int(rng.integers(max(max_new // 8, 1), max_new + 1))
        prompt = rng.integers(0, vocab, size=plen).tolist()
        if rate > 0:
            t += float(rng.exponential(1.0 / rate))
        out.append((prompt, olen, t))
    return out


# Printed (stderr) and embedded in the JSON whenever a wall-clock ratio
# is reported from a CPU host: the 0.85-1.19 spread PERF.md r7 recorded
# was host-load noise being read as a regression/win.
_WALL_NOTE = ("wall-clock ratios on a shared CPU host are load-noisy "
              "(observed 0.85-1.19x swings on identical configs); the "
              "deterministic decode_slot_steps ratio is the headline — "
              "treat wall numbers as median-of-repeats sanity only, and "
              "use the PERF.md on-TPU protocol for real speedups")


def run_serve(model: str, layers, *, slots: int, block_size: int,
              num_blocks: int, prefill_chunk: int, prompt_len: int,
              max_new: int, n_requests: int, rate: float, tp: int = 1,
              decode_interval: int = 4, seed: int = 0,
              repeats: int = 3,
              telemetry: str | None = None) -> dict:
    """Continuous batching + paged KV cache (picotron_tpu/serve) against
    the batch-static `generate` baseline, on the same synthetic arrival
    trace. One JSON line. The HEADLINE value is the deterministic
    structural ratio `static_decode_slot_steps / decode_slot_steps` —
    decode slot-steps each side burns (the engine stops paying for
    retired/ragged sequences; the static sampler decodes the trace max
    for every batch), identical on every host. Wall-clock tokens/s and
    `vs_static` are reported as the MEDIAN over `repeats` timed runs per
    side (both sides compile-warm: a 2-request mini-trace warms the
    engine's two programs, one throwaway generate call warms the
    baseline's) with the per-run walls kept in the row — on a shared CPU
    they are load-noisy (see `wall_note`), so they sanity-check the
    structural ratio rather than headline it. rate > 0 makes the engine
    wall include arrival gaps; use the default rate=0 saturation trace
    for vs_static anchors."""
    import statistics

    import numpy as np

    from picotron_tpu.config import ModelConfig, ServeConfig, resolve_preset
    from picotron_tpu.generate import generate, place_for_decode
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.serve import ServeEngine
    from picotron_tpu.telemetry import JsonlSink, Telemetry

    cap = prompt_len + max_new
    preset = resolve_preset(model)
    preset["max_position_embeddings"] = max(
        preset.get("max_position_embeddings", 0), cap)
    if layers:
        preset["num_hidden_layers"] = layers
    mcfg = ModelConfig(name=model, **preset)
    params = jax.jit(
        lambda k: jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                               init_params(mcfg, k)))(jax.random.key(0))
    if tp > 1:
        # tp=1 placement is semantically a no-op but COMMITS the tree,
        # which pins every jit variant to explicit shardings — skipping
        # it keeps the single-chip path on the fast uncommitted dispatch
        params = place_for_decode(params, mcfg, tp=tp)
    scfg = ServeConfig(decode_slots=slots, block_size=block_size,
                       num_blocks=num_blocks, prefill_chunk=prefill_chunk,
                       max_model_len=cap, decode_interval=decode_interval)
    trace = make_serve_trace(n_requests, rate, prompt_len, max_new,
                             mcfg.vocab_size, seed)
    useful_tokens = sum(olen for _, olen, _ in trace)

    # compile-warm both programs (decode shape = slot count, prefill
    # shape = chunk size — both identical to the real trace's)
    warm = ServeEngine(params, mcfg, scfg)
    warm.run([(trace[0][0], 2), (trace[1 % len(trace)][0], 2)])
    warm.close()

    repeats = max(repeats, 1)
    serve_walls, summary = [], None
    for rep in range(repeats):
        # telemetry on the first repeat only: one stream per bench row
        tel = (Telemetry(sinks=[JsonlSink(telemetry)])
               if telemetry and rep == 0 else None)
        eng = ServeEngine(params, mcfg, scfg, telemetry=tel)
        t0 = time.perf_counter()
        eng.run(trace)
        serve_walls.append(time.perf_counter() - t0)
        summary = summary or eng.summary  # identical across repeats
        eng.close()
        if tel is not None:
            tel.close()
    serve_wall = statistics.median(serve_walls)

    # batch-static baseline: ceil(N/slots) generate() batches in arrival
    # order, every prompt right-padded to the trace max and every batch
    # decoding the trace-max budget (the shapes a static offline sampler
    # is stuck with) — one warm-up call, then timed end to end
    p_max = max(len(p) for p, _, _ in trace)
    o_max = max(olen for _, olen, _ in trace)
    groups = [trace[i:i + slots] for i in range(0, len(trace), slots)]

    def static_batch(group):
        ids = np.zeros((slots, p_max), np.int32)
        for j, (p, _, _) in enumerate(group):
            ids[j, :len(p)] = p
        return jnp.asarray(ids)

    np.asarray(generate(params, mcfg, static_batch(groups[0]), o_max))
    static_walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for g in groups:
            np.asarray(generate(params, mcfg, static_batch(g), o_max))
        static_walls.append(time.perf_counter() - t0)
    static_wall = statistics.median(static_walls)

    serve_tps = useful_tokens / serve_wall
    static_tps = useful_tokens / static_wall
    slot_steps = summary["decode_steps"] * decode_interval
    static_slot_steps = len(groups) * o_max
    print(f"# {_WALL_NOTE}", file=sys.stderr)
    tp_tag = f"-tp{tp}" if tp > 1 else ""
    ms = lambda v: round(v * 1e3, 2) if v is not None else None  # noqa: E731
    return {
        "metric": f"serve_{model.split('/')[-1]}"
                  f"-{mcfg.num_hidden_layers}L{tp_tag}",
        # headline = the structural decode-work ratio, deterministic on
        # any host; > 1 means continuous batching did strictly less
        # slot-step work than the batch-static sampler on this trace
        "value": round(static_slot_steps / max(slot_steps, 1), 3),
        "unit": "static_over_serve_decode_slot_steps",
        "serve_tokens_per_sec": round(serve_tps, 1),
        "vs_static": round(serve_tps / static_tps, 3),
        "static_tokens_per_sec": round(static_tps, 1),
        "wall_repeats": repeats,
        "serve_walls_s": [round(w, 4) for w in serve_walls],
        "static_walls_s": [round(w, 4) for w in static_walls],
        "wall_note": _WALL_NOTE,
        "requests": n_requests,
        "arrival_rate": rate,
        "useful_tokens": useful_tokens,
        "prompt_len_max": p_max,
        "max_new_max": o_max,
        "slots": slots,
        "block_size": block_size,
        "num_blocks": summary["num_blocks"],
        "prefill_chunk": prefill_chunk,
        "tp": tp,
        "ttft_p50_ms": ms(summary["ttft_p50_s"]),
        "ttft_p95_ms": ms(summary["ttft_p95_s"]),
        "token_latency_p50_ms": ms(summary["token_latency_p50_s"]),
        "token_latency_p95_ms": ms(summary["token_latency_p95_s"]),
        "queue_wait_p50_ms": ms(summary["queue_wait_p50_s"]),
        "queue_wait_p95_ms": ms(summary["queue_wait_p95_s"]),
        "slot_occupancy": summary["slot_occupancy"],
        "pool_peak_utilization": summary["pool_peak_utilization"],
        "preemptions": summary["preemptions"],
        "decode_steps": summary["decode_steps"],
        "decode_compiles": summary["decode_compiles"],
        # the raw slot-step counts behind the headline ratio — continuous
        # batching must be strictly lower on any ragged trace
        "decode_slot_steps": slot_steps,
        "static_decode_slot_steps": static_slot_steps,
        "device_kind": jax.devices()[0].device_kind,
    }


def run_serve_fleet(model: str, layers, *, fleet: int, slots: int,
                    block_size: int, num_blocks: int, prefill_chunk: int,
                    prompt_len: int, max_new: int, n_requests: int,
                    rate: float, decode_interval: int = 4, seed: int = 0,
                    temperature: float = 0.0, deadline_ms: float = 0.0,
                    chaos_spec: str | None = None, tick_s: float = 0.001,
                    telemetry: str | None = None) -> dict:
    """Fleet serving (picotron_tpu/serve/fleet): N engine replicas behind
    one queue on a synthetic arrival trace, with optional serve-side
    chaos (engine_dead@REQ / decode_hang@REQ~SECS / shed_storm@REQ) and
    deadline load shedding. One JSON line, built for the recovery
    scenarios in tools/chaos.py: per-request sha1 token digests (the
    failover-parity oracle compares them across fleet sizes and fault
    legs), the shed id set (deterministic on the virtual trace clock),
    and the survivor-pool leak count. Everything except wall seconds is
    structural — identical on any host."""
    import hashlib

    from picotron_tpu.config import ModelConfig, ServeConfig, resolve_preset
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.resilience import chaos as chaos_mod
    from picotron_tpu.serve import FleetSupervisor
    from picotron_tpu.telemetry import JsonlSink, Telemetry

    cap = prompt_len + max_new
    preset = resolve_preset(model)
    preset["max_position_embeddings"] = max(
        preset.get("max_position_embeddings", 0), cap)
    if layers:
        preset["num_hidden_layers"] = layers
    mcfg = ModelConfig(name=model, **preset)
    params = jax.jit(
        lambda k: jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                               init_params(mcfg, k)))(jax.random.key(0))
    scfg = ServeConfig(decode_slots=slots, block_size=block_size,
                       num_blocks=num_blocks, prefill_chunk=prefill_chunk,
                       max_model_len=cap, decode_interval=decode_interval,
                       fleet_size=fleet, deadline_ms=deadline_ms)
    trace = make_serve_trace(n_requests, rate, prompt_len, max_new,
                             mcfg.vocab_size, seed)

    tel = None
    if telemetry:
        from picotron_tpu.telemetry.flightdeck import FlightRecorder

        tel = Telemetry(sinks=[JsonlSink(telemetry)])
        tel.flight = FlightRecorder(
            os.path.dirname(os.path.abspath(telemetry)), max_steps=8)
    if chaos_spec:
        chaos_mod.install(chaos_spec)
    try:
        fl = FleetSupervisor(params, mcfg, scfg, temperature=temperature,
                             seed=seed, telemetry=tel, tick_s=tick_s)
        t0 = time.perf_counter()
        results = fl.run(trace)
        wall = time.perf_counter() - t0
    finally:
        if chaos_spec:
            chaos_mod.install("")  # disarm: one spec, one run
    summary = fl.summary
    shed = fl.all_shed
    digest = lambda toks: hashlib.sha1(  # noqa: E731
        " ".join(map(str, toks)).encode()).hexdigest()[:16]
    row = {
        "metric": f"serve_fleet{fleet}_{model.split('/')[-1]}"
                  f"-{mcfg.num_hidden_layers}L",
        # headline: every admitted request finished — the recovery
        # scenarios assert completed + shed == submitted even with an
        # engine killed mid-burst
        "value": len(results),
        "unit": "completed_requests",
        "fleet": fleet,
        "requests": n_requests,
        "completed": len(results),
        "shed": len(shed),
        "shed_ids": sorted(r["id"] for r in shed),
        "redispatched": summary["redispatched"],
        "engines_dead": summary["engines_dead"],
        "drains": summary["drains"],
        "leaked_blocks": summary["leaked_blocks"],
        "output_tokens": summary["output_tokens"],
        "wall_s": round(wall, 4),
        "wall_note": _WALL_NOTE,
        "arrival_rate": rate,
        "temperature": temperature,
        "deadline_ms": deadline_ms,
        "chaos": chaos_spec or "",
        "tick_s": tick_s,
        "slots": slots,
        "decode_steps": summary["decode_steps"],
        "decode_compiles": summary["decode_compiles"],
        "preemptions": summary["preemptions"],
        "ttft_p50_ms": (round(summary["ttft_p50_s"] * 1e3, 2)
                        if summary["ttft_p50_s"] is not None else None),
        "ttft_p95_ms": (round(summary["ttft_p95_s"] * 1e3, 2)
                        if summary["ttft_p95_s"] is not None else None),
        "queue_wait_p50_ms": (
            round(summary["queue_wait_p50_s"] * 1e3, 2)
            if summary["queue_wait_p50_s"] is not None else None),
        "queue_wait_p95_ms": (
            round(summary["queue_wait_p95_s"] * 1e3, 2)
            if summary["queue_wait_p95_s"] is not None else None),
        "per_engine_requests": [pe["requests"]
                                for pe in summary["per_engine"]],
        # the parity pin: same trace + same seed must produce the same
        # digest per id regardless of fleet size, failover, or shedding
        # of OTHER requests
        "request_digests": {str(r["id"]): digest(r["tokens"])
                            for r in results},
        "device_kind": jax.devices()[0].device_kind,
    }
    fl.close()
    if tel is not None:
        tel.close()
    return row


def run_pp_tick_sweep(model: str, layers, seq: int, mbs: int, *,
                      pp: int = 4, n_micros=(2, 4, 8, 16), steps: int = 4,
                      warmup: int = 1, interleave: int = 2) -> dict:
    """SPMD-vs-MPMD pipeline tick cost: time the train step at several
    microbatch counts and fit step_ms = slope * n_micro + intercept per
    executor — the PERF.md r4 instrument (whose hand-fit put the SPMD
    fill/drain intercept at ~454 ms for pp=4 on simulated devices),
    automated. The slope is the per-tick steady-state cost; the intercept
    is the fill/drain + fixed overhead — the number the MPMD executor
    exists to shrink, because the SPMD lockstep scan pays ~a full traced
    tick per idle schedule slot while the host-side walker pays ~nothing.
    One JSON line per (executor, n_micro) sample, then a summary line
    with both fits, the intercept drop, and the schedule-table tick
    accounting (where interleaved-v2 must beat 1f1b at pp=4)."""
    from picotron_tpu.config import (
        Config, DistributedConfig, ModelConfig, PipelineConfig,
        TrainingConfig, resolve_preset,
    )
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.parallel.api import init_sharded_state, make_train_step
    from picotron_tpu.parallel.mpmd import schedule_stats

    n_chips = len(jax.devices())
    if n_chips % pp != 0 or n_chips < pp:
        raise SystemExit(f"--pp-tick-sweep: {n_chips} device(s) not "
                         f"divisible into pp={pp} stages")
    dp = n_chips // pp
    preset = resolve_preset(model)
    preset["max_position_embeddings"] = max(
        preset.get("max_position_embeddings", seq), seq)
    if layers:
        preset["num_hidden_layers"] = layers
    depth = preset["num_hidden_layers"]
    metric = f"pp_tick_sweep_{model.split('/')[-1]}-{depth}L_pp{pp}"

    def timed(executor: str, n_micro: int) -> float:
        cfg = Config(
            distributed=DistributedConfig(dp_size=dp, pp_size=pp),
            model=ModelConfig(name=model, **preset),
            training=TrainingConfig(seq_length=seq, micro_batch_size=mbs,
                                    gradient_accumulation_steps=n_micro),
            pipeline=(PipelineConfig(executor="mpmd")
                      if executor == "mpmd" else PipelineConfig()),
        )
        cfg.validate()
        menv = MeshEnv.from_config(cfg)
        state = init_sharded_state(cfg, menv, jax.random.key(0))
        step = make_train_step(cfg, menv)
        toks = jax.random.randint(jax.random.key(1),
                                  (n_micro, mbs * dp, seq + 1),
                                  0, cfg.model.vocab_size)
        sharding = menv.batch_sharding()
        batch = (jax.device_put(toks[..., :-1], sharding),
                 jax.device_put(toks[..., 1:], sharding))
        for _ in range(max(warmup, 1)):
            state, metrics = step(state, batch)
        float(metrics["loss"])  # drain the warmup chain
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch)
        float(metrics["loss"])  # value fetch: every step must have run
        return (time.perf_counter() - t0) / steps * 1e3

    fits = {}
    for executor in ("spmd", "mpmd"):
        xs, ys = [], []
        for n_micro in n_micros:
            step_ms = timed(executor, n_micro)
            xs.append(float(n_micro))
            ys.append(step_ms)
            print(json.dumps({"metric": metric, "executor": executor,
                              "n_micro": n_micro,
                              "step_time_ms": round(step_ms, 2)}),
                  flush=True)
        n = len(xs)
        mx, my = sum(xs) / n, sum(ys) / n
        var = sum((x - mx) ** 2 for x in xs) or 1.0
        slope = sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / var
        fits[executor] = {
            "ms_per_microbatch": round(slope, 2),
            "intercept_ms": round(my - slope * mx, 2),
        }

    # Schedule-table accounting (host arithmetic, parallel/mpmd.py): the
    # idle units each schedule implies at the sweep's largest n_micro —
    # in FULL units (1 unit = one stage's F+B), executor-independent.
    nm = max(n_micros)
    acct = {k: schedule_stats(k, nm, pp) for k in ("spmd", "1f1b", "gpipe")}
    slots = -(-depth // pp)  # ceil
    if interleave >= 2 and slots % interleave == 0:
        acct[f"interleaved-v{interleave}"] = schedule_stats(
            "interleaved", nm, pp, interleave)
    acct["zb"] = schedule_stats("zb", nm, pp)

    drop_ms = fits["spmd"]["intercept_ms"] - fits["mpmd"]["intercept_ms"]
    base = fits["spmd"]["intercept_ms"]
    row = {
        "metric": metric,
        "value": round(drop_ms / base, 4) if base else None,
        "unit": "mpmd_intercept_drop_fraction",
        "intercept_drop_ms": round(drop_ms, 2),
        "spmd": fits["spmd"],
        "mpmd": fits["mpmd"],
        "n_micros": list(n_micros),
        "pp": pp, "dp": dp, "mbs": mbs, "seq": seq,
        "schedule_bubble_units": {
            k: round(v["bubble_units"], 3) for k, v in acct.items()},
        "device_kind": jax.devices()[0].device_kind,
    }
    print(json.dumps(row), flush=True)
    return row


def run_cp_flavor_sweep(model: str, layers, seqs, mbs: int, *,
                        cp: int = 0, steps: int = 3,
                        warmup: int = 1) -> list:
    """Ring vs Ulysses vs mesh context parallelism on the same train step:
    one JSON row per (seq, cp_flavor) with the measured step time and the
    ICI cost model's prediction for this device kind — the instrument for
    the PERF.md Round 13 question (where does the 2D mesh schedule's
    crossover land on real ICI, and does the topology model's predicted
    ordering hold?). A flavor the head counts cannot schedule (Ulysses
    needs heads % cp == 0) reports `infeasible` instead of vanishing —
    on GQA models that asymmetry IS the mesh flavor's reason to exist.

    On TPU: `python bench.py --cp-flavor-sweep --model Llama-3.1-8B
    --seqs 8192 16384 32768 65536`. CPU runs (`--cpu`, 8 simulated
    devices, debug-tiny) are structural anchors only — they prove the
    three flavors lower and step, not how fast.
    """
    from picotron_tpu.analysis.cost_model import CostModel
    from picotron_tpu.config import (
        Config, DistributedConfig, ModelConfig, TrainingConfig,
        resolve_preset, resolved_cp_mesh,
    )
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.parallel.api import init_sharded_state, make_train_step

    n_chips = len(jax.devices())
    cp = cp or n_chips
    if n_chips % cp or cp < 2:
        raise SystemExit(f"--cp-flavor-sweep: {n_chips} device(s) not "
                         f"divisible into cp={cp} sequence shards")
    dp = n_chips // cp
    preset = resolve_preset(model)
    max_seq = max(seqs)
    preset["max_position_embeddings"] = max(
        preset.get("max_position_embeddings", max_seq), max_seq)
    if layers:
        preset["num_hidden_layers"] = layers
    cost_model = CostModel(jax.devices()[0].device_kind)
    rows = []
    for seq in seqs:
        for flavor in ("ring", "ulysses", "mesh"):
            row = {"metric": f"cp_flavor_{model.split('/')[-1]}_cp{cp}",
                   "cp_flavor": flavor, "cp": cp, "dp": dp, "seq": seq,
                   "mbs": mbs,
                   "device_kind": jax.devices()[0].device_kind,
                   "is_tpu": jax.devices()[0].platform == "tpu"}
            cfg = Config(
                distributed=DistributedConfig(dp_size=dp, cp_size=cp,
                                              cp_flavor=flavor),
                model=ModelConfig(name=model, **preset),
                training=TrainingConfig(seq_length=seq,
                                        micro_batch_size=mbs),
            )
            try:
                cfg.validate()
            except ValueError as e:
                row["infeasible"] = str(e)[:160]
                rows.append(row)
                print(json.dumps(row), flush=True)
                continue
            if flavor == "mesh":
                cp_x, cp_y = resolved_cp_mesh(cfg)
                row["cp_mesh"] = f"{cp_x}x{cp_y}"
            row["predicted_step_ms"] = round(
                cost_model.predict(cfg).total_s * 1e3, 2)
            menv = MeshEnv.from_config(cfg)
            state = init_sharded_state(cfg, menv, jax.random.key(0))
            step = make_train_step(cfg, menv)
            toks = jax.random.randint(jax.random.key(1),
                                      (1, mbs * dp, seq + 1),
                                      0, cfg.model.vocab_size)
            sharding = menv.batch_sharding()
            batch = (jax.device_put(toks[..., :-1], sharding),
                     jax.device_put(toks[..., 1:], sharding))
            for _ in range(max(warmup, 1)):
                state, metrics = step(state, batch)
            float(metrics["loss"])  # drain the warmup chain
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step(state, batch)
            float(metrics["loss"])  # value fetch: every step must have run
            step_ms = (time.perf_counter() - t0) / steps * 1e3
            tokens_per_step = mbs * dp * seq
            row.update({
                "step_time_ms": round(step_ms, 2),
                "tokens_per_sec": round(tokens_per_step / step_ms * 1e3, 1),
                "loss": float(metrics["loss"]),
            })
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def run_bwd_grid_sweep(model: str, seq: int, batch: int, steps: int = 5,
                       blocks=None) -> list:
    """Block-size sweep of the flash attention KERNEL PAIR (fwd, fwd+bwd)
    at long sequence — the instrument for VERDICT r5 next #8: at seq 16k
    the bwd pair runs ~2.3x the fwd wall but the pair sits below the
    causal roofline, and PERF.md r5 attributes the excess to *pipeline
    overhead* (program count x per-program ramp), which is exactly what
    the (block_q, block_k) grid shape controls. One JSON line per combo
    with the pair's achieved TFLOP/s and fraction of the device's causal
    attention roofline; combos whose fp32 [BQ, BK] score block exceeds
    VMEM report their compile error instead of silently vanishing.

    Run on hardware: `python bench.py --bwd-grid-sweep --seq 16384`.
    The jnp fallback makes CPU runs structural smoke only.
    """
    from picotron_tpu.config import resolve_preset
    from picotron_tpu.ops.flash_attention import flash_attention

    preset = resolve_preset(model)
    hq = preset["num_attention_heads"]
    hkv = preset["num_key_value_heads"]
    d = preset["hidden_size"] // hq
    on_tpu = jax.devices()[0].platform == "tpu"
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (batch, seq, hq, d), dt)
    k = jax.random.normal(ks[1], (batch, seq, hkv, d), dt)
    v = jax.random.normal(ks[2], (batch, seq, hkv, d), dt)
    do = jax.random.normal(ks[3], (batch, seq, hq, d), dt)

    # causal attention flops: qk + pv matmuls over the lower triangle
    # (2 * 2 * B*H*S^2*D / 2); backward re-derives s/p and runs the three
    # grad matmuls -> 2.5x the forward's matmul flops
    fwd_flops = 2 * batch * hq * seq * seq * d
    pair_flops = fwd_flops * 3.5
    peak = peak_flops_or_none()

    def timed(fn, *args) -> float:
        fn(*args).block_until_ready()  # compile
        best = float("inf")
        for _ in range(steps):
            t0 = time.perf_counter()
            float(fn(*args))  # value fetch: the chain must have executed
            best = min(best, time.perf_counter() - t0)
        return best

    blocks = blocks or [(256, 256), (512, 512), (512, 1024), (1024, 512),
                        (1024, 1024), (1024, 2048), (2048, 1024),
                        (2048, 2048)]
    rows = []
    for bq, bk in blocks:
        row = {"metric": f"bwd_grid_{model.split('/')[-1]}_seq{seq}",
               "block_q": bq, "block_k": bk, "seq": seq, "batch": batch,
               "unit": "pair_fraction_of_peak",
               "device_kind": jax.devices()[0].device_kind,
               "is_tpu_kernel": on_tpu}
        try:
            def fwd(q, k, v, bq=bq, bk=bk):
                return jnp.sum(flash_attention(
                    q, k, v, causal=True, block_q=bq, block_k=bk)
                    .astype(jnp.float32))

            def pair(q, k, v, do, bq=bq, bk=bk):
                def f(q_, k_, v_):
                    return flash_attention(q_, k_, v_, causal=True,
                                           block_q=bq, block_k=bk)

                out, vjp = jax.vjp(f, q, k, v)
                dq, dk, dv = vjp(do)
                return (jnp.sum(out.astype(jnp.float32))
                        + jnp.sum(dq.astype(jnp.float32))
                        + jnp.sum(dk.astype(jnp.float32))
                        + jnp.sum(dv.astype(jnp.float32)))

            t_fwd = timed(jax.jit(fwd), q, k, v)
            t_pair = timed(jax.jit(pair), q, k, v, do)
            row.update({
                "fwd_ms": round(t_fwd * 1e3, 3),
                "pair_ms": round(t_pair * 1e3, 3),
                "bwd_over_fwd": round((t_pair - t_fwd) / t_fwd, 2),
                "pair_tflops": round(pair_flops / t_pair / 1e12, 2),
                "value": (round(pair_flops / t_pair / peak, 4)
                          if peak else None),
            })
        except Exception as e:  # VMEM-exceeding combos are data, not noise
            row["error"] = f"{type(e).__name__}: {str(e)[:160]}"
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def run_sweep(ap, args) -> int:
    """`--sweep`: every SWEEP row in a fresh child process, best-of-N per
    row; returns the number of rows that produced no value.

    One process per chip: a process that has touched a JAX backend holds
    the chip, and a child that needs it then fails or hangs. So this
    parent never initializes one — no `require_backend`, no
    `jax.devices()` — before or between children; each child probes the
    backend for itself and fails with its own one-line story."""
    import subprocess

    from picotron_tpu.config import resolve_preset

    # the matrix pins per-config shape flags; only these compose with it
    # (attr name -> (default, real flag spelling), so the error names
    # flags the user can actually type; ADVICE r2)
    defaults = {"model": ("SmolLM-1.7B", "--model"),
                "seq": (2048, "--seq"), "mbs": (None, "--mbs"),
                "grad_acc": (None, "--grad-acc"),
                "layers": (None, "--layers"),
                "ce_chunk": (0, "--ce-chunk"),
                "remat_policy": (None, "--remat-policy"),
                "optimizer_offload": (False, "--optimizer-offload"),
                "profile": (None, "--profile"),
                "profile_steps": (None, "--profile-steps"),
                "tp": (1, "--tp"),
                "telemetry": (None, "--telemetry"),
                "trace": (None, "--trace"),
                "no_remat": (False, "--no-remat")}
    clashing = [flag for k, (v, flag) in defaults.items()
                if getattr(args, k) != v]
    if clashing:
        ap.error(f"--sweep runs a fixed config matrix; incompatible "
                 f"with: {', '.join(clashing)}")
    # One FRESH process per row and best-of-N per row: `attempts` in the
    # JSON records EVERY attempt's value so the spread behind each row is
    # visible, not hidden (ADVICE r4), and a >20% disagreement between the
    # first two attempts triggers a THIRD so no reported value rests on a
    # single non-reproduced run (VERDICT r4 #6). Isolation also means one
    # OOM cannot take the rest down.
    n_errored = 0
    for model, layers, seq, mbs, extra in SWEEP:
        depth = layers or resolve_preset(model)["num_hidden_layers"]
        # the row's extras are serialized into child FLAGS below — an
        # unknown key would silently measure a different config than
        # declared (code review r4)
        unknown = set(extra) - {"grad_acc", "remat_policy",
                                "optimizer_offload"}
        if unknown:
            raise ValueError(f"SWEEP extras {sorted(unknown)} have no "
                             f"child-flag serialization; add them to "
                             f"the cmd construction")
        kw = {"remat_policy": "dots", **extra}
        cmd = [sys.executable, os.path.abspath(__file__),
               "--model", model, "--layers", str(layers or 0),
               "--seq", str(seq), "--mbs", str(mbs),
               "--grad-acc", str(kw.get("grad_acc", 1)),
               "--remat-policy", kw["remat_policy"],
               "--adam-moments-dtype", args.adam_moments_dtype,
               "--steps", str(args.steps),
               "--warmup", str(args.warmup)]
        if kw.get("optimizer_offload"):
            cmd.append("--optimizer-offload")
        if args.cpu:  # the child's only consent to run off the chip
            cmd.append("--cpu")
        results, errs = [], []

        def one_attempt():
            res = subprocess.run(cmd, capture_output=True, text=True)
            line = (res.stdout.strip().splitlines()[-1]
                    if res.stdout.strip() else "")
            if res.returncode == 0 and line.startswith("{"):
                results.append(json.loads(line))
            else:
                errs.append(res.stderr.strip()[-200:] or "no output")

        for attempt in range(2):
            one_attempt()
        vals = sorted(d["value"] or 0.0 for d in results)
        # tie-break a flaky row (VERDICT r4 #6): a >20% disagreement
        # OR fewer than two successful attempts (one errored — or BOTH
        # errored, which the old `len == 1` test missed, ADVICE r5)
        # leave the row unconfirmed — take a third attempt either way
        if len(vals) < 2 or vals[0] < 0.8 * vals[1]:
            one_attempt()
        if results:
            best = max(results, key=lambda d: d["value"] or 0.0)
            best["attempts"] = sorted(d["value"] or 0.0 for d in results)
            print(json.dumps(best), flush=True)
        else:  # one OOM must not kill the matrix — but it fails the sweep
            n_errored += 1
            print(json.dumps({
                "metric": f"mfu_{model.split('/')[-1]}-{depth}L_seq{seq}",
                "error": errs[-1],
            }), flush=True)
    return n_errored


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    # Default (no flags) = the HEADLINE config: the full 24-layer
    # SmolLM-1.7B on one chip via optimizer_offload (fp32 master + Adam
    # moments in pinned host memory), mbs 2 x grad-acc 64 = 262k
    # tokens/step — SmolLM's real ~2M-token global batch at the
    # reference's 8-GPU scale. Any explicit shape flag opts out of the
    # auto-config (see the resolution block below); `--layers 8 --mbs 5`
    # reproduces the depth-reduced peak-MFU proxy (62.6%, PERF.md).
    ap.add_argument("--model", default="SmolLM-1.7B")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--mbs", type=int, default=None)
    ap.add_argument("--grad-acc", type=int, default=None)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--remat-policy", default=None,
                    choices=["full", "dots", "dots_attn", "dots_lean", "dots_norms", "dots_offload"])
    ap.add_argument("--ce-chunk", type=int, default=0,
                    help="stream the LM-head CE over vocab chunks of this "
                         "size (0 = fused): ~tokens*vocab*2B less peak HBM "
                         "for one extra chunk matmul in backward — a "
                         "memory knob for big-vocab models (Llama-3 128k); "
                         "costs ~5%% MFU at SmolLM shapes (PERF.md)")
    ap.add_argument("--optimizer-offload", action="store_true",
                    help="ZeRO-Offload-style optimizer-state offload: fp32 "
                         "master + Adam moments live in pinned HOST memory, "
                         "the device keeps a bf16 compute copy — the lever "
                         "that fits full-depth SmolLM-1.7B (~21 GB of "
                         "state) on one 15.75 GB chip. Amortize the PCIe "
                         "round trip with --grad-acc >= 16")
    ap.add_argument("--adam-moments-dtype", default="bfloat16",
                    choices=["float32", "bfloat16"],
                    help="bf16 moments halve optimizer-state HBM traffic "
                         "(profiled at ~9%% of step time fp32) and memory")
    ap.add_argument("--layers", type=int, default=None,
                    help="override the preset's layer count (bench a "
                         "depth-reduced variant of a big model); pass 0 "
                         "for the preset's full depth. Defaults to 8 for "
                         "the default SmolLM-1.7B only, full depth for any "
                         "explicitly chosen model and for --decode")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a jax.profiler trace of the timed steps "
                         "into DIR (open with xprof/tensorboard; see "
                         "README 'Profiling'). SURVEY.md §5 prescribes "
                         "profiler traces as the TPU observability story.")
    ap.add_argument("--profile-steps", type=int, default=None,
                    help="capture only the LAST N timed steps in the "
                         "--profile trace (default: all). The memory-tight "
                         "configs need a single-step window: a full-window "
                         "capture OOMs the mbs-3 offload headline "
                         "(in-flight fused-scan slices + xprof device "
                         "buffers; PERF.md). Use `--profile DIR "
                         "--profile-steps 1`.")
    ap.add_argument("--trace", metavar="FILE", default=None,
                    help="record a flightdeck span per timed step and "
                         "export Chrome-trace/Perfetto JSON to FILE "
                         "(validate with tools/trace_export.py "
                         "--validate); adds trace_span_cost_us to the "
                         "JSON row — the enabled-path overhead number "
                         "PERF.md documents")
    ap.add_argument("--telemetry", metavar="FILE", default=None,
                    help="write per-step timing samples + the summary row "
                         "to this JSONL file (picotron_tpu/telemetry sink "
                         "schema; summarize with tools/telemetry_report.py)")
    ap.add_argument("--sweep", action="store_true",
                    help="run the breadth matrix (one JSON line per config, "
                         "headline last) instead of a single config")
    ap.add_argument("--shardcheck", action="store_true",
                    help="statically audit the resolved config instead of "
                         "timing it: spec lint, collective-schedule audit, "
                         "donation/recompile hazards (picotron_tpu/"
                         "analysis) — no step execution, works without a "
                         "TPU; exit status reflects the findings")
    ap.add_argument("--decode", action="store_true",
                    help="measure generation instead of training: prefill "
                         "tokens/s + steady-state decode tokens/s on the "
                         "chip (KV-cache path, generate.py)")
    ap.add_argument("--batch", type=int, default=8,
                    help="--decode: sequences decoded in parallel")
    ap.add_argument("--prompt-len", type=int, default=512,
                    help="--decode: prefill length")
    ap.add_argument("--max-new-tokens", type=int, default=128,
                    help="--decode: decode steps measured")
    ap.add_argument("--tp", type=int, default=1,
                    help="--decode: shard the params (and, via GSPMD, the "
                         "KV cache) over N chips with the training TP "
                         "layout (generate.place_for_decode) — the "
                         "7B-scale decode arrangement")
    ap.add_argument("--serve", action="store_true",
                    help="measure the serving stack (picotron_tpu/serve: "
                         "continuous batching + paged KV cache) on a "
                         "synthetic arrival trace vs the batch-static "
                         "generate baseline: tokens/s, p50/p95 TTFT, "
                         "per-token latency, slot/pool utilization")
    ap.add_argument("--requests", type=int, default=16,
                    help="--serve: requests in the synthetic trace")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="--serve: Poisson arrival rate in requests/s "
                         "(0 = all arrive at t=0, the saturation trace "
                         "the vs_static anchor uses)")
    ap.add_argument("--serve-slots", type=int, default=8,
                    help="--serve: in-flight decode batch width (the one "
                         "static shape of the decode program)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="--serve: tokens per paged-cache block")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="--serve: physical blocks in the shared KV pool "
                         "(0 = worst-case auto: slots * ceil(cap/block); "
                         "set lower to exercise preemption)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="--serve: prompt tokens prefilled per engine "
                         "iteration, interleaved 1:1 with decode steps")
    ap.add_argument("--decode-interval", type=int, default=4,
                    help="--serve: decode steps scanned inside one "
                         "dispatch (amortizes host overhead; retirement "
                         "latency quantizes to it)")
    ap.add_argument("--serve-repeats", type=int, default=3,
                    help="--serve: timed wall-clock runs per side; the "
                         "reported wall is the median (the structural "
                         "decode_slot_steps headline needs one run)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="--serve: run N engine replicas behind one "
                         "queue (picotron_tpu/serve/fleet) instead of "
                         "the vs-static comparison — failover "
                         "re-dispatch, deadline shedding, per-request "
                         "token digests for the parity oracle")
    ap.add_argument("--chaos", metavar="SPEC", default=None,
                    help="--serve --fleet: serve-side chaos spec "
                         "(engine_dead@REQ, decode_hang@REQ~SECS, "
                         "shed_storm@REQ[xN]; same grammar as "
                         "resilience.chaos) injected in the fleet "
                         "dispatch loop")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="--serve --fleet: per-request deadline on the "
                         "virtual trace clock; a request still queued "
                         "past it is shed (0 = no deadline)")
    ap.add_argument("--serve-temperature", type=float, default=0.0,
                    help="--serve --fleet: sampling temperature (keys "
                         "fold per (request id, token index), so "
                         "failover parity holds at any temperature)")
    ap.add_argument("--tick-s", type=float, default=0.001,
                    help="--serve --fleet: virtual trace-clock seconds "
                         "per fleet iteration (what makes shed "
                         "decisions deterministic)")
    ap.add_argument("--serve-seed", type=int, default=0,
                    help="--serve --fleet: trace + sampling seed")
    ap.add_argument("--pp-tick-sweep", action="store_true",
                    help="fit step time vs n_micro per pipeline executor "
                         "(SPMD lockstep scan vs MPMD per-stage programs) "
                         "at --pp stages: slope = ms/tick, intercept = "
                         "fill/drain + fixed overhead — the PERF.md r4 "
                         "table, automated, with the MPMD column. One "
                         "JSON line per sample + a summary line with the "
                         "intercept drop and schedule-tick accounting. "
                         "Needs a device count divisible by --pp (use "
                         "--cpu for 8 simulated hosts)")
    ap.add_argument("--pp", type=int, default=4,
                    help="--pp-tick-sweep: pipeline stages")
    ap.add_argument("--n-micros", type=int, nargs="*",
                    default=[2, 4, 8, 16],
                    help="--pp-tick-sweep: microbatch counts to fit over")
    ap.add_argument("--cp-flavor-sweep", action="store_true",
                    help="time the train step under each cp flavor (ring/"
                         "ulysses/mesh) at each --seqs length, with the "
                         "cost model's prediction alongside (PERF.md "
                         "Round 13 protocol); one JSON line per row. "
                         "With --cpu, runs on 8 simulated devices as a "
                         "structural anchor")
    ap.add_argument("--seqs", type=int, nargs="*",
                    default=[8192, 16384, 32768, 65536],
                    help="sequence lengths for --cp-flavor-sweep")
    ap.add_argument("--cp", type=int, default=0,
                    help="cp degree for --cp-flavor-sweep (0 = all "
                         "devices)")
    ap.add_argument("--bwd-grid-sweep", action="store_true",
                    help="sweep flash-attention (block_q, block_k) over "
                         "the fwd / fwd+bwd kernel pair at --seq (use "
                         "16384 for the VERDICT r5 #8 question: is the "
                         "16k bwd-pair excess pipeline overhead the grid "
                         "shape can shrink?); one JSON line per combo")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the host CPU platform (structural smoke "
                         "run, numbers not comparable) instead of failing "
                         "when no TPU backend is reachable")
    args = ap.parse_args(argv)

    if (args.pp_tick_sweep or args.cp_flavor_sweep) and args.cpu:
        # Provision the simulated stage x data devices BEFORE the first
        # backend-initializing jax call (require_backend's jax.devices()
        # pins the client) — same ordering contract as tools/memcheck.py.
        from picotron_tpu.mesh import force_host_device_count

        force_host_device_count(max(args.pp if args.pp_tick_sweep
                                    else args.cp, 8))

    # Backend probe BEFORE any mode: no chip must be one line, not an
    # xla_bridge traceback. Except in the --sweep parent, which must stay
    # off the chip for its children (run_sweep).
    if not args.sweep:
        require_backend(args.cpu)

    if args.shardcheck and (args.sweep or args.decode or args.profile
                            or args.bwd_grid_sweep or args.serve
                            or args.pp_tick_sweep or args.cp_flavor_sweep):
        ap.error("--shardcheck is its own mode; incompatible with "
                 "--sweep/--decode/--profile/--bwd-grid-sweep/--serve/"
                 "--pp-tick-sweep/--cp-flavor-sweep")

    if args.cp_flavor_sweep:
        if (args.sweep or args.decode or args.profile
                or args.bwd_grid_sweep or args.serve or args.pp_tick_sweep):
            ap.error("--cp-flavor-sweep is its own mode; incompatible "
                     "with --sweep/--decode/--profile/--bwd-grid-sweep/"
                     "--serve/--pp-tick-sweep")
        run_cp_flavor_sweep(args.model, args.layers or 0,
                            tuple(args.seqs), args.mbs or 1, cp=args.cp,
                            steps=args.steps, warmup=args.warmup)
        return

    if args.pp_tick_sweep:
        if (args.sweep or args.decode or args.profile
                or args.bwd_grid_sweep or args.serve):
            ap.error("--pp-tick-sweep is its own mode; incompatible with "
                     "--sweep/--decode/--profile/--bwd-grid-sweep/--serve")
        run_pp_tick_sweep(args.model, args.layers or 0, args.seq,
                          args.mbs or 1, pp=args.pp,
                          n_micros=tuple(args.n_micros),
                          steps=args.steps, warmup=args.warmup)
        return

    if args.serve:
        if args.sweep or args.decode or args.profile or args.bwd_grid_sweep:
            ap.error("--serve is its own mode; incompatible with "
                     "--sweep/--decode/--profile/--bwd-grid-sweep")
        if args.max_new_tokens < 1 or args.requests < 2:
            ap.error("--serve needs --max-new-tokens >= 1 and "
                     "--requests >= 2")
        if args.fleet:
            if args.tp > 1:
                ap.error("--fleet places each replica on its own device; "
                         "incompatible with --tp in this bench mode")
            print(json.dumps(run_serve_fleet(
                args.model, args.layers or 0, fleet=args.fleet,
                slots=args.serve_slots, block_size=args.block_size,
                num_blocks=args.num_blocks,
                prefill_chunk=args.prefill_chunk,
                prompt_len=args.prompt_len,
                max_new=args.max_new_tokens, n_requests=args.requests,
                rate=args.rate, decode_interval=args.decode_interval,
                seed=args.serve_seed, temperature=args.serve_temperature,
                deadline_ms=args.deadline_ms, chaos_spec=args.chaos,
                tick_s=args.tick_s, telemetry=args.telemetry)))
            return
        print(json.dumps(run_serve(
            args.model, args.layers or 0, slots=args.serve_slots,
            block_size=args.block_size, num_blocks=args.num_blocks,
            prefill_chunk=args.prefill_chunk, prompt_len=args.prompt_len,
            max_new=args.max_new_tokens, n_requests=args.requests,
            rate=args.rate, tp=args.tp,
            decode_interval=args.decode_interval,
            repeats=args.serve_repeats,
            telemetry=args.telemetry)))
        return

    if args.bwd_grid_sweep:
        if args.sweep or args.decode or args.profile:
            ap.error("--bwd-grid-sweep is its own mode; incompatible with "
                     "--sweep/--decode/--profile")
        run_bwd_grid_sweep(args.model, args.seq, args.mbs or 1,
                           steps=args.steps)
        return

    if args.decode:
        if args.sweep or args.profile:
            ap.error("--decode is its own mode; incompatible with "
                     "--sweep/--profile")
        if args.max_new_tokens < 2:
            # the prefill/decode split differences a max_new=1 run
            # against the full run — guard BEFORE the expensive compiles
            ap.error("--decode needs --max-new-tokens >= 2")
        print(json.dumps(run_decode(
            args.model, args.layers or 0, args.prompt_len,
            args.max_new_tokens, args.batch, steps=args.steps,
            tp=args.tp)))
        return

    if args.sweep:
        n_errored = run_sweep(ap, args)
        if n_errored:
            raise SystemExit(f"bench --sweep: {n_errored} of {len(SWEEP)} "
                             f"row(s) produced no value")
        return

    # Flag resolution: the bare default is the full-depth headline config
    # (offload + mbs 2 x ga 64 + full remat). ANY explicit shape/policy
    # flag opts out of the auto-config (an old invocation like
    # `bench.py --mbs 5` must keep meaning the depth-reduced proxy, not
    # silently become a 24L run that OOMs); --optimizer-offload composes
    # with explicit flags as requested.
    no_shape_flags = (args.layers is None and args.mbs is None
                      and args.grad_acc is None
                      and args.remat_policy is None)
    if args.model == "SmolLM-1.7B" and no_shape_flags \
            and not args.optimizer_offload:
        args.optimizer_offload = True
    if args.optimizer_offload:
        args.layers = args.layers or 0
        args.mbs = args.mbs or 3
        args.grad_acc = args.grad_acc or 43
        args.remat_policy = args.remat_policy or "dots_attn"
    else:
        if args.layers is None and args.model == "SmolLM-1.7B":
            # without offload the full model's state exceeds one chip;
            # 8 layers is the honest depth-reduced proxy (PERF.md)
            args.layers = 8
        args.mbs = args.mbs or 5
        args.grad_acc = args.grad_acc or 1
        args.remat_policy = args.remat_policy or "dots"
    if args.shardcheck:
        import sys

        from picotron_tpu.analysis import run_shardcheck

        cfg = bench_config(
            args.model, args.layers, args.seq, args.mbs,
            grad_acc=args.grad_acc, remat=not args.no_remat,
            remat_policy=args.remat_policy,
            adam_moments_dtype=args.adam_moments_dtype,
            ce_chunk=args.ce_chunk,
            optimizer_offload=args.optimizer_offload)
        rep = run_shardcheck(cfg)
        # human report on stderr; stdout keeps bench's one-JSON-line contract
        print(rep.render(verbose=True), file=sys.stderr)
        print(json.dumps({
            "metric": f"shardcheck_{args.model.split('/')[-1]}"
                      f"-{cfg.model.num_hidden_layers}L_seq{args.seq}",
            "value": 1.0 if rep.ok() else 0.0,
            "unit": "static_analysis_green",
            "errors": len(rep.errors()),
            "warnings": len(rep.warnings()),
        }))
        raise SystemExit(0 if rep.ok() else 1)
    print(json.dumps(run_one(
        args.model, args.layers, args.seq, args.mbs, grad_acc=args.grad_acc,
        steps=args.steps, warmup=args.warmup, remat=not args.no_remat,
        remat_policy=args.remat_policy,
        adam_moments_dtype=args.adam_moments_dtype, ce_chunk=args.ce_chunk,
        optimizer_offload=args.optimizer_offload, profile=args.profile,
        profile_steps=args.profile_steps, telemetry=args.telemetry,
        trace=args.trace)))


if __name__ == "__main__":
    main()
