#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip
    python chip_smoke.py --chips 4  # one four-chip host (tp2 x pp2 / tp4)

Drives the two main paths once, in this one process (a chip belongs to one
process at a time; nothing here starts a child), at the full width and full
depth of SmolLM-1.7B with weights made from a seed:

- train: `picotron_tpu.train.main(["--config", ...])` — the trainer CLI's
  own entry point — on the `model` and `training` sections of
  runs/smollm17-offload-1chip/config.json (24 layers, h 2048, 32x64 heads,
  ffn 8192, vocab 49152, seq 2048, mbs 2, bf16, optimizer offload,
  remat dots_attn, attn_impl auto, grad_engine auto). Only traffic is cut:
  2 microbatches a step, 4 steps, no checkpoints. The corpus is 4 rows of
  uniform random tokens written from a seed and read back through the
  trainer's file-backed dataset path, so every step sees the same rows and
  memorising them is the learning signal. (`dataset: synthetic` cannot
  give that: SyntheticSource seeds its rows by epoch, data.py.)
- train again, 1 step: the same program compiled a second time must come
  out of the persistent compilation cache.
- serve: the same model in bf16 behind `ServeEngine`, 8 requests of mixed
  prompt/output length through chunked prefill + paged decode with the KV
  pool donated, greedy tokens checked against the offline `generate`
  sampler.

Every check reads what the program itself reports (its log lines, its
result dicts). Any failed check, a platform other than `tpu`, or a
`device_kind` outside the peak table ends the run non-zero with one line
naming the phase. There is no CPU mode. On success the last line of stdout
is `{"ok": true, "device": {...}}`; per-phase compile and wall seconds also
go to chiprun_out/chip_smoke.json.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import math
import os
import re
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
BASE_CONFIG = os.path.join(ROOT, "runs", "smollm17-offload-1chip",
                           "config.json")
OUT_DIR = os.path.join(ROOT, "chiprun_out")

TRAIN_STEPS = 4
GRAD_ACC = 2  # >= 2 microbatches: the fused grad engine is the one taken

STARTUP_RE = re.compile(
    r"^model .*\((?P<chips>\d+) chips, (?P<kind>[^)]*)\).*"
    r"platform=(?P<platform>\S+) attention=(?P<attention>\S+) "
    r"offload=(?P<offload>\S+)", re.M)
STEP_RE = re.compile(
    r"^\[step (?P<step>\d+)\] loss: (?P<loss>\S+) \| "
    r"tokens/s: (?P<tps>\S+) \|(?P<rest>.*)$", re.M)
GNORM_RE = re.compile(r"\| grad_norm: (\S+)")


class SmokeFailure(Exception):
    """A phase's check did not hold; str() is the one-line story."""


class CompileMeter:
    """Compile seconds and persistent-cache hits/misses, from
    jax.monitoring, sliced per phase by `window()`."""

    def __init__(self) -> None:
        import jax.monitoring

        self.secs = 0.0
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _on_event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def window(self, into: dict):
        """Fill `into` with this window's wall/compile seconds and cache
        counts (also when the body raises, so a failed phase is timed)."""
        t0 = time.perf_counter()
        before = (self.secs, self.compiles, self.hits, self.misses)
        try:
            yield
        finally:
            into.update(
                wall_s=round(time.perf_counter() - t0, 2),
                compile_s=round(self.secs - before[0], 2),
                compiles=self.compiles - before[1],
                cache_hits=self.hits - before[2],
                cache_misses=self.misses - before[3])


class _Tee(io.TextIOBase):
    def __init__(self, *streams) -> None:
        self.streams = streams

    def write(self, s: str) -> int:
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self) -> None:
        for st in self.streams:
            st.flush()


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def write_corpus(path: str, rows: int, block: int, vocab: int,
                 seed: int = 0) -> None:
    """`rows` x `block` uniform random tokens in the pre-chunked
    save_to_disk layout the trainer's dataloader reads (data.py)."""
    import datasets
    import numpy as np

    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (rows, block), dtype=np.int32)
    datasets.Dataset.from_dict({"input_ids": ids.tolist()}).save_to_disk(path)


def train_config(chips: int, steps: int, data_dir: str, out_dir: str) -> dict:
    """The run config: the repo's one-chip SmolLM-1.7B config with only
    the traffic cut. On four chips the layout is tp2 x pp2 (1F1B) and the
    state stays on device (what `tools/create_config.py --tp 2 --pp 2
    --pp-engine 1f1b` writes); widths, depth, seq and mbs are the same.

    `steps` is set through the token budget, not total_train_steps: the LR
    schedule compiles total_train_steps into the step program, and the
    second train phase must compile the very same program."""
    with open(BASE_CONFIG) as f:
        cfg = json.load(f)
    t = cfg["training"]
    t.update(gradient_accumulation_steps=GRAD_ACC,
             total_train_steps=TRAIN_STEPS, lr_warmup_steps=1,
             max_tokens=steps * t["micro_batch_size"] * GRAD_ACC
             * t["seq_length"])
    if chips == 4:
        cfg["distributed"].update(tp_size=2, pp_size=2, pp_engine="1f1b")
        t["optimizer_offload"] = False
    cfg["dataset"] = {"name": data_dir, "split": "train"}
    cfg["checkpoint"] = {"save_frequency": 0, "auto_resume": False,
                         "save_dir": os.path.join(out_dir, "chip_smoke_ckpt")}
    cfg["logging"] = {"use_wandb": False, "log_frequency": 1,
                      "run_name": f"chip_smoke_{chips}chip"}
    return cfg


def check_train_log(log: str, *, chips: int, steps: int,
                    offload: str) -> dict:
    """Read the trainer's own start-up line and step lines; raise
    SmokeFailure unless they say what a healthy chip run says."""
    m = STARTUP_RE.search(log)
    if m is None:
        raise SmokeFailure("trainer printed no start-up line")
    start = m.groupdict()
    want = dict(platform="tpu", attention="pallas", offload=offload,
                chips=str(chips))
    for k, v in want.items():
        if start[k] != v:
            raise SmokeFailure(f"start-up line says {k}={start[k]}, "
                               f"want {v}")
    rows = [r.groupdict() for r in STEP_RE.finditer(log)]
    if [int(r["step"]) for r in rows] != list(range(1, steps + 1)):
        raise SmokeFailure(f"expected step lines 1..{steps}, got "
                           f"{[r['step'] for r in rows]}")
    if "training done" not in log:
        raise SmokeFailure("trainer did not reach 'training done'")
    losses = [float(r["loss"]) for r in rows]
    gnorms = [float(g.group(1)) if (g := GNORM_RE.search(r["rest"])) else None
              for r in rows]
    if not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"non-finite loss: {losses}")
    if any(g is None or not math.isfinite(g) or g <= 0 for g in gnorms):
        raise SmokeFailure(f"grad norm missing, non-finite or zero: "
                           f"{gnorms}")
    return dict(start, losses=losses, grad_norms=gnorms,
                tokens_per_sec=[r["tps"] for r in rows])


def train_phase(chips: int, steps: int, out_dir: str, name: str) -> dict:
    from picotron_tpu import train

    data_dir = os.path.join(out_dir, "chip_smoke_corpus")
    cfg = train_config(chips, steps, data_dir, out_dir)
    if not os.path.isdir(data_dir):
        # one global batch: mbs x grad_acc rows (dp = 1 in both layouts)
        t = cfg["training"]
        write_corpus(data_dir, t["micro_batch_size"] * GRAD_ACC,
                     t["seq_length"] + 1, cfg["model"]["vocab_size"])
    cfg_path = os.path.join(out_dir, f"{name}_config.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f, indent=1)

    log_path = os.path.join(out_dir, f"{name}.log")
    buf = io.StringIO()
    with open(log_path, "w") as logf, \
            contextlib.redirect_stdout(_Tee(sys.stdout, buf, logf)):
        train.main(["--config", cfg_path])
    got = check_train_log(
        buf.getvalue(), chips=chips, steps=steps,
        offload="pinned_host" if cfg["training"]["optimizer_offload"]
        else "off")
    got["log"] = os.path.relpath(log_path, ROOT)
    return got


def check_learning(losses: list, vocab: int) -> None:
    """Step 1 sits at random-init entropy; step 2 repeats it (the warm-up
    makes the first update lr = 0, and the rows are the same); after that
    the repeated rows must be getting memorised."""
    ln_v = math.log(vocab)
    if not ln_v - 0.3 < losses[0] < ln_v + 0.7:
        raise SmokeFailure(f"step-1 loss {losses[0]} is not near "
                           f"ln {vocab} = {ln_v:.2f}")
    if abs(losses[1] - losses[0]) > 0.02:
        raise SmokeFailure(f"step 2 ({losses[1]}) should repeat step 1 "
                           f"({losses[0]}): same rows, lr 0 update")
    if not losses[-1] < losses[0] - 0.02:
        raise SmokeFailure(f"loss did not fall: {losses}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

# (prompt length, output budget): three prompt lengths (under one prefill
# chunk, two chunks, three chunks) x mixed budgets; 8 requests over 4 slots
# so admission waits on retirement
SERVE_TRACE = [(19, 6), (77, 17), (150, 24), (19, 21), (77, 5), (150, 9),
               (77, 24), (19, 12)]
SERVE = dict(decode_slots=4, block_size=16, prefill_chunk=64,
             decode_interval=4)


TIE_STEPS = 8  # bf16 steps (2^-8 of the top logit) that still count as a tie


def teacher_forced_gap(params, mcfg, prompt, tokens, pad_to: int):
    """(gap, top) per generated token: how far its logit sits under the
    top logit at its position when `prompt + tokens` goes through the
    model's reference forward (models/llama.py `forward`, no cache, no
    kernels) in one pass. Padded to one length so it compiles once."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from picotron_tpu.models.llama import forward

    ids = list(prompt) + list(tokens)
    padded = jnp.asarray([ids + [0] * (pad_to - len(ids))], jnp.int32)
    logits = jax.jit(forward, static_argnames="cfg")(params, padded, cfg=mcfg)
    rows = np.asarray(logits[0, len(prompt) - 1:len(ids) - 1], np.float32)
    top = rows.max(axis=-1)
    return top - rows[np.arange(len(tokens)), np.asarray(tokens)], top


def serve_phase(mcfg, tp: int = 1, trace=SERVE_TRACE) -> dict:
    """Serve `trace` through ServeEngine the way bench.run_serve does and
    check every request against the offline sampler. `mcfg` is the
    ModelConfig; weights are bf16 from seed 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from picotron_tpu.config import ServeConfig
    from picotron_tpu.generate import generate, place_for_decode
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.serve import ServeEngine

    params = jax.jit(
        lambda k: jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                               init_params(mcfg, k)))(jax.random.key(0))
    if tp > 1:
        params = place_for_decode(params, mcfg, tp=tp)
    rng = np.random.default_rng(0)
    requests = [(rng.integers(0, mcfg.vocab_size, size=p).tolist(), n)
                for p, n in trace]
    cap = max(p + n for p, n in trace)
    scfg = ServeConfig(max_model_len=cap, **SERVE)

    eng = ServeEngine(params, mcfg, scfg)
    try:
        t0 = time.perf_counter()
        results = eng.run(requests)
        wall = time.perf_counter() - t0
        summary, leaked, donate = eng.summary, eng.pool.in_use, eng.donate
    finally:
        eng.close()

    if not donate:
        raise SmokeFailure("engine built its programs without KV donation")
    if len(results) != len(requests):
        raise SmokeFailure(f"{len(results)} of {len(requests)} requests "
                           f"retired")
    if leaked:
        raise SmokeFailure(f"{leaked} KV block(s) leaked")

    # offline reference: one generate() batch per prompt length, decoding
    # that group's longest budget (greedy is prefix-stable, so a shorter
    # request's tokens are the head of its row)
    exact, ties, wrong = 0, [], []
    for plen in sorted({p for p, _ in trace}):
        idx = [i for i, (p, _) in enumerate(trace) if p == plen]
        n_max = max(trace[i][1] for i in idx)
        ref = np.asarray(generate(
            params, mcfg, jnp.asarray([requests[i][0] for i in idx]), n_max))
        for row, i in zip(ref, idx):
            want = row[plen:plen + trace[i][1]].tolist()
            got = results[i]["tokens"]
            if got == want:
                exact += 1
                continue
            # Not the sampler's tokens. With bf16 weights from a seed the
            # top logits sit a few bf16 steps apart, and the two paths
            # (chunked prefill + paged cache vs one contiguous pass) may
            # round a tie differently; after that the prefixes differ. So
            # score the engine's tokens under teacher forcing with the
            # model's plain jnp forward: each must be a greedy token to
            # within bf16 resolution of the top logit.
            gap, top = teacher_forced_gap(params, mcfg, requests[i][0], got,
                                          cap)
            j = int(np.argmax(gap / np.maximum(np.abs(top), 1.0)))
            rec = dict(request=i, prompt=plen, worst_token=j,
                       gap=round(float(gap[j]), 4),
                       top_logit=round(float(top[j]), 3))
            tol = TIE_STEPS * 2.0 ** -8 * max(abs(float(top[j])), 1.0)
            (ties if gap[j] <= tol else wrong).append(rec)
    if wrong:
        raise SmokeFailure(f"engine tokens are not greedy tokens of the "
                           f"reference forward: {json.dumps(wrong)}")
    return dict(requests=len(results), leaked_blocks=leaked, donate=donate,
                greedy_exact=exact, greedy_ties=ties, tp=tp,
                output_tokens=summary["output_tokens"],
                decode_steps=summary["decode_steps"],
                prefill_chunks=summary["prefill_chunks"],
                decode_compiles=summary["decode_compiles"],
                serve_wall_s=round(wall, 2))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def device_memory() -> list:
    """Per-device bytes in use now and at peak — the trainer's `mem:` is a
    max over devices and would hide "everything on device 0"."""
    import jax

    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append(dict(id=d.id, bytes_in_use=st.get("bytes_in_use"),
                        peak_bytes_in_use=st.get("peak_bytes_in_use")))
    return out


def check_all_devices_worked(mem: list) -> None:
    peaks = [m["peak_bytes_in_use"] or 0 for m in mem]
    if min(peaks) <= 0 or max(peaks) > 2 * min(peaks):
        raise SmokeFailure(f"per-device peak memory is not of like size "
                           f"across {len(mem)} devices: {peaks}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    try:
        import importlib.metadata as md

        import jax
        import jaxlib

        from picotron_tpu.config import load_config
        from picotron_tpu.utils import (
            device_peak_flops, require_platform, setup_compile_cache,
        )
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from "
              f"the root of a checkout", file=sys.stderr)
        return 2

    cache_dir = setup_compile_cache()
    dev = require_platform("chip_smoke", allow_cpu=False)  # one line + exit

    os.makedirs(OUT_DIR, exist_ok=True)
    report = {
        "ok": False,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "chips_used": args.chips,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": md.version("libtpu")},
        "compile_cache_dir": cache_dir,
        "phases": {},
    }
    print(f"chip_smoke: {json.dumps(report['device'])} "
          f"{json.dumps(report['versions'])} cache={cache_dir}", flush=True)

    mcfg = load_config(BASE_CONFIG).model
    meter = CompileMeter()

    def device():
        if dev.platform != "tpu":
            raise SmokeFailure(f"platform is {dev.platform!r}, not 'tpu'")
        try:
            device_peak_flops(dev)
        except ValueError as e:
            raise SmokeFailure(str(e))
        if len(jax.devices()) < args.chips:
            raise SmokeFailure(f"--chips {args.chips} on a host with "
                               f"{len(jax.devices())} device(s)")
        return {}

    def train_first():
        got = train_phase(args.chips, TRAIN_STEPS, OUT_DIR, "chip_smoke_train")
        check_learning(got["losses"], mcfg.vocab_size)
        got["device_memory"] = device_memory()
        if args.chips > 1:
            check_all_devices_worked(got["device_memory"])
        return got

    def train_again():
        return train_phase(args.chips, 1, OUT_DIR, "chip_smoke_train_again")

    def serve():
        return serve_phase(mcfg, tp=args.chips)

    failed = None
    for name, fn in (("device", device), ("train", train_first),
                     ("train_again", train_again), ("serve", serve)):
        phase = report["phases"][name] = {}
        try:
            with meter.window(phase):
                phase.update(fn())
            if name == "train_again" and phase["cache_hits"] < 1:
                raise SmokeFailure(
                    f"recompiling the train step hit the persistent cache "
                    f"{phase['cache_hits']} time(s) "
                    f"({phase['compile_s']} s of compile; cache {cache_dir})")
        except SmokeFailure as e:
            failed = (name, str(e))
        except SystemExit as e:  # the trainer's own exit codes (75/77/...)
            failed = (name, f"trainer exited {e.code}")
        except Exception as e:  # noqa: BLE001 — phase boundary: the crash
            # is recorded and reported as this phase's failure, exit != 0
            traceback.print_exc()
            failed = (name, f"{type(e).__name__}: "
                            f"{(str(e).splitlines() or [''])[0][:300]}")
        print(f"chip_smoke: phase {name}: {json.dumps(phase)}", flush=True)
        if failed:
            break
        gc.collect()  # the finished phase's device and host buffers go now

    report["ok"] = failed is None
    if failed:
        report["failed_phase"], report["error"] = failed
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1)
    if failed:
        print(f"chip_smoke: FAILED in phase '{failed[0]}': {failed[1]}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
