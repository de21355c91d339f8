#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix or metric is
data under this directory, found by the names in `BENCHMARK.json`:

    workloads/<cell>.json       the cell: runner, traffic, what it reports
    configs/<configuration>.json  the sizes, as the program's own config blocks
    runners/<runner>.py         run(ctx) -> facts
    traffic/<generator>.py      make(params, seed, seconds, vocab) -> requests
    layer_metrics/<metric>.json one per-layer metric: reader + parameters
    readers/<reader>.py         read(params, facts, ctx) -> number or None

This file names no cell, configuration or metric. It needs a TPU whose
`device_kind` is in `peaks.json`; without one it exits non-zero and prints
no result. `--trace 0` reports the cell's end-to-end metrics, `--trace 1`
its per-layer metrics (and `breakdown`).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")  # git-ignored; traces and run files


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits, from
    jax.monitoring (copied from chip_smoke.py)."""

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

    def snapshot(self):
        return dict(compile_s=self.secs, compiles=self.compiles,
                    cache_hits=self.hits, cache_misses=self.misses)


def require_chip(chips: int) -> dict:
    """The devices the cell runs on and their row of `peaks.json`, or exit
    non-zero with one line: no accelerator, too few chips, or a
    `device_kind` nobody has written a peak down for."""
    import jax

    from picotron_tpu.utils import require_platform

    dev = require_platform("benchmark", allow_cpu=False)
    if dev.platform != "tpu":
        raise SystemExit(f"benchmark: platform {dev.platform!r} is not a TPU")
    peaks = load_json("peaks.json")
    if dev.device_kind not in peaks:
        raise SystemExit(f"benchmark: device_kind {dev.device_kind!r} is not in "
                         f"benchmark/peaks.json ({', '.join(peaks)})")
    if len(jax.devices()) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), JAX sees "
                         f"{len(jax.devices())}")
    return dict(devices=jax.devices()[:chips], peak=peaks[dev.device_kind])


class Ctx:
    """What a runner and a reader are given."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.setup_s = None
        self.at_window = None  # the compile meter's reading when the window started

    def seed31(self, salt: int) -> int:
        """A 31-bit seed for `jax.random.key`, from `--seed` (any size) and a salt."""
        import numpy as np

        return int(np.random.SeedSequence([self.seed, salt]).generate_state(1)[0] >> 1)

    def window_starts(self) -> None:
        """The runner calls this at the first measured step or request:
        set-up ends here, and compiles are counted from here."""
        self.setup_s = time.perf_counter() - T_PROCESS
        self.at_window = self.meter.snapshot()

    def window_ends(self) -> dict:
        now = self.meter.snapshot()
        return {k: now[k] - self.at_window[k] for k in now}

    def device_report(self) -> dict:
        """The `device` of the result line; the peak is the fullest chip's."""
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)
        return dict(platform=self.devices[0].platform, kind=self.devices[0].device_kind,
                    count=len(self.devices), memory_peak_bytes=peak)

    def log(self, *a) -> None:
        print("[benchmark]", *a, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = by_name(bench["workloads"], args.workload, "workload")
    cfg_entry = by_name(bench["configs"], cell["config"], "configuration")
    workload = load_json("workloads", cell["name"] + ".json")
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    try:
        import jax

        from picotron_tpu.utils import setup_compile_cache
    except ImportError as e:
        raise SystemExit(f"benchmark: the program is not in this checkout ({e})")
    chip = require_chip(cell["chips"])
    cache_dir = setup_compile_cache()
    meter = CompileMeter()

    trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    ctx = Ctx(cell=cell, workload=workload, config=config, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), trace_dir=trace_dir,
              devices=chip["devices"], peak=chip["peak"], meter=meter,
              chips=cell["chips"], here=HERE, out_dir=OUT_DIR, load_module=load_module)
    ctx.log(f"cell={cell['name']} device_kind={chip['devices'][0].device_kind} "
            f"chips={cell['chips']} jax={jax.__version__} cache={cache_dir}")

    facts = load_module("runners", workload["runner"]).run(ctx)
    if ctx.setup_s is None:
        raise SystemExit("benchmark: the runner never marked the window's start")
    facts["setup_s"] = ctx.setup_s
    facts["compile_s_setup"] = ctx.at_window["compile_s"]
    ctx.log(f"setup_s={ctx.setup_s:.3f} compile_s={facts['compile_s_setup']:.3f} "
            f"cache_hits={ctx.at_window['cache_hits']} "
            f"cache_misses={ctx.at_window['cache_misses']} "
            f"compiles_in_window={facts['compiles_in_window']}")

    device = facts.pop("device")
    facts["memory_peak_bytes"] = device["memory_peak_bytes"]
    metrics, line = {}, {}
    if not args.trace:
        # end-to-end: the facts the cell's file maps each metric to
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                value = facts.get(workload["end_to_end"][m["name"]])
                if value is not None:
                    metrics[m["name"]] = dict(value=float(value), unit=m["unit"])
    else:
        import trace_reduce

        red = trace_reduce.reduce(trace_reduce.load_xplane(trace_dir),
                                  span_names=facts.get("spans", ()))
        if red is None:
            raise SystemExit("benchmark: the trace holds no device operation")
        ctx.trace = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["breakdown"] = trace_reduce.breakdown(red)
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            spec = load_json("layer_metrics", m["name"] + ".json")
            value = load_module("readers", spec["reader"]).read(
                spec.get("params", {}), facts, ctx)
            if value is not None:
                metrics[m["name"]] = dict(value=float(value), unit=m["unit"])
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(os.path.join(OUT_DIR, f"{cell['name']}.trace.json"), "w") as f:
            json.dump(red, f, indent=1)
        shutil.rmtree(trace_dir, ignore_errors=True)  # the raw trace is large

    for note in facts.get("notes", []):
        ctx.log(note)
    correct = bool(facts["correct"]) and facts["compiles_in_window"] == 0
    # `facts`: the runner's scalar facts, for the tools; the driver reads the other keys
    scalars = {k: v for k, v in facts.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    print(json.dumps(dict(correct=correct, attempted=int(facts["attempted"]),
                          failed=int(facts["failed"]), metrics=metrics,
                          device=device, facts=scalars, **line)), flush=True)
    # what `correct` compared, beside its limits, as the last lines of standard error too
    for note in facts.get("notes", []):
        print("[benchmark]", note, file=sys.stderr)
    print(f"[benchmark] compiles_in_window={facts['compiles_in_window']} (limit 0) "
          f"correct={correct}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
