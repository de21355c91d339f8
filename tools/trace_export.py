#!/usr/bin/env python
"""Telemetry -> Chrome-trace converter + trace self-checker.

Two modes:

* Convert: turn a `telemetry.jsonl` stream (picotron_tpu/telemetry; the
  per-host event file next to the checkpoints) into Chrome trace-event
  JSON loadable by Perfetto / chrome://tracing. Phase events become
  complete spans — train-loop phases on the train lane, serve request
  phases (queue_wait/prefill/decode, with their request ids) on
  the serve lane — and resilience events (chaos, guard, rollback,
  preemption, watchdog, resize, recompile, sentinel alerts) become
  instants, so one timeline shows compute, comm phases, and faults
  together. Rotated streams (`telemetry.jsonl.1`, logging.telemetry_max_mb)
  are read oldest-first. A phase event is stamped when it is emitted, so
  the span drawn from it is back-dated by its `secs` and is only as good
  as that: the in-process flightdeck tracer (logging.trace_dir) records
  each region where it starts and ends (telemetry/spans.py: the engine
  step's leaf spans, per-op MPMD spans, none of which hit the JSONL) —
  this converter is the post-hoc fallback for runs that only kept their
  telemetry stream.

* Validate (`--validate`): self-check a trace file — monotonic
  timestamps, balanced B/E begin/end events, pid/tid presence and
  type consistency, non-negative X durations — exiting nonzero on any
  violation. Wired as a tier-1 subprocess smoke (tests/test_flightdeck)
  like the shardcheck gates.

Usage:

  python tools/trace_export.py RUN_DIR_OR_JSONL -o trace.json
  python tools/trace_export.py --validate trace.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from picotron_tpu.telemetry import _INSTANT_KINDS  # noqa: E402
from picotron_tpu.telemetry.flightdeck.tracer import (  # noqa: E402
    TID_SERVE, TID_TRAIN,
)
from picotron_tpu.telemetry.sinks import jsonl_segments  # noqa: E402

_VALID_PH = frozenset("XBEiICMsnftPNODabevR")
# The serve engine's `phase` events, drawn on the serve lane.
_SERVE_PHASES = frozenset(("queue_wait", "prefill", "decode"))


def resolve_jsonl(path: str) -> str:
    if os.path.isdir(path):
        cand = os.path.join(path, "telemetry.jsonl")
        if not os.path.exists(cand):
            raise FileNotFoundError(f"no telemetry.jsonl under {path}")
        return cand
    return path


def load_events(path: str) -> list[dict]:
    """All events of a possibly-rotated stream, oldest segment first."""
    events = []
    for seg in jsonl_segments(path):
        with open(seg) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail of a killed run
                if isinstance(ev, dict):
                    events.append(ev)
    return events


def convert(events: list[dict], pid: int = 0) -> dict:
    """Telemetry events -> Chrome trace document. Wall-clock `ts`
    anchors the timeline (zeroed at the stream's first event)."""
    ts0 = min((e["ts"] for e in events
               if isinstance(e.get("ts"), (int, float))), default=0.0)
    out: list[dict] = [
        {"name": "thread_name", "ph": "M", "pid": pid, "tid": TID_TRAIN,
         "ts": 0, "args": {"name": "train"}},
        {"name": "thread_name", "ph": "M", "pid": pid, "tid": TID_SERVE,
         "ts": 0, "args": {"name": "serve"}},
    ]
    spans: list[dict] = []
    for e in events:
        kind = e.get("kind")
        ts = e.get("ts")
        if not isinstance(ts, (int, float)):
            continue
        if kind in ("phase", "compile", "pp_bubble"):
            secs = e.get("secs")
            if not isinstance(secs, (int, float)):
                continue
            phase = e.get("phase") or kind
            tid = TID_SERVE if phase in _SERVE_PHASES else TID_TRAIN
            args = {k: e[k] for k in ("step", "id", "ids", "tokens")
                    if e.get(k) is not None}
            # the phase event is stamped at phase END; back out the start
            spans.append({"name": phase, "ph": "X", "pid": pid,
                          "tid": tid, "ts": (ts - secs - ts0) * 1e6,
                          "dur": max(secs, 0.0) * 1e6,
                          **({"args": args} if args else {})})
        elif kind in _INSTANT_KINDS:
            args = {k: v for k, v in e.items()
                    if k not in ("ts", "kind")
                    and isinstance(v, (int, float, str, bool))}
            spans.append({"name": kind, "ph": "i", "s": "p", "pid": pid,
                          "tid": TID_TRAIN, "ts": (ts - ts0) * 1e6,
                          **({"args": args} if args else {})})
    spans.sort(key=lambda ev: ev["ts"])
    return {"traceEvents": out + spans, "displayTimeUnit": "ms"}


def validate(path: str) -> list[str]:
    """Self-check a Chrome-trace JSON; returns violation strings."""
    errors: list[str] = []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"unreadable trace: {e}"]
    events = doc.get("traceEvents") if isinstance(doc, dict) else doc
    if not isinstance(events, list):
        return ["trace has no traceEvents list"]
    last_ts: dict[tuple, float] = {}
    stacks: dict[tuple, list] = {}
    prev_global = None
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in _VALID_PH:
            errors.append(f"event {i}: invalid ph {ph!r}")
            continue
        if ph == "M":
            continue
        pid, tid, ts = ev.get("pid"), ev.get("tid"), ev.get("ts")
        if not isinstance(pid, int) or not isinstance(tid, int):
            errors.append(f"event {i} ({ev.get('name')!r}): "
                          f"pid/tid must be integers, got "
                          f"pid={pid!r} tid={tid!r}")
            continue
        if not isinstance(ts, (int, float)):
            errors.append(f"event {i} ({ev.get('name')!r}): missing ts")
            continue
        if prev_global is not None and ts < prev_global - 1e-6:
            errors.append(f"event {i} ({ev.get('name')!r}): ts {ts} "
                          f"not monotonic (prev {prev_global})")
        prev_global = ts
        lane = (pid, tid)
        if ts < last_ts.get(lane, float("-inf")) - 1e-6:
            errors.append(f"event {i}: ts rewinds on lane {lane}")
        last_ts[lane] = ts
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"event {i} ({ev.get('name')!r}): X event "
                              f"needs dur >= 0, got {dur!r}")
        elif ph == "B":
            stacks.setdefault(lane, []).append((i, ev.get("name")))
        elif ph == "E":
            stack = stacks.get(lane) or []
            if not stack:
                errors.append(f"event {i}: E without matching B on "
                              f"lane {lane}")
            else:
                _, bname = stack.pop()
                ename = ev.get("name")
                if ename is not None and ename != bname:
                    errors.append(f"event {i}: E name {ename!r} does "
                                  f"not match open B {bname!r}")
    for lane, stack in stacks.items():
        for i, name in stack:
            errors.append(f"event {i} ({name!r}): B never closed on "
                          f"lane {lane}")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="telemetry.jsonl -> Chrome trace, or --validate a "
                    "trace file")
    ap.add_argument("path", help="telemetry.jsonl / run dir (convert "
                    "mode) or a trace JSON (--validate)")
    ap.add_argument("-o", "--output", default=None,
                    help="output trace path (convert mode; default "
                         "<input dir>/trace.json)")
    ap.add_argument("--validate", action="store_true",
                    help="self-check a trace file instead of converting")
    ap.add_argument("--pid", type=int, default=0,
                    help="process id to stamp on converted events")
    args = ap.parse_args(argv)

    if args.validate:
        errors = validate(args.path)
        if errors:
            for e in errors[:50]:
                print(f"TRACE VIOLATION: {e}", file=sys.stderr)
            print(f"{len(errors)} violation(s) in {args.path}",
                  file=sys.stderr)
            return 1
        with open(args.path) as f:
            doc = json.load(f)
        events = doc.get("traceEvents") if isinstance(doc, dict) else doc
        lanes = {(e.get("pid"), e.get("tid")) for e in events
                 if e.get("ph") != "M"}
        print(f"OK: {len(events)} events across {len(lanes)} lane(s) "
              f"in {args.path}")
        return 0

    src = resolve_jsonl(args.path)
    events = load_events(src)
    if not events:
        print(f"no events in {src}", file=sys.stderr)
        return 1
    doc = convert(events, pid=args.pid)
    out = args.output or os.path.join(os.path.dirname(src) or ".",
                                      "trace.json")
    with open(out, "w") as f:
        json.dump(doc, f)
    n = len(doc["traceEvents"])
    print(f"{n} trace events -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
