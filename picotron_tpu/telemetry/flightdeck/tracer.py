"""Span tracer: an in-process Chrome-trace/Perfetto timeline.

One ``SpanTracer`` per process records complete spans (``ph="X"``) and
instant events (``ph="i"``) into a bounded in-memory list, exported as
the Chrome trace-event JSON format (the ``{"traceEvents": [...]}``
container Perfetto and chrome://tracing both load). Timestamps are
microseconds on the tracer's own monotonic clock, zeroed at
construction, so one export is one self-consistent timeline.

Thread-lane (``tid``) convention, kept stable so traces from different
runs line up:

* 0            train-loop phases (``train.data`` / ``train.step`` / ...)
* 1            the serve engine's step (``serve.step`` and its leaf spans,
               tagged with request ids; ``serve.queue_wait`` per request)
* 2            sentinel / flightdeck bookkeeping instants
* 100 + stage  MPMD pipeline stage lanes (one per local stage), carrying
               the per-op tick spans ``pp.<stage>.<op>`` with tick and
               microbatch as arguments — the same coordinates the
               watchdog's last-touch string uses.

The tracer is deliberately dumb: no flow events, and nesting is
containment on one lane (a span that lies inside another on the same
``tid`` is its child, which is how Perfetto draws them). Spans reach it
through ``telemetry/spans.py``'s ``Span``, which records a region where it
starts and ends; a span is one dict append under a lock.
"""

from __future__ import annotations

import json
import os
import threading
import time

TID_TRAIN = 0
TID_SERVE = 1
TID_SENTINEL = 2
TID_PP_BASE = 100

_THREAD_NAMES = {
    TID_TRAIN: "train",
    TID_SERVE: "serve",
    TID_SENTINEL: "flightdeck",
}


class SpanTracer:
    """Bounded in-memory trace-event recorder.

    ``max_events`` caps memory on long runs: past the cap new events are
    counted in ``dropped`` instead of recorded (the export notes the
    drop count so a truncated trace is never mistaken for a quiet one).
    """

    def __init__(self, pid: int = 0, clock=time.perf_counter,
                 max_events: int = 500_000):
        self.pid = int(pid)
        self.clock = clock
        self._t0 = clock()
        self._events: list[dict] = []
        self._meta: dict[int, dict] = {}
        self._lock = threading.Lock()
        self.max_events = int(max_events)
        self.dropped = 0

    # -- recording ---------------------------------------------------

    def now(self) -> float:
        """Current time on the tracer's clock (seconds)."""
        return self.clock()

    def complete(self, name: str, tid: int = TID_TRAIN, *,
                 start_s: float, dur_s: float, **args) -> None:
        """Record a complete span (``ph="X"``) that started at ``start_s``
        on the tracer's clock domain (``tracer.now()``) and lasted
        ``dur_s``. A span is recorded where it started and ended
        (telemetry/spans.py reads the clock at both): there is no
        back-dating from the moment of the call.
        """
        ev = {"name": name, "ph": "X", "pid": self.pid, "tid": int(tid),
              "ts": (start_s - self._t0) * 1e6,
              "dur": max(dur_s, 0.0) * 1e6}
        if args:
            ev["args"] = args
        self._push(tid, ev)

    def instant(self, name: str, tid: int = TID_TRAIN, **args) -> None:
        """Record an instant event (``ph="i"``, process scope)."""
        ev = {"name": name, "ph": "i", "s": "p", "pid": self.pid,
              "tid": int(tid), "ts": (self.clock() - self._t0) * 1e6}
        if args:
            ev["args"] = args
        self._push(tid, ev)

    def thread_name(self, tid: int, name: str) -> None:
        """Label a lane (metadata event, emitted first in the export)."""
        with self._lock:
            self._meta[int(tid)] = {
                "name": "thread_name", "ph": "M", "pid": self.pid,
                "tid": int(tid), "ts": 0, "args": {"name": name}}

    def _push(self, tid: int, ev: dict) -> None:
        with self._lock:
            if int(tid) not in self._meta:
                label = _THREAD_NAMES.get(int(tid))
                if label is None and int(tid) >= TID_PP_BASE:
                    label = f"pp_stage{int(tid) - TID_PP_BASE}"
                if label is not None:
                    self._meta[int(tid)] = {
                        "name": "thread_name", "ph": "M",
                        "pid": self.pid, "tid": int(tid), "ts": 0,
                        "args": {"name": label}}
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(ev)

    # -- snapshots (flight recorder) ---------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def mark(self) -> int:
        """Watermark for ``since`` — events recorded so far."""
        with self._lock:
            return len(self._events)

    def since(self, mark: int) -> list[dict]:
        """Copy of events recorded after a ``mark()`` watermark."""
        with self._lock:
            return list(self._events[mark:])

    # -- export ------------------------------------------------------

    def to_json(self) -> dict:
        """Chrome-trace document: metadata lanes first, spans sorted by
        timestamp (Perfetto tolerates unsorted input; the validator and
        humans prefer not to)."""
        with self._lock:
            meta = [self._meta[t] for t in sorted(self._meta)]
            # a parent that starts with its first child comes first
            events = sorted(self._events,
                            key=lambda e: (e["ts"], -e.get("dur", 0.0)))
            dropped = self.dropped
        doc = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        if dropped:
            doc["otherData"] = {"dropped_events": dropped}
        return doc

    def export(self, path: str) -> str:
        """Atomically write the trace JSON; returns the path."""
        doc = self.to_json()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path
