"""Structured telemetry: registry, sinks, phase timing, goodput ledger.

The observability substrate the training driver, bench harness, and
resilience machinery report through. One `Telemetry` facade owns:

- a `MetricsRegistry` (counters / gauges / p50-p95 histograms),
- the sink fan-out — stdout (the frozen log-line format
  tools/extract_metrics.py parses), a per-host `telemetry.jsonl` event
  stream next to the checkpoints, and the wandb adapter (rollback-safe:
  monotonic event counter, step as a field),
- a `PhaseTimer` that wraps the step loop's sections AND is the
  watchdog's heartbeat source — timing and liveness share one clock,
- a `GoodputLedger` classifying every accounted second (compute vs
  compile / ckpt I/O / restore+replay / preemption drain / retry backoff
  / data stall / ...), fed by the phases and by events the resilience
  modules emit through `telemetry.bus`,
- a `CompileWatch` (jax.monitoring) that measures XLA compile time
  exactly and flags unexpected re-jits of the step,
- `span(name, **counts)` (telemetry/spans.py), the program's one tracing
  mechanism: a region of host code as a `jax.profiler.TraceAnnotation`
  (in the profiler's trace, beside the device, whenever a profile is
  open) and, with a flightdeck `SpanTracer` installed, a span recorded
  where it starts and ends. `telemetry/scopes.py` holds the names of the
  device programs' regions (`jax.named_scope`).

Post-hoc: `tools/telemetry_report.py` summarizes a JSONL stream (goodput
%, phase breakdown, event counts) for run triage; the per-phase category
mapping is shared so in-process and post-hoc accounting agree.

JSONL schema (one object per line; `ts` = time.time()):

  {"ts", "kind": "phase", "phase", "step", "secs", "category"}
      # serve: phase queue_wait | prefill | decode with id / ids,
      # tokens; prefill carries `waited` (whether `secs` includes the
      # host's wait for the device or only the enqueue); phase serve_host,
      # one an engine step with device work: `secs` the step's seconds
      # with nothing enqueued on the device (serve/engine.py step_account);
      # phase serve_dry beside it, with NO category (its seconds lie inside
      # the prefill and decode phases'): the step's period's seconds in
      # which a probe had seen everything enqueued finished
  {"ts", "kind": "step",  "step", "loss", "tokens_per_sec",
   "tokens_per_sec_per_chip", "mfu", "trained_tokens", "memory_gb", ...}
  {"ts", "kind": "eval",  "step", "val_loss"}
  {"ts", "kind": <event>, ...}        # retry / chaos / guard / preempt /
                                      # recompile / watchdog_timeout ...
  {"ts", "kind": "serve_slow_step", "held_by", "held_s", "limit_s",
   "held_for", "ready", "next_ready", "wall_s", "starved_s", "unspanned_ms",
   "leaves_ms", "starved_by_ms", "dry_by_ms",
   "compile_s", "blocks_freed", "gc_before", "gc_after", "active", "queued",
   "engine"}
      # an engine step one of whose parts (a wait, by the `held_for`
      # dispatches it cleared, or `host`: the rest of the wall) is far
      # over the median of its own kind; of a wait, whether its dispatch
      # had finished when it began and the one behind it when it ended
  {"ts", "kind": "run_summary", "goodput": {...}, "metrics": {...}}
"""

from __future__ import annotations

import time
from typing import Optional

from picotron_tpu.telemetry import bus
from picotron_tpu.telemetry.flightdeck.tracer import TID_TRAIN
from picotron_tpu.telemetry.goodput import (
    CATEGORIES, GOODPUT_CATEGORIES, PHASE_CATEGORY, GoodputLedger,
)
from picotron_tpu.telemetry.phases import PhaseTimer
from picotron_tpu.telemetry.recompile import CompileWatch
from picotron_tpu.telemetry.registry import (
    Counter, Gauge, Histogram, MetricsRegistry,
)
from picotron_tpu.telemetry.sinks import (
    JsonlSink, Sink, StdoutSink, WandbSink, telemetry_jsonl_path,
)
from picotron_tpu.telemetry.spans import Span, span

__all__ = [
    "CATEGORIES",
    "GOODPUT_CATEGORIES",
    "PHASE_CATEGORY",
    "CompileWatch",
    "Counter",
    "Gauge",
    "GoodputLedger",
    "Histogram",
    "JsonlSink",
    "MetricsRegistry",
    "PhaseTimer",
    "Sink",
    "Span",
    "StdoutSink",
    "Telemetry",
    "WandbSink",
    "bus",
    "span",
    "telemetry_jsonl_path",
]

# Resilience/fault event kinds rendered as trace instants so a timeline
# shows the fault next to the phase it interrupted.
_INSTANT_KINDS = frozenset((
    "chaos", "guard", "rollback", "preempted", "preempt_signal",
    "watchdog_timeout", "elastic_resize", "recompile", "retry",
    "sentinel_alert", "slice_lost"))


class Telemetry:
    """Facade wiring registry + sinks + phases + ledger + compile watch.

    Constructed once per run (train.main / bench), installed on the bus so
    library code reaches it, closed in the driver's teardown (writes the
    run_summary event). Sinks may be attached late (wandb initializes
    after the config banner; the watchdog after the resilience block) —
    everything else works from the first emitted event.
    """

    def __init__(self, sinks: Optional[list] = None, watchdog=None,
                 compile_watch: Optional[CompileWatch] = None):
        self.registry = MetricsRegistry()
        self.ledger = GoodputLedger()
        self.sinks: list = list(sinks or [])
        self.compile_watch = (compile_watch if compile_watch is not None
                              else CompileWatch().install())
        self.phases = PhaseTimer(self._phase_done, watchdog=watchdog,
                                 on_enter=self._phase_enter,
                                 on_section=self._section_done,
                                 span=self.span)
        self._step_phases_done = 0
        # Analytic pipeline-bubble share of each step phase (from the
        # schedule table, parallel/mpmd.pipeline_bubble_fraction) —
        # installed by the driver once per run, 0.0 when pp is off.
        self.pp_bubble_fraction = 0.0
        # flightdeck attachments (telemetry/flightdeck): all nullable —
        # the hot-path hooks below are a single `is not None` check when
        # a piece is absent, allocating nothing.
        self.tracer = None          # SpanTracer
        self.flight = None          # FlightRecorder
        self.sentinel = None        # DriftSentinel
        self.trace_path = None      # where close() exports the trace
        self._closed = False
        # Anchor the stream's wall-clock: compiles/setup before the first
        # phase would otherwise make the report's `accounted` exceed its
        # observed `wall`.
        self._fan_out({"ts": time.time(), "kind": "run_start"})

    # -- construction ------------------------------------------------------

    @classmethod
    def from_config(cls, cfg, watchdog=None) -> "Telemetry":
        import jax  # local: keep the package importable without a backend

        is_primary = jax.process_index() == 0
        sinks: list = [StdoutSink(is_primary=is_primary)]
        path = telemetry_jsonl_path(cfg, jax.process_index())
        if path is not None:
            max_mb = float(getattr(cfg.logging, "telemetry_max_mb", 0.0)
                           or 0.0)
            sinks.append(JsonlSink(
                path,
                max_bytes=int(max_mb * 1e6) if max_mb > 0 else None))
        tel = cls(sinks=sinks, watchdog=watchdog)
        from picotron_tpu.telemetry import flightdeck

        flightdeck.install(tel, cfg,
                           process_index=jax.process_index())
        return tel

    def attach_watchdog(self, watchdog) -> None:
        self.phases.watchdog = watchdog

    def set_pp_bubble_fraction(self, fraction: float) -> None:
        """Install the analytic pipeline-bubble share (schedule-table
        fraction of each step's wall spent in fill/drain idle). Every
        subsequent step phase carves this share of its compute into the
        `pp_bubble` ledger category."""
        self.pp_bubble_fraction = min(max(float(fraction), 0.0), 1.0)

    def attach_wandb(self, run) -> "WandbSink":
        sink = WandbSink(run)
        self.sinks.append(sink)
        return sink

    @property
    def jsonl_path(self) -> Optional[str]:
        for s in self.sinks:
            if isinstance(s, JsonlSink):
                return s.path
        return None

    # -- event plumbing ----------------------------------------------------

    def emit(self, kind: str, *, category: Optional[str] = None,
             secs: Optional[float] = None, book: bool = True,
             **fields) -> None:
        """Emit one event. `category` + `secs` book the time into the
        goodput ledger unless `book=False` (phase events arrive already
        booked by book_phase — re-booking would double-count)."""
        self.registry.counter(f"events/{kind}").inc()
        if book and category is not None and secs is not None:
            self.ledger.book(category, secs)
        event = {"ts": time.time(), "kind": kind, **fields}
        if category is not None:
            event["category"] = category
        if secs is not None:
            event["secs"] = round(secs, 6)
        self._fan_out(event)
        if self.tracer is not None:
            self._trace_event(kind, secs, fields)
        if self.flight is not None:
            if kind == "phase":
                self.flight.on_phase(fields.get("phase") or "?",
                                     secs or 0.0,
                                     step=fields.get("step"))
            elif kind not in ("compile", "pp_bubble"):
                self.flight.on_event(kind, fields)
        if self.sentinel is not None and kind == "phase" \
                and isinstance(secs, (int, float)):
            self.sentinel.observe_phase(fields.get("phase") or "", secs)

    def span(self, name: str, tid: int = TID_TRAIN, into=None,
             **counts) -> Span:
        """A region of host code as a `TraceAnnotation` and, with a tracer
        installed, a span on lane `tid`; `into` is the caller's own list,
        which gets `(name, start, secs)` when the region ends
        (telemetry/spans.py)."""
        return Span(name, self.tracer, tid, into, **counts)

    def record_wait(self, name: str, wait_s: float, tid: int = TID_TRAIN,
                    **counts) -> None:
        """A wait that just ended (a request's time in the queue): not a
        region of code, so it has no annotation; the tracer gets it with
        its start (now less the wait) and its end (now)."""
        tr = self.tracer
        if tr is not None:
            tr.complete(name, tid=tid, start_s=tr.now() - wait_s,
                        dur_s=wait_s, **counts)

    def _trace_event(self, kind: str, secs, fields: dict) -> None:
        """Route one bus event onto the span timeline as an instant:
        resilience/fault kinds, and compiles (jax.monitoring reports a
        compile's duration once it is over, not where it started, so it
        is an instant carrying `secs`). Phase events are not spans: the
        regions they time are recorded by `span` where they run."""
        tr = self.tracer
        if kind == "compile" and isinstance(secs, (int, float)):
            args = ({"step": fields["step"]}
                    if fields.get("step") is not None else {})
            tr.instant("compile", tid=TID_TRAIN, secs=round(secs, 6), **args)
        elif kind in _INSTANT_KINDS:
            args = {k: v for k, v in fields.items()
                    if isinstance(v, (int, float, str, bool))}
            tr.instant(kind, tid=TID_TRAIN, **args)

    def _fan_out(self, event: dict) -> None:
        for sink in self.sinks:
            try:
                sink.emit(event)
            except Exception:  # noqa: BLE001 — a sick sink must not kill a step
                pass

    def _phase_enter(self, name: str, step) -> None:
        """Drain compiles that accrued OUTSIDE any phase (jit init /
        warm-up between loop sections) before this phase's clock starts —
        left in the accumulator they would be drained at this phase's END
        and clamped against its wall, silently eating the phase (the
        sigterm-resume restore was booked as 0 this way)."""
        n_compiles, compile_secs = self.compile_watch.drain()
        if n_compiles:
            self.registry.counter("compile/count").inc(n_compiles)
            self.emit("compile", category="compile", secs=compile_secs,
                      phase=None, step=step, compiles=n_compiles)

    def _phase_done(self, name: str, secs: float, step) -> None:
        """PhaseTimer callback: drain exact compile time, book the ledger,
        feed the histograms, emit the phase event(s). The phase event's
        `secs` carries the NON-compile remainder and the compile share
        rides its own category="compile" event, so a post-hoc sum of
        (category, secs) pairs over the JSONL reproduces the ledger."""
        n_compiles, compile_secs = self.compile_watch.drain()
        compile_secs = min(compile_secs, max(secs, 0.0))
        bubble_secs = 0.0
        if name == "step" and self.pp_bubble_fraction > 0.0:
            bubble_secs = self.pp_bubble_fraction * max(
                secs - compile_secs, 0.0)
        category = self.ledger.book_phase(name, secs, step=step,
                                          compile_secs=compile_secs,
                                          bubble_secs=bubble_secs)
        if category != "compute":
            bubble_secs = 0.0  # ledger carves compute only (replay etc.)
        self.registry.histogram(f"phase/{name}").observe(secs)
        if n_compiles:
            self.registry.counter("compile/count").inc(n_compiles)
            self.emit("compile", category="compile", secs=compile_secs,
                      book=False, phase=name, step=step,
                      compiles=n_compiles)
            if name == "step" and self._step_phases_done > 0:
                # Re-jit of an already-compiled step: shape/dtype/weak-type
                # drift — exactly what analysis/hazards.py lints statically.
                self.registry.counter("compile/unexpected_recompiles").inc(
                    n_compiles)
                self.emit("recompile", step=step, compiles=n_compiles,
                          compile_secs=round(compile_secs, 6))
        if name == "step":
            self._step_phases_done += 1
        if bubble_secs > 0.0:
            # the bubble share rides its own category="pp_bubble" event
            # (like compile) so the JSONL (category, secs) sum still
            # reproduces the ledger exactly
            self.emit("pp_bubble", category="pp_bubble", secs=bubble_secs,
                      book=False, phase=name, step=step)
        self.emit("phase", category=category,
                  secs=secs - compile_secs - bubble_secs,
                  book=False, phase=name, step=step)

    def _section_done(self, name: str, secs: float, step) -> None:
        """PhaseTimer section callback: histogram only (see
        PhaseTimer.section for why sections never touch the ledger)."""
        self.registry.histogram(f"section/{name}").observe(secs)

    def observe_section(self, name: str, secs: float) -> None:
        """Record an externally-measured section duration (e.g. the MPMD
        executor's per-stage tick times, timed inside the schedule walker
        where a context manager cannot reach)."""
        self.registry.histogram(f"section/{name}").observe(secs)

    # -- step / eval records ----------------------------------------------

    def record_step(self, step: int, line: str, **fields) -> None:
        """One training-log record: the preformatted console `line` goes to
        stdout byte-identically; the structured fields go to JSONL/wandb."""
        self._fan_out({"ts": time.time(), "kind": "step", "step": step,
                       "line": line, **fields})
        if self.flight is not None:
            self.flight.on_step(step, fields)
        if self.sentinel is not None:
            alert = self.sentinel.on_step(step)
            if alert is not None:
                self.emit("sentinel_alert", **alert)
                if self.flight is not None:
                    self.flight.dump("sentinel_alert",
                                     step=alert.get("step", step),
                                     alert=alert)

    def record_eval(self, step: int, val_loss: float, line: str) -> None:
        self._fan_out({"ts": time.time(), "kind": "eval", "step": step,
                       "val_loss": val_loss, "line": line})

    # -- teardown ----------------------------------------------------------

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        summary = {"ts": time.time(), "kind": "run_summary",
                   "goodput": self.ledger.summary(),
                   "metrics": self.registry.snapshot()}
        if self.sentinel is not None:
            summary["sentinel"] = self.sentinel.stats()
        self._fan_out(summary)
        if self.tracer is not None and self.trace_path:
            try:
                self.tracer.export(self.trace_path)
            except Exception:  # noqa: BLE001 — teardown must not raise
                pass
        self.compile_watch.uninstall()
        for sink in self.sinks:
            try:
                sink.close()
            except Exception:  # noqa: BLE001
                pass
        if bus.active() is self:
            bus.install(None)
