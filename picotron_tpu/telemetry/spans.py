"""The one span: a region of host code, on the profiler's clock.

`Span` is the only tracing mechanism in the program. Entering it opens a
`jax.profiler.TraceAnnotation` under the span's name, so that whenever a
profile is open (`logging.profile_dir`, the benchmark's `--trace 1`) the
region lies in the profiler's own trace beside the device planes, with its
counts as the annotation's keyword arguments. When a `SpanTracer` is
installed the same region is recorded there from the same two clock reads:
where it started and where it ended, never back-dated from a later emit.
With neither a profile nor a tracer a span costs two clock reads and an
inert annotation (under a microsecond) and records nothing. A caller that
adds up its own regions hands the span a list (`into`): the span appends
`(name, start, secs)` there when it ends, from the same two clock reads.

Nesting on one thread is the parent relation. Spans of one request carry
its `id`; a dispatch that serves several carries `ids`, joined by spaces
(an annotation's values are numbers or strings, and a comma ends one).
"""

from __future__ import annotations

import time

import jax

from picotron_tpu.telemetry import bus
from picotron_tpu.telemetry.flightdeck.tracer import TID_TRAIN


class Span:
    __slots__ = ("name", "tid", "counts", "secs", "_tracer", "_clock",
                 "_ann", "_t0", "_into")

    def __init__(self, name: str, tracer=None, tid: int = TID_TRAIN,
                 into=None, **counts):
        self.name = name
        self.tid = tid
        self.counts = {k: v for k, v in counts.items() if v is not None}
        self.secs = 0.0  # the region's duration, once it has ended
        self._tracer = tracer
        self._into = into  # the owner's list of (name, start, secs), or None
        self._clock = tracer.clock if tracer is not None else time.perf_counter

    def set(self, **counts) -> None:
        """Counts known only at the region's end (tokens emitted, requests
        admitted): taken at the same boundary as the work they count."""
        self.counts.update(counts)
        self._ann.set_metadata(**counts)

    def so_far(self):
        """(start, seconds since) on the span's clock while the region
        runs: for an owner whose account of the region has to ride the
        span itself, and so be made before the span ends."""
        return self._t0, self._clock() - self._t0

    def __enter__(self) -> "Span":
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.counts)
        self._ann.__enter__()
        self._t0 = self._clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.secs = self._clock() - self._t0
        self._ann.__exit__(*exc)
        if self._into is not None:
            self._into.append((self.name, self._t0, self.secs))
        if self._tracer is not None:
            self._tracer.complete(self.name, tid=self.tid, start_s=self._t0,
                                  dur_s=self.secs, **self.counts)
        return False


def span(name: str, tid: int = TID_TRAIN, **counts) -> Span:
    """A span from library code that holds no `Telemetry`: it reaches the
    active facade's tracer through the bus, and still annotates when there
    is none."""
    tel = bus.active()
    return Span(name, getattr(tel, "tracer", None), tid, **counts)


def join_ids(ids) -> str:
    """Request ids as one annotation value."""
    return " ".join(str(int(i)) for i in ids)
