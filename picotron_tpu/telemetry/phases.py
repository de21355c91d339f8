"""Step-phase timer: one clock for timing AND watchdog liveness.

PR 2 interleaved `watchdog.beat(phase, step)` calls with ad-hoc wall-clock
reads; the two could drift (a new loop section timed but never beating, or
beating but invisible to timing). The phase timer is the single source:
entering a phase beats the watchdog with that phase name, leaving it hands
the measured duration to a callback (the Telemetry facade books it into
the histogram registry + goodput ledger and emits the JSONL phase event).
The region itself is a `telemetry.spans.Span` named `train.<phase>`, so a
`logging.profile_dir` capture shows data / step / sync / save beside the
device, and the duration handed on is that span's own.
A section that exists for the timer therefore cannot be missed by the
watchdog, and vice versa.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Callable, Optional

from picotron_tpu.telemetry.spans import span as bus_span


class PhaseTimer:
    def __init__(self, on_phase: Callable[[str, float, Optional[int]], None],
                 watchdog=None,
                 on_enter: Optional[Callable[[str, Optional[int]], None]]
                 = None,
                 on_section: Optional[
                     Callable[[str, float, Optional[int]], None]] = None,
                 span: Callable = bus_span):
        self._on_phase = on_phase
        self._on_enter = on_enter
        self._on_section = on_section
        self._span = span  # (name, **counts) -> telemetry.spans.Span
        self.watchdog = watchdog

    @contextmanager
    def phase(self, name: str, step: Optional[int] = None):
        """Time one loop section. Beats the watchdog on ENTRY (the beat
        must land before the potentially-hanging work, not after) and
        books the duration on exit — including the exceptional exit, so a
        phase that dies mid-flight still accounts for the time it burned
        before the exception unwound. `on_enter` fires before the clock
        starts (the facade uses it to drain compile time that accrued
        OUTSIDE any phase, so it cannot be mis-attributed to this one)."""
        if self.watchdog is not None:
            self.watchdog.beat(name, step)
        if self._on_enter is not None:
            self._on_enter(name, step)
        sp = self._span(f"train.{name}", step=step)
        try:
            with sp:
                yield
        finally:
            self._on_phase(name, sp.secs, step)

    @contextmanager
    def section(self, name: str, step: Optional[int] = None):
        """Time a sub-span INSIDE a phase (a pipeline stage's ticks, a
        loss post-process). Sections feed the histogram registry only:
        no watchdog beat (the enclosing phase already armed it) and no
        ledger booking (their wall is part of the enclosing phase — a
        second booking would double-count the same seconds)."""
        sp = self._span(f"train.{name}", step=step)
        try:
            with sp:
                yield
        finally:
            if self._on_section is not None:
                self._on_section(name, sp.secs, step)
