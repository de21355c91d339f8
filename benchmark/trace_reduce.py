"""From a profiler trace (`*.xplane.pb`) to the numbers the benchmark reports.

One reduction, kept with the benchmark, so that every PR computes the same
number the same way: for each device the union of the intervals in which an
operation ran (busy), the idle share of the traced window, self time by
operation name, and the idle gaps with the harness span that covers each.

The reduction works on plain tuples so that it can be checked on a synthetic
trace (`benchmark/tests/test_trace_reduce.py`); `load_xplane` is the only
function that touches JAX.

A trace is a list of planes `(plane_name, [(line_name, [(event_name,
start_ns, duration_ns), ...]), ...])`. Device planes are named
`/device:TPU:<n>`; their `XLA Ops` line holds one event per executed HLO
operation, nested where an operation (a `while`, a fusion's parent) contains
others. Host planes (`/host:CPU`) hold one line per thread; the harness's
`jax.profiler.TraceAnnotation`s are events there under the names it gave.
"""

from __future__ import annotations

import glob
import os
import re

# A device event is named by its whole HLO instruction
# ("%fusion.370 = (f32[4096,151936]{...}, ...) fusion(...)"): the name is the
# part before " = "; the first result shape is kept beside it for the reader
# of a breakdown, since "fusion.370" alone says nothing.
HLO = re.compile(r"^%?(?P<name>[^ ]+) = \(?(?P<shape>[a-z0-9]+\[[0-9,]*\])?")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# Lines of a device plane that are not operations: step and module markers
# span idle time too, so they may not count as busy.
NOT_OPS = re.compile(r"^(Steps|XLA Modules|XLA TraceMe|Framework|Source)", re.I)


def load_xplane(trace_dir: str):
    """The newest `*.xplane.pb` under `trace_dir` as a list of planes."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(e.name, float(e.start_ns), float(e.duration_ns))
                                      for e in line.events]))
        planes.append((plane.name, lines))
    return planes


def short_name(event_name: str):
    """(name, first result shape or '') of a device event's name."""
    m = HLO.match(event_name)
    return (m.group("name"), m.group("shape") or "") if m else (event_name, "")


def union(intervals):
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def complement(busy, lo, hi):
    """The gaps of a sorted disjoint `busy` inside [lo, hi]."""
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def self_times(events):
    """Self time and call count by name for nested events of one line: an
    event's self time is its duration less the part its children cover."""
    secs, calls = {}, {}
    stack = []  # (end, name, self_ns as a one-item list)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            # charge the child's (clipped) extent against its parent
            stack[-1][2][0] -= min(end, stack[-1][0]) - start
        own = [dur]
        stack.append((end, name, own))
        calls[name] = calls.get(name, 0) + 1
        secs.setdefault(name, []).append(own)
    return ({n: max(sum(o[0] for o in owns), 0.0) / 1e9 for n, owns in secs.items()},
            calls)


def device_ops(planes):
    """{device id: [(name, start, dur), ...]} from each device plane's
    operations line (or, where no line has that name, every line that is
    not a step or module marker)."""
    out = {}
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        if not m:
            continue
        named = [ev for lname, ev in lines if lname == OPS_LINE]
        if not named:
            named = [ev for lname, ev in lines if not NOT_OPS.match(lname)]
        out[int(m.group(1))] = [e for ev in named for e in ev]
    return out


def shapes_of(events):
    """{short name: first result shape} for the events that carry one."""
    return {n: sh for n, sh in (short_name(e[0]) for e in events) if sh}


def host_spans(planes, names):
    """[(name, start, end)] of host-plane events whose name is in `names`."""
    names = set(names)
    out = []
    for pname, lines in planes:
        if not pname.startswith("/host:"):
            continue
        for _, events in lines:
            out.extend((n, s, s + d) for n, s, d in events if n in names)
    return sorted(out, key=lambda x: x[1])


def attribute(gaps, spans):
    """For each gap (sorted, disjoint) the name of the span that overlaps it
    most ('' where none does). `spans` are the harness's leaf phases: sorted
    and disjoint, so one sweep does it."""
    out, i = [], 0
    for g in gaps:
        while i < len(spans) and spans[i][2] <= g[0]:
            i += 1
        best, best_ov, j = "", 0.0, i
        while j < len(spans) and spans[j][1] < g[1]:
            ov = min(spans[j][2], g[1]) - max(spans[j][1], g[0])
            if ov > best_ov:
                best, best_ov = spans[j][0], ov
            j += 1
        out.append(best)
    return out


def reduce(planes, span_names=(), window_span: str = "bench.window"):
    """The whole reduction. The traced window is the harness's
    `window_span` annotation where the trace has it and it overlaps the
    device's events; `span_names` are the harness's leaf phases (disjoint) (host and device share a clock in the traces of this
    installation; if they ever do not, the window falls back to the extent
    of the device's own events and `window_from` says so)."""
    ops = device_ops(planes)
    if not ops or not any(ops.values()):
        return None
    wins = host_spans(planes, [window_span])
    spans = host_spans(planes, span_names)
    devices = {}
    for dev, events in sorted(ops.items()):
        if not events:
            continue
        busy_all = union((s, s + d) for _, s, d in events)
        lo, hi = busy_all[0][0], busy_all[-1][1]
        window_from = "device_events"
        if wins:
            wlo, whi = wins[0][1], wins[-1][2]
            if total(clip(busy_all, wlo, whi)) > 0.0:
                lo, hi, window_from = wlo, whi, window_span
        busy = clip(busy_all, lo, hi)
        devices[dev] = dict(busy_s=total(busy) / 1e9, window_s=(hi - lo) / 1e9,
                            window_from=window_from, busy=busy, lo=lo, hi=hi)
    first = min(devices)
    d0 = devices[first]
    # operations and gaps are those of the first device only
    op_s, op_n = self_times([(short_name(n)[0], s, d) for n, s, d in ops[first]
                             if s + d > d0["lo"] and s < d0["hi"]])
    gaps = complement(d0["busy"], d0["lo"], d0["hi"])
    by_span = {}
    longest = []
    for g, name in zip(gaps, attribute(gaps, spans)):
        name = name or "(no harness span)"
        by_span[name] = by_span.get(name, 0.0) + (g[1] - g[0]) / 1e9
        longest.append((name, (g[1] - g[0]) / 1e9))
    longest.sort(key=lambda x: -x[1])
    n = len(devices)
    return dict(
        busy_s=sum(d["busy_s"] for d in devices.values()) / n,
        window_s=sum(d["window_s"] for d in devices.values()) / n,
        per_device={k: dict(busy_s=d["busy_s"], window_s=d["window_s"],
                            window_from=d["window_from"]) for k, d in devices.items()},
        first_device=first,
        op_seconds=op_s, op_calls=op_n, op_shapes=shapes_of(ops[first]),
        idle_by_span=sorted(by_span.items(), key=lambda x: -x[1]),
        longest_gaps=longest[:10],
        n_gaps=len(gaps),
    )


def breakdown(red, top: int = 10):
    """The `breakdown` of the result line: the operations with most self
    time on the first device, and the idle time by covering harness span."""
    ops = sorted(red["op_seconds"].items(), key=lambda x: -x[1])[:top]
    shapes = red.get("op_shapes", {})
    return dict(device_ops=[[f"{n} {shapes[n]}" if n in shapes else n, s] for n, s in ops],
                idle_gaps=[[n, s] for n, s in red["idle_by_span"][:top]])
