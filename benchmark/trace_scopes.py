"""What `trace_reduce.load_xplane` drops from a profiler trace, for the
metrics that read the program's own names: each executed *program* (the
device plane's `XLA Modules` line, one event per execution under the jitted
function's name), each device operation's *named scopes*
(`picotron_tpu/telemetry/scopes.py`), and the host plane's *annotations*
with their keyword arguments (`picotron_tpu/telemetry/spans.py`).

Where each is found, read off a real trace of this installation (JAX 0.9.0,
libtpu 0.0.34, TPU v5 lite; PR 25):

- a program: line `XLA Modules` of `/device:TPU:<n>`, event name
  `jit_<function>(<fingerprint>)`; the program's name is the part before
  the `(` without the `jit_`.
- a scope: the `tf_op` stat of the operation's *event metadata* (not of the
  event), `jit(train_step)/head_ce/dot_general:` = the instruction's
  `op_name` and a colon. `jax.profiler.ProfileData` hands out an event's own
  stats (`device_offset_ps`, `device_duration_ps`) and not its metadata's,
  so this module reads the `.xplane.pb` itself: it is a protobuf of a few
  message types (tsl/profiler/protobuf/xplane.proto), and the wire format
  needs a dozen lines. `benchmark/tests/test_trace_scopes.py` checks the
  reading against `ProfileData` on a recorded trace.
- an annotation: an event of a `/host:` plane under the name it was given;
  its keyword arguments are the event's own stats. Numbers come back as
  numbers; a string is cut at its first comma (the profiler's own
  encoding), which is why the program joins request ids with spaces.

A trace is `[(plane_name, [(line_name, [(name, start_ns, duration_ns,
stats), ...]), ...]), ...]`: `trace_reduce`'s shape with the stats kept, so
its `self_times`, `union`, `clip` and `host_spans` are used as they are.
"""

from __future__ import annotations

import glob
import os
import re
import struct

import trace_reduce

MODULES_LINE = "XLA Modules"
_CACHE: dict = {}  # path of the .xplane.pb -> planes


# ---- the wire format -------------------------------------------------------


def _varint(b, i):
    x = s = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview of its bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, w = key >> 3, key & 7
        if w == 0:
            v, i = _varint(b, i)
        elif w == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif w == 1:
            v = b[i:i + 8]
            i += 8
        elif w == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"xplane: wire type {w}")
        yield f, w, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(b, stat_names):
    """(name, value) of one XStat."""
    name, value = None, None
    for f, w, v in _fields(b):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = _text(v)
        elif f == 7:  # a reference to a string kept among the stat names
            value = stat_names.get(v, "")
    return name, value


def _plane(b):
    """One XPlane -> (name, lines)."""
    name, lines, metas, stat_names = "", [], {}, {}
    raw_lines = []
    for f, w, v in _fields(b):
        if f == 2:
            name = _text(v)
        elif f == 3:
            raw_lines.append(v)
        elif f == 4:  # map<int64, XEventMetadata>
            for f2, _, v2 in _fields(v):
                if f2 == 2:
                    mid, mname, mstats = 0, "", []
                    for f3, _, v3 in _fields(v2):
                        if f3 == 1:
                            mid = v3
                        elif f3 == 2:
                            mname = _text(v3)
                        elif f3 == 5:
                            mstats.append(v3)
                    metas[mid] = (mname, mstats)
        elif f == 5:  # map<int64, XStatMetadata>
            for f2, _, v2 in _fields(v):
                if f2 == 2:
                    sid, sname = 0, ""
                    for f3, _, v3 in _fields(v2):
                        if f3 == 1:
                            sid = v3
                        elif f3 == 2:
                            sname = _text(v3)
                    stat_names[sid] = sname
    meta = {mid: (mname, dict(_stat(s, stat_names) for s in mstats))
            for mid, (mname, mstats) in metas.items()}
    for raw in raw_lines:
        lname, display, t0_ns, events = "", "", 0, []
        for f, w, v in _fields(raw):
            if f == 2:
                lname = _text(v)
            elif f == 11:
                display = _text(v)
            elif f == 3:
                t0_ns = _signed(v)
            elif f == 4:
                mid = off_ps = dur_ps = 0
                own = None
                for f2, _, v2 in _fields(v):
                    if f2 == 1:
                        mid = v2
                    elif f2 == 2:
                        off_ps = _signed(v2)
                    elif f2 == 3:
                        dur_ps = _signed(v2)
                    elif f2 == 4:
                        own = own or []
                        own.append(v2)
                ename, mstats = meta.get(mid, (str(mid), {}))
                stats = mstats
                if own:
                    stats = dict(mstats)
                    stats.update(_stat(s, stat_names) for s in own)
                events.append((ename, t0_ns + off_ps / 1000.0, dur_ps / 1000.0, stats))
        lines.append((lname or display, events))
    return name, lines


def parse(data: bytes):
    """An XSpace's bytes -> planes."""
    return [_plane(v) for f, w, v in _fields(memoryview(data)) if f == 1 and w == 2]


def load(trace_dir: str):
    """The newest `*.xplane.pb` under `trace_dir` as planes with stats,
    read once per process; [] where there is none."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    if paths[-1] not in _CACHE:
        with open(paths[-1], "rb") as f:
            _CACHE[paths[-1]] = parse(f.read())
    return _CACHE[paths[-1]]


# ---- what the readers ask --------------------------------------------------


def bare(planes):
    """The same planes in `trace_reduce`'s shape (no stats)."""
    return [(p, [(ln, [e[:3] for e in ev]) for ln, ev in lines]) for p, lines in planes]


def window(planes, span: str = "bench.window"):
    """(lo, hi) in ns of the harness's window annotation, or None."""
    wins = trace_reduce.host_spans(bare([p for p in planes if p[0].startswith("/host:")]),
                                   [span])
    return (wins[0][1], wins[-1][2]) if wins else None


def device_lines(planes, line_name: str):
    """{device id: events of that line}."""
    out = {}
    for pname, lines in planes:
        m = trace_reduce.DEVICE_PLANE.match(pname)
        if m:
            out[int(m.group(1))] = [e for ln, ev in lines if ln == line_name for e in ev]
    return out


def program_name(event_name: str) -> str:
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def programs(planes, lo=None, hi=None):
    """{device: {program: [duration_ns, ...]}} of the executions that lie
    inside [lo, hi] (all of them where no window is given)."""
    out = {}
    for dev, events in device_lines(planes, MODULES_LINE).items():
        by = out.setdefault(dev, {})
        for name, s, d, _ in events:
            if lo is None or (s >= lo and s + d <= hi):
                by.setdefault(program_name(name), []).append(d)
    return out


def scope_words(tf_op: str):
    """The words of an operation's name stack: `jit(train_step)/while/body/
    transpose(jvp(mlp))/dot_general:` -> {jit, train_step, while, ...,
    mlp, dot_general}. A declared scope is one of them whatever transform
    wraps it."""
    return set(re.split(r"[/():]+", tf_op or ""))


def scope_seconds(planes, lo=None, hi=None):
    """{device: {word of the name stack: self seconds}} over the operations
    that overlap [lo, hi]. An operation under nested scopes counts under
    each; an operation's self time is `trace_reduce.self_times`'s."""
    out = {}
    for dev, events in device_lines(planes, trace_reduce.OPS_LINE).items():
        secs, _ = trace_reduce.self_times(
            [(st.get("tf_op", ""), s, d) for _, s, d, st in events
             if lo is None or (s + d > lo and s < hi)])
        by = out.setdefault(dev, {})
        for tf_op, sec in secs.items():
            for word in scope_words(tf_op):
                by[word] = by.get(word, 0.0) + sec
    return out


def annotations(planes, names=None, lo=None, hi=None):
    """[(name, start_ns, end_ns, counts)] of the host planes' events with
    one of `names` (every event where None) that lie inside [lo, hi],
    sorted by start, a parent before its children."""
    names = None if names is None else set(names)
    out = []
    for pname, lines in planes:
        if not pname.startswith("/host:"):
            continue
        for _, events in lines:
            for name, s, d, stats in events:
                if (names is None or name in names) and (
                        lo is None or (s >= lo and s + d <= hi)):
                    out.append((name, s, s + d, stats))
    return sorted(out, key=lambda a: (a[1], -a[2]))
