"""The one span mechanism (picotron_tpu/telemetry/spans.py): a region of host
code is a `TraceAnnotation` in the profiler's trace and, with a `SpanTracer`
installed, a span recorded where it starts and ends.

A tiny `ServeEngine` is stepped under a CPU `jax.profiler` trace: its leaf
spans are in the host plane, nested inside a `serve.step`, with their counts
readable; the tracer's export holds the same spans in the same order."""

import glob
import os

import jax
import numpy as np
import pytest

from picotron_tpu.config import ModelConfig, ServeConfig, resolve_preset
from picotron_tpu.models.llama import init_params
from picotron_tpu.serve import ServeEngine
from picotron_tpu.serve.engine import prefill_rungs
from picotron_tpu.telemetry import PhaseTimer, Telemetry, bus
from picotron_tpu.telemetry.flightdeck import (
    SpanTracer, TID_SENTINEL, TID_SERVE, TID_TRAIN,
)
from picotron_tpu.telemetry.spans import Span, join_ids, span

LEAVES = ("serve.admit", "serve.prefill.build", "serve.prefill.dispatch",
          "serve.prefill.wait", "serve.prefill.emit", "serve.decode.build",
          "serve.decode.dispatch", "serve.decode.wait", "serve.decode.emit")
CHUNK, SLOTS = 4, 2


class Collect:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)

    def close(self):
        pass


@pytest.fixture(scope="module")
def model():
    mcfg = ModelConfig(dtype="float32", **{
        **resolve_preset("debug-tiny"), "max_position_embeddings": 64})
    return mcfg, init_params(mcfg, jax.random.key(0))


def make_engine(model, tel):
    mcfg, params = model
    return ServeEngine(
        params, mcfg,
        ServeConfig(decode_slots=SLOTS, block_size=4, num_blocks=16,
                    prefill_chunk=CHUNK, max_model_len=32, decode_interval=2),
        telemetry=tel)


def host_annotations(trace_dir):
    """[(name, start_ns, end_ns, {count: value})] of the host plane's
    `serve.*` events, in start order (parents before their children)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda x: (x[1], -x[2]))


def ids_of(value):
    """An `ids` count back as a list (one id reads back as a number)."""
    return [int(x) for x in str(value).split()]


def run_traced(model, tmp_path, prompts, steps=None):
    """Step an engine under a profile and a tracer at once; returns
    (annotations, tracer spans, phase events)."""
    sink = Collect()
    tel = Telemetry(sinks=[sink])
    tel.tracer = SpanTracer()
    eng = make_engine(model, tel)
    # warm both programs outside the profile, so no compile is traced
    eng.submit(list(range(1, CHUNK + 2)), 3)
    while eng.sched.has_work():
        eng.step(0.0)
    mark = tel.tracer.mark()
    sink.events.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i, (prompt, new) in enumerate(prompts):
            eng.submit(prompt, new, req_id=100 + i)
        n = 0
        while eng.sched.has_work() and (steps is None or n < steps):
            eng.step(float(n))
            n += 1
    finally:
        jax.profiler.stop_trace()
    spans = [e for e in tel.tracer.since(mark) if e["ph"] == "X"]
    eng.close()
    tel.close()
    return host_annotations(str(tmp_path)), spans, sink.events


@pytest.mark.parametrize("ends_in_chunk", [True, False])
def test_engine_spans_under_a_profile(model, tmp_path, ends_in_chunk):
    rng = np.random.default_rng(0)
    vocab = model[0].vocab_size
    if ends_in_chunk:
        # prompts of 5 and 7: each ends in its second chunk; run to the end
        prompts = [(list(map(int, rng.integers(0, vocab, size=n))), 4)
                   for n in (5, 7)]
        anns, spans, events = run_traced(model, tmp_path, prompts)
    else:
        # a prompt of 14 is four chunks long; two steps end in none of them
        prompts = [(list(map(int, rng.integers(0, vocab, size=14))), 4)]
        anns, spans, events = run_traced(model, tmp_path, prompts, steps=2)

    names = [a[0] for a in anns]
    steps = [a for a in anns if a[0] == "serve.step"]
    leaves = [a for a in anns if a[0] != "serve.step"]
    assert steps and set(names) <= set(LEAVES) | {"serve.step"}
    if ends_in_chunk:
        assert set(LEAVES) <= set(names)
    else:
        # the host never waited, and no slot reached decode
        assert {"serve.admit", "serve.prefill.build",
                "serve.prefill.dispatch"} <= set(names)
        assert "serve.prefill.wait" not in names
        assert not any(n.startswith("serve.decode.") and n != "serve.decode.build"
                       for n in names)

    # every leaf lies inside one serve.step; siblings do not overlap
    for name, lo, hi, _ in leaves:
        assert sum(1 for _, slo, shi, _ in steps if slo <= lo and hi <= shi) == 1, name
    for (_, _, hi, _), (_, lo, _, _) in zip(leaves, leaves[1:]):
        assert hi <= lo

    # a step with device work carries its own account (serve/engine.py
    # `step_account`): what the wall was and what of it the device starved
    for _, lo, hi, c in steps:
        assert 0 <= c["starved_us"] <= c["wall_us"] <= (hi - lo) / 1e3 + 1
        # ... and, since PR 53, of its whole period: one name a second
        assert set(c) == {"wall_us", "starved_us", "period_us", "empty_us",
                          "caller_starved_us", "dry_us", "dry_slack_us"}
        assert c["wall_us"] <= c["period_us"] + 1
        assert (c["empty_us"] + c["starved_us"] + c["caller_starved_us"]
                + c["dry_us"]) <= c["period_us"]
    host = [e for e in events
            if e.get("kind") == "phase" and e.get("phase") == "serve_host"]
    # one event a step, in the order the steps ran; both hold the same
    # seconds in whole microseconds, the event's rounded (`Telemetry.emit`)
    # and the span's cut off (`_account_step`), so they are equal or the
    # event's is one more. Compared as integers: the rounded seconds times
    # 1e6 is a float a hair off the integer on either side
    assert len(host) == len(steps)
    for e, (*_, c) in zip(host, steps):
        assert 0 <= round(e["secs"] * 1e6) - c["starved_us"] <= 1

    # counts, at the boundary of the work they count
    # (the prefill batch is compacted: `capacity` is the rung's rows, not SLOTS)
    disp = [a[3] for a in anns if a[0] == "serve.prefill.dispatch"]
    assert disp and all(0 < d["tokens"] <= d["capacity"] == d["rows"] * CHUNK
                        and 1 <= d["slots"] <= d["rows"]
                        and d["rows"] in prefill_rungs(SLOTS) for d in disp)
    assert [d["rows"] for d in disp] == ([2, 2] if ends_in_chunk else [1, 1])
    assert sum(d["tokens"] for d in disp) == (12 if ends_in_chunk else 8)
    adm = [a[3] for a in anns if a[0] == "serve.admit"]
    assert sum(d["admitted"] for d in adm) == len(prompts)
    assert all(d["queued"] == 0 for d in adm)
    if ends_in_chunk:
        waits = [a[3] for a in anns if a[0] == "serve.prefill.wait"]
        assert sum(d["finals"] for d in waits) == 2
        # the first tokens are appended under a span of their own; no
        # request ends at its first token here
        first = [a[3] for a in anns if a[0] == "serve.prefill.emit"]
        assert sum(d["tokens"] for d in first) == 2
        assert all(d["retired"] == d["blocks_freed"] == 0 for d in first)
        dec = [a[3] for a in anns if a[0] == "serve.decode.dispatch"]
        assert all(d["interval"] == 2 and 1 <= d["active"] <= SLOTS for d in dec)
        emit = [a[3] for a in anns if a[0] == "serve.decode.emit"]
        # each request's first token comes from its prefill chunk
        assert sum(d["tokens"] for d in emit) == 2 * (4 - 1)
        assert sum(d["retired"] for d in emit) == 2
        # a retirement gives back the blocks its cached positions filled:
        # prompt + 4 tokens less the last, which is never written
        assert sum(d["blocks_freed"] for d in emit) == 2 + 3
        assert all((d["blocks_freed"] > 0) == (d["retired"] > 0) for d in emit)
        build = [a[3] for a in anns if a[0] == "serve.decode.build"]
        assert build[0]["rebuilt"] == 1 and all(d["preempted"] == 0 for d in build)
        # one request's spans share its id
        for rid in (100, 101):
            mine = [a[0] for a in anns if rid in ids_of(a[3].get("ids", ""))]
            assert {"serve.prefill.dispatch", "serve.decode.dispatch"} <= set(mine)

    # the phase event says whether its `secs` waited for the device
    waited = [e["waited"] for e in events
              if e.get("kind") == "phase" and e.get("phase") == "prefill"]
    assert waited and any(waited) == ends_in_chunk

    # the tracer holds the same spans in the same order, each recorded where
    # it started and ended: none ends after its next sibling starts
    mine = sorted((e for e in spans if e["name"] != "serve.queue_wait"),
                  key=lambda e: (e["ts"], -e["dur"]))
    assert [e["name"] for e in mine] == names
    assert all(e["tid"] == TID_SERVE for e in mine)
    flat = [e for e in mine if e["name"] != "serve.step"]
    for a, b in zip(flat, flat[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3
    for e, (_, _, _, counts) in zip(mine, anns):
        assert {k: str(v) for k, v in e.get("args", {}).items()} == {
            k: str(v) for k, v in counts.items()}
    waits = [e for e in spans if e["name"] == "serve.queue_wait"]
    assert sorted(e["args"]["id"] for e in waits) == [100 + i for i in range(len(prompts))]


def test_span_without_profile_or_tracer_records_nothing(model):
    sink = Collect()
    tel = Telemetry(sinks=[sink])
    n0 = len(sink.events)
    with tel.span("serve.step", tid=TID_SERVE, slots=3) as sp:
        sp.set(tokens=5)
    assert tel.tracer is None and len(sink.events) == n0
    assert sp.counts == {"slots": 3, "tokens": 5} and sp.secs >= 0.0
    assert bus.active() is None
    with span("pp.0.F", tick=1) as sp2:  # library code, no facade installed
        pass
    assert sp2.secs >= 0.0
    tel.close()


def test_span_into_appends_once_with_its_own_secs():
    """`into` is the caller's list: one `(name, start, secs)` a span, when
    it ends, from the clock reads the span takes anyway."""
    c = Clock()
    tr = SpanTracer(clock=c)
    mine: list = []
    with Span("serve.step", tr, TID_SERVE):  # no list: appends nowhere
        c.t += 0.001
        with Span("serve.admit", tr, TID_SERVE, mine, queued=2) as a:
            c.t += 0.002
            assert mine == []  # not before it ends
        c.t += 0.003
        with Span("serve.decode.wait", tr, TID_SERVE, into=mine) as w:
            c.t += 0.004
    assert mine == [("serve.admit", 10.001, a.secs),
                    ("serve.decode.wait", 10.006, w.secs)]
    assert (a.secs, w.secs) == (pytest.approx(0.002), pytest.approx(0.004))
    assert a.counts == {"queued": 2}  # `into` is no count
    # the tracer's copy has the same start and duration
    spans = {e["name"]: e for e in tr.since(0)}
    assert spans["serve.admit"]["dur"] == pytest.approx(a.secs * 1e6)
    assert "into" not in spans["serve.admit"].get("args", {})
    # while it runs a span says where it is, on the same clock
    with Span("serve.step", tr, TID_SERVE) as sp:
        c.t += 0.005
        assert sp.so_far() == (pytest.approx(10.010), pytest.approx(0.005))
    # without a tracer the clock is perf_counter, and the list still fills
    with Span("x", into=mine) as x:
        pass
    assert mine[-1][0] == "x" and mine[-1][2] == x.secs and len(mine) == 3


class Clock:
    def __init__(self):
        self.t = 10.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("kind", ["nested", "counts_at_exit", "none_dropped",
                                  "lane", "ids"])
def test_span_into_tracer(kind):
    c = Clock()
    tr = SpanTracer(clock=c)
    if kind == "nested":
        with Span("outer", tr):
            c.t += 0.010
            with Span("inner", tr, mb=1):
                c.t += 0.005
            c.t += 0.001
        outer, inner = [e for e in tr.to_json()["traceEvents"] if e["ph"] == "X"]
        assert (outer["name"], inner["name"]) == ("outer", "inner")  # parent first
        assert outer["ts"] == pytest.approx(0.0) and outer["dur"] == pytest.approx(16_000.0)
        assert inner["ts"] == pytest.approx(10_000.0) and inner["dur"] == pytest.approx(5_000.0)
        assert inner["args"] == {"mb": 1}
    elif kind == "counts_at_exit":
        with Span("emit", tr, tid=TID_SERVE) as sp:
            c.t += 0.002
            sp.set(tokens=7, retired=1)
        (e,) = tr.since(0)
        assert e["args"] == {"tokens": 7, "retired": 1} and sp.secs == pytest.approx(0.002)
    elif kind == "none_dropped":
        with Span("train.data", tr, step=None):
            pass
        assert "args" not in tr.since(0)[0]
    elif kind == "lane":
        # what the removed `counter()` lane test pinned: a lane labels itself
        with Span("watch", tr, tid=TID_SENTINEL, value=1.25):
            pass
        doc = tr.to_json()
        labels = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
        assert labels == {TID_SENTINEL: "flightdeck"}
        assert doc["traceEvents"][-1]["args"] == {"value": 1.25}
    else:
        assert join_ids([1000, np.int32(1003)]) == "1000 1003"
        assert ids_of(join_ids([7])) == [7]


def test_phase_timer_goes_through_span():
    tel = Telemetry(sinks=[])
    tel.tracer = SpanTracer()
    got = []
    timer = PhaseTimer(lambda n, s, st: got.append((n, s, st)),
                       on_section=lambda n, s, st: got.append((n, s, st)),
                       span=tel.span)
    with timer.phase("step", 3):
        with timer.section("pp_stage0", 3):
            pass
    spans = [e for e in tel.tracer.to_json()["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in spans] == ["train.step", "train.pp_stage0"]
    assert all(e["tid"] == TID_TRAIN and e["args"] == {"step": 3} for e in spans)
    # the duration handed on is the span's own
    assert [(n, st) for n, _, st in got] == [("pp_stage0", 3), ("step", 3)]
    assert got[1][1] == pytest.approx(spans[0]["dur"] / 1e6, abs=1e-9)
    # a facade's own phases take the same road, and a phase event is not
    # turned into a second span
    with tel.phases.phase("data", 4):
        pass
    names = [e["name"] for e in tel.tracer.to_json()["traceEvents"] if e["ph"] == "X"]
    assert names.count("train.data") == 1 and "data" not in names
    tel.close()
