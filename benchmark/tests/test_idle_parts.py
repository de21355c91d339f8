"""readers/idle_parts.py: synthetic planes with known gaps (one inside an
execution, one under a wait, one under an emit and the un-spanned tail after
it, one outside the step) give the four parts to the nanosecond and sum to
`trace_reduce`'s idle; a device line shifted against the host is measured and
moved back, and one that no single offset puts in order makes the reader report
nothing and say so; a CPU trace of a tiny `ServeEngine` runs the reader
and the two data-only metrics end to end (counts and structure only: nothing
here is a time of a device)."""
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import trace_reduce  # noqa: E402
import trace_scopes as ts  # noqa: E402

from run import load_json, load_module  # noqa: E402

MS = 1e6  # ns


class Ctx(types.SimpleNamespace):
    def log(self, *a):
        self.logged.append(" ".join(map(str, a)))


def ev(name, lo_ms, hi_ms, **counts):
    return (name, lo_ms * MS, (hi_ms - lo_ms) * MS, counts)


def planes(shift_ms=0.0, second_ms=None):
    """A 100 ms window: a prefill execution 0-5, then one engine step 10-60
    whose decode execution runs 14-38 with a 2 ms hole between its two
    operations. `shift_ms` moves the device plane against the host. With
    `second_ms` the window is 200 ms and holds the same step once more, 100 ms
    later, its execution moved by `second_ms`."""
    if second_ms is not None:
        (dname, (mods, ops)), (hname, ((line, host),)) = planes(shift_ms)
        later = lambda evs, d: [(n, s + (100 + d) * MS, w, c) for n, s, w, c in evs]  # noqa: E731
        return [(dname, [(mods[0], mods[1] + later(mods[1][1:], second_ms - shift_ms)),
                         (ops[0], ops[1] + later(ops[1][1:], second_ms - shift_ms))]),
                (hname, [(line, [ev("bench.window", 0, 200)] + host[1:]
                          + later([e for e in host if e[0].startswith("serve.")], 0))])]
    d = shift_ms
    device = ("/device:TPU:0", [
        ("XLA Modules", [ev("jit_serve_prefill(7)", 0 + d, 5 + d),
                         ev("jit_serve_decode(9)", 14 + d, 38 + d)]),
        ("XLA Ops", [ev("%fusion.1 = f32[8] fusion(%a)", 0 + d, 5 + d),
                     ev("%fusion.2 = f32[8] fusion(%a)", 14 + d, 20 + d),
                     ev("%fusion.3 = f32[8] fusion(%b)", 22 + d, 38 + d)])])
    host = ("/host:CPU", [("python", [
        ev("bench.window", 0, 100),
        ev("engine.step", 9.5, 60.5), ev("observe", 60.5, 62.5), ev("wait.arrival", 70, 100),
        ev("serve.step", 10, 60, wall_us=50_000, starved_us=23_000, dispatches=1),
        ev("serve.admit", 10, 11), ev("serve.decode.build", 11, 13),
        ev("serve.decode.dispatch", 13, 15), ev("serve.decode.wait", 15, 40),
        ev("serve.decode.emit", 40, 50)])])
    return [device, host]


def ctx_for(pl, monkeypatch):
    monkeypatch.setattr(ts, "load", lambda d: pl)
    red = trace_reduce.reduce(ts.bare(pl), span_names=("engine.step",))
    return Ctx(trace_dir="x", logged=[], trace=red), red


def test_parts_to_the_nanosecond_and_they_sum_to_the_idle(monkeypatch):
    ctx, red = ctx_for(planes(), monkeypatch)
    mod = load_module("readers", "idle_parts")
    parts, by_leaf, by_harness, idle, steps = mod.split(
        planes(), (0.0, 100 * MS), 0, ("wait.arrival", "engine.step", "observe"))
    # gaps: 5-14 (before the execution), 20-22 (inside it), 38-100 (after it)
    assert parts == dict(inside_program=2 * MS,      # the hole between two operations
                         round_trip=(1 + 2) * MS,    # 13-14 under the dispatch, 38-40 under the wait
                         starved=(1 + 2 + 10 + 10) * MS,  # admit, build, emit, the tail 50-60
                         outside_step=(5 + 40) * MS)  # 5-10 and 60-100
    assert by_leaf == {"serve.admit": 1 * MS, "serve.decode.build": 2 * MS,
                       "serve.decode.emit": 10 * MS, "unspanned": 10 * MS}
    # 5-10 and 60-100 are outside the step: 70-100 of it with the system empty
    assert by_harness == {"wait.arrival": 30 * MS, "observe": 2 * MS,
                          "(around engine.step and between spans)": 13 * MS}
    d0 = red["per_device"][0]
    assert sum(parts.values()) == idle == 73 * MS
    assert idle == pytest.approx((d0["window_s"] - d0["busy_s"]) * 1e9, abs=0.5)  # kept in seconds there
    facts = dict(spans=("wait.arrival", "submit", "engine.step", "observe"))
    got = {p: mod.read(dict(part=p), facts, ctx) for p in ("starved", "round_trip", "inside_program")}
    assert got == dict(starved=23.0, round_trip=3.0, inside_program=2.0)
    # with the logged remainder they are `device_idle.serve`
    idle_pct = load_module("readers", "trace_idle").read({}, {}, ctx)
    assert sum(got.values()) + 45.0 == pytest.approx(idle_pct, abs=1e-9)
    text = "\n".join(ctx.logged)
    assert "wait.arrival 0.0300 s" in text
    assert len(ctx.logged) == 5  # one reduction a run, whatever number of metrics read it
    assert "outside_step 0.0450 s (45.00%)" in text
    assert "serve.decode.emit 0.0100 s, unspanned 0.0100 s" in text
    # the engine's own count for the same step agrees with the device plane
    assert "starved_us 0.0230 s of wall_us 0.0500 s" in text and "(1.000 x the count)" in text
    assert "1 serve_decode executions" in text
    assert "offsets of -1.000 to +2.000 ms, 0 among them: nothing moved" in text
    assert "launch median 1.000 ms, fastest 1.000; wake median 2.000 ms" in text


def test_a_gap_is_split_by_overlap_not_given_to_a_winner():
    mod = load_module("readers", "idle_parts")
    assert mod.overlap([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    # one gap 0-10 under a wait 0-3, an emit 3-9 and nothing after: each its own
    assert mod.overlap_by_name([(0.0, 10.0)], [("wait", 0.0, 3.0), ("emit", 3.0, 9.0)]) == {
        "wait": 3.0, "emit": 6.0}
    assert trace_reduce.attribute([(0.0, 10.0)], [("wait", 0.0, 3.0), ("emit", 3.0, 9.0)]) == ["emit"]


@pytest.mark.parametrize("shift_ms, moved", [(5.0, "-3.000"), (-5.0, "+4.000")])
def test_a_shifted_device_plane_is_measured_and_moved_back(monkeypatch, shift_ms, moved):
    """The order bounds the offset to the launch (1 ms) on one side and the wake
    (2 ms) on the other; the least move that restores it reads that one as 0."""
    ctx, _ = ctx_for(planes(shift_ms), monkeypatch)
    mod = load_module("readers", "idle_parts")
    got = {p: mod.read(dict(part=p), {}, ctx) for p in ("starved", "round_trip", "inside_program")}
    assert got == dict(starved=23.0, round_trip=3.0, inside_program=2.0)
    text = "\n".join(ctx.logged)
    assert f"the device plane moved by {moved} ms" in text
    assert text.count("clock check") == 1  # checked once, not once a metric


def test_clocks_that_no_offset_reconciles_report_nothing_and_say_so(monkeypatch):
    ctx, _ = ctx_for(planes(5.0, second_ms=-5.0), monkeypatch)
    mod = load_module("readers", "idle_parts")
    for part in ("starved", "round_trip", "inside_program"):
        assert mod.read(dict(part=part), {}, ctx) is None
    (text,) = ctx.logged
    assert "2 serve_decode executions" in text and "offsets of +4.000 to -3.000 ms" in text
    assert "no one offset holds it" in text and "do not share a clock" in text
    # the same two steps under one offset are read
    ctx, _ = ctx_for(planes(5.0, second_ms=5.0), monkeypatch)
    assert mod.read(dict(part="inside_program"), {}, ctx) == pytest.approx(2.0)


def test_no_execution_to_hold_the_clocks_against_reports_nothing(monkeypatch):
    (dname, (mods, ops)), host = planes()
    ctx, _ = ctx_for([(dname, [(mods[0], mods[1][:1]), ops]), host], monkeypatch)
    mod = load_module("readers", "idle_parts")
    assert mod.read(dict(part="starved"), {}, ctx) is None
    assert len(ctx.logged) == 1 and "nothing to hold the two clocks against" in ctx.logged[0]


def test_no_step_span_or_no_trace_reports_nothing(monkeypatch):
    mod = load_module("readers", "idle_parts")
    device, host = planes()
    bare_host = ("/host:CPU", [("python", [e for e in host[1][0][1]
                                           if not e[0].startswith("serve.")])])
    ctx, _ = ctx_for([device, bare_host], monkeypatch)
    assert mod.read(dict(part="starved"), {}, ctx) is None and ctx.logged == []
    monkeypatch.setattr(ts, "load", lambda d: [])
    empty = Ctx(trace_dir="none", logged=[], trace=ctx.trace)
    assert mod.read(dict(part="starved"), {}, empty) is None


def test_the_five_metrics_on_a_cpu_trace_of_a_tiny_engine(tmp_path):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from picotron_tpu.config import ModelConfig, ServeConfig, resolve_preset
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.serve import ServeEngine
    from picotron_tpu.telemetry import Telemetry

    base = load_module("runners", "serve_open_loop")
    sink = base._Collect()
    mcfg = ModelConfig(dtype="float32", **{
        **resolve_preset("debug-tiny"), "max_position_embeddings": 64})
    eng = ServeEngine(init_params(mcfg, jax.random.key(0)), mcfg,
                      ServeConfig(decode_slots=2, block_size=4, num_blocks=16,
                                  prefill_chunk=4, max_model_len=32, decode_interval=2),
                      telemetry=Telemetry(sinks=[sink]))
    eng.submit(list(range(1, 6)), 3)
    while eng.sched.has_work():
        eng.step(0.0)
    sink.phases.clear()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        eng.submit(list(range(1, 6)), 4, req_id=7)
        eng.submit(list(range(1, 8)), 4, req_id=8)
        n = 0
        while eng.sched.has_work():
            eng.step(0.0)
            n += 1
    jax.profiler.stop_trace()
    eng.close()

    ctx = Ctx(trace_dir=str(tmp_path), logged=[],
              trace=dict(first_device=0, per_device={0: dict(busy_s=1.0, window_s=1.0)}))
    facts = dict(phases=list(sink.phases), decode_interval=2)
    spec = {m: load_json("layer_metrics", m + ".json") for m in (
        "device_starved.serve", "host_starved_ms.serve", "idle_starved.serve",
        "idle_round_trip.serve", "idle_inside_program.serve")}

    def read(metric):
        s = spec[metric]
        return load_module("readers", s["reader"]).read(s["params"], facts, ctx)

    # the engine's own count, from the spans and from the phase events
    steps = ts.annotations(ts.load(str(tmp_path)), ["serve.step"])
    assert len(steps) == n and all(0 <= c["starved_us"] <= c["wall_us"] for *_, c in steps)
    share = read("device_starved.serve")
    assert share == pytest.approx(100.0 * sum(c["starved_us"] for *_, c in steps)
                                  / sum(c["wall_us"] for *_, c in steps))
    assert 0.0 < share < 100.0
    host = [s for p, s in sink.phases if p == "serve_host"]
    assert len(host) == n and read("host_starved_ms.serve") == pytest.approx(sum(host) / n * 1e3)
    assert f"over {n} events" in ctx.logged[-1] and "median" in ctx.logged[-1]
    # a CPU trace has no device plane: the three parts report nothing, without raising
    for m in ("idle_starved.serve", "idle_round_trip.serve", "idle_inside_program.serve"):
        assert read(m) is None
    # ... and a program without the counts or the phase (the parent commit's) reports nothing
    assert load_module("readers", "span_ratio").read(
        dict(span="serve.decode.emit", num="starved_us", den="wall_us"), facts, ctx) is None
    assert load_module("readers", "phase_mean").read(
        dict(phase="serve_host"), dict(phases=[("decode", 0.02)]), ctx) is None
    # each metric of the five is declared for the three serving cells, under the scheduler
    import json
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    serving = [w["name"] for w in bench["workloads"] if w["traffic"] != "train-seq4k"]
    for m, s in spec.items():
        (entry,) = [e for e in bench["per_layer"] if e["name"] == m]
        assert entry["workloads"] == serving and entry["better"] == "lower"
        assert entry["layer"] == s["layer"] == "scheduler"
        assert entry["moves"] == s["moves"] == "latency_per_token_p90_ms" and entry["unit"] == s["unit"]
