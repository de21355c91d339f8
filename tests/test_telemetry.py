"""Telemetry subsystem tests: registry instruments, sinks (incl. the
rollback-safe wandb adapter), the step-phase timer / watchdog coupling,
the goodput ledger (replay high-water mark, exact compile split), the
recompile hook, the bus, the frozen stdout log-line contract, and the
post-hoc report tool. The whole-run chaos assertions live with the
scenario tests in test_resilience.py (slow tier)."""

import importlib.util
import json
import os
import sys

import pytest

from picotron_tpu.telemetry import (
    CompileWatch, GoodputLedger, Histogram, JsonlSink, MetricsRegistry,
    PhaseTimer, StdoutSink, Telemetry, WandbSink, bus,
    telemetry_jsonl_path,
)
from picotron_tpu.telemetry.sinks import jsonl_segments


def load_report():
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    r.counter("events/retry").inc()
    r.counter("events/retry").inc(2)
    assert r.counter("events/retry").value == 3
    r.gauge("tokens").set(512)
    assert r.gauge("tokens").value == 512.0
    h = r.histogram("step")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count == 4 and h.sum == 10.0
    assert h.min == 1.0 and h.max == 4.0 and h.mean == 2.5


def test_histogram_percentiles_nearest_rank():
    h = Histogram()
    for v in range(1, 101):  # 1..100
        h.observe(float(v))
    assert h.p50 == 50.0
    assert h.p95 == 95.0
    assert h.percentile(100) == 100.0
    assert h.percentile(0) == 1.0
    assert Histogram().p50 is None  # empty: no value, not a crash


def test_histogram_window_bounds_memory_but_keeps_lifetime_stats():
    h = Histogram(window=8)
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100 and h.min == 0.0 and h.max == 99.0
    # percentiles over the retention window (the recent distribution)
    assert h.p50 >= 92.0


def test_registry_snapshot_shape():
    r = MetricsRegistry()
    r.counter("a").inc()
    r.histogram("b").observe(2.0)
    snap = r.snapshot()
    assert snap["counters"]["a"] == 1
    assert snap["histograms"]["b"]["count"] == 1
    assert snap["histograms"]["b"]["p95"] == 2.0


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------


def test_jsonl_sink_roundtrip_strips_line_and_appends(tmp_path):
    p = str(tmp_path / "t.jsonl")
    s = JsonlSink(p)
    s.emit({"kind": "step", "step": 1, "loss": 2.5, "line": "[step ...]"})
    s.close()
    s2 = JsonlSink(p)  # append mode: a restart continues the stream
    s2.emit({"kind": "step", "step": 2, "loss": 2.4})
    s2.emit({"kind": "step", "step": 3})  # emit after close is a no-op
    s2.close()
    s2.emit({"kind": "step", "step": 9})
    rows = [json.loads(ln) for ln in open(p)]
    assert [r["step"] for r in rows] == [1, 2, 3]
    assert "line" not in rows[0]  # presentation, not data


def test_jsonl_sink_rotates_at_size_cap(tmp_path):
    """logging.telemetry_max_mb: when the live segment crosses the byte
    cap it is renamed to `<path>.1` (one older segment kept) and the
    stream continues in a fresh file — a week-long run's telemetry is
    bounded at ~2x the cap, and no event is lost at the seam."""
    p = str(tmp_path / "t.jsonl")
    s = JsonlSink(p, max_bytes=300)
    for step in range(1, 13):
        s.emit({"kind": "step", "step": step, "loss": 2.5})
    s.close()
    assert os.path.exists(p + ".1")  # rotation happened
    assert os.path.getsize(p) < 400  # live segment stays near the cap
    # oldest-first reading reassembles the unbroken stream
    assert jsonl_segments(p) == [p + ".1", p]
    steps = []
    for seg in jsonl_segments(p):
        steps += [json.loads(ln)["step"] for ln in open(seg)]
    assert steps == list(range(1, 13))
    # a second overflow drops the oldest segment (the documented bound:
    # telemetry disk stays ~2x the cap, the tail survives)
    s2 = JsonlSink(p, max_bytes=300)
    for step in range(13, 25):
        s2.emit({"kind": "step", "step": step, "loss": 2.5})
    s2.close()
    tail = []
    for seg in jsonl_segments(p):
        tail += [json.loads(ln)["step"] for ln in open(seg)]
    assert tail[-1] == 24
    assert tail == sorted(tail) and len(tail) < 24
    # an unrotated stream is a single segment; a missing one is none
    single = str(tmp_path / "single.jsonl")
    JsonlSink(single).close()
    assert jsonl_segments(single) == [single]
    assert jsonl_segments(str(tmp_path / "absent.jsonl")) == []


def test_jsonl_sink_unbounded_by_default(tmp_path):
    p = str(tmp_path / "t.jsonl")
    s = JsonlSink(p)
    for step in range(200):
        s.emit({"kind": "step", "step": step})
    s.close()
    assert not os.path.exists(p + ".1")
    assert len(open(p).readlines()) == 200


def test_stdout_sink_gates_on_primary(capsys):
    StdoutSink(is_primary=True).emit({"kind": "step", "line": "hello"})
    StdoutSink(is_primary=False).emit({"kind": "step", "line": "nope"})
    StdoutSink(is_primary=True).emit({"kind": "phase"})  # no line: silent
    out = capsys.readouterr().out
    assert out == "hello\n"


class FakeWandbRun:
    def __init__(self):
        self.calls = []
        self.defined = []
        self.finished = False

    def define_metric(self, *a, **k):
        self.defined.append((a, k))

    def log(self, data, step=None):
        self.calls.append((data, step))

    def finish(self):
        self.finished = True


def test_wandb_sink_survives_rollback_with_monotonic_steps():
    """The satellite fix: wandb drops log(step=...) calls with
    non-monotonic steps, so after a guard rollback (training step goes
    5 -> 3) the sink must keep its OWN axis monotonic and carry the
    training step as a field."""
    run = FakeWandbRun()
    sink = WandbSink(run)
    for training_step in (1, 2, 3, 4, 5, 3, 4, 5, 6):  # rollback at 5->3
        sink.emit({"kind": "step", "ts": 0.0, "step": training_step,
                   "loss": 1.0, "line": "x"})
    wandb_steps = [s for _, s in run.calls]
    assert wandb_steps == sorted(wandb_steps)
    assert len(set(wandb_steps)) == len(wandb_steps)  # strictly increasing
    assert [d["step"] for d, _ in run.calls] == [1, 2, 3, 4, 5, 3, 4, 5, 6]
    assert all("line" not in d and "ts" not in d for d, _ in run.calls)
    # the step axis was define_metric'd where supported
    assert (("step",), {}) in run.defined


def test_wandb_sink_only_forwards_chart_kinds():
    run = FakeWandbRun()
    sink = WandbSink(run)
    sink.emit({"kind": "phase", "phase": "step", "secs": 0.1})
    sink.emit({"kind": "retry", "secs": 1.0})
    sink.emit({"kind": "eval", "step": 4, "val_loss": 3.2})
    assert len(run.calls) == 1
    assert run.calls[0][0] == {"step": 4, "val_loss": 3.2}
    sink.close()
    assert run.finished


def test_telemetry_jsonl_path_per_host(tmp_path):
    from picotron_tpu.config import Config, CheckpointConfig, LoggingConfig

    cfg = Config(checkpoint=CheckpointConfig(save_dir=str(tmp_path / "ck")))
    assert telemetry_jsonl_path(cfg, 0).endswith("ck/telemetry.jsonl")
    assert telemetry_jsonl_path(cfg, 2).endswith("ck/telemetry.p2.jsonl")
    off = Config(logging=LoggingConfig(telemetry_jsonl=False))
    assert telemetry_jsonl_path(off, 0) is None
    redirected = Config(logging=LoggingConfig(
        telemetry_dir=str(tmp_path / "elsewhere")))
    assert telemetry_jsonl_path(redirected, 0).endswith(
        "elsewhere/telemetry.jsonl")


# ---------------------------------------------------------------------------
# phase timer + watchdog coupling
# ---------------------------------------------------------------------------


class FakeWatchdog:
    def __init__(self):
        self.beats = []

    def beat(self, phase, step=None):
        self.beats.append((phase, step))


def test_phase_timer_beats_watchdog_and_reports_duration():
    done = []
    wd = FakeWatchdog()
    timer = PhaseTimer(lambda n, s, st: done.append((n, s, st)),
                       watchdog=wd)
    with timer.phase("data", 3):
        pass
    assert wd.beats == [("data", 3)]  # beat on ENTRY, before the work
    assert len(done) == 1
    name, secs, step = done[0]
    assert name == "data" and step == 3 and secs >= 0


def test_phase_timer_books_even_on_exception():
    done = []
    timer = PhaseTimer(lambda n, s, st: done.append(n))
    with pytest.raises(RuntimeError):
        with timer.phase("save", 1):
            raise RuntimeError("boom")
    assert done == ["save"]


# ---------------------------------------------------------------------------
# goodput ledger
# ---------------------------------------------------------------------------


def test_ledger_phase_categories_and_goodput_fraction():
    led = GoodputLedger()
    assert led.book_phase("data", 1.0, step=1) == "data_wait"
    assert led.book_phase("step", 6.0, step=1) == "compute"
    assert led.book_phase("save", 2.0, step=1) == "ckpt_io"
    assert led.book_phase("sync", 1.0, step=1) == "host_sync"
    assert led.goodput_fraction() == pytest.approx(0.6)
    s = led.summary()
    assert s["goodput_pct"] == 60.0
    assert s["seconds_by_category"]["ckpt_io"] == 2.0


def test_ledger_books_replay_below_high_water_mark():
    """Steps re-trained after a rollback buy back lost ground — badput."""
    led = GoodputLedger()
    for step in (1, 2, 3, 4):
        assert led.book_phase("step", 1.0, step=step) == "compute"
    # rollback to 2: steps 3 and 4 re-run, then new ground at 5
    assert led.book_phase("rollback", 0.5, step=4) == "restore"
    assert led.book_phase("step", 1.0, step=3) == "replay"
    assert led.book_phase("step", 1.0, step=4) == "replay"
    assert led.book_phase("step", 1.0, step=5) == "compute"
    assert led.seconds["replay"] == 2.0
    assert led.seconds["compute"] == 5.0


def test_ledger_resume_seeds_high_water_mark():
    led = GoodputLedger()
    led.resume_from(10)
    assert led.book_phase("step", 1.0, step=10) == "replay"
    assert led.book_phase("step", 1.0, step=11) == "compute"


def test_ledger_compile_split_subtracts_from_phase():
    led = GoodputLedger()
    cat = led.book_phase("step", 10.0, step=1, compile_secs=8.0)
    assert cat == "compute"
    assert led.seconds["compile"] == 8.0
    assert led.seconds["compute"] == pytest.approx(2.0)
    # compile can never exceed the observed phase wall
    led2 = GoodputLedger()
    led2.book_phase("step", 1.0, step=1, compile_secs=5.0)
    assert led2.seconds["compile"] == 1.0
    assert led2.seconds.get("compute", 0.0) == 0.0


def test_ledger_pp_bubble_carved_from_compute():
    """The schedule-table bubble share is badput carved out of a compute
    phase (like compile); a replayed step is already badput wall-to-wall,
    so its bubble share is NOT double-carved."""
    led = GoodputLedger()
    cat = led.book_phase("step", 10.0, step=1, compile_secs=2.0,
                         bubble_secs=3.0)
    assert cat == "compute"
    assert led.seconds["compile"] == 2.0
    assert led.seconds["pp_bubble"] == 3.0
    assert led.seconds["compute"] == pytest.approx(5.0)
    # replay: the whole phase books as replay, bubble untouched
    led2 = GoodputLedger()
    led2.resume_from(5)
    assert led2.book_phase("step", 4.0, step=5, bubble_secs=1.0) == "replay"
    assert led2.seconds.get("pp_bubble", 0.0) == 0.0
    assert led2.seconds["replay"] == 4.0
    # the carve clamps to the phase wall
    led3 = GoodputLedger()
    led3.book_phase("step", 1.0, step=1, bubble_secs=9.0)
    assert led3.seconds["pp_bubble"] == 1.0
    assert led3.seconds.get("compute", 0.0) == 0.0


def test_phase_timer_section_histogram_only():
    """Sections time sub-spans inside a phase: on_section fires with the
    duration, but no watchdog beat (the enclosing phase armed it) and no
    phase booking."""
    phases, sections = [], []
    wd = FakeWatchdog()
    timer = PhaseTimer(lambda n, s, st: phases.append(n), watchdog=wd,
                       on_section=lambda n, s, st: sections.append((n, st)))
    with timer.phase("step", 7):
        with timer.section("pp_stage0", 7):
            pass
        with timer.section("pp_stage1", 7):
            pass
    assert [s[0] for s in sections] == ["pp_stage0", "pp_stage1"]
    assert phases == ["step"]
    assert wd.beats == [("step", 7)]  # sections never beat


def test_facade_pp_bubble_jsonl_reproduces_ledger(tmp_path):
    """With a bubble fraction installed, every step phase emits a
    category='pp_bubble' event next to the shrunken phase event — the
    documented invariant (a post-hoc sum of (category, secs) pairs
    reproduces the ledger) must survive the carve."""
    p = str(tmp_path / "t.jsonl")
    tel = Telemetry(sinks=[JsonlSink(p)])
    tel.set_pp_bubble_fraction(0.25)
    with tel.phases.phase("step", 1):
        pass
    with tel.phases.phase("data", 1):
        pass  # non-step phases never carve
    tel.close()
    rows = [json.loads(ln) for ln in open(p)]
    bubbles = [r for r in rows if r["kind"] == "pp_bubble"]
    assert len(bubbles) == 1 and bubbles[0]["category"] == "pp_bubble"
    sums: dict = {}
    for r in rows:
        if "category" in r and "secs" in r:
            sums[r["category"]] = sums.get(r["category"], 0.0) + r["secs"]
    for cat, secs in tel.ledger.seconds.items():
        assert sums.get(cat, 0.0) == pytest.approx(secs, abs=1e-5), (
            cat, sums, tel.ledger.seconds)
    assert tel.ledger.seconds["pp_bubble"] == pytest.approx(
        0.25 * (tel.ledger.seconds["pp_bubble"]
                + tel.ledger.seconds["compute"]), rel=1e-6)


def test_serve_host_category_reproduces_and_leaves_training_alone(tmp_path):
    """`serve_host` (serve/engine.py: an engine step's seconds with nothing
    enqueued on the device) is a ledger category of its own: badput beside
    prefill + decode, the JSONL's (category, secs) pairs still reproduce the
    ledger with it, the report shows the device-fed share and lists a slow
    step, and a training stream's goodput % does not know it exists."""
    report = load_report()
    p = str(tmp_path / "serve.jsonl")
    tel = Telemetry(sinks=[JsonlSink(p)])
    tel.emit("phase", phase="queue_wait", category="queue_wait", secs=0.5, id=1)
    tel.emit("phase", phase="prefill", category="prefill", secs=1.0)
    tel.emit("phase", phase="decode", category="decode", secs=2.0)
    for secs in (0.25, 0.75):
        tel.emit("phase", phase="serve_host", category="serve_host",
                 secs=secs, engine=0)
    tel.emit("serve_slow_step", held_by="serve.decode.wait", held_s=2.4,
             limit_s=0.25, wall_s=2.5, starved_s=0.01, unspanned_ms=1.0,
             engine=0, blocks_freed=900,
             leaves_ms={"serve.decode.wait": 2400.0, "serve.decode.emit": 9.0})
    tel.emit("serve_request", id=1, output_tokens=4, ttft_s=0.1,
             queue_wait_s=0.5)
    tel.close()
    assert tel.ledger.seconds["serve_host"] == 1.0
    assert tel.ledger.goodput_seconds == 3.0  # serve_host is badput
    rows = [json.loads(ln) for ln in open(p)]
    sums: dict = {}
    for r in rows:
        if "category" in r and "secs" in r:
            sums[r["category"]] = sums.get(r["category"], 0.0) + r["secs"]
    assert sums == pytest.approx(tel.ledger.seconds)
    s = report.summarize(rows)
    assert s["categories"]["serve_host"] == 1.0
    assert s["goodput_pct"] == pytest.approx(100 * 3.0 / 4.5, abs=0.01)
    sv = s["serving"]
    assert sv["serve_host_s"] == 1.0 and sv["device_fed_share"] == 0.75
    assert [(st["wall_s"], st["longest_leaf"]) for st in sv["slow_steps"]] == [
        (2.5, ("serve.decode.wait", 2400.0))]
    text = report.render(s)
    assert "device fed share 0.75" in text
    assert "serve.decode.wait 2.4 s (limit 0.25) of wall 2.5 s" in text
    assert "longest leaf serve.decode.wait 2400.0 ms, blocks freed 900" in text

    # a training stream: the same goodput % as before the category existed
    tel = Telemetry(sinks=[])
    tel.ledger.book_phase("step", 6.0, step=1)
    tel.ledger.book_phase("data", 2.0, step=1)
    assert tel.ledger.summary()["goodput_pct"] == 75.0
    assert "serve_host" not in tel.ledger.seconds
    tel.ledger.book("serve_host", 1.0)  # a known category, not `other`
    assert tel.ledger.seconds == {"compute": 6.0, "data_wait": 2.0,
                                  "serve_host": 1.0}
    tel.close()


def test_report_prints_the_period_and_what_a_slow_wait_found(tmp_path):
    """The serving view prints the partition of the steps' periods from the
    serve_summary (empty / starved / caller-starved / dry + slack / fed, in
    seconds and %), a slow step's `ready` / `next_ready` in its line, and
    takes the `phase=serve_dry` events for no category: their seconds lie
    inside `decode` and `prefill`, and the sums stay a partition."""
    report = load_report()
    p = str(tmp_path / "serve.jsonl")
    tel = Telemetry(sinks=[JsonlSink(p)])
    tel.emit("phase", phase="decode", category="decode", secs=3.0)
    tel.emit("phase", phase="serve_host", category="serve_host", secs=1.0,
             engine=0)
    for secs in (0.5, 0.0):
        tel.emit("phase", phase="serve_dry", secs=secs, engine=0)
    tel.emit("serve_slow_step", held_by="serve.decode.wait", held_s=2.7,
             limit_s=0.25, wall_s=2.7, starved_s=0.0, unspanned_ms=0.1,
             engine=0, blocks_freed=0, ready=0, next_ready=1,
             leaves_ms={"serve.decode.wait": 2700.0}, dry_by_ms={})
    tel.emit("serve_slow_step", held_by="host", held_s=0.4, limit_s=0.25,
             wall_s=0.4, starved_s=0.0, unspanned_ms=0.1, engine=0,
             blocks_freed=7, ready=None, next_ready=None,
             leaves_ms={"serve.decode.emit": 400.0}, dry_by_ms={})
    tel.emit("serve_request", id=1, output_tokens=4, ttft_s=0.1,
             queue_wait_s=0.0)
    tel.emit("serve_summary", requests=1, output_tokens=4, wall_s=10.0,
             period_s=10.0, empty_s=5.0, starved_s=1.0, caller_starved_s=0.5,
             dry_s=0.5, dry_slack_s=0.25, system_empty_share=0.5,
             device_dry_share=0.05)
    tel.close()
    assert "serve_dry" not in tel.ledger.seconds
    rows = [json.loads(ln) for ln in open(p)]
    s = report.summarize(rows)
    assert set(s["categories"]) == {"decode", "serve_host"}  # a partition
    assert s["phases"]["serve_dry"]["count"] == 2
    assert s["phases"]["serve_dry"]["total_s"] == 0.5
    sv = s["serving"]
    assert sv["period_s"] == 10.0
    assert sv["period"] == {
        "empty": (5.0, 0.5), "starved": (1.0, 0.1),
        "caller_starved": (0.5, 0.05), "dry": (0.5, 0.05), "fed": (3.0, 0.3),
        "dry_slack": (0.25, 0.025)}
    assert sum(secs for k, (secs, _) in sv["period"].items()
               if k != "dry_slack") == sv["period_s"]
    assert [(st["ready"], st["next_ready"]) for st in sv["slow_steps"]] == [
        (0, 1), (None, None)]
    text = report.render(s)
    assert ("period 10.0 s: empty 5.0 s 50.0% | starved 1.0 s 10.0% | "
            "caller-starved 0.5 s 5.0% | dry 0.5 s 5.0% (+ slack 0.25 s) | "
            "fed 3.0 s 30.0%") in text
    assert "serve.decode.wait ready=0 next_ready=1 2.7 s (limit 0.25)" in text
    assert "slow step: engine 0 host 0.4 s (limit 0.25)" in text
    # a stream of an engine before the count: no period, nothing printed
    old = [r for r in rows if r["kind"] != "serve_summary"]
    assert "period" not in report.summarize(old)["serving"]
    assert "period " not in report.render(report.summarize(old))


def test_facade_observe_section_feeds_stage_histograms():
    tel = Telemetry(sinks=[])
    try:
        for secs in (0.01, 0.02, 0.03):
            tel.observe_section("pp_stage0", secs)
        snap = tel.registry.snapshot()["histograms"]["section/pp_stage0"]
        assert snap["count"] == 3
        assert snap["p50"] == pytest.approx(0.02)
    finally:
        tel.close()


def test_ledger_unknown_category_books_as_other():
    led = GoodputLedger()
    led.book("???", 1.0)
    led.book("compute", -1.0)  # non-positive: ignored
    assert led.seconds == {"other": 1.0}


# ---------------------------------------------------------------------------
# compile watch (jax.monitoring)
# ---------------------------------------------------------------------------


def test_compile_watch_counts_real_compiles():
    import jax
    import jax.numpy as jnp

    watch = CompileWatch().install()
    try:
        assert watch.supported  # this JAX publishes the compile events
        assert watch.drain() == (0, 0.0)
        f = jax.jit(lambda x: x * 2 + 1)
        f(jnp.ones(3))
        n, secs = watch.drain()
        assert n >= 1 and secs > 0
        f(jnp.ones(3))  # cached: no new compile
        assert watch.drain()[0] == 0
        f(jnp.ones(5))  # new shape: recompile
        assert watch.drain()[0] >= 1
        assert watch.total_count >= 2
    finally:
        watch.uninstall()


def test_telemetry_flags_unexpected_step_recompile(tmp_path):
    """A compile observed in a 'step' phase after the first flags the
    recompile tripwire (shape/dtype drift symptom)."""
    import jax
    import jax.numpy as jnp

    tel = Telemetry(sinks=[JsonlSink(str(tmp_path / "t.jsonl"))])
    try:
        f = jax.jit(lambda x: x + 1)
        with tel.phases.phase("step", 1):
            f(jnp.ones(3))
        assert tel.registry.counter("compile/unexpected_recompiles").value \
            == 0
        with tel.phases.phase("step", 2):
            f(jnp.ones(7))  # shape drift -> re-jit
    finally:
        tel.close()
    kinds = [json.loads(ln)["kind"] for ln in open(tmp_path / "t.jsonl")]
    assert "recompile" in kinds
    assert tel.registry.counter("compile/unexpected_recompiles").value >= 1
    assert tel.ledger.seconds["compile"] > 0


# ---------------------------------------------------------------------------
# bus + facade
# ---------------------------------------------------------------------------


def test_bus_is_inert_without_install_and_routes_with():
    bus.emit("retry", category="retry_backoff", secs=1.0)  # no-op, no crash
    tel = Telemetry(sinks=[])
    bus.install(tel)
    try:
        bus.emit("retry", category="retry_backoff", secs=1.5, attempt=1)
        assert tel.ledger.seconds["retry_backoff"] == 1.5
        assert tel.registry.counter("events/retry").value == 1
    finally:
        tel.close()
    assert bus.active() is None  # close uninstalls


def test_retry_call_books_backoff_into_ledger():
    from picotron_tpu.resilience.retry import RetryPolicy, retry_call

    tel = Telemetry(sinks=[])
    bus.install(tel)
    try:
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("blip")
            return "ok"

        policy = RetryPolicy(attempts=3, base_delay=0.5, max_delay=1.0,
                             jitter=0.0)
        assert retry_call(flaky, policy=policy, sleep=lambda s: None) == "ok"
        # two retries: delays 0.5 + 1.0 booked as badput
        assert tel.ledger.seconds["retry_backoff"] == pytest.approx(1.5)
        assert tel.registry.counter("events/retry").value == 2
    finally:
        tel.close()


def test_facade_emit_and_run_summary(tmp_path):
    p = str(tmp_path / "t.jsonl")
    tel = Telemetry(sinks=[JsonlSink(p)])
    with tel.phases.phase("data", 1):
        pass
    tel.emit("chaos", chaos_kind="sigterm", step=3)
    tel.record_step(1, "[step 000001] ...", loss=2.0, tokens_per_sec=10.0)
    tel.record_eval(1, 3.5, "[eval  000001] ...")
    tel.close()
    tel.close()  # idempotent
    rows = [json.loads(ln) for ln in open(p)]
    kinds = [r["kind"] for r in rows]
    assert kinds == ["run_start", "phase", "chaos", "step", "eval",
                     "run_summary"]
    assert rows[-1]["goodput"]["accounted_seconds"] >= 0
    assert rows[-1]["metrics"]["counters"]["events/chaos"] == 1
    assert kinds.count("run_summary") == 1


def test_sick_sink_cannot_kill_a_step():
    class Boom(StdoutSink):
        def emit(self, event):
            raise RuntimeError("sink died")

    tel = Telemetry(sinks=[Boom()])
    try:
        tel.record_step(1, "line", loss=1.0)  # must not raise
        tel.emit("retry")
    finally:
        tel.close()


# ---------------------------------------------------------------------------
# the frozen stdout contract
# ---------------------------------------------------------------------------


def test_training_log_line_byte_format_is_frozen():
    """The stdout line is a de-facto API (extract_metrics regex + external
    scrapers): this pins the exact bytes, not just regex-parseability. A
    change here is a breaking change to downstream tooling — don't."""
    from picotron_tpu.utils import training_log_line

    line = training_log_line(7, 2.3456, 13500.0, 1687.5, 0.4321,
                             1230000, 11.5, extras={"grad_norm": 1.25})
    assert line == ("[step 000007] loss: 2.3456 | tokens/s: 13.5K | "
                    "tokens/s/chip: 1.69K | MFU: 43.21% | tokens: 1.23M | "
                    "mem: 11.5GB | grad_norm: 1.2500")


def test_training_log_line_matches_extract_metrics_regex():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    from extract_metrics import LINE_RE, parse_human

    from picotron_tpu.utils import training_log_line

    line = training_log_line(12, 5.4321, 98765.0, 12345.6, 0.1234, 999000,
                             3.2)
    m = LINE_RE.search(line)
    assert m is not None
    assert int(m.group("step")) == 12
    assert float(m.group("loss")) == 5.4321
    assert parse_human(m.group("tps")) == pytest.approx(98765.0, rel=0.01)
    assert float(m.group("mfu")) == 12.34


# ---------------------------------------------------------------------------
# report tool + extract_metrics integration
# ---------------------------------------------------------------------------


def _write_events(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def test_report_reproduces_steps_goodput_and_cross_restart_replay(tmp_path):
    """Two appended process lifetimes: run 1 trains 1-3 and dies; run 2
    resumes from the step-2 checkpoint and re-trains 3 before new ground.
    The report must count distinct steps once, book the re-trained step 3
    as replay, and sum categories exactly."""
    rep = load_report()
    ts = [100.0 + i for i in range(20)]
    events = [
        {"ts": ts[0], "kind": "phase", "phase": "step", "step": 1,
         "category": "compute", "secs": 4.0},
        {"ts": ts[1], "kind": "compile", "phase": "step", "step": 1,
         "category": "compile", "secs": 6.0},
        {"ts": ts[2], "kind": "phase", "phase": "step", "step": 2,
         "category": "compute", "secs": 4.0},
        {"ts": ts[3], "kind": "phase", "phase": "save", "step": 2,
         "category": "ckpt_io", "secs": 1.0},
        {"ts": ts[4], "kind": "phase", "phase": "step", "step": 3,
         "category": "compute", "secs": 4.0},
        # --- process 2 (appended after a kill + auto_resume) ---
        {"ts": ts[5], "kind": "phase", "phase": "restore", "step": None,
         "category": "restore", "secs": 2.0},
        {"ts": ts[6], "kind": "phase", "phase": "step", "step": 3,
         "category": "compute", "secs": 4.0},   # re-trained -> replay
        {"ts": ts[7], "kind": "phase", "phase": "step", "step": 4,
         "category": "compute", "secs": 4.0},
        {"ts": ts[8], "kind": "step", "step": 4, "loss": 2.5,
         "tokens_per_sec": 1000.0, "trained_tokens": 4096},
        {"ts": ts[9], "kind": "retry", "category": "retry_backoff",
         "secs": 1.0, "target": "checkpoint save"},
    ]
    p = tmp_path / "telemetry.jsonl"
    _write_events(p, events)
    s = rep.summarize(rep.load_events(str(p)))
    assert s["steps"] == {"count": 4, "max": 4, "replayed": 1}
    cats = s["categories"]
    assert cats["replay"] == 4.0
    assert cats["compute"] == 16.0
    assert cats["compile"] == 6.0
    assert cats["restore"] == 2.0
    assert cats["retry_backoff"] == 1.0
    assert s["goodput_pct"] == pytest.approx(
        100.0 * 16.0 / (16 + 4 + 6 + 1 + 2 + 1), abs=0.01)
    assert s["training"]["final_loss"] == 2.5
    # directory form resolves to the contained telemetry.jsonl
    s2 = rep.summarize(rep.load_events(rep.resolve_path(str(tmp_path))))
    assert s2["steps"]["count"] == 4


def test_report_render_text_and_markdown(tmp_path):
    rep = load_report()
    p = tmp_path / "telemetry.jsonl"
    _write_events(p, [
        {"ts": 1.0, "kind": "phase", "phase": "step", "step": 1,
         "category": "compute", "secs": 3.0},
        {"ts": 2.0, "kind": "phase", "phase": "save", "step": 1,
         "category": "ckpt_io", "secs": 1.0},
        {"ts": 3.0, "kind": "chaos", "chaos_kind": "sigterm", "step": 1},
    ])
    s = rep.summarize(rep.load_events(str(p)))
    text = rep.render(s)
    assert "goodput 75.00%" in text and "ckpt_io" in text
    md = rep.render(s, markdown=True)
    assert "| category | seconds | share |" in md
    assert "| compute | 3.000 | 75.0% |" in md
    assert "chaos=1" in md


def test_report_resize_row_includes_pp_resizes(tmp_path):
    """The resize row aggregates elastic_resize events regardless of
    axis: a pp resize (and a joint dp x pp one) must surface in
    `resize.events` with its restore seconds booked under the `resize`
    category — the accounting contract the pp_resize chaos scenario
    asserts end-to-end."""
    rep = load_report()
    p = tmp_path / "telemetry.jsonl"
    _write_events(p, [
        {"ts": 1.0, "kind": "phase", "phase": "step", "step": 1,
         "category": "compute", "secs": 3.0},
        {"ts": 2.0, "kind": "phase", "phase": "resize", "step": None,
         "category": "resize", "secs": 2.0},
        {"ts": 3.0, "kind": "elastic_resize", "step": 4, "axes": ["pp"],
         "from": {"dp": 1, "pp": 1}, "to": {"dp": 1, "pp": 2}},
        {"ts": 4.0, "kind": "phase", "phase": "resize", "step": None,
         "category": "resize", "secs": 0.5},
        {"ts": 5.0, "kind": "elastic_resize", "step": 5,
         "axes": ["dp", "pp"], "from": {"dp": 2, "pp": 2},
         "to": {"dp": 1, "pp": 1}},
    ])
    s = rep.summarize(rep.load_events(str(p)))
    assert s["resize"]["events"] == 2
    assert s["resize"]["seconds"] == pytest.approx(2.5)
    assert s["categories"]["resize"] == pytest.approx(2.5)


def test_report_pipeline_row(tmp_path):
    """A pp run's stream: pp_bubble events + per-stage section histograms
    in the run_summary must surface as the pipeline row (bubble share of
    step wall, per-stage tick p50/p95)."""
    rep = load_report()
    p = tmp_path / "telemetry.jsonl"
    _write_events(p, [
        {"ts": 1.0, "kind": "phase", "phase": "step", "step": 1,
         "category": "compute", "secs": 3.0},
        {"ts": 1.5, "kind": "pp_bubble", "phase": "step", "step": 1,
         "category": "pp_bubble", "secs": 1.0},
        {"ts": 2.0, "kind": "run_summary", "goodput": {},
         "metrics": {"histograms": {
             "section/pp_stage0": {"count": 8, "p50": 0.010, "p95": 0.012},
             "section/pp_stage1": {"count": 8, "p50": 0.011, "p95": 0.014},
             "phase/step": {"count": 1, "p50": 4.0, "p95": 4.0},
         }}},
    ])
    s = rep.summarize(rep.load_events(str(p)))
    pp = s["pipeline"]
    assert pp["bubble_s"] == 1.0
    assert pp["bubble_fraction"] == pytest.approx(0.25)
    assert pp["stages"]["pp_stage0"]["p50_ms"] == 10.0
    assert pp["stages"]["pp_stage1"]["p95_ms"] == 14.0
    assert "phase/step" not in pp.get("stages", {})
    text = rep.render(s)
    assert "bubble 25.0% of step wall" in text
    assert "pp_stage1" in text


def test_report_tolerates_torn_tail_line(tmp_path):
    rep = load_report()
    p = tmp_path / "telemetry.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"ts": 1.0, "kind": "phase", "phase": "step",
                            "step": 1, "category": "compute",
                            "secs": 1.0}) + "\n")
        f.write('{"ts": 2.0, "kind": "phase", "ph')  # killed mid-write
    s = rep.summarize(rep.load_events(str(p)))
    assert s["steps"]["count"] == 1


def test_extract_metrics_prefers_telemetry_jsonl(tmp_path):
    """The harvester satellite: a run dir carrying telemetry.jsonl is read
    structurally (full precision + goodput); the console log — present
    with DIFFERENT numbers — must not be consulted."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import extract_metrics as em

    run = tmp_path / "dp2_tp2_pp1_cp1"
    run.mkdir()
    (run / "train.log").write_text(
        "[step 000005] loss: 9.9999 | tokens/s: 1.0K | tokens/s/chip: 500 "
        "| MFU: 1.00% | tokens: 10K | mem: 1.0GB\n")
    events = []
    for s in range(1, 7):
        events.append({"ts": float(s), "kind": "phase", "phase": "step",
                       "step": s, "category": "compute", "secs": 3.0})
        events.append({"ts": float(s), "kind": "step", "step": s,
                       "loss": 6.0 - 0.5 * s, "tokens_per_sec": 2000.0,
                       "tokens_per_sec_per_chip": 500.0, "mfu": 0.45,
                       "trained_tokens": s * 512, "memory_gb": 1.0,
                       "grad_norm": 1.5})
    events.append({"ts": 7.0, "kind": "phase", "phase": "save", "step": 6,
                   "category": "ckpt_io", "secs": 2.0})
    events.append({"ts": 7.5, "kind": "eval", "step": 6, "val_loss": 4.25})
    _write_events(run / "telemetry.jsonl", events)

    stats = em.process_run(str(run), skip_steps=3)
    assert stats["steps"] == 3                      # steps 4..6 from jsonl
    assert stats["final_loss"] == pytest.approx(3.0)  # not the log's 9.9999
    assert stats["mean_mfu_pct"] == pytest.approx(45.0)
    assert stats["mean_grad_norm"] == pytest.approx(1.5)
    assert stats["final_val_loss"] == 4.25
    assert stats["goodput_pct"] == pytest.approx(100.0 * 18 / 20, abs=0.01)

    rows = em.aggregate(str(tmp_path), skip_steps=3)
    assert rows[0]["dp"] == 2 and rows[0]["goodput_pct"] == stats["goodput_pct"]


def test_extract_metrics_telemetry_replay_keeps_last_record(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import extract_metrics as em

    run = tmp_path / "run"
    run.mkdir()
    _write_events(run / "telemetry.jsonl", [
        {"kind": "step", "step": 5, "loss": 99.0, "tokens_per_sec": 1.0,
         "tokens_per_sec_per_chip": 1.0, "mfu": 0.0},
        {"kind": "step", "step": 5, "loss": 2.0, "tokens_per_sec": 1.0,
         "tokens_per_sec_per_chip": 1.0, "mfu": 0.0},  # post-rollback re-run
    ])
    stats = em.process_run(str(run), skip_steps=3)
    assert stats["steps"] == 1 and stats["final_loss"] == 2.0


def test_report_reads_rotated_stream_oldest_first(tmp_path):
    """A size-rotated stream (telemetry.jsonl.1 + telemetry.jsonl) must
    be read oldest segment first: the replayed-step bookkeeping is
    order-sensitive — reading the live segment first would count the
    re-trained step as first-sight and the original as the replay."""
    rep = load_report()
    p = str(tmp_path / "telemetry.jsonl")
    with open(p + ".1", "w") as f:
        for s in (1, 2, 3):
            f.write(json.dumps({"ts": float(s), "kind": "phase",
                                "phase": "step", "step": s,
                                "category": "compute", "secs": 2.0}) + "\n")
    _write_events(p, [
        {"ts": 4.0, "kind": "phase", "phase": "step", "step": 3,
         "category": "compute", "secs": 2.0},  # re-trained after rollback
        {"ts": 5.0, "kind": "phase", "phase": "step", "step": 4,
         "category": "compute", "secs": 2.0},
    ])
    s = rep.summarize(rep.load_events(p))
    assert s["steps"] == {"count": 4, "max": 4, "replayed": 1}
    assert s["categories"]["replay"] == 2.0
    assert s["categories"]["compute"] == 8.0


def test_report_sentinel_section_from_alert_events(tmp_path):
    rep = load_report()
    p = tmp_path / "telemetry.jsonl"
    _write_events(p, [
        {"ts": 1.0, "kind": "phase", "phase": "step", "step": 1,
         "category": "compute", "secs": 1.0},
        {"ts": 2.0, "kind": "sentinel_alert", "quantity": "sync_share",
         "value": 0.45, "baseline": 0.1, "ratio": 4.5, "step": 40},
    ])
    s = rep.summarize(rep.load_events(str(p)))
    assert s["sentinel"] == {"alerts": 1, "quantity": "sync_share",
                             "worst_ratio": 4.5}
    text = rep.render(s)
    assert "sentinel: 1 alert(s) — worst sync_share at 4.50x baseline" \
        in text
    md = rep.render(s, markdown=True)
    assert "**sentinel: 1 alert(s)" in md
    # clean stream: no sentinel row at all
    p2 = tmp_path / "clean.jsonl"
    _write_events(p2, [
        {"ts": 1.0, "kind": "phase", "phase": "step", "step": 1,
         "category": "compute", "secs": 1.0}])
    s2 = rep.summarize(rep.load_events(str(p2)))
    assert "sentinel" not in s2
    assert "sentinel" not in rep.render(s2)


def test_extract_metrics_sentinel_column_and_rotated_stream(tmp_path):
    """The harvester satellite: `sentinel_alerts` is a first-class sweep
    column (0 on a clean run, counted across rotated segments) so a
    regression sweep can filter on it."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import extract_metrics as em

    run = tmp_path / "run"
    run.mkdir()
    p = str(run / "telemetry.jsonl")
    with open(p + ".1", "w") as f:
        for s in range(1, 4):
            f.write(json.dumps({"kind": "step", "step": s, "loss": 3.0,
                                "tokens_per_sec": 1.0,
                                "tokens_per_sec_per_chip": 1.0,
                                "mfu": 0.0}) + "\n")
    _write_events(run / "telemetry.jsonl", [
        {"kind": "sentinel_alert", "quantity": "step_time", "value": 3.0,
         "baseline": 1.0, "ratio": 3.0, "step": 5},
        {"kind": "step", "step": 5, "loss": 2.0, "tokens_per_sec": 1.0,
         "tokens_per_sec_per_chip": 1.0, "mfu": 0.0},
    ])
    stats = em.process_run(str(run), skip_steps=0)
    assert stats["sentinel_alerts"] == 1
    assert stats["steps"] == 4  # both segments consulted
    assert stats["final_loss"] == 2.0

    clean = tmp_path / "clean"
    clean.mkdir()
    _write_events(clean / "telemetry.jsonl", [
        {"kind": "step", "step": 1, "loss": 2.0, "tokens_per_sec": 1.0,
         "tokens_per_sec_per_chip": 1.0, "mfu": 0.0}])
    assert em.process_run(str(clean), skip_steps=0)["sentinel_alerts"] == 0


def test_extract_metrics_falls_back_to_log_when_jsonl_empty(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import extract_metrics as em

    run = tmp_path / "run"
    run.mkdir()
    (run / "telemetry.jsonl").write_text("")
    (run / "train.log").write_text(
        "[step 000005] loss: 4.5000 | tokens/s: 1.0K | tokens/s/chip: 500 "
        "| MFU: 10.00% | tokens: 10K | mem: 1.0GB\n")
    stats = em.process_run(str(run), skip_steps=3)
    assert stats["final_loss"] == 4.5


def test_cli_telemetry_comm_row(tmp_path, capsys):
    """`--config` adds the cost model's exposed-comm prediction for the
    run's layout beside the measured phases (tools/telemetry_report.py
    comm_row), from a config tools/create_config.py wrote."""
    from tests.test_tools import load_tool

    cc = load_tool("create_config")
    args = cc.build_parser().parse_args(
        ["--exp-name", "t", "--out-dir", str(tmp_path), "--model",
         "SmolLM-1.7B", "--dp", "2", "--tp", "4", "--seq-len", "2048",
         "--use-cpu"])
    cfg_path = cc.create_single_config(args)
    capsys.readouterr()

    tele = tmp_path / "telemetry.jsonl"
    tele.write_text(json.dumps(
        {"kind": "phase", "phase": "step", "step": 0,
         "category": "compute", "secs": 0.5, "ts": 1.0}) + "\n")
    assert load_report().main([str(tele), "--config", cfg_path,
                               "--json"]) == 0
    cm = json.loads(capsys.readouterr().out)["comm"]
    assert cm["generation"] == "v5e"
    assert 0 < cm["predicted_comm_ms"] < cm["predicted_step_ms"]
    assert cm["measured_step_p50_ms"] == 500.0
