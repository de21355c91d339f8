"""Flightdeck tests (picotron_tpu/telemetry/flightdeck): the span
tracer's recording/ordering/bounding invariants, the Perfetto-schema
validity of an exported trace carrying every span family (train phases,
MPMD pp2 stage ticks, the serve request lifecycle, resilience
instants), the flight recorder's ring/dump semantics, the drift
sentinel's exactly-one-alert contract (and its silence on a clean
twin), the config-driven install() policy, and the
tools/trace_export.py --validate gate run as a subprocess smoke —
tier-1, like the shardcheck gates."""

import importlib.util
import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, PipelineConfig, ServeConfig,
    TrainingConfig, config_from_dict, resolve_preset,
)
from picotron_tpu.telemetry import JsonlSink, Telemetry, bus
from picotron_tpu.telemetry.flightdeck import (
    DriftSentinel, FlightRecorder, SpanTracer, TID_PP_BASE, TID_SENTINEL,
    TID_SERVE, TID_TRAIN, install as flightdeck_install,
)
from picotron_tpu.telemetry.flightdeck.flight import POSTMORTEM_NAME

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
TRACE_EXPORT = os.path.join(TOOLS, "trace_export.py")


def load_trace_export():
    spec = importlib.util.spec_from_file_location(
        "trace_export", TRACE_EXPORT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Clock:
    """Deterministic tracer clock (seconds)."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_tracer_span_ordering_and_nesting():
    c = Clock()
    tr = SpanTracer(clock=c)
    outer_start = tr.now()
    c.t = 100.010
    inner_start = tr.now()
    c.t = 100.020
    tr.complete("inner", start_s=inner_start, dur_s=0.010, mb=1)
    c.t = 100.030
    tr.complete("outer", start_s=outer_start, dur_s=0.030)
    # a span is recorded with where it started: there is no back-dating
    # from the moment of the call
    with pytest.raises(TypeError):
        tr.complete("late", dur_s=0.010)
    doc = tr.to_json()
    events = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    # sorted by ts regardless of recording order: outer first
    assert [e["name"] for e in events] == ["outer", "inner"]
    outer, inner = events
    assert outer["ts"] == pytest.approx(0.0, abs=1e-6)
    assert outer["dur"] == pytest.approx(30_000.0)  # microseconds
    # nesting invariant: the inner span lies within the outer window
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner["args"] == {"mb": 1}
    assert all(e["ph"] == "X" and e["tid"] == TID_TRAIN for e in events)


def test_tracer_instants_and_lane_labels():
    tr = SpanTracer(clock=Clock())
    tr.instant("rollback", step=4)
    tr.instant("sentinel_alert", tid=TID_SENTINEL)
    tr.complete("pp.1.F", tid=TID_PP_BASE + 1, start_s=100.0, dur_s=0.001)
    tr.complete("serve.prefill.dispatch", tid=TID_SERVE, start_s=100.0,
                dur_s=0.001, ids="0 1")
    doc = tr.to_json()
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    # lanes self-label: train, serve, flightdeck, pp_stage1
    names = {e["tid"]: e["args"]["name"] for e in meta}
    assert names[TID_TRAIN] == "train"
    assert names[TID_SERVE] == "serve"
    assert names[TID_SENTINEL] == "flightdeck"
    assert names[TID_PP_BASE + 1] == "pp_stage1"
    inst = next(e for e in doc["traceEvents"] if e["ph"] == "i")
    assert inst["s"] == "p" and inst["args"] == {"step": 4}


def test_tracer_bounded_ring_counts_drops():
    tr = SpanTracer(clock=Clock(), max_events=5)
    for i in range(8):
        tr.complete(f"s{i}", start_s=100.0, dur_s=0.001)
    assert len(tr) == 5 and tr.dropped == 3
    doc = tr.to_json()
    assert doc["otherData"]["dropped_events"] == 3
    # a truncated trace is never mistaken for a quiet one
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) == 5


def test_tracer_mark_since_and_atomic_export(tmp_path):
    tr = SpanTracer(clock=Clock())
    tr.complete("before", start_s=100.0, dur_s=0.0)
    m = tr.mark()
    tr.complete("after1", start_s=100.0, dur_s=0.0)
    tr.instant("after2")
    assert [e["name"] for e in tr.since(m)] == ["after1", "after2"]
    assert tr.since(tr.mark()) == []
    path = str(tmp_path / "trace.json")
    assert tr.export(path) == path
    assert not os.path.exists(path + ".tmp")
    doc = json.load(open(path))
    assert doc["traceEvents"][0]["ph"] == "M"  # metadata lanes lead


# ---------------------------------------------------------------------------
# the acceptance trace: every span family on one validated timeline
# ---------------------------------------------------------------------------


def _mpmd_cfg():
    return Config(
        distributed=DistributedConfig(pp_size=2, dp_size=1, tp_size=1),
        model=ModelConfig(dtype="float32", hidden_size=64,
                          num_hidden_layers=2, num_attention_heads=8,
                          num_key_value_heads=4),
        training=TrainingConfig(seq_length=32, micro_batch_size=2,
                                gradient_accumulation_steps=2,
                                learning_rate=1e-3, remat=False),
        pipeline=PipelineConfig(executor="mpmd", schedule="1f1b"),
    )


def test_dryrun_trace_has_all_span_families_and_validates(tmp_path):
    """The acceptance pin: a 2-step CPU dryrun (real MPMD pp2 executor +
    real serve engine, driven through the facade) exports
    one Chrome-trace JSON carrying train phases, per-op stage-tick
    spans, the serve request lifecycle (queue_wait -> the engine step's
    prefill / decode spans with request ids), and a resilience
    instant — and
    `tools/trace_export.py --validate` accepts it (subprocess, the same
    gate a CI smoke would run)."""
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.parallel.api import init_sharded_state, make_train_step
    from picotron_tpu.serve import ServeEngine

    trace_path = str(tmp_path / "trace.json")
    tel = Telemetry(sinks=[])
    tel.tracer = SpanTracer()
    tel.trace_path = trace_path
    bus.install(tel)  # the MPMD walker finds the tracer via the bus
    try:
        cfg = _mpmd_cfg()
        menv = MeshEnv.from_config(cfg)
        state = init_sharded_state(cfg, menv, jax.random.key(0))
        step_fn = make_train_step(cfg, menv)
        t = cfg.training
        toks = jax.random.randint(
            jax.random.key(1),
            (t.gradient_accumulation_steps, t.micro_batch_size,
             t.seq_length + 1), 0, cfg.model.vocab_size)
        sh = NamedSharding(menv.mesh, P(None, "dp", "cp"))
        batch = (jax.device_put(toks[..., :-1], sh),
                 jax.device_put(toks[..., 1:], sh))
        for step in (1, 2):
            with tel.phases.phase("data", step):
                pass
            with tel.phases.phase("step", step):
                state, metrics = step_fn(state, batch)
                jax.block_until_ready(metrics)
            tel.record_step(step, "[step] ...", loss=float(metrics["loss"]))

        mcfg = ModelConfig(dtype="float32", **{
            **resolve_preset("debug-tiny"), "max_position_embeddings": 64})
        params = init_params(mcfg, jax.random.key(0))
        rng = np.random.default_rng(0)
        reqs = [(list(map(int, rng.integers(0, mcfg.vocab_size, size=n))), 3)
                for n in (5, 7)]
        eng = ServeEngine(
            params, mcfg,
            ServeConfig(decode_slots=2, block_size=4, num_blocks=16,
                        prefill_chunk=4, max_model_len=32,
                        decode_interval=2),
            telemetry=tel)
        eng.run(reqs)
        eng.close()

        tel.emit("chaos", chaos_kind="sigterm", step=2)
    finally:
        tel.close()  # exports the trace

    doc = json.load(open(trace_path))
    events = doc["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    names_by_lane = {}
    for e in spans:
        names_by_lane.setdefault(e["tid"], set()).add(e["name"])
    # train phases
    assert {"train.data", "train.step"} <= names_by_lane[TID_TRAIN]
    # MPMD per-op spans on both pp stage lanes, tick and microbatch attached
    tick_re = re.compile(r"pp\.\d+\.[FB]")
    for stage in (0, 1):
        lane = names_by_lane.get(TID_PP_BASE + stage, set())
        assert any(tick_re.fullmatch(n) for n in lane), (stage, lane)
    assert all({"tick", "mb", "step"} <= set(e["args"]) for e in spans
               if e["tid"] >= TID_PP_BASE)
    # serve request lifecycle, ids attached
    assert {"serve.queue_wait", "serve.step", "serve.prefill.dispatch",
            "serve.decode.dispatch"} <= names_by_lane[TID_SERVE]
    serve = [e for e in spans if e["tid"] == TID_SERVE]
    assert all("id" in e.get("args", {}) for e in serve
               if e["name"] == "serve.queue_wait")
    assert all("ids" in e.get("args", {}) for e in serve
               if e["name"] in ("serve.prefill.dispatch",
                                "serve.decode.dispatch"))
    # resilience instant
    assert any(e["ph"] == "i" and e["name"] == "chaos" for e in events)
    # lanes are labeled
    labels = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"train", "serve", "pp_stage0", "pp_stage1"} <= labels

    proc = subprocess.run(
        [sys.executable, TRACE_EXPORT, "--validate", trace_path],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.startswith("OK:")


def test_trace_validate_catches_violations(tmp_path):
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 0, "tid": 0, "ts": 100.0,
         "dur": 5.0},
        {"name": "b", "ph": "X", "pid": 0, "tid": 0, "ts": 50.0,
         "dur": -1.0},                                   # rewind + negative
        {"name": "c", "ph": "B", "pid": 0, "tid": 1, "ts": 160.0},
        {"name": "d", "ph": "E", "pid": 0, "tid": 2, "ts": 170.0},
        {"name": "e", "ph": "X", "pid": "zero", "tid": 0, "ts": 180.0,
         "dur": 1.0},                                    # string pid
        {"name": "f", "ph": "??", "ts": 190.0},          # invalid ph
    ]}
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    te = load_trace_export()
    errors = te.validate(str(p))
    text = "\n".join(errors)
    assert "not monotonic" in text
    assert "dur >= 0" in text
    assert "never closed" in text
    assert "E without matching B" in text
    assert "pid/tid must be integers" in text
    assert "invalid ph" in text

    proc = subprocess.run(
        [sys.executable, TRACE_EXPORT, "--validate", str(p)],
        capture_output=True, text=True)
    assert proc.returncode == 1
    assert "TRACE VIOLATION" in proc.stderr

    # a clean trace passes in-module too
    good = tmp_path / "good.json"
    good.write_text(json.dumps({"traceEvents": [
        {"name": "s", "ph": "X", "pid": 0, "tid": 0, "ts": 1.0,
         "dur": 2.0}]}))
    assert te.validate(str(good)) == []


def test_trace_export_converts_jsonl_to_valid_trace(tmp_path):
    """The post-hoc fallback: a telemetry.jsonl becomes a valid trace
    with serve phases on the serve lane (ids carried), train phases on
    the train lane (back-dated from their end-stamped events), and
    resilience kinds as instants."""
    te = load_trace_export()
    src = tmp_path / "telemetry.jsonl"
    with open(src, "w") as f:
        for e in [
            {"ts": 100.0, "kind": "run_start"},
            {"ts": 103.0, "kind": "phase", "phase": "step", "step": 1,
             "category": "compute", "secs": 2.0},
            {"ts": 103.5, "kind": "phase", "phase": "prefill",
             "category": "serve", "secs": 0.25, "ids": [0, 1]},
            {"ts": 104.0, "kind": "chaos", "chaos_kind": "sigterm",
             "step": 1},
        ]:
            f.write(json.dumps(e) + "\n")
    doc = te.convert(te.load_events(str(src)))
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert spans["step"]["tid"] == TID_TRAIN
    # end-stamped at 103.0 with secs=2.0 -> starts 1.0s after run_start
    assert spans["step"]["ts"] == pytest.approx(1.0e6)
    assert spans["step"]["dur"] == pytest.approx(2.0e6)
    assert spans["prefill"]["tid"] == TID_SERVE
    assert spans["prefill"]["args"]["ids"] == [0, 1]
    assert any(e["ph"] == "i" and e["name"] == "chaos"
               for e in doc["traceEvents"])
    out = tmp_path / "converted.json"
    out.write_text(json.dumps(doc))
    assert te.validate(str(out)) == []


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def _feed_step(fr, step, step_s=1.0, **metrics):
    fr.on_phase("data", 0.1, step=step)
    fr.on_phase("step", step_s, step=step)
    fr.on_step(step, {"loss": 2.0, "line": "[step] ...", **metrics})


def test_flight_ring_evicts_oldest_and_dumps(tmp_path):
    fr = FlightRecorder(str(tmp_path), max_steps=3)
    for s in range(1, 7):
        _feed_step(fr, s)
    fr.on_event("chaos", {"chaos_kind": "sigterm", "step": 6,
                          "line": "noise"})
    path = fr.dump("watchdog", step=6, phase="data", stalled_s=12.5)
    assert path == os.path.join(str(tmp_path), POSTMORTEM_NAME)
    doc = json.load(open(path))
    assert doc["reason"] == "watchdog"
    assert doc["step"] == 6
    # the bounded ring holds exactly the last 3 steps
    assert [r["step"] for r in doc["steps"]] == [4, 5, 6]
    rec = doc["steps"][-1]
    assert rec["phases"]["step"] == pytest.approx(1.0)
    assert rec["metrics"]["loss"] == 2.0
    assert "line" not in rec["metrics"]  # presentation, not signal
    assert doc["recent_events"] == [{"kind": "chaos",
                                     "chaos_kind": "sigterm", "step": 6}]
    assert doc["extra"] == {"phase": "data", "stalled_s": 12.5}
    assert fr.dumps == 1


def test_flight_partial_step_and_fallback_step(tmp_path):
    fr = FlightRecorder(str(tmp_path), max_steps=4)
    _feed_step(fr, 1)
    fr.on_phase("data", 0.5, step=2)  # step 2 dies mid-flight
    doc = fr.snapshot("exception")
    assert doc["step"] == 2  # no explicit step: last seen wins
    partial = doc["steps"][-1]
    assert partial["partial"] is True and partial["step"] == 2
    assert partial["phases"] == {"data": 0.5}
    assert doc["steps"][0]["step"] == 1
    assert fr.last_step() == 2
    assert FlightRecorder(str(tmp_path)).last_step() is None


def test_flight_dump_never_raises_and_last_writer_wins(tmp_path):
    # unwritable directory: best-effort None, no exception
    fr = FlightRecorder(str(tmp_path / "does" / "not" / "exist"))
    assert fr.dump("watchdog") is None and fr.dumps == 0
    fr2 = FlightRecorder(str(tmp_path))
    _feed_step(fr2, 1)
    fr2.dump("rollback", step=1)
    _feed_step(fr2, 2)
    fr2.dump("preempted", step=2)
    doc = json.load(open(os.path.join(str(tmp_path), POSTMORTEM_NAME)))
    assert doc["reason"] == "preempted" and doc["step"] == 2
    assert fr2.dumps == 2


def test_flight_attributes_tracer_spans_per_step(tmp_path):
    c = Clock()
    tr = SpanTracer(clock=c)
    tr.complete("preamble", start_s=0.0, dur_s=0.0)  # before the recorder attaches
    fr = FlightRecorder(str(tmp_path), max_steps=4, tracer=tr)
    tr.complete("pp.0.F", tid=TID_PP_BASE, start_s=0.0, dur_s=0.001)
    fr.on_step(1, {})
    tr.complete("pp.0.B", tid=TID_PP_BASE, start_s=0.0, dur_s=0.001)
    fr.on_step(2, {})
    doc = fr.snapshot("watchdog")
    assert [s["name"] for s in doc["steps"][0]["spans"]] == ["pp.0.F"]
    assert [s["name"] for s in doc["steps"][1]["spans"]] == ["pp.0.B"]


# ---------------------------------------------------------------------------
# drift sentinel
# ---------------------------------------------------------------------------


def _run_sentinel(sen, step_times, data=0.0, sync=0.0):
    alerts = []
    for i, st in enumerate(step_times, start=1):
        if data:
            sen.observe_phase("data", data)
        if sync:
            sen.observe_phase("sync", sync)
        sen.observe_phase("step", st)
        a = sen.on_step(i)
        if a is not None:
            alerts.append(a)
    return alerts


def test_sentinel_fires_exactly_once_on_sustained_regression():
    sen = DriftSentinel(window=8, zscore=4.0, ratio=1.5, patience=3)
    # 8 clean steps at 1.0s, then a sustained 3x regression
    alerts = _run_sentinel(sen, [1.0] * 8 + [3.0] * 6)
    assert len(alerts) == 1
    a = alerts[0]
    assert a["quantity"] == "step_time"
    assert a["value"] == pytest.approx(3.0)
    assert a["baseline"] == pytest.approx(1.0)
    assert a["ratio"] == pytest.approx(3.0)
    assert a["streak"] == 3
    assert a["step"] == 11  # the patience'th consecutive breach
    assert a["step_time_p50_s"] == pytest.approx(1.0)
    assert sen.alerted and sen.stats()["alerts"] == 1
    # breaching samples stayed OUT of the baseline window
    assert sen.stats()["step_time_p50_s"] == pytest.approx(1.0)


def test_sentinel_silent_on_clean_twin_and_during_warmup():
    sen = DriftSentinel(window=8, zscore=4.0, ratio=1.5, patience=3)
    jittered = [1.0 + 0.002 * ((i % 5) - 2) for i in range(24)]
    assert _run_sentinel(sen, jittered) == []
    assert not sen.alerted
    assert sen.stats()["step_time_p50_s"] == pytest.approx(1.0, abs=0.01)
    # warmup: a spike before the baseline exists is never judged
    sen2 = DriftSentinel(window=8, zscore=4.0, ratio=1.5, patience=1)
    assert _run_sentinel(sen2, [1.0, 50.0, 1.0]) == []


def test_sentinel_transient_blip_resets_streak():
    sen = DriftSentinel(window=8, zscore=4.0, ratio=1.5, patience=3)
    # two-step blips (below patience) never alert, however many
    alerts = _run_sentinel(
        sen, [1.0] * 8 + [3.0, 3.0, 1.0, 3.0, 3.0, 1.0, 3.0, 3.0, 1.0])
    assert alerts == []


def test_sentinel_zscore_suppresses_noisy_ratio_trips():
    sen = DriftSentinel(window=8, zscore=4.0, ratio=1.5, patience=2)
    noisy = [0.5, 1.5] * 4  # median 1.0, wide std
    assert _run_sentinel(sen, noisy + [1.6] * 4) == []  # ratio yes, z no
    alerts = _run_sentinel(sen, [10.0] * 2)  # far outside the noise
    assert len(alerts) == 1 and alerts[0]["quantity"] == "step_time"


def test_sentinel_sync_share_judged_against_cost_model_prediction():
    sen = DriftSentinel(window=8, zscore=4.0, ratio=1.5, patience=2,
                        predicted={"total_s": 2.0, "exposed_comm_s": 0.2})
    assert sen.predicted_sync_share() == pytest.approx(0.1)
    # measured sync share 0.10 == predicted: clean
    assert _run_sentinel(sen, [0.9] * 6, sync=0.1) == []
    # exposed comm grows to a 0.45 share while step wall stays 1.0s —
    # step_time cannot fire, sync_share (vs the prediction) must
    alerts = _run_sentinel(sen, [0.55] * 2, sync=0.45)
    assert len(alerts) == 1
    assert alerts[0]["quantity"] == "sync_share"
    assert alerts[0]["baseline"] == pytest.approx(0.1)
    assert sen.stats()["predicted_sync_share"] == pytest.approx(0.1)


def test_sentinel_data_wait_share_regression():
    sen = DriftSentinel(window=8, zscore=4.0, ratio=1.5, patience=2)
    assert _run_sentinel(sen, [1.0] * 8, data=0.1) == []
    alerts = _run_sentinel(sen, [1.0] * 2, data=1.0)
    assert len(alerts) == 1
    assert alerts[0]["quantity"] == "data_wait_share"


def test_sentinel_eval_only_iterations_are_skipped():
    sen = DriftSentinel(window=8, patience=1)
    sen.observe_phase("data", 0.5)  # no step/sync phase this iteration
    assert sen.on_step(1) is None
    assert sen.stats()["window"] == 0


# ---------------------------------------------------------------------------
# facade integration: one alert, one auto-dump, report surfaces it
# ---------------------------------------------------------------------------


def test_facade_sentinel_alert_emits_once_and_autodumps(tmp_path):
    p = str(tmp_path / "telemetry.jsonl")
    tel = Telemetry(sinks=[JsonlSink(p)])
    tel.flight = FlightRecorder(str(tmp_path), max_steps=4)
    tel.sentinel = DriftSentinel(window=8, zscore=4.0, ratio=1.5,
                                 patience=2)
    times = [1.0] * 8 + [4.0] * 5
    for i, st in enumerate(times, start=1):
        tel.emit("phase", phase="step", secs=st, book=False, step=i)
        tel.record_step(i, "[step] ...", loss=2.0)
    tel.close()
    rows = [json.loads(ln) for ln in open(p)]
    alerts = [r for r in rows if r["kind"] == "sentinel_alert"]
    assert len(alerts) == 1
    assert alerts[0]["quantity"] == "step_time"
    assert alerts[0]["step"] == 10
    # the run summary carries the sentinel stats block
    summary = rows[-1]
    assert summary["kind"] == "run_summary"
    assert summary["sentinel"]["alerts"] == 1
    # auto-dump: the postmortem names the alert and the fault step
    doc = json.load(open(tmp_path / POSTMORTEM_NAME))
    assert doc["reason"] == "sentinel_alert"
    assert doc["step"] == 10
    assert doc["extra"]["alert"]["quantity"] == "step_time"
    assert doc["steps"]  # the last-K window came along

    # the report tool renders the sentinel row from the same stream
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(TOOLS, "telemetry_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    s = rep.summarize(rep.load_events(p))
    assert s["sentinel"]["alerts"] == 1
    assert s["sentinel"]["quantity"] == "step_time"
    text = rep.render(s)
    assert "sentinel: 1 alert(s)" in text
    assert "flightdeck_postmortem.json" in text


def test_facade_clean_run_emits_no_sentinel_events(tmp_path):
    p = str(tmp_path / "telemetry.jsonl")
    tel = Telemetry(sinks=[JsonlSink(p)])
    tel.flight = FlightRecorder(str(tmp_path), max_steps=4)
    tel.sentinel = DriftSentinel(window=8, zscore=4.0, ratio=1.5,
                                 patience=2)
    for i in range(1, 14):
        tel.emit("phase", phase="step", secs=1.0, book=False, step=i)
        tel.record_step(i, "[step] ...", loss=2.0)
    tel.close()
    kinds = [json.loads(ln)["kind"] for ln in open(p)]
    assert "sentinel_alert" not in kinds
    assert not os.path.exists(tmp_path / POSTMORTEM_NAME)  # no dump


# ---------------------------------------------------------------------------
# install(): the config-driven attachment policy
# ---------------------------------------------------------------------------


def test_install_attaches_per_config(tmp_path):
    cfg = config_from_dict({
        "model": {"name": "debug-tiny"},
        "checkpoint": {"save_dir": str(tmp_path / "ck")},
        "logging": {"trace_dir": str(tmp_path / "tr"),
                    "flight_steps": 4, "sentinel": True,
                    "sentinel_window": 16, "sentinel_patience": 2},
    })
    tel = Telemetry(sinks=[])
    try:
        flightdeck_install(tel, cfg)
        assert tel.tracer is not None
        assert tel.trace_path == str(tmp_path / "tr" / "trace.json")
        assert tel.flight is not None and tel.flight.max_steps == 4
        assert tel.flight.path == str(
            tmp_path / "ck" / POSTMORTEM_NAME)
        assert tel.flight.tracer is tel.tracer
        assert tel.sentinel is not None
        assert tel.sentinel.window == 16 and tel.sentinel.patience == 2
    finally:
        tel.close()


def test_install_defaults_leave_hot_path_untouched(tmp_path):
    # default logging config + a save_dir: the flight recorder is on
    # (abnormal exits always leave a postmortem) but the tracer and
    # sentinel — the pieces with per-phase cost — stay None
    cfg = config_from_dict({
        "model": {"name": "debug-tiny"},
        "checkpoint": {"save_dir": str(tmp_path / "ck")}})
    tel = Telemetry(sinks=[])
    try:
        flightdeck_install(tel, cfg)
        assert tel.tracer is None and tel.trace_path is None
        assert tel.sentinel is None
        assert tel.flight is not None and tel.flight.max_steps == 8
        # flight_steps=0 is the postmortem off-switch
        cfg2 = config_from_dict({
            "model": {"name": "debug-tiny"},
            "checkpoint": {"save_dir": str(tmp_path / "ck2")},
            "logging": {"flight_steps": 0}})
        tel2 = Telemetry(sinks=[])
        flightdeck_install(tel2, cfg2)
        assert tel2.flight is None
        assert tel2.tracer is None and tel2.sentinel is None
        tel2.close()
    finally:
        tel.close()


def test_install_multiprocess_trace_paths(tmp_path):
    cfg = config_from_dict({
        "model": {"name": "debug-tiny"},
        "logging": {"trace_dir": str(tmp_path / "tr")}})
    tel = Telemetry(sinks=[])
    try:
        flightdeck_install(tel, cfg, process_index=2)
        assert tel.trace_path == str(tmp_path / "tr" / "trace.p2.json")
        assert tel.tracer.pid == 2
    finally:
        tel.close()


def test_logging_config_validates_flightdeck_fields():
    def cfg(**logging):
        return config_from_dict({"model": {"name": "debug-tiny"},
                                 "logging": logging})

    cfg(sentinel=True, sentinel_window=4).validate()  # the floor is legal
    with pytest.raises(ValueError):
        cfg(telemetry_max_mb=-1).validate()
    with pytest.raises(ValueError):
        cfg(flight_steps=-1).validate()
    with pytest.raises(ValueError):
        cfg(sentinel_window=2).validate()
    with pytest.raises(ValueError):
        cfg(sentinel_ratio=1.0).validate()
    with pytest.raises(ValueError):
        cfg(sentinel_zscore=0.0).validate()
    with pytest.raises(ValueError):
        cfg(sentinel_patience=0).validate()
