"""Resilience subsystem tests (picotron_tpu/resilience): chaos spec
parsing and firing, retry backoff, divergence-guard policies, watchdog,
preemption handler, the in-jit non-finite skip, loader reset/retry, and
checkpoint-save retry — all on CPU, fault injection included (the chaos
harness exists precisely so these paths are tier-1-testable instead of
being exercised for the first time by a real outage). The slow tier runs
tools/chaos.py's full kill-and-recover scenarios."""

import json
import os
import random
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import config_from_dict
from picotron_tpu.resilience import (
    DivergenceGuard, GuardAction, PreemptionHandler, RetryPolicy, Watchdog,
    backoff_delays, chaos, retry_call,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


@pytest.fixture(autouse=True)
def _chaos_hygiene():
    """Chaos state is process-global (library injection points reach it
    without plumbing); every test starts and ends inert."""
    chaos.install("")
    yield
    chaos.install("")


# ---------------------------------------------------------------------------
# chaos spec + controller
# ---------------------------------------------------------------------------


def test_chaos_spec_parses_all_fields():
    evs = chaos.parse_spec("sigterm@3, ckpt_io@2x2,data_stall@4~1.5,"
                           "nan_grad@5x2")
    assert [(e.kind, e.step, e.count, e.secs) for e in evs] == [
        ("sigterm", 3, 1, 0.0), ("ckpt_io", 2, 2, 0.0),
        ("data_stall", 4, 1, 1.5), ("nan_grad", 5, 2, 0.0)]
    assert chaos.parse_spec("") == []


@pytest.mark.parametrize("bad", [
    "bogus@3",        # unknown kind
    "sigterm",        # missing @STEP
    "ckpt_io@x",      # non-numeric step
    "data_stall@3",   # sleep kind without ~SECS
    "hang@2~0",       # zero-duration sleep
    "ckpt_io@2#1",    # #TICK on a kind with no schedule_tick meaning
    "nan_grad@3#2",   # ditto — poison is a step property, not an op one
    "slice_lost@3#1",  # a slice dies between steps, never mid-schedule
])
def test_chaos_spec_rejects_malformed(bad):
    with pytest.raises(ValueError):
        chaos.parse_spec(bad)


def test_chaos_spec_errors_are_actionable():
    """The two parse errors teach the full surface: a malformed event
    names the complete KIND@STEP[xCOUNT][~SECS][#TICK] grammar, an
    unknown kind enumerates every valid kind (slice_lost included) —
    and the module docstring documents the grammar it parses."""
    with pytest.raises(ValueError, match=r"\[xCOUNT\]\[~SECS\]\[#TICK\]"):
        chaos.parse_spec("sigterm")
    with pytest.raises(ValueError) as ei:
        chaos.parse_spec("bogus@3")
    for kind in chaos.KINDS:
        assert kind in str(ei.value), kind
    assert "slice_lost" in chaos.KINDS
    assert "slice_lost" in chaos.__doc__
    assert "slice_lost" in chaos._POINT_KINDS["step_begin"]
    assert "slice_lost" not in chaos._TICK_KINDS


def test_chaos_slice_lost_fires_sigkill_naming_the_slice(monkeypatch,
                                                         capsys):
    """slice_lost: SIGKILL at step_begin of the named step — per process,
    like a whole slice going dark at once — with the lost slice named in
    the log so a multi-host transcript is attributable."""
    calls = []
    monkeypatch.setattr(chaos.os, "kill",
                        lambda pid, sig: calls.append((pid, sig)))
    ctrl = chaos.ChaosController(chaos.parse_spec("slice_lost@3"))
    ctrl.fire("step_begin", step=2)
    assert not calls
    ctrl.fire("step_begin", step=3)
    assert calls == [(os.getpid(), signal.SIGKILL)]
    ctrl.fire("step_begin", step=3)  # budget of 1: exhausted
    assert len(calls) == 1
    err = capsys.readouterr().err
    assert "slice_lost: the slice hosting process" in err
    assert "elastic_resize.py --slices" in err


def test_chaos_spec_parses_tick_suffix():
    """`KIND@STEP#TICK` addresses a named schedule tick inside the MPMD
    walk; the suffix composes with ~SECS and is None when absent."""
    evs = chaos.parse_spec("sigterm@3#2,hang@4~120#1,kill@5")
    assert [(e.kind, e.step, e.tick) for e in evs] == [
        ("sigterm", 3, 2), ("hang", 4, 1), ("kill", 5, None)]
    assert evs[1].secs == 120.0
    ctrl = chaos.ChaosController(evs)
    assert ctrl.has_tick_events()
    assert "#2" in ctrl.describe() and "#1" in ctrl.describe()
    assert not chaos.ChaosController(
        chaos.parse_spec("sigterm@3")).has_tick_events()


def test_chaos_tick_events_fire_only_at_matching_schedule_tick():
    """The two injection sites are disjoint: a #TICK event ignores
    step_begin and non-matching ticks; an event WITHOUT a tick never
    fires at schedule_tick (it would double-fire with step_begin)."""
    ctrl = chaos.ChaosController(
        chaos.parse_spec("hang@2~0.01#3,ckpt_io@2x1"))
    tick_ev = next(e for e in ctrl.events if e.kind == "hang")
    ctrl.fire("step_begin", step=2)          # #3 event: not its point
    assert tick_ev.fired == 0
    ctrl.fire("schedule_tick", step=2, tick=1, stage=0, op="F", mb=0)
    assert tick_ev.fired == 0                # wrong tick
    ctrl.fire("schedule_tick", step=1, tick=3, stage=0, op="F", mb=0)
    assert tick_ev.fired == 0                # wrong step
    # the tick-less ckpt_io event must NOT raise here either — it is
    # bound to its own points, and never to schedule_tick
    ctrl.fire("schedule_tick", step=2, tick=3, stage=1, op="B", mb=1)
    assert tick_ev.fired == 1                # the named (step, tick)


def test_chaos_io_event_fires_count_times_then_exhausts():
    ctrl = chaos.ChaosController(chaos.parse_spec("ckpt_io@2x2"))
    ctrl.fire("ckpt_save", step=1)  # wrong step: no-op
    for _ in range(2):
        with pytest.raises(OSError):
            ctrl.fire("ckpt_save", step=2)
    ctrl.fire("ckpt_save", step=2)  # budget exhausted: passes
    ctrl.fire("data_produce", step=2)  # wrong point: never fires


def test_chaos_nan_budget_survives_rollback_reencounter():
    """nan_grad@4 poisons the FIRST execution of step 4 only — after a
    guard rollback re-runs step 4, the exhausted event must stay quiet or
    the run would re-live the divergence forever."""
    ctrl = chaos.ChaosController(chaos.parse_spec("nan_grad@4x2"))
    assert ctrl.has_nan_grad()
    assert not ctrl.poison_step(3)
    assert ctrl.poison_step(4)
    assert ctrl.poison_step(5)      # x2: second consecutive execution
    assert not ctrl.poison_step(4)  # re-encounter after rollback: exhausted
    assert not chaos.ChaosController([]).has_nan_grad()


def test_chaos_env_var_overrides_config_spec(monkeypatch):
    monkeypatch.setenv("PICOTRON_CHAOS", "")
    assert not chaos.install("sigterm@3").active  # supervisor-restart story
    monkeypatch.setenv("PICOTRON_CHAOS", "ckpt_io@1")
    ctrl = chaos.install("")
    assert [e.kind for e in ctrl.events] == ["ckpt_io"]


# ---------------------------------------------------------------------------
# retry
# ---------------------------------------------------------------------------


def test_backoff_delays_double_and_cap():
    pol = RetryPolicy(attempts=5, base_delay=0.5, max_delay=3.0, jitter=0.0)
    assert list(backoff_delays(pol)) == [0.5, 1.0, 2.0, 3.0]


def test_backoff_jitter_bounds_are_deterministic_with_seeded_rng():
    pol = RetryPolicy(attempts=4, base_delay=1.0, max_delay=100.0,
                      jitter=0.5)
    a = list(backoff_delays(pol, rng=random.Random(7)))
    b = list(backoff_delays(pol, rng=random.Random(7)))
    assert a == b
    for base, got in zip([1.0, 2.0, 4.0], a):
        assert base <= got <= base * 1.5


def test_retry_call_recovers_then_reraises():
    calls, sleeps = [], []
    pol = RetryPolicy(attempts=3, base_delay=0.25, max_delay=1.0,
                      jitter=0.0)

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("blip")
        return 42

    assert retry_call(flaky, policy=pol, sleep=sleeps.append) == 42
    assert len(calls) == 3 and sleeps == [0.25, 0.5]

    def dead():
        raise OSError("down")

    with pytest.raises(OSError):
        retry_call(dead, policy=pol, sleep=sleeps.append)


def test_retry_call_does_not_retry_programming_errors():
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("logic bug")

    with pytest.raises(ValueError):
        retry_call(broken, policy=RetryPolicy(attempts=5, base_delay=0.0),
                   sleep=lambda d: None)
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# divergence guard
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy,action", [
    ("skip", GuardAction.SKIP),
    ("rollback", GuardAction.ROLLBACK),
    ("abort", GuardAction.ABORT),
])
def test_guard_nonfinite_maps_policy_to_action(policy, action):
    g = DivergenceGuard(policy, max_trips=5)
    assert g.observe(1, 2.5) == (GuardAction.OK, "")
    got, why = g.observe(2, float("nan"))
    assert got is action and "non-finite" in why
    # the in-step detector flag alone must also trip
    got, _ = g.observe(3, 2.5, nonfinite=1.0)
    assert got is action


def test_guard_consecutive_trips_escalate_to_abort():
    g = DivergenceGuard("skip", max_trips=3)
    g.observe(1, 1.0)
    assert g.observe(2, float("inf"))[0] is GuardAction.SKIP
    assert g.observe(3, float("inf"))[0] is GuardAction.SKIP
    action, why = g.observe(4, float("inf"))
    assert action is GuardAction.ABORT and "not recovering" in why
    # a healthy step resets the streak
    g2 = DivergenceGuard("skip", max_trips=2)
    for s in range(1, 9, 2):
        assert g2.observe(s, 1.0)[0] is GuardAction.OK
        assert g2.observe(s + 1, float("nan"))[0] is GuardAction.SKIP


def test_guard_spike_zscore_trips_and_quarantines():
    g = DivergenceGuard("rollback", spike_zscore=6.0, spike_window=8)
    rng = np.random.default_rng(0)
    for s in range(8):  # fill the window with ~N(2, 0.01) losses
        assert g.observe(s, 2.0 + 0.01 * rng.standard_normal())[0] \
            is GuardAction.OK
    action, why = g.observe(9, 8.0)
    assert action is GuardAction.ROLLBACK and "spike" in why
    # the spike was NOT folded into the window: a repeat still trips
    assert g.observe(10, 8.0)[0] is GuardAction.ROLLBACK
    # normal losses keep flowing
    assert g.observe(11, 2.0)[0] is GuardAction.OK


def test_guard_spike_needs_full_window_and_ignores_descent():
    g = DivergenceGuard("abort", spike_zscore=3.0, spike_window=8)
    # window not yet full: even a big jump is not judged
    assert g.observe(1, 2.0)[0] is GuardAction.OK
    assert g.observe(2, 50.0)[0] is GuardAction.OK
    g2 = DivergenceGuard("abort", spike_zscore=3.0, spike_window=8)
    for s in range(8):
        g2.observe(s, 5.0 - 0.1 * s)
    # downward movement (ordinary descent) never trips
    assert g2.observe(9, 1.0)[0] is GuardAction.OK


# ---------------------------------------------------------------------------
# watchdog + preemption
# ---------------------------------------------------------------------------


def test_watchdog_fires_on_stall_and_dumps_stacks(capsys):
    fired = []
    w = Watchdog(timeout=0.2, on_timeout=lambda: fired.append(1))
    w.start()
    try:
        deadline = time.monotonic() + 5.0
        while not fired and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        w.stop()
    assert fired
    err = capsys.readouterr().err
    assert "[watchdog] no progress" in err
    assert "picotron-watchdog" in err  # its own stack is in the dump too


def test_watchdog_beats_keep_it_alive():
    fired = []
    w = Watchdog(timeout=0.3, on_timeout=lambda: fired.append(1))
    w.start()
    try:
        for step in range(12):
            w.beat("step", step)
            time.sleep(0.05)
    finally:
        w.stop()
    assert not fired


def test_watchdog_disabled_is_inert():
    w = Watchdog(timeout=0.0)
    w.start()
    assert not w.started  # timeout 0 never spawns the thread
    w.beat("step", 1)
    w.stop()


def test_retry_backoff_heartbeats_watchdog():
    """A legitimate retry backoff longer than the watchdog timeout must
    not be misread as a hang (retry sleeps are chunked + heartbeat)."""
    fired = []
    w = Watchdog(timeout=1.5, on_timeout=lambda: fired.append(1))
    w.start()
    try:
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 2:
                raise OSError("blip")
            return "ok"

        got = retry_call(flaky, policy=RetryPolicy(
            attempts=2, base_delay=2.5, max_delay=2.5, jitter=0.0))
        assert got == "ok"
        time.sleep(0.1)
    finally:
        w.stop()
    assert not fired


def test_watchdog_fire_writes_flight_postmortem(tmp_path):
    """The flightdeck pin: when the watchdog fires, the installed
    telemetry facade's flight recorder dumps its last-K-steps window
    BEFORE the exit path runs — the postmortem is the only record an
    os._exit(77) leaves behind."""
    from picotron_tpu.telemetry import Telemetry, bus
    from picotron_tpu.telemetry.flightdeck import FlightRecorder
    from picotron_tpu.telemetry.flightdeck.flight import POSTMORTEM_NAME

    tel = Telemetry(sinks=[])
    tel.flight = FlightRecorder(str(tmp_path), max_steps=4)
    bus.install(tel)
    fired = []
    try:
        tel.emit("phase", phase="data", secs=0.1, book=False, step=3)
        tel.record_step(3, "[step] ...", loss=2.0)
        w = Watchdog(timeout=0.2, on_timeout=lambda: fired.append(1))
        w.start()
        try:
            deadline = time.monotonic() + 5.0
            while not fired and time.monotonic() < deadline:
                time.sleep(0.05)
        finally:
            w.stop()
    finally:
        tel.close()
    assert fired
    doc = json.load(open(tmp_path / POSTMORTEM_NAME))
    assert doc["reason"] == "watchdog"
    assert doc["step"] == 3  # the last step the recorder saw
    assert doc["steps"][-1]["step"] == 3
    assert doc["extra"]["stalled_s"] >= 0.2
    # the watchdog_timeout bus event reached the recorder too
    assert any(e["kind"] == "watchdog_timeout"
               for e in doc["recent_events"])


def test_preemption_handler_catches_sigterm_and_restores():
    h = PreemptionHandler()
    prev = signal.getsignal(signal.SIGTERM)
    assert h.install()
    try:
        assert not h.triggered
        signal.raise_signal(signal.SIGTERM)
        assert h.triggered and h.signum == signal.SIGTERM
    finally:
        h.uninstall()
    assert signal.getsignal(signal.SIGTERM) is prev


def test_preemption_second_sigint_raises_keyboardinterrupt():
    with PreemptionHandler() as h:
        signal.raise_signal(signal.SIGINT)
        assert h.triggered
        with pytest.raises(KeyboardInterrupt):
            signal.raise_signal(signal.SIGINT)


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------


def _cfg(resilience=None, training=None, **kw):
    raw = {"model": {"name": "debug-tiny", "dtype": "float32"},
           "training": {"seq_length": 32, **(training or {})},
           "resilience": resilience or {}, **kw}
    return config_from_dict(raw)


def test_resilience_config_defaults_and_validation():
    cfg = _cfg()
    assert cfg.resilience.guard_policy == "abort"
    assert cfg.resilience.watchdog_timeout == 0.0
    with pytest.raises(ValueError):
        _cfg(resilience={"guard_policy": "retry"})
    with pytest.raises(ValueError):
        _cfg(resilience={"chaos": "bogus@3"})
    with pytest.raises(ValueError):
        _cfg(resilience={"retry_attempts": 0})
    with pytest.raises(ValueError):
        _cfg(resilience={"spike_zscore": -1.0})
    with pytest.raises(ValueError):
        _cfg(resilience={"watchdog_timeout": -5})


def test_offload_rejects_in_jit_skip_policy():
    with pytest.raises(ValueError, match="guard_policy"):
        _cfg(resilience={"guard_policy": "skip"},
             training={"optimizer_offload": True,
                       "gradient_accumulation_steps": 2},
             model={"name": "debug-tiny", "dtype": "bfloat16"})


def test_eval_steps_zero_with_eval_enabled_rejected():
    # the train.py:236 ZeroDivisionError class of config: eval on, no
    # batches to average over
    with pytest.raises(ValueError, match="eval"):
        _cfg(training={"eval_frequency": 2, "eval_steps": 0})


# ---------------------------------------------------------------------------
# in-jit guard + loader + checkpoint integration (single tiny compile each)
# ---------------------------------------------------------------------------


def _tiny_cfg(**resilience):
    return config_from_dict({
        "distributed": {"dp_size": 1},
        "model": {"name": "debug-tiny", "dtype": "float32"},
        "training": {"seq_length": 16, "micro_batch_size": 2,
                     "gradient_accumulation_steps": 1, "remat": False},
        "resilience": resilience,
    })


def test_in_jit_skip_preserves_state_on_injected_nan():
    """The poisoned step (chaos nan_grad path) must leave params AND
    optimizer state bit-identical under policy 'skip', advance the step
    counter, and flag the metrics; the next clean step must train."""
    from picotron_tpu.data import MicroBatchDataLoader
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.parallel.api import init_sharded_state, make_train_step

    cfg = _tiny_cfg(guard_policy="skip")
    menv = MeshEnv.from_config(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    dl = MicroBatchDataLoader(cfg, menv)
    poison_fn = make_train_step(cfg, menv, inject_nan=True)
    step_fn = make_train_step(cfg, menv)

    before = [np.asarray(x).copy() for x in jax.tree.leaves(state.params)]
    state, m = poison_fn(state, next(dl))
    m = {k: float(v) for k, v in jax.block_until_ready(m).items()}
    assert m["nonfinite"] == 1.0 and not np.isfinite(m["loss"])
    assert not np.isfinite(m["grad_norm"])
    after = [np.asarray(x) for x in jax.tree.leaves(state.params)]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)
    assert int(state.step) == 1  # the skipped batch still counts a step

    state, m = step_fn(state, next(dl))
    m = {k: float(v) for k, v in jax.block_until_ready(m).items()}
    assert m["nonfinite"] == 0.0 and np.isfinite(m["grad_norm"])
    clean = [np.asarray(x) for x in jax.tree.leaves(state.params)]
    assert any(not np.array_equal(b, c) for b, c in zip(before, clean))


def test_guard_metrics_absent_when_policy_off():
    from picotron_tpu.data import MicroBatchDataLoader
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.parallel.api import init_sharded_state, make_train_step

    cfg = _tiny_cfg(guard_policy="off")
    menv = MeshEnv.from_config(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    dl = MicroBatchDataLoader(cfg, menv)
    _, m = make_train_step(cfg, menv)(state, next(dl))
    assert set(m) == {"loss"}


def test_loader_retries_chaos_data_io_and_resets():
    """An injected transient read failure costs a (tiny) backoff, not the
    run, and the delivered stream is unchanged; reset() repositions an
    already-running prefetch loader for the rollback path."""
    from picotron_tpu.data import MicroBatchDataLoader
    from picotron_tpu.mesh import MeshEnv

    cfg = config_from_dict({
        "distributed": {"dp_size": 1},
        "model": {"name": "debug-tiny"},
        "training": {"seq_length": 16, "micro_batch_size": 2,
                     "gradient_accumulation_steps": 1},
        "dataset": {"num_workers": 2},
        "resilience": {"retry_base_delay": 0.01, "retry_max_delay": 0.02},
    })
    menv = MeshEnv.from_config(cfg)

    def batches(dl, n):
        return [np.asarray(next(dl)[0]) for _ in range(n)]

    clean = batches(MicroBatchDataLoader(cfg, menv), 4)

    chaos.install("data_io@2x2")  # two failures assembling batch 2
    dl = MicroBatchDataLoader(cfg, menv)
    faulted = batches(dl, 4)
    for c, f in zip(clean, faulted):
        np.testing.assert_array_equal(c, f)

    # rollback repositioning: jump back to the start of batch 3
    dl.reset({"epoch": 0, "cursor": 2 * cfg.global_batch_size})
    np.testing.assert_array_equal(np.asarray(next(dl)[0]), clean[2])
    dl.close()


def test_loader_exhausted_retries_surface_on_training_thread():
    from picotron_tpu.data import MicroBatchDataLoader
    from picotron_tpu.mesh import MeshEnv

    cfg = config_from_dict({
        "distributed": {"dp_size": 1},
        "model": {"name": "debug-tiny"},
        "training": {"seq_length": 16, "micro_batch_size": 2,
                     "gradient_accumulation_steps": 1},
        "dataset": {"num_workers": 2},
        "resilience": {"retry_attempts": 2, "retry_base_delay": 0.01,
                       "retry_max_delay": 0.02},
    })
    menv = MeshEnv.from_config(cfg)
    chaos.install("data_io@1x99")  # outlasts the 2-attempt budget
    dl = MicroBatchDataLoader(cfg, menv)
    with pytest.raises(RuntimeError, match="prefetch thread died"):
        next(dl)
    dl.close()


def _toy_state(step=2):
    from picotron_tpu.train_step import TrainState

    return TrainState(params={"w": jnp.arange(4.0)},
                      opt_state={"m": jnp.zeros(4)},
                      step=jnp.asarray(step, jnp.int32))


def test_checkpoint_save_retries_injected_io_error(tmp_path):
    from picotron_tpu.checkpoint import CheckpointManager

    cfg = config_from_dict({
        "model": {"name": "debug-tiny"},
        "checkpoint": {"save_dir": str(tmp_path), "save_frequency": 1,
                       "async_save": False},
        "resilience": {"retry_base_delay": 0.01, "retry_max_delay": 0.02},
    })
    chaos.install("ckpt_io@2x2")  # default 3 attempts absorb 2 failures
    mgr = CheckpointManager(cfg)
    mgr.save(_toy_state(step=2), trained_tokens=128,
             dataloader_state={"epoch": 0, "cursor": 8})
    assert mgr.latest_step() == 2
    meta = json.load(open(tmp_path / "step_00000002" / "meta.json"))
    assert meta["trained_tokens"] == 128

    chaos.install("ckpt_io@3x99")  # outlasts the budget: surfaces
    with pytest.raises(OSError):
        mgr.save(_toy_state(step=3))


def test_durability_probe_retries_transient_errors(tmp_path):
    """The promoted _probe_failed path: a transient metadata-read error
    must cost a short retry, not hide a durable checkpoint from
    auto_resume."""
    from picotron_tpu.checkpoint import CheckpointManager

    cfg = config_from_dict({
        "model": {"name": "debug-tiny"},
        "checkpoint": {"save_dir": str(tmp_path), "save_frequency": 1,
                       "async_save": False},
        "resilience": {"retry_base_delay": 0.01, "retry_max_delay": 0.02},
    })
    mgr = CheckpointManager(cfg)
    mgr.save(_toy_state(step=4))

    real = mgr._ocp.utils.is_checkpoint_finalized
    calls = []

    class FlakyUtils:
        @staticmethod
        def is_checkpoint_finalized(path):
            calls.append(1)
            if len(calls) == 1:
                raise OSError("transient metadata blip")
            return real(path)

    class FakeOcp:
        utils = FlakyUtils

    mgr._ocp = FakeOcp
    assert mgr.latest_step() == 4  # first probe attempt failed, retry won
    assert len(calls) >= 2


# ---------------------------------------------------------------------------
# full kill-and-recover scenarios (tools/chaos.py) — slow tier
# ---------------------------------------------------------------------------


def _load_chaos_cli():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chaos_cli", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "chaos.py"))
    mod = importlib.util.module_from_spec(spec)
    # registered under its spec name so dataclasses can resolve the
    # module's postponed annotations (PEP 563 strings) during class build
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_chaos_cli_lists_every_scenario(capsys):
    cli = _load_chaos_cli()
    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in ("sigterm", "ckpt_io", "nan_skip", "nan_rollback",
                 "data_stall", "ckpt_corrupt_bitflip", "dp_resize",
                 "pp_resize", "slice_lost", "mpmd_sigterm",
                 "serve_engine_dead", "serve_overload"):
        assert name in out


def test_chaos_postmortem_matcher_structural(tmp_path):
    """Fast structural pin for the scenarios' check_after_fault hook:
    tools/chaos.py asserts that each abnormal exit left a flightdeck
    postmortem whose reason and last recorded step equal the injected
    fault — exercised here against crafted dumps instead of a full
    kill-and-recover run."""
    cli = _load_chaos_cli()
    p = tmp_path / "flightdeck_postmortem.json"
    # the hook is wired into the three abnormal-exit scenarios, each
    # bound to its fault's reason and injection step
    expected = {"sigterm": ("preempted", cli.STEPS // 2),
                "nan_rollback": ("rollback", cli.STEPS - 2),
                "data_stall": ("watchdog", cli.STEPS // 2)}
    for name, (reason, fault_step) in expected.items():
        sc = cli.SCENARIOS[name]
        assert sc.check_after_fault is not None, name
        if p.exists():
            p.unlink()
        # with no postmortem on disk the scenario must fail loudly
        err = sc.check_after_fault(str(tmp_path))
        assert err and "flightdeck_postmortem.json" in err, (name, err)
        # the matching dump passes; a wrong reason or step does not
        p.write_text(json.dumps({
            "reason": reason, "step": fault_step, "ts": 0.0,
            "steps": [{"step": fault_step, "phases": {"step": 1.0}}],
            "recent_events": []}))
        assert sc.check_after_fault(str(tmp_path)) is None, name
        p.write_text(json.dumps({
            "reason": "exception", "step": fault_step, "ts": 0.0,
            "steps": [{"step": fault_step}], "recent_events": []}))
        assert "reason" in sc.check_after_fault(str(tmp_path)), name
    good = {"reason": "preempted", "step": 3, "ts": 0.0,
            "steps": [{"step": 3, "phases": {"step": 1.0}}],
            "recent_events": []}
    p.write_text(json.dumps(good))
    assert "step" in cli._postmortem_matches(
        str(tmp_path), reason="preempted", fault_step=4)
    p.write_text(json.dumps({**good, "steps": []}))
    assert "empty" in cli._postmortem_matches(
        str(tmp_path), reason="preempted", fault_step=3)
    p.write_text("{torn")
    assert "unreadable" in cli._postmortem_matches(
        str(tmp_path), reason="preempted", fault_step=3)


# Per-scenario telemetry assertions: the injected fault's cost must be
# BOOKED — as the named badput categories in the goodput ledger and/or as
# the named event kinds in the stream (picotron_tpu/telemetry; the
# telemetry half of the acceptance criteria).
_SCENARIO_TELEMETRY = {
    "sigterm": {"badput": ["preempt", "restore"],
                "events": ["chaos", "preempt_signal", "preempted"]},
    "ckpt_io": {"badput": ["retry_backoff"],
                "events": ["chaos", "retry"]},
    "nan_skip": {"badput": [], "events": ["chaos", "guard"]},
    "nan_rollback": {"badput": ["restore", "replay"],
                     "events": ["chaos", "guard", "rollback"]},
    # the hung data phase never completes, so the stall time reaches the
    # ledger via the watchdog's own timeout event (category data_wait)
    "data_stall": {"badput": ["restore", "data_wait"],
                   "events": ["chaos", "watchdog_timeout"]},
    # newest committed checkpoint bit-flipped then SIGKILL: the restart's
    # verify-on-restore emits ckpt_corrupt, falls back to the prior
    # verified step, and re-buys the lost ground (replayed steps)
    "ckpt_corrupt_bitflip": {"badput": ["restore"],
                             "events": ["chaos", "ckpt_corrupt",
                                        "ckpt_commit"]},
}


@pytest.mark.slow
@pytest.mark.parametrize("scenario", [
    "sigterm", "ckpt_io", "nan_skip", "nan_rollback", "data_stall",
    "ckpt_corrupt_bitflip"])
def test_chaos_scenario_recovers_to_baseline(tmp_path, scenario):
    """The acceptance contract, both halves: under each injected failure
    the supervised run (a) ends at the same final step and trained_tokens
    as a fault-free baseline — the failure cost restarts, not training
    progress — and (b) leaves a telemetry.jsonl from which
    tools/telemetry_report.py reproduces the run's step count and books
    the injected fault's cost as badput."""
    import importlib.util

    cli = _load_chaos_cli()
    assert cli.run_scenario(scenario, str(tmp_path))

    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "telemetry_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    stream = os.path.join(tmp_path, "fault", "ckpt", "telemetry.jsonl")
    s = rep.summarize(rep.load_events(stream))
    # every scenario recovers to the full fault-free step count, and the
    # stream (appended across supervised restarts) shows each step trained
    assert s["steps"]["count"] == cli.STEPS
    assert s["steps"]["max"] == cli.STEPS
    expect = _SCENARIO_TELEMETRY[scenario]
    for cat in expect["badput"]:
        assert s["categories"].get(cat, 0.0) > 0, \
            f"{scenario}: badput category {cat!r} not booked: " \
            f"{s['categories']}"
    for kind in expect["events"]:
        assert s["events"].get(kind, 0) > 0, \
            f"{scenario}: event {kind!r} absent: {s['events']}"
    if scenario in ("nan_rollback", "ckpt_corrupt_bitflip"):
        assert s["steps"]["replayed"] > 0  # re-trained ground is counted


@pytest.mark.slow
def test_chaos_dp_resize_scenario(tmp_path):
    """Elastic scale-out, the full multi-process scenario: dp=2 SIGKILLed,
    re-stamped to dp=1 offline, SIGKILLed again, finished at dp=4 via
    checkpoint.elastic. run_dp_resize itself asserts final step/tokens,
    per-step loss-trajectory parity vs the fault-free dp=2 baseline, the
    `resize` goodput booking, and the elastic_resize event; here we
    additionally pin that the whole saga trained every step exactly once
    (no replay — a resize costs restore time, not ground)."""
    import importlib.util

    cli = _load_chaos_cli()
    assert cli.run_dp_resize(str(tmp_path))

    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "telemetry_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    stream = os.path.join(tmp_path, "fault", "ckpt", "telemetry.jsonl")
    s = rep.summarize(rep.load_events(stream))
    assert s["steps"]["count"] == cli.STEPS
    assert s["steps"]["max"] == cli.STEPS
    assert s["steps"]["replayed"] == 0
    assert s["categories"].get("resize", 0.0) > 0
    assert s["resize"]["events"] >= 1


@pytest.mark.slow
def test_chaos_slice_lost_scenario(tmp_path):
    """Whole-slice loss, the full multi-process scenario: a 2-slice run
    is killed by slice_lost@3,
    the store is re-stamped single-slice offline (--slices 1), and the
    surviving chips finish at dp=1 via checkpoint.elastic. run_slice_lost
    itself asserts the slice-naming log line, the manifest slice counts
    before/after the re-stamp, final step/tokens, per-step loss parity vs
    the single-slice baseline, and the resize booking; here we pin zero
    replay — losing a slice costs a resize, not ground."""
    import importlib.util

    cli = _load_chaos_cli()
    assert cli.run_slice_lost(str(tmp_path))

    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "telemetry_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    stream = os.path.join(tmp_path, "fault", "ckpt", "telemetry.jsonl")
    s = rep.summarize(rep.load_events(stream))
    assert s["steps"]["count"] == cli.STEPS
    assert s["steps"]["max"] == cli.STEPS
    assert s["steps"]["replayed"] == 0
    assert s["categories"].get("resize", 0.0) > 0
    assert s["resize"]["events"] >= 1


def _load_telemetry_report():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "telemetry_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    return rep


@pytest.mark.slow
def test_chaos_pp_resize_scenario(tmp_path):
    """Elastic PIPELINE resize, the full multi-process scenario: pp=2
    MPMD SIGKILLed, re-stamped to pp=1 offline (--pp), SIGKILLed again,
    finished at pp=2 via checkpoint.elastic. run_pp_resize itself asserts
    loss-trajectory parity, final step/tokens, the resize booking, and
    the compile-once prover pin on the rebuilt stage programs; here we
    additionally pin zero replay across the whole saga."""
    cli = _load_chaos_cli()
    assert cli.run_pp_resize(str(tmp_path))

    rep = _load_telemetry_report()
    stream = os.path.join(tmp_path, "fault", "ckpt", "telemetry.jsonl")
    s = rep.summarize(rep.load_events(stream))
    assert s["steps"]["count"] == cli.STEPS
    assert s["steps"]["max"] == cli.STEPS
    assert s["steps"]["replayed"] == 0
    assert s["categories"].get("resize", 0.0) > 0
    assert s["resize"]["events"] >= 1


@pytest.mark.slow
def test_chaos_mpmd_sigterm_scenario(tmp_path):
    """Mid-schedule fault hardening, the full multi-process scenario:
    SIGTERM at a named (stage, tick, op) drains to the step boundary
    (emergency ckpt, exit 75) and resumes losslessly; a forced
    mid-schedule hang is watchdog-reported naming the live op. The
    runner asserts the log markers; here we re-pin the zero-replay claim
    on the sigterm leg's telemetry stream."""
    cli = _load_chaos_cli()
    assert cli.run_mpmd_sigterm(str(tmp_path))

    rep = _load_telemetry_report()
    stream = os.path.join(tmp_path, "sigterm", "ckpt", "telemetry.jsonl")
    s = rep.summarize(rep.load_events(stream))
    assert s["steps"]["count"] == cli.STEPS
    assert s["steps"]["max"] == cli.STEPS
    assert s["steps"]["replayed"] == 0


# ---------------------------------------------------------------------------
# serve-side chaos (PR 20): grammar + firing semantics + full scenarios
# ---------------------------------------------------------------------------


def test_chaos_serve_grammar_and_points():
    """The serving kinds parse under the unchanged KIND@STEP[xCOUNT]
    [~SECS] grammar (the step position carries the REQUEST id), are
    registered in KINDS and the module docstring, and are admitted only
    at their serve points: storms at routing, hangs at dispatch, engine
    death at both."""
    evs = chaos.parse_spec("engine_dead@4,decode_hang@2~0.5,"
                           "shed_storm@6x3")
    assert [(e.kind, e.step, e.count, e.secs) for e in evs] == [
        ("engine_dead", 4, 1, 0.0), ("decode_hang", 2, 1, 0.5),
        ("shed_storm", 6, 3, 0.0)]
    for kind in ("engine_dead", "decode_hang", "shed_storm"):
        assert kind in chaos.KINDS
        assert kind in chaos.__doc__
    assert chaos._POINT_KINDS["serve_route"] == (
        "engine_dead", "shed_storm")
    assert chaos._POINT_KINDS["serve_dispatch"] == (
        "engine_dead", "decode_hang")
    with pytest.raises(ValueError, match="~SECS"):
        chaos.parse_spec("decode_hang@2")  # a hang needs a duration


def test_chaos_engine_dead_raises_with_engine_ctx():
    """engine_dead raises ChaosEngineDead carrying the engine id from
    the firing context — at either serve point, once per budget."""
    ctrl = chaos.ChaosController(chaos.parse_spec("engine_dead@5"))
    ctrl.fire("serve_route", 4, engine=1)  # wrong request: inert
    with pytest.raises(chaos.ChaosEngineDead) as ei:
        ctrl.fire("serve_route", 5, engine=1)
    assert ei.value.engine == 1
    ctrl.fire("serve_route", 5, engine=1)  # budget of 1: exhausted

    ctrl = chaos.ChaosController(chaos.parse_spec("engine_dead@7"))
    with pytest.raises(chaos.ChaosEngineDead) as ei:
        ctrl.fire("serve_dispatch", 7, engine=0)
    assert ei.value.engine == 0


def test_chaos_decode_hang_sleeps_inside_dispatch():
    ctrl = chaos.ChaosController(chaos.parse_spec("decode_hang@2~0.05"))
    t0 = time.monotonic()
    ctrl.fire("serve_dispatch", 2, engine=0)
    assert time.monotonic() - t0 >= 0.05
    t0 = time.monotonic()
    ctrl.fire("serve_dispatch", 2, engine=0)  # budget drained: no sleep
    assert time.monotonic() - t0 < 0.05


def test_chaos_shed_storm_budget_is_consecutive():
    """shed_storm@REQxN is a STORM: it arms on request REQ and then
    sheds every subsequently routed request until the xCOUNT budget
    drains — one event models a contiguous overload burst."""
    ctrl = chaos.ChaosController(chaos.parse_spec("shed_storm@6x3"))
    ctrl.fire("serve_route", 5, engine=0)  # before REQ: inert
    for rid in (6, 7, 8):
        with pytest.raises(chaos.ChaosShed):
            ctrl.fire("serve_route", rid, engine=0)
    ctrl.fire("serve_route", 9, engine=0)  # budget drained
    # storms exist only at routing, never inside a dispatch
    ctrl = chaos.ChaosController(chaos.parse_spec("shed_storm@6"))
    ctrl.fire("serve_dispatch", 6, engine=0)


@pytest.mark.slow
def test_chaos_serve_engine_dead_scenario(tmp_path):
    """Fleet failover, the full subprocess scenario: bench --serve
    --fleet 2 with engine_dead@2 kills a replica mid-burst; the runner
    asserts all 8 requests finish with per-request token digests
    bit-identical to the fleet-of-1 oracle at temperature 0.7, at least
    one re-dispatch, zero leaked blocks, a serve_engine_dead flightdeck
    postmortem, and digest-exact determinism across a repeat leg."""
    cli = _load_chaos_cli()
    assert cli.run_serve_engine_dead(str(tmp_path))


@pytest.mark.slow
def test_chaos_serve_overload_scenario(tmp_path):
    """Deadline load shedding, the full subprocess scenario: a 1-slot
    engine under a 10-request burst with deadline_ms=6 sheds the tail
    deterministically (same shed ids on a repeat leg), the admitted
    requests' digests match the no-deadline leg, queue-wait p95 stays
    within the deadline, and telemetry_report books the shed seconds
    under the `shed` badput category and renders the serving view."""
    cli = _load_chaos_cli()
    assert cli.run_serve_overload(str(tmp_path))
