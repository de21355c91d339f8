"""Fleet serving tests (picotron_tpu/serve/fleet): failover re-dispatch
token parity at temperature > 0 across engine counts, deterministic
deadline shedding (order-invariant, like the PR-7 sampling tests),
graceful drain-then-retire with zero leaked blocks, least-loaded
routing, watchdog hang naming, and the fleet config guards."""

import json

import jax
import numpy as np
import pytest

from picotron_tpu.config import (
    Config, ModelConfig, ServeConfig, resolve_preset,
)
from picotron_tpu.models.llama import init_params
from picotron_tpu.resilience import chaos
from picotron_tpu.serve import FleetSupervisor, ServeEngine
from picotron_tpu.telemetry import Telemetry, bus
from picotron_tpu.telemetry.flightdeck import FlightRecorder


@pytest.fixture(autouse=True)
def _chaos_hygiene():
    chaos.install("")
    yield
    chaos.install("")


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(dtype="float32", **{
        **resolve_preset("debug-tiny"), "max_position_embeddings": 64})
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


@pytest.fixture(scope="module")
def requests5(tiny):
    cfg, _ = tiny
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, size=n)))
               for n in (5, 9, 3, 7, 11)]
    return list(zip(prompts, [6, 3, 8, 5, 4]))


def scfg(**kw):
    base = dict(decode_slots=3, block_size=4, num_blocks=24,
                prefill_chunk=4, max_model_len=32, decode_interval=3)
    base.update(kw)
    return ServeConfig(**base)


@pytest.fixture(scope="module")
def sampled_refs(tiny, requests5):
    """Per-request tokens from a plain single ServeEngine at temperature
    0.7 — the parity oracle every fleet configuration (any size, any
    failover history) must reproduce bit-for-bit."""
    cfg, params = tiny
    eng = ServeEngine(params, cfg, scfg(), temperature=0.7, seed=7)
    res = eng.run(requests5)
    eng.close()
    return {r["id"]: r["tokens"] for r in res}


def make_fleet(params, cfg, n=2, **kw):
    return FleetSupervisor(params, cfg, scfg(fleet_size=n, **kw.pop(
        "cfg_kw", {})), temperature=0.7, seed=7, **kw)


class _Capture:
    """Minimal telemetry sink: keep every event dict for assertions."""

    def __init__(self):
        self.events = []

    def emit(self, e):
        self.events.append(e)

    def close(self):
        pass

    def of(self, kind):
        return [e for e in self.events if e.get("kind") == kind]


# ---------------------------------------------------------------------------
# failover re-dispatch parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_engines", [1, 2])
def test_fleet_token_parity_at_temperature(tiny, requests5, sampled_refs,
                                           n_engines):
    """Fleet size is invisible in the tokens: the sampling key folds
    (request id, token index), never (engine, slot), so 1 and 2 replicas
    emit identical streams at temperature 0.7."""
    cfg, params = tiny
    fl = make_fleet(params, cfg, n=n_engines)
    res = fl.run(requests5)
    assert {r["id"]: r["tokens"] for r in res} == sampled_refs
    assert fl.leaked_blocks() == 0
    assert fl.summary["fleet_size"] == n_engines
    fl.close()


def test_midflight_kill_redispatches_with_bit_parity(tiny, requests5,
                                                     sampled_refs):
    """The tentpole pin: kill 1 of 2 engines while requests are resident
    (some mid-decode), and the survivor finishes EVERYTHING — the
    re-dispatched continuations bit-identical to the fault-free oracle
    at temperature 0.7, zero blocks leaked on the survivor pool, and the
    death + every re-dispatch on the telemetry stream."""
    cfg, params = tiny
    cap = _Capture()
    tel = Telemetry(sinks=[cap])
    fl = make_fleet(params, cfg, n=2, telemetry=tel)
    for p, n in requests5:
        fl.submit(p, n)
    fl.tick()
    resident = sorted(s.req.id for s in fl.engines[0].sched.slots
                      if s is not None)
    assert resident, "nothing resident on engine 0 after a tick"
    moved = fl.kill_engine(0, cause="test")
    assert moved >= len(resident)
    assert fl.alive == [False, True]
    while fl.has_work():
        fl.tick()
    fl._emit_summary(0.0)

    assert {r["id"]: r["tokens"] for r in fl.results} == sampled_refs
    # survivor pool only: engine 0's pool was discarded with the engine
    assert fl.leaked_blocks() == 0
    dead = cap.of("serve_engine_dead")
    assert len(dead) == 1
    assert dead[0]["engine"] == 0 and dead[0]["inflight"] == moved
    redis = cap.of("serve_redispatch")
    assert len(redis) == moved == fl.summary["redispatched"]
    assert set(e["id"] for e in redis) >= set(resident)
    assert all(e["from_engine"] == 0 and e["to_engine"] == 1
               for e in redis)
    assert fl.summary["engines_dead"] == 1
    tel.close()


def test_kill_survivors_then_last_engine_raises(tiny, requests5):
    cfg, params = tiny
    fl = make_fleet(params, cfg, n=2)
    for p, n in requests5:
        fl.submit(p, n)
    fl.tick()
    fl.kill_engine(1)
    with pytest.raises(RuntimeError, match="no replicas survive"):
        fl.kill_engine(0)
    fl.close()


def test_chaos_engine_dead_in_route_loop(tiny, requests5, sampled_refs):
    """The chaos grammar's serve-side kill: `engine_dead@REQ` fires at
    the routing of request REQ, the fleet absorbs it as an abrupt engine
    death, and the run still drains to bit-parity."""
    chaos.install("engine_dead@2")
    cfg, params = tiny
    fl = make_fleet(params, cfg, n=2)
    res = fl.run(requests5)
    assert {r["id"]: r["tokens"] for r in res} == sampled_refs
    assert fl.summary["engines_dead"] == 1
    assert fl.leaked_blocks() == 0
    fl.close()


# ---------------------------------------------------------------------------
# deadline shedding
# ---------------------------------------------------------------------------


def _shed_trace(requests5):
    """Staggered arrivals with a deadline tight enough that a 1-slot
    engine must shed the tail of the burst."""
    return [(p, n, 0.0 if i < 3 else 0.002, 1.0)
            for i, (p, n) in enumerate(requests5)]


def test_shed_is_deterministic_on_the_virtual_clock(tiny, requests5):
    """The shed set is a pure function of the trace: the fleet loop
    advances a virtual clock by tick_s per iteration, so queue waits —
    and therefore shed decisions — cannot depend on host speed. Two runs
    agree exactly; every shed request is accounted (completed + shed =
    submitted) and excluded from results."""
    cfg, params = tiny

    def leg():
        fl = FleetSupervisor(params, cfg,
                             scfg(fleet_size=1, decode_slots=1,
                                  deadline_ms=1.0),
                             temperature=0.7, seed=7, tick_s=0.001)
        res = fl.run(_shed_trace(requests5))
        out = (sorted(s["id"] for s in fl.all_shed),
               {r["id"]: r["tokens"] for r in res},
               fl.leaked_blocks())
        fl.close()
        return out

    shed_a, res_a, leak_a = leg()
    shed_b, res_b, leak_b = leg()
    assert shed_a == shed_b and res_a == res_b
    assert shed_a, "trace shed nothing — the pin proves nothing"
    assert len(shed_a) + len(res_a) == len(requests5)
    assert not set(shed_a) & set(res_a)
    assert leak_a == leak_b == 0


def test_shed_decision_is_submission_order_invariant(tiny, requests5,
                                                     sampled_refs):
    """Like the PR-7 sampling pins: the fleet queue orders by (arrival,
    id), so submitting the same requests in a different order changes
    nothing — same shed set, same tokens for the admitted."""
    cfg, params = tiny
    trace = _shed_trace(requests5)

    def leg(order):
        fl = FleetSupervisor(params, cfg,
                             scfg(fleet_size=1, decode_slots=1,
                                  deadline_ms=1.0),
                             temperature=0.7, seed=7, tick_s=0.001)
        for i in order:
            p, n, arr, dl = trace[i]
            fl.submit(p, n, req_id=i, arrival=arr, deadline_ms=dl)
        while fl.has_work():
            fl.tick()
        out = (sorted(s["id"] for s in fl.all_shed),
               {r["id"]: r["tokens"] for r in fl.results})
        fl.close()
        return out

    fwd = leg(range(len(trace)))
    rev = leg(range(len(trace) - 1, -1, -1))
    assert fwd == rev
    shed, res = fwd
    assert shed
    for rid, toks in res.items():
        assert toks == sampled_refs[rid], rid


def test_shed_storm_chaos_forces_sheds(tiny, requests5):
    """`shed_storm@REQxN` drains an N-request budget through the routing
    point — forced overload independent of any deadline: request 2 and
    the next routed request shed, everything else completes."""
    chaos.install("shed_storm@2x2")
    cfg, params = tiny
    fl = make_fleet(params, cfg, n=2)
    res = fl.run(requests5)
    assert sorted(s["id"] for s in fl.shed_results) == [2, 3]
    assert all(s["shed"] for s in fl.shed_results)
    assert sorted(r["id"] for r in res) == [0, 1, 4]
    assert fl.summary["shed"] == 2
    fl.close()


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------


def test_drain_then_retire_leaves_no_residents_no_leaks(tiny, requests5,
                                                        sampled_refs):
    cfg, params = tiny
    cap = _Capture()
    tel = Telemetry(sinks=[cap])
    fl = make_fleet(params, cfg, n=2, telemetry=tel)
    for p, n in requests5:
        fl.submit(p, n)
    fl.tick()
    fl.drain(0)
    while fl.has_work() or fl.draining:
        fl.tick()
    fl._emit_summary(0.0)

    assert fl.drained == [0] and fl.alive == [False, True]
    eng = fl.engines[0]
    assert not eng.sched.has_work() and eng.pool.in_use == 0
    assert fl.leaked_blocks() == 0
    assert {r["id"]: r["tokens"] for r in fl.results} == sampled_refs
    drains = cap.of("serve_drain")
    assert len(drains) == 1 and drains[0]["engine"] == 0
    assert drains[0]["pool_in_use"] == 0
    assert fl.summary["drains"] == 1
    tel.close()


def test_drain_grace_expiry_redispatches_residents(tiny, requests5):
    """A drain that outlives drain_grace_s (virtual seconds) forcibly
    re-dispatches the stragglers instead of waiting forever. Long
    requests (1 token per dispatch, 16-token budgets) guarantee the
    residents are still mid-decode when the zero grace expires."""
    cfg, params = tiny
    fl = FleetSupervisor(params, cfg,
                         scfg(fleet_size=2, drain_grace_s=0.0,
                              decode_interval=1),
                         temperature=0.7, seed=7, tick_s=0.001)
    for p, _ in requests5:
        fl.submit(p, 16)
    fl.tick()
    assert fl.engines[0].sched.has_work()
    fl.drain(0)
    for _ in range(50):
        fl.tick()
        if 0 in fl.drained:
            break
    assert 0 in fl.drained
    assert fl.engines[0].pool.in_use == 0
    assert fl.n_redispatched > 0
    while fl.has_work():
        fl.tick()
    assert len(fl.results) == len(requests5)
    assert fl.leaked_blocks() == 0
    fl.close()


def test_drain_last_routable_engine_rejected(tiny):
    cfg, params = tiny
    fl = make_fleet(params, cfg, n=2)
    fl.drain(0)
    with pytest.raises(ValueError, match="last routable"):
        fl.drain(1)
    fl.close()


# ---------------------------------------------------------------------------
# routing + health
# ---------------------------------------------------------------------------


def test_routing_is_least_loaded(tiny, requests5):
    cfg, params = tiny
    fl = make_fleet(params, cfg, n=2)
    fl.run(requests5)
    per = [pe["requests"] for pe in fl.summary["per_engine"]]
    assert sum(per) == len(requests5)
    assert all(n > 0 for n in per), (
        f"least-loaded routing left an engine idle: {per}")
    fl.close()


def test_watchdog_names_hung_decode_dispatch(tiny, requests5, tmp_path):
    """A wedged decode dispatch (chaos decode_hang in the fleet loop)
    trips the supervisor watchdog with a phase naming the exact engine
    and dispatch, and the flightdeck postmortem reason is serve_hang —
    the serving twin of the training watchdog contract. on_timeout
    stands in for the supervisor exit(77) so the test survives."""
    cfg, params = tiny
    tel = Telemetry(sinks=[])
    tel.flight = FlightRecorder(str(tmp_path), max_steps=4)
    bus.install(tel)
    chaos.install("decode_hang@0~1.2")
    fired = []
    try:
        fl = make_fleet(params, cfg, n=2, telemetry=tel,
                        watchdog_timeout=0.3,
                        watchdog_on_timeout=lambda: fired.append(
                            fl.watchdog._last))
        fl.run(requests5)
    finally:
        bus.install(None)
    assert fired, "watchdog never fired on a 1.2s hang at 0.3s timeout"
    _, phase, _ = fired[0]
    assert phase.startswith("serve engine=") and "dispatch=decode" in phase
    pm = json.loads(
        (tmp_path / "flightdeck_postmortem.json").read_text())
    assert pm["reason"] == "serve_hang"
    assert "dispatch=decode" in pm["extra"]["phase"]
    fl.close()


# ---------------------------------------------------------------------------
# config guards
# ---------------------------------------------------------------------------


def test_config_rejects_fleet_moe_and_speculator():
    moe = ModelConfig(**resolve_preset("debug-tiny-moe"))
    with pytest.raises(ValueError, match="fleet_size"):
        Config(model=moe, serve=ServeConfig(fleet_size=2)).validate()
    # a dense fleet of 2 is the supported configuration
    Config(model=ModelConfig(**resolve_preset("debug-tiny")),
           serve=ServeConfig(fleet_size=2)).validate()


def test_serve_config_validates_fleet_fields():
    with pytest.raises(ValueError, match="fleet_size"):
        ServeConfig(fleet_size=0).validate()
    with pytest.raises(ValueError, match="deadline_ms"):
        ServeConfig(deadline_ms=-1.0).validate()
    with pytest.raises(ValueError, match="drain_grace_s"):
        ServeConfig(drain_grace_s=-0.1).validate()
