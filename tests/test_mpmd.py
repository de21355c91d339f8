"""MPMD pipeline executor tests: schedule-table correctness on the edge
shapes, loss/param parity against the SPMD scan twin (the same-math
different-schedule invariant test_pp_engines pins for 1f1b vs afab), the
per-stage compile-once proof, and the config validation fence.

The schedule table is pure host code (no devices), so the table tests run
anywhere; the parity tests compile the per-stage programs on the 8-device
simulated CPU mesh."""

import collections

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, PipelineConfig, TrainingConfig,
)
from picotron_tpu.mesh import MeshEnv
from picotron_tpu.parallel.api import init_sharded_state, make_train_step
from picotron_tpu.parallel.mpmd import (
    SCHEDULES, build_schedule, mpmd_microbatch_losses,
    pipeline_bubble_fraction, schedule_stats,
)


# ---------------------------------------------------------------------------
# schedule table
# ---------------------------------------------------------------------------


def check_schedule(table, kind, n_micro, pp, v=1):
    """Structural validity: right op multiset, one op per (group, tick),
    round-robin placement, and every dependency edge respected."""
    V = pp * (v if kind == "interleaved" else 1)
    split = kind == "zb"
    by_kind = collections.Counter(op.op for op in table)
    assert by_kind["F"] == n_micro * V
    if split:
        assert by_kind["BX"] == by_kind["BW"] == n_micro * V
    else:
        assert by_kind["B"] == n_micro * V

    seen = set()
    for op in table:
        assert op.group == op.vstage % pp  # round-robin chunk placement
        assert (op.tick, op.group) not in seen  # one op per group per tick
        seen.add((op.tick, op.group))

    done = {}  # (op_kind, mb, vstage) -> completion tick
    for op in table:
        k = "B" if op.op == "BX" else op.op
        if op.op == "F" and op.vstage > 0:
            assert done[("F", op.mb, op.vstage - 1)] <= op.tick
        if op.op in ("B", "BX"):
            assert done[("F", op.mb, op.vstage)] <= op.tick
            if op.vstage < V - 1:
                assert done[("B", op.mb, op.vstage + 1)] <= op.tick
        if op.op == "BW":
            assert done[("B", op.mb, op.vstage)] <= op.tick
        done[(k, op.mb, op.vstage)] = op.tick + 1


@pytest.mark.parametrize("kind", SCHEDULES)
@pytest.mark.parametrize("n_micro,pp", [(8, 4), (4, 2), (5, 3)])
def test_schedule_table_valid(kind, n_micro, pp):
    v = 2 if kind == "interleaved" else 1
    check_schedule(build_schedule(kind, n_micro, pp, v), kind, n_micro,
                   pp, v)


def test_1f1b_canonical_makespan():
    """The greedy simulator must reproduce the canonical 1F1B makespan,
    2*n_micro + 2*(pp-1) chunk-op ticks, not merely *a* valid schedule."""
    for n, pp in [(8, 4), (4, 2), (16, 4), (4, 4)]:
        s = schedule_stats("1f1b", n, pp)
        assert s["ticks"] == 2 * n + 2 * (pp - 1), (n, pp, s)
        assert s["bubble_units"] == pytest.approx(pp - 1)


# -- edge shapes (the satellite's explicit list) ----------------------------


def test_schedule_n_micro_less_than_pp():
    """n_micro < pp: fewer microbatches than stages — the table must stay
    valid and simply drain early (bubble-dominated, but correct)."""
    for kind in SCHEDULES:
        v = 2 if kind == "interleaved" else 1
        check_schedule(build_schedule(kind, 2, 4, v), kind, 2, 4, v)
    s = schedule_stats("1f1b", 2, 4)
    assert s["ticks"] == 2 * 2 + 2 * 3
    assert s["bubble_fraction"] > 0.5  # mostly bubble, honestly priced


def test_schedule_n_micro_one():
    """n_micro == 1: a single microbatch walks straight down and back up —
    V forwards then V backwards, zero overlap possible."""
    for kind in ("1f1b", "gpipe"):
        table = build_schedule(kind, 1, 4)
        check_schedule(table, kind, 1, 4)
        ops = [(op.op, op.vstage) for op in sorted(table,
                                                   key=lambda o: o.tick)]
        assert ops == [("F", j) for j in range(4)] + \
            [("B", j) for j in reversed(range(4))]


def test_schedule_pp_one_passthrough():
    """pp == 1: no pipeline — an alternating F/B stream (gpipe: all F then
    all B) with zero bubble; the executor degenerates to plain microbatch
    accumulation."""
    for kind in ("1f1b", "gpipe", "zb"):
        table = build_schedule(kind, 4, 1)
        check_schedule(table, kind, 4, 1)
        # one op per tick, no idle ticks anywhere
        ticks = sorted(op.tick for op in table)
        assert ticks == list(range(len(table)))
        assert schedule_stats(kind, 4, 1)["bubble_units"] == \
            pytest.approx(0.0)


def test_schedule_rejects_bad_args():
    with pytest.raises(ValueError, match="unknown schedule"):
        build_schedule("afab", 4, 2)
    with pytest.raises(ValueError, match="n_micro >= 1"):
        build_schedule("1f1b", 0, 2)
    with pytest.raises(ValueError, match="only applies"):
        build_schedule("1f1b", 4, 2, interleave=2)


def test_schedule_ranking_at_pp4():
    """The tick accounting the planner and bench report: at pp=4, n=8 the
    spmd twin's full-price bubble (6 units: what it costs where its units
    run on every stage in every tick) dominates 1f1b (3), interleaved v=2
    beats 1f1b (2.5), and the zero-bubble split beats both (1)."""
    n, pp = 8, 4
    b = {k: schedule_stats(k, n, pp, 2 if k == "interleaved" else 1)
         ["bubble_units"] for k in ("1f1b", "gpipe", "interleaved", "zb")}
    b["spmd"] = schedule_stats("spmd", n, pp, gated=False)["bubble_units"]
    assert b["spmd"] == pytest.approx(6.0)
    assert b["1f1b"] == pytest.approx(3.0)
    assert b["interleaved"] < b["1f1b"]
    assert b["zb"] < b["interleaved"]


@pytest.mark.parametrize("n,pp,live,makespan", [
    # pp 2, 8 microbatches (the benchmark's four-chip cell): tick 0 is stage
    # 0's forward unit alone (1/4), ticks 1, 8, 9 a backward unit (3/4), the
    # six between full
    (8, 2, 24, 0.25 + 3 * 0.75 + 6),
    # pp 4: three forward-only ticks, tick 3 the last stage's first backward
    # unit beside forward units, ticks 4-9 full (stage 2, then others, hold
    # both), ticks 10-13 backward units alone
    (8, 4, 56, 3 * 0.25 + 0.75 + 6 + 4 * 0.75),
    # fewer microbatches than stages (the ring's smaller form): no stage
    # ever holds both units. F at (t, s) for t - s in [0, n), s < pp - 1; B
    # for t - 6 + s in [0, n):  t0 F0 | t1 F0 F1 | t2 F1 F2 | t3 F2 B3 |
    # t4 B3 B2 | t5 B2 B1 | t6 B1 B0 | t7 B0
    (2, 4, 14, 3 * 0.25 + 5 * 0.75),
])
def test_spmd_tick_is_priced_at_its_live_units(n, pp, live, makespan):
    """PR 65: the SPMD 1F1B tick runs a unit only where its stage holds a
    microbatch for it, so the account prices a tick at its slowest stage's
    live units (forward unit 1/4, backward unit 3/4 of a full unit) and
    counts the unit slots: 2 a stage a tick, of which n pp backward units
    and n (pp - 1) forward units are live (the last stage's forward unit is
    its backward unit's) and the rest are skipped."""
    s = schedule_stats("spmd", n, pp)
    ticks = n + 2 * (pp - 1)
    assert s["ticks"] == ticks
    assert s["units_live"] == live == n * pp + n * (pp - 1)
    assert s["units_skipped"] == 2 * pp * ticks - live
    assert s["makespan_units"] == pytest.approx(makespan)
    assert s["busy_units"] == n
    assert s["bubble_units"] == pytest.approx(makespan - n)
    assert s["bubble_fraction"] == pytest.approx((makespan - n) / makespan)
    # where the units run masked-uniform (a ring over cp; AFAB) every tick
    # is a full unit on every device, as before PR 65
    full = schedule_stats("spmd", n, pp, gated=False)
    assert full["makespan_units"] == ticks and full["units_skipped"] == 0
    assert full["units_live"] == live
    assert full["bubble_units"] == pytest.approx(2 * (pp - 1))
    assert s["bubble_units"] < full["bubble_units"]


def test_pipeline_bubble_fraction_from_config():
    base = dict(
        model=ModelConfig(dtype="float32", hidden_size=64,
                          num_attention_heads=8, num_key_value_heads=4),
        training=TrainingConfig(seq_length=32, micro_batch_size=1,
                                gradient_accumulation_steps=8),
    )
    flat = Config(distributed=DistributedConfig(), **base)
    assert pipeline_bubble_fraction(flat) == 0.0
    spmd = Config(distributed=DistributedConfig(pp_size=4), **base)
    assert pipeline_bubble_fraction(spmd) == pytest.approx(2.5 / 10.5)
    # the full price where the tick's units are not in branches: the layers'
    # ring over cp, and AFAB (differentiated through, its units whole)
    for whole in (dict(cp_size=2), dict(pp_engine="afab")):
        cfg = Config(distributed=DistributedConfig(pp_size=4, **whole), **base)
        assert pipeline_bubble_fraction(cfg) == pytest.approx(6.0 / 14.0)
    mpmd = Config(distributed=DistributedConfig(pp_size=4),
                  pipeline=PipelineConfig(executor="mpmd"), **base)
    assert pipeline_bubble_fraction(mpmd) == pytest.approx(
        schedule_stats("1f1b", 8, 4)["bubble_fraction"])
    assert pipeline_bubble_fraction(mpmd) < 6.0 / 14.0


# ---------------------------------------------------------------------------
# config validation fence
# ---------------------------------------------------------------------------


def mpmd_cfg(pp=2, dp=1, tp=1, gas=4, interleave=1, schedule="1f1b",
             remat=False, layers=4, **train_kw):
    return Config(
        distributed=DistributedConfig(pp_size=pp, dp_size=dp, tp_size=tp),
        model=ModelConfig(dtype="float32", hidden_size=64,
                          num_hidden_layers=layers, num_attention_heads=8,
                          num_key_value_heads=4),
        training=TrainingConfig(seq_length=32, micro_batch_size=2,
                                gradient_accumulation_steps=gas,
                                learning_rate=1e-3, remat=remat, **train_kw),
        pipeline=PipelineConfig(executor="mpmd", schedule=schedule,
                                interleave=interleave),
    )


def test_mpmd_config_validation():
    mpmd_cfg().validate()  # the happy path
    mpmd_cfg(pp=2, interleave=2, schedule="interleaved").validate()
    with pytest.raises(ValueError, match="pp_size >= 2"):
        mpmd_cfg(pp=1).validate()
    with pytest.raises(ValueError, match="optimizer"):
        mpmd_cfg(optimizer_offload=True).validate()
    with pytest.raises(ValueError, match="interleave >= 2"):
        mpmd_cfg(schedule="interleaved").validate()
    with pytest.raises(ValueError, match="divide"):
        # 4 layers over pp=2 -> 2 slots per group; v=3 cannot divide it
        mpmd_cfg(interleave=3, schedule="interleaved").validate()
    with pytest.raises(ValueError, match="executor"):
        Config(distributed=DistributedConfig(pp_size=2),
               pipeline=PipelineConfig(executor="simd")).validate()


# ---------------------------------------------------------------------------
# parity with the SPMD twin
# ---------------------------------------------------------------------------


def batch_for(cfg, menv, key=0):
    t = cfg.training
    b_global = t.micro_batch_size * cfg.distributed.dp_size
    toks = jax.random.randint(
        jax.random.key(key),
        (t.gradient_accumulation_steps, b_global, t.seq_length + 1),
        0, cfg.model.vocab_size)
    sh = NamedSharding(menv.mesh, P(None, "dp", "cp"))
    return (jax.device_put(toks[..., :-1], sh),
            jax.device_put(toks[..., 1:], sh))


def run_steps(cfg, steps=3):
    menv = MeshEnv.from_config(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    step = make_train_step(cfg, menv)
    batch = batch_for(cfg, menv)
    losses = []
    for _ in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses, state


def spmd_twin(cfg):
    import dataclasses

    return dataclasses.replace(cfg, pipeline=PipelineConfig())


def assert_parity(cfg_mpmd, steps=3, rtol=1e-5, param_atol=1e-4):
    l_m, s_m = run_steps(cfg_mpmd, steps)
    l_s, s_s = run_steps(spmd_twin(cfg_mpmd), steps)
    np.testing.assert_allclose(l_m, l_s, rtol=rtol, atol=1e-6)
    for name in ("embedding", "lm_head"):
        np.testing.assert_allclose(
            np.asarray(s_m.params[name]), np.asarray(s_s.params[name]),
            rtol=2e-3, atol=param_atol)
    np.testing.assert_allclose(
        np.asarray(s_m.params["layers"]["q"]),
        np.asarray(s_s.params["layers"]["q"]), rtol=2e-3, atol=param_atol)


def test_mpmd_matches_spmd_pp2_dp2():
    """The acceptance pin: the MPMD executor's host-driven schedule must
    train identically to the SPMD lockstep scan (same math, different
    dispatch) with dp grad sync in the finish program."""
    assert_parity(mpmd_cfg(pp=2, dp=2, gas=4))


@pytest.mark.slow
def test_mpmd_matches_spmd_pp4_interleaved_remat():
    """pp=4, interleaved v=2 (8 virtual stage programs), remat'd stage
    bodies, odd n_micro — the deep end of the schedule space."""
    assert_parity(mpmd_cfg(pp=4, dp=2, gas=3, interleave=2,
                           schedule="interleaved", layers=8, remat=True))


@pytest.mark.slow
def test_mpmd_matches_spmd_tp_x_pp():
    """tp x pp: stage programs run on tp-sharded submeshes; the boundary
    device_puts carry tp-sharded activations between stage meshes. Step-1
    losses match at 1e-5; later steps drift a few e-4 because the tp psum
    reduction order differs between the per-stage programs and the twin's
    single lowering, and adam's rescaling amplifies it (near-zero grad
    elements can flip sign, moving a handful of params by ~lr*steps)."""
    assert_parity(mpmd_cfg(pp=2, tp=2, dp=2, gas=4), rtol=5e-4,
                  param_atol=2e-3)


def test_mpmd_per_microbatch_losses_match_spmd():
    """Per-microbatch forward parity (not just the step-mean): each
    microbatch's (nll, count) through the per-stage programs must match a
    replicated single-device forward of the same params."""
    cfg = mpmd_cfg(pp=2, dp=2, gas=4)
    cfg.validate()
    menv = MeshEnv.from_config(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    batch = batch_for(cfg, menv)
    nll, cnt = mpmd_microbatch_losses(cfg, menv, state.params, batch)
    assert nll.shape == (4,) and cnt.shape == (4,)

    # reference: an unsharded single-program forward of the same params
    from picotron_tpu.models.llama import loss_sum_count

    params_g = jax.tree.map(np.asarray, state.params)
    ids, tgt = jax.tree.map(np.asarray, batch)
    for m in range(cfg.training.gradient_accumulation_steps):
        ref_nll, ref_cnt, _ = loss_sum_count(params_g, ids[m], tgt[m],
                                             cfg.model)
        np.testing.assert_allclose(nll[m], float(ref_nll), rtol=2e-4)
        assert cnt[m] == int(ref_cnt)


# ---------------------------------------------------------------------------
# per-stage compile-once proof
# ---------------------------------------------------------------------------


def test_mpmd_stage_programs_proven_compile_once():
    from picotron_tpu.analysis.variants import prove_mpmd_stages

    cfg = mpmd_cfg(pp=2, dp=2, gas=4)
    cfg.validate()
    rep = prove_mpmd_stages(cfg)
    assert rep.ok(), rep.render(verbose=True)
    info = rep.info["variants"]
    assert info["proven"] and info["programs"] == 4  # 2 stages x fwd/bwd
    for entry, sub in info["entries"].items():
        assert sub["proven"], (entry, sub)


@pytest.mark.slow
def test_mpmd_stage_programs_proven_interleaved():
    from picotron_tpu.analysis.variants import prove_mpmd_stages

    cfg = mpmd_cfg(pp=2, dp=2, gas=4, interleave=2, schedule="interleaved")
    cfg.validate()
    rep = prove_mpmd_stages(cfg)
    assert rep.ok(), rep.render(verbose=True)
    assert rep.info["variants"]["programs"] == 8  # 4 virtual stages x f/b


# ---------------------------------------------------------------------------
# elastic pp resize: schedule rebuild + mid-schedule fault surface
# ---------------------------------------------------------------------------


def test_schedule_rebuild_across_stage_counts():
    """The elastic pp-resize contract: the schedule table derives purely
    from (schedule, n_micro, pp) config — a resized run rebuilds a valid
    table for the new stage count with no carried state, at every stage
    count the resize saga visits."""
    for pp in (2, 4):
        for n in (2, 4, 8):
            table = build_schedule("1f1b", n, pp)
            check_schedule(table, "1f1b", n, pp)
            assert len({op.group for op in table}) == pp


class _FakeStage:
    """Host-side stand-in for _StagePrograms (first/last of a pp=2
    pipeline): numpy math, no compiled programs — lets the schedule-walk
    mechanics (buffer lifecycle, heartbeats, chaos ticks, the orphan
    diagnostic) run without building a mesh."""

    x_sharding = None

    def __init__(self, first, last):
        self.first, self.last = first, last

    def fwd(self, params, *a):
        if self.first:
            return np.float32(1.0)  # boundary activation
        nll_acc, cnt_acc = a[-2], a[-1]  # last: (x, tgt, idx, nll, cnt)
        return (np.float32(0.5), np.int32(4),
                nll_acc + np.float32(0.5), cnt_acc + np.int32(4))

    def bwd(self, params, *a):
        acc = a[-1]
        if self.first:  # (ids, idx, g_in, acc) -> acc
            return acc + 1
        return acc + 1, np.float32(0.1)  # (x, tgt, idx, acc) -> acc, g_x


def _fake_walk(table, step=None):
    from picotron_tpu.parallel import mpmd

    stages = [_FakeStage(True, False), _FakeStage(False, True)]
    return mpmd._run_schedule(
        stages, table, [None, None], [0, 0],
        (np.float32(0.0), np.int32(0)), None, None, [0, 1], [0, 1],
        step=step)


def test_schedule_walk_names_orphaned_buffers():
    """A truncated table (the final stage-0 backward dropped) leaves its
    inbound cotangent live: the walk must raise the named diagnostic
    listing exactly the orphaned (vstage, mb) keys — not a bare assert."""
    from picotron_tpu.parallel import mpmd

    table = build_schedule("1f1b", 2, 2)
    accs, nll, cnt, _, _ = _fake_walk(table)  # full table: clean walk
    assert accs == [2, 2] and float(nll) == 1.0 and int(cnt) == 8

    drop = max(i for i, op in enumerate(table)
               if op.op == "B" and op.vstage == 0)
    mb = table[drop].mb
    with pytest.raises(mpmd.ScheduleBufferError) as exc:
        _fake_walk(table[:drop] + table[drop + 1:])
    msg = str(exc.value)
    assert "live boundary buffer" in msg
    assert f"cotangent (vstage=0, mb={mb})" in msg


def test_sigterm_mid_walk_drains_to_step_boundary():
    """A SIGTERM delivered at a named (stage, tick, op) inside the walk
    only sets the preemption flag — the walk drains to the step boundary
    and returns complete accumulators, so the emergency checkpoint the
    driver then writes never sees half-accumulated grads."""
    from picotron_tpu.resilience import chaos
    from picotron_tpu.resilience.preemption import PreemptionHandler

    table = build_schedule("1f1b", 2, 2)
    tick = table[len(table) // 2].tick  # a mid-walk tick
    chaos.install(f"sigterm@7#{tick}")
    try:
        with PreemptionHandler() as ph:
            accs, nll, cnt, _, _ = _fake_walk(table, step=7)
            assert ph.triggered  # the signal landed mid-walk...
        # ...but the walk drained: full gradient accumulation, every
        # boundary buffer consumed (no ScheduleBufferError)
        assert accs == [2, 2] and int(cnt) == 8
    finally:
        chaos.install("")


def test_watchdog_beat_names_live_schedule_op():
    """Each dispatched op heartbeats the armed watchdog with a phase
    naming the live (stage, tick, op, mb) — a mid-schedule stall is
    reported as that op, not a bare stack dump."""
    import re

    from picotron_tpu.resilience import watchdog

    w = watchdog.Watchdog(timeout=60.0)
    w.start()
    try:
        _fake_walk(build_schedule("1f1b", 2, 2), step=3)
        _t, phase, step = w._last
        assert re.fullmatch(r"pp_schedule stage=\d+ tick=\d+ op=\w+ mb=\d+",
                            phase), phase
        assert step == 3
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# the static schedule-table lint
# ---------------------------------------------------------------------------


def test_lint_schedule_clean_tables():
    from picotron_tpu.parallel.mpmd import SCHEDULES, build_schedule

    # build_schedule lints at construction (raises ScheduleBufferError on
    # failure) — a representative sweep must come back clean
    for kind in SCHEDULES:
        for pp in (2, 4, 8):
            for n in (2, 8, 16):
                for v in (1, 2) if kind == "interleaved" else (1,):
                    build_schedule(kind, n, pp, v)


def test_lint_schedule_catches_truncated_table():
    from picotron_tpu.parallel.mpmd import build_schedule, lint_schedule

    table = build_schedule("1f1b", 4, 4, 1)
    truncated = [op for op in table if not (op.op == "B" and op.mb == 3)]
    problems = lint_schedule(truncated, 4, 4, 1, kind="1f1b")
    assert problems and any("never consumed" in p for p in problems)


def test_lint_schedule_catches_missing_producer():
    from picotron_tpu.parallel.mpmd import build_schedule, lint_schedule

    table = build_schedule("1f1b", 4, 4, 1)
    dropped = [op for op in table
               if not (op.op == "F" and op.mb == 2 and op.vstage == 1)]
    problems = lint_schedule(dropped, 4, 4, 1, kind="1f1b")
    assert problems and any("never produced" in p for p in problems)


def test_lint_schedule_catches_unbounded_live_set():
    from picotron_tpu.parallel.mpmd import build_schedule, lint_schedule

    # a gpipe table (save-everything) presented as 1f1b blows the
    # in-flight budget: backwards deferred past the pipeline depth
    table = build_schedule("gpipe", 16, 4, 1)
    problems = lint_schedule(table, 16, 4, 1, kind="1f1b")
    assert any("in-flight budget" in p for p in problems)


def test_build_schedule_raises_on_linted_table(monkeypatch):
    import picotron_tpu.parallel.mpmd as mpmd

    monkeypatch.setattr(mpmd, "lint_schedule",
                        lambda *a, **k: ["planted problem"])
    with pytest.raises(mpmd.ScheduleBufferError, match="static lint"):
        mpmd.build_schedule("1f1b", 4, 2, 1)
