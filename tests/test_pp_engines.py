"""Pipeline-engine tests: 1F1B vs AFAB equivalence, the 1F1B memory bound,
and remat-policy effect in the pipeline path (ref: the reference validates
its schedules by loss parity between pipeline_parallel_1f1b and
pipeline_parallel_afab, pipeline_parallel.py:122-215 vs 77-118)."""

import faulthandler
import functools
import importlib.util
import math
import os

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from picotron_tpu.config import Config, DistributedConfig, ModelConfig, TrainingConfig
from picotron_tpu.mesh import MeshEnv
from picotron_tpu.parallel.api import init_sharded_state, make_train_step


@pytest.fixture
def rendezvous_timeout():
    """A collective that waits for a peer which took the other branch of a
    `lax.cond` never returns, and Python cannot interrupt the wait: after 300
    s dump every thread's stack and end the process, so the hang is one
    failed test (a lost worker) and not the suite's whole clock."""
    faulthandler.dump_traceback_later(300, exit=True)
    yield
    faulthandler.cancel_dump_traceback_later()


def pp_cfg(engine, pp=2, gas=4, tp=1, remat=False, remat_policy="dots",
           seq=32, mbs=2, hidden=64, ep=1, sp=False, model=None):
    return Config(
        distributed=DistributedConfig(pp_size=pp, tp_size=tp, ep_size=ep,
                                      sequence_parallel=sp, pp_engine=engine),
        model=ModelConfig(dtype="float32", hidden_size=hidden,
                          num_attention_heads=8, num_key_value_heads=4,
                          **(model or {})),
        training=TrainingConfig(seq_length=seq, micro_batch_size=mbs,
                                gradient_accumulation_steps=gas,
                                learning_rate=1e-3, remat=remat,
                                remat_policy=remat_policy),
    )


def batch_for(cfg, menv, key=0):
    t = cfg.training
    b_global = (t.micro_batch_size * cfg.distributed.dp_size
                * cfg.distributed.ep_size)
    toks = jax.random.randint(
        jax.random.key(key),
        (t.gradient_accumulation_steps, b_global, t.seq_length + 1),
        0, cfg.model.vocab_size)
    sh = NamedSharding(menv.mesh, P(None, ("dp", "ep"), "cp"))
    return (jax.device_put(toks[..., :-1], sh),
            jax.device_put(toks[..., 1:], sh))


def build(cfg):
    """(jitted train step, initial state, one batch) for cfg on its mesh."""
    menv = MeshEnv.from_config(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    return make_train_step(cfg, menv), state, batch_for(cfg, menv)


def run_metrics(cfg, steps=3):
    """([the step's metrics as floats, a step], final state)."""
    step, state, batch = build(cfg)
    history = []
    for _ in range(steps):
        state, metrics = step(state, batch)
        history.append({k: float(v) for k, v in metrics.items()})
    return history, state


def run_engine(cfg, steps=3):
    history, state = run_metrics(cfg, steps)
    return [m["loss"] for m in history], state


LAYOUTS = {
    # (pp2/gas4 pruned r5: strict subset of pp4/gas4 and pp2xtp2)
    "pp4": dict(pp=4, gas=4),
    "pp2tp2": dict(pp=2, gas=4, tp=2),
    "pp2remat": dict(pp=2, gas=3, remat=True),  # odd n_micro + remat'd ticks
}


@functools.lru_cache(maxsize=None)
def run_1f1b(name):
    """(losses, final state) of three 1F1B steps on LAYOUTS[name]; the two
    tests below read the same run."""
    return run_engine(pp_cfg("1f1b", **LAYOUTS[name]))


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_1f1b_matches_afab(name):
    """The two engines compute the same gradients (same math, different
    schedule); only fp reduction order differs."""
    l_1f1b, s_1f1b = run_1f1b(name)
    l_afab, s_afab = run_engine(pp_cfg("afab", **LAYOUTS[name]))
    np.testing.assert_allclose(l_1f1b, l_afab, rtol=1e-5, atol=1e-6)
    for name in ("embedding", "lm_head"):
        np.testing.assert_allclose(
            np.asarray(s_1f1b.params[name]), np.asarray(s_afab.params[name]),
            rtol=2e-3, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(s_1f1b.params["layers"]["q"]),
        np.asarray(s_afab.params["layers"]["q"]), rtol=2e-3, atol=1e-4)


# Three steps of the 1F1B engine on the parent of PR 38 (commit 977113c, this
# installation, float32 on 8 host devices), whose tick ran a forward unit
# that scored, on every stage, beside the backward unit: the losses it
# reported, and the float64 sum of |p| over the updated parameters. PR 38
# reads the loss from the backward unit's primal and skips the last stage's
# forward unit; the backward unit, which makes every gradient, is the same
# code. Steps 2 and 3 are computed from updated parameters, so equal losses
# hold the updates too. (On pp2tp2 and pp2remat the updated parameters are
# bit-equal to the parent's; on pp4 the CPU compiler fuses the unchanged
# backward differently around the new branch and single parameters move by
# up to 8e-6 after one Adam step, which is why this holds sums and not a
# digest.)
PARENT_1F1B = {
    "pp4": ([5.694900989532471, 5.573522567749023, 5.4533843994140625],
            23417.8854430543),
    "pp2tp2": ([5.694900989532471, 5.573522567749023, 5.4533843994140625],
               23417.885443114465),
    "pp2remat": ([5.687442779541016, 5.551187038421631, 5.4163079261779785],
                 23418.75768325695),
}


def abs_sum(params) -> float:
    return float(sum(np.abs(np.asarray(x, np.float64)).sum()
                     for x in jax.tree.leaves(params)))


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_1f1b_loss_and_update_are_the_parents(name):
    losses, state = run_1f1b(name)
    want_losses, want_sum = PARENT_1F1B[name]
    np.testing.assert_allclose(losses, want_losses, rtol=1e-6, atol=0)
    np.testing.assert_allclose(abs_sum(state.params), want_sum, rtol=1e-6)


def sub_jaxprs(eqn):
    """The jaxprs an equation holds: a scan's body, a cond's branches."""
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def head_forwards_a_tick(cfg) -> int:
    """dot_generals whose result is one microbatch's logits ([tokens,
    vocab / tp]) in the body of the traced step's 1F1B scan: the head's
    forward matmuls a tick. (dx has the hidden size last, dW the hidden size
    first.)"""
    step, state, batch = build(cfg)
    jaxpr = jax.make_jaxpr(lambda s, b: step(s, b))(state, batch).jaxpr
    t, d = cfg.training, cfg.distributed
    tokens = t.micro_batch_size * t.seq_length
    vocab = cfg.model.vocab_size // d.tp_size
    assert tokens != cfg.model.hidden_size  # or dW would read as logits
    ticks = t.gradient_accumulation_steps + 2 * (d.pp_size - 1)

    def walk(jaxpr, in_scan):
        n = 0
        for eqn in jaxpr.eqns:
            inside = in_scan or (eqn.primitive.name == "scan"
                                 and eqn.params["length"] == ticks)
            if in_scan and eqn.primitive.name == "dot_general":
                shape = eqn.outvars[0].aval.shape
                n += shape[-1] == vocab and math.prod(shape[:-1]) == tokens
            n += sum(walk(j, inside) for j in sub_jaxprs(eqn))
        return n

    return walk(jaxpr, False)


@pytest.mark.parametrize("layout", [
    dict(pp=2, gas=4, tp=2),           # the head gated to the last stage
    dict(pp=2, gas=4, tp=2, sp=True),  # masked-uniform on every stage
    dict(pp=2, gas=4),                 # no tp hook: the cond returns the total
], ids=["gated", "sequence_parallel", "unsharded"])
def test_1f1b_tick_runs_one_head_forward(layout):
    """The 1F1B tick computes a microbatch's logits once, under the backward
    unit's `jax.vjp`. Until PR 38 the forward unit scored too (two a tick),
    and kept only a scalar that the vjp's primal also holds."""
    assert head_forwards_a_tick(pp_cfg("1f1b", seq=16, **layout)) == 1


MOE = dict(name="debug-tiny-moe", num_hidden_layers=2, num_experts=8,
           num_experts_per_token=2,
           # tight capacity: drops happen, so the drop sum has a value to lose
           capacity_factor=0.5, router_z_coef=1e-3)


@pytest.mark.parametrize("layout", [
    dict(pp=2, ep=2, gas=3, model=MOE),
    dict(pp=2, gas=3, model=dict(tie_word_embeddings=True)),
    dict(pp=2, tp=2, gas=3, sp=True),
], ids=["moe_ep2", "tied_embedding", "sequence_parallel"])
def test_1f1b_backward_units_sums_match_afab(layout, rendezvous_timeout):
    """What PR 38 moved from the forward unit's outputs to the backward
    unit's primal: the CE sum and count (the head is the embedding when
    tied; masked-uniform on every stage under sequence parallelism), each
    stage's own router term, and the drop / load observability sums. AFAB
    still scores in its differentiated forward, so it is the reference."""
    m_1f1b, s_1f1b = run_metrics(pp_cfg("1f1b", **layout), steps=2)
    m_afab, s_afab = run_metrics(pp_cfg("afab", **layout), steps=2)
    # (grad_norm too, under sequence parallelism as well: until PR 63 1F1B
    # summed the norms' gradients over tp twice there, 0.67146 against
    # AFAB's 0.66442: parallel/pp.py pipeline_1f1b_grads `g_zero`)
    for a, b in zip(m_1f1b, m_afab):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_allclose(
                a[k], b[k], rtol=1e-5 if k == "loss" else 1e-4, atol=1e-6,
                err_msg=k)
    if "num_experts" in layout.get("model", {}):
        assert 0.0 < m_1f1b[0]["moe_drop_frac"] < 1.0
        assert m_1f1b[0]["moe_load_max_over_mean"] > 1.0
    np.testing.assert_allclose(
        np.asarray(s_1f1b.params["embedding"]),
        np.asarray(s_afab.params["embedding"]), rtol=2e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# PR 63: the accumulating tick (parallel/pp.py: the 1F1B backward unit runs
# parallel/fused_bwd.py's two layer scans and every leaf's gradient lands in
# the accumulator where it is produced) against the AD tick and AFAB
# ---------------------------------------------------------------------------

QWEN = dict(attention_bias=True, tie_word_embeddings=True)
# what `resolved_grad_engine` lets the accumulating tick take: the old block
# under remat dots_attn on the spmd executor's 1F1B engine, on every axis the
# manual backward runs at pp = 1
TICK_LAYOUTS = {
    "pp2tp2": dict(dk=dict(pp_size=2, tp_size=2)),
    "pp2dp2": dict(dk=dict(pp_size=2, dp_size=2)),
    "pp2tp2-qwen": dict(dk=dict(pp_size=2, tp_size=2), mk=QWEN),
    "pp2dp2-qwen": dict(dk=dict(pp_size=2, dp_size=2), mk=QWEN),
    # stages that hold neither embedding nor head, an odd microbatch count
    "pp4": dict(dk=dict(pp_size=4), ga=3),
    # 3 layers over 2 stages: the last stage's second slot is a zero layer
    "pp2-padded": dict(dk=dict(pp_size=2), mk=dict(num_hidden_layers=3)),
    # the head's branch gathers the sequence; the norms' grads are tp-partial
    "pp2tp2-sp": dict(dk=dict(pp_size=2, tp_size=2, sequence_parallel=True)),
    # a ring of ppermutes in both layer scans (never in a branch by stage)
    "pp2cp2": dict(dk=dict(pp_size=2, cp_size=2)),
    # each stage's own router term rides the scan's aux fold, weighted by
    # the token count times the tick's cotangent; drops happen
    "pp2ep2-moe": dict(dk=dict(pp_size=2, ep_size=2), mk=MOE,
                       tr=dict(micro_batch_size=2)),
    # PR 65, where most ticks are fill or drain and a unit's branch is idle
    # more often than live: one microbatch (3 ticks, no stage ever holds
    # both units), two at pp 2, and two at pp 4 (8 ticks, n_micro <
    # 2(pp - 1): the ring's smaller form; "pp4" above is three); toy depths
    "pp2-n1": dict(dk=dict(pp_size=2), mk=dict(num_hidden_layers=2), ga=1),
    "pp2-n2": dict(dk=dict(pp_size=2), mk=dict(num_hidden_layers=2), ga=2),
    "pp4-n2": dict(dk=dict(pp_size=4), ga=2),
}
# what it may not take: `auto` keeps the AD tick, and an explicit `fused` is
# refused
AD_ONLY = {
    # the scans do not mask a padded slot's router statistics
    "moe-padded": dict(dk=dict(pp_size=2),
                       mk={**MOE, "num_hidden_layers": 3}),
    "afab": dict(dk=dict(pp_size=2, pp_engine="afab")),
    "dots": dict(dk=dict(pp_size=2), tr=dict(remat_policy="dots")),
}


def tick_cfg(engine, grad_engine, dk, mk=None, ga=4, tr=None):
    from tests.test_fused_bwd import fp32_cfg

    return fp32_cfg(grad_engine,
                    {"num_hidden_layers": 4, "max_position_embeddings": 32,
                     **(mk or {})},
                    {"pp_engine": engine, **dk},
                    **{"seq_length": 32, "micro_batch_size": 1,
                       "gradient_accumulation_steps": ga, **(tr or {})})


@functools.lru_cache(maxsize=None)
def tick_grads(name, engine, grad_engine):
    """(fp32 gradient tree, loss, extras) of one `_device_grads` call."""
    from tests.test_fused_bwd import device_grads_of

    return device_grads_of(
        tick_cfg(engine, grad_engine, **TICK_LAYOUTS[name]))[:3]


@pytest.mark.parametrize("against", ["ad_tick", "afab"])
@pytest.mark.parametrize("name", sorted(TICK_LAYOUTS))
def test_accumulating_tick_grads_match(name, against, rendezvous_timeout):
    """Leaf by leaf, at the tolerance tests/test_fused_bwd.py holds the
    fused engine to (1e-4 of the leaf's largest gradient, float32): the
    gradients the accumulating tick hands the optimizer are the AD tick's
    and AFAB's. A leaf left out of the accumulation (a stage that skipped
    its embedding, a head added on no stage) reads as a whole leaf off.
    Since PR 65 each unit of the tick sits in a branch by whether the stage
    holds a microbatch for it (none with cp 2): a microbatch left out, or a
    tick whose idle branch dropped an accumulator, moves the loss (the sum
    over the token count, which also divides every gradient), the drop sums
    or a leaf."""
    from picotron_tpu.parallel.fused_bwd import resolved_grad_engine

    layout = TICK_LAYOUTS[name]
    assert resolved_grad_engine(tick_cfg("1f1b", "auto", **layout)) == "fused"
    assert resolved_grad_engine(tick_cfg("1f1b", "ad", **layout)) == "ad"
    got, l_got, e_got = tick_grads(name, "1f1b", "fused")
    want, l_want, e_want = tick_grads(name, *{"ad_tick": ("1f1b", "ad"),
                                              "afab": ("afab", "ad")}[against])
    np.testing.assert_allclose(l_got, l_want, rtol=1e-5)
    assert set(e_got) == set(e_want)  # the MoE drop / load sums
    for k in e_want:
        np.testing.assert_allclose(e_got[k], e_want[k], rtol=1e-5, err_msg=k)
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    for (path, a), b in zip(flat, jax.tree.leaves(got)):
        np.testing.assert_array_less(
            np.abs(a - b).max() / (np.abs(a).max() + 1e-12), 1e-4,
            err_msg=jax.tree_util.keystr(path))


def abstract_step(cfg):
    """(jitted train step, abstract state, abstract batch) for cfg."""
    menv = MeshEnv.from_config(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0), abstract=True)
    t = cfg.training
    b = jax.ShapeDtypeStruct(
        (t.gradient_accumulation_steps,
         t.micro_batch_size * cfg.distributed.dp_size, t.seq_length),
        np.int32, sharding=menv.batch_sharding())
    return make_train_step(cfg, menv), state, (b, b)


def tick_scopes(cfg) -> set:
    """The declared scopes on the name stacks of the traced step."""
    from tests.test_scopes import scopes_in

    step, state, batch = abstract_step(cfg)
    return scopes_in(step.lower(state, batch).as_text(debug_info=True))


@pytest.mark.parametrize("name", sorted(AD_ONLY))
def test_uncovered_layout_keeps_the_ad_tick(name):
    """`auto` falls back where the manual backward is not proven, by the one
    predicate every reader shares; `fused` there is refused, not swapped.
    The `dw_accum` scope is the sign in a trace of which tick ran."""
    from picotron_tpu.parallel.fused_bwd import resolved_grad_engine

    layout = AD_ONLY[name]
    engine = layout["dk"].get("pp_engine", "1f1b")
    cfg = tick_cfg(engine, "auto", **layout)
    assert resolved_grad_engine(cfg) == "ad"
    with pytest.raises(ValueError, match="grad_engine='fused'"):
        tick_cfg(engine, "fused", **layout).validate()
    if name == "moe-padded":  # one lowering each way is enough
        assert "dw_accum" not in tick_scopes(cfg)
        assert "dw_accum" in tick_scopes(
            tick_cfg("1f1b", "auto", **TICK_LAYOUTS["pp2tp2"]))


def traced_tick(cfg):
    """The body of the traced step's 1F1B scan, a jaxpr."""
    step, state, batch = abstract_step(cfg)
    ticks = (cfg.training.gradient_accumulation_steps
             + 2 * (cfg.distributed.pp_size - 1))

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan" and eqn.params["length"] == ticks:
                return eqn.params["jaxpr"].jaxpr
            for j in sub_jaxprs(eqn):
                if (got := find(j)) is not None:
                    return got

    return find(jax.make_jaxpr(step)(state, batch).jaxpr)


def count_in(jaxpr, primitive: str) -> int:
    return sum((eqn.primitive.name == primitive)
               + sum(count_in(j, primitive) for j in sub_jaxprs(eqn))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("name,units_in_branches", [("pp2tp2", True),
                                                    ("pp2cp2", False)])
def test_tick_holds_a_conditional_round_each_unit(name, units_in_branches):
    """PR 65: the tick's top level is two conditionals, the forward unit
    (one layer scan) by `(s_idx == pp - 1) | ~f_on` and the backward unit
    (both layer scans, and the three branches by stage inside it) by `b_on`,
    and the two ppermutes outside them. With cp 2 the layers hold a ring of
    ppermutes, which may sit in no branch: the three layer scans stand at
    the tick's top level beside the three branches by stage, as before."""
    cfg = tick_cfg("1f1b", "auto", **TICK_LAYOUTS[name])
    tick = traced_tick(cfg)
    conds = [eqn for eqn in tick.eqns if eqn.primitive.name == "cond"]
    inside = sorted(
        tuple(sum(count_in(j, prim) for j in sub_jaxprs(eqn))
              for prim in ("cond", "scan", "ppermute")) for eqn in conds)
    scans = sum(eqn.primitive.name == "scan" for eqn in tick.eqns)
    if units_in_branches:
        assert inside == [(0, 1, 0), (3, 2, 0)] and scans == 0
        assert count_in(tick, "ppermute") == 2
    else:
        assert inside == [(0, 0, 0)] * 3 and scans == 3
        assert count_in(tick, "ppermute") > 2  # the ring's, in the scans


def test_branch_by_stage_may_hold_a_tp_collective(rendezvous_timeout):
    """parallel/pp.py's branch rule, probed: a cond on the stage index with a
    psum over 'tp' in one branch only, a ppermute over 'pp' after it, in a
    scan (tools/pp_branch_probe.py; the same script ran on the four chips)."""
    spec = importlib.util.spec_from_file_location(
        "pp_branch_probe", os.path.join(os.path.dirname(__file__), "..",
                                        "tools", "pp_branch_probe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ok, got = mod.probe(jax.devices())
    assert ok, got


def _compiled_temp_bytes(cfg):
    step, state, batch = build(cfg)
    stats = step.lower(state, batch).compile().memory_analysis()
    return stats.temp_size_in_bytes


def test_1f1b_memory_bound():
    """1F1B's live activation set is <= pp microbatches (ring buffer);
    AFAB's grows with n_micro (per-tick scan residuals). With activations
    sized to dominate the parameter buffers, compiled temp memory must be
    materially smaller for 1F1B at large n_micro."""
    layout = dict(pp=2, gas=16, seq=512, mbs=4, remat=True,
                  remat_policy="full")
    t_afab = _compiled_temp_bytes(pp_cfg("afab", **layout))
    t_1f1b = _compiled_temp_bytes(pp_cfg("1f1b", **layout))
    # boundary activation = mbs*seq*hidden*4B = 512KB; AFAB stores one per
    # tick (17) vs 1F1B's ring of pp (2) — expect several MB of daylight.
    assert t_1f1b < t_afab, (t_1f1b, t_afab)
    assert t_afab - t_1f1b > 4 * layout["mbs"] * layout["seq"] * 64, \
        (t_1f1b, t_afab)


def test_1f1b_tick_count_and_schedule_rate():
    """The 1F1B scan must run n_micro + 2(pp-1) ticks — the full-rate
    schedule (one active F and one active B per stage per steady tick), not
    the half-rate 2*n_micro + 2(pp-1) - 1 of VERDICT r2 weak #1. Pinned via
    the helper AND the traced scan length."""
    import re

    from picotron_tpu.parallel.pp import pp_1f1b_ring_slots, pp_1f1b_ticks

    assert pp_1f1b_ticks(8, 4) == 14
    assert pp_1f1b_ticks(4, 1) == 4
    assert pp_1f1b_ring_slots(8, 4) == 6
    assert pp_1f1b_ring_slots(2, 4) == 2  # never larger than n_micro
    assert pp_1f1b_ring_slots(4, 1) == 1

    pp_size, gas = 4, 8
    step, state, batch = build(pp_cfg("1f1b", pp=pp_size, gas=gas))
    jaxpr = str(jax.make_jaxpr(lambda s, b: step(s, b))(state, batch))
    lengths = {int(x) for x in re.findall(r"length=(\d+)", jaxpr)}
    assert pp_1f1b_ticks(gas, pp_size) in lengths, lengths
    old_ticks = 2 * gas + 2 * (pp_size - 1) - 1
    assert old_ticks not in lengths, lengths


def test_afab_remat_policy_reaches_pipeline_tick():
    """remat_policy must change what the AFAB tick scan saves (VERDICT r1:
    the pp path used to blanket-full-remat regardless of policy)."""
    jaxprs = {}
    losses = {}
    for policy in ("full", "dots", "dots_attn", "dots_norms"):
        step, state, batch = build(
            pp_cfg("afab", pp=2, gas=2, remat=True, remat_policy=policy))
        jaxprs[policy] = str(jax.make_jaxpr(lambda s, b: step(s, b))(state, batch))
        _, metrics = step(state, batch)
        losses[policy] = float(metrics["loss"])
    assert jaxprs["full"] != jaxprs["dots"]
    # each named policy must actually differ from its neighbors (a
    # checkpoint_name typo would silently degrade it) and keep numerics
    assert jaxprs["dots_norms"] != jaxprs["dots"]
    assert jaxprs["dots_attn"] != jaxprs["dots"]
    assert jaxprs["dots_attn"] != jaxprs["full"]
    for policy in ("dots", "dots_attn", "dots_norms"):
        np.testing.assert_allclose(losses["full"], losses[policy],
                                   rtol=1e-6)


def test_dots_offload_policy_compiles_and_matches():
    """dots_offload (activations parked in pinned host — placement is a
    no-op on CPU but the offload-annotated jaxpr must compile and keep
    numerics; the on-chip economics are recorded in PERF.md r4)."""
    losses = {}
    for policy in ("dots", "dots_offload"):
        step, state, batch = build(
            pp_cfg("afab", pp=2, gas=2, remat=True, remat_policy=policy))
        _, metrics = step(state, batch)
        losses[policy] = float(metrics["loss"])
    np.testing.assert_allclose(losses["dots"], losses["dots_offload"],
                               rtol=1e-6)
