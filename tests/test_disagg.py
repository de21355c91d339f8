"""Disaggregated serving tests
(picotron_tpu/serve/disagg): greedy/sampled token
parity vs the offline oracle and the colocated engine (including under
preemption and across the handoff boundary), both-pools exhaustion
without leak or deadlock, youngest-first preemption across the
boundary, compile-once discipline for the pool programs (runtime and
statically via the variant prover), handoff telemetry, the
bench --serve --disagg stall-drop headline, and the MoE rejection
cross-validation."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import (
    Config, ModelConfig, ServeConfig, resolve_preset,
)
from picotron_tpu.generate import generate
from picotron_tpu.models.llama import init_params
from picotron_tpu.serve import (
    BlockPool, DisaggScheduler, DisaggServeEngine, Request, ServeEngine,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


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


@pytest.fixture(scope="module")
def offline_refs(tiny, requests5):
    """Per-request greedy tokens from the offline contiguous-cache path —
    the parity oracle for every engine configuration."""
    cfg, params = tiny
    return [
        np.asarray(generate(params, cfg, jnp.asarray([p], jnp.int32),
                            n))[0, len(p):].tolist()
        for p, n in requests5
    ]


def scfg(**kw):
    base = dict(decode_slots=3, block_size=4, num_blocks=24,
                prefill_chunk=4, max_model_len=32, decode_interval=3,
                disagg=True)
    base.update(kw)
    return ServeConfig(**base)


def run_disagg(params, cfg, serve_cfg, requests, **kw):
    eng = DisaggServeEngine(params, cfg, serve_cfg, **kw)
    res = eng.run(requests)
    eng.close()
    return eng, res


def tokens_by_id(res):
    return {r["id"]: r["tokens"] for r in res}


# ---------------------------------------------------------------------------
# token parity: disagg must be bit-identical to the offline oracle
# ---------------------------------------------------------------------------


def test_disagg_greedy_parity_matches_offline(tiny, requests5,
                                              offline_refs):
    """Every request crosses the handoff boundary (prefill pool ->
    device_put -> decode pool) and the greedy tokens must still be
    bit-identical to the offline contiguous-cache sampler."""
    cfg, params = tiny
    eng, res = run_disagg(params, cfg, scfg(), requests5)
    by_id = tokens_by_id(res)
    for i, ref in enumerate(offline_refs):
        assert by_id[i] == ref
    s = eng.summary
    assert s["disagg"] is True
    assert s["handoffs"] >= len(requests5) - 1  # first-token-only
    # requests may retire prefill-side without a handoff
    assert s["handoff_blocks"] > 0 and s["handoff_s"] >= 0
    # a drained trace leaves BOTH pools empty — no leaked blocks
    assert eng.sched.pool.in_use == 0
    assert eng.sched.prefill_pool.in_use == 0


def test_disagg_pools_are_separately_placed(tiny, requests5,
                                            offline_refs):
    """With >1 visible device (conftest forces 8 simulated CPU hosts)
    the pools land on DIFFERENT devices and the handoff is a real
    cross-device transfer — parity must survive it."""
    cfg, params = tiny
    eng = DisaggServeEngine(params, cfg, scfg())
    p_dev = next(iter(eng._kv_p[0].sharding.device_set))
    d_dev = next(iter(eng._kv[0].sharding.device_set))
    assert p_dev != d_dev, "prefill and decode pools share a device"
    res = eng.run(requests5)
    eng.close()
    by_id = tokens_by_id(res)
    for i, ref in enumerate(offline_refs):
        assert by_id[i] == ref


def test_disagg_parity_under_decode_pool_preemption(tiny, requests5,
                                                    offline_refs):
    """A decode pool too small for the live set forces youngest-first
    preemption; preempted requests recompute THROUGH the prefill pool
    (a second handoff) and tokens must not change."""
    cfg, params = tiny
    eng, res = run_disagg(params, cfg, scfg(num_blocks=6), requests5)
    assert eng.sched.n_preempted > 0
    assert eng.sched.n_handoffs > len(requests5)  # re-handoffs happened
    by_id = tokens_by_id(res)
    for i, ref in enumerate(offline_refs):
        assert by_id[i] == ref
    assert eng.sched.pool.in_use == 0
    assert eng.sched.prefill_pool.in_use == 0


def test_disagg_sampled_parity_vs_colocated(tiny, requests5):
    """Sampled decode (temperature + top-k) must be bit-identical
    between the colocated and disaggregated engines: sampling keys fold
    (request id, token index) only, so WHERE a token is sampled — which
    pool, which slot, before or after a handoff — cannot perturb it."""
    cfg, params = tiny
    kw = dict(temperature=0.7, top_k=8, seed=3)
    colo = ServeEngine(params, cfg, scfg(disagg=False), **kw)
    res_c = colo.run(requests5)
    colo.close()
    _, res_d = run_disagg(params, cfg, scfg(), requests5, **kw)
    assert tokens_by_id(res_c) == tokens_by_id(res_d)


# ---------------------------------------------------------------------------
# both-pools exhaustion: no leak, no deadlock, youngest-first boundary
# ---------------------------------------------------------------------------


def test_both_pools_exhausted_no_leak_no_deadlock(tiny, requests5,
                                                  offline_refs):
    """Prefill pool (2 slots / 4 blocks) and decode pool (4 blocks) both
    at the survivability minimum, both live at once: the trace must
    drain (no deadlock), every block must return (no leak), preemption
    must fire, and tokens must still match the oracle."""
    cfg, params = tiny
    eng, res = run_disagg(
        params, cfg,
        scfg(num_blocks=4, prefill_slots=2, prefill_num_blocks=4),
        requests5)
    assert len(res) == len(requests5)
    assert eng.sched.n_preempted > 0
    by_id = tokens_by_id(res)
    for i, ref in enumerate(offline_refs):
        assert by_id[i] == ref
    assert eng.sched.pool.in_use == 0
    assert eng.sched.prefill_pool.in_use == 0
    assert eng.sched.pool.free_blocks == eng.sched.pool.num_blocks


def test_handoff_preempts_only_strictly_younger():
    """Youngest-first ACROSS the handoff boundary (pure host logic): an
    OLD candidate at the boundary may evict the youngest decode
    resident; the youngest candidate gets None (it must wait — someone
    older is progressing, so no livelock)."""
    sched = DisaggScheduler(2, 2, BlockPool(8), BlockPool(2), 4, 8)
    for i in range(4):
        sched.submit(Request(i, (1,) * 4, 4))
    # admit 0,1 into prefill; finish their prefills; sample a token
    for slot, st in sched.admit():
        sched.note_prefilled(slot, len(st.prefill_ids))
        st.generated.append(7)
    # hand both off: decode pool (2 blocks) holds exactly both prefixes
    assert sched.handoff(0) is not None
    assert sched.handoff(1) is not None
    # admit 2,3 behind them and bring them to the boundary
    for slot, st in sched.admit():
        sched.note_prefilled(slot, len(st.prefill_ids))
        st.generated.append(7)
    ready = sched.handoff_ready()
    assert ready  # oldest-first ordering
    # candidate 2 is YOUNGER than both decode residents (0, 1): it must
    # not evict either — handoff returns None and nobody was preempted
    assert sched.handoff(ready[0]) is None
    assert sched.n_preempted == 0
    # retire resident 0; its decode slot+block free up; now candidate 2
    # hands off WITHOUT preempting (free resources first)
    slot0 = next(i for i, s in enumerate(sched.slots)
                 if s is not None and s.req.id == 0)
    sched.retire(slot0)
    out = sched.handoff(ready[0])
    assert out is not None and out[3] == []  # no victims
    # decode growth for the OLDER resident (1) preempts the YOUNGER (2)
    slot1 = next(i for i, s in enumerate(sched.slots)
                 if s is not None and s.req.id == 1)
    st1 = sched.slots[slot1]
    st1.generated.extend([7] * 2)  # grow past its block
    preempted = sched.ensure_block(slot1, horizon=1)
    assert preempted, "growth should have evicted the younger resident"
    assert sched.queue[0].req.id == 2  # requeued at the FRONT
    assert sched.n_preempted == 1


def test_handoff_behind_a_decode_dispatch_in_flight_keeps_parity(
        tiny, requests5, offline_refs):
    """The inherited decode tick runs one dispatch ahead: a handoff's copy
    is enqueued while decode dispatch n is in flight and nobody has waited
    for it, dispatch n + 1 is enqueued behind the copy with the request
    that crossed in it, and only then does the host wait for dispatch n.
    Tokens are the offline sampler's, with and without decode-pool
    preemption (a handoff may take the blocks of a resident in flight)."""
    from picotron_tpu.telemetry import Telemetry
    from picotron_tpu.telemetry.flightdeck import SpanTracer

    cfg, params = tiny
    for num_blocks in (24, 7):
        tel = Telemetry(sinks=[])
        tel.tracer = SpanTracer()
        eng, res = run_disagg(params, cfg, scfg(num_blocks=num_blocks),
                              requests5, telemetry=tel)
        by_id = tokens_by_id(res)
        for i, ref in enumerate(offline_refs):
            assert by_id[i] == ref, (num_blocks, i)
        names = ("serve.handoff", "serve.decode.dispatch", "serve.decode.wait")
        spans = sorted((e for e in tel.tracer.to_json()["traceEvents"]
                        if e["ph"] == "X" and e["name"] in names),
                       key=lambda e: e["ts"])
        in_flight, crossed_behind = [], 0
        for i, e in enumerate(spans):
            a = e["args"]
            if e["name"] == "serve.decode.wait":
                assert in_flight.pop(0) == a["seq"]
            elif e["name"] == "serve.decode.dispatch":
                in_flight.append(a["seq"])
            elif in_flight:
                # a copy behind dispatch n: the next decode leaf is
                # dispatch n + 1, with the request in it, before wait n
                nxt = next(x for x in spans[i + 1:]
                           if x["name"] != "serve.handoff")
                assert nxt["name"] == "serve.decode.dispatch"
                # (where blocks are short the build may preempt it again)
                assert (str(a["id"]) in nxt["args"]["ids"].split()
                        or num_blocks == 7)
                crossed_behind += 1
        assert crossed_behind > 0 and not in_flight
        assert eng.stats["decode_ahead"] > 0
        assert eng.pool.in_use == 0 and eng.pool_p.in_use == 0
        assert (eng.sched.n_preempted > 0) == (num_blocks == 7)


# ---------------------------------------------------------------------------
# compile discipline: each pool program compiles exactly once
# ---------------------------------------------------------------------------


def test_disagg_single_decode_compile(tiny, requests5, offline_refs):
    """One decode compile for the whole disaggregated lifetime:
    admissions, handoffs, preemptions, and cross-pool block tables are
    data, not shapes. decode_slots=4 is unique to this module so the jit
    cache cannot hide a second compile behind another test's."""
    cfg, params = tiny
    eng, res = run_disagg(params, cfg,
                          scfg(decode_slots=4, num_blocks=7), requests5)
    assert eng.summary["decode_compiles"] == 1
    assert eng.sched.n_preempted > 0  # tables churned, shapes did not
    by_id = tokens_by_id(res)
    for i, ref in enumerate(offline_refs):
        assert by_id[i] == ref


def test_handoff_blocks_read_back_equal_across_pools():
    """A prefix written into the prefill pool, gathered by its block ids
    and scattered into other blocks of a differently sized decode pool,
    reads back through `layer_view` exactly as it did before the handoff;
    the staging buffer's padding rows drop at the decode pool's sentinel."""
    from picotron_tpu.serve.disagg import (
        _gather_blocks_impl, _scatter_blocks_impl,
    )
    from picotron_tpu.serve.paged_cache import PagedKVCache

    layers, hkv, d, bs, mb, n_tok = 2, 2, 8, 4, 4, 10
    src, dst, n_p, n_d = [5, 1, 3], [7, 2, 8], 6, 9
    kk, kv = jax.random.split(jax.random.key(5))
    k_new = jax.random.normal(kk, (layers, 1, n_tok, hkv, d), jnp.float32)
    v_new = jax.random.normal(kv, (layers, 1, n_tok, hkv, d), jnp.float32)
    zeros_p = jnp.zeros((hkv, layers, n_p, bs, d), jnp.float32)
    pre = PagedKVCache(zeros_p, zeros_p,
                       jnp.asarray([src + [n_p]], jnp.int32))
    for li in range(layers):
        pre = pre.write(li, k_new[li], v_new[li], jnp.arange(n_tok))
    # the engine's fixed-width index vectors: 0-padded source ids,
    # sentinel-padded destination ids (DisaggServeEngine._copy_blocks)
    buf_k, buf_v = _gather_blocks_impl(
        pre.k, pre.v, jnp.asarray(src + [0], jnp.int32))
    assert buf_k.shape == (hkv, layers, mb, bs, d)
    ones_d = jnp.ones((hkv, layers, n_d, bs, d), jnp.float32)
    k_d, v_d = _scatter_blocks_impl(ones_d, ones_d, buf_k, buf_v,
                                    jnp.asarray(dst + [n_d], jnp.int32))
    dec = PagedKVCache(k_d, v_d, jnp.asarray([dst + [n_d]], jnp.int32))
    for li in range(layers):
        for got, want in zip(dec.layer_view(li), pre.layer_view(li)):
            np.testing.assert_array_equal(np.asarray(got)[:, :len(src) * bs],
                                          np.asarray(want)[:, :len(src) * bs])
        np.testing.assert_array_equal(
            np.asarray(dec.layer_view(li)[0])[0, :n_tok], np.asarray(k_new[li, 0]))
    # every block the handoff did not name is as it was
    others = [b for b in range(n_d) if b not in dst]
    assert bool(jnp.all(k_d[:, :, others] == 1) & jnp.all(v_d[:, :, others] == 1))


def test_prove_disagg_programs_static():
    """The PR-9 variant prover proves all four disaggregated programs
    (prefill pool, decode pool, handoff gather/scatter) compile once,
    without touching a device; MoE is rejected with the config error."""
    from picotron_tpu.analysis.variants import (
        CHECK, prove_disagg_programs,
    )

    mcfg = ModelConfig(**resolve_preset("debug-tiny"))
    info = prove_disagg_programs(mcfg, scfg()).info[CHECK]
    assert info["proven"] is True
    assert info["programs"] == 4
    assert set(info["signatures"]) == {
        "prefill_pool", "decode_pool", "handoff_gather",
        "handoff_scatter"}
    with pytest.raises(ValueError, match="MoE"):
        prove_disagg_programs(
            ModelConfig(**resolve_preset("debug-tiny-moe")), scfg())


def test_audit_variants_includes_serve_disagg():
    from picotron_tpu.analysis.variants import CHECK, audit_variants

    cfg = Config(model=ModelConfig(**resolve_preset("debug-tiny")))
    info = audit_variants(cfg).info[CHECK]
    assert info["serve_disagg"]["proven"] is True
    moe = Config(model=ModelConfig(**resolve_preset("debug-tiny-moe")))
    info_moe = audit_variants(moe).info[CHECK]
    assert "unavailable" in info_moe["serve_disagg"]


# ---------------------------------------------------------------------------
# config / engine cross-validation: MoE is rejected early and clearly
# ---------------------------------------------------------------------------


def test_config_rejects_moe_disagg_and_speculator():
    moe = ModelConfig(**resolve_preset("debug-tiny-moe"))
    with pytest.raises(ValueError, match="MoE"):
        Config(model=moe, serve=ServeConfig(disagg=True)).validate()
    # dense passes; MoE without serving features passes
    Config(model=ModelConfig(**resolve_preset("debug-tiny")),
           serve=ServeConfig(disagg=True)).validate()
    Config(model=moe).validate()
    # the speculator went with its options: no such field
    with pytest.raises(TypeError, match="speculator"):
        ServeConfig(speculator="ngram")


def test_engines_reject_moe_at_construction():
    """`ServeEngine` constructs and serves a model with experts (dropless
    at ep 1: its tokens are `generate`'s, whatever the chunking); the
    disaggregated engine still refuses one, since nothing has run its
    handoff with an expert block."""
    moe = ModelConfig(dtype="float32",
                      **resolve_preset("debug-tiny-moe"))
    params = init_params(moe, jax.random.key(5))
    prompt = list(range(3, 24))
    want = np.asarray(generate(params, moe, jnp.asarray([prompt]), 6))[0, 21:]
    for chunk in (4, 16):
        eng = ServeEngine(params, moe, ServeConfig(
            decode_slots=2, block_size=4, prefill_chunk=chunk,
            max_model_len=64, decode_interval=2))
        eng.submit(prompt, 6)
        while eng.sched.has_work():
            eng.step(0.0)
        assert eng.results[0]["tokens"] == want.tolist()
        assert 0 < eng.stats["experts_touched"] <= eng.stats["expert_slots"]
        assert eng.pool.in_use == 0
    with pytest.raises(ValueError, match="num_experts"):
        DisaggServeEngine({}, moe, scfg())


def test_serve_config_validates_disagg_fields():
    with pytest.raises(ValueError, match="prefill_slots"):
        ServeConfig(prefill_slots=-1).validate()
    with pytest.raises(ValueError, match="prefill_device"):
        ServeConfig(prefill_device=-2).validate()


# ---------------------------------------------------------------------------
# telemetry: handoff ledger category + per-pool serving view
# ---------------------------------------------------------------------------


def test_disagg_telemetry_handoff_and_report(tiny, requests5, tmp_path):
    """The disaggregated stream books the handoff transport as its own
    (non-goodput) ledger category, the serve_summary carries the
    per-pool aggregates, and tools/telemetry_report.py renders the
    disagg row from the stream alone."""
    from picotron_tpu.telemetry import JsonlSink, Telemetry
    from picotron_tpu.telemetry.goodput import (
        CATEGORIES, GOODPUT_CATEGORIES,
    )

    assert "handoff" in CATEGORIES
    assert "handoff" not in GOODPUT_CATEGORIES  # transport is badput
    cfg, params = tiny
    path = str(tmp_path / "telemetry.jsonl")
    tel = Telemetry(sinks=[JsonlSink(path)])
    eng = DisaggServeEngine(params, cfg, scfg(), telemetry=tel)
    eng.run(requests5)
    tel.close()

    events = [json.loads(line) for line in open(path)]
    cats = {e.get("category") for e in events if e["kind"] == "phase"}
    assert {"prefill", "decode", "handoff"} <= cats
    summ = next(e for e in events if e["kind"] == "serve_summary")
    assert summ["disagg"] is True and summ["handoffs"] > 0
    assert summ["prefill_slot_occupancy"] > 0

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import telemetry_report

    s = telemetry_report.summarize(events)
    sv = s["serving"]
    assert sv["handoffs"] == summ["handoffs"]
    assert sv["prefill_slot_occupancy"] == summ["prefill_slot_occupancy"]
    assert "handoff" in s["categories"]
    text = telemetry_report.render(s)
    assert "disagg:" in text


def test_extract_metrics_serve_columns(tiny, requests5, tmp_path):
    """A serving-only telemetry stream (no train steps) must still yield
    a harvest row: serve_* TTFT/TPOT columns from the serve_summary
    event."""
    from picotron_tpu.telemetry import JsonlSink, Telemetry

    cfg, params = tiny
    run_dir = tmp_path / "serve_run"
    run_dir.mkdir()
    path = str(run_dir / "telemetry.jsonl")
    tel = Telemetry(sinks=[JsonlSink(path)])
    eng = DisaggServeEngine(params, cfg, scfg(), telemetry=tel)
    eng.run(requests5)
    tel.close()

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import extract_metrics

    stats = extract_metrics.process_telemetry(path)
    assert stats is not None
    assert stats["serve_requests"] == len(requests5)
    assert stats["serve_ttft_p50_ms"] >= 0
    assert stats["serve_tpot_p50_ms"] >= 0
    assert stats["serve_handoffs"] > 0


# ---------------------------------------------------------------------------
# cost model: the handoff has a price
# ---------------------------------------------------------------------------


def test_cost_model_prices_kv_handoff():
    from picotron_tpu.analysis.cost_model import CostModel

    mcfg = ModelConfig(**resolve_preset("debug-tiny"))
    cm = CostModel("v5e")
    secs, nbytes = cm.price_kv_handoff(mcfg, scfg())
    # 2 (K+V) x layers x blocks-for-32-tokens x 4 x Hkv x Dh x 2 bytes
    blocks = -(-32 // 4)
    expect = (2 * mcfg.num_hidden_layers * blocks * 4
              * mcfg.num_key_value_heads * mcfg.head_dim * 2)
    assert nbytes == expect
    assert secs > 0
    # fewer tokens -> strictly cheaper; more hops -> strictly dearer
    secs_small, b_small = cm.price_kv_handoff(mcfg, scfg(), n_tokens=4)
    assert b_small < nbytes and secs_small < secs
    secs2, _ = cm.price_kv_handoff(mcfg, scfg(), hops=2)
    assert secs2 > secs


# ---------------------------------------------------------------------------
# bench --serve --disagg: the stall-drop headline
# ---------------------------------------------------------------------------


def test_bench_disagg_stall_drop_on_burst_trace(tiny):
    """The deterministic long-prefill burst: the colocated engine's
    slot-coupled admission serializes the long prefills behind the
    shorts and stalls decode for the whole grind; the disaggregated
    engine overlaps them — max consecutive decode-dispatch stall ticks
    must DROP. Plus the SLO-curve artifact."""
    import bench

    row = bench.run_serve_disagg(
        "debug-tiny", 2, slots=2, block_size=4, num_blocks=0,
        prefill_chunk=4, prompt_len=24, max_new=16, n_requests=4,
        rate=0.0, decode_interval=2)
    assert row["unit"] == "decode_stall_ticks_drop"
    assert row["value"] > 0, (
        f"disagg did not reduce decode stalls: colocated "
        f"{row['colocated_stall_ticks_max']} vs disagg "
        f"{row['disagg_stall_ticks_max']}")
    assert (row["disagg_stall_ticks_max"]
            < row["colocated_stall_ticks_max"])
    assert row["handoffs"] > 0
    assert row["decode_compiles"] == 0  # warmed before measurement
    assert row["predicted_handoff_ms_worstcase"] > 0
    assert len(row["slo_curve"]) == 1  # rate=0: saturation point only
    for tag in ("colocated", "disagg"):
        assert row["slo_curve"][0][tag]["ttft_p50_ms"] is not None
    assert "wall_note" in row


def test_bench_burst_trace_deterministic():
    import bench

    a = bench.make_burst_trace(3, 32, 4, 3, 24, 256, seed=1)
    b = bench.make_burst_trace(3, 32, 4, 3, 24, 256, seed=1)
    assert a == b
    assert all(t == 0.0 for _, _, t in a)  # everything arrives at once
    lens = [len(p) for p, _, _ in a]
    assert lens[:3] == [4, 4, 4] and lens[3:] == [32, 32, 32]
    budgets = [n for _, n, _ in a]
    assert budgets[0] > budgets[3]  # shorts decode long, longs short
