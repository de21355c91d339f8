"""Serving stack tests (picotron_tpu/serve): paged-vs-contiguous greedy
parity, ragged-batch invariance, block-pool accounting, scheduler
admission/preemption, the full queue -> chunked prefill -> continuous
decode -> retirement loop with telemetry, single-compile decode, and the
bench --serve structural comparison against the batch-static sampler."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import ModelConfig, ServeConfig, resolve_preset
from picotron_tpu.generate import generate
from picotron_tpu.models.llama import init_params
from picotron_tpu.serve import BlockPool, Request, Scheduler, ServeEngine
from picotron_tpu.serve.scheduler import blocks_for

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
                prefill_chunk=4, max_model_len=32, decode_interval=3)
    base.update(kw)
    return ServeConfig(**base)


def run_engine(params, cfg, serve_cfg, requests, **kw):
    eng = ServeEngine(params, cfg, serve_cfg, **kw)
    res = eng.run(requests)
    eng.close()
    return eng, res


# ---------------------------------------------------------------------------
# block pool
# ---------------------------------------------------------------------------


def test_block_pool_accounting():
    pool = BlockPool(6)
    a = pool.alloc(4)
    assert len(a) == 4 and pool.in_use == 4 and pool.free_blocks == 2
    assert pool.alloc(3) is None and pool.in_use == 4  # all-or-nothing
    b = pool.alloc(2)
    assert pool.in_use == 6 and pool.peak_in_use == 6
    pool.free(a)
    assert pool.free_blocks == 4
    with pytest.raises(ValueError):
        pool.free(a[:1])  # double free
    with pytest.raises(ValueError):
        pool.free([99])
    pool.free(b)
    assert pool.in_use == 0 and pool.peak_in_use == 6


class ListPool:
    """The plain reference: the free list alone, a double free found by
    looking through it (what `BlockPool` was until it kept a byte a
    block), and a call refused as a whole."""

    def __init__(self, num_blocks):
        self.num_blocks = num_blocks
        self.free_list = list(range(num_blocks - 1, -1, -1))
        self.peak_in_use = 0

    @property
    def free_blocks(self):
        return len(self.free_list)

    @property
    def in_use(self):
        return self.num_blocks - len(self.free_list)

    def alloc(self, n):
        if n > len(self.free_list):
            return None
        out = [self.free_list.pop() for _ in range(n)]
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return out

    def free(self, blocks):
        for i, b in enumerate(blocks):
            if not 0 <= b < self.num_blocks:
                raise ValueError(f"freeing unknown block {b}")
            if b in self.free_list or b in blocks[:i]:
                raise ValueError(f"double free of block {b}")
        self.free_list.extend(blocks)


@pytest.mark.parametrize("num_blocks", [1, 7, 64, 1000])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_block_pool_matches_a_plain_list_pool(num_blocks, seed):
    """Random alloc / free sequences, some of them faulty: the same ids in
    the same order from every alloc, the same counts and the same refusals
    as a pool that is nothing but its list."""
    rng = np.random.default_rng(1000 * num_blocks + seed)
    pool, ref = BlockPool(num_blocks), ListPool(num_blocks)
    held = []  # the live allocations, as the callers hold them
    n_refused = 0
    for _ in range(400):
        kind = rng.integers(0, 10)
        if kind < 5:
            n = int(rng.integers(0, max(2, num_blocks // 3)))
            got, want = pool.alloc(n), ref.alloc(n)
            assert got == want
            if got:
                held.append(got)
        elif kind < 8 and held:
            blocks = held.pop(int(rng.integers(0, len(held))))
            # part of an allocation, in an order of the caller's own
            cut = int(rng.integers(0, len(blocks) + 1))
            give, keep = blocks[:cut][::-1], blocks[cut:]
            if keep:
                held.append(keep)
            pool.free(give)
            ref.free(give)
        else:
            # a faulty call: a block that is unknown, free already, or given
            # twice, behind some that would have been in order
            good = list(held[-1]) if held else []
            bad = [int(rng.choice([-1, num_blocks, num_blocks + 2]))]
            if kind == 8 and ref.free_list:
                bad = [int(rng.choice(ref.free_list))]
            elif good:
                bad = good[-1:]
            errs = []
            for p in (pool, ref):
                with pytest.raises(ValueError) as e:
                    p.free(good + bad)
                errs.append(str(e.value))
            assert errs[0] == errs[1]
            n_refused += 1
        assert (pool.free_blocks, pool.in_use, pool.peak_in_use) == (
            ref.free_blocks, ref.in_use, ref.peak_in_use)
    assert n_refused > 10
    for blocks in held:
        pool.free(blocks)
        ref.free(blocks)
    # the whole list, in its order
    assert pool.in_use == 0 and pool.alloc(num_blocks) == ref.alloc(num_blocks)


@pytest.mark.parametrize("case", ["unknown", "negative", "across_calls",
                                  "within_one_call", "never_allocated"])
def test_block_pool_refuses_and_stays_as_it_was(case):
    """Each refusal by itself, behind blocks that were in order: the call
    raises, and the pool hands out next what it would have without it."""
    pool, twin = BlockPool(12), BlockPool(12)
    a, b = pool.alloc(5), pool.alloc(3)
    twin.alloc(5), twin.alloc(3)
    pool.free(b[:1])
    twin.free(b[:1])
    call, word = {
        "unknown": (a + [12], "unknown block 12"),
        "negative": (a + [-1], "unknown block -1"),
        "across_calls": (a + b[:1], f"double free of block {b[0]}"),
        "within_one_call": (a + a[1:2], f"double free of block {a[1]}"),
        "never_allocated": (a + [11], "double free of block 11"),
    }[case]
    with pytest.raises(ValueError, match=word):
        pool.free(call)
    assert pool.free_blocks == twin.free_blocks == 5
    assert pool.in_use == 7 and pool.peak_in_use == 8
    # the refused call marked nothing: the same blocks can still be freed,
    # and come back in the order a pool that never saw the call gives them
    pool.free(a)
    twin.free(a)
    assert pool.alloc(10) == twin.alloc(10)
    assert pool.alloc(1) is None and pool.in_use == 12


def test_block_pool_free_does_not_grow_with_the_pool():
    """A long request's retirement costs its blocks: 1,920 blocks back to a
    pool of 32,768 at 6% fill (the longdoc cell's longest request) takes
    about what the same free takes in a pool of 2,048."""
    import time

    def best_of_3(num_blocks, others, n=1920):
        best = float("inf")
        for _ in range(3):
            pool = BlockPool(num_blocks)
            pool.alloc(others)
            mine = pool.alloc(n)
            t0 = time.perf_counter()
            pool.free(mine)
            best = min(best, time.perf_counter() - t0)
            assert pool.in_use == others
        return best

    small = best_of_3(2048, 0)
    large = best_of_3(32768, int(0.06 * 32768) - 1920)
    assert large < 0.025, large
    assert large < 5 * small + 1e-3, (small, large)


# ---------------------------------------------------------------------------
# scheduler (pure host logic)
# ---------------------------------------------------------------------------


def make_sched(slots=2, blocks=8, bs=4, max_blocks=8):
    return Scheduler(slots, BlockPool(blocks), bs, max_blocks)


def test_admission_is_fifo_and_block_budgeted():
    s = make_sched(slots=2, blocks=3, bs=4)
    s.submit(Request(0, (1,) * 8, 4))   # needs 2 blocks
    s.submit(Request(1, (1,) * 4, 4))   # needs 1 block
    s.submit(Request(2, (1,) * 4, 4))
    admitted = s.admit()
    # head-of-line: 0 then 1 fill the pool (3 blocks); 2 must wait even
    # though a slot... both slots taken too
    assert [st.req.id for _, st in admitted] == [0, 1]
    assert s.pool.free_blocks == 0
    assert [st.req.id for st in s.queue] == [2]
    # retiring 1 frees its slot + block; 2 admits
    st1 = next(st for _, st in admitted if st.req.id == 1)
    st1.generated.append(5)
    slot1 = s.slots.index(st1)
    s.retire(slot1)
    assert [st.req.id for _, st in s.admit()] == [2]


def test_head_of_line_blocks_admission():
    s = make_sched(slots=2, blocks=3, bs=4)
    s.submit(Request(0, (1,) * 8, 4))   # admission needs 2 blocks
    s.submit(Request(1, (1,) * 8, 4))   # needs 2, only 1 left
    s.submit(Request(2, (1,) * 4, 4))   # needs 1: would fit, but FIFO
    assert [st.req.id for _, st in s.admit()] == [0]
    assert [st.req.id for st in s.queue] == [1, 2]  # no queue jumping


def test_submit_rejects_unservable_request():
    s = make_sched(slots=1, blocks=4, bs=4, max_blocks=4)
    with pytest.raises(ValueError):  # capacity: 5 blocks > table width
        s.submit(Request(0, (1,) * 16, 8))
    s2 = make_sched(slots=1, blocks=2, bs=4, max_blocks=8)
    with pytest.raises(ValueError):  # pool: needs 3 of 2 blocks
        s2.submit(Request(0, (1,) * 8, 4))
    with pytest.raises(ValueError):
        s.submit(Request(1, (), 4))  # empty prompt


def test_preemption_youngest_first_and_requeue_front():
    s = make_sched(slots=2, blocks=4, bs=2)
    s.submit(Request(0, (1, 2, 3), 4))
    s.submit(Request(1, (4, 5, 6), 4))
    s.admit()  # 2 blocks each: pool drained
    assert s.pool.free_blocks == 0
    for slot in (0, 1):
        st = s.slots[slot]
        st.n_prefilled = len(st.prefill_ids)
        st.generated.append(7)
    # slot 0 (oldest) needs a block for its next tokens; pool is empty ->
    # the YOUNGEST (slot 1) is preempted and requeued at the front
    ok, preempted = s.ensure_block(0, horizon=2)
    assert ok and preempted == [1]
    assert s.slots[1] is None
    assert [st.req.id for st in s.queue] == [1]
    assert s.queue[0].generated == [7]  # recompute keeps generated tokens
    assert s.queue[0].blocks == [] and s.pool.free_blocks == 1
    assert s.n_preempted == 1


def test_preemption_single_request_pool_too_small_raises():
    """submit() rejects any request that cannot fit the pool alone, so
    the exhausted-with-one-live-request state is unreachable through the
    public API — the RuntimeError guard is defense-in-depth, covered by
    injecting the state directly."""
    from picotron_tpu.serve.scheduler import RequestState

    s = make_sched(slots=1, blocks=1, bs=2, max_blocks=8)
    st = RequestState(Request(0, (1, 2), 8))
    st.prefill_ids = st.req.prompt
    st.n_prefilled = 2
    st.blocks = s.pool.alloc(1)
    st.generated.extend([3, 4])
    s.slots[0] = st
    with pytest.raises(RuntimeError):
        s.ensure_block(0, horizon=2)


# ---------------------------------------------------------------------------
# paged cache: write -> layer_view against the contiguous cache
# ---------------------------------------------------------------------------

_UNMAPPED = 14  # = num_blocks of the pool below: the tables' sentinel
# one slot of a [2, 6] prefill chunk each: its block table, the chunk's first
# position, its valid tokens, and the positions whose K/V may reach the pool
# (4 blocks of 4 positions a slot, so position 16 is past the table)
DROP_CASES = {
    "in_row_padding": ([0, 2, 5, 9], 2, 4, range(2, 6)),
    "idle_row": ([_UNMAPPED] * 4, 0, 0, range(0)),
    "unmapped_entry": ([3, 7, 1, _UNMAPPED], 8, 6, range(8, 12)),
    "past_the_table": ([4, 6, 8, 10], 13, 6, range(13, 16)),
}


@pytest.mark.parametrize("case", DROP_CASES)
def test_paged_write_then_view_matches_contiguous(case):
    """What `serve_prefill` does to the pool in one layer: scatter a
    [slots, chunk] span at per-slot positions, gather every slot's view.
    Mapped positions read back exactly what the contiguous cache holds;
    padding (q_pos = -1), an idle row, an unmapped table entry and a
    position past the table all drop, leaving the rest of the pool alone."""
    from picotron_tpu.generate import KVCache
    from picotron_tpu.serve.paged_cache import PagedKVCache

    layers, hkv, d, bs, mb, chunk, li = 2, 2, 8, 4, 4, 6, 1
    table, start, n_valid, kept = DROP_CASES[case]
    # slot 0 is an ordinary full chunk beside the slot under test
    tables = jnp.asarray([[12, 13, _UNMAPPED, _UNMAPPED], table], jnp.int32)
    starts, valid = np.array([1, start]), np.array([chunk, n_valid])
    t = np.arange(chunk)[None, :]
    q_pos = jnp.asarray(
        np.where(t < valid[:, None], starts[:, None] + t, -1), jnp.int32)
    kk, kv = jax.random.split(jax.random.key(3))
    k_new = jax.random.normal(kk, (2, chunk, hkv, d), jnp.float32)
    v_new = jax.random.normal(kv, (2, chunk, hkv, d), jnp.float32)
    pool = jnp.zeros((hkv, layers, _UNMAPPED, bs, d), jnp.float32)
    cache = PagedKVCache(pool, pool, tables).write(li, k_new, v_new, q_pos)
    assert cache.num_blocks == _UNMAPPED and cache.block_size == bs
    got_k, got_v = cache.layer_view(li)
    assert got_k.shape == got_v.shape == (2, mb * bs, hkv, d)

    kept_by_slot = [range(1, 1 + chunk), kept]
    for b, span in enumerate(kept_by_slot):
        empty = jnp.zeros((layers, 1, mb * bs, hkv, d))
        ref = KVCache(empty, empty)
        if len(span):
            rows = slice(span[0] - int(starts[b]), span[-1] + 1 - int(starts[b]))
            ref = ref.write(li, k_new[b:b + 1, rows], v_new[b:b + 1, rows],
                            jnp.arange(span[0], span[-1] + 1))
        ref_k, ref_v = ref.layer_view(li)
        # an unmapped entry's view is clamped garbage behind the causal mask
        mapped = np.repeat(np.asarray(tables[b]) != _UNMAPPED, bs)
        np.testing.assert_array_equal(np.asarray(got_k[b])[mapped],
                                      np.asarray(ref_k[0])[mapped])
        np.testing.assert_array_equal(np.asarray(got_v[b])[mapped],
                                      np.asarray(ref_v[0])[mapped])
    # nothing else landed anywhere: the other layer and every other cell
    n_kept = sum(len(span) for span in kept_by_slot)
    for new in (cache.k, cache.v):
        assert int(jnp.count_nonzero(new[:, li])) == n_kept * hkv * d
        assert not bool(jnp.any(new[:, 1 - li]))


# ---------------------------------------------------------------------------
# engine: paged-vs-contiguous greedy parity
# ---------------------------------------------------------------------------


def test_engine_greedy_parity_vs_offline(tiny, requests5, offline_refs):
    """The full serve loop (chunked prefill + continuous paged decode)
    must emit bit-identical greedy tokens to the offline contiguous-cache
    generate, for every request in a mixed-length trace."""
    cfg, params = tiny
    eng, res = run_engine(params, cfg, scfg(), requests5)
    for r, ref in zip(res, offline_refs):
        assert r["tokens"] == ref
    assert eng.summary["requests"] == len(requests5)


def test_engine_greedy_parity_interval_1(tiny, requests5, offline_refs):
    cfg, params = tiny
    _, res = run_engine(params, cfg, scfg(decode_interval=1), requests5)
    for r, ref in zip(res, offline_refs):
        assert r["tokens"] == ref


def test_engine_parity_under_preemption(tiny, requests5, offline_refs):
    """A pool too small for the full trace forces preemption + recompute
    mid-decode; tokens must not change, and every block must return to
    the pool."""
    cfg, params = tiny
    eng, res = run_engine(params, cfg, scfg(num_blocks=8), requests5)
    assert eng.sched.n_preempted > 0
    for r, ref in zip(res, offline_refs):
        assert r["tokens"] == ref
    assert eng.pool.in_use == 0 and eng.pool.free_blocks == 8


def test_engine_tp_sharded_parity(tiny, requests5, offline_refs):
    """place_for_decode(tp=2) params through the serve engine: pure
    GSPMD, XLA shards the block pool over the kv-head axis — greedy
    tokens must match the single-device offline reference."""
    from picotron_tpu.generate import place_for_decode

    cfg, params = tiny
    sharded = place_for_decode(params, cfg, tp=2)
    assert any(len(x.sharding.device_set) == 2
               for x in jax.tree.leaves(sharded))
    _, res = run_engine(sharded, cfg, scfg(), requests5)
    for r, ref in zip(res, offline_refs):
        assert r["tokens"] == ref


def test_eos_retires_early_and_matches_generate(tiny, requests5):
    """EOS mid-stream: the engine's output must equal the offline path's
    (tokens up to and including the first EOS) and the slot must retire
    without burning the remaining budget."""
    cfg, params = tiny
    prompt, _ = requests5[0]
    full = np.asarray(generate(params, cfg, jnp.asarray([prompt],
                                                       jnp.int32), 8))
    eos = int(full[0, len(prompt) + 2])  # 3rd generated token as EOS
    ref = np.asarray(generate(params, cfg, jnp.asarray([prompt],
                                                       jnp.int32), 8,
                              eos_token_id=eos))[0, len(prompt):]
    ref = list(ref[:list(ref).index(eos) + 1]) if eos in ref else list(ref)
    _, res = run_engine(params, cfg, scfg(), [(prompt, 8)],
                        eos_token_id=eos)
    assert res[0]["tokens"] == ref
    assert res[0]["tokens"][-1] == eos


# ---------------------------------------------------------------------------
# ragged-batch invariance
# ---------------------------------------------------------------------------


def test_ragged_invariance_slot_count_and_order(tiny, requests5,
                                                offline_refs):
    """Emitted tokens are a function of the request alone: slot count,
    submission order, and which requests share the batch must never
    change them (per-slot positions + per-slot block tables + per-request
    sampling keys)."""
    cfg, params = tiny
    for slots in (1, 2, 4):
        _, res = run_engine(params, cfg, scfg(decode_slots=slots),
                            requests5)
        for r, ref in zip(res, offline_refs):
            assert r["tokens"] == ref, f"slots={slots}"
    # reversed submission order (ids pinned so results key back)
    eng = ServeEngine(params, cfg, scfg())
    for i in reversed(range(len(requests5))):
        eng.submit(requests5[i][0], requests5[i][1], req_id=i)
    while eng.sched.has_work():
        eng.step()
    eng.close()
    by_id = {r["id"]: r["tokens"] for r in eng.results}
    for i, ref in enumerate(offline_refs):
        assert by_id[i] == ref


def test_sampling_order_invariance(tiny, requests5):
    """Temperature sampling keys derive from (request id, token index):
    shuffling submission order must reproduce identical tokens per id."""
    cfg, params = tiny
    outs = []
    for order in (range(4), reversed(range(4))):
        eng = ServeEngine(params, cfg, scfg(decode_slots=2,
                                            decode_interval=2),
                          temperature=0.8, top_k=5, seed=7)
        for i in order:
            eng.submit(requests5[i][0], requests5[i][1], req_id=i)
        while eng.sched.has_work():
            eng.step()
        eng.close()
        outs.append({r["id"]: r["tokens"] for r in eng.results})
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# memory: pool scales with blocks, accounting is leak-free
# ---------------------------------------------------------------------------


def test_cache_memory_scales_with_blocks_not_batch_x_maxlen(tiny):
    """The paged pool's persistent cache memory is num_blocks *
    block_size token-slots — an OVERSUBSCRIBED pool (fewer slots than
    decode_slots x max_model_len would need) must be exactly what gets
    allocated, which is the memory the contiguous cache cannot avoid."""
    cfg, params = tiny
    sc = scfg(decode_slots=3, num_blocks=9)  # 36 token-slots
    eng = ServeEngine(params, cfg, sc)
    contiguous_equiv = sc.decode_slots * blocks_for(32, sc.block_size)
    # the pool is [Hkv, L, num_blocks, block_size, D]
    k = eng._kv[0]
    assert k.shape[2] == 9 < contiguous_equiv
    # 3 slots x 32 max_model_len would be 96 token-slots; the pool holds 36
    assert k.shape[2] * k.shape[3] == 36
    eng.close()


def test_pool_accounting_over_full_trace(tiny, requests5):
    """Alloc/free across admission, decode growth, and retirement: peak
    matches live sequences' block need, and a drained trace leaves the
    pool exactly full — no leak, no double free."""
    cfg, params = tiny
    eng, res = run_engine(params, cfg, scfg(), requests5)
    assert eng.pool.in_use == 0
    assert eng.pool.free_blocks == eng.pool.num_blocks
    # peak is bounded by what the live sequences could ever need, and
    # nonzero because sequences really allocated
    worst = sum(blocks_for(len(p) + n, 4) for p, n in requests5)
    assert 0 < eng.pool.peak_in_use <= worst


def test_emit_spans_count_the_blocks_a_run_gave_back(tiny, requests5):
    """`blocks_freed` on the emit spans, summed over a drained run, is what
    the run allocated: every block leaves through a retirement here (the
    pool is wide enough that nothing is preempted), the request whose first
    token ends it through `serve.prefill.emit`."""
    eng, tel = traced_engine(tiny)
    allocated = []
    alloc = eng.pool.alloc

    def counting_alloc(n):
        got = alloc(n)
        allocated.extend(got or [])
        return got

    eng.pool.alloc = counting_alloc
    reqs = requests5 + [(requests5[0][0], 1)]
    eng.run(reqs)
    emits = {name: [e["args"] for e in tel.tracer.to_json()["traceEvents"]
                    if e["ph"] == "X" and e["name"] == name]
             for name in ("serve.decode.emit", "serve.prefill.emit")}
    eng.close()
    assert eng.summary["preemptions"] == 0 and eng.pool.in_use == 0
    freed = {name: sum(a["blocks_freed"] for a in spans)
             for name, spans in emits.items()}
    assert sum(freed.values()) == len(allocated) > 0
    # the budget of one holds its prompt's blocks and never decodes
    assert freed["serve.prefill.emit"] == blocks_for(len(reqs[-1][0]), 4)
    for spans in emits.values():
        assert sum(a["retired"] for a in spans) > 0
        assert all((a["blocks_freed"] > 0) == (a["retired"] > 0)
                   for a in spans)
    assert sum(a["retired"] for spans in emits.values()
               for a in spans) == len(reqs)


# ---------------------------------------------------------------------------
# compile discipline
# ---------------------------------------------------------------------------


def test_single_decode_compile_across_multi_request_trace(tiny, requests5,
                                                          offline_refs):
    """One decode-step compile for the whole continuous-batching
    lifetime: admissions, retirements, ragged lengths, and block-table
    growth are data, not shapes. decode_slots=5 is unique to this test so
    the jit cache cannot hide a second compile behind another test's."""
    cfg, params = tiny
    eng, res = run_engine(params, cfg, scfg(decode_slots=5), requests5)
    assert eng.summary["decode_compiles"] == 1
    assert eng.summary["requests"] == len(requests5)
    for r, ref in zip(res, offline_refs):
        assert r["tokens"] == ref


def prefill_dispatches(tel):
    """The `serve.prefill.dispatch` spans' counts, in order."""
    return [e["args"] for e in tel.tracer.to_json()["traceEvents"]
            if e["ph"] == "X" and e["name"] == "serve.prefill.dispatch"]


def traced_engine(tiny, **kw):
    from picotron_tpu.telemetry import Telemetry
    from picotron_tpu.telemetry.flightdeck import SpanTracer

    cfg, params = tiny
    tel = Telemetry(sinks=[])
    tel.tracer = SpanTracer()
    return ServeEngine(params, cfg, scfg(**kw), telemetry=tel), tel


def test_prefill_rungs_are_a_function_of_the_slot_count():
    from picotron_tpu.serve.engine import prefill_rungs

    assert prefill_rungs(32) == (1, 4, 16, 32)  # the chat cell's ladder
    assert [prefill_rungs(n) for n in (1, 2, 4, 5, 16, 17, 64)] == [
        (1,), (1, 2), (1, 4), (1, 4, 5), (1, 4, 16), (1, 4, 16, 17),
        (1, 4, 16, 64)]


# the ladders the benchmark's serving cells compile: 16 slots (EvaByte,
# Qwen3-Next, openPangu, LongCat), 32 (chat, Mellum2), 48 (K-EXAONE), 128
# (Jamba)
COVER_LADDERS = [(1, 4, 16), (1, 4, 16, 32), (1, 4, 16, 48),
                 (1, 4, 16, 64, 128)]


@pytest.mark.parametrize("rungs", COVER_LADDERS, ids=lambda r: f"slots{r[-1]}")
def test_prefill_cover_over_the_cells_ladders(rungs):
    """Every row count up to the slot count: the pieces are rungs, their
    rows are the tick's, at most one piece (the last) is not full, the
    tick computes no more than four thirds of its rows (so under twice)
    and no more than the one rung that holds them all would, and a rung
    that the rows fill by three quarters or more takes them as it always
    did."""
    from picotron_tpu.serve.engine import prefill_cover, prefill_rungs

    assert prefill_rungs(rungs[-1]) == rungs
    assert prefill_cover(0, rungs) == ()
    for n in range(1, rungs[-1] + 1):
        cover = prefill_cover(n, rungs)
        up = min(r for r in rungs if r >= n)
        assert all(rung in rungs and 1 <= rows <= rung
                   for rung, rows in cover), (n, cover)
        assert sum(rows for _, rows in cover) == n
        assert all(rung == rows for rung, rows in cover[:-1]), (n, cover)
        assert [rung for rung, _ in cover] == sorted(
            (rung for rung, _ in cover), reverse=True)
        computed = sum(rung for rung, _ in cover)
        assert 3 * computed <= 4 * n and computed <= up, (n, cover)
        assert (cover == ((up, n),)) == (4 * n >= 3 * up), (n, cover)


COVER_EXAMPLES = [
    ((1, 4, 16, 64, 128), 17, ((16, 16), (1, 1))),  # a burst: not 64 rows
    ((1, 4, 16, 64, 128), 5, ((4, 4), (1, 1))),
    ((1, 4, 16, 64, 128), 40, ((16, 16), (16, 16), (4, 4), (4, 4))),
    ((1, 4, 16, 64, 128), 31, ((16, 16), (16, 15))),
    ((1, 4, 16, 64, 128), 2, ((1, 1), (1, 1))),  # faster than (4, 2) in
    ((1, 4, 16, 64, 128), 3, ((4, 3),)),  # every cell; 1 + 1 + 1 is not
    ((1, 4, 16, 64, 128), 1, ((1, 1),)),
    ((1, 4, 16, 64, 128), 48, ((64, 48),)),
    ((1, 4, 16, 64, 128), 87, ((64, 64), (16, 16), (4, 4), (4, 3))),
    ((1, 4, 16, 64, 128), 24, ((16, 16), (4, 4), (4, 4))),
    ((1, 4, 16), 2, ((1, 1), (1, 1))),  # EvaByte's two documents
    ((1, 4, 16), 7, ((4, 4), (4, 3))),
    ((1, 4, 16), 12, ((16, 12),)),
    ((1, 4, 16, 32), 20, ((16, 16), (4, 4))),
    ((1, 4, 16, 48), 23, ((16, 16), (4, 4), (4, 3))),
    ((1,), 1, ((1, 1),)),
    ((1, 2), 2, ((2, 2),)),
]


@pytest.mark.parametrize(
    "rungs, n, want", COVER_EXAMPLES,
    ids=[f"slots{r[-1]}-rows{n}" for r, n, _ in COVER_EXAMPLES])
def test_prefill_cover_examples(rungs, n, want):
    from picotron_tpu.serve.engine import prefill_cover

    assert prefill_cover(n, rungs) == want


def cover_of(eng, slots):
    """The (rung, rows) pieces a tick of `slots` mid-prefill rows rides."""
    from picotron_tpu.serve.engine import prefill_cover

    return prefill_cover(slots, eng.prefill_rungs)


def ticks_of(dispatches):
    """The dispatch spans grouped by tick: `piece` counts up from 0."""
    ticks = []
    for d in dispatches:
        if d["piece"] == 0:
            ticks.append([])
        ticks[-1].append(d)
    return ticks


@pytest.mark.parametrize("n, slots", [(1, 6), (2, 6), (4, 6), (5, 6), (6, 6),
                                      (5, 16), (7, 16)])
def test_compacted_prefill_parity_and_rung(tiny, requests5, offline_refs, n,
                                           slots):
    """Exactly n prompts prefill at once (n requests at t = 0): every
    tick's dispatches are the cover of its mid-prefill rows
    (`prefill_cover`) on the engine's ladder, (1, 4, 6) or (1, 4, 16): one
    rung where the rows fill three quarters of the smallest that holds
    them (the 6-slot cases but n = 2, as before PR 56), otherwise full
    rungs and a rest (two rows ride 1 + 1, five of 16 slots 4 + 1, seven
    4 + 3 of 4), and the tokens are the offline sampler's whatever rung a
    chunk rode."""
    reqs = (requests5 + requests5[:2])[:n]
    refs = (offline_refs + offline_refs[:2])[:n]
    eng, tel = traced_engine(tiny, decode_slots=slots, num_blocks=8 * slots)
    assert eng.prefill_rungs == {6: (1, 4, 6), 16: (1, 4, 16)}[slots]
    res = eng.run(reqs)
    for r, ref in zip(res, refs):
        assert r["tokens"] == ref
    ticks = ticks_of(prefill_dispatches(tel))
    want = {(1, 6): [1], (2, 6): [1, 1], (4, 6): [4], (5, 6): [6], (6, 6): [6],
            (5, 16): [4, 1], (7, 16): [4, 4]}
    assert [d["rows"] for d in ticks[0]] == want[n, slots]
    assert sum(d["slots"] for d in ticks[0]) == n
    assert max(sum(d["slots"] for d in t) for t in ticks) == n
    for t in ticks:
        assert ([(d["rows"], d["slots"]) for d in t]
                == list(cover_of(eng, sum(d["slots"] for d in t))))
        for d in t:
            assert d["rows"] in eng.prefill_rungs
            assert d["capacity"] == d["rows"] * eng.scfg.prefill_chunk
    assert eng.pool.in_use == 0
    eng.close()
    tel.close()


@pytest.mark.parametrize("n", [5, 7])
def test_a_tick_of_several_dispatches(tiny, requests5, offline_refs, n):
    """Five and seven prompts mid-prefill at once on the ladder (1, 4, 16),
    driven by hand: a tick's spans are its cover, numbered `piece` of
    `pieces` with consecutive `seq`, all enqueued before the tick's one
    wait (which carries the newest `seq`); every slot advances exactly one
    chunk a tick; the stats count ticks, dispatches and rows; nothing
    compiles past the constructor; the tokens are the offline sampler's."""
    reqs = (requests5 + requests5[:2])[:n]
    refs = (offline_refs + offline_refs[:2])[:n]
    eng, tel = traced_engine(tiny, decode_slots=16, num_blocks=128)
    states = eng.sched.slots
    assert eng.prefill_rungs == (1, 4, 16)
    compiles = (eng.stats["prefill_compiles"], eng.stats["decode_compiles"])
    for i, (p, m) in enumerate(reqs):
        eng.submit(p, m, req_id=i)
    chunk = eng.scfg.prefill_chunk
    while eng.sched.has_work():
        # (positions prefilled, the prompt's) of what this step's tick will
        # carry: the slots mid-prefill and, admitted first, the queue
        before = {st.req.id: (st.n_prefilled, len(st.prefill_ids))
                  for st in (*states, *eng.sched.queue)
                  if st is not None and st.prefilling}
        eng.step(0.0)
        after = {st.req.id: st.n_prefilled
                 for st in states if st is not None}
        for rid, (done, total) in before.items():
            if rid in after:  # not retired at its first token
                assert after[rid] == min(done + chunk, total), rid
    events = [e for e in tel.tracer.to_json()["traceEvents"] if e["ph"] == "X"]
    ticks = ticks_of(prefill_dispatches(tel))
    assert [(d["rows"], d["slots"]) for d in ticks[0]] == {
        5: [(4, 4), (1, 1)], 7: [(4, 4), (4, 3)]}[n]
    seqs = [d["seq"] for t in ticks for d in t]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    for t in ticks:
        assert [d["piece"] for d in t] == list(range(len(t)))
        assert all(d["pieces"] == len(t) for d in t)
        assert ([(d["rows"], d["slots"]) for d in t]
                == list(cover_of(eng, sum(d["slots"] for d in t))))
    # one wait a tick at most, behind the tick's last dispatch, with its seq
    order = [e for e in events if e["name"] in ("serve.prefill.dispatch",
                                                "serve.prefill.wait")]
    order.sort(key=lambda e: e["ts"])
    for a, b in zip(order, order[1:]):
        if b["name"] == "serve.prefill.wait":
            assert a["name"] == "serve.prefill.dispatch"
            assert a["args"]["piece"] == a["args"]["pieces"] - 1
            assert b["args"]["seq"] == a["args"]["seq"]
    waits = [e["args"] for e in order if e["name"] == "serve.prefill.wait"]
    assert sum(w["finals"] for w in waits) == n
    stats = eng.stats
    assert stats["prefill_ticks"] == len(ticks)
    assert stats["prefill_dispatches"] == len(seqs) > len(ticks)
    assert stats["prefill_rows_real"] == stats["prefill_chunks"] == sum(
        -(-len(p) // chunk) for p, _ in reqs)
    assert stats["prefill_rows_padded"] == sum(
        d["rows"] - d["slots"] for t in ticks for d in t)
    # the one rung that holds a tick's rows would have computed more
    assert stats["prefill_rows_real"] + stats["prefill_rows_padded"] < sum(
        min(r for r in eng.prefill_rungs if r >= sum(d["slots"] for d in t))
        for t in ticks)
    assert stats["prefill_compiles"] == compiles[0]  # the constructor's
    assert stats["decode_compiles"] <= compiles[1] + 1
    res = sorted(eng.results, key=lambda r: r["id"])
    assert [r["tokens"] for r in res] == refs
    assert eng.pool.in_use == 0
    eng.close()
    tel.close()


def test_prefill_compiles_once_per_rung_inside_the_constructor(
        tiny, requests5, offline_refs):
    """Every shape the engine can dispatch is held before the constructor
    returns: one prefill compile a rung there, none in a trace whose
    concurrency falls from seven prompts to one and so rides every rung
    (seven fill the top rung; six ride 4 + 1 + 1).
    The pool of 29 blocks is unique to this test (the jit cache is shared,
    and a rung below the top one has the same shapes at any slot count)."""
    eng, tel = traced_engine(tiny, decode_slots=7, num_blocks=29)
    assert eng.prefill_rungs == (1, 4, 7)
    assert eng.stats["prefill_compiles"] == 3
    for i, (p, n) in enumerate(requests5 + requests5[:2]):
        eng.submit(p, n, req_id=i)
    while eng.sched.has_work():
        eng.step(0.0)
    eng.submit(*requests5[0], req_id=7)  # alone: the one-row rung
    while eng.sched.has_work():
        eng.step(1.0)
    assert {d["rows"] for d in prefill_dispatches(tel)} == {1, 4, 7}
    assert eng.stats["prefill_compiles"] == 3
    assert eng.stats["decode_compiles"] == 1
    res = sorted(eng.results, key=lambda r: r["id"])
    for r, ref in zip(res, offline_refs + offline_refs[:2] + offline_refs[:1]):
        assert r["tokens"] == ref
    eng.close()
    tel.close()


def test_pad_rows_leak_nothing(tiny, requests5):
    """A pad row of the compacted batch has no token and an all-unmapped
    table row, so a dispatch of pad rows alone (the constructor's
    warm-up) leaves both pools as they were, and a trace ends with no
    block in use."""
    cfg, params = tiny
    eng = ServeEngine(params, cfg, scfg(decode_slots=6))
    for i in (1, 3, 4):  # 9, 7 and 11 tokens: 2 or 3 chunks
        eng.submit(*requests5[i])
    eng.step(0.0)
    mids = eng.sched.prefill_slots()
    assert len(mids) == 3
    feed, nval, finals = eng._prefill_feed(mids)
    trows = np.asarray(feed[0][0])
    assert trows.shape == (4, eng.max_blocks) and finals == [1]
    assert (trows[:3] == eng._tables[0][mids]).all()
    assert (trows[:3, 0] < eng.num_blocks).all()
    assert (trows[3:] == eng.num_blocks).all()  # unmapped: writes drop
    assert list(nval) == [4, 3, 4, 0]
    k0, v0 = (np.asarray(pool) for pool in eng._kv)
    assert k0.any()  # the first chunks are in the pool
    for r in eng.prefill_rungs:
        eng._run_prefill(eng._prefill_feed([], rows=r)[0])
    assert (np.asarray(eng._kv[0]) == k0).all()
    assert (np.asarray(eng._kv[1]) == v0).all()
    while eng.sched.has_work():
        eng.step(0.0)
    assert eng.pool.in_use == 0
    assert eng.pool.free_blocks == eng.pool.num_blocks
    assert (eng._tables[0] == eng.num_blocks).all()
    eng.close()


# ---------------------------------------------------------------------------
# full loop smoke + telemetry report
# ---------------------------------------------------------------------------


def test_serve_loop_telemetry_and_report(tiny, requests5, tmp_path):
    """Tier-1 CPU smoke for the whole serving story: queue -> chunked
    prefill -> continuous decode -> retirement, with the JSONL stream
    carrying queue_wait/prefill/decode bookings, serve_request events,
    and a serve_summary — and tools/telemetry_report.py rendering the
    serving view from it."""
    from picotron_tpu.telemetry import JsonlSink, Telemetry

    cfg, params = tiny
    path = str(tmp_path / "telemetry.jsonl")
    tel = Telemetry(sinks=[JsonlSink(path)])
    eng = ServeEngine(params, cfg, scfg(), telemetry=tel)
    res = eng.run(requests5)
    tel.close()
    assert len(res) == len(requests5)

    events = [json.loads(line) for line in open(path)]
    kinds = {e["kind"] for e in events}
    assert {"serve_request", "serve_summary", "phase"} <= kinds
    cats = {e.get("category") for e in events if e["kind"] == "phase"}
    assert {"queue_wait", "prefill", "decode"} <= cats
    reqs = [e for e in events if e["kind"] == "serve_request"]
    assert len(reqs) == len(requests5)
    assert all(e["ttft_s"] >= 0 for e in reqs)

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import telemetry_report

    s = telemetry_report.summarize(events)
    sv = s["serving"]
    assert sv["requests"] == len(requests5)
    assert sv["output_tokens"] == sum(n for _, n in requests5)
    assert sv["ttft_p50_ms"] >= 0 and sv["ttft_p95_ms"] >= sv["ttft_p50_ms"]
    assert 0 < sv["slot_occupancy"] <= 1
    assert 0 < sv["pool_peak_utilization"] <= 1
    # goodput: prefill + decode book as productive serving time
    assert s["goodput_pct"] is not None and s["goodput_pct"] > 0
    text = telemetry_report.render(s)
    assert "serving:" in text and "TTFT" in text
    md = telemetry_report.render(s, markdown=True)
    assert "### Serving" in md


def test_serve_summary_slot_occupancy_and_queue(tiny):
    """More requests than slots: the queue holds the overflow (nonzero
    queue_wait) and slot occupancy stays high while the batch refills
    mid-flight."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    reqs = [(list(map(int, rng.integers(0, cfg.vocab_size, size=4))), 6)
            for _ in range(6)]
    eng, res = run_engine(params, cfg, scfg(decode_slots=2), reqs)
    assert len(res) == 6
    assert eng.summary["slot_occupancy"] > 0.5
    assert eng.sched.n_admitted == 6


# ---------------------------------------------------------------------------
# bench --serve: structural comparison vs the batch-static sampler
# ---------------------------------------------------------------------------


def _bench_serve_row(**kw):
    import bench

    args = dict(slots=4, block_size=8, num_blocks=0, prefill_chunk=32,
                prompt_len=32, max_new=96, n_requests=24, rate=0.0,
                decode_interval=6, seed=0, repeats=1)
    args.update(kw)
    return bench.run_serve("debug-tiny", 4, **args)


def test_bench_serve_structural_beats_static():
    """The HEADLINE metric is now the deterministic structural ratio
    static_decode_slot_steps / decode_slot_steps — continuous batching
    on a mixed-length trace must burn strictly fewer decode slot-steps
    than the batch-static sampler (which decodes the trace max for every
    batch), identically on every host. Wall-clock is demoted to a
    median-of-repeats sanity field carrying the CPU noise caveat (the
    KNOWN 0.85-1.19 swing is load noise, not a result)."""
    row = _bench_serve_row(n_requests=12, max_new=48)
    assert row["unit"] == "static_over_serve_decode_slot_steps"
    assert row["value"] > 1.0  # structural win, deterministic
    assert row["decode_slot_steps"] < row["static_decode_slot_steps"]
    assert row["serve_tokens_per_sec"] > 0
    # the ratio is the structural win; wall-clock realizes it modulo
    # dispatch overhead + host noise (10-20x swings documented on this
    # host, PERF.md r4) — bound it loosely rather than flakily
    assert row["vs_static"] > 0.4
    assert row["decode_compiles"] == 0  # warmed by the warm-trace engine
    assert row["ttft_p50_ms"] is not None
    assert row["preemptions"] == 0
    assert "noisy" in row["wall_note"]
    assert len(row["serve_walls_s"]) == row["wall_repeats"] == 1


@pytest.mark.slow
def test_bench_serve_wall_clock_vs_static():
    """Wall-clock tokens/s vs the static sampler, median-of-repeats per
    seed and best-of-3 seeds against host-load noise (the
    max-over-attempts idiom bench --sweep uses, ADVICE r4). At
    debug-tiny scale on a shared CPU the per-dispatch penalty (~1.3x a
    monolithic-scan step) roughly cancels the structural step win, so
    observed ratios sit at parity, 0.9-1.2 across repeated runs
    (PERF.md r7) — the assert pins "no dispatch regression" (>0.85)
    plus the deterministic >=1.4x structural step ratio; the
    unambiguous wall-clock beat is the TPU protocol row in PERF.md,
    where decode is HBM-bound and dispatch overhead is noise."""
    rows = [_bench_serve_row(n_requests=48, prompt_len=16, max_new=96,
                             prefill_chunk=16, seed=s, repeats=3)
            for s in (0, 1, 2)]
    best = max(r["vs_static"] for r in rows)
    assert best > 0.85, f"serve throughput regressed vs static: {best}"
    for r in rows:
        assert len(r["serve_walls_s"]) == 3  # median-of-repeats basis
        assert (r["static_decode_slot_steps"]
                >= 1.4 * r["decode_slot_steps"])


def test_bench_serve_trace_deterministic():
    import bench

    a = bench.make_serve_trace(6, 2.0, 32, 16, 256, seed=5)
    b = bench.make_serve_trace(6, 2.0, 32, 16, 256, seed=5)
    assert a == b
    assert all(t1 <= t2 for (_, _, t1), (_, _, t2) in zip(a, a[1:]))
    assert {len(p) for p, _, _ in a} != {32}  # mixed prompt lengths


# ---------------------------------------------------------------------------
# cancel + deadline shed (PR 20 satellites)
# ---------------------------------------------------------------------------


def test_scheduler_sheds_expired_heads_even_when_slots_busy():
    """Deadline admission is pure host logic on the trace clock: a head
    whose queue wait exceeds its deadline_ms is rejected at the admission
    attempt — even when every slot is busy, so the queue cannot back up
    behind the already-dead. No deadline means never shed."""
    s = make_sched(slots=1, blocks=8, bs=4)
    s.submit(Request(0, (1,) * 4, 4, 0.0))            # no deadline
    s.submit(Request(1, (1,) * 4, 4, 0.0, 10.0))      # 10 ms budget
    s.submit(Request(2, (1,) * 4, 4, 0.0, 50.0))      # 50 ms budget
    admitted = s.admit(0.0)
    assert [st.req.id for _, st in admitted] == [0]
    # 20 ms later: 1 is past its deadline and sheds despite the busy
    # slot; 2 is still inside its budget and stays queued
    assert s.admit(0.020) == []
    assert s.n_shed == 1
    assert [st.req.id for st in s.drain_shed()] == [1]
    assert s.drain_shed() == []
    assert [st.req.id for st in s.queue] == [2]
    s.admit(0.060)
    assert s.n_shed == 2
    assert [st.req.id for st in s.drain_shed()] == [2]


def test_engine_cancel_resident_and_queued_no_leak(tiny, requests5,
                                                   offline_refs):
    """ServeEngine.cancel abandons a request wherever it lives: a
    mid-decode resident frees its blocks back to the pool immediately,
    a queued request just vanishes; neither leaves a result, neither
    leaks a block, and the survivors' greedy tokens are untouched by
    the batch-composition change (ragged-batch invariance)."""
    from picotron_tpu.telemetry import Telemetry

    class _Cap:
        def __init__(self):
            self.events = []

        def emit(self, e):
            self.events.append(e)

        def close(self):
            pass

    cap = _Cap()
    cfg, params = tiny
    eng = ServeEngine(params, cfg, scfg(decode_slots=2),
                      telemetry=Telemetry(sinks=[cap]))
    for p, n in requests5:
        eng.submit(p, n)
    eng.step(0.0)
    eng.step(0.0)  # residents are mid-prefill/decode, not just admitted
    resident = [s.req.id for s in eng.sched.slots if s is not None]
    queued = [s.req.id for s in eng.sched.queue]
    assert resident and queued
    held = eng.pool.in_use
    v_slot, v_queue = resident[0], queued[0]
    assert eng.cancel(v_slot)
    assert eng.pool.in_use < held      # blocks back in the pool NOW
    assert eng.cancel(v_queue)
    assert not eng.cancel(v_slot)      # already gone: unknown id
    assert eng.stats["cancelled"] == 2
    while eng.sched.has_work():
        eng.step()
    assert eng.pool.in_use == 0        # no leak without teardown
    done = {r["id"]: r["tokens"] for r in eng.results}
    assert set(done) == set(range(len(requests5))) - {v_slot, v_queue}
    for rid, toks in done.items():
        assert toks == offline_refs[rid], rid
    cancels = [e for e in cap.events if e.get("kind") == "serve_cancel"]
    assert sorted(e["id"] for e in cancels) == sorted([v_slot, v_queue])
    assert {e["where"] for e in cancels} == {"slot", "queue"}
    eng.close()


# ---------------------------------------------------------------------------
# two kinds of cache state (a model with sliding-window layers), and experts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 10], ids=["full_layer", "sliding_layer"])
@pytest.mark.parametrize("chunk", [1, 7], ids=["decode_step", "prefill_chunk"])
def test_mixed_cache_write_then_attend_matches_plain_attention(window, chunk):
    """`MixedPagedKVCache`: a sequence written chunk by chunk through scattered
    blocks (a ring of 5 blocks of 4 for the sliding layer, which it turns
    four times; a growing table for the full layer) and attended in tiles
    equals plain softmax attention over the positions the layer sees, at
    every chunk, for two rows at different depths, one of them padded."""
    from picotron_tpu.serve.paged_cache import MixedPagedKVCache

    hkv, g, d, bs, ring, n_tok = 2, 2, 8, 4, 5, 83
    rng = np.random.default_rng(0 if window is None else 1)
    k_all, v_all = rng.standard_normal((2, 2, n_tok, hkv, d)).astype(np.float32)
    q_all = rng.standard_normal((2, n_tok, hkv * g, d)).astype(np.float32)
    nb, nwb, mb = 64, 16, 24
    perm, wperm = rng.permutation(nb - 1), rng.permutation(nwb - 1)
    tables = np.stack([perm[:mb], perm[mb:2 * mb]]).astype(np.int32)
    wtables = np.stack([wperm[:ring], wperm[ring:2 * ring]]).astype(np.int32)
    zeros = lambda layers, blocks: jnp.zeros((hkv, layers, blocks, bs, d))  # noqa: E731
    cache = MixedPagedKVCache(zeros(1, nb), zeros(1, nb), zeros(2, nwb), zeros(2, nwb),
                              jnp.asarray(tables), jnp.asarray(wtables))
    ki = 0 if window is None else 1
    depth = np.array([0, 0])  # row 1 lags row 0 by being fed pad rows now and then
    step = 0
    while depth[0] < n_tok:
        n = np.array([min(chunk, n_tok - depth[0]),
                      0 if step % 3 == 2 else min(chunk, n_tok - depth[1])])
        pos = np.where(np.arange(chunk)[None] < n[:, None],
                       depth[:, None] + np.arange(chunk)[None], -1)
        take = np.clip(pos, 0, n_tok - 1)
        rows = np.arange(2)[:, None]
        cache = cache.write(0, jnp.asarray(k_all[rows, take]), jnp.asarray(v_all[rows, take]),
                            jnp.asarray(pos), window=window, ki=ki)
        got = np.asarray(cache.attend(0, jnp.asarray(q_all[rows, take]), jnp.asarray(pos),
                                      window=window, ki=ki))
        for b in range(2):
            for i in range(n[b]):
                p = pos[b, i]
                lo = 0 if window is None else max(p - window + 1, 0)
                for h in range(hkv * g):
                    s = k_all[b, lo:p + 1, h // g] @ q_all[b, p, h] / np.sqrt(d)
                    w = np.exp(s - s.max())
                    want = (w / w.sum()) @ v_all[b, lo:p + 1, h // g]
                    np.testing.assert_allclose(got[b, i, h], want, rtol=2e-5, atol=2e-5)
        depth += n
        step += 1
    assert np.isfinite(np.asarray(cache.wk)).all()


def test_engine_serves_experts_with_generates_tokens():
    """A model with experts through `ServeEngine` (the fence that refused it is
    gone): several requests side by side, chunked prefill, preemption-free:
    the tokens are `generate`'s for each prompt alone, and the decode
    program's count of touched experts stays inside its bounds."""
    cfg = ModelConfig(dtype="float32", **resolve_preset("debug-tiny-moe"))
    params = init_params(cfg, jax.random.key(11))
    rng = np.random.default_rng(3)
    requests = [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m)
                for n, m in ((19, 7), (5, 9), (33, 4), (12, 6))]
    eng = ServeEngine(params, cfg, ServeConfig(
        decode_slots=3, block_size=4, prefill_chunk=8, max_model_len=64, decode_interval=2))
    results = eng.run(requests)
    eng.close()
    for (prompt, n), res in zip(requests, results):
        want = np.asarray(generate(params, cfg, jnp.asarray([prompt]), n))[0, len(prompt):]
        assert res["tokens"] == want.tolist()
        assert len(res["logits"]) == n and np.isfinite(res["logits"]).all()
    assert eng.pool.in_use == 0 and eng.wpool is None
    steps = eng.stats["expert_slots"] // (cfg.num_hidden_layers * cfg.num_experts)
    # a live row is routed to 2 experts a layer: between 2 and 2 x the slots
    lo, hi = 2 * cfg.num_hidden_layers * steps, 2 * 3 * cfg.num_hidden_layers * steps
    assert lo <= eng.stats["experts_touched"] <= hi


# ---------------------------------------------------------------------------
# a third kind of cache state: the latent pool (a model with latent attention)
# ---------------------------------------------------------------------------


def _pangu(seed=3):
    return _toy("debug-tiny-pangu-moe", seed)


def _toy(preset, seed=3, **over):
    """A family's tiny preset at a trained model's embedding scale, so that
    the layers show in the logits."""
    cfg = ModelConfig(dtype="float32", **{**resolve_preset(preset), **over})
    cfg.validate()
    p = init_params(cfg, jax.random.key(seed))
    return cfg, dict(p, embedding=p["embedding"] * 0.1)


# One toy model a kind of `init_serve_cache`, two layers a layer kind.
_GDN, _SSM, _KDA, _FULL, _SLIDE = ("linear_attention", "mamba", "kda",
                                   "full_attention", "sliding_attention")
CACHE_KINDS = {
    "latent": (_pangu, "LatentPagedCache"),
    "eva": (lambda: _toy("debug-tiny-evabyte"), "EvaPagedCache"),
    "state_kv_gdn": (lambda: _toy(
        "debug-tiny-qwen3-next", num_hidden_layers=4,
        layer_types=(_GDN, _FULL) * 2), "HybridPagedCache"),
    "state_tail_kv_mamba": (lambda: _toy(
        "debug-tiny-jamba", num_hidden_layers=4,
        layer_types=(_SSM, _FULL) * 2), "HybridPagedCache"),
    "state_latent_kda": (lambda: _toy(
        "debug-tiny-kimi-linear", num_hidden_layers=4,
        layer_types=(_KDA, _FULL) * 2), "HybridLatentPagedCache"),
    "window_full": (lambda: _toy(
        "debug-tiny-mellum2", seed=5, num_hidden_layers=4,
        layer_types=(_SLIDE, _FULL) * 2), "MixedPagedKVCache"),
}


@pytest.fixture(scope="module")
def toys():
    """kind -> (model config, weights), each built once. Every engine of a
    kind is built with `TOY_SERVE` and so has the same shapes: the module
    compiles a kind's two programs once."""
    built = {}

    def get(kind):
        if kind not in built:
            built[kind] = CACHE_KINDS[kind][0]()
        return built[kind]

    return get


# 24 blocks hold two residents of 48 positions and not two of 49
TOY_SERVE = ServeConfig(decode_slots=2, block_size=4, prefill_chunk=8,
                        max_model_len=64, decode_interval=2, num_blocks=24)


def _pools(eng):
    return [p for p in (eng.pool, eng.wpool) if p is not None]


@pytest.mark.parametrize("kind", [k for k in CACHE_KINDS if k != "window_full"])
def test_engine_retire_cancel_shed_leak_no_block(toys, kind):
    """Every kind of cache over a trace that retires, cancels (a resident
    mid-prefill, a resident mid-decode and a queued request) and sheds (a
    deadline passed in the queue): every block of every pool is back, and
    the requests that then take the two slots the cancelled ones left start
    from nothing of theirs: their tokens are `generate`'s, which knows no
    slot. Nothing on the host clears a slot's state row or its blocks: the
    program starts a row at position 0 from zeros."""
    cfg, params = toys(kind)
    rng = np.random.default_rng(2)
    # 0: cancelled mid-prefill; 1: mid-decode; 2: in the queue; 3, 4: retire
    # from the slots of 0 and 1 (past EvaByte's window of 32); 5: shed
    lens, new = (41, 9, 12, 37, 37, 5), 6
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, size=n)))
               for n in lens]
    want = np.asarray(generate(params, cfg, jnp.asarray(prompts[3:5]), new))[:, 37:]
    eng = ServeEngine(params, cfg, TOY_SERVE)
    assert type(eng.cache).__name__ == CACHE_KINDS[kind][1]
    for i, p in enumerate(prompts):
        eng.submit(p, new, req_id=i, arrival=0.0,
                   **({"deadline_ms": 10.0} if i == 5 else {}))
    slot_of = {}

    def step(now):
        eng.step(now)
        slot_of.update({st.req.id: s for s, st in enumerate(eng.sched.slots)
                        if st is not None})

    while 1 not in slot_of or not eng.sched.slots[slot_of[1]].generated:
        step(0.0)
    first, second = (eng.sched.slots[slot_of[i]] for i in (0, 1))
    assert first.req.id == 0 and first.prefilling and not first.generated
    assert second.req.id == 1 and not second.prefilling
    assert 0 < len(second.generated) < new
    held = sum(p.in_use for p in _pools(eng))
    assert eng.cancel(0) and eng.cancel(1) and eng.cancel(2)
    assert sum(p.in_use for p in _pools(eng)) == 0 < held
    now = 1.0
    while eng.sched.has_work():
        step(now)
        now += 0.1
    assert all(p.in_use == 0 for p in _pools(eng))
    assert [r["id"] for r in eng.shed_results] == [5]
    assert eng.stats["cancelled"] == 3
    done = {r["id"]: r["tokens"] for r in eng.results}
    assert set(done) == {3, 4}
    assert {slot_of[3], slot_of[4]} == {slot_of[0], slot_of[1]} == {0, 1}
    for rid in (3, 4):
        assert done[rid] == want[rid - 3].tolist(), rid
    eng.close()


def _run_to_end(eng, requests, one_at_a_time=False):
    """Tokens by request id: all submitted at once, or each alone."""
    for i, (p, n) in enumerate(requests):
        eng.submit(p, n, req_id=i)
        while one_at_a_time and eng.sched.has_work():
            eng.step(0.0)
    while eng.sched.has_work():
        eng.step(0.0)
    eng.close()
    return {r["id"]: r["tokens"] for r in eng.results}


@pytest.mark.parametrize("kind", ["window_full", "latent"])
def test_preemption_and_recompute_keep_the_tokens(toys, kind):
    """A pool too small for both residents' growth preempts the younger
    mid-decode and recomputes it, through a mixed window + full cache (its
    ring of window blocks is given back and given again) and a latent one:
    every request's tokens are those of the same pool serving it alone,
    where nothing is ever preempted, and every block of every pool is
    back."""
    cfg, params = toys(kind)
    rng = np.random.default_rng(6)
    new = 28
    requests = [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), new)
                for n in (23, 27, 21)]
    alone = ServeEngine(params, cfg, TOY_SERVE)
    want = _run_to_end(alone, requests, one_at_a_time=True)
    assert alone.sched.n_preempted == 0
    eng = ServeEngine(params, cfg, TOY_SERVE)
    assert type(eng.cache).__name__ == CACHE_KINDS[kind][1]
    assert (eng.wpool is not None) == (kind == "window_full")
    got = _run_to_end(eng, requests)
    assert eng.sched.n_preempted > 0
    assert got == want and all(len(t) == new for t in got.values())
    for pool in _pools(eng):
        assert pool.in_use == 0 and pool.free_blocks == pool.num_blocks


def test_sampled_tokens_survive_preemption(tiny, requests5):
    """At temperature 0.7 a request preempted mid-decode and recomputed
    samples the tokens it would have sampled undisturbed: the key of a
    token folds (request id, token index), not where or when it ran."""
    cfg, params = tiny
    runs = {}
    for num_blocks in (24, 8):
        eng = ServeEngine(params, cfg, scfg(num_blocks=num_blocks),
                          temperature=0.7, seed=11)
        runs[num_blocks] = _run_to_end(eng, requests5)
        assert (eng.sched.n_preempted > 0) == (num_blocks == 8)
        assert eng.pool.in_use == 0
    assert runs[8] == runs[24]
    greedy = _run_to_end(ServeEngine(params, cfg, scfg()), requests5)
    assert runs[24] != greedy  # the temperature took


# The boundary PR 46 drew: which cache a model is served from, and every fact
# of its format, is `serve/paged_cache.py`'s; the engine asks the cache it was
# given. Outside `new_cache`, which asks `init_serve_cache` once, neither
# module names a cache class or asks a ModelConfig what kind of model it is.
KIND_PREDICATES = {"mla", "eva", "gdn", "ssm", "kda", "recurrent",
                   "layer_types"}


@pytest.mark.parametrize("module", ["engine", "scheduler"])
def test_the_engine_and_the_scheduler_name_no_cache_kind(module):
    import ast

    serve = os.path.join(os.path.dirname(__file__), "..", "picotron_tpu",
                         "serve")

    def parse(name):
        with open(os.path.join(serve, f"{name}.py")) as f:
            return ast.parse(f.read())

    cache_classes = {n.name for n in ast.walk(parse("paged_cache"))
                     if isinstance(n, ast.ClassDef) and n.name.endswith("Cache")}
    assert {"PagedKVCache", "EvaPagedCache", "HybridPagedCache"} <= cache_classes
    tree = parse(module)
    tree.body = [n for n in tree.body  # the one place that picks a cache
                 if not (isinstance(n, ast.FunctionDef) and n.name == "new_cache")]
    leaks = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id in cache_classes:
            leaks.append((node.lineno, node.id))
        elif isinstance(node, ast.alias) and node.name in cache_classes:
            leaks.append((node.lineno, node.name))
        elif isinstance(node, ast.Attribute) and (
                node.attr in KIND_PREDICATES or node.attr in cache_classes):
            leaks.append((node.lineno, "." + node.attr))
    assert not leaks, f"serve/{module}.py reaches around the cache: {leaks}"


# ---------------------------------------------------------------------------
# the step's own account (serve/engine.py step_account, PR 37)
# ---------------------------------------------------------------------------

MS = 1e-3


def _leaves(t0, *spec):
    """Leaves from (name, gap before it in ms, its length in ms): the
    shape `Span(into=...)` appends."""
    out, at = [], t0
    for name, gap, ms in spec:
        out.append((name, at + gap * MS, ms * MS))
        at += (gap + ms) * MS
    return out, at - t0


PD, DD = "serve.prefill.dispatch", "serve.decode.dispatch"

ACCOUNT_CASES = {
    # name: (the enqueues in flight at the start, oldest first, leaves,
    #        tail ms, starved ms by leaf,
    #        ms with work enqueued: from the start of a dispatch
    #        to the end of the wait that clears it, added up by hand,
    #        the dispatches each wait cleared, in flight at the end)
    "decode_only": (
        (),
        [("serve.admit", 0.5, 1), ("serve.decode.build", 0, 2),
         ("serve.decode.dispatch", 0, 3), ("serve.decode.wait", 0, 20),
         ("serve.decode.emit", 0, 4)],
        1.5,
        {"unspanned": 2.0, "serve.admit": 1, "serve.decode.build": 2,
         "serve.decode.emit": 4}, 3 + 20, [1], ()),
    # the decode's build runs behind a prefill nobody waited for: fed
    "prefill_unwaited_then_decode": (
        (),
        [("serve.admit", 0, 1), ("serve.prefill.build", 0, 2),
         ("serve.prefill.dispatch", 0, 3), ("serve.decode.build", 1, 2),
         ("serve.decode.dispatch", 0, 3), ("serve.decode.wait", 0, 30),
         ("serve.decode.emit", 0.5, 4)],
        0,
        {"serve.admit": 1, "serve.prefill.build": 2, "unspanned": 0.5,
         "serve.decode.emit": 4}, 3 + 1 + 2 + 3 + 30, [2], ()),
    # the prefill was waited for: the device is idle under the build
    "prefill_waited_then_decode": (
        (),
        [("serve.admit", 0, 1), ("serve.prefill.build", 0, 2),
         ("serve.prefill.dispatch", 0, 3), ("serve.prefill.wait", 0, 10),
         ("serve.decode.build", 1, 2), ("serve.decode.dispatch", 0, 3),
         ("serve.decode.wait", 0, 20), ("serve.decode.emit", 0, 4)],
        0,
        {"serve.admit": 1, "serve.prefill.build": 2, "unspanned": 1,
         "serve.decode.build": 2, "serve.decode.emit": 4},
        3 + 10 + 3 + 20, [1, 1], ()),
    "ends_in_flight": (
        (),
        [("serve.admit", 0, 1), ("serve.prefill.build", 0, 2),
         ("serve.prefill.dispatch", 0, 3), ("serve.decode.build", 0, 1)],
        2,
        {"serve.admit": 1, "serve.prefill.build": 2}, 3 + 1 + 2, [], (PD,)),
    # ... and the third step after it: fed until its first wait, which
    # clears the three chunks before it and its own
    "starts_in_flight": (
        (PD,) * 3,
        [("serve.admit", 1, 1), ("serve.prefill.build", 0, 2),
         ("serve.prefill.dispatch", 0, 3), ("serve.prefill.wait", 0, 9),
         ("serve.decode.build", 0, 2)],
        1,
        {"serve.decode.build": 2, "unspanned": 1}, 1 + 1 + 2 + 3 + 9, [4],
        ()),
    # a prefill tick of two pieces, neither waited for
    "two_pieces": (
        (),
        [("serve.admit", 0, 1), ("serve.prefill.dispatch", 0, 2),
         ("serve.prefill.dispatch", 0.5, 2), ("serve.decode.build", 0, 1),
         ("serve.decode.dispatch", 0, 1), ("serve.decode.wait", 0, 8),
         ("serve.decode.emit", 0, 2)],
        0,
        {"serve.admit": 1, "serve.decode.emit": 2},
        2 + 0.5 + 2 + 1 + 1 + 8, [3], ()),
    # ---- one decode dispatch ahead (PR 48): dispatch n + 1, then wait n
    # the first dispatch after an empty system: enqueued, not waited for
    "ahead_first": (
        (),
        [("serve.admit", 0, 1), ("serve.decode.build", 0.5, 2),
         ("serve.decode.dispatch", 0, 3)],
        1,
        {"serve.admit": 1, "unspanned": 0.5, "serve.decode.build": 2},
        3 + 1, [], (DD,)),
    # the steady state: the wait clears the older of two dispatches, the
    # newer one stays in flight, and nothing after the wait is starved
    "ahead_steady": (
        (DD,),
        [("serve.admit", 0.5, 1), ("serve.decode.build", 0, 2),
         ("serve.decode.dispatch", 0, 3), ("serve.decode.wait", 0, 20),
         ("serve.decode.emit", 0.5, 4)],
        1.5, {}, 0.5 + 1 + 2 + 3 + 20 + 0.5 + 4 + 1.5, [1], (DD,)),
    # ... behind a prefill chunk nobody waited for: the wait clears the
    # chunk of the step before and the dispatch behind it, not this step's
    "ahead_behind_unwaited_prefill": (
        (PD, DD),
        [("serve.admit", 0, 1), ("serve.prefill.build", 0, 2),
         ("serve.prefill.dispatch", 0, 3), ("serve.decode.build", 0, 2),
         ("serve.decode.dispatch", 0, 3), ("serve.decode.wait", 0, 20),
         ("serve.decode.emit", 0, 4)],
        1, {}, 1 + 2 + 3 + 2 + 3 + 20 + 4 + 1, [2], (PD, DD)),
    # a prompt ends in the step: its wait clears the decode dispatch in
    # flight too, the device is idle under the prefill's emit and the
    # build, and the decode wait, which finds its tokens there, clears
    # nothing: the newer dispatch stays in flight under it and the emit
    "ahead_prefill_waited": (
        (DD,),
        [("serve.admit", 0, 1), ("serve.prefill.build", 0, 2),
         ("serve.prefill.dispatch", 0, 3), ("serve.prefill.wait", 0, 10),
         ("serve.prefill.emit", 0, 1), ("serve.decode.build", 0.5, 2),
         ("serve.decode.dispatch", 0, 3), ("serve.decode.wait", 0, 0.5),
         ("serve.decode.emit", 0, 4)],
        0,
        {"serve.prefill.emit": 1, "unspanned": 0.5, "serve.decode.build": 2},
        1 + 2 + 3 + 10 + 3 + 0.5 + 4, [2], (DD,)),
    # the last dispatch of a run: nothing is enqueued behind it, and the
    # emit after its wait is starved as it always was
    "ahead_last": (
        (DD,),
        [("serve.admit", 0, 1), ("serve.decode.build", 0, 2),
         ("serve.decode.wait", 0, 20), ("serve.decode.emit", 0, 4)],
        1,
        {"serve.decode.emit": 4, "unspanned": 1}, 1 + 2 + 20, [1], ()),
}


# ---- the step's period and the probes (PR 53). Times in ms from the
# period's start; a probe is (ms, whether the newest output was ready)
PERIOD_CASES = {
    # name: dict(in_flight, gap: ms from the end of the step before to this
    #   step's start, empties: [(from, to)], dry: the period before ended
    #   dry, spec: the leaves, tail, probes,
    #   want: ms of (empty, starved, caller_starved, dry, slack),
    #   dry_by, ends: (in flight, dry) at the end)
    # a decode dispatch is "in flight" and runs out in the middle of a
    # prefill build: dry from the first probe that says so (the build's
    # end) to the end of the next enqueue, the build itself the slack
    "dry_in_the_middle_of_a_build": dict(
        in_flight=(DD,), gap=1,
        spec=[("serve.admit", 0.5, 1), ("serve.prefill.build", 0, 8),
              ("serve.prefill.dispatch", 0, 1), ("serve.decode.build", 0, 2),
              ("serve.decode.dispatch", 0, 1), ("serve.decode.wait", 0, 5),
              ("serve.decode.emit", 0, 2)],
        tail=0.5,
        probes=[(1, False), (1.5, False), (2.5, False), (10.5, True),
                (11.5, False), (13.5, False), (14.5, False), (19.5, False),
                (21.5, False), (22, False)],
        want=(0, 0, 0, 1, 8), dry_by={"serve.prefill.dispatch": 1},
        ends=((PD, DD), False)),
    # the steady state, probed round every leaf: never dry
    "never_dry": dict(
        in_flight=(DD,), gap=2,
        spec=[("serve.admit", 0.5, 1), ("serve.decode.build", 0, 2),
              ("serve.decode.dispatch", 0, 3), ("serve.decode.wait", 0, 20),
              ("serve.decode.emit", 0.5, 4)],
        tail=1.5,
        probes=[(2, False), (2.5, False), (3.5, False), (5.5, False),
                (8.5, False), (28.5, False), (29, False), (33, False),
                (34.5, False)],
        want=(0, 0, 0, 0, 0), dry_by={}, ends=((DD,), False)),
    # the dispatch in flight ended while the caller held the loop: the
    # step's first probe says so, the seconds between the steps are the
    # slack, and the device is dry until the next dispatch is enqueued
    "dry_between_two_steps": dict(
        in_flight=(DD,), gap=6,
        spec=[("serve.admit", 0, 1), ("serve.decode.build", 0, 2),
              ("serve.decode.dispatch", 0, 1), ("serve.decode.wait", 0, 0.5),
              ("serve.decode.emit", 0, 2)],
        tail=0.5,
        probes=[(6, True), (10, False), (10.5, False), (12.5, False),
                (13, False)],
        want=(0, 0, 0, 4, 6),
        dry_by={"serve.admit": 1, "serve.decode.build": 2,
                "serve.decode.dispatch": 1},
        ends=((DD,), False)),
    # ... and the step after a step that ended dry, with nothing to
    # enqueue: dry from the period's first second to its last
    "dry_carried_over": dict(
        in_flight=(PD,), gap=3, dry=True,
        spec=[("serve.admit", 0, 1)], tail=1, probes=[],
        want=(0, 0, 0, 5, 0),
        dry_by={"between_steps": 3, "serve.admit": 1, "unspanned": 1},
        ends=((PD,), True)),
    # a long prompt's chunk left in flight, every probe running: fed
    "a_probe_that_never_reads_ready": dict(
        in_flight=(PD,), gap=1,
        spec=[("serve.admit", 0, 1), ("serve.prefill.build", 0, 2),
              ("serve.prefill.dispatch", 0, 3)],
        tail=1,
        probes=[(1, False), (2, False), (4, False), (7, False), (8, False)],
        want=(0, 0, 0, 0, 0), dry_by={}, ends=((PD, PD), False)),
    # the last request ended on an EOS with a dispatch of padding behind
    # it, which a probe saw finished: empty until the submit, whatever is
    # in flight, and dry only from there
    "empty_over_dry": dict(
        in_flight=(DD,), gap=10, empties=[(0, 7)], dry=True,
        spec=[("serve.admit", 0, 1), ("serve.decode.build", 0, 2),
              ("serve.decode.dispatch", 0, 1)],
        tail=1, probes=[(14, False), (15, False)],
        want=(7, 0, 0, 7, 0),
        dry_by={"between_steps": 3, "serve.admit": 1,
                "serve.decode.build": 2, "serve.decode.dispatch": 1},
        ends=((DD, DD), False)),
    # ... and the same once the engine has forgotten that dispatch: with
    # nothing in flight the seconds are the caller's and the host's,
    # starved, and a probe's word does not make them dry
    "empty_over_starved_over_dry": dict(
        in_flight=(), gap=10, empties=[(0, 7)], dry=True,
        spec=[("serve.admit", 0, 1), ("serve.decode.build", 0, 2),
              ("serve.decode.dispatch", 0, 1)],
        tail=1, probes=[(10, True), (14, False), (15, False)],
        want=(7, 3, 3, 0, 0), dry_by={}, ends=((DD,), False)),
    # the chunk had run before its wait began, which fetched everything:
    # dry up to the wait's end and no further (the emit and the build are
    # starved, and the next dispatch starts afresh)
    "a_wait_that_leaves_nothing_ends_the_dry": dict(
        in_flight=(DD,), gap=0,
        spec=[("serve.admit", 0, 1), ("serve.prefill.build", 0, 2),
              ("serve.prefill.dispatch", 0, 3), ("serve.prefill.wait", 0, 10),
              ("serve.prefill.emit", 0, 1), ("serve.decode.build", 0, 2),
              ("serve.decode.dispatch", 0, 3), ("serve.decode.wait", 0, 0.5)],
        tail=0,
        probes=[(0, False), (1, True), (6, True)],
        want=(0, 3, 0, 15, 1),
        dry_by={"serve.prefill.build": 2, "serve.prefill.dispatch": 3,
                "serve.prefill.wait": 10},
        ends=((DD,), False)),
}


def _account_case(case):
    """A case of either table as `step_account`'s arguments and what it
    should return, the period's parts in ms."""
    t0 = 1000.0
    if case in ACCOUNT_CASES:  # a step alone: no probe, no gap
        in_flight, spec, tail, starved_by, fed_ms, cleared, ends = \
            ACCOUNT_CASES[case]
        c = dict(in_flight=in_flight, spec=spec, tail=tail,
                 want=(0, sum(starved_by.values()), 0, 0, 0),
                 ends=(ends, False))
    else:
        c = PERIOD_CASES[case]
    gap = c.get("gap")
    since = None if gap is None else t0 - gap * MS
    begin = t0 if since is None else since
    leaves, spanned_to = _leaves(t0, *c["spec"])
    kw = dict(
        in_flight=c["in_flight"], since=since, dry=c.get("dry", False),
        probes=[(begin + ms * MS, ready) for ms, ready in c.get("probes", ())],
        empties=[(begin + a * MS, begin + b * MS)
                 for a, b in c.get("empties", ())])
    return c, leaves, t0, spanned_to + c["tail"] * MS, kw


@pytest.mark.parametrize("case", sorted({**ACCOUNT_CASES, **PERIOD_CASES}))
def test_step_account_on_hand_made_leaves(case):
    from picotron_tpu.serve.engine import step_account

    c, leaves, t0, wall, kw = _account_case(case)
    a = step_account(leaves, t0, wall, **kw)
    spec, tail = c["spec"], c["tail"]
    assert a["wall_s"] == wall and a["end"] == t0 + wall
    assert (a["in_flight"], a["dry"]) == c["ends"]
    assert isinstance(a["in_flight"], tuple)
    assert all(secs == a["leaves"][name] for name, secs, _ in a["waits"])
    # each leaf's seconds, and the wall less the leaves
    assert sum(a["leaves"].values()) == pytest.approx(
        sum(ms for _, _, ms in spec) * MS, abs=1e-9)
    assert a["unspanned_s"] == pytest.approx(
        (sum(gap for _, gap, _ in spec) + tail) * MS, abs=1e-9)
    assert set(a["leaves"]) == {name for name, _, _ in spec}
    # every second of the period has one name: the five parts, each
    # counted from its own pieces, add up to it with no clamp and no
    # remainder, and each is what was added up by hand
    period = wall + (c.get("gap") or 0) * MS
    assert a["period_s"] == pytest.approx(period, abs=1e-9)
    parts = [a[k] for k in ("empty_s", "starved_s", "caller_starved_s",
                            "dry_s", "fed_s")]
    assert all(x >= 0.0 for x in parts)
    assert sum(parts) == pytest.approx(period, abs=1e-9)
    got = [a[k] for k in ("empty_s", "starved_s", "caller_starved_s",
                          "dry_s", "dry_slack_s")]
    assert [round(x / MS, 6) for x in got] == [float(x) for x in c["want"]]
    # the slack is in-flight time the probes could not name: part of fed
    assert a["dry_slack_s"] <= a["fed_s"] + 1e-12
    assert a["starved_s"] == pytest.approx(sum(a["starved_by"].values()))
    assert a["dry_s"] == pytest.approx(sum(a["dry_by"].values()))
    if case in PERIOD_CASES:
        assert {k: round(v / MS, 6) for k, v in a["dry_by"].items()} == {
            k: float(v) for k, v in c["dry_by"].items()}
        return
    # ---- a step alone (PR 37, PR 48): the starved seconds by leaf, the
    # waits, and nothing of what came with the period
    _, _, _, want, fed_ms, cleared, _ = ACCOUNT_CASES[case]
    assert [n for _, _, n in a["waits"]] == cleared
    assert {k: round(v / MS, 6) for k, v in a["starved_by"].items()} == {
        k: float(v) for k, v in want.items()}
    # starved + in flight = wall, with no clamp to make it so
    assert a["starved_s"] + fed_ms * MS == pytest.approx(wall, abs=1e-9)
    assert a["fed_s"] == pytest.approx(fed_ms * MS, abs=1e-9)
    assert a["period_s"] == wall and a["dry_by"] == {}


class _Events:
    def __init__(self):
        self.events = []

    def emit(self, e):
        self.events.append(e)

    def close(self):
        pass


def test_serve_host_events_add_up_to_the_stats(tiny, requests5):
    """Every step with device work emits one `phase=serve_host` event whose
    `secs` are the step's starved seconds: they sum to `stats["starved_s"]`
    and to the ledger's `serve_host`, and the summary carries the share."""
    from picotron_tpu.telemetry import Telemetry

    cfg, params = tiny
    cap = _Events()
    tel = Telemetry(sinks=[cap])
    eng = ServeEngine(params, cfg, scfg(), telemetry=tel)
    res = eng.run(requests5)
    assert len(res) == len(requests5)
    host = [e for e in cap.events
            if e["kind"] == "phase" and e.get("phase") == "serve_host"]
    st = eng.stats
    assert len(host) == len(eng._walls) > 0  # one a step with device work
    assert all(e["category"] == "serve_host" and e["secs"] >= 0.0
               and set(e) <= {"ts", "kind", "phase", "category", "secs", "engine"}
               for e in host)
    tol = 1e-6 * len(host)  # an event's `secs` are rounded to the microsecond
    assert sum(e["secs"] for e in host) == pytest.approx(st["starved_s"], abs=tol)
    assert tel.ledger.seconds["serve_host"] == pytest.approx(st["starved_s"])
    assert 0.0 < st["starved_s"] <= st["step_wall_s"]
    # ... and one `phase=serve_dry` event beside it, whose seconds lie
    # inside those the decode and prefill phases book: no category, so
    # neither the ledger nor the report's sums take them for more wall
    dry = [e for e in cap.events
           if e["kind"] == "phase" and e.get("phase") == "serve_dry"]
    assert len(dry) == len(host)
    assert all(set(e) == {"ts", "kind", "phase", "secs", "engine"}
               for e in dry)
    assert sum(e["secs"] for e in dry) == pytest.approx(st["dry_s"], abs=tol)
    assert "serve_dry" not in tel.ledger.seconds
    assert st["period_s"] >= st["step_wall_s"]
    assert (st["empty_s"] + st["starved_s"] + st["caller_starved_s"]
            + st["dry_s"]) <= st["period_s"]
    assert st["probes"] > 0
    assert st["step_wall_max_s"] == max(eng._walls)
    assert sum(eng._walls) == pytest.approx(st["step_wall_s"])
    assert st["slow_steps"] == 0 and "steps" not in st
    s = eng.summary
    assert s["device_starved_share"] == pytest.approx(
        st["starved_s"] / st["step_wall_s"], abs=1e-4)
    assert 0.0 < s["step_wall_p50_s"] <= s["step_wall_max_s"]
    # the gauges nothing read are gone; the summary's own numbers stay
    assert not any(k.startswith("serve/") for k in
                   tel.registry.snapshot()["gauges"])
    assert 0 < s["slot_occupancy"] <= 1 and 0 < s["pool_peak_utilization"] <= 1
    eng.close()
    tel.close()


def test_slow_step_makes_one_event_and_one_log_line(tiny, monkeypatch, caplog):
    """A `device_get` that sleeps once: exactly one `serve_slow_step` with
    the whole account, and one WARNING naming the leaf that held it."""
    import time as _time

    from picotron_tpu.serve import engine as engine_mod
    from picotron_tpu.telemetry import Telemetry

    cfg, params = tiny
    cap = _Events()
    eng = ServeEngine(params, cfg, scfg(decode_interval=1, max_model_len=64),
                      telemetry=Telemetry(sinks=[cap]))
    eng.submit([3, 1, 4, 1, 5], 40)
    for _ in range(engine_mod.SLOW_STEP_AFTER + 4):
        eng.step(0.0)
    assert len(eng._recent["serve.decode.wait"]) >= engine_mod.SLOW_STEP_AFTER
    assert eng.stats["slow_steps"] == 0 and eng.sched.decode_ready()
    real, armed = engine_mod.jax.device_get, [True]

    def sleepy(x):
        if armed.pop() if armed else False:
            _time.sleep(engine_mod.SLOW_STEP_S + 0.15)
        return real(x)

    monkeypatch.setattr(engine_mod.jax, "device_get", sleepy)
    with caplog.at_level("WARNING", logger="picotron_tpu.serve"):
        for _ in range(6):
            eng.step(0.0)
    slow = [e for e in cap.events if e["kind"] == "serve_slow_step"]
    assert len(slow) == 1 and eng.stats["slow_steps"] == 1
    (e,) = slow
    assert e["held_by"] == "serve.decode.wait"
    # the dispatch behind the one awaited ran out under the sleeping wait:
    # the device kept running, and it was not the device that was late
    assert e["ready"] in (0, 1) and e["next_ready"] == 1
    assert set(e["dry_by_ms"]) <= set(e["leaves_ms"]) | {"unspanned",
                                                          "between_steps"}
    assert e["wall_s"] > e["held_s"] > e["limit_s"] >= engine_mod.SLOW_STEP_S
    assert max(e["leaves_ms"], key=e["leaves_ms"].get) == "serve.decode.wait"
    assert e["leaves_ms"]["serve.decode.wait"] >= engine_mod.SLOW_STEP_S * 1e3
    # a wait is in flight, not starved
    assert e["starved_s"] < 0.1 and "serve.decode.wait" not in e["starved_by_ms"]
    assert e["compile_s"] == 0.0 and e["active"] == 1 and e["queued"] == 0
    assert len(e["gc_before"]) == len(e["gc_after"]) == 3
    assert all(b <= a for b, a in zip(e["gc_before"], e["gc_after"]))
    records = [r for r in caplog.records if r.name == "picotron_tpu.serve"]
    assert len(records) == 1 and records[0].levelname == "WARNING"
    assert "longest leaf serve.decode.wait" in records[0].getMessage()
    assert (f"ready={e['ready']} as the wait began, next_ready=1 as it ended"
            in records[0].getMessage())
    assert eng.stats["step_wall_max_s"] >= e["wall_s"] - 1e-6
    eng.close()


def test_a_slow_retirement_says_what_it_gave_back(tiny, monkeypatch, caplog):
    """A step the host holds under the emit (a pool that takes its time
    over a retirement, as the free list's scan did): the event is held by
    `host`, names the emit, and carries the blocks the step gave back. The
    other request's next dispatch was enqueued before the emit, so none of
    its seconds are starved."""
    import time as _time

    from picotron_tpu.serve import engine as engine_mod
    from picotron_tpu.telemetry import Telemetry

    cfg, params = tiny
    cap = _Events()
    eng = ServeEngine(params, cfg, scfg(decode_interval=1, max_model_len=64),
                      telemetry=Telemetry(sinks=[cap]))
    eng.submit([3, 1, 4, 1, 5], 40)
    eng.submit([2, 7, 1, 8, 2, 8], engine_mod.SLOW_STEP_AFTER + 4)
    free = eng.pool.free

    def slow_free(blocks):
        _time.sleep(engine_mod.SLOW_STEP_S + 0.15)
        free(blocks)

    monkeypatch.setattr(eng.pool, "free", slow_free)
    with caplog.at_level("WARNING", logger="picotron_tpu.serve"):
        while eng.sched.n_retired == 0:
            eng.step(0.0)
    (e,) = [e for e in cap.events if e["kind"] == "serve_slow_step"]
    assert e["held_by"] == "host" and e["held_for"] == 1
    assert max(e["leaves_ms"], key=e["leaves_ms"].get) == "serve.decode.emit"
    assert e["leaves_ms"]["serve.decode.emit"] >= engine_mod.SLOW_STEP_S * 1e3
    assert "serve.decode.emit" not in e["starved_by_ms"]
    assert e["starved_s"] < 0.1 and e["active"] == 1
    # 6 prompt + 20 new tokens, the last never written, in blocks of 4
    assert e["blocks_freed"] == blocks_for(6 + 20 - 1, 4) == 7
    (record,) = [r for r in caplog.records if r.name == "picotron_tpu.serve"]
    assert "longest leaf serve.decode.emit" in record.getMessage()
    assert "blocks freed 7" in record.getMessage()
    eng.close()


@pytest.mark.parametrize("usual_s, prompt_len, fires", [
    (0.2, 3, False), (0.02, 3, True), (0.02, 11, False), (0.005, 11, True)])
def test_a_wait_is_slow_among_its_own_kind(tiny, monkeypatch, caplog,
                                           usual_s, prompt_len, fires):
    """A prefill wait of 0.3 s is a stall where a prefill dispatch takes 20
    ms, and the program's own time where it takes 0.2 s or where the wait
    clears three chunks the host ran ahead of, whatever the decode steps
    around it take: no event, no log line, nothing counted."""
    import time as _time

    from picotron_tpu.serve import engine as engine_mod
    from picotron_tpu.telemetry import Telemetry

    cfg, params = tiny
    cap = _Events()
    eng = ServeEngine(params, cfg, scfg(), telemetry=Telemetry(sinks=[cap]))
    eng._recent["serve.prefill.wait"].extend(
        [usual_s] * engine_mod.SLOW_STEP_AFTER)
    real, armed = engine_mod.jax.device_get, [True]

    def sleepy(x):
        if armed.pop() if armed else False:
            _time.sleep(engine_mod.SLOW_STEP_S + 0.05)
        return real(x)

    monkeypatch.setattr(engine_mod.jax, "device_get", sleepy)
    # the host waits where the prompt ends: in its first chunk, or its third
    eng.submit(list(range(1, prompt_len + 1)), 2)
    with caplog.at_level("WARNING", logger="picotron_tpu.serve"):
        while armed:
            assert eng.step(0.0)
    assert eng.stats["step_wall_max_s"] > engine_mod.SLOW_STEP_S
    assert eng._recent["serve.prefill.wait"][-1] > (
        engine_mod.SLOW_STEP_S / -(-prompt_len // 4))
    slow = [e for e in cap.events if e["kind"] == "serve_slow_step"]
    assert len(slow) == eng.stats["slow_steps"] == len(caplog.records) == fires
    if fires:
        assert slow[0]["held_by"] == "serve.prefill.wait"
        assert slow[0]["limit_s"] == engine_mod.SLOW_STEP_S
        assert slow[0]["held_for"] == -(-prompt_len // 4)
        # nothing is enqueued behind the chunk a prefill wait waits for
        assert slow[0]["ready"] in (0, 1) and slow[0]["next_ready"] == -1
        assert f"for {slow[0]['held_for']} dispatched" in caplog.messages[0]
    eng.close()


def test_a_slow_host_is_told_from_a_slow_wait(tiny, monkeypatch, caplog):
    """The wall less the waits is judged among the same of other steps: an
    admission that sleeps is `held_by` the host, under the leaf it ran in,
    and fed by the decode dispatch in flight, not starved."""
    import time as _time

    from picotron_tpu.serve import engine as engine_mod
    from picotron_tpu.telemetry import Telemetry

    cfg, params = tiny
    cap = _Events()
    eng = ServeEngine(params, cfg, scfg(decode_interval=1, max_model_len=64),
                      telemetry=Telemetry(sinks=[cap]))
    eng.submit([3, 1, 4, 1, 5], 40)
    for _ in range(engine_mod.SLOW_STEP_AFTER + 2):
        eng.step(0.0)
    real = eng.sched.admit

    def sleepy(now):
        _time.sleep(engine_mod.SLOW_STEP_S + 0.05)
        monkeypatch.setattr(eng.sched, "admit", real)
        return real(now)

    monkeypatch.setattr(eng.sched, "admit", sleepy)
    with caplog.at_level("WARNING", logger="picotron_tpu.serve"):
        for _ in range(3):
            eng.step(0.0)
    (e,) = [e for e in cap.events if e["kind"] == "serve_slow_step"]
    assert e["held_by"] == "host" and e["held_s"] > e["limit_s"]
    assert e["ready"] is None and e["next_ready"] is None  # no wait held it
    assert e["leaves_ms"]["serve.admit"] >= engine_mod.SLOW_STEP_S * 1e3
    # ... by the account; the probes say the dispatch ran out before or
    # under the sleeping admit (its seconds dry, or the slack): dry at the
    # latest from the admit's end to the end of the next dispatch
    assert e["dry_by_ms"]
    assert "serve.admit" not in e["starved_by_ms"] and e["starved_s"] < 0.1
    (record,) = caplog.records
    assert "slow step: host" in record.getMessage()
    assert "longest leaf serve.admit" in record.getMessage()
    eng.close()


def test_an_idle_poll_is_no_step_and_ends_what_was_in_flight(tiny):
    """A prefill dispatch nobody waited for, then its request cancelled: the
    polls that find no work are not accounted, and the next request's admit
    and build are starved, not fed by a dispatch long since done."""
    from picotron_tpu.telemetry import Telemetry

    cfg, params = tiny
    cap = _Events()
    eng = ServeEngine(params, cfg, scfg(), telemetry=Telemetry(sinks=[cap]))
    rid = eng.submit(list(range(1, 12)), 2)  # three chunks
    assert eng.step(0.0) and eng._in_flight  # ends on the un-waited dispatch
    assert eng.cancel(rid)

    def host_events():
        return [e for e in cap.events if e.get("phase") == "serve_host"]

    n, walls = len(host_events()), eng.stats["step_wall_s"]
    for _ in range(3):
        assert not eng.step(0.0)
    assert not eng._in_flight
    assert len(host_events()) == n and eng.stats["step_wall_s"] == walls
    eng.submit([3, 1, 4], 2)
    assert eng.step(0.0)
    (last,) = host_events()[n:]
    assert last["secs"] > 0.0  # its admit and build were starved
    assert eng.stats["starved_s"] <= eng.stats["step_wall_s"]
    eng.close()


# ---------------------------------------------------------------------------
# the step's period (PR 53): empty, starved, caller-starved, dry, fed
# ---------------------------------------------------------------------------


def _starved_as_pr48(leaves, t0, wall, in_flight):
    """`starved_s` as the function of PR 48 added it up (a copy of its
    loop), for the scenarios in which nothing else may have changed."""
    from picotron_tpu.serve.engine import (
        _DECODE_DISPATCH as DDN, _DECODE_WAIT as DWN, _ENQUEUES, _WAITS,
    )

    fed, unfetched, at, starved = list(in_flight), 0, t0, 0.0
    for name, start, secs in leaves:
        if not fed and start > at:
            starved += start - at
        if name in _ENQUEUES:
            fed.append(name)
        n = 0
        if name == DWN:
            if unfetched:
                unfetched -= 1
            elif DDN in fed:
                n = fed.index(DDN) + 1
            else:
                n = len(fed)
        elif name in _WAITS:
            n = len(fed)
            unfetched += fed.count(DDN)
        if not fed:
            starved += secs
        elif n:
            del fed[:n]
        at = start + secs
    if not fed and t0 + wall > at:
        starved += t0 + wall - at
    return starved


def _recording_accounts(monkeypatch):
    """Every `step_account` call of the engines built after this, as
    (its arguments, its result)."""
    from picotron_tpu.serve import engine as engine_mod

    calls, real = [], engine_mod.step_account

    def recording(leaves, t0, wall, in_flight, probes, since, empties, dry):
        a = real(leaves, t0, wall, in_flight, probes, since, empties, dry)
        calls.append((dict(leaves=list(leaves), t0=t0, wall=wall,
                           in_flight=in_flight, probes=list(probes),
                           since=since, empties=list(empties), dry=dry), a))
        return a

    monkeypatch.setattr(engine_mod, "step_account", recording)
    return calls


def test_an_idle_second_before_a_submit_is_empty_not_starved(
        tiny, requests5, monkeypatch):
    """Two requests, an idle second, three more: the second is `empty_s`
    and none of it starved; every step's five parts add up to its period
    to the microsecond, the periods tile the run from the first submit to
    the last step's end, the span carries the same counts, and the starved
    seconds are what PR 48's function made of the same leaves."""
    import time as _time

    eng, tel = traced_engine(tiny)
    calls = _recording_accounts(monkeypatch)
    t_first = eng._now()
    for p, n in requests5[:2]:
        eng.submit(p, n)
    while eng.sched.has_work():
        eng.step(0.0)
    before = dict(eng.stats)
    assert before["empty_s"] == 0.0 and eng._empty_from is not None
    _time.sleep(1.0)
    for p, n in requests5[2:]:
        eng.submit(p, n)
    assert eng._empty_from is None and len(eng._empties) == 1
    while eng.sched.has_work():
        eng.step(0.0)
    st = eng.stats
    assert 1.0 <= st["empty_s"] < 2.0
    # none of the idle second is starved, by either name
    assert st["starved_s"] + st["caller_starved_s"] < 0.9
    (woke,) = [a for kw, a in calls if a["empty_s"] > 0.0]
    assert woke["empty_s"] == st["empty_s"]
    assert woke["period_s"] > woke["wall_s"] + 1.0
    parts = ("empty_s", "starved_s", "caller_starved_s", "dry_s", "fed_s")
    for kw, a in calls:
        assert sum(a[k] for k in parts) == pytest.approx(a["period_s"],
                                                         abs=1e-6)
        assert all(a[k] >= 0.0 for k in parts)
        assert a["dry_slack_s"] <= a["fed_s"] + 1e-9
        assert a["starved_s"] == pytest.approx(_starved_as_pr48(
            kw["leaves"], kw["t0"], kw["wall"], kw["in_flight"]), abs=1e-9)
    # the periods tile the time since the first submit
    assert calls[0][0]["since"] == pytest.approx(t_first, abs=0.5)
    for (_, a), (kw, _) in zip(calls, calls[1:]):
        assert kw["since"] == a["end"]
    assert st["period_s"] == pytest.approx(
        calls[-1][1]["end"] - calls[0][0]["since"], abs=1e-6)
    for k in ("period_s", "empty_s", "caller_starved_s", "dry_s",
              "dry_slack_s", "starved_s"):
        assert st[k] == pytest.approx(sum(a[k] for _, a in calls))
    assert st["probes"] == sum(len(kw["probes"]) for kw, _ in calls) > 0
    # the `serve.step` spans carry each account, in whole microseconds
    steps = [e["args"] for e in tel.tracer.to_json()["traceEvents"]
             if e["ph"] == "X" and e["name"] == "serve.step" and e.get("args")]
    assert len(steps) == len(calls)
    for c, (_, a) in zip(steps, calls):
        assert set(c) == {"wall_us", "starved_us", "period_us", "empty_us",
                          "caller_starved_us", "dry_us", "dry_slack_us"}
        for k in ("period", "empty", "caller_starved", "dry", "dry_slack",
                  "starved", "wall"):
            assert c[f"{k}_us"] == int(a[f"{k}_s"] * 1e6)
        assert (c["empty_us"] + c["starved_us"] + c["caller_starved_us"]
                + c["dry_us"]) <= c["period_us"]
    s = eng._summary_dict(1.0)
    assert s["system_empty_share"] == round(st["empty_s"] / st["period_s"], 4)
    assert s["device_dry_share"] == round(st["dry_s"] / st["period_s"], 4)
    assert 0.05 < s["system_empty_share"] < 1.0
    assert s["empty_s"] == round(st["empty_s"], 6)
    eng.close()


def test_prefill_dispatches_are_numbered_and_waits_say_what_they_found(tiny):
    """`serve.prefill.dispatch` and `.wait` carry `seq` as the decode pair
    does: a chunk nobody waited for stays in flight, and the wait at the
    prompt's end carries its own chunk's number. Every wait says whether
    its dispatch had run when it began (`ready`) and whether what was
    enqueued behind it had when it ended (`next_ready`; -1: nothing)."""
    eng, tel = traced_engine(tiny)
    eng.submit(list(range(1, 12)), 5)  # three chunks, two decode dispatches
    while eng.sched.has_work():
        eng.step(0.0)
    eng.close()
    spans = sorted((e for e in tel.tracer.to_json()["traceEvents"]
                    if e["ph"] == "X"), key=lambda e: e["ts"])
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e.get("args", {}))
    assert [a["seq"] for a in by["serve.prefill.dispatch"]] == [0, 1, 2]
    (pw,) = by["serve.prefill.wait"]
    assert pw["seq"] == 2 and pw["ready"] in (0, 1) and pw["next_ready"] == -1
    waits = by["serve.decode.wait"]
    assert [a["seq"] for a in waits] == [0, 1]
    assert all(a["ready"] in (0, 1) for a in waits)
    # dispatch 1 was enqueued behind dispatch 0; nothing behind dispatch 1
    assert waits[0]["next_ready"] in (0, 1) and waits[1]["next_ready"] == -1
    assert eng._newest is None  # all fetched: nothing left to probe


# ---------------------------------------------------------------------------
# one decode dispatch ahead (PR 48): dispatch n + 1 is enqueued before the
# host waits for dispatch n's tokens
# ---------------------------------------------------------------------------


def decode_leaves(tel):
    """The decode dispatch, wait and emit spans in the order they began, as
    (the leaf's last name, its counts)."""
    names = {f"serve.decode.{n}": n for n in ("dispatch", "wait", "emit")}
    spans = sorted((e for e in tel.tracer.to_json()["traceEvents"]
                    if e["ph"] == "X" and e["name"] in names),
                   key=lambda e: e["ts"])
    return [(names[e["name"]], e["args"]) for e in spans]


@pytest.mark.parametrize("num_blocks", [24, 8], ids=["roomy", "preempting"])
def test_every_decode_dispatch_but_a_restart_is_enqueued_ahead(
        tiny, requests5, offline_refs, num_blocks):
    """The leaf spans of a multi-request trace: dispatches are numbered in
    order and waited for in order, each once; every dispatch is enqueued
    before the wait for the one before it began, but the first after the
    engine held none in flight (`ahead=0`: a restart); the stats count the
    same, and the tokens are the offline sampler's."""
    eng, tel = traced_engine(tiny, num_blocks=num_blocks)
    res = eng.run(requests5)
    eng.close()
    for r, ref in zip(res, offline_refs):
        assert r["tokens"] == ref
    leaves = decode_leaves(tel)
    n = eng.stats["decode_steps"]
    for kind in ("dispatch", "wait"):
        assert [a["seq"] for k, a in leaves if k == kind] == list(range(n))
    in_flight, restarts = [], 0
    for kind, a in leaves:
        if kind == "wait":
            assert in_flight.pop(0) == a["seq"]  # the oldest, never the newest
        elif kind == "dispatch":
            assert a["ahead"] == bool(in_flight) and a["dispatched"] == 1
            restarts += not in_flight
            in_flight.append(a["seq"])
            assert len(in_flight) <= 2
    assert not in_flight and 1 <= restarts < n
    assert eng.stats["decode_ahead"] == n - restarts
    assert eng.summary["decode_ahead_share"] == round((n - restarts) / n, 4)
    # no row was dropped unless a request left while it was in flight
    dropped = sum(a["dropped"] for kind, a in leaves if kind == "emit")
    assert (dropped > 0) == (eng.sched.n_preempted > 0) == (num_blocks == 8)
    assert eng.pool.in_use == 0


@pytest.mark.parametrize("leaves_by",
                         ["cancel", "cancel_alone", "preempt", "eos"])
def test_a_request_that_leaves_while_its_next_dispatch_is_in_flight(
        tiny, requests5, offline_refs, leaves_by):
    """A request cancelled, preempted or ended by an EOS while a dispatch
    built for it is in flight: no token of that dispatch reaches its
    `generated`, the emit counts the row as dropped, no block leaks, and
    the neighbours' tokens are what they are alone. A dispatch whose only
    row was cancelled (`cancel_alone`) is forgotten: nobody waits for it,
    it is no decode step, and the next dispatch is ahead of nothing."""
    cfg, params = tiny
    eos, refs = None, list(offline_refs)
    if leaves_by == "eos":
        # request 2's third token ends it inside its first dispatch, before
        # the host has seen it: the second is built with it still in
        eos = refs[2][2]
        assert eos not in refs[0] and eos not in refs[2][:2]
        refs = [r[:r.index(eos) + 1] if eos in r else r for r in refs]
    eng, tel = traced_engine(
        tiny, **({"num_blocks": 8} if leaves_by == "preempt" else {}))
    eng.eos_token_id = eos  # read at each dispatch
    for i, (p, n) in enumerate(requests5):
        eng.submit(p, n, req_id=i)
    victim = None  # (its state, its tokens as it left)
    seen = {}  # request id -> (state, tokens) while a row of its is in flight
    while eng.sched.has_work():
        eng.step(0.0)
        rows = eng._rows_in_flight(eng._flying)
        if (victim is None and len(rows)
                == {"cancel": 2, "cancel_alone": 1}.get(leaves_by)):
            st = next(iter(rows.values()))
            victim = (st, list(st.generated))
            assert eng.cancel(st.req.id)
        seen.update({st.req.id: (st, list(st.generated))
                     for st in rows.values()})
    eng.close()
    leaves = decode_leaves(tel)
    dropped = sum(a["dropped"] for kind, a in leaves if kind == "emit")
    assert (dropped >= 1) == (leaves_by != "cancel_alone")
    assert eng.pool.in_use == 0 and eng._flying is None
    done = {r["id"]: r["tokens"] for r in eng.results}
    if leaves_by.startswith("cancel"):
        st, toks = victim
        assert st.generated == toks and st.req.id not in done
        assert eng.stats["cancelled"] == 1
        seqs = {kind: [a["seq"] for k, a in leaves if k == kind]
                for kind in ("dispatch", "wait")}
        if leaves_by == "cancel_alone":
            # dispatch 0 was the victim's alone: no wait, and 1 restarts
            assert seqs["wait"] == seqs["dispatch"][1:]
            assert [a["ahead"] for k, a in leaves if k == "dispatch"][:2] == [0, 0]
            assert eng.stats["decode_steps"] == len(seqs["wait"])
        else:
            assert seqs["wait"] == seqs["dispatch"]
    elif leaves_by == "preempt":
        assert eng.sched.n_preempted > 0
    else:
        assert done[2] == refs[2] and done[2][-1] == eos
        assert len(done[2]) < requests5[2][1]
    for rid, toks in done.items():
        assert toks == refs[rid], rid
    assert len(done) == len(requests5) - leaves_by.startswith("cancel")
    # a row in flight never grew a request's tokens before its own emit:
    # what a state held while a dispatch was in flight for it is a prefix of
    # what it ended with
    for rid, (st, toks) in seen.items():
        assert st.generated[:len(toks)] == toks


def test_a_budgets_end_hands_its_slot_on_before_the_next_dispatch(tiny):
    """A saturated engine loses no slot-step to running ahead: a request
    whose budget ends in the dispatch in flight leaves its slot before the
    step admits, so its successor (a one-chunk prompt) rides the very next
    dispatch. While requests queue, every dispatch carries every slot, all
    but the first are enqueued ahead, and the tokens are the offline
    sampler's."""
    cfg, params = tiny
    eng, tel = traced_engine(tiny)
    rng = np.random.default_rng(7)
    reqs = [(list(map(int, rng.integers(0, cfg.vocab_size, size=3))), n)
            for n in (4, 7, 10, 5, 6, 8, 4, 9, 7)]
    for i, (p, n) in enumerate(reqs):
        eng.submit(p, n, req_id=i)
    full = 0
    while eng.sched.has_work():
        eng.step(0.0)
        if eng.sched.queue:
            assert len(eng._flying["rows"]) == eng.num_slots
            full += 1
    eng.close()
    assert full >= 4
    disp = [a for kind, a in decode_leaves(tel) if kind == "dispatch"]
    assert [a["ahead"] for a in disp] == [0] + [1] * (len(disp) - 1)
    assert eng.stats["decode_ahead"] == eng.stats["decode_steps"] - 1
    # the released rows' tokens were kept, none was dropped
    emits = [a for kind, a in decode_leaves(tel) if kind == "emit"]
    assert sum(a["dropped"] for a in emits) == 0
    assert sum(a["retired"] for a in emits) == len(reqs)
    assert eng.pool.in_use == 0
    for r, (p, n) in zip(sorted(eng.results, key=lambda r: r["id"]), reqs):
        assert r["tokens"] == np.asarray(generate(
            params, cfg, jnp.asarray([p], jnp.int32), n))[0, len(p):].tolist()


def _mellum2_tiny():
    cfg = ModelConfig(dtype="float32", **resolve_preset("debug-tiny-mellum2"))
    return cfg, init_params(cfg, jax.random.key(5))


@pytest.mark.parametrize("kind", ["paged", "windowed", "latent"])
def test_dispatch_span_counts_are_its_own_dispatchs(tiny, kind):
    """The counts on a `serve.decode.dispatch` span (`kv_blocks` and what
    the cache's kind adds) are `cache.decode_counts` at the positions the
    PROGRAM received, one dispatch ahead of the host's own, for a paged, a
    windowed and a latent cache: the roofline readers divide by them."""
    from picotron_tpu.telemetry import Telemetry
    from picotron_tpu.telemetry.flightdeck import SpanTracer

    cfg, params = {"paged": lambda: tiny, "windowed": _mellum2_tiny,
                   "latent": _pangu}[kind]()
    tel = Telemetry(sinks=[])
    tel.tracer = SpanTracer()
    eng = ServeEngine(params, cfg, ServeConfig(
        decode_slots=3, block_size=4, prefill_chunk=8, max_model_len=48,
        decode_interval=3), telemetry=tel)
    rng = np.random.default_rng(4)
    budget = {i: n for i, n in enumerate((11, 7, 14, 5))}
    fed, jit = [], eng._decode_jit

    def recording(params, pools, tables, toks, last, positions, rids, tidx,
                  *a, **k):
        fed.append((positions, rids, tidx))
        return jit(params, pools, tables, toks, last, positions, rids, tidx,
                   *a, **k)

    eng._decode_jit = recording
    for i, n in budget.items():
        eng.submit(list(map(int, rng.integers(0, cfg.vocab_size, size=9 + 6 * i))),
                   n, req_id=i)
    while eng.sched.has_work():
        eng.step(0.0)
    eng.close()
    spans = [a for k, a in decode_leaves(tel) if k == "dispatch"]
    assert len(spans) == len(fed) > 4 and any(a["ahead"] for a in spans)
    for a, (positions, rids, tidx) in zip(spans, jax.device_get(fed)):
        live = positions >= 0
        assert a["active"] == live.sum()
        want = eng.cache.decode_counts(
            [(int(p), min(3, budget[int(r)] - int(t)))
             for p, r, t in zip(positions[live], rids[live], tidx[live])], cfg)
        assert want and {k: a[k] for k in want} == want
