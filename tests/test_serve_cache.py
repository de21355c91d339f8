"""The serving cache behind its one constructor (serve/paged_cache.py
`init_serve_cache`): for each of the five kinds of cache, what the engine
asks of it and nothing of how it answers. The numbers are what `ServeEngine`
built and counted for the same model and settings while it still chose the
cache itself, by flag (PR 45's tree), so a layout moved here is the layout
the serving cells ran on."""

import jax
import numpy as np
import pytest

from picotron_tpu.config import ModelConfig, ServeConfig, resolve_preset
from picotron_tpu.serve.paged_cache import init_serve_cache
from picotron_tpu.serve.scheduler import Request, RequestState, Scheduler

SLOTS, BLOCKS, MAX_LEN = 3, 40, 96
ROW = [5, 2, 7] + [40] * 21  # 24 entries: 96 positions in blocks of 4

# kind: (preset, prefill chunk, what the engine built and counted at PR 45)
KINDS = {
    "plain": ("debug-tiny", 4, dict(
        cls="PagedKVCache", pools=[(2, 4, 40, 4, 16)] * 2, specs=((24, 40),),
        rows=[ROW], prefill={}, decode=dict(kv_blocks=29), sched={})),
    "sliding+full": ("debug-tiny-mellum2", 4, dict(
        cls="MixedPagedKVCache",
        pools=[(2, 2, 40, 4, 32), (2, 6, 12, 4, 32)] * 2,  # K of both, V of both
        specs=((24, 40), (4, 12)),
        rows=[ROW, [3, 1, 12, 12]],  # a ring's tail stays unmapped
        prefill={},
        decode=dict(kv_blocks=29, kv_blocks_full=58, kv_blocks_window=48,
                    kv_blocks_banded=106, kv_blocks_unwindowed=232),
        sched=dict(ring_blocks=4, window_blocks=12))),
    "latent": ("debug-tiny-pangu-moe", 4, dict(
        cls="LatentPagedCache", pools=[(4, 40, 4, 128)], specs=((24, 40),),
        rows=[ROW], prefill=dict(latent_keys=1152),
        decode=dict(kv_blocks=29, latent_blocks=116), sched={})),
    "eva": ("debug-tiny-evabyte", 8, dict(
        cls="EvaPagedCache", pools=[(2, 2, 40, 4, 16)] * 2, specs=((14, 40),),
        # 6 summary entries first (96 / 4 rows in blocks of 4), then a window
        rows=[[9, 4, 40, 40, 40, 40, 5, 2, 7, 40, 40, 40, 40, 40]],
        prefill=dict(eva_summaries_written=10, eva_windows_closed=1),
        decode=dict(kv_blocks=11, eva_summaries_written=4,
                    eva_windows_closed=0, eva_summary_blocks=12,
                    eva_window_blocks=10, eva_blocks_read=22,
                    eva_blocks_full_attention=58,
                    # 7 ends a chunk at the first step, 71 at the second
                    eva_steps=2, eva_steps_summarising=2),
        sched=dict(summary=(32, 4)))),
    # (new with the cache itself, PR 51: no engine ever chose it by flag)
    "state+full": ("debug-tiny-qwen3-next", 8, dict(
        cls="HybridPagedCache",
        # K and V of the 2 full layers, then a state and a tail a slot and mixer
        pools=[(2, 2, 40, 4, 16)] * 2 + [(6, 3, 4, 8, 8), (6, 3, 192)],
        specs=((24, 40), (1, 3)),
        rows=[ROW, [0]],  # the state row is the slot's own index
        # 6 mixers x 3 rows, 1,792 B a row both ways; one row starts at 0
        # (no pad row named: the rung is the 3 rows, and the chunk's kernel skips none)
        prefill=dict(state_rows=18, state_bytes=2 * 18 * 1792, state_resets=6,
                     chunk_rows_batch=18, chunk_rows_idle=0),
        # ... and of the decode batch's 6 x 3 (row, mixer) pairs none is idle
        decode=dict(kv_blocks=29, kv_blocks_banded=58,
                    state_rows=18, state_bytes=2 * 18 * 1792, state_resets=0,
                    state_rows_batch=18, state_rows_idle=0),
        sched={})),
    # (PR 57: the same state half beside a LATENT pool of the full layers alone)
    "state+latent": ("debug-tiny-kimi-linear", 8, dict(
        cls="HybridLatentPagedCache",
        # [c | k_r] of the 2 full layers, then a state and a tail a slot and mixer
        pools=[(2, 40, 4, 128), (6, 3, 4, 8, 8), (6, 3, 288)],
        specs=((24, 40), (1, 3)),
        rows=[ROW, [0]],
        # the latent pool's counts over its 2 rows (`attn_sublayers`), the state's
        # over the 6 mixers: 2,176 B a row both ways
        prefill=dict(attn_sublayers=2, latent_keys=576, state_rows=18,
                     state_bytes=2 * 18 * 2176, state_resets=6, chunk_rows_batch=18,
                     chunk_rows_idle=0),
        decode=dict(attn_sublayers=2, kv_blocks=29, latent_blocks=58, state_rows=18,
                    state_bytes=2 * 18 * 2176, state_resets=0, state_rows_batch=18,
                    state_rows_idle=0),
        sched={})),
}
# (positions already cached, tokens of this chunk) a row of a prefill
# dispatch; (position written first, tokens to emit) a slot of a decode one
PREFILL_SPANS = [(0, 8), (24, 8), (40, 5)]
DECODE_SPANS = [(70, 2), (7, 1), (33, 2)]


def made(kind):
    preset, chunk, want = KINDS[kind]
    cfg = ModelConfig(dtype="float32", **{**resolve_preset(preset),
                                          "max_position_embeddings": 128})
    scfg = ServeConfig(decode_slots=SLOTS, block_size=4, prefill_chunk=chunk,
                       max_model_len=MAX_LEN, decode_interval=2,
                       num_blocks=BLOCKS)
    cache = jax.eval_shape(
        lambda: init_serve_cache(cfg, scfg, SLOTS, BLOCKS, MAX_LEN))
    return cfg, cache, want


@pytest.mark.parametrize("kind", KINDS)
def test_the_constructor_gives_the_class_pools_and_tables(kind):
    cfg, cache, want = made(kind)
    assert type(cache).__name__ == want["cls"]
    assert [p.shape for p in cache.pools] == want["pools"]
    assert cache.table_specs == want["specs"]
    # a program rebuilds the cache from its pools and the dispatch's tables
    tables = cache[len(cache.pools):]
    assert [t.shape for t in tables] == [(SLOTS, w) for w, _ in want["specs"]]
    assert type(cache).of(cache.pools, tables) == cache
    # what the scheduler is told of the format, and a second pool where it has one
    args = cache.scheduler_args(cfg)
    pool = args.pop("window_pool", None)
    assert dict(args, **({"window_blocks": pool.num_blocks} if pool else {})) \
        == want["sched"]
    Scheduler(SLOTS, None, 4, 24, window_pool=pool, **args)


@pytest.mark.parametrize("kind", KINDS)
def test_a_slots_table_rows_from_its_block_lists(kind):
    cfg, cache, want = made(kind)
    st = RequestState(Request(0, tuple(range(1, 12)), 5))
    st.blocks = [5, 2, 7]
    st.wblocks = [3, 1] if len(want["specs"]) > 1 else []
    st.sblocks = [9, 4] if kind == "eva" else []
    rows = cache.slot_rows(st, cfg, 0)
    assert [r.tolist() for r in rows] == want["rows"]
    assert all(r.dtype == np.int32 for r in rows)
    # a free slot: every entry of every table unmapped
    assert [r.tolist() for r in cache.slot_rows(None, cfg, 0)] == [
        [unmapped] * width for width, unmapped in want["specs"]]


@pytest.mark.parametrize("kind", KINDS)
def test_the_counts_a_dispatchs_span_carries(kind):
    cfg, cache, want = made(kind)
    assert cache.prefill_counts(PREFILL_SPANS, cfg) == want["prefill"]
    assert cache.decode_counts(DECODE_SPANS, cfg) == want["decode"]
    assert cache.decode_counts([], cfg)["kv_blocks"] == 0


@pytest.mark.parametrize("live", range(SLOTS + 1))
def test_the_state_rows_a_decode_batch_holds_and_those_it_leaves(live):
    """`state_rows_batch`: mixers x the decode program's rows, a row a slot,
    whatever is live; `state_rows_idle`: that less `state_rows`, the pairs
    whose state a step leaves where it lies (`gdn_rows_skipped.serve` reads
    the two)."""
    cfg, cache, _ = made("state+full")
    got = cache.decode_counts(DECODE_SPANS[:live], cfg)
    mixers = cache.state.shape[0]
    assert got["state_rows_batch"] == mixers * SLOTS == 18
    assert got["state_rows"] == mixers * live
    assert got["state_rows_idle"] == got["state_rows_batch"] - got["state_rows"]


@pytest.mark.parametrize("preset", ["debug-tiny-qwen3-next", "debug-tiny-kimi-linear"])
def test_a_tails_moves_are_the_indexed_moves_and_keep_a_fault_to_its_row(preset):
    """`tail_of` / `put_tail` move a mixer's plane through one product with
    the rows' one-hot map: to the bit what a gather and a scatter by index
    move, and a slot whose tail is not finite leaves every other row's as it
    was (a product sums over every slot, and 0 x nan is nan). (A Mamba
    mixer's tail, in its kernel's rows of lanes, goes by index as it did.)"""
    cfg = ModelConfig(dtype="float32", **{**resolve_preset(preset),
                                          "max_position_embeddings": 128})
    scfg = ServeConfig(decode_slots=4, block_size=4, prefill_chunk=8,
                       max_model_len=MAX_LEN, decode_interval=2, num_blocks=BLOCKS)
    cache = init_serve_cache(cfg, scfg, 4, BLOCKS, MAX_LEN)
    pool = jax.random.normal(jax.random.PRNGKey(3), cache.tail.shape) * 1e3
    # rows: slot 2 mid-sequence, slot 0 at its start, a pad row, an unmapped row
    rows = cache._replace(tail=pool, stables=np.asarray([[2], [0], [1], [4]], np.int32))
    pos = np.asarray([[8, 9], [0, 1], [-1, -1], [5, 6]])
    got = rows.tail_of(1, pos)
    # (an unmapped row's is discarded: by index it reads the last slot's)
    np.testing.assert_array_equal(got[:3], rows._carried(pool, 1, pos)[:3])
    assert not np.asarray(got[1]).any() and not np.asarray(got[3]).any()
    new = jax.random.normal(jax.random.PRNGKey(4), got.shape) * 1e3
    np.testing.assert_array_equal(rows.put_tail(1, new, pos).tail,
                                  rows._carry_on(pool, 1, new, pos))
    # slot 3, which no row holds, and pad row 2's outcome are not finite
    bad = rows._replace(tail=pool.at[1, 3].set(np.nan))
    np.testing.assert_array_equal(bad.tail_of(1, pos), got)
    wrote = bad.put_tail(1, new.at[2].set(np.inf), pos).tail
    np.testing.assert_array_equal(wrote[:, :3], rows.put_tail(1, new, pos).tail[:, :3])
    assert np.isnan(np.asarray(wrote[1, 3])).all()
