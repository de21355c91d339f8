"""EvaByte's mechanisms at the tiny preset (`debug-tiny-evabyte`: window 32,
chunk 4, 2 heads of 16, 3 prediction heads over 320 rows, 1 + w norms, a
float32 residual stream) on the CPU: EVA attention (the open window's keys one
by one, a learned summary a chunk of every closed window, one softmax) through
`forward()`, `generate()` and `ServeEngine`, whose paged cache holds window
blocks and summary blocks in one table a slot. The program is held to
`benchmark/reference_evabyte.py` (plain float32 jax.numpy, no cache, a dense
mask over [k | k~]), which imports nothing from it. The compiled decode kernel
is held by tests/test_chip_compile.py and tests/test_paged_attention.py."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, ServeConfig, TrainingConfig,
    check_eva_serving, model_config_from_hf_json, num_params, resolve_preset,
)
from picotron_tpu.generate import generate
from picotron_tpu.models.llama import forward, init_params, loss_fn, param_count
from picotron_tpu.ops.eva import chunk_summaries, eva_summarise
from picotron_tpu.serve import ServeEngine
from picotron_tpu.serve.paged_cache import PagedKVCache, eva_table_width, init_eva_cache
from picotron_tpu.serve.scheduler import Scheduler

# loaded by its path: `benchmark/` is not put on sys.path, where its own
# `tests` package would shadow this one
_spec = importlib.util.spec_from_file_location(
    "reference_evabyte", os.path.join(os.path.dirname(__file__), "..", "benchmark",
                                      "reference_evabyte.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

W, C, BS = 32, 4, 4  # the tiny preset's window and chunk; the tests' block


def tiny(**over) -> ModelConfig:
    cfg = ModelConfig(dtype="float32", **{**resolve_preset("debug-tiny-evabyte"), **over})
    cfg.validate()
    return cfg


def weights(cfg, seed=1):
    p = init_params(cfg, jax.random.key(seed))
    # norm weights that are not all zero, so that 1 + w is not w + 1 by accident of
    # the init, and a trained model's embedding scale, so that the layers show
    norms = {n: 0.5 * jax.random.normal(jax.random.key(10 + i), p["layers"][n].shape)
             for i, n in enumerate(("input_norm", "post_norm"))}
    p["layers"] = dict(p["layers"], **norms)
    p["final_norm"] = 0.5 * jax.random.normal(jax.random.key(20), p["final_norm"].shape)
    p["embedding"] = p["embedding"] * 0.02
    return p


def published(cfg: ModelConfig) -> dict:
    """The configuration-file keys the reference reads, from a program config."""
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        intermediate_size=cfg.intermediate_size, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, attention_class=cfg.attention_class,
        window_size=cfg.window_size, chunk_size=cfg.chunk_size,
        num_pred_heads=cfg.num_pred_heads, norm_add_unit_offset=cfg.norm_add_unit_offset,
        fp32_skip_add=cfg.fp32_skip_add, attention_bias=cfg.attention_bias,
        tie_word_embeddings=cfg.tie_word_embeddings)


def ref_logits(params, cfg, ids, rows=None, head=0, **faults):
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.arange(len(ids)) if rows is None else jnp.asarray(list(rows))
    return np.asarray(reference.logits_at(params, ids, rows, published(cfg), head=head,
                                          **faults))


def test_as_program_names_the_model_the_program_builds():
    cfg = tiny()
    for k, v in reference.as_program(published(cfg)).items():
        assert getattr(cfg, k) == v, k


# ---------------------------------------------------------------------------
# the published config, the tree, the counts
# ---------------------------------------------------------------------------

HF = dict(
    model_type="evabyte", attention_class="eva", attention_bias=False, chunk_size=16,
    fp32_ln=False, fp32_logits=True, fp32_skip_add=True, hidden_act="silu", hidden_size=4096,
    init_std=0.01275, intermediate_size=11008, max_position_embeddings=32768,
    norm_add_unit_offset=True, num_attention_heads=32, num_hidden_layers=32,
    num_key_value_heads=32, num_pred_heads=8, rms_norm_eps=1e-5, rope_scaling=None,
    rope_theta=100000, tie_word_embeddings=False, vocab_size=320, window_size=2048)


def test_hf_reader_round_trips_the_published_keys_and_the_preset():
    got = model_config_from_hf_json(HF)
    preset = resolve_preset("EvaByte")
    assert {k: got[k] for k in preset if k != "head_dim"} == {
        k: v for k, v in preset.items() if k != "head_dim"}
    cfg = ModelConfig(**got)
    cfg.validate()
    assert (cfg.head_dim, cfg.eva, cfg.stacks[0].block.attn) == (128, True, "eva")
    # ISSUE 43's count: 6.49 B parameters, a layer 202,391,552
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == 202_391_552
    assert num_params(cfg) == 32 * layer + 320 * 4096 + 2560 * 4096 + 4096 == 6_488_330_240
    with pytest.raises(ValueError, match="attention_class"):
        model_config_from_hf_json(dict(HF, attention_class="softmax_window"))


def test_the_published_32_layers_build_at_tiny_widths():
    """The published depth, window, chunk and heads' count at widths a CPU
    holds: the tree builds, its count is `num_params`', forward runs."""
    cfg = tiny(num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=32,
               hidden_size=64, head_dim=2, window_size=2048, chunk_size=16, num_pred_heads=8,
               max_position_embeddings=4096)
    params = init_params(cfg, jax.random.key(0))
    assert param_count(params) == num_params(cfg)
    assert params["layers"]["eva_mu"].shape == (32, 32, 2)
    assert params["lm_head"].shape == (64, 8 * 320)
    out = forward(params, jnp.zeros((1, 40), jnp.int32), cfg)
    assert out.shape == (1, 40, 8, 320) and bool(jnp.isfinite(out).all())


@pytest.mark.parametrize("over,match", [
    (dict(window_size=30), "whole number of chunks"),
    (dict(chunk_size=0), "chunk_size"),
    (dict(attention_class="softmax"), "window_size / chunk_size"),
    (dict(layer_types=("sliding_attention", "full_attention"), sliding_window=8), "eva"),
    (dict(num_pred_heads=0), "num_pred_heads"),
    (dict(tie_word_embeddings=True), "untied"),
])
def test_model_config_refuses_what_eva_is_not(over, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig(**{**resolve_preset("debug-tiny-evabyte"), **over}).validate()


@pytest.mark.parametrize("serve,match", [
    (dict(prefill_chunk=24, block_size=4), "prefill_chunk"),
    (dict(prefill_chunk=2, block_size=4), "prefill_chunk"),
    (dict(prefill_chunk=8, block_size=16), "whole number of blocks"),
    (dict(prefill_chunk=8, block_size=8), None),
])
def test_serve_settings_a_window_cannot_live_in_are_refused(serve, match):
    cfg = tiny()
    if match is None:
        return check_eva_serving(cfg, ServeConfig(**serve))
    with pytest.raises(ValueError, match=match):
        check_eva_serving(cfg, ServeConfig(**serve))


# ---------------------------------------------------------------------------
# forward(), generate() and the engine against the reference
# ---------------------------------------------------------------------------


def test_forward_matches_the_reference_on_every_head():
    """Four windows and a partial chunk at the end; all 3 heads' logits."""
    cfg = tiny()
    params = weights(cfg)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, size=(2, 4 * W + 3))
    got = np.asarray(forward(params, jnp.asarray(ids), cfg))
    assert got.shape == (2, 4 * W + 3, 3, cfg.vocab_size)
    for b in range(2):
        # float32 on the CPU: one fused softmax against the reference's, at the
        # default matmul precision against `highest`
        np.testing.assert_allclose(got[b], ref_logits(params, cfg, ids[b], head=None),
                                   atol=2e-4)


@pytest.mark.parametrize("fault", reference.FAULTS + ("head_1", "int8"))
def test_each_of_the_probes_faults_moves_the_reference(fault):
    """The pooling against a mean, a sliding window against the block-aligned
    one, 1 + w against w, and the probe's other controls: each differs."""
    cfg = tiny()
    params = weights(cfg)
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, size=3 * W)
    rows = range(W, 3 * W)  # behind a closed window: the summaries are in play
    base = ref_logits(params, cfg, ids, rows)
    if fault == "head_1":
        moved = ref_logits(params, cfg, ids, rows, head=1)
    elif fault == "int8":
        moved = ref_logits(reference.rounded_to(params, 8), cfg, ids, rows)
    else:
        moved = ref_logits(params, cfg, ids, rows, **{fault: True})
    assert np.abs(moved - base).max() > (1e-4 if fault == "int8" else 1e-3)


def test_the_programs_pooling_is_not_a_mean():
    cfg = tiny()
    lp = {k: v[0] for k, v in weights(cfg)["layers"].items()}
    k = jax.random.normal(jax.random.key(2), (1, 2 * C, 2, 16))
    ks, vs = chunk_summaries(k, k, lp["eva_mu"], lp["eva_phi"], C)
    mean = k.reshape(1, 2, C, 2, 16).mean(axis=2)
    assert ks.shape == mean.shape
    assert float(jnp.abs(ks - mean).max()) > 1e-2 and float(jnp.abs(vs - ks).max()) > 1e-2


def test_ad_runs_through_forward_and_training_refuses_the_model():
    cfg = tiny()
    params = weights(cfg)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 320, size=(1, W + 5)))
    g = jax.grad(lambda p: forward(p, ids, cfg)[0, -1].sum())(params)
    assert float(jnp.abs(g["layers"]["eva_mu"]).max()) > 0  # behind a closed window
    with pytest.raises(ValueError, match="attention_class 'eva'.*training"):
        loss_fn(params, ids, ids, cfg)


def test_generate_matches_the_reference():
    """Prefill over a window boundary and a partial chunk, then decode
    through the contiguous cache over two more boundaries."""
    cfg = tiny()
    params = weights(cfg)
    ids = np.random.default_rng(3).integers(0, cfg.vocab_size, size=(2, W + 5))
    out = np.asarray(generate(params, cfg, jnp.asarray(ids), 2 * W))
    for b in range(2):
        want = ref_logits(params, cfg, out[b], rows=range(W + 4, 3 * W + 4))
        assert (out[b, W + 5:] == want.argmax(-1)).all()


SERVE = dict(decode_slots=2, block_size=BS, prefill_chunk=8, max_model_len=160,
             decode_interval=4, num_blocks=96)


def run_engine(params, cfg, requests, **over):
    eng = ServeEngine(params, cfg, ServeConfig(**{**SERVE, **over}))
    out = eng.run(requests)
    eng.close()
    assert eng.pool.in_use == 0  # none held after the drain
    return eng, sorted(out, key=lambda r: r["id"])


@pytest.mark.parametrize("chunk", [8, 16])
def test_engine_matches_the_reference(chunk):
    """Chunked prefill on the rungs, then decode, three slots' worth of
    requests over two slots: a prompt of 2 windows and a bit that decodes
    across two more boundaries, a short one that crosses its first boundary
    mid-decode (in the same batch, inside a dispatch of 4 steps), a prompt
    that ends inside a chunk, one that ends on a window boundary. Each served
    token's logit against the reference's full forward under teacher forcing."""
    cfg = tiny()
    params = weights(cfg)
    rng = np.random.default_rng(5)
    requests = [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m)
                for n, m in ((2 * W + 9, 2 * W), (W - 6, 20), (W + 2, 5), (2 * W, 3))]
    eng, out = run_engine(params, cfg, requests, prefill_chunk=chunk)
    assert len(out) == 4 and eng.stats["decode_compiles"] <= 1  # the one decode program
    for (prompt, _), res in zip(requests, out):
        toks = res["tokens"]
        want = ref_logits(params, cfg, prompt + toks,
                          rows=range(len(prompt) - 1, len(prompt) + len(toks) - 1))
        assert (want.argmax(-1) == np.asarray(toks)).all()
        # float32 on the CPU: the tiled online softmax against one softmax a row
        np.testing.assert_allclose(res["logits"], want[np.arange(len(toks)), toks], atol=2e-4)


def test_engine_in_bfloat16_stays_near_the_reference():
    """The dtypes the chip runs: bfloat16 weights and activations, a float32
    residual stream, summaries stored as bfloat16 rows. Each served token's
    logit against the float32 reference on the same (rounded) weights, behind
    a closed window: bfloat16 rounding and no more (a summary left out or a
    stale row moves these logits by 0.1 and more)."""
    cfg = ModelConfig(**resolve_preset("debug-tiny-evabyte"))
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), weights(cfg))
    prompt = list(map(int, np.random.default_rng(1).integers(0, 320, size=W + 7)))
    _, out = run_engine(params, cfg, [(prompt, W)])
    toks = out[0]["tokens"]
    want = ref_logits(params, cfg, prompt + toks, rows=range(W + 6, 2 * W + 6))
    err = np.abs(np.asarray(out[0]["logits"]) - want[np.arange(W), toks])
    assert err.max() < 0.03 and err.mean() < 0.01, (err.max(), err.mean())


def test_decode_through_the_kernel_reads_what_the_tiled_walk_reads(monkeypatch):
    """The decode step a chip runs: `EvaPagedCache.attend` hands the Pallas
    decode kernel (here under the interpreter, at a head of 128 and blocks of
    8, which it takes) the table of what a query may see and one length a
    slot. Same bytes as the tiled walk serves, over two window boundaries."""
    from picotron_tpu.serve import paged_cache

    cfg = tiny(hidden_size=64, num_attention_heads=2, num_key_value_heads=2, head_dim=128)
    params = weights(cfg)
    prompt = list(map(int, np.random.default_rng(9).integers(0, 320, size=W + 5)))
    over = dict(block_size=8, prefill_chunk=8, num_blocks=48)
    _, tiled = run_engine(params, cfg, [(prompt, W + 6)], **over)
    calls = []

    def suits(q, k_pool):
        calls.append(q.shape)
        return q.shape[1] == 1

    monkeypatch.setattr(paged_cache, "decode_kernel_suits", suits)
    # another name: the config is a static argument, so the programs are traced
    # anew, under the patch
    renamed = dataclasses.replace(cfg, name="debug-tiny-evabyte-through-the-kernel")
    _, kernel = run_engine(params, renamed, [(prompt, W + 6)], **over)
    assert any(shape[1] == 1 for shape in calls)
    assert kernel[0]["tokens"] == tiled[0]["tokens"]
    np.testing.assert_allclose(kernel[0]["logits"], tiled[0]["logits"], atol=2e-4)


# ---------------------------------------------------------------------------
# the table's law and the block accounting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,want", [
    (1, (1, 0)), (3, (1, 0)), (4, (1, 1)), (16, (4, 1)), (17, (5, 1)), (20, (5, 2)),
    (32, (8, 2)), (33, (8, 2)), (36, (8, 3)), (100, (8, 7)), (160, (8, 10))])
def test_blocks_a_request_holds_after_n_positions(n, want):
    """Position blocks: what n positions fill, one window's worth (8) at the
    most; summary blocks: a row a complete chunk (n // 4), 4 rows a block."""
    sched = Scheduler(1, None, BS, 40, summary=(W, C))
    assert sched.blocks_at(n) == want
    assert Scheduler(1, None, BS, 40).blocks_at(n) == (-(-n // BS), 0)


def test_engine_holds_what_the_law_says_and_recycles_the_window_in_place():
    cfg = tiny()
    params = weights(cfg)
    eng = ServeEngine(params, cfg, ServeConfig(**SERVE))
    width = eva_table_width(cfg, 160, BS)
    assert width == 10 + 8 and eng._tables[0].shape == (2, width)  # 40 summary rows, a window
    eng.submit(list(range(1, W + 7)), 2 * W + 10)
    seen = {}
    while eng.sched.has_work():
        eng.step(0.0)
        st = eng.sched.slots[0]
        if st is not None and st.generated and not st.prefilling:
            n = st.write_pos  # positions written so far
            assert (len(st.blocks), len(st.sblocks)) >= eng.sched.blocks_at(n)
            # the window's blocks never change once the window is full: recycled
            seen.setdefault("window", list(st.blocks))
            assert st.blocks[:len(seen["window"])] == seen["window"] and len(st.blocks) == 8
            row = eng._tables[0][0]
            assert list(row[:len(st.sblocks)]) == st.sblocks
            assert list(row[10:18]) == st.blocks
            assert (row[len(st.sblocks):10] == eng.num_blocks).all()  # unmapped
            assert eng.pool.in_use == st.held_blocks
    assert eng.pool.in_use == 0 and eng.pool.peak_in_use == sum(
        eng.sched.blocks_at(3 * W + 16))
    eng.close()


def test_a_preempted_request_recomputes_its_window_and_its_summaries():
    """A pool too small for both requests' growth: the younger is preempted
    mid-decode, gives back both kinds of block, prefills again from its prompt
    and what it had generated (its summaries with them) and serves the same
    bytes as in a pool with room."""
    cfg = tiny()
    params = weights(cfg)
    rng = np.random.default_rng(11)
    requests = [(list(map(int, rng.integers(0, 320, size=n))), m)
                for n, m in ((W + 4, W + 20), (W - 3, W + 12))]
    _, roomy = run_engine(params, cfg, requests)
    eng, tight = run_engine(params, cfg, requests, num_blocks=24)
    assert eng.sched.n_preempted >= 1 and eng.pool.peak_in_use <= 24
    assert [r["tokens"] for r in tight] == [r["tokens"] for r in roomy]


def test_a_recycled_windows_rows_are_never_read_again():
    """Poison every row of the pool that the law says no later query sees (a
    closed window's position rows still in the window's blocks past the
    write position) and serve on: the tokens do not change."""
    cfg = tiny()
    params = weights(cfg)
    prompt = list(map(int, np.random.default_rng(7).integers(0, 320, size=W + 3)))
    _, clean = run_engine(params, cfg, [(prompt, W + 8)])

    eng = ServeEngine(params, cfg, ServeConfig(**SERVE))
    eng.submit(prompt, W + 8)
    poisoned = 0
    while eng.sched.has_work():
        eng.step(0.0)
        st = eng.sched.slots[0]
        if st is None or not st.generated:
            continue
        # the window region's rows past the next dispatch's 4 positions are the old
        # window's: whole blocks of them, from `first` on
        first = (st.write_pos % W + 4) // BS + 1
        if st.write_pos >= W and first < W // BS:
            blocks = jnp.asarray(st.blocks[first:])
            k, v = eng._kv
            eng._kv = (k.at[:, :, blocks].set(1e4), v.at[:, :, blocks].set(-1e4))
            poisoned += 1
    assert poisoned >= 3
    assert eng.results[0]["tokens"] == clean[0]["tokens"]
    eng.close()


def test_engine_counts_both_kinds_of_block_on_its_spans():
    cfg = tiny()
    eng = ServeEngine(weights(cfg), cfg, ServeConfig(**SERVE))
    # a query at position 70 (71 positions): windows 0 and 1 closed, 16 summary rows =
    # 4 blocks, and 7 positions of window 2 = 2 blocks, where full attention reads 18
    got = eng.cache.decode_counts([(70, 4)], cfg)
    layers = cfg.num_hidden_layers
    assert got == dict(
        kv_blocks=6, eva_summaries_written=layers * 1, eva_windows_closed=0,
        eva_summary_blocks=layers * 4, eva_window_blocks=layers * 2,
        eva_blocks_read=layers * 6, eva_blocks_full_attention=layers * 18,
        # positions 70..73: the step at 71 ends chunk 17, the other three end none
        eva_steps=4, eva_steps_summarising=1)
    assert eng.cache.blocks_read(71, cfg) == 6
    # a prefill chunk that ends window 0: 8 positions, 2 chunks, 1 window closed
    assert eng.cache.prefill_counts([(24, 8)], cfg) == dict(
        eva_summaries_written=layers * 2, eva_windows_closed=1)
    eng.close()


# ---------------------------------------------------------------------------
# a decode step reads and pools a chunk only where some slot's position ends one
# ---------------------------------------------------------------------------


def filled_cache(cfg, slots=4):
    """An `EvaPagedCache` whose every row holds noise and whose every slot has
    the blocks of 160 positions mapped: 10 summary blocks and a window's 8 a
    slot, all distinct."""
    width = eva_table_width(cfg, 160, BS)
    cache = init_eva_cache(cfg, slots * width + 3, BS, slots, 160)
    kk, kv = jax.random.split(jax.random.key(3))
    return cache._replace(
        k=jax.random.normal(kk, cache.k.shape, cache.k.dtype),
        v=jax.random.normal(kv, cache.v.shape, cache.v.dtype),
        tables=jnp.arange(slots * width, dtype=jnp.int32).reshape(slots, width))


def parents_decode_write(cache, li, k_new, v_new, q_pos, mu, phi, cfg):
    """`EvaPagedCache.write` of a decode step as it stood before PR 49, written
    out: the position's row, then EVERY slot's chunk gathered out of the pool and
    pooled, whatever the positions, and the summary's row index -1 wherever the
    position ends no chunk."""
    b, c = k_new.shape[0], cfg.chunk_size
    cache = PagedKVCache.write(cache, li, k_new, v_new, cache._window_rows(q_pos, cfg))
    first = cache._summary_entries(cfg) * cache.block_size
    at = jnp.maximum(q_pos, c - 1) - (c - 1) + jnp.arange(c)[None, :]   # [B, c]
    rows = first + at % cfg.window_size
    blk = jnp.take_along_axis(cache.tables, rows // cache.block_size, axis=1)
    blk = jnp.minimum(blk, cache.num_blocks - 1)

    def chunk_of(pool):  # [Hkv, L, blocks, bs, D] -> [B, 1, c, Hkv, D]
        return pool[:, li][:, blk, rows % cache.block_size].transpose(1, 2, 0, 3)[:, None]

    ks, vs = eva_summarise(chunk_of(cache.k), chunk_of(cache.v), mu, phi)
    return PagedKVCache.write(
        cache, li, ks, vs, jnp.where((q_pos >= 0) & ((q_pos + 1) % c == 0), q_pos // c, -1))


@pytest.mark.parametrize("positions", [
    (5, 34, 70, 0),        # no slot ends a chunk: nothing is read or pooled
    (5, 35, 70, 0),        # one does (35 ends chunk 8)
    (3, 35, 71, 159),      # every slot does, the last one at the table's end
    (31, -1, 6, -1),       # the last position of a window beside idle rows
    (-1, -1, -1, -1),      # an empty step
    (63, 64, 65, 66),      # a window's last position, and rows inside the next
], ids=["none", "one", "all", "mixed-with-idle", "all-idle", "window-end"])
def test_a_decode_steps_write_leaves_what_the_unconditional_form_leaves(positions):
    """The pools after `EvaPagedCache.write` of one decode step EQUAL, bit for
    bit, what the form without the conditional leaves (PR 49: the chunk's gather
    and the pooling sit behind one test of the step's positions; in a step where
    some slot ends a chunk every slot's summary is computed as before, and its
    row index drops those that end none)."""
    cfg = tiny()
    cache = filled_cache(cfg)
    lp = {k: v[1] for k, v in weights(cfg)["layers"].items()}
    hkv, d = cfg.num_key_value_heads, cfg.head_dim
    k_new = jax.random.normal(jax.random.key(7), (4, 1, hkv, d))
    v_new = jax.random.normal(jax.random.key(8), (4, 1, hkv, d))
    q_pos = jnp.asarray(positions, jnp.int32)[:, None]
    args = (jnp.int32(1), k_new, v_new, q_pos, lp["eva_mu"], lp["eva_phi"])
    got = jax.jit(lambda ch, *a: ch.write(*a, cfg))(cache, *args)
    want = jax.jit(lambda ch, *a: parents_decode_write(ch, *a, cfg))(cache, *args)
    assert type(got) is type(cache)
    assert np.array_equal(got.k, want.k) and np.array_equal(got.v, want.v)
    # and what changed is the positions' rows and the summaries of those that end
    # a chunk, in layer 1 alone
    ends = [p for p in positions if p >= 0 and (p + 1) % C == 0]
    live = sum(p >= 0 for p in positions)
    changed = np.asarray((got.k != cache.k).any(axis=(0, -1)))   # [L, blocks, bs]
    assert changed.sum() == live + len(ends) and not changed[0].any()


def test_a_windows_last_chunk_is_summarised_before_the_next_step_attends():
    """A prompt two bytes short of a window, then ONE decode dispatch of four
    steps over positions W - 2 .. W + 1: the step at W - 1 ends the window's last
    chunk, and the steps at W and W + 1, in the same dispatch, see window 0
    through its summaries, that chunk's among them. Their logits match the
    reference; and they are read from that summary's row: a twin that serves a
    step a dispatch, with that one row overwritten between the step at W - 1 and
    the step at W, serves other logits from there on."""
    cfg = tiny()
    params = weights(cfg)
    prompt = list(map(int, np.random.default_rng(13).integers(0, 320, size=W - 2)))
    eng, out = run_engine(params, cfg, [(prompt, 5)])
    assert eng.stats["decode_steps"] == 1            # one dispatch: four steps
    toks, logits = out[0]["tokens"], np.asarray(out[0]["logits"])
    want = ref_logits(params, cfg, prompt + toks, rows=range(W - 3, W + 2))
    assert (want.argmax(-1) == np.asarray(toks)).all()
    np.testing.assert_allclose(logits, want[np.arange(5), toks], atol=2e-4)
    # the summaries are in play at the last two: without them the reference moves
    bare = ref_logits(params, cfg, prompt + toks, rows=range(W - 3, W + 2),
                      no_summaries=True)
    assert np.abs(bare - want)[3:].max() > 1e-3 > np.abs(bare - want)[:3].max()

    twin = ServeEngine(params, cfg, ServeConfig(**{**SERVE, "decode_interval": 1}))
    jit, poisoned = twin._decode_jit, []

    def poisoning(params, pools, tables, toks, last, positions, *a, **k):
        if int(positions[0]) == W:  # the row of chunk W / C - 1: entry, offset
            blk, off = int(tables[0][0, (W // C - 1) // BS]), (W // C - 1) % BS
            pools = tuple(x.at[:, :, blk, off].set(3.0) for x in pools)
            poisoned.append(blk)
        return jit(params, pools, tables, toks, last, positions, *a, **k)

    twin._decode_jit = poisoning
    moved = twin.run([(prompt, 5)])[0]
    twin.close()
    assert len(poisoned) == 1 and moved["tokens"][:3] == toks[:3]
    np.testing.assert_allclose(moved["logits"][:3], logits[:3], atol=2e-4)
    assert np.abs(np.asarray(moved["logits"][3]) - logits[3]) > 1e-3


def steps_by_hand(spans):
    """(steps with a row that has a byte to emit, those of them in which some
    row's position ends a chunk), a step at a time."""
    steps = closing = 0
    for j in range(8):
        if any(j < n for _, n in spans):
            steps += 1
            closing += any((p + j) % C == C - 1 for p, _ in spans)
    return steps, closing


@pytest.mark.parametrize("spans,want", [
    ([], (0, 0)),                                   # nothing dispatched
    ([(72, 3)], (3, 0)),                            # 72..74: inside chunk 18
    ([(70, 4)], (4, 1)),                            # 71 ends chunk 17
    ([(70, 4), (9, 2)], (4, 2)),                    # and 11 ends chunk 2, at a step of its own
    ([(3, 1), (7, 1), (12, 1)], (1, 1)),            # two rows end a chunk in ONE step
    ([(0, 4), (1, 4), (2, 4), (3, 4)], (4, 4)),     # a row a phase: every step
    ([(W - 2, 4)], (4, 1)),                         # across a window's end
    ([(4, 2), (8, 1)], (2, 0)),                     # would end one at a third step only
], ids=["empty", "inside", "straddles", "two-rows", "same-step", "every-step",
        "window-end", "short"])
def test_decode_counts_say_in_which_steps_the_program_summarises(spans, want):
    """`eva_steps` and `eva_steps_summarising` of `decode_counts`: what
    `eva_summarise_steps.serve` divides."""
    cfg = tiny()
    cache = init_eva_cache(cfg, 8, BS, 4, 160)
    got = cache.decode_counts(spans, cfg)
    assert (got["eva_steps"], got["eva_steps_summarising"]) == want == steps_by_hand(spans)


def test_the_dispatch_spans_count_the_steps_the_program_summarised_in():
    """The two counts on a `serve.decode.dispatch` span are those of the
    positions the PROGRAM received (one dispatch ahead of the host's own), and
    `eva_steps_summarising` is the number of the dispatch's steps in which the
    program's own test (`EvaPagedCache.write`: some live position ends a chunk)
    holds, wherever every row has a byte to emit at every step."""
    from picotron_tpu.telemetry import Telemetry
    from picotron_tpu.telemetry.flightdeck import SpanTracer

    cfg = tiny()
    tel = Telemetry(sinks=[])
    tel.tracer = SpanTracer()
    eng = ServeEngine(weights(cfg), cfg, ServeConfig(**SERVE), telemetry=tel)
    fed, jit = [], eng._decode_jit

    def recording(params, pools, tables, toks, last, positions, *a, **k):
        fed.append(positions)
        return jit(params, pools, tables, toks, last, positions, *a, **k)

    eng._decode_jit = recording
    rng = np.random.default_rng(2)
    eng.run([(list(map(int, rng.integers(0, 320, size=n))), m)
             for n, m in ((W + 3, 18), (9, 14), (21, 7))])
    eng.close()
    spans = sorted((e for e in tel.tracer.to_json()["traceEvents"]
                    if e["ph"] == "X" and e["name"] == "serve.decode.dispatch"),
                   key=lambda e: e["ts"])
    assert len(spans) == len(fed) > 6
    full = 0
    for e, positions in zip(spans, jax.device_get(fed)):
        a, live = e["args"], positions[positions >= 0]
        assert 1 <= a["eva_steps"] <= 4 and a["eva_steps_summarising"] <= a["eva_steps"]
        if a["eva_steps"] == 4:
            full += 1
            assert a["eva_steps_summarising"] == sum(
                bool(((live + j + 1) % C == 0).any()) for j in range(4))
    assert full > 4


# ---------------------------------------------------------------------------
# every path that cannot run the model refuses it by name
# ---------------------------------------------------------------------------

REFUSED = {
    "flash": dict(model=dict(attn_impl="flash")),
    "ring": dict(model=dict(attn_impl="ring"), distributed=dict(cp_size=2)),
    "cp": dict(distributed=dict(cp_size=2)),
    "fused": dict(training=dict(grad_engine="fused")),
    "pp": dict(distributed=dict(pp_size=2)),
    "tp": dict(distributed=dict(tp_size=2)),
    "ep": dict(distributed=dict(ep_size=2)),
    "fleet": dict(serve=dict(fleet_size=2)),
}


@pytest.mark.parametrize("path", REFUSED)
def test_paths_that_cannot_run_the_model_refuse_it_by_name(path):
    over = REFUSED[path]
    cfg = Config(
        distributed=DistributedConfig(**over.get("distributed", {})),
        model=ModelConfig(**{**resolve_preset("debug-tiny-evabyte"),
                             "attn_impl": "reference", **over.get("model", {})}),
        training=TrainingConfig(seq_length=64, **over.get("training", {})),
        serve=ServeConfig(**over.get("serve", {})))
    with pytest.raises(ValueError, match="attention_class 'eva'|mixture-of-experts"):
        cfg.validate()


@pytest.mark.parametrize("feature,over", [
    ("norm_add_unit_offset", dict(norm_add_unit_offset=True)),
    ("fp32_skip_add", dict(fp32_skip_add=True)),
    ("num_pred_heads > 1", dict(num_pred_heads=2)),
])
def test_each_new_feature_is_fenced_by_its_own_name(feature, over):
    model = ModelConfig(**{**resolve_preset("debug-tiny"), "attn_impl": "reference", **over})
    for dist in (dict(tp_size=2), dict(pp_size=2)):
        with pytest.raises(ValueError, match=feature):
            Config(distributed=DistributedConfig(**dist), model=model,
                   training=TrainingConfig(seq_length=64)).validate()
    Config(model=model, training=TrainingConfig(seq_length=64)).validate()
