"""Mellum2 on the normal path, against the benchmark's plain reference
(benchmark/reference_mellum2.py: float32 jax.numpy, nothing imported from the
program): `forward()`, `generate()` and `ServeEngine` at `debug-tiny-mellum2`
(two periods of sliding, sliding, sliding, full; head_dim 32 at hidden 64 / 4
heads; 8 experts, 2 a token; window 8; YaRN over an original length of 16;
block size 4, so that rings wrap) on seeded weights, plus the pieces the model
forced: YaRN tables, the two-pool allocator, the refusals.

Tolerances. Everything here runs in float32 on the CPU, program and reference
alike, so what separates them is the order of float32 sums (the program's
online softmax over tiles, its grouped matmuls): logits of magnitude ~2 agree
to a few 1e-6, and the limits are 2e-4, well under what any of the faults this
model invites would move them by (a missing band, the wrong RoPE law on a
layer kind, a dropped expert: each moves logits by 1e-2 or more, shown by
`test_reference_faults_move_the_logits`).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, ServeConfig, TrainingConfig,
    model_config_from_hf_json, resolve_preset,
)
from picotron_tpu.generate import generate
from picotron_tpu.models.llama import forward, init_params, model_rope_tables
from picotron_tpu.ops.rope import rope_tables
from picotron_tpu.serve import ServeEngine
from picotron_tpu.serve.paged_cache import (
    BlockPool, _ring_positions, ring_blocks_for,
)
from picotron_tpu.serve.scheduler import Request, Scheduler

# loaded by its path: `benchmark/` is not put on sys.path, where its own
# `tests/` directory would shadow this one for `from tests.test_tools import`
_spec = importlib.util.spec_from_file_location(
    "reference_mellum2", os.path.join(os.path.dirname(__file__), "..", "benchmark",
                                      "reference_mellum2.py"))
reference_mellum2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference_mellum2)

TOL = 2e-4
CFG = ModelConfig(dtype="float32", **resolve_preset("debug-tiny-mellum2"))


def published(cfg: ModelConfig) -> dict:
    """The published keys of a config, as the reference reads them from a
    configuration file."""
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps, layer_types=list(cfg.layer_kinds),
        sliding_window=cfg.sliding_window,
        rope_parameters={k: dict(v) for k, v in dict(cfg.rope_parameters).items()},
        num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_token,
        moe_intermediate_size=cfg.moe_intermediate_size,
        norm_topk_prob=cfg.norm_topk_prob,
        tie_word_embeddings=cfg.tie_word_embeddings)


M = published(CFG)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(33))


@pytest.fixture(scope="module")
def ids():
    # 61 tokens: past the window (8), the YaRN original length (16), many
    # block edges (4) and several turns of a ring (5 or 7 blocks)
    return np.asarray(jax.random.randint(jax.random.key(7), (61,), 0, 256))


def ref_logits(params, ids, rows, **faults):
    return np.asarray(reference_mellum2.logits_at(
        params, jnp.asarray(ids), jnp.asarray(rows), M, **faults))


def test_forward_matches_reference(params, ids):
    got = np.asarray(forward(params, jnp.asarray(ids)[None], CFG))[0]
    want = ref_logits(params, ids, np.arange(len(ids)))
    assert np.abs(got - want).max() < TOL


def test_reference_faults_move_the_logits(params, ids):
    """Each fault the tolerance probe injects on the chip moves the tiny
    model's logits by far more than TOL: the comparisons above can see it."""
    rows = np.arange(len(ids))
    want = ref_logits(params, ids, rows)
    for fault in ("no_band", "one_rope", "drop_last_expert"):
        moved = np.abs(ref_logits(params, ids, rows, **{fault: True}) - want).max()
        assert moved > 50 * TOL, (fault, moved)


def test_generate_matches_reference(params, ids):
    """Prefill + decode through the contiguous cache: each generated token
    is the argmax of the reference's logits under teacher forcing."""
    n_new = 12
    out = np.asarray(generate(params, CFG, jnp.asarray(ids[:40])[None], n_new))[0]
    assert np.array_equal(out[:40], ids[:40])
    want = ref_logits(params, out[:-1], np.arange(39, 39 + n_new))
    assert np.array_equal(want.argmax(-1), out[40:])


def serve(params, prompts, new, **scfg):
    eng = ServeEngine(params, CFG, ServeConfig(
        decode_slots=4, block_size=4, max_model_len=128, decode_interval=2, **scfg))
    for p, n in zip(prompts, new):
        eng.submit([int(t) for t in p], n)
    while eng.sched.has_work():
        eng.step(0.0)
    return eng, sorted(eng.results, key=lambda r: r["id"])


@pytest.mark.parametrize("chunk,least_tile", [(8, 16), (16, 16), (16, 1)])
def test_engine_logits_match_reference(params, ids, chunk, least_tile,
                                       monkeypatch, fresh_programs, request):
    """Chunked prefill on the rungs + decode through both pools: the logit
    the engine hands out for every token is the reference's logit of that
    token at that position in ONE full forward; two requests side by side,
    one of which turns its ring several times. The same prompt under
    another `prefill_chunk` gives the same logits (a token's experts do
    not depend on the chunking), and so does the experts' grouped kernel
    under another tiling (`ops/grouped_experts.py row_tile`, from the
    number of rows: at this size every program rides the least tile, 16,
    where an expert is one visit; a least tile of 1 gives the decode step
    tiles of one row, a one-row chunk 4 and a four-row chunk 16, and
    experts whose rows fill several tiles)."""
    from picotron_tpu.ops import grouped_experts

    monkeypatch.setattr(grouped_experts, "MIN_ROW_TILE", least_tile)
    if least_tile != 16:
        # the expert block is jitted and keeps its traces by shape: none
        # made under another tiling may serve this case, none of its may stay
        jax.clear_caches()
        request.addfinalizer(jax.clear_caches)
    prompts, new = [ids[:40], ids[5:28]], [14, 9]
    eng, res = serve(params, prompts, new, prefill_chunk=chunk)
    assert eng.sched.ring_blocks == ring_blocks_for(8, chunk, 4) < (40 + 14) // 4
    for p, r in zip(prompts, res):
        seq = np.concatenate([p, r["tokens"]])
        rows = np.arange(len(p) - 1, len(seq) - 1)
        want = ref_logits(params, seq[:-1], rows)
        assert np.array_equal(want.argmax(-1), r["tokens"])
        got = np.asarray(r["logits"])
        assert np.abs(got - want[np.arange(len(rows)), r["tokens"]]).max() < TOL
    assert (eng.pool.in_use, eng.wpool.in_use) == (0, 0)
    # the router's counter: live rows only, out of layers x steps x experts
    assert 0 < eng.stats["experts_touched"] <= eng.stats["expert_slots"]
    assert eng.stats["expert_slots"] % (8 * 2 * 8) == 0
    # the kernel's counter: an expert is one visit while a tile holds the
    # four slots, and two where both requests chose it and a tile holds one
    visits, touched = eng.stats["expert_visits"], eng.stats["experts_touched"]
    assert visits == touched if least_tile == 16 else visits > touched


def test_idle_rows_touch_no_expert(params, ids):
    """One live slot of four: a decode step's live row is routed to 2
    experts a layer, and the three idle slots to none."""
    eng, _ = serve(params, [ids[:9]], [5], prefill_chunk=16)
    steps = eng.stats["expert_slots"] // (8 * 8)  # decode steps dispatched
    assert eng.stats["experts_touched"] == steps * 8 * 2
    assert eng.stats["expert_visits"] == eng.stats["experts_touched"]


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("preset", ["debug-tiny-mellum2", "Mellum2-12B-A2.5B"])
def test_yarn_tables_match_the_formulas_in_float64(preset):
    """`rope_tables(rope_type=yarn)` against transformers'
    `_compute_yarn_parameters` written out in float64 (truncate at its
    default): frequencies, ramp bounds and the attention factor on both
    cos and sin. The sliding layers' table is the unscaled one."""
    cfg = ModelConfig(**resolve_preset(preset))
    n, d = 64, cfg.head_dim
    cos, sin = model_rope_tables(cfg, max_len=n)
    theta, law = cfg.rope_law("full_attention")
    i = np.arange(d // 2, dtype=np.float64)
    base = theta ** (-2.0 * i / d)

    def dim(r):
        return d * np.log(law["original_max_position_embeddings"] / (2 * np.pi * r)) / (
            2 * np.log(theta))

    low = max(np.floor(dim(law["beta_fast"])), 0)
    high = min(np.ceil(dim(law["beta_slow"])), d - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv = (1 - ramp) * base + ramp * base / law["factor"]
    assert 0 < ramp.sum() < d // 2, "the test's law has to bend some frequencies"
    ang = np.arange(n)[:, None] * inv[None, :]
    amp = law["attention_factor"]
    assert amp == pytest.approx(0.1 * np.log(law["factor"]) + 1)
    np.testing.assert_allclose(cos["full_attention"], amp * np.cos(ang), atol=2e-5)
    np.testing.assert_allclose(sin["full_attention"], amp * np.sin(ang), atol=2e-5)
    plain = rope_tables(n, d, theta)
    np.testing.assert_array_equal(cos["sliding_attention"], plain[0])
    np.testing.assert_array_equal(
        np.asarray(reference_mellum2.inv_freq(
            dict(law, rope_theta=theta), d)), inv)


# ---------------------------------------------------------------------------
# the cache with two kinds of state: rings and the allocator
# ---------------------------------------------------------------------------


def test_ring_positions():
    """What each ring slot holds after positions 0 .. last were written."""
    held = np.asarray(_ring_positions(jnp.asarray([-1, 2, 7, 8, 21]), 8))
    assert (held[0] < 0).all()
    assert held[1].tolist() == [0, 1, 2, -5, -4, -3, -2, -1]
    assert held[2].tolist() == list(range(8))
    assert held[3].tolist() == [8, 1, 2, 3, 4, 5, 6, 7]
    assert held[4].tolist() == [16, 17, 18, 19, 20, 21, 14, 15]


def sched(full=64, window=12, ring=5):
    return Scheduler(2, BlockPool(full), 4, 32, window_pool=BlockPool(window),
                     ring_blocks=ring)


def test_allocator_gives_a_ring_and_growing_blocks():
    s = sched()
    s.submit(Request(0, tuple(range(30)), 20))  # 50 tokens: 13 blocks > the ring
    s.submit(Request(1, tuple(range(6)), 4))    # 10 tokens: 3 blocks < the ring
    (_, a), (_, b) = s.admit()
    assert (len(a.blocks), len(a.wblocks)) == (8, 5)   # prompt's blocks; the ring
    assert (len(b.blocks), len(b.wblocks)) == (2, 3)   # shorter than a ring
    assert (s.pool.in_use, s.window_pool.in_use) == (10, 8)
    a.generated.append(1)
    a.n_prefilled = 30
    s.ensure_block(0, horizon=4)                       # decode grows full blocks only
    assert (len(a.blocks), len(a.wblocks)) == (9, 5)
    s.retire(0)
    s.cancel(1)
    assert (s.pool.in_use, s.window_pool.in_use) == (0, 0)
    assert (s.pool.peak_in_use, s.window_pool.peak_in_use) == (11, 8)


def test_admission_fails_cleanly_when_the_window_pool_runs_out():
    s = sched(window=7)
    for i in range(2):
        s.submit(Request(i, tuple(range(30)), 20))
    assert len(s.admit()) == 1            # 5 of 7 ring blocks gone: no second ring
    assert (s.pool.in_use, s.window_pool.in_use) == (8, 5)  # nothing half-taken
    assert len(s.queue) == 1
    s._preempt(0)                         # preemption returns both kinds
    assert (s.pool.in_use, s.window_pool.in_use) == (0, 0)
    with pytest.raises(ValueError, match="num_window_blocks"):
        sched(window=4).submit(Request(9, tuple(range(30)), 20))


def test_shed_request_holds_no_block():
    s = sched()
    s.submit(Request(0, tuple(range(30)), 20, arrival=0.0, deadline_ms=1.0))
    assert s.admit(now=1.0) == [] and len(s.drain_shed()) == 1
    assert (s.pool.in_use, s.window_pool.in_use) == (0, 0)


def test_engine_leaks_nothing_on_cancel(params, ids):
    eng = ServeEngine(params, CFG, ServeConfig(
        decode_slots=2, block_size=4, max_model_len=128, decode_interval=2,
        prefill_chunk=8))
    a = eng.submit([int(t) for t in ids[:40]], 30)
    eng.submit([int(t) for t in ids[:9]], 3)
    for _ in range(3):
        eng.step(0.0)
    assert eng.wpool.in_use > 0 and eng.cancel(a)
    while eng.sched.has_work():
        eng.step(0.0)
    assert (eng.pool.in_use, eng.wpool.in_use) == (0, 0)
    assert (eng._tables[1] == eng.wpool.num_blocks).all()


# ---------------------------------------------------------------------------
# the published keys, and the refusals
# ---------------------------------------------------------------------------


HF = dict(
    model_type="mellum", vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    max_position_embeddings=2048, rms_norm_eps=1e-6, hidden_act="silu",
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32, norm_topk_prob=True,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 8, sliding_window=8, use_sliding_window=True,
    max_window_layers=0, tie_word_embeddings=False, attention_bias=False,
    rope_parameters={k: dict(v) for k, v in dict(CFG.rope_parameters).items()})


def test_hf_reader_accepts_the_published_keys():
    got = ModelConfig(name=CFG.name, dtype="float32", **model_config_from_hf_json(HF))
    assert got == CFG
    assert got.layer_period == ("sliding_attention",) * 3 + ("full_attention",)
    assert got.head_dim == 32 != got.hidden_size // got.num_attention_heads


def test_dense_mlp_layers_are_refused_by_name():
    """A dense MLP layer loads at the head of the stack only (PR 35:
    `first_k_dense_replace`); behind an expert layer it is refused by name."""
    with pytest.raises(ValueError, match="mlp_layer_types.*dense"):
        model_config_from_hf_json(dict(HF, mlp_layer_types=["sparse", "dense"] + ["sparse"] * 6))
    lead = model_config_from_hf_json(dict(HF, mlp_layer_types=["dense"] + ["sparse"] * 7))
    assert lead["first_k_dense_replace"] == 1
    # ... and a leading dense layer under a layer pattern validates: the pattern is
    # cut where the stacks are, each stack carrying its own slice
    cfg = ModelConfig(**lead)
    cfg.validate()
    assert [st.kinds for st in cfg.stacks] == [cfg.layer_kinds[:1], cfg.layer_kinds[1:]]


@pytest.mark.parametrize("what,kw", [
    ("attn_impl='flash'", dict(model=dict(attn_impl="flash"))),
    ("attn_impl='ring'", dict(model=dict(attn_impl="ring"), dist=dict(cp_size=2))),
    ("attn_impl='ulysses'", dict(model=dict(attn_impl="ulysses"), dist=dict(cp_size=2))),
    ("attn_impl='mesh'", dict(model=dict(attn_impl="mesh"), dist=dict(cp_size=2))),
    ("context parallelism", dict(dist=dict(cp_size=2))),
    ("grad_engine='fused'", dict(train=dict(grad_engine="fused", remat=True,
                                            remat_policy="dots_attn"))),
    ("pipeline parallelism", dict(dist=dict(pp_size=2))),
    ("tensor parallelism", dict(dist=dict(tp_size=2))),
    ("serve.fleet_size > 1", dict(serve=dict(fleet_size=2))),
])
def test_window_layers_are_refused_by_name(what, kw):
    import dataclasses

    cfg = Config(
        distributed=DistributedConfig(**kw.get("dist", {})),
        model=dataclasses.replace(CFG, num_experts=0, **kw.get("model", {})),
        training=TrainingConfig(seq_length=64, **kw.get("train", {})),
        serve=ServeConfig(**kw.get("serve", {})))
    with pytest.raises(ValueError) as e:
        cfg.validate()
    assert "sliding_attention" in str(e.value) and what in str(e.value)


def test_reference_attention_trains_window_layers():
    """attn_impl='reference' is the one training path with a band."""
    import dataclasses

    Config(model=dataclasses.replace(CFG, attn_impl="reference"),
           training=TrainingConfig(seq_length=64)).validate()
