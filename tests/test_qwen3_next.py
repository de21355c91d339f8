"""Qwen3-Next's mechanisms at the tiny preset (`debug-tiny-qwen3-next`: two
periods of three Gated DeltaNet mixers and a gated softmax attention, 16
experts 2 a token beside a gated shared expert) on the CPU, float32: the
recurrent state beside the paged pool, the chunked recurrence, the gated
attention with a partly rotated head, the held share of the experts. The
program is held to `benchmark/reference_qwen3_next.py` (plain float32
jax.numpy, the recurrence token by token, no cache), which imports nothing
from it. Seeded weights throughout. The compiled programs are held by
tests/test_chip_compile.py."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import (
    GDN, Config, DistributedConfig, ModelConfig, ServeConfig, TrainingConfig,
    config_from_dict, model_config_from_hf_json, num_params, resolve_preset,
)
from picotron_tpu.generate import generate, init_cache
from picotron_tpu.models.llama import (
    forward, init_params, layer_leaves, loss_fn, mlp_act, param_count,
    shared_expert,
)
from picotron_tpu.ops.gated_delta import (
    causal_conv, gated_delta_chunked, gated_delta_scan, l2_normalise,
)
from picotron_tpu.ops.moe import moe_mlp_served
from picotron_tpu.ops.rope import apply_rope, rope_tables
from picotron_tpu.serve import ServeEngine
from picotron_tpu.serve.paged_cache import init_hybrid_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")
# loaded by its path: `benchmark/` is not put on sys.path, where its own
# `tests` package would shadow this one
_spec = importlib.util.spec_from_file_location(
    "reference_qwen3_next", os.path.join(ROOT, "benchmark", "reference_qwen3_next.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)
reference.CHUNK = 8  # the probe's chunk-boundary controls, at the tests' chunk

F = "full_attention"
# every expert here | experts 16-31 of 64, as one chip of four holds them
SHARES = {"whole": {}, "share": dict(router_experts=64, expert_first=16)}


def tiny(**over) -> ModelConfig:
    return ModelConfig(dtype="float32", **{**resolve_preset("debug-tiny-qwen3-next"), **over})


def weights(cfg, seed=1):
    p = init_params(cfg, jax.random.key(seed))
    layers = dict(p["layers"])
    # norm weights that are not at their start, so that 1 + w and w differ
    for j, n in enumerate(("input_norm", "post_norm", "q_norm", "k_norm", "gdn_norm")):
        layers[n] = layers[n] + 0.1 * jax.random.normal(jax.random.key(seed + 50 + j),
                                                        layers[n].shape)
    # a trained model's embedding scale, so that the layers show in the logits
    return dict(p, embedding=p["embedding"] * 0.1, layers=layers)


def published(cfg: ModelConfig) -> dict:
    """The keys `reference_qwen3_next` reads, from a ModelConfig."""
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers, num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        full_attention_interval=4, linear_conv_kernel_dim=cfg.linear_conv_kernel_dim,
        linear_key_head_dim=cfg.linear_key_head_dim,
        linear_num_key_heads=cfg.linear_num_key_heads,
        linear_num_value_heads=cfg.linear_num_value_heads,
        linear_value_head_dim=cfg.linear_value_head_dim,
        partial_rotary_factor=cfg.partial_rotary_factor, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps, moe_intermediate_size=cfg.moe_intermediate_size,
        shared_expert_intermediate_size=cfg.n_shared_experts * cfg.moe_intermediate_size,
        num_experts=cfg.num_experts, num_experts_per_tok=cfg.num_experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob, router_experts=cfg.router_width,
        expert_first=cfg.expert_first)


def ref_logits(params, cfg, ids, rows=None, **faults):
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.arange(ids.shape[0]) if rows is None else jnp.asarray(rows)
    return np.asarray(reference.logits_at(params, ids, rows, published(cfg), **faults))


# ---------------------------------------------------------------------------
# (a) forward() against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("share", SHARES)
def test_forward_matches_the_reference(share):
    cfg = tiny(**SHARES[share])
    params = weights(cfg)
    # longer than one sub-chunk of the chunked recurrence (64), and no multiple
    ids = jax.random.randint(jax.random.key(2), (2, 83), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, i: forward(p, i, cfg))(params, ids))
    for b in range(2):
        want = ref_logits(params, cfg, ids[b])
        # float32 round-off is some 1e-5 here and up to 3e-4 at the few
        # positions where a norm over 8 numbers (a head of the tiny mixer)
        # divides by little; the token-by-token form reads the same
        np.testing.assert_allclose(got[b], want, atol=5e-4)
    assert np.abs(want).max() > 1.0  # the layers show


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_control_of_the_reference_moves_the_logits(fault):
    """What the chip's tolerance probe breaks one at a time is in the
    numbers: the program agrees with the reference only when it is whole."""
    cfg = tiny()
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(2), (40,), 0, cfg.vocab_size)
    whole, faulty = ref_logits(params, cfg, ids), ref_logits(params, cfg, ids, **{fault: True})
    assert np.abs(whole - faulty).max() > (1e-4 if fault == "bf16_state" else 1e-3)


def test_forward_runs_under_ad_through_the_chunked_recurrence():
    cfg = tiny()
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(2), (1, 12), 0, cfg.vocab_size)
    g = jax.grad(lambda p: loss_fn(p, ids, ids, cfg))(params)
    for leaf in ("gdn_qkvz", "gdn_conv", "gdn_A_log", "gdn_dt_bias", "gdn_norm", "q",
                 "shared_out_gate"):
        assert float(jnp.abs(g["layers"][leaf]).max()) > 0, leaf


# ---------------------------------------------------------------------------
# (b) the chunked recurrence against the token-by-token one
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sub", [1, 4, 29])
def test_chunked_recurrence_matches_token_by_token(sub):
    """From a non-zero start state, with inert (padded) positions at the end:
    the outputs at the real positions and the final state."""
    b, s, h, dk, dv, pad = 2, 29, 3, 8, 8, 6
    ks = jax.random.split(jax.random.key(0), 6)
    q = l2_normalise(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = l2_normalise(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    live = jnp.arange(s)[None, :, None] < s - pad
    g = jnp.where(live, -jnp.exp(jax.random.normal(ks[3], (b, s, h))), 0.0)
    beta = jnp.where(live, jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))), 0.0)
    start = jax.random.normal(ks[5], (b, h, dk, dv))
    want_o, want_s = gated_delta_scan(q, k, v, g, beta, start)
    got_o, got_s = gated_delta_chunked(q, k, v, g, beta, start, sub=sub)
    np.testing.assert_allclose(got_o[:, :s - pad], want_o[:, :s - pad], atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)
    # the padded positions changed nothing: the state is that of the real ones
    cut = tuple(x[:, :s - pad] for x in (q, k, v, g, beta))
    np.testing.assert_array_equal(gated_delta_scan(*cut, start)[1], want_s)


def test_convolution_carries_its_tail_over_the_last_real_positions():
    b, s, c = 2, 10, 5
    x = jax.random.normal(jax.random.key(0), (b, s, c))
    w = jax.random.normal(jax.random.key(1), (c, 4))
    whole, _ = causal_conv(x, jnp.zeros((b, 3, c)), w, jnp.full((b,), s))
    # 6 real positions and 4 of padding, then the other 4 from the tail
    first, tail = causal_conv(x.at[:, 6:].set(9.0), jnp.zeros((b, 3, c)), w, jnp.full((b,), 6))
    np.testing.assert_array_equal(tail, x[:, 3:6])
    rest, tail2 = causal_conv(x[:, 6:], tail, w, jnp.full((b,), 4))
    np.testing.assert_allclose(jnp.concatenate([first[:, :6], rest], 1), whole, atol=1e-6)
    np.testing.assert_array_equal(tail2, x[:, 7:])
    # a row without a real position keeps the tail it came with
    _, kept = causal_conv(x, tail, w, jnp.zeros((b,), jnp.int32))
    np.testing.assert_array_equal(kept, tail)
    # fewer real positions than the tail is long: the old tail shifts
    _, mixed = causal_conv(x, tail, w, jnp.ones((b,), jnp.int32))
    np.testing.assert_array_equal(mixed, jnp.concatenate([tail[:, 1:], x[:, :1]], 1))


def test_rope_rotates_the_leading_share_of_a_head():
    x = jax.random.normal(jax.random.key(0), (1, 6, 2, 16))
    cos, sin = rope_tables(32, 4, 10000.0)
    got = apply_rope(x, cos, sin)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    np.testing.assert_allclose(got[..., :4], apply_rope(x[..., :4], cos, sin))
    assert np.abs(np.asarray(got[0, 1:, :, :4] - x[0, 1:, :, :4])).min() > 0


# ---------------------------------------------------------------------------
# (c) prefill, then decode through the caches, against the reference's forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("share", SHARES)
def test_generate_matches_the_reference(share):
    cfg = tiny(**SHARES[share])
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(3), (2, 12), 0, cfg.vocab_size)
    out = np.asarray(generate(params, cfg, ids, 6))
    for b in range(2):
        want = ref_logits(params, cfg, out[b], rows=range(11, 17))
        assert (out[b, 12:] == want.argmax(-1)).all()
    cache = init_cache(cfg, 2, 18)
    assert cache.k.shape == (2, 2, 18, 2, 16) and cache.state.shape == (6, 2, 4, 8, 8)
    assert cache.tail.shape == (6, 2, 192) and cache.state.dtype == jnp.float32


def run_engine(params, cfg, requests, **over):
    scfg = ServeConfig(**{**dict(decode_slots=2, block_size=4, prefill_chunk=8,
                                 max_model_len=64, decode_interval=2), **over})
    eng = ServeEngine(params, cfg, scfg)
    out = eng.run(requests)
    eng.close()
    assert eng.pool.in_use == 0
    return eng, sorted(out, key=lambda r: r["id"])


def held_to_the_reference(params, cfg, requests, out, atol=5e-4):
    """Every served token is the reference's first under teacher forcing, at
    the reference's logit."""
    for (prompt, _), res in zip(requests, out):
        toks = res["tokens"]
        want = ref_logits(params, cfg, prompt + toks,
                          rows=range(len(prompt) - 1, len(prompt) + len(toks) - 1))
        assert (want.argmax(-1) == np.asarray(toks)).all()
        np.testing.assert_allclose(res["logits"], want[np.arange(len(toks)), toks], atol=atol)


def some_requests(cfg, sizes, seed=5):
    rng = np.random.default_rng(seed)
    return [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m) for n, m in sizes]


@pytest.mark.parametrize("share,interval", [("whole", 1), ("share", 1), ("whole", 4),
                                            ("share", 4)])
def test_engine_matches_the_reference(share, interval):
    """Prefill in several chunks of 8 (the state handed from dispatch to
    dispatch through the pools), then decode a step a slot and mixer, one
    dispatch ahead: the logit of EVERY decoded position against the
    reference's full forward pass under teacher forcing."""
    cfg = tiny(**SHARES[share])
    params = weights(cfg)
    requests = some_requests(cfg, ((37, 8), (6, 5), (21, 7), (45, 4)))
    eng, out = run_engine(params, cfg, requests, decode_interval=interval)
    assert len(out) == 4 and eng.stats["decode_compiles"] <= 1  # the one decode program
    held_to_the_reference(params, cfg, requests, out)
    # the pools: the full layers' K/V alone, a state and a tail a slot and mixer
    k, v, state, tail = eng._kv
    assert k.shape[1] == cfg.layer_kinds.count(F) == 2
    assert state.shape == (6, 2, 4, 8, 8) and state.dtype == jnp.float32
    assert tail.shape == (6, 2, 192)
    if share == "share":
        assert 0 < eng.stats["picks_here"] < eng.stats["picks_all"]
    else:
        assert eng.stats["picks_here"] == eng.stats["picks_all"] > 0


def test_engine_agrees_with_generate():
    cfg = tiny()
    params = weights(cfg)
    prompt = list(map(int, np.random.default_rng(1).integers(0, 256, size=19)))
    _, out = run_engine(params, cfg, [(prompt, 9)])
    want = np.asarray(generate(params, cfg, jnp.asarray([prompt]), 9))[0, 19:]
    assert out[0]["tokens"] == list(map(int, want))


# ---------------------------------------------------------------------------
# (d) a state row is the slot's, and nobody resets it
# ---------------------------------------------------------------------------


def test_a_slots_next_request_starts_from_a_zero_state():
    """One slot, three requests one after the other: each is admitted into
    the row its predecessor left its state in, while the decode dispatch
    enqueued ahead for the predecessor is still in flight."""
    cfg = tiny()
    params = weights(cfg)
    requests = some_requests(cfg, ((19, 5), (9, 6), (26, 3)), seed=7)
    eng, out = run_engine(params, cfg, requests, decode_slots=1)
    assert eng.stats["decode_ahead"] > 0
    held_to_the_reference(params, cfg, requests, out)
    # the row is not zeros when the next request arrives: the program resets
    assert float(jnp.abs(eng._kv[2]).max()) > 0


def test_a_preempted_request_resumes_from_a_zero_state():
    cfg = tiny()
    params = weights(cfg)
    requests = some_requests(cfg, ((14, 9), (11, 9), (9, 9)), seed=3)
    eng, out = run_engine(params, cfg, requests, decode_slots=3, num_blocks=9,
                          max_model_len=32)
    assert eng.sched.n_preempted > 0
    held_to_the_reference(params, cfg, requests, out)


def test_idle_slots_and_padding_rows_leave_their_state_as_it_was():
    cfg = tiny()
    params = weights(cfg)
    scfg = ServeConfig(decode_slots=4, block_size=4, prefill_chunk=8, max_model_len=64,
                       decode_interval=2)
    eng = ServeEngine(params, cfg, scfg)
    # state in every row, as earlier requests would have left it
    marked = tuple(jnp.full(x.shape, 0.5 + i, x.dtype) for i, x in enumerate(eng._kv))
    eng._kv = jax.device_put(marked)
    requests = some_requests(cfg, ((21, 6), (5, 4), (13, 5)), seed=11)
    for i, (prompt, n) in enumerate(requests):
        eng.submit(prompt, n, req_id=i)
    while eng.sched.has_work():
        eng.step(0.0)
    # three requests through slots 0-2 (a prefill dispatch of 3 rows runs on
    # the 4-row rung: one padding row): slot 3 was idle in every dispatch
    _, _, state, tail = eng._kv
    np.testing.assert_array_equal(state[:, 3], marked[2][:, 3])
    np.testing.assert_array_equal(tail[:, 3], marked[3][:, 3])
    assert not np.array_equal(state[:, 0], marked[2][:, 0])
    out = sorted(eng.results, key=lambda r: r["id"])
    eng.close()
    held_to_the_reference(params, cfg, requests, out)


def test_the_cache_drops_what_it_must_and_resets_at_position_zero():
    cfg = tiny()
    cache = init_hybrid_cache(cfg, 8, 4, 3, 4)
    assert [p.shape for p in cache.pools] == [
        (2, 2, 8, 4, 16), (2, 2, 8, 4, 16), (6, 3, 4, 8, 8), (6, 3, 192)]
    assert cache.table_specs == ((4, 8), (1, 3))
    cache = cache._replace(state=cache.state + 2.0, tail=cache.tail + 3.0)
    # dispatch rows: slot 2 mid-sequence, slot 0 at its start, a padding row
    rows = cache._replace(stables=jnp.asarray([[2], [0], [3]], jnp.int32))
    pos = jnp.asarray([[8, 9, -1], [0, 1, 2], [-1, -1, -1]])
    state, tail = rows.state_of(4, pos), rows.tail_of(4, pos)
    assert float(state[0].min()) == 2.0 and not np.asarray(state[1]).any()
    assert float(tail[0].min()) == 3.0 and not np.asarray(tail[1]).any()
    wrote = rows.put_state(4, state + 1.0, pos).put_tail(4, tail + 1.0, pos)
    changed = np.asarray(jnp.any(wrote.state != cache.state, axis=(2, 3, 4)))
    assert changed.tolist() == [[g == 4 and s in (0, 2) for s in range(3)] for g in range(6)]
    assert np.asarray(jnp.any(wrote.tail != cache.tail, axis=2)).tolist() == changed.tolist()
    # the host's side: a state row is the slot's own, and costs the scheduler nothing
    assert cache.scheduler_args(cfg) == {}
    assert [r.tolist() for r in cache.slot_rows(None, cfg, 1)] == [[8] * 4, [3]]
    row_bytes = 4 * 8 * 8 * 4 + 3 * 64 * 4
    assert cache.state_row_bytes() == row_bytes
    assert cache.prefill_counts([(0, 8), (8, 5)], cfg) == dict(
        state_rows=12, state_bytes=2 * 12 * row_bytes, state_resets=6,
        chunk_rows_batch=12, chunk_rows_idle=0)
    # a rung of 4 rows, two of them pads: the chunk's kernel skips those
    assert cache.prefill_counts([(0, 8), (8, 5)], cfg, rows=4) == dict(
        state_rows=12, state_bytes=2 * 12 * row_bytes, state_resets=6,
        chunk_rows_batch=24, chunk_rows_idle=12)
    assert cache.decode_counts([(5, 2), (9, 2)], cfg) == dict(
        kv_blocks=5, kv_blocks_banded=10, state_rows=12, state_bytes=2 * 12 * row_bytes, state_resets=0,
        state_rows_batch=18, state_rows_idle=6)


def test_a_decode_step_through_the_kernel_serves_what_the_plain_path_serves(monkeypatch):
    """A two-slot engine at widths the decode kernel takes (one period, 2 value
    heads of 128 x 128 over one key head), driven through admission, a slot's
    second and third request, a preemption and with it a decode dispatch in
    flight for a request that has left: once as every CPU run serves it
    (gather, `gated_delta_step`, scatter) and once with the decode steps
    through `gated_delta_step_pooled` (the Pallas interpreter). The same
    tokens; after every engine step the same state pool to float32 rounding,
    and a row the plain path left alone in that step (idle, padding, a
    prefill-only step) is left alone by the kernel too, to the bit."""
    from picotron_tpu.serve import paged_cache

    cfg = tiny(num_hidden_layers=4, layer_types=(GDN, GDN, GDN, F), linear_key_head_dim=128,
               linear_value_head_dim=128, linear_num_key_heads=1, linear_num_value_heads=2)
    params = weights(cfg)
    requests = some_requests(cfg, ((14, 9), (11, 8), (9, 7), (5, 4)), seed=3)
    calls = []

    def served(kernel: bool):
        jax.clear_caches()  # the engines of one process share their compiled programs
        if kernel:
            sound = paged_cache.gated_delta_step_pooled
            monkeypatch.setattr(paged_cache, "gated_delta_kernel_suits", lambda s, pool: s == 1)
            monkeypatch.setattr(paged_cache, "gated_delta_step_pooled",
                                lambda *a, **k: calls.append(a[5].shape) or sound(*a, **k))
        eng = ServeEngine(params, cfg, ServeConfig(
            decode_slots=2, block_size=4, prefill_chunk=8, max_model_len=32, decode_interval=2,
            num_blocks=9))
        # state in every row, as earlier requests would have left it
        eng._kv = jax.device_put(tuple(jnp.full(x.shape, 0.25 + i, x.dtype)
                                       for i, x in enumerate(eng._kv)))
        for i, (prompt, n) in enumerate(requests):
            eng.submit(prompt, n, req_id=i)
        pools = [np.asarray(eng._kv[2])]
        while eng.sched.has_work():
            eng.step(0.0)
            pools.append(np.asarray(eng._kv[2]))
        eng.close()
        assert eng.pool.in_use == 0 and eng.sched.n_preempted > 0
        assert eng.stats["decode_ahead"] > 0
        return sorted(eng.results, key=lambda r: r["id"]), pools

    try:
        plain_out, plain_pools = served(False)
        assert not calls
        kernel_out, kernel_pools = served(True)
    finally:
        jax.clear_caches()  # no later engine may meet the programs traced here
    # traced once a mixer of the one period, in the decode program alone
    assert calls == [(3, 2, 2, 128, 128)] * 3
    assert [r["tokens"] for r in kernel_out] == [r["tokens"] for r in plain_out]
    assert len(plain_out) == 4 and len(kernel_pools) == len(plain_pools) > 8
    for before, after, got_before, got in zip(plain_pools, plain_pools[1:], kernel_pools,
                                              kernel_pools[1:]):
        np.testing.assert_allclose(got, after, rtol=0, atol=1e-5)
        left = ~np.any(after != before, axis=(2, 3, 4))  # [mixer, slot]
        assert left.all(axis=0).tolist() == left.any(axis=0).tolist()  # a slot's mixers together
        np.testing.assert_array_equal(got[left], got_before[left])
    assert any(np.any(a != b) for a, b in zip(kernel_pools, kernel_pools[1:]))
    held_to_the_reference(params, cfg, requests, kernel_out)


def test_a_prefill_chunk_through_the_kernel_serves_what_the_plain_path_serves(monkeypatch):
    """A two-slot engine at widths the chunk kernel takes (one period, 2 value
    heads of 128 x 128 over one key head, chunks of 64 positions), prompts of
    one to three chunks whose last is part padding, two of them side by side
    in a rung and one alone in a rung of two (a pad row): once as every CPU
    run serves it (gather, `gated_delta_chunked`, scatter) and once with the
    prefill chunks through `gated_delta_chunk_pooled` (the Pallas
    interpreter). The same tokens; after every engine step the same state
    pool to float32 rounding, and a row the plain path left alone in that
    step is left alone by the kernel too, to the bit."""
    from picotron_tpu.serve import paged_cache

    cfg = tiny(num_hidden_layers=4, layer_types=(GDN, GDN, GDN, F), linear_key_head_dim=128,
               linear_value_head_dim=128, linear_num_key_heads=1, linear_num_value_heads=2)
    params = weights(cfg)
    requests = some_requests(cfg, ((150, 3), (70, 4), (40, 3)), seed=3)
    calls = []

    def served(kernel: bool):
        jax.clear_caches()  # the engines of one process share their compiled programs
        if kernel:
            sound = paged_cache.gated_delta_chunk_pooled
            monkeypatch.setattr(paged_cache, "gated_delta_chunk_suits",
                                lambda s, hk, pool: s == 64)
            monkeypatch.setattr(paged_cache, "gated_delta_chunk_pooled",
                                lambda *a, **k: calls.append(a[0].shape) or sound(*a, **k))
        eng = ServeEngine(params, cfg, ServeConfig(
            decode_slots=2, block_size=16, prefill_chunk=64, max_model_len=192,
            decode_interval=2))
        # state in every row, as earlier requests would have left it
        eng._kv = jax.device_put(tuple(jnp.full(x.shape, 0.25 + i, x.dtype)
                                       for i, x in enumerate(eng._kv)))
        for i, (prompt, n) in enumerate(requests):
            eng.submit(prompt, n, req_id=i)
        pools = [np.asarray(eng._kv[2])]
        while eng.sched.has_work():
            eng.step(0.0)
            pools.append(np.asarray(eng._kv[2]))
        eng.close()
        return sorted(eng.results, key=lambda r: r["id"]), pools

    try:
        plain_out, plain_pools = served(False)
        assert not calls
        kernel_out, kernel_pools = served(True)
    finally:
        jax.clear_caches()  # no later engine may meet the programs traced here
    # traced once a mixer of the one period and rung, in the prefill program alone
    assert sorted(set(calls)) == [(1, 64, 1, 128), (2, 64, 1, 128)] and len(calls) == 6
    assert [r["tokens"] for r in kernel_out] == [r["tokens"] for r in plain_out]
    assert len(plain_out) == 3 and len(kernel_pools) == len(plain_pools) > 4
    for before, after, got_before, got in zip(plain_pools, plain_pools[1:], kernel_pools,
                                              kernel_pools[1:]):
        np.testing.assert_allclose(got, after, rtol=0, atol=1e-5)
        left = ~np.any(after != before, axis=(2, 3, 4))  # [mixer, slot]
        np.testing.assert_array_equal(got[left], got_before[left])
    held_to_the_reference(params, cfg, requests, kernel_out)


def test_the_seeded_decays_are_a_trained_models_not_the_placeholders():
    """dt = softplus(dt_bias) log-uniform in [0.001, 0.1], A = U(0, 16): a
    step keeps exp(-A dt) of a state, more than half of it in most heads
    (dt_bias = 1, the released code's placeholder, keeps less than half in
    31 heads of 32: a mixer without a memory, where neither a state left by
    another request nor its precision shows in anything)."""
    drawn = [init_params(tiny(), jax.random.key(seed))["layers"] for seed in range(4)]
    dt = np.concatenate([np.asarray(jax.nn.softplus(x["gdn_dt_bias"])).ravel() for x in drawn])
    a = np.exp(np.concatenate([np.asarray(x["gdn_A_log"]).ravel() for x in drawn]))
    assert dt.size >= 48 and 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert 0 < a.min() and a.max() <= 16.0
    kept = np.exp(-a * dt)
    assert np.median(kept) > 0.85 and (kept > 0.5).mean() > 0.85 and kept.max() > 0.99
    assert np.quantile(kept, 0.1) < 0.8  # ... and not all of them 1


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REUSE = dict(first_prompt_tokens=37, prompt_tokens=11, output_tokens=4, state_pool="state",
             limits=dict(reuse_logit_err_mean=0.0, state_err=0.0, state_bf16_share=0.0))


@pytest.mark.parametrize("wrong", [None, "state_kept", "state_in_bf16"])
def test_the_benchmarks_reuse_phase_reads_the_state_the_slots_hold(wrong, monkeypatch):
    """`benchmark/runners/serve_reference_reuse.py`'s second phase on the tiny
    model: every slot used twice, the second round's logits, the first
    mixer's state rows against the reference's carried state, the share of
    the pool a bfloat16 holds. A sound engine reads float32 round-off in all
    three; a cache that hands a slot's next request the state it holds, or
    keeps the state in bfloat16, moves the reading that is there for it."""
    from picotron_tpu.serve import paged_cache

    phase = _bench_module("runners", "serve_reference_reuse")
    mellum = _bench_module("runners", "serve_mellum2")
    cfg = tiny()
    params = weights(cfg)
    if wrong:
        # the engines of one process share their compiled programs
        jax.clear_caches()
    if wrong == "state_kept":
        sound = paged_cache.HybridPagedCache.state_of
        monkeypatch.setattr(paged_cache.HybridPagedCache, "state_of",
                            lambda self, gi, q_pos: sound(self, gi, q_pos + 1))
    if wrong == "state_in_bf16":
        sound_put = paged_cache.HybridPagedCache.put_state
        monkeypatch.setattr(
            paged_cache.HybridPagedCache, "put_state",
            lambda self, gi, state, q_pos: sound_put(
                self, gi, state.astype(jnp.bfloat16).astype(jnp.float32), q_pos))
    try:
        eng = ServeEngine(params, cfg, ServeConfig(decode_slots=3, block_size=4, prefill_chunk=8,
                                                   max_model_len=64, decode_interval=2))
        with jax.default_matmul_precision("highest"):
            got, note = phase.read(eng, reference, mellum, params, published(cfg), REUSE, 7,
                                   cfg.vocab_size)
    finally:
        if wrong:
            jax.clear_caches()  # no later engine may meet the programs traced here
    # (4 tokens asked for, 5 served: the last on the second step of a dispatch of two)
    assert "with 5 served, slots [0, 1, 2]" in note and set(got) == set(REUSE["limits"])
    if wrong is None:
        assert got["reuse_logit_err_mean"] < 1e-4 and got["state_err"] < 1e-4
        assert got["state_bf16_share"] < 0.01
    elif wrong == "state_kept":
        assert got["reuse_logit_err_mean"] > 0.01 and got["state_err"] > 0.01
    else:
        assert got["state_bf16_share"] > 0.99 and 1e-4 < got["state_err"] < 0.05


# ---------------------------------------------------------------------------
# (h) the held share of the experts (the guide's section 4)
# ---------------------------------------------------------------------------


def test_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 8 shares (2 of 16 experts each) plus the gated
    shared expert ONCE are the uncut reference's whole expert layer."""
    cfg = tiny()
    params = weights(cfg)
    lp = layer_leaves(params["layers"], cfg.layer_kinds, 1)
    x = jax.random.normal(jax.random.key(4), (2, 9, cfg.hidden_size), jnp.float32)
    live = jnp.ones((2, 9), bool)
    m = published(cfg)
    with jax.default_matmul_precision("highest"):
        total = shared_expert(x, lp, cfg)
        seen = 0
        for first in range(0, 16, 2):
            held = {n: params["layers"][n][:, first:first + 2]
                    for n in ("w_gate", "w_up", "w_down")}
            routed, counts = moe_mlp_served(
                x, lp["router"], held["w_gate"], held["w_up"], held["w_down"],
                top_k=2, act=mlp_act(cfg), norm_topk_prob=True, live=live, layer=1,
                scoring="softmax", scale=1.0, expert_first=first)
            total = total + routed
            seen += int(counts[2])
            assert int(counts[3]) == 2 * 9 * 2
        assert seen == 2 * 9 * 2  # every pick lands on exactly one share
        flat = x.reshape(18, -1)
        want = (reference.routed(flat, lp, m) + reference.shared(flat, lp, m)).reshape(x.shape)
    np.testing.assert_allclose(total, want, atol=2e-5)
    # the gate is in it: without it the shared expert comes out larger
    plain = shared_expert(x, {n: w for n, w in lp.items() if n != "shared_out_gate"}, cfg)
    assert float(jnp.abs(plain).mean()) > 1.5 * float(jnp.abs(shared_expert(x, lp, cfg)).mean())


# ---------------------------------------------------------------------------
# (e), (f) configuration: the published keys, the counts, the benchmark's file
# ---------------------------------------------------------------------------

HF = {  # the catalog row's `config`, as published
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}


def test_hf_reader_round_trips_the_published_keys():
    got = ModelConfig(**model_config_from_hf_json(HF))
    got.validate()
    want = ModelConfig(**resolve_preset("Qwen3-Next-80B-A3B-Instruct"))
    assert got == ModelConfig(**{**want.__dict__, "name": got.name})
    assert got.layer_kinds == (GDN, GDN, GDN, F) * 12 and got.gdn
    assert (got.rope_dim, got.gdn_channels, got.n_shared_experts) == (64, 8192, 1)
    assert got.stacks[0].kinds == got.layer_kinds and got.stacks[0].block.mlp == "experts"
    with pytest.raises(ValueError, match="decoder_sparse_step"):
        model_config_from_hf_json({**HF, "mlp_only_layers": [0]})
    with pytest.raises(ValueError, match="whole number of experts"):
        model_config_from_hf_json({**HF, "shared_expert_intermediate_size": 700})


# the Motivation's arithmetic of ISSUE 51, by part
MIXER = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 64 + 128 + 4096 * 2048
ATTENTION = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 512
BESIDE = 2048 * 512 + 3 * 2048 * 512 + 2048 + 4096  # router, shared expert + gate, two norms
EXPERT = 3 * 2048 * 512


def test_published_sizes_count_80b_and_the_benchmarks_cut():
    assert (MIXER, ATTENTION, BESIDE) == (33_718_464, 27_263_488, 4_200_448)
    assert MIXER + BESIDE == 37_918_912 and ATTENTION + BESIDE == 31_463_936
    full = ModelConfig(**resolve_preset("Qwen3-Next-80B-A3B-Instruct"))
    assert 79e9 < num_params(full) < 82e9
    assert 2.5e9 < num_params(full, active_only=True) < 4e9
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b-12l-ep8.json")) as f:
        c = json.load(f)
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")}).model
    period = 3 * (MIXER + BESIDE) + ATTENTION + BESIDE
    want = 3 * period + 12 * 64 * EXPERT + 2 * 18992 * 2048 + 2048
    assert period == 145_220_672 and want == 2_929_374_400
    assert num_params(cfg) == c["parameters"] == want
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    assert param_count(shapes) == want
    lay = {n: x.shape for n, x in shapes["layers"].items()}
    assert lay["gdn_qkvz"] == (9, 2048, 12288) and lay["gdn_ba"] == (9, 2048, 64)
    assert lay["gdn_conv"] == (9, 8192, 4) and lay["gdn_out"] == (9, 4096, 2048)
    assert lay["q"] == (3, 2048, 8192) and lay["k"] == lay["v"] == (3, 2048, 512)
    assert lay["o"] == (3, 4096, 2048) and lay["q_norm"] == (3, 256)
    assert lay["router"] == (12, 2048, 512) and lay["w_gate"] == (12, 64, 2048, 512)
    assert lay["shared_gate"] == (12, 2048, 512) and lay["shared_out_gate"] == (12, 2048)
    # every number of the catalog row under its own key, but for `reduced`
    for key, value in HF.items():
        assert c[key] == value or key in c["reduced"], key
    assert set(c["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size",
                                 "max_position_embeddings"}
    # the reference reads the same model from the file's published keys
    for key, value in reference.as_program({k: c[k] for k in reference.KEYS}).items():
        assert getattr(cfg, key) == value, key
    # the state beside the weights: 2 MiB + 96 KiB a slot and mixer, float32 both
    cache = jax.eval_shape(lambda: init_hybrid_cache(cfg, 16, 16, 2, 8))
    assert cache.state_row_bytes() == 2_097_152 + 98_304
    assert cache.state.shape == (9, 2, 32, 128, 128) and cache.k.shape[:2] == (2, 3)


# ---------------------------------------------------------------------------
# (g) what is refused by name
# ---------------------------------------------------------------------------


def sections(**over):
    base = dict(distributed=DistributedConfig(), model=tiny(attn_impl="reference"),
                training=TrainingConfig(grad_engine="ad"), serve=ServeConfig())
    return Config(**{**base, **over})


REFUSALS = [
    (dict(model=tiny(attn_impl="flash")), "attn_impl='flash'"),
    (dict(model=tiny(attn_impl="ring")), "attn_impl='ring'"),
    (dict(training=TrainingConfig(grad_engine="fused")), "grad_engine='fused'"),
    (dict(distributed=DistributedConfig(tp_size=2)), "tensor parallelism"),
    (dict(distributed=DistributedConfig(pp_size=2)), "pipeline parallelism"),
    (dict(distributed=DistributedConfig(ep_size=2)), "expert parallelism"),
    (dict(distributed=DistributedConfig(cp_size=2)), "context parallelism"),
]


@pytest.mark.parametrize("over,message", REFUSALS, ids=[m for _, m in REFUSALS])
def test_validate_refuses_by_name(over, message):
    sections().validate()
    with pytest.raises(ValueError) as e:
        sections(**over).validate()
    assert "linear_attention" in str(e.value) and message in str(e.value)


@pytest.mark.parametrize("over,message", [
    (dict(linear_num_value_heads=3), "whole multiple of the key heads"),
    (dict(layer_types=None), "are a linear_attention layer's"),
    (dict(layer_types=(GDN, GDN, GDN, "sliding_attention") * 2, sliding_window=8),
     "linear_attention layers are built beside full"),
    (dict(partial_rotary_factor=0.2), "even number of rotated"),
    (dict(qk_norm=True), "attn_output_gate splits"),
    (dict(attn_output_gate=False), "gated attention only"),
    (dict(n_shared_experts=0), "shared_expert_gate needs"),
    (dict(layer_types=(GDN, GDN, "dense", F) * 2), "layer_types entries must be"),
])
def test_model_validate_messages(over, message):
    with pytest.raises(ValueError, match=message):
        tiny(**over).validate()
