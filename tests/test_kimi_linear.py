"""Kimi-Linear's mechanisms at the tiny preset (`debug-tiny-kimi-linear`: two
periods of three Kimi Delta Attention mixers and an unrotated latent
attention, the first layer dense, 16 experts 2 a token beside a shared expert
under a sigmoid router with a selection bias) on the CPU, float32: the
recurrent state beside a LATENT pool, the chunked per-channel delta rule, the
decode step's kernel, the held share of the experts. The program is held to
`benchmark/reference_kimi_linear.py` (plain float32 jax.numpy, the recurrence
token by token, the attention un-absorbed, no cache), which imports nothing
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
    KDA, Config, DistributedConfig, ModelConfig, ServeConfig, TrainingConfig,
    config_from_dict, model_config_from_hf_json, num_params, pattern_of, resolve_preset,
)
from picotron_tpu.generate import generate, init_cache
from picotron_tpu.models.llama import (
    forward, init_params, layer_leaves, loss_fn, mlp_act, param_count, shared_expert,
)
from picotron_tpu.ops.gated_delta import (
    gated_delta_scan, gated_delta_step, gated_delta_step_pooled, l2_normalise,
)
from picotron_tpu.ops.kda import kda_chunked
from picotron_tpu.ops.moe import moe_mlp_served
from picotron_tpu.serve import ServeEngine
from picotron_tpu.serve.paged_cache import (
    HybridLatentPagedCache, init_hybrid_latent_cache, init_serve_cache,
)

from test_gated_delta_kernel import seeded_decays  # (tests/ is on the path)

ROOT = os.path.join(os.path.dirname(__file__), "..")
# loaded by its path: `benchmark/` is not put on sys.path, where its own
# `tests` package would shadow this one
_spec = importlib.util.spec_from_file_location(
    "reference_kimi_linear", os.path.join(ROOT, "benchmark", "reference_kimi_linear.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)
reference.CHUNK = 8  # the probe's chunk-boundary controls, at the tests' chunk

F = "full_attention"
# every expert here | experts 16-31 of 64, as one chip of four holds them
SHARES = {"whole": {}, "share": dict(router_experts=64, expert_first=16)}


def tiny(**over) -> ModelConfig:
    return ModelConfig(dtype="float32", **{**resolve_preset("debug-tiny-kimi-linear"), **over})


def weights(cfg, seed=1):
    p = init_params(cfg, jax.random.key(seed))
    out = dict(p, embedding=p["embedding"] * 0.1)  # a trained model's embedding scale
    for j, stack in enumerate(("dense_layers", "layers")):
        layers = dict(p[stack])
        # norm weights that are not at their start, and a selection bias that
        # changes which experts are chosen
        for i, n in enumerate(("input_norm", "post_norm", "kda_norm", "kv_a_norm",
                               "router_bias")):
            if n in layers:
                layers[n] = layers[n] + 0.1 * jax.random.normal(
                    jax.random.key(seed + 50 + 7 * j + i), layers[n].shape)
        out[stack] = layers
    return out


def published(cfg: ModelConfig) -> dict:
    """The keys `reference_kimi_linear` reads, from a ModelConfig."""
    kinds = cfg.layer_kinds
    lin = dict(kda_layers=[i + 1 for i, k in enumerate(kinds) if k == KDA],
               # (a published list names layers beyond a cut model's depth)
               full_attn_layers=[i + 1 for i, k in enumerate(kinds) if k == F] + [99],
               head_dim=cfg.linear_key_head_dim, num_heads=cfg.linear_num_key_heads,
               short_conv_kernel_size=cfg.linear_conv_kernel_dim)
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers, num_attention_heads=cfg.num_attention_heads,
        q_lora_rank=None, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, mla_use_nope=cfg.mla_use_nope, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps, linear_attn_config=lin,
        first_k_dense_replace=cfg.first_k_dense_replace,
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size, num_experts=cfg.num_experts,
        num_experts_per_token=cfg.num_experts_per_token,
        num_shared_experts=cfg.n_shared_experts, moe_renormalize=cfg.norm_topk_prob,
        moe_router_activation_func=cfg.moe_scoring,
        routed_scaling_factor=cfg.routed_scaling_factor,
        router_experts=cfg.router_width, expert_first=cfg.expert_first)


def ref_logits(params, cfg, ids, rows=None, **faults):
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.arange(ids.shape[0]) if rows is None else jnp.asarray(rows)
    return np.asarray(reference.logits_at(params, ids, rows, published(cfg), **faults))


# ---------------------------------------------------------------------------
# (a) forward() and generate() against the reference
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
        np.testing.assert_allclose(got[b], want, atol=5e-4)
    assert np.abs(want).max() > 1.0  # the layers show
    # two stacks: the dense mixer, then (K, K, F, K) once and three left over
    assert [(st.name, st.layers) for st in cfg.stacks] == [("dense_layers", 1), ("layers", 7)]
    assert pattern_of(cfg.stacks[1].kinds) == ((KDA, KDA, F, KDA), 1, (KDA, KDA, F))


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_control_of_the_reference_moves_the_logits(fault):
    """What the chip's tolerance probe breaks one at a time is in the
    numbers: the program agrees with the reference only when it is whole."""
    cfg = tiny()
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(2), (40,), 0, cfg.vocab_size)
    whole, faulty = ref_logits(params, cfg, ids), ref_logits(params, cfg, ids, **{fault: True})
    assert np.abs(whole - faulty).max() > (1e-4 if fault == "bf16_state" else 1e-3)


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
    assert type(cache).__name__ == "HybridLatentCache"
    assert cache.ckr.shape == (2, 2, 18, 40) and cache.state.shape == (6, 2, 4, 8, 8)
    assert cache.tail.shape == (6, 2, 288) and cache.state.dtype == jnp.float32


def test_training_refuses_the_mixer_by_name():
    cfg = tiny()
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="kda layers"):
        loss_fn(weights(cfg), ids, ids, cfg)


# ---------------------------------------------------------------------------
# (b) the rule: chunked against token by token, the kernel against the step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,sub,block", [(150, 64, 16), (5, 64, 16), (37, 8, 4), (64, 16, 16)])
def test_chunked_rule_matches_token_by_token(s, sub, block):
    """From a non-zero state, with padding behind each row's last real
    position, on decays that reach several units a step: the chunked form's
    exponents stay <= 0 where exp(-G) would overflow float32 after a few
    dozen positions."""
    b, h, dk, dv = 2, 3, 16, 8
    ks = jax.random.split(jax.random.key(s), 5)
    q = l2_normalise(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = l2_normalise(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h)))
    state = jax.random.normal(ks[4], (b, h, dk, dv))
    g = seeded_decays(b, s, h, dk, s + 1)
    assert float(g.min()) < -4.0 and float(jnp.cumsum(g, axis=1).min()) < -88.0 * (s > 30)
    live = jnp.arange(s)[None, :] < jnp.asarray([s, max(s - 3, 1)])[:, None]
    g, beta = jnp.where(live[..., None, None], g, 0.0), jnp.where(live[..., None], beta, 0.0)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = gated_delta_scan(q, k, v, g, beta, state)
        got_o, got_s = kda_chunked(q, k, v, g, beta, state, sub, block)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)
    # the padding behind a row's last real position left its state alone
    short = gated_delta_scan(*(x[1:, :max(s - 3, 1)] for x in (q, k, v, g, beta)), state[1:])[1]
    np.testing.assert_allclose(got_s[1:], short, atol=2e-5)


def test_decode_kernel_updates_the_live_rows_in_place():
    """`gated_delta_step_pooled` with a decay a channel (`kda_step_pooled`), in
    the Pallas interpreter: live rows as the step rule, a fresh row from
    zeros, an idle and an unmapped row untouched, no other mixer's rows
    read or written."""
    b, h, dk, dv = 5, 8, 128, 128
    ks = jax.random.split(jax.random.key(3), 6)
    q = l2_normalise(jax.random.normal(ks[0], (b, h, dk))) * dk ** -0.5
    k = l2_normalise(jax.random.normal(ks[1], (b, h, dk)))
    v = jax.random.normal(ks[2], (b, h, dv))
    g = seeded_decays(b, 1, h, dk, 9)[:, 0]
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (b, h)))
    pool = jax.random.normal(ks[4], (2, 6, h, dk, dv))
    rows = jnp.asarray([3, 0, 6, 5, 2], jnp.int32)       # row 2: unmapped
    live = jnp.asarray([True, True, True, False, True])  # row 3: idle
    fresh = jnp.asarray([False, True, False, False, False])
    o, after = gated_delta_step_pooled(q, k, v, g, beta, pool, 1, rows, live, fresh,
                                       interpret=True)
    start = jnp.where(fresh[:, None, None, None], 0.0, pool[1, jnp.minimum(rows, 5)])
    want_o, want_s = gated_delta_step(q, k, v, g, beta, start)
    for row, slot in ((0, 3), (1, 0), (4, 2)):
        np.testing.assert_allclose(o[row], want_o[row], atol=1e-6)
        np.testing.assert_allclose(after[1, slot], want_s[row], atol=1e-6)
    assert not np.asarray(o[2:4]).any()
    np.testing.assert_array_equal(after[0], pool[0])
    for slot in (1, 4, 5):
        np.testing.assert_array_equal(after[1, slot], pool[1, slot])


def test_the_seeded_decays_leave_a_mixer_its_memory():
    """A_log = log U(1, 16) a head, dt_bias the inverse softplus of a step
    log-uniform in [0.001, 0.1] a CHANNEL: a step keeps exp(-A dt) of a
    state's row, most of it in most channels and not all of it in all."""
    cfg = tiny()
    drawn = [init_params(cfg, jax.random.key(seed))["layers"] for seed in range(4)]
    kept = np.concatenate([np.exp(
        -np.exp(np.asarray(x["kda_A_log"]))[:, :, None]
        * np.asarray(jax.nn.softplus(x["kda_dt_bias"])).reshape(5, 4, 8)).ravel() for x in drawn])
    dt = np.concatenate([np.asarray(jax.nn.softplus(x["kda_dt_bias"])).ravel() for x in drawn])
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert np.median(kept) > 0.85 and (kept > 0.5).mean() > 0.85 and kept.max() > 0.99
    assert np.quantile(kept, 0.1) < 0.8
    # a channel's: the channels of one head do not share a step
    assert np.std(np.asarray(drawn[0]["kda_dt_bias"]).reshape(5, 4, 8), axis=-1).min() > 0.1


# ---------------------------------------------------------------------------
# (c) the serving engine
# ---------------------------------------------------------------------------


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


@pytest.mark.parametrize("share,interval", [("whole", 1), ("share", 4)])
def test_engine_matches_the_reference(share, interval):
    """Prefill in several chunks of 8 (the state handed from dispatch to
    dispatch through the pools, the latents through the latent pool), then
    decode a step a slot and mixer, one dispatch ahead: the logit of EVERY
    decoded position against the reference's full forward pass."""
    cfg = tiny(**SHARES[share])
    params = weights(cfg)
    requests = some_requests(cfg, ((37, 8), (6, 5), (21, 7), (45, 4)))
    eng, out = run_engine(params, cfg, requests, decode_interval=interval)
    assert len(out) == 4 and eng.stats["decode_compiles"] <= 1
    held_to_the_reference(params, cfg, requests, out)
    # the pools: the full layers' latents alone, a state and a tail a slot and mixer
    assert type(eng.cache) is HybridLatentPagedCache
    kv, state, tail = eng._kv
    assert kv.shape[0] == cfg.layer_kinds.count(F) == cfg.attention_sublayers == 2
    assert state.shape == (6, 2, 4, 8, 8) and state.dtype == jnp.float32
    assert tail.shape == (6, 2, 288)
    if share == "share":
        assert 0 < eng.stats["picks_here"] < eng.stats["picks_all"]
    else:
        assert eng.stats["picks_here"] == eng.stats["picks_all"] > 0


def test_a_slots_next_request_and_a_preempted_one_start_from_a_zero_state():
    cfg = tiny()
    params = weights(cfg)
    # one slot, three requests one after the other, each admitted into the row
    # its predecessor left its state in
    requests = some_requests(cfg, ((19, 5), (9, 6), (26, 3)), seed=7)
    eng, out = run_engine(params, cfg, requests, decode_slots=1)
    assert eng.stats["decode_ahead"] > 0 and float(jnp.abs(eng._kv[1]).max()) > 0
    held_to_the_reference(params, cfg, requests, out)
    # a pool too small for three: a preempted request's prefill starts again at 0
    requests = some_requests(cfg, ((14, 9), (11, 9), (9, 9)), seed=3)
    eng, out = run_engine(params, cfg, requests, decode_slots=3, num_blocks=9,
                          max_model_len=32)
    assert eng.sched.n_preempted > 0
    held_to_the_reference(params, cfg, requests, out)


def test_idle_slots_and_padding_rows_leave_their_state_as_it_was():
    cfg = tiny()
    params = weights(cfg)
    eng = ServeEngine(params, cfg, ServeConfig(
        decode_slots=4, block_size=4, prefill_chunk=8, max_model_len=64, decode_interval=2))
    marked = tuple(jnp.full(x.shape, 0.5 + i, x.dtype) for i, x in enumerate(eng._kv))
    eng._kv = jax.device_put(marked)
    requests = some_requests(cfg, ((21, 6), (5, 4), (13, 5)), seed=11)
    for i, (prompt, n) in enumerate(requests):
        eng.submit(prompt, n, req_id=i)
    while eng.sched.has_work():
        eng.step(0.0)
    _, state, tail = eng._kv
    np.testing.assert_array_equal(state[:, 3], marked[1][:, 3])
    np.testing.assert_array_equal(tail[:, 3], marked[2][:, 3])
    assert not np.array_equal(state[:, 0], marked[1][:, 0])
    out = sorted(eng.results, key=lambda r: r["id"])
    eng.close()
    held_to_the_reference(params, cfg, requests, out)


def test_a_decode_step_through_the_kernel_serves_what_the_plain_path_serves(monkeypatch):
    """A two-slot engine at widths the decode kernel takes (one period, 8 heads
    of 128 x 128), through admission, a slot's next request and a preemption:
    once as every CPU run serves it (gather, the step rule, scatter) and once
    with the decode steps through `kda_step_pooled` (the Pallas interpreter).
    The same tokens, and the same state pool to float32 rounding."""
    from picotron_tpu.serve import paged_cache

    cfg = tiny(num_hidden_layers=4, layer_types=(KDA, KDA, KDA, F), linear_key_head_dim=128,
               linear_value_head_dim=128, linear_num_key_heads=8, linear_num_value_heads=8)
    params = weights(cfg)
    requests = some_requests(cfg, ((14, 9), (11, 8), (9, 7), (5, 4)), seed=3)
    calls = []

    def served(kernel: bool):
        jax.clear_caches()  # the engines of one process share their compiled programs
        if kernel:
            sound = paged_cache.gated_delta_step_pooled
            monkeypatch.setattr(paged_cache, "gated_delta_kernel_suits", lambda s, pool: s == 1)
            monkeypatch.setattr(paged_cache, "gated_delta_step_pooled",
                                lambda *a, **k: calls.append(a[3].shape) or sound(*a, **k))
        eng = ServeEngine(params, cfg, ServeConfig(
            decode_slots=2, block_size=4, prefill_chunk=8, max_model_len=32, decode_interval=2,
            num_blocks=9))
        eng._kv = jax.device_put(tuple(jnp.full(x.shape, 0.25 + i, x.dtype)
                                       for i, x in enumerate(eng._kv)))
        for i, (prompt, n) in enumerate(requests):
            eng.submit(prompt, n, req_id=i)
        while eng.sched.has_work():
            eng.step(0.0)
        pool = np.asarray(eng._kv[1])
        eng.close()
        assert eng.pool.in_use == 0 and eng.sched.n_preempted > 0
        return sorted(eng.results, key=lambda r: r["id"]), pool

    try:
        plain_out, plain_pool = served(False)
        assert not calls
        kernel_out, kernel_pool = served(True)
    finally:
        jax.clear_caches()  # no later engine may meet the programs traced here
    # traced once a mixer (the dense stack's one, the expert stack's two), with
    # the decay a channel: [rows, heads, d_k]
    assert calls == [(2, 8, 128)] * 3
    assert [r["tokens"] for r in kernel_out] == [r["tokens"] for r in plain_out]
    np.testing.assert_allclose(kernel_pool, plain_pool, rtol=0, atol=1e-5)
    held_to_the_reference(params, cfg, requests, kernel_out)


def test_prefill_chunks_through_the_kernel_serve_the_references_tokens(monkeypatch):
    """A one-slot engine at widths the chunk kernel takes (one period, 2 heads
    of 128 x 128, chunks of 64), a prompt of two chunks with a part chunk at
    its end, then the slot's next request over the state its predecessor
    left (a rung's pad rows: the test above): the prefill chunks go through
    `kda_chunk_pooled` (the Pallas interpreter; `recur` is told the kernel
    suits), and every served token is the reference's at its logit."""
    from picotron_tpu.serve import paged_cache

    cfg = tiny(num_hidden_layers=4, layer_types=(KDA, KDA, KDA, F), linear_key_head_dim=128,
               linear_value_head_dim=128, linear_num_key_heads=2, linear_num_value_heads=2)
    params = weights(cfg)
    requests = some_requests(cfg, ((100, 3), (40, 2)), seed=3)
    calls = []
    sound = paged_cache.kda_chunk_pooled
    monkeypatch.setattr(paged_cache, "kda_chunk_suits",
                        lambda s, heads, pool: s > 1 and s % 64 == 0)
    monkeypatch.setattr(paged_cache, "kda_chunk_pooled",
                        lambda *a, **k: calls.append(a[3].shape) or sound(*a, **k))
    jax.clear_caches()  # the engines of one process share their compiled programs
    try:
        eng = ServeEngine(params, cfg, ServeConfig(
            decode_slots=1, block_size=16, prefill_chunk=64, max_model_len=128,
            decode_interval=2))
        eng._kv = jax.device_put(tuple(jnp.full(x.shape, 0.25 + i, x.dtype)
                                       for i, x in enumerate(eng._kv)))
        for i, (prompt, n) in enumerate(requests):
            eng.submit(prompt, n, req_id=i)
        while eng.sched.has_work():
            eng.step(0.0)
        eng.close()
    finally:
        jax.clear_caches()  # no later engine may meet the programs traced here
    assert eng.pool.in_use == 0
    # traced once a mixer and rung (the dense stack's one, the expert stack's
    # two), with the decay a channel: [rows, 64, heads, d_k]
    assert calls == [(1, 64, 2, 128)] * 3
    held_to_the_reference(params, cfg, requests, sorted(eng.results, key=lambda r: r["id"]))


def test_the_cache_pairs_a_state_pool_with_a_latent_pool():
    cfg = tiny()
    cache = init_serve_cache(cfg, ServeConfig(block_size=4), 3, 8, 16)
    assert type(cache) is HybridLatentPagedCache
    assert [p.shape for p in cache.pools] == [(2, 8, 4, 128), (6, 3, 4, 8, 8), (6, 3, 288)]
    assert cache.table_specs == ((4, 8), (1, 3)) and cache.scheduler_args(cfg) == {}
    assert [r.tolist() for r in cache.slot_rows(None, cfg, 1)] == [[8] * 4, [3]]
    row_bytes = 4 * 8 * 8 * 4 + 3 * 96 * 4
    assert cache.state_row_bytes() == row_bytes
    # a rung of 4 rows, two of them pads; the latent keys over the 2 full layers
    assert cache.prefill_counts([(0, 8), (8, 5)], cfg, rows=4) == dict(
        attn_sublayers=2, latent_keys=2 * (16 + 16), state_rows=12,
        state_bytes=2 * 12 * row_bytes, state_resets=6, chunk_rows_batch=24,
        chunk_rows_idle=12)
    assert cache.decode_counts([(5, 2), (9, 2)], cfg) == dict(
        attn_sublayers=2, kv_blocks=5, latent_blocks=10, state_rows=12,
        state_bytes=2 * 12 * row_bytes, state_resets=0, state_rows_batch=18,
        state_rows_idle=6)
    with pytest.raises(ValueError, match="kda or mamba layers is served from one device"):
        init_serve_cache(cfg, ServeConfig(block_size=4), 3, 8, 16, sharded=True)


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_benchmarks_reuse_phase_reads_the_state_the_slots_hold():
    """`benchmark/runners/serve_reference_reuse.py`'s second phase on the tiny
    model, whose state pool is the cache's second field here: every slot used
    twice, the second round's logits, the first (dense-stack) mixer's state
    rows against the reference's carried state."""
    phase = _bench_module("runners", "serve_reference_reuse")
    mellum = _bench_module("runners", "serve_mellum2")
    cfg = tiny()
    params = weights(cfg)
    spec = dict(first_prompt_tokens=37, prompt_tokens=11, output_tokens=4, state_pool="state",
                limits=dict(reuse_logit_err_mean=0.0, state_err=0.0, state_bf16_share=0.0))
    eng = ServeEngine(params, cfg, ServeConfig(decode_slots=3, block_size=4, prefill_chunk=8,
                                               max_model_len=64, decode_interval=2))
    with jax.default_matmul_precision("highest"):
        got, note = phase.read(eng, reference, mellum, params, published(cfg), spec, 7,
                               cfg.vocab_size)
    assert "with 5 served, slots [0, 1, 2]" in note
    assert got["reuse_logit_err_mean"] < 1e-4 and got["state_err"] < 1e-4
    assert got["state_bf16_share"] < 0.01


# ---------------------------------------------------------------------------
# (d) the held share of the experts (the guide's section 4)
# ---------------------------------------------------------------------------


def test_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 8 shares (2 of 16 experts each) plus the shared
    expert ONCE are the uncut reference's whole expert layer, under the
    sigmoid router with its selection bias, renormalised and scaled."""
    cfg = tiny()
    params = weights(cfg)
    lp = layer_leaves(params["layers"], cfg.stacks[1].kinds, 1)
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
                scoring="sigmoid", scale=2.446, expert_first=first, bias=lp["router_bias"])
            total = total + routed
            seen += int(counts[2])
        assert seen == 2 * 9 * 2  # every pick lands on exactly one share
        flat = x.reshape(18, -1)
        want = (reference.routed(flat, lp, m) + reference.shared(flat, lp, m)).reshape(x.shape)
    np.testing.assert_allclose(total, want, atol=2e-5)


# ---------------------------------------------------------------------------
# (e) configuration: the published keys, the counts, the benchmark's file
# ---------------------------------------------------------------------------

HF = {  # the catalog row's `config`, as published
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


def test_hf_reader_round_trips_the_published_keys():
    got = ModelConfig(**model_config_from_hf_json(HF))
    got.validate()
    want = ModelConfig(**resolve_preset("Kimi-Linear-48B-A3B-Instruct"))
    assert got == ModelConfig(**{**want.__dict__, "name": got.name})
    assert got.layer_kinds == (KDA, KDA, KDA, F) * 6 + (KDA, KDA, F) and got.kda and got.mla
    assert (got.q_lora_rank, got.head_dim, got.gdn_channels) == (0, 72, 12288)
    assert (got.recurrent_layers, got.attention_sublayers) == (20, 7)
    assert [(st.name, st.layers, st.block.mlp) for st in got.stacks] == [
        ("dense_layers", 1, "dense"), ("layers", 26, "experts")]
    # a model cut in depth keeps the published lists: the later entries name no layer
    cut = ModelConfig(**model_config_from_hf_json({**HF, "num_hidden_layers": 12}))
    assert cut.layer_kinds == (KDA, KDA, KDA, F) * 3
    with pytest.raises(ValueError, match="name each of the layers"):
        model_config_from_hf_json({**HF, "linear_attn_config": {
            **HF["linear_attn_config"], "kda_layers": [1, 2, 3]}})
    with pytest.raises(ValueError, match="expert groups"):
        model_config_from_hf_json({**HF, "num_expert_group": 8})


# ISSUE 57's arithmetic, by part
MIXER = (3 * 2304 * 4096 + 3 * 4096 * 4 + 2304 * 128 + 128 * 4096 + 4096 + 32 + 2304 * 32
         + 2304 * 128 + 128 * 4096 + 128 + 4096 * 2304)
ATTENTION = 2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256 + 4096 * 2304
EXPERT = 3 * 2304 * 1024
BESIDE = 2304 * 256 + 256 + EXPERT + 4608  # router + bias, shared expert, two norms
DENSE = 3 * 2304 * 9216


def test_published_sizes_count_49b_and_the_benchmarks_cut():
    assert (MIXER, ATTENTION, BESIDE) == (39_514_272, 29_114_880, 7_672_576)
    full = ModelConfig(**resolve_preset("Kimi-Linear-48B-A3B-Instruct"))
    assert num_params(full) == 49_122_681_728
    assert 2.5e9 < num_params(full, active_only=True) < 4e9
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b-12l-ep8.json")) as f:
        c = json.load(f)
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")}).model
    first = MIXER + DENSE + 4608
    rest = 8 * MIXER + 3 * ATTENTION + 11 * BESIDE
    want = first + rest + 11 * 32 * EXPERT + 2 * 20480 * 2304 + 2304
    assert (first, rest, want) == (103_219_872, 487_857_152, 3_176_867_744)
    assert num_params(cfg) == c["parameters"] == want
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    assert param_count(shapes) == want
    lay = {n: x.shape for n, x in shapes["layers"].items()}
    assert lay["kda_qkv"] == (8, 2304, 12288) and lay["kda_conv"] == (8, 12288, 4)
    assert lay["kda_f_a"] == (8, 2304, 128) and lay["kda_f_b"] == (8, 128, 4096)
    assert lay["kda_dt_bias"] == (8, 4096) and lay["kda_A_log"] == (8, 32)
    assert lay["q_b"] == (3, 2304, 6144) and lay["kv_a"] == (3, 2304, 576)
    assert lay["kv_b"] == (3, 512, 8192) and lay["o"] == (3, 4096, 2304) and "q_a" not in lay
    assert lay["router"] == (11, 2304, 256) and lay["router_bias"] == (11, 256)
    assert lay["w_gate"] == (11, 32, 2304, 1024) and lay["shared_gate"] == (11, 2304, 1024)
    dense = {n: x.shape for n, x in shapes["dense_layers"].items()}
    assert dense["gate"] == (1, 2304, 9216) and dense["kda_qkv"] == (1, 2304, 12288)
    assert "q_b" not in dense and "router" not in dense
    # every number of the catalog row under its own key, but for `reduced`
    for key, value in HF.items():
        assert c[key] == value or key in c["reduced"], key
    assert set(c["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size",
                                 "model_max_length"}
    # the reference reads the same model from the file's published keys
    for key, value in reference.as_program({k: c[k] for k in reference.KEYS}).items():
        assert getattr(cfg, key) == value, key
    # the state beside the weights: 2 MiB + 144 KiB a slot and mixer, float32 both
    cache = jax.eval_shape(lambda: init_hybrid_latent_cache(cfg, 16, 32, 2, 8))
    assert cache.state_row_bytes() == 2_097_152 + 147_456
    assert cache.state.shape == (9, 2, 32, 128, 128) and cache.kv.shape == (3, 16, 32, 640)


# ---------------------------------------------------------------------------
# (f) what is refused by name
# ---------------------------------------------------------------------------


def sections(**over):
    base = dict(distributed=DistributedConfig(), model=tiny(attn_impl="reference"),
                training=TrainingConfig(grad_engine="ad"), serve=ServeConfig())
    return Config(**{**base, **over})


REFUSALS = [
    (dict(model=tiny(attn_impl="flash")), "attn_impl='flash'"),
    (dict(training=TrainingConfig(grad_engine="fused")), "grad_engine='fused'"),
    (dict(distributed=DistributedConfig(tp_size=2)), "tensor parallelism"),
    (dict(distributed=DistributedConfig(pp_size=2)), "pipeline parallelism"),
    (dict(distributed=DistributedConfig(ep_size=2)), "expert parallelism"),
    (dict(distributed=DistributedConfig(cp_size=2)), "context parallelism"),
]  # (a fleet refuses every model with experts before it asks for its layers)


@pytest.mark.parametrize("over,message", REFUSALS, ids=[m for _, m in REFUSALS])
def test_validate_refuses_by_name(over, message):
    sections().validate()
    with pytest.raises(ValueError) as e:
        sections(**over).validate()
    assert "kda" in str(e.value) and message in str(e.value)


@pytest.mark.parametrize("over,message", [
    (dict(linear_num_value_heads=8), "as many as the key heads"),
    (dict(layer_types=None), "layer_types with them"),
    (dict(layer_types=(KDA, KDA, F, "sliding_attention") * 2, sliding_window=8),
     "kda layers are built beside"),
    (dict(kv_lora_rank=0, qk_nope_head_dim=0, qk_rope_head_dim=0, v_head_dim=0,
          mla_use_nope=False), "kda layers are built beside"),
    (dict(q_lora_rank=-1), "0 for no query"),
    (dict(layer_types=(KDA, KDA, "dense", F) * 2), "layer_types entries must be"),
])
def test_model_validate_messages(over, message):
    with pytest.raises(ValueError, match=message):
        tiny(**over).validate()
