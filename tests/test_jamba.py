"""Jamba's mechanisms at the tiny preset (`debug-tiny-jamba`: two periods of
three Mamba-1 mixers, one multi-query attention without positions and two
more mixers; a state of 4 a channel, a step rank of 3, one K/V head under 4
query heads) on the CPU, float32: the selective scan beside the paged pool,
the state a slot, the unrotated attention. The program is held to
`benchmark/reference_jamba.py` (plain float32 jax.numpy, the recurrence token
by token, no cache), which imports nothing from it. Seeded weights
throughout. The compiled programs are held by tests/test_chip_compile.py."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import (
    SSM, Config, DistributedConfig, ModelConfig, ServeConfig, TrainingConfig,
    config_from_dict, model_config_from_hf_json, num_params, resolve_preset,
)
from picotron_tpu.generate import generate, init_cache
from picotron_tpu.models.llama import (
    forward, held_conv, held_scan, init_params, loss_fn, mamba_mixer, mamba_start, param_count,
)
from picotron_tpu.ops.gated_delta import causal_conv
from picotron_tpu.ops.selective_scan import (
    conv_step_pooled, scan_segment, selective_scan, selective_scan_chunk_pooled,
    selective_scan_step, selective_scan_step_pooled,
)
from picotron_tpu.serve import ServeEngine
from picotron_tpu.serve.paged_cache import init_hybrid_cache, init_serve_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")
# loaded by its path: `benchmark/` is not put on sys.path, where its own
# `tests` package would shadow this one
_spec = importlib.util.spec_from_file_location(
    "reference_jamba", os.path.join(ROOT, "benchmark", "reference_jamba.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)
reference.CHUNK = 8  # the probe's chunk-boundary controls, at the tests' chunk

F = "full_attention"
PERIOD = (SSM,) * 3 + (F,) + (SSM,) * 2


def tiny(**over) -> ModelConfig:
    return ModelConfig(dtype="float32", **{**resolve_preset("debug-tiny-jamba"), **over})


def weights(cfg, seed=1):
    p = init_params(cfg, jax.random.key(seed))
    layers = dict(p["layers"])
    # norm weights and D that are not at their start, so that a norm skipped
    # or a term dropped shows
    for j, n in enumerate(("input_norm", "post_norm", "ssm_dt_norm", "ssm_b_norm", "ssm_c_norm",
                           "ssm_D")):
        layers[n] = layers[n] + 0.1 * jax.random.normal(jax.random.key(seed + 50 + j),
                                                        layers[n].shape)
    # a trained model's embedding scale, so that the layers show in the logits
    return dict(p, embedding=p["embedding"] * 0.1, layers=layers)


def published(cfg: ModelConfig) -> dict:
    """The keys `reference_jamba` reads, from a ModelConfig."""
    at = [i for i, k in enumerate(cfg.layer_kinds) if k == F]
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size, num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        attn_layer_period=(at + [at[0] + cfg.num_hidden_layers])[1] - at[0],
        attn_layer_offset=at[0],
        mamba_d_state=cfg.mamba_d_state, mamba_d_conv=cfg.mamba_d_conv,
        mamba_expand=cfg.mamba_expand, mamba_dt_rank=cfg.mamba_dt_rank,
        mamba_conv_bias=cfg.mamba_conv_bias, mamba_proj_bias=cfg.mamba_proj_bias,
        rms_norm_eps=cfg.rms_norm_eps, tie_word_embeddings=True, num_experts=1)


def ref_logits(params, cfg, ids, rows=None, **faults):
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.arange(len(ids)) if rows is None else jnp.asarray(list(rows))
    return np.asarray(reference.logits_at(params, ids, rows, published(cfg), **faults))


# ---------------------------------------------------------------------------
# (a) forward() against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("length", [1, 5, 23])
def test_forward_matches_the_reference(length):
    cfg = tiny()
    assert cfg.layer_kinds == PERIOD * 2 and cfg.ssm and cfg.recurrent and not cfg.gdn
    assert reference.kinds_of(published(cfg)) == cfg.layer_kinds
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(2), (2, length), 0, cfg.vocab_size)
    got = np.asarray(forward(params, ids, cfg))
    for b in range(2):
        np.testing.assert_allclose(got[b], ref_logits(params, cfg, ids[b]), atol=5e-4)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_control_of_the_reference_moves_the_logits(fault):
    cfg = tiny()
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(4), (19,), 0, cfg.vocab_size)
    exact = ref_logits(params, cfg, ids)
    moved = np.abs(ref_logits(params, cfg, ids, **{fault: True}) - exact).max()
    # rounding controls move little, structural ones much; none moves nothing
    assert moved > (1e-4 if fault in ("bf16_state", "bf16_acts") else 1e-2), moved


def test_training_is_refused_by_name():
    cfg = tiny()
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="mamba layers"):
        loss_fn(weights(cfg), ids, ids, cfg)


# ---------------------------------------------------------------------------
# (b) the recurrence and the convolution
# ---------------------------------------------------------------------------


def scan_inputs(rows=3, s=11, di=16, n=4, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    u = jax.random.normal(ks[0], (rows, s, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, s, di)) - 2.0)
    b, c = jax.random.normal(ks[2], (rows, s, n)), jax.random.normal(ks[3], (rows, s, n))
    a = -jnp.exp(jax.random.normal(ks[4], (n, di)))
    state = jax.random.normal(ks[5], (rows, n, di))  # a non-zero start
    return u, dt, b, c, a, state


@pytest.mark.parametrize("seg", [1, 4, 11])
def test_segment_scan_matches_token_by_token_with_pad_positions(seg):
    """Segments of `seg` positions, the state handed from one to the next, the
    last one part padding (dt = 0 there: inert), against the rule applied a
    token at a time in numpy from the same non-zero state."""
    u, dt, b, c, a, state = scan_inputs()
    s = u.shape[1]
    want_y, st = [], np.asarray(state, np.float64)
    for t in range(s):
        x = [np.asarray(v[:, t], np.float64) for v in (u, dt, b, c)]
        st = np.exp(x[1][:, None, :] * np.asarray(a)) * st + (x[1] * x[0])[:, None, :] * x[2][:, :, None]
        want_y.append(np.einsum("rnd,rn->rd", st, x[3]))
    got_y, carried = [], state
    for start in range(0, s, seg):
        n_real = min(seg, s - start)
        sl = [jnp.pad(v[:, start:start + n_real], ((0, 0), (0, seg - n_real), (0, 0)))
              for v in (u, dt, b, c)]  # dt padded with zeros: inert
        y, carried = scan_segment(*sl, a, carried)
        got_y.append(y[:, :n_real])
    np.testing.assert_allclose(np.concatenate(got_y, 1), np.stack(want_y, 1), atol=1e-4)
    np.testing.assert_allclose(carried, st, atol=1e-4)


def test_the_step_is_the_scan_of_one_position():
    u, dt, b, c, a, state = scan_inputs(s=1)
    y1, s1 = selective_scan_step(u[:, 0], dt[:, 0], b[:, 0], c[:, 0], a, state)
    y2, s2 = selective_scan(u, dt, b, c, a, state)
    np.testing.assert_allclose(y1, y2[:, 0], atol=1e-6)
    np.testing.assert_allclose(s1, s2, atol=1e-6)


def test_the_pooled_step_updates_the_live_rows_alone():
    """`selective_scan_step_pooled` in the Pallas interpreter against the plain
    step: a live row, a fresh one (zeros whatever the pool holds), an idle
    one and an unmapped one; every bit outside the worked rows as it was."""
    rows, di, n, slots = 4, 128, 8, 5
    u, dt, b, c, a, _ = scan_inputs(rows=rows, s=1, di=di, n=n, seed=3)
    pool = jax.random.normal(jax.random.key(9), (3, slots, n, di))
    slot = jnp.asarray([3, 0, 1, slots], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    fresh = jnp.asarray([False, True, False, False])
    y, new = selective_scan_step_pooled(u[:, 0], dt[:, 0], b[:, 0], c[:, 0], a, pool, 1, slot,
                                        live, fresh, interpret=True)
    start = jnp.stack([pool[1, 3], jnp.zeros((n, di))])
    want_y, want_s = selective_scan_step(u[:2, 0], dt[:2, 0], b[:2, 0], c[:2, 0], a, start)
    np.testing.assert_allclose(y[:2], want_y, atol=1e-5)
    assert not np.asarray(y[2:]).any()
    np.testing.assert_allclose(new[1, 3], want_s[0], atol=1e-5)
    np.testing.assert_allclose(new[1, 0], want_s[1], atol=1e-5)
    untouched = np.ones((3, slots), bool)
    untouched[1, 3] = untouched[1, 0] = False
    np.testing.assert_array_equal(np.asarray(new)[untouched], np.asarray(pool)[untouched])


@pytest.mark.parametrize("di", [128, 384])
def test_the_pooled_chunk_updates_the_rows_with_real_positions_alone(di):
    """`selective_scan_chunk_pooled` in the Pallas interpreter against the
    plain scan: a whole row, a fresh row that is part padding, a pad row and
    an unmapped one; y where a real position is, every bit of the pool
    outside the worked rows as it was."""
    rows, s, n, slots = 4, 16, 8, 6
    u, dt, b, c, a, _ = scan_inputs(rows=rows, s=s, di=di, n=n, seed=5)
    pool = jax.random.normal(jax.random.key(9), (3, slots, n, di))
    slot = jnp.asarray([4, 1, 0, slots], jnp.int32)
    n_valid = jnp.asarray([16, 5, 0, 9], jnp.int32)
    fresh = jnp.asarray([False, True, False, False])
    dt = jnp.where((jnp.arange(s)[None, :] < n_valid[:, None])[..., None], dt, 0.0)
    y, new = selective_scan_chunk_pooled(u, dt, b, c, a, pool, 1, slot, n_valid, fresh,
                                         interpret=True)
    start = jnp.stack([pool[1, 4], jnp.zeros((n, di))])
    want_y, want_s = selective_scan(u[:2], dt[:2], b[:2], c[:2], a, start)
    np.testing.assert_allclose(y[0], want_y[0], atol=1e-5)
    np.testing.assert_allclose(y[1, :5], want_y[1, :5], atol=1e-5)
    np.testing.assert_allclose(new[1, 4], want_s[0], atol=1e-5)
    np.testing.assert_allclose(new[1, 1], want_s[1], atol=1e-5)
    untouched = np.ones((3, slots), bool)
    untouched[1, 4] = untouched[1, 1] = False
    np.testing.assert_array_equal(np.asarray(new)[untouched], np.asarray(pool)[untouched])


def test_convolution_adds_its_bias_and_carries_its_tail():
    ks = jax.random.split(jax.random.key(0), 4)
    x, w = jax.random.normal(ks[0], (2, 9, 6)), jax.random.normal(ks[1], (6, 4))
    bias, tail = jax.random.normal(ks[2], (6,)), jax.random.normal(ks[3], (2, 3, 6))
    n_valid = jnp.asarray([9, 4])
    y, new_tail = causal_conv(x, tail, w, n_valid, bias)
    plain, same_tail = causal_conv(x, tail, w, n_valid)
    full = np.concatenate([tail, x], 1)
    want = sum(full[:, j:j + 9] * np.asarray(w)[:, j] for j in range(4)) + np.asarray(bias)
    np.testing.assert_allclose(y, jax.nn.silu(want), atol=1e-5)
    assert np.abs(np.asarray(y - plain)).max() > 0.1
    np.testing.assert_array_equal(new_tail, same_tail)
    np.testing.assert_array_equal(new_tail[0], x[0, 6:])
    np.testing.assert_array_equal(new_tail[1], full[1, 4:7])


def test_the_mixer_makes_a_pad_position_inert():
    cfg = tiny()
    lp = {n: w[0] for n, w in weights(cfg)["layers"].items() if n.startswith("ssm_")}
    h = jax.random.normal(jax.random.key(1), (2, 5, cfg.hidden_size))
    state, tail = mamba_start(cfg, 2)
    state = state + 0.5
    live = jnp.asarray([[True] * 5, [True, True, False, False, False]])
    _, (carried, new_tail) = mamba_mixer(h, lp, cfg, held_conv, held_scan, (state, tail), live)
    _, (short, short_tail) = mamba_mixer(h[1:, :2], lp, cfg, held_conv, held_scan,
                                         (state[1:], tail[1:]), live[1:, :2])
    np.testing.assert_allclose(carried[1], short[0], atol=1e-6)
    np.testing.assert_allclose(new_tail[1], short_tail[0], atol=1e-6)


def test_the_pooled_convolution_updates_the_live_rows_alone():
    """`conv_step_pooled` in the Pallas interpreter against `causal_conv` at
    one position: a live row, a fresh one (a tail of zeros whatever the pool
    holds), an idle one and an unmapped one; every bit of the tail pool
    outside the worked rows as it was."""
    rows, c, k, slots = 4, 256, 4, 6
    ks = jax.random.split(jax.random.key(0), 4)
    x, w = jax.random.normal(ks[0], (rows, c)), jax.random.normal(ks[1], (c, k))
    bias = jax.random.normal(ks[2], (c,))
    pool = jax.random.normal(ks[3], (3, slots, (k - 1) * c // 128, 128))
    slot = jnp.asarray([4, 1, 0, slots], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    fresh = jnp.asarray([False, True, False, False])
    y, new = conv_step_pooled(x, w, bias, pool, 1, slot, live, fresh, interpret=True)
    tails = jnp.stack([pool[1, 4], jnp.zeros(pool.shape[2:])]).reshape(2, k - 1, c)
    want_y, want_t = causal_conv(x[:2, None], tails, w, jnp.asarray([1, 1]), bias)
    np.testing.assert_allclose(y[:2], want_y[:, 0], atol=1e-5)
    assert not np.asarray(y[2:]).any()
    np.testing.assert_array_equal(new[1, 4].ravel(), want_t[0].ravel())
    np.testing.assert_array_equal(new[1, 1].ravel(), want_t[1].ravel())
    untouched = np.ones((3, slots), bool)
    untouched[1, 4] = untouched[1, 1] = False
    np.testing.assert_array_equal(np.asarray(new)[untouched], np.asarray(pool)[untouched])
    # without a bias
    plain, _ = conv_step_pooled(x, w, None, pool, 1, slot, live, fresh, interpret=True)
    np.testing.assert_allclose(plain[:1], causal_conv(x[:1, None], tails[:1], w,
                                                      jnp.asarray([1]))[0][:, 0], atol=1e-5)


def test_the_seeded_steps_give_a_state_a_memory():
    """A_log = log(1..N) a channel, D = 1, b_dt the inverse softplus of a step
    log-uniform in [0.001, 0.1]: exp(dt A) keeps a (channel, state) pair's
    content for tens to thousands of positions, neither everything nor
    nothing."""
    cfg = tiny(mamba_d_state=16)
    drawn = [init_params(cfg, jax.random.key(seed))["layers"] for seed in range(3)]
    dt = np.concatenate([np.asarray(jax.nn.softplus(x["ssm_dt_bias"])).ravel() for x in drawn])
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    a = np.exp(np.asarray(drawn[0]["ssm_A_log"]))
    np.testing.assert_allclose(a[0, 0], np.arange(1, 17), rtol=1e-6)
    assert not np.asarray(drawn[0]["ssm_D"] != 1).any()
    kept = np.exp(-dt[:, None] * np.arange(1, 17)[None, :]).ravel()
    lo, hi = np.quantile(kept, [0.05, 0.95])
    assert 0.3 < lo < 0.7 and 0.99 < hi < 0.9999 and np.median(kept) > 0.85


# ---------------------------------------------------------------------------
# (c) generate() and ServeEngine against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("prompt", [1, 12])
def test_generate_matches_the_reference(prompt):
    cfg = tiny()
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(3), (2, prompt), 0, cfg.vocab_size)
    out = np.asarray(generate(params, cfg, ids, 6))
    for b in range(2):
        want = ref_logits(params, cfg, out[b], rows=range(prompt - 1, prompt + 5))
        assert (out[b, prompt:] == want.argmax(-1)).all()
    cache = init_cache(cfg, 2, 18)
    assert cache.k.shape == (2, 2, 18, 1, 16) and cache.state.shape == (10, 2, 4, 128)
    assert cache.tail.shape == (10, 2, 3, 128) and cache.state.dtype == jnp.float32


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


@pytest.mark.parametrize("interval", [1, 4])
def test_engine_matches_the_reference(interval):
    """Prefill in several chunks of 8 (the state handed from dispatch to
    dispatch through the pools), then decode a step a slot and mixer, one
    dispatch ahead: the logit of EVERY decoded position against the
    reference's full forward pass under teacher forcing."""
    cfg = tiny()
    params = weights(cfg)
    requests = some_requests(cfg, ((37, 8), (6, 5), (21, 7), (45, 4)))
    eng, out = run_engine(params, cfg, requests, decode_interval=interval)
    assert len(out) == 4 and eng.stats["decode_compiles"] <= 1  # the one decode program
    held_to_the_reference(params, cfg, requests, out)
    # the pools: the attentions' K/V alone, a state and a tail a slot and mixer
    k, v, state, tail = eng._kv
    assert k.shape[:2] == (1, 2) and cfg.layer_kinds.count(F) == 2
    assert state.shape == (10, 2, 4, 128) and state.dtype == jnp.float32
    assert tail.shape == (10, 2, 3, 128) and tail.dtype == jnp.float32


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


def test_the_cache_is_the_hybrid_one_and_counts_what_a_dispatch_moves():
    cfg = tiny()
    cache = init_serve_cache(cfg, ServeConfig(block_size=4), 3, 8, 16)
    assert type(cache).__name__ == "HybridPagedCache"
    assert [p.shape for p in cache.pools] == [
        (1, 2, 8, 4, 16), (1, 2, 8, 4, 16), (10, 3, 4, 128), (10, 3, 3, 128)]
    assert cache.table_specs == ((4, 8), (1, 3))
    row_bytes = 4 * 128 * 4 + 3 * 128 * 4
    assert cache.state_row_bytes() == row_bytes
    assert cache.prefill_counts([(0, 8), (8, 5)], cfg, rows=4) == dict(
        state_rows=20, state_bytes=2 * 20 * row_bytes, state_resets=10,
        chunk_rows_batch=40, chunk_rows_idle=20, scan_tokens=130)
    assert cache.decode_counts([(5, 2), (9, 2)], cfg) == dict(
        kv_blocks=5, kv_blocks_banded=10, state_rows=20, state_bytes=2 * 20 * row_bytes,
        state_resets=0, state_rows_batch=30, state_rows_idle=10)
    with pytest.raises(ValueError, match="mamba layers is served from one device"):
        init_serve_cache(cfg, ServeConfig(block_size=4), 3, 8, 16, sharded=True)


@pytest.mark.parametrize("which", ["step", "chunk", "conv"])
def test_a_dispatch_through_its_kernel_serves_what_the_plain_path_serves(which, monkeypatch):
    """A two-slot engine at widths the kernels take (one period, a state of
    8 x 128, chunks of 8 positions), driven through admission, a slot's second
    and third request, prompts whose last chunk is part padding, a preemption
    and with it a decode dispatch in flight for a request that has left: once
    as every CPU run serves it (gather, the plain rule, scatter) and once with
    the decode steps through `selective_scan_step_pooled`, the prefill chunks
    through `selective_scan_chunk_pooled` or the decode steps' convolutions
    through `conv_step_pooled` (the Pallas interpreter). The same tokens;
    after every engine step the same state pool (the tail pool for the
    convolution) to float32 rounding, and a row the plain path left alone in
    that step is left alone by the kernel too, to the bit."""
    from picotron_tpu.serve import paged_cache

    cfg = tiny(num_hidden_layers=3, layer_types=(SSM, F, SSM), mamba_d_state=8)
    params = weights(cfg)
    requests = some_requests(cfg, ((14, 9), (11, 8), (9, 7), (5, 4)), seed=3)
    calls = []
    suits, kernel = {"step": ("ssm_kernel_suits", "selective_scan_step_pooled"),
                     "chunk": ("ssm_chunk_suits", "selective_scan_chunk_pooled"),
                     "conv": ("conv_kernel_suits", "conv_step_pooled")}[which]
    held = 3 if which == "conv" else 2  # the pool the kernel works on: tail | state

    def served(through_kernel: bool):
        jax.clear_caches()  # the engines of one process share their compiled programs
        if through_kernel:
            sound = getattr(paged_cache, kernel)
            monkeypatch.setattr(paged_cache, suits,
                                lambda s, pool, *_: (s == 1) == (which != "chunk"))
            monkeypatch.setattr(paged_cache, kernel,
                                lambda *a, **k: calls.append(a[0].shape) or sound(*a, **k))
        eng = ServeEngine(params, cfg, ServeConfig(
            decode_slots=2, block_size=4, prefill_chunk=8, max_model_len=32, decode_interval=2,
            num_blocks=9))
        # state in every row, as earlier requests would have left it
        eng._kv = jax.device_put(tuple(jnp.full(x.shape, 0.25 + i, x.dtype)
                                       for i, x in enumerate(eng._kv)))
        for i, (prompt, n) in enumerate(requests):
            eng.submit(prompt, n, req_id=i)
        pools = [np.asarray(eng._kv[held])]
        while eng.sched.has_work():
            eng.step(0.0)
            pools.append(np.asarray(eng._kv[held]))
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
    # traced a mixer of the one period, in the one program (a prefill rung each)
    assert calls and set(calls) <= ({(1, 8, 128), (2, 8, 128)} if which == "chunk"
                                    else {(2, 128)})
    assert [r["tokens"] for r in kernel_out] == [r["tokens"] for r in plain_out]
    assert len(plain_out) == 4 and len(kernel_pools) == len(plain_pools) > 8
    for before, after, got_before, got in zip(plain_pools, plain_pools[1:], kernel_pools,
                                              kernel_pools[1:]):
        np.testing.assert_allclose(got, after, rtol=0, atol=1e-5)
        left = ~np.any((after != before).reshape(*after.shape[:2], -1), axis=2)  # [mixer, slot]
        np.testing.assert_array_equal(got[left], got_before[left])
    assert any(np.any(a != b) for a, b in zip(kernel_pools, kernel_pools[1:]))
    held_to_the_reference(params, cfg, requests, kernel_out)


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
    model, as it is: every slot used twice, the second round's logits, the
    first mixer's state rows against `reference_jamba.first_state` (in the
    pool's own layout), the share of the pool a bfloat16 holds."""
    from picotron_tpu.serve import paged_cache

    phase = _bench_module("runners", "serve_reference_reuse")
    mellum = _bench_module("runners", "serve_mellum2")
    cfg = tiny()
    params = weights(cfg)
    if wrong:
        jax.clear_caches()  # the engines of one process share their compiled programs
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
    assert "with 5 served, slots [0, 1, 2]" in note and set(got) == set(REUSE["limits"])
    if wrong is None:
        assert got["reuse_logit_err_mean"] < 1e-4 and got["state_err"] < 1e-4
        assert got["state_bf16_share"] < 0.01
    elif wrong == "state_kept":
        assert got["reuse_logit_err_mean"] > 0.01 and got["state_err"] > 0.01
    else:
        assert got["state_bf16_share"] > 0.99 and 1e-4 < got["state_err"] < 0.05


# ---------------------------------------------------------------------------
# (e) the published sizes
# ---------------------------------------------------------------------------

# the catalog row's `config`
# (https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json)
HF = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20,
    "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}


def test_hf_reader_round_trips_the_published_keys():
    got = ModelConfig(**model_config_from_hf_json(HF))
    got.validate()
    want = ModelConfig(**resolve_preset("AI21-Jamba2-3B"))
    assert got == ModelConfig(**{**want.__dict__, "name": got.name})
    assert got.layer_kinds == ((SSM,) * 7 + (F,) + (SSM,) * 6) * 2 and got.ssm
    assert [i for i, k in enumerate(got.layer_kinds) if k == F] == [7, 21]
    assert (got.head_dim, got.ssm_inner, got.num_experts) == (128, 5120, 0)
    assert got.stacks[0].kinds == got.layer_kinds and got.stacks[0].block.mlp == "dense"
    # unrotated: the identity's tables
    assert dict(got.rope_parameters)[F] == (("rope_type", "none"),)
    assert model_config_from_hf_json({**HF, "mamba_dt_rank": "auto"})["mamba_dt_rank"] == 160


def test_the_moe_sibling_is_refused_by_name():
    with pytest.raises(ValueError, match="jamba with num_experts = 8"):
        model_config_from_hf_json({**HF, "num_experts": 8, "num_experts_per_tok": 2})


# ISSUE 55's arithmetic, by part
MIXER = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120 + 5120 * 16 + 5120
         + 192 + 5120 * 2560)
ATTENTION = 2 * 2560 * 2560 + 2 * 2560 * 128
BESIDE = 3 * 2560 * 8192 + 2 * 2560  # the gated MLP, two norms


def test_published_sizes_count_3b_from_shapes_alone():
    assert (MIXER, ATTENTION, BESIDE) == (41_241_792, 13_762_560, 62_919_680)
    full = ModelConfig(**resolve_preset("AI21-Jamba2-3B"))
    want = 26 * (MIXER + BESIDE) + 2 * (ATTENTION + BESIDE) + 65536 * 2560 + 2560
    assert want == 3_029_337_472 == num_params(full)
    shapes = jax.eval_shape(lambda: init_params(full, jax.random.key(0)))
    assert param_count(shapes) == want
    lay = {n: x.shape for n, x in shapes["layers"].items()}
    assert lay["ssm_in"] == (26, 2560, 10240) and lay["ssm_conv"] == (26, 5120, 4)
    assert lay["ssm_conv_bias"] == (26, 5120) and lay["ssm_x"] == (26, 5120, 192)
    assert lay["ssm_dt"] == (26, 160, 5120) and lay["ssm_dt_bias"] == (26, 5120)
    assert lay["ssm_A_log"] == (26, 5120, 16) and lay["ssm_D"] == (26, 5120)
    assert (lay["ssm_dt_norm"], lay["ssm_b_norm"], lay["ssm_c_norm"]) == (
        (26, 160), (26, 16), (26, 16))
    assert lay["ssm_out"] == (26, 5120, 2560)
    assert lay["q"] == lay["o"] == (2, 2560, 2560) and lay["k"] == lay["v"] == (2, 2560, 128)
    assert lay["gate"] == (28, 2560, 8192) and "lm_head" not in shapes
    # the state beside the weights: 320 KiB + 60 KiB a slot and mixer, float32 both
    cache = jax.eval_shape(lambda: init_hybrid_cache(full, 16, 16, 2, 8))
    assert cache.state_row_bytes() == 327_680 + 61_440 == 389_120
    assert cache.state.shape == (26, 2, 16, 5120) and cache.k.shape[:2] == (1, 2)
    assert cache.tail.shape == (26, 2, 120, 128)


def test_the_benchmarks_configuration_is_the_published_model():
    with open(os.path.join(ROOT, "benchmark", "configs", "jamba2-3b.json")) as f:
        c = json.load(f)
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")}).model
    assert num_params(cfg) == c["parameters"] == 3_029_337_472
    assert param_count(jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))) == c[
        "parameters"]
    # every number of the catalog row under its own key, but for `reduced`
    for key, value in HF.items():
        assert c[key] == value or key in c["reduced"], key
    assert set(c["reduced"]) == {"max_position_embeddings"}
    assert c["max_position_embeddings"] == cfg.max_position_embeddings == 32768
    # the reference reads the same model from the file's published keys
    for key, value in reference.as_program({k: c[k] for k in reference.KEYS}).items():
        assert getattr(cfg, key) == value, key
    full = ModelConfig(**{**resolve_preset("AI21-Jamba2-3B"), "name": cfg.name,
                          "max_position_embeddings": 32768, "dtype": cfg.dtype})
    assert cfg == full


# ---------------------------------------------------------------------------
# (f) what is refused by name
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
    (dict(serve=ServeConfig(fleet_size=2)), "serve.fleet_size > 1"),
]


@pytest.mark.parametrize("over,message", REFUSALS, ids=[m for _, m in REFUSALS])
def test_validate_refuses_by_name(over, message):
    sections().validate()
    with pytest.raises(ValueError) as e:
        sections(**over).validate()
    assert "mamba" in str(e.value) and message in str(e.value)


@pytest.mark.parametrize("over,message", [
    (dict(mamba_d_state=0), "mamba_d_state, mamba_expand and mamba_dt_rank must be"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(hidden_size=48, num_attention_heads=3, head_dim=16), "whole number of 128-lane rows"),
    (dict(layer_types=None), "are a mamba layer's"),
    (dict(layer_types=(SSM,) * 12), "mamba layers are built beside full attention"),
    (dict(layer_types=(SSM, SSM, SSM, "sliding_attention", SSM, SSM) * 2, sliding_window=8),
     "mamba layers are built beside full attention"),
    (dict(qk_norm="head"), "mamba layers are built beside full attention"),
    (dict(num_experts=4, num_experts_per_token=2), "mamba layers are built beside full"),
    (dict(layer_types=(SSM, SSM, "dense", F, SSM, SSM) * 2), "layer_types entries must be"),
])
def test_model_validate_messages(over, message):
    with pytest.raises(ValueError, match=message):
        tiny(**over).validate()
