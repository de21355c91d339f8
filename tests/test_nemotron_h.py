"""Nemotron-H's mechanisms at toy sizes on the CPU, float32: layers that are
ONE sublayer each (`debug-tiny-nemotron-h`: two periods of (*, E, M, E, M) and
(*, E) left over; most cases take its first period alone), the Mamba-2 rule in
its three forms and over the state pool, the unrotated attention, non-gated
experts on a latent with a held share and a selection bias. The program is held
to `benchmark/reference_nemotron_h.py` (plain float32 jax.numpy, the recurrence
token by token, no cache), which imports nothing from it. Seeded weights
throughout. The compiled programs are held by tests/test_chip_compile_nemotron.py."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import (
    MOE, SSD, Config, DistributedConfig, ModelConfig, ServeConfig, TrainingConfig,
    config_from_dict, model_config_from_hf_json, num_params, resolve_preset,
)
from picotron_tpu.generate import generate, init_cache
from picotron_tpu.models.llama import (
    forward, held_conv, held_ssd, holds, init_params, layer_leaves, leaf_row, loss_fn,
    mamba2_mixer, mamba2_start, param_count,
)
from picotron_tpu.ops.ssd import (
    ssd, ssd_chunk_pooled, ssd_chunked, ssd_scan, ssd_step, ssd_step_pooled,
)
from picotron_tpu.serve import ServeEngine
from picotron_tpu.serve.paged_cache import init_serve_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")
# loaded by its path: `benchmark/` is not put on sys.path, where its own
# `tests` package would shadow this one
_spec = importlib.util.spec_from_file_location(
    "reference_nemotron_h", os.path.join(ROOT, "benchmark", "reference_nemotron_h.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)
reference.CHUNK = 8  # the probe's chunk-boundary controls, at the tests' chunk

F = "full_attention"
PERIOD = (F, MOE, SSD, MOE, SSD)
LETTER = {SSD: "M", F: "*", MOE: "E"}
CONFIG = "nemotron3-super-120b-a12b-22l-ep8"


def tiny(**over) -> ModelConfig:
    """The preset's first period (5 layers), or what `over` says."""
    base = dict(resolve_preset("debug-tiny-nemotron-h"), num_hidden_layers=5, layer_types=PERIOD)
    return ModelConfig(dtype="float32", **{**base, **over})


def weights(cfg, seed=1):
    p = init_params(cfg, jax.random.key(seed))
    layers = dict(p["layers"])
    # norm weights and D that are not at their start, so that a norm skipped or
    # a term dropped shows; a selection bias that changes some picks
    for j, n in enumerate(("input_norm", "ssd_norm", "ssd_D")):
        layers[n] = layers[n] + 0.1 * jax.random.normal(jax.random.key(seed + 50 + j),
                                                        layers[n].shape)
    layers["router_bias"] = 0.05 * jax.random.normal(jax.random.key(seed + 70),
                                                     layers["router_bias"].shape)
    # a trained model's embedding scale, so that the layers show in the logits
    return dict(p, embedding=p["embedding"] * 0.1, layers=layers)


def published(cfg: ModelConfig) -> dict:
    """The keys `reference_nemotron_h` reads, from a ModelConfig."""
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers,
        hybrid_override_pattern="".join(LETTER[k] for k in cfg.layer_kinds),
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        mamba_num_heads=cfg.mamba_num_heads, mamba_head_dim=cfg.mamba_head_dim,
        n_groups=cfg.n_groups, ssm_state_size=cfg.ssm_state_size, conv_kernel=cfg.mamba_d_conv,
        use_conv_bias=cfg.mamba_conv_bias, n_routed_experts=cfg.num_experts,
        router_experts=cfg.router_experts or cfg.num_experts, expert_first=cfg.expert_first,
        num_experts_per_tok=cfg.num_experts_per_token,
        moe_intermediate_size=cfg.moe_intermediate_size, moe_latent_size=cfg.moe_latent_size,
        moe_shared_expert_intermediate_size=cfg.moe_shared_expert_intermediate_size,
        n_shared_experts=cfg.n_shared_experts, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor, mlp_hidden_act="relu2",
        layer_norm_epsilon=cfg.rms_norm_eps, tie_word_embeddings=False)


def ref_logits(params, cfg, ids, rows=None, **faults):
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.arange(len(ids)) if rows is None else jnp.asarray(list(rows))
    return np.asarray(reference.logits_at(params, ids, rows, published(cfg), **faults))


# ---------------------------------------------------------------------------
# (a) the rule's three forms, and the two over a pool
# ---------------------------------------------------------------------------


def rule_inputs(r=3, s=16, h=8, p=4, g=2, n=8, real=(16, 11, 0), seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    live = jnp.arange(s)[None, :] < jnp.asarray(real)[:, None]
    # a pad position is made inert by its caller: v = 0, g = 0
    v = jnp.where(live[..., None, None], jax.random.normal(ks[0], (r, s, h, p)), 0.0)
    g_ = jnp.where(live[..., None], -0.5 * jnp.abs(jax.random.normal(ks[1], (r, s, h))), 0.0)
    b, c = jax.random.normal(ks[2], (r, s, g, n)), jax.random.normal(ks[3], (r, s, g, n))
    return v, g_, b, c, jax.random.normal(ks[4], (r, h, p, n)), ks[5]


@pytest.mark.parametrize("sub", [8, 5, 128])
def test_step_scan_and_chunked_forms_agree_from_a_nonzero_state(sub):
    """The rule token by token, in sub-chunks that divide the segment, that do
    not, and in one: the same outputs and the same state from a NON-zero start;
    a row of pad positions alone leaves its state as it was, to the bit."""
    v, g, b, c, state, _ = rule_inputs()
    y0, s0 = ssd_scan(v, g, b, c, state)
    y1, s1 = ssd_chunked(v, g, b, c, state, sub=sub)
    np.testing.assert_allclose(y1, y0, atol=2e-5)
    np.testing.assert_allclose(s1, s0, atol=2e-5)
    np.testing.assert_array_equal(s0[2], state[2])
    # one position a row is the step, whichever entry takes it
    y2, s2 = ssd(v[:, :1], g[:, :1], b[:, :1], c[:, :1], state)
    want = ssd_step(v[:, 0], g[:, 0], b[:, 0], c[:, 0], state)
    np.testing.assert_array_equal(y2[:, 0], want[0])
    np.testing.assert_array_equal(s2, want[1])


@pytest.mark.parametrize("form", ["step", "chunk"])
def test_the_pooled_forms_update_the_live_rows_alone(form):
    """`ssd_step_pooled` / `ssd_chunk_pooled` (the Pallas interpreter) against
    the plain forms on the rows' gathered states: a fresh row starts from zeros
    whatever its slot holds, an unmapped or idle row moves nothing and reads
    zeros, and every bit of the pool outside the worked rows is as it was."""
    v, g, b, c, _, key = rule_inputs(real=(16, 11, 16))
    pool = jax.random.normal(key, (2, 5, 8, 4, 8))
    rows, fresh = jnp.asarray([3, 0, 5]), jnp.asarray([False, True, False])
    start = jnp.where(fresh[:, None, None, None], 0.0, pool[1, jnp.minimum(rows, 4)])
    live = jnp.asarray([True, True, True])
    if form == "step":
        y, new = ssd_step_pooled(v[:, 0], g[:, 0], b[:, 0], c[:, 0], pool, 1, rows, live, fresh)
        want_y, want_s = ssd_step(v[:, 0], g[:, 0], b[:, 0], c[:, 0], start)
    else:
        y, new = ssd_chunk_pooled(v, g, b, c, pool, 1, rows, live, fresh, sub=8)
        want_y, want_s = ssd_chunked(v, g, b, c, start, sub=8)
    np.testing.assert_allclose(y[:2], want_y[:2], atol=2e-5)
    np.testing.assert_array_equal(y[2], 0.0)  # slot 5 of 5: unmapped
    np.testing.assert_allclose(new[1, 3], want_s[0], atol=2e-5)
    np.testing.assert_allclose(new[1, 0], want_s[1], atol=2e-5)
    untouched = np.ones(pool.shape, bool)
    untouched[1, 3] = untouched[1, 0] = False
    np.testing.assert_array_equal(np.asarray(new)[untouched], np.asarray(pool)[untouched])
    # a row that is not live leaves its slot alone
    idle = jnp.asarray([False, True, True])
    kernel = ssd_step_pooled if form == "step" else ssd_chunk_pooled
    args = (v[:, 0], g[:, 0], b[:, 0], c[:, 0]) if form == "step" else (v, g, b, c)
    _, kept = kernel(*args, pool, 1, rows, idle, fresh)
    np.testing.assert_array_equal(kept[1, 3], pool[1, 3])


def test_the_mixer_makes_a_pad_position_inert():
    cfg = tiny()
    params = weights(cfg)
    lp = layer_leaves(params["layers"], cfg.layer_kinds, 2)
    h = jax.random.normal(jax.random.key(2), (2, 8, cfg.hidden_size))
    live = jnp.arange(8)[None, :] < jnp.asarray([8, 5])[:, None]
    carry = mamba2_start(cfg, 2)
    carry = (carry[0] + 0.3, carry[1] + 0.1)
    out, (state, tail) = mamba2_mixer(h, lp, cfg, held_conv, held_ssd, carry, live)
    short, (state5, tail5) = mamba2_mixer(h[1:, :5], lp, cfg, held_conv, held_ssd,
                                          (carry[0][1:], carry[1][1:]), live[1:, :5])
    np.testing.assert_allclose(out[1, :5], short[0], atol=1e-5)
    np.testing.assert_allclose(state[1], state5[0], atol=1e-6)
    np.testing.assert_allclose(tail[1], tail5[0], atol=1e-6)
    assert state.shape == (2, 8, 16, 32) and tail.shape == (2, 6, 128)


# ---------------------------------------------------------------------------
# (b) forward(), generate() and the engine against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layers", [5, 12])
def test_forward_matches_the_reference(layers):
    cfg = tiny() if layers == 5 else tiny(**resolve_preset("debug-tiny-nemotron-h"))
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(2), (23,), 0, cfg.vocab_size)
    got = np.asarray(jax.jit(lambda p, i: forward(p, i, cfg))(params, ids[None]))[0]
    np.testing.assert_allclose(got, ref_logits(params, cfg, ids), atol=2e-5)


@pytest.mark.parametrize("fault", [f for f in reference.FAULTS if f != "bf16_state"])
def test_each_control_of_the_reference_moves_the_logits(fault):
    cfg = tiny()
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(2), (23,), 0, cfg.vocab_size)
    moved = np.abs(ref_logits(params, cfg, ids, **{fault: True})
                   - ref_logits(params, cfg, ids)).max()
    assert moved > 0.005, (fault, moved)


def test_leaves_are_stacked_by_the_kind_that_holds_them():
    cfg = tiny(**resolve_preset("debug-tiny-nemotron-h"))
    layers = init_params(cfg, jax.random.key(0))["layers"]
    kinds = cfg.layer_kinds
    n = {k: kinds.count(k) for k in (F, SSD, MOE)}
    assert n == {F: 3, SSD: 4, MOE: 5} and cfg.attention_sublayers == 3
    assert layers["input_norm"].shape[0] == 12 and "post_norm" not in layers
    assert layers["q"].shape[0] == 3 and layers["ssd_in"].shape[0] == 4
    assert {layers[k].shape[0] for k in ("router", "router_bias", "w_up", "w_down", "latent_down",
                                         "latent_up", "shared_up", "shared_down")} == {5}
    assert "w_gate" not in layers and "shared_gate" not in layers  # relu2 is not gated
    assert layers["w_up"].shape[1:] == (16, 32, 24) and layers["w_down"].shape[1:] == (16, 24, 32)
    assert holds("router", MOE, True) and not holds("router", F, True)
    assert holds("router", F) and holds("input_norm", SSD, True) and not holds("q", MOE, True)
    assert leaf_row("router", kinds, 3) == 1 and leaf_row("ssd_in", kinds, 4) == 1
    assert leaf_row("q", kinds, 10) == 2 and leaf_row("input_norm", kinds, 7) == 7
    assert sorted(layer_leaves(layers, kinds, 0)) == ["input_norm", "k", "o", "q", "v"]
    assert param_count(init_params(cfg, jax.random.key(0))) == num_params(cfg)


@pytest.mark.parametrize("prompt", [1, 19])
def test_generate_matches_the_reference(prompt):
    cfg = tiny()
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(3), (2, prompt), 0, cfg.vocab_size)
    out = np.asarray(generate(params, cfg, ids, 6))
    for b in range(2):
        want = ref_logits(params, cfg, out[b], rows=range(prompt - 1, prompt + 5))
        assert (out[b, prompt:] == want.argmax(-1)).all()
    cache = init_cache(cfg, 2, 18)
    assert cache.k.shape == (1, 2, 18, 2, 16) and cache.state.shape == (2, 2, 8, 16, 32)
    assert cache.tail.shape == (2, 2, 6, 128) and cache.state.dtype == jnp.float32


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


@pytest.mark.parametrize("layers,slots", [(12, 2), (5, 1)])
def test_engine_matches_the_reference(layers, slots):
    """Prefill in several chunks of 8 (the state handed from dispatch to
    dispatch through the pools), then decode a step a slot and mixer through
    both pools, one dispatch ahead: the logit of EVERY decoded position
    against the reference's full forward pass under teacher forcing. With one
    slot every request is admitted into the row its predecessor left its state
    in: the program starts it from zeros, nothing on the host resets a row."""
    cfg = tiny() if layers == 5 else tiny(**resolve_preset("debug-tiny-nemotron-h"))
    params = weights(cfg)
    requests = some_requests(cfg, ((37, 8), (6, 5), (21, 7), (45, 4)))
    eng, out = run_engine(params, cfg, requests, decode_slots=slots)
    assert len(out) == 4 and eng.stats["decode_compiles"] <= 1
    held_to_the_reference(params, cfg, requests, out)
    k, v, state, tail = eng._kv
    mixers, full = cfg.layer_kinds.count(SSD), cfg.layer_kinds.count(F)
    assert k.shape[:2] == (2, full) and state.shape == (mixers, slots, 8, 16, 32)
    assert tail.shape == (mixers, slots, 6, 128) and state.dtype == tail.dtype == jnp.float32
    assert float(jnp.abs(state).max()) > 0
    assert eng.stats["experts_touched"] > 0 and eng.stats["picks_here"] == eng.stats["picks_all"]
    if slots == 2:
        want = np.asarray(generate(params, cfg, jnp.asarray([requests[0][0]]), 8))[0, 37:]
        assert out[0]["tokens"] == list(map(int, want))


def test_the_cache_is_the_hybrid_one_and_counts_what_a_dispatch_moves():
    cfg = tiny()
    cache = init_serve_cache(cfg, ServeConfig(block_size=4), 3, 8, 16)
    assert type(cache).__name__ == "HybridPagedCache"
    # the K/V pool holds the ONE attention layer: a layer's row is its ordinal
    # among the layers that have one, and two layers of five have no row at all
    assert [p.shape for p in cache.pools] == [
        (2, 1, 8, 4, 16), (2, 1, 8, 4, 16), (2, 3, 8, 16, 32), (2, 3, 6, 128)]
    row_bytes = 8 * 16 * 32 * 4 + 6 * 128 * 4
    assert cache.state_row_bytes() == row_bytes
    assert cache.prefill_counts([(0, 8), (8, 5)], cfg, rows=4) == dict(
        state_rows=4, state_bytes=2 * 4 * row_bytes, state_resets=2,
        chunk_rows_batch=8, chunk_rows_idle=4, scan_tokens=26)
    assert cache.decode_counts([(5, 2), (9, 2)], cfg) == dict(
        kv_blocks=5, kv_blocks_banded=5, state_rows=4, state_bytes=2 * 4 * row_bytes,
        state_resets=0, state_rows_batch=6, state_rows_idle=2)
    with pytest.raises(ValueError, match="mamba layers is served from one device.*mamba2 layers are held the same way"):
        init_serve_cache(cfg, ServeConfig(block_size=4), 3, 8, 16, sharded=True)


def test_a_dispatch_through_its_kernels_serves_what_the_plain_path_serves(monkeypatch):
    """A two-slot engine, once as every CPU run serves it (gather, the plain
    rule, scatter) and once with the decode steps through `ssd_step_pooled` and
    `conv_step_pooled` and the prefill chunks through `ssd_chunk_pooled` (the
    Pallas interpreter), through admission, a slot's second request and prompts
    whose last chunk is part padding: the same tokens, and after the run the
    same state and tail pools to float32 rounding."""
    from picotron_tpu.serve import paged_cache

    cfg = tiny()
    params = weights(cfg)
    requests = some_requests(cfg, ((14, 6), (11, 5), (9, 4)), seed=3)
    calls = []

    def served(through_kernels: bool):
        jax.clear_caches()  # the engines of one process share their compiled programs
        if through_kernels:
            for suits, kernel, step in (("ssd_kernel_suits", "ssd_step_pooled", True),
                                        ("ssd_chunk_suits", "ssd_chunk_pooled", False),
                                        ("conv_kernel_suits", "conv_step_pooled", True)):
                sound = getattr(paged_cache, kernel)
                monkeypatch.setattr(paged_cache, suits,
                                    lambda s, *_, step=step: (s == 1) == step)
                monkeypatch.setattr(
                    paged_cache, kernel, lambda *a, sound=sound, kernel=kernel, **k:
                    calls.append(kernel) or sound(*a, **k))
        eng, out = run_engine(params, cfg, requests)
        return [r["tokens"] for r in out], eng._kv

    plain_tokens, plain = served(False)
    kernel_tokens, kernels = served(True)
    jax.clear_caches()
    assert set(calls) == {"ssd_step_pooled", "ssd_chunk_pooled", "conv_step_pooled"}
    assert kernel_tokens == plain_tokens
    np.testing.assert_allclose(kernels[2], plain[2], atol=1e-5)
    np.testing.assert_allclose(kernels[3], plain[3], atol=1e-5)


# ---------------------------------------------------------------------------
# (c) LatentMoE: the shares of a layer, and the selection bias
# ---------------------------------------------------------------------------


def expert_layer(cfg, params, x):
    """The model's one expert layer alone over x [S, hidden], through the
    program's own `forward` path (`decoder_layer`)."""
    from picotron_tpu.models.llama import DEFAULT_CTX, decoder_layer

    lp = layer_leaves(params["layers"], cfg.layer_kinds, 0)
    out, _ = decoder_layer(x[None], lp, cfg, DEFAULT_CTX, None, None, kind=MOE,
                           block=cfg.stacks[0].block)
    return np.asarray(out[0])


def test_the_eight_shares_of_a_latent_layer_sum_to_the_uncut_layer():
    """A toy LatentMoE layer, router 16 wide, cut into 8 shares of 2 held
    experts each (`num_experts` 2, `router_experts` 16, `expert_first` 2 i):
    the shares' routed parts, each on its own banks and with no exchange, add
    up to the uncut reference's layer, the shared expert and the residual
    counted once (the up-projection is linear and without bias, so the shares'
    latents add)."""
    whole = tiny(num_hidden_layers=1, layer_types=(MOE,), mamba_num_heads=0, mamba_head_dim=0,
                 n_groups=0, ssm_state_size=0, mamba_d_conv=0)
    params = weights_moe(whole)
    x = 0.5 * jax.random.normal(jax.random.key(9), (13, whole.hidden_size))
    w = {n: v[0] for n, v in params["layers"].items()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.layer(x, w, MOE, published(whole))[0])
    np.testing.assert_allclose(expert_layer(whole, params, x), want, atol=2e-5)
    # the part every share repeats: the residual and the shared expert
    zero = dict(params, layers=dict(params["layers"], w_down=0 * params["layers"]["w_down"]))
    once = expert_layer(whole, zero, x)
    total = once.copy()
    for i in range(8):
        share = tiny(num_hidden_layers=1, layer_types=(MOE,), mamba_num_heads=0,
                     mamba_head_dim=0, n_groups=0, ssm_state_size=0, mamba_d_conv=0,
                     num_experts=2, router_experts=16, expert_first=2 * i)
        cut = dict(params, layers=dict(
            params["layers"], w_up=params["layers"]["w_up"][:, 2 * i:2 * i + 2],
            w_down=params["layers"]["w_down"][:, 2 * i:2 * i + 2]))
        total += expert_layer(share, cut, x) - once
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert np.abs(want - once).max() > 0.01  # the routed experts are in the sum


def weights_moe(cfg, seed=1):
    p = init_params(cfg, jax.random.key(seed))
    layers = dict(p["layers"], router_bias=0.05 * jax.random.normal(
        jax.random.key(seed + 70), p["layers"]["router_bias"].shape))
    return dict(p, layers=layers)


def test_a_selection_bias_changes_picks_and_not_gates():
    from picotron_tpu.ops.moe import topk_gates

    logits = jax.random.normal(jax.random.key(4), (64, 16))
    bias = 0.3 * jax.random.normal(jax.random.key(5), (16,))
    probs, plain_i, plain_g = topk_gates(logits, 4, True, "sigmoid", 5.0)
    _, bias_i, bias_g = topk_gates(logits, 4, True, "sigmoid", 5.0, bias)
    assert (np.sort(plain_i, -1) != np.sort(bias_i, -1)).any()
    # the gates are the chosen columns' SCORES, renormalised and scaled: no bias in them
    top = np.take_along_axis(np.asarray(probs), np.asarray(bias_i), -1)
    np.testing.assert_allclose(bias_g, 5.0 * top / top.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(plain_g).sum(-1), 5.0, rtol=1e-6)
    # and the reference's gates are the program's
    cfg = tiny()
    w = {"router": jnp.eye(16), "router_bias": bias}
    ref = np.asarray(reference.gates(logits, w, published(cfg)))
    np.testing.assert_allclose(np.take_along_axis(ref, np.asarray(bias_i), -1), bias_g, rtol=1e-6)


# ---------------------------------------------------------------------------
# (d) the reader, the published sizes, the configuration's file, the refusals
# ---------------------------------------------------------------------------


def catalog_row() -> dict:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        for line in open(path):
            row = json.loads(line)
            if row["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16":
                return row["config"]
    # (the guide is not part of the checkout: the row's keys as the file holds them)
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        c = json.load(f)
    pattern = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
               "EMEMEMEM*EMEMEMEME")
    skip = ("name", "source", "chips", "deployment", "router_experts", "expert_first",
            "parameters", "reduced", "assumed", "initializer_range",
            "why_these_serve_settings", "distributed", "model", "serve")
    return dict({k: v for k, v in c.items() if k not in skip}, num_hidden_layers=88,
                hybrid_override_pattern=pattern, n_routed_experts=512, vocab_size=131072,
                max_position_embeddings=262144)


def test_the_reader_turns_the_rows_config_into_the_published_model():
    got = ModelConfig(**model_config_from_hf_json(catalog_row()))
    want = ModelConfig(**resolve_preset("NVIDIA-Nemotron-3-Super-120B-A12B"))
    assert got == want
    got.validate()
    kinds = got.layer_kinds
    assert (kinds.count(SSD), kinds.count(F), kinds.count(MOE)) == (40, 8, 40)
    assert got.stacks[0].block.alone and got.single_sublayer and not got.mlp_gated
    # 120.67 B whole, 12.77 B a token: the published "120B-A12B"
    assert num_params(got) == 120_668_707_840
    assert num_params(got, active_only=True) == 12_770_237_440


@pytest.mark.parametrize("over,message", [
    (dict(moe_latent_dim=1024), r"\['moe_latent_dim'\] are not known to this reader"),
    (dict(hybrid_override_pattern="M-" * 44), "a dense MLP layer"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act = 'silu'"),
    (dict(n_group=8), "n_group = 8"),
    (dict(expand=4), "expand x hidden_size"),
    (dict(num_hidden_layers=87), "names 88 layers"),
])
def test_the_reader_refuses_by_name(over, message):
    with pytest.raises(ValueError, match=message):
        model_config_from_hf_json({**catalog_row(), **over})


def test_the_benchmarks_configuration_is_the_published_model_cut_as_it_says():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        c = json.load(f)
    row = catalog_row()
    cut = {"num_hidden_layers": 22, "hybrid_override_pattern": row["hybrid_override_pattern"][25:47],
           "n_routed_experts": 64, "vocab_size": 16384, "max_position_embeddings": 53248}
    assert cut["hybrid_override_pattern"] == "*EMEMEMEMEM" * 2
    assert set(c["reduced"]) == set(cut)
    for k, v in row.items():
        assert c[k] == cut.get(k, v), k
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")})
    m = cfg.model
    held = dict(model_config_from_hf_json({k: c[k] for k in row}), num_experts=64,
                router_experts=512, expert_first=0)
    assert m == ModelConfig(name=m.name, dtype="bfloat16", **held)
    for k, v in reference.as_program({k: c[k] for k in reference.KEYS}).items():
        assert getattr(m, k) == v, k
    # every count of the file against the tree the program builds
    shapes = jax.eval_shape(lambda: init_params(m, jax.random.key(0)))
    assert param_count(shapes) == num_params(m) == c["parameters"] == 5_370_454_784
    sizes = {n: int(np.prod(v.shape[1:])) for n, v in shapes["layers"].items()}
    mixer = sum(v for n, v in sizes.items() if n.startswith("ssd_"))
    attention = sum(sizes[n] for n in "qkvo")
    beside = sum(sizes[n] for n in ("router", "router_bias", "latent_down", "latent_up",
                                    "shared_up", "shared_down"))
    expert = (sizes["w_up"] + sizes["w_down"]) // 64
    assert (mixer, attention, beside, expert) == (109_635_968, 35_651_584, 54_526_464, 5_505_024)
    assert (sizes["ssd_in"], sizes["ssd_conv"] + sizes["ssd_conv_bias"], sizes["ssd_out"]) == (
        76_021_760, 51_200, 33_554_432)
    assert 10 * (mixer + 4096) + 2 * (attention + 4096) + 10 * (
        beside + 4096 + 64 * expert) + 2 * 16384 * 4096 + 4096 == c["parameters"]
    # what a slot and a cached position cost
    cache = jax.eval_shape(lambda: init_serve_cache(m, cfg.serve, 32, 16384, 53248))
    assert cache.state.shape == (10, 32, 128, 64, 128) and cache.tail.shape == (10, 32, 240, 128)
    assert cache.state_row_bytes() == 4_194_304 + 122_880
    assert cache.k.shape == (2, 2, 16384, 32, 128)


def test_training_the_fleet_and_tp_refuse_the_new_kinds_by_name():
    cfg = tiny()
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match=r"mamba2 layers \(layers of one sublayer each\), "
                                         r"experts layers"):
        loss_fn(init_params(cfg, jax.random.key(0)), ids, ids, cfg)
    base = dict(model=cfg, training=TrainingConfig(seq_length=8))
    with pytest.raises(ValueError, match="experts and mamba2 layers, which tensor parallelism"):
        Config(distributed=DistributedConfig(tp_size=2, use_cpu=True), **base).validate()
    with pytest.raises(ValueError, match="does not support MoE models"):
        Config(serve=ServeConfig(fleet_size=2), **base).validate()
    mixers = tiny(layer_types=(F, SSD, SSD, F, SSD), num_experts=0, moe_latent_size=0,
                  n_shared_experts=0, moe_shared_expert_intermediate_size=0,
                  moe_selection_bias=False, moe_scoring="softmax", routed_scaling_factor=1.0)
    with pytest.raises(ValueError, match="mamba2 layers, which serve.fleet_size > 1"):
        Config(serve=ServeConfig(fleet_size=2), model=mixers,
               training=TrainingConfig(seq_length=8)).validate()
    with pytest.raises(ValueError, match="experts and mamba2 layers, which expert parallelism"):
        Config(distributed=DistributedConfig(ep_size=2, use_cpu=True), **base).validate()


@pytest.mark.parametrize("over,message", [
    (dict(layer_types=(F, MOE, SSD, MOE, "sliding_attention"), sliding_window=8),
     "'sliding_attention'.* beside them are not built"),
    (dict(n_groups=3), "the heads a whole number a group"),
    (dict(ssm_state_size=8), "must be whole rows of 128 lanes"),
    (dict(num_experts=0), "'experts' layers exactly when num_experts > 0"),
    (dict(qk_norm="head"), "must be unset"),
    (dict(layer_types=None, num_hidden_layers=5), "a mamba2 layer reads mamba_d_conv alone"),
    (dict(layer_types=None, num_hidden_layers=5, mamba_d_conv=0),
     "describe a model whose layers are one"),
    (dict(hidden_act="silu", layer_types=None, num_hidden_layers=5, mamba_num_heads=0,
          mamba_head_dim=0, n_groups=0, ssm_state_size=0, mamba_d_conv=0, moe_latent_size=0,
          moe_shared_expert_intermediate_size=0), None),
    (dict(hidden_act="relu2", layer_types=None, num_hidden_layers=5, mamba_num_heads=0,
          mamba_head_dim=0, n_groups=0, ssm_state_size=0, mamba_d_conv=0, moe_latent_size=0,
          moe_shared_expert_intermediate_size=0), "a non-gated MLP of two matrices"),
])
def test_model_validate_messages(over, message):
    cfg = tiny(**over)
    if message is None:
        return cfg.validate()
    with pytest.raises(ValueError, match=message):
        cfg.validate()
