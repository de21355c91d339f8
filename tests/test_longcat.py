"""LongCat-Flash's mechanisms at the tiny preset (`debug-tiny-longcat`) on the
CPU: a layer of two (latent attention, dense MLP) pairs with a
shortcut-connected expert branch, each attention with its own row of the latent
cache, scaled latents, a softmax router over routed and zero-compute experts
chosen by score + a selection bias, a held share of the routed experts. The
program is held to `benchmark/reference_longcat.py` (plain float32 jax.numpy,
no cache, its own router), which imports nothing from it. Seeded weights and a
seeded NON-ZERO selection bias throughout. The compiled programs are held by
tests/test_chip_compile.py."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, ServeConfig, TrainingConfig,
    config_from_dict, model_config_from_hf_json, num_params, refuse_training,
    resolve_preset,
)
from picotron_tpu.generate import expert_counts, generate, init_cache
from picotron_tpu.models.llama import (
    forward, init_params, loss_fn, mlp_act, param_count, sublayer,
)
from picotron_tpu.ops.moe import moe_mlp_served, route_topk, topk_gates
from picotron_tpu.serve import ServeEngine
from picotron_tpu.serve.paged_cache import init_latent_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")
# loaded by its path: `benchmark/` is not put on sys.path, where its own
# `tests` package would shadow this one
_spec = importlib.util.spec_from_file_location(
    "reference_longcat", os.path.join(ROOT, "benchmark", "reference_longcat.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

# every routed expert here | experts 16-31 of 64, as one chip of four holds them
SHARES = {"whole": {}, "share": dict(router_experts=64, expert_first=16)}


def tiny(**over) -> ModelConfig:
    return ModelConfig(dtype="float32", **{**resolve_preset("debug-tiny-longcat"), **over})


def weights(cfg, seed=1):
    p = init_params(cfg, jax.random.key(seed))
    bias = 0.02 * jax.random.normal(jax.random.key(seed + 100),
                                    p["layers"]["router_bias"].shape)
    # a trained model's embedding scale, so that the layers show in the logits
    return dict(p, embedding=p["embedding"] * 0.1,
                layers=dict(p["layers"], router_bias=bias))


def published(cfg: ModelConfig) -> dict:
    """The keys `reference_longcat` reads, from a ModelConfig."""
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_layers=cfg.num_hidden_layers, num_attention_heads=cfg.num_attention_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        ffn_hidden_size=cfg.intermediate_size,
        expert_ffn_hidden_size=cfg.moe_intermediate_size,
        n_routed_experts=cfg.num_experts, moe_topk=cfg.num_experts_per_token,
        zero_expert_num=cfg.zero_experts, zero_expert_type="identity",
        routed_scaling_factor=cfg.routed_scaling_factor,
        mla_scale_q_lora=cfg.mla_scale_q_lora, mla_scale_kv_lora=cfg.mla_scale_kv_lora,
        router_experts=cfg.router_width - cfg.zero_experts, expert_first=cfg.expert_first)


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
    ids = jax.random.randint(jax.random.key(2), (2, 40), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(params, ids, cfg))
    for b in range(2):
        want = ref_logits(params, cfg, ids[b])
        np.testing.assert_allclose(got[b], want, atol=2e-5)
    assert np.abs(want).max() > 1.0  # the layers show


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_control_of_the_reference_moves_the_logits(fault):
    """What the chip's tolerance probe leaves out one at a time is in the
    numbers: the program agrees with the reference only when it is whole."""
    cfg = tiny()
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(2), (40,), 0, cfg.vocab_size)
    whole, faulty = ref_logits(params, cfg, ids), ref_logits(params, cfg, ids, **{fault: True})
    assert np.abs(whole - faulty).max() > 1e-3


def test_forward_runs_under_ad_and_training_is_refused_by_name():
    cfg = tiny()
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(2), (1, 12), 0, cfg.vocab_size)
    g = jax.grad(lambda p: jnp.sum(forward(p, ids, cfg) ** 2))(params)
    assert float(jnp.abs(g["layers"]["gate"][:, 1]).max()) > 0
    assert float(jnp.abs(g["layers"]["kv_a"][:, 0]).max()) > 0
    with pytest.raises(ValueError, match="shortcut_moe.*zero_experts.*selection bias"):
        loss_fn(params, ids, ids, cfg)
    with pytest.raises(ValueError, match="training does not implement"):
        refuse_training(cfg)


# ---------------------------------------------------------------------------
# (b) prefill, then decode through the caches, against the reference's forward
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


def run_engine(params, cfg, requests, **over):
    scfg = ServeConfig(**{**dict(decode_slots=2, block_size=4, prefill_chunk=8,
                                 max_model_len=64, decode_interval=2), **over})
    eng = ServeEngine(params, cfg, scfg)
    out = eng.run(requests)
    eng.close()
    assert eng.pool.in_use == 0
    return eng, out


@pytest.mark.parametrize("share,chunk", [("whole", 8), ("share", 8), ("whole", 32),
                                         ("share", 32)])
def test_engine_matches_the_reference(share, chunk):
    """Chunked prefill at two chunk sizes (absorbed at 8 queries a row,
    expanded at 32), then decode through the latent paged cache, two rows a
    layer: the logit of EVERY decoded position against the reference's full
    forward pass under teacher forcing."""
    cfg = tiny(**SHARES[share])
    params = weights(cfg)
    rng = np.random.default_rng(5)
    requests = [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m)
                for n, m in ((37, 8), (6, 5), (21, 7), (45, 4))]
    eng, out = run_engine(params, cfg, requests, prefill_chunk=chunk)
    assert len(out) == 4 and eng.stats["decode_compiles"] <= 1  # the one decode program
    assert eng._kv[0].shape[0] == 2 * cfg.num_hidden_layers
    for (prompt, _), res in zip(requests, sorted(out, key=lambda r: r["id"])):
        toks = res["tokens"]
        want = ref_logits(params, cfg, prompt + toks,
                          rows=range(len(prompt) - 1, len(prompt) + len(toks) - 1))
        assert (want.argmax(-1) == np.asarray(toks)).all()
        np.testing.assert_allclose(res["logits"], want[np.arange(len(toks)), toks], atol=2e-4)
    # the counters of the decode steps
    st = eng.stats
    assert st["picks_all"] % (cfg.num_hidden_layers * cfg.num_experts_per_token) == 0
    assert 0 < st["picks_zero"] < st["picks_all"]
    if share == "share":
        assert st["picks_here"] + st["picks_zero"] < st["picks_all"]
    else:
        assert st["picks_here"] + st["picks_zero"] == st["picks_all"]
    assert 0 <= st["rows_all_zero_or_away"] <= st["picks_all"] // cfg.num_experts_per_token
    assert st["expert_slots"] % (cfg.num_hidden_layers * cfg.num_experts) == 0


def test_engine_agrees_with_generate():
    cfg = tiny()
    params = weights(cfg)
    prompt = list(map(int, np.random.default_rng(1).integers(0, 256, size=19)))
    _, out = run_engine(params, cfg, [(prompt, 9)])
    want = np.asarray(generate(params, cfg, jnp.asarray([prompt]), 9))[0, 19:]
    assert out[0]["tokens"] == list(map(int, want))


# ---------------------------------------------------------------------------
# (c) the shares add up to the uncut layer
# ---------------------------------------------------------------------------


def one_layer(params, li=1):
    """Layer li's leaves (a pair's [2, ...]) and the stack's whole banks."""
    lp = {n: w[li] for n, w in params["layers"].items()}
    return lp, {n: params["layers"][n] for n in ("w_gate", "w_up", "w_down")}


def branch(x, lp, held, first, cfg, li=1, live=None):
    return moe_mlp_served(
        x, lp["router"], held["w_gate"], held["w_up"], held["w_down"],
        top_k=cfg.num_experts_per_token, act=mlp_act(cfg), norm_topk_prob=False,
        live=jnp.ones(x.shape[:2], bool) if live is None else live, layer=li,
        scoring="softmax", scale=cfg.routed_scaling_factor, expert_first=first,
        bias=lp["router_bias"], zero=cfg.zero_experts)


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 16 shares (one held expert each: expert_first
    0, 1, ..., 15), with the zero-compute part, the dense path and both
    attentions counted ONCE, are the reference's UNCUT layer."""
    cfg = tiny()
    params = weights(cfg)
    lp, banks = one_layer(params)
    m = published(cfg)
    x = 0.3 * jax.random.normal(jax.random.key(4), (11, cfg.hidden_size), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference.layer(x, lp, m)
        # everything but the experts, once: the reference's layer of a chip that
        # holds no routed expert and drops the zero-compute term
        none = dict(m, n_routed_experts=0)
        empty = {n: (w[:0] if n in banks else w) for n, w in lp.items()}
        once = reference.layer(x, empty, none, frozenset({"no_zero_term"}))
        # the branch's input, as the layer computes it
        p0 = sublayer(lp, 0)
        a1 = x + reference._mla(reference._norm(x, p0["input_norm"], 1e-5), p0, m,
                                frozenset())[0]
        h1 = reference._norm(a1, p0["post_norm"], 1e-5)[None]
        g = reference.gates(h1[0], lp, m)
        routed_picks, zero_picks = int(jnp.sum(g[:, :16] > 0)), int(jnp.sum(g[:, 16:] > 0))
        # every share computes the zero-compute term alike: counted once
        zero = jnp.sum(g[:, 16:], axis=-1, keepdims=True) * h1[0]
        total, seen = once + zero, 0
        for first in range(16):
            held = {n: w[:, first:first + 1] for n, w in banks.items()}
            out, counts = branch(h1, lp, held, first, cfg)
            total = total + (out[0] - zero)
            seen += int(counts[2])
            assert int(counts[3]) == 11 * 4 and int(counts[4]) == zero_picks
        # every routed pick lands on exactly one share
        assert seen == routed_picks == 11 * 4 - zero_picks and zero_picks > 0
    np.testing.assert_allclose(total, want, atol=2e-5)


# ---------------------------------------------------------------------------
# (d) a token whose picks are all zero-compute, or all held elsewhere
# ---------------------------------------------------------------------------


def test_a_token_with_only_zero_compute_picks_is_its_gates_times_itself():
    cfg = tiny()
    lp, banks = one_layer(weights(cfg))
    # a router that scores four zero-compute experts (columns 16-19) highest
    router = jnp.zeros((cfg.hidden_size, 24)).at[:, 16:20].set(1.0)
    lp = dict(lp, router=router, router_bias=jnp.zeros((24,)))
    x = jnp.abs(jax.random.normal(jax.random.key(6), (1, 5, cfg.hidden_size)))
    out, counts = branch(x, lp, banks, 0, cfg)
    p = jax.nn.softmax((x @ router).astype(jnp.float32), axis=-1)
    gate = 6.0 * jnp.sum(p[..., 16:20], axis=-1, keepdims=True)
    np.testing.assert_allclose(out, gate * x, rtol=1e-5)
    # no bank touched or visited, 20 picks all zero-compute, 5 rows read no bank
    assert list(map(int, counts)) == [0, 0, 0, 20, 20, 5]
    # a row without a token comes out as zeros and counts nowhere
    live = jnp.asarray([[True, False, True, True, False]])
    out, counts = branch(x, lp, banks, 0, cfg, live=live)
    assert not np.asarray(out[0, 1]).any() and not np.asarray(out[0, 4]).any()
    assert list(map(int, counts)) == [0, 0, 0, 12, 12, 3]


def test_a_token_whose_picks_are_all_held_elsewhere_adds_nothing():
    cfg = tiny(router_experts=64, expert_first=48)
    lp, banks = one_layer(weights(cfg))
    # routed experts 0-3 of 64 score highest: held on another chip
    router = jnp.zeros((cfg.hidden_size, 72)).at[:, :4].set(1.0)
    lp = dict(lp, router=router, router_bias=jnp.zeros((72,)))
    x = jnp.abs(jax.random.normal(jax.random.key(6), (1, 5, cfg.hidden_size)))
    out, counts = branch(x, lp, banks, 48, cfg)
    assert not np.asarray(out).any()
    assert list(map(int, counts)) == [0, 0, 0, 20, 0, 5]


# ---------------------------------------------------------------------------
# (e) the selection bias chooses, the scores weigh
# ---------------------------------------------------------------------------


def test_selection_bias_changes_the_choice_and_not_the_gates():
    logits = jax.random.normal(jax.random.key(0), (12, 24))
    probs, plain_i, plain_g = topk_gates(logits, 4, False, "softmax", 6.0)
    # a bias that lifts column 23 over everything
    bias = jnp.zeros((24,)).at[23].set(1.0)
    _, top_i, gate = topk_gates(logits, 4, False, "softmax", 6.0, bias)
    assert (np.asarray(top_i) == 23).any(axis=1).all()
    assert not (np.asarray(plain_i) == 23).any(axis=1).all()
    # a chosen expert's gate is 6 x its score, bias or no bias
    np.testing.assert_allclose(gate, 6.0 * np.take_along_axis(np.asarray(probs), top_i, 1),
                               rtol=1e-6)
    # an expert chosen both ways has the same gate both ways
    for t in range(12):
        for e in set(map(int, top_i[t])) & set(map(int, plain_i[t])):
            a = float(gate[t][list(map(int, top_i[t])).index(e)])
            b = float(plain_g[t][list(map(int, plain_i[t])).index(e)])
            assert a == pytest.approx(b, rel=1e-6)
    # zeros change nothing, and no bias is the path every other model takes
    z = topk_gates(logits, 4, False, "softmax", 6.0, jnp.zeros((24,)))
    np.testing.assert_array_equal(z[1], plain_i)
    np.testing.assert_allclose(z[2], plain_g, rtol=1e-6)


def test_zero_compute_picks_are_in_no_group():
    """`held=(0, 16)` is routed experts 0-15; columns 16-23 are a range of
    their own: their picks are dead assignments, marked in `zero_pick`."""
    logits = jax.random.normal(jax.random.key(1), (9, 24))
    r = route_topk(logits, 4, norm_topk_prob=False, held=(0, 16), zero=8)
    _, top_i, _ = topk_gates(logits, 4, False)
    np.testing.assert_array_equal(r.zero_pick, np.asarray(top_i) >= 16)
    assert r.counts.shape == (17,)
    assert int(r.counts[:16].sum()) == int((np.asarray(top_i) < 16).sum())
    assert int(r.counts[16]) == int(r.zero_pick.sum())
    assert (np.asarray(r.expert_idx)[np.asarray(r.zero_pick)] == 16).all()
    # a router without zero-compute experts marks nothing
    assert route_topk(logits, 4, held=(0, 24)).zero_pick is None


# ---------------------------------------------------------------------------
# (f) the cache is addressed by attention sublayer
# ---------------------------------------------------------------------------


def test_the_latent_pool_has_a_row_an_attention_sublayer():
    cfg = tiny()
    assert cfg.attention_sublayers == 6 and cfg.stacks[0].block.attentions == 2
    cache = init_latent_cache(cfg, 8, 4, 2, 4)
    assert cache.kv.shape == (6, 8, 4, 128) and cache.tables.shape == (2, 4)
    assert init_cache(cfg, 2, 10).ckr.shape == (6, 2, 10, 40)
    # a model of one attention a layer keeps a row a layer
    pangu = ModelConfig(**resolve_preset("debug-tiny-pangu-moe"))
    assert pangu.attention_sublayers == pangu.num_hidden_layers == 4
    assert init_latent_cache(pangu, 8, 4, 2, 4).kv.shape[0] == 4
    # writing sublayer 2k + 1 leaves 2k (and every other row) untouched
    cache = cache._replace(tables=jnp.asarray([[0, 1, 8, 8], [2, 8, 8, 8]], jnp.int32))
    new = jnp.ones((2, 3, 40), jnp.float32)
    pos = jnp.asarray([[0, 1, 2], [0, 1, -1]])
    for k in range(3):
        wrote = cache.write(2 * k + 1, new, pos)
        changed = np.asarray(jnp.any(wrote.kv != cache.kv, axis=(1, 2, 3)))
        assert changed.tolist() == [r == 2 * k + 1 for r in range(6)]
    # ... and the counts on a dispatch's span are summed over the six rows
    assert cache.decode_counts([(5, 2)], cfg) == dict(
        attn_sublayers=6, kv_blocks=2, latent_blocks=12)
    assert cache.prefill_counts([(0, 8)], cfg)["attn_sublayers"] == 6


def test_the_two_attentions_of_a_layer_read_their_own_rows():
    """Decode through the engine equals the reference, and does NOT equal the
    reference whose second attention reads the first one's rows."""
    cfg = tiny()
    params = weights(cfg)
    prompt = list(map(int, np.random.default_rng(3).integers(0, 256, size=23)))
    _, out = run_engine(params, cfg, [(prompt, 6)])
    toks = out[0]["tokens"]
    rows = range(len(prompt) - 1, len(prompt) + len(toks) - 1)
    want = ref_logits(params, cfg, prompt + toks, rows=rows)[np.arange(len(toks)), toks]
    shared = ref_logits(params, cfg, prompt + toks, rows=rows,
                        shared_cache_row=True)[np.arange(len(toks)), toks]
    np.testing.assert_allclose(out[0]["logits"], want, atol=2e-4)
    assert np.abs(np.asarray(out[0]["logits"]) - shared).max() > 1e-2


# ---------------------------------------------------------------------------
# configuration: the published keys, the benchmark's file, what is refused
# ---------------------------------------------------------------------------

HF = {  # the catalog row's `config`, as published
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_layers": 28,
    "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


def test_hf_reader_round_trips_the_published_keys():
    got = ModelConfig(**model_config_from_hf_json(HF))
    want = ModelConfig(**resolve_preset("LongCat-Flash-Omni"))
    assert got == ModelConfig(**{**want.__dict__, "name": got.name})
    assert ModelConfig(**model_config_from_hf_json({**HF, "model_type": "longcat_flash"})) == got
    with pytest.raises(ValueError, match="zero_expert_type"):
        model_config_from_hf_json({**HF, "zero_expert_type": "copy"})
    with pytest.raises(ValueError, match="attention_method"):
        model_config_from_hf_json({**HF, "attention_method": "MHA"})


def test_published_sizes_count_560b_and_the_benchmarks_cut():
    full = ModelConfig(**resolve_preset("LongCat-Flash-Omni"))
    assert 555e9 < num_params(full) < 565e9
    assert full.router_width == 768 and full.attention_sublayers == 56
    assert full.mla_scales == (2.0, 12 ** 0.5)
    with open(os.path.join(ROOT, "benchmark", "configs", "longcat-flash-omni-4l-ep32.json")) as f:
        c = json.load(f)
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")}).model
    assert num_params(cfg) == c["parameters"] == 5_172_749_312
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    assert param_count(shapes) == c["parameters"]
    assert shapes["layers"]["q_b"].shape == (4, 2, 1536, 64 * 192)
    assert shapes["layers"]["router"].shape == (4, 6144, 768)
    assert shapes["layers"]["w_gate"].shape == (4, 16, 6144, 2048)
    assert shapes["layers"]["gate"].shape == (4, 2, 6144, 12288)
    # every number of the catalog row under its own key, but for `reduced`
    for key, value in HF.items():
        assert c[key] == value or key in c["reduced"], key
    assert set(c["reduced"]) == {"num_layers", "n_routed_experts", "vocab_size",
                                 "max_position_embeddings"}
    # the reference reads the same model from the file's published keys
    for key, value in reference.as_program({k: c[k] for k in reference.KEYS}).items():
        assert getattr(cfg, key) == value, key
    assert expert_counts(cfg)[-2:] == ("picks_zero", "rows_all_zero_or_away")


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
    (dict(model=tiny(attn_impl="ring")), "attn_impl='ring'"),
]


@pytest.mark.parametrize("over,message", REFUSALS, ids=[m for _, m in REFUSALS])
def test_validate_refuses_by_name(over, message):
    with pytest.raises(ValueError) as e:
        sections(**over).validate()
    assert "shortcut_moe" in str(e.value) and "zero_experts" in str(e.value)
    assert "moe_selection_bias" in str(e.value) and message in str(e.value)


@pytest.mark.parametrize("over,message", [
    (dict(kv_lora_rank=0, q_lora_rank=0, qk_nope_head_dim=0, qk_rope_head_dim=0,
          v_head_dim=0, mla_scale_q_lora=False, mla_scale_kv_lora=False), "shortcut_moe is built"),
    (dict(n_shared_experts=1), "shortcut_moe is built"),
    (dict(num_experts=0), "need num_experts > 0"),
    (dict(zero_experts=-1), "zero_experts must be >= 0"),
    (dict(router_experts=20, expert_first=8), "do not lie inside the router's 20 routed"),
])
def test_model_validate_messages(over, message):
    with pytest.raises(ValueError, match=message):
        tiny(**over).validate()
