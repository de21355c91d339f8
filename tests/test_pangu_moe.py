"""openPangu-Ultra-MoE's mechanisms at the tiny preset (`debug-tiny-pangu-moe`)
on the CPU: latent attention (MLA) expanded and absorbed, the latent cache
contiguous and paged, a dense layer before expert layers in two stacks, the
shared expert, sigmoid routing, a held share of the experts, sandwich norms.
The program is held to `benchmark/reference_pangu_moe.py` (plain float32
jax.numpy, un-absorbed attention, its own router and norms), which imports
nothing from it. The compiled kernels are held by tests/test_chip_compile.py."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, ServeConfig, TrainingConfig,
    config_from_dict, model_config_from_hf_json, num_params, resolve_preset,
)
from picotron_tpu.generate import _decode_layers, generate, init_cache
from picotron_tpu.models.llama import (
    forward, init_params, mlp_act, model_rope_tables, param_count, shared_expert,
)
from picotron_tpu.ops import mla
from picotron_tpu.ops.moe import moe_mlp_served
from picotron_tpu.serve import ServeEngine
from picotron_tpu.serve.paged_cache import init_latent_cache, latent_row_width

# loaded by its path: `benchmark/` is not put on sys.path, where its own
# `tests` package would shadow this one
_spec = importlib.util.spec_from_file_location(
    "reference_pangu_moe", os.path.join(os.path.dirname(__file__), "..", "benchmark",
                                        "reference_pangu_moe.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

SHARES = {"whole": {}, "share": dict(router_experts=64, expert_first=16)}


def tiny(**over) -> ModelConfig:
    return ModelConfig(dtype="float32", **{**resolve_preset("debug-tiny-pangu-moe"), **over})


def weights(cfg, seed=1):
    p = init_params(cfg, jax.random.key(seed))
    # a trained model's embedding scale, so that the layers show in the logits
    return dict(p, embedding=p["embedding"] * 0.1)


def published(cfg: ModelConfig) -> dict:
    """The keys `reference_pangu_moe` reads, from a ModelConfig."""
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        first_k_dense_replace=cfg.first_k_dense_replace,
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size,
        n_routed_experts=cfg.num_experts, n_shared_experts=cfg.n_shared_experts,
        num_experts_per_tok=cfg.num_experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor, sandwich_norm=cfg.sandwich_norm,
        tie_word_embeddings=cfg.tie_word_embeddings, router_experts=cfg.router_width,
        expert_first=cfg.expert_first)


def ref_logits(params, cfg, ids, rows=None):
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.arange(ids.shape[0]) if rows is None else jnp.asarray(rows)
    return np.asarray(reference.logits_at(params, ids, rows, published(cfg)))


# ---------------------------------------------------------------------------
# the model against the reference
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


def test_built_tree_has_two_stacks_and_the_counted_parameters():
    cfg = tiny()
    params = init_params(cfg, jax.random.key(0))
    assert [st.name for st in cfg.stacks] == ["dense_layers", "layers"]
    assert params["dense_layers"]["gate"].shape == (1, 64, 128)
    assert "w_gate" not in params["dense_layers"] and "gate" not in params["layers"]
    assert params["layers"]["w_gate"].shape == (3, 16, 64, 32)
    assert params["layers"]["shared_gate"].shape == (3, 64, 32)
    assert params["layers"]["kv_a"].shape == (3, 64, 32 + 8)
    for stack in ("dense_layers", "layers"):
        assert {"input_norm", "attn_out_norm", "post_norm", "mlp_out_norm"} <= set(params[stack])
    assert param_count(params) == num_params(cfg)
    share = tiny(**SHARES["share"])
    assert param_count(init_params(share, jax.random.key(0))) == num_params(share)
    # a model of one kind of block is the one `layers` stack it always was
    dense = ModelConfig(**resolve_preset("debug-tiny"))
    assert [st.name for st in dense.stacks] == ["layers"]
    assert set(init_params(dense, jax.random.key(0))) == {
        "embedding", "layers", "final_norm", "lm_head"}


def test_published_sizes_count_718b():
    cfg = ModelConfig(**resolve_preset("openPangu-Ultra-MoE-718B"))
    assert 715e9 < num_params(cfg) < 722e9
    # one chip's share at the benchmark's cut (ISSUE 35's table)
    cut = ModelConfig(**{**resolve_preset("openPangu-Ultra-MoE-718B"),
                         "num_hidden_layers": 5, "first_k_dense_replace": 1,
                         "num_experts": 16, "router_experts": 256, "vocab_size": 19200})
    assert num_params(cut) == 4_919_139_840


@pytest.mark.parametrize("share", SHARES)
def test_generate_matches_the_reference(share):
    """Prefill, then decode through the contiguous latent cache (absorbed):
    every generated token is the reference's argmax under teacher forcing."""
    cfg = tiny(**SHARES[share])
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(3), (2, 12), 0, cfg.vocab_size)
    out = np.asarray(generate(params, cfg, ids, 6))
    for b in range(2):
        want = ref_logits(params, cfg, out[b], rows=range(11, 17))
        assert (out[b, 12:] == want.argmax(-1)).all()


# ---------------------------------------------------------------------------
# the latent cache and its two attention paths
# ---------------------------------------------------------------------------


def test_absorbed_equals_expanded():
    cfg = tiny()
    rng = np.random.default_rng(0)
    b, s, t = 2, 5, 24
    heads, dn, dr, rank = 4, 16, 8, 32
    q_n = jnp.asarray(rng.normal(size=(b, s, heads, dn)), jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(b, s, heads, dr)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(b, t, rank + dr)), jnp.float32)
    kv_b = jnp.asarray(rng.normal(size=(rank, heads * (dn + 16))), jnp.float32) * 0.2
    q_pos = jnp.asarray([[19, 20, 21, 22, 23], [3, 4, 5, -1, -1]])

    def fetch(bi, ti):
        return (jax.lax.dynamic_slice_in_dim(rows[bi], ti * 8, 8, 0), ti * 8 + jnp.arange(8))

    with jax.default_matmul_precision("highest"):
        a = mla.latent_attention(q_n, q_r, q_pos, fetch, 3, 8, kv_b, cfg, absorbed=True)
        e = mla.latent_attention(q_n, q_r, q_pos, fetch, 3, 8, kv_b, cfg, absorbed=False)
        # the plain form: every head's keys and values, one softmax a row
        w_uk, w_uv = mla.up_weights(kv_b, cfg, jnp.float32)
        k = jnp.concatenate([jnp.einsum("btr,rhd->bthd", rows[..., :rank], w_uk),
                             jnp.broadcast_to(rows[:, :, None, rank:], (b, t, heads, dr))], -1)
        sc = jnp.einsum("bshd,bthd->bhst", jnp.concatenate([q_n, q_r], -1), k) / 24 ** 0.5
        seen = jnp.arange(t)[None, None, :] <= jnp.maximum(q_pos, 0)[:, :, None]
        p = jax.nn.softmax(jnp.where(seen[:, None], sc, -jnp.inf), axis=-1)
        want = jnp.einsum("bhst,bthd->bshd", p,
                          jnp.einsum("btr,rhd->bthd", rows[..., :rank], w_uv))
    np.testing.assert_allclose(a, want, atol=1e-5)
    np.testing.assert_allclose(e, want, atol=1e-5)
    assert a.shape == (b, s, heads, 16)


def test_the_form_follows_the_number_of_queries():
    cfg = tiny()
    assert mla.absorbed_suits(1, cfg) and mla.absorbed_suits(31, cfg)
    assert not mla.absorbed_suits(32, cfg)
    full = ModelConfig(**resolve_preset("openPangu-Ultra-MoE-718B"))
    assert mla.absorbed_suits(1, full) and mla.absorbed_suits(170, full)
    assert not mla.absorbed_suits(171, full) and not mla.absorbed_suits(256, full)


def test_latent_pool_bytes_a_position():
    """A cached position is [c | k_r] a layer and nothing per head: 576 x 2
    bytes a layer at the published widths, stored in rows of 640."""
    full = ModelConfig(**{**resolve_preset("openPangu-Ultra-MoE-718B"),
                          "num_hidden_layers": 5, "first_k_dense_replace": 1})
    state = full.kv_lora_rank + full.qk_rope_head_dim
    assert state * 2 * full.num_hidden_layers == 576 * 2 * 5
    assert latent_row_width(full) == 640
    per_head = full.num_attention_heads * (192 + 128) * 2 * full.num_hidden_layers
    assert per_head / (state * 2 * full.num_hidden_layers) > 71
    cache = jax.eval_shape(lambda: init_latent_cache(full, 64, 16, 2, 8))
    assert cache.kv.shape == (5, 64, 16, 640) and cache.kv.dtype == jnp.bfloat16
    assert cache.kv.size * 2 // (64 * 16) == 640 * 2 * 5
    cfg = tiny()
    assert latent_row_width(cfg) == 128
    assert init_cache(cfg, 2, 10).ckr.shape == (4, 2, 10, 40)


def run_engine(params, cfg, requests, **over):
    scfg = ServeConfig(**{**dict(decode_slots=2, block_size=4, prefill_chunk=8,
                                 max_model_len=64, decode_interval=2), **over})
    eng = ServeEngine(params, cfg, scfg)
    out = eng.run(requests)
    eng.close()
    assert eng.pool.in_use == 0
    return eng, out


@pytest.mark.parametrize("share,chunk", [("whole", 8), ("share", 8), ("whole", 32)])
def test_engine_matches_the_reference(share, chunk):
    """Chunked prefill on the rungs (absorbed at 8 queries a row, expanded
    at 32), then decode through the latent paged cache: each served token's
    logit against the reference's full forward under teacher forcing."""
    cfg = tiny(**SHARES[share])
    params = weights(cfg)
    rng = np.random.default_rng(5)
    requests = [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m)
                for n, m in ((37, 8), (6, 5), (21, 7), (45, 4))]
    eng, out = run_engine(params, cfg, requests, prefill_chunk=chunk)
    assert len(out) == 4 and eng.stats["decode_compiles"] <= 1  # the one decode program
    for (prompt, _), res in zip(requests, sorted(out, key=lambda r: r["id"])):
        toks = res["tokens"]
        want = ref_logits(params, cfg, prompt + toks,
                          rows=range(len(prompt) - 1, len(prompt) + len(toks) - 1))
        assert (want.argmax(-1) == np.asarray(toks)).all()
        np.testing.assert_allclose(res["logits"], want[np.arange(len(toks)), toks], atol=2e-4)
    # the counters of the decode steps
    if share == "share":
        assert 0 < eng.stats["picks_here"] < eng.stats["picks_all"]
    else:
        assert eng.stats["picks_here"] == eng.stats["picks_all"] > 0
    assert eng.stats["expert_slots"] % (3 * 16) == 0


def test_engine_agrees_with_generate():
    cfg = tiny()
    params = weights(cfg)
    prompt = list(map(int, np.random.default_rng(1).integers(0, 256, size=19)))
    _, out = run_engine(params, cfg, [(prompt, 9)])
    want = np.asarray(generate(params, cfg, jnp.asarray([prompt]), 9))[0, 19:]
    assert out[0]["tokens"] == list(map(int, want))


# ---------------------------------------------------------------------------
# the held share of the experts (the guide's section 4)
# ---------------------------------------------------------------------------


def expert_layer(cfg, params, li=1):
    lp = {n: w[li] for n, w in params["layers"].items()}
    banks = {n: params["layers"][n] for n in ("w_gate", "w_up", "w_down")}
    return lp, banks


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 16 shares (one held expert each) plus the
    shared expert once are the uncut reference's expert layer."""
    cfg = tiny()
    params = weights(cfg)
    lp, banks = expert_layer(cfg, params)
    x = jax.random.normal(jax.random.key(4), (2, 9, cfg.hidden_size), jnp.float32)
    live = jnp.ones((2, 9), bool)
    total = shared_expert(x, lp, cfg)
    seen = 0
    with jax.default_matmul_precision("highest"):
        for first in range(16):
            held = {n: w[:, first:first + 1] for n, w in banks.items()}
            routed, counts = moe_mlp_served(
                x, lp["router"], held["w_gate"], held["w_up"], held["w_down"],
                top_k=2, act=mlp_act(cfg), norm_topk_prob=True, live=live, layer=1,
                scoring="sigmoid", scale=2.5, expert_first=first)
            total = total + routed
            seen += int(counts[2])
            assert int(counts[3]) == 2 * 9 * 2
        assert seen == 2 * 9 * 2  # every pick lands on exactly one share
        m = dict(published(cfg))
        w = {n: v[1] for n, v in params["layers"].items()}
        want = reference._experts(x.reshape(18, -1), w, m, frozenset()).reshape(x.shape)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_a_token_with_no_pick_here_gets_the_shared_expert_alone():
    cfg = tiny(router_experts=64, expert_first=48)
    params = weights(cfg)
    lp, banks = expert_layer(cfg, params)
    # a router that scores experts 0 and 1 highest for every token: held elsewhere
    router = jnp.zeros((cfg.hidden_size, 64)).at[:, :2].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.key(6), (1, 5, cfg.hidden_size)))
    out, counts = moe_mlp_served(
        x, router, banks["w_gate"], banks["w_up"], banks["w_down"], top_k=2,
        act=mlp_act(cfg), norm_topk_prob=True, live=jnp.ones((1, 5), bool), layer=0,
        scoring="sigmoid", scale=2.5, expert_first=48)
    assert not np.asarray(out).any()
    assert list(map(int, counts)) == [0, 0, 0, 10]
    # ... so the block's output is the shared expert's, through the layer loop too
    from picotron_tpu.generate import _moe_served_block
    lp = dict(lp, router=router)
    got, _ = _moe_served_block(x, lp, banks, 0, cfg, jnp.ones((1, 5), bool))
    from picotron_tpu.ops.rmsnorm import rms_norm
    h = rms_norm(x, lp["post_norm"], cfg.rms_norm_eps)
    np.testing.assert_allclose(got, shared_expert(h, lp, cfg), atol=1e-6)


def test_softmax_models_route_as_before():
    """The scoring law and the held share are configuration: a softmax model
    with every expert here takes the path it took."""
    from picotron_tpu.ops.moe import route_topk
    logits = jax.random.normal(jax.random.key(0), (12, 8))
    a = route_topk(logits, 2)
    b = route_topk(logits, 2, scoring="softmax", scale=1.0, held=(0, 8))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    s = route_topk(logits, 2, scoring="sigmoid", scale=2.5)
    np.testing.assert_allclose(s.gate.sum(-1), 2.5, rtol=1e-6)
    np.testing.assert_array_equal(s.expert_idx, a.expert_idx)  # the same k largest


# ---------------------------------------------------------------------------
# configuration: the published keys, and what is refused by name
# ---------------------------------------------------------------------------

HF = {  # the catalog row's `config`, as published
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}


def test_hf_reader_round_trips_the_published_keys():
    got = ModelConfig(**model_config_from_hf_json(HF))
    want = ModelConfig(**resolve_preset("openPangu-Ultra-MoE-718B"))
    assert got == ModelConfig(**{**want.__dict__, "name": got.name})
    assert (got.q_lora_rank, got.kv_lora_rank, got.qk_nope_head_dim, got.qk_rope_head_dim,
            got.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (got.first_k_dense_replace, got.num_experts, got.router_width,
            got.num_experts_per_token, got.n_shared_experts) == (3, 256, 256, 8, 1)
    assert (got.moe_scoring, got.routed_scaling_factor, got.sandwich_norm) == ("sigmoid", 2.5, True)
    assert got.rope_dim == 64 and got.mla
    # and through config_from_dict with the share as configuration
    cfg = config_from_dict({"model": {**model_config_from_hf_json(HF), "name": "pangu",
                                      "num_experts": 16, "router_experts": 256}})
    assert cfg.model.stacks[0][:2] == ("dense_layers", 3)
    assert cfg.model.stacks[1][:2] == ("layers", 58)


def test_dense_layers_load_only_at_the_head_of_the_stack():
    base = {k: v for k, v in HF.items() if k != "model_type"}
    base.update(model_type="mellum", head_dim=128, num_experts=8)
    ok = model_config_from_hf_json(dict(base, mlp_layer_types=["dense", "dense", "sparse"]))
    assert ok["first_k_dense_replace"] == 2
    for bad in (["sparse", "dense"], ["dense", "sparse", "dense"], ["dense"]):
        with pytest.raises(ValueError, match="head of the stack"):
            model_config_from_hf_json(dict(base, mlp_layer_types=bad))


REFUSALS = [
    (dict(model=dict(attn_impl="flash")), "attn_impl='flash'"),
    (dict(model=dict(attn_impl="ring"), distributed=dict(cp_size=2)), "attn_impl='ring'"),
    (dict(distributed=dict(cp_size=2)), "context parallelism"),
    (dict(training=dict(grad_engine="fused")), "grad_engine='fused'"),
    (dict(distributed=dict(tp_size=2)), "tensor parallelism"),
    (dict(distributed=dict(pp_size=2)), "pipeline parallelism"),
    (dict(distributed=dict(ep_size=2)), "expert parallelism"),
    (dict(serve=dict(fleet_size=2)), "fleet_size"),
]


@pytest.mark.parametrize("sections,message", REFUSALS, ids=[m for _, m in REFUSALS])
def test_validate_refuses_by_name(sections, message):
    raw = {"model": {"name": "debug-tiny-pangu-moe"},
           "training": {"seq_length": 64}}
    for k, v in sections.items():
        raw[k] = {**raw.get(k, {}), **v}
    with pytest.raises(ValueError) as e:
        config_from_dict(raw)
    assert message in str(e.value)


def test_each_mechanism_alone_is_refused_and_named():
    for over, word in ((dict(sandwich_norm=True), "sandwich_norm"),
                       (dict(num_experts=8, n_shared_experts=1), "n_shared_experts"),
                       (dict(num_experts=8, moe_scoring="sigmoid"), "sigmoid"),
                       (dict(num_experts=4, router_experts=8), "held share"),
                       (dict(num_experts=8, first_k_dense_replace=1), "first_k_dense_replace")):
        cfg = Config(distributed=DistributedConfig(tp_size=2),
                     model=ModelConfig(**{**resolve_preset("debug-tiny"), **over}),
                     training=TrainingConfig(seq_length=64))
        with pytest.raises(ValueError, match=word):
            cfg.validate()


def test_model_validate_messages():
    for over, word in ((dict(kv_lora_rank=0), "latent attention's"),
                       (dict(qk_rope_head_dim=7), "even"),
                       (dict(first_k_dense_replace=4), "at least one expert layer"),
                       (dict(moe_scoring="tanh"), "moe_scoring"),
                       (dict(router_experts=8), "do not lie inside"),
                       (dict(attention_bias=True), "no qkv bias")):
        with pytest.raises(ValueError, match=word):
            tiny(**over).validate()
    with pytest.raises(ValueError, match="needs num_experts > 0"):
        ModelConfig(**{**resolve_preset("debug-tiny"), "first_k_dense_replace": 1}).validate()
