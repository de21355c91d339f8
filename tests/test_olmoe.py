"""OLMoE on the normal path, against the benchmark's plain reference
(benchmark/reference_moe.py: float32 jax.numpy, nothing imported from the
program, every token through all k of its experts): un-renormalised top-k
gates, whole-vector QK-norm, the dropless dispatch, both auxiliary losses,
gradients of every weight through both grad engines, the cached decode path,
the preset and the HF import.

Tiny OLMoE-shaped preset (`picotron-tpu/debug-tiny-olmoe`: 16 experts, 4 a
token, QK-norm, untied head), float32, CPU. Float32 on both sides, so the
tolerances are reassociation's: 1e-5 on logits of size 2, 1e-4 of each
gradient leaf's largest element.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# loaded by its path: `benchmark/` is not put on sys.path, where its own
# `tests` package would shadow this one for every test file the worker runs after
_spec = importlib.util.spec_from_file_location(
    "reference_moe", os.path.join(os.path.dirname(__file__), "..", "benchmark",
                                  "reference_moe.py"))
reference_moe = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference_moe)

from picotron_tpu.config import (  # noqa: E402
    Config, DistributedConfig, ModelConfig, TrainingConfig,
    model_config_from_hf_json, resolve_preset,
)
from picotron_tpu.models.llama import forward, init_params, loss_fn  # noqa: E402

S = 48


def tiny_cfg(**kw) -> ModelConfig:
    return ModelConfig(name="picotron-tpu/debug-tiny-olmoe", dtype="float32",
                       attn_impl="reference",
                       **{**resolve_preset("debug-tiny-olmoe"),
                          "max_position_embeddings": 64, **kw})


def tiny_params(cfg, key=0):
    """init_params with the unit-initialised norm weights given values, so
    that a norm left out or applied to the wrong tensor shows."""
    p = init_params(cfg, jax.random.key(key))
    for i, k in enumerate(("q_norm", "k_norm", "input_norm", "post_norm")):
        w = p["layers"][k]
        p["layers"][k] = w * (1.0 + 0.2 * jax.random.normal(
            jax.random.key(10 + i), w.shape))
    return p


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_cfg()
    ids = jax.random.randint(jax.random.key(1), (S,), 0, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.key(2), (S,), 0, cfg.vocab_size)
    return cfg, dataclasses.asdict(cfg), tiny_params(cfg), ids, tgt


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def ref_logits(params, ids, m, **kw):
    return np.asarray(reference_moe.logits_at(
        params, ids, jnp.arange(ids.shape[0]), m, **kw)[0])


def test_forward_logits_match_reference(tiny):
    cfg, m, params, ids, _ = tiny
    got = np.asarray(forward(params, ids[None], cfg)[0])
    np.testing.assert_allclose(got, ref_logits(params, ids, m),
                               rtol=1e-5, atol=1e-5)


def test_loss_carries_both_auxiliary_terms(tiny):
    cfg, m, params, ids, tgt = tiny
    got = float(loss_fn(params, ids[None], tgt[None], cfg))
    t = reference_moe.loss_terms(params, ids, tgt, m)
    ce = float(t["nll_sum"]) / S
    want = ce + 0.01 * float(t["balance"]) + 0.001 * float(t["z"])
    assert got == pytest.approx(want, rel=1e-6)
    assert got == pytest.approx(float(reference_moe.loss(params, ids, tgt, m)),
                                rel=1e-6)
    # each term is there: neither coefficient alone gives the same loss
    for kw in (dict(router_aux_coef=0.0), dict(router_z_coef=0.0)):
        other = float(loss_fn(params, ids[None], tgt[None], tiny_cfg(**kw)))
        assert abs(other - got) > 1e-4, kw
    assert float(t["balance"]) > 1.0 and float(t["z"]) > 0.0


def step_cfg(engine: str) -> Config:
    return Config(
        distributed=DistributedConfig(),
        model=tiny_cfg(),
        training=TrainingConfig(
            seq_length=S, micro_batch_size=1, gradient_accumulation_steps=2,
            remat=True, remat_policy="dots_attn", grad_engine=engine))


@pytest.mark.parametrize("engine", ["ad", "fused"])
def test_engine_gradients_match_reference(engine):
    """Every weight's gradient (q_norm, k_norm and the router among them)
    out of one `_device_grads` call, against `jax.grad` of the reference's
    loss averaged over the step's sequences."""
    from tests.test_fused_bwd import device_grads_of

    cfg = step_cfg(engine)
    host = tiny_params(cfg.model)  # the norm weights moved off one
    grads, loss, extras, params = device_grads_of(cfg, host)
    m = dataclasses.asdict(cfg.model)
    toks = jax.random.randint(jax.random.key(1), (2, 1, S + 1), 0,
                              cfg.model.vocab_size)  # batch_for's draw

    def ref_loss(p):
        return sum(reference_moe.loss(p, toks[a, 0, :-1], toks[a, 0, 1:], m)
                   for a in range(2)) / 2

    want_loss, want = jax.value_and_grad(ref_loss)(host)
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    assert extras["moe_drop_frac"] == 0.0
    assert 1.0 <= extras["moe_load_max_over_mean"] <= 4.0
    assert {"q_norm", "k_norm", "router"} <= set(grads["layers"])
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    for (path, w), g in zip(flat, jax.tree.leaves(grads)):
        w = np.asarray(w)
        assert np.abs(w).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_array_less(
            np.abs(g - w).max() / np.abs(w).max(), 1e-4,
            err_msg=jax.tree_util.keystr(path))


def test_unrenormalised_gates_differ_from_renormalised(tiny):
    cfg, m, params, ids, _ = tiny
    raw = np.asarray(forward(params, ids[None], cfg)[0])
    renorm = np.asarray(forward(params, ids[None],
                                tiny_cfg(norm_topk_prob=True))[0])
    assert np.abs(raw - renorm).max() > 1e-2
    np.testing.assert_allclose(renorm, ref_logits(params, ids, m, renorm_gates=True),
                               rtol=1e-5, atol=1e-5)


def test_reference_probes_each_move_the_logits(tiny):
    _, m, params, ids, _ = tiny
    base = ref_logits(params, ids, m)
    for kw in (dict(renorm_gates=True), dict(drop_last_expert=True),
               dict(skip_qk_norm=True), dict(causal=False),
               dict(skip_layers=(1,))):
        assert np.abs(ref_logits(params, ids, m, **kw) - base).max() > 1e-2, kw


def test_skewed_router_drops_nothing_and_matches_reference(tiny):
    """Every token sends one of its 4 assignments to expert 0: that expert
    holds E / k = 4 times the mean load. The dropless dispatch computes all
    of them (the capacity path at its default factor 1.25 cannot)."""
    from picotron_tpu.ops.moe import moe_mlp

    cfg, m, params, ids, _ = tiny
    p = jax.tree.map(lambda x: x, params)
    # a component every token's hidden state shares, which expert 0's router
    # column reads
    p["embedding"] = p["embedding"].at[:, 0].add(8.0)
    p["layers"]["router"] = p["layers"]["router"].at[:, 0, 0].add(4.0)
    want = ref_logits(p, ids, m)
    got = np.asarray(forward(p, ids[None], cfg)[0])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the counters, at the op: nothing dropped, load exactly E / k
    lp = {k: v[0] for k, v in p["layers"].items()}
    x = jax.random.normal(jax.random.key(3), (1, S, cfg.hidden_size))
    x = x.at[..., 0].set(8.0)
    kw = dict(num_experts=16, top_k=4, norm_topk_prob=False)
    out, _, drop, load = moe_mlp(x, lp["router"], lp["w_gate"], lp["w_up"],
                                 lp["w_down"], capacity_factor=None, **kw)
    assert float(drop) == 0.0 and float(load) == pytest.approx(4.0)
    capped, _, drop_c, _ = moe_mlp(x, lp["router"], lp["w_gate"], lp["w_up"],
                                   lp["w_down"], capacity_factor=1.25, **kw)
    assert float(drop_c) > 0.1 and float(jnp.abs(capped - out).max()) > 1e-3


def test_cached_prefill_and_decode_match_reference(tiny):
    """`generate`'s layer copy: prefill 6 tokens into the KV cache, decode the
    rest one at a time; the logits after each must be the reference's full
    forward at that position."""
    from picotron_tpu.generate import _decode_layers, _logits_last, init_cache
    from picotron_tpu.models.llama import model_rope_tables

    cfg, m, params, ids, _ = tiny
    n, pre = 16, 6
    want = ref_logits(params, ids[:n], m)
    cos, sin = model_rope_tables(cfg)
    cache = init_cache(cfg, 1, n)
    x = params["embedding"][ids[None, :pre]]
    x, cache = _decode_layers(params, x, cache, jnp.arange(pre), cfg, cos, sin)
    got = [np.asarray(_logits_last(params, x, cfg)[0])]
    for t in range(pre, n):
        x = params["embedding"][ids[None, t:t + 1]]
        x, cache = _decode_layers(params, x, cache, jnp.array([t]), cfg, cos, sin)
        got.append(np.asarray(_logits_last(params, x, cfg)[0]))
    np.testing.assert_allclose(np.stack(got), want[pre - 1:], rtol=1e-5, atol=1e-5)


def test_step_metrics_carry_the_moe_counters():
    from picotron_tpu.parallel.api import init_sharded_state, make_train_step
    from tests.test_optimizer_offload import batch_for

    cfg = step_cfg("auto")
    batch, menv = batch_for(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    assert {"q_norm", "k_norm"} <= set(state.params["layers"])
    _, metrics = make_train_step(cfg, menv)(state, batch)
    assert float(metrics["moe_drop_frac"]) == 0.0
    assert 1.0 <= float(metrics["moe_load_max_over_mean"]) <= 4.0
    assert np.isfinite(float(metrics["loss"]))


def test_preset_holds_every_published_key():
    p = resolve_preset("allenai/OLMoE-1B-7B-0125-Instruct")
    assert p == resolve_preset("OLMoE-1B-7B")
    published = dict(
        vocab_size=50304, hidden_size=2048, intermediate_size=1024,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=16,
        max_position_embeddings=4096, rope_theta=10000.0, rms_norm_eps=1e-5,
        num_experts=64, num_experts_per_token=8, norm_topk_prob=False,
        router_aux_coef=0.01)
    assert {k: p[k] for k in published} == published
    cfg = ModelConfig(**p)
    assert cfg.qk_norm and cfg.expert_ffn_size == 1024
    assert not cfg.attention_bias and not cfg.tie_word_embeddings
    assert cfg.head_dim == 128 and cfg.hidden_act == "silu"
    # one layer: 419.6 M parameters, 402.7 M of them in the experts
    from picotron_tpu.config import num_params
    one = (num_params(dataclasses.replace(cfg, num_hidden_layers=1))
           - num_params(dataclasses.replace(cfg, num_hidden_layers=0)))
    assert one == 4 * 2048 * 2048 + 2048 * 64 + 64 * 3 * 2048 * 1024 + 4 * 2048


def test_hf_import_reads_olmoe_keys():
    hf = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
          "hidden_size": 2048, "intermediate_size": 1024,
          "max_position_embeddings": 4096, "model_type": "olmoe",
          "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
          "num_experts_per_tok": 8, "num_hidden_layers": 16,
          "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
          "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
          "router_aux_loss_coef": 0.01}
    got = model_config_from_hf_json(hf)
    preset = resolve_preset("allenai/OLMoE-1B-7B-0125-Instruct")
    preset.pop("router_z_coef")  # the paper's, not a key of config.json
    assert {k: got[k] for k in preset} == preset
    ModelConfig(**got).validate()
    # Mixtral has no norm_topk_prob key and always renormalises; no QK-norm
    mix = model_config_from_hf_json({**hf, "model_type": "mixtral",
                                     "num_local_experts": 8, "num_experts_per_tok": 2})
    assert mix["norm_topk_prob"] is False  # the key is read where present
    del hf["norm_topk_prob"], hf["num_experts"]
    mix = model_config_from_hf_json({**hf, "model_type": "mixtral",
                                     "num_local_experts": 8, "num_experts_per_tok": 2})
    assert mix["norm_topk_prob"] is True and "qk_norm" not in mix
    with pytest.raises(ValueError, match="clip_qkv"):
        model_config_from_hf_json({**hf, "model_type": "olmoe", "num_experts": 64,
                                   "clip_qkv": 8.0})


def test_validate_refuses_a_per_shard_qk_norm():
    cfg = Config(distributed=DistributedConfig(tp_size=2), model=tiny_cfg(),
                 training=TrainingConfig(seq_length=S))
    with pytest.raises(ValueError, match="qk_norm"):
        cfg.validate()
