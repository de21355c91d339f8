"""reference_pangu_moe.py: imports nothing from the program, agrees with the
program's `forward()` at the tiny preset, each of the probe's controls moves the
logits, and the runner's key check names what a parent lacks."""
import ast
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import reference_pangu_moe as reference  # noqa: E402
from picotron_tpu.config import ModelConfig, resolve_preset  # noqa: E402
from picotron_tpu.models.llama import forward, init_params  # noqa: E402


def runner():
    spec = importlib.util.spec_from_file_location(
        "serve_pangu_moe", os.path.join(HERE, "runners", "serve_pangu_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny(**over):
    cfg = ModelConfig(dtype="float32", **{**resolve_preset("debug-tiny-pangu-moe"), **over})
    p = init_params(cfg, jax.random.key(1))
    m = dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers, num_attention_heads=cfg.num_attention_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        first_k_dense_replace=cfg.first_k_dense_replace, intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size, n_routed_experts=cfg.num_experts,
        n_shared_experts=cfg.n_shared_experts, num_experts_per_tok=cfg.num_experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob, routed_scaling_factor=cfg.routed_scaling_factor,
        sandwich_norm=cfg.sandwich_norm, tie_word_embeddings=cfg.tie_word_embeddings,
        router_experts=cfg.router_width, expert_first=cfg.expert_first)
    return cfg, dict(p, embedding=p["embedding"] * 0.1), m


def test_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_pangu_moe.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"__future__", "functools", "jax", "numpy"}, names


@pytest.mark.parametrize("share", [{}, dict(router_experts=64, expert_first=32)],
                         ids=["whole", "share"])
def test_reference_agrees_with_forward(share):
    cfg, params, m = tiny(**share)
    ids = jax.random.randint(jax.random.key(2), (1, 48), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(params, ids, cfg))[0]
    want = np.asarray(reference.logits_at(params, ids[0], jnp.arange(48), m))
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_control_moves_the_logits(fault):
    cfg, params, m = tiny(router_experts=32, expert_first=0)
    ids = jax.random.randint(jax.random.key(3), (64,), 0, cfg.vocab_size)
    rows = jnp.arange(64)
    exact = np.asarray(reference.logits_at(params, ids, rows, m))
    moved = np.asarray(reference.logits_at(params, ids, rows, m, **{fault: True}))
    assert np.abs(moved - exact).max() > 0.05 * np.abs(exact).max(), fault
    with pytest.raises(TypeError):
        reference.hidden_states(params, ids, m, no_such_fault=True)


def test_int8_control_moves_the_logits_and_bf16_hardly():
    cfg, params, m = tiny()
    ids = jax.random.randint(jax.random.key(4), (64,), 0, cfg.vocab_size)
    rows = jnp.arange(64)
    exact = np.asarray(reference.logits_at(params, ids, rows, m))
    int8 = np.asarray(reference.logits_at(reference.rounded_to(params, 8), ids, rows, m))
    int12 = np.asarray(reference.logits_at(reference.rounded_to(params, 12), ids, rows, m))
    assert np.abs(int8 - exact).mean() > 8 * np.abs(int12 - exact).mean() > 0
    only = reference.rounded_to(params, 8, only=("kv_b",))
    assert not np.array_equal(only["layers"]["kv_b"], params["layers"]["kv_b"])
    assert not np.array_equal(only["dense_layers"]["kv_b"], params["dense_layers"]["kv_b"])
    np.testing.assert_array_equal(only["layers"]["q_b"], params["layers"]["q_b"])


def test_absent_experts_add_nothing():
    """Given (first, held) the reference leaves out what the absent experts
    would add: with a share held, the layer's output differs from the whole
    model's by exactly those experts' gated outputs."""
    cfg, params, m = tiny()
    w = {n: v[0] for n, v in params["layers"].items()}
    z = jax.random.normal(jax.random.key(5), (20, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = reference._experts(z, w, m, frozenset())
        parts = []
        for first in (0, 8):
            half = {**w, **{n: w[n][first:first + 8] for n in ("w_gate", "w_up", "w_down")}}
            parts.append(reference._experts(z, half, dict(m, n_routed_experts=8, expert_first=first),
                                            frozenset({"no_shared_expert"})))
        shared = reference._swiglu(z, w["shared_gate"], w["shared_up"], w["shared_down"])
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole, atol=1e-5)


def test_runner_checks_the_file_against_the_program():
    rn = runner()
    with open(os.path.join(HERE, "configs", "openpangu-ultra-moe-5l-ep16.json")) as f:
        c = json.load(f)
    from picotron_tpu.config import config_from_dict

    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")})
    want = rn.as_program(rn.published(c))
    assert all(getattr(cfg.model, k) == v for k, v in want.items())
    assert {"kv_lora_rank", "router_experts", "first_k_dense_replace", "sandwich_norm"} <= set(want)
    assert set(rn.LIMITS) == {"tie", "logit_err_mean", "logit_err_max"}
