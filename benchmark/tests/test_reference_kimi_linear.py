"""reference_kimi_linear.py: imports nothing from the program, agrees with the
program's `forward()` at the tiny preset, carries out of a sequence the state
the program's first mixer carries, the int8 control moves the logits, and the
cell's file names what its runner needs. (Each of the probe's faults moving the
logits, and `generate()` and `ServeEngine` against this file, are held in
tests/test_kimi_linear.py, beside the program.)"""
import ast
import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import reference_kimi_linear as reference  # noqa: E402
from picotron_tpu.config import ModelConfig, config_from_dict, resolve_preset  # noqa: E402
from picotron_tpu.models.llama import (  # noqa: E402
    forward, gdn_start, init_params, kda_mixer, norm_weight, rms_norm,
)
from picotron_tpu.ops.kda import kda  # noqa: E402

CELL = "kimi-linear-48b-a3b-12l-ep8.reason-longout"


def tiny():
    cfg = ModelConfig(dtype="float32", **resolve_preset("debug-tiny-kimi-linear"))
    cfg.validate()
    p = init_params(cfg, jax.random.key(1))
    kinds = cfg.layer_kinds
    lin = dict(kda_layers=[i + 1 for i, k in enumerate(kinds) if k == "kda"],
               full_attn_layers=[i + 1 for i, k in enumerate(kinds) if k != "kda"],
               head_dim=cfg.linear_key_head_dim, num_heads=cfg.linear_num_key_heads,
               short_conv_kernel_size=cfg.linear_conv_kernel_dim)
    m = dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers, num_attention_heads=cfg.num_attention_heads,
        q_lora_rank=None, kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim, mla_use_nope=True,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps, linear_attn_config=lin,
        first_k_dense_replace=1, intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size, num_experts=cfg.num_experts,
        num_experts_per_token=cfg.num_experts_per_token, num_shared_experts=1,
        moe_renormalize=True, moe_router_activation_func="sigmoid",
        routed_scaling_factor=cfg.routed_scaling_factor, router_experts=cfg.router_width,
        expert_first=0)
    assert reference.kinds_of(m) == cfg.layer_kinds
    return cfg, dict(p, embedding=p["embedding"] * 0.1), m


def test_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_kimi_linear.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"__future__", "functools", "jax", "numpy"}, names


def test_reference_agrees_with_forward():
    cfg, params, m = tiny()
    ids = jax.random.randint(jax.random.key(2), (1, 48), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(params, ids, cfg))[0]
    want = np.asarray(reference.logits_at(params, ids[0], jnp.arange(48), m))
    np.testing.assert_allclose(got, want, atol=5e-5)
    with pytest.raises(TypeError):
        reference.hidden_states(params, ids[0], m, no_such_fault=True)


def test_first_state_is_what_the_programs_first_mixer_carries():
    cfg, params, m = tiny()
    ids = jax.random.randint(jax.random.key(3), (29,), 0, cfg.vocab_size)
    want = np.asarray(reference.first_state(params, ids, m))
    assert want.shape == (4, 8, 8)  # the pool's layout: [heads, d_k, d_v]
    lp = {n: w[0] for n, w in params["dense_layers"].items()
          if n.startswith("kda_") or n == "input_norm"}
    x = params["embedding"][ids][None]
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, norm_weight(lp["input_norm"], cfg), cfg.rms_norm_eps)
        state, tail = gdn_start(cfg, 1)
        _, got, _ = kda_mixer(h, lp, cfg, partial(kda, state=state), tail,
                              jnp.ones((1, 29), bool))
    np.testing.assert_allclose(got[0], want, atol=1e-5)
    kept = np.asarray(reference.first_state(params, ids, m, state_kept=True))
    assert np.abs(kept - want).max() > 1e-3


def test_int8_control_moves_the_logits_and_12_bits_hardly():
    cfg, params, m = tiny()
    ids = jax.random.randint(jax.random.key(4), (64,), 0, cfg.vocab_size)
    rows = jnp.arange(64)
    exact = np.asarray(reference.logits_at(params, ids, rows, m))
    int8 = np.asarray(reference.logits_at(reference.rounded_to(params, 8), ids, rows, m))
    int12 = np.asarray(reference.logits_at(reference.rounded_to(params, 12), ids, rows, m))
    # (16 x where the logits answer in proportion; a flipped pick of the router answers with more)
    assert np.median(np.abs(int8 - exact)) > 4 * np.median(np.abs(int12 - exact)) > 0
    only = reference.rounded_to(params, 8, only=("kda_qkv",))
    assert not np.array_equal(only["layers"]["kda_qkv"], params["layers"]["kda_qkv"])
    np.testing.assert_array_equal(only["layers"]["kda_out"], params["layers"]["kda_out"])
    np.testing.assert_array_equal(only["embedding"], params["embedding"])
    # what is no matrix stays
    for n in ("kda_conv", "kda_A_log", "kda_dt_bias", "kda_norm", "router_bias", "kv_a_norm"):
        np.testing.assert_array_equal(reference.rounded_to(params, 8)["layers"][n],
                                      params["layers"][n])


def test_the_cells_file_names_what_its_runner_needs():
    with open(os.path.join(HERE, "workloads", CELL + ".json")) as f:
        w = json.load(f)
    with open(os.path.join(HERE, "configs", w["config"] + ".json")) as f:
        c = json.load(f)
    assert w["runner"] == "serve_reference_reuse" and w["reference"] == "reference_kimi_linear"
    assert set(w["limits"]) == {"tie", "logit_err_mean", "logit_err_max"} and w["picks"] >= 8
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")})
    want = reference.as_program({k: c[k] for k in reference.KEYS})
    assert all(getattr(cfg.model, k) == v for k, v in want.items()), [
        (k, v, getattr(cfg.model, k)) for k, v in want.items() if getattr(cfg.model, k) != v]
    assert {"layer_types", "mla_use_nope", "q_lora_rank", "linear_key_head_dim", "kda"} <= set(want)
    # a program from before this configuration has no `kda`: the runner stops at once
    assert getattr(object(), "kda", None) != want["kda"]
    with open(os.path.join(os.path.dirname(HERE), "picotron_tpu", "serve", "engine.py")) as f:
        src = f.read()
    assert all(f"self.{attr} = " in src for attr in w["pools"].values())
    # `serve_mellum2.pick` reads the first class's longest prompt
    assert w["traffic"]["classes"][0]["prompt_tokens"]["max"] == 4096
