"""reference_nemotron_h.py: imports nothing from the program, agrees with the
program's `forward()` at the tiny preset, carries out of a sequence the state
the program's first mixer carries, the int8 control moves the logits, and the
cell's file names what its runner needs. (Each of the probe's faults moving the
logits, and `generate()` and `ServeEngine` against this file, are held in
tests/test_nemotron_h.py, beside the program.)"""
import ast
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
import reference_nemotron_h as reference  # noqa: E402
from picotron_tpu.config import ModelConfig, config_from_dict, resolve_preset  # noqa: E402
from picotron_tpu.generate import _decode_layers, init_cache  # noqa: E402
from picotron_tpu.models.llama import forward, init_params, model_rope_tables  # noqa: E402

CELL = "nemotron3-super-120b-a12b-22l-ep8.agent-ctx"
LETTER = {"mamba2": "M", "full_attention": "*", "experts": "E"}


def tiny():
    cfg = ModelConfig(dtype="float32", **resolve_preset("debug-tiny-nemotron-h"))
    cfg.validate()
    p = init_params(cfg, jax.random.key(1))
    m = dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers,
        hybrid_override_pattern="".join(LETTER[k] for k in cfg.layer_kinds),
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        mamba_num_heads=cfg.mamba_num_heads, mamba_head_dim=cfg.mamba_head_dim,
        n_groups=cfg.n_groups, ssm_state_size=cfg.ssm_state_size, conv_kernel=cfg.mamba_d_conv,
        use_conv_bias=True, n_routed_experts=cfg.num_experts, router_experts=cfg.num_experts,
        expert_first=0, num_experts_per_tok=cfg.num_experts_per_token,
        moe_intermediate_size=cfg.moe_intermediate_size, moe_latent_size=cfg.moe_latent_size,
        moe_shared_expert_intermediate_size=cfg.moe_shared_expert_intermediate_size,
        n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=5.0,
        mlp_hidden_act="relu2", layer_norm_epsilon=cfg.rms_norm_eps, tie_word_embeddings=False)
    assert reference.kinds_of(m) == cfg.layer_kinds
    return cfg, dict(p, embedding=p["embedding"] * 0.1), m


def test_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_nemotron_h.py")) as f:
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
    assert want.shape == (8, 16, 32)  # the pool's layout: [heads, P, N]
    cos, sin = model_rope_tables(cfg)
    with jax.default_matmul_precision("highest"):
        _, cache = _decode_layers(params, params["embedding"][ids][None], init_cache(cfg, 1, 32),
                                  jnp.arange(29), cfg, cos, sin)
    np.testing.assert_allclose(cache.state[0, 0], want, atol=1e-5)
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
    only = reference.rounded_to(params, 8, only=("ssd_in",))
    assert not np.array_equal(only["layers"]["ssd_in"], params["layers"]["ssd_in"])
    np.testing.assert_array_equal(only["layers"]["ssd_out"], params["layers"]["ssd_out"])
    np.testing.assert_array_equal(only["lm_head"], params["lm_head"])
    # what is no matrix stays
    for n in ("ssd_conv", "ssd_conv_bias", "ssd_A_log", "ssd_dt_bias", "ssd_D", "ssd_norm",
              "router_bias", "input_norm"):
        np.testing.assert_array_equal(reference.rounded_to(params, 8)["layers"][n],
                                      params["layers"][n])


def test_the_cells_file_names_what_its_runner_needs():
    with open(os.path.join(HERE, "workloads", CELL + ".json")) as f:
        w = json.load(f)
    with open(os.path.join(HERE, "configs", w["config"] + ".json")) as f:
        c = json.load(f)
    assert w["runner"] == "serve_reference_reuse" and w["reference"] == "reference_nemotron_h"
    assert set(w["limits"]) == {"tie", "logit_err_mean", "logit_err_max"} and w["picks"] >= 8
    assert w["reuse"]["state_pool"] == "state" and set(w["reuse"]["limits"]) == {
        "reuse_logit_err_mean", "state_err", "state_bf16_share"}
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")})
    want = reference.as_program({k: c[k] for k in reference.KEYS})
    assert all(getattr(cfg.model, k) == v for k, v in want.items()), [
        (k, v, getattr(cfg.model, k)) for k, v in want.items() if getattr(cfg.model, k) != v]
    assert {"layer_types", "mamba_num_heads", "moe_latent_size", "hidden_act", "ssd"} <= set(want)
    # a program from before this configuration has no `ssd`: the runner stops at once
    assert getattr(object(), "ssd", None) != want["ssd"]
    with open(os.path.join(os.path.dirname(HERE), "picotron_tpu", "serve", "engine.py")) as f:
        src = f.read()
    assert all(f"self.{attr} = " in src for attr in w["pools"].values())
    # `serve_mellum2.pick` reads the first class's longest prompt
    assert w["traffic"]["classes"][0]["prompt_tokens"]["max"] == 8192
