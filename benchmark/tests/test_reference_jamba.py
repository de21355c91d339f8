"""reference_jamba.py: imports nothing from the program, agrees with the
program's `forward()` at the tiny preset (whole, and at a depth with one
attention), carries out of a sequence the state the program's mixer carries
(in the pool's layout), the int8 control moves the logits, and the cell's file
names what its runner needs. (Each of the probe's faults moving the logits, and
`generate()` and `ServeEngine` against this file, are held in
tests/test_jamba.py, beside the program.)"""
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
import reference_jamba as reference  # noqa: E402
from picotron_tpu.config import ModelConfig, config_from_dict, resolve_preset  # noqa: E402
from picotron_tpu.models.llama import (  # noqa: E402
    forward, held_conv, held_scan, init_params, mamba_mixer, mamba_start, norm_weight, rms_norm,
)

M, F = "mamba", "full_attention"
CELL = "jamba2-3b.chat-burst"


def tiny(period=6, offset=3, **over):
    cfg = ModelConfig(dtype="float32", **{**resolve_preset("debug-tiny-jamba"), **over})
    cfg.validate()
    p = init_params(cfg, jax.random.key(1))
    lay = dict(p["layers"])  # norm weights and D that are not all one
    for j, n in enumerate(("ssm_dt_norm", "ssm_b_norm", "ssm_c_norm", "ssm_D", "post_norm")):
        lay[n] = lay[n] * (0.7 + 0.15 * j)
    m = dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size, num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, attn_layer_period=period,
        attn_layer_offset=offset, mamba_d_state=cfg.mamba_d_state,
        mamba_d_conv=cfg.mamba_d_conv, mamba_expand=cfg.mamba_expand,
        mamba_dt_rank=cfg.mamba_dt_rank, mamba_conv_bias=cfg.mamba_conv_bias,
        mamba_proj_bias=cfg.mamba_proj_bias, rms_norm_eps=cfg.rms_norm_eps,
        tie_word_embeddings=True, num_experts=1)
    assert reference.kinds_of(m) == cfg.layer_kinds
    return cfg, dict(p, embedding=p["embedding"] * 0.1, layers=lay), m


def test_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_jamba.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"__future__", "functools", "jax", "numpy"}, names


@pytest.mark.parametrize("shape", [
    dict(), dict(period=4, offset=1, num_hidden_layers=4, layer_types=(M, F, M, M)),
    dict(mamba_conv_bias=False)], ids=["two-periods", "one-attention", "no-conv-bias"])
def test_reference_agrees_with_forward(shape):
    cfg, params, m = tiny(**shape)
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
    assert want.shape == (cfg.mamba_d_state, cfg.ssm_inner)  # the pool's layout: S^T
    lp = {n: w[0] for n, w in params["layers"].items()
          if n.startswith("ssm_") or n == "input_norm"}
    x = params["embedding"][ids][None]
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, norm_weight(lp["input_norm"], cfg), cfg.rms_norm_eps)
        _, (got, _) = mamba_mixer(h, lp, cfg, held_conv, held_scan, mamba_start(cfg, 1),
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
    assert np.median(np.abs(int8 - exact)) > 8 * np.median(np.abs(int12 - exact)) > 0
    only = reference.rounded_to(params, 8, only=("ssm_in",))
    assert not np.array_equal(only["layers"]["ssm_in"], params["layers"]["ssm_in"])
    np.testing.assert_array_equal(only["layers"]["ssm_out"], params["layers"]["ssm_out"])
    np.testing.assert_array_equal(only["embedding"], params["embedding"])
    # what is no matrix stays
    for n in ("ssm_conv", "ssm_conv_bias", "ssm_A_log", "ssm_D", "ssm_dt_bias", "ssm_dt_norm"):
        np.testing.assert_array_equal(reference.rounded_to(params, 8)["layers"][n],
                                      params["layers"][n])


def test_the_cells_file_names_what_its_runner_needs():
    """`serve_reference` reads its reference, its pools, its limits and its
    picks from the cell's file, and checks the configuration's published keys
    against the model the program built; `serve_reference_reuse` reads `reuse`."""
    with open(os.path.join(HERE, "workloads", CELL + ".json")) as f:
        w = json.load(f)
    with open(os.path.join(HERE, "configs", w["config"] + ".json")) as f:
        c = json.load(f)
    assert w["runner"] == "serve_reference_reuse" and w["reference"] == "reference_jamba"
    assert os.path.exists(os.path.join(HERE, "runners", w["runner"] + ".py"))
    assert set(w["limits"]) == {"tie", "logit_err_mean", "logit_err_max"} and w["picks"] >= 8
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")})
    want = reference.as_program({k: c[k] for k in reference.KEYS})
    assert all(getattr(cfg.model, k) == v for k, v in want.items()), [
        (k, v, getattr(cfg.model, k)) for k, v in want.items() if getattr(cfg.model, k) != v]
    assert {"layer_types", "rope_parameters", "mamba_d_state", "mamba_dt_rank", "ssm",
            "head_dim"} <= set(want)
    # a program from before this configuration has no `ssm`: the runner stops at once
    assert getattr(object(), "ssm", None) != want["ssm"]
    # the pools the file names are the engine's attributes
    spec = importlib.util.spec_from_file_location(
        "engine", os.path.join(os.path.dirname(HERE), "picotron_tpu", "serve", "engine.py"))
    with open(spec.origin) as f:
        src = f.read()
    assert all(f"self.{attr} = " in src for attr in w["pools"].values())
    # `serve_mellum2.pick` reads the first class's longest prompt
    assert w["traffic"]["classes"][0]["prompt_tokens"]["max"] == 3072
