"""reference_k_exaone.py: imports nothing from the program, agrees with the
program's `forward()` at the tiny preset (whole and as a share, at the cut and
at a depth whose expert stack has layers left over), the int8 control moves the
logits, a share leaves out what the absent experts would add, and the cell's
file names what its runner needs. (Each of the probe's six faults moving the
logits is held in tests/test_k_exaone.py, beside the program.)"""
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
import reference_k_exaone as reference  # noqa: E402
from picotron_tpu.config import ModelConfig, config_from_dict, resolve_preset  # noqa: E402
from picotron_tpu.models.llama import forward, init_params  # noqa: E402

S, F = "sliding_attention", "full_attention"
CELL = "k-exaone-236b-a23b-5l-ep8.reason-decode"


def tiny(**over):
    cfg = ModelConfig(dtype="float32", **{**resolve_preset("debug-tiny-exaone-moe"), **over})
    cfg.validate()
    p = init_params(cfg, jax.random.key(1))
    for name in ("dense_layers", "layers"):  # norm weights that are not all one
        p[name] = dict(p[name], q_norm=p[name]["q_norm"] * 1.3, k_norm=p[name]["k_norm"] * 0.7)
    m = dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers, num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps, layer_types=list(cfg.layer_kinds),
        sliding_window=cfg.sliding_window,
        rope_parameters=dict(rope_theta=cfg.rope_theta, rope_type="default"),
        first_k_dense_replace=cfg.first_k_dense_replace, intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size, num_experts=cfg.num_experts,
        num_shared_experts=cfg.n_shared_experts, num_experts_per_tok=cfg.num_experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob, routed_scaling_factor=cfg.routed_scaling_factor,
        scoring_func=cfg.moe_scoring, tie_word_embeddings=cfg.tie_word_embeddings,
        router_experts=cfg.router_width, expert_first=cfg.expert_first)
    return cfg, dict(p, embedding=p["embedding"] * 0.1), m


def test_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_k_exaone.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"__future__", "functools", "jax", "numpy"}, names


@pytest.mark.parametrize("over", [
    {}, dict(num_experts=4, router_experts=16, expert_first=8),
    dict(num_hidden_layers=8, layer_types=(S, S, S, F) * 2)],
    ids=["whole", "share", "1+7"])
def test_reference_agrees_with_forward(over):
    cfg, params, m = tiny(**over)
    ids = jax.random.randint(jax.random.key(2), (1, 48), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(params, ids, cfg))[0]
    want = np.asarray(reference.logits_at(params, ids[0], jnp.arange(48), m))
    np.testing.assert_allclose(got, want, atol=3e-5)
    with pytest.raises(TypeError):
        reference.hidden_states(params, ids[0], m, no_such_fault=True)


def test_int8_control_moves_the_logits_and_12_bits_hardly():
    cfg, params, m = tiny()
    ids = jax.random.randint(jax.random.key(4), (64,), 0, cfg.vocab_size)
    rows = jnp.arange(64)
    exact = np.asarray(reference.logits_at(params, ids, rows, m))
    int8 = np.asarray(reference.logits_at(reference.rounded_to(params, 8), ids, rows, m))
    int12 = np.asarray(reference.logits_at(reference.rounded_to(params, 12), ids, rows, m))
    # the median: a pick that flips between two nearly tied experts moves a token's
    # logits by much at any precision, and the mean with it
    assert np.median(np.abs(int8 - exact)) > 8 * np.median(np.abs(int12 - exact)) > 0
    only = reference.rounded_to(params, 8, only=("q",))
    assert not np.array_equal(only["layers"]["q"], params["layers"]["q"])
    assert not np.array_equal(only["dense_layers"]["q"], params["dense_layers"]["q"])
    np.testing.assert_array_equal(only["layers"]["o"], params["layers"]["o"])


def test_absent_experts_add_nothing():
    """Given (first, held) the reference leaves out what the absent experts
    would add: the parts all 8 shares give, the shared expert once, are the
    whole layer's."""
    cfg, params, m = tiny()
    w = {n: v[0] for n, v in params["layers"].items()}
    z = jax.random.normal(jax.random.key(5), (20, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = reference._experts(z, w, m, frozenset())
        total = reference._swiglu(z, w["shared_gate"], w["shared_up"], w["shared_down"])
        for first in range(0, 16, 2):
            part = {**w, **{n: w[n][first:first + 2] for n in ("w_gate", "w_up", "w_down")}}
            total = total + reference._experts(
                z, part, dict(m, num_experts=2, expert_first=first),
                frozenset({"no_shared_expert"}))
    np.testing.assert_allclose(total, whole, atol=1e-5)


def test_the_cells_file_names_what_its_runner_needs():
    """`serve_reference` reads its reference, its pools, its limits and its
    picks from the cell's file, and checks the configuration's published keys
    against the model the program built."""
    with open(os.path.join(HERE, "workloads", CELL + ".json")) as f:
        w = json.load(f)
    with open(os.path.join(HERE, "configs", w["config"] + ".json")) as f:
        c = json.load(f)
    assert w["runner"] == "serve_reference" and w["reference"] == "reference_k_exaone"
    assert os.path.exists(os.path.join(HERE, "runners", w["runner"] + ".py"))
    assert set(w["limits"]) == {"tie", "logit_err_mean", "logit_err_max"} and w["picks"] >= 8
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")})
    want = reference.as_program({k: c[k] for k in reference.KEYS})
    assert all(getattr(cfg.model, k) == v for k, v in want.items()), [
        (k, v, getattr(cfg.model, k)) for k, v in want.items() if getattr(cfg.model, k) != v]
    assert {"layer_types", "qk_norm", "rope_parameters", "router_experts",
            "first_k_dense_replace"} <= set(want)
    # the pools the file names are the engine's attributes
    spec = importlib.util.spec_from_file_location(
        "engine", os.path.join(os.path.dirname(HERE), "picotron_tpu", "serve", "engine.py"))
    with open(spec.origin) as f:
        src = f.read()
    assert all(f"self.{attr} = " in src for attr in w["pools"].values())
    # the traffic ISSUE 39 gives
    t = w["traffic"]
    assert (t["generator"], t["shape_seed"] >= 39, w["drain_limit_s"]) == ("code_mixed", True, 150)
    assert [(k["share"], k["prompt_tokens"]) for k in t["classes"]] == [
        (0.8, dict(median=512, sigma=0.8, min=64, max=4096)),
        (0.2, dict(median=6144, sigma=0.5, min=3072, max=12288))]
    assert t["output_tokens"] == dict(median=1536, sigma=0.6, min=256, max=4096)
