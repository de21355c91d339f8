"""reference_evabyte.py: imports nothing from the program, agrees with the
program's `forward()` at the tiny preset on every prediction head, the int8
control moves the logits, the summaries are what the docstring says, and the
cell's file names what its runner needs. (Each of the probe's faults moving
the logits is held in tests/test_evabyte.py, beside the program.)"""
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
import reference_evabyte as reference  # noqa: E402
from picotron_tpu.config import ModelConfig, config_from_dict, resolve_preset  # noqa: E402
from picotron_tpu.models.llama import forward, init_params  # noqa: E402

CELL = "evabyte-6.5b-8l.bytes-longdoc"


def tiny(**over):
    cfg = ModelConfig(dtype="float32", **{**resolve_preset("debug-tiny-evabyte"), **over})
    cfg.validate()
    p = init_params(cfg, jax.random.key(1))
    p["layers"] = dict(p["layers"], input_norm=p["layers"]["input_norm"] + 0.3,
                       post_norm=p["layers"]["post_norm"] - 0.2)  # 1 + w is not 1
    m = dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers, num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, intermediate_size=cfg.intermediate_size,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        attention_class=cfg.attention_class, window_size=cfg.window_size,
        chunk_size=cfg.chunk_size, num_pred_heads=cfg.num_pred_heads,
        norm_add_unit_offset=cfg.norm_add_unit_offset, fp32_skip_add=cfg.fp32_skip_add,
        attention_bias=cfg.attention_bias, tie_word_embeddings=cfg.tie_word_embeddings)
    return cfg, dict(p, embedding=p["embedding"] * 0.1), m


def test_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_evabyte.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"__future__", "functools", "jax", "numpy"}, names


@pytest.mark.parametrize("over", [{}, dict(num_key_value_heads=1)], ids=["32:32", "grouped"])
def test_reference_agrees_with_forward_on_every_head(over):
    cfg, params, m = tiny(**over)
    ids = jax.random.randint(jax.random.key(2), (1, 3 * 32 + 5), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(params, ids, cfg))[0]
    want = np.asarray(reference.logits_at(params, ids[0], jnp.arange(ids.shape[1]), m, head=None))
    assert want.shape == (101, 3, 320)
    np.testing.assert_allclose(got, want, atol=3e-5)
    np.testing.assert_array_equal(
        want[:, 1], reference.logits_at(params, ids[0], jnp.arange(101), m, head=1))
    with pytest.raises(TypeError):
        reference.hidden_states(params, ids[0], m, no_such_fault=True)


def test_a_summary_is_two_softmax_weighted_sums_over_its_chunk():
    k = jax.random.normal(jax.random.key(3), (11, 16))
    v = jax.random.normal(jax.random.key(4), (11, 16))
    mu, phi = jnp.ones((16,)), -jnp.ones((16,))
    ks, vs = reference.summaries(k, v, mu, phi, 4)
    assert ks.shape == vs.shape == (2, 16)  # the partial chunk is in no summary
    a = np.exp(np.asarray(k[4:8] @ mu) / 4.0)
    np.testing.assert_allclose(ks[1], (a / a.sum()) @ np.asarray(k[4:8]), atol=1e-5)
    b = np.exp(np.asarray(k[4:8] @ phi) / 4.0)
    np.testing.assert_allclose(vs[1], (b / b.sum()) @ np.asarray(v[4:8]), atol=1e-5)
    mean = reference.summaries(k, v, mu, phi, 4, mean=True)
    np.testing.assert_allclose(mean[0][0], np.asarray(k[:4]).mean(0), atol=1e-6)


def test_a_query_sees_its_window_and_the_closed_windows_summaries_only():
    """Change a key far back: a query in the same window moves, a query two
    windows on moves only through that chunk's summary, and with the summaries
    left out not at all."""
    cfg, params, m = tiny()
    ids = np.asarray(jax.random.randint(jax.random.key(5), (3 * 32,), 0, 320))
    other = ids.copy()
    other[5] = (other[5] + 1) % 320
    rows = jnp.asarray([20, 40, 90])

    def at(x, **faults):
        return np.asarray(reference.logits_at(params, jnp.asarray(x), rows, m, **faults))

    moved = np.abs(at(ids) - at(other)).max(axis=-1)
    assert (moved > 1e-6).all()
    cut = np.abs(at(ids, no_summaries=True) - at(other, no_summaries=True)).max(axis=-1)
    assert cut[0] > 1e-6 and cut[1] == 0 and cut[2] == 0


def test_int8_control_moves_the_logits_and_12_bits_hardly():
    cfg, params, m = tiny()
    ids = jax.random.randint(jax.random.key(4), (64,), 0, cfg.vocab_size)
    rows = jnp.arange(64)
    exact = np.asarray(reference.logits_at(params, ids, rows, m))
    int8 = np.asarray(reference.logits_at(reference.rounded_to(params, 8), ids, rows, m))
    int12 = np.asarray(reference.logits_at(reference.rounded_to(params, 12), ids, rows, m))
    assert np.median(np.abs(int8 - exact)) > 8 * np.median(np.abs(int12 - exact)) > 0
    only = reference.rounded_to(params, 8, only=("q",))
    assert not np.array_equal(only["layers"]["q"], params["layers"]["q"])
    np.testing.assert_array_equal(only["layers"]["o"], params["layers"]["o"])
    np.testing.assert_array_equal(only["layers"]["eva_mu"], params["layers"]["eva_mu"])


def test_the_cells_file_names_what_its_runner_needs():
    """`serve_reference` reads its reference, its pools, its limits and its
    picks from the cell's file, and checks the configuration's published keys
    against the model the program built."""
    with open(os.path.join(HERE, "workloads", CELL + ".json")) as f:
        w = json.load(f)
    with open(os.path.join(HERE, "configs", w["config"] + ".json")) as f:
        c = json.load(f)
    assert w["runner"] == "serve_reference" and w["reference"] == "reference_evabyte"
    assert set(w["limits"]) == {"tie", "logit_err_mean", "logit_err_max"} and w["picks"] >= 8
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")})
    want = reference.as_program({k: c[k] for k in reference.KEYS})
    assert all(getattr(cfg.model, k) == v for k, v in want.items()), [
        (k, v, getattr(cfg.model, k)) for k, v in want.items() if getattr(cfg.model, k) != v]
    assert {"attention_class", "window_size", "chunk_size", "num_pred_heads",
            "norm_add_unit_offset", "fp32_skip_add"} <= set(want)
    # the one pool the file names is the engine's attribute
    spec = importlib.util.spec_from_file_location(
        "engine", os.path.join(os.path.dirname(HERE), "picotron_tpu", "serve", "engine.py"))
    with open(spec.origin) as f:
        src = f.read()
    assert w["pools"] == {"pool_fill": "pool"} and "self.pool = " in src
    # the traffic ISSUE 43 gives; a prompt and its answer never pass max_model_len
    t = w["traffic"]
    assert (t["generator"], t["shape_seed"], w["drain_limit_s"]) == ("code_mixed", 43, 150)
    assert [(k["share"], k["prompt_tokens"]) for k in t["classes"]] == [
        (0.65, dict(median=2048, sigma=0.8, min=256, max=8192)),
        (0.35, dict(median=20480, sigma=0.4, min=12288, max=30720))]
    assert t["output_tokens"] == dict(median=512, sigma=0.6, min=128, max=2048)
    assert (t["classes"][1]["prompt_tokens"]["max"] + t["output_tokens"]["max"]
            <= c["serve"]["max_model_len"] == 32768)
    # every per-layer metric the cell lists has a file, the three new ones among them
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [x["name"] for x in bench["per_layer"] if CELL in x.get("workloads", [])]
    assert {"eva_summary_read.serve", "eva_cache_read.serve", "eva_summarise_ms.serve",
            "paged_attention_roofline.serve", "kv_pool_fill.serve"} <= set(mine)
    assert all(os.path.exists(os.path.join(HERE, "layer_metrics", n + ".json")) for n in mine)
