"""flops_pangu_moe.py against a hand count at openPangu-Ultra-MoE's widths, the
configuration file's arithmetic against the built parameter tree, and the
cell's traffic block: its mixture and clips, letter for letter."""
import importlib.util
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import flops_pangu_moe  # noqa: E402

CONFIG, CELL = "openpangu-ultra-moe-5l-ep16", "openpangu-ultra-moe-5l-ep16.longdoc-mixed"
CATALOG = {  # the catalog row's `config` (the published keys)
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
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def config():
    with open(os.path.join(HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_latent_step_by_hand():
    m = config()["model"]
    assert flops_pangu_moe.latent_row_values(m) == 576
    assert flops_pangu_moe.latent_block_bytes(m, 16) == 16 * 576 * 2 == 18_432
    # one query against one cached position: 128 heads x (576 + 512) x 2
    assert flops_pangu_moe.latent_decode_ops(m, 1) == 278_528
    per_byte = 278_528 / 1152
    ridge = PEAK["bf16_flops_per_s"] / PEAK["hbm_bytes_per_s"]
    assert 241 < per_byte < 243 and 240 < ridge < 241  # on the ridge
    # 16 slots x 16k positions x 5 layers: 1.5 GB, 365 G operations, 1.9 ms either way
    blocks = 16 * 1024 * 5
    nbytes = blocks * flops_pangu_moe.latent_block_bytes(m, 16)
    ops = flops_pangu_moe.latent_decode_ops(m, blocks * 16)
    assert abs(nbytes / 1e9 - 1.51) < 0.01 and abs(ops / 1e9 - 365) < 1
    least = flops_pangu_moe.latent_decode_least_seconds(m, blocks, 16, PEAK)
    assert least == max(nbytes / 819e9, ops / 197e12) and abs(least * 1e3 - 1.85) < 0.03
    # the forcing arithmetic: as K and V per head a position is 81,920 B a layer
    assert flops_pangu_moe.per_head_cache_bytes(m) == 128 * (192 + 128) * 2 == 81_920
    assert 5 * 81_920 * 524_288 / 1e9 > 214


def test_weights_by_hand():
    m = config()["model"]
    assert flops_pangu_moe.expert_bytes(m) == 3 * 7680 * 2048 * 2 == 94_371_840
    assert flops_pangu_moe.held_banks_bytes(m) == 16 * 94_371_840
    assert flops_pangu_moe.mla_weight_bytes(m) == 2 * 196_575_232
    # the issue's orientation: some 5.9 GB a step with about 6 banks touched a layer
    total = flops_pangu_moe.weights_bytes_a_step(m, 6)
    assert abs(total / 1e9 - 5.77) < 0.05, total / 1e9
    assert abs(total / 819e9 * 1e3 - 7.0) < 0.1


def test_file_keeps_the_published_keys_and_lists_the_five_cuts():
    c = config()
    reduced = {"num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 16,
               "vocab_size": 19200, "max_position_embeddings": 32768}
    assert set(c["reduced"]) == set(reduced)
    for k, v in CATALOG.items():
        assert c[k] == reduced.get(k, v), k
    for k, text in c["reduced"].items():
        assert text.startswith(f"{CATALOG[k]} -> "), (k, text[:40])
    assert (c["router_experts"], c["expert_first"]) == (256, 0)
    assert {"router", "sandwich_norm", "rope", "mtp", "weights"} <= set(c["assumed"])
    assert "16-chip" in c["deployment"] and "Nothing stands in" in c["deployment"]
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == list(c["reduced"]) and entry["source"] == c["source"]
    sv = c["serve"]
    assert (sv["decode_slots"], sv["block_size"], sv["max_model_len"], sv["num_blocks"]) == (
        16, 16, 32768, 32768)
    # the cache's line: 524,288 positions x 5 layers x 576 x 2 B
    assert sv["num_blocks"] * 16 * 5 * 576 * 2 == 3_019_898_880


def test_file_arithmetic_against_the_built_tree():
    """The parameter counts the file states are those of the tree the program
    builds from its `model` block (shapes only; nothing is allocated)."""
    import jax

    from picotron_tpu.config import config_from_dict, num_params
    from picotron_tpu.models.llama import init_params, param_count

    c = config()
    m = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")}).model
    tree = jax.eval_shape(lambda: init_params(m, jax.random.key(0)))
    total = param_count(tree)
    assert total == num_params(m) == 4_919_139_840
    dense = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree["dense_layers"]))
    experts = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree["layers"]))
    assert dense == 621_281_280 and experts == 4 * 1_000_734_720
    assert total - dense - experts == 294_919_680
    text = c["reduced"]["num_hidden_layers"]
    for n in (196_577_280, 621_281_280, 1_000_734_720, 294_919_680, 4_919_139_840):
        assert f"{n:,}" in text, n
    assert tree["layers"]["router"].shape == (4, 7680, 256)
    assert tree["layers"]["w_gate"].shape == (4, 16, 7680, 2048)
    assert tree["embedding"].shape == (19200, 7680)


def test_traffic_block_letter_for_letter():
    with open(os.path.join(HERE, "workloads", CELL + ".json")) as f:
        w = json.load(f)
    t = w["traffic"]
    assert (w["runner"], t["generator"], w["drain_limit_s"]) == ("serve_pangu_moe", "code_mixed", 150)
    short, long_ = t["classes"]
    assert (short["share"], short["prompt_tokens"]) == (
        0.65, {"median": 1024, "sigma": 0.8, "min": 64, "max": 4096})
    assert (long_["share"], long_["prompt_tokens"]) == (
        0.35, {"median": 12288, "sigma": 0.5, "min": 6144, "max": 30720})
    assert t["output_tokens"] == {"median": 128, "sigma": 0.7, "min": 16, "max": 512}
    assert round(t["rate_per_s"] * 10) == t["rate_per_s"] * 10  # rounded down to 0.1/s
    assert w["end_to_end"] == {"latency_per_token_p90_ms": "latency_per_token_ms_p90",
                               "setup_s": "setup_s"}
    spec = importlib.util.spec_from_file_location(
        "code_mixed", os.path.join(HERE, "traffic", "code_mixed.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    reqs = gen.make(t, 2147483659, 51.0, 19200)
    again = gen.make(t, 7, 51.0, 19200)
    assert [(d, len(p), n) for d, p, n in reqs] == [(d, len(p), n) for d, p, n in again]
    lens = np.array([len(p) for _, p, _ in reqs])
    assert lens.min() >= 64 and lens.max() <= 30720
    assert not ((lens > 4096) & (lens < 6144)).any()
    assert max(max(p) for _, p, _ in reqs) < 19200
    assert all(16 <= n <= 512 for _, _, n in reqs)
    # every request fits a slot's table: 30,720 + 512 <= 32,768
    c = config()
    assert lens.max() + 512 <= c["serve"]["max_model_len"]


def test_new_metrics_list_the_cell_alone():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {"mla_attention_ms.serve", "mla_attention_roofline.serve", "mla_proj_ms.serve",
           "moe_shared_ms.serve", "moe_picks_here.serve", "kv_latent_fill.serve"}
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in new:
        assert by[name]["workloads"] == [CELL] and by[name]["moves"] == "latency_per_token_p90_ms"
        with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(HERE, "readers", spec["reader"] + ".py"))
        assert spec["unit"] == by[name]["unit"] and spec["layer"] == by[name]["layer"]
    for name in ("window_read.serve", "kv_window_fill.serve", "decode_view_read.serve",
                 "paged_attention_ms.serve", "paged_attention_roofline.serve"):
        assert CELL not in by[name]["workloads"]
    for name in ("compile_s", "moe_experts_ms.serve", "moe_experts_roofline.serve",
                 "moe_experts_touched.serve", "moe_expert_visits.serve", "decode_program_ms.serve"):
        assert by[name]["workloads"][-1] == CELL
