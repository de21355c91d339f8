"""flops_longcat.py against a hand count at LongCat-Flash-Omni's widths, the
configuration file's arithmetic and its catalog keys, the cell's traffic block
letter for letter, and the cell's places in BENCHMARK.json."""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import flops_longcat  # noqa: E402
import flops_mellum2  # noqa: E402
import flops_pangu_moe  # noqa: E402

CONFIG, CELL = "longcat-flash-omni-4l-ep32", "longcat-flash-omni-4l-ep32.agent-turns"
CATALOG = {  # the catalog row's `config` (the published keys)
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_layers": 28,
    "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def test_parameters_by_hand():
    m = load("configs", CONFIG + ".json")["model"]
    assert flops_longcat.mla_params(m) == 90_572_800
    assert flops_longcat.dense_ffn_params(m) == 226_492_416
    assert flops_longcat.expert_params(m) == 37_748_736
    assert flops_longcat.router_params(m) == 6144 * 768 + 768
    p = flops_longcat.layer_params(m)
    assert sum(p.values()) - p["experts"] == 638_874_368
    assert p["experts"] == 603_979_776 and sum(p.values()) == 1_242_854_144
    assert flops_longcat.total_params(m) == 5_172_749_312
    assert flops_longcat.total_params(m) == load("configs", CONFIG + ".json")["parameters"]
    # the whole model: 28 layers of 512 experts, the whole vocabulary
    whole = dict(m, num_hidden_layers=28, num_experts=512, vocab_size=131072)
    assert 555e9 < flops_longcat.total_params(whole) < 565e9
    # a whole layer is 39.9 GB: a four-chip host holds one
    assert abs(sum(flops_longcat.layer_params(whole).values()) * 2 / 1e9 - 39.9) < 0.1


def test_latent_pool_and_decode_step_by_hand():
    c = load("configs", CONFIG + ".json")
    m, sv = c["model"], c["serve"]
    assert flops_longcat.sublayers(m) == 8
    assert flops_longcat.position_state_bytes(m) == 8 * 576 * 2 == 9_216
    assert flops_longcat.position_pool_bytes(m) == 8 * 640 * 2 == 10_240
    positions = sv["num_blocks"] * sv["block_size"]
    assert positions == 262_144
    assert abs(positions * flops_longcat.position_pool_bytes(m) / 1e9 - 2.68) < 0.01
    # one query against one cached position: 64 heads x (576 + 512) x 2
    assert flops_longcat.latent_decode_ops(m, 1) == 139_264
    assert flops_longcat.latent_decode_ops(m, 1) == flops_pangu_moe.latent_decode_ops(m, 1)
    per_byte = 139_264 / 1152
    assert 120 < per_byte < 122 < PEAK["bf16_flops_per_s"] / PEAK["hbm_bytes_per_s"]  # memory-bound
    # 12 slots at 8k positions, 8 sublayers: 0.9 GB, 1.1 ms by its bytes
    step = flops_longcat.latent_decode_step(m, [8192] * 12, PEAK)
    assert abs(step["bytes"] / 1e9 - 0.906) < 0.001 and step["bytes_s"] > step["ops_s"]
    assert abs(step["bytes_s"] * 1e3 - 1.106) < 0.002
    # what the accepted roofline reader computes from this cell's model block
    blocks = 12 * 512 * 8
    assert flops_pangu_moe.latent_decode_least_seconds(m, blocks, 16, PEAK) == step["bytes_s"]
    # a 256-token chunk behind 12,288 cached positions: 8 sublayers expand 12,544
    # keys (1.7 T operations) and attend them (1.05 T)
    ops = flops_longcat.latent_prefill_ops(m, 256, 12_544)
    assert abs(ops / 1e12 - (8 * 12_544 * 2 * 512 * 64 * 256 + 8 * 256 * 12_544 * 2 * 64 * 320) / 1e12) < 1e-9
    assert 2.7 < ops / 1e12 < 2.8


def test_experts_and_the_dense_path_by_hand():
    m = load("configs", CONFIG + ".json")["model"]
    assert flops_mellum2.expert_bytes(m) == 3 * 6144 * 2048 * 2 == 75_497_472
    picks = flops_longcat.picks_expected(m)
    assert picks == dict(zero=4.0, here=0.25, away=7.75)
    # 12 live rows: 144 picks a layer over 768 columns touch 2.7 of the 16 banks
    assert abs(flops_longcat.banks_touched_expected(m, 12) - 2.74) < 0.01
    w = flops_longcat.weights_bytes_a_step(m, 2.74)
    assert abs(w["dense_ffn"] / 1e9 - 3.62) < 0.01 and abs(w["attention"] / 1e9 - 1.45) < 0.01
    assert abs(w["banks"] / 1e9 - 0.83) < 0.01 and abs(w["head"] / 1e9 - 0.20) < 0.01
    assert 6.0 < sum(w.values()) / 1e9 < 6.3  # 7.5 ms a step at 819 GB/s


def test_the_file_keeps_the_catalogs_keys():
    c = load("configs", CONFIG + ".json")
    entry = next(e for e in load("..", "BENCHMARK.json")["configs"] if e["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    for k, v in CATALOG.items():
        assert c[k] == v or k in entry["reduced"], k
    assert set(entry["reduced"]) == {"num_layers", "n_routed_experts", "vocab_size",
                                     "max_position_embeddings"}
    # floors: four layers, at least 8 routed experts, an eighth of the vocabulary
    assert c["num_layers"] >= 4 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 >= CATALOG["vocab_size"]
    m = c["model"]
    assert m["num_experts"] == c["n_routed_experts"] == 16 and m["zero_experts"] == 256
    assert m["router_experts"] == CATALOG["n_routed_experts"]
    assert m["intermediate_size"] == c["ffn_hidden_size"]
    assert m["moe_intermediate_size"] == c["expert_ffn_hidden_size"]
    assert {"deployment", "assumed", "why_these_serve_settings", "initializer_range"} <= set(c)


def test_the_cells_traffic_letter_for_letter():
    w = load("workloads", CELL + ".json")
    t = w["traffic"]
    assert w["runner"] == "serve_reference" and w["reference"] == "reference_longcat"
    assert w["pools"] == {"pool_fill": "pool"} and w["drain_limit_s"] == 150
    assert t["generator"] == "code_mixed" and t["shape_seed"] == 47
    assert t["classes"] == [
        dict(name="turn", share=0.85,
             prompt_tokens=dict(median=2048, sigma=0.7, min=512, max=6144)),
        dict(name="document", share=0.15,
             prompt_tokens=dict(median=12288, sigma=0.5, min=8192, max=24576))]
    assert t["output_tokens"] == dict(median=512, sigma=0.8, min=128, max=2048)
    assert set(w["end_to_end"]) == {"latency_per_token_p90_ms", "setup_s"}
    assert set(w["limits"]) == {"tie", "logit_err_mean", "logit_err_max"}
    # no request passes the slot's table
    sv = load("configs", CONFIG + ".json")["serve"]
    assert 24576 + 2048 <= sv["max_model_len"] == 32768
    # the schedule the cell's rate draws
    sys.path.insert(0, os.path.join(HERE, "traffic"))
    import code_mixed
    due, which, prompt, out = code_mixed.shape(t, 51.0)
    assert len(due) >= 20 and (np.diff(due) > 0).all()
    assert prompt.max() <= 24576 and prompt.min() >= 512 and out.min() >= 128


def test_the_cell_is_on_the_lists_of_what_it_reports():
    bench = load("..", "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "agent-turns"
    # (`in`, not "last" or "alone": a later PR appends its own cell to these lists)
    mine = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert {"scmoe_branch_ms.serve", "moe_zero_picks.serve", "dense_mlp_ms.serve",
            "mla_attention_roofline.serve", "moe_experts_roofline.serve", "moe_picks_here.serve",
            "kv_latent_fill.serve", "prefill_attention_ms.serve", "decode_rows_live.serve",
            "peak_hbm_gib.serve", "compile_s"} <= mine
    # this model has no shared expert, no K/V pool and no window
    assert not mine & {"moe_shared_ms.serve", "kv_pool_fill.serve", "window_read.serve",
                       "paged_attention_ms.serve"}
    for name in ("scmoe_branch_ms.serve", "moe_zero_picks.serve", "dense_mlp_ms.serve"):
        m = next(x for x in bench["per_layer"] if x["name"] == name)
        assert CELL in m["workloads"] and m["moves"] == "latency_per_token_p90_ms"
        spec = load("layer_metrics", name + ".json")
        assert os.path.exists(os.path.join(HERE, "readers", spec["reader"] + ".py"))
