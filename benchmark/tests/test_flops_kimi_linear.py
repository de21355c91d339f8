"""flops_kimi_linear.py against a hand count at Kimi-Linear's widths, the
configuration file's arithmetic and its catalog keys, the cell's traffic block
letter for letter, the cell's places in BENCHMARK.json, and the two roofline
shares of `readers/kda_roofline.py` held under 100% at the cell's shapes."""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import flops_kimi_linear as fk  # noqa: E402
import flops_mellum2  # noqa: E402
import flops_pangu_moe  # noqa: E402

CONFIG, CELL = "kimi-linear-48b-a3b-12l-ep8", "kimi-linear-48b-a3b-12l-ep8.reason-longout"
CATALOG = {  # the catalog row's `config` (the published keys)
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def test_parameters_by_hand():
    c = load("configs", CONFIG + ".json")
    m = c["model"]
    assert (fk.mixers(m), fk.full_layers(m), fk.conv_channels(m)) == (9, 3, 12288)
    assert fk.mixer_params(m) == 39_514_272 and fk.attention_params(m) == 29_114_880
    assert fk.beside_params(m) == 7_672_576 and fk.expert_params(m) == 7_077_888
    assert fk.dense_mlp_params(m) == 63_700_992
    assert fk.mixer_params(m) + fk.dense_mlp_params(m) + 4608 == 103_219_872
    assert 8 * fk.mixer_params(m) + 3 * fk.attention_params(m) + 11 * fk.beside_params(m) \
        == 487_857_152
    assert fk.total_params(m) == c["parameters"] == 3_176_867_744
    # 6.35 GB in bfloat16: 40% of the chip
    assert abs(fk.total_params(m) * 2 / 16e9 - 0.397) < 0.001
    # the whole model: 27 layers of 256 experts, the whole vocabulary
    whole = dict(m, num_hidden_layers=27, num_experts=256, vocab_size=163840,
                 layer_types=["kda", "kda", "kda", "full_attention"] * 6
                 + ["kda", "kda", "full_attention"])
    assert fk.total_params(whole) == 49_122_681_728
    # a whole expert layer 3.6 GB: a chip holds few layers whole
    assert abs(256 * fk.expert_params(m) * 2 / 1e9 - 3.62) < 0.01


def test_state_pool_and_latent_pool_by_hand():
    c = load("configs", CONFIG + ".json")
    m, sv = c["model"], c["serve"]
    assert fk.state_row_bytes(m) == 32 * 128 * 128 * 4 + 3 * 12288 * 4 == 2_244_608
    assert fk.slot_state_bytes(m) == 9 * 2_244_608  # 20.2 MB a slot, whatever its length
    assert fk.position_latent_bytes(m) == 3 * 576 * 2 == 3_456
    # as stored, 640 wide: what the decode kernel reads a block and layer
    assert flops_pangu_moe.latent_block_bytes(m, sv["block_size"]) in (
        sv["block_size"] * 576 * 2, sv["block_size"] * 640 * 2)
    state = sv["decode_slots"] * fk.slot_state_bytes(m)
    latent = sv["num_blocks"] * sv["block_size"] * 3 * 640 * 2
    total = fk.total_params(m) * 2 + state + latent
    # weights + state + pool: more than the driver's floor of 25% of the chip
    assert 0.45 < total / 16e9 < 0.75, total / 16e9
    # a slot's state equals the latents of 5,845 positions
    assert fk.slot_state_bytes(m) // 3_456 == 5_845
    # the decode kernel takes the slots' tables whole into SMEM
    assert sv["decode_slots"] * (sv["max_model_len"] // sv["block_size"]) * 4 <= 2**19
    assert 32768 + 4096 <= sv["max_model_len"] == m["max_position_embeddings"]


def test_the_recurrences_yardstick_and_its_shares():
    m = load("configs", CONFIG + ".json")["model"]
    assert fk.recurrence_ops(m, 1) == 32 * 6 * 128 * 128 == 3_145_728
    # a decode step at 32 live slots: 288 rows, 1.29 GB both ways, 1.58 ms by its bytes
    step = fk.decode_state_least_seconds(m, 32 * 9, PEAK)
    assert abs(step * 1e3 - 1.579) < 0.001
    assert fk.recurrence_ops(m, 288) / 197e12 < 0.01 * step
    # a share can pass 100% only if the scope's time leaves out part of the work
    for rows, tokens in ((9, 256), (9, 3), (288, 8192), (144, 0)):
        least = fk.prefill_state_least_seconds(m, rows, tokens, PEAK)
        spent = 2 * rows * fk.state_row_bytes(m) / 819e9 + 9 * fk.recurrence_ops(m, tokens) / 197e12
        assert 100.0 * least / spent <= 100.0
    for rows in (9, 144, 576):
        spent = 2 * rows * fk.state_row_bytes(m) / 819e9
        assert 100.0 * fk.decode_state_least_seconds(m, rows, PEAK) / spent <= 100.0 + 1e-9


def test_experts_and_the_weights_a_step_reads():
    m = load("configs", CONFIG + ".json")["model"]
    assert flops_mellum2.expert_bytes(m) == 3 * 2304 * 1024 * 2 == 14_155_776
    assert fk.picks_expected(m) == dict(here=1.0, away=7.0)
    # 32 live rows: 256 picks a layer over 256 columns touch 20.4 of the 32 banks
    assert abs(fk.banks_touched_expected(m, 32) - 20.4) < 0.1
    w = fk.weights_bytes_a_step(m, fk.banks_touched_expected(m, 32))
    assert abs(w["mixers"] / 1e9 - 0.711) < 0.001 and abs(w["attention"] / 1e9 - 0.175) < 0.001
    assert abs(w["dense"] / 1e9 - 0.127) < 0.001 and abs(w["beside"] / 1e9 - 0.169) < 0.001
    assert abs(w["banks"] / 1e9 - 3.18) < 0.02 and abs(w["head"] / 1e9 - 0.094) < 0.001
    assert 4.4 < sum(w.values()) / 1e9 < 4.6  # ISSUE 57's 1.3 GB dense + 3.2 GB of banks


def test_the_file_keeps_the_catalogs_keys():
    c = load("configs", CONFIG + ".json")
    entry = next(e for e in load("..", "BENCHMARK.json")["configs"] if e["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    for k, v in CATALOG.items():
        assert c[k] == v or k in entry["reduced"], k
    assert set(entry["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size",
                                     "model_max_length"}
    # floors: whole periods, four expert layers behind the dense one, at least 8
    # routed experts, an eighth of the vocabulary
    assert c["num_hidden_layers"] % 4 == 0 and c["num_hidden_layers"] - 1 >= 4
    assert c["num_experts"] >= 8 and c["vocab_size"] * 8 >= CATALOG["vocab_size"]
    m = c["model"]
    assert m["num_experts"] == c["num_experts"] == 32
    assert m["router_experts"] == c["router_experts"] == CATALOG["num_experts"]
    assert m["layer_types"] == (["kda"] * 3 + ["full_attention"]) * 3
    assert {"deployment", "assumed", "why_these_serve_settings", "initializer_range"} <= set(c)


def test_the_cells_traffic_letter_for_letter():
    w = load("workloads", CELL + ".json")
    t = w["traffic"]
    assert w["runner"] == "serve_reference_reuse" and w["reference"] == "reference_kimi_linear"
    assert os.path.exists(os.path.join(HERE, "runners", w["runner"] + ".py"))
    # (300, not ISSUE 57's 150: a traced run's profiler stalls the loop for two minutes)
    assert w["pools"] == {"pool_fill": "pool"} and w["drain_limit_s"] == 150
    assert t["generator"] == "code_mixed" and t["shape_seed"] == 57 and t["rate_per_s"] == 0.9
    assert t["classes"] == [
        dict(name="question", share=0.9,
             prompt_tokens=dict(median=1024, sigma=0.7, min=256, max=4096)),
        dict(name="document", share=0.1,
             prompt_tokens=dict(median=16384, sigma=0.5, min=8192, max=32768))]
    assert t["output_tokens"] == dict(median=2048, sigma=0.5, min=512, max=4096)
    assert set(w["end_to_end"]) == {"latency_per_token_p90_ms", "setup_s"}
    assert set(w["limits"]) == {"tie", "logit_err_mean", "logit_err_max"}
    r = w["reuse"]
    assert set(r["limits"]) == {"reuse_logit_err_mean", "state_err", "state_bf16_share"}
    assert r["first_prompt_tokens"] > r["prompt_tokens"] >= 16 and r["output_tokens"] >= 8
    with open(os.path.join(os.path.dirname(HERE), "picotron_tpu", "serve", "paged_cache.py")) as f:
        assert f"    {r['state_pool']}: jnp.ndarray" in f.read()
    sys.path.insert(0, os.path.join(HERE, "traffic"))
    import code_mixed
    due, which, prompt, out = code_mixed.shape(t, 51.0)
    assert len(due) >= 10 and (np.diff(due) > 0).all()
    assert prompt.max() <= 32768 and prompt.min() >= 256 and out.min() >= 512
    assert which.sum() >= 1  # a document among them


def test_the_cell_is_on_the_lists_of_what_it_reports():
    bench = load("..", "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "reason-longout"
    assert len(cell["why"]) <= 200
    mine = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    new = {"kda_mixer_ms.serve", "kda_state_ms.serve", "kda_chunk_ms.serve",
           "kda_state_roofline.serve", "kda_chunk_roofline.serve", "kda_rows_skipped.serve"}
    assert new | {"mla_attention_ms.serve", "mla_attention_roofline.serve", "mla_proj_ms.serve",
                  "kv_latent_fill.serve", "moe_experts_roofline.serve", "moe_picks_here.serve",
                  "moe_shared_ms.serve", "moe_rows_per_bank.serve", "state_resets.serve",
                  "decode_rows_live.serve", "peak_hbm_gib.serve", "compile_s",
                  "prefill_attention_ms.serve"} <= mine
    # no K/V pool, no window, no other family's mixer; and not the three idle_* metrics
    assert not mine & {"kv_pool_fill.serve", "window_read.serve", "paged_attention_ms.serve",
                       "gdn_state_ms.serve", "ssm_step_ms.serve", "idle_starved.serve",
                       "idle_round_trip.serve", "idle_inside_program.serve"}
    for name in new:
        m = next(x for x in bench["per_layer"] if x["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "latency_per_token_p90_ms"
        spec = load("layer_metrics", name + ".json")
        assert os.path.exists(os.path.join(HERE, "readers", spec["reader"] + ".py"))
        assert (m["unit"] == "%") == (name.endswith("_roofline.serve")
                                      or name == "kda_rows_skipped.serve")
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "latency_per_token_p90_ms")
    assert CELL in e2e["workloads"]
