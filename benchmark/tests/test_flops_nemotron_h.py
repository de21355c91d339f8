"""flops_nemotron_h.py against a hand count at Nemotron-H's widths, the
configuration file's arithmetic and its catalog keys, the cell's traffic block
letter for letter, the cell's places in BENCHMARK.json, and the roofline
shares of `readers/ssd_roofline.py` and `readers/latent_experts_roofline.py`
held under 100% at the cell's shapes."""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import flops_mellum2  # noqa: E402
import flops_nemotron_h as fn  # noqa: E402

CONFIG, CELL = "nemotron3-super-120b-a12b-22l-ep8", "nemotron3-super-120b-a12b-22l-ep8.agent-ctx"
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEM*EMEMEMEME")
CATALOG = {  # the catalog row's `config` (the published keys)
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 4096, "hybrid_override_pattern": PATTERN, "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LETTERS = {"M": "mamba2", "*": "full_attention", "E": "experts"}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def test_parameters_by_hand():
    c = load("configs", CONFIG + ".json")
    m = c["model"]
    assert [fn.count(m, k) for k in ("mamba2", "full_attention", "experts")] == [10, 2, 10]
    assert (fn.d_inner(m), fn.conv_channels(m)) == (8192, 10240)
    assert fn.mixer_params(m) == 109_635_968 and fn.attention_params(m) == 35_651_584
    assert fn.beside_experts_params(m) + 4096 == 54_530_560 and fn.expert_params(m) == 5_505_024
    assert fn.beside_experts_params(m) + 4096 + 64 * fn.expert_params(m) == 406_852_096
    assert fn.total_params(m) == c["parameters"] == 5_370_454_784
    # 10.74 GB in bfloat16: 67% of the chip
    assert abs(fn.total_params(m) * 2 / 16e9 - 0.671) < 0.001
    # the whole model: 88 layers of 512 experts, the whole vocabulary; 22 of them a token
    whole = dict(m, num_hidden_layers=88, num_experts=512, vocab_size=131072,
                 layer_types=[LETTERS[ch] for ch in PATTERN])
    assert fn.total_params(whole) == 120_668_707_840
    assert fn.total_params(whole, active_only=True) == 12_770_237_440
    # the fallback ISSUE 62 names: layers 25-39
    short = dict(m, num_hidden_layers=15, layer_types=m["layer_types"][:15])
    assert fn.total_params(short) == 3_711_338_240


def test_a_slot_and_a_position():
    m = load("configs", CONFIG + ".json")["model"]
    assert (fn.state_bytes(m), fn.tail_bytes(m)) == (4_194_304, 122_880)
    assert fn.state_row_bytes(m) == 4_317_184 and fn.slot_state_bytes(m) == 43_171_840
    assert fn.position_kv_bytes(m) == 2048
    assert fn.rule_ops_per_token(m) == 5 * 8192 * 128
    assert fn.token_stream_bytes(m) == (10240 + 8192) * 4
    serve = load("configs", CONFIG + ".json")["serve"]
    pools = (serve["decode_slots"] * fn.slot_state_bytes(m)
             + serve["num_blocks"] * serve["block_size"] * fn.position_kv_bytes(m))
    assert abs(pools / 1e9 - 2.455) < 0.001  # 1.38 GB of state, 1.07 GB of K/V
    # the decode attention kernel takes the slots' tables whole into SMEM
    assert serve["decode_slots"] * (serve["max_model_len"] // serve["block_size"]) * 4 == 212_992


def test_the_rooflines_cannot_pass_100_percent():
    m = load("configs", CONFIG + ".json")["model"]
    # a decode step: each live (slot, mixer) pair's 4.3 MB both ways; no kernel can move
    # a pair in less than its bytes take
    least = fn.decode_step_least_seconds(m, 1.0, PEAK)
    assert abs(least - 2 * 4_317_184 / 819e9) < 1e-12
    # a prefill chunk of 256 real tokens a row: the bytes bound it (the state both ways
    # and each token's x, B, C in and y out in float32), five times the operations' time
    least = fn.prefill_chunk_least_seconds(m, 1.0, 256.0, PEAK)
    assert abs(least - (2 * 4_317_184 + 256 * 73_728) / 819e9) < 1e-12
    assert least > 4 * 256 * 5 * 8192 * 128 / 197e12
    # the banks: two matrices of 1024 x 2688, a sixth of what flops_mellum2 would price
    ours = fn.decode_experts_bytes(m, 100.0, 0.0)
    theirs = flops_mellum2.decode_experts_bytes(m, 100.0, 0.0)
    assert ours == 100 * 5_505_024 * 2 and abs(theirs / ours - 6.0) < 1e-9
    by_part = fn.weights_bytes_a_step(m)
    assert abs(sum(by_part.values()) / 1e9 - 3.56) < 0.01  # 4.3 ms a step at 819 GB/s


def test_the_configuration_file_is_the_catalog_row_cut_as_it_says():
    c = load("configs", CONFIG + ".json")
    cut = {"num_hidden_layers": 22, "hybrid_override_pattern": PATTERN[25:47],
           "n_routed_experts": 64, "vocab_size": 16384, "max_position_embeddings": 53248}
    assert set(c["reduced"]) == set(cut) and c["chips"] == 1
    for k, v in CATALOG.items():
        assert c[k] == cut.get(k, v), k
    bench = load("..", "BENCHMARK.json")
    entry = [e for e in bench["configs"] if e["name"] == CONFIG]
    assert len(entry) == 1 and sorted(entry[0]["reduced"]) == sorted(cut)
    assert entry[0]["file"] == f"benchmark/configs/{CONFIG}.json" and entry[0]["source"] == c["source"]
    assert {"rotation", "split_order", "step", "A_log_dt_bias", "selection_bias", "state",
            "latent", "prediction_module", "embedding", "weights"} <= set(c["assumed"])


def test_the_cell_is_where_issue_62_puts_it():
    bench, w = load("..", "BENCHMARK.json"), load("workloads", CELL + ".json")
    cell = [e for e in bench["workloads"] if e["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1 and cell[0]["traffic"] == "agent-ctx"
    assert len(bench["workloads"]) == 13 and sum(e["chips"] == 4 for e in bench["workloads"]) == 1
    t = w["traffic"]
    assert (t["generator"], t["shape_seed"]) == ("code_mixed", 62)
    assert [(cl["name"], cl["share"], cl["prompt_tokens"]) for cl in t["classes"]] == [
        ("tool turn", 0.8, {"median": 2048, "sigma": 0.7, "min": 512, "max": 8192}),
        ("context turn", 0.2, {"median": 16384, "sigma": 0.5, "min": 8192, "max": 49152})]
    assert t["output_tokens"] == {"median": 512, "sigma": 0.6, "min": 128, "max": 2048}
    assert w["drain_limit_s"] == 150
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    new = {"ssd_mixer_ms.serve", "ssd_step_ms.serve", "ssd_chunk_ms.serve", "moe_latent_ms.serve",
           "ssd_step_roofline.serve", "ssd_chunk_roofline.serve", "latent_experts_roofline.serve"}
    assert new <= listed
    for name in new:  # each new metric lists this cell alone, and its file is there
        (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert metric["workloads"] == [CELL] and load("layer_metrics", name + ".json")["name"] == name
    assert {"compile_s", "moe_experts_ms.serve", "moe_shared_ms.serve",
            "moe_experts_touched.serve", "moe_expert_visits.serve", "moe_picks_here.serve",
            "moe_rows_per_bank.serve", "paged_attention_ms.serve",
            "paged_attention_roofline.serve", "prefill_attention_ms.serve", "kv_pool_fill.serve",
            "kv_write_ms.serve", "state_resets.serve", "decode_rows_live.serve",
            "decode_program_ms.serve", "prefill_program_ms.serve"} <= listed
    # NOT where a reader counts other shapes than this model's, nor where nothing reports
    assert not listed & {"moe_experts_roofline.serve", "idle_starved.serve",
                         "idle_round_trip.serve", "idle_inside_program.serve"}
    (e2e,) = [m for m in bench["end_to_end"] if m["name"] == "latency_per_token_p90_ms"]
    assert CELL in e2e["workloads"]
