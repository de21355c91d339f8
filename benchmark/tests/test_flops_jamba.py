"""flops_jamba.py against a hand count at Jamba2-3B's widths, the configuration
file's arithmetic and its catalog keys, the cell's traffic block letter for
letter, the cell's places in BENCHMARK.json, the generator `chat_bursts`, and
the two roofline shares of `readers/ssm_roofline.py` held under 100% at the
cell's shapes."""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import flops_jamba as fj  # noqa: E402

CONFIG, CELL = "jamba2-3b", "jamba2-3b.chat-burst"
CATALOG = {  # the catalog row's `config` (the published keys)
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20,
    "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def test_parameters_by_hand():
    c = load("configs", CONFIG + ".json")
    m = c["model"]
    assert (fj.mixers(m), fj.attention_layers(m), fj.d_inner(m)) == (26, 2, 5120)
    assert fj.mixer_params(m) == 41_241_792 and fj.attention_params(m) == 13_762_560
    assert fj.beside_params(m) == 62_914_560 + 5_120
    assert fj.mixer_params(m) + fj.beside_params(m) == 104_161_472
    assert fj.attention_params(m) + fj.beside_params(m) == 76_682_240
    assert fj.total_params(m) == c["parameters"] == 3_029_337_472
    # 6.06 GB in bfloat16: 37.9% of the chip; the mixers 35% of what a step reads
    assert abs(fj.total_params(m) * 2 / 16e9 - 0.379) < 0.001
    parts = fj.weights_bytes_a_step(m)
    assert sum(parts.values()) == 2 * (fj.total_params(m) - 2560)  # but the final norm
    assert abs(parts["mixers"] / sum(parts.values()) - 0.354) < 0.001


def test_state_and_kv_by_hand():
    c = load("configs", CONFIG + ".json")
    m, sv = c["model"], c["serve"]
    assert fj.state_bytes(m) == 5120 * 16 * 4 == 327_680
    assert fj.tail_bytes(m) == 3 * 5120 * 4 == 61_440
    assert fj.state_row_bytes(m) == 389_120 and fj.slot_state_bytes(m) == 10_117_120
    assert fj.position_kv_bytes(m) == 1024
    # the pools of the configuration's serve block
    state_pool = sv["decode_slots"] * fj.slot_state_bytes(m)
    kv_pool = sv["num_blocks"] * sv["block_size"] * fj.position_kv_bytes(m)
    assert kv_pool == 2_147_483_648 and sv["num_blocks"] * sv["block_size"] == 2_097_152
    # the decode kernel prefetches the slots' tables whole: under half of SMEM's 1 MiB
    assert sv["decode_slots"] * (sv["max_model_len"] // sv["block_size"]) * 4 <= 2**19
    assert state_pool in (64 * 10_117_120, 128 * 10_117_120)
    # weights + state alone are over 40% of the chip: what every step touches
    assert (2 * fj.total_params(m) + state_pool) / 16e9 > 0.40
    assert sv["max_model_len"] == c["max_position_embeddings"] == 32768


def test_the_recurrences_operations_by_term():
    m = load("configs", CONFIG + ".json")["model"]
    assert fj.SCAN_TERMS == dict(exp=1, dt_times_A=1, decay_times_state=1, dtu_times_B=1, add=1,
                                 contraction_with_C=2)
    assert fj.scan_ops_per_token(m) == 7 * 5120 * 16 == 573_440
    assert fj.token_stream_bytes(m) == 2 * 5120 * 4


def test_the_configuration_is_the_catalog_rows():
    c = load("configs", CONFIG + ".json")
    bench = load("..", "BENCHMARK.json")
    entry = next(x for x in bench["configs"] if x["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == c["source"] and len(entry["why"]) <= 200
    for key, value in CATALOG.items():
        assert c[key] == value or key in c["reduced"], key
    assert set(c["reduced"]) == set(entry["reduced"]) == {"max_position_embeddings"}
    m = c["model"]
    assert m["layer_types"] == (["mamba"] * 7 + ["full_attention"] + ["mamba"] * 6) * 2
    assert [i for i, k in enumerate(m["layer_types"]) if k == "full_attention"] == [7, 21]
    assert m["rope_parameters"] == {"full_attention": {"rope_type": "none"}}
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
                "mamba_conv_bias", "mamba_proj_bias", "hidden_size", "intermediate_size",
                "num_attention_heads", "num_key_value_heads", "vocab_size",
                "num_hidden_layers", "tie_word_embeddings"):
        assert m[key] == CATALOG[key], key
    assert m["head_dim"] * m["num_attention_heads"] == m["hidden_size"]
    assert {"deployment", "assumed", "why_these_serve_settings", "initializer_range"} <= set(c)
    assert {"layer_order", "head_dim", "precision", "seeded_draws", "memory_of_a_state",
            "inner_norms", "state_layout"} <= set(c["assumed"])


def test_the_cells_traffic_letter_for_letter():
    w = load("workloads", CELL + ".json")
    t = w["traffic"]
    assert w["runner"] == "serve_reference_reuse" and w["reference"] == "reference_jamba"
    assert os.path.exists(os.path.join(HERE, "runners", w["runner"] + ".py"))
    assert w["pools"] == {"pool_fill": "pool"} and w["drain_limit_s"] == 150
    assert t["generator"] == "chat_bursts" and t["shape_seed"] == 55
    assert t["arrival_cv"] in (2, 3)  # 3, or 2 with the reason in the cell's why
    assert t["classes"] == [dict(name="chat", share=1.0,
                                 prompt_tokens=dict(median=512, sigma=0.8, min=32, max=3072))]
    assert t["output_tokens"] == dict(median=512, sigma=0.8, min=64, max=2048)
    assert set(w["end_to_end"]) == {"latency_per_token_p90_ms", "setup_s"}
    assert set(w["limits"]) == {"tie", "logit_err_mean", "logit_err_max"}
    r = w["reuse"]
    assert set(r["limits"]) == {"reuse_logit_err_mean", "state_err", "state_bf16_share"}
    assert r["first_prompt_tokens"] > r["prompt_tokens"] >= 16 and r["output_tokens"] >= 8
    with open(os.path.join(os.path.dirname(HERE), "picotron_tpu", "serve", "paged_cache.py")) as f:
        assert f"    {r['state_pool']}: jnp.ndarray" in f.read()
    sv = load("configs", CONFIG + ".json")["serve"]
    assert 3072 + 2048 <= sv["max_model_len"]


def test_chat_bursts_is_a_fixed_schedule_with_the_asked_burstiness():
    sys.path.insert(0, os.path.join(HERE, "traffic"))
    import chat_bursts
    t = load("workloads", CELL + ".json")["traffic"]
    due, prompt, out = chat_bursts.shape(t, 51.0)
    assert len(due) >= 20 and (np.diff(due) >= 0).all() and due.max() < 51.0
    assert prompt.min() >= 32 and prompt.max() <= 3072 and out.min() >= 64 and out.max() <= 2048
    a, b = chat_bursts.make(t, 2**31 + 77, 51.0, 65536), chat_bursts.make(t, 5, 51.0, 65536)
    assert [(x[0], len(x[1]), x[2]) for x in a] == [(x[0], len(x[1]), x[2]) for x in b]
    assert a[0][1] != b[0][1] and a == chat_bursts.make(t, 2**31 + 77, 51.0, 65536)
    assert all(0 <= tok < 65536 for _, p, _ in a[:5] for tok in p)
    # the law: mean gap 1 / rate, coefficient of variation arrival_cv; cv 1 is Poisson
    for cv in (1.0, 3.0):
        p = dict(t, rate_per_s=50.0, arrival_cv=cv, prompt_tokens=t["classes"][0]["prompt_tokens"])
        del p["classes"]
        gaps = np.diff(chat_bursts.shape(p, 400.0)[0])
        assert abs(gaps.mean() * 50.0 - 1.0) < 0.1 and abs(gaps.std() / gaps.mean() - cv) < 0.15 * cv
    # a longer window extends the same arrivals
    longer = chat_bursts.shape(t, 80.0)[0]
    np.testing.assert_array_equal(longer[:len(due)], due)


def test_the_cell_is_on_the_lists_of_what_it_reports():
    bench = load("..", "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "chat-burst"
    assert len(cell["why"]) <= 200
    mine = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    new = {"ssm_mixer_ms.serve", "ssm_step_ms.serve", "ssm_scan_ms.serve",
           "ssm_step_roofline.serve", "ssm_scan_roofline.serve"}
    assert new | {"paged_attention_roofline.serve", "paged_attention_ms.serve",
                  "kv_pool_fill.serve", "decode_rows_live.serve", "peak_hbm_gib.serve",
                  "compile_s", "prefill_attention_ms.serve", "state_resets.serve",
                  "device_idle.serve", "decode_program_ms.serve"} <= mine
    # no experts, no Gated DeltaNet mixer; not the three idle_* metrics (their reader finds
    # nothing to read in any serving cell since PR 48), nor LongCat's dense_mlp_ms.serve
    assert not {n for n in mine if n.startswith(("moe_", "gdn_", "idle_"))}
    assert "dense_mlp_ms.serve" not in mine
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["latency_per_token_p90_ms"]["workloads"]
    for name in new:
        m = next(x for x in bench["per_layer"] if x["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "latency_per_token_p90_ms"
        spec = load("layer_metrics", name + ".json")
        assert os.path.exists(os.path.join(HERE, "readers", spec["reader"] + ".py"))
        assert (m["unit"] == "%") == name.endswith("_roofline.serve")
        assert m["layer"] == spec["layer"] and m["unit"] == spec["unit"]


def test_both_roofline_shares_stay_under_100_percent_at_the_cells_shapes():
    """The least time against the time of the fewest bytes any implementation
    moves (the state once each way; a token's u in and y out): the share of a
    perfect implementation is 100%, never more."""
    m = load("configs", CONFIG + ".json")["model"]
    rows = 26 * 40 * 4  # 40 live slots, a dispatch of 4 steps
    least = fj.decode_step_least_seconds(m, rows, PEAK)
    assert abs(least - 2 * rows * 389_120 / 819e9) < 1e-12
    # a chunk: 16 rows of 256 real tokens
    state_rows, tokens = 26 * 16, 26 * 16 * 256
    least = fj.prefill_scan_least_seconds(m, state_rows, tokens, PEAK)
    fewest = (2 * state_rows * 389_120 + tokens * 40_960) / 819e9
    assert abs(least - fewest) < 1e-12  # the bytes bound it, not the operations
    assert tokens * 573_440 / 197e12 < fewest
