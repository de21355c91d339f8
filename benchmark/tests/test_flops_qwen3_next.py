"""flops_qwen3_next.py against a hand count at Qwen3-Next's widths, the
configuration file's arithmetic and its catalog keys, the cell's traffic block
letter for letter, the cell's places in BENCHMARK.json, and the two roofline
shares of `readers/gdn_roofline.py` held under 100% at the cell's shapes."""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import flops_mellum2  # noqa: E402
import flops_qwen3_next as fq  # noqa: E402

CONFIG, CELL = "qwen3-next-80b-a3b-12l-ep8", "qwen3-next-80b-a3b-12l-ep8.longctx-mixed"
CATALOG = {  # the catalog row's `config` (the published keys)
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def test_parameters_by_hand():
    c = load("configs", CONFIG + ".json")
    m = c["model"]
    assert (fq.mixers(m), fq.full_layers(m), fq.conv_channels(m)) == (9, 3, 8192)
    assert fq.mixer_params(m) == 33_718_464 and fq.attention_params(m) == 27_263_488
    assert fq.beside_params(m) == 4_200_448 and fq.expert_params(m) == 3_145_728
    assert 3 * (fq.mixer_params(m) + fq.beside_params(m)) + fq.attention_params(m) \
        + fq.beside_params(m) == 145_220_672
    assert fq.total_params(m) == c["parameters"] == 2_929_374_400
    # 5.86 GB in bfloat16: 37% of the chip
    assert abs(fq.total_params(m) * 2 / 16e9 - 0.366) < 0.001
    # the whole model: 48 layers of 512 experts, the whole vocabulary: 80B, a layer 3.3 GB
    whole = dict(m, num_hidden_layers=48, num_experts=512, vocab_size=151936,
                 layer_types=m["layer_types"] * 4)
    assert 79e9 < fq.total_params(whole) < 82e9
    assert abs((fq.mixer_params(m) + fq.beside_params(m) + 512 * fq.expert_params(m)) * 2 / 1e9
               - 3.30) < 0.01


def test_state_pool_and_kv_pool_by_hand():
    c = load("configs", CONFIG + ".json")
    m, sv = c["model"], c["serve"]
    assert fq.state_row_bytes(m) == 32 * 128 * 128 * 4 + 3 * 8192 * 4 == 2_195_456
    assert fq.slot_state_bytes(m) == 9 * 2_195_456  # 19.8 MB a slot, whatever its length
    assert abs(sv["decode_slots"] * fq.slot_state_bytes(m) / 1e9 - 0.316) < 0.001
    assert fq.position_kv_bytes(m) == 3 * 2 * 2 * 256 * 2 == 6_144
    assert flops_mellum2.kv_block_bytes(m, sv["block_size"]) * 3 == 16 * 6_144
    positions = sv["num_blocks"] * sv["block_size"]
    assert positions == 786_432 and abs(positions * 6_144 / 1e9 - 4.83) < 0.01
    # weights + state + pool: 69% of the chip
    total = fq.total_params(m) * 2 + sv["decode_slots"] * fq.slot_state_bytes(m) + positions * 6_144
    assert 0.68 < total / 16e9 < 0.70
    # a slot's state equals the K/V of 3,216 positions: past that length the
    # mixers' state is the smaller of the two kinds
    assert fq.slot_state_bytes(m) // 6_144 == 3_216


def test_the_recurrences_yardstick_and_its_shares():
    m = load("configs", CONFIG + ".json")["model"]
    # the rule a token and value head: 6 x 128 x 128 operations
    assert fq.recurrence_ops(m, 1) == 32 * 6 * 128 * 128 == 3_145_728
    # a decode step at 16 live slots: 144 rows, 0.63 GB both ways, 0.77 ms by its bytes
    step = fq.decode_state_least_seconds(m, 16 * 9, PEAK)
    assert abs(step * 1e3 - 2 * 144 * 2_195_456 / 819e9 * 1e3) < 1e-9 and abs(step * 1e3 - 0.772) < 0.001
    # ... and memory-bound: 1.5 operations a byte
    assert fq.recurrence_ops(m, 144) / 197e12 < 0.01 * step
    # a prefill row of 256 tokens: 39.5 MB both ways (48 us), 7.2 G operations (37 us)
    one = fq.prefill_state_least_seconds(m, 9, 256, PEAK)
    assert abs(one * 1e6 - 48.3) < 0.1
    assert abs(9 * fq.recurrence_ops(m, 256) / 197e12 * 1e6 - 36.8) < 0.1
    # a share can pass 100% only if the scope's time leaves out part of the
    # work: an implementation that moves each row once each way at the chip's
    # whole bandwidth and multiplies at its whole peak, one after the other,
    # reads 100% or less whatever the rows and tokens; the driver refuses 105%
    for rows, tokens in ((9, 256), (9, 3), (288, 8192), (144, 0)):
        least = fq.prefill_state_least_seconds(m, rows, tokens, PEAK)
        spent = 2 * rows * fq.state_row_bytes(m) / 819e9 + 9 * fq.recurrence_ops(m, tokens) / 197e12
        assert 100.0 * least / spent <= 100.0
    for rows in (9, 144, 288):
        spent = 2 * rows * fq.state_row_bytes(m) / 819e9
        assert 100.0 * fq.decode_state_least_seconds(m, rows, PEAK) / spent <= 100.0 + 1e-9


def test_experts_and_the_weights_a_step_reads():
    m = load("configs", CONFIG + ".json")["model"]
    assert flops_mellum2.expert_bytes(m) == 3 * 2048 * 512 * 2 == 6_291_456
    assert fq.picks_expected(m) == dict(here=1.25, away=8.75)
    # 16 live rows: 160 picks a layer over 512 columns touch 17.3 of the 64 banks
    assert abs(fq.banks_touched_expected(m, 16) - 17.3) < 0.1
    w = fq.weights_bytes_a_step(m, fq.banks_touched_expected(m, 16))
    assert abs(w["mixers"] / 1e9 - 0.607) < 0.001 and abs(w["attention"] / 1e9 - 0.164) < 0.001
    assert abs(w["beside"] / 1e9 - 0.101) < 0.001 and abs(w["banks"] / 1e9 - 1.31) < 0.01
    assert abs(w["head"] / 1e9 - 0.078) < 0.001
    assert 2.2 < sum(w.values()) / 1e9 < 2.3  # 2.8 ms a step at 819 GB/s


def test_the_file_keeps_the_catalogs_keys():
    c = load("configs", CONFIG + ".json")
    entry = next(e for e in load("..", "BENCHMARK.json")["configs"] if e["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    for k, v in CATALOG.items():
        assert c[k] == v or k in entry["reduced"], k
    assert set(entry["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size",
                                     "max_position_embeddings"}
    # floors: whole periods and four layers, at least 8 routed experts, an eighth
    # of the vocabulary
    assert c["num_hidden_layers"] % c["full_attention_interval"] == 0
    assert c["num_hidden_layers"] >= 4 and c["num_experts"] >= 8
    assert c["vocab_size"] * 8 >= CATALOG["vocab_size"]
    m = c["model"]
    assert m["num_experts"] == c["num_experts"] == 64
    assert m["router_experts"] == c["router_experts"] == CATALOG["num_experts"]
    assert m["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 3
    assert {"deployment", "assumed", "why_these_serve_settings", "initializer_range"} <= set(c)


def test_the_cells_traffic_letter_for_letter():
    w = load("workloads", CELL + ".json")
    t = w["traffic"]
    assert w["runner"] == "serve_reference_reuse" and w["reference"] == "reference_qwen3_next"
    assert os.path.exists(os.path.join(HERE, "runners", w["runner"] + ".py"))
    assert w["pools"] == {"pool_fill": "pool"} and w["drain_limit_s"] == 150
    assert t["generator"] == "code_mixed" and t["shape_seed"] == 51
    assert t["classes"] == [
        dict(name="chat", share=0.7,
             prompt_tokens=dict(median=4096, sigma=0.7, min=1024, max=12288)),
        dict(name="document", share=0.3,
             prompt_tokens=dict(median=24576, sigma=0.5, min=12288, max=49152))]
    assert t["output_tokens"] == dict(median=384, sigma=0.8, min=64, max=1536)
    assert set(w["end_to_end"]) == {"latency_per_token_p90_ms", "setup_s"}
    assert set(w["limits"]) == {"tie", "logit_err_mean", "logit_err_max"}
    # the runner's second phase: every slot used twice, short second requests, and
    # the field of the program's cache that holds the state a slot
    r = w["reuse"]
    assert set(r["limits"]) == {"reuse_logit_err_mean", "state_err", "state_bf16_share"}
    assert r["first_prompt_tokens"] > r["prompt_tokens"] >= 16 and r["output_tokens"] >= 8
    with open(os.path.join(os.path.dirname(HERE), "picotron_tpu", "serve", "paged_cache.py")) as f:
        assert f"    {r['state_pool']}: jnp.ndarray" in f.read()
    # no request passes the slot's table
    sv = load("configs", CONFIG + ".json")["serve"]
    assert 49152 + 1536 <= sv["max_model_len"] == 65536
    # the schedule the cell's rate draws
    sys.path.insert(0, os.path.join(HERE, "traffic"))
    import code_mixed
    due, which, prompt, out = code_mixed.shape(t, 51.0)
    assert len(due) >= 10 and (np.diff(due) > 0).all()
    assert prompt.max() <= 49152 and prompt.min() >= 1024 and out.min() >= 64
    assert which.sum() >= 2  # documents among them


def test_the_cell_is_on_the_lists_of_what_it_reports():
    bench = load("..", "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "longctx-mixed"
    assert len(cell["why"]) <= 200
    # (`in`, not "last" or "alone": a later PR appends its own cell to these lists)
    mine = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    new = {"gdn_mixer_ms.serve", "gdn_state_ms.serve", "gdn_chunk_ms.serve",
           "gdn_state_roofline.serve", "gdn_chunk_roofline.serve", "state_resets.serve"}
    assert new | {"paged_attention_roofline.serve", "moe_experts_roofline.serve",
                  "moe_picks_here.serve", "moe_shared_ms.serve", "kv_pool_fill.serve",
                  "decode_rows_live.serve", "peak_hbm_gib.serve", "compile_s",
                  # the full layers' prefill attention, and the rows a touched bank multiplies
                  "prefill_attention_ms.serve", "moe_rows_per_bank.serve"} <= mine
    # no latent pool, no window; and not the three idle_* metrics, whose reader finds
    # nothing to read in any serving cell since PR 48 (a list names the cells in which
    # the reader finds something)
    assert not mine & {"kv_latent_fill.serve", "window_read.serve", "mla_attention_ms.serve",
                       "idle_starved.serve", "idle_round_trip.serve",
                       "idle_inside_program.serve"}
    for name in new:
        m = next(x for x in bench["per_layer"] if x["name"] == name)
        assert CELL in m["workloads"] and m["moves"] == "latency_per_token_p90_ms"
        spec = load("layer_metrics", name + ".json")
        assert os.path.exists(os.path.join(HERE, "readers", spec["reader"] + ".py"))
        assert (m["unit"] == "%") == name.endswith("_roofline.serve")
