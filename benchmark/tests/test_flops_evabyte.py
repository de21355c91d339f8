"""`flops_evabyte` against the built tree and ISSUE 43's arithmetic; the
configuration's file against the catalog's published keys."""
import json
import os
import sys

import jax
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import flops_evabyte as fl  # noqa: E402
import flops_mellum2  # noqa: E402

NAME = "evabyte-6.5b-8l"


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


def test_the_cut_is_what_the_issue_reckons(c):
    m = c["model"]
    assert fl.attention_params(m) == 4 * 16_777_216 == 67_108_864
    assert fl.mlp_params(m) == 3 * 45_088_768 == 135_266_304
    assert fl.pooling_params(m) == 2 * 32 * 128 == 8_192
    assert fl.layer_params(m) == 202_391_552
    assert fl.ends_params(m) == 1_310_720 + 10_485_760 + 4_096 == 11_800_576
    assert fl.total_params(m) == 8 * 202_391_552 + 11_800_576 == 1_630_932_992
    assert round(fl.total_params(m) * 2 / 1e9, 2) == 3.26
    # the published depth: 6.49 B parameters, 12.98 GB; 16 layers: 6.50 GB
    assert fl.total_params(dict(m, num_hidden_layers=32)) == 6_488_330_240
    assert round(fl.total_params(dict(m, num_hidden_layers=32)) * 2 / 1e9, 2) == 12.98
    assert round(fl.total_params(dict(m, num_hidden_layers=16)) * 2 / 1e9, 2) == 6.50


def test_counts_match_the_built_tree(c):
    from picotron_tpu.config import config_from_dict, num_params
    from picotron_tpu.models.llama import init_params

    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")}).model
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    total = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert total == fl.total_params(c["model"]) == num_params(cfg)
    assert tree["layers"]["eva_mu"].shape == tree["layers"]["eva_phi"].shape == (8, 32, 128)
    assert tree["lm_head"].shape == (4096, 2560) and tree["embedding"].shape == (320, 4096)
    assert [(st.name, st.layers, st.block.attn) for st in cfg.stacks] == [("layers", 8, "eva")]


def test_blocks_and_step_bytes(c):
    from picotron_tpu.serve.paged_cache import eva_table_width
    from picotron_tpu.serve.scheduler import Scheduler
    from picotron_tpu.config import config_from_dict

    m, sv = c["model"], c["serve"]
    bs = sv["block_size"]
    assert fl.kv_position_bytes(m) == 16_384  # 16 KB a layer: eight times Qwen2-7B's
    assert flops_mellum2.kv_block_bytes(m, bs) == 262_144  # the roofline's reader, a layer
    assert fl.block_bytes(m, bs) == 2_097_152
    # a slot at 32,768 positions: 128 window blocks + 128 summary blocks = 0.54 GB, where
    # full attention would hold 2,048 blocks, 4.29 GB
    assert fl.blocks_held(m, 32768, bs) == (128, 128)
    assert round(256 * fl.block_bytes(m, bs) / 1e9, 2) == 0.54
    assert round(fl.full_attention_blocks(32768, bs) * fl.block_bytes(m, bs) / 1e9, 2) == 4.29
    assert round(sv["num_blocks"] * fl.block_bytes(m, bs) / 1e9, 2) == 6.44
    # the last query of a slot reads the window and 15 closed windows' summaries
    assert fl.blocks_read(m, 32768, bs) == (128, 120)
    assert fl.blocks_read(m, 2048, bs) == (128, 0) and fl.blocks_read(m, 2049, bs) == (1, 8)
    assert fl.blocks_held(m, 2049, bs) == (128, 8) and fl.blocks_held(m, 300, bs) == (19, 2)
    # the program's own law, the scheduler's and the table's width
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")}).model
    sched = Scheduler(1, None, bs, 2048, summary=(cfg.window_size, cfg.chunk_size))
    for n in (1, 15, 16, 255, 256, 2048, 2049, 20000, 32768):
        assert sched.blocks_at(n) == fl.blocks_held(m, n, bs)
    assert eva_table_width(cfg, sv["max_model_len"], bs) == 256
    # a chat slot at 900 bytes reads 57 blocks a layer (full attention: the same); a
    # document slot at 22,000 reads 95 + 80 where full attention reads 1,375
    assert sum(fl.blocks_read(m, 900, bs)) == fl.full_attention_blocks(900, bs) == 57
    assert fl.blocks_read(m, 22000, bs) == (95, 80)
    assert fl.full_attention_blocks(22000, bs) == 1375
    step = fl.decode_step_bytes(m, [900] * 7 + [22000] * 4, bs)
    gb = {k: round(v / 1e9, 2) for k, v in step.items()}
    assert gb == dict(layers=3.24, head=0.0, cache=2.3)


def test_the_file_holds_the_published_keys(c):
    """Every number of the catalog row's `config` under the same key, but for
    the one `reduced`."""
    pub = {"attention_bias": False, "attention_class": "eva", "chunk_size": 16, "fp32_ln": False,
           "fp32_logits": True, "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096,
           "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
           "intermediate_size": 11008, "lazy_init": True, "max_position_embeddings": 32768,
           "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
           "norm_add_unit_offset": True, "num_attention_heads": 32, "num_chunks": None,
           "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
           "rope_scaling": None, "rope_theta": 100000, "tie_word_embeddings": False,
           "vocab_size": 320, "window_size": 2048}
    assert {k: c[k] for k in pub} == pub
    assert set(c["reduced"]) == {"num_hidden_layers"} and c["num_hidden_layers"] == 8
    assert c["model"]["num_hidden_layers"] == 8 and c["initializer_range"] == c["init_std"]
    assert {"pooling", "pooled_after_rotation", "summaries_visible", "one_scale",
            "pooling_vectors", "norms", "heads", "weights"} <= set(c["assumed"])
    assert c["source"] == "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [e for e in bench["configs"] if e["name"] == NAME]
    assert len(entry) == 1 and entry[0]["reduced"] == ["num_hidden_layers"]
    assert entry[0]["source"] == c["source"] and entry[0]["file"].endswith(NAME + ".json")
