"""`flops_k_exaone` against the built tree and ISSUE 39's arithmetic; the
configuration's file against the catalog's published keys."""
import json
import os
import sys

import jax
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import flops_k_exaone as fl  # noqa: E402
import flops_mellum2  # noqa: E402

NAME = "k-exaone-236b-a23b-5l-ep8"


@pytest.fixture(scope="module")
def c():
    with open(os.path.join(HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


def test_the_cut_is_what_the_issue_reckons(c):
    m = c["model"]
    assert fl.attention_params(m) == 50_331_648 + 2 * 6_291_456 + 50_331_648 == 113_246_208
    assert fl.attention_params(m) + fl.dense_mlp_params(m) == 452_984_832
    assert fl.expert_params(m) == 37_748_736
    assert fl.expert_layer_params(m) == 113_246_208 + 786_432 + 17 * 37_748_736 == 755_761_152
    assert fl.matrix_params(m) == 3_711_959_040
    assert round(fl.matrix_params(m) * 2 / 1e9, 2) == 7.42
    # nine layers (1 + 8) would be 13.47 GB
    assert round(fl.matrix_params(dict(m, num_hidden_layers=9)) * 2 / 1e9, 2) == 13.47
    # training holds a parameter at 8 bytes at the guide's floors (8 experts held): 20.0 GB
    floor = dict(m, num_experts=8)
    assert fl.matrix_params(floor) == 2_503_999_488


def test_counts_match_the_built_tree(c):
    from picotron_tpu.config import config_from_dict, num_params
    from picotron_tpu.models.llama import init_params

    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "serve")}).model
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    total = sum(x.size for _, x in leaves)
    norms = sum(x.size for p, x in leaves if "norm" in jax.tree_util.keystr(p))
    assert norms == fl.norm_params(c["model"]) == 68_864
    assert total - norms == fl.matrix_params(c["model"])
    assert total == num_params(cfg)
    assert tree["layers"]["w_gate"].shape == (4, 16, 6144, 2048)
    assert tree["layers"]["router"].shape == (4, 6144, 128)
    assert tree["dense_layers"]["gate"].shape == (1, 6144, 18432)
    assert tree["layers"]["q_norm"].shape == (4, 128)
    assert [(st.name, st.layers) for st in cfg.stacks] == [("dense_layers", 1), ("layers", 4)]


def test_cache_and_step_bytes(c):
    from picotron_tpu.serve.paged_cache import ring_blocks_for

    m, sv = c["model"], c["serve"]
    assert fl.kv_position_bytes(m) == 4096
    assert flops_mellum2.kv_block_bytes(m, sv["block_size"]) == 16 * 4096  # the roofline's reader
    ring = ring_blocks_for(m["sliding_window"], sv["prefill_chunk"], sv["block_size"])
    assert ring == 25
    cache = fl.cache_bytes(m, sv, ring)
    assert cache["full"] == sv["decode_slots"] * sv["max_model_len"] * 4096
    assert cache["window"] == 4 * sv["decode_slots"] * 25 * 65536
    # a band of 128 lies in 8 or 9 blocks of 16, whatever the length
    assert {fl.band_blocks(m, n, 16) for n in range(129, 400)} == {8, 9}
    assert fl.band_blocks(m, 5, 16) == 1 and fl.band_blocks(m, 128, 16) == 8
    step = fl.weights_bytes_a_step(m, 16)
    gb = {k: round(v / 1e9, 2) for k, v in step.items()}
    assert gb == dict(attention=1.13, dense_mlp=0.68, routers=0.01, shared=0.30, banks=4.83,
                      head=0.24)
    # 27 live rows touch 13 of the 16 held banks a layer under a uniform router
    assert round(fl.banks_touched_expected(m, 27), 1) == 13.1


def test_the_file_holds_the_published_keys(c):
    """Every number of the catalog row's `config` under the same key, but for
    the four `reduced` ones; nested groups copied whole."""
    pub = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_size": 6144,
        "intermediate_size": 18432, "moe_intermediate_size": 2048, "n_group": 1,
        "num_attention_heads": 64, "num_experts_per_tok": 8, "num_key_value_heads": 8,
        "num_nextn_predict_layers": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "routed_scaling_factor": 2.5, "sliding_window": 128, "topk_group": 1}
    assert {k: c[k] for k in pub} == pub
    assert set(c["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size",
                                 "max_position_embeddings"}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"],
            c["max_position_embeddings"]) == (5, 16, 19200, 32768)
    kinds = ["sliding_attention"] * 3 + ["full_attention"]
    assert c["layer_types"] == kinds * 12 and c["sliding_windows"] == [128, 128, 128, 0] * 12
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert c["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"}
    assert c["model"]["layer_types"] == c["layer_types"][:5]
    assert {"qk_norm", "rope_on_full_layers", "norms", "router", "mtp", "weights"} <= set(
        c["assumed"])
    assert (c["model_type"], c["scoring_func"], c["norm_topk_prob"]) == (
        "exaone_moe", "sigmoid", True)
