"""flops_mellum2.py against a hand count at Mellum2's widths, and the
`code_mixed` generator: deterministic per seed, honours its clips and its
mixture, offers the same work under every seed."""
import importlib.util
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import flops_mellum2  # noqa: E402


def model():
    with open(os.path.join(HERE, "configs", "mellum2-12b-a2.5b-8l.json")) as f:
        return json.load(f)


def test_bytes_by_hand():
    m = model()["model"]
    assert flops_mellum2.expert_bytes(m) == 3 * 2304 * 896 * 2 == 12_386_304
    # a step that touches every expert of every layer: 6.34 GB of banks
    banks = 8 * 64 * flops_mellum2.expert_bytes(m)
    assert abs(banks / 1e9 - 6.34) < 0.01
    got = flops_mellum2.decode_experts_bytes(m, touched=8 * 64, row_steps=32)
    rows = 32 * 8 * 8 * (3 * 2304 + 3 * 896) * 2
    assert got == banks + rows and rows / banks < 0.01
    assert flops_mellum2.kv_block_bytes(m, 16) == 2 * 4 * 16 * 128 * 2 == 32_768
    # the issue's orientation: 7.1 GB a step, 8.7 ms at 819 GB/s
    total = flops_mellum2.weights_bytes_a_step(m)
    assert abs(total / 1e9 - 7.14) < 0.02
    assert abs(flops_mellum2.least_seconds(total, {"hbm_bytes_per_s": 819e9}) * 1e3 - 8.7) < 0.05
    qwen = json.load(open(os.path.join(HERE, "configs", "qwen2-1.5b.json")))["model"]
    assert flops_mellum2.kv_block_bytes(qwen, 16) == 2 * 2 * 16 * 128 * 2


def test_file_states_what_the_issue_asks():
    c = model()
    assert c["num_hidden_layers"] == c["model"]["num_hidden_layers"] == 8
    assert len(c["layer_types"]) == 28 and c["model"]["layer_types"] == c["layer_types"][:8]
    assert set(c["reduced"]) == {"num_hidden_layers", "max_position_embeddings"}
    assert {"qk_norm", "mtp_head", "sliding_window_keys", "weights"} <= set(c["assumed"])
    assert (c["hidden_size"], c["head_dim"], c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["vocab_size"], c["sliding_window"]) == (
                2304, 128, 896, 64, 8, 98304, 1024)
    assert "pipeline" in c["deployment"]


spec = importlib.util.spec_from_file_location(
    "code_mixed", os.path.join(HERE, "traffic", "code_mixed.py"))
gen = importlib.util.module_from_spec(spec)
spec.loader.exec_module(gen)
with open(os.path.join(HERE, "workloads", "mellum2-12b-a2.5b-8l.code-mixed.json")) as f:
    P = json.load(f)["traffic"]


def test_traffic_mixture_and_clips():
    reqs = gen.make(dict(P, rate_per_s=40.0), 2**31 + 77, 50.0, 98304)
    assert reqs == gen.make(dict(P, rate_per_s=40.0), 2**31 + 77, 50.0, 98304)
    lens = np.asarray([len(p) for _, p, _ in reqs])
    assert len(reqs) > 1500 and lens.min() >= 32 and lens.max() <= 14336
    due, which, plen, olen = gen.shape(dict(P, rate_per_s=40.0), 50.0)
    assert abs((which == 1).mean() - 0.3) < 0.04
    assert (plen[which == 0] <= 3072).all() and (plen[which == 1] >= 3072).all()
    assert abs(np.median(plen[which == 1]) - 6144) < 500
    assert abs(np.median(plen[which == 0]) - 512) < 60
    assert 2300 < plen.mean() < 2900  # the issue's "mean about 2,600"
    assert all(16 <= n <= 512 for _, _, n in reqs)
    assert [t for t, _, _ in reqs] == sorted(t for t, _, _ in reqs)


def test_seeds_offer_the_same_work():
    a, b = gen.make(P, 1, 30.0, 1000), gen.make(P, 2, 30.0, 1000)
    assert [(t, len(p), n) for t, p, n in a] == [(t, len(p), n) for t, p, n in b]
    assert a[0][1] != b[0][1]
