"""flops.py against hand counts for both models (run: python -m pytest benchmark/tests)."""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import flops  # noqa: E402


def model(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_qwen2_1p5b_12l_hand_count():
    m = model("qwen2-1.5b-12l")
    # per layer: q 1536x1536, k and v 1536x256 each, o 1536x1536, mlp 3 x 1536x8960
    layer = 1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536 + 3 * 1536 * 8960
    assert layer == 46_792_704
    n = 12 * layer + 1536 * 151_936
    assert n == 794_886_144
    assert flops.multiplying_params(m) == n
    attn_fwd = 12 * 12 * 4 * 128 * 4097 / 2  # layers x heads x 2 matmuls x 2 x d x (S+1)/2
    assert flops.attention_flops_per_token_fwd(m, 4096) == attn_fwd
    assert flops.train_flops_per_token(m, 4096) == 6 * n + 3 * attn_fwd
    assert abs(flops.train_flops_per_token(m, 4096) / 1e9 - 5.222) < 0.001


def test_qwen2_7b_6l_hand_count():
    m = model("qwen2-7b-6l-tp2pp2")
    layer = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18_944
    assert layer == 233_046_016
    n = 6 * layer + 3584 * 152_064  # the embedding look-up multiplies nothing
    assert flops.multiplying_params(m) == n == 1_943_273_472
    assert abs(flops.train_flops_per_token(m, 4096) / 1e9 - 12.188) < 0.001


def test_flash_call_counts_causal_pairs():
    # one head, one sequence of 4 tokens: 10 visible pairs, d = 2
    assert flops.flash_call_flops("fwd", 1, 1, 4, 2) == 2 * 2 * 2 * 10
    assert flops.flash_call_flops("dq", 1, 1, 4, 2) == 3 * 2 * 2 * 10
    assert flops.flash_call_flops("dkv", 1, 1, 4, 2) == 4 * 2 * 2 * 10
    peak = dict(bf16_flops_per_s=100.0, hbm_bytes_per_s=10.0)
    assert flops.least_seconds(1000.0, 50.0, peak) == 10.0  # compute-bound
    assert flops.least_seconds(100.0, 50.0, peak) == 5.0    # bandwidth-bound
