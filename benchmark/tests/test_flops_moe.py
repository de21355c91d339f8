"""flops_moe.py against a hand count at OLMoE's widths, one layer, seq 4096."""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import flops  # noqa: E402
import flops_moe  # noqa: E402


def model(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_olmoe_one_layer_hand_count():
    m = model("olmoe-1b-7b-1l")
    attn = 4 * 2048 * 2048            # q, k, v, o: 16 heads of 128 each way
    router = 2048 * 64
    experts = 8 * 3 * 2048 * 1024     # the 8 a token passes, gate / up / down
    head = 2048 * 50_304
    assert (attn, router, experts, head) == (16_777_216, 131_072, 50_331_648, 103_022_592)
    n = attn + router + experts + head
    assert n == 170_262_528
    assert flops_moe.active_multiplying_params(m) == n
    assert abs(head / n - 0.605) < 0.001          # the head's inflated share at 1 layer
    attn_fwd = 1 * 16 * 4 * 128 * 4097 / 2        # layers x heads x 2 matmuls x 2 x d x (S+1)/2
    assert flops.attention_flops_per_token_fwd(m, 4096) == attn_fwd == 16_781_312
    per_token = flops_moe.train_flops_per_token_active(m, 4096)
    assert per_token == 6 * n + 3 * attn_fwd
    assert abs(per_token / 1e9 - 1.072) < 0.001
    # the dense count this cell must not be read with: one MLP of width 1024
    assert flops.multiplying_params(m) == attn + 3 * 2048 * 1024 + head


def test_expert_block_roofline():
    m = model("olmoe-1b-7b-1l")
    rows = 4096 * 8
    one = flops_moe.grouped_matmul_flops(rows, 2048, 1024)
    assert one == 2 * 32_768 * 2048 * 1024
    nbytes = flops_moe.grouped_matmul_bytes(rows, 2048, 1024, 64)
    assert nbytes == 2 * (32_768 * 2048 + 64 * 2048 * 1024 + 32_768 * 1024)
    peak = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
    # compute-bound, just: 0.698 ms of operations against 0.574 ms of bytes
    assert one / 197e12 > nbytes / 819e9
    least = flops_moe.expert_block_least_seconds(m, 4096, peak)
    assert abs(least - 9 * one / 197e12) < 1e-12
    assert abs(least * 1e3 - 6.279) < 0.001
