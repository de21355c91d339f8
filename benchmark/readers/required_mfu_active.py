"""Reader `required_mfu_active`: `required_mfu` for a model with sparse
experts: tokens/s/chip x the operations the forward and backward passes
require per token counting only the parameters a token passes through
(`flops_moe.py`: attention, router, k experts, head; causal attention; no
recomputation) over the chip's peak (`peaks.json`), in %."""

import flops_moe


def read(params, facts, ctx):
    rate = facts.get(params["rate_key"])
    if rate is None or not facts["model"].get("num_experts"):
        return None
    per_token = flops_moe.train_flops_per_token_active(facts["model"], facts["seq"])
    return 100.0 * rate * per_token / ctx.peak["bf16_flops_per_s"]
