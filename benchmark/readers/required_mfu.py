"""Reader `required_mfu`: tokens/s/chip x the operations the forward and
backward passes require per token (`flops.py`: causal attention, no
recomputation) over the chip's peak (`peaks.json`), in %."""

import flops


def read(params, facts, ctx):
    rate = facts.get(params["rate_key"])
    if rate is None:
        return None
    per_token = flops.train_flops_per_token(facts["model"], facts["seq"])
    return 100.0 * rate * per_token / ctx.peak["bf16_flops_per_s"]
