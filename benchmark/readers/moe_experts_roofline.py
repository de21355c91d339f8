"""Reader `moe_experts_roofline`: for the expert matmuls of the traced steps,
the least time the chip could take (`flops_moe.py`: nine grouped matmuls an
expert block, forward + backward, rows = tokens x k exactly, over
`peaks.json`, the larger of the operations and the bytes bound) over the
device time `scope_and_ops_ms` sums for them (the `moe_experts` scope and the
compiler's `ragged-dot-*` kernels, the recomputed forward included, since the
chip spends it), in %. Nothing found -> nothing reported."""

import flops_moe
import trace_scopes


def read(params, facts, ctx):
    shape, steps = facts.get("moe_shape"), facts.get("traced_steps")
    if not shape or not steps:
        return None
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    if not planes or win is None:
        return None
    by_dev = ctx.load_module("readers", "scope_and_ops_ms").seconds_by_device(
        planes, win, params["scope"], params["ops"])
    dev = ctx.trace["first_device"]
    if dev not in by_dev:
        return None
    spent, calls = by_dev[dev]
    least = steps * shape["blocks_per_step"] * flops_moe.expert_block_least_seconds(
        facts["model"], shape["tokens"], ctx.peak)
    ctx.log(f"moe_experts_roofline: least {least:.4f} s over {spent:.4f} s spent on device "
            f"{dev} ({calls} kernel events named like {params['ops']!r})")
    return 100.0 * least / spent if spent > 0 else None
