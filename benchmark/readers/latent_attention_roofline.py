"""Reader `latent_attention_roofline`: for the latent decode kernel's calls in
the traced window, the least time the chip could take over the blocks they
attend (`flops_pangu_moe.latent_decode_least_seconds`: the LARGER of the
blocks' bytes over the memory bandwidth and the absorbed attention's operations
over the matmul peak, since the kernel sits on the chip's ridge) over the
summed device time of the kernel's events, in %. The blocks: `latent_blocks`
on the engine's `serve.decode.dispatch` span (the blocks the step's slots
hold, summed over the layers) x the dispatch's `interval` steps. The counts
are taken at a dispatch's first token, so a slot that crosses a block edge
inside the interval reads one block more than counted, and a block's last
positions beyond a slot's length are counted as attended: the first under-,
the second overstates by at most one block a slot and layer; the stored rows'
padding (640 of 576) is not counted. No kernel in the trace or no count on the
span (a program without a latent cache) -> nothing reported."""

import re

import flops_pangu_moe
import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    m, sv = ctx.config.get("model"), ctx.config.get("serve")
    if not planes or win is None or not m or not sv or "kv_lora_rank" not in m:
        return None
    pat = re.compile(params["ops"])
    names = [n for n in ctx.trace["op_seconds"] if pat.search(n)]
    secs = sum(ctx.trace["op_seconds"][n] for n in names)
    spans = trace_scopes.annotations(planes, [params["span"]], *win)
    blocks = sum(float(c.get("latent_blocks", 0)) * float(c.get("interval", 0))
                 for *_, c in spans)
    if not secs or not blocks:
        return None
    least = flops_pangu_moe.latent_decode_least_seconds(m, blocks, sv["block_size"], ctx.peak)
    ctx.log(f"latent_attention_roofline: {blocks:.0f} blocks attended in {len(spans)} "
            f"dispatches, least {least:.4f} s (bytes "
            f"{blocks * flops_pangu_moe.latent_block_bytes(m, sv['block_size']) / ctx.peak['hbm_bytes_per_s']:.4f}"
            f", operations "
            f"{flops_pangu_moe.latent_decode_ops(m, blocks * sv['block_size']) / ctx.peak['bf16_flops_per_s']:.4f}"
            f") over {secs:.4f} s in {sum(ctx.trace['op_calls'][n] for n in names)} kernel events")
    return 100.0 * least / secs
