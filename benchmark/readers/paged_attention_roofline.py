"""Reader `paged_attention_roofline`: for the decode kernel's calls in the
traced window, the least time the chip could take to read the K and V blocks
they attend (`flops_mellum2.kv_block_bytes` x the blocks the engine counts on
its `serve.decode.dispatch` span, x the dispatch's `interval` steps) over the
summed device time of the kernel's events, in %. The blocks: `kv_blocks_banded`
where the span has it (a model with sliding layers: full and sliding layers
each summed over the layers of the kind, the sliding ones from the band's
first block on), else `kv_blocks` (what one layer reads) x the model's layers.
The counts are taken at a dispatch's first token, so a slot that crosses a
block edge inside the interval reads one block more than counted: the share
is under-, not overstated. No kernel in the trace or no count on the span
(a program from before PR 32) -> nothing reported."""

import re

import flops_mellum2
import trace_scopes


def read(params, facts, ctx):
    planes = trace_scopes.load(ctx.trace_dir)
    win = trace_scopes.window(planes)
    m, sv = ctx.config.get("model"), ctx.config.get("serve")
    if not planes or win is None or not m or not sv:
        return None
    pat = re.compile(params["ops"])
    names = [n for n in ctx.trace["op_seconds"] if pat.search(n)]
    secs = sum(ctx.trace["op_seconds"][n] for n in names)
    blocks = 0.0
    spans = trace_scopes.annotations(planes, [params["span"]], *win)
    for *_, c in spans:
        per_step = (float(c["kv_blocks_banded"]) if "kv_blocks_banded" in c
                    else float(c.get("kv_blocks", 0)) * m["num_hidden_layers"])
        blocks += per_step * float(c.get("interval", 0))
    if not secs or not blocks:
        return None
    least = flops_mellum2.least_seconds(
        blocks * flops_mellum2.kv_block_bytes(m, sv["block_size"]), ctx.peak)
    ctx.log(f"paged_attention_roofline: {blocks:.0f} blocks read in {len(spans)} dispatches, "
            f"least {least:.4f} s over {secs:.4f} s in "
            f"{sum(ctx.trace['op_calls'][n] for n in names)} kernel events")
    return 100.0 * least / secs
