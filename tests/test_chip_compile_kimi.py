"""The serve programs of `kimi-linear-48b-a3b-12l-ep8` compiled for a described
v5e, as tests/test_chip_compile.py compiles the other configurations' (its
helpers, imported; a file of its own so that neither grows past the other
files' time under `--dist loadfile`)."""

import re

import jax.numpy as jnp
import pytest

from picotron_tpu.telemetry.scopes import SCOPES
from test_chip_compile import (  # noqa: F401 (`topo` is a fixture; tests/ is on the path)
    abstract_params, instructions, load, lower_serve, topo, weights_written, whole_pool_copies,
    words,
)


KIMI = "kimi-linear-48b-a3b-12l-ep8"
KIMI_SERVE = load("configs", KIMI)["serve"]
# What the Kimi-Linear programs may write that is shaped like a stack's
# weights (`weights_written`; PANGU_WEIGHTS_WRITTEN's twin), each at most
# once or twice a DISPATCH and none of them a stack's large matrices: what
# openPangu leaves, `kv_b` re-laid with the heads major for the absorbed
# step's batched matmuls (25 MB); the second matrices of the expert stack's
# two low-rank projections (8 MiB each, the threshold itself; which of them
# the compiler copies at entry and which it fetches into fast memory inside
# the loop moves with the rung); the dense layer's own output projection
# (one layer outside any scan, 19 MB: fetched and re-laid once a dispatch)
KIMI_MAY_WRITE = {"bf16[3,512,8192]", "bf16[8,128,4096]", "bf16[4096,2304]"}


@pytest.mark.parametrize("program,rows", [
    ("serve_decode", None), ("serve_prefill", 1), ("serve_prefill", 16),
    ("serve_prefill", KIMI_SERVE["decode_slots"])])
def test_kimi_linear_serving_programs(topo, monkeypatch, program, rows):
    """Both serve programs of `kimi-linear-48b-a3b-12l-ep8` compile for a v5e
    and fit it beside the weights, the largest prefill rung included; the latent pool holds the three full layers
    alone and the state pool a row a slot and mixer; no pool is copied whole
    and all three ride their program in place; the decode kernel's tables fit
    SMEM; two stacks (the dense layer, a mixer; the expert stack's body one
    period (K, K, F, K) and three layers left over): a decode step calls the
    state's kernel 1 + 3 + 2 times, the latent kernel 1 + 1 times and the
    experts' grouped kernel 4 + 3 times, and gathers no row of state; a
    prefill chunk attends through the latent prefill kernel and runs the
    chunked per-channel rule as one kernel a mixer over the state pool in
    place (`kda_chunk_pooled`, 1 + 3 + 2 calls under `kda_chunk`: no row of
    state gathered, no loop over a sub-chunk's blocks left in `jax.numpy`,
    no triangular solve); a decode step's convolution takes its new tail by
    a select, no `dynamic-slice` under `kda_conv`; the scopes the cell's
    metrics read are there."""
    comp, cache, pools = lower_serve(topo, monkeypatch, KIMI, program, rows)
    text = comp.as_text()
    assert text.startswith(f"HloModule jit_{program}")
    slots, blocks, bs = (KIMI_SERVE[k] for k in ("decode_slots", "num_blocks", "block_size"))
    assert type(cache).__name__ == "HybridLatentPagedCache"
    assert cache.kv.shape == (3, blocks, bs, 640)
    assert cache.state.shape == (9, slots, 32, 128, 128) and cache.state.dtype == jnp.float32
    assert cache.tail.shape == (9, slots, 36864) and cache.tail.dtype == jnp.float32
    # the decode kernel takes the slots' tables whole into SMEM (1 MiB)
    assert slots * cache.tables.shape[1] * 4 <= 2**19
    ins = instructions(text)
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    recurrence = "kda_state" if program == "serve_decode" else "kda_chunk"
    assert found >= {"kda", "kda_conv", "kda_gate", recurrence, "kv_write", "paged_attention",
                     "attn_latent", "mla_q", "mla_kv_latent", "mla_o", "mlp", "moe_router",
                     "moe_dispatch", "moe_experts", "moe_shared", "sample"}
    assert not found & {"kda_state", "kda_chunk"} - {recurrence}
    assert ("mla_absorb" in found) == (program == "serve_decode")
    for shape in (cache.kv.shape, cache.state.shape, cache.tail.shape):
        copies = whole_pool_copies(text, shape)
        if shape == cache.tail.shape:
            # (as Qwen3-Next's: the compiler may keep the tail pool in VMEM)
            copies = [c for c in copies if "copy-done" not in c[2]]
        if shape == cache.state.shape:
            # (the largest rung's [tokens, picks, hidden] bfloat16 rows of the
            # expert block have the float32 state pool's element count)
            copies = [c for c in copies if " bf16[" not in c[2]]
        assert not copies, f"{program} copies a whole pool {shape}: {copies}"
    head = text.splitlines()[0]
    alias = head[head.index("input_output_alias={"):head.index("entry_computation_layout")]
    assert {int(p) for p in re.findall(r"\}: \((\d+), ", alias)} >= pools, alias
    kernels = [(n, op) for n, op, line in ins if "tpu_custom_call" in line]
    attn = re.compile(load("layer_metrics", "mla_attention_ms.serve")["params"]["ops"])
    latent = [(n, op) for n, op in kernels if attn.search(n)]
    grouped = [(n, op) for n, op in kernels if n.startswith("grouped_experts")]
    chunked = [(n, op) for n, op in kernels if n.startswith("latent_prefill_attention")]
    state = [(n, op) for n, op in kernels if n.startswith("kda_step_pooled")]
    rule = [(n, op) for n, op in kernels if n.startswith("kda_chunk_pooled")]
    assert (len(grouped) + len(latent) + len(chunked) + len(state) + len(rule)
            == len(kernels)), kernels
    assert len(grouped) == 7 and "ragged-dot" not in text
    assert all("moe_experts" in words(op) for _, op in grouped), grouped
    rows_of_state = f"f32[{rows or slots},32,128,128]"
    if program == "serve_decode":
        assert len(latent) == 2 and all("attn_latent" in words(op) for _, op in latent)
        assert len(state) == 6 and all({"kda", "kda_state"} <= words(op) for _, op in state)
        assert rows_of_state not in text and not chunked and not rule
        # one position a row: the convolution's new tail is one select
        assert not [n for n, op, line in ins if "kda_conv" in words(op)
                    and "dynamic-slice(" in line]
    else:
        assert not latent and not state and len(chunked) == 2
        assert all({"paged_attention", "attn_latent"} <= words(op) for _, op in chunked)
        assert len(rule) == 6 and all({"kda", "kda_chunk"} <= words(op) for _, op in rule)
        assert "triangular" not in text.lower()
        # the chunked rule's `jax.numpy` form is gone: no row of state
        # gathered and no exponential left under `kda_chunk` (the running
        # sum of g, which the kernel is handed, and the tails' moves are)
        assert rows_of_state not in text
        assert not [line for n, op, line in ins if "kda_chunk" in words(op)
                    and " exponential(" in line]
    ma = comp.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(program, rows, "total GiB", total / 2**30, "temp GiB",
          ma.temp_size_in_bytes / 2**30)
    assert total < 15.75 * 2**30, total / 2**30
    written = weights_written(text, abstract_params(KIMI))
    if (rows or 0) * KIMI_SERVE["prefill_chunk"] == 4096:
        # (a rung of 16 rows is 4,096 tokens: the expert block's combined
        # rows [tokens, hidden], an activation, have the output projection's
        # shape; whether the compiler leaves them a result of their own in
        # the loop as at the entry moves with what surrounds the loop)
        written = [w for w in written if w[1:] != ("fusion", "bf16[4096,2304]")]
    assert {shape for _, _, shape in written} <= KIMI_MAY_WRITE and len(written) <= 4, written
