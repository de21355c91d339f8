"""The serve programs of `nemotron3-super-120b-a12b-22l-ep8` compiled for a
described v5e, as tests/test_chip_compile.py compiles the other configurations'
(its helpers, imported; a file of its own so that neither grows past the other
files' time under `--dist loadfile`)."""

import re

import jax.numpy as jnp
import pytest

from picotron_tpu.telemetry.scopes import SCOPES
from test_chip_compile import (  # noqa: F401 (`topo` is a fixture; tests/ is on the path)
    _jamba_pools_ride_in_place, assert_weights_read_in_place, instructions, kv_write_kernels,
    load, lower_serve, topo, words,
)


NEMOTRON = "nemotron3-super-120b-a12b-22l-ep8"
SERVE = load("configs", NEMOTRON)["serve"]


@pytest.mark.parametrize("program,rows", [
    ("serve_decode", None), ("serve_prefill", 1), ("serve_prefill", SERVE["decode_slots"])])
def test_nemotron_h_serving_programs(topo, monkeypatch, program, rows):
    """Both serve programs of `nemotron3-super-120b-a12b-22l-ep8` compile for a
    v5e and fit it beside 10.7 GB of weights with 0.75 GiB to spare, the largest
    prefill rung included; the K/V pool holds the two attention layers alone
    (a layer's row is its ordinal among them: ten of the 22 layers have no cache
    row at all) and the state pool a row a slot and mixer, float32, [128 heads,
    64, 128] with N along the lanes; no pool is copied whole and all four ride
    their program in place; the decode kernel's tables fit SMEM; the scan body is
    one period of 11 (an attention, then five (experts, mixer) pairs), so a decode
    step calls the state's kernel and the tail's 5 times a body, the attention's
    and the K/V write's once and the experts' grouped kernel (two banks of 1,024
    x 2,688 an expert) 5 times, and a prefill chunk the chunked rule's kernel and
    the grouped kernel 5 times each; neither gathers a row of state; the scopes
    the cell's metrics read are there, under the names benchmark/NEMOTRON_H.md
    gives."""
    comp, cache, pools = lower_serve(topo, monkeypatch, NEMOTRON, program, rows)
    text = comp.as_text()
    assert text.startswith(f"HloModule jit_{program}")
    slots, blocks, bs = (SERVE[k] for k in ("decode_slots", "num_blocks", "block_size"))
    assert type(cache).__name__ == "HybridPagedCache"
    assert cache.k.shape == (2, 2, blocks, bs, 128)
    assert cache.state.shape == (10, slots, 128, 64, 128) and cache.state.dtype == jnp.float32
    assert cache.tail.shape == (10, slots, 240, 128) and cache.tail.dtype == jnp.float32
    # the decode kernel takes the slots' tables whole into SMEM (1 MiB)
    assert slots * cache.tables.shape[1] * 4 <= 2**19
    ins = instructions(text)
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    recurrence = "ssd_step" if program == "serve_decode" else "ssd_chunk"
    assert found >= {"ssd_mixer", "ssd_conv", recurrence, "kv_write", "paged_attention",
                     "attn_full", "mlp", "moe_router", "moe_dispatch", "moe_experts",
                     "moe_shared", "moe_latent", "sample"}
    assert not found & {"ssd_step", "ssd_chunk"} - {recurrence}
    _jamba_pools_ride_in_place(text, cache, pools, program)
    kernels = [(n, op) for n, op, line in ins if "tpu_custom_call" in line]
    attn = re.compile(load("layer_metrics", "paged_attention_ms.serve")["params"]["ops"])
    paged = [(n, op) for n, op in kernels if attn.search(n)]
    grouped = [(n, op) for n, op in kernels if n.startswith("grouped_experts")]
    step = [(n, op) for n, op in kernels if n.startswith("ssd_step_pooled")]
    chunk = [(n, op) for n, op in kernels if n.startswith("ssd_chunk_pooled")]
    conv = [(n, op) for n, op in kernels if n.startswith("ssm_conv_step_pooled")]
    written = kv_write_kernels(ins, [cache.k.shape], program, 1)
    assert (len(paged) + len(grouped) + len(step) + len(chunk) + len(conv) + len(written)
            == len(kernels)), kernels
    assert len(grouped) == 5 and "ragged-dot" not in text
    assert all({"mlp", "moe_experts"} <= words(op) for _, op in grouped), grouped
    # the live rows' states alone: no batch of states is gathered or scattered
    assert f"f32[{rows or slots},128,64,128]" not in text
    if program == "serve_decode":
        # 32 query heads over two K/V heads of 128 through the decode kernel
        assert len(paged) == 1 and "attn_full" in words(paged[0][1]) and not chunk
        assert len(step) == 5 and all({"ssd_mixer", "ssd_step"} <= words(op) for _, op in step)
        # ... and their tails alone: the convolution's kernel, under both scopes
        assert len(conv) == 5 and all({"ssd_mixer", "ssd_conv", "ssd_step"} <= words(op)
                                      for _, op in conv)
        assert f"f32[{slots},240,128]" not in text
    else:
        assert not paged and not step and not conv
        assert len(chunk) == 5 and all({"ssd_mixer", "ssd_chunk"} <= words(op)
                                       for _, op in chunk)
    ma = comp.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(program, rows, "total GiB", total / 2**30, "temp GiB",
          ma.temp_size_in_bytes / 2**30)
    assert total < (15.75 - 0.75) * 2**30, total / 2**30
    assert_weights_read_in_place(text, NEMOTRON)
