"""`ops/paged_attention.py latent_prefill_attention`: a prefill chunk over
the latent pool in one kernel, in the Pallas interpreter, against the plain
form it stands in for on a chip (`ops/mla.py latent_attention`, expanded,
over `LatentPagedCache`'s tiles), at small widths that keep the kernel's
128-lane rules; the rule that decides which form runs; and `ServeEngine`
with the kernel forced in."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import ModelConfig, ServeConfig, resolve_preset
from picotron_tpu.models.llama import init_params
from picotron_tpu.ops import paged_attention as pa
from picotron_tpu.serve import paged_cache
from picotron_tpu.serve.engine import ServeEngine

L, NB, BS, PPT = 3, 64, 16, 8          # tiles of PPT * BS = 128 keys
HEADS, DN, DR, DV, RANK, W = 2, 128, 64, 128, 128, 256
MAX_BLOCKS = 24                         # a row holds up to 384 positions
CFG = SimpleNamespace(kv_lora_rank=RANK, qk_nope_head_dim=DN,
                      qk_rope_head_dim=DR, v_head_dim=DV)

# a row: (positions already in the cache, tokens of this chunk); the chunk
# is `s` queries wide and a row's tail beyond its tokens is padding
CASES = {
    "rows_of_unequal_length": dict(s=16, rows=[(0, 16), (150, 16), (360, 16), (7, 16)]),
    "first_chunk_is_its_own_context": dict(s=32, rows=[(0, 32)]),
    "starts_mid_tile": dict(s=16, rows=[(5, 16), (140, 16)], li=1),
    "crosses_a_tile_edge": dict(s=16, rows=[(120, 16), (248, 16)], li=2),
    "a_padding_row": dict(s=16, rows=[(130, 16), (0, 0), (256, 16)]),
    "a_partly_padded_row": dict(s=16, rows=[(200, 5), (0, 1), (368, 16)], li=1),
    "every_row_padding": dict(s=16, rows=[(0, 0), (0, 0)]),
    "tiles_of_16_pages": dict(s=16, rows=[(250, 16), (3, 9)], ppt=16),
    "bf16": dict(s=16, rows=[(0, 16), (150, 16), (360, 12), (0, 0)], li=2,
                 dtype=jnp.bfloat16, tol=3e-2),
}


def build(case, seed):
    """(q_n, q_r, q_pos, clean pool, poisoned pool, tables, kv_b): the
    poisoned pool is NaN in every block no row maps below its length (the
    block the unmapped sentinel clamps to among them) and in a mapped
    block's positions at or beyond the length."""
    rng = np.random.default_rng(seed)
    dt, s, rows = case.get("dtype", jnp.float32), case["s"], case["rows"]
    lengths = [p + n if n else 0 for p, n in rows]
    q_pos = np.full((len(rows), s), -1, np.int32)
    for b, (p, n) in enumerate(rows):
        q_pos[b, :n] = p + np.arange(n)
    free = list(rng.permutation(NB - 1))       # NB - 1 stays unmapped
    tables = np.full((len(rows), MAX_BLOCKS), NB, np.int32)
    clean = np.zeros((L, NB, BS, W), np.float32)
    clean[..., :RANK + DR] = rng.standard_normal((L, NB, BS, RANK + DR))
    poisoned = np.full_like(clean, np.nan)
    for b, n in enumerate(lengths):
        for j in range(-(-n // BS)):
            tables[b, j] = blk = free.pop()
            live = min(n - j * BS, BS)
            poisoned[:, blk, :live] = clean[:, blk, :live]
    q_n = rng.standard_normal((len(rows), s, HEADS, DN))
    q_r = rng.standard_normal((len(rows), s, HEADS, DR))
    kv_b = rng.standard_normal((RANK, HEADS * (DN + DV))) * 0.1
    return (jnp.asarray(q_n, dt), jnp.asarray(q_r, dt), jnp.asarray(q_pos),
            jnp.asarray(clean, dt), jnp.asarray(poisoned, dt),
            jnp.asarray(tables), jnp.asarray(kv_b, dt))


@pytest.mark.parametrize("name", CASES)
def test_kernel_matches_the_tiles_form(name, monkeypatch):
    case = CASES[name]
    ppt, li = case.get("ppt", PPT), case.get("li", 0)
    q_n, q_r, q_pos, clean, poisoned, tables, kv_b = build(case, len(name))
    monkeypatch.setattr(paged_cache, "TILE_KEYS", ppt * BS)
    with jax.default_matmul_precision("highest"):
        want = paged_cache.LatentPagedCache(clean, tables)._tiled(
            li, q_n, q_r, q_pos, kv_b, CFG)
        got = pa.latent_prefill_attention(
            q_n, q_r, q_pos, poisoned, li, tables, kv_b, pages_per_tile=ppt,
            interpret=True)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape == (len(case["rows"]), case["s"], HEADS, DV)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=case.get("tol", 1e-5))
    padding = np.asarray([n == 0 for _, n in case["rows"]])
    assert not got[padding].any()


def test_causal_inside_the_chunk():
    """A query's output does not move when a LATER position of its own
    chunk changes in the pool, and does when an earlier one does."""
    case = dict(s=16, rows=[(120, 16)])
    q_n, q_r, q_pos, clean, _, tables, kv_b = build(case, 7)

    def run(pool):
        with jax.default_matmul_precision("highest"):
            return np.asarray(pa.latent_prefill_attention(
                q_n, q_r, q_pos, pool, 0, tables, kv_b, pages_per_tile=PPT,
                interpret=True))

    base = run(clean)
    pos = 120 + 9                                 # the chunk's tenth token
    moved = run(clean.at[0, tables[0, pos // BS], pos % BS, :RANK].add(1.0))
    assert np.array_equal(base[0, :9], moved[0, :9])
    assert (np.abs(base[0, 9:] - moved[0, 9:]).max(axis=(1, 2)) > 1e-4).all()


def test_the_rule_decides_the_path(monkeypatch):
    def suits(s=256, heads=128, dn=128, dr=64, dv=128, rank=512, w=640,
              bs=16, dt=jnp.bfloat16, blocks=2048):
        q_n = jax.ShapeDtypeStruct((4, s, heads, dn), dt)
        q_r = jax.ShapeDtypeStruct((4, s, heads, dr), dt)
        pool = jax.ShapeDtypeStruct((5, 64, bs, w), dt)
        kv_b = jax.ShapeDtypeStruct((rank, heads * (dn + dv)), dt)
        return pa.latent_prefill_suits(q_n, q_r, pool, kv_b, blocks)

    assert not suits()                    # the CPU compiles no kernel
    monkeypatch.setattr(pa, "compiled_kernels_available", lambda: True)
    assert suits()                        # openPangu-Ultra's chunk of 256
    assert not suits(s=1)                 # a decode step
    assert not suits(s=4)                 # a speculated handful: no sublane tile
    assert not suits(dn=96) and not suits(dv=64) and not suits(rank=448)
    assert not suits(w=576)               # rows of 4.5 x 128 lanes
    assert not suits(dr=192)              # [c | k_r] wider than a pool row
    assert not suits(bs=4)                # the tiny presets' blocks
    assert suits(blocks=8) and not suits(blocks=4)  # a table of half a lane row of keys
    assert not suits(heads=4, dn=16, dr=8, dv=16, rank=32, w=128, bs=4,
                     dt=jnp.float32)      # debug-tiny-pangu-moe


def test_engine_prefills_through_the_kernel(monkeypatch, fresh_programs):
    """`ServeEngine` of a model with a latent cache, the prefill kernel
    forced in (interpreted) for every chunk: tokens and logits are the
    tiles form's, within rounding; on the CPU, unforced, the rule says no
    and the engine runs the tiles form as it always did."""
    # the tiny preset at widths that keep the kernel's lane rules, in tiles
    # of 128 keys, so a prompt of 150 walks two
    monkeypatch.setattr(pa, "PREFILL_TILE_KEYS", 128)
    monkeypatch.setattr(paged_cache, "TILE_KEYS", 128)
    cfg = ModelConfig(dtype="float32", **{
        **resolve_preset("debug-tiny-pangu-moe"), "num_hidden_layers": 2,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "kv_lora_rank": 128, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128})
    params = init_params(cfg, jax.random.key(2))
    rng = np.random.default_rng(5)
    requests = [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m)
                for n, m in ((150, 4), (6, 3), (21, 4))]
    scfg = ServeConfig(decode_slots=2, block_size=4, prefill_chunk=8,
                       max_model_len=192, decode_interval=2)
    asked = []

    def run(forced):
        rule = pa.latent_prefill_suits
        monkeypatch.setattr(
            paged_cache, "latent_prefill_suits",
            lambda q_n, *a: asked.append((forced, rule(q_n, *a)))
            or (forced and q_n.shape[1] > 1))
        eng = ServeEngine(params, cfg, scfg)
        out = eng.run(requests)
        eng.close()
        assert eng.pool.in_use == 0
        return out

    plain, kernel = run(False), run(True)
    assert asked and not any(said for _, said in asked)
    assert any(forced for forced, _ in asked)
    for a, b in zip(plain, kernel):
        assert a["tokens"] == b["tokens"]
        np.testing.assert_allclose(a["logits"], b["logits"], atol=2e-4)


def prefill_spans(cfg, monkeypatch, prompt_len: int):
    """The `serve.prefill.dispatch` spans' counts of ONE request served
    alone, at tiles of 16 keys."""
    from picotron_tpu.telemetry import Telemetry
    from picotron_tpu.telemetry.flightdeck import SpanTracer

    monkeypatch.setattr(pa, "PREFILL_TILE_KEYS", 16)
    tel = Telemetry(sinks=[])
    tel.tracer = SpanTracer()
    eng = ServeEngine(init_params(cfg, jax.random.key(2)), cfg, ServeConfig(
        decode_slots=2, block_size=4, prefill_chunk=8, max_model_len=64,
        decode_interval=2), telemetry=tel)
    eng.run([(list(range(1, prompt_len + 1)), 3)])
    eng.close()
    tel.close()
    return [e["args"] for e in tel.tracer.to_json()["traceEvents"]
            if e["ph"] == "X" and e["name"] == "serve.prefill.dispatch"]


def test_prefill_dispatch_counts_the_keys_its_rows_may_see(monkeypatch):
    cfg = ModelConfig(dtype="float32", **resolve_preset("debug-tiny-pangu-moe"))
    spans = prefill_spans(cfg, monkeypatch, 37)
    # chunks of 8: 8, 16, 24, 32 and 37 positions seen, in tiles of 16
    assert [d["tokens"] for d in spans] == [8, 8, 8, 8, 5]
    assert [d["latent_keys"] for d in spans] == [
        cfg.num_hidden_layers * k for k in (16, 16, 32, 32, 48)]


def test_no_latent_keys_without_a_latent_cache(monkeypatch):
    cfg = ModelConfig(dtype="float32", **resolve_preset("debug-tiny"))
    spans = prefill_spans(cfg, monkeypatch, 21)
    assert len(spans) == 3 and not any("latent_keys" in d for d in spans)
