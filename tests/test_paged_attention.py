"""The decode step's in-place paged attention (ops/paged_attention.py), on the
CPU through the Pallas interpreter: the kernel against the gathered view +
`generate._cached_attention`, and one engine run with the kernel forced into
`serve_decode`. The compiled kernel at the chat cell's shapes is held by
tests/test_chip_compile.py; its times are chip runs (PERF.md)."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import ModelConfig, ServeConfig, resolve_preset
from picotron_tpu.generate import (
    _cached_attention, _decode_layers, init_cache,
)
from picotron_tpu.models.llama import (
    final_hidden, head_weight, init_params, model_rope_tables,
)
from picotron_tpu.ops.paged_attention import (
    decode_kernel_suits, paged_decode_attention, paged_kv_write,
)
from picotron_tpu.serve import ServeEngine, engine, paged_cache
from picotron_tpu.serve.paged_cache import PagedKVCache

BS, MB, NB, L = 16, 6, 40, 3   # block size, table width, pool blocks, layers
FULL = BS * MB

CASES = {
    # lengths: 1, one block exactly, a block + 1, the full table, idle (0)
    "gqa_12_2_ragged": dict(hq=12, hkv=2, lengths=[1, BS, BS + 1, FULL, 0], li=1),
    "multi_head_4_4": dict(hq=4, hkv=4, lengths=[3, 0, FULL, 2 * BS], li=0),
    "last_layer": dict(hq=12, hkv=2, lengths=[FULL, 5, BS + 1], li=L - 1),
    "chunks_of_1_page": dict(hq=12, hkv=2, lengths=[1, BS, BS + 1, FULL, 0],
                             li=1, ppc=1),
    "chunks_of_4_pages": dict(hq=4, hkv=2, lengths=[4 * BS, 4 * BS + 1, FULL, 7],
                              li=2, ppc=4),
    "every_slot_idle": dict(hq=4, hkv=2, lengths=[0, 0, 0], li=L - 1),
    "bf16_pool": dict(hq=12, hkv=2, lengths=[1, BS + 1, FULL, 0], li=L - 1,
                      dtype=jnp.bfloat16, tol=2e-2),
    "head_dim_32": dict(hq=4, hkv=2, lengths=[9, FULL, 0, BS], li=1, d=32),
}


def scattered_tables(rng, lengths):
    """A table a slot whose held blocks are drawn without order from all
    over the pool (non-monotone, interleaved between slots); entries past
    the blocks held stay at the unmapped sentinel NB. The pool's last
    block, which a clamped sentinel addresses, is never held."""
    tables = np.full((len(lengths), MB), NB, np.int32)
    free = list(rng.permutation(NB - 1))
    for b, n in enumerate(lengths):
        for j in range(-(-n // BS)):
            tables[b, j] = free.pop()
    return tables


@pytest.mark.parametrize("name", CASES)
def test_kernel_matches_the_gathered_view(name):
    """The kernel on a pool poisoned everywhere a live slot must not read
    equals the view path on the clean pool. NaN goes into every block no
    live slot maps (the last block of the last layer, which a clamped
    sentinel would address, among them), into the tail of every partly
    filled block, and into all other layers: a read past a slot's length
    or off the table's live entries would reach the output as NaN."""
    c = CASES[name]
    d, dtype, li = c.get("d", 128), c.get("dtype", jnp.float32), c["li"]
    lengths = np.asarray(c["lengths"], np.int32)
    rng = np.random.default_rng(sorted(CASES).index(name))
    tables = scattered_tables(rng, lengths)
    shape = (c["hkv"], L, NB, BS, d)
    k = jnp.asarray(rng.standard_normal(shape), dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    q = jnp.asarray(rng.standard_normal((len(lengths), c["hq"], d)), dtype)

    # positions of the pool that hold a live slot's cached token at layer li
    held = np.zeros((L, NB, BS), bool)
    for b, n in enumerate(lengths):
        for pos in range(n):
            held[li, tables[b, pos // BS], pos % BS] = True
    assert not held[:, NB - 1].any()  # what a clamped sentinel would read
    poison = jnp.asarray(~held)[None, :, :, :, None]
    got = paged_decode_attention(
        q, jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v), li,
        jnp.asarray(tables), jnp.asarray(lengths),
        pages_per_chunk=c.get("ppc"), interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    assert (got[lengths == 0] == 0).all()

    f32 = lambda a: a.astype(jnp.float32)
    ck, cv = PagedKVCache(f32(k), f32(v), jnp.asarray(tables)).layer_view(li)
    want = _cached_attention(f32(q)[:, None], ck, cv,
                             jnp.asarray(lengths - 1)[:, None])[:, 0]
    live = lengths > 0
    np.testing.assert_allclose(got[live], np.asarray(want)[live],
                               rtol=c.get("tol", 2e-5), atol=c.get("tol", 2e-5))


def test_the_step_decides_the_path(monkeypatch):
    """`PagedKVCache.attend` takes the kernel for a decode step whose shapes
    suit it on a backend that compiles kernels, and the view otherwise:
    from the shapes alone, no option."""
    from picotron_tpu.ops import paged_attention as pa
    pool = jnp.zeros((2, L, NB, 16, 128), jnp.bfloat16)
    q1 = jnp.zeros((3, 1, 12, 128), jnp.bfloat16)
    assert not decode_kernel_suits(q1, pool)  # the CPU compiles no kernel
    monkeypatch.setattr(pa, "compiled_kernels_available", lambda: True)
    assert decode_kernel_suits(q1, pool)
    assert not decode_kernel_suits(jnp.zeros((3, 5, 12, 128)), pool)  # s > 1
    assert not decode_kernel_suits(q1[..., :64], pool[..., :64])  # head 64
    assert not decode_kernel_suits(q1, pool[:, :, :, :8])  # half a bf16 tile
    assert decode_kernel_suits(q1, pool[:, :, :, :8].astype(jnp.float32))


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(dtype="float32", **{
        **resolve_preset("debug-tiny"), "max_position_embeddings": 64})
    return cfg, init_params(cfg, jax.random.key(0))


def reference_logits(params, cfg, ids):
    """[len(ids), V] logits of the offline contiguous-cache forward over
    the whole sequence: row i predicts token i + 1."""
    cos, sin = model_rope_tables(cfg, max_len=len(ids))
    x = params["embedding"][jnp.asarray([ids])].astype(jnp.float32)
    x, _ = _decode_layers(params, x, init_cache(cfg, 1, len(ids)),
                          jnp.arange(len(ids)), cfg, cos, sin)
    hf = final_hidden(params, x, cfg)
    return np.asarray(hf @ head_weight(params).astype(hf.dtype))[0]


def test_sharded_pool_keeps_the_view(tiny, monkeypatch, fresh_programs):
    """tp = 2 serving pins the pool over its KV heads, and the compiler does
    not partition a Pallas call: with the kernel forced in wherever the
    step's shape allows it, no program of such an engine asks for it (a
    prefill chunk of ONE token is a decode-shaped step), nor for the write's
    kernel, and the tokens are the single-device engine's."""
    from picotron_tpu.generate import place_for_decode

    cfg, params = tiny
    asked, wrote = [], []
    monkeypatch.setattr(paged_cache, "decode_kernel_suits",
                        lambda q, k: asked.append(q.shape[1]) or True)
    # (asked and refused: the tiny model's blocks are no sublane tile)
    monkeypatch.setattr(paged_cache, "kv_write_suits",
                        lambda new, k: wrote.append(new.shape[1]) or False)
    scfg = ServeConfig(decode_slots=2, block_size=4, num_blocks=16,
                       prefill_chunk=1, max_model_len=32, decode_interval=2)
    requests = [([3, 1, 4, 1, 5], 4), ([9, 2, 6], 5)]
    tokens = {}
    for tp in (2, 1):
        del asked[:], wrote[:]
        eng = ServeEngine(place_for_decode(params, cfg, tp=tp), cfg, scfg)
        assert (type(eng.cache) is paged_cache.ShardedPagedKVCache) == (tp == 2)
        tokens[tp] = [r["tokens"] for r in eng.run(requests)]
        eng.close()
        assert bool(asked) == (tp == 1) and bool(wrote) == (tp == 1)
    assert tokens[2] == tokens[1]


def test_engine_decodes_through_the_kernel(tiny, monkeypatch, fresh_programs):
    """`serve_decode` with the kernel forced in (interpreted) serves the
    offline reference's greedy tokens wherever the reference's two best
    logits lie further apart than rounding moves them. The bit-identical
    parity tests of test_serve.py keep running the view path."""
    cfg, params = tiny
    taken = []

    def force(q, k_pool):
        taken.append(q.shape[1])
        return q.shape[1] == 1

    monkeypatch.setattr(paged_cache, "decode_kernel_suits", force)
    rng = np.random.default_rng(0)
    requests = [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m)
                for n, m in ((5, 6), (9, 3), (3, 8), (7, 5), (11, 4))]
    eng = ServeEngine(params, cfg, ServeConfig(
        decode_slots=3, block_size=4, num_blocks=24, prefill_chunk=4,
        max_model_len=32, decode_interval=3))
    results = eng.run(requests)
    eng.close()
    assert 1 in taken and any(s > 1 for s in taken)  # decode: kernel; prefill: view
    assert eng.pool.in_use == 0
    tol, checked = 1e-3, 0
    for (prompt, n), res in zip(requests, results):
        toks = res["tokens"]
        assert len(toks) == n
        logits = reference_logits(params, cfg, prompt + toks)
        for i, tok in enumerate(toks):
            row = logits[len(prompt) - 1 + i]
            best, second = np.sort(row)[[-1, -2]]
            if tok != int(row.argmax()):
                # a near-tie that rounding decided; what follows is another
                # sequence than the reference's
                assert best - second <= tol, (res["id"], i, best - second)
                break
            checked += 1
    assert checked >= 20


# ---------------------------------------------------------------------------
# a sliding-window layer: the band's first block on, through a ring
# ---------------------------------------------------------------------------

RING = 4   # blocks in a slot's ring: 64 positions for a band of 40
WINDOW = 40

WINDOW_CASES = {
    # lengths below the window, at it, a block past it, several turns of
    # the ring, idle
    "ragged": dict(hq=12, hkv=2, lengths=[1, WINDOW, WINDOW + BS + 1, 7 * BS + 3, 0]),
    "block_edges": dict(hq=4, hkv=2, lengths=[BS, 4 * BS, 5 * BS, 9 * BS + 1]),
    "chunks_of_1_page": dict(hq=4, hkv=4, lengths=[3, 6 * BS + 5, WINDOW + 1], ppc=1),
    "bf16_pool": dict(hq=12, hkv=2, lengths=[5, 11 * BS + 9, 0], dtype=jnp.bfloat16,
                      tol=2e-2),
    # K-EXAONE's shape of the matter: a band far shorter than the ring that prefill
    # needs (window + chunk), 8 query heads a KV head; the chunk is sized by the band
    "band_shorter_than_ring": dict(hq=16, hkv=2, ring=7, window=2 * BS,
                                   lengths=[1, 2 * BS, 2 * BS + 1, 9 * BS + 5, 0]),
    "band_off_block_edges": dict(hq=8, hkv=1, ring=6, window=BS + 3,
                                 lengths=[BS + 3, 3 * BS, 6 * BS + 1, 13 * BS - 1]),
}


@pytest.mark.parametrize("name", WINDOW_CASES)
def test_windowed_kernel_matches_jnp(name):
    """The kernel with `window` reads, through a ring of `ring` blocks, the
    positions max(length - window, 0) .. length - 1 and nothing else: every
    pool position outside a live slot's band is NaN (the ring's blocks that
    hold positions before the band among them), and the result equals plain
    softmax attention over the band's keys."""
    c = WINDOW_CASES[name]
    d, dtype, li = 128, c.get("dtype", jnp.float32), 1
    ring, window = c.get("ring", RING), c.get("window", WINDOW)
    lengths = np.asarray(c["lengths"], np.int32)
    rng = np.random.default_rng(sorted(WINDOW_CASES).index(name))
    free = list(rng.permutation(NB - 1))
    tables = np.full((len(lengths), ring), NB, np.int32)
    for b, n in enumerate(lengths):
        for j in range(min(-(-n // BS), ring)):
            tables[b, j] = free.pop()
    shape = (c["hkv"], L, NB, BS, d)
    k = np.full(shape, np.nan, np.float32)
    v = np.full(shape, np.nan, np.float32)
    q = rng.standard_normal((len(lengths), c["hq"], d)).astype(np.float32)
    want = np.zeros_like(q)
    g = c["hq"] // c["hkv"]
    for b, n in enumerate(lengths):
        band = np.arange(max(n - window, 0), n)
        kb = rng.standard_normal((len(band), c["hkv"], d)).astype(np.float32)
        vb = rng.standard_normal((len(band), c["hkv"], d)).astype(np.float32)
        if dtype == jnp.bfloat16:
            kb, vb = (np.asarray(jnp.asarray(a, dtype), np.float32) for a in (kb, vb))
        for i, pos in enumerate(band):
            blk = tables[b, (pos // BS) % ring]
            k[:, li, blk, pos % BS], v[:, li, blk, pos % BS] = kb[i], vb[i]
        for h in range(c["hq"]):
            s = kb[:, h // g] @ q[b, h] / np.sqrt(d)
            p = np.exp(s - s.max()) if len(band) else s
            want[b, h] = (p / max(p.sum(), 1e-30)) @ vb[:, h // g] if len(band) else 0
    got = paged_decode_attention(
        jnp.asarray(q, dtype), jnp.asarray(k, dtype), jnp.asarray(v, dtype), li,
        jnp.asarray(tables), jnp.asarray(lengths), window=window,
        pages_per_chunk=c.get("ppc"), interpret=True)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all() and (got[lengths == 0] == 0).all()
    tol = c.get("tol", 2e-5)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("window,ring,pages", [
    (128, 25, 9),     # K-EXAONE: the band's blocks + 1, not the ring that prefill needs
    (1024, 82, 64),   # Mellum2: the band is longer than a chunk, which stays the default
    (None, 1024, 64),  # a full layer
], ids=["k_exaone", "mellum2", "full"])
def test_a_chunk_is_sized_by_the_band(window, ring, pages):
    """The pages a chunk the kernel double-buffers in VMEM: at most what a
    band can lie in, whatever the ring holds beside it for prefill."""
    import functools

    hkv, d, slots = 8, 128, 4
    pool = jax.ShapeDtypeStruct((hkv, 2, 64, BS, d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        functools.partial(paged_decode_attention, window=window, interpret=True),
        static_argnums=(3,))(
        jax.ShapeDtypeStruct((slots, 64, d), jnp.bfloat16), pool, pool, 1,
        jax.ShapeDtypeStruct((slots, ring), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32))
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    bufs = [a.shape for a in call.params["grid_mapping"].scratch_avals][:2]
    assert bufs == [(2, hkv, pages, BS, d)] * 2


def test_a_ring_too_short_for_the_band_is_refused():
    pool = jnp.zeros((2, L, NB, BS, 128))
    with pytest.raises(ValueError, match="ring"):
        paged_decode_attention(jnp.zeros((1, 4, 128)), pool, pool, 0,
                               jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32),
                               window=WINDOW, interpret=True)


def test_mixed_engine_decodes_through_the_windowed_kernel(monkeypatch, fresh_programs):
    """`serve_decode` of a model with sliding and full layers, the kernel
    forced in (interpreted) for both kinds: the tokens and their logits are
    those of the tiled jnp path, within rounding."""
    cfg = ModelConfig(dtype="float32", **resolve_preset("debug-tiny-mellum2"))
    params = init_params(cfg, jax.random.key(2))
    rng = np.random.default_rng(5)
    requests = [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m)
                for n, m in ((37, 8), (6, 5), (21, 7))]
    scfg = ServeConfig(decode_slots=2, block_size=4, prefill_chunk=8,
                       max_model_len=64, decode_interval=2)

    def run():
        eng = ServeEngine(params, cfg, scfg)
        out = eng.run(requests)
        eng.close()
        assert (eng.pool.in_use, eng.wpool.in_use) == (0, 0)
        return out

    plain = run()
    taken = []
    monkeypatch.setattr(paged_cache, "decode_kernel_suits",
                        lambda q, k: taken.append(q.shape[1]) or q.shape[1] == 1)
    kernel = run()
    assert 1 in taken
    for a, b in zip(plain, kernel):
        assert a["tokens"] == b["tokens"]
        np.testing.assert_allclose(a["logits"], b["logits"], atol=2e-4)


# ---------------------------------------------------------------------------
# the latent cache's decode kernel (MLA absorbed)
# ---------------------------------------------------------------------------

LATENT_CASES = {
    "ragged": dict(lengths=[1, BS, BS + 1, FULL, 0], li=1),
    "last_layer_chunks_of_1_page": dict(lengths=[FULL, 5, BS + 1], li=L - 1, ppc=1),
    "chunks_of_4_pages": dict(lengths=[4 * BS, 4 * BS + 1, FULL, 7], li=2, ppc=4),
    "every_slot_idle": dict(lengths=[0, 0, 0], li=0),
    "bf16_pool": dict(lengths=[1, BS + 1, FULL, 0], li=L - 1, dtype=jnp.bfloat16, tol=3e-2),
}


@pytest.mark.parametrize("name", LATENT_CASES)
def test_latent_kernel_matches_the_tiled_walk(name):
    """`latent_decode_attention` (interpreted) on a pool poisoned wherever a
    live slot must not read, against `LatentPagedCache`'s tiled walk on the
    clean pool: the same P c Wuv for every slot, zeros for an idle one."""
    from picotron_tpu.ops import mla
    from picotron_tpu.ops.paged_attention import latent_decode_attention
    from picotron_tpu.serve.paged_cache import LatentPagedCache, latent_row_width

    case = LATENT_CASES[name]
    dt, tol = case.get("dtype", jnp.float32), case.get("tol", 2e-5)
    cfg = ModelConfig(dtype="float32", **resolve_preset("debug-tiny-pangu-moe"))
    heads, dn, dr, rank, w = 4, 16, 8, 32, latent_row_width(cfg)
    rng = np.random.default_rng(len(name))
    lengths = np.asarray(case["lengths"], np.int32)
    tables = scattered_tables(rng, lengths)
    rows = rng.standard_normal((L, NB, BS, rank + dr)).astype(np.float32)
    clean = np.zeros((L, NB, BS, w), np.float32)
    clean[..., :rank + dr] = rows
    poisoned = np.full((L, NB, BS, w), np.nan, np.float32)
    for b, n in enumerate(lengths):
        for j in range(-(-n // BS)):
            poisoned[:, tables[b, j]] = clean[:, tables[b, j]]
    q_n = jnp.asarray(rng.standard_normal((len(lengths), 1, heads, dn)), dt)
    q_r = jnp.asarray(rng.standard_normal((len(lengths), 1, heads, dr)), dt)
    kv_b = jnp.asarray(rng.standard_normal((rank, heads * (dn + 16))) * 0.2, dt)
    q_pos = jnp.asarray(lengths - 1)[:, None]
    with jax.default_matmul_precision("highest"):
        want = LatentPagedCache(jnp.asarray(clean, dt), jnp.asarray(tables))._tiled(
            case["li"], q_n, q_r, q_pos, kv_b, cfg)
        q = jnp.concatenate([mla.absorb_queries(q_n[:, 0], kv_b, cfg), q_r[:, 0]], -1)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, w - q.shape[-1])))
        o_lat = latent_decode_attention(
            q, jnp.asarray(poisoned, dt), case["li"], jnp.asarray(tables),
            jnp.asarray(lengths), rank=rank, sm_scale=1 / 24 ** 0.5,
            pages_per_chunk=case.get("ppc"), interpret=True)
        got = mla.values_from_latent(o_lat, kv_b, cfg)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want[:, 0], np.float32), atol=tol)
    assert not np.asarray(got, np.float32)[lengths == 0].any()


def test_latent_step_decides_the_path(monkeypatch):
    from picotron_tpu.ops import paged_attention as pa

    pool = jnp.zeros((2, 8, 16, 640), jnp.bfloat16)
    assert not pa.latent_kernel_suits(1, pool, 512)  # the CPU compiles no kernel
    monkeypatch.setattr(pa, "compiled_kernels_available", lambda: True)
    assert pa.latent_kernel_suits(1, pool, 512)
    assert not pa.latent_kernel_suits(8, pool, 512)          # a prefill chunk
    assert not pa.latent_kernel_suits(1, pool[..., :576], 512)  # rows of 4.5 x 128 lanes
    assert not pa.latent_kernel_suits(1, jnp.zeros((2, 8, 4, 128)), 32)  # the tiny preset


def test_latent_engine_decodes_through_the_kernel(monkeypatch, fresh_programs):
    """`serve_decode` of a model with a latent cache, the kernel forced in
    (interpreted): tokens and logits are the tiled walk's, within rounding."""
    cfg = ModelConfig(dtype="float32", **resolve_preset("debug-tiny-pangu-moe"))
    params = init_params(cfg, jax.random.key(2))
    rng = np.random.default_rng(5)
    requests = [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m)
                for n, m in ((37, 8), (6, 5), (21, 7))]
    scfg = ServeConfig(decode_slots=2, block_size=4, prefill_chunk=8,
                       max_model_len=64, decode_interval=2)

    def run():
        eng = ServeEngine(params, cfg, scfg)
        out = eng.run(requests)
        eng.close()
        assert eng.pool.in_use == 0
        return out

    plain = run()
    taken = []
    monkeypatch.setattr(paged_cache, "latent_kernel_suits",
                        lambda s, pool, rank: taken.append(s) or s == 1)
    kernel = run()
    assert 1 in taken
    for a, b in zip(plain, kernel):
        assert a["tokens"] == b["tokens"]
        np.testing.assert_allclose(a["logits"], b["logits"], atol=2e-4)


# ---------------------------------------------------------------------------
# the decode step's K/V write: one kernel over both pools in place
# ---------------------------------------------------------------------------

EVA = SimpleNamespace(window_size=64, chunk_size=4)  # what `EvaPagedCache.write` reads of a config

KV_WRITE_CASES = {
    # positions a row (one a slot; -1: an idle slot); `held`: the blocks of that
    # many positions are mapped in a slot's table, the rest of its row unmapped
    "bf16_block_16": dict(bs=16, hkv=2, pos=[3, 16, 40, 95]),
    "bf16_block_32": dict(bs=32, hkv=2, pos=[3, 16, 33, 100]),   # either half of a block
    "f32_block_8": dict(bs=8, hkv=2, pos=[3, 8, 20, 47], dtype=jnp.float32),
    "one_kv_head": dict(bs=16, hkv=1, pos=[0, 17, 34, 51]),
    "two_kv_heads_last_layer": dict(bs=16, hkv=2, pos=[5, 21, 37, 53], li=L - 1),
    "a_kv_head_a_query_head_32": dict(bs=16, hkv=32, pos=[9, 30]),
    "positions_below_zero": dict(bs=16, hkv=2, pos=[-1, 18, -1, 4]),
    "unmapped_entries": dict(bs=16, hkv=2, pos=[40, 18, 95, 50], held=[33, 16, 96, 48]),
    "beyond_the_table": dict(bs=16, hkv=2, pos=[96, 4, 1000], held=[96, 96, 96]),
    "ring_table": dict(bs=16, hkv=2, pos=[3, 96, 200, 1001], ring=True),
    "every_row_dropped": dict(bs=16, hkv=2, pos=[-1, -1, 40], held=[96, 96, 16]),
    "a_blocks_first_and_last_offset": dict(bs=16, hkv=2, pos=[0, 15, 16, 31]),
    # EvaByte's decode write: the window rows, then the summary rows through the
    # same call; 35 ends chunk 8 (the summary write moves one row), no position
    # of the other step ends a chunk (it moves none)
    "eva_step_that_closes_a_chunk": dict(bs=16, hkv=2, pos=[5, 35, 70, -1], eva=True),
    "eva_step_that_closes_none": dict(bs=16, hkv=2, pos=[5, 34, 70, 0], eva=True),
}


def bits(x):
    return np.asarray(x).view(np.uint8)


@pytest.mark.parametrize("name", KV_WRITE_CASES)
def test_kv_write_kernel_leaves_what_the_scatter_leaves(name, monkeypatch):
    """`PagedKVCache.write` of a decode step through `paged_kv_write`
    (interpreted) leaves both pools EQUAL, bit for bit, to what its scatter
    leaves: the same rows in the same places, every other row of a moved tile
    as it was (NaN among them), the rows the scatter drops dropped."""
    c = KV_WRITE_CASES[name]
    bs, hkv, dtype = c["bs"], c["hkv"], c.get("dtype", jnp.bfloat16)
    pos = np.asarray(c["pos"], np.int32)
    rng = np.random.default_rng(sorted(KV_WRITE_CASES).index(name))
    held = c.get("held", [MB * bs] * len(pos))
    if c.get("eva"):
        width = paged_cache.eva_table_width(EVA, MB * bs, bs)
        held = [width * bs] * len(pos)
    else:
        width = MB
    # every slot's blocks its own, drawn from all over the pool without order
    nb = len(pos) * width + 3
    free = list(rng.permutation(nb))
    tables = np.full((len(pos), width), nb, np.int32)
    for b, n in enumerate(held):
        for j in range(-(-n // bs)):
            tables[b, j] = free.pop()
    shape = (hkv, L, nb, bs, 128)
    k = rng.standard_normal(shape).astype(np.float32)
    k[:, :, ::3, 1::4] = np.nan   # rows a moved tile carries back as they were
    k, v = jnp.asarray(k, dtype), jnp.asarray(rng.standard_normal(shape), dtype)
    k_new = jnp.asarray(rng.standard_normal((len(pos), 1, hkv, 128)), dtype)
    v_new = jnp.asarray(rng.standard_normal((len(pos), 1, hkv, 128)), dtype)
    li, q_pos = jnp.int32(c.get("li", 1)), jnp.asarray(pos)[:, None]

    if c.get("eva"):
        cache = paged_cache.EvaPagedCache(k, v, jnp.asarray(tables))
        mu = jnp.asarray(rng.standard_normal((hkv, 128)), dtype)
        phi = jnp.asarray(rng.standard_normal((hkv, 128)), dtype)

        def write(ch):
            return ch.write(li, k_new, v_new, q_pos, mu, phi, EVA)
    else:
        cache = PagedKVCache(k, v, jnp.asarray(tables))

        def write(ch):
            return ch.write(li, k_new, v_new, q_pos, ring=c.get("ring", False))

    want = jax.jit(write)(cache)
    calls = []

    def kernel(k_pool, v_pool, li, k_rows, v_rows, phys, off):
        calls.append(phys)
        return paged_kv_write(k_pool, v_pool, li, k_rows, v_rows, phys, off)

    monkeypatch.setattr(paged_cache, "kv_write_suits", lambda new, pool: True)
    monkeypatch.setattr(paged_cache, "paged_kv_write", kernel)
    got = jax.jit(lambda ch: write(ch))(cache)  # a function jit has not traced
    assert len(calls) == (2 if c.get("eva") else 1)   # K and V in ONE call
    assert type(got) is type(cache)
    assert np.array_equal(bits(got.k), bits(want.k))
    assert np.array_equal(bits(got.v), bits(want.v))
    # the case writes what it says: a row a live position the table maps, one
    # more where a position ends a chunk, in layer li alone
    live = sum(0 <= p and (c.get("ring") or p < -(-h // bs) * bs)
               for p, h in zip(pos, held))
    ends = sum(p >= 0 and (p + 1) % EVA.chunk_size == 0 for p in pos) if c.get("eva") else 0
    changed = (bits(got.v) != bits(v)).reshape(hkv, L, nb, bs, -1).any(axis=(0, -1))
    assert changed.sum() == live + ends and changed[int(li)].sum() == live + ends
    if name == "every_row_dropped":
        assert np.array_equal(bits(got.k), bits(k))


def test_the_step_decides_how_it_writes(monkeypatch):
    """`kv_write_suits`: the decode kernel's predicate on the new rows and the
    pool, and every row's tile of both pools in VMEM at once; no option."""
    from picotron_tpu.ops import paged_attention as pa
    pool = jnp.zeros((2, L, NB, 16, 128), jnp.bfloat16)
    new = jnp.zeros((3, 1, 2, 128), jnp.bfloat16)
    assert not pa.kv_write_suits(new, pool)  # the CPU compiles no kernel
    monkeypatch.setattr(pa, "compiled_kernels_available", lambda: True)
    assert pa.kv_write_suits(new, pool)
    assert not pa.kv_write_suits(jnp.zeros((3, 5, 2, 128)), pool)        # a chunk
    assert not pa.kv_write_suits(new[..., :64], pool[..., :64])          # head 64
    assert not pa.kv_write_suits(new, pool[:, :, :, :8])       # half a bf16 tile
    assert pa.kv_write_suits(new, pool[:, :, :, :8].astype(jnp.float32))
    rows = pa.KV_WRITE_VMEM_BYTES // (2 * 2 * 16 * 128 * 2)    # tiles that fit
    assert pa.kv_write_suits(jnp.zeros((rows, 1, 2, 128)), pool)
    assert not pa.kv_write_suits(jnp.zeros((rows + 1, 1, 2, 128)), pool)


def test_no_two_slots_hold_a_block(tiny):
    """The premise of `paged_kv_write` (and of the scatter): no two rows of a
    decode step write into one block, because a slot's blocks are its own from
    admission to its last token. Held on the host's tables at every step of a
    run with more requests than slots."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    eng = ServeEngine(params, cfg, ServeConfig(
        decode_slots=3, block_size=4, num_blocks=24, prefill_chunk=4,
        max_model_len=32, decode_interval=2))
    for n, m in ((5, 6), (9, 3), (3, 8), (7, 5), (11, 4)):
        eng.submit(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m)
    steps = 0
    while eng.sched.has_work():
        eng.step()
        steps += 1
        (table,), ((_, unmapped),) = eng._tables, eng.cache.table_specs
        mapped = table[table != unmapped]
        assert len(set(mapped.tolist())) == mapped.size, table
    eng.close()
    assert steps > 5 and eng.pool.in_use == 0
