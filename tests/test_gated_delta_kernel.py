"""`ops/gated_delta.py gated_delta_step_pooled` and `gated_delta_chunk_pooled`,
the decode step's and the prefill chunk's kernels over a serving cache's state
pool, through the Pallas interpreter on the CPU at the kernels' own widths
(d_k = d_v = 128) and a few heads: held to `gated_delta_step`, the one written
form of the rule (a chunk: token by token, `gated_delta_scan`), on the rows
that hold a token, and to the pool's own bits everywhere else. The compiled
kernels inside the serve programs are held by tests/test_chip_compile.py, a
serving engine that runs them by tests/test_qwen3_next.py. Beside them the
per-channel rule's chunk kernel (`ops/kda.py kda_chunk_pooled`, Kimi-Linear's;
its engine: tests/test_kimi_linear.py) and the convolution before the rule at
one position a row."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.ops import gated_delta as gd
from picotron_tpu.ops.gated_delta import (
    CHUNK_SUB, causal_conv, gated_delta, gated_delta_chunk_pooled, gated_delta_chunk_suits,
    gated_delta_kernel_suits, gated_delta_scan, gated_delta_step,
    gated_delta_step_pooled, l2_normalise, per_value_head,
)
from picotron_tpu.ops.kda import kda_chunk_pooled, kda_chunk_suits, kda_chunked
from picotron_tpu.serve.paged_cache import HybridLatentPagedCache, HybridPagedCache

MIXERS, SLOTS, ROWS, D = 3, 5, 4, 128
EPS = float(np.finfo(np.float32).eps)


def inputs(heads: int, seed: int):
    """What a mixer hands the rule for one token a row: unit keys, scaled unit
    queries, a decay in (1/e, 1), a write strength in (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    return (l2_normalise(jax.random.normal(ks[0], (ROWS, heads, D))) * D ** -0.5,
            l2_normalise(jax.random.normal(ks[1], (ROWS, heads, D))),
            jax.random.normal(ks[2], (ROWS, heads, D)),
            -jax.random.uniform(ks[3], (ROWS, heads)),
            jax.random.uniform(ks[4], (ROWS, heads)))


def plain(x, pool, gi, rows, live, fresh):
    """The same step by `gated_delta_step` on the gathered rows -> (o, the
    worked rows' state after it {(slot): [H, d_k, d_v]})."""
    work = np.asarray(live) & (np.asarray(rows) < SLOTS)
    state = pool[gi, jnp.minimum(rows, SLOTS - 1)]
    state = jnp.where(fresh[:, None, None, None], 0.0, state)
    o, state = gated_delta_step(*x, state)
    return (np.where(work[:, None, None], np.asarray(o), 0.0),
            {int(rows[b]): np.asarray(state[b]) for b in range(ROWS) if work[b]})


T, F = True, False
CASES = {
    # name: (heads, heads a block, mixer, rows' slots, live, fresh, steps)
    "all_rows_live": (3, 8, 1, [0, 1, 2, 3], [T, T, T, T], [F, F, F, F], 1),
    "some_idle": (3, 8, 1, [0, 5, 2, 5], [T, F, T, F], [F, F, F, F], 1),
    "idle_rows_still_mapped": (3, 8, 1, [0, 1, 2, 3], [F, T, F, F], [F, F, F, F], 1),
    "none_live": (3, 8, 1, [0, 1, 2, 3], [F, F, F, F], [F, F, F, F], 1),
    "position_0_over_a_nonzero_row": (3, 8, 1, [4, 1, 5, 5], [T, T, F, F], [T, F, F, F], 1),
    "an_unmapped_row": (3, 8, 1, [2, 5, 7, 0], [T, T, T, T], [F, F, F, F], 1),
    "slots_out_of_order": (3, 8, 1, [4, 0, 3, 1], [T, T, T, T], [F, T, F, F], 1),
    "first_mixer": (3, 8, 0, [1, 5, 3, 5], [T, F, T, F], [F, F, F, F], 1),
    "last_mixer": (3, 8, MIXERS - 1, [1, 5, 3, 5], [T, F, T, F], [F, F, F, F], 1),
    "two_blocks_of_heads": (4, 2, 1, [3, 5, 0, 2], [T, F, T, T], [F, F, T, F], 1),
    "four_steps_chained": (2, 8, 1, [2, 5, 4, 0], [T, F, T, T], [T, F, F, F], 4),
    "four_steps_two_blocks": (4, 2, 2, [2, 5, 4, 0], [T, F, T, T], [F, F, F, F], 4),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_is_the_rule_on_the_live_rows_and_nothing_elsewhere(case, monkeypatch):
    """o and the worked rows' state against `gated_delta_step` from a non-zero
    pool; every other bit of the pool (idle, padding and unmapped rows, the
    other mixers) as it was.

    The state is held to float32 ROUNDING, not to the bit: S' = exp(g) S and
    the outer product are the plain form's expressions in its order, but r =
    S'^T k is a sum over d_k that the kernel adds down the sublanes in its
    own order, so r, and through k (beta (v - r))^T the state, may differ by
    the rounding of a sum of 128 products (a few eps of the head's largest
    entry). Where a row starts at position 0 there is no sum to reorder (r
    is 0 exactly) and its state is bit-equal."""
    heads, block, gi, rows, live, fresh, steps = CASES[case]
    monkeypatch.setattr(gd, "STEP_HEAD_BLOCK", block)
    rows, live, fresh = jnp.asarray(rows), jnp.asarray(live), jnp.asarray(fresh)
    pool0 = jax.random.normal(jax.random.key(9), (MIXERS, SLOTS, heads, D, D))
    step = jax.jit(gated_delta_step_pooled)
    pool = want_pool = pool0
    for t in range(steps):
        x = inputs(heads, seed=t)
        started = fresh if t == 0 else jnp.zeros_like(fresh)
        o, pool = step(*x, pool, jnp.asarray(gi), rows, live, started)
        want_o, want = plain(x, want_pool, gi, rows, live, started)
        for slot, state in want.items():
            want_pool = want_pool.at[gi, slot].set(state)
        np.testing.assert_allclose(np.asarray(o), want_o, rtol=0, atol=8 * EPS)
        assert np.abs(want_o).max() > 0.01 or not want
    got, want_pool = np.asarray(pool), np.asarray(want_pool)
    worked = sorted(want)
    scale = np.abs(want_pool[gi, worked]).max() if worked else 1.0
    np.testing.assert_allclose(got[gi, worked], want_pool[gi, worked], rtol=0,
                               atol=4 * steps * EPS * scale)
    if steps == 1:
        for b in np.flatnonzero(np.asarray(fresh & live)):
            np.testing.assert_array_equal(got[gi, int(rows[b])], want_pool[gi, int(rows[b])])
    # the step moved the worked rows and nothing else, not by a bit
    moved = np.any(got != np.asarray(pool0), axis=(2, 3, 4))
    assert moved.tolist() == [[g == gi and s in worked for s in range(SLOTS)]
                              for g in range(MIXERS)]


def test_which_steps_take_the_kernel_and_what_the_others_do(monkeypatch):
    """`gated_delta_kernel_suits`: a decode step over whole blocks of 128-lane
    float32 heads on a backend that compiles kernels, nothing else; and where it says no,
    `HybridPagedCache.recur` is gather -> the plain rule -> scatter, bit for
    bit, and never calls the kernel."""
    fa = importlib.import_module("picotron_tpu.ops.flash_attention")
    wide = jnp.zeros((MIXERS, SLOTS, 8, D, D))
    monkeypatch.setattr(fa, "compiled_kernels_available", lambda: True)
    assert gated_delta_kernel_suits(1, wide)
    assert not gated_delta_kernel_suits(1, wide[:, :, :4])  # half a block of heads
    assert not gated_delta_kernel_suits(2, wide) and not gated_delta_kernel_suits(8, wide)
    assert not gated_delta_kernel_suits(1, jnp.zeros((MIXERS, SLOTS, 2, 64, D)))
    assert not gated_delta_kernel_suits(1, jnp.zeros((MIXERS, SLOTS, 2, D, 8)))
    assert not gated_delta_kernel_suits(1, wide.astype(jnp.bfloat16))
    monkeypatch.setattr(fa, "compiled_kernels_available", lambda: False)  # the CPU's own
    assert not gated_delta_kernel_suits(1, wide)

    def refuse(*a, **k):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr("picotron_tpu.serve.paged_cache.gated_delta_step_pooled", refuse)
    pool = jax.random.normal(jax.random.key(1), (MIXERS, SLOTS, 2, D, D))
    cache = HybridPagedCache(
        jnp.zeros((1, 1, 4, 4, 8)), jnp.zeros((1, 1, 4, 4, 8)), pool,
        jnp.ones((MIXERS, SLOTS, 6)), jnp.full((ROWS, 2), 4, jnp.int32),
        jnp.asarray([[3], [SLOTS], [0], [1]], jnp.int32))
    for s, pos in ((1, [[7], [-1], [0], [-1]]), (3, [[7, 8, -1], [-1, -1, -1], [0, 1, 2],
                                                     [-1, -1, -1]])):
        pos = jnp.asarray(pos)
        x = tuple(jnp.stack([a] * s, axis=1) for a in inputs(2, seed=s))
        o, after = cache.recur(1, *x, pos)
        want_o, state = gated_delta(*x, cache.state_of(1, pos))
        np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
        np.testing.assert_array_equal(np.asarray(after.state),
                                      np.asarray(cache.put_state(1, state, pos).state))
        moved = np.any(np.asarray(after.state != pool), axis=(2, 3, 4))
        assert moved.tolist() == [[g == 1 and slot in (0, 3) for slot in range(SLOTS)]
                                  for g in range(MIXERS)]
        assert after.tail is cache.tail and after.k is cache.k


# ---------------------------------------------------------------------------
# the prefill chunk's kernel
# ---------------------------------------------------------------------------


def chunk_inputs(key_heads: int, heads: int, s: int, real, alike: bool, seed: int = 0):
    """What a mixer hands the rule for a chunk of `s` positions a row, `real[b]`
    of them real (the others inert: g = 0, beta = 0). `alike`: the keys of a
    sub-chunk nearly one direction and beta near 1, where the 64 x 64 inverse
    as a series of powers overflows float32."""
    ks = jax.random.split(jax.random.key(seed), 6)
    k = jax.random.normal(ks[1], (ROWS, s, key_heads, D))
    if alike:
        one = jax.random.normal(ks[5], (ROWS, s // CHUNK_SUB, 1, key_heads, D))
        k = 0.02 * k + jnp.repeat(one, CHUNK_SUB, axis=2).reshape(k.shape)
    held = (jnp.arange(s)[None, :] < jnp.asarray(real)[:, None])[..., None]
    beta = jax.random.uniform(ks[4], (ROWS, s, heads), minval=0.97 if alike else 0.0)
    return (l2_normalise(jax.random.normal(ks[0], (ROWS, s, key_heads, D))) * D ** -0.5,
            l2_normalise(k), jax.random.normal(ks[2], (ROWS, s, heads, D)),
            jnp.where(held, -0.2 * jax.random.uniform(ks[3], (ROWS, s, heads)), 0.0),
            jnp.where(held, beta, 0.0))


CHUNK_CASES = {
    # name: (key heads, value heads, positions, mixer, rows' slots, real
    #        positions a row, fresh, the pool's scale, alike keys)
    "all_rows_live": (1, 2, 128, 1, [0, 1, 2, 3], [128] * 4, [F, F, F, F], 1.0, F),
    "pad_rows_in_a_rung": (1, 2, 128, 1, [2, 5, 5, 5], [128, 0, 0, 0], [F, F, F, F], 1.0, F),
    "pad_rows_still_mapped": (1, 2, 64, 1, [0, 1, 2, 3], [0, 64, 0, 0], [F, T, T, F], 1.0, F),
    "no_row_live": (1, 2, 64, 1, [0, 1, 2, 3], [0] * 4, [F, F, F, F], 1.0, F),
    "an_unmapped_row": (1, 2, 64, 1, [2, 5, 7, 0], [64] * 4, [F, F, F, F], 1.0, F),
    "position_0_over_a_nonzero_row": (1, 2, 128, 1, [4, 1, 5, 5], [128, 128, 0, 0],
                                      [T, F, F, F], 1.0, F),
    "last_sub_chunk_part_padding": (1, 2, 192, 1, [3, 0, 4, 5], [150, 192, 65, 0],
                                    [F, T, F, F], 1.0, F),
    "a_large_start_state": (1, 2, 128, 1, [4, 0, 3, 1], [128, 100, 128, 128],
                            [F, F, T, F], 10.0, F),
    "alike_keys_beta_near_1": (1, 2, 128, 1, [1, 5, 3, 5], [128, 0, 128, 0],
                               [F, F, T, F], 1.0, T),
    "first_mixer": (1, 2, 64, 0, [1, 5, 3, 5], [64, 0, 40, 0], [F, F, F, F], 1.0, F),
    "last_mixer": (1, 2, 64, MIXERS - 1, [1, 5, 3, 5], [64, 0, 40, 0], [F, F, F, F], 1.0, F),
    "two_key_heads_four_pairs": (2, 8, 128, 2, [3, 5, 0, 2], [128, 0, 77, 128],
                                 [F, F, T, F], 1.0, F),
}


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_the_chunk_kernel_is_the_rule_on_the_live_rows_and_nothing_elsewhere(case):
    """o and the worked rows' state against the rule token by token
    (`gated_delta_scan`) from a non-zero pool, and the chunked `jax.numpy`
    form no further from it; a row without a real position and an unmapped
    one get zeros for o; every other bit of the pool (pad and unmapped rows,
    the other mixers) as it was. Where the keys of a sub-chunk are alike the
    kernel's blocked inverse stays as close as the triangular solve does."""
    hk, heads, s, gi, rows, real, fresh, scale, alike = CHUNK_CASES[case]
    rows, fresh = jnp.asarray(rows), jnp.asarray(fresh)
    live = jnp.asarray(real) > 0
    work = np.asarray(live) & (np.asarray(rows) < SLOTS)
    x = chunk_inputs(hk, heads, s, real, alike)
    pool0 = scale * jax.random.normal(jax.random.key(9), (MIXERS, SLOTS, heads, D, D))
    o, pool = jax.jit(gated_delta_chunk_pooled)(*x, pool0, jnp.asarray(gi), rows, live, fresh)
    start = jnp.where(fresh[:, None, None, None], 0.0, pool0[gi, jnp.minimum(rows, SLOTS - 1)])
    q, k = (per_value_head(a, heads) for a in x[:2])
    with jax.default_matmul_precision("highest"):
        want_o, want = jax.jit(gated_delta_scan)(q, k, *x[2:], start)
        plain_o, plain = jax.jit(gated_delta)(*x, start)
    want_o = np.where(work[:, None, None, None], np.asarray(want_o), 0.0)
    o_scale, s_scale = np.abs(want_o).max() or 1.0, float(jnp.abs(want).max())
    err_o = np.abs(np.asarray(o) - want_o).max() / o_scale
    assert err_o <= 2e-5, err_o
    assert not np.asarray(o)[~work].any()
    got, pool0 = np.asarray(pool), np.asarray(pool0)
    for b in np.flatnonzero(work):
        err = np.abs(got[gi, int(rows[b])] - np.asarray(want[b])).max() / s_scale
        ref = np.abs(np.asarray(plain[b]) - np.asarray(want[b])).max() / s_scale
        assert err <= max(2e-6, 2 * ref), (b, err, ref)
    assert work.any() == (np.abs(want_o).max() > 0.01)
    # the chunk moved the worked rows and nothing else, not by a bit
    moved = np.any(got != pool0, axis=(2, 3, 4))
    worked = {int(rows[b]) for b in np.flatnonzero(work)}
    assert moved.tolist() == [[g == gi and slot in worked for slot in range(SLOTS)]
                              for g in range(MIXERS)]


def test_which_chunks_take_the_kernel_and_what_the_others_do(monkeypatch):
    """`gated_delta_chunk_suits`: whole sub-chunks of 64 positions over 128-lane
    float32 heads that come in pairs a key head, on a backend that compiles
    kernels, nothing else (never a decode step); and where it says no,
    `HybridPagedCache.recur` is gather -> the chunked form -> scatter and never
    calls the kernel."""
    fa = importlib.import_module("picotron_tpu.ops.flash_attention")
    wide = jnp.zeros((MIXERS, SLOTS, 8, D, D))
    monkeypatch.setattr(fa, "compiled_kernels_available", lambda: True)
    assert gated_delta_chunk_suits(64, 4, wide) and gated_delta_chunk_suits(256, 1, wide)
    assert not gated_delta_chunk_suits(1, 4, wide) and not gated_delta_chunk_suits(96, 4, wide)
    assert not gated_delta_chunk_suits(64, 8, wide)  # a value head a key head: no pairs
    assert not gated_delta_chunk_suits(64, 1, jnp.zeros((MIXERS, SLOTS, 2, 64, D)))
    assert not gated_delta_chunk_suits(64, 1, jnp.zeros((MIXERS, SLOTS, 2, D, 8)))
    assert not gated_delta_chunk_suits(64, 4, wide.astype(jnp.bfloat16))
    assert not gated_delta_chunk_suits(16384, 4, wide)  # a row's q, k, v, o outgrow VMEM
    monkeypatch.setattr(fa, "compiled_kernels_available", lambda: False)  # the CPU's own
    assert not gated_delta_chunk_suits(64, 4, wide)

    def refuse(*a, **k):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr("picotron_tpu.serve.paged_cache.gated_delta_chunk_pooled", refuse)
    pool = jax.random.normal(jax.random.key(1), (MIXERS, SLOTS, 2, D, D))
    cache = HybridPagedCache(
        jnp.zeros((1, 1, 4, 4, 8)), jnp.zeros((1, 1, 4, 4, 8)), pool,
        jnp.ones((MIXERS, SLOTS, 6)), jnp.full((ROWS, 2), 4, jnp.int32),
        jnp.asarray([[3], [SLOTS], [0], [1]], jnp.int32))
    real = [64, 0, 20, 0]
    pos = jnp.where(jnp.arange(64)[None, :] < jnp.asarray(real)[:, None],
                    jnp.asarray([[7], [0], [0], [0]]) + jnp.arange(64)[None, :], -1)
    x = chunk_inputs(1, 2, 64, real, False)
    o, after = cache.recur(1, *x, pos)
    want_o, state = gated_delta(*x, cache.state_of(1, pos))
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
    np.testing.assert_array_equal(np.asarray(after.state),
                                  np.asarray(cache.put_state(1, state, pos).state))


# ---------------------------------------------------------------------------
# the per-channel rule's prefill chunk (Kimi Delta Attention, ops/kda.py)
# ---------------------------------------------------------------------------


def seeded_decays(b, s, h, dk, seed):
    """g a channel as Kimi-Linear's seeded model draws it, on its
    fastest-decaying channels (tests/test_kimi_linear.py): A at the top of
    U(1, 16), the step's softplus around the top of [0.001, 0.1] and three
    sigmas of the projection's noise above it."""
    ks = jax.random.split(jax.random.key(seed), 2)
    a = jax.random.uniform(ks[0], (h, 1), jnp.float32, 12.0, 16.0)
    dt = jax.nn.softplus(jnp.log(jnp.expm1(0.1)) + jax.random.normal(ks[1], (b, s, h, dk)))
    return -a * dt


KDA_CHUNK_CASES = {
    # name: (heads, positions, mixer of 3, rows' slots of 6 (6: unmapped), real
    #        positions a row, fresh, the pool's scale)
    "padded_idle_and_fresh_rows": (2, 128, 1, [3, 1, 0, 4], [128, 100, 0, 77],
                                   [False, False, False, True], 1.0),
    "an_unmapped_row_and_pad_rows": (2, 64, 0, [2, 6, 5, 5], [64, 64, 0, 0],
                                     [False, False, False, False], 1.0),
    "no_row_live": (2, 64, 1, [0, 1, 2, 3], [0, 0, 0, 0], [False, True, False, False], 1.0),
    "position_0_over_a_nonzero_row": (2, 64, 1, [4, 1, 5, 5], [64, 9, 0, 0],
                                      [True, False, False, False], 1.0),
    "three_pairs_one_a_loop_step": (6, 64, 2, [1, 5, 3, 5], [64, 0, 40, 0],
                                    [False, False, True, False], 1.0),
    "two_pairs_side_by_side_three_sub_chunks": (4, 192, 2, [3, 0, 4, 5], [150, 192, 65, 0],
                                                [False, True, False, False], 1.0),
    "a_large_start_state": (2, 128, 0, [4, 0, 3, 1], [128, 100, 128, 1],
                            [False, False, True, False], 10.0),
}


@pytest.mark.parametrize("case", KDA_CHUNK_CASES)
def test_the_chunk_kernel_is_the_chunked_rule_on_the_live_rows_and_nothing_elsewhere(case):
    """`kda_chunk_pooled` in the Pallas interpreter at the kernel's own widths
    (d_k = d_v = 128), on the fastest-decaying channels of the seeded draw,
    from a non-zero pool: o and the worked rows' state against the rule token
    by token (`gated_delta_scan`), and no further from it than the chunked
    `jax.numpy` form; a row without a real position and an unmapped one get
    zeros for o; every other bit of the pool (pad, idle and unmapped rows,
    the other mixers) as it was."""
    heads, s, gi, rows, real, fresh, scale = KDA_CHUNK_CASES[case]
    b, d, mixers, slots = 4, 128, 3, 6
    rows, fresh, real = jnp.asarray(rows), jnp.asarray(fresh), jnp.asarray(real)
    live = real > 0
    work = np.asarray(live) & (np.asarray(rows) < slots)
    ks = jax.random.split(jax.random.key(s + heads), 5)
    q = l2_normalise(jax.random.normal(ks[0], (b, s, heads, d))) * d ** -0.5
    k = l2_normalise(jax.random.normal(ks[1], (b, s, heads, d)))
    v = jax.random.normal(ks[2], (b, s, heads, d))
    g = seeded_decays(b, s, heads, d, gi + 1)
    assert float(g.min()) < -4.0 and float(jnp.cumsum(g, axis=1).min()) < -88.0
    held = jnp.arange(s)[None, :] < real[:, None]
    g = jnp.where(held[..., None, None], g, 0.0)
    beta = jnp.where(held[..., None], jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, heads))), 0.0)
    pool0 = scale * jax.random.normal(ks[4], (mixers, slots, heads, d, d))
    o, pool = jax.jit(kda_chunk_pooled)(q, k, v, g, beta, pool0, jnp.asarray(gi), rows, live,
                                        fresh)
    start = jnp.where(fresh[:, None, None, None], 0.0, pool0[gi, jnp.minimum(rows, slots - 1)])
    want_o, want = jax.jit(gated_delta_scan)(q, k, v, g, beta, start)
    plain_o, plain = jax.jit(kda_chunked)(q, k, v, g, beta, start)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(pool)).all()
    want_o = np.where(work[:, None, None, None], np.asarray(want_o), 0.0)
    o_scale, s_scale = np.abs(want_o).max() or 1.0, float(jnp.abs(want).max())
    err_o = np.abs(np.asarray(o) - want_o).max() / o_scale
    ref_o = np.abs(np.where(work[:, None, None, None], np.asarray(plain_o), 0.0)
                   - want_o).max() / o_scale
    assert err_o <= max(2e-6, 2 * ref_o), (err_o, ref_o)
    assert not np.asarray(o)[~work].any()
    got, pool0 = np.asarray(pool), np.asarray(pool0)
    for row in np.flatnonzero(work):
        err = np.abs(got[gi, int(rows[row])] - np.asarray(want[row])).max() / s_scale
        ref = np.abs(np.asarray(plain[row]) - np.asarray(want[row])).max() / s_scale
        assert err <= max(2e-6, 2 * ref), (row, err, ref)
    assert work.any() == (np.abs(want_o).max() > 0.01)
    # the chunk moved the worked rows and nothing else, not by a bit
    moved = np.any(got != pool0, axis=(2, 3, 4))
    worked = {int(rows[row]) for row in np.flatnonzero(work)}
    assert moved.tolist() == [[m == gi and slot in worked for slot in range(slots)]
                              for m in range(mixers)]


KDA_POOL = (3, 6, 8, 128, 128)
KDA_CHUNK_SUITS = {
    # name: (positions, heads handed over, the pool's shape, its dtype, a
    #        backend that compiles kernels, the answer)
    "one_sub_chunk": (64, 8, KDA_POOL, jnp.float32, True, True),
    "the_cells_chunk": (256, 32, (9, 64, 32, 128, 128), jnp.float32, True, True),
    "a_decode_step": (1, 8, KDA_POOL, jnp.float32, True, False),
    "half_a_sub_chunk_over": (96, 8, KDA_POOL, jnp.float32, True, False),
    "an_odd_head": (64, 7, (3, 6, 7, 128, 128), jnp.float32, True, False),
    "keys_a_key_head": (64, 4, KDA_POOL, jnp.float32, True, False),
    "half_a_row_of_lanes": (64, 8, (3, 6, 8, 64, 128), jnp.float32, True, False),
    "values_of_8": (64, 8, (3, 6, 8, 128, 8), jnp.float32, True, False),
    "a_bfloat16_state": (64, 8, KDA_POOL, jnp.bfloat16, True, False),
    "a_row_that_outgrows_vmem": (2048, 32, (9, 64, 32, 128, 128), jnp.float32, True, False),
    "the_cpu": (64, 8, KDA_POOL, jnp.float32, False, False),
}


@pytest.mark.parametrize("case", KDA_CHUNK_SUITS)
def test_which_chunks_take_the_kda_kernel(monkeypatch, case):
    """`kda_chunk_suits`: whole sub-chunks of 64 positions over 128-lane
    float32 heads in pairs, a row's q, k, G, v and o inside VMEM, on a backend
    that compiles kernels; nothing else, and never a decode step."""
    s, heads, shape, dtype, compiles, suits = KDA_CHUNK_SUITS[case]
    fa = importlib.import_module("picotron_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "compiled_kernels_available", lambda: compiles)
    assert kda_chunk_suits(s, heads, jax.ShapeDtypeStruct(shape, dtype)) is suits


@pytest.mark.parametrize("suits", [False, True])
def test_the_cache_hands_a_chunk_to_the_kernel_that_suits_it(monkeypatch, suits):
    """`recur` with a decay a channel of the key: where `kda_chunk_suits` says
    no (every CPU run) the cache gathers, runs `kda_chunked` and scatters, and
    never calls the kernel; where it says yes the chunk goes through
    `kda_chunk_pooled` over the pool in place (here the Pallas interpreter) and
    comes out as the plain path's, the rows without a real position and the
    unmapped row untouched. The scalar-gated rule's kernel is never asked for
    such a chunk."""
    from picotron_tpu.serve import paged_cache
    b, s, h, d, mixers, slots = 4, 64, 2, 128, 2, 5
    calls = []
    sound = paged_cache.kda_chunk_pooled
    monkeypatch.setattr(paged_cache, "kda_chunk_suits", lambda *a: suits)
    monkeypatch.setattr(paged_cache, "kda_chunk_pooled",
                        lambda *a, **k: calls.append(a[3].shape) or sound(*a, **k))

    def refuse(*a, **k):
        raise AssertionError("the scalar-gated chunk kernel was asked")

    monkeypatch.setattr(paged_cache, "gated_delta_chunk_pooled", refuse)
    ks = jax.random.split(jax.random.key(4), 6)
    cache = HybridLatentPagedCache(
        jnp.zeros((1, 4, 4, 8)), jax.random.normal(ks[5], (mixers, slots, h, d, d)),
        jnp.ones((mixers, slots, 6)), jnp.full((b, 2), 4, jnp.int32),
        jnp.asarray([[3], [slots], [0], [1]], jnp.int32))
    real = jnp.asarray([64, 64, 20, 0])
    held = jnp.arange(s)[None, :] < real[:, None]
    pos = jnp.where(held, jnp.asarray([[7], [3], [0], [0]]) + jnp.arange(s)[None, :], -1)
    q = l2_normalise(jax.random.normal(ks[0], (b, s, h, d))) * d ** -0.5
    k = l2_normalise(jax.random.normal(ks[1], (b, s, h, d)))
    v = jax.random.normal(ks[2], (b, s, h, d))
    g = jnp.where(held[..., None, None], seeded_decays(b, s, h, d, 2), 0.0)
    beta = jnp.where(held[..., None], jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h))), 0.0)
    o, after = cache.recur(1, q, k, v, g, beta, pos)
    want_o, state = kda_chunked(q, k, v, g, beta, cache.state_of(1, pos))
    want = cache.put_state(1, state, pos).state
    assert calls == ([(b, s, h, d)] if suits else [])
    if suits:
        mapped = np.asarray([True, False, True, False])[:, None, None, None]
        np.testing.assert_allclose(o, np.where(mapped, want_o, 0.0), atol=2e-6)
        np.testing.assert_allclose(after.state, want, atol=2e-5)
        moved = np.any(np.asarray(after.state != cache.state), axis=(2, 3, 4))
        assert moved.tolist() == [[False] * slots, [True, False, False, True, False]]
    else:
        np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
        np.testing.assert_array_equal(np.asarray(after.state), np.asarray(want))
    assert after.tail is cache.tail and after.kv is cache.kv


# ---------------------------------------------------------------------------
# the convolution before the rule, at one position a row
# ---------------------------------------------------------------------------


def sliced_conv(x, tail, w, n_valid, bias=None):
    """`causal_conv` as it took the new tail before PR 61, whatever the
    segment's length: one `dynamic_slice` a row."""
    k, s = w.shape[-1], x.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    y = sum(full[:, j:j + s].astype(jnp.float32) * wf[:, j] for j in range(k))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    new_tail = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(f, n, k - 1, axis=0))(
        full, n_valid)
    return jax.nn.silu(y).astype(x.dtype), new_tail.astype(tail.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("n_valid", [[0, 0, 0, 0], [1, 1, 1, 1], [1, 0, 0, 1]],
                         ids=["no_token", "a_token_a_row", "some_idle"])
def test_one_position_a_row_takes_its_new_tail_by_one_select(n_valid, bias, dtype):
    """A decode step's convolution: the output and the tail after it are, bit
    for bit, what one `dynamic_slice` a row gave (the old tail for a row
    without a token, the tail moved up by the token for one with), with and
    without a bias, and the traced step holds no `dynamic_slice`; a longer
    segment keeps its slice a row."""
    rows, kernel, c = 4, 4, 24
    ks = jax.random.split(jax.random.key(sum(n_valid) + bias), 4)
    x = jax.random.normal(ks[0], (rows, 1, c)).astype(dtype)
    tail = jax.random.normal(ks[1], (rows, kernel - 1, c))
    w = jax.random.normal(ks[2], (c, kernel))
    b = jax.random.normal(ks[3], (c,)) if bias else None
    n = jnp.asarray(n_valid, jnp.int32)
    y, new = jax.jit(causal_conv)(x, tail, w, n, b)
    want_y, want = jax.jit(sliced_conv)(x, tail, w, n, b)
    assert y.dtype == x.dtype and new.dtype == tail.dtype and new.shape == tail.shape
    np.testing.assert_array_equal(np.asarray(y, np.float32), np.asarray(want_y, np.float32))
    np.testing.assert_array_equal(np.asarray(new), np.asarray(want))
    sliced = re.compile("dynamic_slice|gather")  # (a slice a row, under `vmap`: a gather)
    assert not sliced.search(str(jax.make_jaxpr(causal_conv)(x, tail, w, n, b)))
    # three positions a row: the slice stays, and so do its values
    x3 = jnp.concatenate([x, x + 1, x - 1], axis=1)
    n3 = jnp.asarray(n_valid, jnp.int32) * jnp.asarray([3, 2, 1, 3])
    y3, new3 = causal_conv(x3, tail, w, n3, b)
    want_y3, want3 = sliced_conv(x3, tail, w, n3, b)
    np.testing.assert_array_equal(np.asarray(y3, np.float32), np.asarray(want_y3, np.float32))
    np.testing.assert_array_equal(np.asarray(new3), np.asarray(want3))
    assert sliced.search(str(jax.make_jaxpr(causal_conv)(x3, tail, w, n3, b)))
