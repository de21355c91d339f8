"""`ops/gated_delta.py gated_delta_step_pooled`, the decode step's kernel over a
serving cache's state pool, through the Pallas interpreter on the CPU at the
kernel's own widths (d_k = d_v = 128) and a few heads: held to
`gated_delta_step`, the one written form of the rule, on the rows that hold a
token, and to the pool's own bits everywhere else. The compiled kernel inside
the decode program is held by tests/test_chip_compile.py, a serving engine
that runs it by tests/test_qwen3_next.py."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.ops import gated_delta as gd
from picotron_tpu.ops.gated_delta import (
    gated_delta, gated_delta_kernel_suits, gated_delta_step,
    gated_delta_step_pooled, l2_normalise,
)
from picotron_tpu.serve.paged_cache import HybridPagedCache

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
