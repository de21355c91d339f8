"""The served experts' grouped kernel (ops/grouped_experts.py) and the
dispatch around it (ops/moe.py `_grouped_experts`, `moe_mlp_served`), on the
CPU through the Pallas interpreter, against a float32 `einsum` over every
expert. The compiled kernel at the Mellum2 cell's shapes is held by
tests/test_chip_compile.py; its times are chip runs (PERF.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.ops import grouped_experts, moe
from picotron_tpu.ops.grouped_experts import (
    ffn_tile, group_tiles, grouped_swiglu, max_tiles, row_tile,
)

L = 3  # layers in the stacks; a case says which one it addresses

# choice[n]: the k experts row n is routed to. live: rows that carry a token
# (None: all). visits: the (row tile, expert) pairs, counted by hand from the
# live rows' choices and the 16-row tile every case here gets (`row_tile`).
CASES = {
    # experts 0, 2-4, 6, 7 get no row: two groups of 12
    "empty_experts": dict(e=8, h=64, f=48, li=1, choice=[[1, 5]] * 12,
                          visits=2),
    "one_row_in_one_expert": dict(e=8, h=64, f=48, li=0, choice=[[6]] * 6,
                                  live=[True] + [False] * 5, visits=1),
    # 17 rows in expert 0 (2 tiles), none in 1, 23 in expert 2 (2 tiles)
    "groups_straddle_tiles": dict(e=4, h=64, f=48, li=2,
                                  choice=[[0]] * 17 + [[2]] * 23, visits=4),
    "all_rows_in_one_expert": dict(e=4, h=64, f=48, li=1, choice=[[3]] * 40,
                                   visits=3),
    # rows 10.. are idle: live rows give expert 0 ten rows, 1 seven, 3 three
    "idle_rows_after_the_last_group": dict(
        e=4, h=64, f=48, li=1,
        choice=[[0, 1]] * 7 + [[0, 3]] * 3 + [[2, 3]] * 14,
        live=[True] * 10 + [False] * 14, visits=3),
    "no_live_row": dict(e=4, h=64, f=48, li=0, choice=[[0, 1]] * 5,
                        live=[False] * 5, visits=0),
    # 2048 x 1024 and 2304 x 896, an eighth of each; eight experts a row
    "olmoe_widths_scaled": dict(e=16, h=256, f=128, li=2, k=8, rows=24,
                                visits=16),
    "mellum2_widths_scaled": dict(e=16, h=288, f=112, li=0, k=8, rows=32,
                                  live=[True] * 25 + [False] * 7, visits=16),
    "bf16": dict(e=8, h=128, f=256, li=1, choice=[[1, 5], [5, 2], [7, 1]] * 9,
                 dtype=jnp.bfloat16, tol=3e-2, visits=6),
    # the F dimension in two slices: the down projection accumulates
    "two_f_slices": dict(e=4, h=64, f=256, li=2, tf=128,
                         choice=[[0]] * 17 + [[2]] * 23, visits=4),
}


def build(c, seed=0):
    dt = c.get("dtype", jnp.float32)
    e, h, f = c["e"], c["h"], c["f"]
    ks = jax.random.split(jax.random.key(seed), 5)
    if "choice" in c:
        choice = np.asarray(c["choice"], np.int32)
    else:  # rows x k distinct experts, every expert the same number of rows
        assert (c["rows"] * c["k"]) % e == 0
        choice = (np.arange(c["rows"] * c["k"]) % e).reshape(c["rows"], c["k"])
    n, k = choice.shape
    live = np.asarray(c.get("live", [True] * n))
    x = jax.random.normal(ks[0], (n, h), jnp.float32).astype(dt)
    banks = [(jax.random.normal(ks[i], (L, e, *s), jnp.float32)
              / s[0] ** 0.5).astype(dt)
             for i, s in ((1, (h, f)), (2, (h, f)), (3, (f, h)))]
    # router logits that choose exactly `choice`, in its order
    logits = np.full((n, e), -9.0, np.float32)
    for j in range(k):
        logits[np.arange(n), choice[:, j]] = 3.0 - 0.25 * j
    return x, banks, jnp.asarray(logits), jnp.asarray(live), choice


def reference(x, banks, r, live, li):
    """float32, every expert, the gate as the weight."""
    wg, wu, wd = (np.asarray(w[li], np.float32) for w in banks)
    x = np.asarray(x, np.float32)
    e = wg.shape[0]
    dense = np.zeros((x.shape[0], e), np.float32)
    idx, gate = np.asarray(r.expert_idx), np.asarray(r.gate)
    for n in np.flatnonzero(np.asarray(live)):
        dense[n, idx[n]] = gate[n]
    g = np.einsum("nh,ehf->nef", x, wg)
    u = np.einsum("nh,ehf->nef", x, wu)
    y = np.einsum("nef,efh->neh", g / (1 + np.exp(-g)) * u, wd)
    return np.einsum("neh,ne->nh", y, dense)


@pytest.mark.parametrize("name", CASES)
def test_grouped_experts_match_every_expert_in_float32(name, monkeypatch):
    c = CASES[name]
    x, banks, logits, live, choice = build(c)
    n, k = choice.shape
    assert row_tile(n * k, c["e"]) == 16
    if "tf" in c:
        monkeypatch.setattr(grouped_experts, "ffn_tile", lambda *a: c["tf"])
    r = moe.route_topk(logits, k, live=live)
    assert np.array_equal(np.asarray(r.expert_idx)[np.asarray(live)],
                          choice[np.asarray(live)])
    # the other layers of the stacks are poisoned: addressing one of them,
    # or reading a bank through a copy of the whole stack, shows
    poisoned = [w.at[jnp.arange(L) != c["li"]].set(jnp.nan) for w in banks]
    out, visits = jax.jit(
        lambda *a: moe._grouped_experts(*a, jax.nn.silu, c["li"]))(
            x, r, live, *poisoned)
    want = reference(x, banks, r, live, c["li"])
    out = np.asarray(out, np.float32)
    assert np.abs(out - want).max() < c.get("tol", 2e-5) * max(
        np.abs(want).max(), 1.0), np.abs(out - want).max()
    # a row without a token: zeros, not what the buffer happened to hold
    assert not out[~np.asarray(live)].any()
    assert int(visits) == c["visits"]
    counts = np.bincount(choice[np.asarray(live)].reshape(-1),
                         minlength=c["e"])
    assert int(visits) == sum(-(-int(n_e) // 16) for n_e in counts)


def test_kernel_writes_the_visited_tiles_and_no_other():
    """`grouped_swiglu` alone on a buffer laid out by `group_tiles`: every
    tile that holds rows is its expert's gated MLP of the addressed layer,
    and a tile past the last visit is not written: it keeps what the output
    buffer held, which the interpreter fills with NaN and the chip with
    whatever was in memory (why `_grouped_experts` selects zeros for the
    rows without a token and multiplies nothing there)."""
    e, h, f, tm = 4, 64, 48, 16
    counts = jnp.asarray([17, 0, 5, 16], jnp.int32)
    n_tiles = max_tiles(64, e, tm)
    first_row, tile_expert, visits = group_tiles(counts, tm, n_tiles)
    assert n_tiles == 8 and int(visits) == 4
    assert np.array_equal(first_row, [0, 32, 32, 48])
    # tiles past the last visit repeat it
    assert np.array_equal(tile_expert, [0, 0, 2, 3, 3, 3, 3, 3])
    ks = jax.random.split(jax.random.key(1), 4)
    xs = jax.random.normal(ks[0], (n_tiles * tm, h), jnp.float32)
    wg, wu = (jax.random.normal(k, (L, e, h, f), jnp.float32) / 8 for k in ks[1:3])
    wd = jax.random.normal(ks[3], (L, e, f, h), jnp.float32) / 7
    ys = np.asarray(grouped_swiglu(xs, wg, wu, wd, tile_expert, visits,
                                   jnp.int32(2), tm=tm))
    for t in range(int(visits)):
        x, ex = np.asarray(xs[t * tm:(t + 1) * tm]), int(tile_expert[t])
        g, u = x @ np.asarray(wg[2, ex]), x @ np.asarray(wu[2, ex])
        want = (g / (1 + np.exp(-g)) * u) @ np.asarray(wd[2, ex])
        assert np.abs(ys[t * tm:(t + 1) * tm] - want).max() < 1e-4, t
    assert np.isnan(ys[int(visits) * tm:]).all()


@pytest.mark.parametrize("rows,experts,tile", [
    (256, 64, 16),      # a decode step: 32 slots x 8 over 64 experts
    (2048, 64, 32),     # one 256-token chunk
    (8192, 64, 128),    # four
    (32768, 64, 256), (65536, 64, 256),
    (8, 8, 16), (2 * 4096, 8, 256),   # generate: one token; Mixtral's 8 experts
])
def test_row_tile_follows_the_rows_an_expert_gets(rows, experts, tile):
    assert row_tile(rows, experts) == tile
    # the buffer holds every layout of `rows` over `experts` groups
    worst = max_tiles(rows, experts, tile)
    assert worst * tile >= rows and worst <= rows // tile + experts


@pytest.mark.parametrize("h,f,tf", [
    (2304, 896, 896), (2048, 1024, 1024), (4096, 14336, 512), (64, 48, 48)])
def test_ffn_tile_fits_the_weight_slices(h, f, tf):
    assert ffn_tile(h, f, 2) == tf and f % tf == 0


def test_banks_split_over_a_mesh_keep_the_compilers_grouped_matmul():
    """`moe_mlp_served` under `place_for_decode(tp=2)`'s sharding of the banks
    (F over `tp`): the compiler cannot partition a Pallas kernel, so that
    case runs `lax.ragged_dot` on the layer's slice and gives what the
    kernel gives on one device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    c = CASES["idle_rows_after_the_last_group"]
    x, banks, logits, live, choice = build(c)
    router = jax.random.normal(jax.random.key(7), (c["h"], c["e"]), jnp.float32)
    kw = dict(top_k=choice.shape[1], act=jax.nn.silu, norm_topk_prob=True,
              live=live[None], layer=c["li"])
    served = jax.jit(lambda x, *w: moe.moe_mlp_served(x[None], router, *w, **kw))
    one, counts = served(x, *banks)
    assert "ragged_dot" not in str(jax.make_jaxpr(served)(x, *banks))
    mesh = Mesh(jax.devices()[:2], ("tp",))
    split = [jax.device_put(w, NamedSharding(mesh, spec)) for w, spec in zip(
        banks, (P(None, None, None, "tp"),) * 2 + (P(None, None, "tp", None),))]
    assert "ragged_dot" in str(jax.make_jaxpr(served)(x, *split))
    two, counts2 = served(x, *split)
    assert np.abs(np.asarray(one) - np.asarray(two)).max() < 2e-5
    assert not np.asarray(two)[0][~np.asarray(live)].any()
    assert counts2[0] == counts2[1] == counts[0] == counts[1]
