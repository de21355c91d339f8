"""`*_bwd_from_saved` twins: the fused grad engine's attention backwards
(ops/attention.py, ops/flash_attention.py, ops/ring_attention.py,
ops/ulysses.py) pinned against AD of the dense forward.

These are FORWARD-only programs (ppermutes/all_to_alls in the primal
direction; no differentiation through collectives). The load-bearing
property: `sdpa_attention_bwd_from_saved` normalizes probabilities by the
PASSED lse, so calling it per visiting block with the GLOBAL (out, lse)
yields that block's additive contribution to the global grads — which is
what the ring backward sums and the AD reference must equal exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from picotron_tpu import compat
from picotron_tpu.mesh import MeshEnv
from picotron_tpu.ops.attention import (
    sdpa_attention, sdpa_attention_bwd_from_saved,
)
from picotron_tpu.ops.flash_attention import flash_attention_bwd_from_saved
from picotron_tpu.ops.ring_attention import (
    ring_attention, ring_attention_bwd_from_saved,
)
from picotron_tpu.ops.ulysses import (
    ulysses_attention, ulysses_attention_bwd_from_saved,
)

TOL = dict(rtol=2e-5, atol=2e-5)


def qkvd(key=0, b=2, s=32, hq=4, hkv=2, d=8, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(key), 4)
    return (jax.random.normal(ks[0], (b, s, hq, d), dtype),
            jax.random.normal(ks[1], (b, s, hkv, d), dtype),
            jax.random.normal(ks[2], (b, s, hkv, d), dtype),
            jax.random.normal(ks[3], (b, s, hq, d), dtype))


def dense_ref(q, k, v, do):
    _, vjp = jax.vjp(
        lambda q_, k_, v_: sdpa_attention(q_, k_, v_, causal=True),
        q, k, v)
    return vjp(do)


def assert_grads(got, want, tag=""):
    for g, w, n in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   err_msg=f"{tag}{n}", **TOL)


def test_sdpa_bwd_from_saved_matches_ad():
    q, k, v, do = qkvd()
    out, lse = sdpa_attention(q, k, v, causal=True, return_lse=True)
    got = sdpa_attention_bwd_from_saved(q, k, v, out, lse, do, causal=True)
    assert_grads(got, dense_ref(q, k, v, do))


def test_flash_bwd_from_saved_fallback_with_rope():
    # the non-TPU dispatch of flash_attention_bwd_from_saved: unrotated
    # q/k in, grads mapped back through the rotation's transpose
    from picotron_tpu.ops.flash_attention import flash_attention
    from picotron_tpu.ops.rope import rope_tables

    q, k, v, do = qkvd()
    cos, sin = rope_tables(64, q.shape[-1], 10000.0)
    out, lse = flash_attention(q, k, v, causal=True, rope=(cos, sin),
                               return_lse=True)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True,
                                           rope=(cos, sin)), q, k, v)
    got = flash_attention_bwd_from_saved(q, k, v, out, lse, do,
                                         causal=True, rope=(cos, sin))
    assert_grads(got, vjp(do), "rope-")


@pytest.mark.parametrize("cp,hq,hkv", [(4, 4, 2), (8, 8, 1)])
def test_ring_bwd_from_saved_matches_dense_grads(cp, hq, hkv):
    menv = MeshEnv.create(cp=cp)
    q, k, v, do = qkvd(hq=hq, hkv=hkv)

    def body(q, k, v, do):
        out, lse = ring_attention(q, k, v, return_lse=True)
        return ring_attention_bwd_from_saved(q, k, v, out, lse, do)

    got = jax.jit(compat.shard_map(
        body, mesh=menv.mesh, in_specs=(P(None, "cp"),) * 4,
        out_specs=(P(None, "cp"),) * 3))(q, k, v, do)
    assert_grads(got, dense_ref(q, k, v, do), f"ring{cp}-")


def test_ring_bwd_from_saved_zigzag_layout():
    cp, s = 4, 32
    menv = MeshEnv.create(cp=cp)
    q, k, v, do = qkvd(s=s)
    half = s // (2 * cp)
    perm = np.concatenate([
        np.concatenate([np.arange(r * half, (r + 1) * half),
                        np.arange((2 * cp - 1 - r) * half,
                                  (2 * cp - r) * half)])
        for r in range(cp)])

    def body(q, k, v, do, pos):
        out, lse = ring_attention(q, k, v, q_positions=pos,
                                  return_lse=True)
        return ring_attention_bwd_from_saved(q, k, v, out, lse, do,
                                             q_positions=pos)

    got = jax.jit(compat.shard_map(
        body, mesh=menv.mesh,
        in_specs=(P(None, "cp"),) * 4 + (P("cp"),),
        out_specs=(P(None, "cp"),) * 3))(
        q[:, perm], k[:, perm], v[:, perm], do[:, perm],
        jnp.asarray(perm))
    inv = np.argsort(perm)
    got = tuple(np.asarray(g)[:, inv] for g in got)
    assert_grads(got, dense_ref(q, k, v, do), "ring-zz-")


def test_ulysses_bwd_from_saved_matches_dense_grads():
    menv = MeshEnv.create(cp=2)
    q, k, v, do = qkvd()

    def body(q, k, v, do):
        out, lse = ulysses_attention(q, k, v, attn_fn=sdpa_attention,
                                     return_lse=True)
        return ulysses_attention_bwd_from_saved(q, k, v, out, lse, do)

    got = jax.jit(compat.shard_map(
        body, mesh=menv.mesh, in_specs=(P(None, "cp"),) * 4,
        out_specs=(P(None, "cp"),) * 3))(q, k, v, do)
    assert_grads(got, dense_ref(q, k, v, do), "uly-")


def test_ulysses_bwd_from_saved_zigzag_sorted():
    # zigzag layout + the static seq_sort the fused engine derives from
    # ulysses_static_layout: the bwd must re-apply the identical sort to
    # the inner domain (the saved lse is in the SORTED inner domain)
    cp, s = 2, 32
    menv = MeshEnv.create(cp=cp)
    q, k, v, do = qkvd(s=s)
    half = s // (2 * cp)
    perm = np.concatenate([
        np.concatenate([np.arange(r * half, (r + 1) * half),
                        np.arange((2 * cp - 1 - r) * half,
                                  (2 * cp - r) * half)])
        for r in range(cp)])
    ss = np.argsort(perm)

    def body(q, k, v, do, pos):
        kw = dict(q_positions=pos, seq_sort=ss, full_positions=perm,
                  positions_static=True)
        out, lse = ulysses_attention(q, k, v, attn_fn=sdpa_attention,
                                     return_lse=True, **kw)
        return ulysses_attention_bwd_from_saved(q, k, v, out, lse, do,
                                                **kw)

    got = jax.jit(compat.shard_map(
        body, mesh=menv.mesh,
        in_specs=(P(None, "cp"),) * 4 + (P("cp"),),
        out_specs=(P(None, "cp"),) * 3))(
        q[:, perm], k[:, perm], v[:, perm], do[:, perm],
        jnp.asarray(perm))
    inv = np.argsort(perm)
    got = tuple(np.asarray(g)[:, inv] for g in got)
    assert_grads(got, dense_ref(q, k, v, do), "uly-zz-")


def test_ring_forward_return_lse_matches_dense():
    # the saved statistic itself: the ring's merged lse == the dense lse
    menv = MeshEnv.create(cp=4)
    q, k, v, _ = qkvd()
    _, lse_ref = sdpa_attention(q, k, v, causal=True, return_lse=True)

    def body(q, k, v):
        return ring_attention(q, k, v, return_lse=True)

    out, lse = jax.jit(compat.shard_map(
        body, mesh=menv.mesh, in_specs=(P(None, "cp"),) * 3,
        out_specs=(P(None, "cp"), P(None, None, "cp"))))(q, k, v)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref), **TOL)
