"""Ring attention equivalence: the cp-sharded ring must match dense causal
attention on the full sequence, forward and backward (the reference has no
such test — its ring is only exercised implicitly; SURVEY.md §4 calls for
parity tests per parallel layer)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from picotron_tpu import compat
from picotron_tpu.mesh import MeshEnv
from picotron_tpu.ops.attention import sdpa_attention
from picotron_tpu.ops.ring_attention import ring_attention


def qkv(key=0, b=2, s=32, hq=4, hkv=2, d=8, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("cp,hq,hkv", [(2, 4, 4), (4, 4, 2), (8, 8, 1)])
def test_ring_matches_dense_forward(cp, hq, hkv):
    menv = MeshEnv.create(cp=cp)
    q, k, v = qkv(hq=hq, hkv=hkv)

    ring = jax.jit(compat.shard_map(
        ring_attention, mesh=menv.mesh,
        in_specs=(P(None, "cp"), P(None, "cp"), P(None, "cp")),
        out_specs=P(None, "cp"),
    ))
    got = ring(q, k, v)
    want = sdpa_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_matches_dense_grads():
    menv = MeshEnv.create(cp=4)
    q, k, v = qkv()

    def ring_loss(q, k, v):
        out = ring_attention(q, k, v)
        return jax.lax.psum(jnp.sum(out ** 2), "cp")

    g_ring = jax.jit(compat.shard_map(
        jax.grad(ring_loss, argnums=(0, 1, 2)), mesh=menv.mesh,
        in_specs=(P(None, "cp"),) * 3,
        out_specs=(P(None, "cp"),) * 3,
    ))(q, k, v)

    def dense_loss(q, k, v):
        return jnp.sum(sdpa_attention(q, k, v, causal=True) ** 2)

    g_ref = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-5, atol=5e-5, err_msg=f"d{name}")


def test_ring_zigzag_layout_matches_dense():
    """Zigzag layout: each cp shard holds one early + one late chunk; the
    position vector travels the ring with its K/V block, so the same ring
    code stays correct (the balanced layout the reference left as a TODO,
    ref: tests/test_dataloader.py:136)."""
    cp, s = 4, 32
    menv = MeshEnv.create(cp=cp)
    q, k, v = qkv(s=s)
    half = s // (2 * cp)
    perm = np.concatenate([
        np.concatenate([np.arange(r * half, (r + 1) * half),
                        np.arange((2 * cp - 1 - r) * half,
                                  (2 * cp - r) * half)])
        for r in range(cp)
    ])
    pos_global = jnp.asarray(perm)

    def ring_zz(q, k, v, pos):
        return ring_attention(q, k, v, q_positions=pos)

    got = jax.jit(compat.shard_map(
        ring_zz, mesh=menv.mesh,
        in_specs=(P(None, "cp"), P(None, "cp"), P(None, "cp"), P("cp")),
        out_specs=P(None, "cp"),
    ))(q[:, perm], k[:, perm], v[:, perm], pos_global)
    want = sdpa_attention(q, k, v, causal=True)
    # got is in zigzag order; un-permute to compare
    inv = np.argsort(perm)
    np.testing.assert_allclose(np.asarray(got)[:, inv], np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_bf16_close_to_dense():
    menv = MeshEnv.create(cp=4)
    q, k, v = qkv(dtype=jnp.bfloat16)
    ring = jax.jit(compat.shard_map(
        ring_attention, mesh=menv.mesh,
        in_specs=(P(None, "cp"),) * 3, out_specs=P(None, "cp"),
    ))
    got = ring(q, k, v).astype(jnp.float32)
    want = sdpa_attention(q, k, v, causal=True).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-2, atol=5e-2)
