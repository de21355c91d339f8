"""On-hardware kernel regression tests — `pytest -m tpu` on a machine with a
TPU (tests/conftest.py ends the session non-zero when there is none; a run
that does not ask for the marker skips these).

The regular suite exercises the Pallas kernels in interpreter mode on the
simulated CPU mesh; these run the COMPILED kernels on the TPU and gate
them against the jnp reference (the pytest version of tools/flash_smoke.py).
Tolerances are bf16-level: blockwise-vs-fused softmax reassociation puts
maxdiffs in the 0.01-0.25 band on real data.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.key(0), 3)
    b, s, hq, hkv, d = 2, 2048, 16, 4, 64
    return (jax.random.normal(ks[0], (b, s, hq, d), jnp.bfloat16),
            jax.random.normal(ks[1], (b, s, hkv, d), jnp.bfloat16),
            jax.random.normal(ks[2], (b, s, hkv, d), jnp.bfloat16))


def _maxdiff(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


def test_flash_forward_matches_sdpa_on_chip(qkv):
    from picotron_tpu.ops.attention import sdpa_attention
    from picotron_tpu.ops.flash_attention import flash_attention

    q, k, v = qkv
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))(q, k, v)
    want = jax.jit(lambda q, k, v: sdpa_attention(q, k, v, causal=True))(
        q, k, v)
    assert _maxdiff(got, want) < 0.05


def test_flash_backward_matches_sdpa_on_chip(qkv):
    from picotron_tpu.ops.attention import sdpa_attention
    from picotron_tpu.ops.flash_attention import flash_attention

    q, k, v = qkv

    def floss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32) ** 2)

    def rloss(q, k, v):
        return jnp.sum(
            sdpa_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    got = jax.jit(jax.grad(floss, (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(rloss, (0, 1, 2)))(q, k, v)
    for x, y, n in zip(got, want, "qkv"):
        # grads of a sum-of-squares over 2048 tokens: bf16 accumulation
        # reassociation puts the band well above fwd's
        assert _maxdiff(x, y) < 0.5, f"d{n}"


def test_flash_fused_rope_matches_unfused_on_chip(qkv):
    from picotron_tpu.ops.flash_attention import flash_attention
    from picotron_tpu.ops.rope import apply_rope, rope_tables

    q, k, v = qkv
    s, d = q.shape[1], q.shape[3]
    cos, sin = rope_tables(s, d)

    def fused(q, k, v):
        return flash_attention(q, k, v, causal=True, rope=(cos, sin),
                               interpret=False).astype(jnp.float32)

    def unfused(q, k, v):
        return flash_attention(apply_rope(q, cos, sin),
                               apply_rope(k, cos, sin), v, causal=True,
                               interpret=False).astype(jnp.float32)

    got = jax.jit(fused)(q, k, v)
    want = jax.jit(unfused)(q, k, v)
    assert _maxdiff(got, want) < 0.05
    gf = jax.jit(jax.grad(lambda *a: jnp.sum(fused(*a) ** 2), (0, 1, 2)))
    gu = jax.jit(jax.grad(lambda *a: jnp.sum(unfused(*a) ** 2), (0, 1, 2)))
    for x, y, n in zip(gf(q, k, v), gu(q, k, v), "qkv"):
        assert _maxdiff(x, y) < 0.5, f"d{n}"


def test_train_step_runs_on_chip():
    """One real bf16 train step of a depth-reduced SmolLM on the chip —
    the bench path's compile+execute sanity, minus the timing."""
    from picotron_tpu.config import (
        Config, DistributedConfig, ModelConfig, TrainingConfig, resolve_preset,
    )
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.parallel.api import init_sharded_state, make_train_step

    preset = resolve_preset("SmolLM-360M")
    preset["num_hidden_layers"] = 4
    cfg = Config(
        distributed=DistributedConfig(dp_size=1),
        model=ModelConfig(name="SmolLM-360M", **preset),
        training=TrainingConfig(seq_length=512, micro_batch_size=1,
                                gradient_accumulation_steps=1, remat=True),
    )
    cfg.validate()
    menv = MeshEnv.create(dp=1, devices=jax.devices()[:1])
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    step = make_train_step(cfg, menv)
    toks = jax.random.randint(jax.random.key(1), (1, 1, 513), 0,
                              cfg.model.vocab_size)
    sh = menv.batch_sharding()
    batch = (jax.device_put(toks[..., :-1], sh),
             jax.device_put(toks[..., 1:], sh))
    state, metrics = step(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and 2.0 < loss < 20.0, loss


def test_optimizer_offload_pinned_host_on_chip():
    """optimizer_offload with REAL memory placement: the fp32 master + Adam
    moments must live in pinned_host, the compute copy in device memory,
    and a step must run and keep the kinds (the CPU-mesh offload tests run
    the same code path placement-free — offload_memory_kind is None there)."""
    from picotron_tpu.config import (
        Config, DistributedConfig, ModelConfig, TrainingConfig, resolve_preset,
    )
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.parallel.api import init_sharded_state, make_train_step

    preset = resolve_preset("SmolLM-360M")
    preset["num_hidden_layers"] = 4
    cfg = Config(
        distributed=DistributedConfig(dp_size=1),
        model=ModelConfig(name="SmolLM-360M", **preset),
        training=TrainingConfig(seq_length=512, micro_batch_size=1,
                                gradient_accumulation_steps=2, remat=True,
                                adam_moments_dtype="bfloat16",
                                optimizer_offload=True),
    )
    cfg.validate()
    menv = MeshEnv.create(dp=1, devices=jax.devices()[:1])
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    assert jax.tree.leaves(state.opt_state.master)[0].sharding.memory_kind \
        == "pinned_host"
    assert jax.tree.leaves(state.opt_state.mu)[0].sharding.memory_kind \
        == "pinned_host"
    assert jax.tree.leaves(state.params)[0].sharding.memory_kind == "device"
    assert jax.tree.leaves(state.params)[0].dtype == jnp.bfloat16

    step = make_train_step(cfg, menv)
    toks = jax.random.randint(jax.random.key(1), (2, 1, 513), 0,
                              cfg.model.vocab_size)
    sh = menv.batch_sharding()
    batch = (jax.device_put(toks[..., :-1], sh),
             jax.device_put(toks[..., 1:], sh))
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]  # repeated batch must memorize
    # the state kinds survive the donated round trip
    assert jax.tree.leaves(state.opt_state.master)[0].sharding.memory_kind \
        == "pinned_host"
    assert int(state.opt_state.count) == 3
