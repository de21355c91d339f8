"""Fused-accumulation grad engine (parallel/fused_bwd.py) parity.

The fused engine re-derives the decoder backward by hand (manual layer
scan, in-scan dW accumulation, *_bwd_from_saved attention backwards) —
every test here pins it against the AD engine on the same config, so any
divergence in the re-implemented forward/backward math shows up as a
loss/grad mismatch.

Two tiers of pinning:

- `assert_grads_match` compares the raw fp32 gradient trees of ONE
  `_device_grads` call per engine (model dtype float32, no optimizer):
  deterministic to ~1e-6, and covers every eligibility axis — tp, SP, cp
  ring (contiguous + zigzag), Ulysses, MoE (+ep, +capacity drops).
- `assert_engines_match` runs the production arrangement (bf16 +
  offload): two full optimizer steps must give the same losses, and each
  engine's raw bf16 gradients are scored against a float32 reference on
  the same weights — the fused engine must be as close to it as the AD
  engine is (see `assert_engines_match` for why not engine-vs-engine).
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from picotron_tpu import compat
from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, TrainingConfig,
)
from tests.test_optimizer_offload import batch_for, run_steps


def engine_cfg(engine: str, model_kw=None, dist_kw=None, **tr) -> Config:
    tr.setdefault("seq_length", 64)
    tr.setdefault("micro_batch_size", 2)
    tr.setdefault("gradient_accumulation_steps", 2)
    tr.setdefault("optimizer_offload", True)
    tr.setdefault("remat", True)
    tr.setdefault("remat_policy", "dots_attn")
    tr.setdefault("learning_rate", 1e-2)
    mk = dict(num_attention_heads=8, num_key_value_heads=4,
              num_hidden_layers=3, hidden_size=64, intermediate_size=96,
              vocab_size=256, max_position_embeddings=64)
    mk.update(model_kw or {})
    return Config(
        distributed=DistributedConfig(**(dist_kw or {"dp_size": 2})),
        model=ModelConfig(**mk),
        training=TrainingConfig(grad_engine=engine, **tr),
    )


def assert_engines_match(mk=None, dk=None, **tr):
    """bf16 parity, judged against a float32 reference.

    The two engines are different XLA graphs, so in bf16 their gradients
    differ by reassociation: measured at this size on JAX 0.9.0/CPU,
    max|g_ad - g_fused| is 4e-3..7e-3 of each leaf's max|g|, while BOTH sit
    5e-3..1.3e-2 from the float32 gradient of the same weights (lm_head and
    final_norm are bit-identical). Comparing fp32 masters after Adam steps
    cannot carry a dtype-derived tolerance: Adam's g/sqrt(v) normalisation
    turns that noise into update differences of up to lr per step on
    small-gradient elements (7-10% of every layer leaf moved by more than
    rtol 3e-3 after two steps, up to the 2*lr = 0.02 bound). So the pin is
    the one reassociation cannot trip and a defect cannot pass: per leaf,
    the fused engine's error against the float32 reference is no worse
    than the AD engine's (x1.5 + 1e-3 of max|g| for noise), plus equal
    two-step losses."""
    ad = engine_cfg("ad", model_kw=mk, dist_kw=dk, **tr)
    fused = engine_cfg("fused", model_kw=mk, dist_kw=dk, **tr)
    np.testing.assert_allclose(run_steps(fused, steps=2)[0],
                               run_steps(ad, steps=2)[0], rtol=2e-4)

    g_ad, params = raw_grads_of(ad)
    g_f, _ = raw_grads_of(fused)
    ref = engine_cfg("ad", model_kw={**(mk or {}), "dtype": "float32"},
                     dist_kw=dk, **{**tr, "optimizer_offload": False})
    g_ref, _ = raw_grads_of(ref, params)
    flat_ref = jax.tree_util.tree_flatten_with_path(g_ref)[0]
    for (path, r), a, f in zip(flat_ref, jax.tree.leaves(g_ad),
                               jax.tree.leaves(g_f)):
        scale = np.abs(r).max() + 1e-12
        err_ad = np.abs(a - r).max() / scale
        err_f = np.abs(f - r).max() / scale
        assert err_f <= 1.5 * err_ad + 1e-3, (
            f"{jax.tree_util.keystr(path)}: fused engine is "
            f"{err_f:.2e} of max|g| from the float32 reference, AD "
            f"engine {err_ad:.2e}")


# ---------------------------------------------------------------------------
# raw fp32 gradient parity — one _device_grads call per engine
# ---------------------------------------------------------------------------


def fp32_cfg(engine, mk=None, dk=None, **tr):
    return engine_cfg(engine, model_kw={"dtype": "float32", **(mk or {})},
                      dist_kw=dk, optimizer_offload=False, **tr)


def device_grads_of(cfg, params=None):
    """(grads, loss, extras, params) from one jitted _device_grads call —
    the engines' actual output, before any optimizer touches it. `params`
    overrides the freshly initialised ones (cast to this config's dtype
    and placement): how a float32 reference sees another config's exact
    weights."""
    from picotron_tpu.parallel.api import _device_grads, init_sharded_state
    from picotron_tpu.parallel.sharding import batch_spec, param_specs

    batch, menv = batch_for(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    if params is not None:
        params = jax.tree.map(
            lambda new, own: jax.device_put(new.astype(own.dtype),
                                            own.sharding),
            params, state.params)
    else:
        params = state.params
    fn = jax.jit(compat.shard_map(
        partial(_device_grads, cfg=cfg), mesh=menv.mesh,
        in_specs=(param_specs(cfg), (batch_spec(), batch_spec())),
        out_specs=(param_specs(cfg), P(), P())))
    grads, loss, extras = fn(params, batch)
    return (jax.tree.map(np.asarray, grads), float(loss),
            {k: float(v) for k, v in extras.items()}, params)


def raw_grads_of(cfg, params=None):
    """Token-mean fp32 gradient tree of one call (the offload path hands
    its grads out undivided, the scale riding in extras), + the params."""
    grads, _, extras, params = device_grads_of(cfg, params)
    scale = extras.get("_grad_scale", 1.0)
    return jax.tree.map(lambda g: g.astype(np.float32) * scale,
                        grads), params


def assert_grads_match(mk=None, dk=None, **tr):
    g_ad, l_ad, e_ad, _ = device_grads_of(fp32_cfg("ad", mk, dk, **tr))
    g_f, l_f, e_f, _ = device_grads_of(fp32_cfg("fused", mk, dk, **tr))
    np.testing.assert_allclose(l_f, l_ad, rtol=2e-4)
    assert set(e_f) == set(e_ad)
    for k in e_ad:
        np.testing.assert_allclose(e_f[k], e_ad[k], rtol=1e-5, err_msg=k)
    flat_ad = jax.tree_util.tree_flatten_with_path(g_ad)[0]
    for (path, a), b in zip(flat_ad, jax.tree.leaves(g_f)):
        scale = np.abs(a).max() + 1e-12
        np.testing.assert_array_less(
            np.abs(a - b).max() / scale, 1e-4,
            err_msg=f"{jax.tree_util.keystr(path)} (rel-to-max)")


def test_parity_dense_dp():
    assert_engines_match()


@pytest.mark.parametrize("engine,dk", [
    ("ad", {"dp_size": 2}),
    ("fused", {"dp_size": 2}),
    ("ad", {"pp_size": 2, "pp_engine": "1f1b"}),
    ("ad", {"pp_size": 2, "pp_engine": "afab"}),
    ("ad", {"dp_size": 2, "pp_size": 2, "pp_engine": "1f1b"}),
    # the tp layouts the cells and the engines run: engine-vs-engine
    # parity is blind to a fault in f/g that both engines share
    ("ad", {"tp_size": 2}),
    ("fused", {"tp_size": 2}),
    ("ad", {"tp_size": 4}),
    ("ad", {"tp_size": 2, "sequence_parallel": True}),
    ("fused", {"tp_size": 2, "sequence_parallel": True}),
    ("fused", {"dp_size": 2, "tp_size": 2}),
    ("ad", {"tp_size": 2, "pp_size": 2, "pp_engine": "1f1b"}),
], ids=["dp2-ad", "dp2-fused", "pp2-1f1b", "pp2-afab", "dp2pp2-1f1b",
        "tp2-ad", "tp2-fused", "tp4-ad", "tp2sp-ad", "tp2sp-fused",
        "dp2tp2-fused", "tp2pp2-1f1b"])
def test_grads_equal_single_device(engine, dk):
    """The gradient a layout hands the optimizer IS the single-device
    gradient of the same global batch — not a multiple of it. Engine-vs-
    engine parity and Adam loss trajectories are both blind to a common
    scale factor: every grad was dp x (and pp-replicated leaves pp x) too
    large on JAX 0.9.0, AD's pvary-transpose psum followed by the explicit
    one (parallel/api._device_grads)."""
    mk = {"num_hidden_layers": 4}
    dp = dk.get("dp_size", 1)
    want, l_want, _, _ = device_grads_of(
        fp32_cfg("ad", mk, {"dp_size": 1}, micro_batch_size=2))
    got, l_got, _, _ = device_grads_of(
        fp32_cfg(engine, mk, dk, micro_batch_size=2 // dp))
    np.testing.assert_allclose(l_got, l_want, rtol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    for (path, a), b in zip(flat, jax.tree.leaves(got)):
        np.testing.assert_array_less(
            np.abs(a - b).max() / (np.abs(a).max() + 1e-12), 1e-4,
            err_msg=jax.tree_util.keystr(path))


def test_parity_tp_vocab_parallel():
    # tp=2 exercises the ctx.f/g hook transposes and the vocab-parallel CE
    # inside the segment VJPs
    assert_engines_match(dk={"dp_size": 2, "tp_size": 2})


def test_parity_qwen_bias_tied():
    # qkv bias leaves + tied embeddings (head grads flow into the
    # embedding leaf through head_weight's transpose)
    assert_engines_match(mk=dict(attention_bias=True,
                                 tie_word_embeddings=True))


def test_parity_sdpa_path():
    assert_engines_match(mk=dict(attn_impl="reference"))


def test_parity_without_offload():
    # the engine is independent of where the optimizer state lives
    assert_engines_match(optimizer_offload=False)


def test_auto_resolves_fused_only_when_supported():
    from picotron_tpu.parallel.fused_bwd import fused_bwd_supported

    assert fused_bwd_supported(engine_cfg("auto"))
    # the widened axes (this PR): SP, cp ring/ulysses, MoE (+ep)
    assert fused_bwd_supported(
        engine_cfg("auto", dist_kw={"dp_size": 2, "tp_size": 2,
                                    "sequence_parallel": True}))
    assert fused_bwd_supported(
        engine_cfg("auto", dist_kw={"dp_size": 2, "cp_size": 2}))
    assert fused_bwd_supported(
        engine_cfg("auto", dist_kw={"cp_size": 2},
                   model_kw={"attn_impl": "ulysses"}))
    assert fused_bwd_supported(
        engine_cfg("auto", model_kw={"num_experts": 4,
                                     "num_experts_per_token": 2}))
    assert fused_bwd_supported(
        engine_cfg("auto", dist_kw={"dp_size": 2, "ep_size": 2},
                   model_kw={"num_experts": 4,
                             "num_experts_per_token": 2}))
    # pp > 1 (PR 63): the 1F1B tick's backward unit runs the two layer
    # scans for a dense model without cp or SP (tests/test_pp_engines.py);
    # AFAB differentiates through its scan and stays AD
    assert fused_bwd_supported(
        engine_cfg("auto", dist_kw={"dp_size": 2, "pp_size": 2}))
    assert not fused_bwd_supported(
        engine_cfg("auto", dist_kw={"dp_size": 2, "pp_size": 2,
                                    "pp_engine": "afab"}))
    # still AD-only: non-dots_attn remat, remat off
    assert not fused_bwd_supported(
        engine_cfg("auto", remat_policy="dots"))
    assert not fused_bwd_supported(engine_cfg("auto", remat=False))


def test_fused_rejects_unsupported_config():
    with pytest.raises(ValueError, match="fused"):
        engine_cfg("fused", remat_policy="dots").validate()
    with pytest.raises(ValueError, match="fused"):
        engine_cfg("fused", dist_kw={"dp_size": 2, "pp_size": 2,
                                     "pp_engine": "afab"}).validate()


# ---------------------------------------------------------------------------
# per-axis fp32 gradient parity (see module doc)
# ---------------------------------------------------------------------------


def test_grads_parity_sequence_parallel():
    # Megatron-SP: the ctx.f/g all_gather / reduce-scatter pair inside the
    # fused engine's segment VJPs, seq-sharded saved layer inputs
    assert_grads_match(dk={"dp_size": 2, "tp_size": 2,
                           "sequence_parallel": True})


def test_grads_parity_cp4_ring_zigzag():
    # ring backward from the saved merged LSE: a second ppermute ring
    # carrying dK/dV accumulators, zigzag positions traveling with blocks
    assert_grads_match(dk={"dp_size": 2, "cp_size": 4})


def test_grads_parity_cp2_ulysses():
    # Ulysses backward: the all_to_all pair in both directions around the
    # bwd-from-saved kernel, inner-domain saved LSE, static zigzag sort
    assert_grads_match(dk={"dp_size": 2, "cp_size": 2},
                       mk={"attn_impl": "ulysses"})


def test_grads_parity_moe_ep():
    # MoE segment VJP: routing recomputed from the saved layer input,
    # router aux fold (aux * count) gradient, expert-parallel all_to_all
    assert_grads_match(dk={"dp_size": 2, "ep_size": 2},
                       mk={"num_experts": 4, "num_experts_per_token": 2})


def test_grads_parity_cp2_ring_contiguous():
    assert_grads_match(dk={"dp_size": 2, "cp_size": 2,
                           "cp_layout": "contiguous"})


def test_grads_parity_moe_capacity_drops():
    # a tight capacity bound forces real drops: the drop statistic must
    # ride the fused path into extras identically, and dropped tokens'
    # zero-contribution must match the AD engine's
    assert_grads_match(
        dk={"dp_size": 2, "ep_size": 2},
        mk={"num_experts": 4, "num_experts_per_token": 2,
            "capacity_factor": 0.25})
    _, _, extras, _ = device_grads_of(fp32_cfg(
        "fused", {"num_experts": 4, "num_experts_per_token": 2,
                  "capacity_factor": 0.25},
        {"dp_size": 2, "ep_size": 2}))
    assert extras["moe_drop_frac"] > 0.0


def test_grads_parity_sp_qwen_bias_tied():
    assert_grads_match(dk={"dp_size": 2, "tp_size": 2,
                           "sequence_parallel": True},
                       mk={"attention_bias": True,
                           "tie_word_embeddings": True})


def test_parity_sequence_parallel_e2e():
    # full bf16 + offload steps through the optimizer (conventions of the
    # dense e2e tests above)
    assert_engines_match(dk={"dp_size": 2, "tp_size": 2,
                             "sequence_parallel": True})


def test_parity_cp4_ring_e2e():
    assert_engines_match(dk={"dp_size": 2, "cp_size": 4})


def test_parity_cp2_ulysses_e2e():
    assert_engines_match(dk={"dp_size": 2, "cp_size": 2},
                         mk={"attn_impl": "ulysses"})


def test_parity_moe_ep_e2e():
    assert_engines_match(dk={"dp_size": 2, "ep_size": 2},
                         mk={"num_experts": 4, "num_experts_per_token": 2})


def test_grad_clip_parity():
    # the global-norm clip consumes the accumulated grads — same totals,
    # same clip scale, regardless of engine
    assert_engines_match(grad_clip_norm=0.1)
