"""reference_moe.py (nothing imported from the program) against the program's
own jnp forward at the tiny OLMoE-shaped preset: same logits and the same
loss with both auxiliary terms in float32, and each of the five probes moves
the result (so a tolerance that one of them passes is too wide)."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import reference_moe  # noqa: E402
from picotron_tpu.config import ModelConfig, resolve_preset  # noqa: E402
from picotron_tpu.models.llama import forward, init_params, loss_fn  # noqa: E402

PROBES = [dict(skip_layers=(1,)), dict(causal=False), dict(renorm_gates=True),
          dict(drop_last_expert=True), dict(skip_qk_norm=True)]


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="debug-tiny-olmoe", **resolve_preset("debug-tiny-olmoe"),
                      dtype="float32", attn_impl="reference")
    params = init_params(cfg, jax.random.key(3))
    for i, k in enumerate(("q_norm", "k_norm")):  # unit-initialised: give them values
        w = params["layers"][k]
        params["layers"][k] = w + 0.2 * jax.random.normal(jax.random.key(7 + i), w.shape)
    ids = jax.random.randint(jax.random.key(5), (48,), 0, cfg.vocab_size)
    return cfg, dataclasses.asdict(cfg), params, ids, jnp.roll(ids, -1)


def test_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_moe.py")) as f:
        src = f.read()
    assert "picotron" not in src.split('"""', 2)[2]


def test_matches_program_forward_and_loss(tiny):
    cfg, m, params, ids, tgt = tiny
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(params, ids[None], cfg=cfg)[0])
        want_loss = float(loss_fn(params, ids[None], tgt[None], cfg))
    got, probs = reference_moe.logits_at(params, ids, jnp.arange(48), m)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    assert probs.shape == (cfg.num_hidden_layers, 48, cfg.num_experts)
    np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0, rtol=1e-5)
    assert float(reference_moe.loss(params, ids, tgt, m)) == pytest.approx(want_loss, rel=1e-5)
    t = reference_moe.loss_terms(params, ids, tgt, m)
    # a balanced router has balance 1 a layer; z is a mean of squares
    assert float(t["balance"]) >= cfg.num_hidden_layers and float(t["z"]) > 0.0


@pytest.mark.parametrize("probe", PROBES, ids=lambda p: next(iter(p)))
def test_each_probe_moves_logits_and_loss(tiny, probe):
    _, m, params, ids, tgt = tiny
    base, _ = reference_moe.logits_at(params, ids, jnp.arange(48), m)
    moved, _ = reference_moe.logits_at(params, ids, jnp.arange(48), m, **probe)
    assert float(jnp.max(jnp.abs(moved - base))) > 1e-2
    l0 = float(reference_moe.loss(params, ids, tgt, m))
    assert abs(float(reference_moe.loss(params, ids, tgt, m, **probe)) - l0) > 1e-5


def test_rounded_operands_move_it_less_than_a_wrong_model(tiny):
    _, m, params, ids, _ = tiny
    rows = jnp.arange(48)
    base, _ = reference_moe.logits_at(params, ids, rows, m)
    bf16, _ = reference_moe.logits_at(params, ids, rows, m, round_to=jnp.bfloat16)
    err = float(jnp.max(jnp.abs(bf16 - base)))
    assert 0.0 < err < 0.1


def test_runner_statistics_tell_a_fault_from_noise(tiny):
    """`train_step_moe`'s three statistics on the tiny model: a program with a
    fault reads a share of 1 in it and a right program 0, under noise as large
    as the fault; near-ties are found from the router's probabilities."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "train_step_moe", os.path.join(HERE, "runners", "train_step_moe.py"))
    rn = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rn)
    _, m, params, ids, _ = tiny
    rows = jnp.arange(48)
    want, probs = (np.asarray(a) for a in reference_moe.logits_at(params, ids, rows, m))
    for name, kw in rn.FAULTS.items():
        wrong = np.asarray(reference_moe.logits_at(params, ids, rows, m, **kw)[0])
        noise = np.random.default_rng(0).normal(size=want.shape) * np.abs(wrong - want).std()
        assert abs(rn.fault_share(want + noise, want, wrong)) < 0.1, name
        assert abs(rn.fault_share(wrong + noise, want, wrong) - 1.0) < 0.1, name
        assert rn.row_errors(wrong, want).max() > 0.0
    assert rn.fault_share(want, want, want) == 0.0  # a fault that changes nothing
    k = m["num_experts_per_token"]
    top = -np.sort(-probs, axis=-1)
    gaps = np.log(top[..., k - 1] / top[..., k]).min(axis=0)
    np.testing.assert_array_equal(rn.tie_rows(probs, k, float(np.median(gaps))),
                                  gaps < np.median(gaps))
