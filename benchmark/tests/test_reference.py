"""reference.py (nothing imported from the program) against the program's own
jnp forward at a tiny size: same logits in float32 for the Qwen2 variants."""
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

import reference  # noqa: E402
from picotron_tpu.config import ModelConfig, resolve_preset  # noqa: E402
from picotron_tpu.models.llama import forward, init_params  # noqa: E402


@pytest.mark.parametrize("preset", ["debug-tiny-qwen", "debug-tiny"])
def test_matches_program_forward(preset):
    cfg = ModelConfig(name=preset, **resolve_preset(preset), dtype="float32",
                      attn_impl="reference")
    m = dataclasses.asdict(cfg)
    params = init_params(cfg, jax.random.key(3))
    if "b_q" in params["layers"]:  # biases are zero-initialised: give them values
        for k in ("b_q", "b_k", "b_v"):
            params["layers"][k] = 0.1 * jax.random.normal(jax.random.key(4), params["layers"][k].shape)
    ids = jax.random.randint(jax.random.key(5), (48,), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(params, ids[None], cfg=cfg)[0])
    got = np.asarray(reference.logits_at(params, ids, jnp.arange(48), m))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # and the probes differ from it: a dropped layer, a non-causal mask
    tgt = jnp.roll(ids, -1)
    base = float(reference.nll_sum(params, ids, tgt, m)[0])
    assert abs(float(reference.nll_sum(params, ids, tgt, m, skip_layers=(1,))[0]) - base) > 1e-3
    assert abs(float(reference.nll_sum(params, ids, tgt, m, causal=False)[0]) - base) > 1e-3


@pytest.mark.parametrize("std, sees", [(1.0, False), (0.02, True)])
def test_what_greedy_tokens_can_show_depends_on_the_embeddings_scale(std, sees):
    """Why the served configuration states `initializer_range`: through a tied
    head, the program's unit-normal embedding makes the model repeat its input
    token whatever the layers compute, so served tokens show no fault; at the
    published standard deviation a wrong mask, a dropped layer and another
    context each put other tokens first, far outside the tie band."""
    preset = "debug-tiny-qwen"
    cfg = ModelConfig(name=preset, **resolve_preset(preset), dtype="float32",
                      attn_impl="reference")
    m = dataclasses.asdict(cfg)
    params = init_params(cfg, jax.random.key(3))
    params["embedding"] = params["embedding"] * std
    ids = jax.random.randint(jax.random.key(5), (64,), 0, cfg.vocab_size)
    head = reference._head(params)

    def first_and_gap(ids, **kw):
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(reference.hidden_states(params, ids, m, **kw) @ head)
        return logits, logits.argmax(axis=-1)

    logits, first = first_and_gap(ids)
    assert bool((first == np.asarray(ids)).all()) is not sees  # repeats its input, or not
    band = 8 * 2.0 ** -8 * np.maximum(np.abs(logits.max(axis=-1)), 1.0)
    other = ids.at[:16].set(ids[:16][::-1])  # another context before position 16
    for faulty in (first_and_gap(ids, causal=False)[1], first_and_gap(ids, skip_layers=(1,))[1],
                   first_and_gap(other)[1]):
        gap = (logits.max(axis=-1) - logits[np.arange(64), faulty])[16:] / band[16:]
        assert bool(gap.max() > 1.0) is sees, gap.max()
    # the control of `correct`: int8 rounding moves the matrices, and nothing else
    low = reference.rounded_to(params, 8)
    assert float(jnp.abs(low["layers"]["up"] - params["layers"]["up"]).max()) > 0
    assert float(jnp.abs(low["layers"]["up"] - params["layers"]["up"]).max()) < 0.01
    assert bool((low["layers"]["input_norm"] == params["layers"]["input_norm"]).all())
