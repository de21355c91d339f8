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
