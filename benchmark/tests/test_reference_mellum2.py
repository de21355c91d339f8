"""reference_mellum2.py (nothing imported from the program) against the
program's own jnp forward at the tiny Mellum2-shaped preset: the same logits
in float32; each of the probe's four controls moves them (so a tolerance that
one of them passes is too wide); its YaRN frequencies against the numbers of
the published law; the runner's comparison and its choice of requests."""
import importlib.util
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import reference_mellum2  # noqa: E402
from picotron_tpu.config import ModelConfig, resolve_preset  # noqa: E402
from picotron_tpu.models.llama import forward, init_params  # noqa: E402


def runner():
    spec = importlib.util.spec_from_file_location(
        "serve_mellum2", os.path.join(HERE, "runners", "serve_mellum2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="debug-tiny-mellum2", **resolve_preset("debug-tiny-mellum2"),
                      dtype="float32")
    params = init_params(cfg, jax.random.key(3))
    pub = dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, rms_norm_eps=1e-6,
        layer_types=list(cfg.layer_kinds) + ["full_attention"] * 4,  # published whole: 12
        sliding_window=8, rope_parameters={k: dict(v) for k, v in dict(cfg.rope_parameters).items()},
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32, norm_topk_prob=True,
        tie_word_embeddings=False)
    ids = jax.random.randint(jax.random.key(5), (53,), 0, cfg.vocab_size)
    return cfg, pub, params, ids


def test_imports_nothing_from_the_program():
    with open(os.path.join(HERE, "reference_mellum2.py")) as f:
        src = f.read()
    assert "picotron" not in src.split('"""', 2)[2]


def test_matches_program_forward(tiny):
    cfg, pub, params, ids = tiny
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(params, ids[None], cfg=cfg)[0])
    got = np.asarray(reference_mellum2.logits_at(params, ids, jnp.arange(53), pub))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # the runner reads the same keys, and the first num_hidden_layers of layer_types
    as_program = runner().as_program(pub)
    assert all(getattr(cfg, k) == v for k, v in as_program.items()), as_program


@pytest.mark.parametrize("fault", ["no_band", "one_rope", "drop_last_expert", "int8"])
def test_each_control_moves_the_logits(tiny, fault):
    cfg, pub, params, ids = tiny
    rows = jnp.arange(53)
    want = np.asarray(reference_mellum2.logits_at(params, ids, rows, pub))
    if fault == "int8":
        got = reference_mellum2.logits_at(reference_mellum2.rounded_to(params, 8), ids, rows, pub)
    else:
        got = reference_mellum2.logits_at(params, ids, rows, pub, **{fault: True})
    moved = np.abs(np.asarray(got) - want)
    assert moved.max() > 5e-3, (fault, moved.max())
    if fault == "no_band":  # the first `window` positions see everything either way
        assert moved[:8].max() < 1e-5


def test_yarn_frequencies_of_the_published_law():
    """Mellum2's full_attention law by hand: dim(32) = 18.08 and dim(1) = 34.99
    at d 128, theta 5e5, L0 8192: low 18, high 35; pairs up to 18 keep their
    frequency, pairs from 35 on are divided by 16, a ramp between."""
    law = dict(rope_type="yarn", rope_theta=500000, factor=16,
               original_max_position_embeddings=8192, beta_fast=32, beta_slow=1,
               attention_factor=1.2772588722239782)
    inv = reference_mellum2.inv_freq(law, 128)
    base = 500000.0 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(inv[:19], base[:19], rtol=1e-12)
    np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=1e-12)
    mid = (1 - (26 - 18) / 17) * base[26] + ((26 - 18) / 17) * base[26] / 16
    assert inv[26] == pytest.approx(mid, rel=1e-12)
    assert reference_mellum2.amplitude(law) == pytest.approx(0.1 * np.log(16) + 1)
    plain = dict(rope_type="default", rope_theta=500000)
    np.testing.assert_array_equal(reference_mellum2.inv_freq(plain, 128), base)
    assert reference_mellum2.amplitude(plain) == 1.0


def test_compare_and_pick():
    r = runner()
    logits = np.zeros((3, 10), np.float32)
    logits[:, 4] = 2.0
    logits[1, 7] = 2.0 - 0.5 * r.TIE_STEPS * 2.0 ** -8 * 2.0  # half a tie band under the top
    got = r.compare([2.0, 1.99, 2.03], [4, 7, 4], logits)
    assert got["tie"] == pytest.approx(0.5)
    np.testing.assert_allclose(got["err"], [0.0, abs(1.99 - logits[1, 7]) / 2, 0.015], atol=1e-6)
    w = {"traffic": {"classes": [{"prompt_tokens": {"max": 100}}, {"prompt_tokens": {"max": 900}}]}}
    plen = {1: 50, 2: 800, 3: 400, 4: 60, 5: 700, 6: 20}
    res = {k: {"output_tokens": 10} for k in plen}
    picks = r.pick(sorted(plen), plen, res, 11, w)
    assert picks[0] == 2 and len(picks) == 6 == len(set(picks))  # fewer than PICKS: all
    assert plen[picks[1]] <= 100  # a short one is always among them
    many = {k: 50 + k for k in range(40)}
    picks = r.pick(sorted(many), many, {k: {"output_tokens": 10} for k in many}, 11, w)
    assert len(picks) == r.PICKS == len(set(picks)) and picks[0] == 39
    assert r.pick([], plen, res, 11, w) == []
