"""Test scaffold: an 8-device simulated CPU mesh in a single process.

This upgrades the reference's test story (two standalone torchrun scripts
needing 4 GPUs + NCCL, ref: tests/test_tensor_parallel.py:2) to pytest on a
host-platform simulated mesh — SURVEY.md §4's recommendation.

Every run pins the CPU platform (env var and jax.config, before any backend
client exists) except `pytest -m tpu`, which runs tests/test_tpu_hw.py on
the chip and fails — not skips — when there is none.
"""

import os
import sys


def _tpu_marker_requested(argv) -> bool:
    """`pytest -m tpu` selects the on-hardware tests (tests/test_tpu_hw.py)
    — those need the REAL chip, so the CPU forcing below must not run."""
    for i, a in enumerate(argv):
        expr = None
        if a == "-m" and i + 1 < len(argv):
            expr = argv[i + 1]
        elif a.startswith("-m=") or a.startswith("--markexpr="):
            expr = a.split("=", 1)[1]
        if expr and "tpu" in expr and "not tpu" not in expr:
            return True
    return False


ON_HARDWARE = _tpu_marker_requested(sys.argv)

if not ON_HARDWARE:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

if not ON_HARDWARE:
    jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)


def pytest_sessionstart(session):
    if not ON_HARDWARE:
        return
    from picotron_tpu.utils import setup_compile_cache

    setup_compile_cache()
    try:
        platform = jax.devices()[0].platform
    except Exception as e:  # noqa: BLE001 — any backend failure = no chip
        platform = f"none ({type(e).__name__})"
    if platform != "tpu":
        # exit, not skip: "0 failed" from a run that tested nothing would
        # read as the kernels passing
        pytest.exit(f"pytest -m tpu needs a TPU; JAX's platform here is "
                    f"{platform} (JAX_PLATFORMS="
                    f"{os.environ.get('JAX_PLATFORMS', '<unset>')})",
                    returncode=1)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: on-hardware kernel regression tests (run `pytest -m tpu` on "
        "a machine with a TPU — it fails without one; a run that does not "
        "ask for them skips them)")
    config.addinivalue_line(
        "markers",
        "slow: multi-process integration and heavy layout-parity compiles. "
        "Dev loop: `pytest -m 'not slow'` (< 10 min); CI/full: plain "
        "`pytest tests/` runs everything — semantics identical, the marker "
        "only partitions wall-time")


def pytest_collection_modifyitems(config, items):
    if ON_HARDWARE:
        return
    skip = pytest.mark.skip(
        reason="on-chip test; run `pytest -m tpu` on a machine with a TPU")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 simulated devices, got {len(devs)}"
    return devs


@pytest.fixture
def fresh_programs(monkeypatch):
    """The engine's two programs under function objects of their own. JAX
    keeps a traced program by the function it was traced from, whichever
    `jax.jit` wraps it, so a patch that tracing consults (here: which form
    of attention a step takes, which form the expert block) needs
    functions that no other test of this process has traced at the same
    shapes, and must leave none behind for the bit-identical parity tests
    to pick up."""
    import functools

    from picotron_tpu.serve import engine

    def jits(donate):
        decode = functools.wraps(engine.serve_decode)(
            lambda *a, **k: engine.serve_decode(*a, **k))
        prefill = functools.wraps(engine.serve_prefill)(
            lambda *a, **k: engine.serve_prefill(*a, **k))
        static = ("cfg", "temperature", "top_k", "cache_cls")
        return (jax.jit(decode, static_argnames=static + (
                    "interval", "eos_token_id")),
                jax.jit(prefill, static_argnames=static))

    monkeypatch.setattr(engine, "_get_jits", jits)


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs_between_files():
    """A worker runs whole files one after the other (`--dist loadfile`) and
    keeps every program it compiled. `tests/test_k_exaone.py` followed by
    `tests/test_generate.py` in ONE process ends in a segmentation fault
    inside the CPU backend's compile of a trivial program (the parent of PR
    47 does the same: which files share a worker is xdist's draw, and a new
    test file moves it). Dropping the compiled programs when a file ends
    keeps a worker's process as small as a fresh one's."""
    yield
    jax.clear_caches()
