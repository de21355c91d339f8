"""The static jit-variant prover (analysis/variants.py): compile-once
certified for the train step and the serve programs on clean inputs,
signature-space explosion and uncommitted feeds flagged on planted ones —
and the runtime twin, where CompileWatch observes the exact extra
executable the prover predicted.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from picotron_tpu.analysis import (
    audit_feeds, check_engine_feed, prove_serve_programs, prove_train_step,
    run_shardcheck,
)
from picotron_tpu.analysis.trace import lower_train_step
from tests.test_shardcheck import MATRIX, mkcfg


def test_train_step_proves_compile_once():
    cfg = mkcfg(**MATRIX["dense-dp2tp2cp2"])
    low = lower_train_step(cfg)
    rep = prove_train_step(cfg, low=low)
    assert rep.ok(), rep.render(verbose=True)
    info = rep.info["variants"]
    assert info["proven"] and info["signatures"] == 1
    assert info["uncommitted"] == 0


def test_audit_feeds_flags_uncommitted_and_divergent():
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    committed = {"x": jax.device_put(jnp.zeros((8,)), sh)}
    uncommitted = {"x": jnp.zeros((8,))}

    rep = audit_feeds([committed], entry="clean")
    assert rep.ok() and rep.info["variants"]["proven"]

    rep = audit_feeds([committed, uncommitted], entry="dirty")
    assert not rep.ok()
    assert rep.info["variants"]["signatures"] == 2
    assert any("UNCOMMITTED" in f.message for f in rep.warnings())
    assert any("compile-once is NOT provable" in f.message
               for f in rep.errors())


def test_uncommitted_device_put_runtime_twin():
    """The end-to-end acceptance fixture: a deliberate no-sharding
    jax.device_put is (a) flagged by the source lint, (b) proven a
    variant hazard statically, and (c) confirmed by CompileWatch — the
    uncommitted re-feed of the SAME shapes mints exactly one extra
    executable, and is stable thereafter."""
    from picotron_tpu.analysis.source_lint import lint_file
    from picotron_tpu.telemetry.recompile import CompileWatch

    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    committed = jax.device_put(jnp.ones((16,), jnp.float32), sh)
    uncommitted = jax.device_put(jnp.ones((16,), jnp.float32))

    # (a) the lint rule names the smell in source form
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write("import jax\n"
                "def feed(x):\n"
                "    return jax.device_put(x)\n")
        path = f.name
    try:
        lrep = lint_file(path, "fixture.py")
        assert any("UNCOMMITTED" in w.message for w in lrep.warnings())
    finally:
        os.unlink(path)

    # (b) the prover: the two feeds split the signature space
    vrep = audit_feeds([{"x": committed}, {"x": uncommitted}], entry="twin")
    assert not vrep.ok() and vrep.info["variants"]["signatures"] == 2

    # (c) the runtime twin
    watch = CompileWatch().install()
    try:
        if not watch.supported:
            pytest.skip("compile events not observable on this jax")
        step = jax.jit(lambda x: x * 2.0)
        step(committed)
        watch.drain()
        step(uncommitted)  # same shape/dtype — only commitment differs
        n, _ = watch.drain()
        assert n == 1  # exactly the executable the prover predicted
        step(uncommitted)
        n, _ = watch.drain()
        assert n == 0  # and the space is closed again
    finally:
        watch.uninstall()


def test_serve_programs_prove_and_flag_uncommitted_params():
    from picotron_tpu.config import ModelConfig, resolve_preset

    mc = ModelConfig(**resolve_preset("debug-tiny"))
    # one decode signature and one prefill signature per rung of the
    # compacted batch's ladder (8 slots by default: 1, 4, 8 rows)
    rep = prove_serve_programs(mc)
    assert rep.ok() and rep.info["variants"]["proven"]
    assert rep.info["variants"]["prefill_rows"] == [1, 4, 8]
    assert rep.info["variants"]["signatures"] == 1 + 3

    uncommitted = {"embedding": jnp.zeros((8, 4))}
    rep = prove_serve_programs(mc, params=uncommitted)
    info = rep.info["variants"]
    assert not info["proven"] and info["uncommitted"] == ["embedding"]
    assert any("place_for_decode" in f.message for f in rep.warnings())


def test_engine_feed_check_proves_live_engine():
    """check_engine_feed over a real ServeEngine: init commits every
    persistent leaf (params included — the hole this prover found), so
    the live feed proves compile-once; engine.variant_report carries it."""
    from picotron_tpu.config import ModelConfig, ServeConfig, resolve_preset
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.serve.engine import ServeEngine

    mc = ModelConfig(dtype="float32", **{
        **resolve_preset("debug-tiny"), "max_position_embeddings": 64})
    params = init_params(mc, jax.random.key(0))  # raw == uncommitted
    eng = ServeEngine(params, mc, ServeConfig(
        decode_slots=2, block_size=4, num_blocks=16, prefill_chunk=4,
        max_model_len=32))
    try:
        rep = check_engine_feed(eng)
        assert rep.ok(), rep.render(verbose=True)
        info = rep.info["variants"]
        assert info["proven"] and info["uncommitted"] == []
        # one decode signature, one prefill signature per rung (2 slots)
        assert info["prefill_rows"] == list(eng.prefill_rungs) == [1, 2]
        assert info["signatures"] == 1 + 2
        assert eng.stats["prefill_compiles"] <= 2  # held by the constructor
        assert eng.variant_report is not None
        assert eng.variant_report.info["variants"]["proven"]
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# full-check integration + CLI
# ---------------------------------------------------------------------------


def test_run_shardcheck_includes_variants():
    rep = run_shardcheck(mkcfg(), checks=("variants",))
    assert rep.ok(), rep.render(verbose=True)
    assert rep.info["variants"]["train_step"]["proven"]
    assert rep.info["variants"]["serve"]["proven"]


def test_cli_variants_flag(capsys):
    from tests.test_tools import load_tool

    sc = load_tool("shardcheck")
    rc = sc.main(["--preset", "tiny-dense", "--variants", "--json"])
    assert rc == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["ok"]
    var = row["info"]["variants"]
    assert var["train_step"]["proven"] and var["serve"]["proven"]
    # the focus flag restricts the run: no collectives/donation tables
    assert "collectives" not in row["info"]

    rc = sc.main(["--preset", "tiny-dense", "--variants"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "proven compile-once" in out
