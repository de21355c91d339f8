"""Shardflow tests — collective provenance, implicit-reshard detection,
and the static jit-variant prover (analysis/dataflow.py + variants.py).

Three layers, mirroring the acceptance criteria:

- provenance over the real config matrix: >= 90% of the lowered step's
  effective collectives attributed to a source site, zero implicit ops,
  zero predicted boundary reshards, every attributed site explained by an
  intended-schedule rule;
- a deliberately mis-specced fixture (declared P('dp') input consumed
  replicated by a shard_map) both predicted statically (with the spec fix
  named) AND confirmed against the compiled module, where the
  GSPMD-minted all-gather is visible;
- the variant prover: compile-once certified for the train step and the
  serve programs on clean inputs, signature-space explosion and
  uncommitted feeds flagged on planted ones — and the runtime twin, where
  CompileWatch observes the exact extra executable the prover predicted.
"""

import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from picotron_tpu.analysis import (
    audit_feeds, check_engine_feed, collect_sites, predict_boundary_reshards,
    prove_serve_programs, prove_train_step, run_shardcheck,
)
from picotron_tpu.analysis.dataflow import (
    attribute_collectives, compiled_collectives, intended_rule, root_paths,
)
from picotron_tpu.analysis.collectives import parse_collectives
from picotron_tpu.analysis.trace import lower_train_step
from tests.test_shardcheck import MATRIX, mkcfg

ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# provenance on the real config matrix
# ---------------------------------------------------------------------------

# the layout classes with distinct collective schedules; the full matrix
# (incl. offload variants) is covered by test_shardcheck's green gate,
# which now runs these checks too
_PROV_CONFIGS = ("dense-dp2tp2cp2", "dense-pp2dp2", "moe-ep2dp2")


@pytest.fixture(scope="module")
def traced():
    """One shared trace per layout class — every provenance assertion
    below reads it, none re-traces."""
    out = {}
    for name in _PROV_CONFIGS:
        cfg = mkcfg(**MATRIX[name])
        out[name] = (cfg, lower_train_step(cfg))
    return out


@pytest.mark.parametrize("name", _PROV_CONFIGS)
def test_provenance_attributes_all_collectives(traced, name):
    """The acceptance bar: >= 90% of the lowered module's effective
    collectives attributed to a jaxpr site; on the shipped layouts it is
    100%, with zero implicit (GSPMD-minted) ops."""
    cfg, low = traced[name]
    sites = collect_sites(low.jaxpr, root_paths(low.state, low.batch))
    ops = [o for o in parse_collectives(low.text) if o.effective]
    attributed, implicit = attribute_collectives(cfg, sites, ops)
    assert ops, "multi-axis layouts must lower effective collectives"
    assert len(attributed) / len(ops) >= 0.90
    assert implicit == [], [op.line for op in implicit]


@pytest.mark.parametrize("name", _PROV_CONFIGS)
def test_provenance_sites_carry_source_and_roots(traced, name):
    """Every site names the picotron_tpu line that issued it; data-carrying
    sites trace back to root state/batch paths (def-use provenance)."""
    _, low = traced[name]
    sites = collect_sites(low.jaxpr, root_paths(low.state, low.batch))
    assert sites
    for s in sites:
        assert s.source.startswith("picotron_tpu/"), s.describe()
        assert s.axes, s.describe()
    rooted = [s for s in sites if s.roots]
    assert len(rooted) >= len(sites) * 0.5, \
        [s.describe() for s in sites if not s.roots]
    roots = {r for s in rooted for r in s.roots}
    assert any(r.startswith("state/params/") for r in roots)


@pytest.mark.parametrize("name", _PROV_CONFIGS)
def test_provenance_every_site_is_intended(traced, name):
    """Each attributed site matches an intended-schedule rule (grad sync,
    TP psum, ring shift, expert dispatch, ...) — the shipped layouts have
    no collective a human would need to explain."""
    cfg, low = traced[name]
    sites = collect_sites(low.jaxpr, root_paths(low.state, low.batch))
    ops = [o for o in parse_collectives(low.text) if o.effective]
    attributed, _ = attribute_collectives(cfg, sites, ops)
    unexplained = [s.describe() for _, s in attributed
                   if intended_rule(cfg, s) is None]
    assert unexplained == []


@pytest.mark.parametrize("name", _PROV_CONFIGS)
def test_no_boundary_reshards_on_shipped_layouts(traced, name):
    cfg, low = traced[name]
    assert predict_boundary_reshards(cfg, low.jaxpr, low.state,
                                     low.batch) == []


# ---------------------------------------------------------------------------
# the mis-specced fixture: predicted statically, confirmed compiled
# ---------------------------------------------------------------------------


def _two_device_mesh():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 simulated devices")
    return Mesh(np.array(jax.devices()[:2]), ("dp",))


def _shard_map_prog(mesh, in_spec):
    from jax import shard_map

    def prog(tree):
        f = shard_map(lambda a: a * 2.0, mesh=mesh,
                      in_specs=in_spec, out_specs=in_spec)
        return {"x": f(tree["x"])}

    return prog


def test_misspecced_input_predicted_with_fix_named():
    """Declared P('dp') input consumed replicated by the program's
    shard_map: the audit predicts the GSPMD reshard at the boundary and
    names the exact spec change that removes it."""
    mesh = _two_device_mesh()
    x = jax.ShapeDtypeStruct((8, 8), jnp.float32,
                             sharding=NamedSharding(mesh, P("dp")))
    state = {"x": x}
    traced_step = jax.jit(_shard_map_prog(mesh, P())).trace(state)
    found = predict_boundary_reshards(None, traced_step.jaxpr, state, ())
    assert len(found) == 1, found
    r = found[0]
    assert r.path == "state/x"
    assert r.declared == "('dp',)" and r.used == "()"
    assert r.nbytes == 8 * 8 * 4
    assert "PartitionSpec()" in r.fix and "state/x" in r.fix

    # negative control: matching specs predict nothing
    traced_ok = jax.jit(_shard_map_prog(mesh, P("dp"))).trace(state)
    assert predict_boundary_reshards(None, traced_ok.jaxpr, state, ()) == []


def test_misspecced_input_mints_all_gather_in_compiled_module():
    """The compiled-module confirmation: the predicted reshard is REAL —
    the optimized HLO contains an all-gather no jaxpr site issued (it is
    invisible in the pre-partitioning StableHLO)."""
    mesh = _two_device_mesh()
    x = jax.ShapeDtypeStruct((8, 8), jnp.float32,
                             sharding=NamedSharding(mesh, P("dp")))
    lowered = jax.jit(_shard_map_prog(mesh, P())).lower({"x": x})
    pre = [o for o in parse_collectives(lowered.as_text()) if o.effective]
    assert pre == []  # nothing authored...
    minted = compiled_collectives(lowered)
    assert any(op.kind == "all_gather" for op in minted), \
        [op.line for op in minted]  # ...yet GSPMD gathered

    # matching specs compile collective-free
    ok = jax.jit(_shard_map_prog(mesh, P("dp"))).lower({"x": x})
    assert [op.kind for op in compiled_collectives(ok)] == []


# ---------------------------------------------------------------------------
# variant prover
# ---------------------------------------------------------------------------


def test_train_step_proves_compile_once(traced):
    cfg, low = traced["dense-dp2tp2cp2"]
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


def test_run_shardcheck_includes_new_checks():
    rep = run_shardcheck(mkcfg(), checks=("provenance", "variants"))
    assert rep.ok(), rep.render(verbose=True)
    assert rep.info["provenance"]["attribution_pct"] >= 90.0
    assert rep.info["variants"]["train_step"]["proven"]
    assert rep.info["variants"]["serve"]["proven"]


def test_cli_provenance_and_variants_flags(capsys):
    from tests.test_tools import load_tool

    sc = load_tool("shardcheck")
    rc = sc.main(["--preset", "tiny-dense", "--provenance", "--variants",
                  "--json"])
    assert rc == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["ok"]
    prov = row["info"]["provenance"]
    assert prov["attribution_pct"] >= 90.0
    assert prov["implicit_ops"] == 0 and prov["boundary_reshards"] == 0
    var = row["info"]["variants"]
    assert var["train_step"]["proven"] and var["serve"]["proven"]
    # focus flags restrict the run: no collectives/donation tables
    assert "collectives" not in row["info"]

    rc = sc.main(["--preset", "tiny-dense", "--provenance", "--variants"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "provenance:" in out and "attributed (100.0%)" in out
    assert "proven compile-once" in out
