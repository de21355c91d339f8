"""shardcheck static-analysis tests: the tier-1 config matrix must audit
green on the simulated CPU mesh, and every analyzer must fail LOUDLY (with
path-level messages) on deliberately broken inputs — a linter that cannot
catch the planted bug is worse than no linter (mutation tests per the
acceptance criteria: non-divisible tp sharding, extra/missing spec leaf,
undonated state buffer)."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from picotron_tpu.analysis import (
    check_donation, check_state_stability, lint_param_specs, lint_sources,
    lint_specs, lower_train_step, parse_collectives, run_shardcheck,
)
from picotron_tpu.analysis.collectives import audit_collectives
from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, TrainingConfig, resolve_preset,
)


def mkcfg(model="debug-tiny", seq=64, mbs=1, ga=1, dist=None, train=None,
          pipe=None):
    from picotron_tpu.config import PipelineConfig

    cfg = Config(
        distributed=DistributedConfig(**(dist or {})),
        model=ModelConfig(name=model, **resolve_preset(model)),
        training=TrainingConfig(seq_length=seq, micro_batch_size=mbs,
                                gradient_accumulation_steps=ga,
                                **(train or {})),
        pipeline=PipelineConfig(**(pipe or {})),
    )
    cfg.validate()
    return cfg


# The breadth matrix the issue asks for: dense/MoE, pp>1, ep>1, offload
# on/off — every layout class the repo trains, audited statically on the
# 8-device simulated mesh.
MATRIX = {
    "dense-1chip": dict(),
    "dense-dp2tp2cp2": dict(dist=dict(dp_size=2, tp_size=2, cp_size=2),
                            ga=2),
    "dense-pp2dp2": dict(dist=dict(pp_size=2, dp_size=2), ga=2),
    # mpmd executor: the audit runs on the SPMD twin lowering; the
    # per-stage programs get their own prover (test_analysis_mpmd)
    "dense-pp2dp2-mpmd": dict(dist=dict(pp_size=2, dp_size=2), ga=2,
                              pipe=dict(executor="mpmd")),
    "moe-ep2dp2": dict(model="debug-tiny-moe",
                       dist=dict(ep_size=2, dp_size=2), ga=2),
    "dense-offload": dict(ga=2, train=dict(optimizer_offload=True)),
    "moe-ep2-offload": dict(model="debug-tiny-moe", dist=dict(ep_size=2),
                            ga=2, train=dict(optimizer_offload=True)),
}


@pytest.mark.parametrize("name", sorted(MATRIX))
def test_matrix_audits_green(name):
    cfg = mkcfg(**MATRIX[name])
    rep = run_shardcheck(cfg)
    assert rep.ok(), rep.render(verbose=True)
    # full donation coverage is part of "green"
    assert rep.info["donation"]["donated"] == \
        rep.info["donation"]["state_leaves"]


# ---------------------------------------------------------------------------
# spec lint mutations
# ---------------------------------------------------------------------------


def test_spec_lint_clean_config():
    rep = lint_param_specs(mkcfg(dist=dict(tp_size=2, pp_size=2)))
    assert rep.ok(), rep.render()


def _spec_fixture(tp=2):
    from picotron_tpu.parallel.api import abstract_master
    from picotron_tpu.parallel.sharding import param_specs

    cfg = mkcfg(dist=dict(tp_size=tp))
    specs = param_specs(cfg)
    params = abstract_master(cfg)
    sizes = {"dp": 1, "pp": 1, "ep": 1, "cp": 1, "tp": tp}
    return specs, params, sizes


def test_spec_lint_rejects_non_divisible_tp():
    specs, params, sizes = _spec_fixture(tp=2)
    sizes["tp"] = 3  # hidden=64, vocab=256: nothing divides by 3
    rep = lint_specs(specs, params, sizes)
    errs = [f for f in rep.errors() if "layers/q" in f.path]
    assert errs, rep.render()
    assert "not divisible" in errs[0].message
    assert "'tp'" in errs[0].message or "tp" in errs[0].message


def test_spec_lint_rejects_missing_and_extra_leaves():
    specs, params, sizes = _spec_fixture()
    del specs["embedding"]
    specs["bogus_extra"] = P()
    rep = lint_specs(specs, params, sizes)
    paths = {f.path: f.message for f in rep.errors()}
    assert "embedding" in paths and "no PartitionSpec" in paths["embedding"]
    assert "bogus_extra" in paths
    assert "no matching param" in paths["bogus_extra"]


def test_spec_lint_rejects_rank_and_duplicate_axis():
    specs, params, sizes = _spec_fixture()
    specs["final_norm"] = P(None, "tp")        # rank-1 param, 2-entry spec
    specs["lm_head"] = P("tp", "tp")           # same axis shards two dims
    rep = lint_specs(specs, params, sizes)
    msgs = {f.path: f.message for f in rep.errors()}
    assert "final_norm" in msgs and "rank" in msgs["final_norm"]
    assert "lm_head" in msgs and "at most one" in msgs["lm_head"]


def test_spec_lint_rejects_unknown_axis():
    specs, params, sizes = _spec_fixture()
    specs["embedding"] = P("tpp", None)
    rep = lint_specs(specs, params, sizes)
    assert any("unknown mesh axis" in f.message and "embedding" in f.path
               for f in rep.errors()), rep.render()


# ---------------------------------------------------------------------------
# collective-schedule audit
# ---------------------------------------------------------------------------


def test_schedule_audit_single_device_has_no_effective_collectives():
    rep = audit_collectives(mkcfg())
    assert rep.ok(), rep.render()
    assert rep.info["collectives"]["total_effective"] == 0
    # size-1 mesh axes DO lower psums, as group-size-1 no-ops
    assert rep.info["collectives"]["compiled_away (size-1 groups)"] > 0


def test_schedule_audit_counts_on_8_device_mesh():
    cfg = mkcfg(dist=dict(dp_size=2, tp_size=2, cp_size=2), ga=2)
    low = lower_train_step(cfg)
    ops = parse_collectives(low.text)
    # the grad/loss psum over the fused data axes: dp*cp = 4
    assert any(op.kind == "all_reduce" and op.group_size == 4
               for op in ops)
    # tp psums: group size 2
    assert any(op.kind == "all_reduce" and op.group_size == 2
               for op in ops)
    # the cp ring moves K/V blocks via collective_permute
    assert any(op.kind == "collective_permute" and op.effective
               for op in ops)
    rep = audit_collectives(cfg, text=low.text, state=low.state)
    assert rep.ok(), rep.render()
    assert rep.info["collectives"]["total_effective"] > 0


def test_schedule_audit_detects_missing_grad_sync():
    """Feed the audit a lowering whose data-axes all-reduce was (textually)
    removed — the detector must call out the missing gradient sync."""
    cfg = mkcfg(dist=dict(dp_size=2), ga=2)
    low = lower_train_step(cfg)
    # delete every dp-group all-reduce line pair marker by renaming the op
    mutated = low.text.replace("stablehlo.all_reduce", "stablehlo.xx_gone")
    rep = audit_collectives(cfg, text=mutated, state=low.state)
    assert not rep.ok()
    assert any("NOT being synchronized" in f.message for f in rep.errors())


def test_gather_budget_flags_oversized_all_gather():
    """Sequence parallelism legitimately all-gathers [mbs, S, H]
    activations; with the budget forced below that size the audit must
    flag every such gather — the 'accidental full replication' detector
    firing on a planted violation."""
    cfg = mkcfg(dist=dict(tp_size=2, sequence_parallel=True), ga=2)
    low = lower_train_step(cfg)
    ok_rep = audit_collectives(cfg, text=low.text, state=low.state)
    assert ok_rep.ok(), ok_rep.render()

    tight = audit_collectives(cfg, text=low.text, state=low.state,
                              budget_bytes=64)
    errs = [f for f in tight.errors() if "all_gather" in f.path]
    assert errs, tight.render()
    assert "replication budget" in errs[0].message


def test_moe_audit_requires_all_to_all():
    cfg = mkcfg(model="debug-tiny-moe", dist=dict(ep_size=2), ga=2)
    low = lower_train_step(cfg)
    mutated = low.text.replace("stablehlo.all_to_all", "stablehlo.xx_gone")
    rep = audit_collectives(cfg, text=mutated, state=low.state)
    assert any("all_to_all" in f.path for f in rep.errors()), rep.render()


def _sp_cfg(engine="fused"):
    return mkcfg(dist=dict(dp_size=2, tp_size=2, sequence_parallel=True),
                 ga=2, train=dict(grad_engine=engine,
                                  remat_policy="dots_attn"))


def test_fused_sp_config_audits_green_not_skipped():
    """A `grad_engine: fused` + SP config must be AUDITED (the fused
    engine's manual backward lowers the same SP all-gather/reduce-scatter
    pair the AD engine's transposes produce), not skipped — the audit
    records which engine it saw and the presence of the f/g pair."""
    cfg = _sp_cfg()
    rep = run_shardcheck(cfg)
    assert rep.ok(), rep.render(verbose=True)
    assert rep.info["collectives"]["grad_engine"] == "fused"
    assert rep.info["collectives"]["reduce_scatter"] > 0
    assert rep.info["collectives"]["all_gather"] > 0


@pytest.mark.parametrize("kind,names", [
    ("reduce_scatter", "row-parallel exit (g)"),
    ("all_gather", "column-parallel entry (f)"),
])
@pytest.mark.parametrize("engine", ["fused", "ad"])
def test_audit_flags_deleted_exit_collective(engine, kind, names):
    """Negative test: textually delete one half of the Megatron-SP f/g
    pair from the lowering — the audit must name the half that is gone,
    whichever engine wrote the backward."""
    cfg = _sp_cfg(engine)
    low = lower_train_step(cfg)
    mutated = low.text.replace(f"stablehlo.{kind}", "stablehlo.xx_gone")
    rep = audit_collectives(cfg, text=mutated, state=low.state)
    assert not rep.ok()
    assert any(f.path == kind and names in f.message
               for f in rep.errors()), rep.render()


def test_fused_cp_ring_audit_requires_collective_permute():
    """cp>1 ring under the fused engine: the K/V ring's collective_permute
    (forward ring + the backward's dK/dV-carrying ring) must be present;
    deleting them must flag."""
    cfg = mkcfg(dist=dict(dp_size=2, cp_size=4), ga=2,
                train=dict(grad_engine="fused", remat_policy="dots_attn"))
    low = lower_train_step(cfg)
    rep = audit_collectives(cfg, text=low.text, state=low.state)
    assert rep.ok(), rep.render()
    assert rep.info["collectives"]["grad_engine"] == "fused"
    assert rep.info["collectives"]["collective_permute"] > 0
    mutated = low.text.replace("stablehlo.collective_permute",
                               "stablehlo.xx_gone")
    bad = audit_collectives(cfg, text=mutated, state=low.state)
    assert any("K/V ring" in f.message for f in bad.errors()), bad.render()


def test_ulysses_audit_requires_cp_all_to_all():
    cfg = mkcfg(model="debug-tiny",
                dist=dict(dp_size=2, cp_size=2), ga=2,
                train=dict(grad_engine="fused", remat_policy="dots_attn"))
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, attn_impl="ulysses",
                                       num_attention_heads=8,
                                       num_key_value_heads=4))
    cfg.validate()
    low = lower_train_step(cfg)
    rep = audit_collectives(cfg, text=low.text, state=low.state)
    assert rep.ok(), rep.render()
    mutated = low.text.replace("stablehlo.all_to_all", "stablehlo.xx_gone")
    bad = audit_collectives(cfg, text=mutated, state=low.state)
    assert any("Ulysses" in f.message for f in bad.errors()), bad.render()


# ---------------------------------------------------------------------------
# donation + recompilation hazards
# ---------------------------------------------------------------------------


def _toy_state_batch():
    state = {"params": {"w": jnp.zeros((8, 8), jnp.float32),
                        "b": jnp.zeros((8,), jnp.float32)},
             "step": jnp.zeros((), jnp.int32)}
    batch = (jnp.zeros((4,), jnp.int32),)
    return state, batch


def test_donation_flags_undonated_state_buffer():
    state, batch = _toy_state_batch()

    def step(state, batch):  # a step that forgot donate_argnums
        new = jax.tree.map(lambda x: x + 1, state)
        return new, jnp.float32(0)

    rep = check_donation(jax.jit(step).lower(state, batch))
    assert not rep.ok()
    paths = {f.path for f in rep.errors()}
    assert "params/w" in paths, rep.render()
    assert any("not donated" in f.message for f in rep.errors())


def test_donation_green_with_donate_argnums():
    state, batch = _toy_state_batch()

    def step(state, batch):
        new = jax.tree.map(lambda x: x + 1, state)
        return new, jnp.float32(0)

    rep = check_donation(
        jax.jit(step, donate_argnums=(0,)).lower(state, batch))
    assert rep.ok(), rep.render()
    assert rep.info["donation"]["donated"] == \
        rep.info["donation"]["state_leaves"]


def test_state_stability_detects_dtype_drift():
    state, batch = _toy_state_batch()

    def stable(state, batch):
        return jax.tree.map(lambda x: x + 1, state), {}

    assert check_state_stability(jax.jit(stable), state, batch).ok()

    def drifting(state, batch):  # params leave the step as bf16
        new = dict(state)
        new["params"] = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), state["params"])
        return new, {}

    rep = check_state_stability(jax.jit(drifting), state, batch)
    assert not rep.ok()
    assert any("recompiles" in f.message and "params/w" in f.path
               for f in rep.errors()), rep.render()


def test_state_stability_warns_on_weak_typed_metric():
    state, batch = _toy_state_batch()

    def step(state, batch):
        # a Python scalar reaching the traced output as a weak type
        return state, {"lr": jnp.asarray(3e-4)}

    rep = check_state_stability(jax.jit(step), state, batch)
    assert rep.ok()
    assert any("weak-typed metric" in f.message for f in rep.warnings())


# ---------------------------------------------------------------------------
# source lint
# ---------------------------------------------------------------------------


def test_source_lint_repo_is_clean_of_jax_core():
    """The rule the satellites retrofitted (ulysses/rope): no semi-private
    jax.core use anywhere in the package, and no host callbacks. Private
    jax._src imports stay warnings (mesh.py's pre-init probe is
    deliberate)."""
    rep = lint_sources()
    assert rep.ok(), rep.render()
    assert rep.info["source_lint"]["files"] > 20  # really walked the repo


def test_source_lint_catches_planted_violations(tmp_path):
    bad = tmp_path / "bad_module.py"
    bad.write_text(
        "import jax\n"
        "from jax.core import Tracer\n"
        "from jax import pure_callback\n"
        "import jax._src.core\n"
        "def f(x):\n"
        "    if isinstance(x, jax.core.Tracer):\n"
        "        return jax.pure_callback(abs, x, x)\n"
        "    return x\n"
        "suppressed = jax.core.get_aval  # shardcheck: ok\n")
    rep = lint_sources([str(bad)])
    msgs = [f.message for f in rep.errors()]
    assert any("jax.core" in m and "import" in m for m in msgs)
    assert any("pure_callback" in m for m in msgs)
    # the inline attribute chain on line 6 (isinstance probe) is caught too
    assert any(f.path.endswith(":6") for f in rep.errors()), rep.render()
    # the jax._src import is a warning, not an error
    assert any("_src" in f.message for f in rep.warnings())
    # line 9 is suppressed
    assert not any(":9" in f.path for f in rep.findings)


def test_source_lint_flags_collective_in_python_loop(tmp_path):
    """The unbatched-collective smell: a lax collective issued once per
    Python loop iteration (over layers/microbatches) flags as a warning;
    the same collective inside a function *defined* in the loop (a scan
    body) or past a `# shardcheck: ok` does not — the negative half the
    satellite requires."""
    bad = tmp_path / "loopy.py"
    bad.write_text(
        "import jax\n"
        "from jax import lax\n"
        "def per_layer_sync(grads):\n"
        "    out = []\n"
        "    for g in grads:\n"
        "        out.append(lax.psum(g, 'dp'))\n"          # line 6: flags
        "    return out\n"
        "def ring(x):\n"
        "    while True:\n"
        "        x = jax.lax.ppermute(x, 'cp', [(0, 1)])\n"  # line 10
        "    return x\n"
        "def scan_body_built_in_loop(xs):\n"
        "    fns = []\n"
        "    for _ in range(4):\n"
        "        def body(c, x):\n"
        "            return c, lax.psum(x, 'tp')\n"  # in a fn: no flag
        "        fns.append(body)\n"
        "    return fns\n"
        "def outside(x):\n"
        "    return lax.psum(x, 'dp')\n"             # no loop: no flag
        "def deliberate(xs):\n"
        "    for x in xs:\n"
        "        lax.ppermute(x, 'cp', [(0, 1)])  # shardcheck: ok\n")
    rep = lint_sources([str(bad)])
    assert rep.ok()  # loop-collective is a warning, not an error
    hits = [f for f in rep.warnings() if "inside a" in f.message]
    lines = sorted(int(f.path.rsplit(":", 1)[1]) for f in hits)
    assert lines == [6, 10], rep.render(verbose=True)


def test_source_lint_repo_has_no_unsuppressed_loop_collectives():
    """The deliberate unrolled rings (ops/ring_attention.py) and the
    per-leaf scalar clip psums (optimizer.py) are suppressed in-line;
    anything else would be a new smell."""
    rep = lint_sources()
    loopy = [f for f in rep.warnings()
             if "inside a Python loop" in f.message]
    assert loopy == [], [f.render() for f in loopy]


def test_preflight_raises_on_broken_spec(monkeypatch):
    """train.py wiring: a mutilated param_specs must abort with a
    ShardcheckError whose text carries the path-level finding."""
    from picotron_tpu.analysis import ShardcheckError, preflight
    from picotron_tpu.parallel import sharding as sharding_mod

    cfg = mkcfg()
    real = sharding_mod.param_specs

    def broken(cfg):
        specs = real(cfg)
        del specs["embedding"]
        return specs

    monkeypatch.setattr(sharding_mod, "param_specs", broken)
    with pytest.raises(ShardcheckError, match="embedding"):
        preflight(cfg, checks=("spec",))


def test_preflight_env_escape_hatch(monkeypatch):
    from picotron_tpu.analysis import preflight
    from picotron_tpu.parallel import sharding as sharding_mod

    monkeypatch.setenv("PICOTRON_PREFLIGHT", "0")
    monkeypatch.setattr(sharding_mod, "param_specs",
                        lambda cfg: (_ for _ in ()).throw(AssertionError(
                            "preflight must be skipped")))
    rep = preflight(mkcfg())
    assert rep.ok() and not rep.findings


# ---------------------------------------------------------------------------
# donation edge cases (shardflow satellites)
# ---------------------------------------------------------------------------


def test_donation_aliased_into_two_outputs():
    """A donated buffer whose ORIGINAL also escapes as a second output:
    XLA can alias it into at most one, but the donation *request* is what
    the static check audits — it stays recorded on every leaf, through
    both the args_info path and the HLO-text fallback. The runtime cost of
    the unusable alias is the CompileWatch/goodput layer's to observe."""
    state, batch = _toy_state_batch()

    def step(s, b):
        new = jax.tree.map(lambda x: x + b[0].sum(), s)
        return new, s  # the donated inputs escape unmodified too

    low = jax.jit(step, donate_argnums=(0,)).lower(state, batch)
    rep = check_donation(low, state, batch)
    assert rep.ok(), rep.render(verbose=True)
    assert rep.info["donation"]["donated"] == \
        rep.info["donation"]["state_leaves"] == 3
    # text-fallback parity on the same module
    rep_text = check_donation(low.as_text(), state, batch)
    assert rep_text.ok(), rep_text.render(verbose=True)
    assert rep_text.info["donation"] == rep.info["donation"]


def test_donation_text_fallback_parity():
    """Donation attributes must read identically from the
    Lowered.args_info view and the raw StableHLO text view."""
    cfg = mkcfg(dist=dict(pp_size=2, dp_size=2), ga=2)
    low = lower_train_step(cfg)
    rep_info = check_donation(low.lowered, low.state, low.batch)
    rep_text = check_donation(low.text, low.state, low.batch)
    assert rep_info.ok(), rep_info.render(verbose=True)
    assert rep_info.info["donation"] == rep_text.info["donation"]
    assert rep_info.info["donation"]["donated"] == \
        rep_info.info["donation"]["state_leaves"]


def test_donation_full_coverage_through_fused_bwd():
    """The fused grad engine's manual backward must not cost donation on
    any TrainState leaf — its scan carries grads through jaxpr-level
    custom plumbing that once made the aliaser lose track."""
    cfg = _sp_cfg()
    low = lower_train_step(cfg)
    rep = check_donation(low.lowered, low.state, low.batch)
    assert rep.ok(), rep.render(verbose=True)
    assert rep.info["donation"]["donated"] == \
        rep.info["donation"]["state_leaves"]


# ---------------------------------------------------------------------------
# source lint: uncommitted device_put (shardflow satellite)
# ---------------------------------------------------------------------------


def test_source_lint_flags_uncommitted_device_put(tmp_path):
    """jax.device_put with no sharding/device produces an UNCOMMITTED
    array (the variant hazard); with an explicit placement, a device=
    kwarg, or a suppression it passes — positive and negative halves."""
    bad = tmp_path / "puts.py"
    bad.write_text(
        "import jax\n"
        "from jax import device_put\n"
        "def feed(x, sh):\n"
        "    a = jax.device_put(x)\n"                       # line 4: flags
        "    b = jax.device_put(x, sh)\n"                   # positional ok
        "    c = jax.device_put(x, device=sh)\n"            # kwarg ok
        "    d = device_put(x)\n"                           # line 7: flags
        "    e = jax.device_put(x)  # shardcheck: ok\n"     # suppressed
        "    return a, b, c, d, e\n")
    rep = lint_sources([str(bad)])
    assert rep.ok()  # warnings, not errors
    hits = [f for f in rep.warnings() if "UNCOMMITTED" in f.message]
    lines = sorted(int(f.path.rsplit(":", 1)[1]) for f in hits)
    assert lines == [4, 7], rep.render(verbose=True)


def test_source_lint_repo_has_no_uncommitted_device_puts():
    """The rule holds repo-wide: every device_put in picotron_tpu/ passes
    an explicit sharding (serve/engine.py's commit-everything discipline,
    checkpoint restore placement, offload host transfers)."""
    rep = lint_sources()
    assert not [f for f in rep.warnings()
                if "UNCOMMITTED" in f.message], rep.render(verbose=True)


# ---------------------------------------------------------------------------
# runs/ preset gate (shardflow tier-1 regression fence)
# ---------------------------------------------------------------------------


def test_shardflow_runs_gate():
    """Variant audit over every shipped runs/ preset, fenced against
    tests/data/shardflow_baseline.json. Fails on REGRESSIONS only: a
    proven jit entry turning unproven, or a config newly failing to
    trace. Improvements pass — regenerate the baseline to lock them in."""
    import subprocess

    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "shardflow_baseline.json")) as f:
        baseline = json.load(f)["configs"]
    cfgs = sorted(
        __import__("glob").glob(os.path.join(root, "runs", "*",
                                             "config.json")))
    assert cfgs, "runs/ presets missing"
    args = [sys.executable, os.path.join(root, "tools", "shardcheck.py"),
            "--variants", "--json"]
    for c in cfgs:
        args += ["--config", c]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(args, capture_output=True, text=True, env=env,
                         timeout=540, cwd=root)
    rows = [json.loads(line) for line in res.stdout.strip().splitlines()]
    assert len(rows) == len(cfgs), res.stderr[-2000:]

    problems = []
    for row in rows:
        name = os.path.basename(os.path.dirname(row["config"]))
        base = baseline.get(name)
        assert base is not None, f"new preset {name}: add it to the baseline"
        if "fatal" in row:
            if base["status"] != "fatal":
                problems.append(f"{name}: newly fatal — {row['fatal']}")
            continue
        if base["status"] == "fatal":
            continue  # improvement: traces now where it could not before
        var = row["info"]["variants"]
        for entry in ("train_step", "serve", "mpmd_stages"):
            if (base.get(f"{entry}_proven")
                    and not var.get(entry, {}).get("proven")):
                problems.append(f"{name}: {entry} no longer proven "
                                f"compile-once")
    assert not problems, "\n".join(problems)
