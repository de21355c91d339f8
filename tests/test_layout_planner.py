"""Layout-planner tests: the search must rank layouts deterministically,
hold the global batch constant across candidates, prune HBM non-fits, and
— the acceptance bar — never propose a config tools/memcheck.py rejects.
Also the fast-tier smoke the CI satellite asks for: the cost model priced
over every preset in runs/ (analytic — a cost-model regression breaks
tier-1, not a fleet decision)."""

import importlib.util
import json
import math
import os

import pytest

from picotron_tpu.analysis.cost_model import CostModel
from picotron_tpu.analysis.planner import (
    best_point, candidate_configs, estimate_hbm_gib, plan, planner_gap,
    reprice_traced, verify_hbm,
)
from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, TrainingConfig, load_config,
    resolve_preset,
)

ROOT = os.path.join(os.path.dirname(__file__), "..")


def tiny_base(ga=8, mbs=1, seq=64, model="debug-tiny"):
    cfg = Config(
        distributed=DistributedConfig(),
        model=ModelConfig(name=model, **resolve_preset(model)),
        training=TrainingConfig(seq_length=seq, micro_batch_size=mbs,
                                gradient_accumulation_steps=ga),
    )
    cfg.validate()
    return cfg


def load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# enumeration + ranking
# ---------------------------------------------------------------------------


def test_candidates_cover_axes_and_hold_global_batch():
    base = tiny_base(ga=8)
    gb = base.global_batch_size
    cands = candidate_configs(base, 8)
    assert len(cands) > 20
    layouts = {(c.distributed.dp_size, c.distributed.tp_size,
                c.distributed.pp_size, c.distributed.cp_size)
               for c in cands}
    assert (8, 1, 1, 1) in layouts and (1, 2, 4, 1) in layouts
    for c in cands:
        assert c.distributed.world_size == 8
        assert c.global_batch_size == gb, c
    # ep > 1 requires a MoE model: no dense candidate may carry it
    assert all(c.distributed.ep_size == 1 for c in cands)
    moe = candidate_configs(tiny_base(model="debug-tiny-moe"), 8)
    assert any(c.distributed.ep_size > 1 for c in moe)


def test_invalid_layouts_are_skipped():
    # debug-tiny has 4 heads / 2 kv heads: tp=8 (heads % tp != 0) and
    # pp=8 (> 4 layers) must not appear
    cands = candidate_configs(tiny_base(), 8)
    assert all(c.distributed.tp_size <= 2 for c in cands)
    assert all(c.distributed.pp_size <= 4 for c in cands)


def _apply_overrides(line, raw):
    """The tools/memcheck.py --override application: dotted paths into the
    raw JSON, JSON values where they parse, bare strings otherwise
    (legitimate only for string-typed fields)."""
    for ov in line.split()[1:]:
        dotted, _, val = ov.partition("=")
        node = raw
        *path, key = dotted.split(".")
        for part in path:
            node = node.setdefault(part, {})
        try:
            node[key] = json.loads(val)
        except ValueError:
            node[key] = val
    return raw


def test_plan_enumerates_mpmd():
    """Every pp>1 layout is priced under both executors (plus interleaved
    variants where v divides the per-group layer slots)."""
    pts = plan(tiny_base(), 8, CostModel("v5e"))
    mpmd_pts = [p for p in pts if "mpmd" in p.label]
    assert mpmd_pts, [p.label for p in pts]
    assert any("interleaved" in p.label for p in mpmd_pts)
    assert any("mpmd-1f1b" in p.label for p in mpmd_pts)


@pytest.mark.parametrize("want", ["mpmd-interleaved", "+sp", "mesh-",
                                  "ulysses"])
def test_overrides_line_round_trips(want):
    """A plan point's --override line, applied the way tools/memcheck.py
    applies it, rebuilds the point's layout: every axis the planner
    enumerates is on the line."""
    from picotron_tpu.config import config_from_dict

    point = next(p for p in plan(tiny_base(), 8, CostModel("v5e"))
                 if want in p.label)
    cfg = config_from_dict(_apply_overrides(
        point.overrides_line(),
        {"model": {"name": "debug-tiny"},
         "training": {"seq_length": 64, "micro_batch_size": 1,
                      "gradient_accumulation_steps": 8}}))  # validates
    assert cfg.distributed == point.cfg.distributed
    assert cfg.pipeline == point.cfg.pipeline
    assert cfg.training.optimizer_offload == \
        point.cfg.training.optimizer_offload


@pytest.mark.parametrize("model,flags,n", [
    ("debug-tiny", True, 111), ("debug-tiny", False, 15),
    ("debug-tiny-moe", True, 142),
])
def test_candidate_count_8_chips(model, flags, n):
    """The layout space for 8 chips: the five parallel axes, and with
    flags the sp / zero1 / offload toggles, the pipeline executors and the
    cp flavors — no other axis. (The parent enumerated the same points
    beside its TP-strategy axes: 111 of its 131 for debug-tiny.)"""
    assert len(candidate_configs(tiny_base(model=model), 8,
                                 flags=flags)) == n


def test_plan_ranks_and_is_deterministic():
    base = tiny_base()
    model = CostModel("v5e")
    pts = plan(base, 8, model)
    assert pts, "8 chips of debug-tiny must have feasible layouts"
    times = [p.cost.total_s for p in pts]
    assert times == sorted(times)
    assert [p.label for p in pts] == [p.label for p in plan(base, 8, model)]
    for p in pts:
        assert p.hbm_fits
        assert math.isfinite(p.cost.total_s) and p.cost.total_s > 0


def test_hbm_prune_rejects_what_cannot_fit():
    base = tiny_base()
    # debug-tiny needs ~MBs; a 1e-5 GiB capacity rejects everything
    assert plan(base, 8, CostModel("v5e"), hbm_gib=1e-5) == []
    pts = plan(base, 8, CostModel("v5e"), hbm_gib=1e-5,
               include_infeasible=True)
    assert pts and not any(p.hbm_fits for p in pts)


def test_estimate_hbm_monotone_in_sharding():
    # more model sharding -> less per-device memory
    whole = estimate_hbm_gib(tiny_base())
    tp2 = estimate_hbm_gib(tiny_base().replace(
        distributed=DistributedConfig(tp_size=2)))
    assert tp2 < whole
    off = estimate_hbm_gib(tiny_base().replace(
        training=TrainingConfig(seq_length=64,
                                optimizer_offload=True)))
    assert off < whole


def test_planner_gap_flags_slow_layout():
    # a deliberately comm-heavy layout of a tiny model must show a
    # positive gap vs the planner's best at the same chip count
    cfg = tiny_base().replace(
        distributed=DistributedConfig(tp_size=2, cp_size=4))
    cur, best, gap = planner_gap(cfg, CostModel("v5e"))
    assert best is not None
    assert gap >= 0.0
    assert best.cost.total_s <= cur.total_s


# ---------------------------------------------------------------------------
# acceptance: memcheck agreement
# ---------------------------------------------------------------------------


def test_winner_passes_memcheck_and_rejected_points_are_skipped():
    """The planner must never propose a config tools/memcheck.py rejects:
    the verified winner's XLA memory breakdown fits the capacity, and a
    capacity below the winner's own footprint forces verify to reject."""
    base = tiny_base(ga=2)
    model = CostModel("v5e")
    pts = plan(base, 8, model)
    winner = best_point(pts, verify=True, hbm_gib=model.gen.hbm_gib,
                        model=model)
    assert winner is not None
    assert winner.memcheck_ok is True
    assert winner.memcheck_gib <= model.gen.hbm_gib
    # the analytic screen agreed with memcheck's verdict on the winner
    assert winner.hbm_fits
    # and a capacity the measured footprint exceeds must flip the verdict
    tight = winner.memcheck_gib / 2
    assert verify_hbm(pts[0], tight) is False
    assert pts[0].memcheck_ok is False


def test_reprice_traced_top_points():
    base = tiny_base(ga=2)
    model = CostModel("v5e")
    pts = plan(base, 8, model)
    pts = reprice_traced(pts, model, top_k=2)
    traced = [p for p in pts if p.traced_comm_s is not None]
    assert len(traced) == 2
    for p in traced:
        assert p.traced_comm_s >= 0


# ---------------------------------------------------------------------------
# CLI + runs/ preset smoke (the fast-tier CI gate)
# ---------------------------------------------------------------------------


def test_cli_plan_chips8(capsys):
    lp = load_tool("layout_planner")
    rc = lp.main(["--chips", "8", "--model", "debug-tiny", "--seq", "64",
                  "--top", "5", "--json"])
    assert rc == 0
    rows = [json.loads(l) for l in
            capsys.readouterr().out.strip().splitlines()]
    assert 1 <= len(rows) <= 5
    assert rows[0]["predicted_step_ms"] > 0
    assert rows[0]["overrides"].startswith("--override ")
    steps = [r["predicted_step_ms"] for r in rows]
    assert steps == sorted(steps)


def test_cli_validate_sweep_reproduces_measured_ranking(capsys):
    """Acceptance: the planner CLI reproduces the measured ranking of the
    SWEEP_r03–r04 configs (per-round Spearman)."""
    lp = load_tool("layout_planner")
    rc = lp.main(["--validate-sweep", "--json"])
    assert rc == 0
    ra = json.loads(capsys.readouterr().out)
    assert ra["min_per_round"] >= 0.85
    assert ra["pooled"] >= 0.85


def test_cli_markdown_table(capsys):
    lp = load_tool("layout_planner")
    rc = lp.main(["--chips", "8", "--model", "debug-tiny", "--seq", "64",
                  "--markdown", "--top", "3"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "| rank | layout |" in out
    assert "predicted fastest:" in out


RUN_PRESETS = sorted(
    d for d in os.listdir(os.path.join(ROOT, "runs"))
    if os.path.isfile(os.path.join(ROOT, "runs", d, "config.json")))


@pytest.mark.parametrize("preset", RUN_PRESETS)
def test_cost_model_prices_every_runs_preset(preset):
    """Analytic smoke over the presets in runs/: every preset must price
    to a finite positive step time with a sane decomposition on both
    shipped generations, and the planner must find a feasible layout at
    the preset's own chip count on its natural generation. Pure
    arithmetic — this is the tier-1 tripwire for cost-model regressions."""
    cfg = load_config(os.path.join(ROOT, "runs", preset, "config.json"))
    gen = "v5p" if "v5p" in preset else "v5e"
    cost = CostModel(gen).predict(cfg)
    assert math.isfinite(cost.total_s) and cost.total_s > 0
    assert cost.compute_s > 0
    assert cost.exposed_comm_s >= 0
    if cfg.distributed.world_size > 1:
        assert cost.comm, f"{preset}: multi-chip layout priced zero comm"
        cur, best, gap = planner_gap(cfg, CostModel(gen))
        assert best is not None, f"{preset}: planner found no layout"
        assert math.isfinite(gap)
        # a negative gap is legal only when the config itself fails the
        # HBM screen (the feasible best can then be slower than an
        # infeasible incumbent)
        from picotron_tpu.analysis.planner import (
            _HBM_MARGIN, estimate_hbm_gib,
        )

        if gap < 0:
            assert estimate_hbm_gib(cfg) > \
                CostModel(gen).gen.hbm_gib * _HBM_MARGIN, preset


def test_shardcheck_cli_cost_smoke(capsys):
    """tools/shardcheck.py --cost over a preset: the costed ranking rides
    the audit report (the CI-wired smoke the ISSUE asks for)."""
    sc = load_tool("shardcheck")
    rc = sc.main(["--preset", "tiny-dense", "--cost", "--json"])
    assert rc == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[0])
    assert row["ok"]
    pc = row["info"]["collectives"]["predicted_comm"]
    assert pc["total_ms"] > 0
    assert row["cost"]["predicted_step_ms"] > 0
    assert row["cost"]["planner_best"]
