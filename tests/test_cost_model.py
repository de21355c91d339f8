"""ICI cost-model tests: analytic hop counts and per-collective byte
volumes pinned for known mesh shapes (no hardware, pure arithmetic), axis
placement by generation, traced-op pricing, and the acceptance bar — the
model must reproduce the measured ranking of the SWEEP_r03–r04 configs
(Spearman rank agreement, per round)."""

import math

import pytest

from picotron_tpu.analysis.calibration import (
    load_measured_rows, measured_step_seconds, rank_agreement, row_to_point,
)
from picotron_tpu.analysis.cost_model import (
    GENERATIONS, AxisLink, Calibration, CostModel, line_diameter,
    place_axes, resolve_generation, ring_diameter, spearman,
    with_calibration,
)
from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, TrainingConfig, resolve_preset,
)


def mkcfg(model="debug-tiny", seq=64, mbs=1, ga=1, dist=None, train=None):
    cfg = Config(
        distributed=DistributedConfig(**(dist or {})),
        model=ModelConfig(name=model, **resolve_preset(model)),
        training=TrainingConfig(seq_length=seq, micro_batch_size=mbs,
                                gradient_accumulation_steps=ga,
                                **(train or {})),
    )
    cfg.validate()
    return cfg


# ---------------------------------------------------------------------------
# hop counts + placement
# ---------------------------------------------------------------------------


def test_ring_vs_line_diameters():
    # ring: bidirectional wraparound halves the worst hop distance
    assert ring_diameter(8) == 4
    assert ring_diameter(16) == 8
    assert ring_diameter(3) == 1
    # line (torus slice without wraparound): worst hop walks the slice
    assert line_diameter(8) == 7
    assert line_diameter(2) == 1


def test_generation_wrap_rule():
    # v5e sub-slices of the 2D torus are meshes: an 8-axis is a line;
    # v5p 3D slices close into rings from a full side of 4
    v5e = place_axes({"tp": 8}, GENERATIONS["v5e"])["tp"]
    v5p = place_axes({"tp": 8}, GENERATIONS["v5p"])["tp"]
    assert v5e.kind == "line" and v5e.diameter == 7
    assert v5p.kind == "ring" and v5p.diameter == 4
    # a full v5e 16-ring wraps
    assert place_axes({"tp": 16}, GENERATIONS["v5e"])["tp"].kind == "ring"


def test_placement_innermost_axes_get_dedicated_dims():
    links = place_axes({"dp": 2, "tp": 4, "cp": 2, "pp": 1, "ep": 1},
                       GENERATIONS["v5e"])
    # tp and cp (innermost) own the two v5e torus dims at full bandwidth;
    # dp folds and pays a stride penalty
    assert links["tp"].stride == 1 and links["cp"].stride == 1
    assert links["dp"].stride > 1
    assert links["dp"].bandwidth < links["tp"].bandwidth
    # size-1 axes are not placed at all
    assert "pp" not in links and "ep" not in links


def test_v5p_three_axes_fit_without_folding():
    links = place_axes({"dp": 2, "tp": 4, "cp": 2, "pp": 1, "ep": 1},
                       GENERATIONS["v5p"])
    assert all(l.stride == 1 for l in links.values())


def test_resolve_generation_from_device_kind():
    assert resolve_generation("TPU v5 lite").name == "v5e"
    assert resolve_generation("TPU v5p").name == "v5p"
    assert resolve_generation("TPU v4").name == "v4"
    # a kind outside the table is an error, never the v5e constants
    for unknown in ("cpu", "cpu-test-device", "TPU v9x", "TPU v3"):
        with pytest.raises(ValueError):
            resolve_generation(unknown)


def test_device_peak_flops_raises_on_unknown_kind():
    from types import SimpleNamespace

    from picotron_tpu.utils import TPU_PEAK_FLOPS, device_peak_flops

    assert device_peak_flops(SimpleNamespace(device_kind="TPU v5 lite")) \
        == TPU_PEAK_FLOPS["v5e"]
    assert device_peak_flops(SimpleNamespace(device_kind="TPU v3")) \
        == TPU_PEAK_FLOPS["v3"]
    for unknown in ("cpu", "TPU v9x", "NVIDIA H100"):
        with pytest.raises(ValueError):
            device_peak_flops(SimpleNamespace(device_kind=unknown))


# ---------------------------------------------------------------------------
# per-collective formulas (byte volumes pinned, alpha removed)
# ---------------------------------------------------------------------------


def _no_latency(gen="v5e"):
    return CostModel(gen, Calibration(alpha_link_s=0.0))


def test_collective_byte_volume_factors():
    cm = _no_latency()
    bw = 45e9
    ring = AxisLink("tp", 4, "ring", bw, 1)
    v = 1e9
    # all-gather / reduce-scatter: V*(n-1)/n over both ring directions
    ag = cm.collective_secs("all_gather", v, ring)
    assert ag == pytest.approx(v * 3 / 4 / (2 * bw))
    assert cm.collective_secs("reduce_scatter", v, ring) == pytest.approx(ag)
    # all-reduce = reduce-scatter + all-gather
    assert cm.collective_secs("all_reduce", v, ring) == pytest.approx(2 * ag)
    # neighbor ppermute: one payload per link
    assert cm.collective_secs("collective_permute", v, ring) == \
        pytest.approx(v / bw)
    # all-to-all: mean distance n/4, both directions
    assert cm.collective_secs("all_to_all", v, ring) == \
        pytest.approx(v * 4 / (4 * 2 * bw))


def test_line_pays_more_than_ring():
    cm = _no_latency()
    bw = 45e9
    ring = AxisLink("cp", 8, "ring", bw, 1)
    line = AxisLink("cp", 8, "line", bw, 1)
    for kind in ("all_gather", "all_reduce", "all_to_all",
                 "collective_permute"):
        assert cm.collective_secs(kind, 1e9, line) > \
            cm.collective_secs(kind, 1e9, ring)
    # the line ppermute wrap walks the whole slice
    assert cm.collective_secs("collective_permute", 1e9, line) == \
        pytest.approx(1e9 * 7 / bw)


def test_size_one_axis_costs_nothing():
    cm = _no_latency()
    one = AxisLink("tp", 1, "line", 45e9, 1)
    assert cm.collective_secs("all_reduce", 1e9, one) == 0.0


# ---------------------------------------------------------------------------
# traced-op pricing
# ---------------------------------------------------------------------------


def test_price_ops_matches_axes():
    from picotron_tpu.analysis.collectives import CollectiveOp

    cfg = mkcfg(dist=dict(dp_size=2, tp_size=2, cp_size=2), ga=2)
    cm = _no_latency()
    ops = [
        # grad sync over the fused data axes dp*ep*cp = 4
        CollectiveOp("all_reduce", 4, 2, 1 << 20, (256, 1024), "f32", 1),
        # a tp-sized all-gather
        CollectiveOp("all_gather", 2, 4, 1 << 18, (64, 1024), "bf16", 2),
        # the cp ring
        CollectiveOp("collective_permute", None, 8, 1 << 16, (64, 256),
                     "bf16", 3),
        # compiled-away op must not be priced
        CollectiveOp("all_reduce", 1, 8, 1 << 20, (1,), "f32", 4),
    ]
    priced = cm.price_ops(cfg, ops)
    assert len(priced) == 3
    by_line = {p["line"]: p for p in priced}
    assert set(by_line[1]["axes"]) == {"dp", "cp"}  # ep=1 drops out
    assert by_line[2]["axes"] in (("tp",), ("cp",))  # both size 2
    assert by_line[3]["axes"] == ("cp",)
    assert all(p["secs"] > 0 for p in priced)


def test_priced_schedule_from_lowered_text():
    # lower a dp=2 step once and price its real schedule
    cfg = mkcfg(dist=dict(dp_size=2), ga=2)
    cm = CostModel("v5e")
    priced, comm_s = cm.priced_schedule(cfg)
    assert priced, "a dp=2 step must emit at least the grad all-reduce"
    assert comm_s > 0


# ---------------------------------------------------------------------------
# analytic step prediction
# ---------------------------------------------------------------------------


def test_predict_decomposition_consistency():
    cfg = mkcfg(dist=dict(dp_size=2, tp_size=2, pp_size=2), ga=4)
    cost = CostModel("v5e").predict(cfg)
    assert cost.n_chips == 8
    assert cost.compute_s > 0
    # spmd lockstep-scan bubble: compute * 2*(pp-1)/ga (full-price idle
    # ticks — the mpmd executor halves this, test below)
    assert cost.bubble_s == pytest.approx(cost.compute_s * 2 / 4)
    assert cost.total_s >= cost.compute_s + cost.bubble_s
    assert cost.exposed_comm_s <= cost.comm_s
    names = {t.name for t in cost.comm}
    assert "grad_sync" in names and "tp_psum" in names
    assert "pp_boundary" in names
    d = cost.as_dict()
    assert d["predicted_step_ms"] == pytest.approx(cost.total_s * 1e3,
                                                   abs=5e-4)  # ms rounding


def test_predict_mpmd_bubble_and_label():
    """The executor knob changes only the bubble term: mpmd halves the
    spmd fill/drain (and divides by v under interleaving) but pays a
    host-dispatch charge per scheduled program — at tiny compute the
    dispatch dominates, at scale the halved bubble wins."""
    import dataclasses

    from picotron_tpu.analysis.cost_model import layout_label
    from picotron_tpu.config import PipelineConfig

    base = mkcfg(dist=dict(dp_size=2, tp_size=2, pp_size=2), ga=4)
    cm = CostModel("v5e")
    spmd = cm.predict(base)
    for pl, v in [(PipelineConfig(executor="mpmd"), 1),
                  (PipelineConfig(executor="mpmd", schedule="interleaved",
                                  interleave=2), 2)]:
        cfg = dataclasses.replace(base, pipeline=pl)
        cfg.validate()
        cost = cm.predict(cfg)
        assert cost.compute_s == pytest.approx(spmd.compute_s)
        dispatch = 2 * 4 * 2 * v * cm.calib.host_dispatch_s
        assert cost.bubble_s == pytest.approx(
            cost.compute_s * 1 / (v * 4) + dispatch)
        assert "mpmd" in layout_label(cfg)
    assert "v2" in layout_label(
        dataclasses.replace(base, pipeline=PipelineConfig(
            executor="mpmd", schedule="interleaved", interleave=2)))
    # the dispatch term scales with ga*pp*v; zeroing it makes mpmd's
    # bubble strictly half of spmd's at v=1
    free = with_calibration(cm, host_dispatch_s=0.0)
    cfg = dataclasses.replace(base, pipeline=PipelineConfig(executor="mpmd"))
    assert free.predict(cfg).bubble_s == pytest.approx(spmd.bubble_s / 2)


def test_predict_prices_every_promised_axis():
    # the same per-axis promises audit_collectives enforces on traces
    cfg = mkcfg(model="debug-tiny-moe", dist=dict(ep_size=2, dp_size=2),
                ga=2)
    names = {t.name for t in CostModel("v5e").predict(cfg).comm}
    assert "ep_dispatch" in names
    cfg = mkcfg(dist=dict(cp_size=4), ga=2)
    names = {t.name for t in CostModel("v5e").predict(cfg).comm}
    assert "cp_ring" in names
    cfg = mkcfg(dist=dict(tp_size=2, dp_size=2, sequence_parallel=True),
                ga=2)
    names = {t.name for t in CostModel("v5e").predict(cfg).comm}
    assert "sp_gather" in names and "sp_scatter" in names


def test_offload_term_scales_with_params_and_pcie():
    cfg = mkcfg(ga=4, train=dict(optimizer_offload=True))
    base = CostModel("v5e").predict(cfg)
    assert base.offload_s > 0
    slow = with_calibration(CostModel("v5e"),
                            pcie_bandwidth=1e9).predict(cfg)
    assert slow.offload_s > base.offload_s


def test_dp_weak_scaling():
    # dp grows the global batch: same per-step compute, 8x the tokens
    one = CostModel("v5e").predict(mkcfg())
    eight = CostModel("v5e").predict(mkcfg(dist=dict(dp_size=8)))
    assert eight.tokens_per_step == 8 * one.tokens_per_step
    assert eight.compute_s == pytest.approx(one.compute_s)
    assert eight.tokens_per_sec > one.tokens_per_sec


# ---------------------------------------------------------------------------
# spearman + calibration data plumbing
# ---------------------------------------------------------------------------


def test_spearman_basics():
    assert spearman([1, 2, 3, 4], [10, 20, 30, 40]) == pytest.approx(1.0)
    assert spearman([1, 2, 3, 4], [40, 30, 20, 10]) == pytest.approx(-1.0)
    assert abs(spearman([1, 2, 3, 4], [1, 3, 2, 4])) < 1.0
    with pytest.raises(ValueError):
        spearman([1], [1])


def test_row_to_point_parses_metric_and_config_string():
    pt = row_to_point({
        "metric": "mfu_SmolLM-1.7B-24L_seq2048",
        "tokens_per_sec_per_chip": 8806.1,
        "config": "mbs3 ga43 dots_attn offload + fused grad engine",
    }, "t")
    assert pt is not None
    t = pt.cfg.training
    assert t.micro_batch_size == 3
    assert t.gradient_accumulation_steps == 43
    assert t.optimizer_offload and t.remat_policy == "dots_attn"
    assert pt.cfg.model.num_hidden_layers == 24
    assert pt.cfg.training.seq_length == 2048
    # decode / error rows are not mfu points
    assert row_to_point({"metric": "decode_SmolLM-1.7B-24L_batch8",
                         "value": 793.9}, "t") is None


def test_rank_agreement_matches_measured_sweeps():
    """The acceptance bar: predicted tokens/s must reproduce the measured
    per-round orderings of SWEEP_r03–r04 (each round ranks internally —
    rows from different rounds ran different code)."""
    points = load_measured_rows()
    assert len(points) >= 10, "SWEEP_r03-r04 rows are the fixture"
    ra = rank_agreement(points)
    assert set(ra["per_round"]) == {"SWEEP_r03.jsonl", "SWEEP_r04.jsonl"}
    for src, rho in ra["per_round"].items():
        assert rho >= 0.85, (src, rho, ra["rows"])
    assert ra["pooled"] >= 0.85


def test_predictions_within_2x_of_measured():
    """Ranking is the contract, but the absolute numbers must stay sane:
    every calibrated prediction within 2x of its measured row."""
    model = CostModel("v5e")
    for p in load_measured_rows():
        pred = model.predict(p.cfg).tokens_per_sec_per_chip
        ratio = pred / p.tokens_per_sec_per_chip
        assert 0.5 < ratio < 2.0, (p.metric, ratio)


def test_measured_step_seconds_from_telemetry_events():
    events = [
        {"kind": "phase", "phase": "step", "secs": 0.10, "step": 1},
        {"kind": "phase", "phase": "step", "secs": 0.12, "step": 2},
        {"kind": "phase", "phase": "sync", "secs": 0.01, "step": 1},
        {"kind": "step", "loss": 1.0},
    ]
    m = measured_step_seconds(events)
    assert m["n_steps"] == 2
    assert m["step_s"] == pytest.approx(0.12)
    assert m["sync_s"] == pytest.approx(0.01)
    assert measured_step_seconds([{"kind": "step"}]) is None


def test_audit_collectives_cost_info():
    """audit_collectives(cost_model=...) prices the traced schedule into
    the report's info table (the shardcheck --cost wiring)."""
    from picotron_tpu.analysis import audit_collectives

    cfg = mkcfg(dist=dict(dp_size=2), ga=2)
    rep = audit_collectives(cfg, cost_model=CostModel("v5e"))
    assert rep.ok(), rep.render()
    pc = rep.info["collectives"]["predicted_comm"]
    assert pc["generation"] == "v5e"
    assert pc["total_ms"] > 0
    assert math.isfinite(pc["total_ms"])
    assert pc["by_kind_ms"].get("all_reduce", 0) > 0


# ---------------------------------------------------------------------------
# the megatron schedule's price, pinned across the removal of the other
# TP strategies' pricing (values printed by the tree at cecb420)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dist,total_s,exposed_comm_s,label", [
    (dict(tp_size=2), 0.9557416907686728, 0.14354957653333333,
     "dp1xtp2xpp1xcp1xep1"),
    (dict(tp_size=4), 0.6219964219176697, 0.2159003648,
     "dp1xtp4xpp1xcp1xep1"),
    (dict(tp_size=4, sequence_parallel=True), 0.6231484219176697,
     0.2170523648, "dp1xtp4xpp1xcp1xep1+sp"),
    (dict(tp_size=2, pp_size=2), 0.6824141820320602, 0.07327009635555555,
     "dp1xtp2xpp2xcp1xep1"),
], ids=["tp2", "tp4", "tp4+sp", "tp2+pp2"])
def test_megatron_prediction_unmoved(dist, total_s, exposed_comm_s, label):
    cost = CostModel("v5e").predict(mkcfg(
        model="HuggingFaceTB/SmolLM-1.7B", seq=2048, mbs=2, ga=4, dist=dist))
    assert cost.total_s == pytest.approx(total_s, rel=1e-12)
    assert cost.exposed_comm_s == pytest.approx(exposed_comm_s, rel=1e-12)
    assert cost.config_label == label
