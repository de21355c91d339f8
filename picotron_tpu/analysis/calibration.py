"""Fit + validate the cost model against the measured rows on disk.

The repo carries real measured step times: the per-round benchmark sweeps
(SWEEP_r03–r04.jsonl), single-run BENCH_*.json rows, and — when a run has
one — the per-phase timings in telemetry.jsonl. This module turns those
into (config, measured tokens/s) pairs, fits the Calibration constants the
rows can pin down (dense-matmul efficiency curve, attention efficiency,
offload PCIe bandwidth — all the sweep rows are single-chip, so the ICI
side stays analytic until a multi-chip cell is measured), and scores rank agreement: the cost model's one job is ordering
layouts, so the metric is Spearman correlation between predicted and
measured tokens/s within each sweep round.

`mfu_<Model>-<L>L_seq<S>` metric names carry the model shape; mbs /
grad-acc / offload come from the row's `config` string when present
(r05+) and otherwise from the benchmark matrix those rounds ran
(bench.py SWEEP — frozen here as _LEGACY_SWEEP so old rows stay
interpretable even if the live matrix moves).
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass
from typing import Iterable, Optional

from picotron_tpu.analysis.cost_model import (
    Calibration, CostModel, DEFAULT_CALIBRATION, spearman,
)
from picotron_tpu.config import (
    Config, ModelConfig, TrainingConfig, resolve_preset,
)

_RE_METRIC = re.compile(r"^mfu_(.+)-(\d+)L_seq(\d+)")

# (model, layers, seq) -> training knobs, for rows predating the per-row
# `config` string. Mirrors the bench.py SWEEP matrix as run in r03/r04.
_LEGACY_SWEEP: dict[tuple, dict] = {
    ("SmolLM-360M", 32, 2048): dict(mbs=6, ga=1),
    ("SmolLM-1.7B", 8, 4096): dict(mbs=2, ga=1),
    ("SmolLM-1.7B", 4, 16384): dict(mbs=1, ga=1),
    ("SmolLM-1.7B", 8, 2048): dict(mbs=5, ga=1),
    ("SmolLM-1.7B", 24, 4096): dict(mbs=1, ga=64, offload=True,
                                    remat_policy="dots_attn"),
    ("SmolLM-1.7B", 24, 2048): dict(mbs=2, ga=64, offload=True,
                                    remat_policy="dots_attn"),
    ("Llama-2-7B", 4, 4096): dict(mbs=2, ga=16, offload=True,
                                  remat_policy="dots_attn"),
    ("Mixtral-8x7B", 1, 2048): dict(mbs=2, ga=64, offload=True,
                                    remat_policy="dots"),
}


@dataclass(frozen=True)
class MeasuredPoint:
    """One measured configuration: the Config it ran and what it achieved."""

    cfg: Config
    tokens_per_sec_per_chip: float
    metric: str
    source: str      # file the row came from (its round groups rankings)
    mfu: Optional[float] = None


def _parse_config_string(s: str) -> dict:
    """mbs/ga/offload/remat out of an r05-style row config string like
    'mbs3 ga43 dots_attn offload + fused grad engine'."""
    out: dict = {}
    m = re.search(r"\bmbs(\d+)\b", s)
    if m:
        out["mbs"] = int(m.group(1))
    m = re.search(r"\bga(\d+)\b", s)
    if m:
        out["ga"] = int(m.group(1))
    if "offload" in s:
        out["offload"] = True
    for pol in ("dots_attn", "dots_norms", "dots_lean", "dots_offload",
                "dots", "full"):
        if re.search(rf"\b{pol}\b", s):
            out["remat_policy"] = pol
            break
    return out


def row_to_point(row: dict, source: str) -> Optional[MeasuredPoint]:
    """A SWEEP/BENCH JSON row -> MeasuredPoint, or None for rows that are
    not mfu measurements (decode rows, error rows)."""
    metric = row.get("metric", "")
    m = _RE_METRIC.match(metric)
    tps = row.get("tokens_per_sec_per_chip")
    if not m or not isinstance(tps, (int, float)) or tps <= 0:
        return None
    model, layers, seq = m.group(1), int(m.group(2)), int(m.group(3))
    try:
        preset = resolve_preset(model)
    except KeyError:
        return None
    knobs = dict(_LEGACY_SWEEP.get((model, layers, seq), {}))
    knobs.update(_parse_config_string(row.get("config", "")))
    preset["num_hidden_layers"] = layers
    preset["max_position_embeddings"] = max(
        preset.get("max_position_embeddings", seq), seq)
    cfg = Config(
        model=ModelConfig(name=model, **preset),
        training=TrainingConfig(
            seq_length=seq,
            micro_batch_size=knobs.get("mbs", 1),
            gradient_accumulation_steps=knobs.get("ga", 1),
            optimizer_offload=knobs.get("offload", False),
            remat_policy=knobs.get("remat_policy", "dots"),
            adam_moments_dtype="bfloat16",  # the bench default
        ),
    )
    cfg.validate()
    return MeasuredPoint(cfg, float(tps), metric, source,
                         mfu=row.get("value"))


def load_measured_rows(paths: Optional[Iterable[str]] = None,
                       root: Optional[str] = None) -> list[MeasuredPoint]:
    """MeasuredPoints from SWEEP_*.jsonl (one row per line) and
    BENCH_*.json (the driver wrapper whose `tail` holds the bench output)
    files. Default: every SWEEP_r*.jsonl in `root` (the repo root)."""
    if paths is None:
        root = root or _repo_root()
        paths = sorted(
            os.path.join(root, f) for f in os.listdir(root)
            if re.match(r"SWEEP_r\d+\.jsonl$", f))
    points = []
    for path in paths:
        name = os.path.basename(path)
        with open(path) as f:
            text = f.read()
        for line in text.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            # BENCH_*.json wrappers nest the row under "parsed"
            if isinstance(row.get("parsed"), dict):
                row = row["parsed"]
            pt = row_to_point(row, name)
            if pt is not None:
                points.append(pt)
    return points


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# Fitting
# ---------------------------------------------------------------------------


def _sq_log_err(model: CostModel, points: list[MeasuredPoint]) -> float:
    import math

    err = 0.0
    for p in points:
        pred = model.predict(p.cfg).tokens_per_sec_per_chip
        err += math.log(pred / p.tokens_per_sec_per_chip) ** 2
    return err / len(points)


def fit_calibration(points: list[MeasuredPoint],
                    generation: str = "v5e",
                    start: Calibration = DEFAULT_CALIBRATION,
                    rounds: int = 3) -> Calibration:
    """Coordinate-descent least squares (on log step time) over the four
    constants single-chip rows can identify: eff_max, h_half, eff_attn,
    pcie_bandwidth. Deterministic and dependency-free — a few hundred
    analytic predictions, well under a second."""
    if not points:
        return start
    from dataclasses import replace

    space = {
        "eff_max": [start.eff_max * f for f in
                    (0.85, 0.95, 1.0, 1.05, 1.15)],
        "h_half": [start.h_half * f for f in (0.6, 0.8, 1.0, 1.25, 1.6)],
        "eff_attn": [0.28, 0.34, 0.40, 0.48, 0.58],
        "pcie_bandwidth": [start.pcie_bandwidth * f for f in
                           (0.6, 0.8, 1.0, 1.3, 1.7)],
    }
    best = start
    best_err = _sq_log_err(CostModel(generation, best), points)
    for _ in range(rounds):
        for key, grid in space.items():
            for val in grid:
                cand = replace(best, **{key: val})
                err = _sq_log_err(CostModel(generation, cand), points)
                if err < best_err - 1e-12:
                    best, best_err = cand, err
    return best


# ---------------------------------------------------------------------------
# Validation: per-round rank agreement
# ---------------------------------------------------------------------------


def rank_agreement(points: list[MeasuredPoint],
                   model: Optional[CostModel] = None) -> dict:
    """Spearman correlation between predicted and measured tokens/s/chip,
    per source file (rounds are ranked internally — cross-round rows mix
    code versions) plus pooled. Sources with < 3 rows are skipped."""
    model = model or CostModel("v5e")
    by_src: dict[str, list[MeasuredPoint]] = {}
    for p in points:
        by_src.setdefault(p.source, []).append(p)
    out: dict = {"per_round": {}, "rows": []}
    all_pred, all_meas = [], []
    for src, pts in sorted(by_src.items()):
        pred = [model.predict(p.cfg).tokens_per_sec_per_chip for p in pts]
        meas = [p.tokens_per_sec_per_chip for p in pts]
        for p, pr in zip(pts, pred):
            out["rows"].append({
                "metric": p.metric, "source": src,
                "measured_tps_chip": round(p.tokens_per_sec_per_chip, 1),
                "predicted_tps_chip": round(pr, 1),
            })
        all_pred += pred
        all_meas += meas
        if len(pts) >= 3:
            out["per_round"][src] = round(spearman(pred, meas), 4)
    if len(all_meas) >= 3:
        out["pooled"] = round(spearman(all_pred, all_meas), 4)
    vals = out["per_round"].values()
    out["min_per_round"] = min(vals) if vals else None
    return out


# ---------------------------------------------------------------------------
# Telemetry-stream calibration hooks
# ---------------------------------------------------------------------------


def measured_step_seconds(events: list[dict]) -> Optional[dict]:
    """Per-step phase medians out of a telemetry.jsonl event list (the
    tools/telemetry_report.py schema): {'step_s': median step-phase secs,
    'sync_s': median sync-phase secs} — the measured side the `comm` row
    compares the model against, and a per-run calibration residual."""
    phases: dict[str, list[float]] = {}
    for e in events:
        if e.get("kind") == "phase" and isinstance(e.get("secs"),
                                                   (int, float)):
            phases.setdefault(e.get("phase", "?"), []).append(e["secs"])

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2] if xs else None

    if not phases.get("step"):
        return None
    return {"step_s": median(phases["step"]),
            "sync_s": median(phases.get("sync", [])),
            "n_steps": len(phases["step"])}
