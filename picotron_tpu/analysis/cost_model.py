"""ICI-topology communication cost model — price a layout's step on CPU.

The collective-schedule audit (analysis/collectives.py) says *which*
collectives a dp×tp×pp×cp×ep layout emits; this module says *what they
cost*, so layouts can be ranked by predicted step time without touching
hardware — the ATP (arxiv 2301.08658) / TASP (arxiv 2509.26541) approach:
a static per-axis topology model is enough to order layouts, which turns
"which layout for model X on slice Y?" into a CPU query.

Three parts:

- **Topology** (`IciGeneration`, `place_axes`): per-TPU-generation link
  bandwidth, physical torus dimensionality, and wraparound rule. Mesh axes
  are placed innermost-first (tp, cp, ep, pp, dp) onto physical ICI axes —
  the same contract mesh.py's `_topology_grid` encodes — so tp gets a
  dedicated ring and outer axes fold (modeled as a bandwidth divide by the
  neighbor stride). An axis big enough for wraparound is a **ring**
  (bidirectional, diameter n//2); smaller slices are a **line** (no wrap,
  diameter n-1) — the v5e-vs-v5p distinction the hop-count tests pin.
- **Per-collective formulas** (`collective_secs`): bandwidth-term costs of
  the standard ring algorithms (all-reduce 2·(n-1)/n·V, all-gather /
  reduce-scatter (n-1)/n·V, all-to-all n/8·V per direction, neighbor
  ppermute V) plus an α·hops latency term, per axis placement. `price_ops`
  applies them to the `CollectiveOp` list parsed off a traced schedule.
- **Step model** (`CostModel.predict`): the analytic whole-step time —
  compute (calibrated dense/attention efficiencies), the executor-dependent
  pipeline bubble (spmd lockstep 2(pp-1)/ga; mpmd (pp-1)/(v·ga) plus
  host-dispatch), optimizer-offload PCIe streaming, and the per-class
  comm terms with exposed-fraction weights (a grad all-reduce overlaps the
  backward; an in-layer TP psum does not). Constants live in `Calibration`
  and are fitted against the measured SWEEP/BENCH rows on disk by
  analysis/calibration.py — the model's job is *ranking*, and the fitted
  defaults reproduce the measured per-round sweep orderings (Spearman ≥
  0.9, pinned in tests/test_cost_model.py).

Everything here is pure arithmetic on a Config — no jax device calls — so
it runs in a preflight, a report CLI, or a 300-point planner sweep in
milliseconds.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Optional

from picotron_tpu.config import (
    Config, num_params, parse_tp_strategy, resolved_cp_flavor,
    resolved_cp_mesh, resolved_tp_mesh,
)
from picotron_tpu.utils import flops_per_token, tpu_generation

# ---------------------------------------------------------------------------
# TPU generations — ICI topology + link/HBM/peak constants.
#
# Bandwidths are per-link per-direction, derived from the published
# aggregate ICI figures (v5e 1600 Gb/s over 4 links; v5p 4800 Gb/s over 6;
# v4 2400 Gb/s over 6) de-rated ~10% for protocol overhead. wrap_min is
# the smallest axis size modeled with wraparound links: v5e sub-slices of
# its 16x16 2D torus are meshes (lines) until a full 16-ring; v5p/v4 3D
# slices get wraparound from a full side of 4. HBM is per chip.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class IciGeneration:
    name: str
    phys_axes: int          # independent torus dims a logical axis can own
    link_bandwidth: float   # bytes/s per link per direction
    wrap_min: int           # smallest axis size that closes into a ring
    hbm_gib: float          # per-chip HBM capacity
    peak_flops: float       # per-chip bf16 peak FLOP/s
    pcie_bandwidth: float   # host<->device streaming bw (offload); see
                            # Calibration — fitted, this is the fallback
    # -- the dcn tier (multi-slice scale-out) -----------------------------
    # Per-slice-exit DCN bandwidth per direction. Slices connect through
    # the data-center network at per-host NIC rates aggregated across the
    # slice boundary — order 50-100 Gb/s per host vs 360-800 Gb/s per
    # chip of ICI. Analytic defaults (derated published figures) awaiting
    # on-TPU multi-slice validation; PERF.md round 16 has the protocol.
    dcn_bandwidth: float = 6.25e9   # bytes/s across the cut per direction
    dcn_alpha_s: float = 2.0e-5     # per-transfer DCN latency (vs 1 µs ICI)


GENERATIONS: dict[str, IciGeneration] = {
    "v4": IciGeneration("v4", 3, 45e9, 4, 32.0, 275e12, 7e9,
                        6.25e9, 2.0e-5),
    "v5e": IciGeneration("v5e", 2, 45e9, 16, 16.0, 197e12, 7e9,
                         6.25e9, 2.0e-5),
    "v5p": IciGeneration("v5p", 3, 90e9, 4, 95.0, 459e12, 7e9,
                         12.5e9, 2.0e-5),
    "v6e": IciGeneration("v6e", 2, 100e9, 16, 32.0, 918e12, 7e9,
                         12.5e9, 2.0e-5),
}


def resolve_generation(name_or_kind: str) -> IciGeneration:
    """Generation from a config string ('v5e') or a jax device_kind
    ('TPU v5 lite', 'TPU v5p'). Raises ValueError on a kind that names no
    generation in the table (utils.tpu_generation) or one this model has
    no ICI constants for — never a default."""
    k = name_or_kind.lower()
    if k in GENERATIONS:
        return GENERATIONS[k]
    gen = tpu_generation(name_or_kind)
    if gen not in GENERATIONS:
        raise ValueError(
            f"no ICI constants for TPU generation {gen!r} "
            f"(device_kind {name_or_kind!r}); have {sorted(GENERATIONS)}")
    return GENERATIONS[gen]


# ---------------------------------------------------------------------------
# Hop counts + axis placement
# ---------------------------------------------------------------------------


def ring_diameter(n: int) -> int:
    """Max hop distance on a bidirectional ring of n chips."""
    return n // 2


def line_diameter(n: int) -> int:
    """Max hop distance on a line (torus slice without wraparound)."""
    return max(n - 1, 0)


@dataclass(frozen=True)
class AxisLink:
    """One mesh axis' modeled ICI placement."""

    axis: str
    size: int
    kind: str          # "ring" | "line"
    bandwidth: float   # effective bytes/s per direction for this axis
    stride: int        # physical hops between logical neighbors (folding)

    @property
    def diameter(self) -> int:
        d = (ring_diameter(self.size) if self.kind == "ring"
             else line_diameter(self.size))
        return d * self.stride

    @property
    def directions(self) -> int:
        # a ring algorithm can stream both ways; a line effectively one
        return 2 if self.kind == "ring" else 1


# placement priority: innermost (most comm-hungry) first — mirrors the
# AXES = (dp, pp, ep, cp, tp) ordering contract in mesh.py, reversed
PLACEMENT_ORDER = ("tp", "cp", "ep", "pp", "dp")


def place_axes(axis_sizes: dict, gen: IciGeneration) -> dict[str, AxisLink]:
    """Model the logical→physical axis assignment: the first `phys_axes`
    non-trivial axes (innermost first) each own a torus dimension at full
    link bandwidth; later axes fold over already-used dimensions, paying a
    neighbor stride equal to the product of the sizes sharing their
    dimension (a folded neighbor hop traverses that many links)."""
    out: dict[str, AxisLink] = {}
    nontrivial = [a for a in PLACEMENT_ORDER if axis_sizes.get(a, 1) > 1]
    dim_load = [1] * max(gen.phys_axes, 1)
    for i, ax in enumerate(nontrivial):
        n = axis_sizes[ax]
        dim = i % len(dim_load)
        stride = dim_load[dim] if i >= len(dim_load) else 1
        dim_load[dim] *= n
        kind = "ring" if n >= gen.wrap_min else "line"
        out[ax] = AxisLink(ax, n, kind,
                           gen.link_bandwidth / max(stride, 1), stride)
    return out


def split_cp_link(link: AxisLink, cp_x: int, cp_y: int,
                  gen: IciGeneration) -> tuple[AxisLink, AxisLink]:
    """Factor one placed cp AxisLink into the mesh flavor's 2D submesh:
    (outer cp_x row-ring link, inner cp_y head-scatter link).

    The inner sub-axis is a contiguous slice of the physical placement, so
    its logical-neighbor stride is the parent's and it closes into a ring
    by the generation's own wrap rule (a cp_y-slice of a v5e 16-torus side
    is a line; a full side is a ring). The outer sub-axis hops cp_y
    physical neighbors per logical step — and all cp_y row rings shift
    concurrently over the same links, so each pair sees 1/cp_y of the
    parent bandwidth — but it inherits the parent's wraparound: if the
    full cp axis closes, the stride-cp_y cycle closes with it. This is the
    TASP-style observation that makes mesh win on wrap-less slices: the
    ring leg shrinks from cp-1 line hops to cp_x-1, while the a2a leg
    stays inside a short contiguous subgroup."""
    inner_kind = "ring" if cp_y >= gen.wrap_min else "line"
    inner = AxisLink(link.axis, cp_y, inner_kind, link.bandwidth, link.stride)
    outer_kind = link.kind if cp_x > 1 else "line"
    outer = AxisLink(link.axis, cp_x, outer_kind,
                     link.bandwidth / max(cp_y, 1), link.stride * cp_y)
    return outer, inner


def split_slice_link(link: AxisLink, n_slices: int,
                     gen: IciGeneration) -> tuple[AxisLink, AxisLink]:
    """Factor one placed DCN-crossing axis into its hierarchical tiers:
    (intra-slice ICI sub-link of size n/slices, inter-slice DCN link of
    size slices). The intra leg keeps the parent's bandwidth/stride and
    re-derives its wrap rule from the shrunk size; the DCN leg is modeled
    as a bidirectional ring of slices at the generation's dcn_bandwidth
    (slice interconnects are switched, so a ring is the conservative
    shape). Mirrors split_cp_link's role for the mesh cp flavor — the
    slice-boundary analogue of the TASP follow-the-network split."""
    m = max(link.size // max(n_slices, 1), 1)
    intra = AxisLink(link.axis, m,
                     "ring" if m >= gen.wrap_min else "line",
                     link.bandwidth, link.stride)
    dcn = AxisLink(f"{link.axis}@dcn", n_slices, "ring",
                   gen.dcn_bandwidth, 1)
    return intra, dcn


# ---------------------------------------------------------------------------
# Calibration constants
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Calibration:
    """Constants the measured rows on disk pin down (analysis/calibration.py
    fits eff_max / h_half / eff_attn / pcie_bandwidth against SWEEP step
    times; the defaults below are the fit to rounds 3–5, of which
    SWEEP_r03–r04 remain on disk).
    The exposure fractions and link latency are analytic defaults awaiting
    on-TPU validation — PERF.md documents the protocol."""

    # dense-matmul efficiency saturates with hidden size:
    #   eff_dense(h) = min(eff_max * h / (h + h_half), eff_cap)
    eff_max: float = 1.07
    h_half: float = 1280.0
    eff_cap: float = 0.92
    # flash-attention FLOPs run below the matmul peak (softmax/mask
    # overhead, shorter arithmetic chains)
    eff_attn: float = 0.40
    # achieved host<->device streaming bandwidth for optimizer offload
    # (fitted: the r05 offload rows' residual over their compute term)
    pcie_bandwidth: float = 5.6e9
    # per-link-hop latency (collective setup + hop): the α in α + V/B
    alpha_link_s: float = 1.0e-6
    # fraction of each comm class NOT hidden under compute
    expose_grad: float = 0.35   # grad all-reduce overlaps the backward
    expose_pp: float = 0.5      # boundary ppermute overlaps the 1f1b scan
    # MPMD executor: host-side cost of dispatching one per-stage program
    # (schedule-table walk + jit cache hit + device_put enqueue). Replaces
    # the SPMD scan's full-priced idle tick — the r4 intercept said an
    # SPMD idle tick costs ~a traced unit (~64.7 ms); a host dispatch is
    # ~0.2 ms. Analytic default awaiting --pp-tick-sweep calibration.
    host_dispatch_s: float = 2.0e-4
    expose_layer: float = 1.0   # in-layer tp/sp/cp/ep collectives serialize
    # deferred tp_sync (parallel/tp_strategies.py): the reduce-scatter at a
    # block's exit still serializes, but its gather half is hoisted to the
    # NEXT block's entry where it overlaps that block's norm + qkv/gate
    # matmul issue window — only this fraction of the all-gather stays
    # exposed. Analytic default awaiting on-TPU validation (PERF.md r15).
    expose_deferred: float = 0.55
    # step-FLOPs multiplier per remat policy (recompute overhead), relative
    # to "dots" whose overhead the efficiency fit absorbs
    remat_flops: tuple = (("full", 1.30), ("dots", 1.0),
                          ("dots_attn", 1.07), ("dots_lean", 1.12),
                          ("dots_norms", 0.98), ("dots_offload", 1.07))

    def remat_multiplier(self, policy: str, remat: bool) -> float:
        if not remat:
            return 1.0
        return dict(self.remat_flops).get(policy, 1.0)


DEFAULT_CALIBRATION = Calibration()

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


# ---------------------------------------------------------------------------
# Cost terms
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CommTerm:
    """One class of collective traffic in a step's schedule."""

    name: str          # e.g. "grad_sync", "tp_psum", "cp_ring"
    kind: str          # a collectives.KINDS member
    axes: tuple        # mesh axes the op spans
    count: int         # ops per step
    bytes_each: float  # payload bytes per op (full logical tensor)
    secs_each: float   # predicted seconds per op
    exposed_frac: float

    @property
    def secs_total(self) -> float:
        return self.secs_each * self.count

    @property
    def secs_exposed(self) -> float:
        return self.secs_total * self.exposed_frac


@dataclass(frozen=True)
class StepCost:
    """Predicted decomposition of one optimizer step."""

    config_label: str
    generation: str
    n_chips: int
    tokens_per_step: int
    compute_s: float
    bubble_s: float      # pipeline bubble: spmd 2(pp-1)/ga of compute;
    #                      mpmd (pp-1)/(v*ga) + host dispatch
    offload_s: float     # optimizer-offload PCIe streaming
    comm: tuple          # CommTerm, ...

    @property
    def comm_s(self) -> float:
        return sum(t.secs_total for t in self.comm)

    @property
    def exposed_comm_s(self) -> float:
        return sum(t.secs_exposed for t in self.comm)

    @property
    def total_s(self) -> float:
        return (self.compute_s + self.bubble_s + self.offload_s
                + self.exposed_comm_s)

    @property
    def tokens_per_sec(self) -> float:
        return self.tokens_per_step / self.total_s

    @property
    def tokens_per_sec_per_chip(self) -> float:
        return self.tokens_per_sec / self.n_chips

    def as_dict(self) -> dict:
        return {
            "config": self.config_label,
            "generation": self.generation,
            "n_chips": self.n_chips,
            "tokens_per_step": self.tokens_per_step,
            "predicted_step_ms": round(self.total_s * 1e3, 3),
            "compute_ms": round(self.compute_s * 1e3, 3),
            "bubble_ms": round(self.bubble_s * 1e3, 3),
            "offload_ms": round(self.offload_s * 1e3, 3),
            "comm_ms": round(self.comm_s * 1e3, 3),
            "exposed_comm_ms": round(self.exposed_comm_s * 1e3, 3),
            "tokens_per_sec": round(self.tokens_per_sec, 1),
            "tokens_per_sec_per_chip": round(self.tokens_per_sec_per_chip,
                                             1),
            "comm_terms": {t.name: round(t.secs_total * 1e3, 3)
                           for t in self.comm},
        }


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


class CostModel:
    """Price collectives and whole steps for one TPU generation."""

    def __init__(self, generation="v5e",
                 calibration: Calibration = DEFAULT_CALIBRATION):
        self.gen = (generation if isinstance(generation, IciGeneration)
                    else resolve_generation(generation))
        self.calib = calibration

    # -- per-collective ----------------------------------------------------

    def collective_secs(self, kind: str, nbytes: float,
                        link: AxisLink, alpha: float = None) -> float:
        """Seconds for one collective of `kind` moving `nbytes` (the full
        logical tensor for group collectives; the per-device payload for a
        ppermute shift) over one placed axis. `alpha` overrides the
        per-hop latency (the dcn tier's is ~20x the ICI default)."""
        n, bw = link.size, link.bandwidth
        if n <= 1 or nbytes <= 0:
            return 0.0
        dirs = link.directions
        if alpha is None:
            alpha = self.calib.alpha_link_s
        if kind == "all_gather" or kind == "reduce_scatter":
            return nbytes * (n - 1) / n / (dirs * bw) + alpha * (n - 1)
        if kind == "all_reduce":
            return 2 * nbytes * (n - 1) / n / (dirs * bw) + alpha * (n - 1)
        if kind == "all_to_all":
            # mean pair distance n/4 on a ring (n/2 on a line) x per-pair
            # V/n payloads crossing shared links
            return nbytes * n / (4 * dirs * bw) + alpha * (n - 1)
        if kind == "collective_permute":
            # neighbor shift: every link carries one payload; on a line
            # the wraparound message re-crosses the whole slice
            hops = 1 if link.kind == "ring" else max(n - 1, 1)
            return nbytes * hops / bw + alpha * hops
        raise ValueError(f"unknown collective kind {kind!r}")

    def axes_for(self, cfg: Config) -> dict[str, AxisLink]:
        d = cfg.distributed
        return place_axes({"dp": d.dp_size, "pp": d.pp_size,
                           "ep": d.ep_size, "cp": d.cp_size,
                           "tp": d.tp_size}, self.gen)

    # -- the dcn tier -----------------------------------------------------

    def dcn_link(self, n_slices: int) -> AxisLink:
        """The inter-slice DCN 'axis': a ring of slices at the
        generation's dcn_bandwidth."""
        return AxisLink("dcn", n_slices, "ring", self.gen.dcn_bandwidth, 1)

    def dcn_secs(self, kind: str, nbytes: float, n_slices: int) -> float:
        """Seconds for one collective leg crossing the slice cut — same
        ring formulas as ICI, at the dcn tier's bandwidth and latency."""
        return self.collective_secs(kind, nbytes, self.dcn_link(n_slices),
                                    alpha=self.gen.dcn_alpha_s)

    def slice_tiers(self, cfg: Config, n_slices: int, axis: str) -> dict:
        """Price the predicted step comm under a slice cut on `axis`
        (one of the DCN-tolerant axes, dp or pp): comm terms spanning the
        axis are re-priced hierarchically — wide legs on the intra-slice
        ICI sub-link, a shard-per-slice leg on the dcn tier — and
        everything else stays on its placed ICI link. Returns the per-tier
        split the planner renders: which axis should absorb the slice
        granules falls out of comparing these rows."""
        cost = self.predict(cfg)
        links = self.axes_for(cfg)
        d = cfg.distributed
        axis_size = {"dp": d.dp_size, "pp": d.pp_size}.get(axis, 1)
        ici_s = dcn_s = 0.0
        dcn_bytes = 0.0
        crossing = []
        for t in cost.comm:
            if axis not in t.axes or axis not in links:
                ici_s += t.secs_total
                continue
            crossing.append(t.name)
            intra, dcn = split_slice_link(links[axis], n_slices, self.gen)
            other_s = sum(self.collective_secs(t.kind, t.bytes_each,
                                               links[a])
                          for a in t.axes if a != axis and a in links)
            if t.kind == "collective_permute":
                # the boundary pairs at the cut cross DCN point-to-point;
                # in-slice pairs keep the ICI price
                ici_s += t.count * (other_s + self.collective_secs(
                    t.kind, t.bytes_each, intra))
                dcn_leg = (t.bytes_each / self.gen.dcn_bandwidth
                           + self.gen.dcn_alpha_s)
                dcn_s += t.count * dcn_leg
                dcn_bytes += t.count * t.bytes_each
            else:
                m = max(axis_size // n_slices, 1)
                ici_s += t.count * (other_s + self.collective_secs(
                    t.kind, t.bytes_each, intra))
                shard = t.bytes_each / m
                dcn_s += t.count * self.dcn_secs(t.kind, shard, n_slices)
                dcn_bytes += t.count * shard * (
                    2 if t.kind == "all_reduce" else 1) * (
                    n_slices - 1) / n_slices
        return {
            "axis": axis, "slices": n_slices,
            "generation": self.gen.name,
            "crossing_terms": crossing,
            "dcn_bytes": int(dcn_bytes),
            "dcn_ms": round(dcn_s * 1e3, 4),
            "ici_ms": round(ici_s * 1e3, 4),
            "total_comm_ms": round((ici_s + dcn_s) * 1e3, 4),
        }

    # -- traced-schedule pricing ------------------------------------------

    def price_ops(self, cfg: Config, ops) -> list[dict]:
        """Price a parsed `CollectiveOp` list (analysis/collectives.py)
        against the config's axis placement. Each op's replica-group size
        is matched to a mesh axis (or, for the fused-data-axes grad
        all-reduce, to the (dp, ep, cp) product, priced hierarchically as
        one pass per constituent axis). Ops whose group no axis explains
        are priced on the worst (slowest) placed axis, flagged
        `axis_guess`."""
        links = self.axes_for(cfg)
        d = cfg.distributed
        sizes = {"dp": d.dp_size, "pp": d.pp_size, "ep": d.ep_size,
                 "cp": d.cp_size, "tp": d.tp_size}
        priced = []
        for op in ops:
            if not op.effective:
                continue
            nbytes = op.nbytes or 0
            axes = self._match_axes(op, sizes)
            if axes:
                secs = sum(
                    self.collective_secs(op.kind, nbytes, links[a])
                    for a in axes if a in links)
                guess = False
            else:
                worst = min(links.values(), key=lambda l: l.bandwidth,
                            default=None)
                secs = (self.collective_secs(op.kind, nbytes, worst)
                        if worst else 0.0)
                guess = True
            priced.append({"kind": op.kind, "line": op.line,
                           "bytes": nbytes, "axes": axes,
                           "secs": secs, "axis_guess": guess})
        return priced

    def price_reshards(self, cfg: Config, reshards) -> tuple:
        """(secs, bytes) for predicted boundary reshards
        (analysis/dataflow.py BoundaryReshard). GSPMD materializes a spec
        mismatch as an all-gather of the full logical tensor; the static
        prediction cannot know which axis the partitioner routes it over,
        so budget the slowest placed axis — the conservative bound the
        planner should price unintended traffic at."""
        links = [l for l in self.axes_for(cfg).values() if l.size > 1]
        worst = min(links, key=lambda l: l.bandwidth, default=None)
        if worst is None:
            return 0.0, sum(r.nbytes for r in reshards)
        secs = sum(self.collective_secs("all_gather", r.nbytes, worst)
                   for r in reshards)
        return secs, sum(r.nbytes for r in reshards)

    def price_kv_handoff(self, model_cfg, serve_cfg=None, *,
                         n_tokens: Optional[int] = None,
                         hops: int = 1) -> tuple:
        """(secs, bytes) for ONE prefill->decode KV-block handoff in the
        disaggregated serving engine (serve/disagg.py): the K and V
        blocks of one finished prefix cross the pool boundary as a
        point-to-point `device_put` over `hops` ICI links (1 = adjacent
        chips, the intended placement; a torus detour raises it).

        Payload = 2 tensors x L x blocks x block_size x Hkv x Dh at the
        serve compute dtype, with `blocks` rounded UP from `n_tokens`
        (default: the full serve.max_model_len prefix — the conservative
        per-request bound admission should budget). The transfer is
        point-to-point, so it prices like a single ppermute hop:
        nbytes * hops / link_bw + alpha * hops. Decode-side stall only
        occurs if the handoff is scheduled synchronously with a decode
        dispatch — the engine interleaves it between dispatches, so this
        number is the budget the scheduler's handoff rate must stay
        under, not a per-token tax."""
        from picotron_tpu.config import ServeConfig

        scfg = serve_cfg or ServeConfig()
        max_len = (scfg.max_model_len
                   or model_cfg.max_position_embeddings)
        if n_tokens is None:
            n_tokens = max_len
        blocks = -(-n_tokens // scfg.block_size)
        kv_bytes = _DTYPE_BYTES.get(model_cfg.dtype, 2)
        nbytes = (2 * model_cfg.num_hidden_layers * blocks
                  * scfg.block_size * model_cfg.num_key_value_heads
                  * model_cfg.head_dim * kv_bytes)
        secs = (nbytes * hops / self.gen.link_bandwidth
                + self.calib.alpha_link_s * hops)
        return secs, nbytes

    @staticmethod
    def _match_axes(op, sizes: dict) -> tuple:
        """Mesh axes a parsed op most plausibly spans."""
        if op.kind == "collective_permute":
            # ppermutes carry pairs, not groups: cp rings issue far more
            # of them than pp boundaries — prefer cp when present
            for a in ("cp", "pp", "dp"):
                if sizes[a] > 1:
                    return (a,)
            return ()
        g = op.group_size or 0
        if g <= 1:
            return ()
        # fused data axes (the grad sync) first, then single axes by
        # comm-frequency priority
        fused = sizes["dp"] * sizes["ep"] * sizes["cp"]
        if g == fused and fused > 1:
            return tuple(a for a in ("dp", "ep", "cp") if sizes[a] > 1)
        prefer = (("ep", "cp", "tp", "dp", "pp")
                  if op.kind == "all_to_all"
                  else ("tp", "cp", "ep", "dp", "pp"))
        for a in prefer:
            if sizes[a] == g:
                return (a,)
        return ()

    def priced_schedule(self, cfg: Config, text: Optional[str] = None):
        """(priced ops, total comm seconds) from a traced schedule —
        lowers the train step when `text` is not given (requires enough
        simulated devices, same contract as analysis/trace.py)."""
        if text is None:
            from picotron_tpu.analysis.trace import lower_train_step

            text = lower_train_step(cfg).text
        from picotron_tpu.analysis.collectives import parse_collectives

        priced = self.price_ops(cfg, parse_collectives(text))
        return priced, sum(p["secs"] for p in priced)

    # -- analytic whole-step prediction -----------------------------------

    def predict(self, cfg: Config, label: Optional[str] = None) -> StepCost:
        """Analytic step-time decomposition for `cfg` on this generation.
        The schedule is derived from the config (the same per-axis
        presence rules audit_collectives enforces on traces), so this
        needs no devices and prices a 64-chip layout in microseconds."""
        c = self.calib
        m, d, t = cfg.model, cfg.distributed, cfg.training
        world = d.world_size
        s, h = t.seq_length, m.hidden_size
        ga, mbs = t.gradient_accumulation_steps, t.micro_batch_size
        act_bytes = _DTYPE_BYTES.get(m.dtype, 2)
        tokens = cfg.tokens_per_step

        # compute: split the 6N+attn formula into dense / attention parts
        f_tok = flops_per_token(m, s)
        f_attn_tok = 12.0 * m.num_hidden_layers * h * s
        f_dense_tok = f_tok - f_attn_tok
        eff_d = min(c.eff_max * h / (h + c.h_half), c.eff_cap)
        mult = c.remat_multiplier(t.remat_policy, t.remat)
        compute_s = (tokens * mult
                     * (f_dense_tok / eff_d + f_attn_tok / c.eff_attn)
                     / (world * self.gen.peak_flops))

        # Non-megatron TP strategies (parallel/tp_strategies.py). The 2d
        # row-side matmuls (o/down) contract a tp_y-times larger slab —
        # weight rows are gathered within the inner subgroup so the
        # contraction replicates tp_y-fold across it. Fold the extra FLOPs
        # into compute_s so the bubble and overlap terms see the true
        # critical path; the comm terms below price the collectives.
        tp_strat = None
        tp_x = tp_y = 1
        if d.tp_size > 1:
            from picotron_tpu.config import resolved_tp_strategy

            tp_strat = resolved_tp_strategy(cfg, generation=self.gen.name)
            if "2d" in tp_strat.values():
                tp_x, tp_y = resolved_tp_mesh(cfg)
                extra_tok = 0.0
                if tp_strat["o"] == "2d":
                    extra_tok += 2.0 * h * h
                if tp_strat["down"] == "2d":
                    extra_tok += 2.0 * h * m.intermediate_size
                compute_s += (tokens * mult * m.num_hidden_layers
                              * extra_tok * (tp_y - 1)
                              / (eff_d * world * self.gen.peak_flops))

        # Pipeline bubble — executor-dependent (parallel/mpmd.py):
        # - spmd: the lockstep scan runs n + 2(pp-1) ticks and EVERY tick
        #   costs a full traced unit on every device (PERF.md r4: idle
        #   ticks are not free), so bubble = compute * 2(pp-1)/ga.
        # - mpmd: idle ticks dispatch nothing. What remains is the
        #   schedule's fill/drain — (pp-1)/ga of compute for 1f1b/gpipe,
        #   divided by the interleave factor v for the interleaved
        #   schedule — plus the per-dispatch host cost of walking the
        #   table (2 programs per microbatch per virtual stage).
        bubble_s = 0.0
        if d.pp_size > 1:
            pl = cfg.pipeline
            if pl.executor == "spmd":
                bubble_s = compute_s * 2 * (d.pp_size - 1) / ga
            else:
                v = pl.interleave if pl.schedule == "interleaved" else 1
                bubble_s = (compute_s * (d.pp_size - 1) / (v * ga)
                            + 2 * ga * d.pp_size * v * c.host_dispatch_s)

        # optimizer offload: master + both moments stream host->device and
        # the refreshed values stream back, once per step, sharded like the
        # params (tp*pp; experts additionally over ep; zero1 over dp)
        offload_s = 0.0
        if t.optimizer_offload:
            n_total = num_params(m)
            n_local = n_total / (d.tp_size * d.pp_size)
            if m.num_experts and d.ep_size > 1:
                bank = (m.num_hidden_layers * m.num_experts
                        * 3 * h * m.expert_ffn_size)
                n_local -= bank / d.tp_size / d.pp_size * (1 - 1 / d.ep_size)
            if d.zero1:
                n_local /= d.dp_size
            mom_b = 2 if t.adam_moments_dtype == "bfloat16" else 4
            per_param = 2 * (4 + 2 * mom_b)  # round trip: master + m + v
            offload_s = n_local * per_param / c.pcie_bandwidth

        links = self.axes_for(cfg)
        terms: list[CommTerm] = []

        def add(name, kind, axes, count, nbytes, exposed):
            axes = tuple(a for a in axes if a in links)
            if not axes or count <= 0 or nbytes <= 0:
                return
            secs = sum(self.collective_secs(kind, nbytes, links[a])
                       for a in axes)
            terms.append(CommTerm(name, kind, axes, int(count), nbytes,
                                  secs, exposed))

        layers_stage = max(m.num_hidden_layers // d.pp_size, 1)
        v_act = mbs * (s // d.cp_size) * h * act_bytes  # one microbatch

        # grad sync over the fused data axes, fp32, once per step
        n_grad_local = num_params(m) / (d.tp_size * d.pp_size)
        add("grad_sync",
            "reduce_scatter" if d.zero1 else "all_reduce",
            ("dp", "ep", "cp"), 1, 4 * n_grad_local, c.expose_grad)
        if d.zero1:
            # the matching param all-gather of the refreshed shards
            add("zero1_gather", "all_gather", ("dp",), 1,
                act_bytes * n_grad_local, c.expose_grad)

        # TP: 2 fwd + 2 bwd boundary collectives per layer per microbatch
        # on the megatron col/row pairing; Megatron-SP replaces each psum
        # with an all-gather/reduce-scatter pair of the same volume, and
        # tp_sync=deferred keeps the SP pair but hoists the gather into the
        # next block's entry (only expose_deferred of it stays exposed).
        # The row-first pairing moves the psum to the block ENTRY (over the
        # full projection width — wider than hidden) and exits with a
        # feature all-gather; the 2d pairing splits tp into tp_x x tp_y
        # subgroups: an activation + weight-rows all-gather over the inner
        # tp_y link and a psum shrunk to the outer tp_x link.
        if d.tp_size > 1 and tp_strat is not None:
            deferred = d.tp_sync == "deferred"
            pair_kinds = (("attn", tp_strat["qkv"]), ("mlp", tp_strat["up"]))
            n_pair = 2 * layers_stage * ga   # fwd + bwd, per pair per micro
            n_boundary = sum(n_pair for _, k in pair_kinds if k == "col")
            if n_boundary:
                if deferred:
                    add("tp_defer_gather", "all_gather", ("tp",),
                        n_boundary, v_act, c.expose_deferred)
                    add("tp_defer_scatter", "reduce_scatter", ("tp",),
                        n_boundary, v_act, c.expose_layer)
                elif d.sequence_parallel:
                    add("sp_gather", "all_gather", ("tp",), n_boundary,
                        v_act, c.expose_layer)
                    add("sp_scatter", "reduce_scatter", ("tp",), n_boundary,
                        v_act, c.expose_layer)
                else:
                    add("tp_psum", "all_reduce", ("tp",), n_boundary,
                        v_act, c.expose_layer)
            tok_mb = mbs * (s // d.cp_size)
            p_bytes = _DTYPE_BYTES.get(m.dtype, 2)
            attn_w = m.num_attention_heads * m.head_dim
            proj = {"attn": attn_w + 2 * m.num_key_value_heads * m.head_dim,
                    "mlp": 2 * m.intermediate_size}
            gath = {"attn": proj["attn"], "mlp": m.intermediate_size}
            wrows = {"attn": attn_w, "mlp": m.intermediate_size}
            for pair, kind in pair_kinds:
                if kind == "row":
                    add(f"tp_row_psum_{pair}", "all_reduce", ("tp",),
                        n_pair, tok_mb * proj[pair] * act_bytes,
                        c.expose_layer)
                    add(f"tp_row_gather_{pair}", "all_gather", ("tp",),
                        n_pair, v_act, c.expose_layer)
                elif kind == "2d" and "tp" in links:
                    outer, inner = split_cp_link(links["tp"], tp_x, tp_y,
                                                 self.gen)
                    if tp_y > 1:
                        v_g = tok_mb * gath[pair] // tp_x * act_bytes
                        terms.append(CommTerm(
                            f"tp2d_gather_{pair}", "all_gather", ("tp",),
                            n_pair, v_g,
                            self.collective_secs("all_gather", v_g, inner),
                            c.expose_layer))
                        v_w = wrows[pair] * h // tp_x * p_bytes
                        terms.append(CommTerm(
                            f"tp2d_wgather_{pair}", "all_gather", ("tp",),
                            n_pair, v_w,
                            self.collective_secs("all_gather", v_w, inner),
                            c.expose_layer))
                    if tp_x > 1:
                        terms.append(CommTerm(
                            f"tp2d_psum_{pair}", "all_reduce", ("tp",),
                            n_pair, v_act,
                            self.collective_secs("all_reduce", v_act,
                                                 outer),
                            c.expose_layer))

        # CP: ring (K/V shift chain fwd, K/V + dK/dV bwd), the Ulysses
        # seq<->head all_to_all pair each way, or the mesh flavor's 2D
        # split — head scatter over the inner cp_y subgroup plus a K/V
        # ring over the outer cp_x rows. The mesh row-block payload
        # (cp_y-times-longer sequence on 1/cp_y of the KV heads) equals
        # the 1D ring's per-hop v_kv exactly; what changes is the hop
        # count (cp_x-1 vs cp-1) and the sub-link each leg runs on.
        if d.cp_size > 1:
            flavor = resolved_cp_flavor(cfg)
            kv_dim = m.num_key_value_heads * m.head_dim
            v_kv = 2 * mbs * (s // d.cp_size) * kv_dim * act_bytes
            if flavor == "ulysses":
                add("ulysses_a2a", "all_to_all", ("cp",),
                    4 * layers_stage * ga, v_act, c.expose_layer)
            elif flavor == "mesh" and "cp" in links:
                cp_x, cp_y = resolved_cp_mesh(cfg)
                outer, inner = split_cp_link(links["cp"], cp_x, cp_y,
                                             self.gen)
                if cp_y > 1:
                    secs = self.collective_secs("all_to_all", v_act, inner)
                    terms.append(CommTerm(
                        "mesh_a2a", "all_to_all", ("cp",),
                        4 * layers_stage * ga, v_act, secs,
                        c.expose_layer))
                if cp_x > 1:
                    secs = self.collective_secs("collective_permute",
                                                v_kv, outer)
                    terms.append(CommTerm(
                        "mesh_ring", "collective_permute", ("cp",),
                        3 * (cp_x - 1) * layers_stage * ga, v_kv, secs,
                        c.expose_layer))
            else:
                add("cp_ring", "collective_permute", ("cp",),
                    3 * (d.cp_size - 1) * layers_stage * ga, v_kv,
                    c.expose_layer)

        # EP: dispatch + combine all_to_all, forward and backward
        if d.ep_size > 1 and m.num_experts:
            v_disp = v_act * m.num_experts_per_token * m.capacity_factor
            add("ep_dispatch", "all_to_all", ("ep",),
                4 * layers_stage * ga, v_disp, c.expose_layer)

        # PP boundary: activation fwd + grad bwd per microbatch
        if d.pp_size > 1:
            v_bound = v_act / (d.tp_size if d.sequence_parallel else 1)
            add("pp_boundary", "collective_permute", ("pp",), 2 * ga,
                v_bound, c.expose_pp)

        return StepCost(
            config_label=label or layout_label(cfg),
            generation=self.gen.name, n_chips=world,
            tokens_per_step=tokens, compute_s=compute_s,
            bubble_s=bubble_s, offload_s=offload_s, comm=tuple(terms))


def layout_label(cfg: Config) -> str:
    d, t = cfg.distributed, cfg.training
    bits = [f"dp{d.dp_size}", f"tp{d.tp_size}", f"pp{d.pp_size}",
            f"cp{d.cp_size}", f"ep{d.ep_size}"]
    flags = []
    if d.cp_size > 1 and d.cp_flavor:
        flags.append(d.cp_flavor + (f"-{d.cp_mesh}"
                                    if d.cp_flavor == "mesh" else ""))
    if d.sequence_parallel:
        flags.append("sp")
    if d.tp_size > 1 and d.tp_strategy != "megatron":
        if d.tp_strategy == "2d":
            tp_x, tp_y = resolved_tp_mesh(cfg)
            flags.append(f"tp2d-{tp_x}x{tp_y}")
        elif d.tp_strategy in ("row", "adaptive"):
            flags.append("tp" + d.tp_strategy)
        else:
            flags.append("tpmix")
    if d.tp_sync == "deferred":
        flags.append("deferred")
    if d.zero1:
        flags.append("zero1")
    if t.optimizer_offload:
        flags.append("offload")
    pl = getattr(cfg, "pipeline", None)
    if pl is not None and pl.executor == "mpmd":
        tag = "mpmd-" + pl.schedule
        if pl.schedule == "interleaved":
            tag += f"-v{pl.interleave}"
        flags.append(tag)
    return "x".join(bits) + (("+" + "+".join(flags)) if flags else "")


# ---------------------------------------------------------------------------
# CP-flavor crossover prediction
# ---------------------------------------------------------------------------


def _tp_local_heads(cfg: Config) -> tuple[int, int]:
    m, tp = cfg.model, cfg.distributed.tp_size
    return m.num_attention_heads // tp, m.num_key_value_heads // tp


def feasible_cp_meshes(cfg: Config, cp: Optional[int] = None) -> list:
    """True-2D (cp_x, cp_y) factorizations of the cp degree — both factors
    > 1 (degenerates ARE ring/ulysses, not a distinct flavor) and cp_y
    dividing the tp-local query AND kv head counts."""
    cp = cp or cfg.distributed.cp_size
    hq, hkv = _tp_local_heads(cfg)
    return [(cp // y, y) for y in range(2, cp)
            if cp % y == 0 and cp // y > 1
            and hq % y == 0 and hkv % y == 0]


def cp_flavor_costs(model: CostModel, cfg: Config) -> dict:
    """Price each feasible cp flavor for cfg's cp degree: 'ring' always,
    'ulysses' when the tp-local heads divide by cp, and 'mesh' as the best
    true-2D factorization (None entries mark infeasible flavors). Mesh
    values are (StepCost, (cp_x, cp_y))."""
    d = cfg.distributed
    out = {"ring": None, "ulysses": None, "mesh": None}
    ring_cfg = replace(cfg, distributed=replace(
        d, cp_flavor="ring", cp_mesh=""))
    out["ring"] = model.predict(ring_cfg)
    hq, hkv = _tp_local_heads(cfg)
    if hq % d.cp_size == 0 and hkv % d.cp_size == 0:
        out["ulysses"] = model.predict(replace(cfg, distributed=replace(
            d, cp_flavor="ulysses", cp_mesh="")))
    best = None
    for cp_x, cp_y in feasible_cp_meshes(cfg):
        cost = model.predict(replace(cfg, distributed=replace(
            d, cp_flavor="mesh", cp_mesh=f"{cp_x}x{cp_y}")))
        if best is None or cost.total_s < best[0].total_s:
            best = (cost, (cp_x, cp_y))
    out["mesh"] = best
    return out


def cp_crossover_table(model: CostModel, base: Config,
                       cp_degrees=(2, 4, 8, 16, 32)) -> list[dict]:
    """Sweep cp degree for `base`'s model/batch on `model`'s generation and
    report, per degree, each flavor's predicted step time and the winner —
    the table `tools/layout_planner.py --cp-crossover` prints. Degrees the
    sequence length cannot shard (zigzag needs 2*cp | seq) are skipped."""
    rows = []
    for cp in cp_degrees:
        if base.training.seq_length % (2 * cp) or cp < 2:
            continue
        cfg = replace(base, distributed=replace(
            base.distributed, cp_size=cp, cp_flavor="", cp_mesh=""))
        costs = cp_flavor_costs(model, cfg)
        row = {"cp": cp, "generation": model.gen.name}
        times = {}
        for flavor in ("ring", "ulysses", "mesh"):
            v = costs[flavor]
            if flavor == "mesh" and v is not None:
                cost, (cp_x, cp_y) = v
                row["mesh_factorization"] = f"{cp_x}x{cp_y}"
                v = cost
            row[f"{flavor}_ms"] = (round(v.total_s * 1e3, 3)
                                   if v is not None else None)
            if v is not None:
                times[flavor] = v.total_s
        row["winner"] = min(times, key=times.get) if times else None
        rows.append(row)
    return rows


def cp_crossover(model: CostModel, base: Config,
                 cp_degrees=(2, 4, 8, 16, 32)) -> Optional[int]:
    """Smallest swept cp degree where the mesh flavor's best factorization
    beats ring AND ulysses — None if mesh never wins. On wrap-less slices
    (v5e/v6e lines) the 1D ring pays cp-1 full-diameter wrap penalties and
    mesh wins early; on wrapped v4/v5p rings the crossover moves out."""
    for row in cp_crossover_table(model, base, cp_degrees):
        if row["winner"] == "mesh":
            return row["cp"]
    return None


# ---------------------------------------------------------------------------
# TP-strategy pricing + adaptive selection
# ---------------------------------------------------------------------------


def feasible_tp_meshes(cfg: Config, tp: Optional[int] = None) -> list:
    """True-2D (tp_x, tp_y) factorizations of the tp degree — both factors
    > 1 (degenerates ARE megatron: tp_y=1 has no inner gather and tp_x=1
    no outer psum shrink) and tp_x dividing the q AND kv head counts (the
    2d attention runs heads/tp_x, tp_y-replicated)."""
    m = cfg.model
    tp = tp or cfg.distributed.tp_size
    return [(tp // y, y) for y in range(2, tp)
            if tp % y == 0 and tp // y > 1
            and m.num_attention_heads % (tp // y) == 0
            and m.num_key_value_heads % (tp // y) == 0]


def price_tp_strategy(model: CostModel, cfg: Config, strategy: str,
                      sync: str = "sync", tp_mesh: str = "") -> StepCost:
    """Price `cfg` with its TP strategy/sync knobs forced — the one-call
    query behind `choose_tp_strategy` and the `--tp-strategy-table` CLI.
    No validation is re-run: this is a pricing probe, so the caller owns
    eligibility (the planner only probes eligible configs)."""
    return model.predict(replace(cfg, distributed=replace(
        cfg.distributed, tp_strategy=strategy, tp_sync=sync,
        tp_mesh=tp_mesh)))


def _pair_spec(attn_kind: str, mlp_kind: str) -> str:
    """Explicit per-class spec string for a (attn-pair, mlp-pair) choice,
    respecting the legal (entry, exit) pairings config.parse_tp_strategy
    enforces: col pairs with row, row with col, 2d with 2d."""
    exit_of = {"col": "row", "row": "col", "2d": "2d"}
    return (f"qkv={attn_kind},o={exit_of[attn_kind]},"
            f"up={mlp_kind},down={exit_of[mlp_kind]},head=col")


def choose_tp_strategy(cfg: Config, generation: str = "v5e") -> dict:
    """Resolve tp_strategy='adaptive': per-class argmin over the legal
    pair partitionings, priced on `generation`'s ICI descriptor (the ATP
    selection loop, arxiv 2301.08658, collapsed to the three partitionings
    this runtime implements). Deterministic: candidates are enumerated in
    a fixed order with a strict < comparison, so megatron (first) wins
    ties — tp degrees where no alternative strictly helps keep the
    reference layout. Pure arithmetic; resolves in microseconds."""
    model = CostModel(generation)
    d = cfg.distributed
    tp_x, tp_y = resolved_tp_mesh(cfg)
    kinds = ["col", "row"] + (["2d"] if tp_x > 1 and tp_y > 1 else [])
    best_s, best_spec = None, _pair_spec("col", "col")
    for ak in kinds:
        for mk in kinds:
            spec = _pair_spec(ak, mk)
            cost = price_tp_strategy(model, cfg, spec, sync=d.tp_sync,
                                     tp_mesh=d.tp_mesh)
            if best_s is None or cost.total_s < best_s:
                best_s, best_spec = cost.total_s, spec
    return parse_tp_strategy(best_spec)


def tp_strategy_table(model: CostModel, base: Config,
                      tp_degrees=(2, 4, 8, 16)) -> list[dict]:
    """Sweep tp degree for `base`'s model/batch on `model`'s generation
    and report, per degree, each strategy x sync-mode's predicted step
    time and exposed-comm time, the best 2d factorization, the adaptive
    resolution, and the winner — the table
    `tools/layout_planner.py --tp-strategy-table` prints. Degrees the
    model cannot shard (head/kv/vocab divisibility) are skipped."""
    m = base.model
    rows = []
    for tp in tp_degrees:
        if (tp < 2 or m.num_attention_heads % tp
                or m.num_key_value_heads % tp or m.vocab_size % tp):
            continue
        cfg = replace(base, distributed=replace(
            base.distributed, tp_size=tp, tp_strategy="megatron",
            tp_sync="sync", tp_mesh=""))
        variants: dict[str, StepCost] = {
            "megatron": model.predict(cfg),
            "deferred": price_tp_strategy(model, cfg, "megatron",
                                          sync="deferred"),
            "row": price_tp_strategy(model, cfg, "row"),
        }
        row = {"tp": tp, "generation": model.gen.name}
        best2d = None
        for tp_mx, tp_my in feasible_tp_meshes(cfg, tp):
            cost = price_tp_strategy(model, cfg, "2d",
                                     tp_mesh=f"{tp_mx}x{tp_my}")
            if best2d is None or cost.total_s < best2d[0].total_s:
                best2d = (cost, f"{tp_mx}x{tp_my}")
        if best2d is not None:
            variants["2d"] = best2d[0]
            row["mesh_factorization"] = best2d[1]
        base_exposed = variants["megatron"].exposed_comm_s
        for name, cost in variants.items():
            row[f"{name}_ms"] = round(cost.total_s * 1e3, 3)
            row[f"{name}_exposed_ms"] = round(cost.exposed_comm_s * 1e3, 3)
            row[f"{name}_exposed_delta_ms"] = round(
                (cost.exposed_comm_s - base_exposed) * 1e3, 3)
        adaptive = choose_tp_strategy(replace(cfg, distributed=replace(
            cfg.distributed, tp_strategy="adaptive")),
            generation=model.gen.name)
        row["adaptive"] = ",".join(
            f"{k}={adaptive[k]}" for k in ("qkv", "o", "up", "down"))
        row["winner"] = min(variants, key=lambda k: variants[k].total_s)
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# Rank statistics (calibration / validation)
# ---------------------------------------------------------------------------


def spearman(xs, ys) -> float:
    """Spearman rank correlation (mean-rank ties)."""
    if len(xs) != len(ys) or len(xs) < 2:
        raise ValueError("spearman needs two equal-length series, n >= 2")

    def ranks(vs):
        order = sorted(range(len(vs)), key=lambda i: vs[i])
        r = [0.0] * len(vs)
        i = 0
        while i < len(order):
            j = i
            while j + 1 < len(order) and vs[order[j + 1]] == vs[order[i]]:
                j += 1
            mean_rank = (i + j) / 2.0
            for k in range(i, j + 1):
                r[order[k]] = mean_rank
            i = j + 1
        return r

    rx, ry = ranks(list(xs)), ranks(list(ys))
    mx = sum(rx) / len(rx)
    my = sum(ry) / len(ry)
    num = sum((a - mx) * (b - my) for a, b in zip(rx, ry))
    den = math.sqrt(sum((a - mx) ** 2 for a in rx)
                    * sum((b - my) ** 2 for b in ry))
    return num / den if den else 0.0


def with_calibration(model: "CostModel", **changes) -> "CostModel":
    """A CostModel with some calibration constants replaced."""
    return CostModel(model.gen, replace(model.calib, **changes))
