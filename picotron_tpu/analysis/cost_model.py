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
    Config, num_params, resolved_cp_flavor, resolved_cp_mesh,
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


GENERATIONS: dict[str, IciGeneration] = {
    "v4": IciGeneration("v4", 3, 45e9, 4, 32.0, 275e12, 7e9),
    "v5e": IciGeneration("v5e", 2, 45e9, 16, 16.0, 197e12, 7e9),
    "v5p": IciGeneration("v5p", 3, 90e9, 4, 95.0, 459e12, 7e9),
    "v6e": IciGeneration("v6e", 2, 100e9, 16, 32.0, 918e12, 7e9),
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
                        link: AxisLink) -> float:
        """Seconds for one collective of `kind` moving `nbytes` (the full
        logical tensor for group collectives; the per-device payload for a
        ppermute shift) over one placed axis."""
        n, bw = link.size, link.bandwidth
        if n <= 1 or nbytes <= 0:
            return 0.0
        dirs = link.directions
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

        # TP: 2 fwd + 2 bwd boundary collectives per layer per microbatch,
        # for each of the attention and MLP col/row pairs; Megatron-SP
        # replaces each psum with an all-gather/reduce-scatter pair of the
        # same volume.
        if d.tp_size > 1:
            n_boundary = 2 * 2 * layers_stage * ga
            if d.sequence_parallel:
                add("sp_gather", "all_gather", ("tp",), n_boundary,
                    v_act, c.expose_layer)
                add("sp_scatter", "reduce_scatter", ("tp",), n_boundary,
                    v_act, c.expose_layer)
            else:
                add("tp_psum", "all_reduce", ("tp",), n_boundary,
                    v_act, c.expose_layer)

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
