"""Collective-schedule audit — read the program's communication off its HLO.

The composed step's collectives are implicit: `lax.psum(..., ('dp','ep',
'cp'))` in the shard_map body, GSPMD-inserted reshardings, pipeline
ppermutes. Whether the schedule is the *intended* one (exactly one grad
all-reduce over the data axes; no all-gather quietly materializing a
replicated tensor bigger than any weight) is checkable by parsing the
lowered module text — no TPU time, no execution.

Two rule families:

- **presence**: the schedule a config promises must exist — a grad-sync
  all-reduce whose replica-group size is dp*ep*cp (the fused data axes),
  a pipeline boundary collective_permute when pp > 1, an expert-dispatch
  all_to_all when ep > 1, the Megatron-SP all-gather/reduce-scatter pair
  over tp under sequence_parallel, the K/V-ring collective_permute when
  cp > 1, and the Ulysses seq<->head all_to_all under attn_impl='ulysses'.
  These rules are grad-engine-independent: the fused engine's manual
  backward (parallel/fused_bwd.py) must lower the same per-axis schedule
  the AD engine's transposes produce, so `grad_engine: fused` configs are
  audited, not skipped.
- **budget** (the accidental-replication detector): no all-gather may
  produce an output larger than the configured byte budget. The default
  budget is the largest thing the program legitimately gathers — the
  biggest single param leaf or one microbatch of full-sequence
  activations, whichever is larger; an all-gather above that is some
  tensor being silently un-sharded.

Collectives over size-1 mesh axes lower to replica groups of size 1 and
cost nothing; the audit counts only *effective* ops (group size > 1, or
any cross-device permute pair).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Optional

import jax

from picotron_tpu.analysis.report import ERROR, INFO, Report
from picotron_tpu.parallel.fused_bwd import resolved_grad_engine

CHECK = "collectives"

KINDS = ("all_reduce", "all_gather", "reduce_scatter", "collective_permute",
         "all_to_all")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
                "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2,
                "ui16": 2, "i8": 1, "ui8": 1, "i1": 1}

# stablehlo: replica_groups = dense<[[0, 1], [2, 3]]> : tensor<GxSxi64>
_RE_GROUPS = re.compile(
    r"replica_groups = dense<[^>]*> : tensor<(\d+)x(\d+)xi64>")
# stablehlo: source_target_pairs = dense<...> : tensor<Nx2xi64>
_RE_PAIRS = re.compile(
    r"source_target_pairs = dense<([^>]*)> : tensor<(\d+)x2xi64>")
# result types: "-> tensor<1x32x64xbf16>" (take the last on the line)
_RE_RESULT = re.compile(r"-> tensor<([0-9x]*)x?([a-z]+[0-9]+|i1)>")
# compiled-HLO dialect (optimized module text): replica_groups={{0,2},{1,3}}
_RE_HLO_GROUPS = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
# compiled-HLO iota form: replica_groups=[2,4]<=[8] -> 2 groups of 4
_RE_HLO_IOTA = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[")
_RE_HLO_PAIRS = re.compile(r"source_target_pairs=\{([^}]*)\}")
_RE_HLO_SHAPE = re.compile(r"=\s*([a-z]+[0-9]+|pred)\[([0-9,]*)\]")


@dataclass(frozen=True)
class CollectiveOp:
    kind: str                       # one of KINDS
    group_size: Optional[int]       # participants per replica group
    n_groups: Optional[int]
    nbytes: Optional[int]           # result size, when parseable
    shape: Optional[tuple]
    dtype: Optional[str]
    line: int                       # 1-based line in the module text

    @property
    def effective(self) -> bool:
        """Moves bytes between devices (vs a compiled-away size-1 group)."""
        if self.kind == "collective_permute":
            return (self.n_groups or 0) > 0
        return (self.group_size or 0) > 1


def _result_bytes(line: str):
    m = None
    for m in _RE_RESULT.finditer(line):
        pass
    if m is None:
        return None, None, None
    dims_txt, dtype = m.group(1), m.group(2)
    dims = tuple(int(d) for d in dims_txt.split("x") if d) if dims_txt \
        else ()
    nbytes = math.prod(dims) * _DTYPE_BYTES.get(dtype, 4) if dims else \
        _DTYPE_BYTES.get(dtype, 4)
    return nbytes, dims, dtype


def parse_collectives(text: str) -> list[CollectiveOp]:
    """Collective ops from module text — StableHLO (`stablehlo.all_reduce`)
    or compiled HLO (`all-reduce(`); both dialects normalize to KINDS."""
    ops: list[CollectiveOp] = []
    lines = text.splitlines()
    for i, line in enumerate(lines):
        kind = None
        for k in KINDS:
            # compiled HLO spells the opcode hyphenated at its call site
            # (`%ag = f32[8,8]{1,0} all-gather(...)`, async `-start`
            # variant included; `-done` and operand references are not
            # new ops)
            if (f"stablehlo.{k}" in line
                    or re.search(
                        rf"(?<![%a-z-]){k.replace('_', '-')}(?:-start)?\(",
                        line)):
                kind = k
                break
        if kind is None:
            continue
        group_size = n_groups = None
        if kind == "collective_permute":
            m = _RE_PAIRS.search(line)
            if m:
                n_groups = int(m.group(2))
            else:
                m = _RE_HLO_PAIRS.search(line)
                if m:
                    n_groups = len([p for p in m.group(1).split("},{") if p])
        else:
            m = _RE_GROUPS.search(line) or _RE_HLO_IOTA.search(line)
            if m:
                n_groups, group_size = int(m.group(1)), int(m.group(2))
            else:
                m = _RE_HLO_GROUPS.search(line)
                if m:
                    groups = m.group(1).split("},{")
                    n_groups = len(groups)
                    group_size = len(groups[0].split(","))
        # result type: same line for region-free ops, else the region's
        # closing `}) : (...) -> type` a few lines down
        nbytes = dims = dtype = None
        if "stablehlo" in line:
            if "-> tensor<" in line:
                nbytes, dims, dtype = _result_bytes(line)
            else:
                for j in range(i + 1, min(i + 64, len(lines))):
                    if lines[j].lstrip().startswith("})"):
                        nbytes, dims, dtype = _result_bytes(lines[j])
                        break
        else:
            m = _RE_HLO_SHAPE.search(line)
            if m:
                dtype = m.group(1)
                dims = tuple(int(d) for d in m.group(2).split(",") if d)
                nbytes = math.prod(dims) * _DTYPE_BYTES.get(dtype, 4)
        ops.append(CollectiveOp(kind, group_size, n_groups, nbytes, dims,
                                dtype, i + 1))
    return ops


def default_gather_budget(cfg, state) -> int:
    """Largest tensor the program legitimately all-gathers in one op: the
    biggest param leaf, or one microbatch of full-sequence activations
    (sequence-parallel / ulysses gathers restore [mbs, S, H])."""
    from picotron_tpu.models.llama import compute_dtype

    import jax.numpy as jnp

    param_max = max(
        (math.prod(p.shape) * jnp.dtype(p.dtype).itemsize
         for p in jax.tree_util.tree_leaves(state.params)), default=0)
    act = (cfg.training.micro_batch_size * cfg.training.seq_length
           * cfg.model.hidden_size
           * jnp.dtype(compute_dtype(cfg.model)).itemsize)
    return max(param_max, act, 1)


def audit_collectives(cfg, *, text: str = None, state=None,
                      budget_bytes: int = None, menv=None,
                      cost_model=None) -> Report:
    """Audit a config's collective schedule. Pass `text` (+ `state`) to
    audit an existing lowering; otherwise the train step is lowered here.
    With `cost_model` (analysis/cost_model.CostModel), the parsed ops are
    additionally priced against the generation's ICI topology and the
    report's info table gains a `predicted_comm` breakdown — the costed
    ranking tools/shardcheck.py --cost surfaces."""
    if text is None:
        from picotron_tpu.analysis.trace import lower_train_step

        low = lower_train_step(cfg, menv)
        text, state = low.text, low.state
    ops = parse_collectives(text)
    eff = [op for op in ops if op.effective]
    d = cfg.distributed
    rep = Report()

    counts = {k: sum(1 for op in eff if op.kind == k) for k in KINDS}
    rep.info[CHECK] = {
        **counts,
        "total_effective": len(eff),
        "compiled_away (size-1 groups)": len(ops) - len(eff),
        # which grad engine the audited program actually lowered — the
        # fused engine's manual backward must emit the same per-axis
        # schedule as the AD engine (SP reduce-scatter/all-gather pair, CP
        # reverse-ring ppermute, Ulysses all-to-all), so the presence
        # rules below audit `grad_engine: fused` configs instead of
        # skipping them; tests/test_shardcheck.py pins the negative case
        # (a deleted SP reduce-scatter must flag).
        "grad_engine": resolved_grad_engine(cfg),
    }

    # -- presence rules ----------------------------------------------------
    grad_group = d.dp_size * d.ep_size * d.cp_size
    if grad_group > 1:
        grad_ars = [op for op in eff if op.kind == "all_reduce"
                    and op.group_size == grad_group]
        if not grad_ars:
            rep.add(CHECK, ERROR, "all_reduce",
                    f"no all-reduce over the fused data axes found "
                    f"(expected replica groups of size dp*ep*cp = "
                    f"{grad_group}): gradients are NOT being synchronized "
                    f"across data-parallel shards")
        else:
            rep.add(CHECK, INFO, "all_reduce",
                    f"{len(grad_ars)} all-reduce op(s) over the fused data "
                    f"axes (group size {grad_group}) — gradient/loss sync")
    if d.pp_size > 1 and not any(op.kind == "collective_permute"
                                 for op in eff):
        rep.add(CHECK, ERROR, "collective_permute",
                f"pp_size={d.pp_size} but the lowered step contains no "
                f"collective_permute: the pipeline boundary exchange is "
                f"missing")
    if (d.ep_size > 1 and cfg.model.num_experts
            and not any(op.kind == "all_to_all" for op in eff)):
        rep.add(CHECK, ERROR, "all_to_all",
                f"ep_size={d.ep_size} with {cfg.model.num_experts} experts "
                f"but no all_to_all: expert dispatch is not crossing the "
                f"'ep' axis (tokens only ever reach local experts)")

    # per-axis attention/SP schedule (engine-independent: the AD engine's
    # transposes and the fused engine's manual backward must both emit
    # these — a fused config that lost one has a broken segment VJP)
    if d.sequence_parallel and d.tp_size > 1:
        sp_rs = [op for op in eff if op.kind == "reduce_scatter"
                 and op.group_size == d.tp_size]
        sp_ag = [op for op in eff if op.kind == "all_gather"
                 and op.group_size == d.tp_size]
        if not sp_rs:
            rep.add(CHECK, ERROR, "reduce_scatter",
                    f"sequence_parallel with tp_size={d.tp_size} but no "
                    f"reduce-scatter over tp: the Megatron-SP row-parallel "
                    f"exit (g) is missing — partial block outputs are "
                    f"never reduced across tp shards")
        if not sp_ag:
            rep.add(CHECK, ERROR, "all_gather",
                    f"sequence_parallel with tp_size={d.tp_size} but no "
                    f"all-gather over tp: the SP column-parallel entry "
                    f"(f) is missing — the seq-sharded residual stream "
                    f"never re-assembles the full sequence")
        if sp_rs and sp_ag:
            rep.add(CHECK, INFO, "sp_pair",
                    f"SP f/g pair present over tp ({len(sp_ag)} "
                    f"all-gather, {len(sp_rs)} reduce-scatter ops of "
                    f"group size {d.tp_size})")
    if d.cp_size > 1:
        from picotron_tpu.config import resolved_cp_flavor, resolved_cp_mesh

        flavor = resolved_cp_flavor(cfg)
        if flavor == "ulysses":
            cp_a2a = [op for op in eff if op.kind == "all_to_all"
                      and op.group_size == d.cp_size]
            if not cp_a2a:
                rep.add(CHECK, ERROR, "all_to_all",
                        f"cp flavor 'ulysses' with cp_size={d.cp_size} "
                        f"but no all_to_all of group size {d.cp_size}: "
                        f"the Ulysses seq<->head trade is missing")
        elif flavor == "mesh":
            # the 2D schedule's signature: a head-scatter all_to_all whose
            # group spans exactly the INNER factor and a row ring's
            # collective_permute for the outer factor — each degenerate
            # factorization drops exactly its own requirement
            cp_x, cp_y = resolved_cp_mesh(cfg)
            if cp_y > 1 and not any(
                    op.kind == "all_to_all" and op.group_size == cp_y
                    for op in eff):
                rep.add(CHECK, ERROR, "all_to_all",
                        f"mesh cp flavor {cp_x}x{cp_y} but no all_to_all "
                        f"of group size {cp_y}: the head scatter over the "
                        f"inner submesh factor is missing")
            if cp_x > 1 and not any(op.kind == "collective_permute"
                                    for op in eff):
                rep.add(CHECK, ERROR, "collective_permute",
                        f"mesh cp flavor {cp_x}x{cp_y} but the lowered "
                        f"step contains no collective_permute: the row "
                        f"ring over the outer submesh factor is missing")
            if cp_x > 1 and cp_y > 1 and any(
                    op.kind == "all_to_all" and op.group_size == d.cp_size
                    for op in eff):
                rep.add(CHECK, ERROR, "all_to_all",
                        f"mesh cp flavor {cp_x}x{cp_y} but an all_to_all "
                        f"spans the FULL cp axis (group size {d.cp_size}): "
                        f"an implicit reshard widened the 2D schedule's "
                        f"subgroup collective")
        elif not any(op.kind == "collective_permute" for op in eff):
            rep.add(CHECK, ERROR, "collective_permute",
                    f"cp_size={d.cp_size} (ring attention) but the "
                    f"lowered step contains no collective_permute: the "
                    f"K/V ring is missing")

    # -- budget rule: the accidental-replication detector ------------------
    if budget_bytes is None and state is not None:
        budget_bytes = default_gather_budget(cfg, state)
    if budget_bytes is not None:
        for op in eff:
            if op.kind != "all_gather" or op.nbytes is None:
                continue
            if op.nbytes > budget_bytes:
                rep.add(CHECK, ERROR, f"all_gather@L{op.line}",
                        f"all-gather output {op.dtype}{list(op.shape)} is "
                        f"{op.nbytes} bytes, over the replication budget "
                        f"of {budget_bytes} bytes — something sharded is "
                        f"being materialized fully replicated")
        rep.info[CHECK]["gather_budget_bytes"] = budget_bytes

    # -- optional ICI cost pricing ----------------------------------------
    if cost_model is not None:
        priced = cost_model.price_ops(cfg, eff)
        by_kind: dict = {}
        for p in priced:
            by_kind[p["kind"]] = by_kind.get(p["kind"], 0.0) + p["secs"]
        rep.info[CHECK]["predicted_comm"] = {
            "generation": cost_model.gen.name,
            "total_ms": round(sum(p["secs"] for p in priced) * 1e3, 4),
            "by_kind_ms": {k: round(v * 1e3, 4)
                           for k, v in sorted(by_kind.items())},
            "unattributed_ops": sum(1 for p in priced if p["axis_guess"]),
        }
    return rep
