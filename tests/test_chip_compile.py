"""The benchmark's two training steps and two serving programs, compiled for a
described TPU v5e (no chip attached): what the scopes of
picotron_tpu/telemetry/scopes.py do to the names a device trace will show, and
whether the serving programs move a whole KV pool.

On this installation a Pallas custom call is named after the innermost
element of the name stack at the call. `benchmark/layer_metrics/
flash_roofline.train.json` finds the fused engine's three flash kernels by
the name the layer scan's body gives them, and `collective_share.train.json`
finds collectives by theirs, so a scope in the wrong place silences an
accepted metric. This file holds the names; nothing here is a measurement.

The serving programs carry the paged KV pool through the layer scan. Where the
pool's scatter (`kv_write`) and gather (`paged_attention`) want different
layouts of it, the compiler copies the whole pool around one of them in every
layer: 58 copies of 1.88 GB a prefill dispatch, a third of its time on the
chip, before serve/paged_cache.py took the order it has. The compiled text
shows such a copy; the last test here counts them.

One file, topology described inside a module-scoped fixture: only one process
may load libtpu, and every xdist worker imports every test file.
"""

import importlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from picotron_tpu.config import config_from_dict
from picotron_tpu.mesh import MeshEnv
from picotron_tpu.models.llama import init_params, model_rope_tables
from picotron_tpu.parallel.api import init_sharded_state, make_train_step
from picotron_tpu.serve.engine import _get_jits, prefill_rungs
from picotron_tpu.serve.paged_cache import init_serve_cache
from picotron_tpu.serve.scheduler import blocks_for
from picotron_tpu.telemetry.scopes import SCOPES

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmark")
# Names matched by collective_share.train's pattern in the four-chip step at
# one layer a stage. The pattern also matches the two parameters of a
# reduction computation named after its psum, so one `psum_invariant.N`
# all-reduce counts 3 and one that the compiler combined with a neighbour
# into a tuple `all-reduce.N` counts 1 + 2. Until PR 38: 46 (34 psum_invariant,
# 8 all-reduce, 2 + 2 collective-permute start / done), counted on the tree
# before any scope was added. PR 38 took the scoring out of the 1F1B forward
# unit and put what is left of the unit in a branch the last stage skips. The
# forward unit's three [1,4096] merge collectives (pmax, two psums) left; they
# had been combined with the backward unit's, so they leave no name behind,
# but the backward unit's lone pmax is now `pmax.N`, which the pattern does
# not match (-1). The embedding's psum and the layers' two (attention, mlp)
# had each been one tuple all-reduce for the forward unit and the backward
# unit's forward together; in a branch and outside it they are two
# `psum_invariant.N` each (+3 names each): 46 - 1 + 9 = 54 (46
# psum_invariant, 4 all-reduce, 2 + 2 collective-permute). As instructions:
# 16 all-reduces before, 19 then, of which the last stage ran 16.
# PR 63 (the accumulating tick): 54 - 6 = 48 (40 psum_invariant, 4
# all-reduce, 2 + 2 collective-permute); 17 all-reduce instructions. The
# head's forward and backward sit in ONE branch by stage and nothing
# differentiates through a cond, so the neutral branch's anchor and its
# `bf16[]` psum are gone (-3); and at one layer a stage the manual backward's
# re-run of the o-projection is the forward's, merged by the compiler with
# its all-reduce, where remat's had its own (-3: at the cell's three layers a
# stage the two scans are loops and each keeps its own).
# PR 65 (each unit of the tick in a branch by whether the stage holds a
# microbatch for it): still 48, the same names. The backward unit's
# collectives moved into its conditional's live branch with it (8 all-reduces
# there, of which 1 + 3 sit in the lookup's and the head's branches by stage
# inside it); the forward unit's 3 stay in the branch PR 38 made, whose
# predicate widened; the two collective-permutes stay at the tick's top
# level, once a tick on every stage.
N_COLLECTIVES = 48
# temp_size_in_bytes of the four-chip step at one layer a stage on the parent
# of PR 38 (commit 977113c, this installation), whose tick held the forward
# unit's fp32 [4096,76032] logits (1.25 GB) beside the backward unit's
PARENT_FOUR_CHIP_TEMP_BYTES = 11_745_135_104
# the same on the parent of PR 63 (commit defc834), whose tick built each
# microbatch's whole gradient tree (the stage's layer stack, a zero-filled
# head and embedding) before it added the tree into the accumulator
PARENT_63_FOUR_CHIP_TEMP_BYTES = 10_661_128_192
# the same at the cell's own depth (three layers a stage) on the parent of
# PR 65 (commit 1d8b6d8), whose tick ran both units on every stage in every
# tick. PR 65's reads 9,314,992,640, 0.77% more: the backward unit's
# conditional keeps what it reads live from its start to its end (the two
# RoPE tables, buffers of the boundary's size), where the parent's scheduler
# let them share a slot with the unit's temporaries
PARENT_65_FOUR_CHIP_TEMP_BYTES_AT_DEPTH = 9_243_948_032


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the chip's compiler away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def compiled_step(topo, monkeypatch, config: str) -> str:
    """`compiled.as_text()` of the cell's train step at the cell's widths
    and one layer a stage, for the described chips."""
    return compile_step(topo, monkeypatch, config, layers=2).as_text()


_COMPILED_STEP: dict = {}  # (config, layers) -> compile_step's result


def compile_step(topo, monkeypatch, config: str, layers: int | None):
    """The cell's train step at the cell's widths and `layers` layers (None:
    the cell's own depth), compiled for the described chips."""
    if (config, layers) in _COMPILED_STEP:
        return _COMPILED_STEP[config, layers]
    # the program asks the backend whether the kernels exist; here the
    # backend is the CPU and the target is the described chip
    fa = importlib.import_module("picotron_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "compiled_kernels_available", lambda: True)
    c = load("configs", config)
    d = c["distributed"]
    if layers is not None:
        c["model"]["num_hidden_layers"] = layers
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "training")})
    n = d["dp_size"] * d["pp_size"] * d["cp_size"] * d["tp_size"]
    menv = MeshEnv.create(dp=d["dp_size"], pp=d["pp_size"], cp=d["cp_size"],
                          tp=d["tp_size"], devices=topo.devices[:n])
    state = init_sharded_state(cfg, menv, jax.random.key(0), abstract=True)
    t = cfg.training
    b = jax.ShapeDtypeStruct(
        (t.gradient_accumulation_steps, t.micro_batch_size * d["dp_size"],
         t.seq_length), jnp.int32, sharding=menv.batch_sharding())
    out = _COMPILED_STEP[config, layers] = make_train_step(
        cfg, menv).lower(state, (b, b)).compile()
    return out


def instructions(text: str):
    """[(name, op_name, whole line)] of a compiled module's instructions."""
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), op.group(1) if op else "", line))
    return out


def words(op_name: str) -> set:
    return set(re.split(r"[/()]+", op_name))


def test_one_chip_step_keeps_the_flash_kernels_names(topo, monkeypatch):
    text = compiled_step(topo, monkeypatch, "qwen2-1.5b-12l")
    assert text.startswith("HloModule jit_train_step")
    ins = instructions(text)
    pat = re.compile(load("layer_metrics", "flash_roofline.train")["params"]["pattern"])
    kernels = [(n, op) for n, op, line in ins if "tpu_custom_call" in line]
    assert len(kernels) == 3  # fwd, dq, dkv of the fused grad engine
    for name, op in kernels:
        assert pat.search(name), (name, op)
        # the rule: no scope is the innermost name-stack element at the call
        assert op.endswith("/pallas_call") and op.split("/")[-2] not in SCOPES, op
        assert not words(op) & set(SCOPES), op
    # the regions the next perf PRs need a number for are on the name stack
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    assert found >= {"embed", "attention", "mlp", "head_ce", "dw_accum", "optimizer"}


def test_four_chip_step_keeps_its_collectives_names(topo, monkeypatch):
    text = compiled_step(topo, monkeypatch, "qwen2-7b-6l-tp2pp2")
    assert text.startswith("HloModule jit_train_step")
    ins = instructions(text)
    pat = re.compile(load("layer_metrics", "collective_share.train")["params"]["pattern"])
    assert sum(1 for n, _, _ in ins if pat.search(n)) == N_COLLECTIVES
    sends = [op for n, op, _ in ins if n.startswith("collective-permute")]
    assert sends and all("pp_boundary" in words(op) for op in sends)
    reduces = [op for n, op, _ in ins if n.startswith("psum_invariant")]
    assert any("tp_reduce" in words(op) for op in reduces)
    # four kernel calls (no accepted metric finds them by name in this
    # cell): the 1F1B forward unit's forward kernel, inside `attention`, in
    # the branch the last stage does not take; and the manual backward's
    # three (PR 63: forward, dq, dkv of parallel/fused_bwd.py's two scans),
    # outside every scope and named after the scan's body as in the
    # one-chip cells: no `jvp`, no `transpose`, and remat adds no call.
    kernels = [op for _, op, line in ins if "tpu_custom_call" in line]
    assert len(kernels) == 4, kernels
    unit = [op for op in kernels if "attention" in words(op)]
    manual = [op for op in kernels if not words(op) & set(SCOPES)]
    assert len(unit) == 1 and len(manual) == 3, kernels
    for op in manual:
        assert op.endswith("closed_call/pallas_call"), op
        assert "jvp" not in op and "transpose" not in op, op
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    assert found >= {"embed", "attention", "mlp", "head_ce", "optimizer",
                     "pp_boundary", "tp_reduce", "dw_accum"}


def test_four_chip_last_stage_runs_each_forward_once(topo, monkeypatch):
    """PR 38: the 1F1B tick's forward unit never scores, and its layer block
    sits in a branch the last stage does not take (the backward unit runs
    that stage's forward of the same microbatch). Until then the last
    stage, which sets the step, ran the layers' forward and the head's
    forward twice a tick. PR 63: the backward unit's layer block is the
    manual backward's two scans, and the head's forward and backward share
    the one branch the last stage takes. PR 65: each unit sits in a branch
    of its own, taken where the stage holds a microbatch for it."""
    comp = compile_step(topo, monkeypatch, "qwen2-7b-6l-tp2pp2", layers=2)
    text = comp.as_text()
    ins = instructions(text)
    kernels = [op for _, op, line in ins if "tpu_custom_call" in line]
    in_branch = [op for op in kernels if "branch_0_fun" in words(op)]
    assert len(in_branch) == 1 and "jvp" not in in_branch[0], kernels
    # one forward matmul of the head a tick (`[4096, 3584] x [3584, 76032]`),
    # in the last stage's branch; until PR 38 the forward unit had its own
    logits = [op for _, op, line in ins if " convolution(" in line
              and re.search(r"= bf16\[(1,)?4096,76032\]", line)]
    assert len(logits) == 1 and "jvp(head_ce)" in logits[0], logits
    assert "cond/branch_1_fun" in logits[0], logits
    # PR 65: two conditionals at the tick's top level, one round each unit,
    # taken where the stage holds a microbatch for the unit this tick. The
    # forward unit's: nothing, against the layer block (the embedding's and
    # the layers' tensor-parallel all-reduces). The backward unit's: nothing
    # (the accumulators passed through), against the whole unit, 8
    # all-reduces, of which 5 sit in the three branches by stage INSIDE it,
    # as they were: the lookup (the embedding's) against the saved input;
    # the head (the CE's merge, pmax and a tuple, and dx's) against the next
    # stage's cotangent; the embedding's rows onto their accumulator (none).
    # No branch holds a collective that crosses stages (devices 0, 1 are
    # stage 0; 2, 3 the last), and no collective-permute at all.
    def conds_at(op_suffix):
        return [branch_collectives(text, line) for _, op, line in ins
                if " conditional(" in line and op.endswith(op_suffix)]

    def sizes(conds):
        return sorted(sorted(map(len, c)) for c in conds)

    assert sum(" conditional(" in line for _, _, line in ins) == 5
    units = conds_at("closed_call/cond")
    by_stage = conds_at("closed_call/cond/branch_1_fun/cond")
    assert sizes(units) == [[0, 3], [0, 8]], units
    assert sizes(by_stage) == [[0, 0], [0, 1], [0, 3]], by_stage
    for line in (line for c in units + by_stage for branch in c for line in branch):
        assert " all-reduce(" in line, line
        assert "replica_groups={{0,1},{2,3}}" in line, line
    sends = [op for n, op, _ in ins if n.startswith("collective-permute")]
    assert len(sends) == 4 and not any("cond" in words(op) for op in sends), sends
    temp = comp.memory_analysis().temp_size_in_bytes
    print(f"four-chip step: temp_size_in_bytes {temp:,} "
          f"(parent of PR 38 {PARENT_FOUR_CHIP_TEMP_BYTES:,}, "
          f"of PR 63 {PARENT_63_FOUR_CHIP_TEMP_BYTES:,})")
    assert temp <= PARENT_FOUR_CHIP_TEMP_BYTES
    # PR 63: under the parent's by at least the layer stack's gradient tree
    # (one layer a stage here, float32, halved by tp 2), which no tick builds
    m = load("configs", "qwen2-7b-6l-tp2pp2")["model"]
    h, f = m["hidden_size"], m["intermediate_size"]
    kv = m["num_key_value_heads"] * h // m["num_attention_heads"]
    layer_tree = 4 * (3 * h * f + 2 * h * h + 2 * h * kv) // 2
    assert temp <= PARENT_63_FOUR_CHIP_TEMP_BYTES - layer_tree, (temp, layer_tree)


_MOVES_NOTHING = re.compile(
    r" (parameter|get-tuple-element|tuple|bitcast|conditional|while)\(")


def makes_f32_of(comps: dict, names, sizes) -> list:
    """The instructions of the computations `names` (fused bodies apart) whose
    result is an fp32 array of one of `sizes` elements and a buffer of its
    own: no parameter, tuple, bitcast, conditional or while."""
    return [line.strip() for name in sorted(names)
            if not name.startswith("fused_computation")
            for line in comps[name]
            if "f32[" in line and set(result_sizes(line)) & set(sizes)
            and not _MOVES_NOTHING.search(line)]


def test_four_chip_tick_touches_a_leaf_only_where_it_is_used(topo, monkeypatch):
    """PR 63: the head's and the embedding's fp32 accumulators (1.09 GB
    each a chip) are written by the operation that produces their gradient,
    in the branch of the stage that produces it: the head's dW matmul with
    the convert and the add in its epilogue, the embedding's rows scattered
    onto the accumulator in place. Outside a branch by stage the tick holds
    no instruction that makes a leaf of either shape: no add, no zero fill,
    no copy (a branch that passes a leaf through must not copy it). Until
    then every tick zero-filled both on every stage and read and wrote the
    whole accumulator to add them (`select_add_fusion f32[3584,76032]` in
    the parent's trace, 4.7 ms a tick on the stage that holds no head)."""
    text = compiled_step(topo, monkeypatch, "qwen2-7b-6l-tp2pp2")
    comps = computations(text)
    ins = instructions(text)
    c = load("configs", "qwen2-7b-6l-tp2pp2")
    leaf = (c["model"]["vocab_size"] // c["distributed"]["tp_size"]
            * c["model"]["hidden_size"])
    in_loop = loop_computations(text, comps)
    in_branch = reachable(comps, {
        b for _, _, line in ins if " conditional(" in line
        for b in called(line)})
    outside = makes_f32_of(comps, in_loop - in_branch, [leaf])
    assert not outside, [line[:200] for line in outside]
    inside = makes_f32_of(comps, in_loop & in_branch, [leaf])
    assert len(inside) == 2 and all(" fusion(" in line for line in inside), inside
    bodies = ["\n".join(comps[re.search(r"calls=%?([\w.\-]+)", line).group(1)])
              for line in inside]
    assert sum(" convolution(" in b for b in bodies) == 1
    assert sum(" scatter(" in b for b in bodies) == 1


def test_four_chip_idle_tick_passes_the_accumulators_through(topo, monkeypatch):
    """PR 65, at the cell's own depth (three layers a stage, so the two
    layer scans are loops): the backward unit's conditional hands the whole
    fp32 accumulator tree (3.58 GB a chip) in and out. A copy of it round
    the conditional, or in the idle branch, would cost 9 ms a tick. Every
    instruction of the tick that makes a result shaped like an accumulator
    leaf (the head's, the embedding's, a layer stack's) sits in a live
    branch and is a fusion that updates the leaf where it lies: the reverse
    scan's `dw_accum` dynamic-update-slices, the head's dW matmul with its
    add, the embedding's scatter. None is a copy, none sits in a branch
    that only passes the accumulators through, none outside a branch."""
    comp = compile_step(topo, monkeypatch, "qwen2-7b-6l-tp2pp2", layers=None)
    text = comp.as_text()
    comps = computations(text)
    ins = instructions(text)
    d = load("configs", "qwen2-7b-6l-tp2pp2")["distributed"]
    # a chip's share of each matrix: a stack's layers over pp, one other axis
    # over tp. Four sizes: embedding = head, gate = up = down, q = o, k = v
    leaves = {math.prod(a.shape) // d["tp_size"] // (d["pp_size"] if a.ndim == 3 else 1)
              for a in jax.tree.leaves(abstract_params("qwen2-7b-6l-tp2pp2"))
              if math.prod(a.shape) >= 2**20}
    assert len(leaves) == 4, leaves
    in_loop = loop_computations(text, comps)
    conds = [line for _, _, line in ins if " conditional(" in line]
    assert len(conds) == 5
    def made(names):
        return makes_f32_of(comps, names, leaves)

    in_branch = reachable(comps, {b for line in conds for b in called(line)})
    assert not made(in_loop - in_branch), [
        line[:200] for line in made(in_loop - in_branch)]
    live = made(in_loop & in_branch)
    # seven stack leaves, the head's and the embedding's: once each
    assert len(live) == 9 and all(" fusion(" in line for line in live), live
    assert not any(re.match(r"%?copy", line) for line in live), live
    # the backward unit's conditional is the one whose branches reach the
    # reverse scan; its other branch, and whatever that calls, makes no leaf
    unit = [line for line in conds
            if any(made(reachable(comps, [b])) for b in called(line))
            and "closed_call/cond\"" in line]
    assert len(unit) == 1, unit
    idle = [b for b in called(unit[0]) if not made(reachable(comps, [b]))]
    assert len(idle) == 1, called(unit[0])
    # (what it does make: the zero cotangent it sends on, one boundary
    # activation, and scalars)
    c = load("configs", "qwen2-7b-6l-tp2pp2")
    boundary = (c["training"]["micro_batch_size"] * c["training"]["seq_length"]
                * c["model"]["hidden_size"])
    big = [line.strip()[:160] for line in comps[idle[0]]
           if not _MOVES_NOTHING.search(line) and max(result_sizes(line), default=0) > boundary]
    assert not big, big
    temp = comp.memory_analysis().temp_size_in_bytes
    print(f"four-chip step at depth: temp_size_in_bytes {temp:,} "
          f"(parent of PR 65 {PARENT_65_FOUR_CHIP_TEMP_BYTES_AT_DEPTH:,})")
    assert temp <= PARENT_65_FOUR_CHIP_TEMP_BYTES_AT_DEPTH * 1.01


def test_olmoe_step_keeps_kernels_scopes_and_fits_one_chip(topo, monkeypatch):
    """The sparse-expert cell at its own depth: the fused engine's three
    flash kernels keep the names `flash_roofline.train` finds, the expert
    matmuls are the compiler's grouped-matmul kernels under the name
    `moe_experts_ms.train` finds (they carry no name stack, so the name is all
    there is), the three expert scopes are on the name stack, and the step
    fits the chip."""
    comp = compile_step(topo, monkeypatch, "olmoe-1b-7b-1l", layers=None)
    ins = instructions(comp.as_text())
    kernels = [(n, op) for n, op, line in ins if "tpu_custom_call" in line]
    flash_pat = re.compile(load("layer_metrics", "flash_roofline.train")["params"]["pattern"])
    flash = [(n, op) for n, op in kernels if flash_pat.search(n)]
    assert len(flash) == 3, kernels
    for name, op in flash:
        assert op.endswith("/pallas_call") and not words(op) & set(SCOPES), op
    grouped_pat = re.compile(load("layer_metrics", "moe_experts_ms.train")["params"]["ops"])
    grouped = [n for n, _ in kernels if grouped_pat.search(n)]
    assert len(flash) + len(grouped) == len(kernels), kernels
    # gate, up, down forward; dX and dW of each (at one layer the fused
    # engine's re-run of gate and up is the forward's, merged by the compiler)
    assert sum(n.startswith("ragged-dot-none") for n in grouped) == 9, grouped
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    assert found >= {"embed", "attention", "mlp", "moe_router", "moe_dispatch",
                     "moe_experts", "head_ce", "dw_accum", "optimizer"}
    ma = comp.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < 15.75 * 2**30, total / 2**30


# temp_size_in_bytes of the same compile on the parent of PR 36 (commit
# 2c20159, this installation), whose head backward scattered the label term
# into a zero-filled copy of one microbatch's fp32 logits
PARENT_TEMP_BYTES = {"qwen2-1.5b-12l": 13_961_579_008,
                     "olmoe-1b-7b-1l": 9_322_046_464}


@pytest.mark.parametrize("config", sorted(PARENT_TEMP_BYTES))
def test_head_backward_takes_no_vocabulary_sized_detour(topo, monkeypatch, config):
    """The only vocabulary-sized tensors of the head + CE backward are the
    logits, dlogits and the fp32 accumulator. Before PR 36 the label pick's
    transpose was a scatter of 4,096 values into a zero-filled [tokens, vocab]
    fp32 tensor, which this compiler runs on a flat relayout of it
    (`reshape f32[622329856]`: 2.5 GB read and written a microbatch, 3.8% of
    the Qwen2 cell's step; a bf16 twin of it after the convert). The head's
    dW and the embedding's rows, on the other hand, the compiler already lands
    in the accumulator: held here so that a rewrite cannot lose it."""
    comp = compile_step(topo, monkeypatch, config, layers=None)
    c = load("configs", config)
    tokens = c["training"]["micro_batch_size"] * c["training"]["seq_length"]
    vocab, hidden = c["model"]["vocab_size"], c["model"]["hidden_size"]
    text = comp.as_text()
    ins = instructions(text)
    # no scatter into, and no flat (rank-1) form of, a tokens x vocab tensor
    flat = [line.strip()[:160] for _, _, line in ins
            if re.search(rf"= \w+\[{tokens * vocab}\]", line)]
    scatters = [line.strip()[:160] for _, _, line in ins
                if " scatter(" in line and tokens * vocab in result_sizes(line)]
    assert not flat and not scatters, (flat, scatters)
    # what the microbatch loop does at the accumulator's size under `head_ce`
    # and `embed`: the dW matmul with the fp32 add in its epilogue, and the
    # embedding's rows scattered onto the accumulator in place; no pass of
    # its own over a dense [V, H] gradient
    comps = computations(text)
    acc = [(n, line) for n, op, line in ins
           if words(op) & {"head_ce", "embed"} and "fusion(" in line
           and vocab * hidden in result_sizes(line) and "f32[" in line.split(" fusion(")[0]]
    assert len(acc) == 2, [n for n, _ in acc]
    bodies = ["\n".join(comps[re.search(r"calls=%?([\w.\-]+)", line).group(1)])
              for _, line in acc]
    assert sum(" convolution(" in b for b in bodies) == 1
    assert sum(" scatter(" in b for b in bodies) == 1
    temp = comp.memory_analysis().temp_size_in_bytes
    print(f"{config}: temp_size_in_bytes {temp:,} (parent {PARENT_TEMP_BYTES[config]:,})")
    assert temp <= PARENT_TEMP_BYTES[config]


_ABSTRACT_PARAMS: dict = {}
# serve programs of a tiny preset, under the name of a configuration: the
# blocks `lower_serve` reads of a configuration's file
TINY_SERVE = {
    # the Jamba-shaped preset with a state and a tail of whole (8, 128) tiles
    # (d_state 8; d_inner 1024, so a slot's tail is 24 rows of 128), which
    # the decode step's kernels take
    "debug-tiny-jamba": dict(
        model=dict(name="debug-tiny-jamba", hidden_size=512, mamba_d_state=8,
                   dtype="bfloat16"),
        serve=dict(decode_slots=4, block_size=4, num_blocks=32, prefill_chunk=8,
                   max_model_len=32, decode_interval=2)),
}


def blocks_of(config: str) -> dict:
    return TINY_SERVE.get(config) or load("configs", config)


def abstract_params(config: str):
    """The shapes of the configuration's parameter tree: bf16 weights, as the
    cell's runner serves them."""
    if config not in _ABSTRACT_PARAMS:
        m = config_from_dict({"model": blocks_of(config)["model"]}).model
        _ABSTRACT_PARAMS[config] = jax.eval_shape(lambda: jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), init_params(m, jax.random.key(0))))
    return _ABSTRACT_PARAMS[config]


CHAT_SLOTS = load("configs", "qwen2-1.5b")["serve"]["decode_slots"]
_LOWERED_SERVE: dict = {}  # (config, program, rows) -> lower_serve's result


def lower_serve(topo, monkeypatch, config: str, program: str, rows=None):
    """(the compiled program, the shapes of the configuration's serving
    cache, the pools' parameter numbers) of `serve_prefill` (at `rows` rows of
    the compacted batch) or `serve_decode` of a benchmark configuration at its
    widths, depth and serve settings on one described chip, pools donated: a
    program `ServeEngine` dispatches there, on the cache `init_serve_cache`
    gives the model, whatever its kind."""
    if (config, program, rows) in _LOWERED_SERVE:
        return _LOWERED_SERVE[config, program, rows]
    # the decode step asks the backend whether kernels compile; here the
    # backend is the CPU and the target is the described chip. Every serve
    # program of this file is traced under the patch: the jits are shared
    fa = importlib.import_module("picotron_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "compiled_kernels_available", lambda: True)
    c = blocks_of(config)
    cfg = config_from_dict({k: c[k] for k in ("model", "serve")})
    m, sc = cfg.model, cfg.serve
    slots = sc.decode_slots
    sh = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)

    params = on_chip(abstract_params(config))
    cache = on_chip(jax.eval_shape(lambda: init_serve_cache(
        m, sc, slots,
        sc.num_blocks or slots * blocks_for(sc.max_model_len, sc.block_size),
        sc.max_model_len)))
    cos, sin = on_chip(jax.eval_shape(
        lambda: model_rope_tables(m, max_len=sc.max_model_len)))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    decode, prefill = _get_jits(True)
    static = dict(cfg=m, temperature=0.0, top_k=0, cache_cls=type(cache))
    r = rows if program == "serve_prefill" else slots
    head = (params, cache.pools,
            tuple(i32(r, width) for width, _ in cache.table_specs))
    if program == "serve_prefill":
        low = prefill.lower(
            *head, i32(rows, sc.prefill_chunk), i32(rows), i32(rows), i32(rows),
            i32(rows), key, cos, sin, **static)
    else:
        low = decode.lower(
            *head, i32(slots), i32(slots), i32(slots), i32(slots), i32(slots),
            key, cos, sin, interval=sc.decode_interval, eos_token_id=None,
            **static)
    n = len(jax.tree.leaves(params))
    out = _LOWERED_SERVE[config, program, rows] = (
        low.compile(), cache, set(range(n, n + len(cache.pools))))
    return out


def compiled_serve(topo, monkeypatch, program: str, rows=None,
                   config: str = "qwen2-1.5b", text: bool = True):
    """(`compiled.as_text()` or with `text` false the compiled program, the
    pool's shape, the pools' parameter numbers) of the chat cell's
    `serve_prefill` or `serve_decode`. `config`: another configuration with
    one K/V pool and one table a slot (EvaByte's, whose table row is as wide
    as its law says)."""
    comp, cache, pools = lower_serve(topo, monkeypatch, config, program, rows)
    return comp.as_text() if text else comp, cache.k.shape, pools


def computations(text: str) -> dict:
    """{computation's name: its lines} of a compiled module."""
    out, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = out.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return out


_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)"
                    r"|branch_computations=\{([^}]*)\}")


def called(line_or_lines: str) -> set:
    """The computations an instruction (or a computation's text) names:
    fusions, reducers, loop bodies and conditions, a conditional's branches."""
    return {x.strip().lstrip("%") for one, many in _CALLS.findall(line_or_lines)
            for x in (one + many).split(",")}


def reachable(comps: dict, roots) -> set:
    """`roots` and every computation they call, directly or not."""
    calls = {name: called("\n".join(lines)) for name, lines in comps.items()}
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo += calls.get(name, ())
    return seen


def branch_collectives(text: str, conditional_line: str) -> list:
    """For each branch of a `conditional` instruction, the collective
    instructions in the branch's computation and whatever it calls."""
    comps = computations(text)
    branches = _CALLS.search(conditional_line).group(2).split(",")
    return [[line.strip() for name in sorted(reachable(comps, [b.strip().lstrip("%")]))
             for line in comps.get(name, ())
             if re.search(r" (all-reduce|all-gather|reduce-scatter|all-to-all"
                          r"|collective-permute)(-start)?\(", line)]
            for b in branches]


def loop_computations(text: str, comps: dict) -> set:
    """The names of the computations that run inside a while loop: the loops'
    bodies and whatever they call."""
    return reachable(comps, set(re.findall(r"body=%?([\w.\-]+)", text)))


def whole_pool_copies(text: str, pool_shape) -> list:
    """[(in a while body?, computation, the instruction up to its operands)]
    for every copy or transpose whose result has as many elements as a KV
    pool, fusions named after their copy included, with its layout."""
    comps = computations(text)
    in_loop = loop_computations(text, comps)
    n_pool = math.prod(pool_shape)
    found = []
    for name, lines in comps.items():
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
                         line)
            if not m or not (m.group(3) in ("copy", "transpose")
                             or "copy" in m.group(1)):
                continue
            if math.prod(int(d) for d in m.group(2).split(",") if d) == n_pool:
                found.append((name in in_loop, name, line.strip().split("(%")[0]))
    return found


# instructions whose result is another's buffer, or none
_NO_WRITE = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
             "conditional", "call", "optimization-barrier", "copy-start",
             "slice-start", "dynamic-slice-start"}


def weights_written(text: str, params, at_least: int = 8 * 2**20) -> list:
    """[(in a while body?, the instruction's name without its number, a result
    of it)] for every result of at least `at_least` bytes that a top-level
    instruction (not one inside a fused computation) of a while body or of the
    entry computation leaves in HBM (no `S(1)`) and that is shaped like one or
    more layers of a stack leaf [L, ...] of `params`, the experts' banks apart
    (they are addressed inside their stacks by the grouped kernel): a layer's
    matrix sliced out of its stack, or a stack's layers laid out anew, and
    written where the matmul that wants it could have read it in place."""
    from picotron_tpu.generate import BANKS

    tails = {(a.dtype.name.replace("bfloat", "bf").replace("float", "f"),
              a.shape[0], tuple(a.shape[1:]))
             for path, a in jax.tree_util.tree_leaves_with_path(params)
             if a.ndim >= 3 and not {getattr(k, "key", None) for k in path} & set(BANKS)}
    comps = computations(text)
    fused = {c for lines in comps.values() for line in lines
             for c in re.findall(r" fusion\(.*calls=%?([\w.\-]+)", line)}
    in_loop = loop_computations(text, comps) - fused
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)
    found = []
    for comp in sorted(in_loop | {entry}):
        for line in comps[comp]:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+?)(?:\.\d+)? = (.*?) ([\w\-]+)\(", line)
            if not m or m.group(3) in _NO_WRITE:
                continue
            for dt, dims, layout in re.findall(r"(\w+)\[([\d,]*)\](\{[^}]*\})?", m.group(2)):
                d = tuple(int(x) for x in dims.split(",") if x)
                like = any(dt == t and (d == tail or (d[1:] == tail and d[0] <= n))
                           for t, n, tail in tails)
                if (like and "S(" not in layout
                        and math.prod(d) * int(dt.lstrip("bf")) // 8 >= at_least):
                    found.append((comp in in_loop, m.group(1), f"{dt}[{dims}]"))
    return found


def assert_weights_read_in_place(text: str, config: str, left=()):
    """No weight is written in a loop or in the entry computation
    (`weights_written`) but those of `left`, which are there, each as often
    as it is listed."""
    found = weights_written(text, abstract_params(config))
    assert sorted(found) == sorted(left), "\n".join(
        f"{'in a loop' if loop else 'at entry '} {name} {shape}"
        for loop, name, shape in found)


# every shape the chat cell's engine dispatches: the prefill program at each
# rung of its ladder of row counts (1, 4, 16, 32), and the decode program
@pytest.mark.parametrize("program,rows", [
    *(("serve_prefill", r) for r in prefill_rungs(CHAT_SLOTS)),
    ("serve_decode", None)])
def test_serving_program_moves_no_whole_pool(topo, monkeypatch, program, rows):
    text, pool_shape, pools = compiled_serve(topo, monkeypatch, program, rows)
    # PR 25's accepted metrics find the programs and their scopes by name
    assert text.startswith(f"HloModule jit_{program}")
    found = set().union(*(words(op) for _, op, _ in instructions(text)))
    assert found >= {"kv_write", "paged_attention"}
    # none inside a loop and none outside one (PR 26; before it `serve_prefill`
    # held 2 a layer inside the scan and 2 outside, all of the V pool: 58 a
    # dispatch). One outside a loop costs 5 ms a dispatch, one inside 140.
    copies = whole_pool_copies(text, pool_shape)
    assert not copies, f"{program} copies a whole KV pool {pool_shape}:\n" + "\n".join(
        f"{'in a loop' if loop else 'outside  '} {comp}: {ins}"
        for loop, comp, ins in copies)
    # the scatters write the donated pools in place: both are aliased to outputs
    head = text.splitlines()[0]
    alias = head[head.index("input_output_alias={"):head.index("entry_computation_layout")]
    aliased = {int(p) for p in re.findall(r"\}: \((\d+), ", alias)}
    assert aliased >= pools, (alias, pools)
    # no layer's matrix is written out of its stack, and no stack laid out
    # anew: until PR 45 `serve_decode` copied q's stack once a dispatch
    # (`copy.19 bf16[28,1536,1536]{1,2,0}`)
    assert_weights_read_in_place(text, "qwen2-1.5b")


def result_sizes(line: str) -> list:
    """The element counts of an instruction's result(s)."""
    m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) [\w\-]+\(", line)
    return [math.prod(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))] if m else []


def kv_write_kernels(ins, pool_shapes, program: str, calls: int) -> list:
    """The K/V write kernels [(name, op_name)] of a compiled serve program,
    found as `kv_write_ms.serve` finds their events. Since PR 59
    `serve_decode` holds `calls` of them, under the scope `kv_write`, and no
    instruction under a scatter's name, fused or not, whose result has a
    pool's shape: a step's rows go into both pools through `paged_kv_write`,
    in place. A prefill program is the parent's: such scatters, no kernel."""
    named = re.compile(load("layer_metrics", "kv_write_ms.serve")["params"]["ops"])
    sizes = [[math.prod(shape)] for shape in pool_shapes]
    written = [(n, op) for n, op, line in ins
               if "tpu_custom_call" in line and named.search(n)]
    scatters = [n for n, op, line in ins if "tpu_custom_call" not in line
                and "scatter" in words(op) and result_sizes(line) in sizes]
    if program == "serve_decode":
        assert len(written) == calls and not scatters, (written, scatters)
        assert all("kv_write" in words(op) for _, op in written), written
    else:
        assert not written and scatters, (written, scatters)
    return written


def test_decode_attends_in_place_and_prefill_keeps_its_views(topo, monkeypatch):
    """The decode program reads K/V through the block table inside one Mosaic
    kernel a layer and builds no view of the pool: before PR 32 it held two
    gathers `bf16[16384,16,128]` a layer (2 KV heads x 32 slots x 256 table
    entries of 16 x 128), 69% of its device time. It writes the step's rows
    by one kernel a layer too, and holds no scatter shaped like a pool: before
    PR 59 two a layer (`fusion.171/.173 bf16[2,28,8192,16,128]`), 7% of the
    cell's busy time. The prefill program is what it was: its rows' views
    gathered, its rows scattered, no kernel."""
    c = load("configs", "qwen2-1.5b")
    m, sc = c["model"], c["serve"]
    max_blocks = blocks_for(sc["max_model_len"], sc["block_size"])

    def view(rows):  # elements of the gathered view of `rows` slots, K or V
        return (m["num_key_value_heads"] * rows * max_blocks * sc["block_size"]
                * (m["hidden_size"] // m["num_attention_heads"]))

    assert view(CHAT_SLOTS) == 2 * 32 * 256 * 16 * 128
    text, pool_shape, _ = compiled_serve(topo, monkeypatch, "serve_decode")
    comps = computations(text)
    big = [line.strip()[:160] for lines in comps.values() for line in lines
           if view(CHAT_SLOTS) in result_sizes(line)]
    assert not big, "serve_decode holds a view-sized result:\n" + "\n".join(big)
    in_loop = loop_computations(text, comps)
    kernels = [(comp, n, op) for comp, lines in comps.items()
               for n, op, line in instructions("\n".join(lines))
               if "tpu_custom_call" in line]
    assert len(kernels) == 2, kernels
    (comp, name, op), (wcomp, wname, _) = sorted(kernels, key=lambda k: k[1])
    assert comp in in_loop and wcomp in in_loop  # inside the layer scan: once a layer
    assert name.startswith("paged_decode_attention")  # what a trace shows
    assert "paged_attention" in words(op)
    written = kv_write_kernels(instructions(text), [pool_shape], "serve_decode", 1)
    assert [n for n, _ in written] == [wname], kernels
    # the prefill program: the view of its rows is there, K and V; no kernel;
    # its chunks' rows are scattered, K and V
    for rows in prefill_rungs(CHAT_SLOTS)[:2]:
        ptext, _, _ = compiled_serve(topo, monkeypatch, "serve_prefill", rows)
        assert "tpu_custom_call" not in ptext
        kv_write_kernels(instructions(ptext), [pool_shape], "serve_prefill", 0)
        views = [n for n, op, line in instructions(ptext)
                 if view(rows) in result_sizes(line)
                 and "paged_attention" in words(op)]
        assert len(views) >= 2, (rows, views)


# ---------------------------------------------------------------------------
# mellum2-12b-a2.5b-8l: experts and two kinds of cache state in both programs
# ---------------------------------------------------------------------------

def compiled_mellum(topo, monkeypatch, program: str, rows=None,
                    config: str = "mellum2-12b-a2.5b-8l"):
    """(compiled, the two pools' shapes, the pools' parameter numbers) of
    `serve_decode` or `serve_prefill` (at `rows` rows) of a configuration
    with sliding and full layers (Mellum2's, K-EXAONE's)."""
    comp, cache, pools = lower_serve(topo, monkeypatch, config, program, rows)
    return comp, (cache.k.shape, cache.wk.shape), pools


@pytest.mark.parametrize("program,rows", [
    ("serve_decode", None), ("serve_prefill", 1), ("serve_prefill", 32)])
def test_mellum2_serving_programs(topo, monkeypatch, program, rows):
    """Both serve programs of `mellum2-12b-a2.5b-8l` compile for a v5e and
    fit it; no pool of either kind is copied whole and all four are written
    in place; no layer's expert bank is sliced out of its stack (the
    grouped kernel addresses a layer's experts inside it); the names the
    cell's metrics read are there: the decode kernel in both layer kinds'
    scopes, the experts' kernel under `moe_experts`, the expert scopes."""
    comp, pool_shapes, pools = compiled_mellum(topo, monkeypatch, program, rows)
    text = comp.as_text()
    assert text.startswith(f"HloModule jit_{program}")
    ins = instructions(text)
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    assert found >= {"kv_write", "paged_attention", "attn_full", "attn_window", "mlp",
                     "moe_router", "moe_dispatch", "moe_experts", "sample"}
    for shape in pool_shapes:
        copies = whole_pool_copies(text, shape)
        assert not copies, f"{program} copies a whole pool {shape}: {copies}"
    head = text.splitlines()[0]
    alias = head[head.index("input_output_alias={"):head.index("entry_computation_layout")]
    assert {int(p) for p in re.findall(r"\}: \((\d+), ", alias)} >= pools, alias
    kernels = [(n, op) for n, op, line in ins if "tpu_custom_call" in line]
    attn = re.compile(load("layer_metrics", "paged_attention_ms.serve")["params"]["ops"])
    paged = [(n, op) for n, op in kernels if attn.search(n)]
    grouped = [(n, op) for n, op in kernels if n.startswith("grouped_experts")]
    # a write kernel a layer of a decode step, into the pools of its kind (PR 59)
    written = kv_write_kernels(ins, pool_shapes, program, 4)
    assert len(grouped) + len(paged) + len(written) == len(kernels), kernels
    # the experts of each of the period's four layers are ONE kernel (gate,
    # up, activation and down; ops/grouped_experts.py) at every number of
    # rows, and its event carries the scope `moe_experts_ms.serve` and
    # `moe_experts_roofline.serve` sum: they find it by that word alone
    scopes = set(load("layer_metrics", "moe_experts_ms.serve")["params"]["scopes"])
    assert len(grouped) == 4, kernels
    assert all(scopes <= words(op) for _, op in grouped), grouped
    # nothing of the two forms it replaced: no compiler's grouped matmul, no
    # [tokens, 64, 896] product of every row with every expert
    assert "ragged-dot" not in text
    m = load("configs", "mellum2-12b-a2.5b-8l")["model"]
    tokens = 32 if program == "serve_decode" else rows * 256
    every = tokens * m["num_experts"] * m["moe_intermediate_size"]
    dense = [line.strip()[:160] for _, _, line in ins if every in result_sizes(line)]
    assert not dense, dense
    if program == "serve_decode":
        assert len(paged) == 4  # three sliding layers and a full one a period
        assert sum("attn_window" in words(op) for _, op in paged) == 3
        assert sum("attn_full" in words(op) for _, op in paged) == 1
    else:
        assert not paged  # a chunk walks its keys in tiles, no kernel
    ma = comp.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < 15.75 * 2**30, total / 2**30
    if program == "serve_decode":
        # no layer's expert banks are copied out of their stacks: scanned as
        # a period's [4, 64, ...] slices they were 3 GiB of temporaries, and a
        # second read and a write of every weight in a memory-bound step
        assert ma.temp_size_in_bytes < 0.5 * 2**30, ma.temp_size_in_bytes / 2**30
    # nor any other leaf of the stack (PR 45). Scanned as a period's [4, ...]
    # slices, q and o were `dynamic-slice_bitcast_fusion.27/.28` an iteration
    # and o's three later layers a `copy-done bf16[4,4096,2304]` back to HBM,
    # and q, wanted with the contracted dimension minor by a matmul that had
    # the head split folded in, was re-laid whole once a dispatch (`copy.165
    # bf16[8,2304,4096]{1,2,0}`) and a layer a `fusion` in the loop
    assert_weights_read_in_place(text, "mellum2-12b-a2.5b-8l")


@pytest.mark.parametrize("program,rows", [
    ("serve_decode", None), ("serve_prefill", 1), ("serve_prefill", 48)])
def test_k_exaone_serving_programs(topo, monkeypatch, program, rows):
    """Both serve programs of `k-exaone-236b-a23b-5l-ep8` compile for a v5e
    and fit it beside each other's pools; no pool of either kind is copied
    whole and all four are written in place through BOTH stacks; the decode
    step attends through the kernel in every layer: the dense stack's one
    sliding layer, the expert stack's whole period (S, S, F) in its scan
    body and the sliding layer left over after it; the band's kernel holds 9
    pages a chunk (window 128 / 16 + 1), not the ring's 25; the experts of
    each expert layer are one grouped kernel; the scopes the cell's metrics
    read are there, the expert ones and the window ones in one program."""
    name = "k-exaone-236b-a23b-5l-ep8"
    comp, pool_shapes, pools = compiled_mellum(topo, monkeypatch, program, rows, name)
    c = load("configs", name)
    assert pool_shapes == ((8, 1, 48 * 1024, 16, 128), (8, 4, 48 * 25, 16, 128))
    text = comp.as_text()
    assert text.startswith(f"HloModule jit_{program}")
    ins = instructions(text)
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    assert found >= {"kv_write", "paged_attention", "attn_full", "attn_window", "mlp",
                     "moe_router", "moe_dispatch", "moe_experts", "moe_shared", "sample"}
    for shape in pool_shapes:
        copies = whole_pool_copies(text, shape)
        assert not copies, f"{program} copies a whole pool {shape}: {copies}"
    head = text.splitlines()[0]
    alias = head[head.index("input_output_alias={"):head.index("entry_computation_layout")]
    assert {int(p) for p in re.findall(r"\}: \((\d+), ", alias)} >= pools, alias
    kernels = [(n, op) for n, op, line in ins if "tpu_custom_call" in line]
    attn = re.compile(load("layer_metrics", "paged_attention_ms.serve")["params"]["ops"])
    paged = [(n, op) for n, op in kernels if attn.search(n)]
    grouped = [(n, op) for n, op in kernels if n.startswith("grouped_experts")]
    # a write kernel beside each decode attention, into the pools of its kind (PR 59)
    written = kv_write_kernels(ins, pool_shapes, program, 5)
    assert len(grouped) + len(paged) + len(written) == len(kernels), kernels
    scopes = set(load("layer_metrics", "moe_experts_ms.serve")["params"]["scopes"])
    assert all(scopes <= words(op) for _, op in grouped), grouped
    assert "ragged-dot" not in text
    # the scan body's three expert layers and the one left over, each one kernel a
    # block of tokens (`ops/moe.py MAX_SORTED_BYTES`)
    assert len(grouped) % 4 == 0 and len(grouped) >= 4, kernels
    if program == "serve_decode":
        assert len(paged) == 5  # (S) | (S, S, F) in the scan body + (S) after it
        assert sum("attn_window" in words(op) for _, op in paged) == 4
        assert sum("attn_full" in words(op) for _, op in paged) == 1
        # (that the windowed calls buffer the band's 9 pages and the full one 64
        # is inside the kernels: tests/test_paged_attention.py
        # test_a_chunk_is_sized_by_the_band)
    else:
        assert not paged  # a chunk walks its keys in tiles, no kernel
    ma = comp.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(program, rows, "total GiB", total / 2**30, "temp GiB",
          ma.temp_size_in_bytes / 2**30)
    assert total < 15.75 * 2**30, total / 2**30
    # every projection is read where it lies (PR 45). Until then the decode
    # step's loop wrote the expert stack's four q projections out of a copy of
    # the stack every step (`fusion.870`: four results `bf16[1,6144,8192]
    # {1,2,0}`, three of them in HBM; k and v likewise, `fusion.899/.904`) and
    # evicted and fetched the dense stack's (`copy-done bf16[1,6144,8192]`);
    # its entry computation re-laid q, k and v of both stacks once a dispatch
    # (`copy.122 bf16[4,6144,8192]{1,2,0}`, `copy.119`, `copy.121/.123`); the
    # prefill program's copied each layer's o (`copy bf16[8192,6144]` x 4 and
    # three `slice_bitcast_fusion`s at one row)
    assert_weights_read_in_place(text, name)


# arguments + outputs - aliased + temporaries of `serve_decode` of
# `evabyte-6.5b-8l` on the parent of PR 49 (commit cc3a921, this installation),
# which gathered and pooled every slot's chunk in every step
PARENT_EVA_DECODE_BYTES = 9_755_770_368


@pytest.mark.parametrize("program,rows", [
    ("serve_decode", None), ("serve_prefill", 1), ("serve_prefill", 16)])
def test_evabyte_serving_programs(topo, monkeypatch, program, rows):
    """Both serve programs of `evabyte-6.5b-8l` compile for a v5e and fit it
    beside the pool; the one pool is not copied whole and is written in place
    (the positions' rows and the summaries' rows: two calls of the write
    kernel a layer in the decode step since PR 59 and no scatter shaped like
    the pool, where there were four, a fifth of the cell's busy time; two
    scatters a tensor and layer in a prefill chunk, as before); the
    decode step attends through THE decode kernel under its own name, one call
    in the layer scan's body, with a chunk of pages that fits its 32 KV heads
    into fast memory; a prefill chunk walks tiles; the pooling's scope is in
    both. The decode step reads a chunk's rows out of the pool and pools them
    inside ONE conditional on the step's positions (PR 49), which returns the
    two summaries and no pool, at the parent's memory."""
    name = "evabyte-6.5b-8l"
    comp, pool_shape, pools = compiled_serve(topo, monkeypatch, program, rows, name,
                                             text=False)
    assert pool_shape == (32, 8, load("configs", name)["serve"]["num_blocks"], 16, 128)
    text = comp.as_text()
    assert text.startswith(f"HloModule jit_{program}")
    ins = instructions(text)
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    assert found >= {"kv_write", "paged_attention", "eva_summarise", "mlp", "sample"}
    copies = whole_pool_copies(text, pool_shape)
    assert not copies, f"{program} copies the whole pool {pool_shape}: {copies}"
    head = text.splitlines()[0]
    alias = head[head.index("input_output_alias={"):head.index("entry_computation_layout")]
    assert {int(p) for p in re.findall(r"\}: \((\d+), ", alias)} >= pools, alias
    kernels = [(n, op) for n, op, line in ins if "tpu_custom_call" in line]
    attn = re.compile(load("layer_metrics", "paged_attention_ms.serve")["params"]["ops"])
    # a decode step: the positions' rows, then the summaries' rows through the
    # same call (ONE lowering: `_kv_write_call` is jitted), each the loop's own
    # instruction with both pools aliased through it; a prefill chunk scatters
    # its blocks and its summaries' rows
    written = kv_write_kernels(ins, [pool_shape], program, 2)
    if program == "serve_decode":
        (paged,) = [k for k in kernels if k not in written]
        assert attn.search(paged[0]) and "paged_attention" in words(paged[1]), kernels
    else:
        assert not kernels  # a chunk walks its keys in tiles, no kernel
    ma = comp.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(program, rows, "total GiB", total / 2**30, "temp GiB",
          ma.temp_size_in_bytes / 2**30)
    assert total < 15.75 * 2**30, total / 2**30
    # until PR 45 `serve_decode` re-laid q, k and v once a dispatch (three
    # `copy bf16[8,4096,4096]{1,2,0}`)
    assert_weights_read_in_place(text, name)
    if program != "serve_decode":
        return
    comps = computations(text)
    in_loop = loop_computations(text, comps)
    # PR 49: the chunk's rows are read and pooled only in a step where some slot's
    # position ends a chunk. ONE conditional, in the layer loop's body (a while
    # body that the step loop's body reaches), which the compiler has not turned
    # into a select over both branches; it READS the pools and returns the two
    # summaries, [slots, 1, Hkv, D] each, and no pool
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    conds = [(c, line) for c in sorted(in_loop) for line in comps[c]
             if " conditional(" in line]
    assert len(conds) == 1, conds
    where, cond = conds[0]
    assert where in bodies and any(
        where in reachable(comps, [b]) for b in bodies - {where}), where
    slots, hkv, d = load("configs", name)["serve"]["decode_slots"], pool_shape[0], pool_shape[-1]
    assert result_sizes(cond) == [slots * hkv * d] * 2, cond
    # every gather of 16 slots x 32 heads x 16 rows x 128 is in a computation the
    # conditional's branches reach: two (K and V), none in the loop body itself
    branches = reachable(comps, called(cond))
    chunk = slots * hkv * load("configs", name)["model"]["chunk_size"] * d
    placed = [(c, words(op), line) for c, lines in comps.items()
              for _, op, line in instructions("\n".join(lines))]
    gathers = [(c, line) for c, w, line in placed
               if "gather" in w and chunk in result_sizes(line)]
    assert sum(" fusion(" in line for _, line in gathers) == 2, gathers
    assert {c for c, _ in gathers} <= branches and where not in branches, (
        {c for c, _ in gathers} - branches)
    # where the chunk's rows lie is worked out outside the conditional, so that
    # the conditional does not read the table: what a query may see (the table's
    # gather under `paged_attention`, the same for every layer) is then hoisted
    # out of the layer loop as it was, one a step and not one a layer
    seen = {c for c, w, line in placed
            if {"paged_attention", "gather"} <= w and " fusion(" in line}
    assert seen and where not in seen, seen
    # and it costs the two summaries' room, no copy of anything it reads
    assert total <= PARENT_EVA_DECODE_BYTES + 2**20, total - PARENT_EVA_DECODE_BYTES


# What PR 45 left of `weights_written` (it took q_b's: `copy.50 bf16[4,1536,
# 24576]{1,2,0}` and `copy.47` at the decode program's entry, a `constant_
# dynamic-slice_fusion` or a `copy` of a layer's in the prefill program's scan).
# `kv_b` is no [rows, in] x [in, out] matmul: the absorbed decode step contracts
# it a head, over `nope` on the way in and over `rank` on the way out (ops/mla.py
# up_weights), and a batched matmul wants its batch, the heads, major. So the
# decode program re-lays the expert stack's four once a dispatch (`copy.35`,
# 134 MB) and keeps the dense stack's, re-laid into fast memory at entry, by
# evicting and fetching it every step (`copy-done.3`, 34 MB each way); the
# prefill program at 16 rows re-lays the dense stack's (`copy.111`). `kv_a`
# [4,7680,576] arrives as {1,2,0} (the runtime's own layout for a minor
# dimension of 576) and is wanted {2,1,0}: `copy.34`, 35 MB a dispatch.
PANGU_WEIGHTS_WRITTEN = {
    ("serve_decode", None): [(False, "copy", "bf16[4,512,32768]"),
                             (False, "copy", "bf16[4,7680,576]"),
                             (True, "copy-done", "bf16[1,512,32768]")],
    ("serve_prefill", 16): [(False, "copy", "bf16[1,512,32768]")],
}


def compiled_pangu(topo, monkeypatch, program: str, rows=None):
    """(compiled, the latent pool's shape, the pool's parameter number) of
    `serve_decode` or `serve_prefill` (at `rows` rows) of the
    openPangu-Ultra-MoE configuration."""
    comp, cache, (pool,) = lower_serve(
        topo, monkeypatch, "openpangu-ultra-moe-5l-ep16", program, rows)
    return comp, cache.kv.shape, pool


@pytest.mark.parametrize("program,rows", [
    ("serve_decode", None), ("serve_prefill", 1), ("serve_prefill", 4),
    ("serve_prefill", 16)])
def test_openpangu_serving_programs(topo, monkeypatch, program, rows):
    """Both serve programs of `openpangu-ultra-moe-5l-ep16` compile for a v5e
    and fit it beside the weights; the latent pool is never copied whole and
    is written in place; the decode step attends through the latent kernel
    (five layers, two stacks: one call a stack's scan body) under
    `attn_latent`, a prefill chunk through the prefill kernel, likewise one
    call a stack, under a name the decode kernel's metrics do not match,
    and no score-shaped array (float32 [heads, chunk, tile]) is left in the
    program; the experts of the expert stack are one grouped kernel; the
    scopes the cell's metrics read are there."""
    comp, pool_shape, pool = compiled_pangu(topo, monkeypatch, program, rows)
    text = comp.as_text()
    assert text.startswith(f"HloModule jit_{program}")
    ins = instructions(text)
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    # `mla_absorb` (queries into the latent space, values out of it) is the
    # decode step's: a prefill chunk expands inside its kernel
    assert found >= {"kv_write", "paged_attention", "attn_latent", "mla_q",
                     "mla_kv_latent", "mla_o", "mlp", "moe_router",
                     "moe_dispatch", "moe_experts", "moe_shared", "sample"}
    assert ("mla_absorb" in found) == (program == "serve_decode")
    copies = whole_pool_copies(text, pool_shape)
    assert not copies, f"{program} copies the latent pool {pool_shape}: {copies}"
    head = text.splitlines()[0]
    alias = head[head.index("input_output_alias={"):head.index("entry_computation_layout")]
    assert pool in {int(p) for p in re.findall(r"\}: \((\d+), ", alias)}, alias
    kernels = [(n, op) for n, op, line in ins if "tpu_custom_call" in line]
    attn = re.compile(load("layer_metrics", "mla_attention_ms.serve")["params"]["ops"])
    latent = [(n, op) for n, op in kernels if attn.search(n)]
    grouped = [(n, op) for n, op in kernels if n.startswith("grouped_experts")]
    chunked = [(n, op) for n, op in kernels
               if n.startswith("latent_prefill_attention")]
    assert len(grouped) + len(latent) + len(chunked) == len(kernels), kernels
    # the expert stack's scan body calls the kernel once a block of tokens
    # (`ops/moe.py MAX_SORTED_BYTES`: up to 4,096 tokens at these widths go
    # in one; 16 rows of 1,024 would go in four)
    chunk = load("configs", "openpangu-ultra-moe-5l-ep16")["serve"]["prefill_chunk"]
    tokens = 16 if program == "serve_decode" else rows * chunk
    assert len(grouped) == max(1, tokens // 4096), kernels
    assert all("moe_experts" in words(op) for _, op in grouped)
    assert "ragged-dot" not in text
    if program == "serve_decode":
        assert len(latent) == 2  # the dense stack's body and the expert stack's
        assert all("attn_latent" in words(op) for _, op in latent)
        assert not chunked
    else:
        assert not latent  # `mla_attention_ms.serve` divides by decode steps
        assert len(chunked) == 2
        assert all({"paged_attention", "attn_latent"} <= words(op)
                   for _, op in chunked)
        heads = load("configs", "openpangu-ultra-moe-5l-ep16")["model"][
            "num_attention_heads"]
        assert f"f32[{heads},{chunk},512]" not in text
    ma = comp.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < 15.75 * 2**30, total / 2**30
    print(program, rows, "total GiB", total / 2**30, "temp GiB",
          ma.temp_size_in_bytes / 2**30)
    assert_weights_read_in_place(text, "openpangu-ultra-moe-5l-ep16",
                                 PANGU_WEIGHTS_WRITTEN.get((program, rows), ()))


# What the LongCat programs write that is shaped like a stack's weights
# (`weights_written`; PANGU_WEIGHTS_WRITTEN's twin). A layer holds two of every
# attention projection and two dense MLPs on a sublayer axis behind the layer
# axis, and both sublayers read q_a, q_b, o, gate, up and down where they lie
# (each by ONE index into the stack read as [2 L, ...]: a layer's [2, ...]
# slice, shared by its two pairs, was written out every iteration, four
# `dynamic-slice_bitcast_fusion`s of 100-150 MB). What is left is what openPangu
# leaves: `kv_b` re-laid once a dispatch with the heads major for the absorbed
# step's batched matmuls (134 MB), and `kv_a`'s stack (57 MB; the runtime's
# layout for a minor dimension of 576 is not the matmul's) kept in fast memory
# by the compiler, which evicts it and fetches a layer's slice inside the loop.
LONGCAT_WEIGHTS_WRITTEN = {
    ("serve_decode", None): [(False, "copy", "bf16[4,2,512,16384]"),
                             (True, "copy-done", "bf16[4,2,6144,576]")],
    # the prefill kernel's expansion wants kv_b's stack laid out anew as well,
    # once a dispatch (0.16 ms of a chunk's 35)
    ("serve_prefill", 1): [(False, "copy", "bf16[4,2,512,16384]")],
}


@pytest.mark.parametrize("program,rows", [("serve_decode", None), ("serve_prefill", 1)])
def test_longcat_serving_programs(topo, monkeypatch, program, rows):
    """Both serve programs of `longcat-flash-omni-4l-ep32` compile for a v5e
    and fit it beside the weights; the latent pool has a row an attention
    sublayer (8 for 4 layers), is never copied whole and is written in place;
    the one stack's scan body holds both attentions of a layer, so the latent
    kernel is called twice; no projection or dense MLP of either sublayer is
    sliced out of its stack or laid out anew; the experts are one grouped
    kernel a layer; the scopes the cell's metrics read are there, the dense
    MLPs under `mlp` and the expert branch under `scmoe_branch` and NOT under
    `mlp`."""
    config = "longcat-flash-omni-4l-ep32"
    comp, cache, (pool,) = lower_serve(topo, monkeypatch, config, program, rows)
    text = comp.as_text()
    assert text.startswith(f"HloModule jit_{program}")
    assert cache.kv.shape == (8, 16384, 16, 640)
    ins = instructions(text)
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    assert found >= {"kv_write", "paged_attention", "attn_latent", "mla_q",
                     "mla_kv_latent", "mla_o", "mlp", "scmoe_branch", "moe_zero",
                     "moe_router", "moe_dispatch", "moe_experts", "sample"}
    assert "moe_shared" not in found
    assert ("mla_absorb" in found) == (program == "serve_decode")
    # the branch is its own region: nothing of it under `mlp`, which is the
    # two dense MLPs (dense_mlp_ms.serve reads `mlp`). The jitted expert block
    # (ops/moe.py moe_mlp_served) is lowered once: some of its operations carry
    # its call site's names and others only those entered inside it, so
    # scmoe_branch_ms.serve reads the union of the five names
    branch = set(load("layer_metrics", "scmoe_branch_ms.serve")["params"]["any_scope"])
    assert branch == {"scmoe_branch", "moe_router", "moe_dispatch", "moe_experts",
                      "moe_zero"}
    assert not [op for _, op, _ in ins if "mlp" in words(op) and words(op) & branch]
    copies = whole_pool_copies(text, cache.kv.shape)
    assert not copies, f"{program} copies the latent pool {cache.kv.shape}: {copies}"
    head = text.splitlines()[0]
    alias = head[head.index("input_output_alias={"):head.index("entry_computation_layout")]
    assert pool in {int(p) for p in re.findall(r"\}: \((\d+), ", alias)}, alias
    kernels = [(n, op) for n, op, line in ins if "tpu_custom_call" in line]
    attn = re.compile(load("layer_metrics", "mla_attention_ms.serve")["params"]["ops"])
    latent = [(n, op) for n, op in kernels if attn.search(n)]
    grouped = [(n, op) for n, op in kernels if n.startswith("grouped_experts")]
    chunked = [(n, op) for n, op in kernels
               if n.startswith("latent_prefill_attention")]
    assert len(grouped) + len(latent) + len(chunked) == len(kernels), kernels
    assert len(grouped) == 1 and "ragged-dot" not in text
    assert all({"moe_experts", "scmoe_branch"} <= words(op) for _, op in grouped)
    if program == "serve_decode":
        assert len(latent) == 2 and not chunked  # one scan body, two attentions
        assert all("attn_latent" in words(op) for _, op in latent)
    else:
        assert len(chunked) == 2 and not latent
        assert all({"paged_attention", "attn_latent"} <= words(op) for _, op in chunked)
    ma = comp.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < 15.75 * 2**30, total / 2**30
    print(program, rows, "total GiB", total / 2**30, "temp GiB",
          ma.temp_size_in_bytes / 2**30)
    assert_weights_read_in_place(text, config,
                                 LONGCAT_WEIGHTS_WRITTEN.get((program, rows), ()))


# ---------------------------------------------------------------------------
# qwen3-next-80b-a3b-12l-ep8: a recurrent-state pool beside the K/V pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("program,rows", [
    ("serve_decode", None), ("serve_prefill", 1), ("serve_prefill", 16)])
def test_qwen3_next_serving_programs(topo, monkeypatch, program, rows):
    """Both serve programs of `qwen3-next-80b-a3b-12l-ep8` compile for a v5e
    and fit it beside the weights; the K/V pool holds the three full layers
    alone and the state pool a row a slot and mixer; neither, nor the tail
    pool, is copied whole, and all four ride their program in place (the rule
    the K/V pool is held to: the state pool is carried through the layer scan
    and the decode program's step scan; a decode step and a prefill chunk
    each hand the pool whole to a kernel that is aliased to it); the scan
    body is one period (L, L, L, F), so the decode step calls the attention's
    kernel once a body, the state's three times and the experts' grouped
    kernel four times, and a prefill chunk the chunk's kernel three times in
    the place of the chunked form's triangular solves; the scopes the cell's
    metrics read are there."""
    config = "qwen3-next-80b-a3b-12l-ep8"
    comp, cache, pools = lower_serve(topo, monkeypatch, config, program, rows)
    text = comp.as_text()
    assert text.startswith(f"HloModule jit_{program}")
    assert cache.k.shape == (2, 3, 49152, 16, 256)
    assert cache.state.shape == (9, 16, 32, 128, 128) and cache.state.dtype == jnp.float32
    assert cache.tail.shape == (9, 16, 24576) and cache.tail.dtype == jnp.float32
    ins = instructions(text)
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    assert found >= {"gdn", "gdn_conv", "gdn_state", "attn_gate", "kv_write",
                     "paged_attention", "attn_full", "mlp", "moe_router", "moe_dispatch",
                     "moe_experts", "moe_shared", "moe_shared_gate", "sample"}
    for shape in (cache.k.shape, cache.state.shape, cache.tail.shape):
        copies = whole_pool_copies(text, shape)
        if shape == cache.tail.shape:
            # the tail pool is 14 MB: the compiler may keep it in VMEM while a
            # step's mixers gather and scatter their rows (`copy-start` /
            # `copy-done` to `S(1)` and back, 17 us each way), which is a move
            # it chose, not a layout two of the pool's users disagree on
            copies = [c for c in copies if "copy-done" not in c[2]]
        assert not copies, f"{program} copies a whole pool {shape}: {copies}"
    head = text.splitlines()[0]
    alias = head[head.index("input_output_alias={"):head.index("entry_computation_layout")]
    assert {int(p) for p in re.findall(r"\}: \((\d+), ", alias)} >= pools, alias
    kernels = [(n, op) for n, op, line in ins if "tpu_custom_call" in line]
    attn = re.compile(load("layer_metrics", "paged_attention_ms.serve")["params"]["ops"])
    paged = [(n, op) for n, op in kernels if attn.search(n)]
    grouped = [(n, op) for n, op in kernels if n.startswith("grouped_experts")]
    state = [(n, op) for n, op in kernels if n.startswith("gated_delta_step_pooled")]
    chunk = [(n, op) for n, op in kernels if n.startswith("gated_delta_chunk_pooled")]
    # the period's one full layer writes its rows by the kernel in a decode step
    # (PR 59), by the scatter in a prefill chunk
    written = kv_write_kernels(ins, [cache.k.shape], program, 1)
    assert (len(grouped) + len(paged) + len(state) + len(chunk) + len(written)
            == len(kernels)), kernels
    assert len(grouped) % 4 == 0 and len(grouped) >= 4 and "ragged-dot" not in text
    assert all("moe_experts" in words(op) for _, op in grouped), grouped
    # a batch's rows of one mixer's state, gathered or to be scattered
    rows_of_state = f"f32[{rows or cache.state.shape[1]},32,128,128]"
    # the chunked form's triangular solve, 64 x 64 a head and sub-chunk
    solves = [n for n, _, line in ins if "InvertDiagBlocksLowerTriangular" in line]
    if program == "serve_decode":
        assert len(paged) == 1 and "attn_full" in words(paged[0][1])
        # PR 52: a step's three mixers of the scan body update the state pool
        # in place, one kernel each, under the scope the cell's metrics read;
        # no row of state is gathered out of the pool or scattered back
        assert len(state) == 3 and all({"gdn", "gdn_state"} <= words(op) for _, op in state)
        assert rows_of_state not in text and not solves and not chunk
    else:
        assert not paged  # a chunk walks its keys in tiles, no kernel
        # PR 54: a chunk's three mixers of the scan body run the rule as one
        # kernel each over the state pool in place, under the scope the
        # cell's metrics read: no row of state is gathered or scattered, and
        # the chunked form's triangular solve (64 sequential rows) is gone
        assert len(chunk) == 3 and all({"gdn", "gdn_state"} <= words(op) for _, op in chunk)
        assert not state and rows_of_state not in text and not solves
        assert "triangular" not in text.lower()
        # q and k reach the kernel as the mixer's fusions wrote them, a row a
        # key head: nothing re-lays them on the way (a reshape to [B, s, Hk x
        # d_k] is a pass over both that a compiled program pays every mixer)
        relaid = [n for n, op, line in ins if "gdn_state" in words(op)
                  and re.search(rf" f32\[{rows},256,2048\]", line)]
        assert not relaid, relaid
    ma = comp.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(program, rows, "total GiB", total / 2**30, "temp GiB",
          ma.temp_size_in_bytes / 2**30)
    assert total < 15.75 * 2**30, total / 2**30
    if program == "serve_decode":
        # what the program held before PR 52, with its 32 MiB gathered copies
        assert total <= 10.37 * 2**30, total / 2**30
    assert_weights_read_in_place(text, config)


def test_qwen3_next_under_ad_keeps_the_chunked_form(topo, monkeypatch):
    """A Gated DeltaNet block of the cell's widths under `jax.grad`, compiled
    for a v5e with kernels available: the recurrence is `gated_delta_chunked`
    (its triangular solve is in the program), and the
    prefill chunk's kernel, which has no VJP, is not reached: `_gdn_block`
    holds its own state and never asks a serving cache."""
    from picotron_tpu.models.llama import _gdn_block

    fa = importlib.import_module("picotron_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "compiled_kernels_available", lambda: True)
    config = "qwen3-next-80b-a3b-12l-ep8"
    m = config_from_dict({"model": load("configs", config)["model"]}).model
    sh = jax.sharding.SingleDeviceSharding(topo.devices[0])
    lp = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype, sharding=sh),
                      {k: v for k, v in abstract_params(config)["layers"].items()
                       if k.startswith("gdn_") or k == "input_norm"})
    x = jax.ShapeDtypeStruct((1, 256, m.hidden_size), jnp.bfloat16, sharding=sh)

    def loss(x, lp):
        return jnp.sum(_gdn_block(x, lp, m).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, lp).compile().as_text()
    assert "gated_delta_chunk_pooled" not in text and "gated_delta_step_pooled" not in text
    assert any("InvertDiagBlocksLowerTriangular" in line for _, _, line in instructions(text))


# ---------------------------------------------------------------------------
# jamba2-3b: Mamba mixers' state pool beside the two attentions' K/V pool
# ---------------------------------------------------------------------------

def _jamba_pools_ride_in_place(text, cache, pools, program, kv: bool = True):
    """Neither the K/V pool, nor the state pool, nor the tail pool is copied
    whole (each is carried through the layer scan, and the decode program's
    step scan, in ONE layout), and all four are aliased to their outputs.
    `kv` false: the state and tail pools alone (a tiny preset's 8 KB K/V pool
    of heads of 16 the compiler re-lays in VMEM, a shape no chip run has)."""
    for shape in ((cache.k.shape,) if kv else ()) + (cache.state.shape, cache.tail.shape):
        copies = whole_pool_copies(text, shape)
        if shape == cache.tail.shape or not kv:
            # a small pool the compiler may park in VMEM (`copy-start` /
            # `copy-done` to `S(1)` and back, in the layout it has): a move it
            # chose, not a disagreement about layout
            parked = [c for c in copies if "copy-done" in c[2]]
            assert len({re.sub(r"S\(1\)", "", c[2].split("{")[1].split("}")[0])
                        for c in parked}) <= 1, parked
            copies = [c for c in copies if c not in parked]
        assert not copies, f"{program} copies a whole pool {shape}: {copies}"
    head = text.splitlines()[0]
    alias = head[head.index("input_output_alias={"):head.index("entry_computation_layout")]
    assert {int(p) for p in re.findall(r"\}: \((\d+), ", alias)} >= pools, alias


JAMBA_SERVE = load("configs", "jamba2-3b")["serve"]
JAMBA_SLOTS = JAMBA_SERVE["decode_slots"]


@pytest.mark.parametrize("program,rows", [
    ("serve_decode", None), ("serve_prefill", 1), ("serve_prefill", 16),
    ("serve_prefill", JAMBA_SLOTS)])
def test_jamba_serving_programs(topo, monkeypatch, program, rows):
    """Both serve programs of `jamba2-3b` compile for a v5e and fit it beside
    the weights, the largest prefill rung included; the K/V pool holds the two
    attention layers alone and the state pool a row a slot and mixer, float32,
    a channel's 16 states down the sublanes; no pool is copied whole and all
    four ride their program in place; the scan body is one period of 14 (7
    mixers, an attention, 6 mixers), so a decode step calls the state's kernel
    13 times a body and the attention's once, and a prefill chunk the chunk's
    kernel 13 times (its attention walks tiles, no kernel); neither gathers a
    row of state; the scopes the cell's metrics read are there."""
    config = "jamba2-3b"
    comp, cache, pools = lower_serve(topo, monkeypatch, config, program, rows)
    text = comp.as_text()
    assert text.startswith(f"HloModule jit_{program}")
    slots = JAMBA_SLOTS
    assert cache.k.shape == (1, 2, JAMBA_SERVE["num_blocks"], JAMBA_SERVE["block_size"], 128)
    # the decode kernel takes the slots' tables whole into SMEM (1 MiB)
    assert slots * cache.tables.shape[1] * 4 <= 2**19
    assert cache.state.shape == (26, slots, 16, 5120) and cache.state.dtype == jnp.float32
    assert cache.tail.shape == (26, slots, 120, 128) and cache.tail.dtype == jnp.float32
    ins = instructions(text)
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    recurrence = "ssm_step" if program == "serve_decode" else "ssm_scan"
    assert found >= {"ssm_mixer", "ssm_conv", recurrence, "kv_write", "paged_attention",
                     "attn_full", "mlp", "sample"}
    assert not found & {"ssm_step", "ssm_scan"} - {recurrence}
    _jamba_pools_ride_in_place(text, cache, pools, program)
    kernels = [(n, op) for n, op, line in ins if "tpu_custom_call" in line]
    attn = re.compile(load("layer_metrics", "paged_attention_ms.serve")["params"]["ops"])
    paged = [(n, op) for n, op in kernels if attn.search(n)]
    step = [(n, op) for n, op in kernels if n.startswith("selective_scan_step_pooled")]
    chunk = [(n, op) for n, op in kernels if n.startswith("selective_scan_chunk_pooled")]
    conv = [(n, op) for n, op in kernels if n.startswith("ssm_conv_step_pooled")]
    # the period's one attention layer writes its rows by the kernel in a decode
    # step (PR 59), by the scatter in a prefill chunk
    written = kv_write_kernels(ins, [cache.k.shape], program, 1)
    assert (len(paged) + len(step) + len(chunk) + len(conv) + len(written)
            == len(kernels)), kernels
    # the live rows' states alone: no batch of states is gathered or scattered
    assert f"f32[{rows or slots},16,5120]" not in text
    if program == "serve_decode":
        # 20 query heads over one K/V head of 128 through the decode kernel
        assert len(paged) == 1 and "attn_full" in words(paged[0][1]) and not chunk
        assert len(step) == 13 and all({"ssm_mixer", "ssm_step"} <= words(op) for _, op in step)
        # ... and their tails alone: the convolution's kernel, under both scopes
        assert len(conv) == 13 and all({"ssm_mixer", "ssm_conv", "ssm_step"} <= words(op)
                                       for _, op in conv)
        assert f"f32[{slots},120,128]" not in text
    else:
        assert not paged and not step and not conv
        assert len(chunk) == 13 and all({"ssm_mixer", "ssm_scan"} <= words(op)
                                        for _, op in chunk)
    ma = comp.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(program, rows, "total GiB", total / 2**30, "temp GiB",
          ma.temp_size_in_bytes / 2**30)
    assert total < 15.75 * 2**30, total / 2**30
    assert_weights_read_in_place(text, config)


@pytest.mark.parametrize("program,rows", [("serve_decode", None), ("serve_prefill", 4)])
def test_tiny_jamba_serving_programs(topo, monkeypatch, program, rows):
    """The tiny Jamba preset's two serve programs (at a state of 8 x 1024 and
    a tail of 24 x 128, which the decode step's kernels take) compile for a v5e with the state pool in
    one layout: no pool-sized copy, every pool aliased; the decode step's
    five mixers a scan body go through the step's kernel, a prefill chunk's
    through the chunk's."""
    comp, cache, pools = lower_serve(topo, monkeypatch, "debug-tiny-jamba", program, rows)
    text = comp.as_text()
    assert cache.state.shape == (10, 4, 8, 1024) and cache.tail.shape == (10, 4, 24, 128)
    assert cache.k.shape == (1, 2, 32, 4, 128)
    _jamba_pools_ride_in_place(text, cache, pools, program, kv=False)
    kernel = "selective_scan_" + ("step" if program == "serve_decode" else "chunk") + "_pooled"
    calls = [n for n, op, line in instructions(text) if "tpu_custom_call" in line]
    ssm = [n for n in calls if n.startswith("selective_scan_")]
    assert len(ssm) == 5 and all(n.startswith(kernel) for n in ssm), ssm
    assert len([n for n in calls if n.startswith("ssm_conv_step_pooled")]) == (
        5 if program == "serve_decode" else 0)
