"""Elastic scale-out (picotron_tpu/resilience/elastic.py +
tools/elastic_resize.py): constant-global-batch resize planning, ZeRO-1
shard round-trip bitwise parity, the restore-time topology guard in both
modes, the offline re-stamp CLI (incl. its refuse-corrupt safety), and
ckpt_doctor's source-topology column. The full multi-process dp_resize
chaos scenario is the slow-marked half in test_resilience.py."""

import json
import os
import sys

import jax
import numpy as np
import pytest

from picotron_tpu.checkpoint import CheckpointManager
from picotron_tpu.config import (
    CheckpointConfig, Config, DistributedConfig, ModelConfig, TrainingConfig,
)
from picotron_tpu.mesh import MeshEnv
from picotron_tpu.parallel.api import (
    abstract_master, init_sharded_state, offload_zero1_info,
)
from picotron_tpu.resilience import elastic


# ---------------------------------------------------------------------------
# Resize planning / cursor translation / topology helpers (pure)
# ---------------------------------------------------------------------------


def test_plan_resize_prefers_keeping_mbs():
    # dp 2 -> 1 at gbs 4: per-replica batch doubles into ga
    p = elastic.plan_resize(micro_batch_size=2,
                            gradient_accumulation_steps=1,
                            dp_size=2, dp_new=1)
    assert (p.micro_batch_size, p.gradient_accumulation_steps) == (2, 2)
    assert p.global_batch_size == 4
    assert p.overrides() == {
        "distributed": {"dp_size": 1},
        "training": {"micro_batch_size": 2,
                     "gradient_accumulation_steps": 2},
    }


def test_plan_resize_shrinks_mbs_on_growth():
    # dp 2 -> 4 at gbs 4: per-replica batch halves below mbs
    p = elastic.plan_resize(micro_batch_size=2,
                            gradient_accumulation_steps=1,
                            dp_size=2, dp_new=4)
    assert (p.micro_batch_size, p.gradient_accumulation_steps) == (1, 1)
    assert p.global_batch_size == 4


def test_plan_resize_respects_ep_and_rejects_indivisible():
    p = elastic.plan_resize(micro_batch_size=2,
                            gradient_accumulation_steps=2,
                            dp_size=4, dp_new=2, ep_size=2)
    assert p.global_batch_size == 32
    assert (p.micro_batch_size * p.gradient_accumulation_steps
            * p.dp_new * 2) == 32
    with pytest.raises(ValueError, match="not divisible"):
        elastic.plan_resize(micro_batch_size=2,
                            gradient_accumulation_steps=1,
                            dp_size=2, dp_new=3)


def test_translate_cursor_is_passthrough_at_constant_gbs():
    st = {"epoch": 3, "cursor": 12}
    assert elastic.translate_dataloader_state(st, gbs_old=4,
                                              gbs_new=4) == st
    # a changed gbs whose boundary the cursor doesn't land on is a hard
    # error — never a silent replay/skip
    with pytest.raises(ValueError, match="step boundary"):
        elastic.translate_dataloader_state({"epoch": 0, "cursor": 6},
                                           gbs_old=6, gbs_new=4)


def test_topology_mismatch_and_describe():
    a = elastic.topology_from_distributed(
        DistributedConfig(dp_size=2, tp_size=2))
    assert elastic.describe_topology(a) == "dp2 pp1 ep1 cp1 tp2"
    assert a["world_size"] == 4
    b = dict(a, dp=4, world_size=8)
    assert elastic.topology_mismatch(a, b) == ["dp"]
    assert elastic.topology_mismatch(a, dict(a)) == []
    assert elastic.topology_mismatch(None, a) == []  # nothing recorded


def test_topology_records_and_compares_slices():
    """The slice count rides the topology schema as placement metadata:
    recorded by topology_from_distributed, rendered only when > 1 (the
    single-slice string stays byte-identical to the pre-slices one),
    compared with a default of 1 so pre-slices checkpoints read as
    single-slice — and it never multiplies into world_size."""
    multi = elastic.topology_from_distributed(
        DistributedConfig(dp_size=2, tp_size=2, cp_size=2, slices=2))
    assert multi["slices"] == 2
    assert multi["world_size"] == 8  # slices partition the axes, not x2
    assert elastic.describe_topology(multi) == \
        "dp2 pp1 ep1 cp2 tp2 slices2"
    solo = elastic.topology_from_distributed(
        DistributedConfig(dp_size=2, tp_size=2, cp_size=2))
    assert "slices" not in elastic.describe_topology(solo)
    assert elastic.topology_mismatch(multi, solo) == ["slices"]
    # a pre-slices topology dict (no field at all) means single-slice
    legacy = {ax: solo[ax] for ax in elastic.TOPOLOGY_AXES}
    assert elastic.topology_mismatch(legacy, multi) == ["slices"]
    assert elastic.topology_mismatch(legacy, solo) == []


def test_saved_topology_meta_fallback(tmp_path):
    """Pre-manifest (legacy) step dirs fall back to meta.json's recorded
    config; a dir recording neither yields None (guard disengages)."""
    step = tmp_path / "step_00000001"
    step.mkdir()
    assert elastic.saved_topology(str(step)) is None
    (step / "meta.json").write_text(json.dumps(
        {"config": {"distributed": {"dp_size": 2, "tp_size": 4}}}))
    topo = elastic.saved_topology(str(step))
    assert topo["dp"] == 2 and topo["tp"] == 4 and topo["world_size"] == 8


# ---------------------------------------------------------------------------
# ZeRO-1 shard arithmetic: N -> M -> N bitwise round trip
# ---------------------------------------------------------------------------


def test_zero1_resize_round_trip_is_bitwise():
    """The acceptance-criteria pin: fp32 ZeRO-1 optimizer shards pushed
    through a 4 -> 2 -> 4 resize round trip are bitwise identical to the
    never-resized twin — on the REAL per-leaf placements offload_zero1_
    info derives for a dp=4 zero1 run, not synthetic shapes."""
    cfg = Config(distributed=DistributedConfig(dp_size=4, zero1=True))
    info = offload_zero1_info(cfg, abstract_master(cfg))
    assert info is not None and any(p is not None for p in info)
    leaves = jax.tree.leaves(abstract_master(cfg))
    rng = np.random.default_rng(0)
    checked = 0
    for leaf, place in zip(leaves, info):
        if place is None:
            continue
        dim, _axes, sizes = place
        n = int(np.prod(sizes))
        full = rng.standard_normal(leaf.shape).astype(np.float32)
        never = elastic.split_zero1(full, dim, n)  # the un-resized twin
        round_trip = elastic.resize_zero1(
            elastic.resize_zero1(never, dim, n // 2), dim, n)
        assert len(round_trip) == n
        for a, b in zip(never, round_trip):
            assert a.tobytes() == b.tobytes()  # bitwise, not allclose
        # and the regathered full leaf is the original bytes
        assert elastic.regather_zero1(round_trip,
                                      dim).tobytes() == full.tobytes()
        checked += 1
    assert checked > 0


def test_zero1_resize_leaves_and_indivisible():
    shards = elastic.split_zero1(np.arange(8, dtype=np.float32), 0, 4)
    with pytest.raises(ValueError, match="not divisible"):
        elastic.resize_zero1(shards, 0, 3)
    # leaf-list form: None placements pass through untouched
    out = elastic.resize_zero1_leaves(
        [shards, np.float32(7.0)],
        [(0, ("dp",), (4,)), None])
    assert out[1] == np.float32(7.0)
    for a, b in zip(out[0], shards):
        assert a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# Restore-time guard + offline re-stamp CLI (real checkpoint stores)
# ---------------------------------------------------------------------------


def make_cfg(tmp_path, *, elastic_on=False, mbs=1, ga=1, **dist):
    return Config(
        distributed=DistributedConfig(**dist),
        model=ModelConfig(dtype="float32"),
        training=TrainingConfig(seq_length=32, micro_batch_size=mbs,
                                gradient_accumulation_steps=ga,
                                remat=False),
        checkpoint=CheckpointConfig(save_dir=str(tmp_path / "ckpt"),
                                    async_save=False, elastic=elastic_on),
    )


def _save_step(cfg):
    menv = MeshEnv.from_config(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    mgr = CheckpointManager(cfg, menv)
    mgr.save(state, trained_tokens=64,
             dataloader_state={"epoch": 0, "cursor": 0})
    mgr.wait_until_finished()
    return state


def _load_tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "elastic_resize", os.path.join(os.path.dirname(__file__), "..",
                                       "tools", "elastic_resize.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_elastic_restore_rejects_changed_global_batch(tmp_path):
    """checkpoint.elastic permits the topology change but still pins the
    invariant: a resize that would drift global_batch_size is refused,
    with the overrides that restore it named in the error."""
    cfg_a = make_cfg(tmp_path, dp_size=2, mbs=1, ga=1)  # gbs 2
    _save_step(cfg_a)
    cfg_b = make_cfg(tmp_path, dp_size=1, mbs=1, ga=1,  # gbs 1
                     elastic_on=True)
    menv_b = MeshEnv.from_config(cfg_b)
    template = init_sharded_state(cfg_b, menv_b, jax.random.key(1))
    with pytest.raises(RuntimeError, match="global_batch_size") as exc:
        CheckpointManager(cfg_b, menv_b).restore(template)
    assert "gradient_accumulation_steps=2" in str(exc.value)


def test_elastic_resize_tool_restamps_store(tmp_path):
    """tools/elastic_resize.py end-to-end: dry-run touches nothing; the
    real run rewrites meta.json + re-commits the manifest for dp=1 at
    constant global batch, the step re-verifies, and the re-stamped store
    restores into a dp=1 mesh with elastic OFF — byte-identical params."""
    cfg_a = make_cfg(tmp_path, dp_size=2, tp_size=2, mbs=2, ga=1)
    state = _save_step(cfg_a)
    save_dir = cfg_a.checkpoint.save_dir
    [step_dir] = [os.path.join(save_dir, d) for d in os.listdir(save_dir)
                  if d.startswith("step_")]
    tool = _load_tool()

    before = open(os.path.join(step_dir, "meta.json")).read()
    assert tool.main([save_dir, "--dp", "1", "--dry-run"]) == 0
    assert open(os.path.join(step_dir, "meta.json")).read() == before

    assert tool.main([save_dir, "--dp", "1"]) == 0
    meta = json.load(open(os.path.join(step_dir, "meta.json")))
    assert meta["config"]["distributed"]["dp_size"] == 1
    assert meta["config"]["training"]["micro_batch_size"] == 2
    assert meta["config"]["training"]["gradient_accumulation_steps"] == 2
    assert meta["elastic_restamp"]["to"]["dp"] == 1
    topo = elastic.saved_topology(step_dir)
    assert topo["dp"] == 1 and topo["tp"] == 2 and topo["world_size"] == 2

    from picotron_tpu.ckpt_integrity import verify_step_dir
    assert verify_step_dir(step_dir).status == "verified"

    # the re-stamped store now IS a dp=1 checkpoint: restoring it on a
    # dp=1 mesh needs no elastic flag
    cfg_b = make_cfg(tmp_path, dp_size=1, tp_size=2, mbs=2, ga=2)
    menv_b = MeshEnv.from_config(cfg_b)
    template = init_sharded_state(cfg_b, menv_b, jax.random.key(1))
    restored, meta2 = CheckpointManager(cfg_b, menv_b).restore(template)
    assert "elastic_resize" not in meta2
    np.testing.assert_array_equal(
        np.asarray(restored.params["embedding"]),
        np.asarray(state.params["embedding"]))


def test_elastic_resize_tool_refuses_corrupt_store(tmp_path):
    """Safety pin: re-stamping rebuilds the manifest from current bytes,
    so running on a corrupt step would bless the corruption as verified.
    The tool must refuse and leave the store untouched."""
    cfg = make_cfg(tmp_path, dp_size=2, mbs=2, ga=1)
    _save_step(cfg)
    save_dir = cfg.checkpoint.save_dir
    [step_dir] = [os.path.join(save_dir, d) for d in os.listdir(save_dir)
                  if d.startswith("step_")]
    state_files = [os.path.join(r, f)
                   for r, _d, fs in os.walk(os.path.join(step_dir, "state"))
                   for f in fs]
    victim = max(state_files, key=os.path.getsize)
    with open(victim, "r+b") as f:
        b = f.read(1)
        f.seek(0)
        f.write(bytes([b[0] ^ 0xFF]))

    tool = _load_tool()
    assert tool.main([save_dir, "--step", "0", "--dp", "1"]) == 1
    meta = json.load(open(os.path.join(step_dir, "meta.json")))
    assert meta["config"]["distributed"]["dp_size"] == 2  # untouched
    assert "elastic_restamp" not in meta


# ---------------------------------------------------------------------------
# pp / joint dp x pp elastic allow-path
# ---------------------------------------------------------------------------


def _fake_step(tmp_path, *, mbs=1, ga=1, **dist):
    """A legacy (meta.json-only) step dir recording a saved topology —
    enough for check_restore_topology, without a real Orbax store."""
    step = tmp_path / "saved" / "step_00000002"
    step.mkdir(parents=True)
    dcfg = {f"{ax}_size": int(dist.get(f"{ax}_size", 1))
            for ax in elastic.TOPOLOGY_AXES}
    dcfg["slices"] = int(dist.get("slices", 1))
    meta = {"config": {
        "distributed": dcfg,
        "training": {"micro_batch_size": mbs,
                     "gradient_accumulation_steps": ga},
    }}
    (step / "meta.json").write_text(json.dumps(meta))
    return str(step), meta


def test_restore_topology_pp_allow_path(tmp_path):
    """A pure-pp mismatch rides the elastic allow-path: pp does not enter
    the global batch, so the resize record comes back with no batch
    re-plan needed (the padded-layer-stack slot check in
    checkpoint.restore gates even splits separately)."""
    step_dir, meta = _fake_step(tmp_path, mbs=2, ga=2, pp_size=2)
    cfg = make_cfg(tmp_path, mbs=2, ga=2, elastic_on=True)  # pp=1 mesh
    rec = elastic.check_restore_topology(
        step_dir, meta, cfg, step=2, save_dir=str(tmp_path / "saved"))
    assert rec["axes"] == ["pp"]
    assert rec["from"]["pp"] == 2 and rec["to"]["pp"] == 1


def test_restore_topology_joint_dp_pp_allow_path(tmp_path):
    """dp and pp resize jointly: the record names both axes and the
    constant-global-batch invariant is still enforced through the dp
    half (mbs x ga x dp unchanged)."""
    step_dir, meta = _fake_step(tmp_path, mbs=1, ga=1, dp_size=2,
                                pp_size=2)                       # gbs 2
    cfg = make_cfg(tmp_path, dp_size=1, mbs=1, ga=2,             # gbs 2
                   elastic_on=True)
    rec = elastic.check_restore_topology(
        step_dir, meta, cfg, step=2, save_dir=str(tmp_path / "saved"))
    assert rec["axes"] == ["dp", "pp"]

    # same joint mismatch with elastic OFF: the error names both axes
    # and quotes a re-stamp invocation carrying BOTH flags
    cfg_off = make_cfg(tmp_path, dp_size=1, mbs=1, ga=2)
    with pytest.raises(RuntimeError, match="dp, pp") as exc:
        elastic.check_restore_topology(
            step_dir, meta, cfg_off, step=2,
            save_dir=str(tmp_path / "saved"))
    assert "--dp 1" in str(exc.value) and "--pp 1" in str(exc.value)


def test_restore_topology_pure_pp_mismatch_renders_pp_flag(tmp_path):
    """The elastic-off error for a pure-pp mismatch must quote a --pp
    re-stamp line, not a --dp no-op that would not fix it."""
    step_dir, meta = _fake_step(tmp_path, mbs=2, ga=2, pp_size=2)
    cfg = make_cfg(tmp_path, mbs=2, ga=2)  # pp=1 mesh, elastic off
    with pytest.raises(RuntimeError) as exc:
        elastic.check_restore_topology(
            step_dir, meta, cfg, step=2, save_dir=str(tmp_path / "saved"))
    assert "--pp 1" in str(exc.value)
    assert "--dp" not in str(exc.value)


@pytest.mark.parametrize("axis", ["tp", "cp", "ep"])
def test_restore_topology_rejects_unsupported_axis_even_elastic(
        tmp_path, axis):
    """The allow-path is {dp, pp} ONLY: nothing re-partitions the weight
    math tp/cp/ep split, so a mismatch there must raise even with
    checkpoint.elastic on — never proceed into an unsupported reshard."""
    step_dir, meta = _fake_step(tmp_path, mbs=2, ga=1,
                                **{f"{axis}_size": 2})
    cfg = make_cfg(tmp_path, mbs=2, ga=1, elastic_on=True)  # all axes 1
    with pytest.raises(RuntimeError, match="not elastic-resizable") as exc:
        elastic.check_restore_topology(
            step_dir, meta, cfg, step=2, save_dir=str(tmp_path / "saved"))
    assert axis in str(exc.value)


def test_restore_topology_slices_mismatch_names_both(tmp_path):
    """Satellite pin: restoring a 2-slice checkpoint into a single-slice
    (and dp-shrunk) mesh with elastic OFF fails naming BOTH topologies —
    slices included — and quotes a re-stamp line carrying --slices; with
    elastic on, the resize record lists slices among the changed axes
    (the slice-loss recovery allow-path)."""
    step_dir, meta = _fake_step(tmp_path, mbs=1, ga=1, dp_size=2,
                                slices=2)                        # gbs 2
    cfg_off = make_cfg(tmp_path, dp_size=1, mbs=1, ga=2)         # gbs 2
    with pytest.raises(RuntimeError) as exc:
        elastic.check_restore_topology(
            step_dir, meta, cfg_off, step=2,
            save_dir=str(tmp_path / "saved"))
    msg = str(exc.value)
    assert "slices2" in msg                      # the saved topology
    assert "dp1 pp1 ep1 cp1 tp1" in msg          # the current one
    assert "dp, slices" in msg                   # mismatched axes named
    assert "--dp 1" in msg and "--slices 1" in msg

    cfg_on = make_cfg(tmp_path, dp_size=1, mbs=1, ga=2, elastic_on=True)
    rec = elastic.check_restore_topology(
        step_dir, meta, cfg_on, step=2, save_dir=str(tmp_path / "saved"))
    assert rec["axes"] == ["dp", "slices"]
    assert rec["from"]["slices"] == 2 and rec["to"]["slices"] == 1


def test_resize_invocation_renders_mismatched_axes():
    """The quoted re-stamp command renders a flag per ACTUALLY-mismatched
    supported axis (regression: it used to always print --dp)."""
    cur = {"dp": 4, "pp": 2}
    pp_only = elastic.resize_invocation("/s", 3, cur, axes=("pp",))
    assert pp_only.endswith("--pp 2") and "--dp" not in pp_only
    both = elastic.resize_invocation("/s", 3, cur, axes=("dp", "pp"))
    assert "--dp 4" in both and "--pp 2" in both
    dp_only = elastic.resize_invocation("/s", 3, cur)
    assert "--dp 4" in dp_only and "--pp" not in dp_only


def test_elastic_resize_tool_restamps_pp(tmp_path):
    """--pp on the offline tool: an uneven split is refused (store
    untouched, slot mismatch named), an even split re-stamps pp as pure
    metadata — the batch plan is untouched when --dp is absent — and the
    re-stamped store restores on a pp=1 mesh with elastic OFF,
    byte-identical params."""
    cfg_a = make_cfg(tmp_path, pp_size=2, mbs=2, ga=2)
    state = _save_step(cfg_a)
    save_dir = cfg_a.checkpoint.save_dir
    [step_dir] = [os.path.join(save_dir, d) for d in os.listdir(save_dir)
                  if d.startswith("step_")]
    tool = _load_tool()

    # uneven split: 4 layers pad to 4 slots at pp=2 but 6 at pp=3 —
    # refused before anything is rewritten
    before = open(os.path.join(step_dir, "meta.json")).read()
    assert tool.main([save_dir, "--pp", "3"]) == 1
    assert open(os.path.join(step_dir, "meta.json")).read() == before

    assert tool.main([save_dir, "--pp", "1"]) == 0
    meta = json.load(open(os.path.join(step_dir, "meta.json")))
    assert meta["config"]["distributed"]["pp_size"] == 1
    # pure-pp: the batch plan is untouched
    assert meta["config"]["training"]["micro_batch_size"] == 2
    assert meta["config"]["training"]["gradient_accumulation_steps"] == 2
    assert meta["elastic_restamp"]["to"]["pp"] == 1
    topo = elastic.saved_topology(step_dir)
    assert topo["pp"] == 1 and topo["world_size"] == 1

    from picotron_tpu.ckpt_integrity import verify_step_dir
    assert verify_step_dir(step_dir).status == "verified"

    cfg_b = make_cfg(tmp_path, pp_size=1, mbs=2, ga=2)
    menv_b = MeshEnv.from_config(cfg_b)
    template = init_sharded_state(cfg_b, menv_b, jax.random.key(1))
    restored, meta2 = CheckpointManager(cfg_b, menv_b).restore(template)
    assert "elastic_resize" not in meta2
    np.testing.assert_array_equal(
        np.asarray(restored.params["embedding"]),
        np.asarray(state.params["embedding"]))


def test_elastic_resize_tool_restamps_joint_dp_pp(tmp_path):
    """--dp and --pp together: the dp half re-factors the batch at
    constant global batch, the pp half re-stamps the stage count, and one
    manifest re-commit covers both."""
    cfg_a = make_cfg(tmp_path, dp_size=2, pp_size=2, mbs=2, ga=1)  # gbs 4
    _save_step(cfg_a)
    save_dir = cfg_a.checkpoint.save_dir
    [step_dir] = [os.path.join(save_dir, d) for d in os.listdir(save_dir)
                  if d.startswith("step_")]
    tool = _load_tool()
    assert tool.main([save_dir, "--dp", "1", "--pp", "1"]) == 0
    meta = json.load(open(os.path.join(step_dir, "meta.json")))
    assert meta["config"]["distributed"]["dp_size"] == 1
    assert meta["config"]["distributed"]["pp_size"] == 1
    assert meta["config"]["training"]["micro_batch_size"] == 2
    assert meta["config"]["training"]["gradient_accumulation_steps"] == 2
    topo = elastic.saved_topology(step_dir)
    assert topo["dp"] == 1 and topo["pp"] == 1 and topo["world_size"] == 1


def test_elastic_resize_tool_restamps_slices(tmp_path):
    """--slices on the offline tool (the slice-loss recovery re-stamp):
    a 2-slice store records slices=2 in its manifest topology; a target
    count the resumed config would refuse (slices > dp*pp) is rejected
    with the store untouched; --slices 1 re-stamps it single-slice as
    pure placement metadata — dp and the batch plan untouched — and the
    step re-verifies."""
    cfg_a = make_cfg(tmp_path, dp_size=2, tp_size=2, mbs=2, ga=1, slices=2)
    _save_step(cfg_a)
    save_dir = cfg_a.checkpoint.save_dir
    [step_dir] = [os.path.join(save_dir, d) for d in os.listdir(save_dir)
                  if d.startswith("step_")]
    # satellite pin: the manifest topology records the slice count
    topo = elastic.saved_topology(step_dir)
    assert topo["slices"] == 2
    assert elastic.describe_topology(topo).endswith("slices2")

    tool = _load_tool()
    before = open(os.path.join(step_dir, "meta.json")).read()
    assert tool.main([save_dir, "--slices", "3"]) == 1  # 3 ∤ dp*pp = 2
    assert open(os.path.join(step_dir, "meta.json")).read() == before

    assert tool.main([save_dir, "--slices", "1"]) == 0
    meta = json.load(open(os.path.join(step_dir, "meta.json")))
    assert meta["config"]["distributed"]["slices"] == 1
    assert meta["config"]["distributed"]["dp_size"] == 2   # untouched
    assert meta["config"]["training"]["micro_batch_size"] == 2
    assert meta["config"]["training"]["gradient_accumulation_steps"] == 1
    assert meta["elastic_restamp"]["to"]["slices"] == 1
    topo = elastic.saved_topology(step_dir)
    assert topo.get("slices", 1) == 1 and topo["dp"] == 2

    from picotron_tpu.ckpt_integrity import verify_step_dir
    assert verify_step_dir(step_dir).status == "verified"


# ---------------------------------------------------------------------------
# ckpt_doctor source-topology column
# ---------------------------------------------------------------------------


def test_ckpt_doctor_reports_source_topology(tmp_path, capsys):
    import importlib.util

    cfg = make_cfg(tmp_path, dp_size=2, tp_size=2, slices=2)
    _save_step(cfg)
    spec = importlib.util.spec_from_file_location(
        "ckpt_doctor_topo", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "ckpt_doctor.py"))
    doctor = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = doctor
    spec.loader.exec_module(doctor)

    rows = doctor.scan(cfg.checkpoint.save_dir)
    assert rows[0]["topology"]["dp"] == 2
    assert rows[0]["topology"]["tp"] == 2
    assert rows[0]["topology"]["slices"] == 2

    assert doctor.main([cfg.checkpoint.save_dir, "--markdown"]) == 0
    md = capsys.readouterr().out
    assert "dp2 pp1 ep1 cp1 tp2 slices2" in md
    assert "| step | verdict | topology |" in md
    assert doctor.main([cfg.checkpoint.save_dir, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["steps"][0]["topology"]["dp"] == 2
    assert out["steps"][0]["topology"]["slices"] == 2
