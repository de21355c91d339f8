"""The benchmark's two training steps, compiled for a described TPU v5e (no
chip attached): what the scopes of picotron_tpu/telemetry/scopes.py do to the
names a device trace will show.

On this installation a Pallas custom call is named after the innermost
element of the name stack at the call. `benchmark/layer_metrics/
flash_roofline.train.json` finds the fused engine's three flash kernels by
the name the layer scan's body gives them, and `collective_share.train.json`
finds collectives by theirs, so a scope in the wrong place silences an
accepted metric. This file holds the names; nothing here is a measurement.

One file, topology described inside a module-scoped fixture: only one process
may load libtpu, and every xdist worker imports every test file.
"""

import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from picotron_tpu.config import config_from_dict
from picotron_tpu.mesh import MeshEnv
from picotron_tpu.parallel.api import init_sharded_state, make_train_step
from picotron_tpu.telemetry.scopes import SCOPES

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmark")
# matched by collective_share.train's pattern in the four-chip step at one
# layer a stage, counted on the tree before any scope was added (34
# psum_invariant, 8 all-reduce, 2 + 2 collective-permute start / done)
N_COLLECTIVES = 46


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
    # the program asks the backend whether the kernels exist; here the
    # backend is the CPU and the target is the described chip
    fa = importlib.import_module("picotron_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "compiled_kernels_available", lambda: True)
    c = load("configs", config)
    d = c["distributed"]
    c["model"]["num_hidden_layers"] = 2
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "training")})
    n = d["dp_size"] * d["pp_size"] * d["cp_size"] * d["tp_size"]
    menv = MeshEnv.create(dp=d["dp_size"], pp=d["pp_size"], cp=d["cp_size"],
                          tp=d["tp_size"], devices=topo.devices[:n])
    state = init_sharded_state(cfg, menv, jax.random.key(0), abstract=True)
    t = cfg.training
    b = jax.ShapeDtypeStruct(
        (t.gradient_accumulation_steps, t.micro_batch_size * d["dp_size"],
         t.seq_length), jnp.int32, sharding=menv.batch_sharding())
    return make_train_step(cfg, menv).lower(state, (b, b)).compile().as_text()


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
    # the AD engine's kernel calls sit inside `attention` (no accepted
    # metric finds them by name in this cell)
    kernels = [op for _, op, line in ins if "tpu_custom_call" in line]
    assert len(kernels) == 4 and all("attention" in words(op) for op in kernels)
    found = set().union(*(words(op) for _, op, _ in ins)) & set(SCOPES)
    assert found >= {"embed", "attention", "mlp", "head_ce", "optimizer",
                     "pp_boundary", "tp_reduce"}
