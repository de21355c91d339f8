"""analysis/trace.py coverage: the abstract lowering that feeds every
analyzer (collectives audit, hazards, cost pricing) must capture the
lowered module text without materializing arrays, for single- and
multi-axis layouts."""

import jax

from picotron_tpu import compat
from picotron_tpu.analysis.collectives import parse_collectives
from picotron_tpu.analysis.trace import abstract_batch, lower_train_step
from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, TrainingConfig, resolve_preset,
)
from picotron_tpu.mesh import MeshEnv


def mkcfg(dist=None, ga=1, seq=64):
    cfg = Config(
        distributed=DistributedConfig(**(dist or {})),
        model=ModelConfig(name="debug-tiny",
                          **resolve_preset("debug-tiny")),
        training=TrainingConfig(seq_length=seq, micro_batch_size=1,
                                gradient_accumulation_steps=ga),
    )
    cfg.validate()
    return cfg


def test_lowering_captures_module_text_without_materializing():
    low = lower_train_step(mkcfg())
    # the five LoweredStep fields are all populated
    assert isinstance(low.text, str) and len(low.text) > 100
    assert "module" in low.text  # StableHLO module header
    assert low.lowered is not None and low.step_fn is not None
    # state and batch are ABSTRACT: shape/dtype only, nothing on device
    for leaf in jax.tree_util.tree_leaves(low.state):
        assert isinstance(leaf, jax.ShapeDtypeStruct), type(leaf)
    ids, targets = low.batch
    assert isinstance(ids, jax.ShapeDtypeStruct)
    assert ids.shape == targets.shape == (1, 1, 64)


def test_abstract_batch_shape_tracks_layout():
    cfg = mkcfg(dist=dict(dp_size=2, cp_size=2), ga=3)
    menv = MeshEnv.from_config(cfg)
    ids, targets = abstract_batch(cfg, menv)
    # [grad_acc, dp*ep*mbs, seq], seq kept FULL (cp shards via sharding)
    assert ids.shape == (3, 2, 64)
    assert ids.sharding.spec == menv.batch_sharding().spec


def test_lowered_text_carries_the_promised_collectives():
    # the dp=2 grad all-reduce must be parseable straight off the capture
    low = lower_train_step(mkcfg(dist=dict(dp_size=2), ga=2))
    ops = [op for op in parse_collectives(low.text) if op.effective]
    assert any(op.kind == "all_reduce" and op.group_size == 2
               for op in ops), low.text[:500]


def test_explicit_menv_is_honored():
    cfg = mkcfg(dist=dict(dp_size=2))
    menv = MeshEnv.from_config(cfg)
    low = lower_train_step(cfg, menv)
    assert low.batch[0].sharding.mesh == menv.mesh


def test_compat_reexports():
    """compat.py is plain re-exports of the installed JAX: shard_map and
    pcast are the public functions, and vma reads a frozenset."""
    from jax import lax

    assert compat.shard_map is jax.shard_map
    assert compat.pcast is lax.pcast
    assert compat.vma(jax.numpy.ones((4,))) == frozenset()
