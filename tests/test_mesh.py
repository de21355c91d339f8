import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from picotron_tpu.mesh import AXES, MeshEnv


def test_mesh_axes_and_sizes(devices):
    env = MeshEnv.create(dp=2, pp=2, cp=1, tp=2)
    assert env.mesh.axis_names == AXES
    assert (env.dp, env.pp, env.cp, env.tp) == (2, 2, 1, 2)
    assert env.world_size == 8


def test_tp_innermost(devices):
    # TP must be the fastest-varying axis: adjacent device ids in the same tp
    # group (ref: process_group_manager.py:13 grid layout).
    env = MeshEnv.create(dp=2, pp=1, cp=2, tp=2)
    grid = np.array(env.mesh.devices)
    ids = np.vectorize(lambda d: d.id)(grid)
    # along tp, ids are consecutive
    assert (ids[..., 1] - ids[..., 0] == 1).all()


def test_oversubscription_raises(devices):
    with pytest.raises(ValueError):
        MeshEnv.create(dp=4, pp=2, cp=2, tp=2)


def test_batch_sharding_slices_seq_over_cp(devices):
    env = MeshEnv.create(dp=2, cp=2, tp=2)
    x = np.arange(1 * 4 * 8, dtype=np.int32).reshape(1, 4, 8)
    arr = jax.device_put(x, env.batch_sharding())
    # each shard holds the full micro dim, batch/dp, seq/cp
    shard = arr.addressable_shards[0]
    assert shard.data.shape == (1, 2, 4)
    np.testing.assert_array_equal(np.asarray(arr), x)


def test_cluster_env_detection():
    from picotron_tpu.mesh import _cluster_env_detected

    assert not _cluster_env_detected({})
    assert not _cluster_env_detected({"TPU_WORKER_HOSTNAMES": ""})
    assert not _cluster_env_detected({"TPU_WORKER_HOSTNAMES": "host0"})
    assert _cluster_env_detected({"TPU_WORKER_HOSTNAMES": "host0,host1"})
    assert _cluster_env_detected({"COORDINATOR_ADDRESS": "10.0.0.1:1234"})
    # Single-task launches must stay local even under a launcher env: a
    # 1-task mpirun or a single-node SLURM interactive shell would hang in
    # jax.distributed.initialize() waiting for a coordinator (ADVICE r2).
    assert not _cluster_env_detected({"OMPI_COMM_WORLD_SIZE": "1"})
    assert not _cluster_env_detected({"OMPI_COMM_WORLD_SIZE": "garbage"})
    assert _cluster_env_detected({"OMPI_COMM_WORLD_SIZE": "4"})
    assert not _cluster_env_detected({"SLURM_JOB_ID": "42"})
    assert not _cluster_env_detected({"SLURM_JOB_ID": "42",
                                      "SLURM_NTASKS": "1"})
    assert _cluster_env_detected({"SLURM_JOB_ID": "42",
                                  "SLURM_NTASKS": "8"})
    assert _cluster_env_detected({"SLURM_JOB_ID": "42",
                                  "SLURM_JOB_NUM_NODES": "2"})
    # NTASKS without a SLURM job id is not a SLURM launch
    assert not _cluster_env_detected({"SLURM_NTASKS": "8"})


def test_split_axes_over_dcn():
    from picotron_tpu.mesh import _split_axes_over_dcn

    # 2 slices absorbed by dp
    dcn, per = _split_axes_over_dcn((4, 2, 1, 1, 2), 2)
    assert dcn == (2, 1, 1, 1, 1) and per == (2, 2, 1, 1, 2)
    # 4 slices: dp takes 2, pp takes the remaining 2
    dcn, per = _split_axes_over_dcn((2, 2, 1, 2, 2), 4)
    assert dcn == (2, 2, 1, 1, 1) and per == (1, 1, 1, 2, 2)
    # slice counts that would have to split ep/cp/tp over DCN must raise —
    # even when the inner axis sizes are divisible (tp=8, 2 slices)
    with pytest.raises(ValueError, match="DCN-tolerant"):
        _split_axes_over_dcn((1, 1, 1, 1, 8), 2)
    with pytest.raises(ValueError, match="DCN-tolerant"):
        _split_axes_over_dcn((1, 1, 1, 4, 2), 2)
    with pytest.raises(ValueError, match="DCN-tolerant"):
        _split_axes_over_dcn((1, 1, 1, 1, 8), 3)


def test_topology_grid_unsatisfiable_multislice_raises(devices):
    """A slice count dp*pp cannot absorb must be a hard layout error, not a
    warning + naive reshape that silently routes tp over DCN."""
    from picotron_tpu import mesh as mesh_mod

    class FakeDev:
        def __init__(self, d, s):
            self._d = d
            self.slice_index = s

        def __getattr__(self, name):
            return getattr(self._d, name)

    devs = [FakeDev(d, i // 4) for i, d in enumerate(devices[:8])]
    with pytest.raises(ValueError, match="DCN-tolerant"):
        mesh_mod._topology_grid((1, 1, 1, 2, 4), devs)


def test_launcher_contract_partial_raises(monkeypatch):
    from picotron_tpu.mesh import launcher_contract

    for k in ("PICOTRON_COORDINATOR", "PICOTRON_NUM_PROCESSES",
              "PICOTRON_PROCESS_ID"):
        monkeypatch.delenv(k, raising=False)
    assert launcher_contract() is None
    monkeypatch.setenv("PICOTRON_NUM_PROCESSES", "2")
    with pytest.raises(ValueError, match="partial PICOTRON"):
        launcher_contract()
    monkeypatch.setenv("PICOTRON_COORDINATOR", "127.0.0.1:1234")
    monkeypatch.setenv("PICOTRON_PROCESS_ID", "0")
    assert launcher_contract() == ("127.0.0.1:1234", 2, 0)


def test_topology_grid_routes_multislice_to_hybrid(devices, monkeypatch):
    """Devices reporting distinct slice_index values must go through
    create_hybrid_device_mesh with dp over DCN (VERDICT r2 missing #1)."""
    from jax.experimental import mesh_utils

    from picotron_tpu import mesh as mesh_mod

    calls = {}

    def fake_hybrid(per_slice, dcn, devices=None, **kw):
        calls["per_slice"], calls["dcn"] = tuple(per_slice), tuple(dcn)
        return np.array(devices).reshape(
            tuple(a * b for a, b in zip(per_slice, dcn)))

    monkeypatch.setattr(mesh_utils, "create_hybrid_device_mesh", fake_hybrid)

    class FakeDev:
        def __init__(self, d, s):
            self._d = d
            self.slice_index = s

        def __getattr__(self, name):
            return getattr(self._d, name)

    devs = [FakeDev(d, i // 4) for i, d in enumerate(devices[:8])]
    grid = mesh_mod._topology_grid((2, 2, 1, 1, 2), devs)
    assert grid.shape == (2, 2, 1, 1, 2)
    assert calls["dcn"] == (2, 1, 1, 1, 1)
    assert calls["per_slice"] == (1, 2, 1, 1, 2)


def test_topology_grid_mesh_utils_failure_propagates(devices, monkeypatch):
    """No enumeration-order reshape behind a failed topology mapping: the
    error reaches the caller."""
    from jax.experimental import mesh_utils

    from picotron_tpu import mesh as mesh_mod

    def boom(*a, **kw):
        raise ValueError("unsatisfiable torus mapping")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", boom)
    with pytest.raises(ValueError, match="unsatisfiable torus mapping"):
        mesh_mod._topology_grid((2, 1, 1, 2, 2), list(devices[:8]))


def test_multihost_initialize_singlehost_noop():
    """On a single host (no cluster env), multihost_initialize must be a
    no-op rather than hanging waiting for a coordinator (SURVEY §2 row 22:
    the launcher path)."""
    import os

    from picotron_tpu.mesh import multihost_initialize

    saved = {k: os.environ.pop(k, None) for k in
             ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS",
              "SLURM_JOB_ID", "OMPI_COMM_WORLD_SIZE",
              "TPU_WORKER_HOSTNAMES")}
    try:
        multihost_initialize()  # returns immediately, initializes nothing
    finally:
        for k, v in saved.items():
            if v is not None:
                os.environ[k] = v
