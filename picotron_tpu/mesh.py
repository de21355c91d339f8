"""4D device mesh — the TPU-native replacement for the reference's
process-group singleton (ref: picotron/process_group_manager.py).

The reference builds a rank grid `arange(world).view(dp, pp, cp, tp)` with TP
fastest-varying (ref: process_group_manager.py:13) and derives 6 communicator
subgroups from it. On TPU the grid *is* a `jax.sharding.Mesh` with named axes
``('dp', 'pp', 'cp', 'tp')``; every communicator the reference creates becomes
a named-axis collective:

- tp group      -> `lax.psum(..., 'tp')` / `lax.all_gather(..., 'tp')`
- cp ring       -> `lax.ppermute(..., 'cp', ...)`
- pp p2p        -> `lax.ppermute(..., 'pp', ...)`
- cp_dp group   -> `lax.pmean(..., ('cp', 'dp'))` (gradient sync, ref:
                   data_parallel.py:83)
- pp_dp group   -> axis tuple ('pp', 'dp')

TP is innermost so it maps to the fastest ICI axis, same ordering rationale as
the reference's grid. Axis order here is (dp, pp, cp, tp) — identical to the
reference's view order.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis names, outermost to innermost. 'ep' (expert parallelism,
# beyond the reference's 4D: SURVEY §2.2 marks EP absent) acts as an extra
# data axis for everything except expert weights, which shard their expert
# dim over it; MoE dispatch rides `lax.all_to_all(..., 'ep')`.
AXES = ("dp", "pp", "ep", "cp", "tp")


def force_host_device_count(n: int, exact: bool = False) -> None:
    """Request `n` simulated host (CPU) devices. Must run before JAX backends
    initialize — the test conftest and the multichip dry-run use this
    (the TPU analogue of the reference's gloo/CPU path, ref: train.py:83).

    Raises if the flag is already pinned to a smaller count (a silent skip
    would surface later as a confusing mesh-oversubscription error). With
    `exact=True` any pinned mismatch raises: in a multi-process launch each
    process must provision exactly its share of the world, and a stale
    inherited XLA_FLAGS (e.g. exported for an earlier single-process run)
    would make every process bring the full count — the global device list
    then holds n_proc times the world and the mesh lands entirely on
    process 0's devices, failing far from the cause.
    """
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m:
        have = int(m.group(1))
        if have < n or (exact and have != n):
            raise RuntimeError(
                f"XLA_FLAGS already pins host device count to {have}, but "
                f"{'exactly ' if exact else 'at least '}{n} per process "
                f"is required; unset XLA_FLAGS or restart with the right "
                f"count"
            )
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}"
    ).strip()


@dataclass(frozen=True)
class MeshEnv:
    """Owns the 4D mesh and the sharding vocabulary built on it."""

    mesh: Mesh

    # -- construction ------------------------------------------------------

    @staticmethod
    def create(
        dp: int = 1,
        pp: int = 1,
        cp: int = 1,
        tp: int = 1,
        ep: int = 1,
        devices: Optional[Sequence[jax.Device]] = None,
    ) -> "MeshEnv":
        devices = list(devices if devices is not None else jax.devices())
        world = dp * pp * ep * cp * tp
        if world > len(devices):
            raise ValueError(
                f"dp*pp*ep*cp*tp = {world} exceeds available devices "
                f"({len(devices)}). (ref parity: train.py:86 asserts "
                "world_size == dp*pp*cp*tp)"
            )
        grid = _topology_grid((dp, pp, ep, cp, tp), devices[:world])
        return MeshEnv(Mesh(grid, AXES))

    @staticmethod
    def from_config(cfg) -> "MeshEnv":
        d = cfg.distributed
        return MeshEnv.create(dp=d.dp_size, pp=d.pp_size, cp=d.cp_size,
                              tp=d.tp_size, ep=getattr(d, "ep_size", 1))

    # -- axis sizes --------------------------------------------------------

    @property
    def dp(self) -> int:
        return self.mesh.shape["dp"]

    @property
    def pp(self) -> int:
        return self.mesh.shape["pp"]

    @property
    def cp(self) -> int:
        return self.mesh.shape["cp"]

    @property
    def tp(self) -> int:
        return self.mesh.shape["tp"]

    @property
    def ep(self) -> int:
        return self.mesh.shape["ep"]

    @property
    def world_size(self) -> int:
        return self.dp * self.pp * self.ep * self.cp * self.tp

    # -- sharding vocabulary ----------------------------------------------

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self) -> NamedSharding:
        """Sharding for a [micro, batch, seq] token block: batch over the
        fused (dp, ep) data axes, sequence over cp. The contiguous
        per-cp-rank sequence slice the reference does by hand in its collate
        fn (ref: data.py:105-109) falls out of sharding the sequence
        dimension."""
        return self.sharding(None, ("dp", "ep"), "cp")


def _topology_grid(shape: tuple, devices: list) -> np.ndarray:
    """Device grid for `Mesh(grid, AXES)` that respects the physical
    network topology.

    The reference's whole reason for its rank-grid ordering is mapping TP
    onto the fastest links (ref: process_group_manager.py:13-23 — TP
    fastest-varying onto NVLink). A naive `reshape(jax.devices())` encodes
    that ordering over the *enumeration* order, which on a real pod slice
    has no relation to the ICI torus. `mesh_utils.create_device_mesh`
    assigns logical axes to physical torus axes so that later (more
    network-intensive) mesh axes land on better-connected device groups —
    AXES is ordered (dp, pp, ep, cp, tp) for exactly this contract. For
    DCN-spanning jobs (multiple pod slices), `create_hybrid_device_mesh`
    keeps ICI-hungry axes inside a slice and routes the outermost axes
    (dp first, then pp) over DCN.

    Non-TPU devices (the simulated CPU meshes tests use) reduce to the
    plain reshape inside mesh_utils, keeping single-host behavior and
    device order unchanged. A mesh_utils failure (a shape the torus mapper
    cannot satisfy for this device set) propagates: an enumeration-order
    reshape in its place would put tp on arbitrary links and only show up
    as slow collectives.
    """
    if len(devices) == 1:
        return np.array(devices).reshape(shape)
    from jax.experimental import mesh_utils

    slice_ids = {getattr(d, "slice_index", 0) for d in devices}
    if len(slice_ids) > 1:
        dcn_shape, per_slice_shape = _split_axes_over_dcn(
            shape, len(slice_ids))
        return mesh_utils.create_hybrid_device_mesh(
            per_slice_shape, dcn_shape, devices=devices,
            allow_split_physical_axes=True)
    return mesh_utils.create_device_mesh(
        shape, devices=devices, allow_split_physical_axes=True)


def _split_axes_over_dcn(shape: tuple, n_slices: int) -> tuple[tuple, tuple]:
    """Factor the logical mesh shape into (dcn_shape, per_slice_shape) for
    `create_hybrid_device_mesh`: the n_slices DCN granules are absorbed by
    the outermost axes first (dp, then pp, ...), since gradient all-reduce
    over dp (once per step, overlappable) and pipeline boundary ppermute
    over pp (point-to-point) tolerate DCN latency, while cp/tp collectives
    must stay on ICI."""
    import math

    N_DCN_TOLERANT_AXES = 2  # dp, pp only — never ep/cp/tp over DCN
    dcn = [1] * len(shape)
    per_slice = list(shape)
    rem = n_slices
    for i in range(N_DCN_TOLERANT_AXES):
        g = math.gcd(per_slice[i], rem)
        dcn[i] = g
        per_slice[i] //= g
        rem //= g
        if rem == 1:
            break
    if rem != 1:
        raise ValueError(
            f"cannot distribute {n_slices} DCN slices over mesh axes "
            f"{dict(zip(AXES, shape))}: the slice count must divide the "
            f"product of the DCN-tolerant axis sizes (dp * pp = "
            f"{shape[0] * shape[1]}) — ep/cp/tp collectives must stay on "
            f"ICI. Rebalance the layout so dp*pp absorbs the slice count.")
    return tuple(dcn), tuple(per_slice)


def multihost_initialize() -> None:
    """Initialize the JAX distributed runtime for multi-host pods.

    One process per host over ICI/DCN replaces the reference's
    one-process-per-GPU torchrun + NCCL rendezvous (ref: base_job.slurm:64,
    train.py:94). Two entry paths:

    - **Explicit contract** — `PICOTRON_COORDINATOR` / `_NUM_PROCESSES` /
      `_PROCESS_ID` env vars (the framework's own launcher contract, the
      analogue of torchrun's MASTER_ADDR/RANK/WORLD_SIZE). This is what the
      multi-process integration test and any non-auto-detected cluster use.
      On the CPU platform this also selects gloo cross-process collectives
      (the role the reference's gloo backend plays, ref: train.py:83) —
      which must happen before the first backend client exists.
    - **Auto-detect** — `jax.distributed.initialize()` sniffs Cloud TPU pod
      metadata, SLURM, and MPI environments; attempted whenever such an
      environment is plausibly multi-host (see `_cluster_env_detected`).
    """
    # Must not touch any backend-initializing jax API before initialize();
    # consult the distributed global state directly instead.
    from jax._src import distributed as _jdist

    if _jdist.global_state.client is not None:
        return  # already initialized
    contract = launcher_contract()
    if contract is not None:
        coord, num_processes, process_id = contract
        if num_processes > 1 and jax.config.jax_platforms == "cpu":
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=num_processes,
                                   process_id=process_id)
        return
    if _cluster_env_detected(os.environ):
        jax.distributed.initialize()


def launcher_contract() -> Optional[tuple[str, int, int]]:
    """The explicit PICOTRON_* launcher contract, validated as a unit:
    (coordinator, num_processes, process_id), or None when unset. All three
    vars must appear together — a partial contract (e.g. a stale
    PICOTRON_NUM_PROCESSES without a coordinator) would otherwise make
    different components disagree about the process count and fail far from
    the cause."""
    names = ("PICOTRON_COORDINATOR", "PICOTRON_NUM_PROCESSES",
             "PICOTRON_PROCESS_ID")
    present = [n for n in names if os.environ.get(n)]
    if not present:
        return None
    missing = [n for n in names if not os.environ.get(n)]
    if missing:
        raise ValueError(
            f"partial PICOTRON launcher contract: {present} set but "
            f"{missing} missing — set all three or none")
    return (os.environ["PICOTRON_COORDINATOR"],
            int(os.environ["PICOTRON_NUM_PROCESSES"]),
            int(os.environ["PICOTRON_PROCESS_ID"]))


def _cluster_env_detected(env) -> bool:
    """True when a multi-host cluster environment is plausibly present:
    an explicit coordinator address, a SLURM/OpenMPI job spanning more than
    one task, or a Cloud TPU pod worker list with more than one host.
    Single-host runs (including a TPU_WORKER_HOSTNAMES containing just this
    host, a 1-task `mpirun -n 1`, or a single-node SLURM interactive shell)
    stay local — jax.distributed.initialize() there would hang waiting for
    a coordinator that never comes (ADVICE r2)."""
    if env.get("COORDINATOR_ADDRESS") or env.get("JAX_COORDINATOR_ADDRESS"):
        return True

    def _int(name: str) -> int:
        try:
            return int(env.get(name, "") or 0)
        except ValueError:
            return 0

    if _int("OMPI_COMM_WORLD_SIZE") > 1:
        return True
    if env.get("SLURM_JOB_ID") and (
            _int("SLURM_NTASKS") > 1 or _int("SLURM_JOB_NUM_NODES") > 1):
        return True
    hosts = [h for h in env.get("TPU_WORKER_HOSTNAMES", "").split(",")
             if h.strip()]
    return len(hosts) > 1
