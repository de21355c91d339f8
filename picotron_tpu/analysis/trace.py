"""Abstract lowering of the composed train step — shared by the analyzers.

`jax.jit(...).lower()` on ShapeDtypeStructs traces and lowers the exact
program a real run would execute, without materializing a single array or
touching an accelerator: the same recipe tools/memcheck.py uses for memory
estimates, here reused to hand the collective-schedule and hazard analyzers
the StableHLO text plus the abstract (state, batch) the arg list refers to.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class LoweredStep(NamedTuple):
    step_fn: object      # the jitted step (for eval_shape-level checks)
    lowered: object      # jax Lowered
    text: str            # StableHLO module text
    state: object        # abstract TrainState
    batch: tuple         # abstract (ids, targets)


def abstract_batch(cfg, menv):
    t = cfg.training
    b = (t.micro_batch_size * cfg.distributed.dp_size
         * cfg.distributed.ep_size)
    ids = jax.ShapeDtypeStruct(
        (t.gradient_accumulation_steps, b, t.seq_length), jnp.int32,
        sharding=menv.batch_sharding())
    return (ids, ids)


def lower_train_step(cfg, menv=None) -> LoweredStep:
    """Build + lower the config's train step on an abstract mesh. Requires
    enough local (simulated) devices for cfg's world size — the CLI forces
    a host-device count first, exactly like tools/memcheck.py."""
    import dataclasses

    from picotron_tpu.config import PipelineConfig
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.parallel.api import init_sharded_state, make_train_step

    cfg.validate()
    if cfg.pipeline.executor == "mpmd":
        # The MPMD executor is a host-side schedule walker over per-stage
        # programs — there is no single jit to lower. Trace-level checks
        # (collectives, donation, stability) run on its SPMD
        # twin: same math, one program. The per-stage compile-once claim
        # is proven separately by variants.prove_mpmd_stages.
        cfg = dataclasses.replace(cfg, pipeline=PipelineConfig())
    menv = menv if menv is not None else MeshEnv.from_config(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0), abstract=True)
    step = make_train_step(cfg, menv)
    batch = abstract_batch(cfg, menv)
    lowered = step.lower(state, batch)
    return LoweredStep(step, lowered, lowered.as_text(), state, batch)
