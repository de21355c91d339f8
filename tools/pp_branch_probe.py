"""Probe for parallel/pp.py's branch rule: may a `lax.cond` taken by pipeline
stage hold a collective over another mesh axis?

A shard_map over (pp 2, tp 2) whose scan body has a cond on
axis_index('pp') with a psum over 'tp' in ONE branch only, and a ppermute
over 'pp' after it. Every member of a tp replica group sits on one stage and
takes the same branch, so no member waits for a peer that never arrives.
Prints one JSON line; `ok` is whether the values are the expected ones. Runs
on whatever backend JAX has (four devices): the chip through the chip tool,
or the CPU with XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""

import json
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def probe(devices, ticks=6):
    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("pp", "tp"))

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=P("pp", "tp"),
             out_specs=P("pp", "tp"))
    def run(x):
        s = lax.axis_index("pp")

        def tick(carry, _):
            # stage 0 reduces over tp; stage 1 takes the branch without it
            y = lax.cond(
                s == 1, lambda c: c * 0.0,
                lambda c: lax.pcast(lax.psum(c, "tp"), "tp", to="varying"),
                carry)
            return carry + lax.ppermute(y, "pp", [(0, 1)]), None

        return lax.scan(tick, x, None, length=ticks)[0]

    x = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8) + 1.0
    got = np.asarray(run(x))
    want = np.asarray(x).copy()
    # stage 0 (rows 0-1) receives nothing; stage 1 (rows 2-3) receives, every
    # tick, stage 0's tp-sum at its own tp rank's columns
    half = want[:2, :4] + want[:2, 4:]
    want[2:, :4] += ticks * half
    want[2:, 4:] += ticks * half
    return bool(np.array_equal(got, want)), got


if __name__ == "__main__":
    ok, got = probe(jax.devices())
    print(json.dumps({"probe": "cond_by_stage_with_tp_psum", "ok": ok,
                      "platform": jax.devices()[0].platform,
                      "device_kind": jax.devices()[0].device_kind,
                      "devices": len(jax.devices()),
                      "first_row_stage1": got[2].tolist()}))
    sys.exit(0 if ok else 1)
