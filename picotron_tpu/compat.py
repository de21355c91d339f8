"""The typed-shard_map names the parallel layer uses, in one place.

Plain re-exports of the installed JAX's API (`jax.shard_map`, `lax.pcast`)
plus `vma`, the one-line read of the mesh axes a value varies over.
"""

from __future__ import annotations

import jax
from jax import lax

shard_map = jax.shard_map
pcast = lax.pcast


def vma(x) -> frozenset:
    """Mesh axes `x` varies over inside shard_map."""
    return frozenset(jax.typeof(x).vma)
