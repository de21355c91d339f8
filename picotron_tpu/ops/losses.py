"""Cross-entropy losses.

`cross_entropy`: the plain full-vocab loss (ref: train.py:49 and
pipeline_parallel.py:102-104 use F.cross_entropy over flattened logits).
Computed in fp32 with an ignore_index mask matching torch's default semantics
(mean over non-ignored tokens).

The vocab-parallel variant (no full-logit materialization — an improvement
over the reference's TP gather, ref: tensor_parallel.py:50) lives in
picotron_tpu/parallel/tp.py next to the TP collectives it needs.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from picotron_tpu.telemetry.scopes import scope

IGNORE_INDEX = -100


def pick_label(logits: jnp.ndarray, rel: jnp.ndarray) -> jnp.ndarray:
    """`logits[..., rel]` along the last axis where `0 <= rel < V`, 0 where
    `rel` lies outside (a label another vocab shard or chunk holds).

    The one label pick of the cross-entropy paths (the dense loss below and
    both branches of parallel/tp.py's local stats). Forward it is the gather
    it always was. Its backward is written by hand: the cotangent of the
    logits is `where(iota(V) == rel, g, 0)`, a compare that fuses into the
    pass that forms `softmax * g`. The gather's own transpose is a scatter
    of N values into a zero-filled [..., V] fp32 tensor, which the TPU
    compiler runs on a flat relayout of the whole tensor: 2.5 GB read and
    written a microbatch at [4096, 151936], for 4,096 numbers (PERF.md,
    PR 36)."""
    return _pick_label(logits, rel, logits.shape[-1])


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pick_label(logits, rel, v):
    ok = (rel >= 0) & (rel < v)
    relc = jnp.clip(rel, 0, v - 1)
    return (jnp.take_along_axis(logits, relc[..., None], axis=-1)
            .squeeze(-1) * ok.astype(logits.dtype))


def _pick_label_fwd(logits, rel, v):
    return _pick_label(logits, rel, v), rel


def _pick_label_bwd(v, rel, g):
    # the backward of a custom_vjp is traced far from the forward's
    # decorator (the 1F1B engine's last-stage cond), so the scope that
    # `head_ce_ms.train` reads is entered here. No collective, no pcast:
    # this runs inside that cond (parallel/pp.py's branch rules).
    with scope("head_ce"):
        hit = jnp.arange(v, dtype=rel.dtype) == rel[..., None]
        return jnp.where(hit, g[..., None], 0), None


_pick_label.defvjp(_pick_label_fwd, _pick_label_bwd)


def cross_entropy_sum_count(logits: jnp.ndarray, targets: jnp.ndarray):
    """(sum of per-token NLL, number of non-ignored tokens) — the reduction
    pieces, so data-parallel shards can psum both and divide once (a per-shard
    mean followed by an unweighted pmean would mis-weight shards whose
    IGNORE_INDEX counts differ).

    logits: [..., vocab] (any float dtype; upcast to fp32)
    targets: [...] int labels, IGNORE_INDEX entries excluded.
    """
    logits = logits.astype(jnp.float32)
    valid = targets != IGNORE_INDEX
    safe_targets = jnp.where(valid, targets, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logit = pick_label(logits, safe_targets)
    nll = jnp.where(valid, logz - label_logit, 0.0)
    return jnp.sum(nll), jnp.sum(valid)


def cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Token-mean cross entropy over the non-ignored tokens."""
    total, count = cross_entropy_sum_count(logits, targets)
    return total / jnp.maximum(count, 1)
