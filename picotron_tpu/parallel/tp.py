"""Tensor parallelism: vocab-parallel embedding, sharded cross-entropy, and
the TP hooks for ParallelCtx.

Megatron-style 1D TP (capability parity with ref: picotron/tensor_parallel/):

- Column-parallel linears (q/k/v/gate/up) shard the output features over
  'tp'; row-parallel linears (o/down) shard the input features and psum the
  partial outputs (ref: tensor_parallel.py:54-189). In this framework the
  *sharding specs* (parallel/sharding.py) put the weights on the mesh and the
  only explicit collective needed in the forward is the row-parallel exit
  psum — the backward psum of the column-parallel entry
  (ref: tp_communications.py:19-33, the `f` function) is inserted
  automatically when JAX transposes the psum/pvary pair under shard_map.

- The vocab-parallel embedding masks out-of-shard tokens and psums
  (ref: tensor_parallel.py:191-271 does the same with an explicit mask +
  all-reduce).

- `vocab_parallel_ce` improves on the reference, which all-gathers full-vocab
  logits on every rank before cross-entropy (ref: tensor_parallel.py:50
  `gather_output=True` + train.py:49): we compute the softmax statistics with
  a pmax/psum pair and never materialize the gathered [B, S, V] tensor —
  at SmolLM's 49k vocab this saves tp x the logit memory and an all-gather
  per microbatch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.ops.losses import IGNORE_INDEX, pick_label
from picotron_tpu.telemetry.scopes import scope

# Every collective of the tensor-parallel hooks is entered under the
# `tp_reduce` scope, so that a device trace can tell them from the
# pipeline's sends (`pp_boundary`, parallel/pp.py) whatever the compiler
# calls the instruction.


@scope("tp_reduce")
def tp_psum(x: jnp.ndarray, axis: str = "tp") -> jnp.ndarray:
    """The row-parallel exit (`g`): psum over tp forward, identity
    backward."""
    return lax.psum(x, axis)


def vocab_parallel_embed(w_shard: jnp.ndarray, ids: jnp.ndarray,
                         axis: str = "tp",
                         scatter_seq: bool = False) -> jnp.ndarray:
    """Embedding lookup with the vocab dimension sharded over `axis`.

    w_shard: [vocab/tp, hidden] local shard; ids replicated.
    Out-of-shard ids contribute zero; psum over tp assembles the full row.
    With `scatter_seq` (sequence parallelism) the psum becomes a
    psum_scatter over the sequence dim, handing each tp rank its
    [*, S/tp, H] slice of the residual stream.
    """
    vshard = w_shard.shape[0]
    lo = lax.axis_index(axis) * vshard
    rel = ids - lo
    ok = (rel >= 0) & (rel < vshard)
    rel = jnp.clip(rel, 0, vshard - 1)
    x = w_shard[rel] * ok[..., None].astype(w_shard.dtype)
    with scope("tp_reduce"):
        if scatter_seq:
            return lax.psum_scatter(x, axis, scatter_dimension=1, tiled=True)
        return lax.psum(x, axis)


# -- sequence parallelism (SP) hooks ----------------------------------------
# Megatron-SP's g/ḡ pair (Korthikanti et al. 2022): with the residual
# stream seq-sharded over tp, the column-parallel entry gathers the
# sequence (backward: reduce-scatter of the grad — JAX's transpose of a
# tiled all_gather) and the row-parallel exit reduce-scatters the partial
# sums (backward: all_gather). Same total bytes as the psum pair they
# replace; tp x less activation memory between blocks.
#
# These transposes are load-bearing beyond AD: the fused grad engine
# (parallel/fused_bwd.py) reaches both hooks through jax.vjp over segment
# closures, so its manual backward scan emits the SAME all_gather/
# reduce-scatter pair per layer as the AD engine — the schedule
# picotron_tpu/analysis/collectives.py's SP presence rule audits on both
# engines.


@scope("tp_reduce")
def sp_gather_seq(x: jnp.ndarray, axis: str = "tp") -> jnp.ndarray:
    """[*, S/tp, H] -> [*, S, H]; the SP column-parallel entry (`f`)."""
    return lax.all_gather(x, axis, axis=1, tiled=True)


@scope("tp_reduce")
def sp_scatter_seq(x: jnp.ndarray, axis: str = "tp") -> jnp.ndarray:
    """partial [*, S, H] -> reduced [*, S/tp, H]; the SP row-parallel
    exit (`g`)."""
    return lax.psum_scatter(x, axis, scatter_dimension=1, tiled=True)


def vocab_parallel_ce_sum_count(hidden: jnp.ndarray, head_shard: jnp.ndarray,
                                targets: jnp.ndarray, axis: str = "tp",
                                chunk_size: int = 0):
    """(sum of per-token NLL, valid-token count) against a vocab-sharded LM
    head — the reduction pieces, so dp/cp shards can psum both and divide once.

    hidden: [B, S, H] (replicated over tp); head_shard: [H, vocab/tp];
    targets: [B, S] with IGNORE_INDEX allowed. Both outputs are replicated
    over tp. Matches ops.losses.cross_entropy_sum_count numerically.
    """
    # One implementation, two entry points: this delegates to the
    # local-stats/merge split the pipeline engines use, so the fused and
    # gated scoring paths cannot numerically diverge (code review r3).
    stats = vocab_parallel_ce_local_stats(hidden, head_shard, targets, axis,
                                          chunk_size=chunk_size)
    total = vocab_parallel_ce_merge(stats, targets, axis)
    return total, jnp.sum(targets != IGNORE_INDEX)


def vocab_parallel_ce_local_stats(hidden: jnp.ndarray,
                                  head_shard: jnp.ndarray,
                                  targets: jnp.ndarray, axis: str = "tp",
                                  chunk_size: int = 0):
    """The collective-free half of `vocab_parallel_ce_sum_count`: this
    shard's softmax statistics, (local_max, local_sumexp, local_label), each
    [B, S] fp32. Pair with `vocab_parallel_ce_merge` for the cross-shard
    reduction.

    The split exists for the pipeline engines: the expensive part (the
    [B*S, H] x [H, V/tp] head matmul and the exp) runs inside a `lax.cond`
    taken only by the last pp stage, which therefore must contain no
    cross-device collectives — a collective whose replica group spans
    devices that take different branches leaves the in-branch members
    waiting on peers that never arrive (a rendezvous deadlock on the CPU
    backend; here the risk is the pvary-transpose psums over 'pp' that
    implicit varying-type promotion would insert into the backward cond).
    The [B, S]-sized pmax/psum merge runs unconditionally on every stage —
    three tiny uniform collectives per tick.
    """
    vshard = head_shard.shape[-1]
    lo = lax.axis_index(axis) * vshard
    valid = targets != IGNORE_INDEX
    rel = jnp.where(valid, targets, 0) - lo

    if chunk_size and chunk_size < vshard and vshard % chunk_size == 0:
        return _chunked_local_stats(hidden, head_shard, rel, chunk_size)

    logits = (hidden @ head_shard.astype(hidden.dtype)).astype(jnp.float32)
    m_loc = jax.lax.stop_gradient(jnp.max(logits, axis=-1))  # [B, S]
    sumexp_loc = jnp.sum(jnp.exp(logits - m_loc[..., None]), axis=-1)
    return m_loc, sumexp_loc, pick_label(logits, rel)


def _chunked_local_stats(hidden, head_shard, rel, chunk_size: int):
    """Streaming form of the local CE stats: scan vocab chunks, keeping a
    running (max, sumexp, label) merge, so the [N, V_local] logits tensor
    never materializes — neither in forward nor as a saved residual (the
    chunk body is jax.checkpoint'd, so backward recomputes each chunk's
    logits from hidden/head instead of loading ~N*V saved values). At
    SmolLM shapes ([10240, 49152] fp32 stats path) that trades one extra
    chunk matmul in backward for ~1 GB of saved-residual HBM — the memory
    that caps the micro-batch size (see PERF.md). Numerics match the fused
    path: the running max-merge is the same logsumexp shift, stop_gradient
    on every max."""
    vshard = head_shard.shape[-1]
    b_shape = rel.shape

    def body(carry, off):
        m_acc, se_acc, lab_acc = carry
        wc = lax.dynamic_slice_in_dim(head_shard, off, chunk_size, axis=1)
        logits = (hidden @ wc.astype(hidden.dtype)).astype(jnp.float32)
        m_c = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
        m_new = jnp.maximum(m_acc, m_c)
        se = (se_acc * jnp.exp(m_acc - m_new)
              + jnp.sum(jnp.exp(logits - m_new[..., None]), axis=-1))
        return (m_new, se, lab_acc + pick_label(logits, rel - off)), None

    # The scan carry must already hold the varying type the body produces
    # (tp via head/rel, data axes via hidden). Anchored with zero-weighted
    # operand elements, NOT lax.pcast: this function also runs inside the
    # pipeline's last-stage scoring cond, where a pcast's transpose would
    # put a psum inside the divergent backward branch (parallel/pp.py's
    # branch rules).
    anchor = (hidden.ravel()[0].astype(jnp.float32)
              + head_shard.ravel()[0].astype(jnp.float32)
              + rel.ravel()[0].astype(jnp.float32)) * 0.0
    init = (jnp.full(b_shape, -jnp.inf, jnp.float32) + anchor,
            jnp.zeros(b_shape, jnp.float32) + anchor,
            jnp.zeros(b_shape, jnp.float32) + anchor)
    # exp(m_acc - m_new) with m_acc = -inf on the first chunk: m_new = m_c
    # is finite (real logits), so the factor is exp(-inf) = 0, scaling the
    # zero se_acc — no nan path.
    offsets = jnp.arange(0, vshard, chunk_size)
    (m_loc, sumexp_loc, label_loc), _ = lax.scan(
        jax.checkpoint(body), init, offsets)
    return m_loc, sumexp_loc, label_loc


def vocab_parallel_ce_merge(stats, targets: jnp.ndarray, axis: str = "tp"):
    """Cross-shard merge of `vocab_parallel_ce_local_stats` -> NLL sum.
    Numerically identical to `vocab_parallel_ce_sum_count`'s fused path:
    psum_r[exp(m_r - m) * sum_v exp(l_rv - m_r)] == psum over the full
    vocab of exp(l - m)."""
    m_loc, sumexp_loc, label_loc = stats
    # m is a pure shift constant (its gradient contribution cancels exactly
    # — the standard logsumexp trick); stop_gradient here also covers the
    # pipeline's cond-anchored neutral stats, whose m_loc arrives with a
    # (zero-valued but non-symbolic) tangent that pmax cannot differentiate.
    m_loc = jax.lax.stop_gradient(m_loc)
    with scope("tp_reduce"):
        m = lax.pmax(m_loc, axis)
        sumexp = lax.psum(sumexp_loc * jnp.exp(m_loc - m), axis)
        label = lax.psum(label_loc, axis)
    logz = m + jnp.log(sumexp)
    valid = targets != IGNORE_INDEX
    nll = jnp.where(valid, logz - label, 0.0)
    return jnp.sum(nll)


def vocab_parallel_ce(hidden: jnp.ndarray, head_shard: jnp.ndarray,
                      targets: jnp.ndarray, axis: str = "tp") -> jnp.ndarray:
    """Token-mean cross-entropy against a vocab-sharded LM head."""
    total, count = vocab_parallel_ce_sum_count(hidden, head_shard, targets, axis)
    return total / jnp.maximum(count, 1)


@scope("tp_reduce")
def gather_logits(logits: jnp.ndarray, axis: str = "tp") -> jnp.ndarray:
    """all-gather vocab-sharded logits to full vocab on the last dim (the
    eval/debug path; ref: tp_communications.py:51-64 GatherFromModelParallel)."""
    return lax.all_gather(logits, axis, axis=logits.ndim - 1, tiled=True)
