"""Speculative multi-token decode inside the serving decode scan.

Self-drafting n-gram speculation (prompt lookup, in the spirit of
"Inference with Reference" / vLLM's ngram speculator): each slot keeps a
small rolling window of its own recent tokens on device; per decode
iteration the drafter finds the most recent earlier occurrence of the
trailing bigram inside that window and proposes the `draft_len` tokens
that followed it. One [S, 1 + draft_len] forward pass then plays both
roles at once — it IS the next-token pass the non-speculative scan would
have run (column 0 consumes the real last token), and it verifies the
draft columns for free. The target token is sampled at EVERY position
with the same (request id, token index) key fold as the non-speculative
path, and the longest draft prefix whose tokens match the targets is
accepted.

Because acceptance only decides HOW MANY of the target-sampled tokens
one iteration emits — never WHICH tokens — the emitted stream is
bit-identical to non-speculative decode at any temperature, under any
accept/reject pattern, preemption, or slot reshuffle. The tests pin
this.

Rejected-draft K/V writes are left in place deliberately: the next
iteration (and the next dispatch) always re-writes positions starting at
the first unconfirmed slot before anything reads them, and the causal
mask (`arange(s_max) <= q_pos`) screens positions beyond the query — the
same argument that makes stale slots safe in the contiguous cache.

The whole verify-accept loop runs as ONE jitted program per engine
lifetime (a lax.scan of `decode_interval` iterations), preserving the
compile-once discipline the variant prover audits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from picotron_tpu.config import ModelConfig
from picotron_tpu.generate import _decode_layers
from picotron_tpu.models.llama import compute_dtype, final_hidden, head_weight
from picotron_tpu.serve.engine import _fold_keys
from picotron_tpu.serve.paged_cache import PagedKVCache
from picotron_tpu.telemetry.scopes import scope

# Drafter constants (static — baked into the compiled program).
NGRAM_K = 2    # trailing gram length the drafter matches on
CTX_W = 32     # per-slot rolling context window the drafter searches

# -1 pads empty context slots; real token ids are >= 0, so padding can
# never match a gram and the drafter falls back to repeat-last-token.
CTX_PAD = -1


def max_draft_len() -> int:
    """Largest draft_len the [CTX_W]-wide context can source a
    continuation for (needs >= 1 candidate gram start)."""
    return CTX_W - NGRAM_K


def context_rows(states, slots, num_slots: int):
    """Host-side [num_slots, CTX_W] int32 context buffer for the drafter:
    per live slot, the last CTX_W tokens of prompt + generated,
    left-padded with CTX_PAD. `states[s]` must have .req.prompt and
    .generated for every s in `slots`."""
    import numpy as np

    ctx = np.full((num_slots, CTX_W), CTX_PAD, np.int32)
    for s in slots:
        st = states[s]
        toks = list(st.req.prompt) + list(st.generated)
        tail = toks[-CTX_W:]
        if tail:
            ctx[s, -len(tail):] = tail
    return ctx


def _ngram_draft(ctx, last_tok, draft_len: int):
    """[S, draft_len] draft per slot by prompt lookup: match the trailing
    NGRAM_K-gram of ctx (newest token = last column) against every
    earlier window, take the LAST (most recent) match, and propose the
    tokens that followed it. Slots with no match repeat their last token
    — a draft is only a guess, correctness never depends on it."""
    s, w = ctx.shape
    tail = ctx[:, w - NGRAM_K:]                              # [S, k]
    n_cand = w - NGRAM_K - draft_len + 1
    starts = jnp.arange(n_cand)                              # [n_cand]
    gram_idx = starts[:, None] + jnp.arange(NGRAM_K)[None, :]
    grams = ctx[:, gram_idx]                                 # [S, n_cand, k]
    ok = ((grams >= 0).all(-1)
          & (grams == tail[:, None, :]).all(-1))             # [S, n_cand]
    has = ok.any(-1)
    best = jnp.argmax(jnp.where(ok, starts + 1, 0), axis=-1)
    cont = best[:, None] + NGRAM_K + jnp.arange(draft_len)[None, :]
    draft = jnp.take_along_axis(ctx, cont, axis=1)
    return jnp.where(has[:, None], draft, last_tok[:, None])


def serve_decode_spec(params, k, v, tables, toks, positions, rids,
                      tidx, ctx, base_key, cos, sin,
                      cfg: ModelConfig, temperature: float,
                      top_k: int, interval: int, eos_token_id,
                      draft_len: int):
    """`interval` speculative decode iterations over all slots in ONE
    dispatch. Shapes mirror engine.serve_decode with two additions:
    ctx [S, CTX_W] (drafter window) and the ragged outputs — each
    iteration emits between 1 and 1 + draft_len tokens per slot, so
    tokens come back as [S, interval, 1 + draft_len] plus a per-iteration
    valid count [S, interval]; columns past the count are padding the
    host skips. Returns (tokens, n_valid, last, positions, tidx, ctx,
    k, v) — the trailing carries feed the steady-state fast path exactly
    like the non-speculative program."""
    live = positions >= 0
    d1 = draft_len + 1
    offs = jnp.arange(d1)[None, :]                           # [1, 1+d]

    def one(carry, _):
        toks, positions, tidx, ctx, cache, done = carry
        draft = _ngram_draft(ctx, toks, draft_len)           # [S, d]
        seq = jnp.concatenate([toks[:, None], draft], 1)     # [S, 1+d]
        pos = jnp.where(live[:, None], positions[:, None] + offs, -1)
        x = params["embedding"][seq].astype(compute_dtype(cfg))
        x, cache = _decode_layers(params, x, cache, pos, cfg, cos, sin)
        hf = final_hidden(params, x, cfg)                    # [S, 1+d, H]
        logits = (hf @ head_weight(params).astype(hf.dtype)
                  ).astype(jnp.float32)                      # [S, 1+d, V]
        with scope("sample"):
            if temperature == 0.0:
                tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                lg = logits / temperature
                if top_k > 0:
                    kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
                    lg = jnp.where(lg < kth, -jnp.inf, lg)
                # column j's token, if emitted, is output token tidx + j —
                # key it exactly as the non-speculative step would
                keys = jax.vmap(_fold_keys, in_axes=(None, None, 0),
                                out_axes=1)(base_key, rids, (tidx[:, None]
                                                             + offs).T)
                tgt = jax.vmap(jax.vmap(
                    lambda l, key: jax.random.categorical(key, l)
                ))(lg, keys).astype(jnp.int32)
        if eos_token_id is not None:
            tgt = jnp.where(done[:, None], eos_token_id, tgt)
        # accept the longest draft prefix matching the targets: draft
        # column j (= seq column j+1) is confirmed iff it equals the
        # target sampled after consuming seq[:, :j+1]
        acc = jnp.cumprod((seq[:, 1:] == tgt[:, :draft_len])
                          .astype(jnp.int32), axis=1)        # [S, d]
        n_acc = acc.sum(axis=1)                              # [S]
        n_emit = n_acc + 1
        if eos_token_id is not None:
            # an EOS inside the emitted window finishes the slot; its
            # remaining iterations emit forced EOS like the non-spec scan
            emitted = offs < n_emit[:, None]
            done = done | ((tgt == eos_token_id) & emitted).any(axis=1)
        new_last = jnp.take_along_axis(tgt, n_acc[:, None], axis=1)[:, 0]
        step = jnp.where(live, n_emit, 0)
        positions = positions + step
        tidx = tidx + step
        # roll the drafter window: drop `step` oldest, append the
        # emitted targets (columns >= n_emit of tgt never enter — the
        # gather below stops at combined column CTX_W + step - 1)
        combined = jnp.concatenate([ctx, tgt], axis=1)       # [S, W+1+d]
        idx = step[:, None] + jnp.arange(ctx.shape[1])[None, :]
        ctx = jnp.take_along_axis(combined, idx, axis=1)
        return ((new_last, positions, tidx, ctx, cache, done),
                (tgt, jnp.where(live, n_emit, 0)))

    cache = PagedKVCache(k, v, tables)
    done = jnp.zeros(toks.shape, bool)
    (last, positions, tidx, ctx, cache, _), (toks_all, n_all) = \
        jax.lax.scan(one, (toks, positions, tidx, ctx, cache, done),
                     None, length=interval)
    # scan stacks along axis 0: [interval, S, ...] -> slot-major
    return (toks_all.transpose(1, 0, 2), n_all.T, last, positions, tidx,
            ctx, cache.k, cache.v)


_SPEC_JITS: dict = {}


def get_spec_jit(donate: bool):
    """Jitted speculative decode step, cached module-level like
    engine._get_jits so repeated engine construction shares one compile
    cache. Donation off-CPU only (CPU ignores it with a warning)."""
    if donate not in _SPEC_JITS:
        dargs = (1, 2) if donate else ()
        _SPEC_JITS[donate] = jax.jit(
            serve_decode_spec, donate_argnums=dargs,
            static_argnames=("cfg", "temperature", "top_k", "interval",
                             "eos_token_id", "draft_len"))
    return _SPEC_JITS[donate]
