"""Serving engine: continuous batching over a paged KV cache.

The host loop owns the scheduler (admission / chunked prefill /
preemption / retirement, serve/scheduler.py) and drives exactly TWO
jitted device programs, each compiled once for the whole serving
lifetime (the prefill program once per row count of a short ladder):

- ``decode step``: a fixed batch of `decode_slots` slots, one token per
  slot per call. Slot count is the static shape; which request occupies
  which slot, every slot's position, and the block tables are ordinary
  device DATA, so requests enter and leave mid-flight without a
  recompile (asserted via CompileWatch in tests — one decode compile
  across a multi-request trace). Idle/prefilling slots ride along at
  position -1: their q-rows compute masked garbage that is discarded and
  their K/V writes resolve to the sentinel block and drop. On a chip the
  step attends in place: a kernel reads the blocks each slot holds
  through the block table (ops/paged_attention.py), nothing for an idle
  slot, and no view of the pool is gathered.
- ``prefill chunk``: `prefill_chunk` tokens of every mid-prefill slot's
  prompt, one row a slot, interleaved one dispatch per engine iteration
  so a long prompt never stalls the in-flight decode batch. The batch is
  COMPACTED: it holds the slots that are mid-prefill and no others,
  padded up to the next rung of `prefill_rungs(decode_slots)` (1, 4, 16,
  ... , decode_slots), so the program computes the rows that prefill and
  not `decode_slots` rows whatever prefills. One executable a rung, each
  compiled (or loaded from the cache) by the constructor, none later. A
  row whose prompt ends in the chunk returns its last valid position's
  token — the request's first token (TTFT).

Both run `generate._decode_layers` against a paged cache — the same
layer math as the offline contiguous path, which is what makes greedy
token parity between the two cache implementations a pinned test
invariant. Which kind of paged cache a model has, and everything about
its format (its pools, the rows of its tables, what a step reads of it),
is decided and known in serve/paged_cache.py (`init_serve_cache` and the
class it returns): the engine holds that cache's shapes (`cache`), its
pools on the device (`_kv`) and the host mirrors of its tables
(`_tables`), and asks the cache whatever depends on its kind. tp-sharded
params from `generate.place_for_decode` work unchanged: the programs are pure GSPMD, XLA propagates the shardings
through the block pool and inserts the collectives.

Sampling keys derive from (request id, token index), so tokens are
independent of slot assignment, arrival interleaving, and preemption —
the ragged-batch-invariance property the tests pin.

Sparse experts are served through the dropless dispatch (`ops/moe.py`
`moe_mlp_served`): every expert is on the device, a token's experts depend
on that token alone, so a prompt prefilled in any chunking routes alike;
rows without a token (idle slots, pad rows, chunk padding) are routed
nowhere and touch no expert, and the grouped kernel reads no expert that
no live row chose. The decode program returns how many experts its live
rows touched, which decides the bytes a step needs, and how many (row
tile, expert) pairs the kernel visited, which is what it read.

The step loop runs ONE DECODE DISPATCH AHEAD (`ServeEngine._decode_tick`):
a step enqueues decode dispatch n + 1 before it waits for the tokens of
dispatch n. Each slot's position, token index and blocks after dispatch n
are arithmetic the host can do without its tokens, and the token itself
stays on the device (`serve_decode`'s `last`), so the device has its next
program queued while the host copies tokens back, emits them, admits and
builds. What only the device knew (an EOS) or what happened meanwhile (a
cancel, a preemption) costs the rows of one dispatch, dropped at its emit.

Observability rides the existing telemetry machinery: the GoodputLedger
books queue_wait / prefill / decode (compile time drained out exactly
via CompileWatch), per-request TTFT and per-token latency land in the
registry histograms and as ``serve_request`` / ``serve_summary`` JSONL
events, and tools/telemetry_report.py renders the serving view
(p50/p95 TTFT, tok/s, slot occupancy, pool utilization). Every step
with device work adds up its own leaf spans (`step_account`), and gives
every second of its PERIOD, from the end of the step with device work
before it to its own end, one name, the first of these that fits:
``empty`` (no request in the system: from the end of a step that left
nothing pending to the next `submit`), ``starved`` (work pending and
nothing enqueued on the device, inside the step; ``caller_starved`` the
same between steps), ``dry`` (something enqueued that a probe has seen
finished: between the leaf spans the engine asks the newest output it
enqueued `is_ready()`, which does not block, so "in flight" does not
pass for "fed"; a lower bound, with the slack up to the last probe that
read running beside it), and ``fed``, the rest. The parts go onto the
``serve.step`` span, into a ``phase=serve_host`` (starved) and a
``phase=serve_dry`` event and into `stats`, with the profiler on or off.
Every wait says whether the dispatch it waited for had finished when it
began and whether the one behind it had when it ended (``ready``,
``next_ready``), and a step far over the median says which leaf held it
and, of a wait, both (``serve_slow_step``).
"""

from __future__ import annotations

import gc
import logging
import statistics
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from picotron_tpu.config import ModelConfig, ServeConfig
from picotron_tpu.generate import (
    _decode_layers, _logits_last, expert_counts,
)
from picotron_tpu.resilience import watchdog
from picotron_tpu.models.llama import (
    compute_dtype, final_hidden, model_rope_tables, served_head,
)
from picotron_tpu.serve.paged_cache import BlockPool, init_serve_cache
from picotron_tpu.serve.scheduler import (
    Request, Scheduler, blocks_for, ended,
)
from picotron_tpu.telemetry import Telemetry
from picotron_tpu.telemetry.flightdeck.tracer import TID_SERVE
from picotron_tpu.telemetry.scopes import scope
from picotron_tpu.telemetry.spans import join_ids


log = logging.getLogger("picotron_tpu.serve")

# ---------------------------------------------------------------------------
# Device programs (module-level so every engine shares one jit cache). The
# functions' names are the programs' module names (`jit_serve_decode`,
# `jit_serve_prefill`): a device trace lists each execution under them on
# its `XLA Modules` line, which is where the benchmark finds the two
# programs' times. Pinned by tests/test_scopes.py.
# ---------------------------------------------------------------------------


def _fold_keys(base_key, rids, tidx):
    """[S] sampling keys from (request id, token index) — slot/order
    independent, so continuous batching and preemption replay cannot
    perturb sampled tokens."""
    return jax.vmap(
        lambda r, t: jax.random.fold_in(jax.random.fold_in(base_key, r), t)
    )(rids, tidx)


@scope("sample")
def _sample_slots(logits, temperature: float, top_k: int, base_key, rids,
                  tidx):
    """Each slot's next token from its logits [S, V]: greedy, or a draw
    under the slot's own (request id, token index) key — one sampling law
    for the prefill and the decode program."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits / temperature
    if top_k > 0:
        kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    keys = _fold_keys(base_key, rids, tidx)
    return jax.vmap(
        lambda l, key: jax.random.categorical(key, l)
    )(lg, keys).astype(jnp.int32)


def _logit_of(logits, toks):
    """The float32 logit of each row's chosen token [S]: one number a
    token out of the [S, V] row the program holds anyway, handed out so
    that a served token can be held to a reference's logit, not only to
    its argmax."""
    return jnp.take_along_axis(logits, toks[:, None], axis=1)[:, 0]


def serve_decode(params, pools, tables, toks, last, positions, rids, tidx,
                 base_key, cos, sin, cfg: ModelConfig,
                 temperature: float, top_k: int, interval: int,
                 eos_token_id, cache_cls: type):
    """`interval` decode steps over all slots inside ONE dispatch (a
    lax.scan — amortizes per-dispatch host overhead over interval tokens
    per slot; the same reason offline generate scans its whole decode).
    toks/last/positions/rids/tidx: [S]; positions < 0 = idle slot
    (output ignored, write dropped). The host enqueues this dispatch
    before it has read the tokens of the one before, so a slot that
    continues (`toks` < 0) takes its input token from `last`, that
    dispatch's `next tokens` output, still on the device; only a slot
    that joins takes the uploaded `toks`. Slots that emit EOS mid-interval are forced
    to keep emitting EOS — identical semantics to generate.py's scan —
    and the host truncates + retires them when it emits the dispatch,
    by which time the next one is in flight with that slot still in it:
    padding, whose rows the host drops. Returns
    (tokens [S, interval], their logits [S, interval] float32, next
    tokens, next positions, next tidx, expert counts, pools); the
    position/index outputs feed the steady-state fast path straight back
    in, so an unchanged slot roster costs zero host->device uploads
    (measured ~2x the whole dispatch on the CPU tiny-model bench).
    Expert counts: the experts at least one live slot was routed to, the
    (row tile, expert) pairs the experts' kernel visited, the live slots'
    picks that landed on experts held here and all their picks, each
    summed over the layers and the interval's steps (zeros for a dense
    model); two more where the router has zero-compute experts
    (`generate.expert_counts` names them all).
    `pools`, `tables`, `cache_cls`: the model's serving cache
    (serve/paged_cache.py `init_serve_cache`) as its class, the tuple of
    its pools (donated, handed back as they are after the writes) and the
    tuple of its tables."""
    live = positions >= 0
    toks = jnp.where(toks < 0, last, toks)

    def one(carry, _):
        toks, positions, tidx, cache, done, touched = carry
        x = params["embedding"][toks[:, None]].astype(compute_dtype(cfg))
        x, cache, t = _decode_layers(params, x, cache, positions[:, None],
                                     cfg, cos, sin, with_touched=True)
        logits = _logits_last(params, x, cfg)  # [S, V] fp32
        nxt = _sample_slots(logits, temperature, top_k, base_key, rids, tidx)
        if eos_token_id is not None:
            nxt = jnp.where(done, eos_token_id, nxt)
            done = done | (nxt == eos_token_id)
        positions = jnp.where(live, positions + 1, positions)
        tidx = jnp.where(live, tidx + 1, tidx)
        return ((nxt, positions, tidx, cache, done, touched + t),
                (nxt, _logit_of(logits, nxt)))

    cache = cache_cls.of(pools, tables)
    done = jnp.zeros(toks.shape, bool)
    (last, positions, tidx, cache, _, touched), (toks_all, lg_all) = \
        jax.lax.scan(one, (toks, positions, tidx, cache, done,
                           jnp.zeros((len(expert_counts(cfg)),), jnp.int32)),
                     None, length=interval)
    return (toks_all.T, lg_all.T, last, positions, tidx, touched,
            cache.pools)


def serve_prefill(params, pools, table_rows, chunk_ids, start_pos,
                  n_valid, rids, tidx, base_key, cos, sin,
                  cfg: ModelConfig, temperature: float, top_k: int,
                  cache_cls: type):
    """Prefill the next chunk of R mid-prefill slots in one dispatch:
    chunk_ids [R, C] (padded), start_pos/n_valid/rids/tidx [R],
    table_rows a tuple of [R, width] (a table of the cache each). A row
    is a mid-prefill slot, not a slot index: the host compacts the batch
    (`ServeEngine._prefill_feed`), so R is a rung of `prefill_rungs` (a
    tick's slots ride one rung or several, `prefill_cover`), and the
    row's table row says where its K/V live. Rows with n_valid = 0 pad
    the batch up to the rung (all positions -1 and an all-unmapped table
    row: writes sentinel-drop, outputs discarded); padded positions in a
    live row behave the same. Batching matters: a per-slot prefill dispatch
    measured ~2x the static sampler's batched prompt pass on the CPU
    bench — one [R, C] program closes that. Samples each row's next
    token off its last valid position's logits with the same (request
    id, token index) key derivation as the decode step — one sampling
    law everywhere. `pools`, `cache_cls`: as `serve_decode`'s. Returns
    (pools, tokens [R], their logits [R])."""
    s, c = chunk_ids.shape
    t = jnp.arange(c)[None, :]
    pos = jnp.where(t < n_valid[:, None], start_pos[:, None] + t, -1)
    cache = cache_cls.of(pools, table_rows)
    x = params["embedding"][chunk_ids].astype(compute_dtype(cfg))
    x, cache = _decode_layers(params, x, cache, pos, cfg, cos, sin)
    last = jnp.maximum(n_valid - 1, 0)  # [S]
    h_last = jnp.take_along_axis(x, last[:, None, None], axis=1)  # [S,1,H]
    hf = final_hidden(params, h_last, cfg)
    logits = (hf @ served_head(params, cfg).astype(hf.dtype))[:, 0]
    logits = logits.astype(jnp.float32)  # [S, V]
    toks = _sample_slots(logits, temperature, top_k, base_key, rids, tidx)
    return cache.pools, toks, _logit_of(logits, toks)


_JITS: dict = {}


def _get_jits(donate: bool):
    """Jitted (decode, prefill) pair, shared across engines so repeated
    engine construction (tests, bench baseline+serve in one process)
    reuses the compile cache. Cache donation is only requested off-CPU —
    the CPU backend ignores donation with a warning per call site."""
    if donate not in _JITS:
        dargs = (1,) if donate else ()  # the pools
        _JITS[donate] = (
            jax.jit(serve_decode, donate_argnums=dargs,
                    static_argnames=("cfg", "temperature", "top_k",
                                     "interval", "eos_token_id",
                                     "cache_cls")),
            jax.jit(serve_prefill, donate_argnums=dargs,
                    static_argnames=("cfg", "temperature", "top_k",
                                     "cache_cls")),
        )
    return _JITS[donate]


def prefill_rungs(num_slots: int) -> tuple:
    """The row counts the prefill program is compiled for, a function of
    the pool's slot count and nothing else: the powers of 4 below it,
    then the slot count itself (1, 4, 16, 32 at 32 slots). A tick's
    mid-prefill slots ride one rung or several (`prefill_cover`, below
    the engine): never padded beyond 4/3, and every row count up to
    `num_slots` has a rung. A dispatch costs by its rows, so a finer
    ladder serves faster (powers of 2 measured 13% under this one on the
    chat cell's TTFT p90), but a rung costs start-up a trace and a cache
    load, and the set-up bound had no room for six (PERF.md, PR 28)."""
    rungs, r = [], 1
    while r < num_slots:
        rungs.append(r)
        r *= 4
    return (*rungs, num_slots)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


# The decode kernel's Mosaic body carries the file and line of its callers
# (`serve_decode` among them) where the compile cache's key still sees them:
# a change that moves the lines of the device programs above costs every
# checkout at the same path one recompile of the serve programs. So the
# host's code stands below them, the step's account (PR 53 moved it here)
# among it.


# ---------------------------------------------------------------------------
# The step's own account
# ---------------------------------------------------------------------------

# A slow step: one of its parts is over the larger of SLOW_STEP_S and
# SLOW_STEP_X times the median of the last SLOW_STEP_WINDOW parts of its own
# kind, once SLOW_STEP_AFTER of them have been seen. The parts are each
# `*.wait` leaf, by the dispatch it waited for (the host runs ahead of a
# long prompt's chunks, and the one wait at its end is as long as all of
# them), and the rest of the wall (`host`): a prefill dispatch's own time is
# held against other prefill dispatches and not against decode steps. The
# first SLOW_STEP_LOGS are logged, the rest counted.
SLOW_STEP_S, SLOW_STEP_X = 0.25, 8
SLOW_STEP_WINDOW, SLOW_STEP_AFTER, SLOW_STEP_LOGS = 64, 16, 32


# The leaves that enqueue work on the device, and those that wait for it.
_ENQUEUES = frozenset(("serve.prefill.dispatch", "serve.decode.dispatch"))
_DECODE_DISPATCH, _DECODE_WAIT = "serve.decode.dispatch", "serve.decode.wait"
_WAITS = frozenset(("serve.prefill.wait", _DECODE_WAIT))
# `dry_by`'s name for the seconds between two steps (the caller's), as
# `unspanned` is its name for the code between two spans inside a step
BETWEEN_STEPS = "between_steps"


def _inside(a: float, b: float, intervals) -> float:
    """The seconds of [a, b) inside `intervals` (disjoint, in order)."""
    return sum(min(b, hi) - max(a, lo) for lo, hi in intervals
               if lo < b and hi > a)


def step_account(leaves, t0: float, wall: float, in_flight=(), probes=(),
                 since: Optional[float] = None, empties=(),
                 dry: bool = False) -> dict:
    """Where one engine step's PERIOD went, from its own leaf spans, its
    probes of the device and the stamps between steps. The period runs
    from `since`, when the step with device work before this one ended
    (None: from `t0`), to the end of this step's wall. Each second of it
    has one name, the first of these that fits:

    - ``empty``: no request in the system. `empties` are those intervals,
      `(from, to)` in order: from the end of a step that left nothing
      pending (what is in flight then is padding nobody waits for) to the
      first `submit` after it. They lie between steps.
    - ``starved``: work pending, nothing enqueued, so the device is idle
      whatever a profiler does to the host. `starved_s` counts it inside
      the step, by leaf in `starved_by` (`unspanned`: the code between
      spans); `caller_starved_s` is the same between steps (the caller's
      reading of the tokens, its submits, its sleep's granularity).
    - ``dry``: something enqueued that a probe has seen finished: the
      device ran out of work under a name the account calls in flight.
    - ``fed``: the rest.

    `leaves` are the step's leaf spans in the order they ran, `(name,
    start, secs)` on the clock of `t0`, the step's start; `wall` is the
    step's seconds so far. The device has work from the start of a
    `*.dispatch` leaf (`_ENQUEUES`) until a `*.wait` leaf (`_WAITS`) has
    fetched its outputs, or those of something enqueued behind it. A
    `serve.prefill.wait` fetches the dispatch enqueued last, so it clears
    all that is in flight. A `serve.decode.wait` fetches the OLDEST
    decode dispatch nobody has fetched: the engine runs one decode
    dispatch ahead, so a newer one is usually enqueued behind it and stays
    in flight, and the emit, the next admit and the next build are fed by
    it. Where a prefill wait has cleared that dispatch already, the decode
    wait clears nothing and is a leaf like any other. `in_flight` names
    the leaves that enqueued what the steps before left in flight, oldest
    first (it holds from `since` on); `"in_flight"` of the result is the
    same for the next step, and `waits` lists each wait that cleared
    something as `(name, secs, dispatches it cleared)`.

    `probes` are `(stamp, ready)` in order: whether the NEWEST output
    enqueued had finished at `stamp` (one stream runs in order, so the
    newest ready means all ready). From the first probe that reads ready
    the device is dry, until the end of the next enqueuing leaf or of a
    wait that leaves nothing in flight (`dry`: the period before ended
    so). `dry_s` counts those seconds where something is in flight, by
    leaf in `dry_by` (`between_steps`, `unspanned`): a lower bound, since
    the device ran dry somewhere between the last probe that read running
    (or the enqueue) and that first one; `dry_slack_s` are the in-flight
    seconds between the two, so `dry_s + dry_slack_s` is the upper bound.
    `"dry"` of the result is `dry` for the next step.

    `unspanned_s` is the wall less the leaves, `leaves` each leaf's
    seconds, `period_s` the period, `end` its last instant; `empty_s +
    starved_s + caller_starved_s + dry_s + fed_s` is `period_s`, every
    part counted and none the remainder of the others."""
    fed = list(in_flight)  # the enqueues nobody waited for, oldest first
    unfetched = 0  # decode dispatches a prefill wait cleared
    begin, end = (t0 if since is None else since), t0 + wall
    at = begin
    # the period in pieces, each under one key and either in flight or not
    pieces = []
    empty_s = 0.0
    for lo, hi in empties:  # between the steps, by how they are stamped
        lo, hi = max(lo, at), min(hi, t0)
        if hi > lo:
            if lo > at:
                pieces.append((BETWEEN_STEPS, at, lo, bool(fed)))
            empty_s += hi - lo
            at = hi
    if t0 > at:
        pieces.append((BETWEEN_STEPS, at, t0, bool(fed)))
    # when what the probes watched was added to or had all been fetched:
    # the end of an enqueuing leaf (and its start, with nothing in flight
    # before it), the end of a wait that left nothing in flight
    resets = []
    at, spanned = t0, 0.0
    secs_by: dict = {}
    waits = []
    for name, start, secs in leaves:
        if start > at:  # the code between two spans
            pieces.append(("unspanned", at, start, bool(fed)))
        if name in _ENQUEUES:
            if not fed:  # nothing in flight: nothing to have run dry
                resets.append(start)
            fed.append(name)
            resets.append(start + secs)
        secs_by[name] = secs_by.get(name, 0.0) + secs
        spanned += secs
        n = 0  # the enqueues this leaf clears, oldest first
        if name == _DECODE_WAIT:
            if unfetched:
                unfetched -= 1
            elif _DECODE_DISPATCH in fed:
                n = fed.index(_DECODE_DISPATCH) + 1
            else:
                n = len(fed)
        elif name in _WAITS:
            n = len(fed)
            unfetched += fed.count(_DECODE_DISPATCH)
        pieces.append((name, start, start + secs, bool(fed)))
        if fed and n:
            waits.append((name, secs, n))
            del fed[:n]
            if not fed:
                resets.append(start + secs)
        at = start + secs
    if end > at:
        pieces.append(("unspanned", at, end, bool(fed)))

    # the intervals the probes call dry, and the slack before each
    dry_in, slack_in = [], []
    dry_from = begin if dry else None
    if probes or dry:
        running_at = begin
        # a reset before a probe with the same stamp: the sort is stable
        marks = sorted([*((r, False) for r in resets), *probes],
                       key=lambda m: m[0])
        for stamp, ready in marks:
            if not ready:  # a reset, or the newest output still running
                if dry_from is not None:
                    dry_in.append((dry_from, stamp))
                    dry_from = None
                running_at = stamp
            elif dry_from is None:
                dry_from = stamp
                slack_in.append((running_at, stamp))
        if dry_from is not None:
            dry_in.append((dry_from, end))

    starved_by: dict = {}
    dry_by: dict = {}
    caller_starved = fed_s = slack = 0.0
    for key, lo, hi, flying in pieces:
        if flying:
            d = _inside(lo, hi, dry_in) if dry_in else 0.0
            if d:
                dry_by[key] = dry_by.get(key, 0.0) + d
            fed_s += hi - lo - d
            if slack_in:
                slack += _inside(lo, hi, slack_in)
        elif key is BETWEEN_STEPS:
            caller_starved += hi - lo
        else:
            starved_by[key] = starved_by.get(key, 0.0) + hi - lo
    return {"wall_s": wall, "starved_s": sum(starved_by.values()),
            "unspanned_s": max(wall - spanned, 0.0), "leaves": secs_by,
            "starved_by": starved_by, "waits": waits,
            "in_flight": tuple(fed),
            "period_s": wall + (t0 - begin), "end": end, "empty_s": empty_s,
            "caller_starved_s": caller_starved,
            "dry_s": sum(dry_by.values()), "dry_slack_s": slack,
            "dry_by": dry_by, "fed_s": fed_s,
            "dry": dry_from is not None and bool(fed)}


def _ms(secs_by: dict) -> dict:
    """An account's seconds by leaf as an event's milliseconds."""
    return {k: int(v * 1e6) / 1e3 for k, v in secs_by.items()}


def mesh_shardings(params):
    """(replicated, KV pool) shardings on the mesh `params` are sharded
    over, None where no leaf is. With tp > 1 the KV pool is pinned over
    the kv-head axis — the layout GSPMD picks for TP attention."""
    from jax.sharding import NamedSharding, PartitionSpec
    for leaf in jax.tree.leaves(params):
        mesh = getattr(getattr(leaf, "sharding", None), "mesh", None)
        if mesh is not None:  # a NamedSharding
            tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("tp", 1)
            return (NamedSharding(mesh, PartitionSpec()),
                    NamedSharding(mesh, PartitionSpec("tp") if tp > 1
                                  else PartitionSpec()))
    return None


def new_cache(model_cfg: ModelConfig, scfg: ServeConfig, num_slots: int,
              num_blocks: int, max_len: int, kv_sh):
    """What an engine keeps of the model's serving cache
    (`init_serve_cache`), new: its shapes, which is all the host asks the
    cache about; its pools on the device under `kv_sh`, which live with the
    engine between dispatches (a program is handed them, donated, and hands
    them back); and the host mirrors of its tables, all unmapped."""
    cache = init_serve_cache(model_cfg, scfg, num_slots, num_blocks, max_len,
                             sharded=not kv_sh.is_fully_replicated)
    return (jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         cache),
            jax.device_put(cache.pools, kv_sh),
            tuple(np.full((num_slots, width), unmapped, np.int32)
                  for width, unmapped in cache.table_specs))


class _Probed:
    """A leaf span of an engine with a probe of its device on either side
    (`ServeEngine._probe`): the span itself knows nothing of devices."""

    __slots__ = ("_engine", "_span")

    def __init__(self, engine, span):
        self._engine, self._span = engine, span

    def __enter__(self):
        self._engine._probe()
        return self._span.__enter__()

    def __exit__(self, *exc) -> bool:
        self._span.__exit__(*exc)
        self._engine._probe()
        return False


class ServeEngine:
    def __init__(self, params, model_cfg: ModelConfig,
                 serve_cfg: Optional[ServeConfig] = None, *,
                 eos_token_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 telemetry: Optional[Telemetry] = None,
                 device=None, engine_id: int = 0):
        scfg = serve_cfg or ServeConfig()
        scfg.validate()
        self.params = params
        self.cfg = model_cfg
        self.scfg = scfg
        self.eos_token_id = eos_token_id
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.base_key = jax.random.key(seed)

        self.max_len = scfg.max_model_len or model_cfg.max_position_embeddings
        self.block_size = scfg.block_size
        self.max_blocks = blocks_for(self.max_len, self.block_size)
        self.num_blocks = (scfg.num_blocks
                           or scfg.decode_slots * self.max_blocks)
        self.num_slots = scfg.decode_slots
        self.prefill_rungs = prefill_rungs(self.num_slots)

        self.cos, self.sin = model_rope_tables(model_cfg,
                                               max_len=self.max_len)

        # Sharding discipline: every decode/prefill input keeps ONE
        # explicit sharding for the engine's whole lifetime. Committed
        # and uncommitted arrays key DIFFERENT jit variants, and
        # commitment spreads through outputs — one committed argument
        # (e.g. place_for_decode'd params) cascades into k/v and then
        # every upload, minting fresh 0.6 s recompiles mid-trace (caught
        # on the CPU bench). Committing everything up front collapses the
        # variant space to exactly one per program.
        on_mesh = mesh_shardings(params)
        if on_mesh is not None:
            self._rep_sh, kv_sh = on_mesh
        else:
            # `device` pins the whole engine (params, KV pool, rope
            # tables, key) to ONE device — the fleet's per-replica
            # placement: N engines on N distinct (simulated) devices,
            # each a self-contained replica whose state can be discarded
            # wholesale on failover.
            dev = device if device is not None else jax.devices()[0]
            self._rep_sh = jax.sharding.SingleDeviceSharding(dev)
            kv_sh = self._rep_sh
        self.cache, self._kv, self._tables = new_cache(
            model_cfg, scfg, self.num_slots, self.num_blocks, self.max_len,
            kv_sh)
        self.cos = jax.device_put(self.cos, self._rep_sh)
        self.sin = jax.device_put(self.sin, self._rep_sh)
        self.base_key = jax.device_put(self.base_key, self._rep_sh)
        # ... and the params themselves: raw init_params / checkpoint
        # loads hand over uncommitted arrays, the one hole the variant
        # prover (analysis/variants.check_engine_feed) found in this
        # discipline — an uncommitted re-feed of the same shapes would
        # mint a second executable. Already-committed leaves (e.g.
        # place_for_decode output) pass through untouched.
        self.params = jax.tree.map(
            lambda x: x if getattr(x, "committed", True)
            else jax.device_put(x, self._rep_sh), self.params)
        self.pool = BlockPool(self.num_blocks)
        sched_args = self.cache.scheduler_args(model_cfg)
        # the sliding layers' pool (None: the cache has no second pool)
        self.wpool = sched_args.get("window_pool")
        self.sched = Scheduler(self.num_slots, self.pool, self.block_size,
                               self.max_blocks, **sched_args)

        self._owns_telemetry = telemetry is None
        self.telemetry = telemetry or Telemetry(sinks=[])
        # KV pool donation: on wherever the backend implements it (XLA:CPU
        # ignores donation with a warning per call). Public so a caller
        # checking the chip path can see which programs it got.
        self.donate = jax.default_backend() != "cpu"
        self._decode_jit, self._prefill_jit = _get_jits(self.donate)

        self._t0 = time.perf_counter()  # trace clock zero (run() resets)
        self.engine_id = int(engine_id)  # fleet replica index (0 = solo)
        # steady-state decode fast path: device-resident step inputs,
        # valid while the slot roster and block tables are unchanged
        self._decode_state: Optional[dict] = None
        self.results: list = []
        self.shed_results: list = []
        self.stats = {
            "decode_steps": 0, "decode_compiles": 0, "prefill_compiles": 0,
            "prefill_chunks": 0, "occupancy_sum": 0.0, "output_tokens": 0,
            "decode_stall_ticks_max": 0, "cancelled": 0,
            # experts the decode steps' live rows were routed to and (row
            # tile, expert) pairs their kernel visited, out of layers x
            # steps x experts (all 0 for a dense model)
            "experts_touched": 0, "expert_visits": 0, "expert_slots": 0,
            # the decode steps' picks (live slots x experts a token, summed
            # over layers) and those that landed on experts held here:
            # equal unless the device holds a share of the experts
            "picks_here": 0, "picks_all": 0,
            # a router with zero-compute experts: the picks that fell on
            # them, and the live rows for which no held bank was read
            # (every pick zero-compute or held elsewhere)
            "picks_zero": 0, "rows_all_zero_or_away": 0,
        }
        self._init_step_account()
        self._stall_streak = 0  # consecutive ticks: work queued, no decode
        self._next_auto_id = 0
        self._warm_prefill()

        # Static variant-prover check over the feed the engine just built
        # (analysis/variants.py): every persistent leaf must be committed,
        # or the first decode after an uncommitted re-feed mints a second
        # executable for the same shapes. Advisory — findings go to
        # telemetry, never raise; the runtime CompileWatch twin
        # (stats["decode_compiles"]) remains the ground truth.
        try:
            from picotron_tpu.analysis.variants import check_engine_feed

            self.variant_report = check_engine_feed(self)
            for f in self.variant_report.warnings():
                self.telemetry.emit("variant_hazard", category="serve",
                                    path=f.path, message=f.message)
        except Exception:  # analysis is best-effort at serve time
            self.variant_report = None

    # -- intake ------------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               req_id: Optional[int] = None, arrival: float = 0.0,
               deadline_ms: Optional[float] = None) -> int:
        if req_id is None:
            req_id = self._next_auto_id
        self._next_auto_id = max(self._next_auto_id, req_id + 1)
        self.sched.submit(Request(req_id, tuple(prompt), max_new_tokens,
                                  arrival, deadline_ms))
        self._woke(self._now())
        return req_id

    def cancel(self, request_id: int) -> bool:
        """Abandon a request mid-generation (client hung up, upstream
        timeout): its blocks go straight back to the pool and its slot
        frees for the next admission — no result is recorded, nothing
        leaks until teardown. Returns False for an unknown id (already
        retired, shed, or never submitted)."""
        got = self.sched.cancel(request_id)
        if got is None:
            return False
        where, idx, st = got
        if where == "slot":
            self._sync_table(idx)
        self.stats["cancelled"] += 1
        self.telemetry.emit("serve_cancel", id=request_id, where=where,
                            tokens=len(st.generated))
        return True

    # -- helpers -----------------------------------------------------------

    def _span(self, name: str, **counts):
        """A leaf span on the serve lane (telemetry/spans.py) between two
        probes of the device (`_probe`); it adds itself to the step's list
        when it ends."""
        return _Probed(self, self.telemetry.span(
            name, tid=TID_SERVE, into=self._leaves, **counts))

    def _now(self) -> float:
        """Now, on the clock the spans are stamped on."""
        tracer = self.telemetry.tracer
        return tracer.clock() if tracer is not None else time.perf_counter()

    def _enqueued(self, out) -> None:
        """`out` is the newest output enqueued on the device: what the
        probes ask until one reads ready (one stream runs in order, so the
        newest output says whether all of it has run)."""
        self._newest, self._newest_ready = out, False

    def _fetched(self, out) -> None:
        """The host has `out`: if nothing was enqueued behind it, nothing is
        left to probe."""
        if self._newest is out:
            self._newest = None

    def _probe(self) -> int:
        """Has the device run everything it was given? 1: the newest output
        enqueued is ready; 0: it is not; -1: the host has fetched it. One
        non-blocking `is_ready()`, stamped for `step_account`, and none
        once a probe has read ready."""
        if self._newest is None:
            return -1
        if not self._newest_ready:
            self._newest_ready = self._newest.is_ready()
            self._probes.append((self._now(), self._newest_ready))
        return int(self._newest_ready)

    def _woke(self, stamp: float) -> None:
        """A request is in the system at `stamp`: the empty interval that
        the last step opened ends there (none open: nothing to do); the
        first request of all starts the account."""
        if self._since is None:
            self._since = stamp
        elif self._empty_from is not None:
            self._empties.append((self._empty_from, stamp))
        self._empty_from = None

    def _init_step_account(self) -> None:
        """The state behind `step`'s account of its own leaves, and its
        running totals in `stats` (over the steps that had device work)."""
        self.stats.update(step_wall_s=0.0, starved_s=0.0,
                          step_wall_max_s=0.0, slow_steps=0,
                          # the steps' periods and their other parts
                          # (`step_account`; fed is the rest)
                          period_s=0.0, empty_s=0.0, caller_starved_s=0.0,
                          dry_s=0.0, dry_slack_s=0.0,
                          # `is_ready()` calls made for them (`_probe`)
                          probes=0,
                          # of `decode_steps`, those enqueued while the
                          # dispatch before was in flight (`_decode_tick`)
                          decode_ahead=0, **dict.fromkeys(PREFILL_COUNTS, 0))
        self._leaves: list = []  # this step's (name, start, secs)
        # the leaves that enqueued what the last steps left un-waited
        self._in_flight: tuple = ()
        # the newest output enqueued on the device (None: fetched), whether
        # a probe has read it ready, the period's (stamp, ready) probes,
        # and whether the period before ended dry
        self._newest, self._newest_ready = None, False
        self._probes: list = []
        self._dry = False
        # when the last step with device work ended (None: no request
        # yet), since when the system has been empty (None: it is not),
        # and the period's closed (from, to) empty intervals
        self._since: Optional[float] = None
        self._empty_from: Optional[float] = None
        self._empties: list = []
        # (ready, next_ready) of the step's last wait of each name
        self._wait_probes: dict = {}
        self._prefill_seq = 0  # the number of the next prefill dispatch
        # the decode dispatch in flight (`_enqueue_decode`'s record), the
        # number of the next one, and when the last wait for either
        # program ended
        self._flying: Optional[dict] = None
        self._decode_seq = 0
        self._waited_at = 0.0
        # `toks` of a dispatch that uploads none: every slot continues
        self._no_toks = jax.device_put(
            np.full((self.num_slots,), -1, np.int32), self._rep_sh)
        self._step_t0 = time.perf_counter()  # when this step started
        self._step_compile_s = 0.0  # compile seconds drained in this step
        self._step_blocks_freed = 0  # blocks its retirements gave back
        self._gc_count, self._gc_before = None, None  # see `step`
        self._walls: deque = deque(maxlen=4096)  # for the summary's median
        # the last seconds of each part a step is judged slow by
        self._recent = {kind: deque(maxlen=SLOW_STEP_WINDOW)
                        for kind in ("host", *_WAITS)}

    def _write_rows(self, cache, tables, slot: int, st) -> None:
        """Slot `slot`'s row of each host table mirror, from the blocks its
        request `st` holds (None: a free slot, all unmapped)."""
        for table, row in zip(tables, cache.slot_rows(st, self.cfg, slot)):
            table[slot] = row

    def _sync_table(self, slot: int) -> None:
        self._write_rows(self.cache, self._tables, slot,
                         self.sched.slots[slot])
        self._decode_state = None  # roster/table changed: slow path next

    # -- the prefill program's side of the engine

    def _retire_prefilled(self, slot: int, t: float) -> int:
        """A request whose first token already ends it (EOS, a budget of
        one) leaves as soon as it is prefilled. Returns the blocks it gave
        back (0: it stays)."""
        if not self.sched.should_retire(slot, self.eos_token_id):
            return 0
        freed = self.sched.slots[slot].held_blocks
        st = self.sched.retire(slot)
        self._sync_table(slot)
        self._emit_retired(st, t)
        return freed

    def _run_prefill(self, feed):
        """One dispatch of the prefill program on `feed`; returns the
        rows' tokens and their logits, still on the device."""
        self._kv, toks, logits = self._prefill_jit(
            self.params, self._kv, *feed, self.base_key,
            self.cos, self.sin, cfg=self.cfg,
            temperature=self.temperature, top_k=self.top_k,
            cache_cls=type(self.cache))
        return toks, logits

    def _prefill_feed(self, slots, rows: Optional[int] = None):
        """The compacted prefill batch: row i carries the next chunk of
        slot slots[i] and that slot's table row; the batch is padded up
        to `rows`, the rung `prefill_cover` gave these slots (None: the
        smallest that holds them). A pad row has n_valid 0 and an
        all-unmapped table row: its writes drop. Returns (device feed in
        `serve_prefill`'s argument order, n_valid [R] on the host, the
        rows whose prompt ends in this chunk)."""
        states, cache = self.sched.slots, self.cache
        c = self.scfg.prefill_chunk
        r = rows or next(x for x in self.prefill_rungs if x >= len(slots))
        trows = tuple(np.full((r, width), unmapped, np.int32)
                      for width, unmapped in cache.table_specs)
        ids = np.zeros((r, c), np.int32)
        start, nval, rids, tidx = np.zeros((4, r), np.int32)
        finals = []
        for row, s in enumerate(slots):
            st = states[s]
            chunk = st.prefill_ids[st.n_prefilled:st.n_prefilled + c]
            for rows_of, table in zip(trows, self._tables):
                rows_of[row] = table[s]
            ids[row, :len(chunk)] = chunk
            start[row] = st.n_prefilled
            nval[row] = len(chunk)
            rids[row] = st.req.id
            tidx[row] = len(st.generated)
            if st.n_prefilled + len(chunk) >= len(st.prefill_ids):
                finals.append(row)
        feed = jax.device_put((trows, ids, start, nval, rids, tidx),
                              self._rep_sh)
        return feed, nval, finals

    def _warm_prefill(self) -> None:
        """Compile (or load from the cache) the prefill program at every
        rung, by dispatching each once with every row a pad row: the
        pools come back as they went in. A serving window then meets no
        shape the constructor has not held, whatever the traffic's
        concurrency; `stats["prefill_compiles"]` counts the rungs that
        compiled here, and stays there. Largest rung first and no wait:
        the device runs a rung's idle batch while the host traces the
        next, smaller one, so set-up pays the tracing alone."""
        for r in reversed(self.prefill_rungs):
            self._drain_compile()
            self._run_prefill(self._prefill_feed([], rows=r)[0])
            self.stats["prefill_compiles"] += bool(self._drain_compile())

    def _drain_compile(self) -> float:
        n, secs = self.telemetry.compile_watch.drain()
        if n:
            self.telemetry.emit("compile", category="compile", secs=secs,
                                compiles=n)
            self._step_compile_s += secs
        return secs if n else 0.0

    def _emit_retired(self, st, now: float) -> dict:
        req = st.req
        ttft = (st.t_first_token - req.arrival
                if st.t_first_token is not None else None)
        # TPOT: mean inter-token time AFTER the first token — the decode
        # SLO, as distinct from TTFT (the prefill/queueing SLO)
        tpot = None
        if st.t_first_token is not None and len(st.generated) > 1:
            tpot = (max(now - st.t_first_token, 0.0)
                    / (len(st.generated) - 1))
            self.telemetry.registry.histogram("serve/tpot").observe(tpot)
        res = {
            "id": req.id,
            "prompt_len": len(req.prompt),
            "tokens": list(st.generated),
            # the float32 logit each token was chosen at
            "logits": list(st.logits),
            "output_tokens": len(st.generated),
            "queue_wait_s": max((st.t_admit or 0.0) - req.arrival, 0.0),
            "ttft_s": ttft,
            "latency_s": max(now - req.arrival, 0.0),
            "tpot_s": tpot,
            "n_preempted": st.n_preempted,
        }
        self.results.append(res)
        self.telemetry.emit(
            "serve_request",
            id=req.id, prompt_tokens=res["prompt_len"],
            output_tokens=res["output_tokens"],
            queue_wait_s=round(res["queue_wait_s"], 6),
            ttft_s=round(ttft, 6) if ttft is not None else None,
            latency_s=round(res["latency_s"], 6),
            tpot_s=round(tpot, 6) if tpot is not None else None,
            preempted=st.n_preempted, engine=self.engine_id)
        return res

    def _emit_shed(self, st, now: float) -> dict:
        """Report one deadline-shed request: the queue seconds it burned
        book to the `shed` ledger category (pure badput — the wait
        bought nothing, the request never ran) and it lands in
        `shed_results`, never `results` — shed requests are excluded
        from goodput and throughput by construction."""
        wait = max(now - st.req.arrival, 0.0)
        res = {"id": st.req.id, "prompt_len": len(st.req.prompt),
               "queue_wait_s": wait, "deadline_ms": st.req.deadline_ms,
               "shed": True}
        self.shed_results.append(res)
        self.telemetry.emit("serve_shed", category="shed", secs=wait,
                            id=st.req.id, deadline_ms=st.req.deadline_ms,
                            queue_wait_s=round(wait, 6),
                            engine=self.engine_id)
        return res

    # -- one engine iteration ---------------------------------------------

    def step(self, now: Optional[float] = None) -> bool:
        """Admit; enqueue ONE prefill chunk a mid-prefill slot (a tick: one
        dispatch or several) and wait where a prompt ends; enqueue ONE decode
        dispatch over the slot batch; THEN wait for the decode dispatch
        the step before enqueued, emit its tokens and retire. The engine
        runs one decode dispatch ahead (`_decode_tick`): the tokens a step
        returns with are those of the dispatch before the one it enqueued,
        and the first step after an empty system returns with none. A
        slot whose request's budget ends in the dispatch in flight is free
        before the step admits (`_settle_flying`), so its successor rides
        the dispatch this step enqueues.
        Returns whether any device work ran (an enqueue or a wait).

        The step is one `serve.step` span whose leaf spans say what the
        host was doing (`serve.admit`, `serve.prefill.build | dispatch |
        wait | emit`, `serve.decode.build | dispatch | wait | emit`), each with
        its counts taken at the same boundary: telemetry/spans.py. A step
        with device work ends by adding those leaves up (`_account_step`)."""
        self._step_t0 = time.perf_counter()
        if now is None:
            now = self._step_t0 - self._t0
        self._leaves.clear()
        self._step_compile_s = 0.0
        self._step_blocks_freed = 0
        # `gc.get_stats()` as the step starts, for a slow step's report:
        # read anew only when the cheaper `get_count` says that a
        # collection ran since the last look
        count = gc.get_count()[1:]
        if count != self._gc_count:
            self._gc_count, self._gc_before = count, gc.get_stats()
        self._wait_probes.clear()
        with self.telemetry.span("serve.step", tid=TID_SERVE) as sp:
            if ((self._empty_from is not None or self._since is None)
                    and self.sched.has_work()):
                self._woke(sp.so_far()[0])  # a request came past `submit`
            self._probe()
            self._settle_flying()
            worked = self._step(now)
            self._probe()
            if worked:
                self._account_step(sp)
                end = self._since
            else:
                # nothing pending (an un-waited prefill's request was
                # cancelled or shed): what is enqueued runs out unwatched,
                # and the next step with work starts its account afresh;
                # this step's seconds are part of that step's period
                self._in_flight = ()
                end = self._now()
            if self._empty_from is None and not self.sched.has_work():
                self._empty_from = end  # until the next `submit`
        return worked

    def _account_step(self, sp) -> None:
        """The step's account (`step_account`), three ways: as counts on
        its `serve.step` span, beside the device plane under a profile;
        as one `phase=serve_host` event (category `serve_host`, `secs` the
        starved seconds) and one `phase=serve_dry` event (`secs` the dry
        seconds; no category, since they lie inside the seconds the
        `prefill` and `decode` phases book); and in `stats`. A slow step
        says so. `_since` is then when the step's period ended."""
        t0, wall = sp.so_far()
        acct = step_account(self._leaves, t0, wall, self._in_flight,
                            self._probes, self._since, self._empties,
                            self._dry)
        self._in_flight, self._dry = acct["in_flight"], acct["dry"]
        self._since = acct["end"]
        st = self.stats
        st["probes"] += len(self._probes)
        self._probes.clear()
        self._empties.clear()
        starved, dry = acct["starved_s"], acct["dry_s"]
        sp.set(wall_us=int(wall * 1e6), starved_us=int(starved * 1e6),
               period_us=int(acct["period_s"] * 1e6),
               empty_us=int(acct["empty_s"] * 1e6),
               caller_starved_us=int(acct["caller_starved_s"] * 1e6),
               dry_us=int(dry * 1e6),
               dry_slack_us=int(acct["dry_slack_s"] * 1e6))
        st["step_wall_s"] += wall
        st["starved_s"] += starved
        for part in ("period_s", "empty_s", "caller_starved_s", "dry_s",
                     "dry_slack_s"):
            st[part] += acct[part]
        if wall > st["step_wall_max_s"]:
            st["step_wall_max_s"] = wall
        self.telemetry.emit("phase", phase="serve_host",
                            category="serve_host", secs=starved,
                            engine=self.engine_id)
        self.telemetry.emit("phase", phase="serve_dry", secs=dry,
                            engine=self.engine_id)
        self._walls.append(wall)
        waits = acct["waits"]
        held = None  # the part furthest over its limit
        for kind, secs, n in (
                ("host", wall - sum(w[1] for w in waits), 1), *waits):
            seen = self._recent[kind]
            if secs > SLOW_STEP_S and len(seen) >= SLOW_STEP_AFTER:
                limit = max(SLOW_STEP_S,
                            SLOW_STEP_X * statistics.median(seen) * n)
                if secs > limit and (held is None
                                     or secs / limit > held[1] / held[2]):
                    held = (kind, secs, limit, n)
            seen.append(secs / n)
        if held is not None:
            self._slow_step(acct, *held)

    def _slow_step(self, acct: dict, kind: str, secs: float, limit: float,
                   cleared: int) -> None:
        """One `serve_slow_step` event with the whole account, the part
        that was over its limit (`held_by`: a wait, for the `held_for`
        dispatches it cleared, or `host`, the wall less the waits) and
        what else could hold a step (a compile, a collection, the load),
        and one WARNING line: the leaf that held the seconds says where to
        look (a wait: device or runtime; a build or emit: the host;
        `unspanned`: the code between spans; none: the caller). A wait
        says which of the two: `ready`, whether the dispatch it waited for
        had finished when it began, and `next_ready`, whether the one
        enqueued behind that had when it ended (-1: none was). A long wait
        that ends with a 20 ms dispatch behind it finished means the
        device kept running and the runtime held the host; one that ends
        with it still running means the device or its queue was late."""
        self.stats["slow_steps"] += 1
        n = self.stats["slow_steps"]
        collections = [[g["collections"] for g in stats]
                       for stats in (self._gc_before, gc.get_stats())]
        active = sum(s is not None for s in self.sched.slots)
        ready, next_ready = self._wait_probes.get(kind, (None, None))
        self.telemetry.emit(
            "serve_slow_step", held_by=kind, held_s=round(secs, 6),
            limit_s=round(limit, 6), held_for=cleared,
            ready=ready, next_ready=next_ready,
            wall_s=round(acct["wall_s"], 6),
            starved_s=round(acct["starved_s"], 6),
            unspanned_ms=round(acct["unspanned_s"] * 1e3, 3),
            leaves_ms=_ms(acct["leaves"]),
            starved_by_ms=_ms(acct["starved_by"]),
            dry_by_ms=_ms(acct["dry_by"]),
            compile_s=round(self._step_compile_s, 6),
            blocks_freed=self._step_blocks_freed,
            gc_before=collections[0], gc_after=collections[1],
            active=active, queued=len(self.sched.queue),
            engine=self.engine_id)
        if n > SLOW_STEP_LOGS:
            return
        name, longest = max({**acct["leaves"],
                             "unspanned": acct["unspanned_s"]}.items(),
                            key=lambda kv: kv[1])
        log.warning(
            "serve engine=%d slow step: %s %.3f s (limit %.3f for %d "
            "dispatched%s) of wall %.3f s, longest leaf %s %.3f s, starved "
            "%.3f s, dry %.3f s, compile %.3f s, blocks freed %d, "
            "collections %s -> %s, active %d, queued %d%s", self.engine_id,
            kind, secs, limit, cleared,
            "" if ready is None else
            f"; ready={ready} as the wait began, next_ready={next_ready} "
            "as it ended", acct["wall_s"], name, longest,
            acct["starved_s"], acct["dry_s"], self._step_compile_s,
            self._step_blocks_freed, *collections, active,
            len(self.sched.queue),
            "; further slow steps are counted, not logged"
            if n == SLOW_STEP_LOGS else "")

    def _step(self, now: float) -> bool:
        reg = self.telemetry.registry

        with self._span("serve.admit") as sp:
            admitted = self.sched.admit(now)
            for slot, st in admitted:
                self._sync_table(slot)
                self._note_admitted(st, now, reg)
            for st in self.sched.drain_shed():
                self._emit_shed(st, now)
            sp.set(admitted=len(admitted), queued=len(self.sched.queue))

        # ---- one prefill chunk per mid-prefill slot, batched into a
        # single dispatch and interleaved with the decode step
        worked = self._prefill_tick(now, reg)

        # ---- one decode step over every slot with a live sequence
        decode_ran = self._decode_tick(now, reg)
        worked = worked or decode_ran
        # max consecutive ticks with work in the system, no decode dispatch
        if decode_ran:
            self._stall_streak = 0
        elif self.sched.has_work():
            self._stall_streak += 1
            self.stats["decode_stall_ticks_max"] = max(
                self.stats["decode_stall_ticks_max"], self._stall_streak)
        return worked

    def _note_admitted(self, st, now: float, reg) -> None:
        """One request left the queue: its wait is a `phase` event (which
        carries (category, secs) so a post-hoc sum of the JSONL reproduces
        the in-process ledger, like the training stream's), a histogram
        sample, and a `serve.queue_wait` span that ends now."""
        wait = max(now - st.req.arrival, 0.0)
        self.telemetry.emit("phase", phase="queue_wait",
                            category="queue_wait", secs=wait, id=st.req.id)
        self.telemetry.record_wait("serve.queue_wait", wait, tid=TID_SERVE,
                                   id=st.req.id)
        reg.histogram("serve/queue_wait").observe(wait)

    def _prefill_tick(self, now: float, reg) -> bool:
        """One prefill tick: the next chunk of every mid-prefill slot,
        laid onto the rungs the constructor compiled (`prefill_cover`:
        seventeen rows ride the 16-row and the one-row rung, not the
        64-row one). A piece is a dispatch of its own (`_prefill_feed` at
        its rung) with a span, a `seq` and counts of its own, `piece` of
        `pieces`; all are enqueued before any is waited for, the pools
        chained through the donated argument, so the device runs them back
        to back. Every slot advances one chunk a tick whatever piece
        carries it, and samples under its own (request id, token index)
        key, so the served tokens are those of one dispatch over all the
        rows. What the tick books is `_emit_prefilled`'s. Returns whether a
        dispatch ran."""
        slots = self.sched.prefill_slots()
        if not slots:
            return False
        states, cache = self.sched.slots, self.cache
        chunk = self.scfg.prefill_chunk
        with self._span("serve.prefill.build"):
            pieces, at = [], 0
            for rung, n in prefill_cover(len(slots), self.prefill_rungs):
                mine = slots[at:at + n]  # oldest admitted first
                feed, nval, finals = self._prefill_feed(mine, rows=rung)
                pieces.append({"slots": mine, "nval": nval, "feed": feed,
                               "finals": finals})
                at += n
        self._drain_compile()
        t0 = time.perf_counter()
        # every piece enqueued before any is waited for
        for i, p in enumerate(pieces):
            mine, nval = p["slots"], p["nval"]
            if watchdog.active():
                # a hang inside a dispatch is reported as THAT dispatch, not
                # a bare stack dump (the fleet's serve_hang detection)
                watchdog.touch(
                    f"serve engine={self.engine_id} dispatch=prefill")
            p["seq"] = self._prefill_seq
            self._prefill_seq += 1
            # `capacity` is what the program computes: the rung's `rows`, of
            # which `slots` carry a request; `seq` numbers the dispatch
            with self._span("serve.prefill.dispatch", slots=len(mine),
                            rows=len(nval), tokens=int(nval.sum()),
                            capacity=len(nval) * chunk,
                            ids=join_ids(states[s].req.id for s in mine),
                            seq=p["seq"], piece=i, pieces=len(pieces),
                            **cache.prefill_counts(
                                [(states[s].n_prefilled, int(nval[row]))
                                 for row, s in enumerate(mine)], self.cfg,
                                rows=len(nval))):
                p["toks"], p["logits"] = self._run_prefill(p["feed"])
                self._enqueued(p["toks"])
        n_finals = sum(len(p["finals"]) for p in pieces)
        newest = pieces[-1]
        if n_finals:
            # the host needs a token only when a prompt ends in the tick's
            # chunks; otherwise its pieces are left in flight across steps,
            # as a decode dispatch is. The ONE wait fetches the newest piece
            # too, so it clears all that is in flight (`step_account`) and
            # carries that piece's `seq`. `ready`: whether the newest had
            # run when the wait began; nothing is enqueued behind it
            probes = (int(newest["toks"].is_ready()), -1)
            with self._span("serve.prefill.wait", finals=n_finals,
                            seq=newest["seq"], ready=probes[0],
                            next_ready=probes[1]):
                want = [p for p in pieces if p["finals"] or p is newest]
                for p, out in zip(want, jax.device_get(
                        [(p["toks"], p["logits"]) for p in want])):
                    p["out"] = out  # the rows' tokens and their logits
                self._fetched(newest["toks"])
            self._wait_probes["serve.prefill.wait"] = probes
            self._waited_at = time.perf_counter()
        dt = time.perf_counter() - t0
        csecs = self._drain_compile()
        # the constructor held every rung: a compile here is a shape or
        # a sharding the feed should not have produced
        self.stats["prefill_compiles"] += bool(csecs)
        dt -= min(csecs, dt)
        self._emit_prefilled(pieces, n_finals, now, dt, reg)
        return True

    def _decode_tick(self, now: float, reg) -> bool:
        """The decode side of one step, ONE DISPATCH AHEAD: enqueue decode
        dispatch n + 1 over every decode-ready slot, from the host's
        projection of where dispatch n leaves each (`_enqueue_decode`),
        and only then wait for dispatch n's tokens and emit them
        (`_collect_decode`). The device always has a program queued behind
        the one it runs, so the copy back, the wake, the emit, the caller's
        reading of the tokens, the next admit and the next build happen
        while it computes. With nothing in flight (the first dispatch
        after an empty system) the step enqueues and returns, and the next
        step is ahead. Returns whether a dispatch was enqueued or waited
        for."""
        flying = self._flying
        self._flying = self._enqueue_decode(flying)
        if flying is not None:
            self._collect_decode(flying, now, reg)
        return flying is not None or self._flying is not None

    def _rows_in_flight(self, flying) -> dict:
        """{slot: its request} for the rows of the dispatch in flight that
        still stand: the slot holds the request the dispatch was built
        for, where it was built. A request that was cancelled, shed,
        preempted, displaced or retired since (an EOS inside the dispatch
        before, which only the device knew) has no row: what the dispatch
        computes for it is padding. Nor has one that `_settle_flying`
        released: its tokens are kept, and its slot is another's."""
        if flying is None:
            return {}
        return {s: st for s, st, n in flying["rows"]
                if self.sched.slots[s] is st and len(st.generated) == n}

    def _settle_flying(self) -> None:
        """What a step knows of the dispatch in flight before it admits.
        A row whose budget ends inside it needs its slot and its blocks
        for nothing more, whatever its tokens are: both are given back
        now (`released`), so that this step's admission can hand them on
        and the successor rides the dispatch this step enqueues, as it
        would behind a wait; whatever writes those blocks next is enqueued
        behind the dispatch in flight, on the same stream. The request's
        tokens are emitted at this step's end (`_collect_decode`), so it
        is out of the scheduler for no longer than the step. A dispatch of
        which no row stands (its requests were cancelled, or retired on an
        EOS inside the dispatch before) is forgotten: nobody waits for it,
        and the next one is not ahead of anything."""
        flying = self._flying
        if flying is None:
            return
        rows = self._rows_in_flight(flying)
        if not rows:
            self._flying = None
            self._in_flight = tuple(x for x in self._in_flight
                                    if x != _DECODE_DISPATCH)
            return
        ending = [s for s, st in rows.items()
                  if st.req.max_new_tokens - len(st.generated)
                  <= self.scfg.decode_interval]
        if not ending:
            return
        # the half of the emit that needs no token, under the emit's name:
        # its counts add up with those of the span behind the wait
        with self._span("serve.decode.emit") as sp:
            n_freed = sum(rows[s].held_blocks for s in ending)
            for s in ending:
                flying["released"][s] = self.sched.retire(s)
                self._sync_table(s)
            sp.set(tokens=0, retired=len(ending), blocks_freed=n_freed,
                   dropped=0)
        self._step_blocks_freed += n_freed

    def _enqueue_decode(self, flying) -> Optional[dict]:
        """Build and enqueue one decode dispatch behind `flying`, the one
        in flight (None: none is), without its tokens. What the host can
        project it does: a slot with a row in flight stands `interval`
        tokens further (its position, its token index, the blocks it
        needs, what the step reads of the cache); one whose budget ends
        inside the dispatch in flight has left its slot already
        (`_settle_flying`). What it cannot project is the token, which
        the program takes from the device (`serve_decode`: `last`).
        Returns the record `_collect_decode` reads, None where nothing
        was enqueued."""
        ready = self.sched.decode_ready()
        if not ready:
            return None
        interval = self.scfg.decode_interval
        rows = self._rows_in_flight(flying)
        with self._span("serve.decode.build") as sp:
            active, ahead_of, left_of = [], {}, {}
            dropped: set = set()
            for s in ready:
                if s in dropped:
                    continue
                st = self.sched.slots[s]
                # the tokens in flight for it, and its budget after them
                # (some: `_settle_flying`)
                ahead = interval if s in rows else 0
                left = st.req.max_new_tokens - len(st.generated) - ahead
                n_before = st.held_blocks
                ok, preempted = self.sched.ensure_block(
                    s, ahead + min(interval, left))
                dropped.update(preempted)
                for p in preempted:
                    self._sync_table(p)
                if ok:
                    if st.held_blocks != n_before:
                        self._sync_table(s)
                    active.append(s)
                    ahead_of[s], left_of[s] = ahead, min(interval, left)
            # a later ensure_block can preempt a slot already activated
            # (it was younger than the one needing the block)
            active = [s for s in active if s not in dropped]
            ds = self._decode_state
            rebuilt = bool(active) and (flying is None or ds is None
                                        or ds["active"] != active)
            if rebuilt:
                # slow path: roster changed — rebuild inputs on host,
                # uploaded with the shardings earlier calls produced
                # so the rebuild cannot mint a new jit variant
                toks = np.zeros((self.num_slots,), np.int32)
                positions = np.full((self.num_slots,), -1, np.int32)
                rids = np.zeros((self.num_slots,), np.int32)
                tidx = np.zeros((self.num_slots,), np.int32)
                for s in active:
                    st = self.sched.slots[s]
                    positions[s] = st.write_pos + ahead_of[s]
                    rids[s] = st.req.id
                    tidx[s] = len(st.generated) + ahead_of[s]
                    # it joins, its token is here; or it continues (-1)
                    toks[s] = -1 if ahead_of[s] else st.last_token
                # the tables as they stand, in a copy: the mirrors change
                # (`_sync_table`) while this dispatch is in flight, and an
                # upload may read its source until the program has run
                ds = dict(zip(
                    ("tables", "toks", "positions", "rids", "tidx"),
                    jax.device_put((tuple(t.copy() for t in self._tables),
                                    toks, positions, rids, tidx),
                                   self._rep_sh)),
                    active=list(active))
            sp.set(rebuilt=int(rebuilt), preempted=len(dropped))
        if not active:
            return None
        self._drain_compile()
        if watchdog.active():
            watchdog.touch(
                f"serve engine={self.engine_id} dispatch=decode")
        # the requests the dispatch advances, where it finds each: they
        # tag the dispatch span and the decode phase event, and say at the
        # emit which rows still stand
        live = [self.sched.slots[s] for s in active]
        dec_ids = [st.req.id for st in live]
        # what the step reads of the cache at THIS dispatch's first token
        # (`kv_blocks` and the counts of the cache's kind), and
        # `view_blocks`: what the gathered view spans, whatever is live
        read = self.cache.decode_counts(
            [(st.write_pos + ahead_of[s], left_of[s])
             for s, st in zip(active, live)], self.cfg)
        seq = self._decode_seq
        self._decode_seq += 1
        t0 = time.perf_counter()
        with self._span("serve.decode.dispatch", active=len(active),
                        interval=interval,
                        view_blocks=self.num_slots * self.max_blocks,
                        ids=join_ids(dec_ids), seq=seq,
                        ahead=int(flying is not None), dispatched=1, **read):
            (toks_d, lg_d, last_d, pos_d, tidx_d, touched_d,
             self._kv) = self._decode_jit(
                self.params, self._kv, ds["tables"], ds["toks"],
                # with nothing in flight no slot continues: read for none
                ds["toks"] if flying is None else flying["last"],
                ds["positions"], ds["rids"], ds["tidx"], self.base_key,
                self.cos, self.sin, cfg=self.cfg,
                temperature=self.temperature, top_k=self.top_k,
                interval=interval, eos_token_id=self.eos_token_id,
                cache_cls=type(self.cache))
            self._enqueued(toks_d)
        csecs = self._drain_compile()
        if csecs:
            self.stats["decode_compiles"] += 1
        # feed outputs forward: the next dispatch's inputs while the
        # roster stands; any roster/table change nulls this via _sync_table
        self._decode_state = dict(ds, toks=self._no_toks, positions=pos_d,
                                  tidx=tidx_d)
        return {"seq": seq, "ahead": flying is not None, "ids": dec_ids,
                "rows": [(s, st, len(st.generated) + ahead_of[s])
                         for s, st in zip(active, live)],
                "out": (toks_d, lg_d, touched_d), "last": last_d,
                "t0": t0, "compile_s": csecs,
                # {slot: the request `_settle_flying` released}
                "released": {}}

    def _collect_decode(self, flying, now: float, reg) -> None:
        """Wait for the tokens of `flying`, the oldest decode dispatch in
        flight, and emit them: each row that still stands
        (`_rows_in_flight`) or was released (`_settle_flying`) gets its
        tokens up to its EOS or its budget and retires there; the others'
        are dropped, and regenerated alike under the (request id, token
        index) key where the request lives on. A retirement is stamped
        when the host has the token."""
        interval = self.scfg.decode_interval
        # `ready`: whether the dispatch had run when the wait began;
        # `next_ready`: whether what was enqueued behind it (the dispatch
        # this step enqueued, as a rule) had when the wait ended
        ready = int(flying["out"][0].is_ready())
        with self._span("serve.decode.wait", seq=flying["seq"],
                        ready=ready) as sp:
            # tokens and their logits [S, interval], and the experts the
            # steps touched and visited: known once the dispatch has run,
            # so the counts ride this span and not the dispatch's
            nxt, lgs, counts = jax.device_get(flying["out"])
            self._fetched(flying["out"][0])
            probes = self._wait_probes[_DECODE_WAIT] = (ready, self._probe())
            sp.set(next_ready=probes[1])
            if self.cfg.num_experts:
                step = dict(zip(expert_counts(self.cfg), map(int, counts)),
                            expert_slots=(self.cfg.stacks[-1].layers
                                          * interval * self.cfg.num_experts))
                for name, n in step.items():
                    self.stats[name] += n
                sp.set(**step)
        t_end = time.perf_counter()
        # the dispatch's seconds as the host saw them pass: to the end of
        # its wait from its enqueue, or, where it was enqueued ahead of
        # that, from the end of the wait before (the decode dispatch's
        # before it, or a prompt's last chunk's, whose seconds are the
        # prefill's): a steady state reads the step's period, and no second
        # is booked to two phases
        dt = t_end - max(flying["t0"], self._waited_at)
        dt -= min(flying["compile_s"], dt)
        self._waited_at = t_end
        t_done = now + t_end - self._step_t0
        rows, released = self._rows_in_flight(flying), flying["released"]
        n_tokens = n_retired = n_freed = n_dropped = 0
        with self._span("serve.decode.emit") as sp:
            for s, st, _ in flying["rows"]:
                if s not in rows and s not in released:
                    n_dropped += 1
                    continue
                for t in range(interval):
                    st.generated.append(int(nxt[s, t]))
                    st.logits.append(float(lgs[s, t]))
                    n_tokens += 1
                    if ended(st, self.eos_token_id):
                        # tokens past EOS/budget are padding
                        if s in rows:  # an EOS: nobody knew
                            n_freed += st.held_blocks
                            self.sched.retire(s)
                            self._sync_table(s)
                            n_retired += 1
                        self._emit_retired(st, t_done)
                        break
            # `retired`, `blocks_freed`: the retirements here and what they
            # gave back to the pools, the size of the one thing here that
            # grows with a request (a budget's end gave its own back at
            # the step's start, `_settle_flying`); `dropped`: rows whose
            # request left while they were in flight
            sp.set(tokens=n_tokens, retired=n_retired, blocks_freed=n_freed,
                   dropped=n_dropped)
        self._step_blocks_freed += n_freed
        self.telemetry.emit("phase", phase="decode",
                            category="decode", secs=dt,
                            tokens=n_tokens, ids=flying["ids"])
        n_rows = len(flying["rows"])
        reg.histogram("serve/token_latency").observe(
            dt / max(n_rows * interval, 1))
        self.stats["decode_steps"] += 1
        self.stats["decode_ahead"] += flying["ahead"]
        self.stats["occupancy_sum"] += n_rows / self.num_slots
        self.stats["output_tokens"] += n_tokens

    def _emit_prefilled(self, pieces, n_finals: int, now: float, dt: float,
                        reg) -> None:
        """What a prefill tick books once its pieces are enqueued and, where
        a prompt ended in one, fetched (`_prefill_tick`): ONE `phase=prefill`
        event for the tick (its `secs` lie on one clock: a piece has no
        seconds of its own on the host; `dispatches` says how many it
        covers), every slot's chunk noted, the tick's counts in `stats`
        (`prefill_ticks`, `prefill_dispatches`, and the rows the rungs
        computed as `prefill_rows_real` + `prefill_rows_padded`), and the
        first token of each prompt that ended, under `serve.prefill.emit`.
        `dt`: the seconds since the first piece's enqueue, less compiles."""
        states = self.sched.slots
        slots = [s for p in pieces for s in p["slots"]]
        # `waited`: whether `secs` is the device's time for the chunks or
        # only the enqueues
        self.telemetry.emit("phase", phase="prefill", category="prefill",
                            secs=dt,
                            tokens=sum(int(p["nval"].sum()) for p in pieces),
                            ids=[states[s].req.id for s in slots],
                            waited=bool(n_finals), dispatches=len(pieces))
        for p in pieces:
            for row, s in enumerate(p["slots"]):
                self.sched.note_prefilled(s, int(p["nval"][row]))
        stats = self.stats
        stats["prefill_chunks"] += len(slots)
        stats["prefill_ticks"] += 1
        stats["prefill_dispatches"] += len(pieces)
        stats["prefill_rows_real"] += len(slots)
        stats["prefill_rows_padded"] += (sum(len(p["nval"]) for p in pieces)
                                         - len(slots))
        if not n_finals:
            return
        n_retired = n_freed = 0
        with self._span("serve.prefill.emit") as sp:
            for p in pieces:
                if not p["finals"]:
                    continue
                toks, logits = p["out"]
                for row in p["finals"]:
                    slot = p["slots"][row]
                    st = states[slot]
                    st.generated.append(int(toks[row]))
                    st.logits.append(float(logits[row]))
                    stats["output_tokens"] += 1
                    if st.t_first_token is None:
                        st.t_first_token = now + dt
                        ttft = max(st.t_first_token - st.req.arrival, 0.0)
                        reg.histogram("serve/ttft").observe(ttft)
                    freed = self._retire_prefilled(slot, now + dt)
                    n_retired += bool(freed)
                    n_freed += freed
            sp.set(tokens=n_finals, retired=n_retired, blocks_freed=n_freed)
        self._step_blocks_freed += n_freed

    # -- trace driver ------------------------------------------------------

    def run(self, requests=(), watchdog_timeout: float = 0.0) -> list:
        """Drive a whole trace: submit each (prompt, max_new_tokens[,
        arrival[, deadline_ms]]) when its arrival time passes on the
        trace clock, loop engine steps until queue and slots drain.
        Returns per-request result dicts sorted by request id (shed
        requests are in `self.shed_results`, not here).

        watchdog_timeout > 0 arms a resilience watchdog for the trace:
        every dispatch heartbeats with a phase naming this engine and
        dispatch kind, so a wedged device call is reported as `serve
        engine=K dispatch=decode` — flightdeck postmortem reason
        `serve_hang`, then exit 77 for the supervisor (same contract as
        a hung training collective)."""
        wd = None
        if watchdog_timeout > 0:
            from picotron_tpu.resilience.watchdog import Watchdog
            wd = Watchdog(watchdog_timeout, reason="serve_hang")
            wd.start()
        try:
            pending = sorted(requests,
                             key=lambda r: r[2] if len(r) > 2 else 0.0)
            self._t0 = t0 = time.perf_counter()
            while pending or self.sched.has_work():
                now = time.perf_counter() - t0
                while pending and (pending[0][2] if len(pending[0]) > 2
                                   else 0.0) <= now:
                    r = pending.pop(0)
                    self.submit(r[0], r[1],
                                arrival=r[2] if len(r) > 2 else 0.0,
                                deadline_ms=r[3] if len(r) > 3 else None)
                if not self.sched.has_work():
                    time.sleep(min(max(pending[0][2] - now, 0.0), 0.01))
                    continue
                self.step(now)
        finally:
            if wd is not None:
                wd.stop()
        self._emit_summary(time.perf_counter() - t0)
        return sorted(self.results, key=lambda r: r["id"])

    def _emit_summary(self, wall: float) -> None:
        self.summary = self._summary_dict(wall)
        self.telemetry.emit("serve_summary", **self.summary)

    def _summary_dict(self, wall: float) -> dict:
        reg = self.telemetry.registry
        ttft = reg.histogram("serve/ttft")
        lat = reg.histogram("serve/token_latency")
        qw = reg.histogram("serve/queue_wait")
        tpot = reg.histogram("serve/tpot")
        steps = max(self.stats["decode_steps"], 1)
        return {
            "requests": len(self.results),
            "output_tokens": sum(r["output_tokens"] for r in self.results),
            "wall_s": round(wall, 6),
            "tokens_per_sec": round(
                sum(r["output_tokens"] for r in self.results)
                / max(wall, 1e-9), 2),
            "ttft_p50_s": ttft.p50, "ttft_p95_s": ttft.p95,
            "token_latency_p50_s": lat.p50, "token_latency_p95_s": lat.p95,
            "tpot_p50_s": tpot.p50, "tpot_p95_s": tpot.p95,
            "queue_wait_p50_s": qw.p50, "queue_wait_p95_s": qw.p95,
            "slot_occupancy": round(self.stats["occupancy_sum"] / steps, 4),
            "pool_peak_utilization": round(
                self.pool.peak_in_use / self.num_blocks, 4),
            "window_pool_peak_utilization": (
                round(self.wpool.peak_in_use / self.wpool.num_blocks, 4)
                if self.wpool is not None else None),
            "experts_touched": self.stats.get("experts_touched", 0),
            "expert_visits": self.stats.get("expert_visits", 0),
            "expert_slots": self.stats.get("expert_slots", 0),
            "decode_steps": self.stats["decode_steps"],
            # of them, the share enqueued while the dispatch before was in
            # flight: how often the device had its next program queued
            "decode_ahead_share": round(
                self.stats["decode_ahead"] / steps, 4),
            "decode_compiles": self.stats["decode_compiles"],
            "prefill_compiles": self.stats["prefill_compiles"],
            "prefill_chunks": self.stats["prefill_chunks"],
            # the ticks that carried them, the dispatches those were laid
            # onto (`prefill_cover`), and the rows the rungs computed: with
            # a request, and pad
            **{k: self.stats[k] for k in PREFILL_COUNTS},
            "decode_stall_ticks_max":
                self.stats["decode_stall_ticks_max"],
            # of the steps' wall, the share with nothing enqueued on the
            # device (`step_account`)
            "device_starved_share": (
                round(self.stats["starved_s"] / self.stats["step_wall_s"], 4)
                if self.stats["step_wall_s"] else None),
            # the steps' periods (each from the end of the step with
            # device work before it) and their parts, one name a second:
            # empty, starved (`device_starved_share`'s, inside the steps),
            # caller-starved, dry (a lower bound; with the slack, the
            # upper), and the rest fed
            **{k: round(self.stats[k], 6)
               for k in ("period_s", "empty_s", "starved_s",
                         "caller_starved_s", "dry_s", "dry_slack_s")},
            "system_empty_share": self._share("empty_s"),
            "device_dry_share": self._share("dry_s"),
            "step_wall_p50_s": (round(statistics.median(self._walls), 6)
                                if self._walls else None),
            "step_wall_max_s": round(self.stats["step_wall_max_s"], 6),
            "slow_steps": self.stats["slow_steps"],
            "preemptions": self.sched.n_preempted,
            "shed": self.sched.n_shed,
            "cancelled": self.stats["cancelled"],
            "slots": self.num_slots,
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
        }

    def _share(self, part: str) -> Optional[float]:
        """One part of the steps' periods as a share of them."""
        period = self.stats["period_s"]
        return round(self.stats[part] / period, 4) if period else None

    def close(self) -> None:
        self._flying = None  # a dispatch of padding nobody waited for
        if self._owns_telemetry:
            self.telemetry.close()


# ---------------------------------------------------------------------------
# A prefill tick's rows on the compiled rungs (host code: below the device
# programs and their callers, see above `step_account`)
# ---------------------------------------------------------------------------

# `engine.stats` of the prefill ticks (`ServeEngine._emit_prefilled` counts
# them; `_init_step_account` zeroes them)
PREFILL_COUNTS = ("prefill_ticks", "prefill_dispatches", "prefill_rows_real",
                  "prefill_rows_padded")

# The least share of the smallest rung that holds a tick's rows at which the
# rung takes them all, padded. A measurement, not a setting (PERF.md section
# 6, PR 56: every rung of all eight serving cells timed on the chip, and ticks
# of 2-65 rows each way). A pad row costs what a real row costs (84-100% of
# it) and a dispatch's fixed part is less than one row's arithmetic in every
# cell (0.3-0.9 of it, the models with expert banks highest), so two rows run
# faster as 1 + 1 than padded to four everywhere (by 20-68%) and nine as
# 4 + 4 + 1 than padded to sixteen; three rows as 1 + 1 + 1 lose to the
# four-row rung in the three cells that stream expert banks (by 8-10%), which
# is what holds the fraction at three quarters and not above.
PAD_UP_FROM = 0.75


def prefill_cover(n: int, rungs) -> tuple:
    """The pieces that carry a tick's `n` mid-prefill rows, `((rung, rows),
    ...)`, largest first: each `rung` one of `rungs` (`prefill_rungs`:
    ascending, the last holds every slot), `rows` the real rows it carries,
    summing to `n`. Walking down from `n`: where the rows left fill at least
    `PAD_UP_FROM` of the smallest rung that holds them, that rung takes them
    all, padded; otherwise the largest rung they fill takes that many, full,
    and the walk goes on with the rest. So every piece but the last is full
    and a tick computes at most `n / PAD_UP_FROM` rows: 17 rows of (1, 4, 16,
    64, 128) ride (16, 16) + (1, 1) and not 64, 5 ride (4, 4) + (1, 1), 2
    ride (1, 1) + (1, 1), 40 ride 16 + 16 + 4 + 4, 31 ride (16, 16) + (16,
    15), and 1, 3, 4 or 13 ride one rung as they always did. It reads the
    count and the engine's own ladder, nothing else."""
    pieces = []
    while n > 0:
        up = next(r for r in rungs if r >= n)
        if n >= PAD_UP_FROM * up:
            pieces.append((up, n))
            break
        full = max(r for r in rungs if r <= n)
        pieces.append((full, full))
        n -= full
    return tuple(pieces)
