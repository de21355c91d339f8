"""Disaggregated serving: prefill and decode as separately placed pools.

Under heavy mixed traffic the colocated engine has one structural
weakness: admission couples a request's PREFILL to a DECODE slot, so a
burst of long prompts occupies decode slots with chunked prefill work
and the in-flight decode batch starves — the classic TTFT/TPOT SLO
killer. This module splits the two phases into independent pools, in the
MPMD spirit of parallel/mpmd.py (arxiv 2412.14374): each pool is its own
separately PLACED jitted program over its own paged-KV block pool, and
finished prefixes cross the boundary through an explicit
``jax.device_put`` handoff — the same transfer_guard-clean ring-buffer
discipline the pipeline executor uses for boundary activations.

- **Prefill pool**: `prefill_slots` slots over `prefill_num_blocks`
  blocks on `prefill_device`, running the SAME `serve_prefill`
  program as the colocated engine (chunked, batched over mid-prefill
  slots). Admission is budgeted against THIS pool only.
- **Decode pool**: `decode_slots` slots over `num_blocks` blocks on
  `decode_device`, running the same decode program via the
  `ServeEngine._decode_tick` it inherits, one dispatch ahead as there:
  a step's handoffs are enqueued behind the decode dispatch in flight
  and in front of the one the step builds. Long-prompt bursts
  cannot touch it: `bench.py --serve --disagg` measures the max
  consecutive decode-stall ticks collapsing vs colocated.
- **Handoff**: a jitted block gather on the prefill device ->
  `jax.device_put` of the staging buffer to the decode placement (the
  ONLY inter-pool transfer, always explicit) -> a jitted sentinel-drop
  scatter into the decode pool. Index vectors are fixed [max_blocks]
  wide (padding gathers garbage that the scatter's sentinel drops), so
  both programs compile exactly once per engine lifetime — proven
  statically by `analysis/variants.prove_disagg_programs` and priced by
  `analysis/cost_model.price_kv_handoff`.

Token parity: the device programs, the paged-cache layout, and the
(request id, token index) sampling-key fold are all shared with the
colocated engine, so disaggregated output is bit-identical to colocated
(and to the offline sampler) on any trace, including under preemption —
a pinned test invariant, not an aspiration.

When params arrive tp-sharded (NamedSharding), both pools degrade to
the shared mesh placement: the pools and the handoff still exist (the
device_put becomes a same-sharding copy), only the physical separation
collapses. CPU tests use the 8 simulated devices from conftest to
exercise REAL cross-device handoff.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from picotron_tpu.config import ModelConfig, ServeConfig
from picotron_tpu.models.llama import model_rope_tables
from picotron_tpu.serve.engine import (
    ServeEngine, _get_jits, mesh_shardings, new_cache, prefill_rungs,
)
from picotron_tpu.serve.paged_cache import BlockPool
from picotron_tpu.serve.scheduler import DisaggScheduler, blocks_for
from picotron_tpu.telemetry import Telemetry


# ---------------------------------------------------------------------------
# Handoff device programs (module-level: one jit cache for all engines)
# ---------------------------------------------------------------------------


def _gather_blocks_impl(k, v, idx):
    """Pull the handed-off sequence's blocks out of the prefill pool
    into a dense staging buffer: k/v [Hkv, L, N_p, bs, D], idx
    [max_blocks] physical block ids (0-padded past the sequence's
    blocks — the padding rows carry garbage the scatter side drops).
    Runs ON the prefill placement; the returned buffer is what crosses
    the pool boundary via device_put."""
    return k[:, :, idx], v[:, :, idx]


def _scatter_blocks_impl(k, v, buf_k, buf_v, idx):
    """Scatter the staging buffer into the decode pool's blocks: idx
    [max_blocks] destination block ids, sentinel (= N_d) past the
    sequence's blocks so padding rows DROP — the same sentinel
    discipline as the paged cache's write path. Runs ON the decode
    placement."""
    return (k.at[:, :, idx].set(buf_k, mode="drop"),
            v.at[:, :, idx].set(buf_v, mode="drop"))


_HANDOFF_JITS: dict = {}


def _get_handoff_jits(donate: bool):
    if donate not in _HANDOFF_JITS:
        _HANDOFF_JITS[donate] = (
            jax.jit(_gather_blocks_impl),
            jax.jit(_scatter_blocks_impl,
                    donate_argnums=(0, 1) if donate else ()),
        )
    return _HANDOFF_JITS[donate]


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class DisaggServeEngine(ServeEngine):
    """Same public surface as ServeEngine (submit / step / run / summary
    / results / close — bench and the tests drive either through one
    code path), backed by two pools. Inherits the decode tick, the
    retirement/telemetry plumbing, and the trace driver; owns admission
    -> prefill -> handoff."""

    def __init__(self, params, model_cfg: ModelConfig,
                 serve_cfg: Optional[ServeConfig] = None, *,
                 eos_token_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 telemetry: Optional[Telemetry] = None,
                 engine_id: int = 0):
        scfg = serve_cfg or ServeConfig()
        scfg.validate()
        if (model_cfg.num_experts or model_cfg.layer_types is not None
                or model_cfg.mla or model_cfg.eva):
            raise ValueError(
                "disaggregated serving does not support MoE models "
                "(num_experts > 0), sliding-window layers, latent "
                "attention or attention_class 'eva': the block handoff "
                "between the two pools has never run an expert block or "
                "another kind of cache state, and nothing tests it with "
                "one. Serve them through ServeEngine.")
        self.wpool = None  # one pool a side, full layers
        self.cfg = model_cfg
        self.scfg = scfg
        self.eos_token_id = eos_token_id
        self.temperature = float(temperature)
        self.top_k = int(top_k)

        self.max_len = scfg.max_model_len or model_cfg.max_position_embeddings
        self.block_size = scfg.block_size
        self.max_blocks = blocks_for(self.max_len, self.block_size)
        self.num_slots = scfg.decode_slots
        self.num_blocks = (scfg.num_blocks
                           or scfg.decode_slots * self.max_blocks)
        self.num_pslots = scfg.prefill_slots or scfg.decode_slots
        self.prefill_rungs = prefill_rungs(self.num_pslots)
        self.pnum_blocks = (scfg.prefill_num_blocks
                            or self.num_pslots * self.max_blocks)

        # ---- placement: one sharding per pool, everything committed up
        # front (the colocated engine's variant discipline, doubled).
        # tp-sharded params pin both pools to the mesh; otherwise each
        # pool gets its own device, defaulting to distinct devices when
        # the backend has more than one.
        from jax.sharding import SingleDeviceSharding
        on_mesh = mesh_shardings(params)
        if on_mesh is not None:
            mesh_sh, kv_sh_d = on_mesh
            self._sh_p = self._sh_d = self._rep_sh = mesh_sh
            kv_sh_p = kv_sh_d
        else:
            devices = jax.devices()
            d_idx = scfg.decode_device if scfg.decode_device >= 0 else 0
            p_idx = (scfg.prefill_device if scfg.prefill_device >= 0
                     else (1 if len(devices) > 1 else 0))
            for name, idx in (("decode_device", d_idx),
                              ("prefill_device", p_idx)):
                if idx >= len(devices):
                    raise ValueError(
                        f"serve.{name} = {idx} but only {len(devices)} "
                        f"device(s) are visible")
            self._sh_d = SingleDeviceSharding(devices[d_idx])
            self._sh_p = SingleDeviceSharding(devices[p_idx])
            self._rep_sh = self._sh_d  # decode-side alias _decode_tick uses
            kv_sh_p, kv_sh_d = self._sh_p, self._sh_d

        # per-pool params (weight replication is the standard disagg
        # cost; with a shared mesh the "copy" is the same array — only
        # uncommitted leaves get committed, tp shardings stay untouched)
        put_p = partial(jax.device_put, device=self._sh_p)
        put_d = partial(jax.device_put, device=self._sh_d)
        if on_mesh is not None:
            self.params_p = self.params = jax.tree.map(
                lambda x: x if getattr(x, "committed", True)
                else jax.device_put(x, mesh_sh), params)
        else:
            self.params_p = jax.tree.map(put_p, params)
            self.params = (self.params_p if self._sh_p == self._sh_d
                           else jax.tree.map(put_d, params))

        cos, sin = model_rope_tables(model_cfg, max_len=self.max_len)
        self.cos, self.sin = put_d(cos), put_d(sin)
        self.cos_p, self.sin_p = put_p(cos), put_p(sin)
        self.base_key = put_d(jax.random.key(seed))
        self.base_key_p = put_p(jax.random.key(seed))

        # a cache a pool, kept as the colocated engine keeps its one:
        # `cache` / `_kv` / `_tables` are the decode pool's
        self.cache, self._kv, self._tables = new_cache(
            model_cfg, scfg, self.num_slots, self.num_blocks, self.max_len,
            kv_sh_d)
        self.cache_p, self._kv_p, self._tables_p = new_cache(
            model_cfg, scfg, self.num_pslots, self.pnum_blocks,
            self.max_len, kv_sh_p)
        self.pool = BlockPool(self.num_blocks)
        self.pool_p = BlockPool(self.pnum_blocks)
        self.sched = DisaggScheduler(self.num_pslots, self.num_slots,
                                     self.pool_p, self.pool,
                                     self.block_size, self.max_blocks)

        self._owns_telemetry = telemetry is None
        self.telemetry = telemetry or Telemetry(sinks=[])
        donate = jax.default_backend() != "cpu"
        self._decode_jit, self._prefill_jit = _get_jits(donate)
        self._gather_jit, self._scatter_jit = _get_handoff_jits(donate)

        self._t0 = time.perf_counter()
        self.engine_id = int(engine_id)
        self._decode_state: Optional[dict] = None
        self.results: list = []
        self.shed_results: list = []
        self.stats = {
            "decode_steps": 0, "decode_compiles": 0, "prefill_compiles": 0,
            "prefill_chunks": 0, "occupancy_sum": 0.0,
            "prefill_occupancy_sum": 0.0, "prefill_occupancy_steps": 0,
            "output_tokens": 0, "decode_stall_ticks_max": 0, "cancelled": 0,
            "handoffs": 0, "handoff_s": 0.0, "handoff_blocks": 0,
        }
        self._init_step_account()
        self._stall_streak = 0
        self._next_auto_id = 0
        self._warm_prefill()

        try:
            from picotron_tpu.analysis.variants import check_engine_feed

            self.variant_report = check_engine_feed(self)
            for f in self.variant_report.warnings():
                self.telemetry.emit("variant_hazard", category="serve",
                                    path=f.path, message=f.message)
        except Exception:  # analysis is best-effort at serve time
            self.variant_report = None

    # -- the prefill pool: its table mirror, and what points the
    # inherited `_prefill_tick` / `_warm_prefill` at it

    def _sync_ptable(self, pslot: int) -> None:
        self._write_rows(self.cache_p, self._tables_p, pslot,
                         self.sched.pslots[pslot])

    _PREFILL_PHASE = {"pool": "prefill"}

    # two pools, each maybe on a device of its own with a stream of its
    # own: the newest output says nothing of the other pool's work, so the
    # device is not probed, the account's dry seconds stay 0 (what is in
    # flight counts as fed) and a decode wait's `next_ready` reads -1
    _PROBED = False

    def _prefill_pool(self):
        return (self.sched.pslots, self.cache_p, self._tables_p,
                self._sh_p)

    def _run_prefill(self, feed):
        self._kv_p, toks, logits = self._prefill_jit(
            self.params_p, self._kv_p, *feed, self.base_key_p,
            self.cos_p, self.sin_p, cfg=self.cfg,
            temperature=self.temperature, top_k=self.top_k,
            cache_cls=type(self.cache_p))
        return toks, logits

    def _retire_prefilled(self, pslot: int, t: float) -> int:
        # first token already finishes it: retire straight from the
        # prefill pool, no handoff needed
        if not self.sched.should_retire(pslot, self.eos_token_id,
                                        pslot=True):
            return 0
        freed = self.sched.pslots[pslot].held_blocks
        st = self.sched.retire_prefill(pslot)
        self._sync_ptable(pslot)
        self._emit_retired(st, t)
        return freed

    # -- handoff -----------------------------------------------------------

    def _copy_blocks(self, src: list, dst: list) -> None:
        """Carry one sequence's K/V across the pool boundary: gather on
        the prefill placement, ONE explicit device_put of the staging
        buffer, sentinel-drop scatter on the decode placement. Fixed
        [max_blocks] index shapes keep both programs compile-once."""
        idx_src = np.zeros((self.max_blocks,), np.int32)
        idx_src[:len(src)] = src
        idx_dst = np.full((self.max_blocks,), self.num_blocks, np.int32)
        idx_dst[:len(dst)] = dst
        buf_k, buf_v = self._gather_jit(
            *self._kv_p, jax.device_put(idx_src, self._sh_p))
        buf_k, buf_v = jax.device_put((buf_k, buf_v), self._sh_d)
        self._kv = self._scatter_jit(
            *self._kv, buf_k, buf_v, jax.device_put(idx_dst, self._sh_d))

    # -- one engine iteration ---------------------------------------------

    def _step(self, now: float) -> bool:
        """Admit into the prefill pool; run ONE batched prefill chunk on
        the prefill placement; hand finished prefixes across the
        boundary; enqueue ONE decode dispatch on the decode placement,
        then wait for the one before it (the inherited `_decode_tick`).
        Returns whether any device work ran. (`ServeEngine.step` wraps it
        in the `serve.step` span; the leaf spans are the same, plus
        `serve.handoff`.)"""
        reg = self.telemetry.registry

        with self._span("serve.admit") as sp:
            admitted = self.sched.admit(now)
            for pslot, st in admitted:
                self._sync_ptable(pslot)
                self._note_admitted(st, now, reg)
            for st in self.sched.drain_shed():
                self._emit_shed(st, now)
            sp.set(admitted=len(admitted), queued=len(self.sched.queue))

        # ---- prefill chunks, compacted over the PREFILL pool's slots
        # (inherited — runs against `_prefill_pool`, on its placement)
        worked = self._prefill_tick(now, reg)
        # every step, with a dispatch or without (`prefill_ticks`, the
        # inherited count, has those with one)
        self.stats["prefill_occupancy_steps"] += 1
        self.stats["prefill_occupancy_sum"] += (
            sum(s is not None for s in self.sched.pslots)
            / self.num_pslots)

        # ---- handoff: oldest finished prefixes cross the boundary
        for pslot in self.sched.handoff_ready():
            got = self.sched.handoff(pslot)
            if got is None:
                break  # youngest everywhere — wait for decode capacity
            dslot, src, dst, preempted = got
            t0 = time.perf_counter()
            with self._span("serve.handoff", blocks=len(src),
                            id=self.sched.slots[dslot].req.id):
                self._copy_blocks(src, dst)
            dt = time.perf_counter() - t0
            dt -= min(self._drain_compile(), dt)
            self._sync_ptable(pslot)
            for p in preempted:
                self._sync_table(p)
            self._sync_table(dslot)
            self.stats["handoffs"] += 1
            self.stats["handoff_s"] += dt
            self.stats["handoff_blocks"] += len(src)
            self.telemetry.emit("phase", phase="handoff",
                                category="handoff", secs=dt,
                                id=self.sched.slots[dslot].req.id,
                                blocks=len(src))
            worked = True

        # ---- decode dispatch on the decode pool (inherited — operates
        # on the decode-side context and the scheduler's decode half)
        decode_ran = self._decode_tick(now, reg)
        worked = worked or decode_ran
        if decode_ran:
            self._stall_streak = 0
        elif self.sched.has_work():
            self._stall_streak += 1
            self.stats["decode_stall_ticks_max"] = max(
                self.stats["decode_stall_ticks_max"], self._stall_streak)
        return worked

    # -- summary -----------------------------------------------------------

    def _summary_dict(self, wall: float) -> dict:
        pticks = max(self.stats["prefill_occupancy_steps"], 1)
        return dict(
            super()._summary_dict(wall),
            disagg=True,
            prefill_slots=self.num_pslots,
            prefill_num_blocks=self.pnum_blocks,
            prefill_slot_occupancy=round(
                self.stats["prefill_occupancy_sum"] / pticks, 4),
            prefill_pool_peak_utilization=round(
                self.pool_p.peak_in_use / self.pnum_blocks, 4),
            handoffs=self.stats["handoffs"],
            handoff_s=round(self.stats["handoff_s"], 6),
            handoff_blocks=self.stats["handoff_blocks"],
        )
