"""Serving stack: continuous batching + paged KV cache on the decode
path — the "millions of users, heavy traffic" half of the north star.

- `paged_cache`: block-pool KV cache (fixed-size blocks, per-slot block
  tables, memory ~ blocks allocated, not batch x max_length) behind the
  same interface as the offline contiguous `generate.KVCache`; which
  kind of cache a model is served from (`init_serve_cache`) and every
  fact of its format.
- `scheduler`: FIFO admission into a fixed decode-slot batch, chunked
  prefill, youngest-first preemption with recompute, retirement — pure
  host logic.
- `engine`: the driver — two jitted device programs (one decode step, one
  prefill chunk; each compiled exactly once per serving lifetime) plus
  telemetry (queue_wait/prefill/decode in the GoodputLedger,
  TTFT/TPOT/per-token latency histograms, serve_request/serve_summary
  JSONL).
- `fleet`: `FleetSupervisor` — N engine replicas behind one queue, with
  failover re-dispatch (bit-identical continuations), deadline load
  shedding, hang detection, and graceful drain.
"""

from picotron_tpu.serve.engine import ServeEngine
from picotron_tpu.serve.fleet import FleetSupervisor
from picotron_tpu.serve.paged_cache import (
    BlockPool, PagedKVCache, init_paged_cache,
)
from picotron_tpu.serve.scheduler import Request, Scheduler, blocks_for

__all__ = [
    "BlockPool",
    "FleetSupervisor",
    "PagedKVCache",
    "Request",
    "Scheduler",
    "ServeEngine",
    "blocks_for",
    "init_paged_cache",
]
