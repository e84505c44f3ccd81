"""Continuous-batching inference serving (ROADMAP item 1).

Three layers:

* ``kv_cache.SlotKVCache`` — the device half: a fixed slot table of KV
  buffers sharded over the training mesh, one compiled single-token decode
  step for the whole table, and a compiled per-bucket prefill-insert so
  admission never recompiles decoding.  Chunk-resumable prefill
  (``begin_insert``/``prefill_chunk``) splits an admission into fixed
  token-budget chunks, and the optional block-granular prefix pool
  (``prefix_cache_blocks``) reuses cached shared-prompt KV with LRU
  eviction and hit/miss accounting.  ``kv_layout="paged"`` swaps the
  per-slot rows for ``PagedSlotKVCache``'s refcounted physical block
  pool (vLLM PagedAttention): prefix-pool hits alias blocks by pointer
  (zero KV bytes copied), first write into a shared block copies on
  write, decode/verify read through the block table in one fused Pallas
  kernel (``ops/paged_attention.py``, in-kernel int8 dequant), and pool
  pressure defers admission (``can_admit``) or raises
  ``BlockPoolExhausted``.
* ``scheduler.ContinuousBatcher`` — the host half: an iteration-level
  request scheduler (admit between decode steps, evict finished slots,
  with ``prefill_chunk`` at most one prompt chunk interleaved per decode
  iteration — Sarathi-Serve stall bounding) with MLPerf-style TTFT/ITL
  percentile accounting, a prefill/decode token split, and per-request
  trace spans through the existing observability stack.
* ``fleet.ReplicaSet`` — the fault-tolerance layer: N batcher replicas
  behind a least-loaded router, a request journal with an exactly-once
  emission fence, no-loss failover with bounded retry (resume
  re-prefills prompt + emitted prefix, greedy-exact), seeded fault
  injection (``FaultInjector``), and graceful drain + zero-downtime
  weight hot-swap (``SlotKVCache.swap_params``) that never drops the
  fleet below N−1 admitting replicas.  Round 18 makes the fleet
  heterogeneous, all default-off: ``roles`` disaggregates prefill from
  decode with a serialized KV handoff
  (``SlotKVCache.extract_handoff``/``restore_handoff``), so decode
  replicas never share an iteration with a long prompt;
  ``routing="affinity"`` lands shared-prefix traffic where its first
  prefix block is already warm; ``autoscale`` (``AutoscalePolicy``)
  drives the serving-replica count from arrived queue depth with
  ``serve_replica_seconds`` as the efficiency ledger; and
  ``parallel_lanes`` gives each replica its own virtual-time lane so
  fleet time overlaps replicas deterministically.

The harness's ``--serve`` flag runs a post-training serving window whose
summary lands in the run report, gated by ``analyze diff`` exactly like the
training metrics; ``benchmarks/drivers/serve.py`` drives the open-loop cells
of the benchmark through ``SlotKVCache`` and ``ContinuousBatcher`` directly.
"""

from distributed_tensorflow_tpu.serving.fleet import (  # noqa: F401
    AutoscalePolicy, CorruptionDetected, FaultInjector, FaultSpec,
    InjectedFault, ReplicaSet, RequestJournal, build_replica_kvs)
from distributed_tensorflow_tpu.serving.kv_cache import (  # noqa: F401
    BlockPoolExhausted, PagedSlotKVCache, SlotKVCache, SlotOverflow)
from distributed_tensorflow_tpu.serving.scheduler import (  # noqa: F401
    ContinuousBatcher, Request, RequestQueue, RequestResult, VirtualClock,
    WallClock)
