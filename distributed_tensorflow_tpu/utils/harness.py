"""L5 experiment harness: configure → train → time → evaluate → report.

Reproduces the reference's measurement window semantics: the clock runs from
"all workers ready" to "all workers finished" (start/end barriers, reference
server.py:76-79, 115-119) — here from just before the first training step to
`block_until_ready` after the last — and final accuracy is evaluated on the
full unsharded test set (reference server.py:179-180).  Compile time is
reported separately (`compile_s`): XLA traces/compiles on the first step,
which the wall-clock window includes, exactly as TF's first-batch graph
build was included in the reference's window.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np

from distributed_tensorflow_tpu import models as modellib
from distributed_tensorflow_tpu.data import loaders
from distributed_tensorflow_tpu.engines import create_engine
from distributed_tensorflow_tpu.engines.allreduce import Trainer
from distributed_tensorflow_tpu.parallel import mesh as meshlib
from distributed_tensorflow_tpu.utils.supervisor import ResultSink


@dataclasses.dataclass
class ExperimentConfig:
    """Everything the reference CLI configures (reference initializer.py:72-114),
    plus the TPU-native knobs."""

    engine: str = "sync"            # sync | async | allreduce | gossip | fsdp
    model: str = "mlp"
    dataset: str = "mnist"
    n_devices: int | None = None    # the reference's -n, as TPU device count
    batch_size: int = 32            # global batch (reference -b is per-worker;
                                    # global = b × n, see run() docstring)
    per_worker_batch: bool = True   # interpret batch_size per device like -b
    epochs: int = 1                 # reference fixes 1 (SURVEY.md §2.4(6))
    learning_rate: float = 1e-3
    lr_schedule: str = "constant"   # constant | cosine | linear (each with
                                    # optional linear warmup); horizon =
                                    # epochs × steps-per-epoch
    warmup_steps: int = 0           # linear LR warmup from 0 over this many
                                    # steps (0 disables)
    schedule_horizon_steps: int | None = None  # decay horizon override for
                                    # --lr-schedule; default = epochs ×
                                    # steps-per-epoch (steps_to_accuracy sets
                                    # it to max_steps: its loop runs far past
                                    # one epoch, and a horizon computed from
                                    # config.epochs would decay LR to 0 with
                                    # thousands of steps still to train)
    grad_accum: int = 1             # microbatches accumulated per optimizer
                                    # step (sync/allreduce engines): ~K× less
                                    # activation memory at identical math
    grad_compression: str = "none"  # cross-device gradient/parameter
                                    # exchange codec: none | bf16 | int8
                                    # (parallel/compression.py; pipeline
                                    # modes reject it)
    precision: str = "f32"          # end-to-end mixed-precision policy
                                    # (parallel/precision.py): f32 | bf16 |
                                    # bf16-f32master | fp16-f32master.
                                    # Storage + compute + grad-reduce
                                    # dtypes with an optional f32 master
                                    # copy inside the optimizer state;
                                    # 'f32' compiles the byte-identical
                                    # pre-policy programs.  Distinct from
                                    # `dtype` (the activation-only knob):
                                    # a non-f32 policy OWNS the model
                                    # dtype — see _resolve_precision
    grad_bucket_mb: float = 0.0     # >0: communication/compute overlap —
                                    # partition the grad pytree into
                                    # size-targeted buckets (reverse-
                                    # backward order, parallel/overlap.py)
                                    # whose independent collectives XLA's
                                    # latency-hiding scheduler runs behind
                                    # backward compute; the codec applies
                                    # per bucket.  0 (default): bitwise
                                    # pre-overlap programs.  ~4 is the
                                    # recommended size; pipeline modes
                                    # reject it like grad_compression
    weight_decay: float = 0.0       # >0: AdamW decoupled weight decay
    clip_norm: float = 0.0          # >0: clip gradients to this global norm
                                    # before the optimizer update
    sync_every: int = 10            # async engine's averaging period
    degree: int = 1                 # gossip neighbor degree (the -d flag)
    seed: int = 0
    eval_batch: int = 100           # reference's test batch (server.py:179)
    log_every: int = 50
    steps_per_call: int | None = None  # steady-state drain chunk: steps per
                                    # jitted lax.scan dispatch (None = auto —
                                    # 8, downshifting to 1 only for
                                    # steps-to-target runs; telemetry rides
                                    # the chunk — resolve_steps_per_call)
    prefetch: int = 2               # device-prefetch depth: batches staged
                                    # on the mesh ahead of the step loop so
                                    # transfer N+1 overlaps compute N
    result_path: str | None = None
    supervisor_address: str | None = None  # reference's -sa / port-4000 channel
    model_fn: Callable | None = None       # user plug-in override (README.md:12)
    dataset_fn: Callable | None = None
    target_accuracy: float | None = None   # e.g. 0.97 for steps-to-97%
    seq_parallel: int = 1                  # >1: shard sequences over a 'seq'
                                           # mesh axis (long-context mode)
    attention_impl: str = "ring"           # ring | ring_flash | ulysses |
                                           # ulysses_flash (when
                                           # seq_parallel>1); flash (Pallas
                                           # kernel) when seq_parallel==1
    positional: str = "learned"            # GPT positions: learned | rope
    kv_heads: int | None = None            # GPT GQA: K/V heads < query heads
    remat: bool = False                    # activation checkpointing: store
                                           # block inputs only, recompute in
                                           # backward (transformer models
                                           # and the GPipe tick body)
    model_args: dict | None = None         # extra model constructor fields
                                           # (--model-arg KEY=VALUE): sizes
                                           # like hidden/layers/heads for the
                                           # registered models; applied on
                                           # the DP and model-parallel
                                           # paths (pipeline stages size via
                                           # --pipeline-hidden instead)
    tensor_parallel: int = 1               # >1: shard weights over a 'model'
                                           # mesh axis (Megatron-style TP)
    pipeline_parallel: int = 1             # >1: shard stages over a 'pipe'
                                           # mesh axis (GPipe microbatching)
    microbatches: int = 4                  # pipeline microbatches per step
    pipeline_schedule: str = "gpipe"       # gpipe | 1f1b (bounded stash)
    expert_parallel: int = 1               # >1: shard MoE experts over an
                                           # 'expert' mesh axis
    num_experts: int = 8                   # MoE expert count
    aux_weight: float = 0.01               # MoE load-balance loss weight
    router_top_k: int = 1                  # MoE routing: 1 (Switch) | 2 (GShard)
    router_z_weight: float = 0.0           # MoE router z-loss weight
    pipeline_hidden: int = 128             # pipeline stage width
    checkpoint_dir: str | None = None      # enable TrainState checkpointing
    checkpoint_every: int = 0              # steps between checkpoints (0=end only)
    async_checkpoint: bool = True          # overlap checkpoint writes with
                                           # training (AsyncCheckpointManager:
                                           # device snapshot on the training
                                           # thread, Orbax write + retention
                                           # on a background writer); False =
                                           # the synchronous blocking save
    resume: bool = False                   # restore latest checkpoint first
    elastic_restore: bool = False          # mesh-shape-independent resume
                                           # (elastic/reshard.py): restore
                                           # the latest checkpoint onto
                                           # THIS run's mesh whatever mesh
                                           # wrote it (GSPMD family), with
                                           # exactly-once data resume from
                                           # the checkpoint's data state
                                           # and preemption accounting
                                           # (preemption_lost_s /
                                           # resume_replay_steps in the
                                           # run report)
    max_steps_per_lease: int = 0           # >0: graceful lease drain
                                           # (elastic/lease.py) — stop at
                                           # the first chunk boundary at/
                                           # after N steps this run, write
                                           # the final checkpoint (data
                                           # state included) and return a
                                           # `preempted` result instead of
                                           # training on.  Checkpointed
                                           # runs also arm a SIGTERM
                                           # preemption-notice handler
                                           # that triggers the same drain
    metrics_path: str | None = None        # per-step metrics JSONL (async
                                           # crash-durable sink; rides the
                                           # chunked drain — no downshift)
    trace_path: str | None = None          # structured span/event JSONL
                                           # timeline (observability/trace)
    timeline: bool = False                 # periodic gauge sampler (queue
                                           # depth, KV blocks, replica load)
                                           # + XLA program ledger (per-
                                           # program memory_analysis,
                                           # compile wall-time).  Host-side
                                           # only; off compiles the exact
                                           # pre-timeline program set
    timeline_interval: float = 0.05        # min seconds between samples
                                           # per gauge group (throttle —
                                           # sampling happens at existing
                                           # iteration boundaries, never
                                           # on a timer thread)
    roofline: bool = False                 # analytic FLOPs/bytes cost
                                           # model + MFU/MBU attribution
                                           # (observability/roofline) on
                                           # the fit result, the serve
                                           # summary and the run report;
                                           # arms the XLA program ledger
                                           # for cost_analysis capture.
                                           # Host-side only; off keeps the
                                           # program + key sets
                                           # byte-identical (parity pin)
    profile_dir: str | None = None         # XLA profiler trace output
    dtype: str = "float32"                 # model compute dtype; 'bfloat16'
                                           # enables mixed precision (params
                                           # stay f32, activations/matmuls
                                           # run bf16 on the MXU)
    watchdog_timeout: float = 0.0          # >0: stall detector around the
                                           # step loop (utils/failure.py)
    watchdog_abort: bool = False           # on stall: report, then exit(75)
                                           # for an external relaunch with
                                           # resume (in-process recovery of
                                           # a wedged XLA runtime is not
                                           # possible)
    nan_guard: bool = True                 # divergence check at log cadence
                                           # (legacy alias: --health on
                                           # subsumes it with the per-step
                                           # anomaly policy)
    health: str = "off"                    # 'on': per-step numeric-health
                                           # stats on device inside the
                                           # scan (observability/health.py)
                                           # — zero downshift, stacked like
                                           # metrics; 'off' compiles the
                                           # exact pre-health program
    on_anomaly: str = "warn"               # health anomaly policy: 'warn'
                                           # records structured anomaly
                                           # events; 'halt' raises at the
                                           # offending step
    max_restarts: int = 0                  # >0: checkpoint-resume crash
                                           # recovery (run_with_recovery)
    sample_tokens: int = 0                 # >0: after training an LM, decode
                                           # this many tokens per prompt from
                                           # the final params (KV-cache
                                           # sampler, models/gpt.py generate)
                                           # and record them in the summary
    sample_prompt_len: int = 8             # prompt tokens taken from the
                                           # test split per sampled row
    serve_requests: int = 0                # >0: after training an LM, run a
                                           # continuous-batching serving
                                           # window of this many requests
                                           # (serving/: slot KV cache +
                                           # in-flight scheduler) and carry
                                           # its TTFT/ITL percentiles +
                                           # requests/sec/chip in the
                                           # summary and run report —
                                           # serving gets the same
                                           # trajectory and `analyze diff`
                                           # gating training has
    serve_slots: int = 4                   # KV slot table size (requests in
                                           # flight at once; shards over
                                           # the 'data' axis when it
                                           # divides)
    serve_max_new: int = 16                # tokens generated per request
    serve_prompt_len: int = 8              # prompt tokens taken from the
                                           # test split per request
    serve_kv_dtype: str | None = None      # --serve KV-table storage dtype
                                           # ('bfloat16' halves KV memory →
                                           # double the slots per chip;
                                           # 'int8' halves bf16's payload
                                           # again — int8 K/V + one f32
                                           # max-abs scale per written
                                           # vector, tolerance-based token
                                           # parity vs the bf16 oracle);
                                           # None: the model's dtype
    serve_prefill_chunk: int = 0           # >0: chunked prefill token
                                           # budget (Sarathi-Serve) — at
                                           # most one ≤N-token prompt chunk
                                           # rides each decode iteration,
                                           # so a long admission cannot
                                           # stall live slots for more
                                           # than a chunk; 0 = monolithic
                                           # (pre-round-10 programs)
    serve_prefix_cache: int = 0            # >0: prefix-cache pool capacity
                                           # in KV blocks (vLLM-style
                                           # block reuse; LRU past the
                                           # bound); admission copies the
                                           # longest cached prompt prefix
                                           # into the slot and prefills
                                           # only the uncached tail
    serve_prefix_block: int = 16           # tokens per prefix-cache block
                                           # (reuse granularity)
    serve_shared_prefix: int = 0           # >0: prepend a fixed synthetic
                                           # N-token system prompt to
                                           # every request (the shared-
                                           # prefix traffic shape;
                                           # deterministic from seed)
    serve_slo_ttft: float = 2.0            # TTFT SLO target in seconds:
                                           # a request is goodput only
                                           # when arrival→first-token
                                           # (queue wait included) meets
                                           # this AND the ITL target
    serve_slo_itl: float = 0.5             # ITL SLO target in seconds,
                                           # judged at each request's own
                                           # p99 inter-token gap
    serve_queue_cap: int = 0               # >0: bounded admission — the
                                           # arrived-but-unadmitted
                                           # backlog is capped; excess
                                           # sheds with 429 accounting
                                           # (shed_requests/
                                           # serve_shed_rate + a
                                           # structured `overload` trace
                                           # event) so overload degrades
                                           # to bounded queue wait, not
                                           # unbounded TTFT.  0 = admit
                                           # everything (PR 10 behavior)
    serve_draft_config: str | None = None  # speculative decoding: 'self'
                                           # (draft = the served model —
                                           # accept rate 1, the mechanism
                                           # check) or 'k=v,...' GPT size
                                           # overrides (hidden/layers/
                                           # heads/ffn; vocab + max_len
                                           # inherited, fresh-initialized
                                           # from --seed).  None = off:
                                           # the pre-round-14 programs,
                                           # byte-identical
    serve_draft_k: int = 4                 # draft tokens proposed per
                                           # verify round (k draft steps →
                                           # one batched k+1-position
                                           # target verify; greedy
                                           # acceptance keeps the stream
                                           # bitwise non-speculative)
    serve_replicas: int = 1                # >1: serve through a ReplicaSet
                                           # fleet (serving/fleet.py) —
                                           # N batcher replicas, each with
                                           # its own serve_slots-slot KV
                                           # table, behind a least-loaded
                                           # router with journaled
                                           # no-loss failover; the serve
                                           # section gains `serve_fleet`
                                           # + the failover gate keys
    serve_fault_spec: str | None = None    # seeded fault injection into
                                           # the fleet (FaultInjector
                                           # grammar: 'crash:replica=0,
                                           # iter=3;stall:replica=1,
                                           # iter=2,stall_s=1' ...) — the
                                           # chaos-test substrate; forces
                                           # the fleet path even at
                                           # serve_replicas == 1
    serve_watchdog_s: float = 0.0          # >0: fleet supervisor watchdog
                                           # — a replica busy with no
                                           # token progress for this many
                                           # seconds is failed over (its
                                           # zombie fenced).  Set it above
                                           # worst-case first-program XLA
                                           # compile; 0 = off (stall
                                           # faults then just sleep).
                                           # Fleet mode only
    serve_hot_swap: bool = False           # zero-downtime weight hot-swap
                                           # drill: after half the window
                                           # completes, drain + re-install
                                           # the served params replica-by-
                                           # replica (never below N-1
                                           # admitting) — swap_generations
                                           # >= 1 proves the mechanism,
                                           # greedy tokens unchanged (the
                                           # swapped-in weights are the
                                           # same trained params)
    serve_kv_layout: str = "monolithic"    # --serve-kv-layout paged: the KV
                                           # table becomes a refcounted
                                           # physical block pool + per-slot
                                           # block tables (PagedSlotKVCache
                                           # — vLLM PagedAttention): prefix
                                           # hits alias blocks zero-copy,
                                           # CoW isolates writers, decode
                                           # reads fused through the Pallas
                                           # paged kernel (tolerance-based
                                           # token parity, the int8
                                           # precedent).  'monolithic'
                                           # keeps the per-slot rows and a
                                           # byte-identical program set
    serve_paged_block: int = 0             # tokens per physical KV block
                                           # under paged (0: inherit
                                           # serve_prefix_block — the two
                                           # MUST agree when the prefix
                                           # pool is on: hits alias
                                           # physical blocks by pointer)
    serve_paged_blocks: int = 0            # physical block-pool capacity
                                           # under paged (0: auto-size so
                                           # slots*max_len + prefix pool
                                           # always fit — never exhausts);
                                           # explicit smaller pools defer
                                           # admissions when the free list
                                           # cannot cover a request's
                                           # worst-case block need
    serve_disaggregate: str | None = None  # 'P:D': disaggregated fleet —
                                           # P prefill replicas (admission
                                           # + chunked prefill, then a
                                           # serialized KV handoff) and D
                                           # decode replicas (never share
                                           # an iteration with a long
                                           # prompt).  Overrides
                                           # serve_replicas (P+D total);
                                           # handoff time is charged
                                           # inside TTFT.  Decode-side
                                           # tables carry no prefix pool
                                           # (pool warmth lives where
                                           # prefill runs).  None = the
                                           # homogeneous fleet, summary-
                                           # key-identical to round 17
    serve_routing: str = "least-loaded"    # fleet request routing:
                                           # 'least-loaded' (PR 13) or
                                           # 'affinity' — key on the
                                           # chained SHA-256 digest of the
                                           # first prefix block and land
                                           # shared-prefix traffic where
                                           # that block is already warm;
                                           # adds serve_fleet_prefix_
                                           # hit_rate to the summary
    serve_autoscale: str | None = None     # 'MIN:MAX': queue-driven
                                           # replica autoscaling — start
                                           # at MIN serving replicas,
                                           # scale toward MAX on arrived-
                                           # backlog high watermark, drain
                                           # an idle replica back down;
                                           # serve_replica_seconds becomes
                                           # the efficiency ledger.  With
                                           # serve_disaggregate the policy
                                           # drives each role pool
                                           # independently (range clamped
                                           # per pool) and the ledger
                                           # splits per role
    serve_multi_step: int | None = None    # k: fuse k decode iterations
                                           # into ONE device dispatch
                                           # (lax.scan with on-device
                                           # token feedback + EOS/budget
                                           # deactivation) and pipeline
                                           # round i+1's dispatch ahead of
                                           # round i's drain.  Greedy
                                           # streams stay bitwise equal to
                                           # k=1; admissions wait at most
                                           # k fused iterations.  Adds
                                           # serve_dispatches and
                                           # serve_host_gap_s to the
                                           # summary.  None = the legacy
                                           # per-iteration loop, program-
                                           # and key-set identical to
                                           # round 19


def resolve_compile_cache() -> str | None:
    """Place XLA's persistent compilation cache and return its directory.

    The entry points (``cli.main``, ``chip_smoke.py``, the examples) call
    this before their first compile; package import and
    ``run()`` do not, so a library caller gets a cache only if the
    environment asks for one.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it itself and no directory is set in code.  Otherwise the
    cache lives in one fixed directory of the checkout, ``.jax_cache``
    beside the package: the directory is part of the cache key, so one
    that moved between runs would never hit.  jax's minimum-compile-time
    and entry-size gates are dropped either way, so every program of a run
    is a hit on the next one.

    A process held to the CPU (``jax_platforms == "cpu"``, the setting of
    the tests and of the development recipe) gets no directory of its own
    and returns None: nothing is measured there, and XLA:CPU's loader logs
    a multi-kilobyte feature-mismatch error for every entry it reads back.
    The backend is not touched to find this out — ``cli.main`` may still
    have ``jax.distributed.initialize`` ahead of it."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if jax.config.jax_platforms == "cpu":
        return None
    path = Path(__file__).resolve().parents[2] / ".jax_cache"
    path.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)


# XLA knobs that let the TPU compiler actually HIDE the bucketed gradient
# collectives parallel/overlap.py makes schedulable: the latency-hiding
# scheduler plus async-collective fusion (the production TPU overlap set).
# They ride LIBTPU_INIT_ARGS — read only by libtpu, so setting them is
# inert on CPU/GPU containers (an unknown flag in XLA_FLAGS would abort
# backend init; LIBTPU_INIT_ARGS is the safe carrier).  The effective
# values are recorded in the run report's `environment` section
# (observability/report.runtime_environment) so a run's numbers stay
# attributable across containers.
OVERLAP_XLA_TPU_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
)


def enable_overlap_flags(env=None) -> str:
    """Append the communication/compute-overlap XLA flags to
    ``LIBTPU_INIT_ARGS`` (idempotent: a flag whose key is already present
    — e.g. user-overridden to false — is left alone).  Must run BEFORE
    backend initialization; ``run()`` calls it when
    ``--grad-bucket-mb`` > 0.  Returns the resulting value, which the run
    report records for reproducibility."""
    env = os.environ if env is None else env
    parts = env.get("LIBTPU_INIT_ARGS", "").split()
    have = {p.split("=", 1)[0] for p in parts}
    for flag in OVERLAP_XLA_TPU_FLAGS:
        if flag.split("=", 1)[0] not in have:
            parts.append(flag)
    env["LIBTPU_INIT_ARGS"] = " ".join(parts)
    return env["LIBTPU_INIT_ARGS"]


@dataclasses.dataclass
class _Experiment:
    """Resolved experiment: mesh, data, model, engine, global batch.

    ``name`` is the summary's engine label, set by the _setup_* function
    that chose the engine — the ONE place that knows which mode resolved
    (run() used to re-derive it from the config flags in a parallel
    if/elif ladder, which drifted: ep×sp runs were reported as
    'seq_parallel[ring]' until round 5)."""

    mesh: Any
    n: int
    train_ds: Any
    test_ds: Any
    engine: Any
    global_batch: int
    name: str


def _reject_flash_under_sp(config: ExperimentConfig) -> None:
    """Every seq-sharded mode shares this rejection so the option list
    cannot drift between modes (the seq-capable set is
    ring / ring_flash / ulysses / ulysses_flash; 'flash' is the
    single-device Pallas kernel)."""
    if config.attention_impl == "flash":
        raise ValueError(
            "--attention flash is the single-device Pallas kernel; with "
            "--seq-parallel use ring, ring_flash, ulysses or ulysses_flash")


def _is_pipeline(engine) -> bool:
    """Pipeline engines have no monolithic ``model`` — params are stacked
    per 'pipe' stage — so sampling/eval paths branch on the engine type."""
    from distributed_tensorflow_tpu.engines.pipeline import PipelineEngine

    return isinstance(engine, PipelineEngine)


def _validate_grad_bucket(config: ExperimentConfig) -> None:
    """Reject bad --grad-bucket-mb configs.  Called from _setup AND from
    run() BEFORE enable_overlap_flags() — the overlap flags mutate
    process-global LIBTPU_INIT_ARGS, so a config that _setup would reject
    must never get to mutate the environment of later runs in the same
    process."""
    if not config.grad_bucket_mb:
        return
    if config.grad_bucket_mb < 0:
        raise ValueError(
            f"--grad-bucket-mb must be >= 0 (0 disables bucketing), "
            f"got {config.grad_bucket_mb}")
    if config.pipeline_parallel > 1:
        # same named rejection as --grad-compression: the pipeline
        # schedules own per-stage params inside a manual shard_map
        # axis — there is no single post-AD gradient tree to bucket
        raise ValueError(
            "--grad-bucket-mb is implemented for the data-parallel "
            "and GSPMD engines (sync/async/allreduce/gossip/fsdp, -tp, "
            "-sp, -ep and their composites); the pipeline schedules "
            "(-pp) are not supported — drop the flag or train "
            "without -pp")


def _resolve_precision(config: ExperimentConfig) -> ExperimentConfig:
    """Validate ``--precision`` and resolve the model dtype it implies.

    The policy owns end-to-end precision (storage + compute + grad
    reduce), so with a non-f32 policy the model's compute dtype FOLLOWS
    the policy: ``--dtype`` left at its float32 default is overridden to
    the policy's compute dtype; an explicit matching ``--dtype`` is
    fine; a CONFLICTING one is rejected (silently computing f32 over
    bf16-stored params would promote every matmul back to f32 and hand
    the user neither win).  ``--precision f32`` leaves ``--dtype``'s
    activation-only behavior exactly as before (MIGRATING.md).  Pipeline
    modes reject non-f32 policies with the same named reason as
    --grad-compression: stage params live per-'pipe' inside a manual
    shard_map axis with their own optimizer handling."""
    from distributed_tensorflow_tpu import models as modellib
    from distributed_tensorflow_tpu.parallel import precision as precisionlib

    pol = precisionlib.make_policy(config.precision)  # typo → full menu
    if not pol.active:
        return config
    if config.pipeline_parallel > 1:
        raise ValueError(
            "--precision is implemented for the data-parallel and GSPMD "
            "engines (sync/async/allreduce/gossip/fsdp, -tp, -sp, -ep and "
            "their composites); the pipeline schedules (-pp) are not "
            "supported — drop the flag or train without -pp")
    compute = modellib.resolve_dtype(pol.compute_dtype)
    asked = modellib.resolve_dtype(config.dtype)
    if asked is not modellib.resolve_dtype("float32") and asked is not compute:
        raise ValueError(
            f"--dtype {config.dtype} conflicts with --precision "
            f"{pol.name} (compute dtype {jnp_name(compute)}): a non-f32 "
            f"policy owns the model dtype — drop --dtype or make them "
            f"agree")
    return dataclasses.replace(config, dtype=str(np.dtype(compute)))


def jnp_name(dtype) -> str:
    import jax.numpy as jnp

    return jnp.dtype(dtype).name


def _setup(config: ExperimentConfig) -> _Experiment:
    config = _resolve_precision(config)
    # the z-loss is applied by the MoE-aware engines: the -ep paths, and
    # the tp×sp composite when the model carries MoE blocks
    # (--model-arg moe_experts=N)
    composite_moe = (config.tensor_parallel > 1 and config.seq_parallel > 1
                     and bool((config.model_args or {}).get("moe_experts")))
    if (config.router_z_weight and config.expert_parallel <= 1
            and not composite_moe):
        raise ValueError(
            "--router-z-weight is applied by the MoE-aware engines; "
            "without --expert-parallel > 1 (or a tp×sp composite with "
            "--model-arg moe_experts=N) it would be silently ignored")
    if config.grad_compression != "none":
        from distributed_tensorflow_tpu.parallel import compression

        # fail on typos here, not deep inside an engine constructor
        compression.make_codec(config.grad_compression)
        if config.pipeline_parallel > 1:
            # named rejection, not a silent gap: the pipeline schedules'
            # data-axis gradient reduce rides the manual (data, pipe)
            # shard_map with per-stage param ownership — there is no
            # single post-AD gradient tree to run the codec over, and
            # silently training uncompressed would misreport the wire
            # bytes the flag promises to shrink
            raise ValueError(
                "--grad-compression is implemented for the data-parallel "
                "and GSPMD engines (sync/async/allreduce/gossip/fsdp, -tp, "
                "-sp, -ep and their composites); the pipeline schedules "
                "(-pp) are not supported yet — drop the flag or train "
                "without -pp")
    _validate_grad_bucket(config)
    if config.sample_tokens:
        # pipeline runs sample too (sequential-forward decode over the
        # pipe-stacked stages, engines/pipeline.py generate); family/shape
        # specifics are checked post-setup in _validate_sampling
        if config.model_fn is None and config.model not in _LM_MODELS:
            raise ValueError(
                f"--sample decodes autoregressively and needs a causal LM "
                f"({'/'.join(_LM_MODELS)}), got --model {config.model}")
    multi = [f for f in ("seq_parallel", "tensor_parallel", "pipeline_parallel",
                         "expert_parallel")
             if getattr(config, f) > 1]
    if len(multi) > 1:
        combos = {
            frozenset({"seq_parallel", "tensor_parallel"}): _setup_composite,
            frozenset({"pipeline_parallel", "tensor_parallel"}):
                _setup_pipeline_tp,
            frozenset({"expert_parallel", "tensor_parallel"}): _setup_expert_tp,
            frozenset({"pipeline_parallel", "seq_parallel"}):
                _setup_pipeline_sp,
            frozenset({"pipeline_parallel", "tensor_parallel",
                       "seq_parallel"}): _setup_pipeline_tp_sp,
            frozenset({"expert_parallel", "seq_parallel"}): _setup_expert_sp,
            frozenset({"expert_parallel", "tensor_parallel",
                       "seq_parallel"}): _setup_expert_tp_sp,
            frozenset({"pipeline_parallel", "expert_parallel"}):
                _setup_pipeline_ep,
            frozenset({"pipeline_parallel", "expert_parallel",
                       "tensor_parallel"}): _setup_pipeline_ep_tp,
            frozenset({"pipeline_parallel", "expert_parallel",
                       "seq_parallel"}): _setup_pipeline_ep_sp,
            frozenset({"pipeline_parallel", "expert_parallel",
                       "tensor_parallel", "seq_parallel"}):
                _setup_pipeline_ep_tp_sp,
        }
        # every >= 2-factor subset of the four model-parallel axes is
        # composable (6 pairs, 4 triples, the 5-D quad) — the dict is
        # total over frozenset(multi).  The one remaining rejection,
        # pipeline × the fsdp ENGINE, is enforced where the mesh splits
        # (_split_mesh) with its reason: ZeRO shards state over 'data',
        # a manual axis in the pipeline shard_map, so the gather-per-use
        # all-gathers cannot be GSPMD-inserted mid-schedule.
        return combos[frozenset(multi)](config)
    if config.seq_parallel > 1:
        return _setup_seq_parallel(config)
    if config.tensor_parallel > 1:
        if config.engine == "fsdp":
            return _setup_fsdp_tp(config)
        return _setup_tensor_parallel(config)
    if config.pipeline_parallel > 1:
        return _setup_pipeline_parallel(config)
    if config.expert_parallel > 1:
        return _setup_expert_parallel(config)
    mesh = meshlib.create_mesh(config.n_devices)
    n = mesh.shape[meshlib.DATA_AXIS]
    if (config.engine == "fsdp" and n > 1 and config.attention_impl in (
            "flash", "ring_flash", "ulysses_flash")):
        # the fsdp engine is one GSPMD program over the mesh, and Mosaic
        # refuses to be partitioned by it ("Mosaic kernels cannot be
        # automatically partitioned. Please wrap the call in a shard_map",
        # four-chip run, PR 21); the sync/allreduce engines run the model
        # inside shard_map, where the kernel compiles
        raise ValueError(
            f"--attention {config.attention_impl} runs the Pallas flash "
            f"kernel, which the fsdp engine cannot hold on more than one "
            f"device: its step is one GSPMD-partitioned program and a "
            f"Mosaic kernel cannot be partitioned automatically.  Train "
            f"with the sync/allreduce engine (the model runs inside "
            f"shard_map there), or drop --attention under -ds fsdp")

    train_ds, test_ds = _load_data(config)
    if config.model in _LM_MODELS and config.model_fn is None:
        # fail with the dataset hint, not a cryptic Embed trace error
        _require_token_data(train_ds, config, f"engine '{config.engine}'")
    model = _resolve_model(config, train_ds.num_classes)

    # reference -b is the PER-WORKER batch (reference client.py:64 feeds each
    # worker's shard with batch_size b); global batch = b × n matches its
    # aggregate examples-per-round
    global_batch = _global_batch(config, n)

    engine_kw: dict[str, Any] = dict(
        mesh=mesh, learning_rate=config.learning_rate,
        optimizer=_make_optimizer(config, train_ds, global_batch),
        grad_compression=config.grad_compression,
        grad_bucket_mb=config.grad_bucket_mb,
        precision=config.precision)
    if config.engine == "async":
        engine_kw["sync_every"] = config.sync_every
    elif config.engine == "gossip":
        engine_kw["degree"] = config.degree
    if config.grad_accum > 1:
        if config.engine not in ("sync", "allreduce", "fsdp"):
            raise ValueError(
                f"grad_accum is implemented by the sync/allreduce/fsdp "
                f"engines (got engine='{config.engine}')")
        if (global_batch // n) % config.grad_accum:
            raise ValueError(
                f"per-device batch {global_batch // n} not divisible by "
                f"grad_accum {config.grad_accum}")
    if config.engine in ("sync", "allreduce", "fsdp"):
        engine_kw["grad_accum"] = config.grad_accum
    engine = create_engine(config.engine, model, **engine_kw)
    return _Experiment(mesh=mesh, n=n, train_ds=train_ds, test_ds=test_ds,
                       engine=engine, global_batch=global_batch,
                       name=config.engine)


def make_lr_schedule(config: ExperimentConfig, total_steps: int):
    """Learning-rate schedule from --lr-schedule/--warmup-steps, or None for
    the default (constant, no warmup).  The decay horizon is the full run:
    ``total_steps`` = epochs × steps-per-epoch.  No reference counterpart
    (the reference's Adam runs at its constructor default forever, reference
    server.py:52-55) — schedules are table stakes for the transformer-scale
    models this framework adds."""
    import optax

    lr, warm = config.learning_rate, max(config.warmup_steps, 0)
    if config.lr_schedule not in ("constant", "cosine", "linear"):
        raise ValueError(
            f"unknown lr_schedule '{config.lr_schedule}'; "
            f"known: constant, cosine, linear")
    if config.lr_schedule == "constant" and warm == 0:
        return None
    total = max(total_steps, warm + 1)
    decay = max(total - warm, 1)
    if config.lr_schedule == "cosine":
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0 if warm else lr, peak_value=lr,
            warmup_steps=warm, decay_steps=total)
    if config.lr_schedule == "linear":
        main = optax.linear_schedule(lr, 0.0, decay)
    else:  # constant after warmup
        main = optax.constant_schedule(lr)
    if warm == 0:
        return main
    return optax.join_schedules(
        [optax.linear_schedule(0.0, lr, warm), main], [warm])


def _make_optimizer(config: ExperimentConfig, train_ds,
                    global_batch: int):
    """Adam over the run's LR schedule, or None → the engine's stock
    adam(learning_rate).

    The horizon counts GLOBAL steps: a process-sharded dataset (multi-host,
    Dataset.process_shard_of) holds 1/P of the examples but every process
    still takes the same global-batch steps over the full set — scaling by
    P keeps the decay reaching 0 at the run's true end, not P× early."""
    import optax

    if config.schedule_horizon_steps is not None:
        total = config.schedule_horizon_steps
    else:
        shard = getattr(train_ds, "process_shard", None)
        n_global = len(train_ds) * (shard[1] if shard else 1)
        total = config.epochs * max(n_global // max(global_batch, 1), 1)
    sched = make_lr_schedule(config, total)
    if sched is None and not config.weight_decay and not config.clip_norm:
        return None
    lr = sched if sched is not None else config.learning_rate
    if config.weight_decay:
        tx = optax.adamw(lr, weight_decay=config.weight_decay,
                         mask=_decay_mask)
    else:
        tx = optax.adam(lr)
    if config.clip_norm:
        tx = optax.chain(optax.clip_by_global_norm(config.clip_norm), tx)
    return tx


def _decay_mask(params):
    """Standard transformer decay mask: weight-decay matmul kernels only —
    biases and LayerNorm scales (ndim < 2) and embedding tables (flax names
    the param 'embedding') drift toward zero under decoupled decay with no
    regularization benefit, measurably hurting convergence."""
    def keep(path, p):
        names = {getattr(k, "key", None) for k in path}
        return p.ndim >= 2 and "embedding" not in names

    return jax.tree_util.tree_map_with_path(keep, params)


def _lm_model_kw(config: ExperimentConfig) -> dict[str, Any]:
    """GPT-only model kwargs (--positional/--kv-heads) — only passed when
    non-default so non-LM models never see unknown fields."""
    kw: dict[str, Any] = {}
    if config.model in _LM_MODELS:
        if config.positional != "learned":
            kw["positional"] = config.positional
        if config.kv_heads is not None:
            kw["kv_heads"] = config.kv_heads
    return kw


def _resolve_model(config: ExperimentConfig, num_classes: int):
    """Model for the data-parallel engines: plug-in ``model_fn`` wins (and
    owns its dtype — warn if --dtype would be silently ignored); registered
    models get ``dtype`` only if their Module accepts it."""
    if config.model_fn is not None:
        if (modellib.resolve_dtype(config.dtype)
                is not modellib.resolve_dtype("float32")):
            import warnings

            warnings.warn(
                f"--dtype {config.dtype} is ignored for plug-in model_fn "
                f"models; the model_fn owns its dtype", stacklevel=2)
        return config.model_fn()
    kw = dict(config.model_args or {})
    forced = _lm_model_kw(config)
    if config.remat:
        if config.model not in _SEQUENCE_MODELS:
            raise ValueError(
                f"--remat checkpoints transformer blocks; --model "
                f"{config.model} has none (sequence models: "
                f"{'/'.join(_SEQUENCE_MODELS)})")
        forced["remat"] = True
    if config.model in ("moe", "moe_mlp"):
        # router_top_k is a MODEL knob — it applies under any engine (a
        # -ep 1 run still routes).  router_z_weight is an ENGINE knob that
        # only the expert-parallel engine consumes; reject it elsewhere
        # instead of silently ignoring it (checked in _setup)
        forced["router_top_k"] = config.router_top_k
    _check_reserved_model_args(
        config, {"num_classes", "dtype", *forced},
        f"--model {config.model}")
    kw.update(forced)
    if config.model in _SEQUENCE_MODELS and config.attention_impl in (
            "flash", "ring_flash", "ulysses_flash"):
        # the Pallas kernel is valid without a seq axis (single-device
        # blockwise attention); ring_flash degrades to it honestly — the
        # user asked for the flash kernel, and at sp==1 the ring schedule
        # is a no-op around it.  Plain ring/ulysses (ring is the flag
        # default) stay ignored here because they require the seq mesh
        # the DP path doesn't build.
        kw["attention_impl"] = "flash"
    try:
        return modellib.create_model(config.model, num_classes=num_classes,
                                     dtype=config.dtype, **kw)
    except TypeError as dtype_err:
        # user-register()ed Modules may not declare a dtype field; probe by
        # retrying WITHOUT dtype but WITH the remaining kwargs — a typo'd
        # --model-arg key must still fail loudly (the probe once dropped
        # ALL kwargs, which silently trained the default-size model), and
        # if the kwarg-preserving probe also fails the original error
        # surfaces, not a misleading dtype message
        try:
            model = modellib.create_model(config.model,
                                          num_classes=num_classes, **kw)
        except TypeError:
            raise dtype_err
        if (modellib.resolve_dtype(config.dtype)
                is not modellib.resolve_dtype("float32")):
            raise ValueError(
                f"model '{config.model}' does not accept a dtype field; "
                f"drop --dtype {config.dtype} or add dtype support to the "
                f"model") from dtype_err
        return model


def _load_data(config: ExperimentConfig):
    """(train, test) datasets.  On a multi-process pod the TRAIN split is
    sharded by process (reference initializer.py:44's per-worker `.shard`,
    previously honored only in spirit): each host materializes ~1/P of the
    train set and the Trainer assembles global batches from local rows.
    Eval stays unsharded — every process computes the same full-test-set
    numbers, matching the reference's single server-side eval.  User
    ``dataset_fn`` plug-ins own their sharding: call
    ``Dataset.process_shard_of(process_count, process_index)`` (or
    `data.make_dataset_fn`'s ``shard=True, process=True``) to opt in to
    per-process global-batch assembly."""
    if config.dataset_fn is not None:
        return (config.dataset_fn(config.batch_size, type="train"),
                config.dataset_fn(config.eval_batch, type="test"))
    train = loaders.load_dataset(config.dataset, split="train")
    test = loaders.load_dataset(config.dataset, split="test")
    n_proc = jax.process_count()
    if n_proc > 1:
        train = train.process_shard_of(n_proc, jax.process_index())
    return train, test


def _global_batch(config: ExperimentConfig, dp: int) -> int:
    return max(config.batch_size * dp if config.per_worker_batch
               else config.batch_size, dp)


def _split_mesh(config: ExperimentConfig, factor: int, factor_name: str,
                second_axis: str, *more: tuple[int, str],
                engines: tuple[str, ...] = ("sync", "allreduce"),
                grad_accum_ok: bool = False):
    """(data, <second_axis>, ...) mesh: the named factors take their axes,
    the remaining devices shard data.  Shared by every model-parallel setup.

    ``engines`` names the engine semantics the mode supports (fsdp×tp passes
    ('fsdp',)); ``grad_accum_ok`` marks modes whose engine implements
    K-microbatch accumulation (the GSPMD engines — tp, fsdp)."""
    import jax as _jax

    if config.engine not in engines:
        why = ""
        if config.engine == "fsdp" and "pipeline" in factor_name:
            # named rejection, not a silent gap (VERDICT r4 #5): the
            # schedules run manual over 'pipe' with per-stage param
            # ownership; ZeRO's GSPMD gather-per-use would have to cross
            # that manual axis mid-schedule, which shard_map forbids
            why = (" (fsdp × pipeline is rejected by design: the pipeline "
                   "schedules own params per 'pipe' stage inside a manual "
                   "shard_map axis, so ZeRO's gather-per-use collectives "
                   "cannot cross it; shard the optimizer inside each stage "
                   "with --engine sync + --grad-accum instead)")
        raise ValueError(
            f"{factor_name} supports {'/'.join(engines)} semantics only, "
            f"got engine='{config.engine}'{why}")
    if config.grad_accum > 1 and not grad_accum_ok:
        raise ValueError(
            f"grad_accum composes with sync/allreduce/fsdp, tensor_parallel, "
            f"fsdp×tp, seq_parallel, expert_parallel, and the tp×sp / ep×sp "
            f"/ ep×tp×sp composites, not with {factor_name}: the pipeline "
            f"schedules already microbatch — size their chunks with "
            f"--microbatches")
    factors = [(factor, second_axis), *more]
    total = config.n_devices or len(_jax.devices())
    prod = 1
    for f, _ in factors:
        prod *= f
    if total % prod != 0:
        raise ValueError(f"n_devices {total} not divisible by {factor_name} {prod}")
    dp = total // prod
    mesh = meshlib.create_mesh(
        total, shape=(dp, *[f for f, _ in factors]),
        axis_names=(meshlib.DATA_AXIS, *[a for _, a in factors]))
    return mesh, dp


_SEQUENCE_MODELS = ("bert_tiny", "bert", "gpt", "gpt_tiny")
_LM_MODELS = ("gpt", "gpt_tiny")  # causal LMs: (B, L) next-token targets


def _setup_seq_parallel(config: ExperimentConfig) -> _Experiment:
    """Long-context mode: 2-D (data, seq) mesh + ring/Ulysses attention.

    ``n_devices`` still plays the reference's -n role; ``seq_parallel`` of
    them shard the sequence, the rest shard the batch."""
    from distributed_tensorflow_tpu.engines.seq_parallel import SeqParallelEngine

    _reject_flash_under_sp(config)
    mesh, dp = _split_mesh(config, config.seq_parallel, "seq_parallel",
                           meshlib.SEQ_AXIS, grad_accum_ok=True)
    train_ds, test_ds = _load_data(config)
    model = _sequence_model(config, train_ds, "seq_parallel",
                            attention_impl=config.attention_impl)

    # the seq engine scans K chunks of each data shard's LOCAL batch
    if config.grad_accum > 1 and (_global_batch(config, dp) // dp) % config.grad_accum:
        raise ValueError(
            f"seq_parallel: per-data-shard batch "
            f"{_global_batch(config, dp) // dp} not divisible by "
            f"grad_accum {config.grad_accum}")
    engine = SeqParallelEngine(
        model, mesh=mesh, learning_rate=config.learning_rate,
        optimizer=_make_optimizer(config, train_ds,
                                  _global_batch(config, dp)),
        grad_accum=config.grad_accum,
        grad_compression=config.grad_compression,
        grad_bucket_mb=config.grad_bucket_mb,
        precision=config.precision)
    return _Experiment(mesh=mesh, n=dp, train_ds=train_ds, test_ds=test_ds,
                       engine=engine, global_batch=_global_batch(config, dp),
                       name=f"seq_parallel[{config.attention_impl}]")


def _tp_model(config: ExperimentConfig, train_ds, mode: str):
    """Model for the ('data','model')-mesh modes (tp, fsdp×tp): the
    Megatron-annotated MLP for the reference's default model names, or a
    TP-annotated sequence model."""
    from distributed_tensorflow_tpu.engines.tensor_parallel import TPMLP

    if config.model_fn is None and config.model in ("mlp", "tp_mlp",
                                                    "mnist_mlp"):
        return TPMLP(num_classes=train_ds.num_classes,
                     dtype=modellib.resolve_dtype(config.dtype))
    return _sequence_model(config, train_ds, mode,
                           partition_model=True, attention_impl="dense")


def _check_accum_divides(config: ExperimentConfig, global_batch: int,
                         mode: str) -> None:
    if config.grad_accum > 1 and global_batch % config.grad_accum:
        raise ValueError(
            f"{mode}: global batch {global_batch} not divisible by "
            f"grad_accum {config.grad_accum}")


def _setup_tensor_parallel(config: ExperimentConfig) -> _Experiment:
    """Megatron-style TP: 2-D (data, model) mesh, weights sharded by GSPMD."""
    from distributed_tensorflow_tpu.engines.tensor_parallel import (
        TensorParallelEngine)

    mesh, dp = _split_mesh(config, config.tensor_parallel, "tensor_parallel",
                           meshlib.MODEL_AXIS, grad_accum_ok=True)
    train_ds, test_ds = _load_data(config)
    model = _tp_model(config, train_ds, "tensor_parallel")
    _check_accum_divides(config, _global_batch(config, dp), "tensor_parallel")

    engine = TensorParallelEngine(
        model, mesh=mesh, learning_rate=config.learning_rate,
        optimizer=_make_optimizer(config, train_ds,
                                  _global_batch(config, dp)),
        grad_accum=config.grad_accum,
        grad_compression=config.grad_compression,
        grad_bucket_mb=config.grad_bucket_mb,
        precision=config.precision)
    return _Experiment(mesh=mesh, n=dp, train_ds=train_ds, test_ds=test_ds,
                       engine=engine, global_batch=_global_batch(config, dp),
                       name="tensor_parallel")


def _setup_fsdp_tp(config: ExperimentConfig) -> _Experiment:
    """fsdp × tp: ('data','model') mesh — the model's Megatron annotations
    take their dims (compute sharding), then FSDP shards each leaf's
    largest free dim over 'data' (storage sharding, engines/fsdp.py
    fsdp_spec base=): per-device state bytes ~1/(dp·tp)."""
    from distributed_tensorflow_tpu.engines.fsdp import FSDPEngine

    mesh, dp = _split_mesh(config, config.tensor_parallel,
                           "fsdp×tensor_parallel", meshlib.MODEL_AXIS,
                           engines=("fsdp",), grad_accum_ok=True)
    train_ds, test_ds = _load_data(config)
    model = _tp_model(config, train_ds, "fsdp×tensor_parallel")
    _check_accum_divides(config, _global_batch(config, dp),
                         "fsdp×tensor_parallel")

    engine = FSDPEngine(
        model, mesh=mesh, learning_rate=config.learning_rate,
        optimizer=_make_optimizer(config, train_ds,
                                  _global_batch(config, dp)),
        grad_accum=config.grad_accum,
        grad_compression=config.grad_compression,
        grad_bucket_mb=config.grad_bucket_mb,
        precision=config.precision)
    return _Experiment(mesh=mesh, n=dp, train_ds=train_ds, test_ds=test_ds,
                       engine=engine, global_batch=_global_batch(config, dp),
                       name="fsdp_tp[fsdp*tp]")


def _require_token_data(train_ds, config: ExperimentConfig, mode: str) -> None:
    if not np.issubdtype(train_ds.x.dtype, np.integer):
        hint = ("lm_synth" if config.model in _LM_MODELS else "glue_synth")
        raise ValueError(
            f"{mode} with a sequence model needs a token dataset (integer "
            f"ids), got --dataset {config.dataset} with dtype "
            f"{train_ds.x.dtype}; use --dataset {hint}")
    if config.model in _LM_MODELS and train_ds.y.ndim < 2:
        raise ValueError(
            f"--model {config.model} is a causal LM and needs per-token "
            f"(B, L) targets, got labels of shape {train_ds.y.shape} from "
            f"--dataset {config.dataset}; use --dataset lm_synth")


def _sequence_model(config: ExperimentConfig, train_ds, mode: str, **kw):
    """Resolve a sequence model for a model-parallel mode: user ``model_fn``
    wins as-is; registered sequence models get the mode's sharding kwargs;
    anything else is an error (non-sequence models carry no seq/TP layout)."""
    if config.model_fn is not None:
        return config.model_fn()
    if config.model in _SEQUENCE_MODELS:
        _require_token_data(train_ds, config, mode)
        if config.remat:
            kw["remat"] = True
        _check_reserved_model_args(
            config, {"num_classes", "dtype", *kw, *_lm_model_kw(config)},
            mode)
        kw = {**(config.model_args or {}), **kw}
        kw.update(_lm_model_kw(config))
        return modellib.create_model(
            config.model, num_classes=train_ds.num_classes,
            dtype=config.dtype, **kw)
    raise ValueError(
        f"{mode} needs a sequence model ({'/'.join(_SEQUENCE_MODELS)}), got "
        f"--model {config.model}; pass model_fn for a custom model")


def _check_reserved_model_args(config: ExperimentConfig, reserved,
                               where: str) -> None:
    """--model-arg keys that a dedicated flag or the mode itself sets would
    otherwise surface as a raw ``got multiple values`` TypeError (or be
    silently overridden) when splatted into create_model (ADVICE r3).
    Reject them with the same clean style as the other CLI validations."""
    bad = sorted(set(config.model_args or {}) & set(reserved))
    if bad:
        raise ValueError(
            f"--model-arg key(s) {bad} are reserved for {where}: they are "
            f"set by a dedicated flag or by the mode itself (e.g. "
            f"--num-experts, --dtype, --kv-heads, --positional, "
            f"--attention); drop them from --model-arg")


def _reject_model_args(config: ExperimentConfig, mode: str) -> None:
    """The built-in MLP pipeline stages are sized by --pipeline-hidden, not
    --model-arg — reject rather than silently train a default-size model
    (same policy as --router-z-weight outside EP).  The BERT/GPT stage
    families DO take --model-arg (see _stage_model_args)."""
    if config.model_args:
        raise ValueError(
            f"--model-arg does not reach {mode} stage modules; size them "
            f"with --pipeline-hidden (got {sorted(config.model_args)})")


_STAGE_MODEL_ARGS = ("heads", "ffn", "layers_per_stage")
_STAGE_MOE_ARGS = ("moe_capacity_factor",)  # the overflow monitor's advised
                                            # remediation must be reachable
                                            # from the CLI on pp×ep runs


def _stage_model_args(config: ExperimentConfig, mode: str,
                      moe: bool = False) -> dict:
    """--model-arg keys the BERT/GPT pipeline-stage families accept
    (VERDICT r3 #6: an 8-head or 2-layers-per-stage pipeline should not
    require Python).  Width still comes from --pipeline-hidden; everything
    else is either a dedicated flag (--kv-heads, --positional) or not a
    per-stage knob — reject with the full picture.  MoE stages (pp×ep)
    additionally accept ``moe_capacity_factor``."""
    allowed = _STAGE_MODEL_ARGS + (_STAGE_MOE_ARGS if moe else ())
    extra = dict(config.model_args or {})
    bad = sorted(set(extra) - set(allowed))
    if bad:
        raise ValueError(
            f"--model-arg key(s) {bad} do not reach {mode} stage modules; "
            f"stages accept {'/'.join(allowed)} via --model-arg, "
            f"width via --pipeline-hidden, and K/V heads / positional "
            f"encoding via --kv-heads / --positional")
    return extra


def _pipeline_stages(config: ExperimentConfig, train_ds, test_ds, mode: str,
                     partition_model: bool = False,
                     attention_impl: str = "dense",
                     seq_axis: str | None = None,
                     moe: bool = False):
    """(embed, block, head) for the pipeline setups, by model family:
    BERT encoder (models/bert.py) or GPT decoder LM (models/gpt.py).
    ``attention_impl``/``seq_axis`` make the GPT stages sequence-parallel
    for dp×pp×sp.  ``moe=True`` (pp×ep) makes each stage block's FFN a
    routed MoE sized by ``--num-experts``/``--router-top-k``, with
    'expert'-axis partitioning annotations.  ``--model-arg
    heads/ffn/layers_per_stage`` size the stages (_stage_model_args)."""
    _require_token_data(train_ds, config, mode)
    dtype = modellib.resolve_dtype(config.dtype)
    extra = _stage_model_args(config, mode, moe=moe)
    if moe:
        extra.update(moe_experts=config.num_experts,
                     moe_top_k=config.router_top_k,
                     partition_experts=True)
    if config.model in _LM_MODELS:
        from distributed_tensorflow_tpu.models.gpt import gpt_pipeline_stages

        return gpt_pipeline_stages(
            vocab_size=train_ds.num_classes,
            hidden=config.pipeline_hidden,
            max_len=train_ds.x.shape[1],
            partition_model=partition_model,
            positional=config.positional,
            kv_heads=config.kv_heads,
            attention_impl=attention_impl,
            seq_axis=seq_axis,
            dtype=dtype,
            **extra)
    from distributed_tensorflow_tpu.models.bert import bert_pipeline_stages

    # vocab must cover BOTH splits: nn.Embed silently clamps out-of-range
    # ids, which would skew eval on unseen test tokens
    return bert_pipeline_stages(
        num_classes=train_ds.num_classes,
        vocab_size=int(max(train_ds.x.max(), test_ds.x.max())) + 1,
        hidden=config.pipeline_hidden,
        max_len=train_ds.x.shape[1],
        partition_model=partition_model,
        dtype=dtype,
        **extra)


def _setup_composite(config: ExperimentConfig) -> _Experiment:
    """dp×tp×sp composition: 3-D (data, model, seq) mesh, GSPMD tensor
    parallelism + manual-seq ring/Ulysses attention (engines/composite.py)."""
    from distributed_tensorflow_tpu.engines.composite import CompositeEngine

    mesh, dp = _split_mesh(config, config.tensor_parallel,
                           "tensor_parallel×seq_parallel", meshlib.MODEL_AXIS,
                           (config.seq_parallel, meshlib.SEQ_AXIS),
                           grad_accum_ok=True)
    train_ds, test_ds = _load_data(config)
    model = _sequence_model(config, train_ds, "tensor_parallel×seq_parallel",
                            partition_model=True,
                            attention_impl=config.attention_impl)
    _check_accum_divides(config, _global_batch(config, dp),
                         "tensor_parallel×seq_parallel")
    # a --model-arg moe_experts=N model makes the composite MoE-aware, so
    # the balance-loss weights must reach the engine here too (not only on
    # the -ep paths) — otherwise --aux-weight would be silently ignored
    engine = CompositeEngine(
        model, mesh=mesh, learning_rate=config.learning_rate,
        optimizer=_make_optimizer(config, train_ds,
                                  _global_batch(config, dp)),
        aux_weight=config.aux_weight,
        router_z_weight=config.router_z_weight,
        grad_accum=config.grad_accum,
        grad_compression=config.grad_compression,
        grad_bucket_mb=config.grad_bucket_mb,
        precision=config.precision)
    return _Experiment(mesh=mesh, n=dp, train_ds=train_ds, test_ds=test_ds,
                       engine=engine, global_batch=_global_batch(config, dp),
                       name=f"composite[dp*tp*sp,{config.attention_impl}]")


def _setup_pipeline_parallel(config: ExperimentConfig) -> _Experiment:
    """GPipe mode: 2-D (data, pipe) mesh.  The engine stacks stage params
    over 'pipe'; --model picks the stage family — the built-in MLP stages or
    a BERT encoder split layer-per-stage (models/bert.py
    bert_pipeline_stages)."""
    from distributed_tensorflow_tpu.engines.pipeline import PipelineEngine

    mesh, dp = _split_mesh(config, config.pipeline_parallel,
                           "pipeline_parallel", meshlib.PIPE_AXIS)
    train_ds, test_ds = _load_data(config)
    stages = None
    if config.model in _SEQUENCE_MODELS and config.model_fn is None:
        stages = _pipeline_stages(config, train_ds, test_ds,
                                  "pipeline_parallel")
    elif config.model_fn is not None or config.model not in (
            "mlp", "mnist_mlp", "pipeline_mlp"):
        raise ValueError(
            f"pipeline_parallel ships stages for mlp and "
            f"{'/'.join(_SEQUENCE_MODELS)} (got --model {config.model}); "
            f"custom models pass stages=(embed, block, head) to "
            f"PipelineEngine directly")
    else:
        # built-in MLP stages: sized by --pipeline-hidden only
        _reject_model_args(config, "pipeline_parallel")
    if (_global_batch(config, dp) // dp) % config.microbatches:
        raise ValueError(
            f"per-data-shard batch {_global_batch(config, dp) // dp} not "
            f"divisible by microbatches {config.microbatches}")
    engine = PipelineEngine(num_classes=train_ds.num_classes,
                            hidden=config.pipeline_hidden,
                            microbatches=config.microbatches, mesh=mesh,
                            learning_rate=config.learning_rate,
                            optimizer=_make_optimizer(
                                config, train_ds,
                                _global_batch(config, dp)),
                            dtype=modellib.resolve_dtype(config.dtype),
                            stages=stages,
                            schedule=config.pipeline_schedule,
                            remat=config.remat)
    return _Experiment(mesh=mesh, n=dp, train_ds=train_ds, test_ds=test_ds,
                       engine=engine, global_batch=_global_batch(config, dp),
                       name="pipeline_parallel")


def _setup_pipeline_tp(config: ExperimentConfig) -> _Experiment:
    """dp×pp×tp: 3-D (data, pipe, model) mesh — GPipe/1F1B schedule manual
    over (data, pipe), Megatron TP inside each stage as a GSPMD auto axis
    (engines/pipeline.py).  Sequence-model stages only (BERT encoder or GPT
    decoder): the built-in MLP stages carry no Megatron annotations."""
    from distributed_tensorflow_tpu.engines.pipeline import PipelineEngine

    mesh, dp = _split_mesh(config, config.pipeline_parallel,
                           "pipeline_parallel×tensor_parallel",
                           meshlib.PIPE_AXIS,
                           (config.tensor_parallel, meshlib.MODEL_AXIS))
    train_ds, test_ds = _load_data(config)
    if config.model not in _SEQUENCE_MODELS or config.model_fn is not None:
        raise ValueError(
            f"pipeline×tensor parallelism ships TP-annotated stages for "
            f"{'/'.join(_SEQUENCE_MODELS)} (got --model {config.model}); "
            f"custom models pass stages=(embed, block, head) with "
            f"with_partitioning('model', ...) annotations to PipelineEngine")
    stages = _pipeline_stages(config, train_ds, test_ds,
                               "pipeline_parallel×tensor_parallel",
                               partition_model=True)
    if (_global_batch(config, dp) // dp) % config.microbatches:
        raise ValueError(
            f"per-data-shard batch {_global_batch(config, dp) // dp} not "
            f"divisible by microbatches {config.microbatches}")
    engine = PipelineEngine(microbatches=config.microbatches, mesh=mesh,
                            learning_rate=config.learning_rate,
                            optimizer=_make_optimizer(
                                config, train_ds,
                                _global_batch(config, dp)),
                            stages=stages,
                            schedule=config.pipeline_schedule,
                            remat=config.remat)
    return _Experiment(mesh=mesh, n=dp, train_ds=train_ds, test_ds=test_ds,
                       engine=engine, global_batch=_global_batch(config, dp),
                       name=f"pipeline_tp[dp*pp*tp,{config.pipeline_schedule}]")


def _setup_pipeline_ep(config: ExperimentConfig, tp: int = 1,
                       sp: int = 1) -> _Experiment:
    """dp×pp×ep: 3-D (data, pipe, expert) mesh — GPipe schedule manual over
    (data, pipe), each stage block's FFN a routed MoE whose experts shard
    over 'expert' as a GSPMD auto axis (engines/pipeline.py; same
    partial-manual recipe as pp×tp's 'model' axis).  The batch shards over
    'data' only — the expert axis holds experts, not tokens, exactly as the
    'model' axis holds Megatron shards in pp×tp.  GPipe only: 1F1B's
    hand-scheduled backward carries no router aux cotangent (the engine
    rejects it with that reason).

    ``tp > 1`` adds a 'model' GSPMD axis (dp×pp×ep×tp, 4-D mesh): GShard's
    2-D expert layout inside pipeline stages — each expert's FFN is
    additionally Megatron-split, w1 sharded ('pipe','expert',·,'model').
    ``sp > 1`` adds a manual 'seq' axis (dp×pp×ep×sp): the long-context
    MoE pipeline — ring attention over seq-sharded carries while each seq
    device routes its token block to the globally-sharded experts."""
    from distributed_tensorflow_tpu.engines.pipeline import PipelineEngine

    mode = "pipeline_parallel×expert_parallel" + (
        "×tensor_parallel" if tp > 1 else "") + (
        "×seq_parallel" if sp > 1 else "")
    lm_only = sp > 1  # a seq-sharded carry cannot serve a [CLS] head
    family = _LM_MODELS if lm_only else _SEQUENCE_MODELS
    if config.model not in family or config.model_fn is not None:
        raise ValueError(
            f"{mode} ships MoE-FFN stages for {'/'.join(family)} "
            f"(got --model {config.model}); custom models pass stages "
            f"whose block carries moe_experts/partition_experts "
            f"(models/moe.py MoELayer) to PipelineEngine")
    if sp > 1:
        _reject_flash_under_sp(config)
    if config.num_experts % config.expert_parallel:
        raise ValueError(
            f"num_experts {config.num_experts} not divisible by "
            f"expert_parallel {config.expert_parallel}")
    extra = [(config.expert_parallel, meshlib.EXPERT_AXIS)]
    if tp > 1:
        extra.append((tp, meshlib.MODEL_AXIS))
    if sp > 1:
        extra.append((sp, meshlib.SEQ_AXIS))
    mesh, dp = _split_mesh(config, config.pipeline_parallel, mode,
                           meshlib.PIPE_AXIS, *extra)
    train_ds, test_ds = _load_data(config)
    stages = _pipeline_stages(
        config, train_ds, test_ds, mode, moe=True,
        partition_model=tp > 1,
        attention_impl=config.attention_impl if sp > 1 else "dense",
        seq_axis=meshlib.SEQ_AXIS if sp > 1 else None)
    if (_global_batch(config, dp) // dp) % config.microbatches:
        raise ValueError(
            f"per-data-shard batch {_global_batch(config, dp) // dp} not "
            f"divisible by microbatches {config.microbatches}")
    engine = PipelineEngine(microbatches=config.microbatches, mesh=mesh,
                            learning_rate=config.learning_rate,
                            optimizer=_make_optimizer(
                                config, train_ds,
                                _global_batch(config, dp)),
                            stages=stages,
                            schedule=config.pipeline_schedule,
                            remat=config.remat,
                            aux_weight=config.aux_weight,
                            router_z_weight=config.router_z_weight)
    tag = (f"pipeline_ep_tp_sp[dp*pp*ep*tp*sp,{config.attention_impl}]"
           if tp > 1 and sp > 1
           else "pipeline_ep_tp[dp*pp*ep*tp]" if tp > 1
           else f"pipeline_ep_sp[dp*pp*ep*sp,{config.attention_impl}]"
           if sp > 1 else f"pipeline_ep[dp*pp*ep,{config.pipeline_schedule}]")
    return _Experiment(mesh=mesh, n=dp, train_ds=train_ds, test_ds=test_ds,
                       engine=engine, global_batch=_global_batch(config, dp),
                       name=tag)


def _setup_pipeline_ep_tp(config: ExperimentConfig) -> _Experiment:
    """dp×pp×ep×tp (4-D mesh) — see _setup_pipeline_ep(tp=...)."""
    return _setup_pipeline_ep(config, tp=config.tensor_parallel)


def _setup_pipeline_ep_sp(config: ExperimentConfig) -> _Experiment:
    """dp×pp×ep×sp (4-D mesh) — see _setup_pipeline_ep(sp=...)."""
    return _setup_pipeline_ep(config, sp=config.seq_parallel)


def _setup_pipeline_ep_tp_sp(config: ExperimentConfig) -> _Experiment:
    """dp×pp×ep×tp×sp (5-D mesh): every model-parallel axis at once — pipe
    schedule + ring attention manual over (data, pipe, seq); Megatron and
    GShard-2-D expert sharding GSPMD over ('model', 'expert').  See
    _setup_pipeline_ep(tp=..., sp=...)."""
    return _setup_pipeline_ep(config, tp=config.tensor_parallel,
                              sp=config.seq_parallel)


def _setup_expert_parallel(config: ExperimentConfig,
                           tp: int = 1) -> _Experiment:
    """MoE mode: (data, expert) mesh, experts sharded over 'expert', tokens
    over the data×expert plane (engines/expert_parallel.py).  ``tp > 1``
    adds a 'model' axis — dp×ep×tp: each expert's FFN is also
    Megatron-split (models/moe.py partition_model), still one GSPMD jit."""
    from distributed_tensorflow_tpu.engines.expert_parallel import (
        ExpertParallelEngine)

    mode = ("expert_parallel×tensor_parallel" if tp > 1
            else "expert_parallel")
    extra = [(tp, meshlib.MODEL_AXIS)] if tp > 1 else []
    mesh, dp = _split_mesh(config, config.expert_parallel, mode,
                           meshlib.EXPERT_AXIS, *extra, grad_accum_ok=True)
    train_ds, test_ds = _load_data(config)
    if config.model_fn is not None:
        model = config.model_fn()
    elif config.model in ("moe", "moe_mlp", "mlp"):
        if config.num_experts % config.expert_parallel:
            raise ValueError(
                f"num_experts {config.num_experts} not divisible by "
                f"expert_parallel {config.expert_parallel}")
        _check_reserved_model_args(
            config, {"num_classes", "num_experts", "partition_experts",
                     "partition_model", "router_top_k", "dtype"}, mode)
        model = modellib.create_model(
            "moe", num_classes=train_ds.num_classes,
            **(config.model_args or {}),
            num_experts=config.num_experts, partition_experts=True,
            partition_model=tp > 1, router_top_k=config.router_top_k,
            dtype=config.dtype)
    else:
        raise ValueError(
            f"{mode} needs the MoE model (got --model {config.model}); "
            f"custom MoEs pass model_fn with with_partitioning('expert' "
            f"{'+ ''model'' ' if tp > 1 else ''}...) annotations")

    # tokens shard over (data, expert); a model axis replicates them, so the
    # global batch scales with the token-shard count only
    n_token_shards = dp * config.expert_parallel
    _check_accum_divides(config, _global_batch(config, n_token_shards), mode)
    engine = ExpertParallelEngine(
        model, mesh=mesh, learning_rate=config.learning_rate,
        optimizer=_make_optimizer(config, train_ds,
                                  _global_batch(config, n_token_shards)),
        aux_weight=config.aux_weight,
        router_z_weight=config.router_z_weight,
        grad_accum=config.grad_accum,
        grad_compression=config.grad_compression,
        grad_bucket_mb=config.grad_bucket_mb,
        precision=config.precision)
    return _Experiment(mesh=mesh, n=dp, train_ds=train_ds, test_ds=test_ds,
                       engine=engine,
                       global_batch=_global_batch(config, n_token_shards),
                       name=("expert_tp[dp*ep*tp]" if tp > 1 else "expert_parallel"))


def _setup_pipeline_sp(config: ExperimentConfig, tp: int = 1) -> _Experiment:
    """dp×pp×sp: 3-D (data, pipe, seq) mesh — GPipe schedule manual over
    (data, pipe), ring/Ulysses attention manual over 'seq' inside each
    stage (engines/pipeline.py).  GPT decoder stages only: a seq-sharded
    carry cannot serve a [CLS] classification head, and the LM's per-token
    loss is what the schedule's drain reduces correctly.

    ``tp > 1`` adds a 'model' GSPMD axis — dp×pp×tp×sp on a 4-D mesh: the
    shard_map stays manual over (data, pipe, seq) while each stage's
    Megatron annotations drive in-stage model-axis collectives (the same
    partial-manual composition as pp×tp, engines/pipeline.py
    _wrap_pipe_step)."""
    from distributed_tensorflow_tpu.engines.pipeline import PipelineEngine

    mode = ("pipeline_parallel×tensor_parallel×seq_parallel" if tp > 1
            else "pipeline_parallel×seq_parallel")
    if config.model not in _LM_MODELS or config.model_fn is not None:
        raise ValueError(
            f"{mode} ships GPT decoder stages only "
            f"(got --model {config.model}); custom models pass seq-aware "
            f"stages to PipelineEngine directly")
    _reject_flash_under_sp(config)
    extra = [(tp, meshlib.MODEL_AXIS)] if tp > 1 else []
    mesh, dp = _split_mesh(config, config.pipeline_parallel, mode,
                           meshlib.PIPE_AXIS,
                           (config.seq_parallel, meshlib.SEQ_AXIS), *extra)
    train_ds, test_ds = _load_data(config)
    stages = _pipeline_stages(config, train_ds, test_ds, mode,
                              attention_impl=config.attention_impl,
                              seq_axis=meshlib.SEQ_AXIS,
                              partition_model=tp > 1)
    if (_global_batch(config, dp) // dp) % config.microbatches:
        raise ValueError(
            f"per-data-shard batch {_global_batch(config, dp) // dp} not "
            f"divisible by microbatches {config.microbatches}")
    engine = PipelineEngine(microbatches=config.microbatches, mesh=mesh,
                            learning_rate=config.learning_rate,
                            optimizer=_make_optimizer(
                                config, train_ds, _global_batch(config, dp)),
                            stages=stages,
                            schedule=config.pipeline_schedule,
                            remat=config.remat)
    return _Experiment(mesh=mesh, n=dp, train_ds=train_ds, test_ds=test_ds,
                       engine=engine, global_batch=_global_batch(config, dp),
                       name=(f"pipeline_tp_sp[dp*pp*tp*sp,{config.attention_impl}]" if tp > 1
                             else f"pipeline_sp[dp*pp*sp,{config.attention_impl}]"))


def _setup_pipeline_tp_sp(config: ExperimentConfig) -> _Experiment:
    """dp×pp×tp×sp (4-D mesh) — see _setup_pipeline_sp(tp=...)."""
    return _setup_pipeline_sp(config, tp=config.tensor_parallel)


def _setup_expert_tp(config: ExperimentConfig) -> _Experiment:
    """dp×ep×tp — see _setup_expert_parallel(tp=...)."""
    return _setup_expert_parallel(config, tp=config.tensor_parallel)


def _setup_expert_sp(config: ExperimentConfig, tp: int = 1) -> _Experiment:
    """dp×ep×sp (the long-context MoE shape): ('data','expert','seq') mesh
    — GPT decoder with MoE-FFN blocks (models/gpt.py ``moe_experts``),
    ring/Ulysses attention manual over 'seq', expert dispatch GSPMD over
    'expert' (engines/composite.py).  ``tp > 1`` adds a 'model' axis
    (ep×tp×sp on a 4-D mesh): attention/embeddings Megatron-sharded and
    each expert's FFN additionally model-split (GShard 2-D experts)."""
    from distributed_tensorflow_tpu.engines.composite import CompositeEngine

    mode = ("expert_parallel×tensor_parallel×seq_parallel" if tp > 1
            else "expert_parallel×seq_parallel")
    if config.model not in _SEQUENCE_MODELS:
        raise ValueError(
            f"{mode} routes a transformer's FFN blocks (moe_experts on "
            f"models/gpt.py or models/bert.py); got --model {config.model} "
            f"— use --model gpt (--dataset lm_synth) or --model bert_tiny "
            f"(--dataset glue_synth)")
    _reject_flash_under_sp(config)
    if config.num_experts % config.expert_parallel:
        raise ValueError(
            f"num_experts {config.num_experts} not divisible by "
            f"expert_parallel {config.expert_parallel}")
    extra = [(tp, meshlib.MODEL_AXIS)] if tp > 1 else []
    mesh, dp = _split_mesh(config, config.expert_parallel, mode,
                           meshlib.EXPERT_AXIS,
                           (config.seq_parallel, meshlib.SEQ_AXIS), *extra,
                           grad_accum_ok=True)
    train_ds, test_ds = _load_data(config)
    model = _sequence_model(
        config, train_ds, mode,
        attention_impl=config.attention_impl,
        moe_experts=config.num_experts,
        moe_top_k=config.router_top_k,
        partition_experts=True,
        partition_model=tp > 1)
    _check_accum_divides(config, _global_batch(config, dp), mode)
    engine = CompositeEngine(
        model, mesh=mesh, learning_rate=config.learning_rate,
        optimizer=_make_optimizer(config, train_ds,
                                  _global_batch(config, dp)),
        aux_weight=config.aux_weight,
        router_z_weight=config.router_z_weight,
        grad_accum=config.grad_accum,
        grad_compression=config.grad_compression,
        grad_bucket_mb=config.grad_bucket_mb,
        precision=config.precision)
    return _Experiment(mesh=mesh, n=dp, train_ds=train_ds, test_ds=test_ds,
                       engine=engine, global_batch=_global_batch(config, dp),
                       name=(f"expert_tp_sp[dp*ep*tp*sp,{config.attention_impl}]" if tp > 1
                             else f"expert_sp[dp*ep*sp,{config.attention_impl}]"))


def _setup_expert_tp_sp(config: ExperimentConfig) -> _Experiment:
    """dp×ep×tp×sp (4-D mesh) — see _setup_expert_sp(tp=...)."""
    return _setup_expert_sp(config, tp=config.tensor_parallel)


def run(config: ExperimentConfig) -> dict[str, Any]:
    """Run one experiment; returns the summary dict (also emitted as JSONL).

    With ``max_restarts > 0`` the run is wrapped in checkpoint-resume crash
    recovery (utils/failure.py run_with_recovery).
    """
    if config.max_restarts > 0:
        from distributed_tensorflow_tpu.utils.failure import run_with_recovery

        return run_with_recovery(
            dataclasses.replace(config, max_restarts=0),
            max_restarts=config.max_restarts, run_fn=run)
    if config.watchdog_abort and config.watchdog_timeout <= 0:
        raise ValueError("watchdog_abort requires watchdog_timeout > 0 "
                         "(nothing would ever detect the stall)")
    if config.timeline_interval < 0:
        raise ValueError(f"--timeline-interval must be >= 0 seconds "
                         f"(0 = sample at every boundary), got "
                         f"{config.timeline_interval}")
    if config.grad_bucket_mb:
        # before backend init: the latency-hiding/async-collective flags
        # only take effect at compile time (recorded in the run report's
        # `environment` section either way).  Validate FIRST — a config
        # _setup would reject must not leave LIBTPU_INIT_ARGS mutated for
        # later runs in this process
        _validate_grad_bucket(config)
        enable_overlap_flags()
    ex = _setup(config)
    # numeric-health layer: must be enabled BEFORE any state init (the
    # optimizer tree gains its capture slots at tx.init) — including the
    # --resume template below
    if config.health not in ("off", "on"):
        raise ValueError(
            f"--health must be 'off' or 'on', got '{config.health}'")
    if config.health == "on":
        ex.engine.enable_health()
    n, train_ds, test_ds = ex.n, ex.train_ds, ex.test_ds
    global_batch = ex.global_batch
    if config.sample_tokens:
        _validate_sampling(config, ex, test_ds)
    if config.serve_requests:
        # like sampling: every deterministically-knowable --serve failure
        # raises BEFORE the run spends a training budget on it
        _validate_serving(config, ex, test_ds)

    # in a multi-host pod only process 0 reports — N processes each emitting
    # the start/done/results triple would corrupt an external supervisor's
    # accounting (the reference has exactly one reporting server)
    supervisor = (config.supervisor_address
                  if jax.process_index() == 0 else None)
    sink = ResultSink(config.result_path, echo=False,
                      supervisor_address=supervisor)
    trainer = Trainer(None, engine=ex.engine, seed=config.seed)

    ckpt_mgr = None
    resume_requested = config.resume or config.elastic_restore
    if config.resume and not config.checkpoint_dir:
        raise ValueError("--resume requires --checkpoint-dir")
    if config.elastic_restore and not config.checkpoint_dir:
        raise ValueError("--elastic-restore requires --checkpoint-dir")
    if config.checkpoint_every and not config.checkpoint_dir:
        raise ValueError("--checkpoint-every requires --checkpoint-dir "
                         "(no checkpoints would be written otherwise)")
    if config.max_steps_per_lease < 0:
        raise ValueError(f"--max-steps-per-lease must be >= 0, got "
                         f"{config.max_steps_per_lease}")
    if config.max_steps_per_lease and not config.checkpoint_dir:
        raise ValueError("--max-steps-per-lease requires --checkpoint-dir "
                         "(the lease drain's final checkpoint needs "
                         "somewhere to go)")
    # elastic-resume accounting, filled by the restore below and carried
    # into the run report: seconds the preemption cost (save → resume
    # wall-clock gap) and the data state the resumed fit continues from
    resume_data_state = None
    preemption_lost = None
    restored_step = None
    if config.checkpoint_dir:
        from distributed_tensorflow_tpu.utils.checkpoint import (
            AsyncCheckpointManager, CheckpointManager)

        # async (the default) takes the Orbax write off the training
        # thread; --async-checkpoint off restores the synchronous
        # blocking-save path bit-for-bit (same on-disk format either way).
        # Constructing EITHER manager sweeps any tmp_step_* left by a
        # crashed write, so --resume below only ever sees complete
        # (renamed) checkpoints.
        ckpt_mgr = (AsyncCheckpointManager(config.checkpoint_dir)
                    if config.async_checkpoint
                    else CheckpointManager(config.checkpoint_dir))
        if resume_requested:
            if ckpt_mgr.latest_step() is None:
                flag = ("--elastic-restore" if config.elastic_restore
                        else "--resume")
                print(f"warning: {flag} set but no checkpoint found under "
                      f"{config.checkpoint_dir}; training from scratch")
            else:
                rng = jax.random.key(config.seed)
                template = ex.engine.init_state(
                    rng, train_ds.x[: max(1, ex.n)])
                try:
                    if config.elastic_restore:
                        # mesh-shape-independent restore (elastic/
                        # reshard.py): policy-aware per-leaf load, then
                        # re-placement under THIS engine's spec map on
                        # THIS mesh — the checkpoint may have been
                        # written by a different device count or axis
                        # layout.  The elastic sidecar comes back with
                        # it: data state for the exactly-once resume
                        # ({} when the checkpoint predates it → replay
                        # accounting) and the save wall time the
                        # preemption_lost_s figure is measured from.
                        from distributed_tensorflow_tpu import (
                            elastic as elasticlib)

                        trainer.state, extra = elasticlib.elastic_restore(
                            ckpt_mgr, ex.engine, template)
                        resume_data_state = (
                            (extra or {}).get("data_state") or {})
                        preemption_lost = elasticlib.preemption_lost_s(
                            extra)
                    else:
                        # policy-aware restore: a checkpoint written under
                        # the SAME --precision restores directly; an
                        # f32-era checkpoint restored into a master policy
                        # is adopted (restored f32 params become the
                        # master, their downcast the stored params —
                        # precision.py)
                        from distributed_tensorflow_tpu.parallel import (
                            precision as precisionlib)

                        trainer.state = precisionlib.restore_into_policy(
                            ckpt_mgr, template, ex.engine.precision)
                except Exception as e:
                    # the most common structure mismatch here is a --health
                    # toggle across the resume boundary: enable_health
                    # grows the optimizer tree by two capture slots, so a
                    # checkpoint written under the other setting no longer
                    # matches the template — name that cause instead of
                    # surfacing the checkpoint library's raw tree error
                    raise ValueError(
                        f"--resume could not restore the checkpoint under "
                        f"{config.checkpoint_dir} into this run's state "
                        f"layout (--health {config.health}, --precision "
                        f"{config.precision}).  If the checkpointed run "
                        f"used a different --health setting, the optimizer "
                        f"tree differs (the health capture slots live in "
                        f"it) — resume with the original setting.  An f32 "
                        f"checkpoint restores into a master --precision "
                        f"policy automatically; other precision crossings "
                        f"need the original policy.  Original error: "
                        f"{type(e).__name__}: {e}") from e
                restored_step = ckpt_mgr.latest_step()
                sink.emit("resumed", step=restored_step,
                          elastic=config.elastic_restore,
                          **({"preemption_lost_s": preemption_lost}
                             if config.elastic_restore else {}))

    metrics_logger = None
    if config.metrics_path:
        from distributed_tensorflow_tpu.utils.metrics import MetricsLogger

        metrics_logger = MetricsLogger(config.metrics_path,
                                       log_every=max(1, config.log_every))

    # the tracer is always live: file-backed when --trace is set,
    # aggregate-only otherwise (the run report reads its span table and
    # measured overhead either way; the aggregate cost is two perf_counter
    # calls per chunk-level span)
    from distributed_tensorflow_tpu.observability import (
        Tracer, build_run_report)

    tracer = Tracer(path=config.trace_path,
                    process_index=jax.process_index())

    # --timeline: the sensor substrate.  One flag arms BOTH halves —
    # the gauge sampler (Timeline, sampled at boundaries the loops
    # already cross) and the XLA program ledger (ProgramLedger, riding
    # the serve path's jit sites via ledger.jit).  Off means the objects
    # are None at every call site, so the compiled program set and the
    # summary key set are byte-identical to a pre-timeline run (the
    # parity pin tests/test_timeline.py enforces).
    timeline = None
    ledger = None
    if config.timeline or config.roofline:
        # --roofline arms the ledger too: cost_analysis flops/bytes ride
        # the same AOT-compiled executables memory_analysis does, and the
        # attribution table needs them.  ledger.jit compiles the SAME
        # programs the plain path does (the round-17 discipline), so the
        # parity pin stays about flag-OFF byte-identity.
        from distributed_tensorflow_tpu.observability import ProgramLedger

        ledger = ProgramLedger()
    if config.timeline:
        from distributed_tensorflow_tpu.observability import Timeline

        timeline = Timeline(interval_s=config.timeline_interval)

    # --roofline: device peaks (honest None off-TPU) + the engine's
    # analytic cost model (None for non-GPT models — MFU then reports
    # None, never a number against an invented peak), normalized over the
    # run's total device count.  Threaded through fit, the serve window
    # and the run report below.
    roofline = None
    if config.roofline:
        from distributed_tensorflow_tpu.observability.roofline import (
            Roofline, _dtype_key, device_peaks)

        rf_devices = (n * config.seq_parallel * config.tensor_parallel
                      * config.pipeline_parallel * config.expert_parallel)
        rf_cost = (ex.engine.roofline_model()
                   if hasattr(ex.engine, "roofline_model") else None)
        roofline = Roofline(
            device_peaks(jax.local_devices()[0].device_kind),
            rf_devices, rf_cost,
            _dtype_key(getattr(getattr(ex.engine, "model", None),
                               "dtype", "float32")))

    # elastic lease + straggler detection (distributed_tensorflow_tpu/
    # elastic/): every checkpointed run arms the graceful SIGTERM drain —
    # a preemption notice finishes the in-flight chunk, writes a final
    # checkpoint with its data state and returns a structured `preempted`
    # result instead of a corpse; --max-steps-per-lease adds the step
    # budget.  The straggler detector rides the step times the Trainer
    # already measures (zero extra syncs) and emits structured
    # `straggler` trace events on outliers.
    from distributed_tensorflow_tpu.elastic import (
        LeaseManager, StragglerDetector)

    lease = None
    if config.checkpoint_dir:
        lease = LeaseManager(
            max_steps_per_lease=config.max_steps_per_lease).install()
    straggler = StragglerDetector(tracer=tracer)

    # one-time exposed-vs-hidden collective measurement (the overlap
    # opt-in pays two extra step compiles for the number BASELINE.md
    # gates): spanned/evented as `collective_overlap`, surfaced by the
    # run report as grad_collective_exposed_s / grad_collective_hidden_s
    overlap_probe = None
    if config.grad_bucket_mb:
        overlap_probe = _probe_collective_overlap(ex, global_batch, tracer)

    from distributed_tensorflow_tpu.utils.metrics import profile

    watchdog = None
    if config.watchdog_timeout > 0:
        from distributed_tensorflow_tpu.utils.failure import Watchdog

        def _on_stall(elapsed: float) -> None:
            sink.emit("stall", elapsed=elapsed)
            if config.watchdog_abort:
                # the step loop is wedged inside the XLA runtime; no Python
                # exception can reach it — exit so a supervisor relaunches
                # with --resume (EX_TEMPFAIL).  os._exit skips every
                # finally block AND kills the async sinks' daemon writer
                # threads, so drain them here first: the records leading up
                # to the stall are exactly the ones worth keeping
                if metrics_logger is not None:
                    metrics_logger.close()
                tracer.close()
                sink.close()
                os._exit(75)

        watchdog = Watchdog(timeout=config.watchdog_timeout,
                            on_stall=_on_stall)

    sink.start()
    try:  # noqa: the sink (and its supervisor socket) must close on ANY exit
        try:
            with profile(config.profile_dir, tracer=tracer):
                fit = trainer.fit(train_ds, epochs=config.epochs,
                                  batch_size=global_batch,
                                  log_every=config.log_every,
                                  checkpoint_manager=ckpt_mgr,
                                  checkpoint_every=config.checkpoint_every,
                                  metrics_logger=metrics_logger,
                                  watchdog=watchdog,
                                  nan_guard=config.nan_guard,
                                  on_anomaly=config.on_anomaly,
                                  steps_per_call=config.steps_per_call,
                                  prefetch=config.prefetch,
                                  tracer=tracer,
                                  should_stop=(lease.should_stop
                                               if lease is not None
                                               else None),
                                  data_state=resume_data_state,
                                  straggler_detector=straggler,
                                  timeline=timeline,
                                  roofline=roofline)
        finally:
            if watchdog is not None:
                watchdog.close()
            if lease is not None and not config.serve_requests:
                # restore the previous SIGTERM disposition as soon as
                # training ends: nothing after fit consults the lease on
                # a non-serving run, and a still-armed handler would
                # SWALLOW a preemption notice during eval/report.  With
                # --serve the lease stays armed through the serving
                # window (its should_stop hook drains it) and the outer
                # finally uninstalls (idempotent) afterwards.
                lease.uninstall()
        if config.grad_bucket_mb:
            # ride the fit result into the run report (None when the
            # probe was unsupported/failed — "measured 0" stays
            # distinguishable from "not measured")
            fit["collective_overlap"] = overlap_probe
        # preemption accounting (elastic/): the restore-side numbers ride
        # the fit result into the run report next to the fit-side ones
        # (preempted / resume_replay_steps / stragglers), and a drained
        # lease emits the structured `preempted` event an external
        # supervisor reads instead of finding a corpse
        if config.elastic_restore:
            fit["preemption_lost_s"] = preemption_lost
            fit["restored_step"] = restored_step
        if lease is not None:
            fit["lease"] = lease.report()
        if fit.get("preempted"):
            # the supervisor-protocol drain notice (utils/supervisor.py
            # ResultSink.preempted): an external harness sees a planned
            # ['preempted', reason, step] instead of a dead socket
            sink.preempted(fit["preempted"],
                           fit.get("start_step", 0) + fit["steps"])
        sink.done(fit["elapsed"])
        with tracer.span("eval", final=True):
            ev = trainer.evaluate(test_ds, batch_size=config.eval_batch)
        sink.results(ev["accuracy"], loss=ev["loss"])

        # the summary's engine label comes from the _setup_* function that
        # chose the engine (_Experiment.name) — re-deriving it here from
        # the config flags drifted from the dispatch table twice
        engine_name = ex.name
        total_devices = (n * config.seq_parallel * config.tensor_parallel
                         * config.pipeline_parallel * config.expert_parallel)
        model_name = config.model if config.model_fn is None else getattr(
            config.model_fn, "__name__", "custom_model_fn")
        mesh_devices = list(ex.mesh.devices.flat)
        summary = {
            "engine": engine_name,
            "model": model_name,
            # where the run executed, read from the mesh it built: a CPU
            # fallback shows here, at top level, not in a nested report
            "platform": mesh_devices[0].platform,
            "device_kind": mesh_devices[0].device_kind,
            "dataset": train_ds.name,
            "synthetic_data": train_ds.synthetic,
            # which producer fed the last epoch: the C++ prefetcher
            # ("native", needs g++ on the host) or the Python gather
            "input_pipeline": train_ds.input_path,
            "n_devices": total_devices,
            "data_parallel": n,
            "seq_parallel": config.seq_parallel,
            "tensor_parallel": config.tensor_parallel,
            "pipeline_parallel": config.pipeline_parallel,
            "expert_parallel": config.expert_parallel,
            "num_experts": (config.num_experts
                            if config.expert_parallel > 1 else None),
            "microbatches": (config.microbatches
                             if config.pipeline_parallel > 1 else None),
            "global_batch": global_batch,
            "epochs": config.epochs,
            "precision": fit.get("precision", config.precision),
            "steps": fit["steps"],
            # graceful-drain outcome: the lease reason when this run was
            # preempted (SIGTERM notice / --max-steps-per-lease), None on
            # a normal finish — relaunch with --elastic-restore
            "preempted": fit.get("preempted"),
            # resolved steady-state drain shape (auto may downshift to 1)
            "steps_per_call": fit.get("steps_per_call"),
            "prefetch_depth": fit.get("prefetch_depth"),
            "elapsed_s": fit["elapsed"],
            "examples_per_sec": fit["examples_per_sec"],
            "examples_per_sec_per_device": fit["examples_per_sec"] / total_devices,
            "test_accuracy": ev["accuracy"],
            "test_loss": ev["loss"],
            # next-token cross-entropy exponentiated = perplexity, the
            # standard LM quality number (reported only for LM models —
            # exp(classification loss) would be meaningless)
            **({"test_perplexity": float(np.exp(min(ev["loss"], 80.0)))}
               if config.model in _LM_MODELS else {}),
        }
        # expert-parallel runs surface the router-health watch (sustained
        # capacity overflow warns during training; the summary records it)
        monitor = getattr(ex.engine, "overflow_monitor", None)
        if monitor is not None:
            summary.update(monitor.report())
        if config.sample_tokens:
            summary.update(_sample_from_state(config, ex, trainer.state,
                                              test_ds))
        serve_sec = None
        if config.serve_requests:
            # the serve window rides the lease's SIGNAL hook only (budget
            # steps are a TRAINING budget — a budget-drained fit still
            # runs its cheap post-work, but a preemption notice drains
            # the serving loop too: stop admitting, finish in-flight,
            # flush the partial section into the report before exit)
            serve_stop = ((lambda _iters: lease.should_stop(0))
                          if lease is not None else None)
            serve_sec = _serve_from_state(config, ex, trainer.state,
                                          test_ds, tracer, total_devices,
                                          should_stop=serve_stop,
                                          timeline=timeline,
                                          ledger=ledger,
                                          roofline=roofline)
            summary["serve"] = serve_sec
            # supervisor exit policy: a serve window that lost requests
            # (unserved > 0 — lease drain, retry exhaustion, dead fleet)
            # or delivered a duplicate token must not bury it in the
            # middle of a summary — emit a structured warning event AND
            # a machine-checkable flag (0 = clean) so CI gates on it
            violations = []
            if serve_sec.get("unserved_requests"):
                violations.append(
                    f"unserved_requests="
                    f"{serve_sec['unserved_requests']}")
            if serve_sec.get("serve_duplicate_emissions"):
                violations.append(
                    f"duplicate_emissions="
                    f"{serve_sec['serve_duplicate_emissions']}")
            summary["serve_exit_policy"] = 1 if violations else 0
            if violations:
                tracer.event("serve_warning", reasons=violations,
                             preempted=serve_sec.get("preempted"))
                sink.emit("serve_warning", reasons=violations,
                          preempted=serve_sec.get("preempted"))
                print(f"warning: serve window degraded "
                      f"({', '.join(violations)}); "
                      f"serve_exit_policy=1", file=sys.stderr)
        # end-of-run report: steady-state percentiles split from compile,
        # chunk shapes actually used, watchdog/prefetch/sink health, and
        # the telemetry's own measured overhead (observability/report) —
        # emitted as its own event AND carried in the summary
        if metrics_logger is not None:
            # drain the async sink first: stats() read mid-drain would
            # report written < records, which reads as silent record loss
            metrics_logger.flush()
        if timeline is not None:
            # flush the sampled series into the trace file as bulk
            # `timeline_series` events — `analyze timeline` and the
            # Perfetto counter tracks render from the trace alone, no
            # run report needed
            timeline.emit(tracer)
        report = build_run_report(fit, watchdog=watchdog,
                                  metrics_logger=metrics_logger,
                                  tracer=tracer, serve=serve_sec,
                                  timeline=timeline, ledger=ledger,
                                  roofline=roofline, devices=mesh_devices)
        summary["run_report"] = report
        sink.emit("run_report", **report)
        sink.emit("summary", **summary)
        return summary
    finally:
        if lease is not None:
            # restore the previous SIGTERM disposition: a later run in
            # this process must not drain into THIS run's lease (kept
            # armed until here so the --serve window drains on it too)
            lease.uninstall()
        if ckpt_mgr is not None:
            # drain + join the checkpoint writer on ANY exit: a restart
            # (run_with_recovery) must never begin its restore with a
            # previous run's write still in flight.  reraise=False — the
            # normal path already surfaced writer errors at fit's final
            # drain, and the exception path must not mask its error.
            ckpt_mgr.close(reraise=False)
        if metrics_logger is not None:
            metrics_logger.close()  # drain + flush the async JSONL sink
        tracer.close()
        sink.close()


def _probe_collective_overlap(ex: _Experiment, global_batch: int, tracer):
    """One-time exposed-vs-hidden collective split for --grad-bucket-mb
    runs (parallel/overlap.probe_engine_overlap): spans the measurement as
    ``collective_overlap`` and emits the split as a ``collective_overlap``
    event.  Returns the split dict, or None when the engine has no probe
    (compiler-inserted collectives), the probe fails, or the job is
    multi-process (the probe's throwaway programs would have to rendezvous
    across hosts for no benefit) — a failed probe must never kill a
    training run, it only leaves the report's exposed/hidden keys None."""
    from distributed_tensorflow_tpu.parallel import overlap as overlaplib

    result = None
    error = None
    with tracer.span("collective_overlap", probe=True):
        try:
            if jax.process_count() > 1:
                error = "probe skipped on multi-process jobs"
            else:
                batch = None
                for bx, by, _bm in ex.train_ds.batches(global_batch,
                                                       shuffle=False):
                    batch = (bx, by)
                    break
                if batch is None:
                    error = "dataset yielded no probe batch"
                else:
                    xs, ys = ex.engine.shard_batch(*batch)
                    result = overlaplib.probe_engine_overlap(
                        ex.engine, xs, ys,
                        sample_x=ex.train_ds.x[: max(1, ex.n)])
                    if result is None:
                        error = ("engine has no overlap probe "
                                 "(compiler-inserted collectives)")
        except Exception as e:
            error = f"{type(e).__name__}: {e}"
    if result is None:
        tracer.event("collective_overlap", supported=False, error=error)
        return None
    tracer.event("collective_overlap", **result)
    return result


def _validate_sampling(config: ExperimentConfig, ex: _Experiment,
                       test_ds) -> None:
    """Every deterministically-knowable --sample failure is raised BEFORE
    training: a post-train ValueError would waste the whole run — and
    under --max-restarts it would be caught by run_with_recovery as a
    restartable crash and re-train up to max_restarts more times, failing
    identically after each."""
    from distributed_tensorflow_tpu.models.gpt import GPTLM, GPTPipeEmbed

    if config.sample_tokens < 0:
        raise ValueError(
            f"--sample must be positive, got {config.sample_tokens}")
    if _is_pipeline(ex.engine):
        # pipeline runs sample via the engine's sequential-forward decode
        # (engines/pipeline.py generate) — GPT stage families only
        if not isinstance(ex.engine.embed, GPTPipeEmbed):
            raise ValueError(
                f"--sample under --pipeline-parallel needs GPT decoder "
                f"stages (vocab-head output); this run's embed stage is "
                f"{type(ex.engine.embed).__name__}")
        if ex.engine.moe:
            # raised pre-train (a post-train raise would waste the run):
            # the fixed-length decode's padding-invisibility argument is a
            # causal-attention property — MoE routing's capacity-limited
            # dispatch sees the zero padding (engines/pipeline.py generate)
            raise ValueError(
                "--sample is unavailable for MoE pipeline stages "
                "(-pp with --num-experts): expert routing's capacity "
                "depends on every buffer position, so the fixed-length "
                "decode would not be the true greedy continuation — "
                "sample a dense-FFN pipeline run, or train MoE without "
                "-pp and use the KV-cache sampler")
        max_len = ex.engine.embed.max_len
    else:
        model = ex.engine.model
        if not isinstance(model, GPTLM):
            raise ValueError(
                f"--sample requires the GPT causal LM; the resolved model "
                f"is {type(model).__name__}")
        max_len = model.max_len
    plen = config.sample_prompt_len
    if plen < 1 or plen > test_ds.x.shape[1]:
        raise ValueError(
            f"--sample-prompt-len {plen} outside the test sequences' "
            f"length {test_ds.x.shape[1]}")
    if plen + config.sample_tokens > max_len:
        raise ValueError(
            f"--sample-prompt-len {plen} + --sample {config.sample_tokens} "
            f"exceeds the model's capacity max_len={max_len}")
    n_prompts = ex.mesh.shape.get(meshlib.DATA_AXIS, 1)
    if len(test_ds.x) < n_prompts:
        raise ValueError(
            f"--sample takes one prompt per data shard ({n_prompts}), but "
            f"the test split has only {len(test_ds.x)} rows")


def _sample_from_state(config: ExperimentConfig, ex: _Experiment, state,
                       test_ds) -> dict[str, Any]:
    """--sample N: greedy-decode N tokens per prompt from the trained
    params (models/gpt.py ``generate`` — KV-cache sampler; multi-device
    when the run's mesh has >1 device: batch over 'data', Megatron layout
    kept under a 'model' axis).

    Prompts are the first ``sample_prompt_len`` tokens of one test row per
    data-axis shard (divisibility with the 'data' axis by construction).
    Greedy, so the recorded continuation is a deterministic function of
    the final params — reproducible evidence of what the model learned,
    not a dice roll.  Engines whose state stacks per-device copies
    (async/gossip) are averaged first via their ``eval_params`` — the same
    consensus model their evaluation uses.  Pipeline engines decode via
    their sequential-forward ``generate`` (engines/pipeline.py) — stage
    params stay pipe-stacked; there is no KV cache to thread through the
    schedule.  Arguments were validated pre-train (_validate_sampling)."""
    from distributed_tensorflow_tpu.models.gpt import generate

    n_prompts = ex.mesh.shape.get(meshlib.DATA_AXIS, 1)
    prompts = np.asarray(test_ds.x[:n_prompts, :config.sample_prompt_len],
                         dtype=np.int32)
    if _is_pipeline(ex.engine):
        # engine.generate returns prompt+continuation; slice to the
        # continuation so 'samples' has ONE schema — (B, N) decoded
        # tokens — regardless of engine (models/gpt.py generate already
        # returns continuations only)
        full = np.asarray(ex.engine.generate(state, prompts,
                                             config.sample_tokens))
        toks = full[:, config.sample_prompt_len:]
    else:
        get_params = getattr(ex.engine, "eval_params", None)
        params = (get_params(state) if get_params is not None
                  else state.params)
        mesh = ex.mesh if ex.mesh.devices.size > 1 else None
        toks = np.asarray(generate(ex.engine.model, params, prompts,
                                   config.sample_tokens, greedy=True,
                                   mesh=mesh))
    return {
        "sample_prompts": prompts.tolist(),
        "samples": toks.tolist(),
    }


def parse_draft_config(spec: str) -> dict[str, int] | None:
    """``--serve-draft-config`` parser: the literal ``'self'`` → None
    (the draft IS the served model and shares its params — accept rate 1,
    the mechanism/parity configuration) or ``'key=int,...'`` GPT size
    overrides (hidden/layers/heads/ffn/kv_heads; vocab and max_len always
    inherit from the served model — draft proposals must be target
    tokens, and the draft mirrors every slot position)."""
    if spec == "self":
        return None
    allowed = ("ffn", "heads", "hidden", "kv_heads", "layers")
    out: dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, eq, val = part.partition("=")
        key = key.strip()
        if not eq or key not in allowed:
            raise ValueError(
                f"--serve-draft-config entries must be key=int with key "
                f"in {allowed} (or the literal 'self'); got '{part}' — "
                f"vocab/max_len inherit from the served model")
        try:
            out[key] = int(val)
        except ValueError:
            raise ValueError(
                f"--serve-draft-config value for '{key}' must be an "
                f"int, got '{val.strip()}'") from None
    if not out:
        raise ValueError(
            "--serve-draft-config needs at least one key=int override "
            "(or the literal 'self')")
    return out


def parse_disaggregate(spec: str) -> tuple[int, int]:
    """``--serve-disaggregate`` parser: ``'P:D'`` → (prefill_replicas,
    decode_replicas).  Both sides must be >= 1 — a disaggregated fleet
    needs somewhere to prefill AND somewhere to decode (the handoff has
    no same-replica fallback by design: falling back would silently
    reintroduce the prefill/decode interference the mode exists to
    remove)."""
    p_s, colon, d_s = spec.partition(":")
    try:
        if not colon:
            raise TypeError
        p, d = int(p_s), int(d_s)
    except (TypeError, ValueError):
        raise ValueError(
            f"--serve-disaggregate must be P:D (prefill:decode replica "
            f"counts, e.g. 1:2), got '{spec}'") from None
    if p < 1 or d < 1:
        raise ValueError(
            f"--serve-disaggregate needs at least one prefill and one "
            f"decode replica, got '{spec}'")
    return p, d


def _resolve_serve_kv_dtype(name: str):
    """``--serve-kv-dtype`` resolver: float dtype names via
    models.resolve_dtype, plus ``'int8'`` — the quantized slot table
    (int8 K/V + per-vector f32 scales, SlotKVCache kv_dtype)."""
    if name == "int8":
        return "int8"
    try:
        return modellib.resolve_dtype(name)
    except KeyError:
        raise ValueError(
            f"--serve-kv-dtype '{name}' unknown: float32/bfloat16/"
            f"float16 (and aliases) or int8") from None


def _validate_serving(config: ExperimentConfig, ex: _Experiment,
                      test_ds) -> None:
    """Pre-train validation of the --serve window (same contract as
    _validate_sampling: a post-train raise would waste the whole run and,
    under --max-restarts, re-train to fail identically)."""
    from distributed_tensorflow_tpu.models.gpt import GPTLM

    if config.serve_requests < 0:
        raise ValueError(
            f"--serve must be positive, got {config.serve_requests}")
    if config.serve_slots < 1:
        raise ValueError(
            f"--serve-slots must be positive, got {config.serve_slots}")
    if config.serve_max_new < 1:
        raise ValueError(
            f"--serve-max-new must be positive, got {config.serve_max_new}")
    if _is_pipeline(ex.engine):
        raise ValueError(
            "--serve needs flat GPTLM params for the slot KV cache; a "
            "pipeline engine's stage params are pipe-stacked — train "
            "without -pp (or restore the checkpoint into a non-pipeline "
            "layout) to serve")
    model = ex.engine.model
    if not isinstance(model, GPTLM):
        raise ValueError(
            f"--serve requires the GPT causal LM; the resolved model is "
            f"{type(model).__name__}")
    if config.serve_prefill_chunk < 0:
        raise ValueError(
            f"--serve-prefill-chunk must be >= 0 (0 = monolithic "
            f"prefill), got {config.serve_prefill_chunk}")
    if config.serve_prefix_cache < 0:
        raise ValueError(
            f"--serve-prefix-cache must be >= 0 (0 = off), got "
            f"{config.serve_prefix_cache}")
    if config.serve_prefix_block < 1:
        raise ValueError(
            f"--serve-prefix-block must be positive, got "
            f"{config.serve_prefix_block}")
    if config.serve_shared_prefix < 0:
        raise ValueError(
            f"--serve-shared-prefix must be >= 0, got "
            f"{config.serve_shared_prefix}")
    if config.serve_slo_ttft <= 0 or config.serve_slo_itl <= 0:
        raise ValueError(
            f"--serve-slo-ttft/--serve-slo-itl must be positive seconds, "
            f"got {config.serve_slo_ttft}/{config.serve_slo_itl}")
    if config.serve_queue_cap < 0:
        raise ValueError(
            f"--serve-queue-cap must be >= 0 (0 = unbounded admission), "
            f"got {config.serve_queue_cap}")
    if config.serve_draft_k < 1:
        raise ValueError(
            f"--serve-draft-k must be positive, got "
            f"{config.serve_draft_k}")
    if config.serve_draft_config is not None:
        # a malformed draft spec must fail BEFORE the training budget is
        # spent, like every other deterministically-knowable serve flag
        parse_draft_config(config.serve_draft_config)
    if config.serve_kv_dtype:
        _resolve_serve_kv_dtype(config.serve_kv_dtype)
    if config.serve_kv_layout not in ("monolithic", "paged"):
        raise ValueError(
            f"--serve-kv-layout must be 'monolithic' or 'paged', got "
            f"{config.serve_kv_layout!r}")
    if config.serve_paged_block < 0 or config.serve_paged_blocks < 0:
        raise ValueError(
            f"--serve-paged-block/--serve-paged-blocks must be >= 0, got "
            f"{config.serve_paged_block}/{config.serve_paged_blocks}")
    if config.serve_kv_layout != "paged" and (config.serve_paged_block
                                              or config.serve_paged_blocks):
        raise ValueError(
            "--serve-paged-block/--serve-paged-blocks need "
            "--serve-kv-layout paged")
    if config.serve_kv_layout == "paged":
        # the paged pool's fatal misconfigurations are all knowable
        # pre-train: block granularity must tile max_len, and with the
        # prefix pool on it must equal the prefix block (hits alias
        # physical blocks by pointer)
        block = config.serve_paged_block or config.serve_prefix_block
        if model.max_len % block:
            raise ValueError(
                f"--serve-paged-block {block} must divide the model's "
                f"max_len={model.max_len}")
        if (config.serve_prefix_cache and config.serve_paged_block
                and config.serve_paged_block != config.serve_prefix_block):
            raise ValueError(
                f"--serve-paged-block ({config.serve_paged_block}) must "
                f"equal --serve-prefix-block "
                f"({config.serve_prefix_block}) when the prefix pool is "
                f"on: pool hits alias physical blocks")
    if config.serve_replicas < 1:
        raise ValueError(
            f"--serve-replicas must be >= 1, got {config.serve_replicas}")
    n_fleet = max(config.serve_replicas, 1)
    if config.serve_disaggregate is not None:
        # round 18: --serve-disaggregate P:D builds a heterogeneous
        # fleet of P prefill + D decode replicas (overriding
        # --serve-replicas); the spec and its interactions are all
        # knowable pre-train
        p, d = parse_disaggregate(config.serve_disaggregate)
        n_fleet = p + d
        if config.serve_draft_config is not None:
            raise ValueError(
                "--serve-disaggregate cannot combine with "
                "--serve-draft-config: speculative decoding drafts in "
                "slot lockstep with its target table, which a KV "
                "handoff across replicas would break")
        if config.serve_hot_swap:
            raise ValueError(
                "--serve-disaggregate cannot combine with "
                "--serve-hot-swap: the swap drill drains replicas "
                "role-blind and could leave zero admitting prefill "
                "replicas")
    if config.serve_routing not in ("least-loaded", "affinity"):
        raise ValueError(
            f"--serve-routing must be 'least-loaded' or 'affinity', "
            f"got {config.serve_routing!r}")
    if config.serve_routing == "affinity" and not config.serve_prefix_cache:
        raise ValueError(
            "--serve-routing affinity keys on the prefix pool's block "
            "digests; enable --serve-prefix-cache (> 0) or use "
            "least-loaded routing")
    if config.serve_autoscale is not None:
        from distributed_tensorflow_tpu.serving.fleet import AutoscalePolicy

        # round 20: composes with --serve-disaggregate — the fleet
        # drives each role pool independently, clamping the MIN:MAX
        # range to the pool's size; only the homogeneous range is
        # checked against the whole fleet here
        policy = AutoscalePolicy.parse(config.serve_autoscale)
        n_max = policy.max_replicas or n_fleet
        if config.serve_disaggregate is None and n_max > n_fleet:
            raise ValueError(
                f"--serve-autoscale max ({n_max}) exceeds the built "
                f"fleet (--serve-replicas {n_fleet}): autoscale wakes "
                f"dormant replicas, it cannot build new ones")
    if config.serve_multi_step is not None and config.serve_multi_step < 1:
        raise ValueError(
            f"--serve-multi-step must be >= 1 fused decode iterations "
            f"per dispatch, got {config.serve_multi_step}")
    if config.serve_watchdog_s < 0:
        raise ValueError(
            f"--serve-watchdog must be >= 0 (0 = off), got "
            f"{config.serve_watchdog_s}")
    if config.serve_fault_spec:
        # fault grammar + replica bounds checked pre-train, like every
        # other deterministically-knowable serve flag
        from distributed_tensorflow_tpu.serving.fleet import FaultInjector

        for fault in FaultInjector.parse(config.serve_fault_spec):
            if fault.replica >= n_fleet:
                raise ValueError(
                    f"--serve-fault-spec targets replica {fault.replica} "
                    f"but the fleet has {n_fleet} replicas")
    plen = config.serve_prompt_len
    if plen < 1 or plen > test_ds.x.shape[1]:
        raise ValueError(
            f"--serve-prompt-len {plen} outside the test sequences' "
            f"length {test_ds.x.shape[1]}")
    total_prompt = plen + config.serve_shared_prefix
    if total_prompt + config.serve_max_new > model.max_len:
        raise ValueError(
            f"--serve-shared-prefix {config.serve_shared_prefix} + "
            f"--serve-prompt-len {plen} + --serve-max-new "
            f"{config.serve_max_new} exceeds the model's capacity "
            f"max_len={model.max_len}")


def _serve_from_state(config: ExperimentConfig, ex: _Experiment, state,
                      test_ds, tracer, total_devices: int,
                      should_stop=None, timeline=None,
                      ledger=None, roofline=None) -> dict[str, Any]:
    """--serve N: run a continuous-batching serving window over the
    trained params (serving/SlotKVCache + ContinuousBatcher) and return
    the run report's ``serve`` section.

    Prompts are test-split rows (``--serve-prompt-len`` tokens each,
    wrapping when N exceeds the split); arrivals are all-at-zero under the
    wall clock, so with N > slots the queue drains continuously as slots
    free — admission, eviction and queue wait are all exercised without
    sleeping, and TTFT percentiles include the queue time (BASELINE.md
    rule).  The slot table rides the run's mesh when its axes are the
    GSPMD serving set ({data, model}) and the slot count divides the data
    axis; otherwise it serves replicated.  Greedy decode: like --sample,
    the recorded window is a deterministic function of the final params.
    Engines whose state stacks per-device copies (async/gossip) serve
    their consensus ``eval_params``, same as evaluation and sampling.

    SLO observability (round 13): every window runs under an SLOMonitor
    (``--serve-slo-ttft``/``--serve-slo-itl``, p99 ITL per request) so the
    serve section always carries ``serve_goodput_under_slo`` and the
    p50/p95/p99 phase percentiles; ``--serve-queue-cap`` arms the
    bounded-admission overload mode.  ``should_stop`` is the lease-drain
    hook: a SIGTERM'd serve window stops admitting, finishes in-flight
    requests, and its partial section still flushes into the report."""
    from distributed_tensorflow_tpu.observability import (
        SLOMonitor, device_memory, serve_section)
    from distributed_tensorflow_tpu.serving import (
        ContinuousBatcher, Request, SlotKVCache)

    def section(summary):
        """The serve section, with each mesh device's memory read while
        the slot table is still alive: a table that shards over 'data'
        shows as bytes on every device, not on device 0 alone."""
        sec = serve_section(summary, total_devices, tracer=tracer)
        sec["device_memory"] = device_memory(ex.mesh.devices.flat)
        return sec

    get_params = getattr(ex.engine, "eval_params", None)
    params = get_params(state) if get_params is not None else state.params
    mesh = None
    if (ex.mesh.devices.size > 1
            and set(ex.mesh.axis_names) <= {meshlib.DATA_AXIS,
                                            meshlib.MODEL_AXIS}
            and config.serve_slots
            % ex.mesh.shape.get(meshlib.DATA_AXIS, 1) == 0):
        mesh = ex.mesh
    kv_dtype = None
    if config.serve_kv_dtype:
        # --serve-kv-dtype bfloat16: store the KV slot table in bf16 —
        # half the KV memory per slot (double the slots per chip at equal
        # HBM); greedy tokens stay oracle-exact on the shipped models
        # (tests/test_serving.py), the attention math still runs at the
        # model's compute dtype via promotion.  int8 halves bf16's
        # payload again (int8 K/V + per-vector f32 scales); token parity
        # vs the bf16 oracle is tolerance-based, not bitwise.
        kv_dtype = _resolve_serve_kv_dtype(config.serve_kv_dtype)
    # fleet mode (--serve-replicas / --serve-fault-spec / --serve-hot-
    # swap): N independent slot tables behind the ReplicaSet supervisor —
    # a fault spec or a hot-swap drill forces the fleet path even at one
    # replica, so the supervision/journal machinery is what gets tested.
    # Round 18's heterogeneous flags (--serve-disaggregate P:D roles,
    # --serve-routing affinity, --serve-autoscale MIN:MAX) are fleet
    # concepts, so any of them forces the fleet path too.
    roles = None
    if config.serve_disaggregate is not None:
        n_prefill, n_decode = parse_disaggregate(config.serve_disaggregate)
        roles = ["prefill"] * n_prefill + ["decode"] * n_decode
        n_replicas = n_prefill + n_decode
    else:
        n_replicas = max(config.serve_replicas, 1)
    fleet = (n_replicas > 1 or bool(config.serve_fault_spec)
             or config.serve_hot_swap or roles is not None
             or config.serve_routing != "least-loaded"
             or config.serve_autoscale is not None)
    kv_kwargs: dict[str, Any] = dict(
        mesh=mesh, kv_dtype=kv_dtype,
        prefix_cache_blocks=config.serve_prefix_cache,
        prefix_block=config.serve_prefix_block)
    if ledger is not None:
        # conditional-kwarg pattern (same as the paged block below): the
        # flag-off construction stays byte-identical, and with the ledger
        # on every kv jit site routes through ledger.jit — observed
        # compiles, memory_analysis captured, same executable dispatched
        kv_kwargs.update(ledger=ledger)
    if config.serve_kv_layout == "paged":
        # --serve-kv-layout paged: SlotKVCache's __new__ dispatches to
        # PagedSlotKVCache — refcounted block pool, zero-copy prefix
        # aliasing, fused Pallas decode attention.  The kwargs are only
        # passed under paged so the monolithic construction stays
        # byte-identical (program-set pin).
        kv_kwargs.update(kv_layout="paged",
                         paged_blocks=config.serve_paged_blocks,
                         paged_block=config.serve_paged_block)
    kv = SlotKVCache(ex.engine.model, params, config.serve_slots,
                     **kv_kwargs)
    # --roofline serve half: rebuild the cost model FROM THE KV TABLE so
    # the byte accounting reflects the layout actually serving (storage
    # dtype, paged blocks, measured param bytes) — the train-side model
    # knows none of that.  Device peaks / device count carry over.
    serve_roofline = None
    if roofline is not None:
        from distributed_tensorflow_tpu.observability.roofline import (
            Roofline)

        serve_roofline = Roofline.for_kv(
            kv, roofline.peaks.device_kind if roofline.peaks else None,
            total_devices)
    draft_kv = None
    if config.serve_draft_config:
        # --serve-draft-config: speculative decoding — the draft runs its
        # own full-precision SlotKVCache in slot lockstep with the target
        # table.  'self' shares the served model AND params (zero extra
        # param memory; the mechanism/parity configuration); a size spec
        # builds a fresh GPT at those dims (vocab/max_len inherited) from
        # the run seed — production use restores a trained draft here.
        import jax.numpy as jnp

        overrides = parse_draft_config(config.serve_draft_config)
        model = ex.engine.model
        if overrides is None:
            draft_model, draft_params = model, kv.params
        else:
            draft_model = modellib.create_model(
                "gpt", num_classes=int(model.vocab_size),
                max_len=int(model.max_len), dropout_rate=0.0,
                dtype=model.dtype, **overrides)
            dummy = jnp.zeros((1, min(8, int(model.max_len))), jnp.int32)
            draft_params = jax.jit(
                lambda k: draft_model.init(k, dummy, train=False)
            )(jax.random.key(config.seed))["params"]
        draft_kv = SlotKVCache(draft_model, draft_params,
                               config.serve_slots, mesh=mesh)
    rows = np.asarray(test_ds.x, np.int32)
    plen = config.serve_prompt_len
    # --serve-shared-prefix: a fixed synthetic system prompt every request
    # shares (deterministic from the run seed) — the traffic shape the
    # prefix pool exists for; with the pool on, every admission after the
    # first reuses the shared blocks instead of recomputing them
    shared = np.zeros(0, np.int32)
    if config.serve_shared_prefix:
        vocab = int(ex.engine.model.vocab_size)
        shared = np.random.default_rng(config.seed).integers(
            0, vocab, config.serve_shared_prefix).astype(np.int32)
    requests = [
        Request(rid=i,
                prompt=np.concatenate([shared, rows[i % len(rows), :plen]]),
                max_new_tokens=config.serve_max_new, arrival_s=0.0)
        for i in range(config.serve_requests)]
    slo = SLOMonitor(config.serve_slo_ttft, config.serve_slo_itl)
    if fleet:
        from distributed_tensorflow_tpu.serving.fleet import (
            FaultInjector, ReplicaSet, build_replica_kvs)

        if roles is None:
            kvs = [kv] + build_replica_kvs(
                ex.engine.model, kv.params, n_replicas - 1,
                config.serve_slots, **kv_kwargs)
        else:
            # disaggregated fleets keep the prefix pool prefill-side
            # only: decode replicas receive finished KV via handoff and
            # never prefill, so a warm pool there would be dead memory —
            # and the affinity router's hit accounting should reflect
            # where reuse can actually happen.  Replica 0 (the ``kv``
            # built above, pool included) is always a prefill replica
            # because roles lists prefills first.
            decode_kwargs = dict(kv_kwargs)
            decode_kwargs["prefix_cache_blocks"] = 0
            kvs = [kv]
            for role in roles[1:]:
                kvs += build_replica_kvs(
                    ex.engine.model, kv.params, 1, config.serve_slots,
                    **(kv_kwargs if role == "prefill" else decode_kwargs))
        draft_kvs = None
        if draft_kv is not None:
            draft_kvs = [draft_kv] + build_replica_kvs(
                draft_model, draft_kv.params, n_replicas - 1,
                config.serve_slots, mesh=mesh)
        injector = (FaultInjector(config.serve_fault_spec,
                                  seed=config.seed)
                    if config.serve_fault_spec else None)
        fleet_kwargs: dict[str, Any] = {}
        if roles is not None:
            # conditional-kwarg pattern (same as the paged block above):
            # the round-17 fleet construction stays byte-identical when
            # the round-18 flags are off
            fleet_kwargs.update(roles=roles)
        if config.serve_routing != "least-loaded":
            fleet_kwargs.update(routing=config.serve_routing)
        if config.serve_autoscale is not None:
            fleet_kwargs.update(autoscale=config.serve_autoscale)
        if config.serve_multi_step is not None:
            fleet_kwargs.update(multi_step=config.serve_multi_step)
        replica_set = ReplicaSet(
            kvs, tracer=tracer,
            prefill_chunk=config.serve_prefill_chunk,
            queue_cap=config.serve_queue_cap, slo=slo,
            draft_kvs=draft_kvs, draft_k=config.serve_draft_k,
            watchdog_timeout_s=config.serve_watchdog_s,
            fault_injector=injector, timeline=timeline,
            roofline=serve_roofline, **fleet_kwargs)
        if config.serve_hot_swap:
            # the drill: re-install the SAME trained params after half
            # the window — proves drain + swap_generations + N-1
            # availability with greedy tokens unchanged; a real rollout
            # passes new checkpoint params here
            replica_set.schedule_swap(
                params, after_completions=max(config.serve_requests // 2,
                                              1))
        with tracer.span("serve", requests=config.serve_requests,
                         slots=config.serve_slots, replicas=n_replicas):
            try:
                summary = replica_set.run(requests,
                                          should_stop=should_stop)
            finally:
                replica_set.close()
        return section(summary)
    batcher_kwargs: dict[str, Any] = {}
    if config.serve_multi_step is not None:
        # conditional-kwarg pattern: the round-19 batcher construction
        # stays byte-identical with the flag off
        batcher_kwargs.update(multi_step=config.serve_multi_step)
    with tracer.span("serve", requests=config.serve_requests,
                     slots=config.serve_slots):
        summary = ContinuousBatcher(
            kv, tracer=tracer,
            prefill_chunk=config.serve_prefill_chunk,
            slo=slo,
            queue_cap=config.serve_queue_cap,
            should_stop=should_stop,
            draft_kv=draft_kv, draft_k=config.serve_draft_k,
            timeline=timeline,
            roofline=serve_roofline, **batcher_kwargs).run(requests)
    return section(summary)


def steps_to_accuracy(
    config: ExperimentConfig,
    target: float,
    max_steps: int = 10_000,
    eval_every: int = 50,
) -> dict[str, Any]:
    """Steps-to-target measurement (BASELINE.md north star: steps-to-97%).

    Counts *global* batches, the normalization BASELINE.md requires when
    comparing against the reference's sequential-apply sync PS
    (SURVEY.md §2.4(1)).  Runs through ``Trainer.fit`` — ONE training loop
    in the codebase, so the measured path gets the hardened loop's
    throttling/nan-guard for free — with adaptive eval cadence: every
    ``eval_every`` steps far from the target, every ≤10 steps once within
    0.05 of it, so the returned step count has ≤10-step resolution.
    """
    import math

    from distributed_tensorflow_tpu.engines.allreduce import Trainer

    if config.schedule_horizon_steps is None:
        # this loop runs up to max_steps, far past config.epochs — an
        # epochs-derived LR horizon would decay to 0 almost immediately and
        # the target would silently never be reached
        config = dataclasses.replace(config,
                                     schedule_horizon_steps=max_steps)
    ex = _setup(config)
    trainer = Trainer(None, engine=ex.engine, seed=config.seed)
    steps_per_epoch = max(len(ex.train_ds) // ex.global_batch, 1)
    epochs = math.ceil(max_steps / steps_per_epoch) + 1

    t0 = time.perf_counter()
    fit = trainer.fit(
        ex.train_ds, epochs=epochs, batch_size=ex.global_batch, log_every=0,
        max_steps=max_steps, eval_ds=ex.test_ds, target_accuracy=target,
        eval_every=eval_every, eval_batch=config.eval_batch,
        # steps_per_call auto-downshifts to 1 under target_accuracy (the
        # steps-to-target resolution IS the per-step cadence); an explicit
        # config value still passes through for chunk-boundary eval
        steps_per_call=config.steps_per_call, prefetch=config.prefetch)
    return {
        "reached": bool(fit["reached_target"]),
        "steps": fit["steps"],
        "accuracy": fit["eval_accuracy"],
        "elapsed_s": time.perf_counter() - t0,
        # measured, not assumed: the gap between the crossing eval and the
        # one before it (a >0.05 jump between coarse evals is resolved at
        # eval_every, not 10)
        "step_resolution": fit["eval_resolution"],
        "synthetic": bool(getattr(ex.train_ds, "synthetic", False)),
    }
