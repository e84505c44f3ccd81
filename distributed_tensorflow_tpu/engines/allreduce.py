"""Keras-fit-like Trainer — the dist_keras replacement.

The reference's decentralized 'keras' mode wraps training in
`strategy.scope(); model.compile(); model.fit(epochs=1); model.evaluate()`
(reference dist_keras.py:22-58).  This Trainer offers the same ergonomics
over any engine (default: SyncEngine, whose `pmean` *is* the RING allreduce,
reference dist_keras.py:77-78), with the timing window around fit() matching
the reference's elapsed metric (reference dist_keras.py:41-43).
"""

from __future__ import annotations

import math
import time
from typing import Callable

import jax
import numpy as np

from distributed_tensorflow_tpu.data.device_prefetch import DevicePrefetch
from distributed_tensorflow_tpu.engines.sync import SyncEngine
from distributed_tensorflow_tpu.utils.metrics import StepTimer

# steady-state chunk length when no per-step cadence demands step-granular
# host control (see Trainer.resolve_steps_per_call)
DEFAULT_STEPS_PER_CALL = 8


class Trainer:
    def __init__(self, model, engine=None, mesh=None, learning_rate: float = 1e-3,
                 seed: int = 0, max_in_flight: int = 4, **engine_kw):
        self.engine = engine if engine is not None else SyncEngine(
            model, mesh=mesh, learning_rate=learning_rate, **engine_kw)
        self.model = self.engine.model
        self.seed = seed
        # Bound async dispatch: without a sync point the host enqueues the
        # whole epoch; on oversubscribed hosts (1-core CI with an 8-device
        # fake mesh) queued partitions can miss XLA's 40s collective
        # rendezvous timeout.  Costs nothing on real TPUs.
        self.max_in_flight = max_in_flight
        self.state = None
        self.history: list[dict] = []

    @staticmethod
    def resolve_steps_per_call_with_reason(
            steps_per_call: int | None, *,
            metrics_logger=None, watchdog=None,
            target_accuracy: float | None = None,
            checkpoint_every: int = 0,
            checkpoint_async: bool = False) -> tuple[int, str | None]:
        """(k, clamp_reason) — ``resolve_steps_per_call`` plus WHY auto
        mode downshifted ('target_accuracy' | 'checkpoint_sync' |
        'checkpoint_async' | None).  The reason comes from the SAME branch
        that picked k, so the run report's clamp attribution cannot desync
        from the resolution rules.  The two checkpoint reasons share one
        rule (the crash-loss window is a durability promise either way)
        but are reported distinctly: a synchronous sub-chunk cadence also
        costs a blocking save per chunk — worth a warning — while an
        overlapped save costs only a snapshot, so the async label tells
        the report reader the clamp is cadence-only, not a stall."""
        del metrics_logger, watchdog  # telemetry rides the chunked drain
        if steps_per_call is not None:
            if steps_per_call < 1:
                raise ValueError(
                    f"steps_per_call must be >= 1, got {steps_per_call}")
            return int(steps_per_call), None
        if target_accuracy is not None:
            return 1, "target_accuracy"
        if 0 < checkpoint_every < DEFAULT_STEPS_PER_CALL:
            return checkpoint_every, ("checkpoint_async" if checkpoint_async
                                      else "checkpoint_sync")
        return DEFAULT_STEPS_PER_CALL, None

    @staticmethod
    def resolve_steps_per_call(steps_per_call: int | None, *,
                               metrics_logger=None, watchdog=None,
                               target_accuracy: float | None = None,
                               checkpoint_every: int = 0) -> int:
        """Chunk length of the steady-state drain (``fit(steps_per_call=)``).

        An explicit value wins (validated ≥ 1).  Auto (``None``) picks
        ``DEFAULT_STEPS_PER_CALL`` unless a per-step cadence demands the
        host between every step:

        * ``target_accuracy`` — downshifts to 1: the near-target eval
          cadence (≤10 steps) IS the steps-to-target figure's resolution
          (BASELINE.md), and evals need boundary state every step.

        Telemetry does NOT downshift (the zero-downshift contract,
        observability/):

        * ``metrics_logger`` — per-step records ride the scan's stacked
          trajectory and are flushed to the async JSONL sink once per
          chunk, step-exact and bitwise identical to k=1;
        * ``watchdog`` — beats once per chunk flush with its stall budget
          rescaled to ``k × per-step timeout`` (Watchdog.rescale): k×
          coarser detection resolution, k× fewer host syncs;
        * heartbeat logging (``log_every``) — the drain returns the full
          per-step trajectory each chunk, so log lines stay step-exact.

        A ``checkpoint_every`` shorter than the chunk caps auto's k to it
        (state only exists at chunk boundaries, and silently saving
        k-coarser than asked would widen the crash-loss window); with an
        EXPLICIT steps_per_call, checkpoints land on the first chunk
        boundary at/after their due step instead.  ``metrics_logger`` and
        ``watchdog`` stay in the signature so call sites document what
        rides along, but no longer affect the result.
        """
        del metrics_logger, watchdog  # telemetry rides the chunked drain
        return Trainer.resolve_steps_per_call_with_reason(
            steps_per_call, target_accuracy=target_accuracy,
            checkpoint_every=checkpoint_every)[0]

    def fit(self, train_ds, epochs: int = 1, batch_size: int | None = None,
            log_every: int = 50, log_fn: Callable[[str], None] = print,
            checkpoint_manager=None, checkpoint_every: int = 0,
            metrics_logger=None, watchdog=None, nan_guard: bool = True,
            max_steps: int | None = None, eval_ds=None,
            target_accuracy: float | None = None, eval_every: int = 50,
            eval_batch: int = 100, steps_per_call: int | None = None,
            prefetch: int = 2, tracer=None,
            on_anomaly: str = "warn",
            should_stop: Callable[[int], str | None] | None = None,
            data_state: dict | None = None,
            straggler_detector=None, timeline=None, roofline=None) -> dict:
        """Train; returns {'elapsed': seconds_around_fit, 'steps': n, ...} —
        the reference's only training metrics (reference dist_keras.py:41-49).

        ``checkpoint_manager``/``checkpoint_every``: periodic TrainState
        checkpoints (+ one final); ``metrics_logger``: per-step JSONL sink.
        ``watchdog``: a utils.failure.Watchdog — beaten once per loop
        iteration (the throttle keeps the loop within max_in_flight of
        device progress, so a hung device stops the beats within that
        window and the watchdog's on_stall callback fires).
        ``nan_guard``: divergence check on metrics already materialized at
        the logging cadence (no extra device syncs; utils/failure.py).
        When the engine's health layer is on (``Engine.enable_health`` /
        ``--health on``) the per-step anomaly policy SUBSUMES this
        loss-only guard: every step's on-device health stats (grad norm,
        update ratio, non-finite leaf count, loss spike —
        observability/health.py) are checked host-side at chunk flush,
        and ``on_anomaly`` decides the response — ``'warn'`` records
        structured ``anomaly`` trace events and a ``health`` summary in
        the result, ``'halt'`` additionally raises ``AnomalyDetected`` at
        the offending step.  (At ``steps_per_call == 1`` the policy
        materializes each step's metrics — step-exact detection at the
        cost of a per-step host sync; the chunked drain keeps the
        zero-downshift contract.)
        ``tracer``: an observability.Tracer — spans ``compile`` /
        ``chunk_dispatch`` / ``materialize`` / ``checkpoint`` / ``eval``
        plus prefetch queue-depth gauges at chunk boundaries; defaults to
        the process-wide ``recorder()`` (records in memory, no file; pass
        NULL_TRACER to record nothing).  Only ``span``, ``event`` and
        ``gauge`` are called on it.  An async checkpoint manager
        (utils/checkpoint.AsyncCheckpointManager) replaces the blocking
        ``checkpoint`` span with ``ckpt_snapshot`` (training-thread
        blocked time: previous-write backpressure + device snapshot) and
        ``ckpt_write`` (the background Orbax write, emitted by the writer
        thread) — the fit result then splits the cost as
        ``checkpoint_wait_s`` (charged against throughput) vs
        ``checkpoint_overlapped_s`` (hidden behind training).
        ``max_steps``: hard step cap across epochs.  ``target_accuracy``
        (with ``eval_ds``): early-stop when test accuracy reaches the
        target — evaluated every ``eval_every`` steps far from the target
        and every ≤10 steps once within 0.05 of it, so the steps-to-target
        figure (BASELINE.md north star) has ≤10-step resolution without
        paying full-eval cost on every step.  The result then carries
        ``reached_target`` and ``eval_accuracy``.

        Elastic hooks (distributed_tensorflow_tpu/elastic/):
        ``should_stop(steps_done) -> reason | None`` is consulted at every
        chunk boundary (each step at k=1) — a truthy reason finishes the
        in-flight chunks, writes the final checkpoint (data state
        included) and returns with ``result['preempted'] = reason``: the
        graceful lease drain, composing with ``steps_per_call > 1`` by
        construction.  ``data_state`` (a checkpoint's elastic sidecar
        payload, possibly ``{}``) positions the batch stream for an
        exactly-once resume: a matching state continues the identical
        batch sequence at its (epoch, batch) and the result reports
        ``resume_replay_steps = 0``; a missing/mismatched state restarts
        the stream from epoch 0 and reports the unrecoverable positions
        (``resume_replay_steps = start_step``) — pass ``None`` (default)
        for the legacy non-elastic resume with no accounting.  Every
        checkpoint this fit writes carries its own data state + save wall
        time as the elastic sidecar, read-ahead drained/discounted (the
        position is the step counter, never the prefetch producer).
        ``straggler_detector`` (elastic.StragglerDetector) observes the
        per-chunk step times the loop already measures and emits
        structured ``straggler`` trace events on outliers; its summary
        rides the result as ``stragglers``.
        ``roofline`` (observability/roofline.Roofline, ``--roofline``):
        analytic model-FLOPs attribution — the result gains
        ``train_model_flops_per_step`` / ``train_achieved_flops_per_sec``
        / ``train_mfu`` (None when the model family or device kind is
        outside the analytic tables — a peak is never invented), and the
        chunked drain samples a per-chunk ``achieved_flops_per_sec``
        gauge on the ``--timeline`` series at the boundaries it already
        syncs.  With ``roofline=None`` (default) the result key set is
        byte-identical to round 18 — the parity pin.

        Steady state: host batches are staged onto the mesh ``prefetch``
        batches ahead (data/device_prefetch.py — transfer N+1 overlaps
        compute N), and ``steps_per_call`` > 1 drains chunks of k
        pre-staged batches through one jitted ``lax.scan`` of the engine's
        train step (``Engine.build_many_step``), with the per-step
        loss/accuracy trajectory carried on-device and materialized once
        per chunk — and, when no chunk-boundary state consumer (periodic
        checkpoints, target eval) is active, up to ``max_in_flight``
        dispatched chunks stay unmaterialized so a slow host↔device link
        is paid per window, not per chunk.  Default auto:
        ``resolve_steps_per_call`` — 8, unless ``target_accuracy``
        downshifts to 1 or a shorter ``checkpoint_every`` caps it;
        telemetry (metrics_logger, watchdog) rides the chunked drain
        without downshifting.  Checkpoint/eval/early-stop/
        nan-guard semantics hold at chunk boundaries; the chunked
        trajectory is step-for-step identical to ``steps_per_call=1`` on
        the same seed.
        """
        from distributed_tensorflow_tpu.observability import health as healthlib
        from distributed_tensorflow_tpu.observability.trace import recorder
        from distributed_tensorflow_tpu.utils.failure import (
            AnomalyDetected, check_finite)
        if tracer is None:
            tracer = recorder()
        if on_anomaly not in ("warn", "halt"):
            raise ValueError(
                f"on_anomaly must be 'warn' or 'halt', got '{on_anomaly}'")
        # health policy state: the engine's health layer (enable_health)
        # carries the per-step stats; the anomaly decisions live here.
        # With health on, the loss-only nan_guard's CADENCE checks are
        # subsumed — but its fail-fast SEMANTIC survives as the alias:
        # divergence ('nonfinite' anomalies) stays fatal under
        # on_anomaly='warn' unless nan_guard was explicitly disabled, so
        # adding --health never silently downgrades a NaN'd run from
        # abort to train-to-completion.  'halt' makes every anomaly kind
        # fatal; 'warn' + nan_guard=False observes only (MIGRATING.md).
        health_cfg = getattr(self.engine, "health", None)
        guard_divergence = nan_guard
        nan_guard = nan_guard and health_cfg is None
        h_max: dict = {}
        anomaly_steps: list[int] = []
        first_anomaly = None
        n_anomalies = 0
        warned_anomaly = False
        # mixed-precision policy (engine --precision; parallel/precision.py)
        # — the fit result names it, and a loss-scaling policy gets its
        # per-step skip accounting surfaced: every skipped (non-finite-
        # grad) step becomes a structured `loss_scale` tracer event, and
        # the nan-guard's fatal-divergence response is WAIVED for that
        # step — the scaler already handled the overflow (backoff + no
        # update), which is the whole point of fp16-f32master
        precision_pol = getattr(self.engine, "precision", None)
        precision_name = getattr(precision_pol, "name", "f32")
        ls_active = bool(getattr(precision_pol, "loss_scaling", False))
        ls_skipped_steps: list[int] = []
        ls_n_skipped = 0
        ls_last_scale = None
        warned_skip = False

        def note_loss_scale(gstep: int, floats: dict) -> None:
            """Per-step loss-scale bookkeeping over materialized floats:
            record the running scale and turn each skipped step into a
            structured trace event (the observable half of the grow/
            backoff loop)."""
            nonlocal ls_last_scale, warned_skip, ls_n_skipped
            scale = floats.get("loss_scale")
            if scale is not None:
                ls_last_scale = scale
            if not floats.get("ls_skipped"):
                return
            ls_n_skipped += 1
            if len(ls_skipped_steps) < 64:  # bounded like anomaly_steps
                ls_skipped_steps.append(gstep)
            tracer.event("loss_scale", step=gstep, action="backoff_skip",
                         scale=scale)
            if not warned_skip:
                warned_skip = True
                log_fn(f"step {gstep}  LOSS-SCALE SKIP (non-finite grads; "
                       f"scale backed off to {scale}) — continuing")

        def note_health(gstep: int, floats: dict) -> None:
            """Per-step anomaly policy over materialized health floats:
            update the run maxima, emit one structured ``anomaly`` trace
            event per offending stat, and on 'halt' raise at THIS step —
            the metrics record was already logged (record first, so the
            diverging step's numbers reach the sink)."""
            nonlocal first_anomaly, n_anomalies, warned_anomaly
            for stat in ("grad_norm", "update_ratio", "loss_spike"):
                v = floats.get(stat)
                if v is not None and math.isfinite(v):
                    h_max[stat] = max(h_max.get(stat, v), v)
            anomalies = healthlib.detect_anomalies(floats, health_cfg)
            if not anomalies:
                return
            n_anomalies += len(anomalies)
            if first_anomaly is None:
                first_anomaly = gstep
            if len(anomaly_steps) < 64:  # bounded: a NaN'd run flags every
                anomaly_steps.append(gstep)  # step until it ends
            for a in anomalies:
                tracer.event("anomaly", step=gstep, policy=on_anomaly, **a)
            a = anomalies[0]
            if floats.get("ls_skipped"):
                # the loss scaler already answered this step's non-finite
                # gradients (skip + backoff — note_loss_scale recorded the
                # structured event): halting or raising here would defeat
                # fp16 training, where occasional overflow is EXPECTED and
                # handled.  The anomaly events above still reach the
                # trace, so nothing is silent.
                return
            if on_anomaly == "halt":
                raise AnomalyDetected(
                    f"health anomaly at step {gstep}: {a['stat']}="
                    f"{a['value']} ({a['reason']}; limit {a['limit']}) — "
                    f"halted by on_anomaly='halt'")
            diverged = [x for x in anomalies if x["kind"] == "nonfinite"]
            if guard_divergence and diverged:
                # the nan_guard alias: divergence is fatal even under
                # 'warn' (now step-exact, vs the old log-cadence check);
                # --no-nan-guard opts into observe-only
                d = diverged[0]
                raise AnomalyDetected(
                    f"training diverged at step {gstep}: {d['stat']}="
                    f"{d['value']} ({d['reason']}) — fatal under the "
                    f"nan-guard default; pass nan_guard=False "
                    f"(--no-nan-guard) to record and continue")
            if not warned_anomaly:
                warned_anomaly = True
                log_fn(f"step {gstep}  ANOMALY {a['stat']}={a['value']} "
                       f"({a['reason']}) — continuing under "
                       f"on_anomaly='warn'")
        if target_accuracy is not None and eval_ds is None:
            raise ValueError("target_accuracy requires eval_ds (nothing "
                             "would ever be evaluated against the target)")
        if prefetch < 1:
            # same contract as DevicePrefetch itself: reject, don't clamp
            # (a silently-promoted --prefetch 0 would misreport its depth)
            raise ValueError(f"prefetch depth must be >= 1, got {prefetch}")
        eng = self.engine
        bs = batch_size or train_ds.batch_size or 32
        bs = max(bs, eng.n_devices)
        bs = (bs // eng.n_devices) * eng.n_devices
        # process-sharded input (multi-host): this process's dataset holds
        # 1/P of the examples, so it iterates LOCAL batches of bs/P rows and
        # each step's global batch is assembled from every process's rows
        # (Engine.shard_batch process_local).  Shards are even (.shard
        # even=True), so all processes run the same number of steps — a
        # batch-count mismatch would wedge the collectives.
        shard = getattr(train_ds, "process_shard", None)
        n_procs = shard[1] if shard else 1
        if n_procs > 1:
            if n_procs != jax.process_count():
                # a mismatched shard count would feed
                # make_array_from_process_local_data wrongly-sized rows
                # (multi-process) or silently shrink the global batch to
                # one shard (single-process)
                raise ValueError(
                    f"dataset is sharded {n_procs} ways but this job has "
                    f"{jax.process_count()} process(es); shard with "
                    f"n_shards == process_count (Dataset.process_shard_of)")
            if bs % n_procs:
                # keep BOTH divisibilities: round to a multiple of
                # lcm(n_devices, n_procs) so per-device sharding survives
                unit = math.lcm(eng.n_devices, n_procs)
                bs = max((bs // unit) * unit, unit)
            local_bs = bs // n_procs
        else:
            local_bs = bs
        if self.state is None:
            rng = jax.random.key(self.seed)
            sample = train_ds.x[: max(1, eng.n_devices)]
            self.state = eng.init_state(rng, sample)
        # global step offset: nonzero after a checkpoint --resume, so metric
        # records and checkpoint cadence continue the original numbering
        # instead of restarting at 1
        # (.reshape(-1)[0]: async engine's step is per-device, one per shard)
        start_step = int(np.asarray(jax.device_get(self.state.step)).reshape(-1)[0])
        # exactly-once data resume (elastic/data_state.py): a restored
        # checkpoint's data state positions the batch stream at the exact
        # (epoch, batch) the saved step had consumed, so the resumed run
        # continues the IDENTICAL batch sequence — None (default) keeps
        # the legacy resume (stream restarts at epoch 0, no accounting);
        # a dict that fails to match this run's seed/batch-size/dataset
        # falls back to the same restart but REPORTS the unrecoverable
        # positions as resume_replay_steps
        start_epoch = 0
        start_batch = 0
        replay_steps = None
        if data_state is not None:
            from distributed_tensorflow_tpu.elastic.data_state import (
                DataState)

            restored_ds = DataState.from_json(data_state)
            if restored_ds is not None and restored_ds.matches(
                    seed=self.seed, batch_size=local_bs,
                    dataset_len=len(train_ds),
                    dataset=getattr(train_ds, "name", "dataset")):
                start_epoch, start_batch = (restored_ds.epoch,
                                            restored_ds.batch_index)
                replay_steps = 0
            else:
                replay_steps = start_step
                if start_step:
                    log_fn(f"elastic resume: checkpoint carries no "
                           f"matching data state — the batch stream "
                           f"restarts from epoch 0 "
                           f"(resume_replay_steps={start_step})")
        # async checkpoint discipline (utils/checkpoint.py
        # AsyncCheckpointManager): saves cost the training thread a device
        # snapshot; the write overlaps the next chunks on a background
        # writer.  The manager's writer emits ckpt_write spans through the
        # fit tracer so the timeline shows blocked vs overlapped time.
        ckpt_async = bool(getattr(checkpoint_manager, "asynchronous", False))
        if ckpt_async:
            checkpoint_manager.tracer = tracer
        ckpt_wait = 0.0  # training-thread seconds spent in checkpointing
        ckpt_last_step = None  # skip a final save the cadence already wrote
        # a manager may outlive a fit: report THIS fit's
        # overlapped seconds, not the manager's lifetime total
        ckpt_overlap0 = getattr(checkpoint_manager, "overlapped_s", 0.0)
        # batch-stream position of the CURRENT epoch, maintained by the
        # epoch loop: cur_epoch's stream started at epoch_offset and
        # epoch_base was the step counter then, so the boundary position
        # is epoch_offset + (steps - epoch_base) — the step counter, not
        # the prefetch producer, which is how read-ahead gets discounted
        cur_epoch = start_epoch
        epoch_base = 0
        epoch_offset = start_batch
        last_data_state = None

        def current_data_state() -> dict:
            from distributed_tensorflow_tpu.elastic.data_state import (
                DataState)

            return DataState(
                epoch=cur_epoch,
                batch_index=epoch_offset + (steps - epoch_base),
                seed=self.seed, batch_size=local_bs,
                dataset_len=len(train_ds),
                dataset=getattr(train_ds, "name", "dataset")).to_json()

        def do_checkpoint(step: int, final: bool = False) -> None:
            """One boundary checkpoint, both disciplines: sync blocks for
            the full write under a ``checkpoint`` span; async pays only
            the snapshot (+ any previous-write backpressure) under
            ``ckpt_snapshot`` — the final save additionally drains, so fit
            never returns with a write in flight.  Every write carries
            the elastic sidecar (data state + save wall time) that makes
            the checkpoint a resumable object."""
            nonlocal ckpt_wait, ckpt_last_step, last_data_state
            t0 = time.perf_counter()
            # the final boundary often IS the last cadence boundary (steps
            # divisible by checkpoint_every): that state is already saved
            # — or in flight — so re-writing it would only re-pay the full
            # write; the final call then just drains
            skip_write = final and step == ckpt_last_step
            if not skip_write:
                last_data_state = current_data_state()
                extra = {"data_state": last_data_state,
                         "wall_time": time.time(), "step": step,
                         "schema": 1}
            # the boundary step is known here — passing it spares save()
            # its state.step device sync on the training thread
            if ckpt_async:
                attrs = {"step": step, **({"final": True} if final else {})}
                with tracer.span("ckpt_snapshot", **attrs):
                    if not skip_write:
                        checkpoint_manager.save(self.state, step=step,
                                                extra=extra)
                    if final:
                        checkpoint_manager.wait()
            elif not skip_write:
                with tracer.span("checkpoint", step=step,
                                 **({"final": True} if final else {})):
                    jax.block_until_ready(self.state)
                    checkpoint_manager.save(self.state, step=step,
                                            extra=extra)
            ckpt_last_step = step
            ckpt_wait += time.perf_counter() - t0

        k, clamp_reason = self.resolve_steps_per_call_with_reason(
            steps_per_call, metrics_logger=metrics_logger, watchdog=watchdog,
            target_accuracy=target_accuracy,
            checkpoint_every=(checkpoint_every
                              if checkpoint_manager is not None else 0),
            checkpoint_async=ckpt_async)
        # surface auto-mode downshifts (the run report carries the reason,
        # attributed by the resolver itself; SYNC checkpoint clamps
        # additionally warn — the shortened chunk also costs a blocking
        # save per chunk, whereas an async clamp is cadence-only — and an
        # explicit steps_per_call is never clamped, checkpoints then land
        # on chunk boundaries)
        spc_clamp = None
        if clamp_reason is not None:
            spc_clamp = {"requested": DEFAULT_STEPS_PER_CALL,
                         "effective": k, "reason": clamp_reason}
            if clamp_reason == "checkpoint_sync":
                import warnings

                warnings.warn(
                    f"checkpoint_every={checkpoint_every} caps the "
                    f"steady-state drain at steps_per_call={k} (auto "
                    f"default {DEFAULT_STEPS_PER_CALL}): state exists only "
                    f"at chunk boundaries, so the requested crash-loss "
                    f"window shortens the chunk — and each boundary pays a "
                    f"blocking synchronous save.  Pass an explicit "
                    f"--steps-per-call to keep longer chunks (checkpoints "
                    f"then land on the first boundary at/after each due "
                    f"step), or use the async checkpoint manager to take "
                    f"the save off the critical path.", stacklevel=2)
        if watchdog is not None:
            # one beat per host sync = one beat per chunk: the per-step
            # stall budget becomes a per-beat budget of k × timeout, so
            # the watchdog rides the chunked drain instead of forcing k=1
            watchdog.rescale(k)
        # --roofline: analytic model FLOPs of one optimizer step (grad-
        # accum invariant — K microbatches sum to the same tokens).  The
        # cost model covers the GPT family only; a 2-D token batch is the
        # shape it describes, anything else keeps the honest None.
        rf_flops_step = None
        if roofline is not None and roofline.cost is not None:
            xshape = np.shape(train_ds.x)
            if len(xshape) == 2:
                rf_flops_step = roofline.cost.train_step_flops(
                    bs, int(xshape[1]),
                    grad_accum=int(getattr(eng, "grad_accum", 1) or 1))
        grad_bytes = eng.grad_collective_bytes(self.state)        # wire
        grad_bytes_raw = eng.grad_collective_bytes_raw(self.state)
        # per-device state footprint (Engine.param_bytes_per_device /
        # opt_state_bytes_per_device): the storage numbers the precision
        # policy moves — bf16 storage halves param bytes, a master policy
        # grows optimizer bytes by the f32 copy.  Measured off the real
        # shard sizes, reported in the run report and gated lower-is-
        # better by `analyze diff`.
        param_bytes_dev = eng.param_bytes_per_device(self.state)
        opt_bytes_dev = eng.opt_state_bytes_per_device(self.state)
        grad_codec = getattr(getattr(eng, "grad_codec", None), "name", "none")
        # overlap bucketing (parallel/overlap.py): 0.0 when the codec is
        # unbucketed — the wire figure above is then per-leaf, else
        # per-bucket (the honest int8 scale accounting)
        grad_bucket_mb = float(getattr(
            getattr(eng, "grad_codec", None), "bucket_mb", 0.0) or 0.0)
        if grad_bytes:
            # WIRE bytes one gradient collective moves per round under the
            # engine's --grad-compression codec, plus the raw (f32-era)
            # figure for comparison — the collective-path size every
            # scaling analysis starts from (param dtypes are real)
            tracer.event("collective_profile",
                         grad_allreduce_bytes=grad_bytes,
                         grad_allreduce_bytes_raw=grad_bytes_raw,
                         grad_compression=grad_codec,
                         grad_bucket_mb=grad_bucket_mb,
                         n_devices=eng.n_devices)
        timer = StepTimer()
        t0 = time.perf_counter()
        steps = 0
        examples = 0
        last_metrics = {}
        in_flight: list = []
        eval_acc = 0.0
        reached = False
        stop = False
        preempted = None     # should_stop's reason once the drain fires
        compiled = False     # first dispatch carries the XLA compile —
        chunk_sizes: set[int] = set()  # its span is named 'compile'
        pf_starvation = 0    # prefetch gauges accumulated across epochs
        pf_fill_wait = 0.0
        prev_eval_step = 0   # step of the eval BEFORE the current one —
        eval_gap = None      # the honest resolution of a reached target

        def place(batch):
            # staged with the engine's input NamedSharding; device_put is
            # non-blocking, so the prefetcher's read-ahead IS the overlap
            bx, by, _mask = batch
            return self.engine.shard_batch(bx, by, process_local=n_procs > 1)

        def eval_and_maybe_stop(prev_steps: int, at_cap: bool) -> bool:
            """Target-accuracy eval at the cadence boundary (shared by both
            drain shapes); True = target reached, stop now.  Fine cadence
            when the answer could be near: the first window (fast-saturating
            tasks cross before a coarse first eval) and once accuracy is
            within 0.05 of the target; coarse in between.  Always evaluates
            at the cap so hitting max_steps can't return a stale (or
            never-computed) accuracy."""
            nonlocal eval_acc, prev_eval_step, eval_gap, reached, stop
            if target_accuracy is None or eval_ds is None:
                return False
            near = (eval_acc >= target_accuracy - 0.05 or steps <= eval_every)
            cadence = max(min(eval_every, 10) if near else eval_every, 1)
            # crossing test, not modulo: chunk boundaries may step past the
            # due step without landing on it (k == 1 reduces to steps%cadence)
            if not (steps // cadence > prev_steps // cadence or at_cap):
                return False
            gap = steps - prev_eval_step
            prev_eval_step = steps
            with tracer.span("eval", step=steps):
                eval_acc = self.evaluate(
                    eval_ds, batch_size=eval_batch)["accuracy"]
            if eval_acc >= target_accuracy:
                # the crossing lies somewhere in the gap since the previous
                # eval — report THAT as the steps-to-target resolution
                eval_gap = gap
                reached = stop = True
                return True
            return False

        def record_step(gstep: int, floats_fn) -> None:
            """Per-step sinks shared by both drain shapes: metrics-logger
            (log FIRST — a diverging step's NaN record must reach the sink
            before check_finite raises), then the log_every heartbeat with
            its nan guard.  ``floats_fn`` materializes the step's float
            metrics lazily: the k==1 path must not sync the device unless
            a cadence actually fires (max_in_flight keeps it async)."""
            nonlocal last_metrics
            if metrics_logger is not None and metrics_logger.should_log(gstep):
                floats = floats_fn()
                metrics_logger.log(gstep, **floats)
                if nan_guard:
                    check_finite(floats, gstep)
            if log_every and steps % log_every == 0:
                m = floats_fn()
                if nan_guard:
                    check_finite(m, gstep)
                last_metrics = m
                # progress heartbeat — reference client.py:92-94
                log_fn(f"step {gstep}  loss {m['loss']:.4f}"
                       f"  acc {m['accuracy']:.4f}")

        # A failed fit (AnomalyDetected halt, divergence, watchdog abort
        # path, a raising engine) must not leak background work: the
        # prefetcher is closed by its per-epoch finally below, and the
        # except block drains the async checkpoint writer and flushes the
        # telemetry sinks before the error propagates — no writer thread
        # or half-buffered JSONL record outlives the fit.  The cleanup
        # never masks the original error: the drain runs reraise=False
        # and the flushes swallow their own failures.
        try:
            for epoch in range(start_epoch, epochs):
                if stop:
                    break
                # mid-epoch resume: only the FIRST resumed epoch starts at
                # the restored batch offset; the shuffle permutation is a
                # function of (seed, epoch) alone, so the stream continues
                # the exact sequence the uninterrupted run would have
                ebatch = start_batch if epoch == start_epoch else 0
                cur_epoch, epoch_base, epoch_offset = epoch, steps, ebatch
                pf = DevicePrefetch(
                    train_ds.batches(local_bs, shuffle=True, seed=self.seed,
                                     epoch=epoch, drop_remainder=True,
                                     start_batch=ebatch),
                    place, depth=prefetch)
                try:
                    if k == 1:
                        for xs, ys in pf:
                            chunk_sizes.add(1)  # per ACTUAL dispatch: a
                            # zero-batch epoch must not report a chunk shape
                            with timer:  # amortized dispatch+throttle time
                                if not compiled:
                                    # first dispatch traces+compiles the step
                                    # synchronously — span it under the name
                                    # the run report splits out
                                    with tracer.span("compile", steps=1):
                                        self.state, metrics = eng.step(
                                            self.state, xs, ys)
                                    compiled = True
                                else:
                                    self.state, metrics = eng.step(
                                        self.state, xs, ys)
                                in_flight.append(metrics)
                                if len(in_flight) > self.max_in_flight:
                                    jax.block_until_ready(in_flight.pop(0))
                            if watchdog is not None:
                                # beat AFTER dispatch+throttle: the first beat
                                # arms the clock past the first-step XLA compile,
                                # and throttling bounds how far this loop runs
                                # ahead of the device, so a hung collective stops
                                # the beats within the window
                                watchdog.beat()
                            steps += 1
                            gstep = start_step + steps
                            examples += bs  # global examples per step
                            if straggler_detector is not None:
                                # the amortized dispatch+throttle time just
                                # appended — the k=1 rendering of the
                                # per-chunk average the drain observes
                                straggler_detector.observe(
                                    gstep, timer.times[-1])
                            dev_metrics = metrics
                            if health_cfg is not None or ls_active:
                                # the anomaly/loss-scale policy needs this
                                # step's values: materialize now (per-step
                                # sync — the honest cost of step-exact
                                # detection at k=1; the chunked drain pays
                                # one sync per chunk)
                                floats = {kk: float(v)
                                          for kk, v in dev_metrics.items()}
                                record_step(gstep, lambda f=floats: f)
                                if ls_active:
                                    note_loss_scale(gstep, floats)
                                if health_cfg is not None:
                                    note_health(gstep, floats)
                            else:
                                record_step(gstep, lambda: {
                                    kk: float(v) for kk, v in dev_metrics.items()})
                            if checkpoint_manager is not None and \
                                    checkpoint_every and \
                                    gstep % checkpoint_every == 0:
                                do_checkpoint(gstep)
                            if should_stop is not None:
                                # graceful drain: every step IS a chunk
                                # boundary at k=1 — the final checkpoint
                                # (data state included) runs at loop exit
                                reason = should_stop(steps)
                                if reason:
                                    preempted = reason
                                    stop = True
                                    break
                            at_cap = max_steps is not None and steps >= max_steps
                            if eval_and_maybe_stop(steps - 1, at_cap):
                                break
                            if at_cap:
                                stop = True
                                break
                    else:
                        # chunk-level in-flight window — the chunk rendering of
                        # the k==1 path's max_in_flight throttle: without
                        # chunk-boundary STATE consumers (periodic checkpoints,
                        # target eval — which auto mode downshifts for anyway)
                        # up to max_in_flight dispatched chunks stay
                        # unmaterialized, so the host↔device round trip is
                        # paid once per window, not per chunk, and the
                        # device always has queued work.  With state consumers,
                        # window 0: every chunk flushes eagerly at its boundary
                        # so checkpoint/eval see exactly the boundary state.
                        # should_stop (the lease drain) is a chunk-boundary
                        # STATE consumer too: its decision must see flushed
                        # boundary state, so it forces the eager window
                        window = (self.max_in_flight
                                  if checkpoint_manager is None
                                  and target_accuracy is None
                                  and should_stop is None else 0)
                        in_flight_chunks: list = []  # (n_steps, t_disp, stacked)
                        t_mark = 0.0  # end of the previous flush (timing ref)

                        def flush_chunk():
                            """Materialize the oldest dispatched chunk — ONE
                            host sync for its (k,)-stacked per-step trajectory —
                            and run its per-step bookkeeping."""
                            nonlocal steps, examples, metrics, last_metrics, \
                                t_mark
                            n_chunk, t_disp, stacked = in_flight_chunks.pop(0)
                            with tracer.span("materialize", steps=n_chunk):
                                floats = {kk: np.asarray(jax.device_get(v))
                                          for kk, v in stacked.items()}
                            # chunk boundary: prefetch queue-depth/starvation
                            # gauges ride the same host sync
                            tracer.gauge("prefetch_depth", pf.queue_depth,
                                         starvation=pf.starvation)
                            now = time.perf_counter()
                            # per-step wall time as the chunk average over the
                            # non-overlapped span (the first chunk smears its
                            # XLA compile over its k entries)
                            dt = (now - max(t_disp, t_mark)) / n_chunk
                            t_mark = now
                            if timeline is not None:
                                # --timeline: chunk step-time + prefetch
                                # depth series at the SAME boundary the
                                # gauges above use — no extra syncs
                                tl_vals = {"chunk_step_time_s": dt,
                                           "prefetch_depth": pf.queue_depth}
                                if rf_flops_step is not None and dt > 0:
                                    # --roofline: the per-chunk achieved
                                    # model-flops rate on the same series
                                    tl_vals["achieved_flops_per_sec"] = \
                                        rf_flops_step / dt
                                timeline.sample_many(tl_vals,
                                                     group="trainer")
                            timer.times.extend([dt] * n_chunk)
                            if straggler_detector is not None:
                                # per-chunk average step time vs the
                                # running median (elastic/stragglers.py);
                                # labeled with the chunk's last step
                                straggler_detector.observe(
                                    start_step + steps + n_chunk, dt)
                            if watchdog is not None:
                                # flush beat: real device progress confirmed
                                # (the stall budget is k × per-step timeout —
                                # Watchdog.rescale above)
                                watchdog.beat()
                            for i in range(n_chunk):
                                steps += 1
                                gstep = start_step + steps
                                examples += bs  # global examples per step
                                m = {kk: float(v[i]) for kk, v in floats.items()}
                                metrics = m
                                record_step(gstep, lambda m=m: m)
                                if ls_active:
                                    note_loss_scale(gstep, m)
                                if health_cfg is not None:
                                    note_health(gstep, m)

                        dispatched = steps
                        next_chunk = pf.take(k if max_steps is None
                                             else min(k, max_steps - dispatched))
                        while not stop and next_chunk:
                            chunk = next_chunk
                            t_disp = time.perf_counter()
                            span_name = "chunk_dispatch" if compiled \
                                else "compile"
                            with tracer.span(span_name, steps=len(chunk)):
                                self.state, stacked = eng.many_step(
                                    self.state, [c[0] for c in chunk],
                                    [c[1] for c in chunk])
                            if not compiled:
                                # the first chunk smears its XLA compile over
                                # its k per-step time entries — tell the timer
                                # where steady state starts
                                timer.compile_steps = len(chunk)
                                compiled = True
                            if watchdog is not None:
                                # beat at dispatch too, not only at flush: the
                                # first dispatch's synchronous trace+compile is
                                # behind us here, so this arms the clock BEFORE
                                # the first flush — a device that hangs inside
                                # the first window would otherwise never arm an
                                # arm_on_first_beat watchdog (dispatches are
                                # bounded by the in-flight window, so a hung
                                # device still stops the beats within it)
                                watchdog.beat()
                            chunk_sizes.add(len(chunk))
                            dispatched += len(chunk)
                            in_flight_chunks.append((len(chunk), t_disp, stacked))
                            # assemble chunk N+1 while the device runs chunk N
                            # (dispatch above is async): host batch prep
                            # overlaps device compute
                            nxt = k if max_steps is None else min(
                                k, max_steps - dispatched)
                            next_chunk = pf.take(nxt) if nxt > 0 else []
                            while len(in_flight_chunks) > window:
                                chunk_start = steps
                                flush_chunk()
                                if window:
                                    continue
                                # eager boundary: state consumers run with
                                # self.state == the just-flushed boundary state
                                if checkpoint_manager is not None and \
                                        checkpoint_every and \
                                        (start_step + steps) // checkpoint_every \
                                        > (start_step + chunk_start) // checkpoint_every:
                                    # first chunk boundary at/after the due step
                                    do_checkpoint(start_step + steps)
                                if should_stop is not None:
                                    # graceful drain at the chunk boundary:
                                    # the in-flight chunk finished (it was
                                    # just flushed); remaining dispatched
                                    # chunks drain below and the final
                                    # checkpoint runs at loop exit
                                    reason = should_stop(steps)
                                    if reason:
                                        preempted = reason
                                        stop = True
                                        break
                                at_cap = (max_steps is not None
                                          and steps >= max_steps)
                                # evaluated at chunk boundaries (auto mode runs
                                # k=1 under target_accuracy, so boundary == step)
                                if eval_and_maybe_stop(chunk_start, at_cap):
                                    break
                        # epoch end (or early stop): drain the window in order
                        while in_flight_chunks:
                            flush_chunk()
                        if not stop and should_stop is not None:
                            # window > 0 fallback (no other state consumer):
                            # the drained epoch end is still a boundary
                            reason = should_stop(steps)
                            if reason:
                                preempted = reason
                                stop = True
                        if max_steps is not None and steps >= max_steps:
                            stop = True
                finally:
                    # the prefetcher read ahead of the consumer: release the
                    # source (a native batcher's busy claim) deterministically,
                    # folding its gauges into the run totals first
                    pf_starvation += pf.starvation
                    pf_fill_wait += pf.fill_wait_s
                    pf.close()
            if (target_accuracy is not None and eval_ds is not None
                    and not reached and steps and prev_eval_step != steps):
                # loop ended by exhausting epochs (not the cap): still finish
                # with a real eval so eval_accuracy is never stale/uncomputed
                eval_gap = steps - prev_eval_step
                eval_acc = self.evaluate(eval_ds, batch_size=eval_batch)["accuracy"]
                reached = eval_acc >= target_accuracy
                if not reached:
                    eval_gap = None
            jax.block_until_ready(self.state)
            if nan_guard and steps:
                final = {k: float(v) for k, v in metrics.items()}
                check_finite(final, start_step + steps)
                last_metrics = last_metrics or final
            elapsed = time.perf_counter() - t0
            if checkpoint_manager is not None:
                # final=True drains the async writer too: fit never returns
                # (or hands state to a resume) with a write still in flight
                do_checkpoint(start_step + steps, final=True)
        except BaseException:
            if checkpoint_manager is not None:
                try:
                    checkpoint_manager.wait(reraise=False)
                except Exception:
                    pass
            for _sink in (metrics_logger, tracer):
                _flush = getattr(_sink, "flush", None)
                if _flush is not None:
                    try:
                        _flush()
                    except Exception:
                        pass
            raise
        # --roofline: achieved model flops/s over the whole fit window
        # (compile included — the honest end-to-end number; the per-chunk
        # timeline gauge shows steady state) and its MFU against the
        # fleet peak.  None device kind / None cost model → None MFU.
        rf_achieved = (rf_flops_step * steps / elapsed
                       if rf_flops_step and steps and elapsed > 0 else None)
        result = {
            "elapsed": elapsed, "steps": steps, "epochs": epochs,
            # resolved drain shape (tests/tools read these back: auto mode
            # downshifts steps_per_call to 1 under target_accuracy)
            "steps_per_call": k, "prefetch_depth": prefetch,
            # chunk lengths actually dispatched (tail chunks, max_steps
            # truncation and the auto resolution all show up here)
            "chunk_sizes": sorted(chunk_sizes),
            # input-path gauges (run-report fodder): hand-offs with zero
            # read-ahead left, and seconds blocked on host batch production
            "prefetch_starvation": pf_starvation,
            "prefetch_fill_wait_s": pf_fill_wait,
            **({"grad_allreduce_bytes": grad_bytes,
                "grad_allreduce_bytes_raw": grad_bytes_raw,
                "grad_compression": grad_codec,
                "grad_bucket_mb": grad_bucket_mb} if grad_bytes else {}),
            # mixed-precision policy + the per-device storage footprint it
            # moves (parallel/precision.py; f32 reports the same keys so
            # trajectories stay comparable across policies)
            "precision": precision_name,
            "param_bytes_per_device": param_bytes_dev,
            "opt_state_bytes_per_device": opt_bytes_dev,
            # dynamic loss scaling (fp16-f32master): skip accounting — the
            # scaler's grow/backoff story, mirrored from the per-step
            # loss_scale/ls_skipped metrics riding the scan
            **({"loss_scale": {
                "final_scale": ls_last_scale,
                "skipped_steps": ls_n_skipped,
                "skipped_step_list": ls_skipped_steps,
            }} if ls_active else {}),
            # checkpoint cost accounting (MLPerf-style: blocked time is
            # charged against throughput, overlapped time is not):
            # checkpoint_wait_s = training-thread seconds inside save/
            # drain calls; checkpoint_overlapped_s = background-writer
            # seconds that ran concurrently with training (0.0 sync)
            **({"checkpoint_wait_s": ckpt_wait,
                "checkpoint_overlapped_s": (
                    getattr(checkpoint_manager, "overlapped_s", 0.0)
                    - ckpt_overlap0),
                "checkpoint_async": ckpt_async}
               if checkpoint_manager is not None else {}),
            **({"steps_per_call_clamp": spc_clamp} if spc_clamp else {}),
            # graceful-drain outcome (elastic/lease.py): the should_stop
            # reason when a lease ended the fit, None on a normal finish
            "preempted": preempted,
            # exactly-once resume accounting (only when this fit WAS an
            # elastic resume — data_state given): steps whose data
            # position could not be restored (0 = exact resume)
            **({"resume_replay_steps": replay_steps}
               if data_state is not None else {}),
            # step-time outlier summary (elastic/stragglers.py)
            **({"stragglers": straggler_detector.report()}
               if straggler_detector is not None else {}),
            # the batch-stream position of the LAST checkpoint written —
            # what its elastic sidecar carries
            **({"data_state": last_data_state}
               if last_data_state is not None else {}),
            **({"watchdog_beats": watchdog.beats,
                "watchdog_stalls": watchdog.stall_episodes}
               if watchdog is not None else {}),
            # numeric-health summary (engine health layer on): run maxima
            # of the per-step stats plus the anomaly record — the section
            # the run report carries forward
            **({"health": {
                "on_anomaly": on_anomaly,
                "anomalies": n_anomalies,
                "anomaly_steps": anomaly_steps,
                "first_anomaly_step": first_anomaly,
                "max_grad_norm": h_max.get("grad_norm"),
                "max_update_ratio": h_max.get("update_ratio"),
                "max_loss_spike": h_max.get("loss_spike"),
            }} if health_cfg is not None else {}),
            "start_step": start_step, "examples": examples,
            "examples_per_sec": examples / elapsed if elapsed > 0 else 0.0,
            **({"reached_target": reached, "eval_accuracy": eval_acc,
                "eval_resolution": eval_gap}
               if target_accuracy is not None else {}),
            # per-step wall times.  steps_per_call == 1: first_step_s
            # isolates XLA compile, steady percentiles measure dispatch
            # pace (device-throughput-bound once the max_in_flight window
            # fills).  Chunked drain: entries are per-chunk AVERAGES, so
            # the first chunk smears its compile over its k entries —
            # compare step_time only between runs of equal steps_per_call
            "step_time": timer.summary(),
            # --roofline (flag-on keys only — flag-off parity is pinned):
            # analytic model flops per step, the achieved rate, and MFU
            # normalized over n_devices × the peak-table peak (None on an
            # unknown device kind or a non-GPT model — never invented)
            **({"train_model_flops_per_step": rf_flops_step,
                "train_achieved_flops_per_sec": rf_achieved,
                "train_mfu": roofline.mfu(rf_achieved),
                "roofline_peak_table_revision": roofline.revision}
               if roofline is not None else {}),
            **{f"final_{k}": v for k, v in last_metrics.items()},
        }
        self.history.append(result)
        return result

    def evaluate(self, test_ds, batch_size: int = 100) -> dict:
        """Full-test-set eval (reference parity: server.py:179-180)."""
        return self.engine.evaluate(self.state, test_ds, batch_size)
