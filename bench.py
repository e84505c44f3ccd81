#!/usr/bin/env python
"""Benchmarks. Default mode prints ONE JSON line for the driver:

  {"metric": "...", "value": N, "unit": "...", "vs_baseline": null, ...}

Modes:
  python bench.py               throughput + MFU of the flagship MNIST CNN
  python bench.py --stream      input pipeline: fresh host batches per step,
                                C++ prefetcher vs pure Python vs resident
  python bench.py --attention   flash (Pallas) vs dense (XLA) attention

Measurement protocol:

* The headline number is **device-bound**: training steps are rolled into
  one jitted ``lax.scan`` so Python dispatch is out of the measured window,
  and two window lengths (``SCAN_SHORT``/``SCAN_LONG``) are differenced so
  any fixed per-call overhead cancels.  The differenced window repeats
  ``REPEATS`` times and the **median** is reported with its min-max spread.
  The scan unit is the PRODUCTION program — ``Engine.build_many_step``,
  the same jitted drain ``Trainer.fit`` dispatches ``steps_per_call``
  chunks through — not a bench-private reimplementation; the long window
  chains unit calls exactly like the ``--attention`` protocol (the calls
  pipeline on-device, so per-call overhead both overlaps and cancels in
  the difference).
* ``dispatch_value`` is the steady-state rate of the SHIPPED ``Trainer.fit``
  loop itself (device-prefetched fresh host batches + the ``steps_per_call=8``
  scanned drain), replacing the old resident-batch Python-dispatch loop it
  descends from — the production counterpart of the scan headline.
* **MFU** uses an analytic FLOPs model of the training step (3× forward for
  backward, conv+dense matmul FLOPs only — the standard accounting) against
  the chip's bf16 peak, detected from ``jax.devices()[0].device_kind``.
  XLA's own cost analysis is reported alongside as a cross-check.
* The reference publishes no numbers (BASELINE.md §published: none) and
  no baseline of ours has been recorded on the chip, so ``vs_baseline`` is
  ``null``.
* A mode that fails exits non-zero with its traceback; nothing is caught
  and reported as a skip.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

import numpy as np

WARMUP_STEPS = 5
DISPATCH_STEPS = 32  # Trainer-path window: 4 full steps_per_call=8 chunks
SCAN_SHORT = 100     # differenced windows: per-step = (t_long − t_short) /
SCAN_LONG = 2100     # (SCAN_LONG − SCAN_SHORT); any fixed per-call overhead
                     # cancels
REPEATS = 5
# overridable for smoke runs (tests invoke --stream with a tiny batch so the
# bench harness itself is exercised in CI without TPU-scale compute)
PER_CHIP_BATCH = int(os.environ.get("BENCH_PER_CHIP_BATCH", "512"))

def peak_flops(device_kind: str) -> float | None:
    """Peak bf16 matmul FLOPs/s per chip — delegates to the shared
    observability peak table (observability/roofline.py, the single place
    public chip figures and their revision live since round 19).  Same
    contract as always: None for an unknown device_kind, never an
    invented peak."""
    from distributed_tensorflow_tpu.observability.roofline import (
        device_peaks)

    peaks = device_peaks(device_kind)
    return peaks.flops_per_s["bf16"] if peaks is not None else None


def _rf_revision() -> int:
    """Peak-table revision riding every MFU/MBU-bearing bench line — the
    BASELINE.md rule: an MFU claim is only comparable when the peak it was
    divided by is versioned alongside it."""
    from distributed_tensorflow_tpu.observability.roofline import (
        PEAK_TABLE_REVISION)

    return PEAK_TABLE_REVISION


def cnn_train_flops_per_example(shape=(28, 28, 1), features=(32, 64),
                                dense=128, num_classes=10) -> float:
    """Analytic FLOPs for one training example of models/cnn.py: conv and
    dense matmul FLOPs (2·MACs) for the forward pass, ×3 for fwd+bwd (the
    backward pass costs ~2× forward — standard MFU accounting)."""
    h, w, c = shape
    fwd = 0.0
    for feat in features:
        fwd += 2.0 * h * w * feat * 9 * c  # 3×3 SAME conv
        c, h, w = feat, h // 2, w // 2     # 2×2 max-pool
    fwd += 2.0 * (h * w * c) * dense + 2.0 * dense * num_classes
    return 3.0 * fwd


def _median_spread(vals: list[float]) -> tuple[float, float]:
    """(median, relative spread).  Spread is the interquartile range over the
    median when n≥5 (robust to an occasional outlier window), max-min over
    median otherwise."""
    med = statistics.median(vals)
    if not med:
        return med, 0.0
    if len(vals) >= 5:
        q = statistics.quantiles(vals, n=4)
        return med, (q[2] - q[0]) / med
    return med, (max(vals) - min(vals)) / med


def _sync(tree) -> None:
    """Completion barrier: materialize one leaf's bytes on the host.  The
    returned leaf of the last step depends on the whole chain, so the
    fetch cannot return before the chain has run."""
    import jax

    np.asarray(jax.device_get(jax.tree.leaves(tree)[0]))


def measure_windows(fn, repeats: int) -> list:
    """``repeats`` measurement windows; ``fn(rep)`` returns one window's
    value.  A window that fails fails the bench."""
    return [fn(rep) for rep in range(repeats)]


@contextlib.contextmanager
def _bench_checkpointing(fit_kw: dict, checkpoint_every: int):
    """--checkpoint-every N: arm ``fit_kw`` with an N-step async
    checkpoint cadence into a throwaway dir, so the Trainer window's JSON
    line carries the blocked-vs-overlapped seconds split (the durability
    cost actually charged against throughput).  Teardown (writer join +
    dir removal) runs even when a benched fit raises — a failed bench
    must not leak TrainState checkpoints under /tmp or a live writer
    thread.  No-op when ``checkpoint_every`` is 0."""
    if not checkpoint_every:
        yield None
        return
    import shutil
    import tempfile

    from distributed_tensorflow_tpu.utils.checkpoint import (
        AsyncCheckpointManager)

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    ckpt_mgr = AsyncCheckpointManager(ckpt_dir)
    fit_kw.update(checkpoint_manager=ckpt_mgr,
                  checkpoint_every=checkpoint_every)
    try:
        yield ckpt_mgr
    finally:
        # reraise=False: fit's own final drain already surfaced writer
        # errors on the normal path; the failure path must not mask
        ckpt_mgr.close(reraise=False)
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _probe_elastic_resume(ckpt_mgr, eng, sample_x, *, seed: int,
                          batch_size: int, dataset_len: int,
                          dataset: str):
    """Elastic resume probe (--checkpoint-every): restore the benched
    window's last checkpoint through the elastic restore path
    (elastic/reshard.py) and account the resume exactly the way a real
    preempted relaunch would — ``preemption_lost_s`` is the save→resume
    wall gap and ``resume_replay_steps`` is 0 iff the checkpoint's data
    state describes the benched stream (an exactly-once resume), else
    the restored step count (everything would replay).  These ride the
    bench line next to the checkpoint split, gated lower-is-better by
    `analyze diff` like the run report's copies.  Any failure Nones the
    keys — a probe must never kill the bench line."""
    import jax

    from distributed_tensorflow_tpu import elastic as elasticlib

    try:
        template = eng.init_state(jax.random.key(0), sample_x)
        state, extra = elasticlib.elastic_restore(ckpt_mgr, eng, template)
        step = int(np.asarray(jax.device_get(state.step)).reshape(-1)[0])
        ds_state = elasticlib.DataState.from_json(
            (extra or {}).get("data_state"))
        exact = ds_state is not None and ds_state.matches(
            seed=seed, batch_size=batch_size, dataset_len=dataset_len,
            dataset=dataset)
        return {"preemption_lost_s": elasticlib.preemption_lost_s(extra),
                "resume_replay_steps": 0 if exact else step,
                "restored_step": step}
    except Exception as e:  # noqa: BLE001 — the probe must not kill the bench
        print(f"[bench] elastic resume probe failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return None


def _train_step_ledger_probe(eng, state, xs, ys):
    """Train-step memory/compile accounting (observability/xla_stats):
    AOT-compile the engine's jitted step once, time the compile, read the
    executable's ``memory_analysis`` through a ProgramLedger.  Returns
    ``(peak_hbm_bytes_est, compile_total_s, compiled)`` — all None on any
    failure (private ``_step_fn``, exotic engines); a probe must never
    kill the bench line.  The compiled executable is returned so callers
    reuse it (cost_analysis) at zero extra compiles."""
    try:
        from distributed_tensorflow_tpu.observability import ProgramLedger

        t0 = time.perf_counter()
        compiled = eng._step_fn.lower(state, xs, ys).compile()
        ledger = ProgramLedger()
        ledger.capture("train_step", compiled,
                       compile_s=time.perf_counter() - t0)
        manifest = ledger.manifest()
        return (manifest["peak_hbm_bytes_est"] or None,
                round(manifest["compile_total_s"], 6), compiled)
    except Exception:
        return None, None, None


# ---------------------------------------------------------------------------
# default mode: training throughput + MFU
# ---------------------------------------------------------------------------

def _bench_model_and_engine(ds, mesh, grad_compression: str,
                            grad_bucket_mb: float, precision: str):
    """Model + SyncEngine of the training benches, precision-policy
    aware: a non-f32 ``--precision`` builds the model at the policy's
    compute dtype (the same dtype-follows-policy rule as the harness)
    and threads the policy into the engine — param storage, optimizer
    layout and the emitted bytes keys all reflect it."""
    from distributed_tensorflow_tpu.engines import SyncEngine
    from distributed_tensorflow_tpu.models import create_model
    from distributed_tensorflow_tpu.parallel import precision as precisionlib

    policy = precisionlib.make_policy(precision)
    kw = {}
    if policy.active:
        kw["dtype"] = policy.compute_dtype
    model = create_model("cnn", num_classes=ds.num_classes, **kw)
    eng = SyncEngine(model, mesh=mesh, grad_compression=grad_compression,
                     grad_bucket_mb=grad_bucket_mb, precision=precision)
    return model, eng


def bench_throughput(grad_compression: str = "none",
                     health: str = "off",
                     checkpoint_every: int = 0,
                     grad_bucket_mb: float = 0.0,
                     precision: str = "f32") -> None:
    import jax

    from distributed_tensorflow_tpu.data.loaders import load_dataset
    from distributed_tensorflow_tpu.parallel import mesh as meshlib

    mesh = meshlib.create_mesh()
    device_kind = mesh.devices.flat[0].device_kind
    n = mesh.shape[meshlib.DATA_AXIS]
    global_batch = PER_CHIP_BATCH * n

    ds = load_dataset("mnist", split="train")
    # measured f32 by default: for this small CNN (1 input channel, 28×28)
    # the bf16 cast overhead outweighs MXU-rate gains — 1.73M vs 2.19M
    # ex/s/chip on v5e.  --precision bf16/bf16-f32master switches the
    # whole stack (storage + compute + reduce) and the line reports the
    # policy + per-device bytes so the trajectory stays attributable.
    model, eng = _bench_model_and_engine(ds, mesh, grad_compression,
                                         grad_bucket_mb, precision)
    if health == "on":
        # before init_state: the optimizer tree gains its capture slots
        eng.enable_health()

    rng = np.random.default_rng(0)
    idx = rng.integers(0, len(ds.x), global_batch)
    x, y = ds.x[idx], ds.y[idx]

    xs, ys = eng.shard_batch(x, y)

    def _warm():
        st = eng.init_state(jax.random.key(0), x[:n])
        for _ in range(WARMUP_STEPS):
            st, _m = eng.step(st, xs, ys)
        _sync(st)
        return st

    state = _warm()

    # exposed-vs-hidden collective split (parallel/overlap.py): the
    # engine's real step vs a collective-free twin vs the exchange alone
    # — grad_collective_exposed_s is the number `analyze diff` gates
    # lower-is-better (BASELINE.md).  Probe failure only Nones the keys.
    overlap_probe = None
    try:
        from distributed_tensorflow_tpu.parallel import overlap as overlaplib

        overlap_probe = overlaplib.probe_engine_overlap(
            eng, xs, ys, state=state)
    except Exception as e:  # noqa: BLE001 — the probe must not kill the bench
        print(f"[bench] overlap probe failed: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)

    # device-bound windows THROUGH THE PRODUCTION PATH: the scan unit is
    # Engine.build_many_step — the same jitted lax.scan drain
    # Trainer.fit dispatches steps_per_call chunks through — fed the
    # resident batch unit_len times per call.  1 vs SCAN_LONG/unit_len
    # chained unit calls are differenced (the --attention chaining
    # protocol): the chained calls pipeline on-device because each consumes
    # the previous state, and the fixed per-call overhead cancels.
    # the unit scans over a stacked copy of its inputs (the production
    # program shape), so unit_len × batch must fit HBM comfortably: cap
    # the stacked inputs at ~512 MB/chip (mnist b=512 → the full 100)
    batch_bytes = max(x.nbytes + y.nbytes, 1)
    unit_len = max(8, min(SCAN_SHORT, (512 << 20) // batch_bytes))
    xs_k, ys_k = (xs,) * unit_len, (ys,) * unit_len
    calls_long = max(SCAN_LONG // unit_len, 2)

    def run_unit(st):
        # many_step caches the compiled drain per k and threads the health
        # layer's loss-EMA carry when --health on — same production program
        st, _metrics = eng.many_step(st, xs_k, ys_k)
        return st

    state = run_unit(state)  # compile outside the window
    _sync(state)

    def window(m, st):
        t0 = time.perf_counter()
        for _ in range(m):
            st = run_unit(st)
        _sync(st)
        return st, time.perf_counter() - t0

    state_box = [state]

    def _scan_window(_rep):
        st, t_short = window(1, state_box[0])
        st, t_long = window(calls_long, st)
        state_box[0] = st
        per_step = (t_long - t_short) / ((calls_long - 1) * unit_len)
        return global_batch / per_step

    scan_rates = measure_windows(_scan_window, REPEATS)
    state = state_box[0]

    # steady-state rate of the SHIPPED Trainer.fit loop (device prefetch +
    # steps_per_call=8 drain, fresh host batches) — reported as
    # dispatch_value for continuity with the Python-dispatch figure it
    # replaces (see module docstring)
    from distributed_tensorflow_tpu.engines import Trainer

    # bounded by the dataset: at high chip counts the epoch holds fewer
    # full global batches than DISPATCH_STEPS (or none — then the Trainer
    # row is skipped rather than reporting a rate over zero steps)
    dispatch_steps = min(DISPATCH_STEPS, len(ds.x) // global_batch)
    dispatch_rates = []
    last_fit = {}
    elastic_probe = None
    if dispatch_steps:
        trainer = Trainer(None, engine=eng, seed=0)
        trainer.state = state
        fit_kw = dict(epochs=1, batch_size=global_batch, log_every=0,
                      steps_per_call=8, max_steps=dispatch_steps)
        fit_box: dict = {}

        def _dispatch_window(_rep):
            fit = trainer.fit(ds, **fit_kw)
            fit_box["fit"] = fit
            return fit["examples"] / fit["elapsed"]

        with _bench_checkpointing(fit_kw, checkpoint_every) as ckpt_mgr:
            trainer.fit(ds, **fit_kw)  # warm: compiles the k=8 drain
            dispatch_rates = measure_windows(_dispatch_window, REPEATS)
            if ckpt_mgr is not None:
                # while the manager (and its checkpoints) still exist:
                # the elastic resume accounting of the benched window
                elastic_probe = _probe_elastic_resume(
                    ckpt_mgr, eng, x[:n], seed=trainer.seed,
                    batch_size=global_batch, dataset_len=len(ds),
                    dataset=getattr(ds, "name", "dataset"))
        last_fit = fit_box.get("fit", {})
        state = trainer.state

    scan_med, scan_spread = _median_spread(scan_rates)
    scan_per_chip = scan_med / n
    if dispatch_rates:
        disp_med, disp_spread = _median_spread(dispatch_rates)
        disp_per_chip = disp_med / n
    else:
        disp_per_chip = disp_spread = None

    flops_ex = cnn_train_flops_per_example(
        shape=ds.x.shape[1:], features=model.features, dense=model.dense,
        num_classes=model.num_classes)
    peak = peak_flops(device_kind)
    mfu = (scan_med * flops_ex) / (n * peak) if peak else None

    # XLA's own count for the whole per-device step program (cross-check;
    # includes elementwise/optimizer FLOPs the analytic model excludes).
    # The same compiled executable feeds the program ledger: its
    # memory_analysis (peak_hbm_bytes_est) and the measured AOT compile
    # wall time ride the bench line at zero extra compiles — the
    # `analyze diff` memory/compile gates (BASELINE.md "Memory/compile
    # accounting")
    xla_flops = None
    peak_hbm, compile_total_s, compiled = _train_step_ledger_probe(
        eng, state, xs, ys)
    try:
        ca = compiled.cost_analysis() if compiled is not None else None
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        if ca is not None:
            xla_flops = float(ca.get("flops", 0.0)) or None
    except Exception:
        pass


    print(json.dumps({
        "metric": "mnist_cnn_sync_examples_per_sec_per_chip",
        "value": round(scan_per_chip, 1),
        "unit": "examples/sec/chip",
        "vs_baseline": None,
        "method": (f"production many_step({unit_len}) chained "
                   f"{calls_long}-1 diff, median of {REPEATS}"),
        "spread": round(scan_spread, 4),
        "dispatch_value": (round(disp_per_chip, 1)
                           if disp_per_chip is not None else None),
        "dispatch_method": ((f"Trainer.fit steps_per_call=8 prefetch=2, "
                             f"{dispatch_steps} fresh-batch steps, "
                             f"median of {REPEATS}")
                            if disp_per_chip is not None else None),
        "dispatch_spread": (round(disp_spread, 4)
                            if disp_spread is not None else None),
        # steady-state per-step wall-time percentiles of the shipped fit
        # loop (compile chunk excluded — StepTimer.compile_steps) and its
        # input-starvation counter, from the run's own telemetry: the same
        # numbers the harness's run_report carries (observability/report)
        "step_time_p50": (last_fit.get("step_time") or {}).get("steady_p50_s"),
        "step_time_p95": (last_fit.get("step_time") or {}).get("steady_p95_s"),
        "prefetch_starvation": last_fit.get("prefetch_starvation"),
        # per-step gradient-collective payload: wire bytes under
        # --grad-compression vs the raw (uncompressed) figure — the BENCH
        # trajectory's view of the comm win
        "grad_bytes_per_step_wire": eng.grad_collective_bytes(state),
        "grad_bytes_per_step_raw": eng.grad_collective_bytes_raw(state),
        "grad_compression": eng.grad_codec.name,
        # mixed-precision attribution (--precision): the active policy +
        # the per-device state footprint it moves — environment-
        # attribution style, like the jax_version keys below
        "precision": eng.precision.name,
        "param_bytes_per_device": eng.param_bytes_per_device(state),
        "opt_state_bytes_per_device": eng.opt_state_bytes_per_device(state),
        # communication/compute overlap (--grad-bucket-mb): exposed
        # collective seconds still on the critical path vs hidden behind
        # compute (parallel/overlap.py probe; exposed is the `analyze
        # diff` gate — BASELINE.md).  None: probe unavailable.
        "grad_bucket_mb": grad_bucket_mb,
        "grad_collective_exposed_s": (overlap_probe or {}).get("exposed_s"),
        "grad_collective_hidden_s": (overlap_probe or {}).get("hidden_s"),
        "collective_overlap": overlap_probe,
        # --checkpoint-every: blocked-vs-overlapped checkpoint seconds of
        # the Trainer window (async manager; observability/report rule —
        # only wait_s is charged against throughput)
        **({"checkpoint_every": checkpoint_every,
            "checkpoint_wait_s": last_fit.get("checkpoint_wait_s"),
            "checkpoint_overlapped_s":
                last_fit.get("checkpoint_overlapped_s"),
            "checkpoint_async": last_fit.get("checkpoint_async")}
           if checkpoint_every else {}),
        # elastic resume accounting of the checkpointed window (the
        # _probe_elastic_resume restore-and-account pass): save→resume
        # wall gap + replay steps, the same keys the run report carries —
        # gated lower-is-better by `analyze diff` (BASELINE.md
        # "Preemption accounting")
        **(elastic_probe or {}),
        # numeric-health summary of the Trainer-path window (--health on):
        # the same section the fit result / run report carry
        **({"health_max_update_ratio":
                (last_fit.get("health") or {}).get("max_update_ratio"),
            "health_anomaly_steps":
                (last_fit.get("health") or {}).get("anomaly_steps")}
           if health == "on" else {}),
        "mfu": round(mfu, 4) if mfu is not None else None,
        # round 19: the canonical spelling `analyze diff` gates higher-is-
        # better (BASELINE.md "Roofline accounting"); "mfu" above stays for
        # line continuity with pre-19 BENCH_*.json
        "train_mfu": round(mfu, 4) if mfu is not None else None,
        "roofline_peak_table_revision": _rf_revision(),
        "flops_per_example_analytic": int(flops_ex),
        "xla_flops_per_step": xla_flops,
        # train-step program memory/compile accounting (same executable
        # as xla_flops_per_step; None when the AOT probe failed)
        "peak_hbm_bytes_est": peak_hbm,
        "compile_total_s": compile_total_s,
        "device": device_kind,
        "n_devices": n,
        "global_batch": global_batch,
        "dtype": str(np.dtype(getattr(model, "dtype", np.float32))),
        "synthetic": bool(ds.synthetic),
        # attribution: which toolchain/flags made these numbers —
        # diffable across machines
        "jax_version": jax.__version__,
        "xla_flags": os.environ.get("XLA_FLAGS"),
        "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS"),
    }))


# ---------------------------------------------------------------------------
# --stream: input pipeline (fresh host batches per step)
# ---------------------------------------------------------------------------

def bench_stream(steps: int = 100, grad_compression: str = "none",
                 health: str = "off", checkpoint_every: int = 0,
                 grad_bucket_mb: float = 0.0,
                 precision: str = "f32") -> None:
    """Training throughput when every step consumes a FRESH host batch —
    the configuration the C++ prefetcher (native/src/pipeline.cc) exists
    for.  'resident' (one device batch reused, the default bench) bounds the
    attainable rate from above."""
    import jax

    from distributed_tensorflow_tpu.data.loaders import load_dataset
    from distributed_tensorflow_tpu.native import load as native_load
    from distributed_tensorflow_tpu.parallel import mesh as meshlib

    mesh = meshlib.create_mesh()
    n = mesh.shape[meshlib.DATA_AXIS]
    global_batch = PER_CHIP_BATCH * n

    ds = load_dataset("mnist", split="train")
    _model, eng = _bench_model_and_engine(ds, mesh, grad_compression,
                                          grad_bucket_mb, precision)
    if health == "on":
        eng.enable_health()  # before init_state: capture slots in tx.init

    def run_epoch_stream(native: bool | None, st, max_steps: int):
        done = 0
        epoch = 0
        t0 = time.perf_counter()
        while done < max_steps:
            for bx, by, _ in ds.batches(global_batch, shuffle=True, seed=0,
                                        epoch=epoch, drop_remainder=True,
                                        native=native):
                xs, ys = eng.shard_batch(bx, by)
                st, _m = eng.step(st, xs, ys)
                done += 1
                if done >= max_steps:
                    break
            epoch += 1
        _sync(st)
        return st, done * global_batch / (time.perf_counter() - t0)

    # compile + warm both producer paths (the native pass also constructs
    # the C++ pool and staging buffers outside the timed window)
    have_native = native_load() is not None

    def _warm():
        st = eng.init_state(jax.random.key(0), ds.x[:n])
        st, _ = run_epoch_stream(False, st, WARMUP_STEPS)
        if have_native:
            st, _ = run_epoch_stream(True, st, WARMUP_STEPS)
        return st

    state = _warm()

    rows: dict[str, float] = {}
    for label, native in [("python", False)] + (
            [("native", True)] if have_native else []):
        rates = []
        for _ in range(3):
            state, r = run_epoch_stream(native, state, steps)
            rates.append(r)
        rows[label], _ = _median_spread(rates)

    # resident upper bound: one device batch, no host input at all (same
    # 3-repeat median as the streamed rows — single windows are exactly the
    # jitter trap the methodology section documents)
    rng = np.random.default_rng(0)
    idx = rng.integers(0, len(ds.x), global_batch)
    xs, ys = eng.shard_batch(ds.x[idx], ds.y[idx])
    for _ in range(WARMUP_STEPS):
        state, _m = eng.step(state, xs, ys)
    _sync(state)
    resident_rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, _m = eng.step(state, xs, ys)
        _sync(state)
        resident_rates.append(
            steps * global_batch / (time.perf_counter() - t0))
    rows["resident"], _ = _median_spread(resident_rates)

    # trainer-path telemetry row: the SHIPPED fit loop (device prefetch +
    # steps_per_call=8 scanned drain) over the same fresh-batch stream —
    # its steady-state step-time percentiles (compile chunk excluded) and
    # prefetch starvation counter are the bench's view of the run_report
    from distributed_tensorflow_tpu.engines import Trainer

    trainer = Trainer(None, engine=eng, seed=0)
    trainer.state = state
    # steady percentiles need steps BEYOND the compile chunk (StepTimer
    # reports None otherwise) — short smoke runs drop to k=1 so even a
    # 2-step window has a steady tail
    k_fit = 8 if steps > 8 else 1
    fit_kw = dict(epochs=1, batch_size=global_batch, log_every=0,
                  steps_per_call=k_fit, prefetch=2, max_steps=steps)
    with _bench_checkpointing(fit_kw, checkpoint_every):
        trainer.fit(ds, **fit_kw)  # warm: compiles the drain
        trainer_fit = trainer.fit(ds, **fit_kw)
    state = trainer.state
    fit_st = trainer_fit.get("step_time", {})

    # train-step program memory/compile accounting (same probe as the
    # default line; the stream path reuses the last resident batch)
    peak_hbm, compile_total_s, _ = _train_step_ledger_probe(
        eng, state, xs, ys)

    # host-only producer rate: the C++ gather pool vs the numpy gather,
    # device out of the loop entirely (this is where the prefetcher acts;
    # the end-to-end rows above also carry host→device transfer)
    producer: dict[str, float] = {}
    for label, native in [("python", False)] + (
            [("native", True)] if have_native else []):
        for _b in ds.batches(global_batch, shuffle=True, native=native):
            pass  # warm
        rates = []
        for rep in range(3):
            t0 = time.perf_counter()
            count = 0
            for bx, _by, _bm in ds.batches(global_batch, shuffle=True,
                                           seed=rep, native=native):
                count += len(bx)
            rates.append(count / (time.perf_counter() - t0))
        producer[label], _ = _median_spread(rates)

    # round 19: trainer-row MFU (analytic CNN flops over the fleet peak;
    # None on an unknown device — the honesty rule)
    _flops_ex = cnn_train_flops_per_example(
        shape=ds.x.shape[1:], features=_model.features, dense=_model.dense,
        num_classes=_model.num_classes)
    _peak = peak_flops(jax.devices()[0].device_kind)
    _trainer_rate = trainer_fit["examples"] / trainer_fit["elapsed"]
    _stream_mfu = (round(_trainer_rate * _flops_ex / (n * _peak), 4)
                   if _peak else None)

    print(json.dumps({
        "metric": "mnist_cnn_stream_examples_per_sec",
        "unit": "examples/sec",
        "global_batch": global_batch,
        "steps": steps,
        "native_available": have_native,
        "host_cores": os.cpu_count(),
        **{f"{k}_examples_per_sec": round(v, 1) for k, v in rows.items()},
        "native_vs_python": (round(rows["native"] / rows["python"], 3)
                             if "native" in rows else None),
        "step_time_p50": fit_st.get("steady_p50_s"),
        "step_time_p95": fit_st.get("steady_p95_s"),
        "prefetch_starvation": trainer_fit.get("prefetch_starvation"),
        "grad_bytes_per_step_wire": eng.grad_collective_bytes(state),
        "grad_bytes_per_step_raw": eng.grad_collective_bytes_raw(state),
        "grad_compression": eng.grad_codec.name,
        # mixed-precision attribution (--precision), environment-
        # attribution style like jax_version below
        "precision": eng.precision.name,
        "param_bytes_per_device": eng.param_bytes_per_device(state),
        "opt_state_bytes_per_device": eng.opt_state_bytes_per_device(state),
        **({"checkpoint_every": checkpoint_every,
            "checkpoint_wait_s": trainer_fit.get("checkpoint_wait_s"),
            "checkpoint_overlapped_s":
                trainer_fit.get("checkpoint_overlapped_s"),
            "checkpoint_async": trainer_fit.get("checkpoint_async")}
           if checkpoint_every else {}),
        **({"health_max_update_ratio":
                (trainer_fit.get("health") or {}).get("max_update_ratio"),
            "health_anomaly_steps":
                (trainer_fit.get("health") or {}).get("anomaly_steps")}
           if health == "on" else {}),
        "trainer_examples_per_sec": round(
            trainer_fit["examples"] / trainer_fit["elapsed"], 1),
        # round 19: MFU of the SHIPPED fit loop's row (trainer path, the
        # rate above) — analytic model flops only, same accounting as the
        # default line; None off-TPU (BASELINE.md "Roofline accounting")
        "train_mfu": _stream_mfu,
        "roofline_peak_table_revision": _rf_revision(),
        "peak_hbm_bytes_est": peak_hbm,
        "compile_total_s": compile_total_s,
        **{f"producer_{k}_rows_per_sec": round(v, 1)
           for k, v in producer.items()},
        "producer_native_vs_python": (
            round(producer["native"] / producer["python"], 3)
            if "native" in producer else None),
        "device": jax.devices()[0].device_kind,
        "synthetic": bool(ds.synthetic),
        "grad_bucket_mb": grad_bucket_mb,
        "jax_version": jax.__version__,
        "xla_flags": os.environ.get("XLA_FLAGS"),
        "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS"),
    }))


# ---------------------------------------------------------------------------
# --attention: Pallas flash kernel vs XLA dense attention
# ---------------------------------------------------------------------------

def bench_attention(batch: int = 4, heads: int = 8, head_dim: int = 128,
                    seq_lens: tuple[int, ...] = (1024, 4096),
                    dtypes: tuple[str, ...] = ("float32", "bfloat16"),
                    causal: bool = True) -> None:
    """fwd+bwd step time of flash (ops/flash_attention.py) vs dense (XLA)
    attention, per (seq_len, dtype).  This is the measurement behind any
    speed claim the flash kernel makes (VERDICT r2: 'measure it on the chip
    or delete the claim'); the bf16 rows are the MXU-rate numbers that
    matter at scale (VERDICT r3 #4 — the f32-only table under- or
    over-sells the kernel depending on MXU behavior)."""
    import itertools

    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.ops.flash_attention import flash_attention
    from distributed_tensorflow_tpu.parallel.ring_attention import dense_attention

    device_kind = jax.devices()[0].device_kind
    results = []
    for L, dtype_name in itertools.product(seq_lens, dtypes):
        dtype = jnp.dtype(dtype_name)
        key = jax.random.key(0)
        kq, kk, kv = jax.random.split(key, 3)
        shape = (batch, L, heads, head_dim)
        q = jax.random.normal(kq, shape, jnp.float32).astype(dtype)
        k = jax.random.normal(kk, shape, jnp.float32).astype(dtype)
        v = jax.random.normal(kv, shape, jnp.float32).astype(dtype)

        def make_scan(attn, length):
            """fwd+bwd chained ``length`` times inside one jit: the next q
            depends on ALL THREE grads (a tiny epsilon keeps dk/dv live —
            carrying dq alone would let XLA dead-code the dk/dv backward,
            and asymmetrically so between the two impls), so the calls
            serialize on the device and nothing is DCE'd; two lengths
            difference away fixed dispatch overhead."""
            grad_fn = jax.grad(lambda q_, k_, v_: attn(q_, k_, v_).sum(),
                               argnums=(0, 1, 2))

            def body(q_c, _):
                dq, dk, dv = grad_fn(q_c, k, v)
                return dq + 1e-30 * (dk + dv), None

            return jax.jit(lambda q0: jax.lax.scan(
                body, q0, None, length=length)[0])

        impls = {
            "dense": lambda q_, k_, v_: dense_attention(
                q_, k_, v_, causal=causal),
            "flash": lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=causal),
        }
        row = {"seq_len": L, "dtype": dtype_name}
        K_UNIT = 100  # one compiled scan per impl; windows chain m calls
        for name, attn in impls.items():
            unit = make_scan(attn, K_UNIT)

            def window(m, unit=unit):
                """m chained unit-scan calls, timed to real completion."""
                t0 = time.perf_counter()
                qq = q
                for _ in range(m):
                    qq = unit(qq)
                _sync(qq)
                return time.perf_counter() - t0

            _sync(unit(q))  # compile (the only compile for this impl/L)
            # probe: size the long window to ~2 s of real compute so
            # per-call jitter averages out; (t(6)−t(1))/5 cancels the
            # fixed per-call cost
            u = max((window(6) - window(1)) / 5, 1e-4)
            m_long = int(min(max(round(2.0 / u), 2), 60))
            times = []
            for _ in range(REPEATS):
                t_long, t_short = window(m_long), window(1)
                times.append((t_long - t_short) / ((m_long - 1) * K_UNIT))
            med, spread = _median_spread(times)
            row[f"{name}_ms"] = round(med * 1e3, 3)
            row[f"{name}_spread"] = round(spread, 3)
            row[f"{name}_window_calls"] = m_long * K_UNIT
        row["flash_speedup"] = round(row["dense_ms"] / row["flash_ms"], 3)
        results.append(row)

    print(json.dumps({
        "metric": "attention_fwd_bwd_step_ms",
        "config": {"batch": batch, "heads": heads, "head_dim": head_dim,
                   "causal": causal, "dtypes": list(dtypes)},
        "device": device_kind,
        "rows": results,
    }))


# ---------------------------------------------------------------------------
# --lm: GPT decoder training throughput + MFU (the transformer flagship)
# ---------------------------------------------------------------------------

def gpt_train_flops_per_token(hidden: int, layers: int, ffn: int,
                              seq_len: int, vocab: int,
                              causal: bool = True) -> float:
    """Analytic matmul FLOPs for one trained token of models/gpt.py:
    per-layer QKV+out projections (8h²) and FFN (4·h·ffn), the attention
    score/PV einsums (4·h·L, halved causal), plus the tied LM head (2·h·V);
    ×3 for fwd+bwd.  Embedding gathers excluded (not matmuls)."""
    per_layer = 2.0 * hidden * (4 * hidden + 2 * ffn)
    attn = 4.0 * hidden * seq_len * (0.5 if causal else 1.0)
    fwd = layers * (per_layer + attn) + 2.0 * hidden * vocab
    return 3.0 * fwd


def _measure_gpt_variant(label: str, tag: str, mesh, x, y,
                         tokens_per_step: int, **model_kwargs) -> list:
    """One differenced-scan throughput measurement of a GPT variant under
    the sync engine — THE shared protocol for the --lm and --moe modes (a
    protocol change edits exactly this function).  Returns the list of
    per-rep tokens/sec rates; progress goes to stderr (a silent
    multi-minute compile is indistinguishable from a hang)."""
    import sys

    import jax

    from distributed_tensorflow_tpu.engines import SyncEngine
    from distributed_tensorflow_tpu.models import create_model

    def note(msg):
        print(f"[bench {tag}] {msg}", file=sys.stderr, flush=True)

    n = mesh.shape["data"]
    t_build = time.perf_counter()
    model = create_model("gpt", dropout_rate=0.0, **model_kwargs)
    eng = SyncEngine(model, mesh=mesh)
    state = eng.init_state(jax.random.key(0), x[:n])
    xs, ys = eng.shard_batch(x, y)
    state, _ = eng.step(state, xs, ys)  # compile the single step
    _sync(state)
    note(f"{label}: step compiled in {time.perf_counter() - t_build:.0f}s")

    def scan_body(st, _):
        st, _m = eng.step(st, xs, ys)
        return st, None

    short, long = 3, 13
    runs = {k: jax.jit(lambda st, k=k: jax.lax.scan(
        scan_body, st, None, length=k)[0]) for k in (short, long)}
    for k, run in runs.items():
        t0 = time.perf_counter()
        state = run(state)
        _sync(state)
        note(f"{label}: scan({k}) compiled+ran in "
             f"{time.perf_counter() - t0:.0f}s")
    rates = []
    for rep in range(REPEATS):
        t = {}
        for k, run in runs.items():
            t0 = time.perf_counter()
            state = run(state)
            _sync(state)
            t[k] = time.perf_counter() - t0
        per_step = (t[long] - t[short]) / (long - short)
        rates.append(tokens_per_step / per_step)
        note(f"{label}: rep {rep}: {rates[-1] / 1e3:.1f}k tokens/s")
    return rates


def bench_lm(batch: int = 8, seq_len: int = 1024, vocab: int = 16384,
             hidden: int = 512, layers: int = 8, heads: int = 8,
             ffn: int = 2048) -> None:
    """Training throughput (tokens/sec/chip) + MFU of a GPT-2-small-ish
    decoder LM in bf16, flash vs dense attention — the transformer
    counterpart of the default CNN bench, same differenced-scan-window
    protocol (_measure_gpt_variant)."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.parallel import mesh as meshlib

    mesh = meshlib.create_mesh()
    n = mesh.shape[meshlib.DATA_AXIS]
    device_kind = jax.devices()[0].device_kind
    peak = peak_flops(device_kind)
    flops_tok = gpt_train_flops_per_token(hidden, layers, ffn, seq_len, vocab)
    tokens_per_step = batch * n * seq_len

    rng = np.random.default_rng(0)
    tok = rng.integers(0, vocab, (batch * n, seq_len + 1))
    x = tok[:, :-1].astype(np.int32)
    y = tok[:, 1:].astype(np.int32)

    rows = {}
    for impl in ("dense", "flash"):
        rates = _measure_gpt_variant(
            impl, "--lm", mesh, x, y, tokens_per_step,
            num_classes=vocab, hidden=hidden, layers=layers, heads=heads,
            ffn=ffn, max_len=seq_len, attention_impl=impl,
            dtype=jnp.bfloat16)
        med, spread = _median_spread(rates)
        rows[impl] = {
            "tokens_per_sec_per_chip": round(med / n, 1),
            "spread": round(spread, 4),
            "mfu": (round(med * flops_tok / (n * peak), 4) if peak else None),
        }

    print(json.dumps({
        "metric": "gpt_lm_sync_tokens_per_sec_per_chip",
        "config": {"batch_per_chip": batch, "seq_len": seq_len,
                   "vocab": vocab, "hidden": hidden, "layers": layers,
                   "heads": heads, "ffn": ffn, "dtype": "bfloat16"},
        "flops_per_token_analytic": int(flops_tok),
        # round 19: the production impl's (flash) MFU under the canonical
        # key `analyze diff` gates higher-is-better; per-impl *_mfu keys
        # below keep the flash-vs-dense attribution
        "train_mfu": rows["flash"]["mfu"],
        "roofline_peak_table_revision": _rf_revision(),
        "device": device_kind,
        "n_devices": n,
        "synthetic": True,
        **{f"{k}_{kk}": vv for k, v in rows.items() for kk, vv in v.items()},
        "flash_vs_dense": round(
            rows["flash"]["tokens_per_sec_per_chip"]
            / rows["dense"]["tokens_per_sec_per_chip"], 3),
    }))


def bench_moe(batch: int = 8, seq_len: int = 1024, vocab: int = 16384,
              hidden: int = 512, layers: int = 8, heads: int = 8,
              ffn: int = 2048, experts: int = 8) -> None:
    """MoE-FFN vs dense-FFN GPT training throughput (tokens/sec/chip) —
    the on-chip cost of the GShard dense-dispatch formulation
    (models/moe.py): both models have IDENTICAL active FLOPs per token
    (top-1 routing through one ffn-wide expert vs one dense ffn), so the
    reported ratio isolates router + dispatch/combine einsum overhead.
    Single-chip: all experts resident (the multi-chip expert all-to-all is
    exercised by the dryrun's ep modes, not measurable on one device).
    Same differenced-scan protocol as --lm (_measure_gpt_variant)."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.parallel import mesh as meshlib

    mesh = meshlib.create_mesh()
    n = mesh.shape[meshlib.DATA_AXIS]
    device_kind = jax.devices()[0].device_kind
    tokens_per_step = batch * n * seq_len

    rng = np.random.default_rng(0)
    tok = rng.integers(0, vocab, (batch * n, seq_len + 1))
    x = tok[:, :-1].astype(np.int32)
    y = tok[:, 1:].astype(np.int32)

    rows = {}
    for kind, extra in (("dense", {}),
                        ("moe", {"moe_experts": experts})):
        rates = _measure_gpt_variant(
            kind, "--moe", mesh, x, y, tokens_per_step,
            num_classes=vocab, hidden=hidden, layers=layers, heads=heads,
            ffn=ffn, max_len=seq_len, attention_impl="flash",
            dtype=jnp.bfloat16, **extra)
        med, spread = _median_spread(rates)
        rows[kind] = {
            "tokens_per_sec_per_chip": round(med / n, 1),
            "spread": round(spread, 4),
        }

    print(json.dumps({
        "metric": "gpt_moe_sync_tokens_per_sec_per_chip",
        "config": {"batch_per_chip": batch, "seq_len": seq_len,
                   "vocab": vocab, "hidden": hidden, "layers": layers,
                   "heads": heads, "ffn": ffn, "experts": experts,
                   "router_top_k": 1, "dtype": "bfloat16",
                   "attention": "flash"},
        "device": device_kind,
        "n_devices": n,
        "synthetic": True,
        **{f"{k}_{kk}": vv for k, v in rows.items() for kk, vv in v.items()},
        "moe_vs_dense": round(
            rows["moe"]["tokens_per_sec_per_chip"]
            / rows["dense"]["tokens_per_sec_per_chip"], 3),
    }))


def bench_decode(batch: int = 8, prompt_len: int = 32, vocab: int = 16384,
                 hidden: int = 512, layers: int = 8, heads: int = 8,
                 ffn: int = 2048) -> None:
    """Inference: steady-state KV-cache decode throughput of the --lm
    flagship config (models/gpt.py ``generate`` path — the compiled
    prefill+decode scan).

    Protocol: the sampler compiles once per decode length; two lengths
    (64 / 576 new tokens, same prompt) are timed and DIFFERENCED, so the
    prefill, dispatch, and host↔device overhead cancel and the quotient is
    the marginal per-token decode step.  Decode is HBM-bandwidth-bound
    (every step reads all weights to emit B tokens), so alongside
    tokens/sec the line reports the achieved weight-streaming bandwidth
    params_bytes × steps/sec — comparable against the chip's HBM spec."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import create_model
    from distributed_tensorflow_tpu.models.gpt import generate as gpt_generate
    from distributed_tensorflow_tpu.observability import exact_percentile

    def note(msg):
        print(f"[bench --decode] {msg}", file=sys.stderr, flush=True)

    short, long = 64, 576
    max_len = prompt_len + long
    model = create_model("gpt", num_classes=vocab, hidden=hidden,
                         layers=layers, heads=heads, ffn=ffn,
                         max_len=max_len, dropout_rate=0.0,
                         dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, vocab, (batch, prompt_len)),
                         jnp.int32)
    t0 = time.perf_counter()
    params = jax.jit(lambda k: model.init(k, prompt, train=False))(
        jax.random.key(0))["params"]
    _sync(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    note(f"init done in {time.perf_counter() - t0:.0f}s "
         f"({n_params / 1e6:.1f}M params)")

    # the public sampling entry: its _compiled_sampler is lru-cached per
    # (model config, length, mode), so after these warm-ups every timed
    # call below reuses the same two compiled prefill+decode programs
    for n_new in (short, long):
        t0 = time.perf_counter()
        _sync(gpt_generate(model, params, prompt, n_new, greedy=True))
        note(f"decode({n_new}) compiled+ran in "
             f"{time.perf_counter() - t0:.0f}s")

    rates = []
    per_steps = []
    for rep in range(REPEATS):
        t = {}
        for n_new in (short, long):
            t0 = time.perf_counter()
            _sync(gpt_generate(model, params, prompt, n_new, greedy=True))
            t[n_new] = time.perf_counter() - t0
        per_step = (t[long] - t[short]) / (long - short)
        rates.append(batch / per_step)
        per_steps.append(per_step)
        note(f"rep {rep}: {rates[-1] / 1e3:.2f}k tokens/s, "
             f"{per_step * 1e3:.3f} ms/step")
    med, spread = _median_spread(rates)
    steps_per_sec = med / batch

    # TTFT vs per-token split (serving comparability): TTFT is a 1-new-
    # token generate — the prefill cost the differenced marginal rate
    # above deliberately cancels — so decode lines report BOTH halves of
    # a request's latency, like the serving bench and the training
    # benches' compile-vs-steady split
    _sync(gpt_generate(model, params, prompt, 1, greedy=True))  # compile
    ttft_times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        _sync(gpt_generate(model, params, prompt, 1, greedy=True))
        ttft_times.append(time.perf_counter() - t0)
    ttft_med, ttft_spread = _median_spread(ttft_times)
    # weights stream once per decode STEP (all B rows share the read);
    # byte count from the ACTUAL param leaf dtypes — flax keeps
    # param_dtype=float32 under bf16 compute today, and summing itemsize
    # keeps the figure honest if param storage ever changes
    params_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(params))
    gbps = params_bytes * steps_per_sec / 1e9
    # round 19 MBU: achieved must-read bytes/s over the HBM peak.  The
    # must-read set per marginal decode step is all param bytes (the
    # ACTUAL leaf dtypes, matching the GBps figure above) plus each row's
    # live KV — priced by the analytic cost model at the mean context of
    # the differenced window (the marginal steps span prompt+short ..
    # prompt+long).  None off-TPU rather than a number against a
    # fabricated peak (BASELINE.md "Roofline accounting").
    from distributed_tensorflow_tpu.observability.roofline import (
        GPTCostModel, device_peaks)

    _cost = GPTCostModel(vocab=vocab, hidden=hidden, layers=layers,
                         heads=heads, ffn=ffn, max_len=max_len,
                         kv_dtype="bfloat16",
                         param_bytes_override=params_bytes)
    _mid_ctx = prompt_len + (short + long) // 2
    _step_bytes = _cost.decode_step_bytes([_mid_ctx] * batch)
    _peaks = device_peaks(jax.devices()[0].device_kind)
    decode_mbu = (round(_step_bytes * steps_per_sec
                        / _peaks.hbm_bytes_per_s, 4)
                  if _peaks is not None else None)
    print(json.dumps({
        "metric": "gpt_lm_decode_tokens_per_sec_per_chip",
        "value": round(med, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,
        "method": f"differenced decode scans {long}-{short}, "
                  f"median of {REPEATS}",
        "spread": round(spread, 4),
        "ms_per_step": round(1e3 / steps_per_sec, 3),
        # TTFT (prompt prefill + first token, batch-wide) vs the marginal
        # per-token decode step — the split serving latency budgets are
        # written in (BASELINE.md "Serving comparisons").  p99 over the
        # repeat samples rides along (stdlib-percentile path, the serve
        # section convention) — the tail SLOs are written against.
        "ttft_s": round(ttft_med, 6),
        "ttft_spread": round(ttft_spread, 4),
        "ttft_p99_s": round(exact_percentile(ttft_times, 0.99), 6),
        "per_token_s": round(1.0 / steps_per_sec, 6),
        "per_token_p99_s": round(exact_percentile(per_steps, 0.99), 6),
        "achieved_weight_stream_GBps": round(gbps, 1),
        # round 19: the `analyze diff` higher-is-better gate key — the
        # bandwidth figure above, normalized by the chip's HBM peak and
        # widened to count the KV reads the weight-stream number omits
        "serve_decode_mbu": decode_mbu,
        "decode_must_read_bytes_per_step": int(_step_bytes),
        "roofline_peak_table_revision": _rf_revision(),
        "params_millions": round(n_params / 1e6, 1),
        "params_bytes": params_bytes,
        "config": {"batch": batch, "prompt_len": prompt_len,
                   "vocab": vocab, "hidden": hidden, "layers": layers,
                   "heads": heads, "ffn": ffn, "dtype": "bfloat16",
                   "greedy": True},
        "device": jax.devices()[0].device_kind,
        "n_devices": 1,
        "synthetic": True,
        # environment attribution: decode numbers are only comparable across runs when the
        # toolchain/flags that made them ride the line
        "jax_version": jax.__version__,
        "xla_flags": os.environ.get("XLA_FLAGS"),
        "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS"),
    }))


# ---------------------------------------------------------------------------
# --serve: continuous-batching serving under an open-loop arrival process
# ---------------------------------------------------------------------------

def bench_serve(stream: bool = False, trace_path: str | None = None,
                sweep: bool = False, slo_ttft: float | None = None,
                slo_itl: float | None = None, queue_cap: int = 0,
                kv_dtype: str | None = None, draft: str | None = None,
                draft_k: int | None = None, replicas: int = 0,
                kv_layout: str | None = None,
                disagg: str | None = None,
                multi_step: int | None = None) -> None:
    """Serving throughput + latency percentiles of the continuous-batching
    engine (distributed_tensorflow_tpu/serving/) against the static-batch
    restart-per-``generate`` baseline, on the SAME synthetic open-loop
    arrival trace (Poisson arrivals, mixed prompt/continuation lengths) —
    the BASELINE.md serving rule: equal arrival process, equal latency
    budget, percentile accounting.

    TTFT/ITL are MLPerf-style latency percentiles (queue wait included in
    TTFT); the headline is requests/sec/chip.  Round 13: every window
    runs under an SLOMonitor (``--serve-slo-ttft``/``--serve-slo-itl``,
    p99 ITL per request) so the line carries p99 latency +
    ``serve_goodput_under_slo``; ``--sweep`` turns the bench into the
    MLPerf-style SLO load harness — the Poisson arrival rate walks a
    geometric ladder on the SAME seeded trace (the exponential draws
    rescale exactly) until goodput falls, the line reports
    ``serve_max_goodput_under_slo`` + the knee rate, and a saturation
    window at 2× the knee with a queue cap proves shedding engages
    (nonzero ``serve_shed_rate``, bounded queue-wait p99).  Round 10: the default
    workload carries a shared system prefix and periodic 2×-length
    prompts, and the production windows run chunked prefill + the prefix
    pool — a monolithic/no-cache continuous run on the SAME seeded trace
    rides the line (``monolithic_itl_p95_s``/``monolithic_ttft_p50_s``)
    so the decode-interference and shared-prompt claims are measured,
    not asserted, plus the prefill/decode token split and the pool hit
    rate.  ``--stream`` exercises the per-token streaming delivery hook
    (tokens reach the host every decode iteration in all modes; --stream
    additionally counts deliveries through the callback) and emits the
    same key set.  Round 14: ``--serve-kv-dtype`` (BENCH_SERVE_KV_DTYPE)
    stores the production windows' KV table in bf16 or int8 — with int8
    a model-dtype comparison window runs on the SAME seeded trace and
    the line carries serve_kv_dtype / serve_kv_bytes_per_slot + the
    bytes ratio and greedy-token agreement — and ``--serve-draft``
    (BENCH_SERVE_DRAFT, 'self' or a GPT size spec) turns the production
    windows speculative (draft-k → verify-1; serve_accept_rate + the
    proposed/accepted ledger ride the line; the monolithic/static
    baselines stay non-speculative on the same trace).  Round 20:
    ``--serve-multi-step K`` (BENCH_SERVE_MULTI_STEP) runs the
    production windows with K decode iterations fused per host dispatch
    (the batcher's pipelined ``advance_multi`` path) plus a K=1 twin
    window on the SAME seeded trace — the line carries
    ``serve_host_gap_s`` / ``serve_dispatches`` and the K-vs-1
    ``serve_tokens_per_sec`` ratio (greedy streams are bitwise
    identical across K; only the dispatch count and host gap move).
    Smoke runs
    shrink the workload via BENCH_SERVE_* env vars (model dims, slots,
    request count, arrival rate, chunk/pool shape) exactly like
    BENCH_PER_CHIP_BATCH."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import create_model
    from distributed_tensorflow_tpu.observability import (
        NULL_TRACER, SLOMonitor, Tracer, serve_section)
    from distributed_tensorflow_tpu.parallel import mesh as meshlib
    from distributed_tensorflow_tpu.serving import (
        ContinuousBatcher, Request, SlotKVCache)

    env = os.environ.get

    def note(msg):
        print(f"[bench --serve] {msg}", file=sys.stderr, flush=True)

    hidden = int(env("BENCH_SERVE_HIDDEN", "512"))
    layers = int(env("BENCH_SERVE_LAYERS", "8"))
    heads = int(env("BENCH_SERVE_HEADS", "8"))
    ffn = int(env("BENCH_SERVE_FFN", "2048"))
    vocab = int(env("BENCH_SERVE_VOCAB", "16384"))
    prompt_len = int(env("BENCH_SERVE_PROMPT_LEN", "32"))
    max_new = int(env("BENCH_SERVE_MAX_NEW", "64"))
    slots = int(env("BENCH_SERVE_SLOTS", "8"))
    n_requests = int(env("BENCH_SERVE_REQUESTS", "32"))
    rate = float(env("BENCH_SERVE_RATE", "4"))  # requests/sec, open loop
    repeats = int(env("BENCH_SERVE_REPEATS", "3"))
    # round-10 workload shape + serving optimizations (defaults model the
    # dominant real-traffic pattern: a shared system prompt on every
    # request, an occasional long prompt that would stall decode):
    # chunked prefill budget (0 = monolithic), prefix-pool capacity in
    # blocks (0 = off), block granularity, shared-prefix length, and
    # every LONG_EVERY-th request carrying a 2×-length prompt
    chunk = int(env("BENCH_SERVE_PREFILL_CHUNK", "16"))
    cache_blocks = int(env("BENCH_SERVE_PREFIX_CACHE", "128"))
    prefix_block = int(env("BENCH_SERVE_PREFIX_BLOCK", "8"))
    shared_len = int(env("BENCH_SERVE_SHARED_PREFIX",
                         str(prompt_len // 2)))
    long_every = int(env("BENCH_SERVE_LONG_EVERY", "4"))
    # SLO targets (BASELINE.md "Goodput accounting": the SLO is part of
    # the number — it rides the line's config) + the sweep/overload shape
    if slo_ttft is None:
        slo_ttft = float(env("BENCH_SERVE_SLO_TTFT", "1.0"))
    if slo_itl is None:
        slo_itl = float(env("BENCH_SERVE_SLO_ITL", "0.25"))
    sweep_points = int(env("BENCH_SERVE_SWEEP_POINTS", "6"))
    sweep_factor = float(env("BENCH_SERVE_SWEEP_FACTOR", "2.0"))
    # round 14: KV storage dtype for the production windows (int8 = int8
    # payload + per-vector f32 scales; with it set, a model-dtype
    # comparison window runs on the SAME seeded trace) and speculative
    # decoding ('self' or a draft GPT size spec; baselines stay
    # non-speculative on the same trace)
    kv_dtype = kv_dtype or env("BENCH_SERVE_KV_DTYPE", "") or None
    draft = draft or env("BENCH_SERVE_DRAFT", "") or None
    # round 16: --serve-kv-layout paged (BENCH_SERVE_KV_LAYOUT) — the
    # production windows run the paged block pool + fused Pallas decode
    # attention; the `kv_base` monolithic window on the SAME seeded trace
    # is then ALSO the paged-vs-monolithic comparison
    # (paged_vs_monolithic_itl_p95), alongside the pool utilization and
    # zero-copy ledger keys
    kv_layout = kv_layout or env("BENCH_SERVE_KV_LAYOUT", "") or "monolithic"
    if kv_layout not in ("monolithic", "paged"):
        raise SystemExit(f"BENCH_SERVE_KV_LAYOUT must be 'monolithic' or "
                         f"'paged', got {kv_layout!r}")
    paged = kv_layout == "paged"
    if draft_k is None:
        draft_k = int(env("BENCH_SERVE_DRAFT_K", "4"))
    # round 15: --replicas N — fleet mode (serving/fleet.py ReplicaSet):
    # a clean N-replica window plus a kill-one-replica chaos window at a
    # seeded decode iteration, emitted as its own line
    replicas = replicas or int(env("BENCH_SERVE_REPLICAS", "0"))
    kill_iter = int(env("BENCH_SERVE_KILL_ITER", "8"))
    # round 18: --disagg P:D (BENCH_SERVE_DISAGG) — the heterogeneous-
    # fleet scenario line: a disaggregated P-prefill/D-decode fleet vs
    # the homogeneous (P+D)-replica fleet on the SAME seeded trace
    # (disagg_vs_homogeneous_itl_p95/p99 + greedy-token parity), an
    # affinity-vs-least-loaded router pair on the same trace
    # (serve_fleet_prefix_hit_rate), and a diurnal burst trace where a
    # queue-driven autoscaled fleet is compared against the static
    # sizes it scales between (serve_replica_seconds + the goodput
    # fraction of the best static)
    disagg = disagg or env("BENCH_SERVE_DISAGG", "") or None
    if disagg and (replicas > 1 or sweep or draft):
        raise SystemExit("--disagg is its own scenario: drop --replicas/"
                         "--sweep/--serve-draft")
    # round 20: --serve-multi-step K (BENCH_SERVE_MULTI_STEP) — the
    # production windows fuse K decode iterations per host dispatch and
    # a K=1 twin window on the SAME seeded trace supplies the ratio;
    # restricted to the default single-replica line (the fleet/disagg/
    # sweep scenarios have their own comparison structure)
    multi_step = multi_step or int(env("BENCH_SERVE_MULTI_STEP",
                                       "0")) or None
    if multi_step is not None and multi_step < 1:
        raise SystemExit(f"--serve-multi-step must be >= 1, "
                         f"got {multi_step}")
    if multi_step and (replicas > 1 or sweep or disagg):
        raise SystemExit("--serve-multi-step rides the default serve "
                         "line: drop --replicas/--sweep/--disagg")

    mesh = meshlib.create_mesh()
    n = mesh.shape[meshlib.DATA_AXIS]
    if slots % n:
        slots = ((slots + n - 1) // n) * n  # slot dim shards over 'data'
    device_kind = jax.devices()[0].device_kind
    # round 19: every window's batcher carries a roofline built from ITS
    # table (storage dtype/layout price the must-read bytes), so the
    # serve lines report serve_prefill_mfu / serve_decode_mbu.  Bench
    # lines are not parity-pinned — roofline rides unconditionally; on
    # an unknown device the utilization keys are None, never invented.
    from distributed_tensorflow_tpu.observability.roofline import Roofline

    long_len = 2 * prompt_len
    max_len = shared_len + long_len + max_new
    model = create_model("gpt", num_classes=vocab, hidden=hidden,
                         layers=layers, heads=heads, ffn=ffn,
                         max_len=max_len, dropout_rate=0.0,
                         dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()

    def _init():
        dummy = jnp.zeros((1, prompt_len), jnp.int32)
        return jax.jit(lambda k: model.init(k, dummy, train=False))(
            jax.random.key(0))["params"]

    params = _init()
    _sync(params)
    note(f"init done in {time.perf_counter() - t0:.0f}s")

    # one open-loop arrival trace shared by EVERY mode and ALL windows:
    # Poisson arrivals at `rate`, mixed prompt and continuation lengths
    # (the staggered-traffic shape static batching idles on), a shared
    # system prefix on every prompt (the shape the prefix pool exists
    # for), and every `long_every`-th request carrying a 2× prompt (the
    # arrival monolithic prefill stalls decode on)
    arrivals = rng.exponential(1.0 / max(rate, 1e-9), n_requests).cumsum()
    p_lens = rng.integers(max(prompt_len // 2, 1), prompt_len + 1,
                          n_requests)
    if long_every:
        p_lens[::long_every] = long_len
    n_news = rng.integers(max(max_new // 2, 1), max_new + 1, n_requests)
    shared = rng.integers(0, vocab, shared_len).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, vocab, pl).astype(np.int32)])
               for pl in p_lens]

    def workload(rate_scale: float = 1.0):
        # one seeded trace for EVERY mode/rate: rescaling the exponential
        # draws is an exact Poisson process at rate/rate_scale with the
        # same request order and lengths — the --sweep ladder stays a
        # same-trace comparison (BASELINE.md rule)
        return [Request(rid=i, prompt=prompts[i],
                        max_new_tokens=int(n_news[i]),
                        arrival_s=float(arrivals[i] * rate_scale))
                for i in range(n_requests)]

    # tables, one workload: `kv` runs the production path (chunk-resumable
    # prefill + prefix pool, at --serve-kv-dtype storage); `kv_base` runs
    # the monolithic/no-cache programs for the chunked-vs-monolithic and
    # continuous-vs-static comparisons on the SAME seeded trace; with a
    # non-default --serve-kv-dtype, `kv_cmp` is the model-dtype twin of
    # the production config for the bf16-vs-int8 same-trace comparison
    resolved_kv_dtype = None
    if kv_dtype:
        resolved_kv_dtype = ("int8" if kv_dtype == "int8"
                             else jnp.dtype(jnp.bfloat16))
    fleet_mode = bool(replicas and replicas > 1)
    disagg_mode = bool(disagg)
    # fleet/disagg modes build their own per-replica tables below and
    # never dispatch these — skip the construction too (each table
    # allocates the full slots×max_len KV buffers on device)
    kv = kv_base = kv_cmp = None
    # paged layout applies to the PRODUCTION tables only: kv_base stays
    # monolithic by construction — it IS the paged-vs-monolithic
    # comparison window on the same trace
    layout_kwargs = {"kv_layout": "paged"} if paged else {}
    if not fleet_mode and not disagg_mode:
        kv = SlotKVCache(model, params, slots, mesh=mesh,
                         kv_dtype=resolved_kv_dtype,
                         prefix_cache_blocks=cache_blocks,
                         prefix_block=prefix_block, **layout_kwargs)
        kv_base = SlotKVCache(model, params, slots, mesh=mesh)
        if resolved_kv_dtype is not None:
            kv_cmp = SlotKVCache(model, params, slots, mesh=mesh,
                                 prefix_cache_blocks=cache_blocks,
                                 prefix_block=prefix_block,
                                 **layout_kwargs)
    # speculative decoding: the draft's own full-precision table, in slot
    # lockstep with `kv` (windows evict everything on exit, so sharing
    # one draft table across windows is safe like sharing `kv`)
    draft_kv = None
    if draft:
        from distributed_tensorflow_tpu.utils.harness import (
            parse_draft_config)

        overrides = parse_draft_config(draft)
        if overrides is None:
            draft_model, draft_params = model, params
        else:
            draft_model = create_model(
                "gpt", num_classes=vocab, max_len=max_len,
                dropout_rate=0.0, dtype=jnp.bfloat16, **overrides)
            dummy = jnp.zeros((1, prompt_len), jnp.int32)
            draft_params = jax.jit(lambda k: draft_model.init(
                    k, dummy, train=False))(
                        jax.random.key(1))["params"]
        if not fleet_mode:
            draft_kv = SlotKVCache(draft_model, draft_params, slots,
                                   mesh=mesh)

    def _serve_ledger_probe():
        """Serving memory/compile accounting (observability/xla_stats):
        compile the production table config's decode + prefill programs
        once through a ProgramLedger on a THROWAWAY table — the timed
        windows stay ledger-free (the observed-jit's per-call signature
        hashing must not ride the latency percentiles).  Returns
        (peak_hbm_bytes_est, compile_total_s), None/None on failure —
        a probe must never kill the bench line."""
        try:
            from distributed_tensorflow_tpu.observability import (
                ProgramLedger)

            ledger = ProgramLedger()
            t = SlotKVCache(model, params, slots, mesh=mesh,
                            kv_dtype=resolved_kv_dtype,
                            prefix_cache_blocks=cache_blocks,
                            prefix_block=prefix_block, ledger=ledger,
                            **layout_kwargs)
            slot, _ = t.begin_insert(
                np.asarray(prompts[0], np.int32))
            while t.prefill_chunk(slot, chunk or None) is None:
                pass
            t.advance()
            t.evict(slot)
            m = ledger.manifest()
            return (m["peak_hbm_bytes_est"] or None,
                    round(m["compile_total_s"], 6))
        except Exception as e:  # noqa: BLE001
            note(f"ledger probe failed: {type(e).__name__}: {e}")
            return None, None

    def _warm():
        # compile the decode step + every prefill bucket AND chunk bucket
        # the workload can hit, outside the timed windows (first-request
        # TTFT must measure serving, not XLA).  Chunk tails bucket to
        # powers of two ≤ the budget, and a prefix hit can shift the
        # resume point anywhere, so warm every power-of-two bucket.
        lens = [len(p) for p in prompts]
        for plen in sorted(set(lens)):
            slot, _ = kv_base.insert(prompts[lens.index(plen)])
            kv_base.advance()
            kv_base.evict(slot)
        buckets = [chunk] if chunk else []
        b = 1
        while chunk and b < chunk:
            buckets.append(b)
            b *= 2
        for table in [kv] + ([kv_cmp] if kv_cmp is not None else []):
            for blen in sorted(set(buckets)):
                slot, _ = table.begin_insert(
                    rng.integers(0, vocab, blen).astype(np.int32))
                while table.prefill_chunk(slot, chunk or None) is None:
                    pass
                table.advance()
                table.evict(slot)
            if not chunk:
                for plen in sorted(set(lens)):
                    slot, _ = table.insert(prompts[lens.index(plen)])
                    table.advance()
                    table.evict(slot)
            if cache_blocks:
                # force one pool HIT so the block-restore program
                # compiles here too (the read side compiled when the
                # admissions above pooled their blocks; the write side
                # only runs on a hit — without this, the first
                # shared-prefix request of window 1 pays its XLA compile
                # inside the measured TTFT)
                longest = max(prompts, key=len)
                for _ in range(2):
                    slot, _ = table.begin_insert(longest)
                    while table.prefill_chunk(slot, chunk or None) is None:
                        pass
                    table.advance()
                    table.evict(slot)
            table.reset_prefix_cache()  # timed windows start cold
        if draft_kv is not None:
            # speculative path: throwaway spec windows compile the
            # draft's decode step, its prefill buckets, and EVERY verify
            # width a round can hit — _spec_k shrinks k_eff to
            # remaining-budget/capacity, so widths 2..draft_k+1 all
            # occur as requests wind down; compiling one inside a timed
            # window would inflate that window's tail percentiles (the
            # first-compile-inside-measurement bug class the prefix-pool
            # warm already guards)
            spec_warm = ContinuousBatcher(
                kv, mode="continuous", prefill_chunk=chunk,
                draft_kv=draft_kv, draft_k=draft_k,
                # round 20: with --serve-multi-step the production
                # windows fuse the draft's proposal loop — the fused
                # widths must compile here, not inside a timed window
                **({"multi_step": multi_step} if multi_step else {}))
            for m in range(2, draft_k + 3):
                spec_warm.run([Request(rid=-m, prompt=prompts[m % 2],
                                       max_new_tokens=m,
                                       arrival_s=0.0)])
            kv.reset_prefix_cache()
        if multi_step:
            # round 20: the fused K-step decode scan compiles once per
            # (shape, K) — warm BOTH widths the windows dispatch (K and
            # the K=1 twin) with the same outside-the-timed-windows
            # discipline as the prefill buckets above
            for k_w in sorted({1, multi_step}):
                slot, _ = kv.begin_insert(prompts[0])
                while kv.prefill_chunk(slot, chunk or None) is None:
                    pass
                kv.advance_multi(k_w)
                kv.evict(slot)
            if cache_blocks:
                kv.reset_prefix_cache()  # timed windows start cold
        note(f"warm: production {kv.compiled_programs()}, "
             f"baseline {kv_base.compiled_programs()}")

    if not fleet_mode and not disagg_mode:
        # fleet/disagg modes warm their own per-replica tables below —
        # the single-replica kv/kv_base/kv_cmp tables are not even built
        _warm()

    tracer = Tracer(path=trace_path) if trace_path else NULL_TRACER
    delivered = [0]
    on_token = ((lambda rid, tok: delivered.__setitem__(0, delivered[0] + 1))
                if stream else None)

    def med(windows, key, vals=None):
        if vals is None:
            vals = [w[key] for w in windows if w.get(key) is not None]
        vals = [v for v in vals if v is not None]
        return statistics.median(vals) if vals else None

    def window(mode, table, budget, label, rate_scale=1.0, cap=0,
               spec=False, sink=None, multi=None):
        def _one(rep):
            delivered[0] = 0   # per-window count: the emitted number must
            if table.prefix_cache_blocks:
                # cold pool per window: the hit rate is then a
                # deterministic property of the workload, not of how many
                # windows ran before this one
                table.reset_prefix_cache()
            deliver = on_token
            if sink is not None:
                # token-collecting window (the kv-dtype greedy-agreement
                # comparison): per-rid streams instead of the counter
                deliver = (lambda rid, tok:
                           sink.setdefault(rid, []).append(tok))
            # one SLOMonitor per window (goodput is a per-window number)
            batcher = ContinuousBatcher(
                table, tracer=tracer, mode=mode, prefill_chunk=budget,
                slo=SLOMonitor(slo_ttft, slo_itl), queue_cap=cap,
                draft_kv=draft_kv if spec else None, draft_k=draft_k,
                roofline=Roofline.for_kv(table, device_kind, n),
                # flag-off windows must construct the batcher exactly
                # as before (multi_step=None is the same thing, but the
                # conditional keeps the call-site byte-honest)
                **({"multi_step": multi} if multi else {}))
            summary = serve_section(batcher.run(workload(rate_scale),
                                                on_token=deliver), n)
            if stream:         # describe ONE window, not every mode×repeat
                summary["tokens_delivered"] = delivered[0]
            note(f"{label} window {rep}: "
                 f"{summary['serve_requests_per_sec_per_chip']:.3f} "
                 f"req/s/chip, ttft_p95 "
                 f"{summary['serve_ttft_p95_s'] * 1e3:.1f} ms, "
                 f"goodput {summary['serve_goodput_under_slo']:.3f}/s, "
                 f"{summary['decode_iterations']} decode iterations, "
                 f"{summary['shed_requests']} shed")
            return summary
        return _one

    if disagg_mode:
        # ---------------------------------------- disagg scenario (round 18)
        # Three same-trace comparisons on one line:
        #   1. disaggregated P-prefill/D-decode fleet vs the homogeneous
        #      (P+D)-replica fleet — decode replicas never share an
        #      iteration with a long prompt, so the disagg ITL tail
        #      should drop (disagg_vs_homogeneous_itl_p95/p99, < 1 =
        #      disagg wins) with greedy tokens unchanged;
        #   2. affinity vs least-loaded routing on the homogeneous fleet
        #      — shared-prefix traffic lands where the pool is warm
        #      (serve_fleet_prefix_hit_rate vs the least-loaded rate);
        #   3. a diurnal quiet→burst→quiet trace where the autoscaled
        #      fleet (1:N on queue depth) is compared against every
        #      static size it scales between — goodput fraction of the
        #      best static at the replica-seconds actually spent.
        from distributed_tensorflow_tpu.serving import ReplicaSet
        from distributed_tensorflow_tpu.utils.harness import (
            parse_disaggregate)

        n_prefill, n_decode = parse_disaggregate(disagg)
        total = n_prefill + n_decode
        roles = ["prefill"] * n_prefill + ["decode"] * n_decode

        def mk_tables(spec_roles):
            """One production-config table per entry of ``spec_roles``
            (None = homogeneous, pool on).  Disagg decode tables carry no
            prefix pool (they never prefill — pool warmth lives prefill-
            side) but DO warm the handoff restore program; prefill
            tables warm extract.  Same warm discipline as fleet mode:
            every program a window can hit compiles here, outside the
            timed windows."""
            tables = []
            lens = sorted({len(p) for p in prompts})
            for role in spec_roles:
                pool = 0 if role == "decode" else cache_blocks
                t = SlotKVCache(model, params, slots, mesh=mesh,
                                kv_dtype=resolved_kv_dtype,
                                prefix_cache_blocks=pool,
                                prefix_block=prefix_block,
                                **layout_kwargs)
                if chunk and role != "decode":
                    buckets, b = [chunk], 1
                    while b < chunk:
                        buckets.append(b)
                        b *= 2
                    for blen in sorted(set(buckets)):
                        slot, _ = t.begin_insert(
                            rng.integers(0, vocab, blen).astype(np.int32))
                        while t.prefill_chunk(slot, chunk) is None:
                            pass
                        t.advance()
                        t.evict(slot)
                for plen in lens:
                    slot, _ = t.insert(prompts[
                        [len(p) for p in prompts].index(plen)])
                    t.advance()
                    if role == "prefill":
                        # prefill side serializes finished KV out —
                        # warm the extract program at every length
                        t.extract_handoff(slot)
                    t.evict(slot)
                if role == "decode":
                    # decode side admits via restore only: warm it from
                    # a throwaway extract at every prompt length
                    for plen in lens:
                        slot, _ = t.insert(prompts[
                            [len(p) for p in prompts].index(plen)])
                        payload = t.extract_handoff(slot)
                        t.evict(slot)
                        rslot, _ = t.restore_handoff(payload)
                        t.advance()
                        t.evict(rslot)
                if pool:
                    longest = max(prompts, key=len)
                    for _ in range(2):
                        slot, _ = t.insert(longest)
                        t.advance()
                        t.evict(slot)
                    t.reset_prefix_cache()
                tables.append(t)
            return tables

        homog_tables = mk_tables([None] * total)
        disagg_tables = mk_tables(roles)

        def diurnal_workload():
            # one seeded quiet→burst→quiet trace (the diurnal shape
            # autoscaling exists for): same prompts/lengths as the flat
            # trace, arrivals re-drawn at [rate, 4×rate, rate]
            rng_d = np.random.default_rng(7)
            seg = max(n_requests // 3, 1)
            t_arr, arr = 0.0, []
            for k, r in enumerate((rate, 4.0 * rate, rate)):
                count = (n_requests - 2 * seg) if k == 2 else seg
                for _ in range(max(count, 0)):
                    t_arr += rng_d.exponential(1.0 / max(r, 1e-9))
                    arr.append(t_arr)
            return [Request(rid=i, prompt=prompts[i],
                            max_new_tokens=int(n_news[i]),
                            arrival_s=float(arr[i]))
                    for i in range(n_requests)]

        def hetero_window(label, tables, *, w_roles=None,
                          routing="least-loaded", autoscale=None,
                          wl=None, sink=None):
            def _one(rep):
                for t in tables:
                    if t.prefix_cache_blocks:
                        t.reset_prefix_cache()
                kwargs = {}
                if w_roles is not None:
                    kwargs["roles"] = w_roles
                if routing != "least-loaded":
                    kwargs["routing"] = routing
                if autoscale is not None:
                    kwargs["autoscale"] = autoscale
                deliver = on_token
                if sink is not None and rep == 0:
                    deliver = (lambda rid, tok:
                               sink.setdefault(rid, []).append(tok))
                rs = ReplicaSet(tables, tracer=tracer,
                                prefill_chunk=chunk, queue_cap=queue_cap,
                                slo=SLOMonitor(slo_ttft, slo_itl),
                                roofline=Roofline.for_kv(
                                    tables[0], device_kind, n),
                                **kwargs)
                t_w = time.perf_counter()
                try:
                    summary = serve_section(
                        rs.run(wl() if wl else workload(),
                               on_token=deliver), n)
                finally:
                    rs.close()
                summary["window_elapsed_s"] = time.perf_counter() - t_w
                note(f"{label} window {rep}: "
                     f"{summary['completed']}/{summary['offered']} done, "
                     f"itl_p95 {summary['serve_itl_p95_s'] * 1e3:.1f} ms, "
                     f"goodput {summary['serve_goodput_under_slo']:.3f}/s")
                return summary
            return _one

        homog_sink: dict[int, list] = {}
        disagg_sink: dict[int, list] = {}
        try:
            homog = measure_windows(
                hetero_window("homog", homog_tables, sink=homog_sink),
                repeats)
            dis = measure_windows(
                hetero_window("disagg", disagg_tables, w_roles=roles,
                              sink=disagg_sink),
                repeats)
            aff = (measure_windows(
                hetero_window("affinity", homog_tables,
                              routing="affinity"),
                1) if cache_blocks else [])
            auto = measure_windows(
                hetero_window("diurnal_autoscale", homog_tables,
                              autoscale=f"1:{total}",
                              wl=diurnal_workload),
                1)
            statics = []
            for n_static in sorted({1, total}):
                w = measure_windows(
                    hetero_window(f"diurnal_static{n_static}",
                                  homog_tables[:n_static],
                                  wl=diurnal_workload),
                    1)
                if w:
                    statics.append((n_static, w[0]))
        finally:
            tracer.close()

        h95 = med(homog, "serve_itl_p95_s")
        h99 = med(homog, "serve_itl_p99_s")
        d95 = med(dis, "serve_itl_p95_s")
        d99 = med(dis, "serve_itl_p99_s")
        parity = (sorted(homog_sink) == sorted(disagg_sink)
                  and all(homog_sink[r] == disagg_sink[r]
                          for r in homog_sink))
        aff_rate = (aff[0].get("serve_fleet_prefix_hit_rate")
                    if aff else None)
        ll_rate = med(homog, "serve_prefix_cache_hit_rate")
        auto_w = auto[0] if auto else None
        best_static = max(statics, key=lambda s:
                          s[1].get("serve_goodput_under_slo") or 0.0,
                          default=None)
        frac = None
        if auto_w and best_static:
            bg = best_static[1].get("serve_goodput_under_slo") or 0.0
            ag = auto_w.get("serve_goodput_under_slo") or 0.0
            frac = round(ag / bg, 4) if bg else None
        print(json.dumps({
            "metric": "gpt_serve_disagg_itl_p95_ratio",
            "value": (round(d95 / h95, 3) if d95 and h95 else None),
            "unit": "disagg/homogeneous itl_p95 ratio (< 1 = disagg wins)",
            "vs_baseline": None,
            "method": (f"{n_prefill}P+{n_decode}D disaggregated fleet "
                       f"(KV handoff) vs {total} homogeneous replicas "
                       f"on the SAME seeded Poisson trace ({rate}/s × "
                       f"{n_requests}, long prompt every {long_every}), "
                       f"median of {len(dis)}/{len(homog)}; affinity "
                       f"router vs least-loaded on the same trace; "
                       f"diurnal quiet/4×burst/quiet trace: autoscaled "
                       f"1:{total} vs static sizes"),
            # the three `analyze diff` gate keys (ISSUE 18)
            "disagg_vs_homogeneous_itl_p95": (
                round(d95 / h95, 3) if d95 and h95 else None),
            "disagg_vs_homogeneous_itl_p99": (
                round(d99 / h99, 3) if d99 and h99 else None),
            "serve_fleet_prefix_hit_rate": aff_rate,
            "serve_replica_seconds": (
                auto_w.get("serve_replica_seconds") if auto_w else None),
            "greedy_tokens_match": parity,
            "least_loaded_prefix_hit_rate": ll_rate,
            "affinity_beats_least_loaded": (
                aff_rate > ll_rate
                if aff_rate is not None and ll_rate is not None
                else None),
            "autoscale_goodput_fraction_of_best_static": frac,
            "best_static_replicas": (best_static[0]
                                     if best_static else None),
            "best_static_goodput": (
                best_static[1].get("serve_goodput_under_slo")
                if best_static else None),
            "static_replica_seconds": {
                str(ns): round(ns * w["window_elapsed_s"], 3)
                for ns, w in statics},
            "autoscale": auto_w.get("autoscale") if auto_w else None,
            "serve_disagg": dis[0].get("serve_disagg"),
            "homogeneous": {k: med(homog, k) for k in (
                "serve_requests_per_sec_per_chip", "serve_ttft_p95_s",
                "serve_itl_p95_s", "serve_itl_p99_s",
                "serve_goodput_under_slo",
                "serve_prefill_mfu", "serve_decode_mbu")},
            "disagg": {k: med(dis, k) for k in (
                "serve_requests_per_sec_per_chip", "serve_ttft_p95_s",
                "serve_itl_p95_s", "serve_itl_p99_s",
                "serve_goodput_under_slo",
                "serve_prefill_mfu", "serve_decode_mbu")},
            "slo": {"ttft_s": slo_ttft, "itl_s": slo_itl,
                    "quantile": 0.99},
            "config": {"disaggregate": disagg,
                       "prefill_replicas": n_prefill,
                       "decode_replicas": n_decode,
                       "slots_per_replica": slots,
                       "requests": n_requests,
                       "arrival_rate_per_s": rate,
                       "prompt_len": prompt_len,
                       "max_new_tokens": max_new, "vocab": vocab,
                       "hidden": hidden, "layers": layers,
                       "heads": heads, "ffn": ffn, "max_len": max_len,
                       "dtype": "bfloat16", "greedy": True,
                       "prefill_chunk": chunk,
                       "prefix_cache_blocks": cache_blocks,
                       "prefix_block": prefix_block,
                       "shared_prefix": shared_len,
                       "long_every": long_every,
                       "kv_dtype": homog_tables[0].kv_dtype,
                       "kv_layout": kv_layout},
            "device": device_kind,
            "n_devices": n,
            "synthetic": True,
            "jax_version": jax.__version__,
            "xla_flags": os.environ.get("XLA_FLAGS"),
            "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS"),
        }))
        return

    if fleet_mode:
        # ------------------------------------------- fleet mode (round 15)
        # A clean N-replica ReplicaSet window (least-loaded router, every
        # replica its own production-config table) and a CHAOS window on
        # the SAME seeded trace with one replica crash-injected at a
        # seeded decode iteration — the failover keys
        # (serve_failover_recovery_p95_s, serve_duplicate_emissions) and
        # the exactly-once conservation check come from the chaos window;
        # throughput is the clean window's.
        from distributed_tensorflow_tpu.serving import (
            FaultInjector, ReplicaSet)

        def fleet_tables(count):
            tables = []
            for _ in range(count):
                t = SlotKVCache(model, params, slots, mesh=mesh,
                                kv_dtype=resolved_kv_dtype,
                                prefix_cache_blocks=cache_blocks,
                                prefix_block=prefix_block,
                                **layout_kwargs)
                # warm THIS table's programs outside the timed windows
                # (same discipline as _warm: chunk buckets + monolithic
                # buckets + one pool hit)
                lens = sorted({len(p) for p in prompts})
                if chunk:
                    # same doubling enumeration as _warm: every
                    # power-of-two chunk-tail bucket below the budget,
                    # plus the budget itself
                    buckets, b = [chunk], 1
                    while b < chunk:
                        buckets.append(b)
                        b *= 2
                    for blen in sorted(set(buckets)):
                        slot, _ = t.begin_insert(
                            rng.integers(0, vocab, blen).astype(np.int32))
                        while t.prefill_chunk(slot, chunk) is None:
                            pass
                        t.advance()
                        t.evict(slot)
                for plen in lens:
                    slot, _ = t.insert(prompts[
                        [len(p) for p in prompts].index(plen)])
                    t.advance()
                    t.evict(slot)
                if cache_blocks:
                    longest = max(prompts, key=len)
                    for _ in range(2):
                        slot, _ = t.insert(longest)
                        t.advance()
                        t.evict(slot)
                t.reset_prefix_cache()
                tables.append(t)
            return tables

        def fleet_drafts(count):
            if not draft:
                return None
            return [SlotKVCache(draft_model, draft_params, slots,
                                mesh=mesh) for _ in range(count)]

        # one table set for the clean windows, a FRESH set for the chaos
        # window (arming a FaultInjector monkeypatches table methods —
        # the clean tables must stay pristine); compiles happen here,
        # outside every timed window — incl. the drafts' programs and
        # every verify width a speculative round can hit (throwaway spec
        # windows, the same first-compile guard _warm's spec-warm gives
        # the single-replica path)
        clean_tables = fleet_tables(replicas)
        chaos_tables = fleet_tables(replicas)
        clean_drafts = fleet_drafts(replicas)
        chaos_drafts = fleet_drafts(replicas)

        def warm_spec(tables, drafts):
            if drafts is None:
                return
            for t, d in zip(tables, drafts):
                spec_warm = ContinuousBatcher(
                    t, mode="continuous", prefill_chunk=chunk,
                    draft_kv=d, draft_k=draft_k)
                for m in range(2, draft_k + 3):
                    spec_warm.run([Request(rid=-m, prompt=prompts[m % 2],
                                           max_new_tokens=m,
                                           arrival_s=0.0)])
                if t.prefix_cache_blocks:
                    t.reset_prefix_cache()

        warm_spec(clean_tables, clean_drafts)
        warm_spec(chaos_tables, chaos_drafts)

        def fleet_window(label, tables, drafts, fault_spec=None):
            def _one(rep):
                for t in tables:
                    if t.prefix_cache_blocks:
                        # cold pool per window (the BASELINE pool-warmth
                        # rule): the hit rate is a property of the
                        # workload, not the window ordinal
                        t.reset_prefix_cache()
                injector = (FaultInjector(fault_spec, seed=rep)
                            if fault_spec else None)
                rs = ReplicaSet(
                    tables, tracer=tracer, prefill_chunk=chunk,
                    queue_cap=queue_cap,
                    slo=SLOMonitor(slo_ttft, slo_itl),
                    roofline=Roofline.for_kv(tables[0], device_kind, n),
                    draft_kvs=drafts, draft_k=draft_k,
                    watchdog_timeout_s=float(
                        env("BENCH_SERVE_WATCHDOG_S", "0")),
                    fault_injector=injector)
                try:
                    summary = serve_section(rs.run(workload(),
                                                   on_token=on_token), n)
                finally:
                    rs.close()
                fl = summary["serve_fleet"]
                note(f"{label} window {rep}: "
                     f"{summary['completed']}/{summary['offered']} done, "
                     f"{fl['failovers']} failovers, "
                     f"{fl['duplicate_emissions']} dups, "
                     f"{summary['serve_requests_per_sec_per_chip']:.3f} "
                     f"req/s/chip")
                return summary
            return _one

        try:
            clean = measure_windows(
                fleet_window("fleet", clean_tables, clean_drafts),
                repeats)
            chaos_spec = f"crash:replica=0,iter={kill_iter}"
            chaos_wins = measure_windows(
                fleet_window("fleet_chaos", chaos_tables, chaos_drafts,
                             fault_spec=chaos_spec),
                1)
            chaos = chaos_wins[0] if chaos_wins else None
        finally:
            tracer.close()
        line = {k: med(clean, k) for k in (
            "serve_requests_per_sec_per_chip", "serve_requests_per_sec",
            "serve_tokens_per_sec", "serve_ttft_p50_s",
            "serve_ttft_p95_s", "serve_ttft_p99_s", "serve_itl_p50_s",
            "serve_itl_p95_s", "serve_itl_p99_s",
            "serve_goodput_under_slo", "serve_shed_rate",
            "serve_prefill_mfu", "serve_decode_mbu")}
        peak_hbm, ledger_compile_s = _serve_ledger_probe()
        rps = line["serve_requests_per_sec_per_chip"]
        chaos_fl = (chaos or {}).get("serve_fleet") or {}
        print(json.dumps({
            "metric": "gpt_serve_fleet_requests_per_sec_per_chip",
            "value": round(rps, 4) if rps else None,
            "unit": "requests/sec/chip",
            "vs_baseline": None,
            "method": (f"{replicas}-replica ReplicaSet, least-loaded "
                       f"router, open-loop Poisson {rate}/s × "
                       f"{n_requests} requests, median of {len(clean)}; "
                       f"chaos window: seeded crash of replica 0 at "
                       f"decode iteration {kill_iter}"),
            **{k: (round(v, 6) if isinstance(v, float) else v)
               for k, v in line.items()},
            "replicas": replicas,
            # per-replica decode/prefill program footprint + compile cost
            # (one replica's table; N replicas hold N copies)
            "peak_hbm_bytes_est": peak_hbm,
            "compile_total_s": ledger_compile_s,
            # round 19: fleet roofline section of the first clean window
            # (per-replica tallies + the peak-table revision the MFU/MBU
            # medians in `line` divide by)
            "roofline_peak_table_revision": _rf_revision(),
            "roofline": clean[0].get("roofline"),
            "serve_fleet": clean[0].get("serve_fleet"),
            # the failover gate keys come from the CHAOS window (the
            # clean window has no failovers to measure)
            "serve_failover_recovery_p95_s": (
                (chaos or {}).get("serve_failover_recovery_p95_s")),
            "serve_duplicate_emissions": (
                (chaos or {}).get("serve_duplicate_emissions")),
            "chaos": None if chaos is None else {
                "kill_iteration": kill_iter,
                "offered": chaos["offered"],
                "completed": chaos["completed"],
                "unserved_requests": chaos["unserved_requests"],
                "shed_requests": chaos["shed_requests"],
                "conservation_exact": (
                    chaos["completed"] + chaos["shed_requests"]
                    + chaos["unserved_requests"] == chaos["offered"]),
                "completed_exactly_once": (
                    chaos["completed"] == chaos["offered"]
                    and chaos["serve_duplicate_emissions"] == 0),
                "failovers": chaos_fl.get("failovers"),
                "retries": chaos_fl.get("retries"),
                "requeued_requests": chaos_fl.get("requeued_requests"),
                "fenced_emissions": chaos_fl.get("fenced_emissions"),
                "recovery_p95_s": chaos_fl.get(
                    "failover_recovery_p95_s"),
            },
            "slo": {"ttft_s": slo_ttft, "itl_s": slo_itl,
                    "quantile": 0.99},
            "config": {"slots_per_replica": slots, "replicas": replicas,
                       "requests": n_requests,
                       "arrival_rate_per_s": rate,
                       "prompt_len": prompt_len,
                       "max_new_tokens": max_new, "vocab": vocab,
                       "hidden": hidden, "layers": layers,
                       "heads": heads, "ffn": ffn, "max_len": max_len,
                       "dtype": "bfloat16", "greedy": True,
                       "prefill_chunk": chunk,
                       "prefix_cache_blocks": cache_blocks,
                       "prefix_block": prefix_block,
                       "shared_prefix": shared_len,
                       "long_every": long_every,
                       "kv_dtype": clean_tables[0].kv_dtype,
                       "draft": draft,
                       "draft_k": draft_k if draft else None,
                       "kill_iter": kill_iter},
            "device": device_kind,
            "n_devices": n,
            "synthetic": True,
            "jax_version": jax.__version__,
            "xla_flags": os.environ.get("XLA_FLAGS"),
            "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS"),
        }))
        return

    if sweep:
        # ------------------------------------------------ SLO load harness
        # walk the arrival rate up a geometric ladder on the SAME seeded
        # trace; goodput-under-SLO rises with offered load until the
        # batcher saturates, then falls (requests still complete, but
        # outside the SLO) — the knee is the capacity number.  Early-stop
        # once goodput falls below the best seen: points past the knee
        # only measure collapse.
        sweep_repeats = int(env("BENCH_SERVE_SWEEP_REPEATS", "1"))
        ladder = []
        best = None
        try:
            for k in range(sweep_points):
                r = rate * sweep_factor ** k
                wins = measure_windows(
                    window("continuous", kv, chunk, f"sweep@{r:g}/s",
                           rate_scale=rate / r, spec=True),
                    sweep_repeats)
                if not wins:
                    break
                row = {
                    "arrival_rate_per_s": r,
                    "goodput_under_slo": med(wins,
                                             "serve_goodput_under_slo"),
                    "slo_attainment": med(
                        wins, None,
                        vals=[w["slo"]["slo_attainment"] for w in wins
                              if w.get("slo")]),
                    "requests_per_sec": med(wins, "serve_requests_per_sec"),
                    "ttft_p99_s": med(wins, "serve_ttft_p99_s"),
                    "itl_p99_s": med(wins, "serve_itl_p99_s"),
                    "queue_wait_p99_s": med(wins,
                                            "serve_queue_wait_p99_s"),
                    "completed": med(wins, "completed"),
                }
                ladder.append(row)
                g = row["goodput_under_slo"] or 0.0
                note(f"sweep rate {r:g}/s: goodput {g:.3f}/s under SLO")
                if best is None or g > (best["goodput_under_slo"] or 0.0):
                    best = row
                elif g < (best["goodput_under_slo"] or 0.0) * 0.95:
                    note("goodput fell past the knee — early stop")
                    break
            knee = best["arrival_rate_per_s"] if best else None
            max_goodput = best["goodput_under_slo"] if best else None
            # saturation window: 2× the knee rate with bounded admission —
            # proves the service DEGRADES (sheds with accounting, queue
            # wait stays bounded) instead of collapsing into unbounded
            # queue wait (the ISSUE/ROADMAP graceful-overload criterion)
            over = None
            over_rate = None
            cap = queue_cap or slots
            if knee:
                over_rate = 2.0 * knee
                over_wins = measure_windows(
                    window("continuous", kv, chunk,
                           f"overload@{over_rate:g}/s",
                           rate_scale=rate / over_rate, cap=cap,
                           spec=True),
                    sweep_repeats)
                if over_wins:
                    over = over_wins[0]
        finally:
            tracer.close()
        print(json.dumps({
            "metric": "gpt_serve_max_goodput_under_slo",
            "value": (round(max_goodput, 4)
                      if max_goodput is not None else None),
            "unit": "requests/sec under SLO",
            "vs_baseline": None,
            "method": (f"Poisson arrival-rate sweep ×{sweep_factor:g} "
                       f"from {rate:g}/s (same seeded trace, "
                       f"{len(ladder)} points, early-stop on goodput "
                       f"fall), SLO ttft≤{slo_ttft:g}s itl(p99)≤"
                       f"{slo_itl:g}s; overload window at 2×knee with "
                       f"queue cap {cap}"),
            "serve_max_goodput_under_slo": max_goodput,
            "serve_knee_rate_per_s": knee,
            "sweep": ladder,
            # the saturation window's accounting: shedding engaged,
            # queue wait bounded, conservation exact
            "serve_shed_rate": (over or {}).get("serve_shed_rate"),
            "serve_overload_queue_wait_p99_s": (
                (over or {}).get("serve_queue_wait_p99_s")),
            "serve_overload_rate_per_s": over_rate,
            "overload": over,
            "slo": {"ttft_s": slo_ttft, "itl_s": slo_itl,
                    "quantile": 0.99},
            "config": {"slots": slots, "requests": n_requests,
                       "base_arrival_rate_per_s": rate,
                       "sweep_factor": sweep_factor,
                       "sweep_points": sweep_points,
                       "queue_cap": cap, "prompt_len": prompt_len,
                       "max_new_tokens": max_new, "vocab": vocab,
                       "hidden": hidden, "layers": layers,
                       "heads": heads, "ffn": ffn, "max_len": max_len,
                       "dtype": "bfloat16", "greedy": True,
                       "prefill_chunk": chunk,
                       "prefix_cache_blocks": cache_blocks,
                       "prefix_block": prefix_block,
                       "shared_prefix": shared_len,
                       "long_every": long_every,
                       "kv_dtype": kv.kv_dtype,
                       "draft": draft,
                       "draft_k": draft_k if draft else None},
            "device": device_kind,
            "n_devices": n,
            "synthetic": True,
            "jax_version": jax.__version__,
            "xla_flags": os.environ.get("XLA_FLAGS"),
            "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS"),
        }))
        return

    try:
        # production path: chunked prefill + prefix pool (+ the bounded-
        # admission cap when --serve-queue-cap is set; speculative when
        # --serve-draft is; at --serve-kv-dtype storage)
        cont = measure_windows(window("continuous", kv, chunk, "serve",
                                      cap=queue_cap, spec=True,
                                      multi=multi_step),
                               repeats)
        # round 20: the K=1 twin of the production config on the SAME
        # seeded trace — one host dispatch per decode iteration through
        # the same pipelined path, so the K-vs-1 tokens/sec ratio and
        # dispatch delta isolate the fusion win (greedy streams are
        # bitwise identical across K by construction)
        ms1 = []
        if multi_step and multi_step > 1:
            ms1 = measure_windows(
                window("continuous", kv, chunk, "serve_multi_k1",
                       cap=queue_cap, spec=True, multi=1),
                1)
        # monolithic/no-cache continuous on the same trace — the
        # chunked-vs-monolithic comparison (BASELINE.md "Prefill
        # accounting": same arrivals, same per-iteration token budget
        # question answered by the ITL/TTFT deltas, not throughput alone)
        mono = measure_windows(
            window("continuous", kv_base, 0, "serve_monolithic"),
            repeats)
        stat = measure_windows(window("static", kv_base, 0,
                                      "serve_static"),
                               repeats)
        # --serve-kv-dtype: the model-dtype twin of the production config
        # on the SAME seeded trace (BASELINE same-trace rule) — one
        # token-collecting window each side gives the greedy-agreement
        # number alongside the bytes/latency comparison
        kv_cmp_line = None
        if kv_cmp is not None:
            prod_sink: dict[int, list[int]] = {}
            base_sink: dict[int, list[int]] = {}
            prod_wins = measure_windows(
                window("continuous", kv, chunk, "serve_kv_prod",
                       spec=True, sink=prod_sink),
                1)
            cmp_wins = measure_windows(
                window("continuous", kv_cmp, chunk, "serve_kv_baseline",
                       sink=base_sink),
                1)
            if prod_wins and cmp_wins:
                shared = sorted(set(prod_sink) & set(base_sink))
                matched = sum(prod_sink[r] == base_sink[r]
                              for r in shared)
                cmp_w = cmp_wins[0]
                prod_bytes = prod_wins[0]["serve_kv_bytes_per_slot"]
                cmp_bytes = cmp_w["serve_kv_bytes_per_slot"]
                kv_cmp_line = {
                    "kv_dtype": cmp_w["serve_kv_dtype"],
                    "serve_kv_bytes_per_slot": cmp_bytes,
                    "tokens_per_sec": cmp_w["serve_tokens_per_sec"],
                    "itl_p95_s": cmp_w["serve_itl_p95_s"],
                    "ttft_p50_s": cmp_w["serve_ttft_p50_s"],
                    # stored-bytes ratio (production / model-dtype) and
                    # the fraction of requests whose greedy streams agree
                    # token-for-token — the tolerance-based acceptance
                    "kv_bytes_ratio": (round(prod_bytes / cmp_bytes, 4)
                                       if cmp_bytes else None),
                    "greedy_token_match": (matched / len(shared)
                                           if shared else None),
                }
    finally:
        # drain the span sink even when every window died — the spans up
        # to the failure are exactly the ones worth keeping
        tracer.close()

    serve_keys = ("serve_requests_per_sec_per_chip",
                  "serve_requests_per_sec", "serve_tokens_per_sec",
                  "serve_ttft_p50_s", "serve_ttft_p95_s",
                  "serve_ttft_p99_s",
                  "serve_itl_p50_s", "serve_itl_p95_s",
                  "serve_itl_p99_s",
                  # round 10: prefill/decode token split + prefix-pool
                  # hit rate ride the default AND --stream lines, so the
                  # BENCH_*.json serving trajectory captures them
                  "serve_prefill_tokens_per_sec",
                  "serve_decode_tokens_per_sec",
                  "serve_prefix_cache_hit_rate",
                  # round 13: queue-pressure percentiles + goodput under
                  # the SLO + shed accounting (0.0 shed at an uncapped
                  # fixed rate — the key exists so `analyze diff` gates
                  # it the day a cap or a regression sheds)
                  "serve_queue_wait_p50_s", "serve_queue_wait_p95_s",
                  "serve_queue_wait_p99_s",
                  "serve_goodput_under_slo", "serve_shed_rate",
                  # round 14: KV-table bytes per slot (the --serve-kv-
                  # dtype capacity number) + the speculative-decode
                  # accept rate (None without a draft; tokens/sec stays
                  # emitted-tokens-only either way)
                  "serve_kv_bytes_per_slot", "serve_accept_rate",
                  # round 16: paged KV pool accounting (None under
                  # monolithic — the keys exist so `analyze diff` gates
                  # them when both runs page): physical blocks in use,
                  # pool utilization, and the fraction of reusable
                  # prefix blocks shared zero-copy by pointer
                  "serve_kv_blocks_in_use", "serve_kv_block_utilization",
                  "serve_prefix_zero_copy_hit_rate",
                  # round 19: per-phase utilization from the batcher's
                  # roofline (analytic model flops / must-read bytes over
                  # the peak table) — `analyze diff` gates both
                  # higher-is-better; None on an unknown device
                  "serve_prefill_mfu", "serve_decode_mbu")
    line = {k: med(cont, k) for k in serve_keys}
    # serving program memory/compile accounting — probed outside the
    # timed windows on a throwaway ledger-observed table
    peak_hbm, ledger_compile_s = _serve_ledger_probe()
    rps = line["serve_requests_per_sec_per_chip"]
    static_rps = med(stat, "serve_requests_per_sec_per_chip")
    mono_itl95 = med(mono, "serve_itl_p95_s")
    mono_ttft50 = med(mono, "serve_ttft_p50_s")
    # round 20: K=1 twin numbers for the fusion ratio (at K=1 the twin
    # IS the production window — the ratio degenerates to 1.0)
    k1_tps = k1_disp = None
    if multi_step:
        src = ms1 if ms1 else cont
        k1_tps = med(src, "serve_tokens_per_sec")
        k1_disp = med(src, "serve_dispatches")
    print(json.dumps({
        "metric": "gpt_serve_requests_per_sec_per_chip",
        "value": round(rps, 4) if rps else None,
        "unit": "requests/sec/chip",
        "vs_baseline": None,
        "method": (f"continuous batching, {slots} slots, open-loop "
                   f"Poisson {rate}/s × {n_requests} requests "
                   f"(shared {shared_len}-token prefix, 2× prompt every "
                   f"{long_every}), chunked prefill {chunk} + prefix "
                   f"cache {cache_blocks}×{prefix_block}, median "
                   f"of {len(cont)}"),
        **{k: (round(v, 6) if isinstance(v, float) else v)
           for k, v in line.items()},
        "serve_decode_iterations": med(cont, "decode_iterations"),
        "serve_completed": med(cont, "completed"),
        "serve_prefill_chunks": med(cont, "prefill_chunks"),
        "serve_shed_requests": med(cont, "shed_requests"),
        "serve_queue_depth_p95": med(cont, "queue_depth_p95"),
        # round 14: KV storage attribution (environment-style — the dtype
        # is part of the number) + the speculative ledger of the FIRST
        # production window (counts, not rates — medians would tear the
        # conservation identity) and the same-trace model-dtype baseline
        # when --serve-kv-dtype is set
        "serve_kv_dtype": (cont[0].get("serve_kv_dtype")),
        # round 17: decode/prefill program footprint (memory_analysis,
        # summed per program) + measured compile seconds of the
        # production table config — the `analyze diff` memory gates
        "peak_hbm_bytes_est": peak_hbm,
        "compile_total_s": ledger_compile_s,
        # round 19: the window roofline's provenance + per-phase tallies
        # (model flops / must-read bytes / phase seconds) of the first
        # production window — the MFU/MBU medians above divide by the
        # peak-table revision stated here
        "roofline_peak_table_revision": _rf_revision(),
        "roofline": cont[0].get("roofline"),
        "speculative": cont[0].get("speculative"),
        "kv_baseline": kv_cmp_line,
        "slo": {"ttft_s": slo_ttft, "itl_s": slo_itl, "quantile": 0.99,
                "attainment": med(cont, None,
                                  vals=[(w.get("slo") or {}).get(
                                      "slo_attainment") for w in cont])},
        # monolithic/no-cache continuous on the SAME trace: the ITL-p95
        # and TTFT-p50 deltas are THE round-10 headline numbers (decode
        # interference bounded by the chunk budget; shared prompts not
        # recomputed)
        "monolithic_itl_p95_s": mono_itl95,
        "monolithic_ttft_p50_s": mono_ttft50,
        "monolithic_requests_per_sec_per_chip": med(
            mono, "serve_requests_per_sec_per_chip"),
        "monolithic_decode_iterations": med(mono, "decode_iterations"),
        "chunked_vs_monolithic_itl_p95": (
            round(line["serve_itl_p95_s"] / mono_itl95, 3)
            if line["serve_itl_p95_s"] and mono_itl95 else None),
        # round 16: with --serve-kv-layout paged the production windows
        # page and `kv_base` is the monolithic twin on the SAME seeded
        # trace — this ratio is THE paged-vs-monolithic latency number
        # (< 1 = the fused paged kernel beats the monolithic gather);
        # None under monolithic (the two windows would be the same
        # layout, a ratio of noise).  The paged section (pool shape +
        # zero-copy/CoW ledger) comes from the first production window.
        "paged_vs_monolithic_itl_p95": (
            round(line["serve_itl_p95_s"] / mono_itl95, 3)
            if paged and line["serve_itl_p95_s"] and mono_itl95
            else None),
        "serve_kv_layout": kv_layout,
        "paged": cont[0].get("paged"),
        # round 20: multi-step dispatch accounting — gated on the flag
        # so the flag-off line's key set is unchanged: fused width K,
        # host dispatches + host gap of the production windows (the
        # `analyze diff` lower-is-better gates), and the K-vs-1
        # tokens/sec ratio on the SAME seeded trace (> 1 = fusing K
        # iterations per dispatch beat one-dispatch-per-iteration)
        **({"serve_multi_step": multi_step,
            "serve_dispatches": med(cont, "serve_dispatches"),
            "serve_host_gap_s": med(cont, "serve_host_gap_s"),
            "k1_serve_tokens_per_sec": k1_tps,
            "k1_serve_dispatches": k1_disp,
            "multi_step_vs_k1_tokens_per_sec": (
                round(line["serve_tokens_per_sec"] / k1_tps, 3)
                if line["serve_tokens_per_sec"] and k1_tps else None)}
           if multi_step else {}),
        "cached_vs_uncached_ttft_p50": (
            round(line["serve_ttft_p50_s"] / mono_ttft50, 3)
            if line["serve_ttft_p50_s"] and mono_ttft50 else None),
        # the static-batch generate baseline on the SAME arrival trace —
        # the headline claim is the ratio at equal latency budget
        "static_requests_per_sec_per_chip": (
            round(static_rps, 6) if static_rps else None),
        "static_ttft_p95_s": med(stat, "serve_ttft_p95_s"),
        "static_itl_p95_s": med(stat, "serve_itl_p95_s"),
        "static_decode_iterations": med(stat, "decode_iterations"),
        "continuous_vs_static": (round(rps / static_rps, 3)
                                 if rps and static_rps else None),
        "stream": stream,
        **({"tokens_delivered": med(cont, "tokens_delivered")}
           if stream else {}),
        "config": {"slots": slots, "requests": n_requests,
                   "arrival_rate_per_s": rate, "prompt_len": prompt_len,
                   "max_new_tokens": max_new, "vocab": vocab,
                   "hidden": hidden, "layers": layers, "heads": heads,
                   "ffn": ffn, "max_len": max_len, "dtype": "bfloat16",
                   "greedy": True, "prefill_chunk": chunk,
                   "prefix_cache_blocks": cache_blocks,
                   "prefix_block": prefix_block,
                   "shared_prefix": shared_len,
                   "long_every": long_every, "long_len": long_len,
                   "slo_ttft_s": slo_ttft, "slo_itl_s": slo_itl,
                   "queue_cap": queue_cap,
                   "kv_dtype": kv.kv_dtype,
                   "kv_layout": kv_layout,
                   "draft": draft, "draft_k": draft_k if draft else None,
                   "multi_step": multi_step},
        "device": device_kind,
        "n_devices": n,
        "synthetic": True,
        "jax_version": jax.__version__,
        "xla_flags": os.environ.get("XLA_FLAGS"),
        "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS"),
    }))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--stream", action="store_true",
                   help="input-pipeline bench (fresh host batches per step)")
    p.add_argument("--attention", action="store_true",
                   help="flash vs dense attention on-chip microbench")
    p.add_argument("--lm", action="store_true",
                   help="GPT decoder LM training throughput + MFU (bf16)")
    p.add_argument("--moe", action="store_true",
                   help="MoE-FFN vs dense-FFN GPT throughput (router + "
                        "dispatch overhead at matched active FLOPs)")
    p.add_argument("--decode", action="store_true",
                   help="KV-cache decode throughput (tokens/sec + achieved "
                        "weight-streaming bandwidth) of the --lm config")
    p.add_argument("--serve", action="store_true",
                   help="continuous-batching serving bench: open-loop "
                        "Poisson arrivals through the slot-based KV cache "
                        "(serving/) vs the static-batch generate baseline "
                        "on the same trace; reports requests/sec/chip + "
                        "TTFT/ITL p50/p95 (combine with --stream for the "
                        "per-token streaming delivery mode; "
                        "BENCH_SERVE_* env vars shrink smoke runs)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="--serve: write the scheduler's request/prefill/"
                        "decode span timeline to this JSONL (readable by "
                        "observability.analyze spans/export/serve)")
    p.add_argument("--sweep", action="store_true",
                   help="--serve: SLO load harness — sweep the Poisson "
                        "arrival rate up a geometric ladder on the same "
                        "seeded trace (early-stop once goodput falls), "
                        "report serve_max_goodput_under_slo + the knee "
                        "rate, and prove graceful overload with a "
                        "queue-capped saturation window at 2× the knee "
                        "(nonzero serve_shed_rate, bounded queue-wait "
                        "p99); BENCH_SERVE_SWEEP_* env vars shape the "
                        "ladder")
    p.add_argument("--serve-slo-ttft", type=float, default=None,
                   metavar="S",
                   help="--serve: TTFT SLO target in seconds (default "
                        "BENCH_SERVE_SLO_TTFT or 1.0) — goodput counts "
                        "requests meeting this AND the ITL target")
    p.add_argument("--serve-slo-itl", type=float, default=None,
                   metavar="S",
                   help="--serve: ITL SLO target in seconds, judged at "
                        "each request's own p99 gap (default "
                        "BENCH_SERVE_SLO_ITL or 0.25)")
    p.add_argument("--serve-queue-cap", type=int, default=0, metavar="N",
                   help="--serve: bounded admission — cap the arrived "
                        "backlog at N, shed the excess with 429 "
                        "accounting (the --sweep overload window uses "
                        "this cap, defaulting to the slot count)")
    p.add_argument("--serve-kv-dtype", default=None,
                   choices=["bfloat16", "bf16", "int8"], metavar="DTYPE",
                   help="--serve: KV slot-table storage dtype for the "
                        "production windows (default BENCH_SERVE_KV_DTYPE "
                        "or the model's bf16).  With int8 the line also "
                        "runs a model-dtype (bf16) comparison window on "
                        "the SAME seeded trace (BASELINE same-trace "
                        "rule) and emits serve_kv_dtype / "
                        "serve_kv_bytes_per_slot + the bytes ratio and "
                        "greedy-token agreement vs that baseline")
    p.add_argument("--serve-kv-layout", default=None,
                   choices=["monolithic", "paged"], metavar="LAYOUT",
                   help="--serve: KV layout for the production windows "
                        "(default BENCH_SERVE_KV_LAYOUT or monolithic). "
                        "'paged' runs the refcounted block pool + fused "
                        "Pallas paged decode attention; the monolithic "
                        "window on the SAME seeded trace then also "
                        "yields paged_vs_monolithic_itl_p95, and the "
                        "line carries serve_kv_blocks_in_use / "
                        "serve_kv_block_utilization / "
                        "serve_prefix_zero_copy_hit_rate + the paged "
                        "pool section")
    p.add_argument("--serve-draft", default=None, metavar="SPEC",
                   help="--serve: speculative decoding for the "
                        "production windows — 'self' (draft = the bench "
                        "model + params) or 'hidden=..,layers=..' GPT "
                        "size overrides (default BENCH_SERVE_DRAFT).  "
                        "The monolithic/static baselines stay "
                        "non-speculative on the same trace; the line "
                        "gains serve_accept_rate + the speculative "
                        "ledger")
    p.add_argument("--serve-draft-k", type=int, default=None, metavar="K",
                   help="--serve-draft: draft tokens proposed per verify "
                        "round (default BENCH_SERVE_DRAFT_K or 4)")
    p.add_argument("--replicas", type=int, default=0, metavar="N",
                   help="--serve: fleet mode (serving/fleet.py) — a "
                        "clean N-replica ReplicaSet window plus a "
                        "kill-one-replica chaos window (seeded crash at "
                        "decode iteration BENCH_SERVE_KILL_ITER, default "
                        "8) on the same trace; the line reports fleet "
                        "requests/sec/chip, serve_failover_recovery_"
                        "p95_s, serve_duplicate_emissions and the "
                        "exactly-once conservation check (default "
                        "BENCH_SERVE_REPLICAS or off)")
    p.add_argument("--disagg", default=None, metavar="P:D",
                   help="--serve: disaggregated-fleet scenario line "
                        "(round 18) — a P-prefill/D-decode fleet with "
                        "serialized KV handoff vs the homogeneous "
                        "(P+D)-replica fleet on the SAME seeded trace "
                        "(disagg_vs_homogeneous_itl_p95/p99 + greedy "
                        "parity), affinity vs least-loaded routing "
                        "(serve_fleet_prefix_hit_rate), and a diurnal "
                        "burst trace comparing the 1:(P+D) autoscaled "
                        "fleet against its static sizes "
                        "(serve_replica_seconds + goodput fraction of "
                        "the best static); default BENCH_SERVE_DISAGG")
    p.add_argument("--serve-multi-step", type=int, default=None,
                   metavar="K",
                   help="--serve: fuse K decode iterations per host "
                        "dispatch in the production windows (round 20 "
                        "multi-step dispatch; default "
                        "BENCH_SERVE_MULTI_STEP or off) — a K=1 twin "
                        "window on the SAME seeded trace supplies the "
                        "K-vs-1 serve_tokens_per_sec ratio, and the "
                        "line gains serve_host_gap_s / "
                        "serve_dispatches (greedy streams are bitwise "
                        "identical across K)")
    p.add_argument("--steps", type=int, default=100,
                   help="--stream: measured steps per repetition (the test "
                        "suite's smoke invocation shrinks this, plus "
                        "BENCH_PER_CHIP_BATCH, so the harness is exercised "
                        "off-TPU without TPU-scale compute)")
    p.add_argument("--grad-compression", default="none",
                   choices=["none", "bf16", "int8"],
                   help="gradient-collective codec for the default/--stream "
                        "training benches (parallel/compression.py); the "
                        "JSON line reports grad_bytes_per_step wire vs raw "
                        "either way")
    p.add_argument("--precision", default="f32",
                   choices=["f32", "bf16", "bf16-f32master",
                            "fp16-f32master"],
                   help="mixed-precision policy for the default/--stream "
                        "training benches (parallel/precision.py): the "
                        "model computes at the policy dtype, params/"
                        "optimizer store per policy, and the JSON line "
                        "reports precision + param/opt_state bytes per "
                        "device either way")
    p.add_argument("--grad-bucket-mb", type=float, default=0.0,
                   metavar="MB",
                   help="communication/compute overlap for the default/"
                        "--stream training benches: bucket the gradient "
                        "collectives (~MB per bucket, parallel/overlap.py) "
                        "and enable the TPU latency-hiding XLA flags; the "
                        "default line reports the measured exposed-vs-"
                        "hidden collective split either way "
                        "(grad_collective_exposed_s)")
    p.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                   help="default/--stream: run the Trainer-path window "
                        "with an N-step async checkpoint cadence into a "
                        "throwaway dir and report the blocked-vs-"
                        "overlapped seconds split (checkpoint_wait_s / "
                        "checkpoint_overlapped_s — the durability cost "
                        "actually charged against throughput)")
    p.add_argument("--health", default="off", choices=["off", "on"],
                   help="numeric-health layer for the default/--stream "
                        "training benches (observability/health.py): the "
                        "JSON line gains health_max_update_ratio + "
                        "health_anomaly_steps from the Trainer-path "
                        "window's fit result")
    args = p.parse_args()
    from distributed_tensorflow_tpu.utils.harness import (
        enable_overlap_flags, resolve_compile_cache)

    resolve_compile_cache()
    if args.grad_bucket_mb:
        # before backend init: the latency-hiding/async-collective flags
        # apply at compile time (LIBTPU_INIT_ARGS — inert off-TPU); the
        # emitted line records the effective value for attribution
        enable_overlap_flags()
    # --serve wins over --stream: "--serve --stream" is the serving
    # bench's per-token streaming mode, not the input-pipeline bench
    mode = ("serve" if args.serve else "stream" if args.stream
            else "attention" if args.attention
            else "lm" if args.lm else "moe" if args.moe
            else "decode" if args.decode else "default")
    if mode == "serve":
        bench_serve(stream=args.stream, trace_path=args.trace,
                    sweep=args.sweep, slo_ttft=args.serve_slo_ttft,
                    slo_itl=args.serve_slo_itl,
                    queue_cap=args.serve_queue_cap,
                    kv_dtype=args.serve_kv_dtype,
                    draft=args.serve_draft,
                    draft_k=args.serve_draft_k,
                    replicas=args.replicas,
                    kv_layout=args.serve_kv_layout,
                    disagg=args.disagg,
                    multi_step=args.serve_multi_step)
    elif mode == "stream":
        bench_stream(steps=max(args.steps, 1),
                     grad_compression=args.grad_compression,
                     health=args.health,
                     checkpoint_every=args.checkpoint_every,
                     grad_bucket_mb=args.grad_bucket_mb,
                     precision=args.precision)
    elif mode == "attention":
        bench_attention()
    elif mode == "lm":
        bench_lm()
    elif mode == "moe":
        bench_moe()
    elif mode == "decode":
        bench_decode()
    else:
        bench_throughput(grad_compression=args.grad_compression,
                         health=args.health,
                         checkpoint_every=args.checkpoint_every,
                         grad_bucket_mb=args.grad_bucket_mb,
                         precision=args.precision)


if __name__ == "__main__":
    main()
