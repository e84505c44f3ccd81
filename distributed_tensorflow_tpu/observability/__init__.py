"""Observability: on-device metric trajectories, trace spans, run reports.

The reference's only observability is ``print()`` plus one wall-clock
window (SURVEY.md §5; logging is actively disabled in dist_keras.py:67-68).
This package is the opposite pole — telemetry that observes the shipped
fast path instead of disabling it:

  sink     — AsyncJsonlSink: background writer thread over a bounded
             queue (drop counter on overflow), line-buffered JSONL so a
             killed run leaves only whole lines.  The host cost of a
             record is one queue put.
  trace    — structured span/event timeline (monotonic clock, run/host/
             process ids) shared with XProf via
             ``jax.profiler.TraceAnnotation``; every span's record
             (start, end, id, parent) is kept in a bounded ring in memory
             and aggregated for the run report even when no file sink is
             configured.  ``recorder()`` is the process-wide tracer the
             serve loop and ``Trainer.fit`` use when none is passed.
  report   — the end-of-run structured summary: steady-state step-time
             percentiles split from compile, chunk shapes actually used,
             watchdog heartbeat/stall counts, prefetch starvation totals,
             sink drops, the health section, and the measured telemetry
             overhead itself.
  health   — per-step numeric-health stats computed ON DEVICE inside the
             jitted many-step scan (grad/param/update norms, update
             ratio, non-finite leaf count, loss-spike vs a running EMA)
             via optimizer-level capture transforms, plus the host-side
             anomaly policy behind ``--on-anomaly warn|halt``.
  metrics  — LogHistogram / MetricsRegistry: streaming log-bucketed
             histograms (fixed geometric buckets, O(1) record, mergeable
             across windows/replicas) — serving latency p50/p95/p99
             computed online without storing every sample.
  slo      — SLOMonitor: goodput-under-SLO accounting (requests/sec
             meeting BOTH the TTFT and ITL targets; shed requests are
             offered load, never goodput).
  timeline — GaugeSeries / Timeline: bounded-ring time-series gauges
             (O(1) record, exact merge, per-series interval throttle,
             self-measured overhead) sampled at existing iteration
             boundaries — the autoscaler's sensor substrate, rendered by
             ``analyze timeline`` and the Perfetto counter tracks.
  xla_stats— ProgramLedger: per-compiled-program XLA memory_analysis +
             compile wall-time (``ledger.jit`` observes a call site's
             compiles; flag off = literal ``jax.jit``), with a manifest
             the ``analyze programs`` drift gate diffs; round 19 adds
             cost_analysis flops/bytes columns for roofline attribution.
  roofline — GPTCostModel / DevicePeaks / Roofline: analytic model
             FLOPs and must-read bytes from config alone, the device
             peak table (unknown kind → None, never an invented peak),
             and the MFU/MBU wiring object ``--roofline`` threads
             through trainer, batcher, fleet and run report.  Stdlib-
             only — ``analyze roofline`` renders offline.
  analyze  — the offline read side: span aggregation, stall summaries,
             Chrome-trace-event export (Perfetto-loadable), health
             timelines, and the run-vs-run regression diff.  Stdlib-only,
             usable as ``python -m
             distributed_tensorflow_tpu.observability.analyze``.

Why this lives OUTSIDE the step loop's downshift logic: per-step metric
records ride the ``lax.scan`` carry of ``Engine.build_many_step`` and are
materialized once per chunk (one host sync per k steps), so enabling
``--metrics-path`` or the watchdog no longer forces ``Trainer.fit`` down
to ``steps_per_call=1`` (see Trainer.resolve_steps_per_call).
"""

from distributed_tensorflow_tpu.observability.metrics import (
    LogHistogram, MetricsRegistry, exact_percentile)
from distributed_tensorflow_tpu.observability.report import (
    build_run_report, device_memory, runtime_environment, serve_section)
from distributed_tensorflow_tpu.observability.sink import (
    SCHEMA_VERSION, AsyncJsonlSink)
from distributed_tensorflow_tpu.observability.roofline import (
    PEAK_TABLE_REVISION, DevicePeaks, GPTCostModel, Roofline, device_peaks,
    program_attribution)
from distributed_tensorflow_tpu.observability.slo import SLOMonitor
from distributed_tensorflow_tpu.observability.timeline import (
    GaugeSeries, Timeline, sparkline)
from distributed_tensorflow_tpu.observability.trace import (
    NULL_TRACER, Tracer, recorder)
from distributed_tensorflow_tpu.observability.xla_stats import (
    ProgramLedger, diff_manifests)

__all__ = [
    "AsyncJsonlSink",
    "DevicePeaks",
    "GPTCostModel",
    "GaugeSeries",
    "HealthConfig",
    "LogHistogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "PEAK_TABLE_REVISION",
    "ProgramLedger",
    "Roofline",
    "SCHEMA_VERSION",
    "SLOMonitor",
    "Timeline",
    "Tracer",
    "build_run_report",
    "device_memory",
    "device_peaks",
    "diff_manifests",
    "program_attribution",
    "recorder",
    "runtime_environment",
    "serve_section",
    "sparkline",
    "exact_percentile",
]


def __getattr__(name: str):
    # lazy: health pulls in jax/optax, which the stdlib-only analyze CLI
    # (and anything else reading JSONL offline) must not pay for
    if name == "HealthConfig":
        from distributed_tensorflow_tpu.observability.health import (
            HealthConfig)

        return HealthConfig
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
