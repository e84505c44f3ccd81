"""End-of-run report: one structured summary of how the run behaved.

This is the layer every later scaling PR reads its numbers from — a single
dict (emitted as the harness's ``run_report`` event and carried in the
summary) that answers the operational questions a throughput number alone
cannot:

* steady-state step time p50/p95 SPLIT from compile (the first chunk
  smears its XLA compile over its k entries; percentiles over the rest);
* which chunk shapes the drain actually dispatched (``chunk_sizes`` —
  auto-resolution, tail chunks and ``max_steps`` truncation all show up
  here);
* watchdog heartbeat/stall counts, prefetch starvation totals, metric
  sink drops — the "did telemetry or input starve the device" trio;
* the measured cost of the telemetry itself (``telemetry_overhead_s`` /
  ``_frac``): the "metrics+tracing within 5% of telemetry-off" budget is
  reported by the run, not assumed.
"""

from __future__ import annotations

import os
from typing import Any

from distributed_tensorflow_tpu.observability.sink import SCHEMA_VERSION


def runtime_environment(devices=None) -> dict[str, Any]:
    """The execution-environment facts that make perf numbers attributable
    across machines: jax version, the platform, device kind and count of
    ``devices`` — the devices of the mesh the run built, so the section
    says where the work ran, not what a backend peek found — and the
    effective XLA flag carriers (``XLA_FLAGS`` / ``LIBTPU_INIT_ARGS`` — the
    overlap flags ``utils/harness.enable_overlap_flags`` sets ride the
    latter).  Without ``devices`` the device fields are None: this function
    never initializes a backend itself, which would lock in whatever flags
    are set NOW, before a caller's ``enable_overlap_flags`` could act."""
    import jax

    devices = list(devices) if devices is not None else []
    first = devices[0] if devices else None
    return {
        "jax_version": jax.__version__,
        "platform": first.platform if first else None,
        "device_kind": first.device_kind if first else None,
        "device_count": len(devices) or None,
        "xla_flags": os.environ.get("XLA_FLAGS"),
        "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS"),
    }


def device_memory(devices) -> list[dict[str, Any]]:
    """What each device the run used holds, as its backend reports it
    (``memory_stats()``): ``bytes_in_use`` now and ``peak_bytes_in_use``
    since the process started — one row per device, so state that sits on
    device 0 alone shows as rows of zeros beside it.  Both are None where
    the backend keeps no such count (XLA:CPU)."""
    rows = []
    for d in devices:
        stats = d.memory_stats() or {}
        rows.append({"id": d.id,
                     "bytes_in_use": stats.get("bytes_in_use"),
                     "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return rows


def serve_section(summary: dict[str, Any] | None,
                  n_devices: int = 1, tracer=None) -> dict[str, Any] | None:
    """Normalize a ContinuousBatcher summary into the run report's
    ``serve`` section: the per-request result objects are reduced to
    their token streams (``generated_tokens``, one list per request in rid
    order — the section must stay JSON, and a greedy window is compared
    with another by what it generated), and the per-chip rates — requests/sec (the
    round-7 headline) and goodput-under-SLO (the round-13 one, mirroring
    examples_per_sec_per_device) — are derived here so every surface
    divides by the same device count.  ``tracer`` (when enabled) adds the
    serve window's telemetry self-accounting — sink drop counter + span
    bookkeeping overhead, previously train-report-only — gated
    lower-is-better by `analyze diff`."""
    if summary is None:
        return None
    sec = {k: v for k, v in summary.items() if k != "results"}
    sec["generated_tokens"] = [[int(t) for t in r.tokens]
                               for r in summary.get("results", [])]
    for key in ("serve_requests_per_sec", "serve_goodput_under_slo"):
        v = sec.get(key)
        sec[f"{key}_per_chip"] = (
            v / n_devices if isinstance(v, (int, float)) and n_devices
            else None)
    if tracer is not None and getattr(tracer, "enabled", False):
        tstats = tracer.stats()
        sec["serve_sink_dropped"] = tstats.get("dropped")
        sec["serve_sink_written"] = tstats.get("written")
        sec["serve_trace_overhead_s"] = tstats.get("overhead_s", 0.0)
    return sec


def build_run_report(fit_result: dict[str, Any], *,
                     watchdog=None, metrics_logger=None, tracer=None,
                     serve: dict[str, Any] | None = None,
                     timeline=None, ledger=None, roofline=None,
                     devices=None) -> dict[str, Any]:
    """Assemble the run report from the Trainer's fit result and the live
    telemetry objects.  Every argument except ``fit_result`` is optional —
    absent subsystems report as None, so readers can distinguish
    "disabled" from "zero".  ``serve`` is a post-training serving window's
    section (``serve_section``) — serving gets the same trajectory and
    regression gating training has (`analyze diff` flattens the nested
    serve_* keys).  ``devices`` are the run's mesh devices: named in the
    ``environment`` section, and read for ``device_memory`` while the
    training state is still alive."""
    st = fit_result.get("step_time") or {}
    elapsed = float(fit_result.get("elapsed") or 0.0)

    report: dict[str, Any] = {
        "schema_version": SCHEMA_VERSION,
        "steps": fit_result.get("steps"),
        # the measured value, even when it is 0.0 (an instantly-ending run
        # is a real observation); None only when fit never reported one —
        # `elapsed or None` used to collapse the two
        "elapsed_s": (float(fit_result["elapsed"])
                      if fit_result.get("elapsed") is not None else None),
        # resolved drain shape + the chunk lengths actually dispatched,
        # and WHY auto mode downshifted when it did (None: no clamp)
        "steps_per_call": fit_result.get("steps_per_call"),
        "steps_per_call_clamp": fit_result.get("steps_per_call_clamp"),
        "chunk_sizes": fit_result.get("chunk_sizes"),
        "prefetch_depth": fit_result.get("prefetch_depth"),
        # gradient-collective payload: wire bytes under --grad-compression
        # vs the raw (uncompressed) figure (None: stateless engine)
        "grad_allreduce_bytes": fit_result.get("grad_allreduce_bytes"),
        "grad_allreduce_bytes_raw": fit_result.get(
            "grad_allreduce_bytes_raw"),
        "grad_compression": fit_result.get("grad_compression"),
        # mixed-precision policy (--precision; parallel/precision.py) +
        # the per-device state footprint it moves: param bytes halve
        # under bf16 storage, optimizer bytes grow by a master policy's
        # f32 copy — both gated lower-is-better by `analyze diff`.
        # loss_scale is the fp16 skip-accounting section (None: policy
        # without dynamic scaling).
        "precision": fit_result.get("precision"),
        "param_bytes_per_device": fit_result.get("param_bytes_per_device"),
        "opt_state_bytes_per_device": fit_result.get(
            "opt_state_bytes_per_device"),
        "loss_scale": fit_result.get("loss_scale"),
        # communication/compute overlap (--grad-bucket-mb;
        # parallel/overlap.py): the bucket size in effect, and the
        # exposed-vs-hidden collective split the one-time probe measured
        # (exposed_s is the gated number — BASELINE.md; None = overlap
        # off or probe unsupported, distinguishable from a measured 0.0)
        "grad_bucket_mb": fit_result.get("grad_bucket_mb"),
        "grad_collective_exposed_s": (
            fit_result.get("collective_overlap") or {}).get("exposed_s"),
        "grad_collective_hidden_s": (
            fit_result.get("collective_overlap") or {}).get("hidden_s"),
        "collective_overlap": fit_result.get("collective_overlap"),
        # steady-state percentiles (compile excluded — see StepTimer)
        "compile_s": st.get("compile_s", st.get("first_step_s")),
        "step_time_p50_s": st.get("steady_p50_s"),
        "step_time_p95_s": st.get("steady_p95_s"),
        "step_time_mean_s": st.get("steady_mean_s"),
        # checkpoint cost split (BASELINE.md accounting rule): wait_s is
        # training-thread blocked time — the only part charged against
        # throughput — overlapped_s ran on the background writer behind
        # training.  None when the run had no checkpoint manager.
        "checkpoint_wait_s": fit_result.get("checkpoint_wait_s"),
        "checkpoint_overlapped_s": fit_result.get("checkpoint_overlapped_s"),
        "checkpoint_async": fit_result.get("checkpoint_async"),
        # elastic preemption tolerance (distributed_tensorflow_tpu/
        # elastic/): the graceful-drain outcome (the lease's should_stop
        # reason, None on a normal finish), the resume-side accounting of
        # an --elastic-restore run — preemption_lost_s (save → resume
        # wall-clock gap, the MLPerf time-to-quality cost of the
        # preemption) and resume_replay_steps (steps whose data position
        # could not be restored; 0 = exact resume), both gated
        # lower-is-better by `analyze diff` — plus the step the restore
        # came from, the lease arming record and the straggler summary.
        # None throughout when the run was not elastic — "not an elastic
        # run" stays distinguishable from a measured 0.
        "preempted": fit_result.get("preempted"),
        "preemption_lost_s": fit_result.get("preemption_lost_s"),
        "resume_replay_steps": fit_result.get("resume_replay_steps"),
        "restored_step": fit_result.get("restored_step"),
        "lease": fit_result.get("lease"),
        "stragglers": fit_result.get("stragglers"),
    }

    report["watchdog"] = None if watchdog is None else {
        "beats": watchdog.beats,
        "stall_episodes": watchdog.stall_episodes,
        "timeout_s": watchdog.timeout,
    }

    starvation = fit_result.get("prefetch_starvation")
    report["prefetch"] = None if starvation is None else {
        "depth": fit_result.get("prefetch_depth"),
        "starvation": starvation,
        "fill_wait_s": fit_result.get("prefetch_fill_wait_s"),
    }

    report["metrics_sink"] = None if metrics_logger is None else \
        metrics_logger.stats()

    # numeric-health summary (Trainer fit with the engine's health layer
    # on): anomaly record + run maxima of the per-step stats.  None when
    # health was off — "disabled" stays distinguishable from "healthy".
    report["health"] = fit_result.get("health")

    # serving window (--serve): requests/sec/chip + TTFT/ITL percentiles
    # of the post-training continuous-batching run.  None when serving was
    # off — the section, not its absence, is what `analyze diff` gates.
    report["serve"] = serve

    overhead = 0.0
    if tracer is not None and tracer.enabled:
        report["spans"] = tracer.span_summary()
        tstats = tracer.stats()
        # an ENABLED tracer always reports a dict — written/dropped are
        # ints for a file-backed sink (0 = enabled but idle), None for an
        # aggregate-only tracer (no file).  The old `... or None` collapsed
        # enabled-but-idle into the same None as disabled.
        report["trace"] = {"written": tstats.get("written"),
                           "dropped": tstats.get("dropped")}
        overhead += tracer.overhead_s
    else:
        report["spans"] = None
        report["trace"] = None
    if metrics_logger is not None:
        overhead += getattr(metrics_logger, "overhead_s", 0.0)

    # --timeline sections (None when sampling/ledger are off — "disabled"
    # stays distinguishable from "measured zero"):
    # * `timeline`: per-series digests + the sampler's own measured cost
    #   (the < 1% budget is reported, not assumed);
    # * `xla`: the per-compiled-program memory/compile manifest, with the
    #   two headline keys — peak_hbm_bytes_est (per-program XLA peak
    #   estimates SUMMED per run) and compile_total_s (the `compile`
    #   span total + ledger-observed compiles) — hoisted to the top
    #   level for `analyze diff`'s lower-is-better gates.
    compile_span_s = 0.0
    if tracer is not None and tracer.enabled:
        compile_span_s = (tracer.span_summary().get("compile") or
                          {}).get("total_s", 0.0)
    if timeline is not None:
        report["timeline"] = {
            "interval_s": timeline.interval_s,
            "overhead_s": round(timeline.overhead_s, 6),
            "overhead_frac": (round(timeline.overhead_s / elapsed, 6)
                              if elapsed > 0 else None),
            "series": timeline.summary(),
        }
        overhead += timeline.overhead_s
    else:
        report["timeline"] = None
    if ledger is not None:
        manifest = ledger.manifest()
        report["xla"] = manifest
        report["peak_hbm_bytes_est"] = manifest["peak_hbm_bytes_est"]
        report["compile_total_s"] = round(
            compile_span_s + manifest["compile_total_s"], 6)
    else:
        report["xla"] = None
        report["peak_hbm_bytes_est"] = None
        report["compile_total_s"] = (round(compile_span_s, 6)
                                     if compile_span_s else None)

    # --roofline section: ONLY present when a Roofline was attached —
    # with the flag off the report key set stays byte-identical to
    # round 18 (parity pin; note the contrast with the always-present
    # None sections above, which predate the parity discipline).
    # The train half echoes the Trainer's flag-gated result keys, the
    # serve half points at the serve section's own roofline block, and
    # `programs` is the per-compiled-program attribution table —
    # intensity, compute/bandwidth bound, attainable fraction of peak —
    # from the ledger manifest's cost_analysis columns.
    if roofline is not None:
        from distributed_tensorflow_tpu.observability.roofline import (
            flops_crosscheck, program_attribution)

        rf_train = {
            "model_flops_per_step": fit_result.get(
                "train_model_flops_per_step"),
            "achieved_flops_per_sec": fit_result.get(
                "train_achieved_flops_per_sec"),
            "mfu": fit_result.get("train_mfu"),
        }
        programs = None
        if ledger is not None:
            manifest = report["xla"] or {}
            programs = program_attribution(
                manifest.get("programs", {}),
                peaks=roofline.peaks, dtype=roofline.dtype)
            # analytic-vs-XLA cross-check on the train step: the ratio of
            # XLA's counted flops to the analytic model flops (None when
            # either side is missing; ~3x is remat's signature)
            xla_train = next(
                (rec.get("flops")
                 for name, rec in manifest.get("programs", {}).items()
                 if "train" in name and rec.get("flops")), None)
            rf_train["xla_flops_crosscheck"] = flops_crosscheck(
                rf_train["model_flops_per_step"], xla_train)
        report["roofline"] = {
            "device": roofline.describe(),
            "train": rf_train,
            "serve": (serve or {}).get("roofline"),
            "programs": programs,
        }
        # hoisted for `analyze diff`'s higher-is-better gate (the serve
        # keys flatten from the serve section's serve_* prefix already)
        report["train_mfu"] = fit_result.get("train_mfu")

    # execution environment (jax version, platform, device kind and
    # count, effective XLA flags): trajectories stay attributable across
    # machines
    report["environment"] = runtime_environment(devices)
    report["device_memory"] = (device_memory(devices)
                               if devices is not None else None)

    # the telemetry's own measured cost, against the run's wall clock —
    # this is the number the 5%-overhead acceptance bound reads
    report["telemetry_overhead_s"] = round(overhead, 6)
    report["telemetry_overhead_frac"] = (
        round(overhead / elapsed, 6) if elapsed > 0 else None)
    return report
