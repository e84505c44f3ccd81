#!/usr/bin/env python3
"""One process, one cell, one run.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, one configuration or one metric is a
data file found by the name ``BENCHMARK.json`` gives it:

    benchmarks/workloads/<cell>.json    driver, configuration, job or traffic, limits
    benchmarks/configs/<config>.json    the model's sizes as run
    benchmarks/metrics/<metric>.json    a reader (``module:function``) and its parameters

This file names none of them.  It places the compile cache, refuses
anything but the cell's TPU devices, lets the cell's driver set up (weights
from the seed, every shape warmed), measures ``--seconds`` of the driver's
window with nothing compiling inside, reads the peak memory, lets the
driver compare what the window produced with the plain reference, and
prints one JSON object as the last line of standard output."""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up counts from here

import argparse
import importlib
import json
import shutil
import sys
import threading
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def note(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_PROCESS:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict]:
    """``(BENCHMARK.json, the cell's file, its configuration's file)``."""
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json(root / "benchmarks" / "workloads" / f"{name}.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cell = {**cell, "name": name, "chips": int(entry["chips"])}
    return bench, cell, load_json(root / conf["file"])


def resolve(dotted: str):
    """``package.module:function`` under ``benchmarks/``."""
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(f"benchmarks.{module}"), attr)


def metric_entries(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this run reports: the end-to-end ones with ``--trace
    0``, the per-layer ones with ``--trace 1``, each only in the cells its
    ``workloads`` lists (all cells where it lists none)."""
    entries = bench["per_layer" if trace else "end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def evaluate(entries: list[dict], obs: dict, ctx: dict,
             root: Path = ROOT) -> dict:
    out = {}
    for m in entries:
        params = load_json(root / "benchmarks" / "metrics" / f"{m['name']}.json")
        got = resolve(params["reader"])(params, obs, ctx)
        if got is not None:
            out[m["name"]] = {"value": float(got), "unit": m["unit"]}
    return out


def require_devices(chips: int):
    """The cell's TPU devices of a kind in the peak table, or exit."""
    import jax

    from benchmarks.lib import peaks

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: the cell needs {chips} TPU device(s); JAX found "
              f"{len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind}); nothing was run", file=sys.stderr)
        raise SystemExit(2)
    devices = devices[:chips]
    return devices, peaks.lookup(devices[0].device_kind)


class CompileCounter:
    """Counts the programs XLA builds or loads: all of them while armed
    (the window), and over the whole run how many came out of the
    persistent cache and how many had to be compiled."""

    def __init__(self):
        import jax.monitoring

        self.count = self.hits = self.misses = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_) -> None:
        if self.armed and event == COMPILE_EVENT:
            self.count += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class SliceProfiler:
    """Profiles ``length_s`` seconds starting ``after_s`` into the window,
    from a timer thread, while the window's own call blocks."""

    def __init__(self, log_dir: Path, after_s: float, length_s: float):
        self.log_dir, self.after_s, self.length_s = log_dir, after_s, length_s
        self.error: BaseException | None = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        import jax

        if self._stop.wait(self.after_s):
            return
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
            self._stop.wait(self.length_s)
            jax.profiler.stop_trace()
        except BaseException as e:     # reported by close(), in the run's thread
            self.error = e

    def __enter__(self):
        shutil.rmtree(self.log_dir, ignore_errors=True)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        if self.error is not None and exc[0] is None:
            raise self.error


def memory_peak(devices) -> int:
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices))


def run_cell(bench: dict, cell: dict, config: dict, *, seed: int,
             seconds: float, trace: bool, devices, peaks: dict,
             root: Path = ROOT, trace_dir: Path | None = None) -> dict:
    """Everything after the look for a chip; the tests drive this on the
    CPU with the timed path broken underneath."""
    from benchmarks.lib import xplane

    driver = importlib.import_module(f"benchmarks.drivers.{cell['driver']}")
    compiles = CompileCounter()
    run = driver.Run(cell, config, seed=seed, seconds=seconds,
                     devices=devices, note=note)
    run.setup()
    setup_s = time.perf_counter() - T_PROCESS
    note(f"set-up done in {setup_s:.1f} s ({compiles.hits} programs from the "
         f"compile cache, {compiles.misses} compiled); window of {seconds} s")

    compiles.armed = True
    if trace:
        trace_dir = trace_dir or root / ".bench_trace"
        after, length = run.trace_slice()
        with SliceProfiler(trace_dir, after, length):
            obs = run.window()
    else:
        obs = run.window()
    compiles.armed = False
    peak = memory_peak(devices)
    obs["setup_s"] = setup_s
    note(f"window closed: {obs.get('window_s', 0):.2f} s, "
         f"{compiles.count} compilation(s) inside")

    ctx = {"config": config, "cell": cell, "chips": len(devices),
           "peaks": peaks, "trace": None}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        reduced = xplane.reduce(xplane.load(xplane.find(str(trace_dir))))
        ctx["trace"] = reduced
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        breakdown = reduced.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
        note(f"trace reduced: busy {reduced.busy_s:.3f} s of "
             f"{reduced.window_s:.3f} s")
    metrics = evaluate(metric_entries(bench, cell["name"], trace), obs, ctx,
                       root)

    checks = run.check(obs)      # frees the program's state, runs the reference
    checks.append({"name": "compiles_in_window", "value": compiles.count,
                   "limit": 0})
    correct = all(c["value"] <= c["limit"] for c in checks)
    result = {"correct": bool(correct), "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    bench, cell, config = load_cell(args.workload)
    # the program under test; alone with BENCHMARK.json this import fails
    from distributed_tensorflow_tpu.utils.harness import resolve_compile_cache

    resolve_compile_cache()
    devices, peaks = require_devices(cell["chips"])
    result = run_cell(bench, cell, config, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, peaks=peaks)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})"
              f"{'' if c['value'] <= c['limit'] else '  <-- FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
