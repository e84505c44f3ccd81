#!/usr/bin/env python3
"""What a benchmark PR runs on the chip once, by hand, to set numbers.

    python3 benchmarks/calibrate.py readings --workload <cell> --seeds 1,2,3 [--controls 3]
    python3 benchmarks/calibrate.py sweep    --workload <cell> --seed 1 --rates 1,2,3 --seconds 30
    python3 benchmarks/calibrate.py trace    --workload <cell> --seed 1 --seconds 20
    python3 benchmarks/calibrate.py sets     --workload <cell> --seeds 1,2,3,4,5,6 --traced 7,8,9

``readings`` prints, for each seed, the numbers ``correct`` compares (the
program against the reference: the lower readings) and, for the first
``--controls`` seeds, the same numbers of the control and of each planted
fault (the upper readings), all in one process so that programs compile
once.  ``sweep`` serves the same seeded trace at several rates on one
server and prints the tails and the drain, to find the knee.  ``trace``
makes one traced run and lists the device's operations and the host's
spans by name, for reading by hand.  ``sets`` runs the benchmark's own
command as the driver does, a process a run: two sets over the same seeds
and a few traced runs, and prints each metric's quartile spread, from
which the bounds are set (this process never touches JAX, so each child
can hold the chip).  None of this is part of a benchmark
run; every line is JSON on standard output, copied to ``chiprun_out/``."""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmarks import run as runmod            # noqa: E402
from benchmarks.lib import stats, xplane        # noqa: E402

OUT = ROOT / "chiprun_out"


def emit(tag: str, record: dict) -> None:
    line = json.dumps({"calibrate": tag, **record})
    print(line, flush=True)
    OUT.mkdir(exist_ok=True)
    with open(OUT / f"calibrate_{tag}.jsonl", "a") as f:
        f.write(line + "\n")


def start(workload: str, seed: int, seconds: float):
    from distributed_tensorflow_tpu.utils.harness import resolve_compile_cache

    resolve_compile_cache()
    bench, cell, config = runmod.load_cell(workload)
    devices, peaks = runmod.require_devices(cell["chips"])
    driver = importlib.import_module(f"benchmarks.drivers.{cell['driver']}")
    run = driver.Run(cell, config, seed=seed, seconds=seconds,
                     devices=devices, note=runmod.note)
    return bench, cell, config, devices, peaks, run


def worst_leaves(run, got, ref, n):
    """The n leaves with the widest gap of norms, by name, for each of
    the two state numbers: what one looks at when a seed reads far off."""
    import numpy as np

    from benchmarks.lib import weights

    layers = int(run.config["n_layer"])
    names = []      # in the order of drivers/train.flat
    for key in sorted(["wte", "wpe", "lnf_g", "lnf_b", "blocks"]):
        names += [f"{leaf}[{i}]" for leaf in sorted(weights._BLOCK)
                  for i in range(layers)] if key == "blocks" else [key]
    out = {}
    moved = ref["moment"] >= 1e-3 * np.median(ref["moment"])
    for what in ("moment", "change"):
        scale = np.maximum(ref[what], np.median(ref[what]))
        gap = np.abs(got[what] - ref[what]) / scale
        if what == "change":        # as compare() leaves them out
            gap = np.where(moved, gap, 0.0)
        out[what] = [[names[i], float(gap[i]), float(got[what][i]),
                      float(ref[what][i])] for i in np.argsort(-gap)[:n]]
        out[what + "_median_norm"] = float(np.median(ref[what]))
    return out


def readings_train(run, seeds, n_controls, tag, faults, leaves=0):
    from benchmarks.drivers.train import compare
    run.build()
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        run.seed_state(seed)
        got = run.first_chunk()
        run.trainer.state = None
        t1 = time.perf_counter()
        ref = run.reference()
        emit(tag, {"seed": seed, "who": "program", **compare(got, ref),
                   "losses": got["losses"], "ref_losses": ref["losses"],
                   "program_s": t1 - t0, "reference_s":
                   time.perf_counter() - t1})
        if leaves:
            emit(tag, {"seed": seed, "who": "worst_leaves",
                       **worst_leaves(run, got, ref, leaves)})
        if i < n_controls:
            for who, kw in [("control_fp8", {"mode": "fp8"})] + [
                    (f"fault_{f}", {"fault": f}) for f in faults]:
                emit(tag, {"seed": seed, "who": who,
                           **compare(run.reference(**kw), ref)})


def readings_serve(run, seeds, n_controls, tag, seconds):
    import jax

    from benchmarks.drivers import gpt_tree
    from benchmarks.lib import traffic, weights

    run.build()
    run.warm()
    for i, seed in enumerate(seeds):
        # the server keeps the first seed's weights; the traffic, the
        # sample and (through swap_params) the weights follow the seed
        t0 = time.perf_counter()
        if seed != run.seed:
            run.seed = seed
            run.kv.swap_params(jax.jit(gpt_tree.to_flax)(
                weights.make(run.config, seed)))
        trace = traffic.request_trace(seed, run.mix, seconds, run.vocab,
                                      run.max_len)
        obs = run.serve(trace)
        sample = run.sample()
        rec = {"seed": seed, "who": "program", **run.gaps(sample),
               "failed": obs["failed"], "requests": obs["attempted"]}
        emit(tag, {**rec, "s": time.perf_counter() - t0})
        if i < n_controls:
            emit(tag, {"seed": seed, "who": "control_fp8",
                       **run.gaps(sample, mode="fp8")})


def cmd_readings(args):
    seeds = [int(s) for s in args.seeds.split(",")]
    *_, run = start(args.workload, seeds[0], args.seconds)
    tag = f"readings_{args.workload}"
    if hasattr(run, "first_chunk"):
        readings_train(run, seeds, args.controls, tag,
                       [f for f in args.faults.split(",") if f], args.leaves)
    else:
        readings_serve(run, seeds, args.controls, tag, args.seconds)


def cmd_sweep(args):
    from benchmarks.lib import traffic

    *_, run = start(args.workload, args.seed, args.seconds)
    run.build()
    run.warm()
    for rate in (float(r) for r in args.rates.split(",")):
        trace = traffic.request_trace(
            args.seed, {**run.mix, "rate_per_s": rate}, args.seconds,
            run.vocab, run.max_len)
        obs = run.serve(trace)
        waits = obs["queue_wait_s"]
        third = max(1, len(waits) // 3)
        emit(f"sweep_{args.workload}", {
            "rate_per_s": rate, "requests": obs["attempted"],
            "failed": obs["failed"], "window_s": obs["window_s"],
            "drain_s": obs["drain_s"],
            "tok_s": obs["tokens"] / obs["window_s"],
            "ttft_p50_ms": 1e3 * stats.percentile(obs["ttft_s"], 50),
            "ttft_p90_ms": 1e3 * stats.percentile(obs["ttft_s"], 90),
            "itl_p50_ms": 1e3 * stats.percentile(obs["itl_s"], 50),
            "itl_p95_ms": 1e3 * stats.percentile(obs["itl_s"], 95),
            # a backlog that grows shows as waits rising through the run
            "queue_wait_first_third_ms": 1e3 * sum(waits[:third]) / third,
            "queue_wait_last_third_ms": 1e3 * sum(waits[-third:]) / third})


def cmd_trace(args):
    bench, cell, config, devices, peaks, run = start(
        args.workload, args.seed, args.seconds)
    trace_dir = ROOT / ".bench_trace"
    run.setup()
    after, length = run.trace_slice()
    with runmod.SliceProfiler(trace_dir, after, length):
        obs = run.window()
    path = xplane.find(str(trace_dir))
    trace = xplane.load(path, keep_planes=r".", device_lines=r".")
    tag = f"trace_{args.workload}"
    emit(tag, {"file_bytes": Path(path).stat().st_size, "planes": {
        p: {line: len(evs) for line, evs in lines.items()}
        for p, lines in trace.planes.items()}})
    red = xplane.reduce(trace)
    emit(tag, {"window_s": red.window_s, "busy_s": red.busy_s,
               "idle_share": red.idle_share, "obs_window_s": obs["window_s"]})
    first = next(iter(red.ops.values()))
    emit(tag, {"device_ops_top60": xplane.top_ops(first, 60)})
    full: dict[str, list] = {}
    for e, self_ns in xplane.self_times(first):
        agg = full.setdefault(e.name[:260], [0, 0.0])
        agg[0] += 1
        agg[1] += self_ns / 1e9
    emit(tag, {"device_ops_by_instruction_top40": sorted(
        full.items(), key=lambda kv: -kv[1][1])[:40]})
    emit(tag, {"idle_gaps": xplane.longest_gaps(first, red.host, red.t0,
                                                red.t1, 20)})
    names: dict[str, list] = {}
    for e in red.host:
        agg = names.setdefault(e.name, [0, 0.0])
        agg[0] += 1
        agg[1] += e.dur_ns / 1e9
    emit(tag, {"host_events_top40": sorted(
        names.items(), key=lambda kv: -kv[1][1])[:40]})
    if args.keep:
        import shutil

        OUT.mkdir(exist_ok=True)
        shutil.copy(path, OUT / f"{args.workload}.xplane.pb")


def cmd_sets(args):
    import statistics
    import subprocess

    bench = runmod.load_json(ROOT / "BENCHMARK.json")
    seconds = str(bench["run_seconds"])
    tag = f"sets_{args.workload}"
    plan = [(s, int(seed), 0) for s in (1, 2)
            for seed in args.seeds.split(",")]
    plan += [(0, int(seed), 1) for seed in args.traced.split(",") if seed]
    values: dict[tuple[str, int], list[float]] = {}
    for which, seed, traced in plan:
        t0 = time.perf_counter()
        out = subprocess.run(
            bench["command"] + ["--workload", args.workload, "--seed",
                                str(seed), "--seconds", seconds, "--trace",
                                str(traced)],
            cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t0
        last = (out.stdout.strip().splitlines() or ["null"])[-1]
        result = json.loads(last) if out.returncode == 0 else None
        emit(tag, {"set": which, "seed": seed, "trace": traced, "rc":
                   out.returncode, "wall_s": wall, "result": result,
                   "stderr_tail": out.stderr[-1500:]})
        if result and not traced:
            for name, m in result["metrics"].items():
                values.setdefault((name, which), []).append(m["value"])
    for (name, which), vals in sorted(values.items()):
        if len(vals) >= 2:
            emit(tag, {"metric": name, "set": which,
                       "median": statistics.median(vals),
                       "quartile_spread": stats.quartile_spread(vals),
                       "values": vals})


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)
    for name, fn in (("readings", cmd_readings), ("sweep", cmd_sweep),
                     ("trace", cmd_trace), ("sets", cmd_sets)):
        s = sub.add_parser(name)
        s.set_defaults(fn=fn)
        s.add_argument("--workload", required=True)
        s.add_argument("--seconds", type=float, default=20.0)
    sub.choices["readings"].add_argument("--seeds", required=True)
    sub.choices["readings"].add_argument("--controls", type=int, default=3)
    sub.choices["readings"].add_argument(
        "--faults", default="half_batch,first_batch_again,stale_weights")
    sub.choices["readings"].add_argument("--leaves", type=int, default=0)
    sub.choices["sets"].add_argument("--seeds", required=True)
    sub.choices["sets"].add_argument("--traced", default="")
    sub.choices["sweep"].add_argument("--seed", type=int, required=True)
    sub.choices["sweep"].add_argument("--rates", required=True)
    sub.choices["trace"].add_argument("--seed", type=int, required=True)
    sub.choices["trace"].add_argument("--keep", type=int, default=0)
    args = p.parse_args(argv)
    args.fn(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
