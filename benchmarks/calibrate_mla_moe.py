#!/usr/bin/env python3
"""Readings for the limits of a cell of ``drivers/serve_mla_moe.py``: what
``calibrate.py readings`` is for the GPT-2 serve cell (its loop swaps
GPT-2's tree into one server; 7.6 GB of weights cannot be held twice, so
here each seed builds its own).

    python3 benchmarks/calibrate_mla_moe.py --workload <cell> --seeds 1,2,3 \
        [--controls 3] [--fault-seeds 1] \
        [--faults no_shared,no_routed_scale,k_rope_unrotated]

For each seed: the cell's own trace served at the cell's own load, a
sample drawn as ``check`` draws it, and ``token_logit_gap`` of the program
(the lower reading); for the first ``--controls`` seeds also that of the
float8 control, and for the first ``--fault-seeds`` of each planted fault,
on the same sample (the upper readings); beside each, what it would read
at other near-tie margins.  Run by hand; every line is JSON on standard output and in
``chiprun_out/``."""

from __future__ import annotations

import argparse
import importlib
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmarks import calibrate, run as runmod       # noqa: E402
from benchmarks.lib import stats                      # noqa: E402


MARGINS = (0.0, 0.0005, 0.001, 0.002, 0.003, 0.005, 0.0075, 0.01, 0.015,
           0.02, 0.03)


def by_margin(judged: dict) -> dict:
    """What the widest gap and the share set apart would read at each
    near-tie margin, and the gap's median and 99th percentile over all
    positions: from which ``check.near_tie_margin`` is chosen."""
    gap, margin = judged["gap"], judged["margin"]
    curve = [[m, float(gap[margin >= m].max(initial=0.0)),
              float((margin < m).mean())] for m in MARGINS]
    return {"gap_p50": stats.percentile(list(gap), 50),
            "gap_p99": stats.percentile(list(gap), 99),
            "margin_gap_share": curve}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--faults",
                   default="no_shared,no_routed_scale,k_rope_unrotated")
    p.add_argument("--fault-seeds", type=int, default=1)
    p.add_argument("--seconds", type=float, default=None)
    args = p.parse_args(argv)

    from benchmarks.lib import traffic
    from distributed_tensorflow_tpu.utils.harness import resolve_compile_cache

    resolve_compile_cache()
    bench, cell, config = runmod.load_cell(args.workload)
    seconds = args.seconds or float(bench["run_seconds"])
    devices, _ = runmod.require_devices(cell["chips"])
    driver = importlib.import_module(
        f"benchmarks.drivers.{cell['driver']}")
    tag = f"readings_{args.workload}"
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = driver.Run(cell, config, seed=seed, seconds=seconds,
                         devices=devices, note=runmod.note)
        run.build()
        run.warm()
        obs = run.serve(traffic.request_trace(seed, run.mix, seconds,
                                              run.vocab, run.max_len))
        sample = run.sample()
        run.free()
        calibrate.emit(tag, {
            "seed": seed, "who": "program", **run.gaps(sample),
            **by_margin(run.judged), "failed": obs["failed"],
            "requests": obs["attempted"],
            "tok_s": obs["tokens"] / obs["window_s"],
            "s": time.perf_counter() - t0})
        if i < args.controls:
            variants = [("control_fp8", {"mode": "fp8"})] + [
                (f"fault_{f}", {"fault": f})
                for f in args.faults.split(",") if f and i < args.fault_seeds]
            for who, kw in variants:
                calibrate.emit(tag, {"seed": seed, "who": who,
                                     **run.gaps(sample, **kw),
                                     **by_margin(run.judged)})
        run.weights = None      # the next seed's 7.6 GB need the room
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
