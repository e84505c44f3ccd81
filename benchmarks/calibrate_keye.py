#!/usr/bin/env python3
"""Readings for the limits of ``serve-keye2-64k`` (``drivers/serve_keye.py``):
the program, the float8 control, the faults planted in the reference and
the faults planted in the program, each judged by the cell's own
comparison.

    python3 benchmarks/calibrate_keye.py --seeds 1,2,3 [--controls 3] \
        [--faults prefill_dense,...] [--reference-faults no_index_rope,...] \
        [--new 256]

A fault planted in the program needs programs of its own, and a window of
the cell's traffic is a minute of serving: so each reading serves TWO
requests of the seed's trace through the cell's slot table directly
(``SlotKVCache.insert`` and ``advance``, the programs the window runs):
the longest prompt of at most half the table, then, in the same slot, the
shortest prompt, the probe, with the first one's rows left past its length;
at most ``--new`` tokens each.  For each seed the program's readings and,
at each near-tie margin, its widest token gap (``calibrate_mla_moe.by_margin``:
how much of it comes from positions where two experts' scores nearly tie);
for the first ``--controls`` seeds the control on the same sample; for the
first seed each fault planted in the reference, on that sample, and each
fault planted in the program (``serve_keye.planted``), which serves its own
pair.  Run by hand; every line is JSON on standard output and is appended
to the file ``calibrate.emit`` keeps."""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmarks import calibrate, run as runmod       # noqa: E402
from benchmarks.calibrate_mla_moe import by_margin    # noqa: E402


def pair(run, model, new: int) -> list:
    """Serve the seed's pair (module docstring) through a fresh table of
    ``model`` over ``run.weights``, draw the cell's sample from it, free the
    table: the sample."""
    import numpy as np

    from benchmarks.lib import traffic

    run.table(model)
    trace = traffic.request_trace(run.seed, run.mix, run.seconds, run.vocab,
                                  run.max_len)
    first = max((r for r in trace if len(r["prompt"]) <= run.max_len // 2),
                key=lambda r: len(r["prompt"]))
    probe = min(trace, key=lambda r: len(r["prompt"]))
    kv, done = run.kv, []
    for req in (first, probe):
        n = min(int(req["max_new_tokens"]), new)
        _, tok = kv.insert(np.asarray(req["prompt"], np.int32), slot=0)
        toks = [int(tok)]
        while len(toks) < n:
            toks.append(int(kv.advance()[0]))
        done.append(({**req, "max_new_tokens": n}, toks))
        if req is first:
            kv.evict(0)
    run.finished = done
    run.slots_held = [(first["rid"], 0), (probe["rid"], 0)]
    sample = run.sample()
    run.free()
    return sample


def readings(cell: dict, config: dict, seeds: list[int], devices, *,
             controls: int, faults: list[str], reference_faults: list[str],
             new: int, seconds: float, emit, note=print) -> None:
    from benchmarks.drivers import serve_keye
    from benchmarks.lib import keye_weights

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        run = serve_keye.Run(cell, config, seed=seed, seconds=seconds,
                             devices=devices, note=note)
        model = run.model()
        run.weights = keye_weights.make(config, seed)
        sample = pair(run, model, new)
        info = {"requests": [len(r["prompt"]) + len(t) for r, t in sample],
                "probe_stale": run.probe["stale"]}
        emit({"seed": seed, "who": "program", **run.gaps(sample),
              **by_margin(run.judged), **info,
              "s": time.perf_counter() - t0})
        variants = [("control_fp8", {"mode": "fp8"})] if i < controls else []
        if i == 0:
            variants += [(f"reference_{f}", {"fault": f})
                         for f in reference_faults]
        for who, kw in variants:
            emit({"seed": seed, "who": who, **run.gaps(sample, **kw),
                  **by_margin(run.judged)})
        for fault in faults if i == 0 else []:
            t0 = time.perf_counter()
            try:
                with serve_keye.planted(fault):
                    sample = pair(run, model, new)
            except Exception as e:      # the other faults are still read
                run.free()
                emit({"seed": seed, "who": f"program_{fault}",
                      "error": repr(e)[:2000]})
                continue
            emit({"seed": seed, "who": f"program_{fault}", **run.gaps(sample),
                  **by_margin(run.judged), "probe_stale": run.probe["stale"],
                  "s": time.perf_counter() - t0})
        run.weights = None      # the next seed's 6.25 GB need the room


def main(argv=None) -> int:
    from benchmarks.drivers import serve_keye
    from benchmarks.lib import keye_reference

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="serve-keye2-64k")
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--faults", default=",".join(serve_keye.PROGRAM_FAULTS))
    p.add_argument("--reference-faults",
                   default=",".join(keye_reference.FAULTS))
    p.add_argument("--new", type=int, default=256)
    args = p.parse_args(argv)

    from distributed_tensorflow_tpu.utils.harness import resolve_compile_cache

    resolve_compile_cache()
    bench, cell, config = runmod.load_cell(args.workload)
    devices, _ = runmod.require_devices(cell["chips"])
    tag = f"readings_{args.workload}"
    readings(cell, config, [int(s) for s in args.seeds.split(",")], devices,
             controls=args.controls,
             faults=[f for f in args.faults.split(",") if f],
             reference_faults=[f for f in args.reference_faults.split(",")
                               if f],
             new=args.new, seconds=float(bench["run_seconds"]),
             emit=lambda record: calibrate.emit(tag, record),
             note=runmod.note)
    return 0


if __name__ == "__main__":
    sys.exit(main())
