"""The drivers every line of ``PERF_LEDGER.jsonl`` is measured through, on
the CPU: each tiny cell of ``benchmarks/tests/data/cells`` goes through
``benchmarks/run.run_cell`` and its committed driver on one device, under the
name of the real cell it stands for.  A rename of an entry the drivers call
(``Trainer.fit``, ``SlotKVCache.insert``, ``ContinuousBatcher.run``, ...)
fails here, in tier-1, and not first on the chip.  The benchmark's own
suite (``benchmarks/tests``) keeps the controls and the planted faults; this
file copies none of it and calls its helper."""

import json

import pytest

from benchmarks.tests.helpers import ROOT, run_tiny

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("tiny_cell, stands_for", [
    ("tiny-train", "train-gpt2m-1k"),
    ("tiny-serve", "serve-gpt2l-chat"),
    ("tiny-serve-mla-moe", "serve-kanana2-longdoc"),
    ("tiny-serve-hybrid-ssm", "serve-nemotron3s-chat"),
    ("tiny-serve", "serve-gpt2l-gen"),
    ("tiny-serve-window-moe", "serve-commandaplus-mixedlen"),
    ("tiny-serve-jamba", "serve-jamba2-longctx"),
])
def test_tiny_cell_runs_through_the_committed_driver(tiny_cell, stands_for):
    result = run_tiny(tiny_cell, stands_for)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in BENCH["end_to_end"]
        if stands_for in m.get("workloads", CELLS)}
