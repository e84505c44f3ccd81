"""Tiny cells for the CPU: the drivers and ``run_cell`` as a run uses them,
without the look for a chip."""

from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELLS = Path(__file__).resolve().parent / "data" / "cells"


def load(name: str) -> dict:
    with open(CELLS / f"{name}.json") as f:
        return json.load(f)


def tiny(cell_file: str, stands_for: str, chips: int = 1):
    """``(BENCHMARK.json, tiny cell, tiny configuration)``; the tiny cell
    takes the name of the real cell it stands for, so the metrics that list
    that cell are the ones computed."""
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = {**load(cell_file), "name": stands_for, "chips": chips}
    return bench, cell, load(cell["config"])


def cpu_peaks() -> dict:
    from benchmarks.lib import peaks

    return {**peaks.lookup("TPU v5e"), "source": "test stand-in, not a peak"}


def run_tiny(cell_file: str, stands_for: str, chips: int = 1, seed: int = 7,
             seconds: float = 1.0, patch_cell=None) -> dict:
    import jax

    from benchmarks import run as runmod

    bench, cell, config = tiny(cell_file, stands_for, chips)
    if patch_cell:
        patch_cell(cell)
    return runmod.run_cell(bench, cell, config, seed=seed, seconds=seconds,
                           trace=False, devices=jax.devices()[:chips],
                           peaks=cpu_peaks())
