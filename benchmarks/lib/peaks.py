"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

A device that is not in the table is an error, never a default: a share of
a peak against an invented peak is worse than no number."""

from __future__ import annotations

_V5E = {
    "bf16_flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
              "819 GB/s of HBM2e per chip",
}

PEAKS: dict[str, dict] = {
    "TPU v5 lite": _V5E,   # what jax reports on a v5e (PERF.md section 3)
    "TPU v5e": _V5E,
}


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in the peak table "
            f"({sorted(PEAKS)}); add a row with its published source "
            f"before measuring on it") from None
