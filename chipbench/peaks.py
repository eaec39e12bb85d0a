"""Published peaks of the accelerators the benchmark runs on.

Keyed by ``jax.Device.device_kind``.  A device missing from the table is
an error: a roofline share against a guessed peak means nothing.
"""
from __future__ import annotations

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes": 16e9,
    "hbm_bytes_per_s": 819e9,
    "ici_bits_per_s": 1600e9,
    "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
}

PEAKS = {
    "TPU v5 lite": _V5E,      # what JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to chipbench/peaks.py with its source "
                       f"(known: {sorted(PEAKS)})") from None
