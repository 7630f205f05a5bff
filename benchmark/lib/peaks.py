"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` JAX reports. A device that is not here is an error: no
utilization is ever computed against a guessed or measured-that-day peak."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it to "
            f"benchmark/lib/peaks.py with its source (known: {sorted(PEAKS)})"
        ) from None
