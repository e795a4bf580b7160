"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

A kind that is not here is an error: a default peak would print a
utilization that means nothing.
"""

from __future__ import annotations

DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bits_per_s": 1600e9},
}


def device_peaks(kind: str) -> dict:
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {kind!r}; add it to "
            f"benchmarks/lib/peaks.py with its source (known: "
            f"{sorted(DEVICE_PEAKS)})") from None
