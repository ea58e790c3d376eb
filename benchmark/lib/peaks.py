"""THE peaks table of the benchmark, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s. Only the peaks a metric
reads are rows here. A device that is not in the table is an error,
never a default (``bench.PEAKS`` stays the program's own).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a "
            f"row with its source to benchmark/lib/peaks.py "
            f"(known: {sorted(PEAKS)})") from None
