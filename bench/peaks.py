"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports. A device that is not listed is an error:
no share of a peak is ever computed against a guessed one."""

from __future__ import annotations

_V5E = {
    "bf16_flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "source": (
        "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip"
    ),
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
