"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16 and 819 GB/s HBM bandwidth per chip, 16 GiB
of HBM.  A device that is not listed is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud TPU v5e documentation",
    },
}


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
