"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,          # FLOP/s
        "int8_ops": 393e12,            # OP/s
        "hbm_bytes_per_s": 819e9,      # B/s
        "hbm_bytes": 16e9,             # B
        "source": "Google Cloud documentation, 'TPU v5e' system "
                  "architecture: 197 TFLOP/s bf16, 393 TOP/s int8, "
                  "16 GB HBM2 at 819 GB/s per chip",
    },
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
