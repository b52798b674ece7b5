"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a share
of a peak is only as good as the peak it is taken of.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,        # FLOP/s, MXU, bfloat16 operands
        "int8_ops": 393e12,          # OP/s
        "hbm_bytes_s": 819e9,        # bytes/s
        "hbm_bytes": 16e9,           # bytes
        "ici_bits_s": 1600e9,        # bits/s, chip to chip
        "source": "Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 "
                  "Gbit/s ICI",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a device the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to bench/peaks.py with their source") from None
