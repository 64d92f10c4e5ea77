"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default."""

from __future__ import annotations

#: device_kind -> (bf16 FLOP/s, HBM bytes/s, source)
PEAKS = {
    "TPU v5 lite": (197e12, 819e9,
                    "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                    "bf16, 819 GB/s HBM"),
}


def peaks(device_kind: str):
    """(peak bf16 FLOP/s, peak HBM bytes/s) of one chip of this kind."""
    try:
        flops, bw, _ = PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       f"them to chipbench/peaks.py with their source")
    return flops, bw
