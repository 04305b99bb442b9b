"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` string JAX reports. A kind that is not here is an error,
never a default (a made-up peak makes every share of it meaningless).

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
HBM2e at 819 GB/s per chip, 1,600 Gbit/s of inter-chip interconnect. The
figures equal ``parallel/mesh.py``'s; this copy is the benchmark's own so a
later PR cannot move a roofline by editing the program's table.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"(with its source) to benchmarks/harness/peaks.py") from None
