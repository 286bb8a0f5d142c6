"""Published peaks of the chips a cell may be measured on, keyed by the exact
``jax.devices()[0].device_kind`` string. Every utilization the benchmark
prints divides by these and nothing else; a device that is not in the table
is an error, never a default.

"TPU v5 lite" is what a TPU v5e reports through jax 0.9.0 / libtpu 0.0.34.
Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
HBM at 819 GB/s. (Copied from ``ddlbench_tpu.config.DEVICE_PEAKS`` so that a
later change to the program cannot move the yardstick.)
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float  # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float  # capacity, decimal GB as published


DEVICE_PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=1.97e14, hbm_bytes_per_s=8.19e11,
                         hbm_bytes=16e9),
}


def device_peaks(device_kind: str) -> Peaks:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add it to benchmarks/harness/peaks.py "
            f"with its source") from None
