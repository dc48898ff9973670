"""Published peaks of the accelerators this benchmark may run on, keyed by
``device_kind`` as JAX reports it. A device that is not here is an error: the
harness refuses to measure on it (no default, no CPU entry).

Every number carries its source. Utilization and roofline shares divide by
these and by nothing else; ``deepspeed_tpu.telemetry.introspect.PEAK_TABLE``
is the program's own table and is not read here (its ICI figure, 4.0e10 B/s,
is not the published one; PERF.md, Open questions).
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    flops_bf16: float        # FLOP/s, dense bf16
    hbm_bytes_per_s: float   # B/s
    ici_bytes_per_s: float   # B/s, chip-to-chip, all links of one chip
    hbm_bytes: float         # B
    source: str


PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s
    # bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interchip interconnect.
    "TPU v5 lite": Peak(
        flops_bf16=197e12,
        hbm_bytes_per_s=819e9,
        ici_bytes_per_s=1600e9 / 8,
        hbm_bytes=16e9,
        source="Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, 1600 Gbit/s ICI",
    ),
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]  # the same chip under its other reported name


class UnknownDevice(RuntimeError):
    pass


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in perfbench/peaks.py; "
            f"the benchmark measures only on {sorted(PEAKS)}"
        ) from None
