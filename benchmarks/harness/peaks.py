"""Published peak rates, keyed by the prefix of ``device_kind``. The
benchmark's own copy of ``deepspeed_tpu.accelerator.device.PEAKS``: a PR to
the program cannot move the yardstick. A device that is not here is an error,
never a default."""

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float    # FLOP/s
    int8_ops: float      # OP/s
    hbm_bytes_s: float   # bytes/s
    hbm_bytes: int       # device memory
    ici_bits_s: float    # chip-to-chip interconnect, bit/s
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        197e12, 393e12, 819e9, 16 * 10**9, 1600e9,
        'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s '
        "int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s ICI per chip",
    ),
}


def device_peaks(device_kind: str) -> Peaks:
    for prefix, peaks in PEAKS.items():
        if device_kind.startswith(prefix):
            return peaks
    raise KeyError(
        f"no published peaks for device kind {device_kind!r}: add it to "
        "benchmarks/harness/peaks.py with its source"
    )
