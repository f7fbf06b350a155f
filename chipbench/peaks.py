"""Published per-chip peak rates, keyed by `jax.Device.device_kind`.

The benchmark's own copy of the table (kept apart from the program so that
no change to the program moves the yardstick). Every conversion of a time
into a share of peak reads it. A device kind that is not listed is an
error, never a default.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture
page: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect per chip (four ICI links of 50 GB/s each).

The MXU multiplies bf16 operands only. A float32 matmul at HIGHEST
precision, which the program requests for float32, runs as 6 bf16 passes
(the `jax.lax.Precision` docstring: "HIGHEST ... On TPU: performs float32
computations in 6 bfloat16"), so its peak is the bf16 peak over
`FP32_HIGHEST_PASSES`.
"""

from __future__ import annotations

from typing import NamedTuple


FP32_HIGHEST_PASSES = 6


class DevicePeaks(NamedTuple):
    bf16_flops: float            # MXU FLOP/s with bf16 operands
    hbm_bytes_per_s: float       # HBM bandwidth
    ici_link_bytes_per_s: float  # one chip-to-chip link

    @property
    def fp32_flops(self) -> float:
        """MXU FLOP/s of a float32 matmul at HIGHEST precision."""
        return self.bf16_flops / FP32_HIGHEST_PASSES


V5E = "TPU v5 lite"  # how jax reports a v5e chip's device_kind

DEVICE_PEAKS: dict[str, DevicePeaks] = {
    V5E: DevicePeaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                     ici_link_bytes_per_s=50e9),
}


def peaks_for(device_kind: str) -> DevicePeaks:
    """The table's entry for `device_kind`; KeyError for any other kind."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add it to "
            f"chipbench.peaks.DEVICE_PEAKS with its source") from None


def mxu_flops(peaks: DevicePeaks, compute_dtype: str) -> float:
    """MXU peak at a configuration's compute dtype: "float32" (at HIGHEST)
    or "bfloat16"."""
    if compute_dtype == "float32":
        return peaks.fp32_flops
    if compute_dtype == "bfloat16":
        return peaks.bf16_flops
    raise KeyError(f"no MXU peak for compute dtype {compute_dtype!r}")
