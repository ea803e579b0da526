"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``. A device kind that is not here is an error.

Source: Google Cloud documentation, "TPU v5e"
(https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s in bf16, 393 TOP/s in
int8, 16 GB of HBM at 819 GB/s per chip. The bf16 peak is the only matmul
peak given; float32 matmuls at the default precision run as bf16 passes, so
it bounds them too."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_time(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time a piece of work can take on the chip: the larger of
    its operations over the peak rate and its bytes over the bandwidth."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
