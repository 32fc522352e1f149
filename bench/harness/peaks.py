"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not listed is an error."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s per chip.  A float32 matmul at "highest"
    # precision takes six bf16 passes through the MXU.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "f32_highest_passes": 6,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(platform: str, device_kind: str) -> dict:
    if platform != "tpu":
        raise RuntimeError(f"the benchmark needs a TPU; JAX found platform {platform!r}")
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peaks for device kind {device_kind!r}; add them to "
            "bench/harness/peaks.py with their source") from None
