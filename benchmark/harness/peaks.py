"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. Source: Google Cloud documentation, "TPU v5e"
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). Copied from
``bench.DEVICE_PEAKS`` (the program's table) so later PRs cannot move the
yardstick. A kind that is not here is an error, not a default."""

DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(have {sorted(DEVICE_PEAKS)}); add the chip's figures with "
            "their source to benchmark/harness/peaks.py"
        ) from None
