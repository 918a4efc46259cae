"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
A device that is not in the table is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(
            f"benchmark: no published peaks for device_kind={device_kind!r}; "
            f"add it to benchmark/harness/peaks.py with its source")
    return PEAKS[device_kind]
