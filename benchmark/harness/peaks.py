"""Published peaks of the chips the benchmark may run on, keyed by
`device_kind` as JAX reports it. A kind that is not here is an error."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16 * 1024**3,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(f"benchmark: device kind {device_kind!r} is not in "
                         "harness/peaks.py; add it with its source")
    return PEAKS[device_kind]
