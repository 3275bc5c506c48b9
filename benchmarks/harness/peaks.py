"""The chip's published peaks, keyed by ``device_kind``.  A device that
is not here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB HBM2e at 819 GB/s per chip
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise SystemExit(f"no peaks for device kind {device_kind!r}: add "
                         "it to benchmarks/harness/peaks.py with its source")
    return PEAKS[device_kind]
