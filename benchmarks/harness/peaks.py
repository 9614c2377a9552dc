"""Peak rates of the chips the benchmark knows, keyed by ``device_kind`` as
jax reports it. A kind that is not here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s, per chip
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak rates for device kind {device_kind!r}: add it to "
            "benchmarks/harness/peaks.py with its source") from None
