"""Published peaks of the chips the benchmark knows, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, never
a default.

TPU v5e: Google Cloud documentation, "TPU v5e" system architecture:
819 GB/s of HBM bandwidth, 197 TFLOP/s bf16, 16 GB of HBM per chip.
"""

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                "hbm_bytes": 16e9},
}


def peak(device_kind: str, key: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it "
            "to benchmarks/harness/peaks.py with its source"
        )
    return PEAKS[device_kind][key]
