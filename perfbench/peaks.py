"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not listed is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
"""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
    },
}
