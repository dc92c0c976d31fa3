"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""

DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197.0e12, "int8_ops": 393.0e12,
                    "hbm_bytes_per_s": 819.0e9, "hbm_bytes": 16.0e9},
}


def peaks(device_kind):
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no published peaks for device kind %r (known: %s); add its row "
            "to benchmark/lib/peaks.py with its source"
            % (device_kind, sorted(DEVICE_PEAKS))) from None
