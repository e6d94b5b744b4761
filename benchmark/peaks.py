"""Published peaks of the chips the benchmark runs on, keyed by
``jax.devices()[0].device_kind``. One table, no CPU row, no override:
a device that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture —
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
"""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "benchmark/peaks.py has no row for device kind %r; add one "
            "with its published source" % (device_kind,)) from None
