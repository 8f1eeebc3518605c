"""Published per-chip peaks, keyed by jax ``device_kind``: the benchmark's own
table (the program has one too; the yardstick does not read it).

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
819 GB/s of HBM bandwidth, 16 GB of HBM.  A kind that is not listed has no
peak: a metric that needs one is an error there, not the v5e's number.
"""

DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind, field):
    try:
        return DEVICE_PEAKS[device_kind][field]
    except KeyError:
        raise KeyError("no published %s for device kind %r in the "
                       "benchmark's table" % (field, device_kind)) from None
