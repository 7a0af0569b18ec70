"""Peaks of the chips the benchmark may run on, keyed by the
``device_kind`` jax reports.

One table, no override and no default: a kind that is not here is an
error, because a utilization against a guessed peak is worse than none.
"""

#: Google Cloud documentation, "TPU v5e" (system architecture, per
#: chip): 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s. Only what a
#: reader under ``layer_metrics/`` divides by is kept here.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


class UnknownDevice(LookupError):
    """``device_kind`` is not in :data:`PEAKS`."""


def peaks_of(device_kind):
    """-> the peak figures of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            "no peaks for device_kind %r in benchmark/reduce/peaks.py "
            "(known: %s); add the chip with its published source before "
            "measuring on it" % (device_kind, sorted(PEAKS))) from None
