"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect.  The benchmark keeps its own copy (the program
has one in ``deepspeed_tpu/utils/chip.py`` and may change it).  A device
missing here is an error for every utilization, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bits_per_s": 1600e9, "hbm_bytes": 16e9},
}


def peaks(device_kind):
    """The peaks of ``device_kind``; raises for a kind not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind={device_kind!r} (known: "
            f"{sorted(PEAKS)}); add its row, with the source, to "
            "perf/peaks.py before reporting a utilization on it") from None
