"""What every script that runs on the chip shares (chip_smoke.py,
benchmarks/): the device it ran on, that device's published peaks, and
where compiled programs are cached.  The benchmark (perf/) keeps its own
copy of the peaks on purpose (perf/peaks.py says why).

Importing this module does not initialize a JAX backend, so a launcher
parent may import it without taking the chip from its children.
"""

import os

# Published per-chip peaks, keyed by jax's ``device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 819 GB/s HBM.  A device missing here is an error for
# anything that reports a utilization — never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}

# Fixed, inside the checkout, ignored by git.  The path is part of how a
# cache entry is found again, so it carries no pid, time or temp name.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def device_summary() -> dict:
    """The device as JAX reports it: platform, kind and count."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def device_peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; raises for an unknown kind."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind={device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add its row with a source to "
            "deepspeed_tpu/utils/chip.py DEVICE_PEAKS before reporting a "
            "utilization on it") from None


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and nothing is set here; otherwise the cache goes to
    ``COMPILE_CACHE_DIR``.  Call before the first compilation."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
