"""The device the run is on: the check for chips, the published peaks,
and what the last line says about the device. No CPU branch: a run that
finds no TPU fails before anything is measured."""
from __future__ import annotations

import sys
from typing import Any, Dict

# Published peaks per chip, matched as a substring of jax's device_kind.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
# 1,600 Gbit/s inter-chip interconnect). A device that is not here has no
# roofline and no MFU: `peaks` raises rather than invent one.
_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9}
PEAKS = {"v5 lite": _V5E, "v5e": _V5E}


def peaks(device_kind: str) -> Dict[str, float]:
    kind = device_kind.lower()
    for key, row in PEAKS.items():
        if key in kind:
            return row
    raise ValueError(f"no published peaks for device_kind {device_kind!r}: "
                     f"add a sourced row to benchmark/harness/device.py")


def start(chips: int) -> Dict[str, Any]:
    """What the command and every tool do before they touch the chip: the
    compile cache at its fixed place (inside the checkout, or where
    JAX_COMPILATION_CACHE_DIR says), kept for small programs too, so that
    a second run compiles nothing; then the check for the cell's chips.
    Returns the device's description with the cache's directory."""
    import jax
    from paddle_tpu.core.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return {**require(chips), "compile_cache": cache_dir}


def require(chips: int) -> Dict[str, Any]:
    """Exit non-zero, naming the platform, unless JAX found `chips` TPU
    devices. Returns what the last line reports about the device."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"benchmark: needs a TPU; jax.devices()[0].platform is "
                 f"{devs[0].platform!r} ({len(devs)} device(s)): no CPU "
                 f"branch, no result")
    if len(devs) < chips:
        sys.exit(f"benchmark: the cell needs {chips} TPU chips, jax sees "
                 f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips the cell used."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
