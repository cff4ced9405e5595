"""What every entry point that measures or proves something on the chip
shares: where compiled programs are cached, which device a result came
from, and the refusal to go on without an accelerator.

Called by entry points (chip_smoke.py, bench.py, ``python -m
starway_tpu.bench --payload device``, scripts/kernel_bench.py), never at
library import.
"""

from __future__ import annotations

import os
from pathlib import Path

# One fixed path inside the checkout: the directory is part of the cache
# key, so a temporary name, a pid or a time would never hit.
_REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"

# Published peaks of one chip, keyed by ``device_kind`` as JAX reports it
# (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM).
# A device that is not in the table is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and no
    directory is set in code.  Unset: ``<repo>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program: a cold process on the chip machine otherwise
    # recompiles the many sub-second ones each time.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info() -> dict:
    """The device as JAX reports it; printed with every result."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_accelerator() -> dict:
    """:func:`device_info`, or a non-zero exit when JAX found only the CPU:
    a number from a CPU run is never a device metric, so a measurement
    path without a chip fails instead of falling back."""
    info = device_info()
    if info["platform"] == "cpu":
        raise SystemExit(
            f"no accelerator: jax.devices() is {info['count']} x "
            f"{info['kind']!r} on platform 'cpu'; this entry point "
            f"measures the chip and does not fall back to the CPU")
    return info


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise SystemExit(
            f"no published peaks for device_kind {kind!r}; add them to "
            f"starway_tpu.utils.chip.PEAKS with their source") from None
