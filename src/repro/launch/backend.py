"""Backend setup helpers (platform, compile cache, precision defaults).

The Pallas kernels run compiled on a TPU and interpreted everywhere else
(``kernels.common.pallas_interpret_default``, which ``pallas_call``
consults at every launch).  The setup helpers only take effect when
called *before* jax initializes its backends (first device query / first
trace), which is why none of them are called at import time anywhere in
the library — launch scripts call :func:`setup` as their first
statement.

``backend_info`` is safe to call any time and is what benches record
next to their numbers, so a result file says which backend (and whether
fp8 storage was real or degraded) produced it.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = [
    "backend_info",
    "enable_x64",
    "set_host_device_count",
    "set_platform",
    "setup",
]

#: Persistent compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is not
#: set: a fixed directory inside the checkout (listed in ``.gitignore``).
#: The path is part of the cache key, so it never depends on a tempdir,
#: a pid or the clock.
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def set_platform(platform: str = "cpu") -> None:
    """Pin jax to ``'cpu'`` or ``'tpu'``; call before any jax use."""
    if platform not in ("cpu", "tpu"):
        raise ValueError(f"unknown platform {platform!r}")
    import jax

    jax.config.update("jax_platform_name", platform)


def set_host_device_count(n: int) -> None:
    """Force ``n`` host (CPU) devices via XLA_FLAGS — the multi-device test
    lane's mechanism.  Must run before the first jax import in the
    process to take effect; appending here keeps other flags intact."""
    n = int(n)
    if n < 1:
        raise ValueError(f"need n >= 1, got {n}")
    cur = os.environ.get("XLA_FLAGS", "")
    kept = [f for f in cur.split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    kept.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(kept)


def enable_x64(on: bool = True) -> None:
    """Toggle 64-bit jax defaults (off everywhere in this repo: the
    kernels' accumulation contract is f32; x64 is for oracle checks)."""
    import jax

    jax.config.update("jax_enable_x64", bool(on))


def setup(platform: str | None = None, *, host_devices: int | None = None,
          x64: bool = False) -> dict:
    """One-call launch-script prologue: optionally pin the platform and
    host device count, keep compiled programs in the persistent cache,
    set precision defaults, and return :func:`backend_info` (plus the
    cache directory) for logging.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    no cache directory is set here; otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE`."""
    import jax

    if host_devices is not None:
        set_host_device_count(host_devices)
    if platform is not None:
        set_platform(platform)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    enable_x64(x64)
    return {**backend_info(), "compile_cache": cache_dir}


def backend_info() -> dict:
    """Snapshot of the realized backend: platform, device kind/count,
    whether fp8 storage is on (vs the bf16 degradation,
    ``core.dtypes.fp8_supported``), and the Pallas interpret default."""
    import jax

    from ..core.dtypes import fp8_supported
    from ..kernels.common import pallas_interpret_default

    devs = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "none",
        "device_count": len(devs),
        "fp8": fp8_supported(),
        "interpret": pallas_interpret_default(),
    }
