"""Process-level JAX set-up shared by the entry points: the virtual
n-device CPU platform the tests and the multichip dry run use, the
persistent compile cache every entry point that compiles places, and the
gate every entry point on the device path passes.

The first two must run before the first JAX backend initialization in the
process: XLA flags are parsed once per process at first backend init, and
the cache directory is read when the first executable is compiled.
"""

from __future__ import annotations

import os
import re
import sys
from pathlib import Path

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# A fixed path inside the checkout: the directory is part of the cache key's
# lookup, so a path that moves between runs (tempfile, pid, timestamp) never
# hits. Git-ignored.
_IN_TREE_CACHE = Path(__file__).resolve().parents[1] / ".jax_cache"


def force_cpu_devices(n: int) -> None:
    """Point JAX at >= n virtual CPU devices.

    Env var for a not-yet-imported jax, config update for an
    imported-but-uninitialized one. An existing smaller device-count flag is
    raised to n; a larger one is kept.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    match = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    if match:
        if int(match.group(1)) < n:
            flags = re.sub(rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n}", flags)
            os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = f"{flags} {_COUNT_FLAG}={n}".strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # backend already initialized; callers fall back to jax.devices("cpu")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and nothing
    is set in code, so whoever runs the program decides where the cache
    lives. Otherwise the cache is `<checkout>/.jax_cache`.
    """
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside

    import jax

    jax.config.update("jax_compilation_cache_dir", str(_IN_TREE_CACHE))
    return str(_IN_TREE_CACHE)


def require_tpu(who: str) -> list:
    """jax.devices() when they are TPUs; otherwise exits non-zero, naming
    the platform found. Initializes the backend in THIS process: no probe
    child (it would take the chip and hand it back), no retry, and no run
    on a host under a device metric's name."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"{who} runs on a TPU; jax.devices()[0].platform is "
            f"'{devices[0].platform}' ({devices[0].device_kind}). "
            "Nothing was run.")
    return devices
