"""Backend selection and the persistent compile cache, for entry points.

Three rules, shared by `python -m karpenter_tpu`, the sidecar and
chip_smoke.py:

- The CPU runs only when asked for by name (`JAX_PLATFORMS=cpu`, as the
  tests and the split-mode control plane do). A process that asked for
  nothing gets the TPU or fails (`require_backend`): JAX's own default
  would quietly fall back to the CPU when no chip initializes.
- A chip belongs to one process: nothing here starts a child that
  touches JAX.
- The compile cache is armed by entry points only, never on import, so
  the test suite stays cache-free (`arm_compile_cache`).

`force_virtual_cpu` builds the virtual multi-device CPU backend the tests
and the multi-chip dryrun use; XLA_FLAGS is read at CPU client creation,
so it must run before the first backend init.
"""

from __future__ import annotations

import os
import re
from typing import Optional

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# the checkout root (karpenter_tpu/utils/ -> two levels up): the default
# cache lives at a fixed path inside it, because the path is part of the
# cache key — a directory that moves between runs never hits
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_COMPILE_CACHE = os.path.join(_CHECKOUT, ".jax_compile_cache")


def force_virtual_cpu(n_devices: int) -> None:
    """Make the CPU backend the jax default with n_devices virtual devices.

    Must run before the first backend init. Safe to call repeatedly; an
    existing device-count flag with a smaller count is replaced so a later
    caller asking for more devices is not silently truncated (which would
    fail mesh construction).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    match = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    if match is None:
        flags = f"{flags} {_COUNT_FLAG}={n_devices}".strip()
    elif int(match.group(1)) < n_devices:
        flags = (
            flags[: match.start()]
            + f"{_COUNT_FLAG}={n_devices}"
            + flags[match.end() :]
        )
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"  # for subprocesses we spawn

    import jax

    jax.config.update("jax_platforms", "cpu")


def require_backend() -> str:
    """Initialize the backend an entry point runs on and return its
    platform name. With no platform requested (`JAX_PLATFORMS` unset)
    that is the TPU, and a process that cannot get one raises
    RuntimeError here instead of quietly running on the CPU. A named
    request (`JAX_PLATFORMS=cpu`) is honoured as given."""
    import jax

    if not jax.config.jax_platforms:
        jax.config.update("jax_platforms", "tpu")
    try:
        devices = jax.devices()
    except RuntimeError as error:
        raise RuntimeError(
            f"no usable {jax.config.jax_platforms!r} backend ({error}); "
            "set JAX_PLATFORMS=cpu to run on the CPU on purpose"
        ) from error
    return devices[0].platform


def resolve_compile_cache(flag: Optional[str] = None) -> str:
    """The persistent compile cache directory: JAX_COMPILATION_CACHE_DIR
    when set (it wins over everything), else `flag` (--compile-cache-dir,
    for deployments that mount a volume), else the fixed in-checkout
    DEFAULT_COMPILE_CACHE."""
    return (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or flag
        or DEFAULT_COMPILE_CACHE
    )


def arm_compile_cache(flag: Optional[str] = None) -> str:
    """Point JAX's persistent compile cache at resolve_compile_cache(flag)
    and return the directory. Call from entry points before the first
    compile. With JAX_COMPILATION_CACHE_DIR set, JAX already reads it and
    nothing here sets another directory. The cache singleton latches its
    directory at the first compile, so it is reset to take the new one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = resolve_compile_cache(flag)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    compilation_cache.reset_cache()
    return path
