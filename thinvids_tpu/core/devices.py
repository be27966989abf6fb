"""Device bootstrap shared by the process entry points and the tests.

jax picks its platform from the environment: on a TPU host the `tpu`
backend is the default, and `JAX_PLATFORMS=cpu` holds a process to the
CPU (the tests, the smoke's reference leg). A chip belongs to ONE
process at a time, so a process that must stay off the chip sets that
variable before it first touches jax. `force_cpu_devices(n)` is the way
to get N virtual CPU devices for the multi-device paths (tests, the
driver's `dryrun_multichip`); `configure_compile_cache()` places jax's
persistent compilation cache for the processes that compile.
"""

from __future__ import annotations

import os
import re

_FLAG = "--xla_force_host_platform_device_count"

#: the fixed in-checkout compile cache (`<checkout>/.jax_cache`,
#: git-ignored). The path is part of jax's cache key, so it is derived
#: from the package location — never from a temp name, pid or time.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def force_cpu_devices(n: int) -> None:
    """Arrange for jax to expose >= `n` virtual CPU devices.

    Must run before any jax backend is initialized; raises RuntimeError
    (instead of failing later with a misleading device-count error) when
    backends already exist with fewer devices.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_FLAG}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = f"{flags} {_FLAG}={n}".strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = flags.replace(m.group(0), f"{_FLAG}={n}")

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError as exc:  # backends already initialized
        if len(jax.devices()) < n:
            raise RuntimeError(
                f"jax backends already initialized with "
                f"{len(jax.devices())} device(s); force_cpu_devices({n}) "
                f"must be called before the first jax backend use"
            ) from exc


def configure_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns its directory.

    Called once from each process entry point that compiles (cli
    coordinator/worker, the driver entry) — never from the
    tests. `JAX_COMPILATION_CACHE_DIR` wins: jax reads it itself and
    this code sets nothing. Unset, the cache goes to the fixed
    `DEFAULT_COMPILE_CACHE` so every process of a checkout shares one
    cache and a second run finds what the first compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env

    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE

