"""Virtual multi-device CPU platform + the compile cache's location.

Tests, rehearsals and the jax-free supervisors run on the CPU backend
with N virtual devices (`--xla_force_host_platform_device_count`). Both
switches must be in place before jax initializes its first backend;
`force_cpu_devices` sets them in-process so a caller does not depend on
its environment.
"""

from __future__ import annotations

import os
import re

#: Where compiled executables persist when `JAX_COMPILATION_CACHE_DIR` is
#: unset: a fixed path under the checkout (git-ignored). The directory is
#: part of nothing's identity but must not move — a cache under a temp
#: name, pid or timestamp never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "artifacts", "xla_cache")

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_COUNT_FLAG = "--xla_force_host_platform_device_count"


def compile_cache_dir(requested: str | None = None) -> str:
    """The one rule for the cache's place: `JAX_COMPILATION_CACHE_DIR`
    where it is set — nothing in the program overrides it — else
    `requested` (the `train.compile_cache_dir` field), else the fixed
    `COMPILE_CACHE_DIR`."""
    return os.environ.get(_CACHE_ENV) or requested or COMPILE_CACHE_DIR


def force_cpu_devices(n: int = 8) -> None:
    """Redirect jax onto a CPU platform with >= n virtual devices.

    Must run before any backend initialization; backends that are
    already live are unaffected, so callers that need n devices should
    assert on `len(jax.devices())`. Also points the persistent
    compilation cache at `compile_cache_dir()` (the workloads behind
    this helper are XLA-compile-dominated).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    if m is None:
        flags = (flags + f" {_COUNT_FLAG}={n}").strip()
    elif int(m.group(1)) < n:
        flags = flags.replace(m.group(0), f"{_COUNT_FLAG}={n}")
    os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    # jax's 1 s min-compile-time default stays: the multi-second
    # model/step compiles that dominate cold starts all clear it, and
    # thousands of sub-second entries are not worth their files.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
