"""Test harness: run the suite on a virtual 8-device CPU mesh.

Multi-device tests follow SURVEY.md §4: simulate a mesh with
`--xla_force_host_platform_device_count=8` on CPU. `force_cpu_devices`
sets the flag and the platform before jax's first backend init, so the
suite does not depend on `JAX_PLATFORMS=cpu` in its environment. Nothing
here (or in any test module's import) touches `jax.devices()` or the
TPU's library: every xdist worker must collect the same tests.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepof_tpu.core.hostmesh import force_cpu_devices  # noqa: E402

# The suite is XLA-compile-dominated (multi-device train steps on the CPU
# mesh); force_cpu_devices also enables the persistent compilation cache,
# which cuts repeat runs from minutes to seconds. The suite owns its cache:
# a JAX_COMPILATION_CACHE_DIR from outside (the chip machine ships one)
# would override every directory the cache tests hand in, and fill with
# CPU entries. The subprocess tests that pin the variable's rule set it
# themselves (tests/test_chip_smoke.py).
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
force_cpu_devices(8)

import socket  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Shared networking helpers for every server-shaped test (test_serve,
# test_fleet): the canonical wait-for-listen lives next to the fleet's
# own spawn logic — one definition, no port-collision or
# connect-before-bind flakes.
from deepof_tpu.serve.fleet import wait_for_listen  # noqa: E402, F401


def free_port(host: str = "127.0.0.1") -> int:
    """An ephemeral port that was free at bind time. Prefer binding the
    server to port 0 and reading its bound address (race-free); use this
    only where a port number must exist before the server does."""
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.fixture
def on_a_tpu(monkeypatch):
    """Steer `ops.warp`'s `impl="auto"` as a TPU would take it, with the
    Pallas warp kernels in interpret mode (the gate asks
    `jax.default_backend()`; steering belongs to the test, not to an
    option of the program). Yields the list of `sweep_limit`s the kernel
    wrapper was called with."""
    import jax

    import deepof_tpu.ops.pallas.warp as kernel_mod

    calls, real = [], kernel_mod.backward_warp_pallas

    def recording(image, flow, batch_axes=("data",), sweep_limit=None):
        calls.append(sweep_limit)
        return real(image, flow, interpret=True, batch_axes=batch_axes,
                    sweep_limit=sweep_limit)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel_mod, "backward_warp_pallas", recording)
    return calls
