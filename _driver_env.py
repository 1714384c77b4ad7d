"""The environment of a CPU-only child process, for the standalone
drivers (``__graft_entry__.py``) and the tests that start CPU children.

A child that must never take the chip from its parent gets
``JAX_PLATFORMS=cpu`` with ``n_devices`` virtual devices, an empty
``PYTHONPATH`` (it imports only the checkout and the installation) and no
variable that points a PJRT plug-in at a device; keeping this in one
place keeps the callers in lockstep.
"""

from __future__ import annotations

import os

_PLUGIN_ENV_VARS = ("JAX_PLATFORM_NAME", "TPU_LIBRARY_PATH", "PJRT_DEVICE")


def cpu_scrubbed_env(n_devices: int = 8, cache_dir: str | None = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ""
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    for k in _PLUGIN_ENV_VARS:
        env.pop(k, None)
    if cache_dir:
        # Persistent compilation cache: repeat driver invocations skip the
        # CPU-mesh XLA compiles that dominate wall time.
        env.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir)
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    return env
