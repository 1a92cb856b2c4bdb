"""Force tests onto a virtual 8-device CPU mesh before JAX is imported.

Mirrors the reference's test stance (SURVEY §4): everything runs in-process
without cluster/TPU hardware; multi-device behavior is exercised on host
devices. What runs on the chip is chip_smoke.py, not the tests.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Tests never touch an accelerator: pin the config as well as the
# environment, before any JAX dispatch. The environment variable is bound
# when jax is first imported, and something else (a plugin, a sitecustomize)
# may have imported it already. chip_smoke.py is what runs on the chip.
import jax

jax.config.update("jax_platforms", "cpu")
