"""Test config: simulate an 8-chip mesh on CPU.

Forces the CPU platform with 8 virtual devices so multi-chip
sharding/collective logic is exercised without TPU hardware — the JAX
equivalent of the reference faking a cluster with env vars in ``local.sh``
(SURVEY.md §4).  ``JAX_PLATFORMS=cpu`` in the environment does the same
for the platform; the update below makes a bare ``pytest`` safe too.
``XLA_FLAGS`` must be set before the first backend initialization.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# In-process `launch.main(...)` calls place the persistent compile cache
# (utils/backend.py), which would switch it on for the rest of the
# session; tests neither read nor fill the checkout's cache.
jax.config.update("jax_enable_compilation_cache", False)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
