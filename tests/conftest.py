"""Test env: force CPU backend with 8 virtual devices so distributed tests
exercise real meshes/collectives without TPU hardware (SURVEY.md §4:
multi-node is simulated; here multi-chip is simulated the XLA way).

Both settings go through the process environment, before jax is imported,
so the subprocesses that tests start inherit them.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu", "tests must run on CPU backend"
