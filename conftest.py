"""Root pytest conftest: tests run on the CPU with 8 virtual devices.

CPU is the oracle device (SURVEY.md §4) and the fake cluster is
``--xla_force_host_platform_device_count``. Both are environment that JAX
reads when it is first imported, so they are set here, at conftest import,
before any test module imports jax (``pytest.ini`` disables the plugins
that would import it earlier).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
