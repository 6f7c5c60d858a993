"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding/collective paths are
validated on 8 virtual CPU devices (tests/test_chip_compile.py compiles them
for a described v5e, chip_smoke.py --chips 4 runs them on four chips). The
environment is set before any jax import, and daemons and task children that
tests spawn inherit it.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

from tpumr.fs.filesystem import FileSystem  # noqa: E402


@pytest.fixture(autouse=True)
def _clear_fs_cache():
    """Each test gets fresh FileSystem instances (mem: FS is stateful)."""
    FileSystem.clear_cache()
    yield
    FileSystem.clear_cache()
