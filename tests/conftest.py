"""Test harness: run on a virtual 8-device CPU mesh.

The reference tests multi-node behavior with in-process Dask workers
(reference: tests/python_package_test/test_dask.py:26). Here the analog is
8 virtual CPU devices via XLA host-platform device count; distributed tests
build a jax.sharding.Mesh over them. Pallas kernels run only where a test
sets the interpret flag (``monkeypatch.setattr(partition, "_INTERPRET",
True)`` or ``LGBTPU_PALLAS_INTERPRET=1``); nothing picks the interpreter
from the backend.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent compile cache: the jitted tree builder dominates test wall-clock
from lightgbm_tpu.runtime import enable_compile_cache  # noqa: E402

_CACHE_DIR = enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    """The 8 virtual CPU devices; skips when the suite was started on
    another backend or with fewer devices."""
    import jax

    devs = jax.devices()
    if jax.default_backend() != "cpu" or len(devs) < 8:
        pytest.skip("needs the 8-device CPU mesh (JAX_PLATFORMS=cpu + "
                    "xla_force_host_platform_device_count=8)")
    return devs


def clean_cpu_env(n_devices: int = 8) -> dict:
    """Environment for subprocesses that must run on the virtual CPU mesh
    whatever this process is attached to (a child never needs the chip)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = _CACHE_DIR   # one cache per suite run
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_devices}")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    return env
