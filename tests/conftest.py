"""Test configuration: force jax onto a virtual 8-device CPU mesh.

Mirrors the reference strategy of testing multi-node logic without hardware
(SURVEY.md §4: in-process multi-"node" fixtures + fake topology providers).
The platform is pinned both in the environment (for the processes tests
spawn) and in jax.config (for this one, should a TPU be attached).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# One agent subprocess per node would slow every cluster test; the
# dedicated agent test re-enables it for its own cluster.
os.environ.setdefault("RAY_TPU_DISABLE_AGENT", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Many tests build engines that compile IDENTICAL programs (the decode
# tick, prefill buckets, ...); the persistent cache dedupes those, which
# is most of the suite's wall time on a small CI host. The directory is
# the one every process of the repo uses (ray_tpu/util/compile_cache.py);
# the thresholds are lowered because CPU test programs compile in well
# under the default second.
from ray_tpu.util import compile_cache  # noqa: E402

compile_cache.ensure()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: expensive test excluded from the tier-1 window "
        "(-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection test driven by the deterministic chaos "
        "harness (ray_tpu/_private/chaos.py); fast ones stay in tier-1")


@pytest.fixture
def ray_start_regular():
    """In-process runtime, fresh per test (reference: conftest.py::ray_start_regular)."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    info = ray_tpu.init(num_cpus=4, num_tpus=0)
    yield info
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    import ray_tpu

    yield None
    ray_tpu.shutdown()


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Force pallas kernels into interpret mode so TPU kernel tests run
    under tier-1 (``JAX_PLATFORMS=cpu``) without TPU-only skips.

    The ops dispatchers (``ops/dispatch.py::interpret_default``) resolve
    ``interpret=None`` via ``RAY_TPU_PALLAS_INTERPRET`` before falling
    back to backend detection, so this works on CPU (where it is also
    the backend default) AND pins interpret mode on a TPU host — kernel
    tests behave identically everywhere."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    yield


@pytest.fixture(scope="session")
def cpu_mesh8():
    """8-device CPU mesh for sharding tests."""
    from ray_tpu.parallel import MeshConfig, make_mesh

    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 virtual CPU devices, got {len(devices)}"
    return make_mesh(MeshConfig(fsdp=-1), devices=devices)
