"""Test rig: 8 fake CPU devices in one process (SURVEY.md §4.2).

The analog of TF's `create_in_process_cluster` ($TF/python/distribute/
multi_worker_test_base.py:123): every collective/sharding test runs on CI
hardware with no TPU. Tests never touch the chip: the CPU backend and the
device count are pinned through the environment before jax is imported,
so every subprocess a test starts inherits them too.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The persistent compilation cache stays OFF in the test rig, for this
# process and every child (entry points turn it on through
# cluster.configure_compile_cache). An earlier jaxlib deserialized CPU
# executables with stale donation aliasing — heap-corruption aborts and
# NaN params on restore-and-resume. On jaxlib 0.9.0
# tests/test_resilience.py::test_kill_resume_bit_identical passes cold
# then warm with the cache on, but a cache shared between test runs is
# state the rig does not need. To exercise it: JAX_ENABLE_COMPILATION_CACHE
# =true JAX_COMPILATION_CACHE_DIR=<dir>.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402

# backends initialize lazily, so this also holds when a pytest plug-in
# imported jax before the environment above was set
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, (
        f"test rig expects >=8 fake devices, got {len(devs)}; "
        "was a jax backend initialized before conftest?"
    )
    return devs


@pytest.fixture()
def mesh8(devices):
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=8), devices[:8])


@pytest.fixture()
def mesh_dp4_tp2(devices):
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=4, model=2), devices[:8])
