"""Cluster bootstrap: pod auto-detection + config plumbing (SURVEY.md §2b
'Cluster bootstrap' row — the TPUClusterResolver analog must engage
without hand-exported env vars)."""

import pytest

from distributed_tensorflow_tpu.parallel import cluster


@pytest.fixture
def fresh_cluster(monkeypatch):
    """Reset the idempotence latch and capture initialize calls."""
    calls = []

    def fake_init(*a, **kw):
        calls.append((a, kw))

    monkeypatch.setattr(cluster, "_initialized", False)
    import jax

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    monkeypatch.delenv("COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    monkeypatch.delenv("MEGASCALE_COORDINATOR_ADDRESS", raising=False)
    yield calls
    monkeypatch.setattr(cluster, "_initialized", False)


def test_single_process_no_init(fresh_cluster):
    cluster.initialize()
    assert fresh_cluster == []


def test_explicit_coordinator(fresh_cluster):
    cluster.initialize(cluster.ClusterConfig(
        coordinator_address="10.0.0.1:1234", num_processes=2, process_id=1,
    ))
    (a, kw), = fresh_cluster
    assert kw["coordinator_address"] == "10.0.0.1:1234"
    assert kw["num_processes"] == 2 and kw["process_id"] == 1


def test_pod_markers_trigger_argless_init(fresh_cluster, monkeypatch):
    """Multi-host TPU pod: TPU_WORKER_HOSTNAMES lists >1 peer → argless
    initialize (metadata autodetection)."""
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-0,host-1,host-2,host-3")
    cluster.initialize()
    assert fresh_cluster == [((), {})]


def test_single_host_tpu_vm_no_init(fresh_cluster, monkeypatch):
    """One hostname (single-host TPU VM): no distributed init needed."""
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-0")
    cluster.initialize()
    assert fresh_cluster == []


def test_megascale_marker_triggers_init(fresh_cluster, monkeypatch):
    monkeypatch.setenv("MEGASCALE_COORDINATOR_ADDRESS", "coord:8080")
    cluster.initialize()
    assert fresh_cluster == [((), {})]


def test_auto_detect_never(fresh_cluster, monkeypatch):
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-0,host-1")
    cluster.initialize(cluster.ClusterConfig(auto_detect="never"))
    assert fresh_cluster == []


def test_cluster_config_plumbed_from_cli(fresh_cluster):
    """--cluster.* and --mesh.dcn_* reach their destinations through the
    workload config tree (round-1 Weak #7: ClusterConfig was unreachable)."""
    from distributed_tensorflow_tpu.utils import config as config_lib
    from distributed_tensorflow_tpu.workloads import mnist_mlp

    cfg = config_lib.apply_overrides(
        mnist_mlp.default_config(),
        ["--cluster.coordinator_address=10.1.1.1:9",
         "--cluster.num_processes=4",
         "--cluster.process_id=0",
         "--mesh.dcn_data=2"],
    )
    assert cfg.cluster.coordinator_address == "10.1.1.1:9"
    assert cfg.mesh.dcn_data == 2 and cfg.mesh.num_slices == 2
    cluster.initialize(cfg.cluster)
    (a, kw), = fresh_cluster
    assert kw["coordinator_address"] == "10.1.1.1:9"
    assert kw["num_processes"] == 4


def test_compilation_cache_config(monkeypatch):
    """The compile cache is placed from outside or at one fixed path:
    with JAX_COMPILATION_CACHE_DIR set the helper sets no directory in
    code (jax reads the variable itself); unset, it is <repo>/.jax_cache.
    Whether entries are hit is a chip fact (chip_smoke.py run twice)."""
    import os

    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        cluster.configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cluster.configure_compile_cache() == \
            os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(repo, ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert not hasattr(cluster.ClusterConfig(), "compilation_cache_dir")
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
