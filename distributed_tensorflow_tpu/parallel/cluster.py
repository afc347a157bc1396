"""Multi-host cluster bootstrap — replaces ClusterSpec/Server/TF_CONFIG.

Reference mechanism (SURVEY.md §3.1, substrate $TF/python/training/
server_lib.py:96,243): every process parses ``--job_name/--task_index``,
builds a ClusterSpec naming every peer, and starts an in-process gRPC server;
PS processes then block in ``server.join()`` forever.

TPU-native shape: every host runs the *same* program. ``jax.distributed
.initialize`` stands up the coordination service (the control plane the
reference got from gRPC + TF_CONFIG), after which ``jax.devices()`` is global
and XLA owns the data plane (ICI within a slice, DCN between slices). There
are no roles — no PS, no "chief session" — only process 0 conventionally
doing singleton host work (logging, checkpoint metadata), mirroring how the
reference's chief ran init and the sync token queue (SURVEY.md §3.1).
"""

from __future__ import annotations

import dataclasses
import logging
import os

import jax

logger = logging.getLogger(__name__)

_initialized = False


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    """Topology flags. All default to single-process (the common TPU-VM case,
    where the TPU runtime discovers peers itself and none of these are
    needed — the analog of how TPUClusterResolver replaced hand-written
    ClusterSpecs, $TF/python/distribute/cluster_resolver/tpu/
    tpu_cluster_resolver.py:95).
    """

    coordinator_address: str | None = None  # "host:port" of process 0
    num_processes: int | None = None
    process_id: int | None = None
    local_device_ids: tuple[int, ...] | None = None
    # "auto" (default): argless jax.distributed.initialize() when TPU-pod
    # environment markers are present (the TPUClusterResolver analog,
    # $TF tpu_cluster_resolver.py:95 — metadata autodetection); "always":
    # force argless init; "never": only explicit/env-configured init.
    auto_detect: str = "auto"


#: Where the persistent XLA compilation cache lives when the environment
#: does not place it: one fixed directory inside the checkout. The path is
#: part of the cache key, so it is never a temp name, pid or timestamp.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Every entry point passes through here (``initialize``, the serve
    entry, ``benchmark/run.py``, ``chip_smoke.py``) before its first compile.
    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside — jax
    reads that variable itself, so no directory is set in code; unset,
    the cache goes to ``DEFAULT_COMPILE_CACHE_DIR``. Quick compiles are
    cached too (a restart replays the whole start-up, so every skipped
    compile counts) unless ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``
    says otherwise."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def initialize(config: ClusterConfig | None = None) -> None:
    """Idempotent multi-host init. Safe to call in single-process runs.

    Replaces the per-role bootstrap of SURVEY.md §3.1 (ClusterSpec → Server →
    ps? join : build graph). Call once at program start, before any
    device-touching JAX call.
    """
    global _initialized
    if _initialized:
        return
    config = config or ClusterConfig()
    configure_compile_cache()
    explicit = config.coordinator_address is not None
    env = "COORDINATOR_ADDRESS" in os.environ
    if explicit or env:
        jax.distributed.initialize(
            coordinator_address=config.coordinator_address,
            num_processes=config.num_processes,
            process_id=config.process_id,
            local_device_ids=config.local_device_ids,
        )
        _log_topology()
    elif config.auto_detect == "always" or (
        config.auto_detect == "auto" and _on_multihost_tpu_pod()
    ):
        # Pod-idiomatic path: argless initialize lets jax's cluster
        # autodetection (GCE/TPU metadata) discover coordinator + peers —
        # the TPUClusterResolver analog. Never triggered on single-host
        # TPU-VMs or CPU test rigs.
        jax.distributed.initialize()
        _log_topology()
    _initialized = True


def _on_multihost_tpu_pod() -> bool:
    """True when env markers say this process is one worker of a multi-host
    Cloud-TPU pod slice. `TPU_WORKER_HOSTNAMES` lists every peer host of
    the slice (set by the TPU runtime); more than one entry means
    multi-host, where argless jax.distributed.initialize is both safe and
    required for a global jax.devices() view."""
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if "," in hostnames:
        return True
    # Multislice (MEGASCALE) deployments always need the coordination
    # service, even with one host per slice.
    if os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        return True
    return False


def _log_topology() -> None:
    logger.info(
        "jax.distributed initialized: process %d/%d, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_chief() -> bool:
    """Process 0 — the singleton-host-work role. Unlike the reference's chief
    (ChiefSessionCreator, $TF monitored_session.py:623) it holds no special
    graph state: any process could take over after a restart."""
    return jax.process_index() == 0


def sync_hosts(name: str = "sync") -> None:
    """Host-level barrier across processes (the reference's analog was the
    token queue + wait_for_session, SURVEY.md §3.1). No-op single-process."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)
