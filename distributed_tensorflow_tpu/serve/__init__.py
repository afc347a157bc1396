"""TPU-native inference serving: KV-cached decode with continuous batching.

The fifth subsystem — the first that makes the framework an inference
stack rather than a trainer. Composition over the existing layers, per
the TF-Replicator thin-layer lesson (PAPERS.md): the cache is an
ordinary pytree placed by parallel/sharding.py rules, the decode path is
the SAME ``models.Transformer`` with a ``kv_cache`` argument, attention
falls back to the masked dense form where the flash kernel doesn't apply
(ops.attention.cached_attention), and the engine is a host-drives/
device-computes loop like train/loop.py. Above the single engine sits
the serve FLEET (fleet.py + router.py): N replica engines behind a
prefix-aware, SLO-laned router under heartbeat supervision — the
serving twin of resilience/fleet.py. See docs/serving.md.
"""

from .decode import (  # noqa: F401
    copy_block,
    decode_step,
    jit_copy_block,
    jit_decode_step,
    jit_paged_decode_step,
    jit_paged_prefill_chunk,
    jit_prefill,
    paged_decode_step,
    paged_prefill_chunk,
    prefill,
    prefill_bucket,
)
from .engine import ServeEngine, StepStats  # noqa: F401
from .fleet import (  # noqa: F401
    EngineBridge,
    LocalReplica,
    ServeFleetExhausted,
    ServeFleetSupervisor,
    SubprocessReplica,
)
from .kv_cache import (  # noqa: F401
    CACHE_LOGICAL,
    PAGED_CACHE_LOGICAL,
    BlockAllocator,
    HybridCache,
    KVCache,
    NoFreeBlocks,
    PagedKVCache,
    SnapshotTable,
    cache_specs,
    init_cache,
    init_hybrid_cache,
    init_paged_cache,
    paged_cache_specs,
    shard_cache,
    shard_paged_cache,
)
from .router import (  # noqa: F401
    LANE_BATCH,
    LANE_INTERACTIVE,
    LANES,
    FleetRequest,
    Router,
    UnknownLane,
)
from .sampling import sample  # noqa: F401
from .scheduler import (  # noqa: F401
    FINISH_CANCELLED,
    FINISH_EOS,
    FINISH_MAX_LEN,
    FINISH_MAX_NEW,
    FINISH_REASONS,
    FINISH_TIMEOUT,
    QueueFull,
    Request,
    Scheduler,
    SchedulerClosed,
)
