"""KV caches — the resident state of the decode engine.

Four layouts live here (docs/serving.md):

- **Paged** (the default): a fixed pool of KV blocks (``PagedKVCache``)
  plus host-side free/used accounting with copy-on-write refcounts and
  a shared-prefix cache (``BlockAllocator``). A resident request costs
  ``ceil(tokens / block_size)`` blocks instead of a dense ``max_len``
  row, and requests sharing a common prefix map the same physical
  blocks until their first divergent write.
- **Slot-dense** (``KVCache``, the exact-parity fallback): the PR-1
  layout described below, kept bit-for-bit for parity testing and as
  the ``ServeEngine(paged=False)`` escape hatch.
- **Hybrid** (``HybridCache``, for ``models/olmo_hybrid.py``): the paged
  pool for the full-attention layers only and, beside it, per slot and
  per linear layer, the recurrent state and the convolution's window,
  plus a pool of state snapshots for prefix reuse (``SnapshotTable``).

- **Latent** (``LatentCache``, for ``models/gigachat3_5.py``): the same
  beside-the-pool state and snapshots, with a pool of latent rows (one a
  token, shared by every head) in place of the K/V pool, and the running
  counts of the expert layer's local assignments.

Dense layout: one pair of buffers for the whole model, layers stacked
on the leading axis::

    k, v : [num_layers, num_slots, num_heads, max_len, head_dim]

``num_slots`` is the fixed decode-batch width (continuous batching keeps
it full by admitting a queued request the moment a slot frees up —
scheduler.py); ``max_len`` is the per-slot token budget. Each slot is a
ring-less append buffer with a per-sequence write index owned by the
engine: a slot's positions ``0..written-1`` hold real tokens and
everything above is stale garbage that ``cached_attention``'s
``j <= q_pos`` predicate masks, so slot reuse needs NO zeroing — a new
request's prefill simply overwrites from position 0.

Sharding: the cache is a pytree like any other, so the rules of
parallel/sharding.py apply unchanged (docs/serving.md): the ``heads``
dim shards over ``model`` exactly as the attention weights do under
TP_RULES (a TP shard holds the K/V of its own heads — no gather), and
the ``slots`` dim shards over the batch axes ``(data, fsdp)`` like any
input batch. ``CACHE_LOGICAL`` names the dims; ``cache_specs`` maps them
through a logical-rule table.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.transformer import TransformerConfig
from ..ops.latent_attention import stored_width
from ..parallel import mesh as mesh_lib
from ..parallel import sharding


@dataclasses.dataclass
class KVCache:
    """k/v: [num_layers, num_slots, num_heads, max_len, head_dim]."""

    k: jax.Array
    v: jax.Array

    @property
    def num_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[3]

    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v"], meta_fields=[]
)

#: Logical dim names of each cache buffer, resolvable by the same rule
#: tables that place the model weights (sharding.spec_from_logical).
CACHE_LOGICAL = ("layers", "batch", "heads", "len", "kv")

#: Partition-rules table for the dense cache (the default layout of
#: ``cache_specs``): heads → ``model`` exactly as the attention weights
#: under TRANSFORMER_RULES (a TP shard holds the K/V of its own heads),
#: slots → the batch axes like any input batch. Equal by construction
#: to ``spec_from_logical(CACHE_LOGICAL, TP_RULES)`` — pinned by
#: tests/test_serve.py::test_cache_specs_match_rules_table.
KV_CACHE_RULES = sharding.partition_rules(
    "serve-kv-cache",
    ((r"^(k|v)$",
      P(None, (mesh_lib.DATA, mesh_lib.FSDP), mesh_lib.MODEL,
        None, None)),),
    coverage=("k", "v"),
)


def init_cache(
    cfg: TransformerConfig,
    num_slots: int,
    max_len: int | None = None,
    dtype: str | jnp.dtype | None = None,
) -> KVCache:
    """Zero-filled cache for ``cfg``. ``max_len`` defaults to the model's
    context window; ``dtype`` to the model compute dtype (bf16 on TPU —
    halving cache HBM is usually the right serving trade; tests pin
    float32 for exact parity with the uncached forward)."""
    M = cfg.max_len if max_len is None else max_len
    if M > cfg.max_len:
        raise ValueError(
            f"cache max_len={M} exceeds the model context window "
            f"(cfg.max_len={cfg.max_len}: pos_embed has no row for it)"
        )
    dt = jnp.dtype(cfg.dtype if dtype is None else dtype)
    shape = (cfg.num_layers, num_slots, cfg.num_heads, M, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt))


def cache_specs(rules: sharding.LogicalRules | None = None) -> KVCache:
    """PartitionSpec pytree for the cache. The default is the
    KV_CACHE_RULES partition-rules table (heads → ``model``, slots →
    ``(data, fsdp)``) resolved under the engine's strict coverage
    contract; passing explicit logical ``rules`` keeps the
    spec_from_logical escape hatch (tests re-derive the layout from
    custom tables). Feed to ``sharding.shard_tree`` / ``jax.jit``
    in/out shardings."""
    if rules is None:
        return sharding.match_partition_rules(
            KV_CACHE_RULES, KVCache(k=0, v=0)
        )
    spec = sharding.spec_from_logical(CACHE_LOGICAL, rules)
    return KVCache(k=spec, v=spec)


def shard_cache(
    cache: KVCache, mesh, rules: sharding.LogicalRules | None = None
) -> KVCache:
    """Place the cache on a mesh per ``cache_specs`` (device_put)."""
    return sharding.shard_tree(cache, mesh, cache_specs(rules))


# ---------------------------------------------------------------------------
# Paged cache: fixed block pool + host-side block tables (docs/serving.md
# "Paged KV cache"). The dense KVCache above stays as the exact-parity
# fallback (ServeEngine(paged=False)).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PagedKVCache:
    """k/v: [num_layers, num_blocks + 1, num_heads, block_size, head_dim]
    (on the TPU ``head_dim`` rounded up to the lanes: ``stored_head_dim``).

    The device side of the paged cache is ONLY this pool of physical
    blocks — no slot dimension. Which blocks belong to which request is
    the per-slot block table, a small host-owned int32 array handed to
    every jit call (``models.Transformer(..., block_table=)``); free/
    used accounting and copy-on-write refcounts live in the host-side
    ``BlockAllocator``. A resident request therefore costs
    ``ceil(tokens / block_size)`` blocks instead of a dense ``max_len``
    row, and requests sharing a common prefix map the SAME physical
    blocks until their first divergent write.

    The last physical block belongs to no one: the write kernel sends
    there what idle slots and padding must not write
    (``ops.flash_attention.paged_write_kv``), and the tables' sentinel
    entry (``num_blocks``) names it. ``HybridCache`` keeps its pool the
    same way."""

    k: jax.Array
    v: jax.Array

    #: the block pools (``decode.copy_block`` copies a block in each)
    POOLS = ("k", "v")

    @property
    def num_blocks(self) -> int:
        """Blocks a table can name (the allocator's count, and the table's
        sentinel): the pool's, less the write-off block."""
        return self.k.shape[1] - 1

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes

    def block_nbytes(self) -> int:
        """Bytes of ONE physical block across both buffers and all
        layers — the unit of KV-per-request accounting."""
        return self.nbytes() // self.k.shape[1]


jax.tree_util.register_dataclass(
    PagedKVCache, data_fields=["k", "v"], meta_fields=[]
)

#: Logical dims of the pool. ``kv_blocks`` has no rule-table entry, so
#: it resolves to None (replicated): blocks are shared across requests,
#: and a request's blocks must not scatter over the batch axes. Heads
#: still shard over ``model`` exactly like the dense cache.
PAGED_CACHE_LOGICAL = ("layers", "kv_blocks", "heads", "len", "kv")

#: Partition-rules table for the block pool (default of
#: ``paged_cache_specs``): heads → ``model``, blocks REPLICATED — a
#: request's blocks must not scatter over the batch axes. Pinned to the
#: logical-rules derivation by
#: tests/test_serve.py::test_paged_cache_specs_match_rules_table.
PAGED_KV_CACHE_RULES = sharding.partition_rules(
    "serve-paged-kv-cache",
    ((r"^(k|v)$", P(None, None, mesh_lib.MODEL, None, None)),),
    coverage=("k", "v"),
)


def stored_head_dim(head_dim: int) -> int:
    """The width a head's K and V rows are stored at in the pool: on the
    TPU, ``head_dim`` rounded up to the 128 lanes (GPT-2's heads of 64 are
    stored as 128, zeros in the padding). The Pallas kernels read and write
    a block ``[heads, block_size, head_dim]`` of a row-major pool, and that
    is where such a head takes its 128 lanes anyway. Left at 64, XLA lays
    the array out with the BLOCK axis last (a last dimension under the lane
    width buys it no padding there), and a program that calls the kernels
    then copies both pools into their layout and back, every run: the copy
    the in-place write exists to avoid. (The layout can also be asked for,
    ``jax.experimental.layout``; jax 0.9.0's persistent compile cache
    hands such a program back expecting the default one.) Elsewhere: as it
    is. ``ops.attention.paged_layer_attention`` pads what it writes and
    drops the padding of what it reads."""
    lanes = 128
    if jax.default_backend() != "tpu":
        return head_dim
    return -(-head_dim // lanes) * lanes


def init_paged_cache(
    cfg: TransformerConfig,
    num_blocks: int,
    block_size: int,
    dtype: str | jnp.dtype | None = None,
) -> PagedKVCache:
    """Zero-filled block pool for ``cfg``. Unlike the dense cache there
    is no per-slot ``max_len`` row: capacity is simply
    ``num_blocks * block_size`` tokens shared by every resident
    request. One physical block more is allocated: the write-off block
    (``PagedKVCache``); heads are as wide as ``stored_head_dim`` says."""
    if num_blocks < 1:
        raise ValueError("num_blocks must be >= 1")
    if block_size < 1:
        raise ValueError("block_size must be >= 1")
    dt = jnp.dtype(cfg.dtype if dtype is None else dtype)
    shape = (cfg.num_layers, num_blocks + 1, cfg.num_heads, block_size,
             stored_head_dim(cfg.head_dim))
    return PagedKVCache(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt))


def paged_cache_specs(
    rules: sharding.LogicalRules | None = None,
) -> PagedKVCache:
    """PartitionSpec pytree for the block pool (heads → ``model``,
    blocks replicated) — PAGED_KV_CACHE_RULES by default, explicit
    logical ``rules`` as the escape hatch."""
    if rules is None:
        return sharding.match_partition_rules(
            PAGED_KV_CACHE_RULES, PagedKVCache(k=0, v=0)
        )
    spec = sharding.spec_from_logical(PAGED_CACHE_LOGICAL, rules)
    return PagedKVCache(k=spec, v=spec)


def shard_paged_cache(
    cache: PagedKVCache, mesh, rules: sharding.LogicalRules | None = None
) -> PagedKVCache:
    """Place the pool on a mesh per ``paged_cache_specs``."""
    return sharding.shard_tree(cache, mesh, paged_cache_specs(rules))


# ---------------------------------------------------------------------------
# Hybrid cache: recurrent state beside the paged pool (docs/serving.md
# "Hybrid cache")
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HybridCache:
    """The serving state of a decoder that mixes full-attention and
    linear-attention (gated delta rule) layers.

    - ``k``/``v``: [full layers, num_blocks + 1, heads, block_size,
      head_dim], the paged pool as ``PagedKVCache`` keeps it (write-off
      block last), for the full layers ONLY; the block table, the
      allocator and the prefix cache are the paged engine's.
    - ``state``: [linear layers, slots, H, dk, dv] float32, each resident
      request's recurrent state; ``conv``: [linear layers, slots, taps - 1,
      channels] float32, the last inputs of the short convolution. A slot's
      rows are its request's: the first prefill chunk of a request starts
      them from nought, a decode step leaves the rows of slots that do not
      decode as they were.
    - ``snap_state``/``snap_conv``: the same with snapshot rows in place of
      slots: copies of a slot's rows taken at a block-aligned chunk end,
      which a later request with the same prefix starts from
      (``SnapshotTable`` keeps which row holds which prefix)."""

    k: jax.Array
    v: jax.Array
    state: jax.Array
    conv: jax.Array
    snap_state: jax.Array
    snap_conv: jax.Array

    POOLS = ("k", "v")

    @property
    def num_blocks(self) -> int:
        """Blocks a table can name (the allocator's count, and the table's
        sentinel): the pool's, less the write-off block."""
        return self.k.shape[1] - 1

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    def nbytes(self) -> int:
        return sum(x.nbytes for x in jax.tree.leaves(self))


jax.tree_util.register_dataclass(
    HybridCache,
    data_fields=["k", "v", "state", "conv", "snap_state", "snap_conv"],
    meta_fields=[],
)

#: Partition-rules table of the hybrid cache: heads over ``model`` in the
#: pool and in the recurrent state, as the weights that produce them; the
#: convolution's channels follow their heads; blocks, slots and snapshot
#: rows replicated.
HYBRID_CACHE_RULES = sharding.partition_rules(
    "serve-hybrid-cache",
    ((r"^(k|v)$", P(None, None, mesh_lib.MODEL, None, None)),
     (r"^(snap_)?state$", P(None, None, mesh_lib.MODEL, None, None)),
     (r"^(snap_)?conv$", P(None, None, None, mesh_lib.MODEL))),
    coverage=("k", "v", "state", "conv", "snap_state", "snap_conv"),
)


def init_hybrid_cache(cfg, num_slots: int, num_blocks: int, block_size: int,
                      num_snapshots: int,
                      dtype: str | jnp.dtype = jnp.bfloat16) -> HybridCache:
    """Zero-filled hybrid cache for a ``models.olmo_hybrid.OlmoHybridConfig``;
    ``dtype`` is the pool's, the recurrent state is float32."""
    if min(num_blocks, block_size, num_slots) < 1 or num_snapshots < 0:
        raise ValueError("num_blocks, block_size and num_slots must be >= 1, "
                         "num_snapshots >= 0")
    n_full, n_lin = (cfg.count("full_attention"),
                     cfg.count("linear_attention"))
    pool = (n_full, num_blocks + 1, cfg.num_heads, block_size, cfg.head_dim)
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    taps = cfg.conv_kernel - 1
    f32 = jnp.float32
    return HybridCache(
        k=jnp.zeros(pool, dtype), v=jnp.zeros(pool, dtype),
        state=jnp.zeros((n_lin, num_slots, H, dk, dv), f32),
        conv=jnp.zeros((n_lin, num_slots, taps, cfg.conv_channels), f32),
        snap_state=jnp.zeros((n_lin, num_snapshots, H, dk, dv), f32),
        snap_conv=jnp.zeros((n_lin, num_snapshots, taps, cfg.conv_channels),
                            f32))


@dataclasses.dataclass
class LatentCache:
    """The serving state of a decoder with latent-attention layers beside
    linear-attention layers and an expert layer
    (``models.gigachat3_5.GigaChat35``).

    - ``kv``: [latent layers, num_blocks + 1, 1, block_size, W], one row a
      cached token, ``[c_kv || k_r]`` shared by every head
      (``ops.latent_attention``; W the row's lanes, on the TPU rounded up
      to 128), write-off block last, under the paged engine's block
      tables, allocator and prefix cache.
    - ``state``/``conv``/``snap_state``/``snap_conv``: as ``HybridCache``
      keeps them, for the linear layers.
    - ``moe_counts``: [2, held experts] int32, running totals over every
      expert layer and step: the local assignments each held expert
      received, and the calls in which it received any. The engine reads
      the change at each fetch it makes (wrapping at 2**32)."""

    kv: jax.Array
    state: jax.Array
    conv: jax.Array
    snap_state: jax.Array
    snap_conv: jax.Array
    moe_counts: jax.Array

    POOLS = ("kv",)

    @property
    def num_blocks(self) -> int:
        return self.kv.shape[1] - 1

    @property
    def block_size(self) -> int:
        return self.kv.shape[3]


jax.tree_util.register_dataclass(
    LatentCache,
    data_fields=["kv", "state", "conv", "snap_state", "snap_conv",
                 "moe_counts"],
    meta_fields=[],
)


def init_latent_cache(cfg, num_slots: int, num_blocks: int, block_size: int,
                      num_snapshots: int,
                      dtype: str | jnp.dtype = jnp.bfloat16) -> LatentCache:
    """Zero-filled latent cache for a ``models.gigachat3_5.GigaChat35Config``;
    ``dtype`` is the latent pool's, the recurrent state is float32."""
    if min(num_blocks, block_size, num_slots) < 1 or num_snapshots < 0:
        raise ValueError("num_blocks, block_size and num_slots must be >= 1, "
                         "num_snapshots >= 0")
    n_lin = cfg.count("linear_attention")
    pool = (cfg.count("full_attention"), num_blocks + 1, 1, block_size,
            stored_width(cfg.latent_width))
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    window = (cfg.conv_kernel - 1, cfg.conv_channels)
    f32 = jnp.float32
    return LatentCache(
        kv=jnp.zeros(pool, dtype),
        state=jnp.zeros((n_lin, num_slots, H, dk, dv), f32),
        conv=jnp.zeros((n_lin, num_slots, *window), f32),
        snap_state=jnp.zeros((n_lin, num_snapshots, H, dk, dv), f32),
        snap_conv=jnp.zeros((n_lin, num_snapshots, *window), f32),
        moe_counts=jnp.zeros((2, cfg.experts_held), jnp.int32))


class SnapshotTable:
    """Which snapshot row holds the recurrent state after which token
    prefix: host-side, jax-free, least recently used first out.

    Keys are the allocator's own: the token prefix up to a block boundary,
    under which ``BlockAllocator`` caches the block that ends there. A
    snapshot is of use only while that block is cached (a match has to
    reach it), so the table hangs on the allocator's ``on_uncache`` hook: a
    snapshot dies with its block, and ``flush_prefix_cache()`` leaves
    none."""

    def __init__(self, num_rows: int, alloc: BlockAllocator):
        self.num_rows = num_rows
        self.block_size = alloc.block_size
        #: prefix -> row; insertion order is the LRU order (hits re-insert)
        self._rows: dict[tuple[int, ...], int] = {}
        self._free = list(range(num_rows - 1, -1, -1))
        self.taken = self.hits = self.evictions = 0
        alloc.on_uncache = self.drop

    def __len__(self) -> int:
        return len(self._rows)

    def __contains__(self, prefix) -> bool:
        return tuple(prefix) in self._rows

    def longest(self, tokens: tuple[int, ...], limit: int,
                touch: bool = True) -> tuple[int, int | None]:
        """The longest block-aligned prefix of ``tokens``, at most ``limit``
        tokens, that has a snapshot: (its length, its row), or (0, None).
        ``touch`` counts it a hit and makes it the most recently used."""
        bs = self.block_size
        for n in range(limit // bs * bs, 0, -bs):
            key = tuple(tokens[:n])
            row = self._rows.get(key)
            if row is not None:
                if touch:
                    self._rows[key] = self._rows.pop(key)
                    self.hits += 1
                return n, row
        return 0, None

    def take(self, prefix: tuple[int, ...]) -> int | None:
        """A row for a snapshot after ``prefix`` (the caller copies the
        state into it): a free one, else the least recently used one's.
        None where the table has no rows or the prefix has one already."""
        prefix = tuple(prefix)
        if not self.num_rows or prefix in self._rows:
            return None
        if not self._free:
            oldest = next(iter(self._rows))
            self._free.append(self._rows.pop(oldest))
            self.evictions += 1
        row = self._free.pop()
        self._rows[prefix] = row
        self.taken += 1
        return row

    def drop(self, prefix: tuple[int, ...]) -> None:
        """The block that ends ``prefix`` left the prefix cache."""
        row = self._rows.pop(tuple(prefix), None)
        if row is not None:
            self._free.append(row)
            self.evictions += 1


class NoFreeBlocks(RuntimeError):
    """The pool is exhausted and nothing is evictable — the engine's
    cue to preempt a resident request (backpressure, not corruption)."""


class BlockAllocator:
    """Host-side free/used accounting for the block pool — plain
    Python, jax-free, so every invariant (used + free == pool size,
    refcounts hit zero, no leaked blocks) is testable with no device.

    Three kinds of ownership, all through one refcount array:

    - a resident request holds one ref on every block in its table;
    - the **prefix cache** holds one ref on each registered full block
      (``register_prefix``), so a popular system-prompt prefix survives
      the request that wrote it; entries are LRU-evicted when ``alloc``
      finds the free list empty (``evictions`` counts them);
    - **partially filled tail blocks** are registered weakly (no ref,
      validated by a per-block generation counter), so an identical
      prompt can map the same tail block — the copy-on-write case: the
      first APPEND into a block with refcount > 1 must copy it
      (``ensure `` via the engine's COW path), because the writer and
      the sharers diverge at that position.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1:
            raise ValueError("num_blocks must be >= 1")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # pop() hands out 0, 1, 2, ... — deterministic block placement
        self._free = list(range(num_blocks - 1, -1, -1))
        self._ref = [0] * num_blocks
        #: bumped on every alloc — stale weak (partial) registrations
        #: carry the generation they were made under and are pruned lazily
        self._gen = [0] * num_blocks
        #: full-block prefix cache: token prefix (length k*block_size,
        #: as a tuple) → physical block id of block k-1. Insertion order
        #: doubles as LRU (move_to_end on hit).
        self._prefix: dict[tuple[int, ...], int] = {}
        #: weak partial-tail registrations: full-block prefix → list of
        #: (tail_content, block_id, generation)
        self._partial: dict[tuple[int, ...],
                            list[tuple[tuple[int, ...], int, int]]] = {}
        #: prefix-cache blocks evicted under pressure (feeds the
        #: kv_block_evictions_total counter)
        self.evictions = 0
        #: copy-on-write block copies performed (engine bumps this when
        #: it resolves a shared-block write)
        self.cow_copies = 0
        #: called with the token prefix of every full-block entry that
        #: leaves the prefix cache (eviction, ``release_cached``, flush):
        #: what is keyed by the same prefixes (state snapshots) dies with it
        self.on_uncache = None

    def _uncached(self, key: tuple[int, ...]) -> None:
        if self.on_uncache is not None:
            self.on_uncache(key)

    def is_cached(self, prefix: tuple[int, ...]) -> bool:
        """Whether the full block that ends token prefix ``prefix`` is in
        the prefix cache."""
        return prefix in self._prefix

    # -- accounting --------------------------------------------------------

    @property
    def blocks_free(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        return self.num_blocks - len(self._free)

    def refcount(self, bid: int) -> int:
        return self._ref[bid]

    def evictable(self) -> int:
        """Prefix-cache blocks held ONLY by the cache (refcount 1) —
        freeable on demand, so admission may count them as capacity."""
        return sum(1 for bid in self._prefix.values()
                   if self._ref[bid] == 1)

    # -- alloc / free ------------------------------------------------------

    def alloc(self) -> int:
        """Hand out a free block (refcount 1). When the free list is
        empty, evict least-recently-used prefix-cache entries whose
        block nothing else holds; raises ``NoFreeBlocks`` when even
        that finds nothing."""
        if not self._free:
            self._evict_cached()
        if not self._free:
            raise NoFreeBlocks(
                f"all {self.num_blocks} KV blocks are referenced and no "
                f"prefix-cache entry is evictable"
            )
        bid = self._free.pop()
        self._ref[bid] = 1
        self._gen[bid] += 1
        return bid

    def incref(self, bid: int) -> None:
        if self._ref[bid] < 1:
            raise ValueError(f"incref on free block {bid}")
        self._ref[bid] += 1

    def decref(self, bid: int) -> bool:
        """Drop one reference; returns True when the block was freed."""
        if self._ref[bid] < 1:
            raise ValueError(f"decref on free block {bid}")
        self._ref[bid] -= 1
        if self._ref[bid] == 0:
            self._free.append(bid)
            return True
        return False

    def release_tail(self, blocks: list[int], keep: int) -> None:
        """Speculation rollback: drop ownership of every block past the
        first ``keep`` — a refcount/length edit, never a data copy. Pops
        ``blocks`` in place so the caller's per-slot block list stays
        the single source of truth; decref's double-free tripwire still
        guards each drop (a rejected suffix must not free a block the
        prefix cache or another slot co-owns more times than this slot
        held it)."""
        if keep < 0:
            raise ValueError(f"keep must be >= 0, got {keep}")
        while len(blocks) > keep:
            self.decref(blocks.pop())

    def _evict_cached(self) -> None:
        """LRU-evict prefix-cache entries whose block only the cache
        holds, until one block is actually freed."""
        for key in list(self._prefix):
            bid = self._prefix[key]
            if self._ref[bid] == 1:
                del self._prefix[key]
                self._uncached(key)
                self.evictions += 1
                if self.decref(bid):
                    return

    # -- prefix reuse ------------------------------------------------------

    def match_prefix(
        self, tokens: tuple[int, ...] | list[int]
    ) -> tuple[list[int], int]:
        """Longest reusable prefix of ``tokens``: full cached blocks
        first, then optionally one weakly-registered partial tail
        block. Returns ``(block_ids, matched_tokens)`` with one ref
        taken on every returned block (the caller now co-owns them)."""
        tokens = tuple(int(t) for t in tokens)
        bs = self.block_size
        blocks: list[int] = []
        matched = 0
        while matched + bs <= len(tokens):
            key = tokens[: matched + bs]
            bid = self._prefix.get(key)
            if bid is None:
                break
            self._prefix[key] = self._prefix.pop(key)  # LRU touch
            self.incref(bid)
            blocks.append(bid)
            matched += bs
        # partial tail: a registered block whose content agrees with the
        # remaining tokens on their common prefix
        tail = tokens[matched:]
        if tail:
            hit = self._lookup_partial(tokens[:matched], tail)
            if hit is not None:
                bid, common = hit
                self.incref(bid)
                blocks.append(bid)
                matched += common
        return blocks, matched

    def peek_match(self, tokens: tuple[int, ...] | list[int]) -> int:
        """``match_prefix`` without taking refs — how many FULL blocks
        admission could reuse (the gate's conservative estimate)."""
        tokens = tuple(int(t) for t in tokens)
        bs, n = self.block_size, 0
        while (n + 1) * bs <= len(tokens) \
                and tokens[: (n + 1) * bs] in self._prefix:
            n += 1
        return n

    def _lookup_partial(
        self, full_prefix: tuple[int, ...], tail: tuple[int, ...]
    ) -> tuple[int, int] | None:
        cands = self._partial.get(full_prefix)
        if not cands:
            return None
        live = []
        for content, bid, gen in cands:
            if self._ref[bid] < 1 or self._gen[bid] != gen:
                continue  # block was freed/reallocated: stale entry
            live.append((content, bid, gen))
        if len(live) != len(cands):
            if live:
                self._partial[full_prefix] = live
            else:
                del self._partial[full_prefix]
        best: tuple[int, int] | None = None
        for content, bid, _gen in live:
            common = 0
            for a, b in zip(content, tail):
                if a != b:
                    break
                common += 1
            if common > 0 and (best is None or common > best[1]):
                best = (bid, common)
        return best

    def register_prefix(
        self, tokens: tuple[int, ...] | list[int], blocks: list[int]
    ) -> None:
        """Publish a prefilled prompt's blocks for reuse: each FULL
        block enters the prefix cache (one cache ref, survives the
        request), a partially filled tail block is registered weakly
        (valid only while the block lives). Re-registering content that
        is already cached is a no-op — no double refs."""
        tokens = tuple(int(t) for t in tokens)
        bs = self.block_size
        n_full = len(tokens) // bs
        for j in range(min(n_full, len(blocks))):
            key = tokens[: (j + 1) * bs]
            if key in self._prefix:
                continue
            bid = blocks[j]
            self.incref(bid)
            self._prefix[key] = bid
        tail = tokens[n_full * bs:]
        if tail and len(blocks) > n_full:
            bid = blocks[n_full]
            key = tokens[: n_full * bs]
            entry = (tail, bid, self._gen[bid])
            cands = self._partial.setdefault(key, [])
            if entry not in cands:
                cands.append(entry)
            # weak entries are pruned lazily on lookup, which never
            # happens for prompts no one repeats — sweep when the map
            # outgrows the pool so host memory stays bounded
            if sum(len(c) for c in self._partial.values()) \
                    > max(64, 2 * self.num_blocks):
                self._prune_partials()

    def _prune_partials(self) -> None:
        """Drop every stale weak entry (block freed or reallocated)."""
        for key in list(self._partial):
            live = [(c, bid, gen) for c, bid, gen in self._partial[key]
                    if self._ref[bid] >= 1 and self._gen[bid] == gen]
            if live:
                self._partial[key] = live
            else:
                del self._partial[key]

    def note_write(self, bid: int, offset: int) -> None:
        """The sole owner is about to write block ``bid`` in place from
        ``offset`` on: weak partial entries claiming content AT or past
        that offset would describe overwritten K/V — drop them. (An
        append past an entry's registered fill leaves it valid; a COW
        writer gets a fresh block and never invalidates the original.)
        The engine calls this for every block a prefill chunk or decode
        write touches, so the weak registry can never serve stale
        content even if the engine's COW ordering ever changes. Cost:
        nothing when the registry is empty (reuse off, or no partial
        prompts), else one scan of a map the register-time sweep keeps
        bounded at ``max(64, 2 * num_blocks)`` entries."""
        if not self._partial:
            return
        for key in list(self._partial):
            kept = [(c, b, g) for c, b, g in self._partial[key]
                    if not (b == bid and len(c) > offset)]
            if kept:
                self._partial[key] = kept
            else:
                del self._partial[key]

    def release_cached(self, bid: int) -> bool:
        """Drop every prefix-cache ref on ``bid`` (full-block entries;
        weak partial entries hold no ref and die by generation).
        Returns True when an entry was removed. The engine's last
        resort when a copy-on-write target cannot be allocated: if the
        only other holder of a block is the cache itself, un-caching it
        makes the writer sole owner, who then writes in place — no copy
        needed."""
        removed = False
        for key in [k for k, b in self._prefix.items() if b == bid]:
            del self._prefix[key]
            self._uncached(key)
            self.evictions += 1
            self.decref(bid)
            removed = True
        return removed

    def flush_prefix_cache(self) -> int:
        """Drop every cached prefix ref (shutdown / leak audits):
        afterwards only resident requests hold blocks. Returns the
        number of blocks freed outright."""
        freed = 0
        for key, bid in self._prefix.items():
            freed += bool(self.decref(bid))
            self._uncached(key)
        self._prefix.clear()
        self._partial.clear()
        return freed
