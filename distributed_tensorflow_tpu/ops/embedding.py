"""Sharded embedding lookup — the TPU-native embedding-parallel data plane.

Reference analog (SURVEY.md §2c 'Embedding parallel'): the Wide&Deep config
(BASELINE.json:11) kept embedding tables as sparse variables on parameter
servers; workers issued sparse gather RPCs and pushed `IndexedSlices`
gradients back through `SparseConditionalAccumulator`
($TF/python/ops/data_flow_ops.py:1478, sync path
sync_replicas_optimizer.py:286-291). The substrate's TPU answer is
`TPUEmbedding` ($TF/python/tpu/tpu_embedding_v2.py:76) backed by native
sparse cores.

TPU-native design here: tables are **mod-sharded over the ``model`` mesh
axis** (row r lives on shard ``r % n`` — mod, not contiguous range, so hot
ids spread across shards), and the lookup exchange is explicit collectives
under ``shard_map``:

- ``mod_sharded_lookup`` — ids replicated across the axis (the usual case:
  batch is sharded over data/fsdp, tables over model). Each shard gathers
  the rows it owns, zero-fills the rest, and one ``psum`` assembles full
  embeddings. The backward pass is the transpose — scatter-add into the
  local shard — which is exactly the PS sparse-gradient push, minus the RPC.
- ``batch_sharded_lookup`` — ids *sharded* over the same axis (embedding-
  parallel recommenders where the batch rides the model axis). Ids are
  all-gathered, contributions computed locally, and a ``reduce_scatter``
  returns each device only its batch slice — the same wire bytes as the
  all_to_all exchange of TPUEmbedding, with static shapes XLA can schedule.

Both are pure jnp + lax collectives: differentiable (JAX transposes
gather→scatter-add and psum→identity automatically), jittable, and
mesh-agnostic (axis size 1 degrades to a plain take).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel import mesh as mesh_lib
from ..parallel import sharding as sharding_lib


def shard_vocab(vocab_size: int, n_shards: int) -> int:
    """Rows per shard: tables are padded so every shard holds the same
    count (static shapes — SPMD programs must be shape-identical)."""
    return -(-vocab_size // n_shards)


def local_rows(table: jax.Array, shard: jax.Array, n_shards: int) -> jax.Array:
    """The mod-shard view of a replicated [V, D] table: rows
    ``shard, shard + n, shard + 2n, …`` padded to shard_vocab rows.
    Test/oracle helper; in training the table is born sharded."""
    v, d = table.shape
    rows = shard_vocab(v, n_shards)
    idx = shard + n_shards * jnp.arange(rows)
    return jnp.where(
        (idx < v)[:, None], jnp.take(table, jnp.minimum(idx, v - 1), axis=0), 0.0
    )


def _owned_lookup(ids: jax.Array, local_table: jax.Array, shard, n: int):
    """Gather rows this shard owns; zeros elsewhere. ids: any int shape."""
    owner = ids % n
    row = ids // n
    mine = (owner == shard)[..., None]
    safe = jnp.minimum(row, local_table.shape[0] - 1)
    return jnp.where(mine, jnp.take(local_table, safe, axis=0), 0.0)


def mod_sharded_lookup(
    ids: jax.Array,
    local_table: jax.Array,
    axis: str = mesh_lib.MODEL,
) -> jax.Array:
    """Inside ``shard_map``: full [*, D] embeddings from a mod-sharded table.

    ids are replicated over ``axis``; ``local_table`` is this device's
    [ceil(V/n), D] shard. One psum over ``axis`` replaces the reference's
    PS gather round-trip (§3.1: variable read = gRPC hop per step).
    """
    n = jax.lax.axis_size(axis)
    part = _owned_lookup(ids, local_table, lax.axis_index(axis), n)
    return lax.psum(part, axis)


def range_sharded_lookup(
    ids: jax.Array,
    local_table: jax.Array,
    axis: str = mesh_lib.MODEL,
) -> jax.Array:
    """Inside ``shard_map``: like ``mod_sharded_lookup`` but for
    *range*-sharded tables — shard s owns ids [s·rows, (s+1)·rows), which is
    exactly the layout GSPMD gives a param annotated P(axis, None). Lets a
    plain flax table param feed the explicit exchange with zero re-layout."""
    rows = local_table.shape[0]
    shard = lax.axis_index(axis)
    owner = ids // rows
    row = ids % rows
    mine = (owner == shard)[..., None]
    part = jnp.where(mine, jnp.take(local_table, row, axis=0), 0.0)
    return lax.psum(part, axis)


def batch_sharded_lookup(
    ids: jax.Array,
    local_table: jax.Array,
    axis: str = mesh_lib.MODEL,
) -> jax.Array:
    """Inside ``shard_map``: lookup where the *batch* (dim 0 of ids) is also
    sharded over ``axis``. all_gather ids → local contributions →
    reduce_scatter back to the caller's batch slice. Wire-equivalent to the
    TPUEmbedding all_to_all exchange, static-shaped."""
    n = jax.lax.axis_size(axis)
    all_ids = lax.all_gather(ids, axis, axis=0, tiled=True)
    part = _owned_lookup(all_ids, local_table, lax.axis_index(axis), n)
    return lax.psum_scatter(part, axis, scatter_dimension=0, tiled=True)


def make_sharded_lookup(mesh: Mesh, axis: str = mesh_lib.MODEL):
    """jit-ready f(ids, table_shards) -> embeddings over ``mesh``.

    ``table_shards`` is the [n * ceil(V/n), D] global array whose dim 0 is
    sharded over ``axis`` (shard i holds rows it owns under mod-sharding,
    i.e. the array is the concatenation of ``local_rows`` views). Batch dims
    of ``ids`` ride (data, fsdp) as usual.
    """
    bspec = P(mesh_lib.BATCH_AXES)
    out_spec = P(mesh_lib.BATCH_AXES, None)

    def fn(ids, table_shards):
        return jax.shard_map(
            lambda i, t: mod_sharded_lookup(i, t, axis),
            mesh=mesh,
            in_specs=(bspec, P(axis, None)),
            out_specs=out_spec,
            check_vma=False,
        )(ids, table_shards)

    return fn


def make_range_sharded_lookup(mesh: Mesh, axis: str = mesh_lib.MODEL):
    """jit-ready f(ids, table) for a plain [V, D] table laid out
    P(axis, None) — the GSPMD-layout twin of ``make_sharded_lookup``. Owns
    the pad-to-divisible step so callers hand in the raw param."""
    bspec = P(mesh_lib.BATCH_AXES)
    out_spec = P(mesh_lib.BATCH_AXES, None)

    def fn(ids, table):
        n = mesh.shape[axis]
        rows = shard_vocab(table.shape[0], n)
        padded = jnp.pad(table, ((0, n * rows - table.shape[0]), (0, 0)))
        return jax.shard_map(
            lambda i, t: range_sharded_lookup(i, t, axis),
            mesh=mesh,
            in_specs=(bspec, P(axis, None)),
            out_specs=out_spec,
            check_vma=False,
        )(ids, padded)

    return fn


def to_mod_sharded(table: jax.Array, mesh: Mesh, axis: str = mesh_lib.MODEL):
    """Re-layout a replicated [V, D] table into the mod-sharded global array
    expected by ``make_sharded_lookup`` (dim 0 = n shards × rows-per-shard),
    placed with dim 0 over ``axis`` (through the sharding seam — no
    ad-hoc NamedSharding here)."""
    n = mesh.shape[axis]
    shards = [local_rows(table, s, n) for s in range(n)]
    global_ = jnp.concatenate(shards, axis=0)
    return sharding_lib.shard_leading_dim(global_, mesh, axis)
