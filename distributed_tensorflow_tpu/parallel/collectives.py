"""Collective verbs over named mesh axes — the framework's data plane.

The reference had three transports (SURVEY.md §2d): gRPC rendezvous for every
PS↔worker variable read / gradient push, NCCL ring allreduce intra-host
($TF/python/ops/nccl_ops.py:208), and the RING/NCCL collective executor for
multi-worker ($TF/python/ops/collective_ops.py:19). On TPU there is no
user-space transport to write: XLA compiles these primitives directly onto
ICI (intra-slice torus) and bridges DCN between slices. What the framework
owns is the *vocabulary* — the same five verbs the reference got from
NCCL+gRPC (allreduce, allgather, reducescatter, broadcast, barrier), plus the
two that long-context/MoE parallelism needs (all_to_all, ring permute),
expressed over named mesh axes.

All functions here must run inside a collective context: ``shard_map`` over a
mesh (the explicit path — pipeline, ring attention, embedding exchange) or
``vmap``/``pmap`` with a named axis. Under plain ``jit`` + NamedSharding,
GSPMD inserts the equivalents automatically and user code never calls these.

``groups``: optional list of index-groups restricting the collective to
subgroups of the axis — the TPU-native descendant of the reference's NCCL
communicator subgroups and of ``group_assignment`` on CrossReplicaSum
($TF/python/tpu/ops/tpu_ops.py:32-40).
"""

from __future__ import annotations

import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

AxisNames = str | tuple[str, ...]
Groups = Sequence[Sequence[int]] | None

# The emulated ``groups=`` path below costs the FULL axis in wire traffic
# (all_gather then mask) regardless of group size. Fine for the small
# ad-hoc meshes it exists for; a silent O(axis) collective on a pod axis
# would be a production footgun, so past this axis
# size it is an error — structural subgroups belong on
# ``mesh.factor_mesh_axis`` (true subgroup collectives, HLO-asserted).
EMULATED_GROUP_AXIS_LIMIT = 8


def _check_emulated_groups(axis: str, groups, verb: str) -> None:
    n = axis_size(axis)
    if n > EMULATED_GROUP_AXIS_LIMIT:
        raise ValueError(
            f"{verb}(groups=...) over axis {axis!r} of size {n}: the "
            f"emulated grouped path gathers the FULL axis (O(axis) wire "
            f"for O(group) semantics) and is capped at axis size "
            f"{EMULATED_GROUP_AXIS_LIMIT}. For structural (contiguous) "
            f"subgroups, split the axis with mesh.factor_mesh_axis and "
            f"run the collective on one sub-axis — XLA then emits a true "
            f"subgroup collective."
        )
    warnings.warn(
        f"{verb}(groups=...) is emulated: O(axis={n}) wire traffic for "
        f"O(group={len(groups[0])}) semantics; prefer "
        f"mesh.factor_mesh_axis for structural subgroups",
        stacklevel=3,
    )


def _group_mask(axis: str, groups) -> jax.Array:
    """(N,) one-hot-per-group membership mask for this device's group.

    ``shard_map`` does not lower ``axis_index_groups`` (JAX 0.9), so grouped
    collectives are emulated: gather the full axis, then reduce the members
    of this device's group — O(axis) wire traffic for O(group) semantics.
    Use this ONLY for ad-hoc/irregular groups. When a subgroup pattern is
    *structural* (contiguous blocks — per-slice reductions, per-replica
    shards), use ``mesh.factor_mesh_axis`` to split the axis into named
    sub-axes and run the collective on one sub-axis: XLA then emits a true
    subgroup collective with no full-axis gather (asserted in
    tests/test_collectives.py::test_factored_axis_avoids_full_gather).
    That is the idiomatic TPU-native form of the reference's NCCL
    communicator subgroups / CrossReplicaSum ``group_assignment``
    ($TF tpu_ops.py:32-40)."""
    n = axis_size(axis)
    groups_arr = jnp.asarray(groups)  # (G, M), a partition of range(n)
    g = groups_arr.shape[0]
    membership = jnp.zeros((g, n), jnp.float32)  # membership[g, i] = i in group g
    membership = membership.at[
        jnp.arange(g)[:, None], groups_arr
    ].set(1.0)
    mine = membership[:, lax.axis_index(axis)]  # (G,) one-hot: my group
    return mine @ membership  # (N,) members of my group


def all_reduce(x, axis: AxisNames, groups: Groups = None):
    """Sum across the axis. Replaces: the whole SyncReplicasOptimizer
    accumulator+token protocol (494 LoC of Python over C++ queue kernels,
    SURVEY.md §3.1) and NCCL all_sum — one compiled op, inherently
    synchronous, no staleness by construction."""
    if groups is None:
        return lax.psum(x, axis)
    _check_emulated_groups(axis, groups, "all_reduce")
    return _emulated_group_reduce(x, axis, groups)


def _emulated_group_reduce(x, axis: AxisNames, groups):
    mask = _group_mask(axis, groups)
    gathered = lax.all_gather(x, axis, axis=0)  # (N, *x.shape)
    return jnp.tensordot(mask, gathered.astype(jnp.float32), axes=1).astype(x.dtype)


def all_reduce_mean(x, axis: AxisNames, groups: Groups = None):
    """Mean across the axis — gradient aggregation semantics
    (SyncReplicasOptimizer averaged; take_grad / N, SURVEY.md §3.1)."""
    if groups is None:
        return lax.pmean(x, axis)
    size = len(groups[0])
    return all_reduce(x, axis, groups=groups) / size


def all_gather(x, axis: AxisNames, *, tiled_axis: int = 0, groups: Groups = None):
    """Concatenate shards along ``tiled_axis``. NCCL all_gather analog."""
    if groups is None:
        return lax.all_gather(x, axis, axis=tiled_axis, tiled=True)
    _check_emulated_groups(axis, groups, "all_gather")
    # Emulated grouped gather: full gather, then select my group's members.
    gathered = lax.all_gather(x, axis, axis=0)  # (N, *x.shape)
    mask = _group_mask(axis, groups)  # (N,)
    m = len(groups[0])
    members = jnp.sort(jnp.argsort(-mask, stable=True)[:m])  # my group's ids, ascending
    mine = jnp.take(gathered, members, axis=0)  # (M, *x.shape)
    return _tile(mine, tiled_axis)


def _tile(stacked: jax.Array, tiled_axis: int) -> jax.Array:
    """(M, *shape) → concat along tiled_axis."""
    m = stacked.shape[0]
    moved = jnp.moveaxis(stacked, 0, tiled_axis)  # (..., M, dim, ...)
    shape = list(stacked.shape[1:])
    shape[tiled_axis] *= m
    return moved.reshape(shape)


def reduce_scatter(x, axis: AxisNames, *, scatter_axis: int = 0, groups: Groups = None):
    """Sum then keep this device's shard of ``scatter_axis``. The building
    block of ZeRO-style weight-update sharding (arXiv:2004.13336): grads are
    reduce-scattered over fsdp, each device updates its slice, params are
    all-gathered back."""
    if groups is None:
        return lax.psum_scatter(
            x, axis, scatter_dimension=scatter_axis, tiled=True
        )
    _check_emulated_groups(axis, groups, "reduce_scatter")
    reduced = _emulated_group_reduce(x, axis, groups)
    # my chunk = position within my group row along scatter_axis
    groups_arr = jnp.asarray(groups)
    idx = lax.axis_index(axis)
    pos = jnp.argmax(jnp.any(groups_arr == idx, axis=0))
    m = len(groups[0])
    chunk = x.shape[scatter_axis] // m
    return lax.dynamic_slice_in_dim(reduced, pos * chunk, chunk, scatter_axis)


def broadcast(x, axis: AxisNames, *, src: int = 0):
    """Every device gets ``src``'s value. The reference's analog was implicit:
    workers *read* variables from the PS shard over gRPC each step."""
    # Select src's contribution and sum: avoids materializing a full gather.
    idx = lax.axis_index(axis)
    contrib = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis)


def barrier(axis: AxisNames) -> jax.Array:
    """Device-level barrier: a trivial psum every participant must reach.
    Replaces the FIFOQueue token barrier ($TF data_flow_ops.py:774, used at
    sync_replicas_optimizer.py:303-322). Returns the axis size; consume it
    (e.g. via jax.block_until_ready) to enforce ordering."""
    return lax.psum(jnp.ones((), jnp.int32), axis)


def all_to_all(
    x,
    axis: AxisNames,
    *,
    split_axis: int,
    concat_axis: int,
    groups: Groups = None,
):
    """Transpose sharding between two tensor dimensions across the axis —
    the primitive under Ulysses sequence parallelism and MoE token dispatch
    (SURVEY.md §2c; $TF analog tpu_ops.py:43)."""
    return lax.all_to_all(
        x, axis, split_axis=split_axis, concat_axis=concat_axis,
        tiled=True, axis_index_groups=groups,
    )


def ring_permute(x, axis: str, *, shift: int = 1):
    """Rotate shards around the axis ring (device i → i+shift mod N): the
    K/V-block rotation of ring attention (SURVEY.md §5.7). ICI's torus makes
    each hop a single physical link."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm=perm)


def axis_index(axis: AxisNames):
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    return lax.axis_size(axis)
