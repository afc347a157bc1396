"""Sharded-embedding lookup vs dense-take oracle (SURVEY.md §4.4 pattern:
k-shard result == unsharded result on the same data)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.ops import embedding as emb
from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
from distributed_tensorflow_tpu.parallel import mesh as mesh_lib

V, D = 96, 16


@pytest.fixture()
def mesh_tp4(devices):
    return build_mesh(MeshSpec(data=2, model=4), devices[:8])


def _table_and_ids(seed=0, n_ids=32):
    rng = np.random.RandomState(seed)
    table = jnp.asarray(rng.randn(V, D).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, V, size=n_ids).astype(np.int32))
    return table, ids


def test_mod_sharded_lookup_matches_take(mesh_tp4):
    table, ids = _table_and_ids()
    fn = emb.make_sharded_lookup(mesh_tp4)
    got = fn(ids, emb.to_mod_sharded(table, mesh_tp4))
    np.testing.assert_allclose(got, jnp.take(table, ids, axis=0), rtol=1e-6)


def test_mod_sharded_lookup_grad_matches_take(mesh_tp4):
    table, ids = _table_and_ids(1)
    mod = emb.to_mod_sharded(table, mesh_tp4)
    fn = emb.make_sharded_lookup(mesh_tp4)

    g_sharded = jax.grad(lambda t: fn(ids, t).sum())(mod)
    g_dense = jax.grad(lambda t: jnp.take(t, ids, axis=0).sum())(table)
    # map the mod-sharded grad back to vocab order and compare
    n = mesh_tp4.shape[mesh_lib.MODEL]
    rows = emb.shard_vocab(V, n)
    back = np.zeros((V, D), np.float32)
    g_np = np.asarray(g_sharded)
    for s in range(n):
        for r in range(rows):
            gid = s + n * r
            if gid < V:
                back[gid] = g_np[s * rows + r]
    np.testing.assert_allclose(back, g_dense, rtol=1e-6)


def test_range_sharded_lookup_matches_take(mesh_tp4):
    table, ids = _table_and_ids(2)
    got = jax.shard_map(
        lambda i, t: emb.range_sharded_lookup(i, t, mesh_lib.MODEL),
        mesh=mesh_tp4,
        in_specs=(P(mesh_lib.BATCH_AXES), P(mesh_lib.MODEL, None)),
        out_specs=P(mesh_lib.BATCH_AXES, None),
        check_vma=False,
    )(ids, table)
    np.testing.assert_allclose(got, jnp.take(table, ids, axis=0), rtol=1e-6)


def test_batch_sharded_lookup_matches_take(mesh_tp4):
    # batch sharded over the SAME axis as the table (all_to_all-style path)
    table, ids = _table_and_ids(3, n_ids=32)
    mod = emb.to_mod_sharded(table, mesh_tp4)
    got = jax.shard_map(
        lambda i, t: emb.batch_sharded_lookup(i, t, mesh_lib.MODEL),
        mesh=mesh_tp4,
        in_specs=(P(mesh_lib.MODEL), P(mesh_lib.MODEL, None)),
        out_specs=P(mesh_lib.MODEL, None),
        check_vma=False,
    )(ids, mod)
    np.testing.assert_allclose(got, jnp.take(table, ids, axis=0), rtol=1e-6)


def test_single_axis_degrades_to_take(devices):
    mesh1 = build_mesh(MeshSpec(data=8), devices[:8])
    table, ids = _table_and_ids(4)
    fn = emb.make_sharded_lookup(mesh1)
    got = fn(ids, emb.to_mod_sharded(table, mesh1))
    np.testing.assert_allclose(got, jnp.take(table, ids, axis=0), rtol=1e-6)
