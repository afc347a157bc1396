"""Collective-verb correctness on the fake 8-device mesh — the
'distributed-correctness oracle' pattern (SURVEY.md §4: assert allreduce
across k fake replicas equals the single-replica reduction)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_tpu.parallel import collectives as col


def smap(mesh, fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def test_all_reduce_sum(mesh8):
    x = jnp.arange(8.0).reshape(8, 1)
    out = smap(mesh8, lambda v: col.all_reduce(v, "data"), P("data"), P("data"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 28.0))


def test_all_reduce_mean_matches_single_device(mesh8):
    rng = np.random.RandomState(0)
    x = rng.randn(8, 4).astype(np.float32)
    out = smap(
        mesh8, lambda v: col.all_reduce_mean(v, "data"), P("data"), P("data")
    )(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out)[0], x.mean(0), rtol=1e-6)


def test_all_reduce_groups(mesh8):
    groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
    x = jnp.arange(8.0).reshape(8, 1)
    out = smap(
        mesh8,
        lambda v: col.all_reduce(v, "data", groups=groups),
        P("data"),
        P("data"),
    )(x)
    np.testing.assert_allclose(np.asarray(out)[:, 0], [6, 6, 6, 6, 22, 22, 22, 22])


def test_all_gather(mesh8):
    x = jnp.arange(16.0).reshape(8, 2)
    out = smap(
        mesh8,
        lambda v: col.all_gather(v, "data"),
        P("data"),
        P("data", None),
    )(x)
    # each shard gathers the full array along dim 0
    assert out.shape == (64, 2)
    np.testing.assert_allclose(np.asarray(out)[:8], np.asarray(x))


def test_reduce_scatter_roundtrip(mesh8):
    rng = np.random.RandomState(1)
    x = rng.randn(8, 8).astype(np.float32)

    def fn(v):  # v: (1, 8)
        scattered = col.reduce_scatter(v, "data", scatter_axis=1)  # (1, 1)
        return scattered

    out = smap(mesh8, fn, P("data", None), P("data", None))(jnp.asarray(x))
    np.testing.assert_allclose(
        np.asarray(out).ravel(), x.sum(0), rtol=1e-5
    )


def test_broadcast(mesh8):
    x = jnp.arange(8.0).reshape(8, 1)
    out = smap(
        mesh8, lambda v: col.broadcast(v, "data", src=3), P("data"), P("data")
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 3.0))


def test_barrier(mesh8):
    out = smap(mesh8, lambda: col.barrier("data"), (), P())()
    assert int(out) == 8


def test_all_to_all(mesh8):
    # 8 shards each hold (1, 8); all_to_all transposes the sharding.
    x = jnp.arange(64.0).reshape(8, 8)
    out = smap(
        mesh8,
        lambda v: col.all_to_all(v, "data", split_axis=1, concat_axis=0),
        P("data", None),
        P(None, "data"),
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))  # global transpose of sharding, same values


def test_ring_permute(mesh8):
    x = jnp.arange(8.0).reshape(8, 1)
    out = smap(
        mesh8, lambda v: col.ring_permute(v, "data", shift=1), P("data"), P("data")
    )(x)
    np.testing.assert_allclose(np.asarray(out)[:, 0], [7, 0, 1, 2, 3, 4, 5, 6])


def test_all_gather_groups(mesh8):
    groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
    x = jnp.arange(8.0).reshape(8, 1)
    out = smap(
        mesh8,
        lambda v: col.all_gather(v, "data", groups=groups),
        P("data"),
        P("data", None),
    )(x)
    # each device gathers its group's 4 shards → global (32, 1)
    assert out.shape == (32, 1)
    np.testing.assert_allclose(np.asarray(out)[:4, 0], [0, 1, 2, 3])
    np.testing.assert_allclose(np.asarray(out)[16:20, 0], [4, 5, 6, 7])


def test_reduce_scatter_groups(mesh8):
    groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
    x = jnp.ones((8, 4), jnp.float32)

    def fn(v):  # (1, 4) per device
        return col.reduce_scatter(v, "data", scatter_axis=1, groups=groups)

    out = smap(mesh8, fn, P("data", None), P("data", None))(x)
    # each group of 4 sums 4 ones → each device holds one chunk of value 4
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 4.0))


def test_subgroup_collective_on_2d_mesh(mesh_dp4_tp2):
    # psum over 'model' only: pairs of devices reduce independently.
    x = jnp.arange(8.0).reshape(4, 2)

    def fn(v):
        return col.all_reduce(v, "model")

    out = jax.shard_map(
        fn, mesh=mesh_dp4_tp2, in_specs=P("data", "model"), out_specs=P("data", "model")
    )(x)
    expected = np.asarray(x).reshape(4, 2).sum(1, keepdims=True).repeat(2, 1)
    np.testing.assert_allclose(np.asarray(out), expected)


def test_factor_mesh_axis_numerics(mesh8):
    """Factored sub-axis psum == emulated grouped all_reduce with the
    matching contiguous groups (mesh.factor_mesh_axis API)."""
    from distributed_tensorflow_tpu.parallel import factor_mesh_axis

    x = jnp.arange(8.0)
    groups = [[0, 1], [2, 3], [4, 5], [6, 7]]
    emulated = jax.shard_map(
        lambda v: col.all_reduce(v, "data", groups=groups),
        mesh=mesh8, in_specs=P("data"), out_specs=P("data"),
    )(x)

    sub = factor_mesh_axis(mesh8, "data", {"outer": 4, "inner": 2})
    factored = jax.shard_map(
        lambda v: col.all_reduce(v, "inner"),
        mesh=sub, in_specs=P(("outer", "inner")), out_specs=P(("outer", "inner")),
    )(x)
    np.testing.assert_allclose(np.asarray(factored), np.asarray(emulated))


def test_factored_axis_avoids_full_gather(mesh8):
    """The factored path must compile to a subgroup all-reduce with NO
    full-axis all-gather; the emulated path provably contains one."""
    from distributed_tensorflow_tpu.parallel import factor_mesh_axis

    x = jnp.arange(8.0)
    sub = factor_mesh_axis(mesh8, "data", {"outer": 4, "inner": 2})
    factored = jax.jit(jax.shard_map(
        lambda v: col.all_reduce(v, "inner"),
        mesh=sub, in_specs=P(("outer", "inner")), out_specs=P(("outer", "inner")),
    ))
    hlo = factored.lower(x).compile().as_text()
    assert "all-reduce" in hlo, hlo[:2000]
    assert "all-gather" not in hlo, "factored subgroup reduce gathered the full axis"
    # replica groups of size 2, not 8
    import re

    m = re.search(r"replica_groups=\{(\{[\d,]+\})", hlo)
    assert m is not None, hlo[:2000]
    first_group = m.group(1)
    assert len(first_group.strip("{}").split(",")) == 2, first_group

    groups = [[0, 1], [2, 3], [4, 5], [6, 7]]
    emulated = jax.jit(jax.shard_map(
        lambda v: col.all_reduce(v, "data", groups=groups),
        mesh=mesh8, in_specs=P("data"), out_specs=P("data"),
    ))
    hlo_e = emulated.lower(x).compile().as_text()
    assert "all-gather" in hlo_e  # documents why factoring is the fast path


def test_factor_mesh_axis_validation(mesh8):
    from distributed_tensorflow_tpu.parallel import factor_mesh_axis

    import pytest as _pytest

    with _pytest.raises(ValueError, match="no axis"):
        factor_mesh_axis(mesh8, "nope", {"a": 2})
    with _pytest.raises(ValueError, match="multiply"):
        factor_mesh_axis(mesh8, "data", {"a": 3})
    with _pytest.raises(ValueError, match="already in mesh"):
        factor_mesh_axis(mesh8, "data", {"model": 8})


def test_emulated_groups_warn_and_cap(mesh8, monkeypatch):
    """The emulated groups= path is fenced: it warns
    on every use, and past EMULATED_GROUP_AXIS_LIMIT it refuses outright,
    pointing at factor_mesh_axis."""
    import re

    import pytest

    groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
    x = jnp.arange(8.0).reshape(8, 1)
    run = smap(
        mesh8, lambda v: col.all_reduce(v, "data", groups=groups),
        P("data"), P("data"),
    )
    with pytest.warns(UserWarning, match="emulated"):
        run(x)

    monkeypatch.setattr(col, "EMULATED_GROUP_AXIS_LIMIT", 4)
    for verb in (
        lambda v: col.all_reduce(v, "data", groups=groups),
        lambda v: col.all_gather(v, "data", groups=groups),
        lambda v: col.reduce_scatter(v, "data", scatter_axis=1,
                                     groups=groups),
    ):
        with pytest.raises(ValueError,
                           match=re.escape("factor_mesh_axis")):
            smap(mesh8, verb, P("data"), P("data"))(
                jnp.arange(32.0).reshape(8, 4))
