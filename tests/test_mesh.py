import jax
import pytest

from distributed_tensorflow_tpu.parallel import (
    AXIS_NAMES,
    MeshSpec,
    build_mesh,
    describe,
    mesh_axis_size,
    rescale_for_world,
    single_device_mesh,
)


def test_rescale_for_world_batch_axes_only():
    """Elastic resize seam: only the batch axes absorb a worker-count
    change — a wildcard data axis passes through, an explicit one
    scales exactly, and non-integral scalings are refused with the fix
    named."""
    wild = MeshSpec(data=-1)
    assert rescale_for_world(wild, 3, 2) is wild          # absorbs
    assert rescale_for_world(MeshSpec(data=6), 3, 3).data == 6  # no-op
    assert rescale_for_world(MeshSpec(data=6), 3, 2).data == 4  # shrink
    assert rescale_for_world(MeshSpec(data=4), 2, 3).data == 6  # grow
    # fsdp is a batch axis too: when data cannot absorb the change
    # (extent 1, or non-integral), fsdp does
    out = rescale_for_world(MeshSpec(data=1, fsdp=8), 4, 3)
    assert (out.data, out.fsdp) == (1, 6)
    # model/pipe extents ride along untouched (parameter layouts)
    spec = MeshSpec(data=4, model=2, pipe=1)
    out = rescale_for_world(spec, 2, 1)
    assert (out.data, out.model) == (2, 2)
    with pytest.raises(ValueError, match="data=-1"):
        rescale_for_world(MeshSpec(data=3), 2, 1)
    with pytest.raises(ValueError, match=">= 1"):
        rescale_for_world(MeshSpec(), 0, 2)


def test_axis_names_order():
    assert AXIS_NAMES == ("pipe", "data", "fsdp", "seq", "expert", "model")


def test_resolve_wildcard():
    spec = MeshSpec(data=-1, model=2).resolve(8)
    assert spec.data == 4 and spec.model == 2


def test_resolve_exact():
    spec = MeshSpec(pipe=2, data=2, model=2).resolve(8)
    assert spec.data == 2


def test_resolve_errors():
    with pytest.raises(ValueError):
        MeshSpec(data=3).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec(data=-1, model=3).resolve(8)


def test_from_dict_rejects_unknown():
    with pytest.raises(ValueError):
        MeshSpec.from_dict({"tensor": 2})


def test_pod_topology_two_level_spec():
    """Fault-domain descriptor → flat mesh: the data axis grows
    num_pods-fold and the pod boundary is declared DCN, everything
    intra-pod rides along untouched."""
    from distributed_tensorflow_tpu.parallel import PodTopology

    topo = PodTopology(num_pods=2, pod_spec=MeshSpec(data=2, model=2))
    flat = topo.to_mesh_spec()
    assert (flat.data, flat.model) == (4, 2)
    assert flat.dcn_data == 2 and flat.num_slices == 2
    resolved = topo.resolve(8)
    assert resolved.devices_per_pod == 4
    # a pod_spec wildcard resolves against the PER-POD device count
    wild = PodTopology(num_pods=2, pod_spec=MeshSpec(data=-1)).resolve(8)
    assert wild.pod_spec.data == 4
    assert wild.to_mesh_spec().data == 8
    assert "2 pod(s)" in wild.describe()
    rt = PodTopology.from_dict({"num_pods": 2, "pod": {"data": 2}})
    assert rt.num_pods == 2 and rt.pod_spec.data == 2


def test_pod_topology_validation():
    from distributed_tensorflow_tpu.parallel import PodTopology

    with pytest.raises(ValueError, match="num_pods"):
        PodTopology(num_pods=0)
    # the pod_spec is ONE pod's ICI mesh — its own dcn factors are
    # meaningless (the only inter-pod dimension is num_pods)
    with pytest.raises(ValueError, match="dcn"):
        PodTopology(num_pods=2, pod_spec=MeshSpec(data=2, dcn_data=2))
    with pytest.raises(ValueError, match="divisible"):
        PodTopology(num_pods=3, pod_spec=MeshSpec(data=2)).resolve(8)
    with pytest.raises(ValueError, match="resolve"):
        _ = PodTopology(num_pods=2, pod_spec=MeshSpec()).devices_per_pod
    with pytest.raises(ValueError, match="Unknown"):
        PodTopology.from_dict({"num_pods": 2, "pods": {}})


def test_pod_topology_mesh_builds(devices):
    """The two-level descriptor builds a real hybrid mesh: cross-pod
    hops only on the outermost data sub-dimension."""
    from distributed_tensorflow_tpu.parallel import PodTopology

    topo = PodTopology(num_pods=2, pod_spec=MeshSpec(data=2, model=2))
    mesh = build_mesh(topo.to_mesh_spec(), devices[:8])
    assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2
    assert mesh.size == 8


def test_build_mesh_shape(mesh_dp4_tp2):
    assert mesh_dp4_tp2.shape["data"] == 4
    assert mesh_dp4_tp2.shape["model"] == 2
    assert mesh_dp4_tp2.size == 8
    assert mesh_axis_size(mesh_dp4_tp2, ("data", "fsdp")) == 4


def test_single_device_mesh():
    m = single_device_mesh()
    assert m.size == 1
    assert set(m.shape.keys()) == set(AXIS_NAMES)


def test_describe(mesh8):
    s = describe(mesh8)
    assert "data=8" in s and "8 devices" in s


def test_all_devices_used(mesh_dp4_tp2):
    ids = sorted(d.id for d in mesh_dp4_tp2.devices.flat)
    assert ids == sorted(d.id for d in jax.devices()[:8])


# ---- hybrid ICI x DCN mesh (SURVEY.md §2d) ----

def test_hybrid_mesh_dcn_data_blocks(devices):
    """dcn_data=2 over 8 devices: the data axis splits into 2 DCN blocks
    of 4 ICI-contiguous devices — slice 0's devices fill data rows 0-3."""
    m = build_mesh(MeshSpec(data=8, dcn_data=2), devices[:8])
    assert m.shape["data"] == 8
    ids = [d.id for d in m.devices.reshape(8)]
    base = sorted(d.id for d in devices[:8])
    # first half of the data axis = first 4 devices (slice-major order)
    assert sorted(ids[:4]) == base[:4]
    assert sorted(ids[4:]) == base[4:]


def test_hybrid_mesh_mixed_axes(devices):
    """data(total 4, dcn 2) x model 2: ICI data=2 within a slice; model
    stays entirely intra-slice (per-layer TP must never cross DCN)."""
    m = build_mesh(MeshSpec(data=4, model=2, dcn_data=2), devices[:8])
    arr = m.devices.reshape(4, 2)  # (data, model)
    base = sorted(d.id for d in devices[:8])
    slice0 = set(base[:4])
    # data rows 0-1 (slice 0): all their devices come from slice 0
    got = {d.id for d in arr[:2].flat}
    assert got == slice0, (got, slice0)


def test_hybrid_requires_divisible():
    with pytest.raises(ValueError, match="DCN factor"):
        MeshSpec(data=3, dcn_data=2).resolve(3)


def test_hybrid_step_trains(devices):
    """A dp step over a hybrid dcn_data=2 mesh runs and matches the flat
    dp8 mesh (same math, different collective layout)."""
    import numpy as np
    import optax

    from distributed_tensorflow_tpu.parallel import sharding as sh
    from distributed_tensorflow_tpu.train import (
        StepOptions, init_train_state, jit_train_step, make_train_step,
    )
    from test_step import linear_init, linear_loss, make_batch

    results = []
    for spec in (MeshSpec(data=8), MeshSpec(data=8, dcn_data=2)):
        mesh = build_mesh(spec, devices[:8])
        tx = optax.sgd(0.1)
        state, specs = init_train_state(
            linear_init, tx, mesh, jax.random.PRNGKey(0)
        )
        step = jit_train_step(make_train_step(linear_loss, tx), mesh, specs)
        batch = jax.tree.map(
            lambda x: jax.device_put(
                x, jax.sharding.NamedSharding(mesh, sh.batch_spec(x.ndim))
            ),
            make_batch(16),
        )
        state, metrics = step(state, batch)
        results.append(float(metrics["loss"]))
    assert np.isclose(results[0], results[1], rtol=1e-6), results
