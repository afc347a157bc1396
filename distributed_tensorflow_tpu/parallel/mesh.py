"""Device mesh & named-axis abstraction — the topology core of the framework.

This replaces the reference harness's cluster-topology layer
(``tf.train.ClusterSpec`` / ``tf.train.Server`` / ``replica_device_setter`` —
see SURVEY.md §2b rows 1-3; substrate: $TF/python/training/server_lib.py:243,
device_setter.py:129). Where the reference mapped *ops* onto *processes*
(variables round-robin onto PS tasks, compute onto the local worker, gRPC
inserted at every job boundary), a TPU-native design maps one SPMD program onto
a named device mesh and lets XLA compile collectives onto ICI/DCN
(SURVEY.md §2d, §5.8).

Axis vocabulary (all six are always present; unused axes have size 1 so that
every PartitionSpec in the codebase is valid on every mesh):

- ``pipe``   — pipeline-parallel stages (1F1B schedule, parallel/pipeline.py)
- ``data``   — pure data parallelism (gradient psum rides this axis)
- ``fsdp``   — data parallelism with parameter/optimizer-state sharding
               (ZeRO-style weight-update sharding, arXiv:2004.13336)
- ``seq``    — sequence/context parallelism (ring attention / Ulysses,
               parallel/ring_attention.py, SURVEY.md §5.7)
- ``expert`` — expert parallelism for MoE token dispatch (mesh-axis stub per
               SURVEY.md §2c; full MoE is out of baseline scope)
- ``model``  — tensor parallelism (megatron-style column/row sharding)

Axis order puts ``model`` innermost: ``mesh_utils.create_device_mesh`` assigns
innermost axes to physically adjacent chips, so the highest-traffic collectives
(TP all-gather / reduce-scatter every layer) ride the shortest ICI hops.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

logger = logging.getLogger(__name__)

# Canonical axis order, outermost → innermost.
AXIS_NAMES: tuple[str, ...] = ("pipe", "data", "fsdp", "seq", "expert", "model")

PIPE, DATA, FSDP, SEQ, EXPERT, MODEL = AXIS_NAMES

#: Axes over which a batch is split. Gradients are summed over these axes
#: (explicitly under shard_map; implicitly by GSPMD under jit).
BATCH_AXES: tuple[str, ...] = (DATA, FSDP)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. ``-1`` on at most one axis means "absorb the rest".

    The TPU-native analog of the reference's ``--ps_hosts/--worker_hosts``
    flags (SURVEY.md §5.6): instead of listing host:port endpoints, the user
    names how many ways each *meaning* of parallelism is applied, and the
    device mesh is derived from the physical topology.
    """

    pipe: int = 1
    data: int = -1  # default: all remaining devices do data parallelism
    fsdp: int = 1
    seq: int = 1
    expert: int = 1
    model: int = 1
    # DCN (inter-slice) factors for multislice pods (BASELINE.json:10
    # "pod-scale"): the TOTAL size of an axis is its ICI part × its DCN
    # part. E.g. data=8, dcn_data=2 → each of 2 slices holds 4-way ICI
    # data parallelism, and the gradient psum's final hop rides DCN.
    # Only axes whose collectives tolerate DCN latency (data/pipe grad
    # reduction, not per-layer TP) get dcn_* knobs — the
    # mesh_utils.create_hybrid_device_mesh recipe.
    dcn_data: int = 1
    dcn_pipe: int = 1

    def sizes(self) -> dict[str, int]:
        return {name: getattr(self, name) for name in AXIS_NAMES}

    def dcn_sizes(self) -> dict[str, int]:
        return {PIPE: self.dcn_pipe, DATA: self.dcn_data, FSDP: 1,
                SEQ: 1, EXPERT: 1, MODEL: 1}

    @property
    def num_slices(self) -> int:
        return self.dcn_data * self.dcn_pipe

    def resolve(self, n_devices: int) -> "MeshSpec":
        """Fill in the single -1 axis so the product equals ``n_devices``.
        Axis fields are TOTALS (ICI × DCN); each must divide by its dcn_*
        factor."""
        sizes = self.sizes()
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"At most one axis may be -1, got {wild}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product "
                    f"{fixed} ({sizes})"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"Mesh {sizes} needs {fixed} devices but {n_devices} are "
                f"available"
            )
        out = MeshSpec(**sizes, dcn_data=self.dcn_data, dcn_pipe=self.dcn_pipe)
        for name, dcn in out.dcn_sizes().items():
            if dcn > 1 and out.sizes()[name] % dcn != 0:
                raise ValueError(
                    f"axis {name}={out.sizes()[name]} not divisible by its "
                    f"DCN factor dcn_{name}={dcn}"
                )
        return out

    @classmethod
    def from_dict(cls, d: Mapping[str, int]) -> "MeshSpec":
        valid = set(AXIS_NAMES) | {"dcn_data", "dcn_pipe"}
        unknown = set(d) - valid
        if unknown:
            raise ValueError(f"Unknown mesh axes {unknown}; valid: {sorted(valid)}")
        return cls(**dict(d))


@dataclasses.dataclass(frozen=True)
class PodTopology:
    """Two-level fault-domain topology: ``num_pods`` identical pods.

    ``pod_spec`` is the ICI mesh of ONE pod — its ``dcn_*`` factors
    must be 1, because the only inter-pod dimension is the one this
    descriptor adds. The flat mesh the trainer builds is
    ``to_mesh_spec()``: the data axis grows ``num_pods``-fold and its
    new outer hop is declared DCN (``dcn_data = num_pods``), so the
    gradient psum reduces intra-pod first and crosses pod boundaries
    exactly once — the same hybrid-mesh recipe as multislice, with the
    slice boundary reinterpreted as the FAULT boundary
    (resilience/podfleet.py supervises one fault domain per pod; a
    pod's outage shrinks or holds this axis, never the intra-pod ones).

    Only ``data`` may span pods: ``model`` / ``pipe`` / ``seq`` /
    ``expert`` collectives are latency-critical per layer and a pod
    restart must never re-partition parameter state — the same rule
    ``rescale_for_world`` enforces one level down.
    """

    num_pods: int
    pod_spec: MeshSpec = MeshSpec()

    def __post_init__(self):
        if self.num_pods < 1:
            raise ValueError(f"num_pods must be >= 1, got {self.num_pods}")
        if self.pod_spec.num_slices != 1:
            raise ValueError(
                "pod_spec describes ONE pod's ICI mesh: its dcn_* factors "
                f"must be 1 (got dcn_data={self.pod_spec.dcn_data}, "
                f"dcn_pipe={self.pod_spec.dcn_pipe}); cross-pod DCN comes "
                "from num_pods")

    def to_mesh_spec(self) -> MeshSpec:
        """The flat (total-extent) MeshSpec for the whole fleet: pod
        data extent × num_pods on the data axis, pod boundary = DCN."""
        data = self.pod_spec.data
        total = data if data == -1 else data * self.num_pods
        return dataclasses.replace(
            self.pod_spec, data=total, dcn_data=self.num_pods)

    def resolve(self, n_devices: int) -> "PodTopology":
        """Fill the pod_spec wildcard from the PER-POD device count."""
        if n_devices % self.num_pods != 0:
            raise ValueError(
                f"{n_devices} devices not divisible into {self.num_pods} "
                "pods")
        return dataclasses.replace(
            self, pod_spec=self.pod_spec.resolve(n_devices // self.num_pods))

    @property
    def devices_per_pod(self) -> int:
        """Device count of one pod (pod_spec must be resolved)."""
        sizes = self.pod_spec.sizes()
        if -1 in sizes.values():
            raise ValueError("pod_spec has an unresolved -1 axis; call "
                             "resolve(n_devices) first")
        return math.prod(sizes.values())

    @classmethod
    def from_dict(cls, d: Mapping) -> "PodTopology":
        """``{"num_pods": n, "pod": {<MeshSpec axes>}}``."""
        unknown = set(d) - {"num_pods", "pod"}
        if unknown:
            raise ValueError(
                f"Unknown PodTopology keys {unknown}; valid: num_pods, pod")
        return cls(num_pods=int(d.get("num_pods", 1)),
                   pod_spec=MeshSpec.from_dict(d.get("pod", {})))

    def describe(self) -> str:
        sizes = " ".join(f"{a}={v}" for a, v in self.pod_spec.sizes().items())
        return f"{self.num_pods} pod(s) × [{sizes}]"


def build_mesh(
    spec: MeshSpec | Mapping[str, int] | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a ``jax.sharding.Mesh`` with the canonical six named axes.

    Replaces ``ClusterSpec`` + ``Server`` bootstrap (SURVEY.md §3.1 frames
    1-3): there is no per-process server to start — the runtime owns
    transport, and this mesh is the only topology object the user touches.
    """
    if devices is None:
        devices = jax.devices()
    if spec is None:
        spec = MeshSpec()
    if not isinstance(spec, MeshSpec):
        spec = MeshSpec.from_dict(spec)
    spec = spec.resolve(len(devices))
    shape = tuple(spec.sizes()[name] for name in AXIS_NAMES)
    if spec.num_slices > 1:
        dev_array = _hybrid_device_array(spec, devices)
    else:
        try:
            dev_array = mesh_utils.create_device_mesh(
                shape, devices=np.asarray(devices, dtype=object)
            )
            logger.info("mesh %s placed by mesh_utils.create_device_mesh",
                        shape)
        except (ValueError, AssertionError, NotImplementedError) as e:
            # Fallback for device sets mesh_utils cannot place (a subset
            # of a slice): plain row-major reshape. Collective placement
            # is still correct, just not hop-optimal — so say so.
            logger.warning("mesh %s: create_device_mesh refused (%s); "
                           "row-major device order, not hop-optimal",
                           shape, e)
            dev_array = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(dev_array, AXIS_NAMES)


def rescale_for_world(spec: MeshSpec, old_world: int,
                      new_world: int) -> MeshSpec:
    """Respec a mesh for an elastic fleet resize (docs/resilience.md
    "Elastic fleet"): the worker count changed ``old_world →
    new_world``, so the device pool scales by the same ratio.

    Only the BATCH axes may absorb a world change — ``model`` / ``pipe``
    / ``seq`` / ``expert`` extents are baked into parameter and
    activation layouts, and resizing them would re-partition state, not
    just re-partition the batch. Concretely:

    - ``data == -1`` passes through: the wildcard already absorbs
      whatever devices the surviving workers contribute.
    - otherwise the first of ``data``, ``fsdp`` (both are BATCH_AXES)
      whose explicit extent scales integrally by
      ``new_world / old_world`` absorbs the change; the DCN factor
      constraint is re-validated by ``resolve`` at build time.

    Anything else raises with the fix spelled out. The returned spec is
    what a (re)launched worker passes to ``build_mesh`` for the resized
    gang; the data-stream half of the resize is
    ``data/pipeline.ElasticStream``."""
    if old_world < 1 or new_world < 1:
        raise ValueError("old_world and new_world must be >= 1")
    if new_world == old_world or spec.data == -1:
        return spec
    for axis in (DATA, FSDP):
        extent = getattr(spec, axis)
        scaled = extent * new_world
        if scaled % old_world == 0 and scaled >= old_world:
            return dataclasses.replace(spec, **{axis: scaled // old_world})
    raise ValueError(
        f"neither batch axis scales by {new_world}/{old_world} "
        f"(data={spec.data}, fsdp={spec.fsdp}): the resized extent would "
        f"not be integral — use data=-1 so the batch axis absorbs the "
        f"surviving devices, or pick a fleet size dividing a batch-axis "
        f"extent")


def _hybrid_device_array(spec: MeshSpec, devices: Sequence[jax.Device]) -> np.ndarray:
    """Device array for a multislice ICI×DCN mesh (SURVEY.md §2d: ICI
    within a slice, DCN between slices; the DeviceAssignment/Topology
    analog, $TF device_assignment.py:70).

    Per axis, the DCN factor is the OUTER sub-dimension: neighboring
    indices along an axis stay on the same slice (ICI), and only the
    outermost hop crosses DCN — so e.g. a gradient psum over `data`
    reduces intra-slice first. Uses mesh_utils.create_hybrid_device_mesh
    (slice-topology-aware) when device slice metadata exists; falls back
    to a slice-major block construction for test rigs without it."""
    totals = spec.sizes()
    dcn = spec.dcn_sizes()
    ici_shape = tuple(totals[a] // dcn[a] for a in AXIS_NAMES)
    dcn_shape = tuple(dcn[a] for a in AXIS_NAMES)
    np_devices = np.asarray(devices, dtype=object)
    try:
        return mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=np_devices
        )
    except (ValueError, AssertionError, NotImplementedError, KeyError):
        # Fake-device fallback: jax.devices() is process-/slice-major, so
        # reshape (dcn..., ici...) then interleave to put each axis's DCN
        # part just outside its ICI part.
        arr = np_devices.reshape(*dcn_shape, *ici_shape)
        n = len(AXIS_NAMES)
        perm = [k for pair in zip(range(n), range(n, 2 * n)) for k in pair]
        arr = arr.transpose(perm)
        return arr.reshape(tuple(totals[a] for a in AXIS_NAMES))


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    """A 1×1×1×1×1×1 mesh: lets every sharded code path run on one chip."""
    if device is None:
        device = jax.devices()[0]
    return build_mesh(MeshSpec(data=1), [device])


def factor_mesh_axis(
    mesh: Mesh, axis: str, factors: Mapping[str, int]
) -> Mesh:
    """Split one named mesh axis into ordered sub-axes (outer → inner).

    This is the API form of "structural subgroups get their own mesh axis"
    (SURVEY.md §5.8; the TPU-native descendant of NCCL communicator
    subgroups / CrossReplicaSum ``group_assignment``, $TF tpu_ops.py:32-40):
    a collective over ONE sub-axis compiles to a true subgroup collective —
    XLA emits an all-reduce over just those replica groups, no full-axis
    gather — unlike the mask-emulated ``groups=`` path in
    parallel/collectives.py, whose wire cost is the whole axis.

    >>> sub = factor_mesh_axis(mesh, "data", {"replica": 2, "shard": 4})
    >>> # inside shard_map over `sub`: lax.psum(x, "shard") reduces within
    >>> # each group of 4; lax.psum(x, ("replica", "shard")) == old axis.

    Device placement is unchanged — only the naming is refined, so
    sub-axis groups are exactly the contiguous index blocks the emulated
    path expresses as ``groups=[[0..k-1], [k..2k-1], ...]``.
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
    for name in factors:
        if name in mesh.axis_names:
            raise ValueError(f"sub-axis name {name!r} already in mesh")
    size = mesh.shape[axis]
    if math.prod(factors.values()) != size:
        raise ValueError(
            f"factors {dict(factors)} do not multiply to {axis}={size}"
        )
    idx = mesh.axis_names.index(axis)
    new_shape = (
        mesh.devices.shape[:idx]
        + tuple(factors.values())
        + mesh.devices.shape[idx + 1:]
    )
    new_names = (
        mesh.axis_names[:idx] + tuple(factors) + mesh.axis_names[idx + 1:]
    )
    return Mesh(mesh.devices.reshape(new_shape), new_names)


def mesh_axis_size(mesh: Mesh, axes: str | Sequence[str]) -> int:
    """Product of the named axis sizes (e.g. total batch shards)."""
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def describe(mesh: Mesh) -> str:
    """Human-readable one-liner, e.g. 'pipe=1 data=4 fsdp=1 seq=1 expert=1 model=2 (8 devices, cpu)'."""
    parts = " ".join(f"{a}={mesh.shape[a]}" for a in AXIS_NAMES)
    plat = mesh.devices.flat[0].platform
    return f"{parts} ({mesh.size} devices, {plat})"
