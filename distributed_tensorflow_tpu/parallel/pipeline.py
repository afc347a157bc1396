"""Pipeline parallelism over the ``pipe`` mesh axis.

SURVEY.md §2c row 'Pipeline parallel (PP)': absent from the reference
(its model-parallelism was variables round-robined over PS processes,
device_setter.py:147-149); this is the TPU-native mechanism — a
collective-permute microbatch schedule expressed as one ``shard_map``
island, activations hopping stage→stage over ICI via ``ppermute``.

Design (the standard GPipe-on-SPMD formulation, cf. the scaling-book's
pipelining chapter and praxis' LayerwiseShardablePipelined):

- Every stage runs the SAME program (SPMD); stage identity comes from
  ``lax.axis_index('pipe')``. Stage s holds the parameters for its layer
  slice — every parameter leaf carries a leading ``[n_stages, ...]`` dim
  sharded ``P('pipe')``, so each device materializes only its own slice.
- The schedule is a ``lax.scan`` over ``M + S - 1`` ticks (M microbatches,
  S stages). At tick t, stage 0 injects microbatch t (while t < M), every
  stage applies its layers to its current buffer, and the buffer rotates
  one hop around the ring. Stage S-1's outputs are collected into the
  result; trailing-edge devices compute on garbage that is masked out —
  the classic (S-1)/(M+S-1) bubble.
- Backward is autodiff through the scan: ``ppermute``'s transpose is the
  reverse-direction ``ppermute``, so the backward pipeline (activations'
  cotangents flowing stage S-1 → 0) falls out of ``jax.grad`` — no
  hand-written 1F1B needed for correctness. ``jax.checkpoint`` on the
  stage fn keeps activation memory at O(layers_per_stage) per tick.
- Output collection: only stage S-1 holds real outputs; they are
  broadcast to all pipe ranks with a masked ``psum`` so downstream global
  code (loss over the full batch) sees a pipe-replicated array. Traffic
  analysis (why this is kept): the psum moves ~2(S-1)/S of the output
  bytes once per step, and its *transpose is communication-free* (the
  cotangent arrives already pipe-replicated from the replicated loss and
  is masked locally). The alternatives measure the same or worse:
  all_gather+index is (S-1)/S forward but its transpose is a
  psum_scatter of the same order, and riding outputs around the existing
  ppermute ring for S-1 extra drain ticks moves exactly the same bytes
  as the psum while adding S-1 ticks of garbage compute.
- Memory schedule: ``jax.checkpoint`` on the stage fn bounds live
  activations at one stage-IO buffer per in-flight microbatch — O(M)
  per device (GPipe), not 1F1B's O(S). True 1F1B needs hand-interleaved
  forward/backward ticks (a custom VJP over the whole schedule) because
  autodiff-through-scan replays the forward schedule before starting the
  backward one; documented as the known delta vs Megatron-style
  schedulers rather than half-built.

Constraints (documented, standard): stage_fn must be shape-preserving
([mb, ...] -> [mb, ...]); heterogeneous ends (embedding lookup, output
head) run OUTSIDE the pipeline, pipe-replicated — see the pipelined
path in models/transformer.py (to_pipeline_params/pipelined_apply). Composes with data/fsdp (batch dim sharded inside
the same shard_map) AND with tensor parallelism inside a stage: pass
``param_specs`` that shard kernel dims over `model` and a ``stage_fn``
that does the matching manual collectives — the transformer family wires
this via ``Block(tp_shards=...)`` (megatron column/row slices + psum),
see models/transformer.pipelined_apply.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import mesh as mesh_lib
from . import sharding


def stage_param_specs(stage_params: Any) -> Any:
    """P('pipe', None, ...) for every leaf (leading dim = stage) —
    constructed at the sharding seam (sharding.stacked_stage_specs)."""
    return sharding.stacked_stage_specs(stage_params)


def stack_stages(per_stage: list) -> Any:
    """[tree_0, ..., tree_{S-1}] (same structure) -> one tree with a
    leading stage dim on every leaf."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage)


def pipeline_apply(
    stage_fn: Callable[..., jax.Array],
    stage_params: Any,
    x_mb: jax.Array,
    mesh: Mesh,
    aux_mb: Any = None,
    n_virtual: int = 1,
    param_specs: Any = None,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Run ``x_mb`` through the S-stage (optionally interleaved) pipeline.

    stage_fn: (params_slice, x [mb, ...]) -> y [mb, ...] — shape-preserving.
        With ``aux_mb``, (params_slice, x, aux) -> y.
    stage_params: every leaf [S, ...] (``n_virtual == 1``) or
        [S, V, ...] (interleaved: device d holds chunks v·S+d for
        v in [0, V)), to be sharded P('pipe') on the leading dim.
    x_mb: [M, mb, ...] microbatches; mb dim is sharded over (data, fsdp),
        the microbatch dim M is replicated. Returns [M, mb, ...] outputs,
        pipe-replicated.
    aux_mb: optional pytree of [M, mb, ...] per-microbatch side inputs
        (e.g. attention masks) that do NOT hop the ring: every rank holds
        all microbatches' aux (they are small), and the schedule indexes
        the slice for the microbatch currently at this stage.
    n_virtual: V > 1 runs the Megatron-style interleaved (circular)
        schedule — the network is cut into S·V chunks of L/(S·V) layers,
        each device owns V non-contiguous chunks, and the bubble shrinks
        V-fold to (S-1)/(M·V+S-1) at the cost of retaining ~V× more
        per-tick activations for the backward (the scan is V× longer).
        Requires M % S == 0.
    param_specs: override the default P('pipe', None, ...) per-leaf specs
        — for PP×TP, pass specs that ALSO shard kernel dims over `model`
        (models/transformer.pipeline_param_specs(tp=True)); stage_fn is
        then responsible for the matching manual collectives (Block's
        tp_shards psums). Specs must keep 'pipe' on the leading dim.
    rng: optional PRNG key enabling STOCHASTIC stage fns (dropout in
        pipelined training). When given, stage_fn is
        called with two extra trailing args ``(mb_key, chunk_idx)``:
        ``mb_key = fold_in(rng, m)`` is unique per microbatch and
        ``chunk_idx = v·S + stage`` identifies the chunk, so the stage fn
        can derive a key per (microbatch, layer) that is INDEPENDENT of
        the schedule — fold the global layer index ``chunk_idx ·
        layers_per_chunk + local_idx`` into ``mb_key`` and the same key
        tree falls out for any (S, V) decomposition (asserted by
        tests/test_pipeline.py dropout-parity). Keys are replayed
        identically in the backward (jax.checkpoint re-runs the forward
        with the same folded values), so dropout masks are consistent
        across fwd/bwd by construction.
    """
    n_stages = mesh.shape[mesh_lib.PIPE]
    M = x_mb.shape[0]
    V = n_virtual
    for leaf in jax.tree.leaves(aux_mb):
        if jnp.ndim(leaf) < 2 or leaf.shape[0] != M:
            raise ValueError(
                f"aux_mb leaves must be [M={M}, mb, ...] microbatched "
                f"(use microbatch()); got shape {jnp.shape(leaf)}"
            )
    if V == 1:
        # canonical internal layout has the virtual-chunk dim: [S, 1, ...]
        stage_params = jax.tree.map(lambda p: p[:, None], stage_params)
        if param_specs is not None:
            # caller's specs describe the pre-insert layout; track the
            # new virtual dim (replicated) at position 1
            def _insert_vdim(s):
                e = tuple(s)
                return P(e[0], None, *e[1:])

            param_specs = jax.tree.map(
                _insert_vdim, param_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
    else:
        for leaf in jax.tree.leaves(stage_params):
            if jnp.ndim(leaf) < 2 or leaf.shape[1] != V:
                raise ValueError(
                    f"n_virtual={V} needs stage_params leaves laid out "
                    f"[S, V, ...]; got shape {jnp.shape(leaf)} (build with "
                    "to_pipeline_params(..., n_virtual=V) or stack chunks "
                    "v*S+d at [d, v])"
                )
    if n_stages == 1:
        if param_specs is not None:
            raise ValueError(
                "param_specs on a pipe=1 mesh: the degenerate path runs "
                "outside shard_map, so a TP stage_fn's collectives would "
                "hit unbound axis names — use the GSPMD path instead"
            )
        # degenerate: no pipe axis — scan this device's chunks in order
        # (S=1, so chunk index c = v, matching the pipelined c = v·S+d)
        sq = jax.tree.map(lambda p: p.reshape(-1, *p.shape[2:]), stage_params)
        n_chunks = jax.tree.leaves(sq)[0].shape[0]

        def through_chunks(x, aux=None, mb_key=None):
            def chunk(x, pc):
                p, c = pc
                args = [p, x] + ([] if aux is None else [aux])
                if mb_key is not None:
                    args += [mb_key, c]
                return stage_fn(*args), None

            y, _ = jax.lax.scan(chunk, x, (sq, jnp.arange(n_chunks)))
            return y

        mb_keys = (
            None if rng is None
            else jax.vmap(lambda i: jax.random.fold_in(rng, i))(
                jnp.arange(M))
        )
        return jax.vmap(
            through_chunks,
            in_axes=(0,
                     0 if aux_mb is not None else None,
                     0 if mb_keys is not None else None),
        )(x_mb, aux_mb, mb_keys)
    if M < n_stages:
        raise ValueError(
            f"need at least as many microbatches ({M}) as stages "
            f"({n_stages}) — bubble would dominate and the schedule "
            "below assumes M >= S"
        )
    if V > 1 and M % n_stages:
        raise ValueError(
            f"interleaved schedule needs microbatches ({M}) divisible by "
            f"stages ({n_stages})"
        )

    batch_shards = mesh_lib.mesh_axis_size(mesh, mesh_lib.BATCH_AXES)
    if x_mb.shape[1] % batch_shards:
        raise ValueError(
            f"microbatch size {x_mb.shape[1]} not divisible by "
            f"data×fsdp={batch_shards}; use fewer microbatches or a larger "
            "global batch"
        )

    if param_specs is None:
        param_specs = stage_param_specs(stage_params)
    mb_spec = lambda leaf: P(
        None, mesh_lib.BATCH_AXES, *([None] * (jnp.ndim(leaf) - 2))
    )
    x_spec = mb_spec(x_mb)
    aux_specs = jax.tree.map(mb_spec, aux_mb)

    body = functools.partial(
        _pipeline_body, stage_fn, n_stages=n_stages, n_microbatches=M,
        n_virtual=V,
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, x_spec, aux_specs, P()),
        out_specs=x_spec,
        check_vma=False,
    )(stage_params, x_mb, aux_mb, rng)


def _pipeline_body(stage_fn, stage_params, x_mb, aux_mb, rng, *, n_stages,
                   n_microbatches, n_virtual):
    """Per-device schedule; runs inside shard_map. stage_params leaves are
    [1, V, ...] local slices; x_mb is [M, mb_local, ...].

    One unified schedule covers GPipe (V=1) and interleaved (V>1): chunk
    c = v·S + d lives on device d; every tick runs ONE chunk per device
    and hops the ring once. Device d at tick t is at local time
    λ = t - d; with (g, r) = divmod(λ, S·V), (v, j) = divmod(r, S), it
    runs chunk v on microbatch m = g·S + j. Producer-consumer timing is
    exact by construction: chunk c's output for m (tick m + c) arrives at
    chunk c+1 exactly when that chunk processes m (tick m + c + 1) — the
    wraparound d = S-1 → d = 0 lands on v+1 with the same algebra."""
    stage = jax.lax.axis_index(mesh_lib.PIPE)
    params_local = jax.tree.map(lambda p: p[0], stage_params)  # [V, ...]
    M, S, V = n_microbatches, n_stages, n_virtual
    perm = [(i, (i + 1) % S) for i in range(S)]

    fn = jax.checkpoint(stage_fn)

    def tick(carry, t):
        buf, outputs = carry
        lam = t - stage
        active = (lam >= 0) & (lam < M * V)
        g, r = jnp.divmod(jnp.maximum(lam, 0), S * V)
        v, j = jnp.divmod(r, S)
        m = jnp.clip(g * S + j, 0, M - 1)
        params_v = jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(p, v, 0, keepdims=False),
            params_local,
        )
        # device 0 injects a fresh microbatch whenever it starts chunk 0
        x_t = jax.lax.dynamic_index_in_dim(x_mb, m, 0, keepdims=False)
        inp = jnp.where((stage == 0) & (v == 0) & active, x_t, buf)
        args = [params_v, inp]
        if aux_mb is not None:
            args.append(jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, m, 0, keepdims=False
                ),
                aux_mb,
            ))
        if rng is not None:
            # (mb_key, chunk): schedule-independent RNG identity — see
            # the pipeline_apply docstring
            args += [jax.random.fold_in(rng, m), v * S + stage]
        y = fn(*args)
        # the last device finishing the last chunk holds microbatch m's
        # final output; collect it (only stage S-1's buffer survives the
        # masked psum below, so garbage writes on other ranks are inert)
        updated = jax.lax.dynamic_update_index_in_dim(
            outputs, y.astype(outputs.dtype), m, 0
        )
        outputs = jnp.where(active & (v == V - 1), updated, outputs)
        buf = jax.lax.ppermute(y, mesh_lib.PIPE, perm)
        return (buf, outputs), None

    buf0 = jnp.zeros_like(x_mb[0])
    out0 = jnp.zeros_like(x_mb)
    (_, outputs), _ = jax.lax.scan(
        tick, (buf0, out0), jnp.arange(M * V + S - 1)
    )
    # broadcast stage S-1's outputs to every pipe rank (masked psum); the
    # other ranks' buffers hold zeros/garbage masked to zero above
    outputs = jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs))
    return jax.lax.psum(outputs, mesh_lib.PIPE)


def microbatch(x: jax.Array, n_microbatches: int) -> jax.Array:
    """[B, ...] -> [M, B//M, ...] with STRIDED assignment (microbatch m
    takes rows m, M+m, 2M+m, ...): a device owning a contiguous batch
    slice keeps exactly its own rows in every microbatch, so the
    (data, fsdp) sharding lands on dim 1 with no cross-device movement —
    a contiguous split would shard the M dim instead and force an
    all-to-all at pipeline_apply's shard_map boundary."""
    B = x.shape[0]
    if B % n_microbatches:
        raise ValueError(f"batch {B} not divisible by microbatches {n_microbatches}")
    return x.reshape(B // n_microbatches, n_microbatches, *x.shape[1:]).swapaxes(0, 1)


def unmicrobatch(y: jax.Array) -> jax.Array:
    """Inverse of :func:`microbatch` (restores original row order)."""
    return y.swapaxes(0, 1).reshape(y.shape[0] * y.shape[1], *y.shape[2:])
