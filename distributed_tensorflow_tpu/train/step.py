"""The train-step engine: one jit-compiled SPMD program per step.

This is the structural replacement for the reference's entire per-step
machinery (SURVEY.md §3.1): ``SyncReplicasOptimizer.apply_gradients``'s
per-variable ConditionalAccumulators, the sync token FIFOQueue, the chief's
QueueRunner thread, and the two gRPC round-trips per variable per step all
collapse into a single XLA-compiled function — gradients are aggregated by
collectives the compiler places on ICI, and the barrier is the collective
itself. The host does one dispatch per step (the inversion described in
SURVEY.md §3.3).

Design notes
------------
- **GSPMD, not explicit collectives**: the step is ``jax.jit``-ed over a
  mesh; input arrays carry NamedShardings (batch over (data, fsdp), params
  per the sharding rules), and XLA inserts the gradient all-reduce /
  reduce-scatter. The explicit-collective path (shard_map) is reserved for
  schedules XLA can't infer (pipeline, ring attention).
- **Gradient accumulation** is the legitimate descendant of the reference's
  ConditionalAccumulator ($TF data_flow_ops.py:1386): microbatches are
  scanned on-device in f32, no staleness protocol needed.
- **State**: a single pytree (step, params, opt_state, model_state, rng) —
  the global_step variable, PS-resident parameters, and slot variables of
  the reference, as one shardable object.
- **RNG**: the state holds one base key; each step folds in the step number,
  so resume-from-checkpoint reproduces the exact dropout stream.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh

from ..parallel import sharding as sh

# loss_fn(params, model_state, batch, rng) -> (loss, (new_model_state, aux_metrics))
LossFn = Callable[[Any, Any, Any, jax.Array], tuple[jax.Array, tuple[Any, dict]]]


@struct.dataclass
class TrainState:
    """Everything that must survive a step / a checkpoint / a preemption."""

    step: jax.Array  # i32 scalar — replaces the global_step variable
    params: Any
    opt_state: Any
    model_state: Any  # mutable collections (e.g. BatchNorm stats); {} if none
    rng: jax.Array  # base key; per-step keys are fold_in(rng, step)


#: re-export — the optax spec-inheritance logic lives at the sharding
#: seam now (parallel/sharding.py), next to every other spec producer
opt_state_specs = sh.opt_state_specs


def state_specs(state_shape: TrainState, param_specs: Any) -> TrainState:
    """PartitionSpec tree covering the whole TrainState."""
    return TrainState(
        step=sh.REPLICATED,
        params=param_specs,
        opt_state=sh.opt_state_specs(
            state_shape.opt_state, state_shape.params, param_specs),
        model_state=sh.replicated_specs(state_shape.model_state),
        rng=sh.REPLICATED,
    )


def init_train_state(
    init_fn: Callable[[jax.Array], tuple[Any, Any]],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    rng: jax.Array,
    *,
    param_rules: sh.PathRules | None = None,
    param_specs: Any | None = None,
    fsdp: bool = False,
    fsdp_min_size: int = 2**14,
) -> tuple[TrainState, TrainState]:
    """Build a fully sharded TrainState without ever materializing it
    unsharded (critical when params exceed one chip's HBM).

    Returns ``(state, spec_tree)``. Replaces the reference's chief-side
    ``Scaffold``/init_op dance ($TF monitored_session.py:52): there is no
    chief — every process runs the same jit-ed init and XLA places shards.

    ``param_rules``: a sharding.PartitionRules table (strict
    match_partition_rules contract) or legacy regex path rules
    (sharding.specs_from_path_rules);
    ``param_specs``: explicit spec tree (wins over rules);
    ``fsdp``: additionally shard unmatched params via auto_fsdp_specs.
    """

    def full_init(key):
        params, model_state = init_fn(key)
        opt_state = tx.init(params)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
            model_state=model_state,
            rng=key,
        )

    abstract = jax.eval_shape(full_init, rng)
    if param_specs is None:
        if param_rules is not None:
            param_specs = sh.specs_from_rules(abstract.params, param_rules)
        else:
            param_specs = sh.replicated_specs(abstract.params)
    if fsdp:
        auto = sh.auto_fsdp_specs(abstract.params, mesh, min_size=fsdp_min_size)
        param_specs = sh.merge_specs(param_specs, auto)
    specs = state_specs(abstract, param_specs)
    shardings = sh.tree_shardings(mesh, specs)
    state = jax.jit(full_init, out_shardings=shardings)(rng)
    return state, specs


@dataclasses.dataclass(frozen=True)
class StepOptions:
    grad_accum_steps: int = 1
    # Debug signals are OPT-IN: each is a full extra pass over every gradient
    # leaf per step (real HBM bandwidth on conv nets). NaNGuard works without
    # them — it reads the loss, which the host fetches anyway, and a NaN in
    # the grads poisons the loss within one step.
    compute_grad_norm: bool = False
    check_grads_finite: bool = False
    clip_grad_norm: float | None = None  # applied here, before tx
    # No-update-on-nonfinite (docs/resilience.md "Numeric anomalies"): when
    # the step's loss or any gradient leaf is non-finite, the compiled step
    # returns the OLD state bit-identically — step counter included — via a
    # device-side select over the update (apply_if_finite-style), and
    # reports a per-step ``nonfinite`` flag in its metrics. SAFETY is pure
    # device work: poisoned params never exist and donation stays legal
    # without any host check before the update. CONSUMING the flag on the
    # host (resilience/anomaly.AnomalyPolicy skip/blame/quarantine, or the
    # Trainer's fail-fast check when no policy is wired) fetches one
    # scalar per step — that read trades the dispatch-ahead overlap for
    # exactness (``step_nonfinite``). Covers both the single-batch and
    # the grad-accumulation scan paths.
    skip_nonfinite: bool = False


def make_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    options: StepOptions = StepOptions(),
) -> Callable[[TrainState, Any], tuple[TrainState, dict]]:
    """Build the (un-jitted) train step. Wrap with ``jax.jit(...,
    donate_argnums=0)`` — the Trainer does this — so the old state's buffers
    are reused in place, the TPU analog of the reference's in-place PS
    variable updates."""

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    accum = options.grad_accum_steps

    def train_step(state: TrainState, batch: Any) -> tuple[TrainState, dict]:
        step_rng = jax.random.fold_in(state.rng, state.step)

        if accum == 1:
            (loss, (model_state, aux)), grads = grad_fn(
                state.params, state.model_state, batch, step_rng
            )
        else:
            # Microbatch scan: mean-of-means gradient, sequential model_state
            # threading. The descendant of ConditionalAccumulator semantics
            # minus the staleness protocol (SURVEY.md §2b).
            def to_micro(x):
                b = x.shape[0]
                return x.reshape(accum, b // accum, *x.shape[1:])

            micro = jax.tree.map(to_micro, batch)
            keys = jax.random.split(step_rng, accum)

            def body(carry, xs):
                g_acc, l_acc, mstate = carry
                mb, key = xs
                (loss_i, (mstate, aux_i)), g_i = grad_fn(
                    state.params, mstate, mb, key
                )
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / accum, g_acc, g_i
                )
                return (g_acc, l_acc + loss_i / accum, mstate), aux_i

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (grads, loss, model_state), aux_stack = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32), state.model_state),
                (micro, keys),
            )
            aux = jax.tree.map(lambda x: x.mean(axis=0), aux_stack)

        metrics = {"loss": loss.astype(jnp.float32), **aux}

        if options.compute_grad_norm or options.clip_grad_norm:
            gnorm = optax.global_norm(grads)
            metrics["grad_norm"] = gnorm
        if options.clip_grad_norm:
            scale = jnp.minimum(1.0, options.clip_grad_norm / (gnorm + 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)

        if options.check_grads_finite:
            # NaN guard signal, computed on-device and piggybacked on the step
            # output (SURVEY.md §5.5) — the NanTensorHook replacement. Off by
            # default: NaNGuard's loss check catches the same failures one
            # step later at zero cost.
            metrics["grads_finite"] = jnp.all(
                jnp.asarray([jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads)])
            ).astype(jnp.float32)
        elif options.compute_grad_norm or options.clip_grad_norm:
            # Free same-step guard: the global norm is already computed,
            # and one non-finite gradient leaf poisons it — so its
            # finiteness IS grads-finiteness, at zero extra passes. This
            # closes the "NaNGuard fires one step late" window whenever
            # grad-norm/clipping is on.
            metrics["grads_finite"] = jnp.isfinite(gnorm).astype(jnp.float32)

        if options.skip_nonfinite:
            # One reduce per gradient leaf + the loss: the exact
            # apply_if_finite predicate. Computed BEFORE tx.update so the
            # flag reflects the step's inputs, not NaNs the optimizer math
            # may have laundered (Adam's eps can turn inf into finite).
            finite = [jnp.isfinite(loss)] + [
                jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads)
            ]
            ok = jnp.all(jnp.stack(finite))
            metrics["nonfinite"] = 1.0 - ok.astype(jnp.float32)

        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1,
            params=params,
            opt_state=opt_state,
            model_state=model_state,
            rng=state.rng,
        )
        if options.skip_nonfinite:
            # Select OLD vs NEW per leaf on device: a non-finite step is a
            # no-op — params, opt_state, model_state AND the step counter
            # stay bit-identical, so the batch is provably droppable (the
            # trajectory becomes a pure function of (seed, quarantine
            # set); data/pipeline.QuarantineFilter is the other half).
            # Leaves the candidate state shares with the old one (rng)
            # pass through untouched — jnp.where on them would choke on
            # non-numeric leaves like typed PRNG keys.
            new_state = jax.tree.map(
                lambda new, old: new if new is old else jnp.where(ok, new, old),
                new_state, state,
            )
        return new_state, metrics

    return train_step


def step_nonfinite(metrics) -> bool:
    """Host-side read of the per-step ``nonfinite`` flag a
    ``skip_nonfinite`` step piggybacks on its metrics (False when the
    flag is absent). One scalar fetch — it blocks until the step
    completes, the one place flag exactness costs the dispatch-ahead
    overlap. Every consumer (the Trainer loop's fail-fast check,
    NaNGuard, AnomalyPolicy) reads through here, so the flag's encoding
    has a single read-side contract next to its producer."""
    import numpy as np

    flag = metrics.get("nonfinite")
    return flag is not None and float(np.asarray(flag)) != 0.0


def make_eval_step(eval_fn):
    """eval_fn(params, model_state, batch) -> dict of summed metrics."""

    def eval_step(state: TrainState, batch):
        return eval_fn(state.params, state.model_state, batch)

    return eval_step


def jit_train_step(step_fn, mesh: Mesh, spec_tree: TrainState):
    """jit with explicit state shardings (batch/output shardings inferred).

    Donation makes the update in-place in HBM — without it, peak memory
    doubles (params + new params live simultaneously)."""
    state_shardings = sh.tree_shardings(mesh, spec_tree)
    return jax.jit(
        step_fn,
        in_shardings=(state_shardings, None),
        out_shardings=(state_shardings, None),
        donate_argnums=0,
    )
