"""Workload runner — the L4 'train script' layer (SURVEY.md §1), one
implementation for all workloads.

A reference train script did: parse flags → ClusterSpec/Server → device
placement scope → model fn → SyncReplicasOptimizer → MonitoredTrainingSession
loop (SURVEY.md §3.1). `run()` is that whole stack TPU-native: config →
mesh → sharded init-or-restore → jit step → callback loop. Each workload
module contributes a preset config and a builder; everything else is shared.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Iterable

import jax
import numpy as np

from ..data import DataConfig, Prefetcher
from ..parallel import MeshSpec, build_mesh, cluster, describe
from ..train import (
    CheckpointConfig,
    Checkpointer,
    OptimizerConfig,
    ShardedEvaluator,
    StepOptions,
    Trainer,
    callbacks as cb,
    derive_metrics,
    init_or_restore,
    init_train_state,
    make_optimizer,
    make_train_step,
)
from ..utils import config as config_lib

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class TrainSection:
    num_steps: int = 1000
    log_every: int = 100
    grad_accum_steps: int = 1
    seed: int = 0
    eval_every: int = 0  # 0 = no mid-train eval
    # Pipeline schedule (engaged when mesh.pipe > 1 on a workload that
    # supports it): microbatches per step (0 = auto, 2x stages) and the
    # interleaved-schedule virtual-chunk count (1 = plain GPipe).
    pipeline_microbatches: int = 0
    pipeline_virtual: int = 1
    # Pipeline-memory guard: before a pipelined run
    # on an accelerator backend, estimate the per-device working set via
    # XLA's memory analysis (CPU-backend subprocess, layout-portable to
    # ~10% — tools/pipeline_memory_analysis.py) and WARN with the
    # measured mitigation (grad_accum_steps=2) when it presses HBM. The
    # estimate costs one CPU compile (~1-2 min for BERT-base) against a
    # run that is hours; set False to skip it.
    check_pipeline_memory: bool = True
    eval_batches: int = 16
    profile: bool = False
    profile_dir: str = "/tmp/dtf_tpu_profile"
    # Non-empty = write TensorBoard scalar event files there (chief-only,
    # log_every cadence) — the reference's SummarySaverHook surface.
    summary_dir: str = ""
    # Adds grad_norm + grads_finite to the step metrics — an extra pass over
    # every gradient leaf per step; off in production.
    debug_metrics: bool = False
    # > 0: clip gradients to this global norm (the transformer-pretrain
    # standard). Side benefit: the norm's finiteness doubles as a FREE
    # same-step grads_finite signal for NaNGuard (train/step.py), closing
    # the one-step-delayed-loss window without debug_metrics' extra pass.
    clip_grad_norm: float = 0.0
    # Numeric-anomaly defense (docs/resilience.md "Numeric anomalies"):
    # the in-graph no-update-on-nonfinite guard plus the AnomalyPolicy —
    # a non-finite batch is skipped device-side (old state survives
    # bit-identically), blamed by raw (seed, index) into quarantine.json
    # next to the checkpoints, and re-seeked AROUND on every later
    # incarnation. Requires checkpoint.directory (the quarantine file
    # lives there). Trades the dispatch-ahead overlap for the per-step
    # flag fetch; prefetch is bypassed so the blamed index is exact.
    anomaly_defense: bool = False
    # non-finite batches skipped before escalating to the poisoned path
    anomaly_skip_budget: int = 8


@dataclasses.dataclass(frozen=True)
class FleetSection:
    """This process's membership in a FleetSupervisor gang
    (resilience/fleet.py). ``dir`` is the fleet control dir
    (INCARNATION / RESTORE_STEP / SHARD_PLAN / heartbeats); empty =
    standalone run. With ``elastic`` the runner reads the current
    SHARD_PLAN at startup — worker-sharded data via
    ``data/pipeline.ElasticStream``, mesh respec'd through
    ``parallel.rescale_for_world`` — and follows live resizes from the
    step seam (``callbacks.ElasticCallback``). One jax process per fleet
    worker: the worker shard replaces process-count data sharding."""

    dir: str = ""
    worker: int = 0
    elastic: bool = False
    # worker-side budget for an abandoned resize hold. SIZE AT OR ABOVE
    # the fleet's FleetConfig.hold_timeout_s: if the worker gives up
    # first, a legitimate slow resize turns into an attempt restart
    # while the fleet still counts this worker as holding.
    hold_timeout_s: float = 120.0

    def __post_init__(self):
        if self.worker < 0:
            raise ValueError("fleet.worker must be >= 0")
        if self.elastic and not self.dir:
            raise ValueError("fleet.elastic=true needs fleet.dir (the "
                             "SHARD_PLAN lives there)")
        if self.hold_timeout_s <= 0:
            raise ValueError("fleet.hold_timeout_s must be > 0")


@dataclasses.dataclass(frozen=True)
class RunConfig:
    workload: str = "mnist_mlp"
    model: Any = None  # workload-specific config dataclass, set by preset
    cluster: cluster.ClusterConfig = cluster.ClusterConfig()
    mesh: MeshSpec = MeshSpec()
    data: DataConfig = DataConfig()
    optimizer: OptimizerConfig = OptimizerConfig()
    train: TrainSection = TrainSection()
    checkpoint: CheckpointConfig = CheckpointConfig()
    fleet: FleetSection = FleetSection()


@dataclasses.dataclass
class WorkloadParts:
    """What a workload module's build() returns."""

    init_fn: Callable  # rng -> (params, model_state)
    loss_fn: Callable  # engine LossFn
    # start_step -> host-batch iterable; the runner calls it with the
    # restored step so resume continues the data stream, not batch 0.
    dataset_fn: Callable[[int], Iterable] = None
    eval_fn: Callable | None = None
    eval_dataset_fn: Callable[[int], Iterable] | None = None
    flops_per_step: float | None = None  # analytic, for MFU
    param_rules: Any = None  # sharding path rules
    # explicit spec tree (wins over rules — init_train_state contract);
    # the pipelined paths use this for their stacked [S,...] layouts
    param_specs: Any = None
    # workload-supplied optimizer (e.g. a make_multi_optimizer split);
    # None = runner builds one from cfg.optimizer
    tx: Any = None
    fsdp: bool = False
    batch_size: int | None = None  # examples/step for throughput logs
    # Prefix for the eval AUC key (e.g. "train_" when the workload's eval
    # stream draws from the training file — wide_deep ctr: fallback)
    eval_metric_prefix: str = ""
    # Did build() consult cfg.data.eval_dataset? Workloads that honor the
    # flag set this True; the runner rejects an explicit eval_dataset the
    # workload would silently ignore (no silent eval-source degradation).
    consumed_eval_dataset: bool = False
    # cached ShardedEvaluator (train/evaluation.py) — built on first
    # eval so repeated mid-train evals never retrace
    _jit_eval: Any = dataclasses.field(default=None, repr=False)


def _pipeline_memory_guard(cfg: RunConfig, mesh) -> None:
    """Warn before a pipelined transformer run whose estimated per-device
    working set presses v5e HBM.

    The estimator is XLA's own memory analysis of the REAL pipelined
    step, compiled for the CPU backend in a subprocess (allocation sizes
    are layout-portable within ~10% — tools/pipeline_memory_analysis.py
    docstring). The measured grid (artifacts/podshape_r4/
    memory_grid.jsonl) showed the M=64 pod rows NOT fitting, with
    ``train.grad_accum_steps=2`` the tested mitigation (halves the
    per-accumulation-step batch, hence the in-flight microbatch set).
    Best-effort: any estimator failure logs and continues."""
    from ..parallel import mesh as mesh_lib

    pipe = mesh.shape.get(mesh_lib.PIPE, 1)
    if (pipe <= 1 or not cfg.train.check_pipeline_memory
            or not cluster.is_chief()):
        return
    if jax.default_backend() == "cpu":
        return  # test/demo rig: the run itself is the CPU evidence
    from ..models.transformer import TransformerConfig

    if not isinstance(cfg.model, TransformerConfig):
        return  # estimator covers the transformer pipeline paths only
    import json
    import os
    import subprocess
    import sys

    from ..utils import config as config_lib

    data_shards = max(
        1, int(np.prod([mesh.shape.get(ax, 1) for ax in mesh_lib.BATCH_AXES])))
    n_virtual = cfg.train.pipeline_virtual
    req = {
        "model": config_lib.to_dict(cfg.model),
        "S": pipe, "V": n_virtual,
        # the same auto rule the workload builder applies
        "M": cfg.train.pipeline_microbatches or 2 * pipe * n_virtual,
        "batch": cfg.data.global_batch_size // data_shards,
        "seq": cfg.data.seq_len,
        "mlm": cfg.workload != "gpt_lm",
    }
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))),
        "tools", "pipeline_memory_analysis.py")
    # the CPU estimate must never touch the accelerator this process holds
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    try:
        proc = subprocess.run(
            [sys.executable, tool, "--check", json.dumps(req)],
            capture_output=True, text=True, timeout=600, env=env,
        )
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        if row.get("fits_v5e"):
            logger.info("pipeline memory estimate: %.1f GiB/device "
                        "(fits v5e)", row["gib"])
        else:
            logger.warning(
                "pipeline memory estimate %.1f GiB/device EXCEEDS the "
                "~14.4 GiB usable v5e HBM (S=%d V=%d M=%d per-shard "
                "batch %d). Measured mitigation: train.grad_accum_steps"
                "=2 (artifacts/podshape_r4/memory_grid.jsonl; exact-"
                "parity tested). Set train.check_pipeline_memory=false "
                "to silence.", row["gib"], req["S"], req["V"], req["M"],
                req["batch"])
    except Exception as e:  # noqa: BLE001 — guard must never kill a run
        logger.info("pipeline memory estimate unavailable: %s", e)


@dataclasses.dataclass
class RunResult:
    state: Any
    history: list[dict]
    eval_metrics: dict | None
    mesh: Any


def run(cfg: RunConfig, build: Callable[[RunConfig, Any], WorkloadParts],
        extra_callbacks: Iterable[cb.Callback] = ()) -> RunResult:
    """``build(cfg, mesh) -> WorkloadParts``: every workload takes the mesh
    (models embedding collective schedules — seq-parallel attention,
    pipeline stages — need it at construction; others ignore it)."""
    cluster.initialize(cfg.cluster)
    fleet_writer = fleet_plan = None
    mesh_spec = cfg.mesh
    if cfg.fleet.dir:
        from ..parallel import rescale_for_world
        from ..resilience import fleet as fleet_lib

        fleet_writer = fleet_lib.HeartbeatWriter(
            fleet_lib.heartbeat_path(cfg.fleet.dir, cfg.fleet.worker),
            incarnation=fleet_lib.read_incarnation(cfg.fleet.dir))
        if cfg.fleet.elastic:
            fleet_plan = fleet_lib.read_shard_plan(cfg.fleet.dir)
            if fleet_plan is not None:
                # the config's mesh is authored for the NOMINAL fleet;
                # a shrunken gang gets the batch axes rescaled to the
                # surviving world (parameter axes never resize)
                mesh_spec = rescale_for_world(
                    cfg.mesh, fleet_plan.fleet_size or fleet_plan.world,
                    fleet_plan.world)
    mesh = build_mesh(mesh_spec)
    if cluster.is_chief():
        logger.info("mesh: %s", describe(mesh))
        logger.info("config:\n%s", config_lib.to_json(cfg))

    parts = build(cfg, mesh)
    _check_eval_dataset_consumed(cfg, parts)
    _pipeline_memory_guard(cfg, mesh)
    tx = parts.tx if parts.tx is not None else make_optimizer(cfg.optimizer)
    rng = jax.random.PRNGKey(cfg.train.seed)

    ckpt = None
    if cfg.checkpoint.directory:
        # heartbeat: saves beat phase "save" so the fleet's elastic path
        # can tell a mid-checkpoint death (gang-stop) from a clean one
        ckpt = Checkpointer(cfg.checkpoint, mesh, heartbeat=fleet_writer)
        state, specs, restored = init_or_restore(
            ckpt, parts.init_fn, tx, mesh, rng,
            param_rules=parts.param_rules, param_specs=parts.param_specs,
            fsdp=parts.fsdp,
        )
        ckpt.save_config(cfg)
    else:
        state, specs = init_train_state(
            parts.init_fn, tx, mesh, rng,
            param_rules=parts.param_rules, param_specs=parts.param_specs,
            fsdp=parts.fsdp,
        )

    metrics_logger = cb.MetricsLogger(
        every_n=cfg.train.log_every,
        batch_size=parts.batch_size or cfg.data.global_batch_size,
        model_flops_per_step=parts.flops_per_step,
        history=True,
    )
    callbacks: list[cb.Callback] = [metrics_logger, cb.NaNGuard()]
    if cfg.train.summary_dir:
        # after metrics_logger so `last` is fresh at shared cadence
        callbacks.append(cb.SummaryWriter(
            cfg.train.summary_dir, every_n=cfg.train.log_every,
            metrics_logger=metrics_logger,
        ))
    if ckpt is not None:
        callbacks.append(cb.CheckpointCallback(ckpt))
    if cfg.train.profile:
        callbacks.append(cb.Profiler(cfg.train.profile_dir))
    callbacks.extend(extra_callbacks)

    step_fn = make_train_step(
        parts.loss_fn, tx,
        StepOptions(
            grad_accum_steps=cfg.train.grad_accum_steps,
            compute_grad_norm=cfg.train.debug_metrics,
            check_grads_finite=cfg.train.debug_metrics,
            clip_grad_norm=cfg.train.clip_grad_norm or None,
            skip_nonfinite=cfg.train.anomaly_defense,
        ),
    )

    start_step = int(state.step)
    policy = None
    if cfg.train.anomaly_defense and cfg.fleet.elastic:
        raise ValueError(
            "train.anomaly_defense and fleet.elastic are mutually "
            "exclusive: both must own the raw stream cursor (the blame "
            "index and the reshard barrier bind to it) — run the elastic "
            "fleet with the in-graph guard alone, or the anomaly defense "
            "outside an elastic gang")
    if cfg.train.anomaly_defense:
        if not cfg.checkpoint.directory:
            raise ValueError(
                "train.anomaly_defense needs checkpoint.directory — the "
                "quarantine file lives next to the checkpoints")
        from ..data.pipeline import QuarantineFilter
        from ..resilience.anomaly import AnomalyConfig, AnomalyPolicy
        from ..resilience.anomaly import load_quarantine

        # no Prefetcher here: the policy blames through the stream's raw
        # cursor, and a prefetch depth would run it ahead of the step
        # being blamed (data/pipeline.QuarantineFilter docstring)
        data = QuarantineFilter(
            parts.dataset_fn, load_quarantine(cfg.checkpoint.directory),
            start_step=start_step,
        )
        policy = AnomalyPolicy(
            cfg.checkpoint.directory,
            AnomalyConfig(skip_budget=cfg.train.anomaly_skip_budget),
            index_fn=lambda: data.raw,
        )
    elif cfg.fleet.elastic:
        from ..data.pipeline import ElasticStream, WorkerShard
        from ..resilience import fleet as fleet_lib

        from ..parallel import BATCH_AXES, mesh_axis_size

        batch_extent = mesh_axis_size(mesh, BATCH_AXES)

        def _check_world(world: int) -> None:
            # WorkerShard tolerates ragged slices, but the device
            # placement path does not: put_host_batch shards the batch
            # dim over the mesh batch axes, so every worker's slice must
            # be uniform AND divide the mesh's batch-axes extent — fail
            # at config/reshard time with the fix named, not at the
            # first step with a shape error
            if cfg.data.global_batch_size % world != 0:
                raise ValueError(
                    f"data.global_batch_size={cfg.data.global_batch_size} "
                    f"not divisible by elastic world={world}: worker "
                    f"slices must be uniform to shard across the mesh "
                    f"batch axes — pick a global batch divisible by "
                    f"every fleet size the gang can shrink to")
            local = cfg.data.global_batch_size // world
            if local % batch_extent != 0:
                raise ValueError(
                    f"per-worker slice {local} "
                    f"(global_batch_size={cfg.data.global_batch_size} / "
                    f"world={world}) not divisible by the mesh batch-axes "
                    f"extent {batch_extent}: pick a global batch whose "
                    f"per-world slices divide the mesh for every fleet "
                    f"size the gang can shrink to")

        shard = None
        if fleet_plan is not None:
            _check_world(fleet_plan.world)
            rank = fleet_plan.ranks.get(cfg.fleet.worker)
            if rank is not None:
                shard = WorkerShard(rank, fleet_plan.world)

        def _on_reshard(rank, world, at):
            _check_world(world)
            data.reshard(
                WorkerShard(rank, world) if rank is not None else None, at)

        # no Prefetcher: a prefetch depth would run the stream cursor
        # past the barrier a live reshard binds to (ElasticStream
        # docstring — same rule as the anomaly defense's blame cursor)
        data = ElasticStream(parts.dataset_fn, shard,
                             start_index=start_step)
        elastic_client = fleet_lib.ElasticWorker(
            cfg.fleet.dir, cfg.fleet.worker, fleet_writer,
            on_reshard=_on_reshard,
            hold_timeout_s=cfg.fleet.hold_timeout_s)
        if (fleet_plan is not None
                and fleet_plan.phase == fleet_lib.PLAN_STEADY):
            # pre-ack ONLY a steady plan. A PLAN_HOLD naming this worker
            # must go through poll() -> _hold at train start: pre-acking
            # it would skip the barrier handshake and stall the fleet's
            # resize until hold_timeout_s (restarted-worker-races-resize)
            elastic_client.applied_version = fleet_plan.version
            fleet_writer.note_plan(fleet_plan.version, fleet_plan.world)
        # before the CheckpointCallback: a resize hold must land between
        # steps, never between a step and its cadence save
        ckpt_at = next(
            (i for i, c in enumerate(callbacks)
             if isinstance(c, cb.CheckpointCallback)), len(callbacks))
        callbacks.insert(ckpt_at, cb.ElasticCallback(elastic_client))
    else:
        data = Prefetcher(parts.dataset_fn(start_step), depth=2)
    if fleet_writer is not None:
        # first: the heartbeat must record the step even when a later
        # callback raises (PreemptionSaved skips the rest of the round)
        callbacks.insert(0, cb.HeartbeatCallback(fleet_writer))

    trainer = Trainer(step_fn, state, mesh, specs, callbacks=callbacks,
                      anomaly_policy=policy)

    if cfg.train.eval_every > 0 and parts.eval_fn is not None:
        trainer.callbacks.append(_EvalCallback(cfg, parts))

    state = trainer.fit(data, num_steps=cfg.train.num_steps)

    eval_metrics = None
    if parts.eval_fn is not None and parts.eval_dataset_fn is not None:
        eval_metrics = evaluate(
            trainer, parts, cfg.train.eval_batches
        )
        if cluster.is_chief():
            logger.info("final eval: %s", eval_metrics)
    if ckpt is not None:
        ckpt.wait()
        ckpt.close()
    if fleet_writer is not None:
        fleet_writer.close()
    return RunResult(state, metrics_logger.history, eval_metrics, mesh)


def _check_eval_dataset_consumed(cfg: RunConfig, parts: WorkloadParts) -> None:
    """An explicit --data.eval_dataset the workload does not support must
    error, not silently evaluate on the default stream (the same
    no-masquerade rule as wide_deep's train_auc tagging)."""
    # getattr: text workloads swap in TextDataConfig, which defines its
    # own eval convention (held-out token files) and has no such field
    ev = getattr(cfg.data, "eval_dataset", "")
    if ev and not parts.consumed_eval_dataset:
        raise ValueError(
            f"workload {cfg.workload!r} does not support "
            f"data.eval_dataset (got {ev!r}); its eval "
            "stream is workload-defined — drop the flag or use a "
            "workload that honors it (wide_deep)")


def _run_eval(state: Any, mesh, parts: WorkloadParts,
              num_batches: int, step: int | None = None,
              flightrec=None) -> dict:
    """Shared eval loop — DISTRIBUTED: batches shard over the mesh's
    batch axes and every device evaluates its chunk with the full
    weights, with the cross-shard reduction done host-side in a fixed
    order so the result is bit-identical to a serial evaluator
    (train/evaluation.py has the construction). The evaluator (and its
    jitted step) is cached on parts so repeated mid-train evals don't
    retrace. Summed sufficient statistics — scalars AND fixed-size
    arrays (e.g. the AUC histograms, utils/metrics.py) — merge by
    addition; ratio metrics derive via the shared
    ``evaluation.derive_metrics``."""
    if parts._jit_eval is None:
        parts._jit_eval = ShardedEvaluator(parts.eval_fn, mesh,
                                           flightrec=flightrec)
    totals = parts._jit_eval.run(
        state, parts.eval_dataset_fn(num_batches), num_batches, step=step)
    return derive_metrics(totals, parts.eval_metric_prefix)


def evaluate(trainer: Trainer, parts: WorkloadParts, num_batches: int) -> dict:
    """Eval from live trainer state; shares the mesh and runs sharded
    across it (distributed eval — the train state never moves)."""
    return _run_eval(trainer.state, trainer.mesh, parts, num_batches,
                     step=int(trainer.state.step),
                     flightrec=trainer.flightrec)


def evaluate_from_checkpoint(
    cfg: RunConfig, build: Callable[[RunConfig, Any], WorkloadParts],
    num_batches: int | None = None,
) -> dict:
    """Standalone eval-from-checkpoint — no Trainer (SURVEY.md §3.5: the
    reference ran eval single-process from `latest_checkpoint`,
    $TF checkpoint_management.py:329). Restores the latest (or ``step``)
    checkpoint from cfg.checkpoint.directory, runs classification_eval_fn
    over the eval split, returns the metric dict."""
    if not cfg.checkpoint.directory:
        raise ValueError("evaluate_from_checkpoint needs checkpoint.directory")
    cluster.initialize(cfg.cluster)
    mesh = build_mesh(cfg.mesh)
    parts = build(cfg, mesh)
    _check_eval_dataset_consumed(cfg, parts)
    if parts.eval_fn is None or parts.eval_dataset_fn is None:
        raise ValueError(f"workload {cfg.workload!r} has no eval surface")

    # same tx resolution as run(): the restored opt_state's structure
    # must match the workload's optimizer (e.g. wide_deep's multi split)
    tx = parts.tx if parts.tx is not None else make_optimizer(cfg.optimizer)
    ckpt = Checkpointer(cfg.checkpoint, mesh)
    try:
        state, _, restored = init_or_restore(
            ckpt, parts.init_fn, tx, mesh, jax.random.PRNGKey(cfg.train.seed),
            param_rules=parts.param_rules, param_specs=parts.param_specs,
            fsdp=parts.fsdp,
        )
        if not restored:
            raise FileNotFoundError(
                f"no checkpoint found in {cfg.checkpoint.directory}"
            )

        n = num_batches if num_batches is not None else cfg.train.eval_batches
        metrics = _run_eval(state, mesh, parts, n, step=int(state.step))
        metrics["step"] = int(state.step)
        if cluster.is_chief():
            logger.info("eval from checkpoint @ step %d: %s",
                        int(state.step), metrics)
        return metrics
    finally:
        ckpt.close()


class _EvalCallback(cb.Callback):
    """Periodic distributed eval from the step seam. The eval pass runs
    sharded over the training mesh (no state movement, no second
    evaluator process) and its wall time is reported to every
    ``note_pause``-aware callback so the cadence meters —
    ``train_step_seconds``, steps/sec, the goodput ledger — keep
    measuring the train loop, not the eval pauses interleaved with it."""

    def __init__(self, cfg, parts, clock=time.perf_counter):
        self.cfg, self.parts = cfg, parts
        self.clock = clock

    def on_step_end(self, trainer, step, metrics):
        if step % self.cfg.train.eval_every == 0:
            t0 = self.clock()
            m = evaluate(trainer, self.parts, self.cfg.train.eval_batches)
            pause = self.clock() - t0
            for other in trainer.callbacks:
                note = getattr(other, "note_pause", None)
                if note is not None:
                    note(pause)
            if cluster.is_chief():
                logger.info("eval @ step %d: %s", step, m)
