"""Single-process chaos worker (tests/test_resilience.py, tools/chaos_smoke.py).

Trains a tiny MLP on batches derived deterministically from the GLOBAL
step index, with an optional FaultPlan. The kill→restart→resume oracle:

    run A (straight):    --steps N                → params_a.npz
    run B (interrupted): --steps N --sigterm-at K → PreemptionSaved exit
    run C (resume, same workdir as B): --steps N  → params_b.npz

A and C must produce BIT-IDENTICAL params: the preemption save captured
the full state exactly, and resume replays exactly the batches the
straight run would have seen (batch i feeds global step i, i seeded).

With ``--supervise`` the whole run goes through the in-process
``resilience.Supervisor`` instead: SIGTERM → coordinated save →
in-process restart, ``--corrupt-at-restart`` truncates the newest
checkpoint at the restart boundary (fallback restore must quarantine it
and land on an older valid step), and transient data faults are absorbed
by a re-seeking ``RetryingIterator`` — one process, every recovery path.

With ``--fleet`` the process is ONE WORKER of a FleetSupervisor gang
(resilience/fleet.py): it reads the fleet incarnation and restore
ceiling from ``--fleet-dir``, heartbeats to its per-worker file through
the Supervisor attempt seam + HeartbeatCallback step seam, and speaks
the fleet exit-code protocol (0 done / EXIT_PREEMPTED / EXIT_FAILED).
Injected faults are gated on ``--fault-incarnation`` (default 1): the
incarnation counter is the cross-process analog of the plan's
fire-once state, so a relaunched gang does not re-injure itself.

Markers on stdout (the drivers assert on these):
    CHAOS-DONE step=N        run reached the target step
    CHAOS-PREEMPTED step=K   clean PreemptionSaved exit, checkpoint at K
    CHAOS-DATAFAULT saved=K  injected IOError; emergency checkpoint at K
    CHAOS-SUPERVISED step=N restarts=R finite=F quarantined=Q ordered=O
                             supervised run finished; F/Q/O are 0/1 flags
                             (O: flight-recorder timeline causal order)
    CHAOS-ANOMALY skipped=N quarantined=I,J refused=R
                             numeric-anomaly defense (--anomaly): batches
                             skipped in-graph, quarantine-file indices,
                             R=1 if any save was refused by validation
    CHAOS-POSTMORTEM path=P events=N ordered=O
                             flight recorder dumped to P (--flightrec)
    CHAOS-GOODPUT fraction=F productive_s=P wall_s=W ok=K
                             goodput gauge vs measured wall-clock
    FLEET-DONE step=N incarnation=K restarts=R
                             fleet worker reached the target step
    FLEET-PREEMPTED step=K   fleet worker exited via a preemption save
    FLEET-FAILED cause=C     fleet worker's in-process supervision exhausted
    FLEET-DYING step=K       scripted --die-at hard exit (elastic rounds)

With ``--elastic`` the worker additionally follows the fleet's
SHARD_PLAN (resilience/fleet.ElasticWorker): it pauses at resize
barriers, acknowledges plans through its heartbeat, and appends every
applied ``(rank, world, at)`` to ``<workdir>/reshard_log.jsonl`` — the
consistency oracle the elastic E2E reads. The rig is collective-free,
so every worker trains on the FULL global batch (the stand-in for the
data-parallel allreduce); the recorded schedule, not the tensors, is
what a resize changes here.
"""

import argparse
import os
import sys

# must precede any jax import in this process
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
# persistent compile cache off, mirroring tests/conftest.py: this worker
# exists to catch silently-wrong resumes, so it shares no state with
# earlier runs
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def global_step_batch(i: int) -> dict:
    """The batch that feeds global step ``i`` — a pure function of i, so
    straight and resumed runs see identical data."""
    rng = np.random.RandomState(1000 + i)
    return {
        "image": rng.randn(8, 8).astype(np.float32),
        "label": rng.randint(0, 4, 8).astype(np.int32),
    }


def _supervised(args, mesh, model, tx) -> int:
    """One supervised run: faults from the CLI become a FaultPlan, every
    recovery path (retrying data, preemption restart, fallback restore)
    runs in THIS process under resilience.Supervisor — and the flight
    recorder + goodput ledger must agree with what actually happened:
    the postmortem timeline is asserted to contain the injected fault,
    the restart, and the fallback restore IN CAUSAL ORDER, and the
    exported ``goodput_fraction`` gauge to equal productive-step seconds
    over total wall-clock within tolerance."""
    import logging
    import time

    import optax  # noqa: F401  (kept symmetric with main's imports)

    from distributed_tensorflow_tpu.data.pipeline import (
        QuarantineFilter, RetryingIterator,
    )
    from distributed_tensorflow_tpu.models import common
    from distributed_tensorflow_tpu.obs import flightrec as fr
    from distributed_tensorflow_tpu.obs import goodput
    from distributed_tensorflow_tpu.obs.registry import default_registry
    from distributed_tensorflow_tpu.resilience import (
        AnomalyConfig, AnomalyPolicy, CorruptCheckpoint, FaultPlan,
        NaNBatch, RetryPolicy, Sigterm, Supervisor, SupervisorConfig,
        TransientIOError, load_quarantine,
    )
    from distributed_tensorflow_tpu.train import (
        CheckpointConfig, Checkpointer, StepOptions, Trainer,
        callbacks as cb, init_or_restore, make_train_step,
    )

    faults = []
    if args.sigterm_at is not None:
        faults.append(Sigterm(args.sigterm_at))
    if args.transient_io_at is not None:
        faults.append(TransientIOError(args.transient_io_at, times=2))
    if args.corrupt_at_restart:
        faults.append(CorruptCheckpoint(restart=1))
    if args.nan_at is not None:
        # recurring: the index is bad on EVERY fetch, every incarnation —
        # only the quarantine-aware stream never fetching it ends it
        faults.append(NaNBatch(args.nan_at, recur=True))
    plan = FaultPlan(tuple(faults))
    loss_fn = common.classification_loss_fn(model)

    # "validate_before_save never refuses a save" is part of the anomaly
    # acceptance: the in-graph guard means poisoned params never exist
    refused = {"n": 0}

    class _RefusalCounter(logging.Handler):
        def emit(self, record):
            if "refusing to checkpoint" in record.getMessage():
                refused["n"] += 1

    logging.getLogger(
        "distributed_tensorflow_tpu.train.checkpoint"
    ).addHandler(_RefusalCounter())

    def batches_from(i0: int):
        i = i0
        while True:
            i += 1
            yield global_step_batch(i)

    def build(restart_index: int):
        ckpt = Checkpointer(
            CheckpointConfig(directory=args.workdir, save_interval_steps=2,
                             async_save=False, preemption_check_every=1),
            mesh,
        )
        state, specs, _ = init_or_restore(
            ckpt, common.make_init_fn(model, (8,)), tx, mesh,
            jax.random.PRNGKey(0), fallback=True,
        )
        start = int(state.step)

        def retrying(raw):
            return RetryingIterator(
                lambda i: plan.wrap(batches_from(i), start=i),
                RetryPolicy(max_attempts=4, base_s=0.0, jitter=0.0),
                start_index=raw, sleep=lambda s: None,
            )

        policy = None
        if args.anomaly:
            # quarantine holes re-read from disk at every attempt
            # boundary; the policy blames via the stream's raw cursor
            data = QuarantineFilter(retrying, load_quarantine(args.workdir),
                                    start_step=start)
            policy = AnomalyPolicy(
                args.workdir, AnomalyConfig(skip_budget=args.skip_budget),
                index_fn=lambda: data.raw,
            )
        else:
            data = retrying(start)
        trainer = Trainer(
            make_train_step(loss_fn, tx,
                            StepOptions(skip_nonfinite=args.anomaly)),
            state, mesh, specs,
            # telemetry FIRST: maybe_save raises PreemptionSaved from
            # CheckpointCallback, skipping later callbacks for that step
            callbacks=[cb.TelemetryCallback(every_n=10 ** 6),
                       cb.CheckpointCallback(ckpt), plan.callback()],
            anomaly_policy=policy,
        )
        return trainer, data, ckpt

    sup = Supervisor(
        build, num_steps=args.steps,
        cfg=SupervisorConfig(max_restarts=args.max_restarts,
                             backoff=RetryPolicy(base_s=0.0, jitter=0.0)),
        on_restart=[plan.restart_hook(args.workdir)],
        sleep=lambda s: None,
    )
    # reviewed: measuring REAL elapsed wall time is this oracle's job —
    # wall_s is the reference the goodput ledger is checked against, not
    # a trajectory input (params stay bit-identical regardless)
    t_run0 = time.monotonic()  # dtflint: disable=wall-clock-in-seam
    state = sup.run()
    wall_s = time.monotonic() - t_run0  # dtflint: disable=wall-clock-in-seam
    leaves = [np.asarray(x) for x in
              jax.tree.leaves(jax.device_get(state.params))]
    finite = all(np.isfinite(x).all() for x in leaves)
    quarantined = os.path.isdir(os.path.join(args.workdir, ".corrupt"))
    if args.out:
        np.savez(args.out, **{f"p{i}": x for i, x in enumerate(leaves)})

    # -- flight-recorder causal-order assertion (ISSUE 6 acceptance) ------
    events = fr.default_recorder().events()
    ordered = True
    if args.sigterm_at is not None and args.corrupt_at_restart:
        # the postmortem timeline must tell the recovery story in order:
        # injected SIGTERM → preemption (emergency) checkpoint → restart
        # → corruption fault at the boundary → quarantine → fallback
        # restore onto an older valid step
        ordered = fr.contains_in_order(events, [
            ("fault_fired", {"fault": "sigterm"}),
            ("ckpt_save", {"trigger": "preemption"}),
            ("sup_restart", {}),
            ("fault_fired", {"fault": "ckpt_corrupt"}),
            ("ckpt_quarantine", {}),
            ("ckpt_restore", {"fallback": True}),
        ])
    if args.nan_at is not None and args.anomaly:
        # the anomaly-defense causal chain: recurring bad batch fired →
        # skipped in-graph → blamed into the quarantine file — and, when
        # a SIGTERM also restarts the run, the recovery restores and
        # replays around the hole (tools/chaos_smoke.py nan-blame round)
        specs = [
            ("fault_fired", {"fault": "nan_batch"}),
            ("anomaly_skip", {"index": args.nan_at}),
            ("anomaly_blame", {"index": args.nan_at}),
        ]
        if args.sigterm_at is not None:
            specs += [("ckpt_save", {"trigger": "preemption"}),
                      ("sup_restart", {}), ("ckpt_restore", {})]
        ordered = ordered and fr.contains_in_order(events, specs)
    if args.flightrec:
        fr.default_recorder().dump(args.flightrec, reason="chaos_worker")
        print(f"CHAOS-POSTMORTEM path={args.flightrec} "
              f"events={len(events)} ordered={int(ordered)}", flush=True)

    # -- goodput accounting vs real wall-clock (ISSUE 6 acceptance) -------
    reg = default_registry()
    productive = reg.total(goodput.PRODUCTIVE_SECONDS)
    frac_gauge = reg.get(goodput.GOODPUT_FRACTION)
    frac = frac_gauge.value if frac_gauge is not None else float("nan")
    # the tracked buckets partition sup.run()'s wall time up to small
    # untracked slivers (classification, final save, ckpt.close), so the
    # exported fraction must track productive/wall within tolerance
    goodput_ok = (0.0 < frac <= 1.0
                  and abs(frac - productive / wall_s) <= 0.15)
    print(
        f"CHAOS-GOODPUT fraction={frac:.4f} productive_s={productive:.4f} "
        f"wall_s={wall_s:.4f} ok={int(goodput_ok)}", flush=True,
    )

    ok = (int(state.step) == args.steps and finite and ordered
          and goodput_ok)
    if args.anomaly:
        q = sorted(load_quarantine(args.workdir))
        m = reg.get("anomaly_skipped_batches_total", cause="nonfinite")
        print(
            f"CHAOS-ANOMALY skipped={int(m.value if m else 0)} "
            f"quarantined={','.join(map(str, q)) or '-'} "
            f"refused={refused['n']}",
            flush=True,
        )
        ok = ok and refused["n"] == 0
    print(
        f"CHAOS-SUPERVISED step={int(state.step)} restarts={sup.restarts} "
        f"finite={int(finite)} quarantined={int(quarantined)} "
        f"ordered={int(ordered)}",
        flush=True,
    )
    return 0 if ok else 1


def _fleet(args, mesh, model, tx) -> int:
    """One fleet-gang worker: in-process Supervisor for transient/
    poisoned/stalled failures, but PREEMPTION exits the process (the
    FleetSupervisor owns process-level restarts), heartbeats through
    both production seams, restore capped at the fleet's common-step
    ceiling."""
    import optax  # noqa: F401  (kept symmetric with main's imports)

    from distributed_tensorflow_tpu.models import common
    from distributed_tensorflow_tpu.resilience import (
        AsyncCommitKill, ControlPlanePartition, FaultPlan, Hang, PodOutage,
        RetryPolicy, Sigterm, SlowControlPlane, SlowWriter,
        Supervisor, SupervisorConfig, SupervisorExhausted,
        fleet as fleet_lib, podfleet as podfleet_lib,
    )
    from distributed_tensorflow_tpu.resilience.supervisor import (
        POISONED, STALLED, TRANSIENT,
    )
    from distributed_tensorflow_tpu.train import (
        CheckpointConfig, Checkpointer, StepOptions, Trainer,
        callbacks as cb, init_or_restore, make_train_step,
    )

    class _DieAt(cb.Callback):
        """Hard, uncoordinated death at an exact global step — the
        elastic round's scripted fault. os._exit skips every handler
        and atexit hook: no preemption save, no final heartbeat — the
        fleet sees a raw nonzero exit (classified transient). The
        launcher owns the schedule (pass --die-at only to the launch
        that should die)."""

        def __init__(self, step):
            self.step = step

        def on_step_end(self, trainer, step, metrics):
            if step == self.step:
                print(f"FLEET-DYING step={step}", flush=True)
                os._exit(86)

    class _StepSleep(cb.Callback):
        """Slow the loop so real-subprocess elastic rounds overlap: the
        members must still be training when the replacement comes up.
        Pure pacing — wall time never feeds the trajectory."""

        def __init__(self, seconds):
            self.seconds = seconds

        def on_step_end(self, trainer, step, metrics):
            import time

            time.sleep(self.seconds)

    from distributed_tensorflow_tpu.obs import fleetview, flightrec as fr

    incarnation = fleet_lib.read_incarnation(args.fleet_dir)
    # pod mode (resilience/podfleet.py): --fleet-dir is one pod's
    # subdirectory and the GLOBAL_EPOCH file lives one level up; the
    # (global_epoch, pod_incarnation) pair is the two-level fence, so
    # fault flags are additionally gated on --fault-epoch — a pod
    # relaunched under a NEW epoch never re-injures itself even though
    # its per-pod incarnation counter restarted
    epoch = None
    if args.pod is not None:
        epoch = podfleet_lib.read_global_epoch(
            os.path.dirname(os.path.abspath(args.fleet_dir)))
    writer = fleet_lib.HeartbeatWriter(
        fleet_lib.heartbeat_path(args.fleet_dir, args.worker_index),
        incarnation=incarnation,
        # pod mode pulses: the partition-fencing judgment (pod
        # supervisor: frozen heartbeat + live pid = fenced, not dead)
        # is only sound when silence really means partition — a pulsed
        # writer beats through compile/restore windows, so the ONLY
        # thing that freezes the file is the control plane itself
        pulse_interval_s=0.5 if args.pod is not None else None,
    )
    # fleet observatory (obs/fleetview.py): periodic telemetry snapshots
    # next to the heartbeat, and a flight-recorder dump on every exit
    # path — identity-stamped so postmortem.py --merge can align this
    # process's clock with the fleet's
    exporter = fleetview.SnapshotExporter(
        fleetview.fleetsnap_path(args.fleet_dir, args.worker_index),
        worker=args.worker_index, incarnation=incarnation)

    def dump_flightrec() -> None:
        if not args.flightrec_dir:
            return
        os.makedirs(args.flightrec_dir, exist_ok=True)
        stem = (f"flightrec-p{args.pod}w{args.worker_index}i{incarnation}"
                if args.pod is not None
                else f"flightrec-w{args.worker_index}i{incarnation}")
        base = os.path.join(args.flightrec_dir, stem)
        # never clobber: an elastic replacement reuses (worker,
        # incarnation), and overwriting would destroy the dead
        # process's dump — the one artifact the merge exists to
        # explain. Two dumps for one slot make the merge fail LOUDLY
        # with a label collision instead, which is the truthful outcome.
        path, n = f"{base}.jsonl", 0
        while os.path.exists(path):
            n += 1
            path = f"{base}-{n}.jsonl"
        extra = {"worker": args.worker_index, "incarnation": incarnation}
        if args.pod is not None:
            extra["pod"] = args.pod
        fr.default_recorder().dump(
            path, reason="fleet_worker_exit", extra=extra)
    ceiling = fleet_lib.read_restore_step(args.fleet_dir)
    elastic_client = None
    if args.elastic:
        plan = fleet_lib.read_shard_plan(args.fleet_dir)
        if plan is not None and args.worker_index not in plan.ranks:
            # we are a catching-up replacement (elastic shrink relaunch),
            # not a gang-restarted member: any RESTORE_STEP on disk
            # belongs to an earlier gang restart and must not roll our
            # restore back below our own newest valid step
            ceiling = None
            if args.p2p_catchup:
                # ask a live survivor for its newest valid step before
                # building: a successful import becomes OUR newest valid
                # step, so the restore below lands on it and the
                # deterministic replay shrinks to the tail the survivor
                # had not yet checkpointed. No answer within the budget
                # = replay from our own newest, exactly as before.
                fleet_lib.request_catchup(
                    args.fleet_dir, args.worker_index, incarnation,
                    args.workdir, budget_s=args.catchup_budget)

        # replica-mode reshard seam: the collective-free rig trains
        # every worker on the FULL global batch (the stand-in for the
        # data-parallel allreduce), so a reshard changes no tensor —
        # the realized schedule is recorded for the E2E consistency
        # oracle instead (same (world, barrier) sequence on every
        # survivor, ranks a bijection)
        reshard_log = os.path.join(args.workdir, "reshard_log.jsonl")

        def on_reshard(rank, world, at):
            import json

            os.makedirs(args.workdir, exist_ok=True)
            with open(reshard_log, "a") as f:
                f.write(json.dumps(
                    {"rank": rank, "world": world, "at": at,
                     "incarnation": incarnation}) + "\n")

        elastic_client = fleet_lib.ElasticWorker(
            args.fleet_dir, args.worker_index, writer,
            on_reshard=on_reshard,
            # serve peer catch-up requests from the step seam and from
            # inside resize-barrier holds (p2p rounds only)
            ckpt_dir=args.workdir if args.p2p_catchup else None)
    faults = []
    # the incarnation counter is the cross-process fired-state: a gang
    # relaunched after this fault must not re-fire it; under a pod
    # coordinator the gate is TWO-level — (--fault-epoch,
    # --fault-incarnation) — because a pod restart resets neither alone
    gate = incarnation == args.fault_incarnation
    if args.fault_epoch is not None:
        gate = gate and epoch == args.fault_epoch
    if gate:
        if args.hang_at is not None:
            faults.append(Hang(args.hang_at))
        if args.sigterm_at is not None:
            faults.append(Sigterm(args.sigterm_at))
        if args.async_kill_at is not None:
            faults.append(AsyncCommitKill(args.async_kill_at))
        if args.slow_writer_at is not None:
            faults.append(SlowWriter(args.slow_writer_at,
                                     delay_s=args.slow_writer_delay))
        if args.pod_outage_at is not None:
            faults.append(PodOutage(args.pod_outage_at))
        if args.partition_at is not None:
            faults.append(ControlPlanePartition(
                args.partition_at, steps=args.partition_steps))
        if args.slow_beat_at is not None:
            faults.append(SlowControlPlane(
                args.slow_beat_at, delay_s=args.slow_beat_delay,
                steps=args.slow_beat_steps))
    plan = FaultPlan(tuple(faults))
    loss_fn = common.classification_loss_fn(model)

    def batches_from(i0: int):
        i = i0
        while True:
            i += 1
            yield global_step_batch(i)

    def build(restart_index: int):
        ckpt = Checkpointer(
            CheckpointConfig(directory=args.workdir, save_interval_steps=2,
                             max_to_keep=10, async_save=args.async_save,
                             preemption_check_every=1),
            mesh,
            # elastic: saves beat phase "save" so a death landing
            # mid-checkpoint makes the fleet gang-stop, never shrink
            # around a possibly-torn step dir (async: the bracket spans
            # the whole background commit window)
            heartbeat=writer if args.elastic else None,
        )
        # production fault seam: AsyncCommitKill/SlowWriter fire inside
        # the background writer's commit stages; the flight recorder is
        # flushed BEFORE the SIGKILL so the postmortem can prove where
        # the death landed
        ckpt.save_hooks.append(plan.save_hook(flush=dump_flightrec))
        fb = not args.strict_restore
        state, specs, restored = init_or_restore(
            ckpt, common.make_init_fn(model, (8,)), tx, mesh,
            jax.random.PRNGKey(0), fallback=fb,
            # the gang ceiling binds the incarnation's FIRST restore
            # only: an in-process restart later in the same incarnation
            # must resume from its own newest valid step, not replay
            # from (or re-init below) the gang restart point
            step=ceiling if restart_index == 0 else None,
        )
        start = int(state.step)
        if restored:
            writer.note_restore(start, fallback=fb)
        # heartbeat FIRST: it must record the step even when
        # CheckpointCallback raises PreemptionSaved (which skips every
        # later callback for that step), and before the fault callback
        # can hang the loop; the elastic poll sits between heartbeat and
        # checkpoint so a resize hold lands between steps
        # telemetry BEFORE the snapshot export so each snapshot already
        # carries the step it was cut at; heartbeat stays first (it must
        # record the step even when a later callback raises)
        callbacks = [cb.HeartbeatCallback(
                         writer,
                         # slow-control-plane seam: bounded delay on the
                         # beat path only when the round scripts it
                         pace=(plan.beat_pace()
                               if args.slow_beat_at is not None else None)),
                     cb.TelemetryCallback(every_n=10 ** 6),
                     cb.FleetSnapshotCallback(exporter)]
        if elastic_client is not None:
            callbacks.append(cb.ElasticCallback(elastic_client))
        # writer: the ControlPlanePartition redirect seam; flush: the
        # flight recording must reach disk before PodOutage's SIGKILL
        callbacks += [cb.CheckpointCallback(ckpt),
                      plan.callback(writer=writer, flush=dump_flightrec)]
        if args.die_at is not None:
            callbacks.append(_DieAt(args.die_at))
        if args.step_sleep > 0:
            callbacks.append(_StepSleep(args.step_sleep))
        trainer = Trainer(
            make_train_step(loss_fn, tx, StepOptions()), state, mesh, specs,
            callbacks=callbacks,
        )
        return trainer, plan.wrap(batches_from(start), start=start), ckpt

    sup = Supervisor(
        build, num_steps=args.steps,
        cfg=SupervisorConfig(
            max_restarts=args.max_restarts,
            # PREEMPTION deliberately absent: a SIGTERM means the fleet
            # is tearing the gang down — exit so it can relaunch us
            restart_on=(TRANSIENT, POISONED, STALLED),
            backoff=RetryPolicy(base_s=0.0, jitter=0.0),
        ),
        heartbeat=writer,
    )
    try:
        state = sup.run()
    except SupervisorExhausted as e:
        writer.finish("failed", cause=e.cause)
        dump_flightrec()
        print(f"FLEET-FAILED cause={e.cause}", flush=True)
        return fleet_lib.EXIT_FAILED
    except BaseException as e:
        # non-restartable classes are RE-RAISED by the Supervisor, not
        # wrapped: without this they'd crash rc=1 and the fleet would
        # misclassify a deterministic fatal bug as a transient death and
        # burn its whole gang-restart budget replaying it
        from distributed_tensorflow_tpu.resilience import classify_failure

        import traceback

        traceback.print_exc()
        cause = classify_failure(e)
        writer.finish("failed", cause=cause)
        dump_flightrec()
        print(f"FLEET-FAILED cause={cause}", flush=True)
        return fleet_lib.EXIT_FAILED
    if int(state.step) < args.steps:
        writer.finish("preempted")
        dump_flightrec()
        print(f"FLEET-PREEMPTED step={int(state.step)}", flush=True)
        return fleet_lib.EXIT_PREEMPTED
    if args.out:
        leaves = jax.tree.leaves(jax.device_get(state.params))
        np.savez(args.out, **{f"p{i}": np.asarray(x)
                              for i, x in enumerate(leaves)})
    writer.finish("done")
    dump_flightrec()
    print(f"FLEET-DONE step={int(state.step)} incarnation={incarnation} "
          f"restarts={sup.restarts}", flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workdir", help="checkpoint directory")
    ap.add_argument("--steps", type=int, default=8,
                    help="absolute target step (StopAtStepHook semantics)")
    ap.add_argument("--sigterm-at", type=int, default=None,
                    help="SIGTERM ourselves after this GLOBAL step")
    ap.add_argument("--data-error-at", type=int, default=None,
                    help="data iterator raises IOError feeding this GLOBAL step")
    ap.add_argument("--out", default=None,
                    help="write final params to this .npz on completion")
    ap.add_argument("--supervise", action="store_true",
                    help="run under resilience.Supervisor (in-process "
                         "restarts, fallback restore, retrying data)")
    ap.add_argument("--corrupt-at-restart", action="store_true",
                    help="supervised mode: truncate the newest checkpoint "
                         "at the first restart boundary")
    ap.add_argument("--transient-io-at", type=int, default=None,
                    help="supervised mode: data fetch for this GLOBAL step "
                         "raises IOError twice, then succeeds")
    ap.add_argument("--nan-at", type=int, default=None,
                    help="supervised mode: the batch feeding this GLOBAL "
                         "step is NaN-poisoned on EVERY fetch (recurring "
                         "bad index — the quarantine target)")
    ap.add_argument("--anomaly", action="store_true",
                    help="supervised mode: enable the numeric-anomaly "
                         "defense (in-graph no-update-on-nonfinite guard, "
                         "AnomalyPolicy skip budget, quarantine-aware "
                         "stream)")
    ap.add_argument("--skip-budget", type=int, default=4,
                    help="anomaly mode: non-finite batches skipped before "
                         "the poisoned escalation")
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--flightrec", default=None,
                    help="supervised mode: dump the flight recorder to this "
                         "JSONL path at the end of the run")
    ap.add_argument("--fleet", action="store_true",
                    help="run as one worker of a resilience.FleetSupervisor "
                         "gang (heartbeats, incarnation, exit-code protocol)")
    ap.add_argument("--fleet-dir", default=None,
                    help="fleet control dir (INCARNATION, RESTORE_STEP, "
                         "heartbeat files)")
    ap.add_argument("--worker-index", type=int, default=0)
    ap.add_argument("--hang-at", type=int, default=None,
                    help="fleet mode: hang the host loop after this GLOBAL "
                         "step (heartbeats stop, process stays alive)")
    ap.add_argument("--fault-incarnation", type=int, default=1,
                    help="fleet mode: inject faults only when the fleet "
                         "incarnation equals this (default 1 — first launch)")
    ap.add_argument("--elastic", action="store_true",
                    help="fleet mode: follow the fleet's SHARD_PLAN "
                         "(elastic resize client: barrier holds, reshard "
                         "schedule recorded to <workdir>/reshard_log.jsonl)")
    ap.add_argument("--die-at", type=int, default=None,
                    help="fleet mode: hard os._exit at this GLOBAL step "
                         "(no save, no final heartbeat — the elastic "
                         "round's scripted death; the LAUNCHER gates which "
                         "launch gets it)")
    ap.add_argument("--step-sleep", type=float, default=0.0,
                    help="fleet mode: sleep this long after every step "
                         "(pacing for real-subprocess elastic rounds)")
    ap.add_argument("--flightrec-dir", default=None,
                    help="fleet mode: dump the flight recorder as "
                         "flightrec-w<i>i<incarnation>.jsonl into this "
                         "dir on every exit path (postmortem --merge "
                         "input)")
    ap.add_argument("--async-save", action="store_true",
                    help="fleet mode: cadence saves go through the "
                         "background snapshot-then-commit writer "
                         "(emergency/preemption/final stay synchronous)")
    ap.add_argument("--async-kill-at", type=int, default=None,
                    help="fleet mode: SIGKILL inside the async commit "
                         "window (shards written, manifest NOT yet "
                         "published) of the first async save at/after "
                         "this GLOBAL step; gated on --fault-incarnation")
    ap.add_argument("--slow-writer-at", type=int, default=None,
                    help="fleet mode: stall the background writer before "
                         "the first async commit at/after this GLOBAL "
                         "step; gated on --fault-incarnation")
    ap.add_argument("--slow-writer-delay", type=float, default=1.0,
                    help="seconds --slow-writer-at stalls the writer")
    ap.add_argument("--strict-restore", action="store_true",
                    help="fleet mode: restore with fallback=False — the "
                         "ceiling step must verify and restore directly "
                         "(the async-kill round's proof that the torn "
                         "step is invisible, not quarantined)")
    ap.add_argument("--pod", type=int, default=None,
                    help="fleet mode: this worker's POD index under a "
                         "resilience/podfleet.py coordinator — --fleet-dir "
                         "is the pod's subdirectory, the GLOBAL_EPOCH file "
                         "lives one level up, and flight-recorder dumps "
                         "are named flightrec-p<pod>w<i>i<k>.jsonl")
    ap.add_argument("--fault-epoch", type=int, default=None,
                    help="pod mode: inject faults only when the global "
                         "epoch ALSO equals this — the second half of the "
                         "two-level (epoch, incarnation) fire-once fence")
    ap.add_argument("--pod-outage-at", type=int, default=None,
                    help="fleet mode: SIGKILL at this GLOBAL step (flight "
                         "recorder flushed first); give the same flag to "
                         "every worker of one pod and the pod dies as a "
                         "unit — the PodOutage round's scripted fault")
    ap.add_argument("--partition-at", type=int, default=None,
                    help="fleet mode: redirect heartbeat writes to a "
                         "shadow file starting at this GLOBAL step — the "
                         "control-plane partition: the process keeps "
                         "training while its liveness record goes stale")
    ap.add_argument("--partition-steps", type=int, default=3,
                    help="steps the --partition-at window lasts before the "
                         "real heartbeat path is restored (plus an "
                         "immediate beat)")
    ap.add_argument("--slow-beat-at", type=int, default=None,
                    help="fleet mode: delay every heartbeat write by "
                         "--slow-beat-delay for --slow-beat-steps steps "
                         "from this GLOBAL step (SlowControlPlane gray "
                         "failure — beats late but regular)")
    ap.add_argument("--slow-beat-delay", type=float, default=0.2,
                    help="seconds each slowed heartbeat write is delayed")
    ap.add_argument("--slow-beat-steps", type=int, default=3,
                    help="steps the --slow-beat-at window lasts")
    ap.add_argument("--p2p-catchup", action="store_true",
                    help="elastic mode: a rejoining replacement requests "
                         "the newest valid step from a live survivor "
                         "(resilience/fleet.request_catchup) before "
                         "restoring; survivors serve peer requests from "
                         "the step seam")
    ap.add_argument("--catchup-budget", type=float, default=15.0,
                    help="p2p mode: seconds the joiner waits for a "
                         "survivor's offer before falling back to "
                         "deterministic replay")
    args = ap.parse_args(argv)
    if args.fleet and not args.fleet_dir:
        raise SystemExit("--fleet requires --fleet-dir")

    import optax

    from distributed_tensorflow_tpu.models import MLP, MLPConfig, common
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributed_tensorflow_tpu.resilience import (
        DataError, FaultPlan, Sigterm,
    )
    from distributed_tensorflow_tpu.train import (
        CheckpointConfig, Checkpointer, StepOptions, Trainer,
        callbacks as cb, init_or_restore, make_train_step,
    )

    mesh = build_mesh(MeshSpec(data=-1))
    model = MLP(MLPConfig(hidden_sizes=(16,), num_classes=4))
    tx = optax.adam(1e-2)

    if args.fleet:
        return _fleet(args, mesh, model, tx)
    if args.supervise:
        return _supervised(args, mesh, model, tx)
    ckpt = Checkpointer(
        CheckpointConfig(directory=args.workdir, save_interval_steps=10**6,
                         async_save=False, preemption_check_every=1),
        mesh,
    )
    state, specs, restored = init_or_restore(
        ckpt, common.make_init_fn(model, (8,)), tx, mesh, jax.random.PRNGKey(0)
    )
    start = int(state.step)

    faults = []
    if args.sigterm_at is not None:
        # FaultCallback sees the trainer's GLOBAL step — no offset
        if args.sigterm_at <= start:
            raise SystemExit(f"--sigterm-at {args.sigterm_at} is already "
                             f"behind the restored step {start}")
        faults.append(Sigterm(args.sigterm_at))
    if args.data_error_at is not None:
        # iterator batches are 1-based PER PROCESS: batch i = step start+i
        if args.data_error_at <= start:
            raise SystemExit(f"--data-error-at {args.data_error_at} is "
                             f"already behind the restored step {start}")
        faults.append(DataError(args.data_error_at - start))
    plan = FaultPlan(tuple(faults))

    trainer = Trainer(
        make_train_step(common.classification_loss_fn(model), tx,
                        StepOptions()),
        state, mesh, specs,
        callbacks=[cb.CheckpointCallback(ckpt), plan.callback()],
    )

    def batches():
        i = start
        while True:
            i += 1
            yield global_step_batch(i)

    try:
        state = trainer.fit(plan.wrap(batches()), num_steps=args.steps)
    except IOError:
        saved = ckpt.latest_step()
        ckpt.close()
        print(f"CHAOS-DATAFAULT saved={saved}", flush=True)
        return 0
    saved = ckpt.latest_step()
    ckpt.close()
    if "preempted" in (trainer._stop_reason or ""):
        print(f"CHAOS-PREEMPTED step={saved}", flush=True)
        return 0
    if int(state.step) != args.steps:
        print(f"CHAOS-SHORT step={int(state.step)} want={args.steps}",
              flush=True)
        return 1
    if args.out:
        leaves = jax.tree.leaves(jax.device_get(state.params))
        np.savez(args.out, **{f"p{i}": np.asarray(x)
                              for i, x in enumerate(leaves)})
    print(f"CHAOS-DONE step={int(state.step)}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
