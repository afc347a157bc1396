"""End-to-end workload tests (SURVEY.md §4.5): MNIST-MLP to convergence on
fake devices, CIFAR-CNN sync-DP smoke — the M6 'smallest thing that proves
the framework'."""

import os

import numpy as np
import pytest

from distributed_tensorflow_tpu import workloads

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_registry():
    assert "mnist_mlp" in workloads.available()
    with pytest.raises(ValueError, match="Unknown workload"):
        workloads.get("nope")


def test_mnist_mlp_converges(tmp_path):
    result = workloads.run_workload(
        "mnist_mlp",
        [
            "--train.num_steps=60",
            "--train.log_every=10",
            "--train.eval_batches=4",
            "--data.global_batch_size=256",
            "--optimizer.learning_rate=0.3",
            f"--checkpoint.directory={tmp_path}/ck",
            "--checkpoint.save_interval_steps=50",
            "--checkpoint.async_save=false",
            "--checkpoint.save_on_preemption=false",
        ],
    )
    hist = result.history
    assert hist[0]["loss"] > hist[-1]["loss"], "loss did not decrease"
    # linear-teacher task: must beat 10-class chance comfortably
    assert result.eval_metrics["accuracy"] > 0.3
    # top-5 dominates top-1 and must beat it on a 10-class head
    assert (result.eval_metrics["top5_accuracy"]
            >= result.eval_metrics["accuracy"])
    assert result.eval_metrics["top5_accuracy"] > 0.7
    assert int(result.state.step) == 60
    # checkpoint written and config serialized
    assert (tmp_path / "ck" / "config.json").exists()


@pytest.mark.slow
def test_cifar10_cnn_sync_dp8_smoke():
    result = workloads.run_workload(
        "cifar10_cnn",
        [
            "--train.num_steps=6",
            "--train.log_every=3",
            "--train.eval_batches=2",
            "--train.debug_metrics=true",
            "--data.global_batch_size=64",
            "--mesh.data=8",
        ],
    )
    assert int(result.state.step) == 6
    assert all(
        h["grads_finite"] == 1.0 for h in result.history
    ), "non-finite grads in CNN smoke"


def test_unimplemented_workload_friendly_error(monkeypatch):
    monkeypatch.setitem(workloads._REGISTRY, "ghost", ".ghost")
    with pytest.raises(ValueError, match="not implemented"):
        workloads.get("ghost")


def test_mid_train_eval_runs(caplog):
    import logging

    with caplog.at_level(logging.INFO, logger="distributed_tensorflow_tpu.workloads.runner"):
        workloads.run_workload(
            "mnist_mlp",
            ["--train.num_steps=4", "--train.log_every=2",
             "--train.eval_every=2", "--train.eval_batches=1",
             "--data.global_batch_size=64"],
        )
    assert any("eval @ step" in r.message for r in caplog.records), (
        "mid-train eval callback never fired"
    )


def test_resume_advances_data_stream(tmp_path):
    """After restore at step N, the runner must feed batch N, not batch 0."""
    from distributed_tensorflow_tpu.workloads import mnist_mlp, runner

    cfg = mnist_mlp.default_config()
    parts = mnist_mlp.build(cfg)
    b0 = next(iter(parts.dataset_fn(0)))
    b5 = next(iter(parts.dataset_fn(5)))
    import numpy as np

    assert not np.array_equal(b0["image"], b5["image"])
    # and the offset stream matches the straight stream at the same index
    straight = parts.dataset_fn(0)
    it = iter(straight)
    for _ in range(5):
        next(it)
    np.testing.assert_array_equal(next(it)["image"], b5["image"])


def test_anomaly_defense_wiring_runs(tmp_path):
    """train.anomaly_defense=true engages the in-graph guard + policy +
    quarantine-filtered stream through the runner: a clean run finishes
    with the flag reporting 0 and nothing quarantined."""
    from distributed_tensorflow_tpu.resilience import load_quarantine

    result = workloads.run_workload(
        "mnist_mlp",
        [
            "--train.num_steps=6",
            "--train.log_every=3",
            "--train.eval_batches=2",
            "--train.anomaly_defense=true",
            "--data.global_batch_size=64",
            f"--checkpoint.directory={tmp_path}/ck",
            "--checkpoint.save_interval_steps=100",
            "--checkpoint.async_save=false",
            "--checkpoint.save_on_preemption=false",
        ],
    )
    assert int(result.state.step) == 6
    # the per-step flag rides the fetched metrics; every step was clean
    assert result.history[-1]["nonfinite"] == 0.0
    assert load_quarantine(str(tmp_path / "ck")) == frozenset()


def test_elastic_fleet_wiring_slices_and_live_reshards(tmp_path, monkeypatch):
    """fleet.elastic=true engages the production elastic seam in the
    runner: the worker's data stream is its SHARD_PLAN slice of every
    global batch (ElasticStream), heartbeats + plan acks flow from the
    step seam, and a NEW plan written mid-run reshards the live stream
    exactly at its barrier index."""
    import dataclasses

    import distributed_tensorflow_tpu.data.pipeline as pl
    from distributed_tensorflow_tpu.resilience import fleet as fl
    from distributed_tensorflow_tpu.train import callbacks as cb
    from distributed_tensorflow_tpu.workloads import mnist_mlp, runner

    fleet_dir = str(tmp_path / "fleet")
    fl.write_shard_plan(fleet_dir, fl.ShardPlan(
        version=1, phase=fl.PLAN_STEADY, world=2, ranks={0: 0, 1: 1},
        barrier_step=0, fleet_size=2))

    sizes = []

    class Spy(pl.ElasticStream):
        def __next__(self):
            b = super().__next__()
            sizes.append(len(b["image"]))
            return b

    monkeypatch.setattr(pl, "ElasticStream", Spy)

    class RejoinAt2(cb.Callback):
        """Plays the fleet: after step 2 the gang is back at world 1
        (this worker absorbs everything), binding to batches > 2."""

        def on_step_end(self, trainer, step, metrics):
            if step == 2:
                fl.write_shard_plan(fleet_dir, fl.ShardPlan(
                    version=2, phase=fl.PLAN_STEADY, world=1, ranks={0: 0},
                    barrier_step=2, fleet_size=2))

    cfg = mnist_mlp.default_config()
    cfg = dataclasses.replace(
        cfg,
        train=dataclasses.replace(cfg.train, num_steps=4, log_every=2,
                                  eval_batches=2),
        data=dataclasses.replace(cfg.data, global_batch_size=32),
        fleet=runner.FleetSection(dir=fleet_dir, worker=0, elastic=True),
    )
    result = runner.run(cfg, mnist_mlp.build,
                        extra_callbacks=[RejoinAt2()])
    assert int(result.state.step) == 4
    # steps 1-2 trained rank 0 of 2 (16 of 32); the live reshard at
    # barrier 2 restored the full batch for steps 3-4
    assert sizes == [16, 16, 32, 32]
    hb = fl.read_heartbeat(fl.heartbeat_path(fleet_dir, 0))
    assert hb.step == 4 and hb.plan_version == 2 and hb.world == 1


def test_elastic_runner_restarted_mid_hold_still_reaches_barrier(tmp_path):
    """A worker (re)started while a resize HOLD naming it is on disk
    must enter the barrier at train start — pre-acking the hold would
    leave the fleet waiting until hold_timeout_s and spuriously
    escalate the resize to a gang restart."""
    import threading
    import time

    import dataclasses

    from distributed_tensorflow_tpu.resilience import fleet as fl
    from distributed_tensorflow_tpu.workloads import mnist_mlp, runner

    fleet_dir = str(tmp_path / "fleet")
    fl.write_shard_plan(fleet_dir, fl.ShardPlan(
        version=1, phase=fl.PLAN_STEADY, world=2, ranks={0: 0, 1: 1},
        barrier_step=0, fleet_size=2))
    # a resize is in flight: the hold names worker 0
    fl.write_shard_plan(fleet_dir, fl.ShardPlan(
        version=2, phase=fl.PLAN_HOLD, world=2, ranks={0: 0, 1: 1},
        barrier_step=0, hold=(0,), fleet_size=2))
    hb_path = fl.heartbeat_path(fleet_dir, 0)
    saw_barrier = []

    def fleet_side():
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            hb = fl.read_heartbeat(hb_path)
            if hb is not None and hb.phase == "barrier" \
                    and hb.plan_version == 2:
                saw_barrier.append(hb.step)
                fl.write_shard_plan(fleet_dir, fl.ShardPlan(
                    version=3, phase=fl.PLAN_STEADY, world=1, ranks={0: 0},
                    barrier_step=hb.step or 0, fleet_size=2))
                return
            time.sleep(0.02)

    t = threading.Thread(target=fleet_side)
    t.start()
    cfg = mnist_mlp.default_config()
    cfg = dataclasses.replace(
        cfg,
        train=dataclasses.replace(cfg.train, num_steps=2, log_every=1,
                                  eval_batches=2),
        data=dataclasses.replace(cfg.data, global_batch_size=32),
        fleet=runner.FleetSection(dir=fleet_dir, worker=0, elastic=True),
    )
    result = runner.run(cfg, mnist_mlp.build)
    t.join(timeout=5)
    assert saw_barrier, "worker never acknowledged the hold"
    assert int(result.state.step) == 2
    hb = fl.read_heartbeat(hb_path)
    assert hb.plan_version == 3 and hb.world == 1


def test_elastic_fleet_cli_knobs_and_anomaly_exclusion(tmp_path):
    """The fleet section parses from the CLI like every other config
    section, and the elastic stream refuses to share the raw cursor
    with the anomaly defense."""
    from distributed_tensorflow_tpu.resilience import fleet as fl

    fleet_dir = str(tmp_path / "fleet")
    fl.write_shard_plan(fleet_dir, fl.ShardPlan(
        version=1, phase=fl.PLAN_STEADY, world=2, ranks={0: 0, 1: 1},
        barrier_step=0, fleet_size=2))
    result = workloads.run_workload(
        "mnist_mlp",
        ["--train.num_steps=2", "--train.log_every=1",
         "--train.eval_batches=2", "--data.global_batch_size=32",
         f"--fleet.dir={fleet_dir}", "--fleet.worker=1",
         "--fleet.elastic=true"],
    )
    assert int(result.state.step) == 2
    hb = fl.read_heartbeat(fl.heartbeat_path(fleet_dir, 1))
    assert hb.step == 2 and hb.world == 2
    with pytest.raises(ValueError, match="mutually exclusive"):
        workloads.run_workload(
            "mnist_mlp",
            ["--train.num_steps=2", "--train.anomaly_defense=true",
             f"--checkpoint.directory={tmp_path}/ck",
             f"--fleet.dir={fleet_dir}", "--fleet.elastic=true"],
        )
    # ragged worker slices cannot shard over the mesh batch axes: a
    # non-dividing (global batch, world) pair fails at CONFIG time with
    # the fix named, not at the first step with a shape error
    fl.write_shard_plan(fleet_dir, fl.ShardPlan(
        version=2, phase=fl.PLAN_STEADY, world=3,
        ranks={0: 0, 1: 1, 2: 2}, barrier_step=0, fleet_size=3))
    with pytest.raises(ValueError, match="not divisible by elastic world"):
        workloads.run_workload(
            "mnist_mlp",
            ["--train.num_steps=2", "--data.global_batch_size=32",
             f"--fleet.dir={fleet_dir}", "--fleet.worker=0",
             "--fleet.elastic=true"],
        )
    # a uniform slice that does not divide the mesh batch-axes extent
    # (8 fake devices) fails the same way
    fl.write_shard_plan(fleet_dir, fl.ShardPlan(
        version=3, phase=fl.PLAN_STEADY, world=2, ranks={0: 0, 1: 1},
        barrier_step=0, fleet_size=2))
    with pytest.raises(ValueError, match="mesh batch-axes extent"):
        workloads.run_workload(
            "mnist_mlp",
            ["--train.num_steps=2", "--data.global_batch_size=8",
             f"--fleet.dir={fleet_dir}", "--fleet.worker=0",
             "--fleet.elastic=true"],
        )
    from distributed_tensorflow_tpu.workloads import runner

    with pytest.raises(ValueError, match="hold_timeout_s"):
        runner.FleetSection(dir=fleet_dir, elastic=True, hold_timeout_s=0)


def test_anomaly_defense_requires_checkpoint_dir():
    with pytest.raises(ValueError, match="anomaly_defense"):
        workloads.run_workload(
            "mnist_mlp",
            ["--train.num_steps=2", "--train.anomaly_defense=true"],
        )


def test_mnist_grad_accum_runs():
    result = workloads.run_workload(
        "mnist_mlp",
        [
            "--train.num_steps=4",
            "--train.log_every=2",
            "--train.grad_accum_steps=4",
            "--data.global_batch_size=64",
        ],
    )
    assert int(result.state.step) == 4


def test_summary_event_files_written(tmp_path):
    """SummarySaverHook analog (SURVEY.md §5.5): a short fit with
    train.summary_dir set leaves TensorBoard scalar events on disk."""
    logdir = str(tmp_path / "tb")
    workloads.run_workload(
        "mnist_mlp",
        [
            "--train.num_steps=6",
            "--train.log_every=2",
            f"--train.summary_dir={logdir}",
            "--data.global_batch_size=64",
        ],
    )
    from tensorboard.backend.event_processing import event_accumulator

    acc = event_accumulator.EventAccumulator(logdir)
    acc.Reload()
    tags = acc.Tags()["scalars"]
    assert "train/loss" in tags, tags
    assert "train/steps_per_sec" in tags, tags
    events = acc.Scalars("train/loss")
    assert len(events) >= 2
    assert all(np.isfinite(e.value) for e in events)
    steps = [e.step for e in events]
    assert steps == sorted(steps)


def test_eval_from_checkpoint_matches_live(tmp_path):
    """SURVEY.md §3.5: train 3 steps + save, then evaluate from disk with
    no Trainer; numbers must match the live eval at train end."""
    ckdir = str(tmp_path / "ck")
    args = [
        "--train.num_steps=3",
        "--train.log_every=2",
        "--train.eval_batches=2",
        "--data.global_batch_size=64",
        f"--checkpoint.directory={ckdir}",
        "--checkpoint.save_interval_steps=1",
        "--checkpoint.async_save=false",
    ]
    live = workloads.run_workload("mnist_mlp", args)
    assert live.eval_metrics is not None
    offline = workloads.eval_workload("mnist_mlp", args)
    assert offline["step"] == 3
    assert abs(offline["accuracy"] - live.eval_metrics["accuracy"]) < 1e-6
    assert abs(offline["top5_accuracy"]
               - live.eval_metrics["top5_accuracy"]) < 1e-6
    assert abs(offline["loss"] - live.eval_metrics["loss"]) < 1e-5


def test_eval_from_checkpoint_missing_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="no checkpoint"):
        workloads.eval_workload("mnist_mlp", [
            f"--checkpoint.directory={tmp_path / 'empty'}",
        ])


@pytest.mark.slow
def test_gpt_lm_workload_trains_and_long_context_preset():
    """The sixth workload: causal LM through the full runner; the
    long-context preset wires ring attention + remat + a seq-wildcard
    mesh."""
    from distributed_tensorflow_tpu import workloads
    from distributed_tensorflow_tpu.workloads import gpt_lm

    result = workloads.run_workload(
        "gpt_lm",
        [
            "--train.num_steps=40",
            "--train.log_every=10",
            "--mesh.data=4",
            "--mesh.model=2",
            "--data.global_batch_size=32",
            "--data.seq_len=16",
            "--data.vocab_size=48",
            "--model.vocab_size=48",
            "--model.max_len=16",
            "--model.num_layers=2",
            "--model.d_model=32",
            "--model.num_heads=4",
            "--model.d_ff=64",
            "--model.dropout=0.0",
            "--model.dtype=float32",
            "--optimizer.learning_rate=3e-3",
            "--optimizer.warmup_steps=5",
            "--optimizer.total_steps=40",
        ],
    )
    hist = result.history
    assert hist[-1]["loss"] < hist[0]["loss"], hist

    lc = gpt_lm.long_context(seq_len=4096)
    assert lc.model.seq_impl == "ring" and lc.model.remat
    assert lc.model.max_len == 4096 and lc.data.seq_len == 4096
    assert lc.mesh.seq == -1


def test_profiler_callback_writes_trace(tmp_path):
    """The Profiler callback (ProfilerHook analog) leaves an XPlane trace
    on disk for TensorBoard after its start/stop window."""
    import os

    from distributed_tensorflow_tpu import workloads

    logdir = tmp_path / "prof"
    workloads.run_workload(
        "mnist_mlp",
        [
            "--train.num_steps=20",
            "--train.log_every=10",
            "--train.profile=true",
            f"--train.profile_dir={logdir}",
            "--data.global_batch_size=16",
        ],
    )
    traces = [
        os.path.join(r, f)
        for r, _, fs in os.walk(logdir) for f in fs
        if f.endswith(".xplane.pb")
    ]
    assert traces, f"no xplane trace under {logdir}"


@pytest.mark.slow
def test_convergence_demo_machinery(tmp_path):
    """tools/convergence_demo.py end to end at smoke scale: real digit
    scans -> JPEG records -> run_workload (decode+augment+train+ckpt) ->
    eval_workload restore on the held-out pair. The committed 400-step
    run reaches 98.4%; here 20 steps must beat 3x chance
    and the machinery must produce valid JSON."""
    import json
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "convergence_demo.py"),
         "--steps", "20", "--workdir", str(tmp_path), "--min-top1", "0.3"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["eval_top1"] > 0.3, result


def test_clip_grad_norm_knob_gives_same_step_nan_signal():
    """--train.clip_grad_norm clips AND yields the free grads_finite
    metric (derived from the global norm) without debug_metrics."""
    result = workloads.run_workload("mnist_mlp", [
        "--train.num_steps=3", "--train.log_every=1",
        "--train.clip_grad_norm=1.0", "--data.global_batch_size=16",
        "--mesh.data=-1",
    ])
    last = result.history[-1]
    assert "grad_norm" in last and "grads_finite" in last
    assert last["grads_finite"] == 1.0


@pytest.mark.slow
def test_convergence_demo_ctr_machinery():
    """tools/convergence_demo_ctr.py end to end at smoke scale:
    teacher-labeled Criteo-format TSV -> make_ctr_records.py -> ctr:
    training through the native loader -> held-out AUC. The committed
    600-step run reaches AUC 0.77; here 40 steps must
    clear a weak above-chance gate and emit valid JSON."""
    import json
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "convergence_demo_ctr.py"),
         "--steps", "40", "--min-auc", "0.55"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["eval_auc"] > 0.55, result


@pytest.mark.slow
def test_convergence_demo_mlm_machinery():
    """tools/convergence_demo_mlm.py at smoke scale: repo .md prose ->
    byte token files -> tokens_mlm: training -> held-out masked-byte
    accuracy. The committed 1600-step run reaches 0.50;
    here 60 steps must beat the unigram floor and emit valid JSON."""
    import json
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "convergence_demo_mlm.py"),
         "--steps", "60", "--min-acc", "0.1"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["eval_masked_acc"] > 0.1, result


@pytest.mark.slow
def test_convergence_demo_long_ring_machinery():
    """The --long variant (causal LM at seq 256 THROUGH ring attention
    on a seq=4 mesh + remat) at smoke scale: the arg plumbing, ring mesh
    build, and extended JSON shape must work before a multi-hour run
    depends on them. The committed 3600-step run reaches 0.303
    (artifacts/lm_long_ring_r4.json)."""
    import json
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "convergence_demo_mlm.py"),
         "--long", "--steps", "12", "--min-acc", "0.0"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["objective"] == "lm_long_ring", result
    assert result["seq_len"] == 256 and result["seq_impl"] == "ring", result
    # conflicting flags error loudly
    bad = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "convergence_demo_mlm.py"),
         "--long", "--objective", "mlm"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert bad.returncode != 0 and "causal-LM variant" in bad.stderr


@pytest.mark.slow
def test_train_and_eval_cli_scripts(tmp_path):
    """The examples/{train,eval}.py SCRIPTS (not the API): the exact
    commands the README/MIGRATION show users, run as subprocesses with a
    checkpoint handoff between them. The round-3b on-chip profile step
    drives examples/train.py directly, so script-level rot would cost a
    chip window."""
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    ck = str(tmp_path / "ck")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "train.py"),
         "mnist_mlp", "--train.num_steps=4", "--train.log_every=2",
         "--data.global_batch_size=32", f"--checkpoint.directory={ck}",
         "--checkpoint.async_save=false",
         "--checkpoint.save_on_preemption=false",
         "--train.eval_batches=0", "--mesh.data=-1"],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "eval.py"),
         "mnist_mlp", f"--checkpoint.directory={ck}",
         "--train.eval_batches=2", "--data.global_batch_size=32",
         "--mesh.data=-1"],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "accuracy" in proc.stdout or "accuracy" in proc.stderr
