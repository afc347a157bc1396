"""Multi-process test worker (driven by tests/test_multiprocess.py).

One forked localhost process per "host": jax.distributed over CPU devices
— the MultiProcessRunner analog ($TF/python/distribute/
multi_process_runner.py:107; SURVEY.md §4.3). Scenario selected by argv.
"""

import os
import sys

# must precede any jax import in this process
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def main() -> None:
    scenario, coord, num, pid, workdir = sys.argv[1:6]
    num, pid = int(num), int(pid)

    from distributed_tensorflow_tpu.parallel import cluster

    cluster.initialize(cluster.ClusterConfig(
        coordinator_address=coord, num_processes=num, process_id=pid,
    ))
    assert jax.process_count() == num, jax.process_count()
    assert jax.device_count() == 2 * num

    if scenario == "psum":
        scenario_psum()
    elif scenario == "hybrid":
        scenario_hybrid()
    elif scenario == "divergence":
        scenario_divergence(pid)
    elif scenario == "pipeline":
        scenario_pipeline()
    elif scenario == "checkpoint":
        scenario_checkpoint(workdir, resume="--resume" in sys.argv)
    elif scenario == "preempt":
        scenario_preempt(workdir)
    else:
        raise ValueError(scenario)


def scenario_psum() -> None:
    """Global-mesh allreduce across processes: the DCN init smoke test."""
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributed_tensorflow_tpu.parallel import sharding as sh
    from jax.sharding import NamedSharding

    mesh = build_mesh(MeshSpec(data=-1))  # all global devices
    n = mesh.size
    from jax.experimental import multihost_utils

    local = np.arange(2, dtype=np.float32) + 2 * jax.process_index()
    arr = multihost_utils.host_local_array_to_global_array(
        local, mesh, sh.batch_spec(1)
    )
    total = jax.jit(
        lambda x: jnp.sum(x),
        in_shardings=NamedSharding(mesh, sh.batch_spec(1)),
        out_shardings=NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )(arr)
    want = sum(range(2 * jax.process_count()))
    got = float(jax.device_get(total))
    assert got == want, (got, want)
    print(f"PSUM-OK {jax.process_index()} {got}", flush=True)


def scenario_hybrid() -> None:
    """2-process ICI×DCN hybrid mesh: each
    process plays one 'slice' (dcn_data=2), runs a full train step over
    the hybrid data axis, and checks the loss agrees across hosts."""
    import jax.numpy as jnp
    import optax

    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributed_tensorflow_tpu.parallel import sharding as sh
    from distributed_tensorflow_tpu.train import (
        StepOptions, init_train_state, jit_train_step, make_train_step,
    )

    mesh = build_mesh(MeshSpec(data=4, dcn_data=2))
    assert mesh.shape["data"] == 4

    def linear_init(rng):
        return {"w": jnp.ones((4, 2)), "b": jnp.zeros((2,))}, {}

    def linear_loss(params, mstate, batch, rng):
        pred = batch["x"] @ params["w"] + params["b"]
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, (mstate, {})

    tx = optax.sgd(0.1)
    state, specs = init_train_state(
        linear_init, tx, mesh, jax.random.PRNGKey(0)
    )
    step = jit_train_step(make_train_step(linear_loss, tx), mesh, specs)
    rng = np.random.RandomState(0)  # same seed: identical global batch halves
    local = {
        "x": rng.randn(8, 4).astype(np.float32)[
            jax.process_index() * 4:(jax.process_index() + 1) * 4],
        "y": np.zeros((4, 2), np.float32),
    }
    batch = sh.put_host_batch(mesh, local)
    state, metrics = step(state, batch)
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss)
    from distributed_tensorflow_tpu.utils import multihost

    multihost.assert_same_across_hosts(
        {"loss": np.asarray(loss, np.float32)}, "hybrid-loss"
    )
    print(f"HYBRID-OK {jax.process_index()} {loss:.6f}", flush=True)


def scenario_divergence(pid: int) -> None:
    """assert_same_across_hosts must trip when one host diverges."""
    from distributed_tensorflow_tpu.utils import multihost

    multihost.assert_same_across_hosts({"step": np.asarray(7)}, "agree")
    print(f"AGREE-OK {pid}", flush=True)
    try:
        multihost.assert_same_across_hosts(
            {"step": np.asarray(7 + (1 if pid == 1 else 0))}, "diverge"
        )
        print(f"DIVERGE-MISSED {pid}", flush=True)
    except AssertionError:
        print(f"DIVERGE-CAUGHT {pid}", flush=True)


def scenario_pipeline() -> None:
    """Pipeline stages on DIFFERENT hosts: dcn_pipe=2 forces the pipe
    axis across the process boundary, so every ppermute hop (activations
    stage->stage, fwd AND transposed bwd) crosses DCN. One stochastic
    (dropout) pipelined train step; loss finite and host-agreeing."""
    import jax.numpy as jnp
    import optax

    from distributed_tensorflow_tpu.models import transformer as tfm
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributed_tensorflow_tpu.parallel import sharding as sh
    from distributed_tensorflow_tpu.train import (
        StepOptions, init_train_state, jit_train_step, make_train_step,
    )

    mesh = build_mesh(MeshSpec(pipe=2, data=2, dcn_pipe=2))
    cfg = tfm.TransformerConfig(
        vocab_size=32, max_len=8, num_layers=2, d_model=16, num_heads=2,
        d_ff=32, causal=True, pre_ln=True, dropout=0.1, dtype="float32",
    )
    init_fn = tfm.make_pipelined_init_fn(cfg, n_stages=2, seq_len=8)
    specs = tfm.pipeline_param_specs(
        jax.eval_shape(init_fn, jax.random.PRNGKey(0))[0])
    tx = optax.sgd(0.05)
    state, sspecs = init_train_state(
        init_fn, tx, mesh, jax.random.PRNGKey(0), param_specs=specs)
    step = jit_train_step(
        make_train_step(tfm.pipelined_lm_loss_fn(cfg, mesh, 2), tx,
                        StepOptions()),
        mesh, sspecs,
    )
    rng = np.random.RandomState(0)  # same seed: agreed global batch
    ids = rng.randint(0, 32, (8, 8)).astype(np.int32)
    # the data axis is INTRA-process here (pipe spans the hosts), so each
    # host's addressable shards cover every batch row: pass the full
    # pipe-replicated batch, not a per-host slice
    batch = sh.put_host_batch(mesh, {"input_ids": ids})
    state, metrics = step(state, batch)
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss), loss
    from distributed_tensorflow_tpu.utils import multihost

    multihost.assert_same_across_hosts(
        {"loss": np.asarray(loss, np.float32)}, "pipeline-loss"
    )
    print(f"PIPELINE-OK {jax.process_index()} {loss:.6f}", flush=True)


def scenario_checkpoint(workdir: str, resume: bool) -> None:
    """Every host writes its shards; resume restores step + params."""
    import optax

    from distributed_tensorflow_tpu.models import MLP, MLPConfig, common
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributed_tensorflow_tpu.train import (
        CheckpointConfig, Checkpointer, StepOptions, Trainer, callbacks as cb,
        init_or_restore, jit_train_step, make_train_step,
    )

    mesh = build_mesh(MeshSpec(data=-1, fsdp=1))
    cfg = MLPConfig(hidden_sizes=(16,), num_classes=4)
    model = MLP(cfg)
    tx = optax.adam(1e-2)
    ckpt = Checkpointer(
        CheckpointConfig(directory=workdir, save_interval_steps=5,
                         async_save=False),
        mesh,
    )
    state, specs, restored = init_or_restore(
        ckpt, common.make_init_fn(model, (8,)), tx, mesh, jax.random.PRNGKey(0)
    )
    start = int(state.step)
    if resume:
        assert restored and start == 10, (restored, start)
    trainer = Trainer(
        make_train_step(common.classification_loss_fn(model), tx,
                        StepOptions()),
        state, mesh, specs, callbacks=[cb.CheckpointCallback(ckpt)],
    )

    def batches():
        rng = np.random.RandomState(0)
        while True:
            yield {
                "image": rng.randn(8, 8).astype(np.float32),
                "label": rng.randint(0, 4, 8).astype(np.int32),
            }

    # num_steps is the absolute target step (StopAtStepHook's last_step
    # semantics): resume runs from the restored step up to start+10
    state = trainer.fit(batches(), num_steps=start + 10)
    ckpt.wait()
    assert int(state.step) == start + 10, (
        int(state.step), start, trainer._stop_reason, trainer.failed
    )
    assert ckpt.latest_step() == start + 10, (
        ckpt.latest_step(), start, ckpt.manager.all_steps()
    )
    ckpt.close()
    print(f"CKPT-OK {jax.process_index()} step={int(state.step)}", flush=True)


def scenario_preempt(workdir: str) -> None:
    """Host 0 is SIGTERMed mid-run; every host must coordinate one final
    save and exit cleanly (PreemptionSaved)."""
    import optax

    from distributed_tensorflow_tpu.models import MLP, MLPConfig, common
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributed_tensorflow_tpu.train import (
        CheckpointConfig, Checkpointer, StepOptions, Trainer, callbacks as cb,
        init_or_restore, make_train_step,
    )

    mesh = build_mesh(MeshSpec(data=-1))
    cfg = MLPConfig(hidden_sizes=(16,), num_classes=4)
    model = MLP(cfg)
    tx = optax.adam(1e-2)
    ckpt = Checkpointer(
        CheckpointConfig(directory=workdir, save_interval_steps=10**6,
                         async_save=False, preemption_check_every=2),
        mesh,
    )
    state, specs, _ = init_or_restore(
        ckpt, common.make_init_fn(model, (8,)), tx, mesh, jax.random.PRNGKey(0)
    )
    trainer = Trainer(
        make_train_step(common.classification_loss_fn(model), tx,
                        StepOptions()),
        state, mesh, specs, callbacks=[cb.CheckpointCallback(ckpt)],
    )

    print(f"READY {jax.process_index()}", flush=True)  # parent sends SIGTERM

    def batches():
        rng = np.random.RandomState(0)
        import time

        while True:
            time.sleep(0.05)  # slow steps so the signal lands mid-run
            yield {
                "image": rng.randn(8, 8).astype(np.float32),
                "label": rng.randint(0, 4, 8).astype(np.int32),
            }

    # Trainer converts PreemptionSaved into a clean stop (loop.py)
    trainer.fit(batches(), num_steps=2000)
    saved = ckpt.latest_step()
    ckpt.close()
    if (not trainer.failed and saved is not None
            and "preempted" in (trainer._stop_reason or "")):
        print(f"PREEMPT-SAVED {jax.process_index()} step={saved}", flush=True)
    else:
        print(f"PREEMPT-MISSED {jax.process_index()} reason="
              f"{trainer._stop_reason!r} saved={saved}", flush=True)


if __name__ == "__main__":
    main()
