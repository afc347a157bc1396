"""Trainer loop + callbacks + checkpoint/resume tests (SURVEY.md §4.3/§5.4:
save, kill, resume must reproduce the uninterrupted run)."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_tensorflow_tpu.train import (
    CheckpointConfig,
    Checkpointer,
    OptimizerConfig,
    Trainer,
    init_or_restore,
    init_train_state,
    make_optimizer,
    make_train_step,
    callbacks as cb,
)

from test_step import linear_init, linear_loss, make_batch


def batches(n, size=16):
    for i in range(n):
        yield make_batch(size, seed=i)


def build_trainer(mesh, tx=None, callbacks=(), state=None, specs=None):
    tx = tx or optax.sgd(0.1)
    if state is None:
        state, specs = init_train_state(
            linear_init, tx, mesh, jax.random.PRNGKey(0)
        )
    step = make_train_step(linear_loss, tx)
    return Trainer(step, state, mesh, specs, callbacks=callbacks)


def test_fit_runs_and_stops_at_num_steps(mesh8):
    trainer = build_trainer(mesh8)
    state = trainer.fit(batches(100), num_steps=5)
    assert int(state.step) == 5


def test_stop_at_step_callback(mesh8):
    trainer = build_trainer(mesh8, callbacks=[cb.StopAtStep(3)])
    state = trainer.fit(batches(100))
    assert int(state.step) == 3


def test_metrics_logger(mesh8, caplog):
    logger_cb = cb.MetricsLogger(every_n=2, batch_size=16, history=True)
    trainer = build_trainer(mesh8, callbacks=[logger_cb, cb.StopAtStep(6)])
    with caplog.at_level(logging.INFO):
        trainer.fit(batches(100))
    assert logger_cb.history, "logger recorded nothing"
    assert "loss" in logger_cb.history[-1]
    assert "steps_per_sec" in logger_cb.history[-1]


def test_nan_guard_raises(mesh8):
    def nan_loss(params, model_state, batch, rng):
        loss = jnp.sum(params["w"]) * jnp.nan
        return loss, (model_state, {})

    tx = optax.sgd(0.1)
    state, specs = init_train_state(linear_init, tx, mesh8, jax.random.PRNGKey(0))
    trainer = Trainer(
        make_train_step(nan_loss, tx), state, mesh8, specs,
        callbacks=[cb.NaNGuard(every_n=1)],
    )
    with pytest.raises(FloatingPointError):
        trainer.fit(batches(10), num_steps=5)


@pytest.mark.slow
def test_optimizer_zoo_smoke(mesh8):
    for name in ["sgd", "momentum", "adam", "adamw", "adagrad", "rmsprop",
                 "lamb", "ftrl", "adafactor"]:
        tx = make_optimizer(OptimizerConfig(name=name, learning_rate=1e-2))
        trainer = build_trainer(mesh8, tx=tx)
        state = trainer.fit(batches(3), num_steps=2)
        assert int(state.step) == 2, name


def test_schedules_smoke():
    from distributed_tensorflow_tpu.train import make_schedule

    for sched in ["constant", "cosine", "warmup_cosine", "exponential", "linear"]:
        fn = make_schedule(OptimizerConfig(
            schedule=sched, learning_rate=0.1, warmup_steps=5, total_steps=50
        ))
        vals = [float(fn(i)) for i in [0, 10, 49]]
        assert all(np.isfinite(vals)), sched


def test_schedule_shapes_analytic():
    """Analytic checkpoints of the LR curves, not just finiteness:
    warmup ramps linearly 0 -> peak, cosine lands on end_lr_factor at
    total_steps, linear interpolates exactly halfway at midpoint."""
    from distributed_tensorflow_tpu.train import make_schedule

    lr, W, T = 0.1, 10, 110
    cos = make_schedule(OptimizerConfig(
        schedule="cosine", learning_rate=lr, warmup_steps=W, total_steps=T,
        end_lr_factor=0.01))
    # linear warmup: exact fractions of peak
    for i in (0, 5, 10):
        np.testing.assert_allclose(float(cos(i)), lr * i / W, rtol=1e-6)
    # peak right after warmup (f32 schedule arithmetic), floor at the end
    np.testing.assert_allclose(float(cos(W)), lr, rtol=1e-6)
    np.testing.assert_allclose(float(cos(T)), lr * 0.01, rtol=1e-5)
    # cosine midpoint: halfway between peak and floor
    np.testing.assert_allclose(
        float(cos(W + (T - W) // 2)), lr * (1 + 0.01) / 2, rtol=1e-5)
    # monotone decay after warmup
    pts = [float(cos(i)) for i in range(W, T, 10)]
    assert all(a >= b for a, b in zip(pts, pts[1:])), pts

    lin = make_schedule(OptimizerConfig(
        schedule="linear", learning_rate=lr, warmup_steps=0, total_steps=100,
        end_lr_factor=0.0))
    np.testing.assert_allclose(float(lin(50)), lr / 2, rtol=1e-6)
    np.testing.assert_allclose(float(lin(100)), 0.0, atol=1e-9)


def test_checkpoint_save_restore_resume(mesh8, tmp_path):
    """The §5.4 oracle: train 6 steps straight == train 3, 'crash', resume 3."""
    tx = optax.adam(1e-2)

    # straight run, 6 steps
    state, specs = init_train_state(linear_init, tx, mesh8, jax.random.PRNGKey(0))
    trainer = Trainer(make_train_step(linear_loss, tx), state, mesh8, specs)
    straight = trainer.fit(batches(6), num_steps=6)

    # interrupted run: 3 steps, save, fresh process simulation, resume 3
    ckpt_dir = str(tmp_path / "ckpt")
    ckpt = Checkpointer(
        CheckpointConfig(directory=ckpt_dir, save_interval_steps=1,
                         async_save=False, save_on_preemption=False),
        mesh8,
    )
    state, specs, restored = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    assert not restored
    trainer = Trainer(
        make_train_step(linear_loss, tx), state, mesh8, specs,
        callbacks=[cb.CheckpointCallback(ckpt)],
    )
    trainer.fit(batches(3), num_steps=3)
    ckpt.wait()
    assert ckpt.latest_step() == 3
    ckpt.close()

    ckpt2 = Checkpointer(
        CheckpointConfig(directory=ckpt_dir, save_interval_steps=1,
                         async_save=False, save_on_preemption=False),
        mesh8,
    )
    state2, specs2, restored2 = init_or_restore(
        ckpt2, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    assert restored2
    assert int(state2.step) == 3
    trainer2 = Trainer(make_train_step(linear_loss, tx), state2, mesh8, specs2)
    # feed the same batches 4..6 the straight run saw
    resumed = trainer2.fit(
        (make_batch(16, seed=i) for i in range(3, 6)), num_steps=6
    )
    assert int(resumed.step) == 6
    for a, b in zip(jax.tree.leaves(straight.params), jax.tree.leaves(resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)
    ckpt2.close()


def test_failed_run_never_checkpoints_poisoned_state(mesh8, tmp_path):
    """NaN abort must not overwrite the latest checkpoint with bad state."""
    def nan_loss(params, model_state, batch, rng):
        return jnp.sum(params["w"]) * jnp.nan, (model_state, {})

    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "nan"), save_interval_steps=100,
                         async_save=False, save_on_preemption=False),
        mesh8,
    )
    state, specs, _ = init_or_restore(ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0))
    trainer = Trainer(
        make_train_step(nan_loss, tx), state, mesh8, specs,
        callbacks=[cb.NaNGuard(every_n=1), cb.CheckpointCallback(ckpt)],
    )
    with pytest.raises(FloatingPointError):
        trainer.fit(batches(10), num_steps=5)
    assert trainer.failed
    assert ckpt.latest_step() is None  # nothing poisoned was written
    ckpt.close()


def test_save_refuses_nonfinite_params(mesh8, tmp_path):
    """validate_before_save: a direct save() of NaN params is refused — the
    guard that holds even when debug metrics (grads_finite) are off and the
    loss hasn't gone non-finite yet."""
    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "v"), async_save=False,
                         save_on_preemption=False),
        mesh8,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    poisoned = state.replace(
        params=jax.tree.map(lambda p: p * jnp.nan, state.params)
    )
    assert ckpt.save(0, poisoned, force=True) is False
    assert ckpt.latest_step() is None
    # and a clean state still saves
    assert ckpt.save(0, state, force=True) is True
    assert ckpt.latest_step() == 0
    ckpt.close()


def test_preemption_with_poisoned_state_fails_not_saves(mesh8, tmp_path):
    """A preemption save refused by validate_before_save must raise
    FloatingPointError (run exits FAILED), not PreemptionSaved — the latter
    would tell the scheduler a checkpoint exists when nothing was written."""
    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "p"), async_save=False,
                         save_on_preemption=True),
        mesh8,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    poisoned = state.replace(
        params=jax.tree.map(lambda p: p * jnp.nan, state.params)
    )
    ckpt.watcher._event.set()  # simulate SIGTERM observed
    with pytest.raises(FloatingPointError, match="non-finite"):
        ckpt.maybe_save(3, poisoned)
    assert ckpt.latest_step() is None
    # healthy state at preemption still takes the clean-exit path
    from distributed_tensorflow_tpu.train.checkpoint import PreemptionSaved
    with pytest.raises(PreemptionSaved):
        ckpt.maybe_save(3, state)
    assert ckpt.latest_step() == 3
    ckpt.close()


def test_preemption_poisoned_with_earlier_checkpoint_still_fails(mesh8, tmp_path):
    """The maybe_save refusal branch with an EARLIER checkpoint on disk:
    latest < step still means the preemption save wrote nothing for this
    step, so the run must exit FAILED (FloatingPointError naming the
    stale latest) — and the earlier healthy checkpoint must survive."""
    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "pe"), async_save=False,
                         save_on_preemption=True),
        mesh8,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    assert ckpt.save(2, state, force=True)  # healthy save at step 2
    poisoned = state.replace(
        params=jax.tree.map(lambda p: p * jnp.nan, state.params)
    )
    ckpt.watcher._event.set()
    with pytest.raises(FloatingPointError, match="latest on disk: 2"):
        ckpt.maybe_save(5, poisoned)
    assert ckpt.latest_step() == 2  # the stale-but-healthy save is intact
    ckpt.close()


def test_preemption_poisoned_but_step_already_saved_is_clean(mesh8, tmp_path):
    """If the preempted step is ALREADY covered on disk (save() dedups,
    latest == step), the refusal of the poisoned in-memory state doesn't
    matter — the PreemptionSaved contract holds and the run exits
    cleanly, resuming from the healthy copy of the same step."""
    from distributed_tensorflow_tpu.train.checkpoint import PreemptionSaved

    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "pc"), async_save=False,
                         save_on_preemption=True),
        mesh8,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    assert ckpt.save(5, state, force=True)  # step 5 is on disk, healthy
    poisoned = state.replace(
        params=jax.tree.map(lambda p: p * jnp.nan, state.params)
    )
    ckpt.watcher._event.set()
    with pytest.raises(PreemptionSaved):
        ckpt.maybe_save(5, poisoned)
    assert ckpt.latest_step() == 5
    ckpt.close()


def test_emergency_checkpoint_on_callback_exception(mesh8, tmp_path):
    """An exception out of ANY callback aborts fit() — but the Trainer's
    emergency save keeps the last completed step (crash-safe exits,
    docs/resilience.md). Discovery is implicit: wiring a
    CheckpointCallback is enough, no extra argument."""
    class Boom(cb.Callback):
        def on_step_end(self, trainer, step, metrics):
            if step == 3:
                raise RuntimeError("callback exploded")

    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "em"),
                         save_interval_steps=10**6, async_save=False,
                         save_on_preemption=False),
        mesh8,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    trainer = Trainer(
        make_train_step(linear_loss, tx), state, mesh8, specs,
        callbacks=[cb.CheckpointCallback(ckpt), Boom()],
    )
    assert trainer.emergency_checkpoint is ckpt
    with pytest.raises(RuntimeError, match="callback exploded"):
        trainer.fit(batches(10), num_steps=10)
    assert trainer.failed
    assert ckpt.latest_step() == 3  # the emergency save
    ckpt.close()


def test_no_emergency_checkpoint_without_checkpointer(mesh8):
    """No CheckpointCallback and no explicit emergency_checkpoint: the
    failure path must still re-raise cleanly (no AttributeError from the
    best-effort save)."""
    tx = optax.sgd(0.1)
    state, specs = init_train_state(linear_init, tx, mesh8, jax.random.PRNGKey(0))
    trainer = Trainer(make_train_step(linear_loss, tx), state, mesh8, specs)
    assert trainer.emergency_checkpoint is None
    with pytest.raises(IOError):
        def dies():
            yield make_batch(16, seed=0)
            raise IOError("dead feed")
        trainer.fit(dies(), num_steps=10)
    assert trainer.failed


def _traced_trainer(mesh, tracer, callbacks=(), **kw):
    tx = optax.sgd(0.1)
    state, specs = init_train_state(linear_init, tx, mesh,
                                    jax.random.PRNGKey(0))
    return Trainer(make_train_step(linear_loss, tx), state, mesh, specs,
                   callbacks=callbacks, tracer=tracer, **kw)


def test_fit_spans_show_a_slow_iterator_in_next_batch_not_in_dispatch(mesh8):
    """One train.step per loop iteration with its four phases as children,
    in order; a data iterator that sleeps lands in next_batch."""
    import time

    from distributed_tensorflow_tpu import obs

    def slow():
        for i in range(4):
            time.sleep(0.05)
            yield make_batch(16, seed=i)

    tracer = obs.Tracer(annotate=False)
    trainer = _traced_trainer(mesh8, tracer)
    trainer.fit(slow(), num_steps=5)
    steps = [s for s in tracer.events if s.name == "train.step"]
    # four real steps and the iteration that found the feed exhausted
    assert [s.attrs["step"] for s in steps] == [1, 2, 3, 4, 5]
    assert all(s.parent is None for s in steps)
    for sp in steps[:4]:
        kids = [s for s in tracer.events if s.parent == sp.id]
        assert [k.name for k in kids] == [
            "train.step.next_batch", "train.step.put_batch",
            "train.step.dispatch", "train.step.callbacks"]
        assert all(sp.start <= k.start <= k.end <= sp.end for k in kids)
        assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))
        nb, _, dispatch, _ = kids
        assert nb.duration >= 0.05
        if sp is not steps[0]:  # the first dispatch compiles the step
            assert dispatch.duration < 0.05
    last = [s.name for s in tracer.events if s.parent == steps[-1].id]
    assert last == ["train.step.next_batch"]
    assert trainer.stop_reason == "data exhausted"


def test_raising_callback_still_closes_train_step_and_dumps_the_ring(
        mesh8, tmp_path):
    """A span that dies still records: the step whose callback raised is in
    the ring with its callbacks child, nothing is left open, and the ring
    is dumped beside the flight recorder's postmortem, same suffix."""
    import json

    from distributed_tensorflow_tpu import obs

    class Boom(cb.Callback):
        def on_step_end(self, trainer, step, metrics):
            if step == 2:
                raise RuntimeError("callback exploded")

    tracer = obs.Tracer(annotate=False)
    for n in ("", "-1"):
        trainer = _traced_trainer(mesh8, tracer, callbacks=[Boom()],
                                  flightrec=obs.FlightRecorder(),
                                  postmortem_dir=str(tmp_path))
        with pytest.raises(RuntimeError, match="callback exploded"):
            trainer.fit(batches(10), num_steps=10)
        assert tracer.current() is None
        died = [s for s in tracer.events if s.name == "train.step"][-1]
        assert died.attrs == {"step": 2} and died.end >= died.start
        assert [s.name for s in tracer.events if s.parent == died.id][-1] \
            == "train.step.callbacks"
        assert (tmp_path / f"postmortem{n}.jsonl").exists()
        with open(tmp_path / f"spans{n}.jsonl") as f:
            header, *rows = [json.loads(line) for line in f]
        assert header["schema"] == "dtf-spans-1"
        assert header["spans"] == len(rows) == len(tracer.events)
        assert rows[-1]["name"] == "train.step" and rows[-1]["id"] == died.id


def test_no_postmortem_dir_no_span_dump_and_a_failed_dump_masks_nothing(
        mesh8, tmp_path, monkeypatch):
    from distributed_tensorflow_tpu import obs

    def dies():
        yield make_batch(16, seed=0)
        raise IOError("dead feed")

    tracer = obs.Tracer(annotate=False)
    trainer = _traced_trainer(mesh8, tracer)
    assert trainer.postmortem_dir is None
    monkeypatch.chdir(tmp_path)
    with pytest.raises(IOError, match="dead feed"):
        trainer.fit(dies(), num_steps=10)
    assert list(tmp_path.iterdir()) == []  # nothing written anywhere
    # the iteration whose next() raised is recorded, with its next_batch
    assert [s.name for s in tracer.events][-2:] == [
        "train.step.next_batch", "train.step"]

    def broken(path):
        raise OSError("disk full")

    monkeypatch.setattr(tracer, "dump", broken)
    trainer = _traced_trainer(mesh8, tracer, flightrec=obs.FlightRecorder(),
                              postmortem_dir=str(tmp_path / "run"))
    with pytest.raises(IOError, match="dead feed"):  # not the OSError
        trainer.fit(dies(), num_steps=10)
    assert (tmp_path / "run" / "postmortem.jsonl").exists()
    assert not (tmp_path / "run" / "spans.jsonl").exists()


def test_optimizer_clip_grad_norm_wired(mesh8):
    """clip_grad_norm on OptimizerConfig must actually clip."""
    big = make_batch(16)
    big["y"] = big["y"] * 1e6  # huge grads
    tx = make_optimizer(OptimizerConfig(name="sgd", learning_rate=1.0,
                                        clip_grad_norm=1e-3))
    state, specs = init_train_state(linear_init, tx, mesh8, jax.random.PRNGKey(0))
    trainer = Trainer(make_train_step(linear_loss, tx), state, mesh8, specs)
    before = np.asarray(jax.tree.leaves(state.params)[0]).copy()
    state2 = trainer.fit([big], num_steps=1)
    after = np.asarray(jax.tree.leaves(state2.params)[0])
    # update magnitude bounded by lr * clip_norm
    assert np.abs(after - before).max() <= 1e-3 + 1e-6


@pytest.mark.parametrize("name", ["sgd", "momentum", "adam", "rmsprop",
                                  "adagrad"])
def test_weight_decay_honored_everywhere(name):
    """OptimizerConfig.weight_decay must shrink kernels (ndim>1) for every
    non-decoupled optimizer, not silently no-op."""
    tx = make_optimizer(OptimizerConfig(name=name, learning_rate=0.1,
                                        weight_decay=0.5))
    params = {"kernel": jnp.ones((2, 2)), "bias": jnp.ones((2,))}
    grads = jax.tree.map(jnp.zeros_like, params)
    updates, _ = tx.update(grads, tx.init(params), params)
    assert float(jnp.max(updates["kernel"])) < 0  # decay pulls down
    assert float(jnp.abs(updates["bias"]).max()) < 1e-5  # biases exempt


def test_ftrl_l1_applies():
    tx = make_optimizer(OptimizerConfig(name="ftrl", learning_rate=0.1, l1=0.5))
    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.zeros((4,))}
    opt_state = tx.init(params)
    updates, _ = tx.update(grads, opt_state, params)
    # zero grads + positive weights + l1 → negative (shrinking) update
    assert float(jnp.max(updates["w"])) < 0


def test_preemption_saved_is_clean_stop(mesh8, tmp_path):
    """PreemptionSaved must stop the loop cleanly (failed=False) with the
    state on disk — the restart-and-resume contract (SURVEY.md §5.3)."""
    from distributed_tensorflow_tpu.train.checkpoint import PreemptionSaved

    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "pre"), save_interval_steps=100,
                         async_save=False, save_on_preemption=False),
        mesh8,
    )
    state, specs, _ = init_or_restore(ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0))

    class FakePreempt(cb.Callback):
        def on_step_end(self, trainer, step, metrics):
            if step == 2:
                ckpt.save(step, trainer.state, force=True)
                ckpt.wait()
                raise PreemptionSaved(step)

    trainer = Trainer(
        make_train_step(linear_loss, tx), state, mesh8, specs,
        callbacks=[FakePreempt(), cb.CheckpointCallback(ckpt)],
    )
    final = trainer.fit(batches(10), num_steps=10)  # must not raise
    assert not trainer.failed
    assert int(final.step) == 2
    assert ckpt.latest_step() == 2
    ckpt.close()


def test_restore_none_when_empty(mesh8, tmp_path):
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "empty"), async_save=False),
        mesh8,
    )
    assert ckpt.latest_step() is None
    ckpt.close()


def test_manifest_written_and_verified(mesh8, tmp_path):
    """Production saves stamp each step dir with a CRC-trailered
    MANIFEST.dtf via the native IO path, and
    restore refuses a checkpoint whose shards don't match it."""
    import os

    from distributed_tensorflow_tpu.runtime import io as io_lib

    tx = optax.sgd(0.1)
    ckdir = tmp_path / "m"
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(ckdir), async_save=False,
                         save_on_preemption=False),
        mesh8,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    assert ckpt.save(0, state, force=True)
    man = ckdir / "0" / "MANIFEST.dtf"
    assert man.exists()
    payload = io_lib.read_payload(str(man))  # CRC round-trips
    import json as json_lib

    manifest = json_lib.loads(payload)
    assert manifest["step"] == 0 and manifest["files"]
    assert ckpt.verify_manifest(0) is True

    # restore succeeds with intact shards
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
    )
    assert ckpt.restore(abstract, step=0) is not None

    # truncate a listed shard -> restore refuses
    biggest = max(manifest["files"], key=lambda e: e["bytes"])
    victim = ckdir / "0" / biggest["path"]
    victim.write_bytes(victim.read_bytes()[:-1])
    with pytest.raises(OSError, match="manifest says|missing shard"):
        ckpt.restore(abstract, step=0)
    ckpt.close()


def test_manifest_async_save(mesh8, tmp_path):
    """Async saves stamp the manifest after the background commit."""
    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "a"), async_save=True,
                         save_on_preemption=False),
        mesh8,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    assert ckpt.save(0, state, force=True)
    ckpt.wait()
    assert (tmp_path / "a" / "0" / "MANIFEST.dtf").exists()
    assert ckpt.verify_manifest(0) is True
    ckpt.close()


def test_close_joins_last_manifest_stamper(mesh8, tmp_path):
    """Regression (ISSUE 12 satellite): saves only PRUNE dead entries
    from _manifest_threads, so the LAST save's async stamper has nobody
    behind it — close() (via wait()) must join it, or the final
    checkpoint silently lacks MANIFEST.dtf."""
    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "j"), async_save=True,
                         save_on_preemption=False),
        mesh8,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    assert ckpt.save(0, state, force=True)
    # close() WITHOUT an explicit wait(): the stamper must still be
    # drained and the manifest on disk
    ckpt.close()
    assert ckpt._manifest_threads == []
    assert (tmp_path / "j" / "0" / "MANIFEST.dtf").exists()
    assert ckpt.verify_manifest(0) is True


class _FakeHeartbeatWriter:
    """The HeartbeatWriter duck-type Checkpointer(heartbeat=) consumes:
    ``beat`` + ``phase``, with every beat recorded for assertions."""

    def __init__(self):
        self._phase = "train"
        self.beats = []

    @property
    def phase(self):
        return self._phase

    def beat(self, step=None, attempt=None, phase=None):
        if phase is not None:
            self._phase = phase
        self.beats.append((step, phase))


def test_save_brackets_fleet_heartbeat_phase(mesh8, tmp_path):
    """With a fleet heartbeat wired, every save beats phase ``save`` for
    the write's duration and then restores the previous phase — the
    signal the elastic fleet reads to gang-stop (not shrink) around a
    death that landed mid-checkpoint."""
    w = _FakeHeartbeatWriter()
    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "hb"), async_save=False,
                         save_on_preemption=False),
        mesh8, heartbeat=w,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    assert ckpt.save(0, state, force=True)
    phases = [p for _, p in w.beats if p is not None]
    assert phases == ["save", "train"]  # bracketed, previous restored
    assert w.phase == "train"
    # a refused/duplicate save never beats (no write happened)
    n = len(w.beats)
    assert not ckpt.save(0, state, force=True)
    assert len(w.beats) == n
    ckpt.close()


def test_async_save_holds_save_phase_until_commit(mesh8, tmp_path):
    """With async_save the heavy shard writes happen on orbax's threads
    AFTER save() returns — the heartbeat must keep phase ``save`` for
    that whole window (a death during the background writes can tear
    the step dir, and the elastic fleet reads the phase to gang-stop
    instead of shrinking around it), restoring the previous phase only
    once the commit lands."""
    import time

    w = _FakeHeartbeatWriter()
    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "ah"), async_save=True,
                         save_on_preemption=False),
        mesh8, heartbeat=w,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    assert ckpt.save(0, state, force=True)
    ckpt.wait()  # commit landed; the phase-restore thread races us only
    deadline = time.monotonic() + 10.0
    while w.phase != "train" and time.monotonic() < deadline:
        time.sleep(0.01)
    phases = [p for _, p in w.beats if p is not None]
    assert phases == ["save", "train"], phases
    ckpt.close()


def test_stale_phase_restore_cannot_clear_a_newer_save_window(mesh8,
                                                              tmp_path):
    """Back-to-back async saves: the FIRST save's phase-restore thread
    waking after a NEWER save began must not beat the phase back to
    'train' while the newer save's shard writes are in flight — the
    save-sequence guard drops the stale restore."""
    tx = optax.sgd(0.1)
    w = _FakeHeartbeatWriter()
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "sq"), async_save=False,
                         save_on_preemption=False),
        mesh8, heartbeat=w,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    assert ckpt.save(0, state, force=True)  # seq 1, bracketed normally
    # simulate a newer save owning the window while save 1's restore
    # thread wakes late
    with ckpt._hb_lock:
        ckpt._hb_save_seq += 1  # "save 2" started
    w.beat(phase="save")        # ...and beat its save window
    ckpt._restore_phase("train", seq=1)  # save 1's stale restore
    assert w.phase == "save"    # the newer window survives
    # and a restore landing while something ELSE owns the phase (a
    # resize barrier) must never clobber it either
    w.beat(phase="barrier")
    ckpt._restore_phase("train", seq=ckpt._hb_save_seq)
    assert w.phase == "barrier"
    w.beat(phase="save")
    ckpt._restore_phase("train", seq=ckpt._hb_save_seq)  # the owner's
    assert w.phase == "train"
    ckpt.close()


def test_wait_bounds_straggler_join_and_logs_step(mesh8, tmp_path, caplog):
    """A stamper that outlives the bounded join must not hang wait()
    forever: it is logged BY STEP (naming the checkpoint that may lack
    its manifest) and retained so a later wait() retries the join."""
    import logging
    import threading

    tx = optax.sgd(0.1)
    ckpt = Checkpointer(
        CheckpointConfig(directory=str(tmp_path / "s"), async_save=False,
                         save_on_preemption=False),
        mesh8,
    )
    state, specs, _ = init_or_restore(
        ckpt, linear_init, tx, mesh8, jax.random.PRNGKey(0)
    )
    release = threading.Event()
    t = threading.Thread(target=release.wait, daemon=True)
    t.start()
    ckpt._manifest_threads = [(7, t)]
    with caplog.at_level(logging.ERROR,
                         logger="distributed_tensorflow_tpu.train.checkpoint"):
        ckpt.wait(manifest_join_s=0.05)  # bounded: returns, never hangs
    assert "manifest thread for step 7" in caplog.text
    assert [s for s, _ in ckpt._manifest_threads] == [7]  # retained
    release.set()
    ckpt.wait(manifest_join_s=5.0)  # the retry drains it
    assert ckpt._manifest_threads == []
    ckpt.close()


def test_ftrl_matches_tf_reference():
    """Exact-FTRL parity oracle: our optax ftrl() tracks
    tf.compat.v1.train.FtrlOptimizer ($TF/python/training/ftrl.py) step
    for step on the same gradient sequence, including L1 sparsification
    and L2 shrinkage."""
    import optax
    tf = pytest.importorskip("tensorflow")

    from distributed_tensorflow_tpu.train.optimizers import ftrl

    rng = np.random.RandomState(0)
    w0 = rng.randn(12).astype(np.float32)
    grads = [rng.randn(12).astype(np.float32) * 0.5 for _ in range(6)]
    lr, l1, l2 = 0.1, 0.5, 0.02

    # TF reference trajectory
    var = tf.Variable(w0)
    opt = tf.compat.v1.train.FtrlOptimizer(
        learning_rate=lr, learning_rate_power=-0.5,
        l1_regularization_strength=l1, l2_regularization_strength=l2,
    )
    tf_traj = []
    for g in grads:
        opt.apply_gradients([(tf.constant(g), var)])
        tf_traj.append(var.numpy().copy())

    # ours
    tx = ftrl(lr, lr_power=-0.5, l1=l1, l2=l2)
    params = jnp.asarray(w0)
    state = tx.init(params)
    for g, want in zip(grads, tf_traj):
        upd, state = tx.update(jnp.asarray(g), state, params)
        params = optax.apply_updates(params, upd)
        np.testing.assert_allclose(np.asarray(params), want,
                                   rtol=1e-5, atol=1e-6)
    # L1 actually sparsifies
    assert (np.asarray(params) == 0).sum() > 0


def test_ftrl_warmup_and_bf16_and_tuple_trees():
    """Regressions: lr=0 warmup step is a no-op (not NaN); accumulator
    dtypes are stable f32 for bf16 params; tuple-containing pytrees work."""
    import optax

    from distributed_tensorflow_tpu.train.optimizers import ftrl

    # warmup: step 0 has lr=0
    sched = optax.linear_schedule(0.0, 0.1, 3)
    tx = ftrl(sched)
    params = jnp.ones((4,), jnp.bfloat16)
    state = tx.init(params)
    assert state["z"].dtype == jnp.float32
    assert state["n"].dtype == jnp.float32
    upd, state = tx.update(jnp.ones((4,), jnp.bfloat16), state, params)
    assert np.all(np.asarray(upd, np.float32) == 0), "lr=0 must be a no-op"
    assert state["z"].dtype == jnp.float32  # unchanged across steps
    params = optax.apply_updates(params, upd)
    for _ in range(3):
        upd, state = tx.update(jnp.ones((4,), jnp.bfloat16), state, params)
        params = optax.apply_updates(params, upd)
    assert np.all(np.isfinite(np.asarray(params, np.float32)))

    # tuple-structured param tree
    tx2 = ftrl(0.1)
    pt = ({"w": jnp.ones((2,))}, {"b": jnp.zeros((3,))})
    st = tx2.init(pt)
    g = ({"w": jnp.ones((2,))}, {"b": jnp.ones((3,))})
    upd, st = tx2.update(g, st, pt)
    assert upd[0]["w"].shape == (2,) and upd[1]["b"].shape == (3,)


def test_multi_optimizer_path_rules():
    """make_multi_optimizer routes params to per-group transforms by path
    regex (first match wins) and falls through to the default."""
    import optax

    from distributed_tensorflow_tpu.train import make_multi_optimizer

    tx = make_multi_optimizer(
        rules=((r"(^|/)wide_", OptimizerConfig(name="sgd", learning_rate=1.0)),),
        default=OptimizerConfig(name="sgd", learning_rate=0.1),
    )
    params = {"wide_dense": jnp.ones((3,)), "deep_0": jnp.ones((3,))}
    state = tx.init(params)
    grads = {"wide_dense": jnp.ones((3,)), "deep_0": jnp.ones((3,))}
    upd, _ = tx.update(grads, state, params)
    # wide gets lr 1.0, deep gets lr 0.1
    np.testing.assert_allclose(np.asarray(upd["wide_dense"]), -np.ones(3),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(upd["deep_0"]), -0.1 * np.ones(3),
                               rtol=1e-6)
