"""Distributed eval (train/evaluation.py, ISSUE 11): the sharded eval
path must be BIT-identical to a serial evaluator on the 8-device CPU
mesh, tick its obs surface, and leave the train loop's step cadence
unperturbed (the note_pause seam)."""

import numpy as np
import optax
import pytest

import jax

from distributed_tensorflow_tpu import obs
from distributed_tensorflow_tpu.models import MLP, MLPConfig, common
from distributed_tensorflow_tpu.obs import flightrec as fr
from distributed_tensorflow_tpu.parallel import (
    MeshSpec, build_mesh, single_device_mesh,
)
from distributed_tensorflow_tpu.train import (
    ShardedEvaluator, callbacks as cb, derive_metrics, init_train_state,
)
from distributed_tensorflow_tpu.train.evaluation import batch_shards


def _mlp_fixture(mesh, hidden=(512, 512), classes=100, dim=64):
    cfg = MLPConfig(hidden_sizes=hidden, num_classes=classes)
    model = MLP(cfg)
    eval_fn = common.classification_eval_fn(model)
    state, _ = init_train_state(
        common.make_init_fn(model, (dim,)), optax.sgd(0.1), mesh,
        jax.random.PRNGKey(0),
    )
    return eval_fn, state


def _batches(n, batch, dim=64, classes=100, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {"image": rng.randn(batch, dim).astype(np.float32),
         "label": rng.randint(0, classes, batch).astype(np.int32)}
        for _ in range(n)
    ]


def test_sharded_eval_bit_identical_to_serial(devices):
    """THE acceptance gate: eval loss (and every summed statistic) from
    the dp8-sharded evaluator equals a serial single-device evaluator
    bit for bit — at a shape where naive GSPMD partitioning provably
    differs in the last ulp (512-wide MLP, batch 256; measured)."""
    mesh8 = build_mesh(MeshSpec(data=8), devices[:8])
    mesh1 = single_device_mesh(devices[0])
    batches = _batches(3, 256)

    eval_fn8, state8 = _mlp_fixture(mesh8)
    evaluator = ShardedEvaluator(eval_fn8, mesh8, registry=obs.Registry())
    sharded = evaluator.run(state8, iter(batches), 3)

    # serial path: same weights on ONE device, same chunks in the same
    # order through the plain per-chunk jit, float64 host accumulation
    eval_fn1, state1 = _mlp_fixture(mesh1)
    chunk_step = jax.jit(
        lambda state, b: eval_fn1(state.params, state.model_state, b))
    shards = batch_shards(mesh8)
    serial: dict = {}
    for batch in batches:
        per = batch["label"].shape[0] // shards
        for s in range(shards):
            chunk = jax.tree.map(
                lambda x: x[s * per:(s + 1) * per], batch)
            chunk = jax.device_put(chunk, devices[0])
            out = chunk_step(state1, chunk)
            for k, v in out.items():
                serial[k] = serial.get(k, 0.0) + np.asarray(v, np.float64)

    assert set(sharded) == set(serial)
    for k in sharded:
        a = np.asarray(sharded[k], np.float64)
        b = np.asarray(serial[k], np.float64)
        assert a.tobytes() == b.tobytes(), (
            f"{k}: sharded {a!r} != serial {b!r} (bitwise)")
    m = derive_metrics(sharded)
    assert m["loss"] == pytest.approx(
        float(serial["loss_sum"] / serial["count"]))


def test_sharded_eval_same_result_across_meshes(devices):
    """The reduction tree is pinned by the program — the per-shard chunk
    — not by the rest of the mesh: meshes that cut the batch into the
    same number of shards (dp4×tp2 and dp2×fsdp2×tp2) evaluate to the
    same bits. A different shard count (dp8) is a different chunking, so
    XLA sums each chunk's float32 statistics in another order: counts
    stay exact, float sums agree to a few float32 ulps."""
    meshes = [build_mesh(MeshSpec(data=4, model=2), devices[:8]),
              build_mesh(MeshSpec(data=2, fsdp=2, model=2), devices[:8]),
              build_mesh(MeshSpec(data=8), devices[:8])]
    batches = _batches(2, 128)
    results = []
    for mesh in meshes:
        eval_fn, state = _mlp_fixture(mesh, hidden=(64, 64))
        ev = ShardedEvaluator(eval_fn, mesh, registry=obs.Registry())
        results.append({k: np.asarray(v, np.float64) for k, v in
                        ev.run(state, iter(batches), 2).items()})
    same_chunks, same_chunks_2, other_chunks = results
    for k, a in same_chunks.items():
        assert a.tobytes() == same_chunks_2[k].tobytes(), \
            f"{k} differs across meshes with the same batch shards"
        np.testing.assert_allclose(
            other_chunks[k], a, atol=0,
            rtol=0 if k == "count" else 4 * np.finfo(np.float32).eps,
            err_msg=f"{k} differs across shard counts beyond rounding")


def test_eval_obs_surface(devices):
    """Each eval batch ticks eval_steps_total; each pass brackets its
    batches with eval_start/eval_end in the flight recorder."""
    mesh = build_mesh(MeshSpec(data=8), devices[:8])
    reg = obs.Registry()
    rec = fr.FlightRecorder(capacity=64)
    eval_fn, state = _mlp_fixture(mesh, hidden=(64, 64))
    ev = ShardedEvaluator(eval_fn, mesh, registry=reg, flightrec=rec)
    ev.run(state, iter(_batches(3, 64)), 3, step=7)
    assert reg.get("eval_steps_total").value == 3
    assert fr.contains_in_order(
        rec.events(),
        [("eval_start", {"step": 7, "shards": 8}),
         ("eval_end", {"step": 7, "batches": 3})])
    ev.run(state, iter(_batches(2, 64)), 2)
    assert reg.get("eval_steps_total").value == 5


def test_indivisible_batch_falls_back_flat(devices, caplog):
    """A batch that doesn't divide by the shard count still evaluates
    (flat fallback), with a one-time warning — correct, just outside
    the bit-exact contract."""
    import logging

    mesh = build_mesh(MeshSpec(data=8), devices[:8])
    eval_fn, state = _mlp_fixture(mesh, hidden=(64, 64))
    ev = ShardedEvaluator(eval_fn, mesh, registry=obs.Registry())
    batches = _batches(2, 60)  # 60 % 8 != 0
    with caplog.at_level(logging.WARNING,
                         logger="distributed_tensorflow_tpu.train.evaluation"):
        totals = ev.run(state, iter(batches), 2)
    assert totals["count"] == pytest.approx(120.0)
    assert sum("does not divide" in r.message for r in caplog.records) == 1
    m = derive_metrics(totals)
    assert 0.0 <= m["accuracy"] <= 1.0 and np.isfinite(m["loss"])


def test_note_pause_keeps_cadence_clean():
    """A mid-train eval pause reported through note_pause must not leak
    into train_step_seconds, the productive-seconds ledger, or
    MetricsLogger's steps/sec — the 'eval does not perturb the step
    cadence' half of the distributed-eval contract."""
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    reg = obs.Registry()
    tc = cb.TelemetryCallback(registry=reg, every_n=10**9, clock=clock)
    ml = cb.MetricsLogger(every_n=2, batch_size=10, clock=clock)

    for c in (tc, ml):
        c.on_train_start(None)
    t[0] += 5.0  # compile window
    for c in (tc, ml):
        c.on_step_end(None, 1, {})
    t[0] += 1.0
    for c in (tc, ml):
        c.on_step_end(None, 2, {})
    # eval pause: 3s off the train path between steps 2 and 3
    t[0] += 3.0
    for c in (tc, ml):
        c.note_pause(3.0)
    t[0] += 1.0
    for c in (tc, ml):
        c.on_step_end(None, 3, {})
    t[0] += 1.0
    for c in (tc, ml):
        c.on_step_end(None, 4, {})

    h = reg.get("train_step_seconds")
    assert h.count == 3 and h.sum == pytest.approx(3.0)  # 3 × 1s steps
    assert reg.get("goodput_productive_seconds_total").value == \
        pytest.approx(3.0)
    assert reg.get("wasted_seconds_total",
                   cause="compile_warmup").value == pytest.approx(5.0)
    # MetricsLogger cadence window steps 2→4 spans 5s wall incl. the 3s
    # pause; steps/sec must read 2 steps / 2s of train time
    assert ml.last["steps_per_sec"] == pytest.approx(1.0)


def test_note_pause_rearms_watchdog_and_heartbeat():
    """The pause protocol reaches the liveness observers too: a
    finished eval re-arms the Watchdog beat (no stall abort right after
    a long eval) and writes a heartbeat (the fleet monitor's silent
    window ends at the pause boundary)."""
    t = [0.0]
    w = cb.Watchdog(budget_s=5.0, clock=lambda: t[0],
                    registry=obs.Registry(), poll_s=1000.0)
    w.on_train_start(None)
    try:
        t[0] = 10.0  # eval pause longer than the budget just ended
        w.note_pause(10.0)
        with w._lock:
            assert w._beat == 10.0  # budget clock restarted at pause end
    finally:
        w.on_train_end(None)

    class FakeWriter:
        calls = 0

        def beat(self, **kw):
            FakeWriter.calls += 1

    hb = cb.HeartbeatCallback(FakeWriter())
    hb.note_pause(3.0)
    assert FakeWriter.calls == 1


def test_note_pause_inside_warmup_window():
    """A pause before the first completed step must stay out of the
    compile_warmup waste bucket too."""
    t = [0.0]
    reg = obs.Registry()
    tc = cb.TelemetryCallback(registry=reg, every_n=10**9,
                              clock=lambda: t[0])
    tc.on_train_start(None)
    t[0] += 4.0
    tc.note_pause(3.0)
    t[0] += 1.0
    tc.on_step_end(None, 1, {})
    assert reg.get("wasted_seconds_total",
                   cause="compile_warmup").value == pytest.approx(2.0)


def test_runner_eval_paths_use_sharded_evaluator(devices, tmp_path):
    """The runner's standalone eval-from-checkpoint flows through the
    distributed evaluator and agrees with the live-trainer eval it
    checkpointed from (both sharded, same reduction)."""
    from distributed_tensorflow_tpu import workloads

    overrides = [
        "--train.num_steps=6", "--train.log_every=3",
        "--train.eval_batches=2", "--data.global_batch_size=64",
        f"--checkpoint.directory={tmp_path}/ck",
        "--checkpoint.save_interval_steps=5",
        "--checkpoint.async_save=false",
        "--checkpoint.save_on_preemption=false",
    ]
    result = workloads.run_workload("mnist_mlp", overrides)
    mod = workloads.get("mnist_mlp")
    cfg = mod.default_config()
    from distributed_tensorflow_tpu.utils import config as config_lib

    cfg = config_lib.apply_overrides(cfg, overrides)
    again = workloads.evaluate_from_checkpoint(cfg, mod.build)
    assert again["step"] == 6
    assert again["loss"] == pytest.approx(result.eval_metrics["loss"])


def test_flash_attention_model_evaluates_sharded(devices, caplog):
    """The transformer's flash call wraps itself in shard_map on a mesh
    (a Mosaic kernel cannot be partitioned by GSPMD); inside the
    evaluator's own shard_map it must call the kernel directly instead of
    nesting — otherwise every multi-chip eval is demoted to the flat
    path. Interpret-mode kernel here; same values as dense attention."""
    import dataclasses
    import logging

    from distributed_tensorflow_tpu.models import transformer as tfm

    mesh = build_mesh(MeshSpec(data=4, model=2), devices[:8])
    cfg = tfm.TransformerConfig(
        vocab_size=128, max_len=256, num_layers=1, d_model=32, num_heads=2,
        d_ff=64, dropout=0.0, dtype="float32", causal=True, pre_ln=True,
        attention_impl="flash")
    rng = np.random.RandomState(0)
    batches = [{"input_ids": rng.randint(0, 128, (8, 256)).astype(np.int32)}]
    totals = {}
    for impl in ("flash", "dense"):
        model = tfm.Transformer(
            dataclasses.replace(cfg, attention_impl=impl), mesh)
        state, _ = init_train_state(
            tfm.make_init_fn(model, 256), optax.sgd(0.1), mesh,
            jax.random.PRNGKey(0), param_rules=tfm.transformer_rules(cfg))
        ev = ShardedEvaluator(tfm.lm_eval_fn(model), mesh,
                              registry=obs.Registry())
        with caplog.at_level(logging.WARNING):
            totals[impl] = ev.run(state, iter(batches), 1)
    assert "falling back" not in caplog.text, caplog.text
    np.testing.assert_allclose(totals["flash"]["loss_sum"],
                               totals["dense"]["loss_sum"], rtol=1e-5)
