"""Train-step engine tests, incl. the k-replica == 1-replica numerical
parity oracle (SURVEY.md §4.4, the strategy_test_lib pattern)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.parallel import (
    MeshSpec,
    build_mesh,
    sharding as sh,
    single_device_mesh,
)
from distributed_tensorflow_tpu.train import (
    StepOptions,
    init_train_state,
    jit_train_step,
    make_train_step,
)


def linear_init(key):
    k1, k2 = jax.random.split(key)
    params = {
        "w": jax.random.normal(k1, (8, 4)) * 0.1,
        "b": jnp.zeros((4,)),
    }
    return params, {}


def linear_loss(params, model_state, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    loss = jnp.mean((pred - batch["y"]) ** 2)
    return loss, (model_state, {"mse": loss})


def make_batch(n=16, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "x": rng.randn(n, 8).astype(np.float32),
        "y": rng.randn(n, 4).astype(np.float32),
    }


def _put(batch, mesh):
    return jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, sh.batch_spec(x.ndim))
        ),
        batch,
    )


def run_steps(mesh, n_steps=3, accum=1, batch=None):
    tx = optax.sgd(0.1)
    state, specs = init_train_state(
        linear_init, tx, mesh, jax.random.PRNGKey(0)
    )
    step = jit_train_step(
        make_train_step(linear_loss, tx, StepOptions(grad_accum_steps=accum)),
        mesh,
        specs,
    )
    batch = batch or make_batch()
    losses = []
    for _ in range(n_steps):
        state, metrics = step(state, _put(batch, mesh))
        losses.append(float(metrics["loss"]))
    return state, losses


def test_loss_decreases_single_device():
    _, losses = run_steps(single_device_mesh(jax.devices()[0]))
    assert losses[-1] < losses[0]


def test_dp8_matches_single_device(devices):
    """The distributed-correctness oracle: 8-way sync DP on the same global
    batch must produce bit-comparable results to 1 device."""
    mesh1 = single_device_mesh(devices[0])
    mesh8 = build_mesh(MeshSpec(data=8), devices[:8])
    batch = make_batch(n=16)
    s1, l1 = run_steps(mesh1, batch=batch)
    s8, l8 = run_steps(mesh8, batch=batch)
    np.testing.assert_allclose(l1, l8, rtol=1e-5, atol=1e-7)
    # tolerance covers cross-device reduction-order float noise
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s8.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)


def test_grad_accum_matches_full_batch(devices):
    """accum=4 over the same global batch == accum=1 (mean-of-means)."""
    mesh = build_mesh(MeshSpec(data=8), devices[:8])
    batch = make_batch(n=32)
    s1, l1 = run_steps(mesh, accum=1, batch=batch)
    s4, l4 = run_steps(mesh, accum=4, batch=batch)
    np.testing.assert_allclose(l1, l4, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s4.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4)


def test_metrics_contents(mesh8):
    tx = optax.sgd(0.1)
    state, specs = init_train_state(linear_init, tx, mesh8, jax.random.PRNGKey(0))
    step = jit_train_step(
        make_train_step(linear_loss, tx,
                        StepOptions(clip_grad_norm=1.0, check_grads_finite=True)),
        mesh8, specs,
    )
    state, metrics = step(state, _put(make_batch(), mesh8))
    assert {"loss", "mse", "grad_norm", "grads_finite"} <= set(metrics)
    assert float(metrics["grads_finite"]) == 1.0
    assert int(state.step) == 1


def test_sharded_params_tp(mesh_dp4_tp2):
    """Params sharded over model axis via path rules; step still correct."""
    tx = optax.adam(1e-2)
    state, specs = init_train_state(
        linear_init, tx, mesh_dp4_tp2, jax.random.PRNGKey(0),
        param_rules=[(r"w", P(None, "model"))],
    )
    assert state.params["w"].sharding.spec == P(None, "model")
    # Adam slots inherit the param sharding (weight-update sharding hook).
    mu_w = state.opt_state[0].mu["w"]
    assert mu_w.sharding.spec == P(None, "model")
    step = jit_train_step(make_train_step(linear_loss, tx), mesh_dp4_tp2, specs)
    state, metrics = step(state, _put(make_batch(), mesh_dp4_tp2))
    assert np.isfinite(float(metrics["loss"]))


def test_fsdp_auto_sharding(devices):
    mesh = build_mesh(MeshSpec(data=2, fsdp=4), devices[:8])

    def big_init(key):
        return {"w": jax.random.normal(key, (256, 128))}, {}

    tx = optax.sgd(0.1)
    state, specs = init_train_state(
        big_init, tx, mesh, jax.random.PRNGKey(0), fsdp=True
    )
    spec = state.params["w"].sharding.spec
    assert "fsdp" in str(spec)


def test_grads_finite_free_via_grad_norm(devices):
    """When grad-norm/clipping is already on, grads_finite derives from
    the global norm at zero extra cost — same-step NaN signal without
    the per-leaf isfinite pass."""
    mesh = build_mesh(MeshSpec(data=2), devices[:2])
    tx = optax.sgd(0.1)

    def loss_fn(params, model_state, batch, rng):
        loss = (params["w"] * batch["x"]).sum() * batch["scale"]
        return loss, (model_state, {})

    state, specs = init_train_state(
        lambda rng: ({"w": jnp.ones(4)}, {}), tx, mesh,
        jax.random.PRNGKey(0),
    )
    step = jit_train_step(
        make_train_step(loss_fn, tx, StepOptions(clip_grad_norm=1.0)),
        mesh, specs,
    )
    good = {"x": jnp.ones(4), "scale": jnp.float32(1.0)}
    state, m = step(state, good)
    assert float(m["grads_finite"]) == 1.0 and "grad_norm" in m
    bad = {"x": jnp.ones(4), "scale": jnp.float32(np.nan)}
    _, m = step(state, bad)
    assert float(m["grads_finite"]) == 0.0  # SAME step, not one later
