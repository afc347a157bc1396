import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import common
from distributed_tensorflow_tpu.models.resnet import (
    ResNet50,
    ResNetConfig,
    flops_per_example,
)


def tiny_cfg(**kw):
    defaults = dict(stage_sizes=(1, 1, 1, 1), width=8, num_classes=10,
                    dtype="float32")
    defaults.update(kw)
    return ResNetConfig(**defaults)


@pytest.mark.slow
def test_resnet_forward_shape_and_params():
    model = ResNet50(tiny_cfg())
    init_fn = common.make_init_fn(model, (32, 32, 3))
    params, mstate = init_fn(jax.random.PRNGKey(0))
    assert "batch_stats" in mstate
    logits = model.apply(
        {"params": params, **mstate}, jnp.zeros((2, 32, 32, 3)), train=False
    )
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32


def test_space_to_depth_stem():
    from distributed_tensorflow_tpu.models.resnet import space_to_depth

    # fold/unfold bookkeeping: channels carry the 2x2 patch
    x = jnp.arange(2 * 4 * 4 * 3, dtype=jnp.float32).reshape(2, 4, 4, 3)
    y = space_to_depth(x, 2)
    assert y.shape == (2, 2, 2, 12)
    np.testing.assert_array_equal(y[0, 0, 0, :3], x[0, 0, 0])
    np.testing.assert_array_equal(y[0, 0, 0, 3:6], x[0, 0, 1])

    # stem kernel is the folded 4x4x(C*4) layout — shape-level only
    # (eval_shape: no compile; the compiled end-to-end twin is the slow
    # test below, so the fast tier stays under the 200s budget)
    cfg = tiny_cfg(stem="space_to_depth")
    model = ResNet50(cfg)
    init_fn = common.make_init_fn(model, (32, 32, 3))
    params, _ = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    assert params["stem_conv_s2d"]["kernel"].shape == (4, 4, 12, 8)
    assert flops_per_example(cfg, 32) != flops_per_example(tiny_cfg(), 32)


@pytest.mark.slow
def test_space_to_depth_stem_forward_compiles():
    cfg = tiny_cfg(stem="space_to_depth")
    model = ResNet50(cfg)
    params, mstate = common.make_init_fn(model, (32, 32, 3))(
        jax.random.PRNGKey(0)
    )
    logits = model.apply(
        {"params": params, **mstate}, jnp.zeros((2, 32, 32, 3)), train=False
    )
    assert logits.shape == (2, 10)


@pytest.mark.slow
def test_resnet_train_step_updates_bn_stats(mesh8):
    import optax

    from distributed_tensorflow_tpu.train import (
        init_train_state, jit_train_step, make_train_step,
    )

    model = ResNet50(tiny_cfg())
    loss_fn = common.classification_loss_fn(model)
    tx = optax.sgd(0.1)
    state, specs = init_train_state(
        common.make_init_fn(model, (16, 16, 3)), tx, mesh8, jax.random.PRNGKey(0)
    )
    before = np.asarray(
        jax.tree.leaves(state.model_state["batch_stats"])[0]
    ).copy()
    step = jit_train_step(make_train_step(loss_fn, tx), mesh8, specs)
    batch = {
        "image": jnp.asarray(np.random.RandomState(0).randn(8, 16, 16, 3),
                             jnp.float32),
        "label": jnp.zeros((8,), jnp.int32),
    }
    from jax.sharding import NamedSharding
    from distributed_tensorflow_tpu.parallel import sharding as sh

    batch = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh8, sh.batch_spec(x.ndim))),
        batch,
    )
    state, metrics = step(state, batch)
    after = np.asarray(jax.tree.leaves(state.model_state["batch_stats"])[0])
    assert not np.array_equal(before, after), "BN stats did not update"
    assert np.isfinite(float(metrics["loss"]))


def test_resnet50_flops_sane():
    # ResNet-50 ≈ 4.1 GMACs = 8.2 GFLOPs fwd @224 (fwd-only contract,
    # utils/flops.py; the ×3 train multiplier is the consumer's job)
    f = flops_per_example(ResNetConfig(), 224)
    assert 6.5e9 < f < 9.5e9, f


@pytest.mark.slow
def test_resnet_bf16_params_stay_f32():
    model = ResNet50(tiny_cfg(dtype="bfloat16"))
    params, _ = common.make_init_fn(model, (16, 16, 3))(jax.random.PRNGKey(0))
    kinds = {p.dtype for p in jax.tree.leaves(params)}
    assert kinds == {jnp.dtype("float32")}, kinds


@pytest.mark.slow
def test_fused_block_impl_matches_standard():
    """Same params through the fused-kernel blocks == the standard flax
    blocks, forward (train + eval) and gradients, and the batch_stats
    updates agree — the param trees are identical by construction."""
    cfg_std = tiny_cfg()
    cfg_fused = tiny_cfg(block_impl="fused")
    m_std = ResNet50(cfg_std)
    m_fused = ResNet50(cfg_fused)
    params, mstate = common.make_init_fn(m_std, (32, 32, 3))(
        jax.random.PRNGKey(0)
    )
    params_f, mstate_f = common.make_init_fn(m_fused, (32, 32, 3))(
        jax.random.PRNGKey(0)
    )
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a.shape, b.shape),
                 params, params_f)

    x = jnp.asarray(np.random.RandomState(0).randn(8, 32, 32, 3), jnp.float32)

    # eval forward
    e_std = m_std.apply({"params": params, **mstate}, x, train=False)
    e_fused = m_fused.apply({"params": params, **mstate}, x, train=False)
    np.testing.assert_allclose(np.asarray(e_fused), np.asarray(e_std),
                               rtol=1e-4, atol=1e-4)

    # train forward + batch_stats updates
    def fwd(model, p):
        out, mut = model.apply(
            {"params": p, **mstate}, x, train=True, mutable=["batch_stats"]
        )
        return out, mut["batch_stats"]

    t_std, bs_std = fwd(m_std, params)
    t_fused, bs_fused = fwd(m_fused, params)
    np.testing.assert_allclose(np.asarray(t_fused), np.asarray(t_std),
                               rtol=2e-3, atol=2e-3)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        ),
        bs_fused, bs_std,
    )

    # gradients
    def loss(model):
        def go(p):
            out, _ = model.apply(
                {"params": p, **mstate}, x, train=True,
                mutable=["batch_stats"],
            )
            return (out.astype(jnp.float32) ** 2).mean()
        return go

    g_std = jax.grad(loss(m_std))(params)
    g_fused = jax.grad(loss(m_fused))(params)
    flat_s, _ = jax.flatten_util.ravel_pytree(g_std)
    flat_f, _ = jax.flatten_util.ravel_pytree(g_fused)
    np.testing.assert_allclose(np.asarray(flat_f), np.asarray(flat_s),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.slow
def test_fused_block_impl_through_dp_mesh(devices):
    """Fused blocks under a data=8 mesh (shard_map psum stats) match the
    standard model under plain GSPMD on the same global batch."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=8), devices[:8])
    cfg_fused = tiny_cfg(block_impl="fused")
    m_std = ResNet50(tiny_cfg())
    m_fused = ResNet50(cfg_fused, mesh)
    params, mstate = common.make_init_fn(m_std, (32, 32, 3))(
        jax.random.PRNGKey(0)
    )
    x = jnp.asarray(np.random.RandomState(1).randn(16, 32, 32, 3), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P(("data",))))

    def fwd(model, p, xin):
        out, mut = model.apply(
            {"params": p, **mstate}, xin, train=True, mutable=["batch_stats"]
        )
        return out, mut["batch_stats"]

    want, bs_want = jax.jit(lambda p: fwd(m_std, p, x))(params)
    got, bs_got = jax.jit(lambda p: fwd(m_fused, p, xs))(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
        ),
        bs_got, bs_want,
    )

    # gradients through shard_map + psum'd BN stats + the Pallas
    # custom_vjp — the fused blocks' path on TPU
    def loss(model, xin):
        def go(p):
            out, _ = model.apply(
                {"params": p, **mstate}, xin, train=True,
                mutable=["batch_stats"],
            )
            return (out.astype(jnp.float32) ** 2).mean()
        return go

    g_std = jax.jit(jax.grad(loss(m_std, x)))(params)
    g_fused = jax.jit(jax.grad(loss(m_fused, xs)))(params)
    flat_s, _ = jax.flatten_util.ravel_pytree(jax.device_get(g_std))
    flat_f, _ = jax.flatten_util.ravel_pytree(jax.device_get(g_fused))
    np.testing.assert_allclose(np.asarray(flat_f), np.asarray(flat_s),
                               rtol=5e-3, atol=5e-3)
