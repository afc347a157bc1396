"""Pipeline-parallel tests: schedule parity vs sequential oracle (forward
and gradients), pp×dp composition through the train engine — the
strategy_test_lib-style distributed-correctness oracles of SURVEY.md §4.4."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.models import transformer as tfm
from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
from distributed_tensorflow_tpu.parallel import sharding as sh
from distributed_tensorflow_tpu.parallel.pipeline import (
    microbatch,
    pipeline_apply,
    stack_stages,
    unmicrobatch,
)
from distributed_tensorflow_tpu.train import (
    StepOptions, init_train_state, jit_train_step, make_train_step,
)


def test_microbatch_roundtrip():
    x = jnp.arange(24.0).reshape(12, 2)
    mb = microbatch(x, 4)
    assert mb.shape == (4, 3, 2)
    np.testing.assert_array_equal(unmicrobatch(mb), x)
    with pytest.raises(ValueError, match="not divisible"):
        microbatch(x, 5)


def _toy_stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _toy_params(key, n_stages, d):
    keys = jax.random.split(key, n_stages)
    return stack_stages([
        {"w": jax.random.normal(k, (d, d)) * 0.5, "b": jnp.zeros((d,))}
        for k in keys
    ])


def _toy_sequential(params, x_mb):
    def per_mb(x):
        def body(x, p):
            return _toy_stage_fn(p, x), None

        y, _ = jax.lax.scan(body, x, params)
        return y

    return jax.vmap(per_mb)(x_mb)


def test_pipeline_matches_sequential(devices):
    mesh = build_mesh(MeshSpec(pipe=4, data=2), devices[:8])
    params = _toy_params(jax.random.PRNGKey(0), 4, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 6, 8))  # [M, mb, d]
    want = _toy_sequential(params, x)
    got = jax.jit(
        lambda p, x: pipeline_apply(_toy_stage_fn, p, x, mesh)
    )(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_pipeline_gradients_match(devices):
    mesh = build_mesh(MeshSpec(pipe=4), devices[:4])
    params = _toy_params(jax.random.PRNGKey(0), 4, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 4))

    def loss_pipe(p):
        return (pipeline_apply(_toy_stage_fn, p, x, mesh) ** 2).sum()

    def loss_seq(p):
        return (_toy_sequential(p, x) ** 2).sum()

    g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    g_seq = jax.jit(jax.grad(loss_seq))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        g_pipe, g_seq,
    )


def test_pipeline_rejects_too_few_microbatches(devices):
    mesh = build_mesh(MeshSpec(pipe=4), devices[:4])
    params = _toy_params(jax.random.PRNGKey(0), 4, 4)
    x = jnp.zeros((2, 2, 4))  # M=2 < S=4
    with pytest.raises(ValueError, match="microbatches"):
        pipeline_apply(_toy_stage_fn, params, x, mesh)


def _toy_chunks(key, n_chunks, d):
    keys = jax.random.split(key, n_chunks)
    return stack_stages([
        {"w": jax.random.normal(k, (d, d)) * 0.5, "b": jnp.zeros((d,))}
        for k in keys
    ])


def test_interleaved_matches_sequential(devices):
    """V=2 circular schedule == scanning all S*V chunks in order."""
    S, V, d = 4, 2, 8
    mesh = build_mesh(MeshSpec(pipe=S, data=2), devices[:8])
    flat = _toy_chunks(jax.random.PRNGKey(0), S * V, d)  # [S*V, ...]
    # device layout [S, V, ...]: chunk c = v*S + stage
    dev = jax.tree.map(
        lambda p: p.reshape(V, S, *p.shape[1:]).swapaxes(0, 1), flat
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 6, d))  # [M, mb, d]
    want = _toy_sequential(flat, x)
    got = jax.jit(
        lambda p, x: pipeline_apply(_toy_stage_fn, p, x, mesh, n_virtual=V)
    )(dev, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_interleaved_gradients_match(devices):
    S, V, d = 2, 2, 4
    mesh = build_mesh(MeshSpec(pipe=S), devices[:2])
    flat = _toy_chunks(jax.random.PRNGKey(0), S * V, d)
    dev = jax.tree.map(
        lambda p: p.reshape(V, S, *p.shape[1:]).swapaxes(0, 1), flat
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, d))

    def loss_pipe(p):
        return (pipeline_apply(_toy_stage_fn, p, x, mesh,
                               n_virtual=V) ** 2).sum()

    def loss_seq(p):
        return (_toy_sequential(p, x) ** 2).sum()

    g_pipe = jax.jit(jax.grad(loss_pipe))(dev)
    g_seq = jax.jit(jax.grad(loss_seq))(flat)
    g_pipe_flat = jax.tree.map(
        lambda p: p.swapaxes(0, 1).reshape(S * V, *p.shape[2:]), g_pipe
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        g_pipe_flat, g_seq,
    )


def test_interleaved_rejects_misaligned_microbatches(devices):
    mesh = build_mesh(MeshSpec(pipe=4), devices[:4])
    params = jax.tree.map(
        lambda p: p.reshape(2, 4, *p.shape[1:]).swapaxes(0, 1),
        _toy_chunks(jax.random.PRNGKey(0), 8, 4),
    )
    x = jnp.zeros((6, 2, 4))  # M=6 not divisible by S=4
    with pytest.raises(ValueError, match="divisible"):
        pipeline_apply(_toy_stage_fn, params, x, mesh, n_virtual=2)


def _tiny_cfg(**kw):
    base = dict(vocab_size=64, max_len=16, num_layers=4, d_model=32,
                num_heads=4, d_ff=64, causal=True, pre_ln=True,
                dtype="float32", dropout=0.0)
    base.update(kw)
    return tfm.TransformerConfig(**base)


def test_pipeline_params_roundtrip():
    cfg = _tiny_cfg()
    params, _ = tfm.make_init_fn(tfm.Transformer(cfg), 16)(
        jax.random.PRNGKey(0)
    )
    pparams = tfm.to_pipeline_params(params, cfg, n_stages=2)
    assert pparams["blocks"]["attn"]["query"]["kernel"].shape[:2] == (2, 2)
    back = tfm.from_pipeline_params(pparams, cfg)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        params, back,
    )


def test_pipelined_transformer_rejects_moe():
    cfg = _tiny_cfg(num_experts=4)
    with pytest.raises(ValueError, match="homogeneous"):
        tfm.make_pipelined_init_fn(cfg, n_stages=2, seq_len=16)


@pytest.mark.parametrize("family", [
    pytest.param("gpt", marks=pytest.mark.slow), "bert"])
def test_pipelined_transformer_matches_dense(devices, family):
    """Same weights through the pipeline schedule == the dense flax
    forward (the family shares the Block module, so this is an exact
    schedule-correctness oracle — including the masked/aux path for
    BERT)."""
    cfg = (
        _tiny_cfg()
        if family == "gpt"
        else _tiny_cfg(causal=False, pre_ln=False)
    )
    mesh = build_mesh(MeshSpec(pipe=4, data=2), devices[:8])
    model = tfm.Transformer(cfg)
    params, _ = tfm.make_init_fn(model, 16)(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)), jnp.int32)
    mask = None
    if family == "bert":
        mask = jnp.asarray(rng.rand(8, 16) < 0.9, jnp.int32)
    want = model.apply({"params": params}, ids, mask, train=False)
    pparams = tfm.to_pipeline_params(params, cfg, n_stages=4)
    got = jax.jit(
        lambda p, i: tfm.pipelined_apply(p, i, mask, cfg, mesh,
                                         n_microbatches=4)
    )(pparams, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_pipelined_transformer_interleaved_matches_dense(devices):
    """num_layers=4 over pipe=2 with n_virtual=2 (4 chunks of 1 layer,
    each device owning chunks {d, d+2}) == the dense forward; round-trip
    back to the dense layout is exact."""
    cfg = _tiny_cfg()
    mesh = build_mesh(MeshSpec(pipe=2, data=2), devices[:4])
    model = tfm.Transformer(cfg)
    params, _ = tfm.make_init_fn(model, 16)(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)), jnp.int32)
    want = model.apply({"params": params}, ids, None, train=False)
    pparams = tfm.to_pipeline_params(params, cfg, n_stages=2, n_virtual=2)
    assert pparams["blocks"]["attn"]["query"]["kernel"].shape[:3] == (2, 2, 1)
    got = jax.jit(
        lambda p, i: tfm.pipelined_apply(p, i, None, cfg, mesh,
                                         n_microbatches=4, n_virtual=2)
    )(pparams, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    back = tfm.from_pipeline_params(pparams, cfg, n_virtual=2)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        params, back,
    )


@pytest.mark.slow
def test_pipelined_transformer_trains(devices):
    """Full train-engine integration on a pipe=2 × data=2 × fsdp=2 mesh:
    loss decreases on the deterministic-walk corpus."""
    cfg = _tiny_cfg()
    mesh = build_mesh(MeshSpec(pipe=2, data=2, fsdp=2), devices[:8])
    tx = optax.adam(3e-3)
    init_fn = tfm.make_pipelined_init_fn(cfg, n_stages=2, seq_len=16)
    state, specs = init_train_state(
        init_fn, tx, mesh, jax.random.PRNGKey(0),
        param_specs=tfm.pipeline_param_specs(
            jax.eval_shape(init_fn, jax.random.PRNGKey(0))[0]
        ),
    )
    assert (
        state.params["blocks"]["attn"]["query"]["kernel"].sharding.spec[0]
        == "pipe"
    )
    step = jit_train_step(
        make_train_step(tfm.pipelined_lm_loss_fn(cfg, mesh, 4), tx,
                        StepOptions(check_grads_finite=True)),
        mesh, specs,
    )
    rng = np.random.RandomState(0)
    losses = []
    for i in range(25):
        start = rng.randint(0, cfg.vocab_size, (16, 1))
        ids = (start + np.arange(16)[None]) % cfg.vocab_size
        batch = {"input_ids": jnp.asarray(ids, jnp.int32)}
        batch = jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(mesh, sh.batch_spec(x.ndim))
            ),
            batch,
        )
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        assert float(metrics["grads_finite"]) == 1.0
    assert losses[-1] < losses[0] * 0.8, losses


@pytest.mark.slow
def test_pipelined_transformer_pp_tp_matches_dense(devices):
    """PP×TP: pipe=2 × model=2 × data=2 — manual megatron TP inside the
    pipeline island (column/row slices + psum, Block.tp_shards) must
    reproduce the dense flax forward exactly, and the gradients must
    match the dense model's gradients transposed into the pipe layout."""
    cfg = _tiny_cfg()
    mesh = build_mesh(MeshSpec(pipe=2, model=2, data=2), devices[:8])
    model = tfm.Transformer(cfg)
    params, _ = tfm.make_init_fn(model, 16)(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)), jnp.int32)
    want = model.apply({"params": params}, ids, None, train=False)
    pparams = tfm.to_pipeline_params(params, cfg, n_stages=2)
    got = jax.jit(
        lambda p, i: tfm.pipelined_apply(p, i, None, cfg, mesh,
                                         n_microbatches=4)
    )(pparams, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)

    # gradient parity: d mean(logits^2) — dense grads transposed to the
    # pipe layout == grads through the PP×TP schedule
    def dense_loss(p):
        lg = model.apply({"params": p}, ids, None, train=False)
        return (lg ** 2).mean()

    def piped_loss(pp):
        lg = tfm.pipelined_apply(pp, ids, None, cfg, mesh,
                                 n_microbatches=4)
        return (lg ** 2).mean()

    g_dense = jax.jit(jax.grad(dense_loss))(params)
    want_g = tfm.to_pipeline_params(g_dense, cfg, n_stages=2)
    got_g = jax.jit(jax.grad(piped_loss))(pparams)
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = jax.tree_util.tree_leaves_with_path(got_g)
    for (path, w), (_, g) in zip(flat_w, flat_g):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=5e-4,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.slow
def test_pipelined_transformer_pp_tp_trains(devices):
    """Train-engine integration on pipe=2 × model=2 × data=2: the stacked
    leaves shard over BOTH pipe and model (pipeline_param_specs(tp=True))
    and the loss decreases."""
    cfg = _tiny_cfg()
    mesh = build_mesh(MeshSpec(pipe=2, model=2, data=2), devices[:8])
    tx = optax.adam(3e-3)
    init_fn = tfm.make_pipelined_init_fn(cfg, n_stages=2, seq_len=16)
    specs = tfm.pipeline_param_specs(
        jax.eval_shape(init_fn, jax.random.PRNGKey(0))[0], tp=True
    )
    # kernels must actually carry the model axis (vacuity guard)
    qk = specs["blocks"]["attn"]["query"]["kernel"]
    ok_ = specs["blocks"]["attn"]["attn_out"]["kernel"]
    assert qk[-1] == "model" and ok_[-2] == "model", (qk, ok_)
    state, sspecs = init_train_state(
        init_fn, tx, mesh, jax.random.PRNGKey(0), param_specs=specs,
    )
    step = jit_train_step(
        make_train_step(
            tfm.pipelined_lm_loss_fn(cfg, mesh, n_microbatches=4), tx,
            StepOptions(check_grads_finite=True)), mesh, sspecs,
    )
    rng = np.random.RandomState(1)
    ids = rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    batch = {"input_ids": jax.device_put(
        jnp.asarray(ids), NamedSharding(mesh, sh.batch_spec(2)))}
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        assert float(metrics["grads_finite"]) == 1.0
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_block_tp_guards():
    """Manual-TP misuse fails loudly: indivisible heads/d_ff, MoE, and
    fused-LN are all rejected."""
    x = jnp.zeros((2, 8, 32), jnp.float32)
    with pytest.raises(ValueError, match="not divisible"):
        tfm.Block(_tiny_cfg(num_heads=3), tp_shards=2).init(
            jax.random.PRNGKey(0), x, None, False)
    with pytest.raises(ValueError, match="d_ff"):
        tfm.Block(_tiny_cfg(d_ff=66), tp_shards=4).init(
            jax.random.PRNGKey(0), x, None, False)
    with pytest.raises(ValueError, match="MoE"):
        tfm.Block(_tiny_cfg(num_experts=2), None, True, tp_shards=2).init(
            jax.random.PRNGKey(0), x, None, False)
    with pytest.raises(ValueError, match="fused_ln_matmul"):
        tfm.Block(_tiny_cfg(fused_ln_matmul=True), tp_shards=2).init(
            jax.random.PRNGKey(0), x, None, False)


def test_pipelined_transformer_pp_tp_interleaved_matches_dense(devices):
    """PP×TP × interleaved: the [S, V, lc, ...] stacking must place the
    `model` axis on the same trailing kernel dims (a wrong-but-square
    placement on the d_model×d_model qkv kernels would still be
    shape-compatible — only numerical parity catches it)."""
    cfg = _tiny_cfg()  # 4 layers
    mesh = build_mesh(MeshSpec(pipe=2, model=2, data=2), devices[:8])
    model = tfm.Transformer(cfg)
    params, _ = tfm.make_init_fn(model, 16)(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)), jnp.int32)
    want = model.apply({"params": params}, ids, None, train=False)
    pparams = tfm.to_pipeline_params(params, cfg, n_stages=2, n_virtual=2)
    got = jax.jit(
        lambda p, i: tfm.pipelined_apply(p, i, None, cfg, mesh,
                                         n_microbatches=4, n_virtual=2)
    )(pparams, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_pipeline_apply_rejects_param_specs_on_degenerate_mesh(devices):
    """pipe=1 runs outside shard_map: TP param_specs must be rejected,
    not silently dropped (a TP stage_fn's psum would hit unbound axes)."""
    mesh = build_mesh(MeshSpec(data=2), devices[:2])
    params = _toy_params(jax.random.PRNGKey(0), 1, 8)
    x_mb = jnp.ones((2, 4, 8))
    with pytest.raises(ValueError, match="degenerate"):
        pipeline_apply(_toy_stage_fn, params, x_mb, mesh,
                       param_specs=jax.tree.map(
                           lambda _: P("pipe"), params,
                       ))


@pytest.mark.slow
def test_pipelined_dropout_schedule_independent(devices):
    """Dropout through the pipeline: the per-
    (microbatch, global-layer, batch-shard) key derivation must be
    independent of the S>1 (S, V) schedule decomposition — pipe=2/V=1,
    pipe=2/V=2 and pipe=4/V=1 draw the SAME masks at a fixed batch
    sharding — and must actually drop (differs from train=False)."""
    cfg = _tiny_cfg(dropout=0.5)
    model = tfm.Transformer(cfg)
    params, _ = tfm.make_init_fn(model, 16)(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)), jnp.int32)
    key = jax.random.PRNGKey(7)

    outs = []
    for spec, n_devs, n_stages, n_virtual in (
        (MeshSpec(pipe=2, data=2), 4, 2, 1),
        (MeshSpec(pipe=2, data=2), 4, 2, 2),
        (MeshSpec(pipe=4, data=2), 8, 4, 1),
    ):
        mesh = build_mesh(spec, devices[:n_devs])
        pp = tfm.to_pipeline_params(params, cfg, n_stages=n_stages,
                                    n_virtual=n_virtual)
        outs.append(jax.jit(
            lambda p, i, k, mesh=mesh, nv=n_virtual: tfm.pipelined_apply(
                p, i, None, cfg, mesh, n_microbatches=4, n_virtual=nv,
                train=True, rng=k,
            )
        )(pp, ids, key))

    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[2]),
                               atol=2e-4)

    mesh = build_mesh(MeshSpec(pipe=2, data=2), devices[:4])
    pp = tfm.to_pipeline_params(params, cfg, n_stages=2)
    eval_out = jax.jit(
        lambda p, i: tfm.pipelined_apply(p, i, None, cfg, mesh,
                                         n_microbatches=4)
    )(pp, ids)
    assert not np.allclose(np.asarray(outs[0]), np.asarray(eval_out)), (
        "dropout had no effect")
    # a different key draws different masks (keys really reach the blocks)
    other = jax.jit(
        lambda p, i, k: tfm.pipelined_apply(
            p, i, None, cfg, mesh, n_microbatches=4, train=True, rng=k)
    )(pp, ids, jax.random.PRNGKey(8))
    assert not np.allclose(np.asarray(outs[0]), np.asarray(other))
    # pipe=1 degenerate: a different (global-shape) stream, but dropout
    # is active, deterministic, and decorrelated across layers/keys
    mesh1 = build_mesh(MeshSpec(data=2), devices[:2])
    pp1 = tfm.to_pipeline_params(params, cfg, n_stages=1)
    f1 = jax.jit(lambda p, i, k: tfm.pipelined_apply(
        p, i, None, cfg, mesh1, n_microbatches=4, train=True, rng=k))
    a, b = f1(pp1, ids, key), f1(pp1, ids, key)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.allclose(np.asarray(a), np.asarray(
        f1(pp1, ids, jax.random.PRNGKey(8))))


@pytest.mark.slow
def test_pipelined_dropout_trains_and_grads_flow(devices):
    """Grad through the stochastic schedule: masks replay identically in
    the backward (jax.checkpoint) and the train engine runs."""
    import optax

    from distributed_tensorflow_tpu.train import (
        StepOptions, init_train_state, jit_train_step, make_train_step,
    )

    cfg = _tiny_cfg(dropout=0.1)
    mesh = build_mesh(MeshSpec(pipe=2, data=2), devices[:4])
    init_fn = tfm.make_pipelined_init_fn(cfg, n_stages=2, seq_len=16)
    state, specs = init_train_state(
        init_fn, optax.adam(3e-3), mesh, jax.random.PRNGKey(0),
        param_specs=tfm.pipeline_param_specs(
            jax.eval_shape(init_fn, jax.random.PRNGKey(0))[0]
        ),
    )
    step = jit_train_step(
        make_train_step(tfm.pipelined_lm_loss_fn(cfg, mesh, 4),
                        optax.adam(3e-3),
                        StepOptions(check_grads_finite=True)),
        mesh, specs,
    )
    rng = np.random.RandomState(0)
    losses = []
    for i in range(20):
        start = rng.randint(0, cfg.vocab_size, (16, 1))
        ids = (start + np.arange(16)[None]) % cfg.vocab_size
        batch = {"input_ids": jax.device_put(
            jnp.asarray(ids, jnp.int32),
            NamedSharding(mesh, sh.batch_spec(2)))}
        state, metrics = step(state, batch)
        assert float(metrics["grads_finite"]) == 1.0
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses


@pytest.mark.slow
def test_pipelined_composes_with_grad_accum(devices):
    """PP × ConditionalAccumulator-descendant: grad_accum_steps=2 through
    the pipelined loss must equal the accum=1 step on the same batch
    (causal LM: every chunk has identical valid-token counts, so
    mean-of-means == full-batch mean exactly; dropout off)."""
    import optax

    from distributed_tensorflow_tpu.train import (
        StepOptions, init_train_state, jit_train_step, make_train_step,
    )

    cfg = _tiny_cfg()  # causal, dropout=0.0
    mesh = build_mesh(MeshSpec(pipe=2, data=2), devices[:4])
    init_fn = tfm.make_pipelined_init_fn(cfg, n_stages=2, seq_len=16)
    specs = tfm.pipeline_param_specs(
        jax.eval_shape(init_fn, jax.random.PRNGKey(0))[0])
    tx = optax.sgd(0.1)
    loss_fn = tfm.pipelined_lm_loss_fn(cfg, mesh, n_microbatches=4)

    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (16, 16))
    batch = {"input_ids": jax.device_put(
        jnp.asarray(ids, jnp.int32),
        NamedSharding(mesh, sh.batch_spec(2)))}

    results = []
    for accum in (1, 2):
        state, sspecs = init_train_state(
            init_fn, tx, mesh, jax.random.PRNGKey(0), param_specs=specs)
        step = jit_train_step(
            make_train_step(loss_fn, tx,
                            StepOptions(grad_accum_steps=accum)),
            mesh, sspecs,
        )
        state, metrics = step(state, batch)
        results.append((state.params, float(metrics["loss"])))

    (p1, l1), (p2, l2) = results
    assert abs(l1 - l2) < 1e-5, (l1, l2)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5),
        p1, p2,
    )


@pytest.mark.slow
def test_pipelined_dropout_consistent_under_tp(devices):
    """PP×TP × dropout: model-axis devices must draw IDENTICAL masks
    (the shard fold uses only the data/fsdp index), so the TP=2 forward
    equals the TP=1 forward exactly — a wrong per-device key would break
    the row-parallel psum math, which only numerical parity catches."""
    cfg = _tiny_cfg(dropout=0.5)
    model = tfm.Transformer(cfg)
    params, _ = tfm.make_init_fn(model, 16)(jax.random.PRNGKey(0))
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 16)),
        jnp.int32)
    key = jax.random.PRNGKey(3)
    pp = tfm.to_pipeline_params(params, cfg, n_stages=2)

    outs = []
    for spec, nd in ((MeshSpec(pipe=2, data=2), 4),
                     (MeshSpec(pipe=2, model=2, data=2), 8)):
        mesh = build_mesh(spec, devices[:nd])
        outs.append(np.asarray(jax.jit(
            lambda p, i, k, mesh=mesh: tfm.pipelined_apply(
                p, i, None, cfg, mesh, n_microbatches=4,
                train=True, rng=k)
        )(pp, ids, key)))
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-4)
