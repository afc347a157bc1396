"""Fused 1x1-conv + BN kernel tests (interpret mode on CPU): forward and
gradient parity against the pure-jnp oracle, for every prologue/stats
combination the ResNet integration uses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.ops.fused_conv_bn import (
    bn_scale_shift,
    conv1x1_bn_act,
    conv1x1_bn_act_reference,
    moments_from_sums,
)


def _mk(M=64, cin=32, cout=48, dtype=jnp.float32, seed=0):
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(M, cin), dtype)
    w = jnp.asarray(r.randn(cin, cout) * 0.1, dtype)
    gamma = jnp.asarray(r.rand(cin) + 0.5, jnp.float32)
    beta = jnp.asarray(r.randn(cin) * 0.1, jnp.float32)
    mean = jnp.asarray(r.randn(cin) * 0.2, jnp.float32)
    var = jnp.asarray(r.rand(cin) + 0.3, jnp.float32)
    scale, shift = bn_scale_shift(mean, var, gamma, beta, 1e-5)
    return x, w, scale, shift


@pytest.mark.parametrize("prologue", [False, True])
@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("emit_stats", [False, True])
def test_forward_matches_reference(prologue, relu, emit_stats):
    x, w, scale, shift = _mk()
    kw = dict(relu=relu, emit_stats=emit_stats)
    args = (x, w, scale, shift) if prologue else (x, w)
    got = conv1x1_bn_act(*args, **kw)
    want = conv1x1_bn_act_reference(*args, **kw)
    if emit_stats:
        for g, wnt in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(wnt), rtol=1e-5, atol=1e-4
            )
    else:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4
        )


@pytest.mark.parametrize("prologue", [False, True])
@pytest.mark.parametrize("bwd_impl,shape", [
    ("xla", (48, 24, 40)),
    ("pallas", (48, 24, 40)),    # tiny: two-pass fallback (bm < 64)
    ("pallas", (256, 32, 48)),   # larger: the single-pass kernel
], ids=["xla", "pallas-two-pass", "pallas-single-pass"])
def test_gradients_match_reference(prologue, bwd_impl, shape):
    """Full-pathway gradient check: the loss consumes y AND the emitted
    stats (through moments, like the next BN does), so the stats-output
    cotangent path into dy is exercised. The two pallas shapes route to
    the two-pass pair vs the single-pass kernel respectively — asserted
    against the picker so the ids stay honest."""
    from distributed_tensorflow_tpu.ops import _tiling

    M, cin, cout = shape
    single = _tiling.pick_single_pass_bm(
        M, cin, cout, in_bytes=4, emit_stats=True) is not None
    assert single == (shape == (256, 32, 48))

    x, w, scale, shift = _mk(M=M, cin=cin, cout=cout)

    def loss(fn):
        def go(x, w, scale, shift):
            args = (x, w, scale, shift) if prologue else (x, w)
            kw = {"bwd_impl": bwd_impl} if fn is conv1x1_bn_act else {}
            y, s, ssq = fn(*args, relu=True, emit_stats=True, **kw)
            mean, var = moments_from_sums(s, ssq, y.shape[0])
            return (
                (y * y).mean()
                + (mean * mean).sum()
                + jnp.sqrt(var + 1e-3).sum()
            )

        return go

    got = jax.grad(loss(conv1x1_bn_act), argnums=(0, 1, 2, 3))(
        x, w, scale, shift
    )
    want = jax.grad(loss(conv1x1_bn_act_reference), argnums=(0, 1, 2, 3))(
        x, w, scale, shift
    )
    names = ["dx", "dw", "dscale", "dshift"]
    n_checked = 4 if prologue else 2
    for name, g, wnt in list(zip(names, got, want))[:n_checked]:
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(wnt), rtol=2e-4, atol=2e-4,
            err_msg=name,
        )


def test_bf16_io_f32_accumulation():
    x, w, scale, shift = _mk(M=128, cin=64, cout=64, dtype=jnp.bfloat16)
    y, s, ssq = conv1x1_bn_act(x, w, scale, shift)
    assert y.dtype == jnp.bfloat16
    assert s.dtype == jnp.float32 and ssq.dtype == jnp.float32
    yr, sr, ssqr = conv1x1_bn_act_reference(x, w, scale, shift)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32),
        rtol=2e-2, atol=2e-2,
    )
    # stats are computed on the quantized output -> exact match vs oracle
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ssq), np.asarray(ssqr), rtol=1e-5)


def test_moments_and_affine_helpers_match_batchnorm():
    r = np.random.RandomState(0)
    y = jnp.asarray(r.randn(256, 16), jnp.float32)
    s, ssq = y.sum(0), (y * y).sum(0)
    mean, var = moments_from_sums(s, ssq, y.shape[0])
    np.testing.assert_allclose(np.asarray(mean), np.asarray(y.mean(0)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(y.var(0)),
                               rtol=1e-4, atol=1e-5)
    gamma = jnp.asarray(r.rand(16) + 0.5, jnp.float32)
    beta = jnp.asarray(r.randn(16), jnp.float32)
    scale, shift = bn_scale_shift(mean, var, gamma, beta, 1e-5)
    want = (y - mean) * gamma * jax.lax.rsqrt(var + 1e-5) + beta
    np.testing.assert_allclose(np.asarray(y * scale + shift),
                               np.asarray(want), rtol=1e-4, atol=1e-4)




def test_sharded_batch_partitions_without_gather(devices):
    """The fused op under GSPMD with a batch-sharded input must partition
    along M (zero all-gathers in the compiled HLO) and keep the output
    batch-sharded — the multi-chip data-parallel contract. (Interpret
    mode proves the CPU/virtual-mesh path; single-chip hardware cannot
    exercise the Mosaic partitioner — docs/kernels.md notes the gap.)"""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices[:8]).reshape(8), ("data",))
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(64, 32), jnp.float32)
    w = jnp.asarray(r.randn(32, 48) * 0.1, jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    ws = jax.device_put(w, NamedSharding(mesh, P(None, None)))

    def f(x, w):
        return conv1x1_bn_act(x, w, emit_stats=True)

    hlo = jax.jit(f).lower(xs, ws).compile().as_text()
    assert hlo.count("all-gather") == 0
    y, s, q = jax.jit(f)(xs, ws)
    yr, sr, qr = conv1x1_bn_act_reference(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-5, atol=1e-4)
    assert "data" in str(y.sharding)


def test_pallas_bwd_known_slow_guard(monkeypatch):
    """DTF_FUSED_BWD=pallas must refuse shapes whose
    Mosaic compile is known-pathological — warn, fall back to the XLA
    backward (same math), and still produce correct gradients.
    DTF_FUSED_BWD_FORCE=1 bypasses the guard (measurement runs)."""
    from distributed_tensorflow_tpu.ops import _tiling

    M, cin, cout = 48, 24, 40
    monkeypatch.setattr(
        _tiling, "PALLAS_BWD_KNOWN_SLOW", {(M, cin, cout)})
    monkeypatch.delenv("DTF_FUSED_BWD_FORCE", raising=False)
    # fresh custom_vjp closures: the op cache is keyed on bwd_impl only,
    # and the guard runs inside bwd at trace time, so no cache clear is
    # needed — but guard against a stale jit cache anyway
    jax.clear_caches()
    x, w, scale, shift = _mk(M=M, cin=cin, cout=cout)

    def loss(x, w, scale, shift):
        y, s, ssq = conv1x1_bn_act(
            x, w, scale, shift, relu=True, emit_stats=True,
            bwd_impl="pallas")
        mean, var = moments_from_sums(s, ssq, y.shape[0])
        return (y * y).mean() + (mean * mean).sum() + var.sum()

    def ref_loss(x, w, scale, shift):
        y, s, ssq = conv1x1_bn_act_reference(
            x, w, scale, shift, relu=True, emit_stats=True)
        mean, var = moments_from_sums(s, ssq, y.shape[0])
        return (y * y).mean() + (mean * mean).sum() + var.sum()

    with pytest.warns(UserWarning, match="known to stall"):
        got = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, scale, shift)
    want = jax.grad(ref_loss, argnums=(0, 1, 2, 3))(x, w, scale, shift)
    for g, wnt in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(wnt), rtol=2e-4, atol=2e-4)

    # FORCE bypass: no warning, pallas path taken (still correct)
    monkeypatch.setenv("DTF_FUSED_BWD_FORCE", "1")
    jax.clear_caches()
    import warnings as _warnings

    with _warnings.catch_warnings():
        _warnings.simplefilter("error", UserWarning)
        got2 = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, scale, shift)
    for g, wnt in zip(got2, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(wnt), rtol=2e-4, atol=2e-4)
