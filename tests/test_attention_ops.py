"""Numerics oracle tests: blockwise and Pallas flash attention vs the O(S²)
reference (SURVEY.md §4 "numerical parity oracles"), forward and grad."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.ops import (
    attention_reference,
    blockwise_attention,
    flash_attention,
)


def make_qkv(key, B=2, H=3, S=256, D=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, S, D), dtype)
    k = jax.random.normal(kk, (B, H, S, D), dtype)
    v = jax.random.normal(kv, (B, H, S, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_blockwise_matches_reference(causal, masked):
    q, k, v = make_qkv(jax.random.PRNGKey(0))
    kv_mask = None
    if masked:
        # mask out a ragged tail per batch row (BERT-style padding)
        lens = np.array([200, 137])
        kv_mask = jnp.asarray(np.arange(256)[None, :] < lens[:, None])
    ref = attention_reference(q, k, v, causal=causal, kv_mask=kv_mask)
    out = blockwise_attention(
        q, k, v, causal=causal, kv_mask=kv_mask, block_k=64
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_blockwise_ragged_block_padding():
    # Sk not a multiple of block_k: internal padding path
    q, k, v = make_qkv(jax.random.PRNGKey(1), S=100)
    ref = attention_reference(q, k, v)
    out = blockwise_attention(q, k, v, block_k=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_blockwise_grads_match_reference():
    q, k, v = make_qkv(jax.random.PRNGKey(2), B=1, H=2, S=128)

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    def loss_blk(q, k, v):
        return blockwise_attention(q, k, v, causal=True, block_k=32).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_flash_forward_matches_reference(causal, masked):
    q, k, v = make_qkv(jax.random.PRNGKey(3), B=2, H=2, S=256)
    kv_mask = None
    if masked:
        lens = np.array([256, 130])
        kv_mask = jnp.asarray(np.arange(256)[None, :] < lens[:, None])
    ref = attention_reference(q, k, v, causal=causal, kv_mask=kv_mask)
    out = flash_attention(
        q, k, v, causal=causal, kv_mask=kv_mask, block_q=128, block_k=128
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    q, k, v = make_qkv(jax.random.PRNGKey(4), B=1, H=2, S=128)
    lens = np.array([128])
    kv_mask = jnp.asarray(np.arange(128)[None, :] < lens[:, None])

    def loss_ref(q, k, v):
        out = attention_reference(q, k, v, causal=causal, kv_mask=kv_mask)
        return (out * out).sum()  # non-trivial cotangent

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, causal=causal, kv_mask=kv_mask, block_q=64, block_k=64
        )
        return (out * out).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_flash_bf16_close_to_f32_reference():
    q, k, v = make_qkv(jax.random.PRNGKey(5), S=128, dtype=jnp.bfloat16)
    ref = attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out.astype(np.float32), ref, atol=3e-2, rtol=3e-2
    )


def test_flash_rejects_ragged_seq():
    q, k, v = make_qkv(jax.random.PRNGKey(6), S=100)
    with pytest.raises(ValueError, match="multiples"):
        flash_attention(q, k, v, block_q=64, block_k=64)


def test_flash_fully_masked_rows_are_zero():
    q, k, v = make_qkv(jax.random.PRNGKey(7), B=1, H=1, S=128)
    kv_mask = jnp.zeros((1, 128), bool)  # nothing to attend to
    out = flash_attention(q, k, v, kv_mask=kv_mask, block_q=64, block_k=64)
    np.testing.assert_allclose(out, np.zeros_like(out), atol=1e-6)


def test_blockwise_fully_masked_rows_are_zero():
    # must match flash_attention semantics (blockwise is its CPU fallback)
    q, k, v = make_qkv(jax.random.PRNGKey(8), B=1, H=1, S=128)
    kv_mask = jnp.zeros((1, 128), bool)
    out = blockwise_attention(q, k, v, kv_mask=kv_mask, block_k=32)
    np.testing.assert_allclose(out, np.zeros_like(out), atol=1e-6)


def _out_and_grads(attend, q, k, v, w, **kw):
    """[out, dq, dk, dv] in float32 under the cotangent ``w``."""
    def loss(q, k, v):
        out = attend(q, k, v, **kw)
        return (out.astype(jnp.float32) * w).sum(), out
    (_, out), grads = jax.value_and_grad(
        loss, (0, 1, 2), has_aux=True)(q, k, v)
    return [np.asarray(x.astype(jnp.float32)) for x in (out, *grads)]


def _masked(mask, B, Sk):
    if mask == "padded":  # a ragged tail per batch row (BERT-style padding)
        lens = np.array([Sk, Sk // 2 + 3])
    elif mask == "row_fully_masked":  # nothing to attend to in batch row 1
        lens = np.array([Sk, 0])
    else:
        return None
    return jnp.asarray(np.arange(Sk)[None, :] < lens[:B, None])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("blocks", ["picked", "forced_chunks"])
@pytest.mark.parametrize(
    "Sq,Sk", [(128, 128), (384, 384), (512, 512), (256, 512)])
@pytest.mark.parametrize("mask", ["none", "padded", "row_fully_masked"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_parity_forward_and_grads(causal, mask, Sq, Sk, blocks, dtype):
    """Output and dq, dk, dv against the O(S^2) reference, over the picker's
    own tiles and over blocks that force several chunks either side of the
    diagonal. bf16 inputs go to the matmuls as bf16 and are held to the
    float32 reference at test_flash_bf16_close_to_f32_reference's tolerance."""
    B, H, D = 2, 2, 64
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(Sq + Sk), 4)
    q = jax.random.normal(kq, (B, H, Sq, D), dtype)
    k = jax.random.normal(kk, (B, H, Sk, D), dtype)
    v = jax.random.normal(kv, (B, H, Sk, D), dtype)
    w = jax.random.normal(kw, (B, H, Sq, D), jnp.float32)  # the cotangent
    kv_mask = _masked(mask, B, Sk)
    forced = {} if blocks == "picked" else dict(
        block_q=min(128, Sq // 2), block_k=min(128, Sk // 2))
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

    want = _out_and_grads(attention_reference, f32(q), f32(k), f32(v), w,
                          causal=causal, kv_mask=kv_mask)
    got = _out_and_grads(flash_attention, q, k, v, w,
                         causal=causal, kv_mask=kv_mask, **forced)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    rows = slice(0, 1) if mask == "row_fully_masked" else slice(None)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            a[rows], b[rows], atol=tol, rtol=tol, err_msg=name)
        if mask == "row_fully_masked":
            # the contract, not the reference's (a softmax over nothing is
            # uniform there): zero output, so nothing flows back either
            np.testing.assert_array_equal(a[1], 0.0, err_msg=name)


@pytest.mark.parametrize("block_q,block_k", [
    (128, 128), (128, 256), (256, 128), (64, 128), (None, None)])
@pytest.mark.parametrize("Sq,Sk", [(512, 512), (256, 512)])
def test_flash_causal_loop_ends_on_the_diagonal(Sq, Sk, block_q, block_k):
    """With q = 0 every kept key weighs exp(0) = 1, so row r is exactly
    sum(v[:r+1]) / (r+1) with integer sums: a loop one chunk short loses a
    whole chunk of keys for the rows of a block (row block_q*i would miss its
    own key), one chunk long without the triangle lets row block_q*i - 1 see
    past itself; either moves the count, and the comparison is exact."""
    D = 64
    q = jnp.zeros((1, 1, Sq, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(0), (1, 1, Sk, D), jnp.float32)
    v = jnp.broadcast_to(
        jnp.arange(1, Sk + 1, dtype=jnp.float32)[:, None], (1, 1, Sk, D))
    out = flash_attention(
        q, k, v, causal=True, block_q=block_q, block_k=block_k)
    kept = np.arange(Sq) + (Sk - Sq) + 1  # keys row r may attend
    want = (np.cumsum(np.arange(1, Sk + 1, dtype=np.float32))[kept - 1]
            / kept.astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(out)[0, 0], np.broadcast_to(want[:, None], (Sq, D)))


@pytest.mark.parametrize("mask", ["none", "padded"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("Sq,Sk", [(512, 512), (256, 512)])
def test_flash_spans_that_do_not_fit_vmem(monkeypatch, Sq, Sk, causal, mask):
    """Ring attention's long per-device blocks: where a head's whole K/V
    (forward, dQ) or q/dO (dKV) do not fit the budget, the largest span
    that does becomes a grid axis and the same loops run inside it. A
    budget of two chunks stands in for a long sequence."""
    from distributed_tensorflow_tpu.ops import _tiling

    B, H, D = 2, 2, 64
    monkeypatch.setattr(_tiling, "FULL_VMEM_BUDGET", _tiling.flash_vmem_bytes(
        128, 128, 1, 256, 256, D, 4))
    _tiling.flash_tile_plan.cache_clear()
    try:
        plan = _tiling.flash_tile_plan(B, H, Sq, Sk, D, 4, causal)
        assert not plan.resident and plan.kv_span == 256
        kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(11), 4)
        q = jax.random.normal(kq, (B, H, Sq, D))
        k = jax.random.normal(kk, (B, H, Sk, D))
        v = jax.random.normal(kv, (B, H, Sk, D))
        w = jax.random.normal(kw, (B, H, Sq, D))
        kv_mask = _masked(mask, B, Sk)

        got, want = (
            _out_and_grads(attend, q, k, v, w, causal=causal, kv_mask=kv_mask)
            for attend in (flash_attention, attention_reference))
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4,
                                       err_msg=name)
    finally:
        _tiling.flash_tile_plan.cache_clear()


def test_paged_attention_impls_match_gather_oracle():
    """Every paged attention impl answers identically (PR 20): the fused
    block-layout einsum and the Pallas kernel (interpreter off-TPU) must
    match the PR-13 gather+cached_attention oracle on random pools with
    ragged positions, sentinel table entries, an idle all-sentinel row,
    and verify-shaped (S>1) queries — the shapes the serve engine feeds
    the dispatch in decode, chunked prefill, and speculative verify."""
    from distributed_tensorflow_tpu.ops.attention import paged_attention

    key = jax.random.PRNGKey(7)
    B, H, D, bs, NB, MB = 3, 2, 16, 8, 10, 4
    kq, kk, kv = jax.random.split(key, 3)
    k_pool = jax.random.normal(kk, (NB, H, bs, D))
    v_pool = jax.random.normal(kv, (NB, H, bs, D))
    table = np.full((B, MB), NB, np.int32)
    table[0, :3] = [4, 9, 1]      # 3 live blocks, non-contiguous
    table[1, :1] = [0]            # 1 live block
    # row 2 stays all-sentinel: an idle slot (its output is garbage the
    # engine discards, but every impl must compute the SAME garbage)
    table = jnp.asarray(table)
    oob = MB * bs
    for S, q_pos in (
        (1, jnp.asarray([[17], [0], [oob]], jnp.int32)),
        (5, jnp.asarray([[17, 18, 19, 20, 21], [0, 1, 2, 3, 4],
                         [oob] * 5], jnp.int32)),
    ):
        q = jax.random.normal(kq, (B, H, S, D))
        want = paged_attention(
            q, k_pool, v_pool, table, q_pos=q_pos, impl="gather")
        for impl in ("fused", "pallas"):
            got = paged_attention(
                q, k_pool, v_pool, table, q_pos=q_pos, impl=impl)
            np.testing.assert_allclose(
                got, want, atol=2e-5, rtol=2e-5,
                err_msg=f"impl={impl} S={S}")
    with pytest.raises(ValueError, match="impl"):
        paged_attention(q, k_pool, v_pool, table, q_pos=q_pos, impl="nope")
