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


def test_flash_env_block_fallback(monkeypatch):
    # DTF_FLASH_BLOCK_Q/K are process-global trace-time knobs;
    # a sweep value that doesn't divide some OTHER call site's seq len
    # must fall back to the 128 default with a warning, not raise.
    # 384 % 256 != 0 (and 256 < 384, so min() doesn't clamp it away),
    # while the 128 fallback divides
    q, k, v = make_qkv(jax.random.PRNGKey(7), B=1, H=2, S=384)
    ref = attention_reference(q, k, v)
    monkeypatch.setenv("DTF_FLASH_BLOCK_Q", "256")
    monkeypatch.setenv("DTF_FLASH_BLOCK_K", "256")
    with pytest.warns(UserWarning, match="falling back to 128"):
        out = flash_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # an EXPLICIT non-dividing block argument still errors loudly
    with pytest.raises(ValueError, match="multiples of block sizes"):
        flash_attention(q, k, v, block_q=256, block_k=256)


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
