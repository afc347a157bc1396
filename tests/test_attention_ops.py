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


_PAGED_BS = 8  # block size of the paged cases below


def _paged_positions(kind, C, oob):
    """q_pos [B, S] of one call of the kind the engine makes, over slots
    whose contexts end inside the first block, on a block boundary, one
    token past it, on exactly ``C`` blocks and on ``C`` + 1, beside an idle
    slot (every row at the past-the-table sentinel ``oob``)."""
    bs = _PAGED_BS
    if kind == "decode":  # S = 1: the position each slot writes now
        return [[3], [bs - 1], [bs], [C * bs - 1], [C * bs], [oob]]
    if kind == "verify":  # S = spec_k + 1 = 5, unused draft rows at oob
        last = [bs - 1, bs, C * bs - 1, C * bs + 4]
        rows = [list(range(p - 4, p + 1)) for p in last]
        rows.append([0, 1, 2, oob, oob])
        rows.append([oob] * 5)
        return rows
    # a prefill chunk of 8 rows: whole, padded past its length, idle
    whole = [list(range(lo, lo + 8)) for lo in (0, bs, C * bs - 8, C * bs)]
    padded = [list(range(13, 18)) + [oob] * 3, [0] + [oob] * 7]
    return whole + padded + [[oob] * 8]


def _paged_case(kind, layer, C, *, H=2, D=16, dtype=jnp.float32, seed=7):
    """(q, clean pools, poisoned pools, table, q_pos, pool row). The table
    is wider than any slot needs and names, past each slot's own blocks, a
    block id far outside the pool; every block that no slot owns (and
    every other layer's row) is NaN in the poisoned pools, random in the
    clean ones that the XLA oracles read."""
    bs, MB = _PAGED_BS, C + 3
    pos = np.asarray(_paged_positions(kind, C, MB * bs), np.int32)
    B, S = pos.shape
    valid = (pos >= 0) & (pos < MB * bs)
    need = -(-np.where(valid, pos + 1, 0).max(axis=1) // bs)
    NB = int(need.sum()) + 5
    rng = np.random.default_rng(seed)
    ids = rng.permutation(NB)
    table = np.full((B, MB), 2**30, np.int32)
    at = 0
    for b in range(B):
        table[b, :need[b]] = ids[at:at + need[b]]
        at += need[b]
    owned = np.zeros(NB, bool)
    owned[ids[:at]] = True
    layers = 1 if layer is None else 3
    row = 0 if layer is None else layer
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, H, S, D), dtype)
    clean = [jax.random.normal(key, (layers, NB, H, bs, D), dtype)
             for key in (kk, kv)]
    live = np.zeros((layers, NB), bool)
    live[row] = owned
    poisoned = [jnp.where(live[:, :, None, None, None], pool, jnp.nan)
                for pool in clean]
    if layer is None:
        clean, poisoned = ([pool[0] for pool in pools]
                           for pools in (clean, poisoned))
    return (q, clean, poisoned, jnp.asarray(table), jnp.asarray(pos),
            row, valid)


def _check_paged_case(kind, layer, C, *, atol, **case):
    """The Pallas kernel on the poisoned pools against the gather oracle on
    the clean ones: equal on every valid row, zeros on every other (an
    idle slot's, a chunk's padding); the fused XLA path answers as the
    oracle on every row (PR 20)."""
    from distributed_tensorflow_tpu.ops import _tiling
    from distributed_tensorflow_tpu.ops.attention import paged_attention
    from distributed_tensorflow_tpu.ops.flash_attention import (
        paged_flash_attention,
    )

    q, clean, poisoned, table, q_pos, row, valid = _paged_case(
        kind, layer, C, **case)
    f32 = lambda t: t.astype(jnp.float32)
    oracle_pools = [f32(pool if layer is None else pool[row])
                    for pool in clean]
    want = paged_attention(f32(q), *oracle_pools, table, q_pos=q_pos,
                           impl="gather")
    fused = paged_attention(f32(q), *oracle_pools, table, q_pos=q_pos,
                            impl="fused")
    np.testing.assert_allclose(fused, want, atol=2e-5, rtol=2e-5)
    if layer is None:
        got = paged_attention(q, *poisoned, table, q_pos=q_pos,
                              impl="pallas")
    else:
        got = paged_flash_attention(q, *poisoned, table, q_pos=q_pos,
                                    layer=jnp.int32(layer))
    assert got.dtype == q.dtype
    rows = np.broadcast_to(valid[:, None, :, None], got.shape)
    np.testing.assert_allclose(
        f32(got), np.where(rows, want, 0), atol=atol, rtol=atol)
    assert not valid[-1].any()  # the idle slot
    plan = _tiling.paged_attn_plan(
        q.shape[2], q.shape[1], table.shape[1], _PAGED_BS, q.shape[3],
        q.dtype.itemsize)
    assert plan.chunk_blocks == C
    return plan


@pytest.fixture()
def paged_chunk(monkeypatch):
    """Sets how many blocks the paged kernel takes a loop iteration (the
    shapes served give 2 to 16; the toy blocks here would all fit one)."""
    from distributed_tensorflow_tpu.ops import _tiling

    def set_chunk(C):
        monkeypatch.setattr(_tiling, "PAGED_CHUNK_POSITIONS", C * _PAGED_BS)
        _tiling.paged_attn_plan.cache_clear()
    yield set_chunk
    _tiling.paged_attn_plan.cache_clear()


@pytest.mark.parametrize("layer", [None, 0, 2],
                         ids=["flat", "layer0", "last_layer"])
@pytest.mark.parametrize("kind,C", [
    ("decode", 1), ("decode", 2), ("decode", 4),
    ("verify", 2), ("verify", 4), ("prefill", 1), ("prefill", 2),
])
def test_paged_attention_impls_match_gather_oracle(paged_chunk, kind, C,
                                                   layer):
    """Every paged attention impl answers as the PR-13
    gather+cached_attention oracle at the shapes the serve engine feeds the
    dispatch (decode, speculative verify, a prefill chunk with padded
    rows), on both pool layouts, over contexts of one chunk, exactly ``C``
    blocks and ``C`` + 1, with the trip count and not the table's width
    bounding the walk. float32 throughout: 2e-5."""
    paged_chunk(C)
    _check_paged_case(kind, layer, C, atol=2e-5)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_paged_attention_bf16_operands_close_to_f32_oracle(paged_chunk, kind):
    """bf16 pools and q: K, V, q and p enter the matmuls as bf16. Against
    the float32 oracle on the same bf16 values what is left is p's rounding
    (2^-9 relative, on values of order 1) and the bf16 output's: 2e-2."""
    paged_chunk(2)
    _check_paged_case(kind, 1, 2, atol=2e-2, dtype=jnp.bfloat16)


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_paged_attention_head_groups(monkeypatch, paged_chunk, kind):
    """25 heads under a budget that all 25 do not fit: the plan's next
    choice is 5 heads a grid step, five steps a slot."""
    from distributed_tensorflow_tpu.ops import _tiling

    paged_chunk(2)
    S = len(_paged_positions(kind, 2, 0)[0])
    fits = _tiling.paged_attn_vmem_bytes(S, 5, 2, _PAGED_BS, 16, 4)
    monkeypatch.setattr(_tiling, "FULL_VMEM_BUDGET", fits)
    plan = _check_paged_case(kind, None, 2, atol=2e-5, H=25)
    assert plan.hb == 5


def test_paged_attention_rejects_unknown_impl():
    from distributed_tensorflow_tpu.ops.attention import paged_attention

    q, clean, _, table, q_pos, _, _ = _paged_case("decode", None, 1)
    with pytest.raises(ValueError, match="impl"):
        paged_attention(q, *clean, table, q_pos=q_pos, impl="nope")
