"""The in-place K/V write kernel (`ops.flash_attention.paged_write_kv`,
interpreter on the CPU) against the scatter of the XLA paths
(`ops.attention.paged_append_kv`) on the same layer of the same pool, for
every shape of write the two engines send: consecutive positions from any
start, the real ones first and the padding last."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.ops.attention import paged_append_kv
from distributed_tensorflow_tpu.ops.flash_attention import paged_write_kv

LAYERS, NB, H, BS, D = 3, 12, 3, 16, 64   # tables name blocks 0..NB-1
OOB = 4 * BS                              # past a table of four blocks


def chunk(start, length, S):
    """The positions of a prefill chunk of ``S`` rows, ``length`` real."""
    idx = np.arange(S)
    return np.where(idx < length, start + idx, OOB)[None]


def verify_rows():
    """K + 1 = 5 rows for each of 16 slots: written from anywhere inside a
    block, fewer drafts than K near the table's end, idle slots at the
    sentinel, each live slot on blocks of its own."""
    table = np.full((16, 4), NB)
    pos = np.full((16, 5), OOB)
    for slot, (blocks, w, rows) in {
            0: ([0, 1], 14, 5), 3: ([2, 3, 4, 5], 3 * BS + 11, 5),
            7: ([6], 0, 5), 9: ([7, 8], BS - 1, 2), 15: ([9, 10, 11], 40, 1),
    }.items():
        table[slot, :len(blocks)] = blocks
        pos[slot, :rows] = np.arange(w, w + rows)
    return table, pos


CASES = {
    # a 32-token chunk behind a partial-tail prefix match: three blocks
    "chunk_from_mid_block": ([[4, 2, 5, 7]], chunk(9, 32, 32), 2),
    "chunk_padding_last": ([[4, 2, 5, NB]], chunk(21, 13, 32), 0),
    "chunk_ends_at_the_tables_end": ([[4, 2, 5, 7]], chunk(40, 24, 32), 1),
    "verify_rows_of_16_slots": (*verify_rows(), 1),
    "one_token_a_slot": ([[4, 2, NB, NB], [NB] * 4, [0, 3, 5, NB]],
                         [[17], [OOB], [47]], 2),
    "a_table_all_sentinel": ([[NB] * 4], chunk(5, 20, 32), 0),
    "a_slot_all_padding": ([[4, 2, 5, 7]], chunk(0, 0, 32), 1),
    "aligned_whole_blocks": ([[4, 2, 5, NB]], chunk(16, 27, 32), 1),
}


@pytest.mark.parametrize("case", CASES)
def test_write_kernel_matches_the_scatter(case):
    table, pos, layer = CASES[case]
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    pool = jax.random.normal(jax.random.PRNGKey(0),
                             (LAYERS, NB + 1, H, BS, D), jnp.bfloat16)
    new = jax.random.normal(jax.random.PRNGKey(1),
                            (*pos.shape[:1], H, pos.shape[1], D))
    wrote = np.asarray(paged_write_kv(pool, new, table, pos, layer=layer))
    want = np.asarray(paged_append_kv(pool, new, table, pos, layer=layer))
    # every block a table can name, in every layer; the write-off block
    # (the pool's last) holds whatever went nowhere
    np.testing.assert_array_equal(wrote[:, :NB], want[:, :NB])
    real = np.asarray(pos) < OOB
    live = real & (np.take_along_axis(
        np.asarray(table), np.minimum(np.asarray(pos) // BS, 3), 1) < NB)
    assert np.array_equal(wrote[:, :NB], np.asarray(pool)[:, :NB]) == (
        not live.any())


def test_positions_that_do_not_run_on_are_refused():
    pool = jnp.zeros((1, NB + 1, H, BS, D))
    table = jnp.asarray([[4, 2, 5, 7]])
    new = jnp.zeros((1, H, 4, D))
    for pos in ([[3, 5, 6, 7]], [[3, OOB, 4, 5]], [[OOB, 0, 1, 2]]):
        with pytest.raises(ValueError, match="consecutive"):
            paged_write_kv(pool, new, table, jnp.asarray(pos), layer=0)


@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_heads_stored_wider_than_the_models_change_no_output(impl):
    """`paged_layer_attention` over a pool whose heads are stored at 128
    (what `kv_cache.stored_head_dim` gives GPT-2's heads of 64 on the TPU)
    against the same over a pool as wide as the model: the same output,
    the same K and V in the first 64 columns, zeros written beside them."""
    from distributed_tensorflow_tpu.ops.attention import paged_layer_attention

    Dm, layer = 64, 1
    table = jnp.asarray([[4, 2, NB, NB], [NB] * 4, [0, 3, 5, NB]])
    pos = jnp.asarray([[17], [OOB], [47]])
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    narrow = [jax.random.normal(k, (LAYERS, NB + 1, H, BS, Dm))
              for k in keys[:2]]
    # what lies in the padding of positions not written now is whatever was
    # written there before: zeros
    wide = [jnp.pad(p, ((0, 0),) * 4 + ((0, 128 - Dm),)) for p in narrow]
    q, k, v = (jax.random.normal(kk, (3, H, 1, Dm)) for kk in keys[2:])
    want, wk, wv = paged_layer_attention(q, k, v, *narrow, table, pos,
                                         layer=layer, impl=impl)
    got, gk, gv = paged_layer_attention(q, k, v, *wide, table, pos,
                                        layer=layer, impl=impl)
    assert got.shape == want.shape
    live = np.array([0, 2])  # the idle slot's row: garbage or zeros by impl
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-5)
    for g, w in ((gk, wk), (gv, wv)):
        g = np.asarray(g)
        np.testing.assert_array_equal(g[:, :NB, ..., :Dm],
                                      np.asarray(w)[:, :NB])
        assert not g[:, :NB, ..., Dm:].any()


def test_the_pool_stores_heads_at_the_lane_width_on_the_tpu(monkeypatch):
    from distributed_tensorflow_tpu.models.transformer import TransformerConfig
    from distributed_tensorflow_tpu.serve import kv_cache

    cfg = TransformerConfig(num_layers=2, d_model=128, num_heads=2,
                            causal=True)
    shape = lambda: jax.eval_shape(
        lambda: kv_cache.init_paged_cache(cfg, 6, 16)).k.shape
    assert shape() == (2, 6 + 1, 2, 16, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert shape() == (2, 6 + 1, 2, 16, 128)
    assert [kv_cache.stored_head_dim(d) for d in (64, 96, 128, 160)] == [
        128, 128, 128, 256]
