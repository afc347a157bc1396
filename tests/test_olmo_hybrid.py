"""The hybrid decoder (models/olmo_hybrid.py), its kernels
(ops/gated_delta.py, the paged kernels with a layer axis) and its serving
path (HybridCache, state snapshots) at a toy size: two periods of three
linear-attention layers and one full-attention layer, small widths, every
mechanism on. The oracle is the benchmark's plain reference
(benchmark/reference/olmo_hybrid.py), which imports nothing of the
program."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import olmo_hybrid as ref  # noqa: E402
from distributed_tensorflow_tpu import serve  # noqa: E402
from distributed_tensorflow_tpu.models import olmo_hybrid as oh  # noqa: E402
from distributed_tensorflow_tpu.ops import gated_delta as gd  # noqa: E402
from distributed_tensorflow_tpu.ops.attention import paged_append_kv  # noqa: E402
from distributed_tensorflow_tpu.ops.flash_attention import (  # noqa: E402
    paged_flash_attention,
    paged_write_kv,
)
from distributed_tensorflow_tpu.parallel import sharding  # noqa: E402
from distributed_tensorflow_tpu.serve import kv_cache  # noqa: E402

L, F = "linear_attention", "full_attention"
CFG = {
    "model_type": "olmo_hybrid", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 160, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4, "hidden_act": "silu",
    "max_position_embeddings": 512, "attention_bias": False,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "layer_types": [L, L, L, F] * 2, "linear_num_key_heads": 4,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "initializer_range": 0.05,
}
MODEL_CFG = oh.OlmoHybridConfig(
    vocab_size=512, d_model=64, d_ff=160, num_heads=4,
    layer_types=tuple(CFG["layer_types"]), linear_heads=4, linear_key_dim=8,
    linear_value_dim=16, max_len=512)

#: logits of the toy model (largest entry about 2) against the reference,
#: on weights that are float32 on both sides, so that what is compared is
#: the program's arithmetic and not the rounding of its matmul operands:
#: the sound program reads under 1e-4; the same with the recurrent state
#: rounded to bfloat16 at every write reads 0.1 and more
TOL = 2e-3
#: on the model's own bfloat16 weights the matmul operands are rounded too
TOL_BF16 = 0.5


@pytest.fixture(scope="module")
def weights():
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        ref.make_weights(CFG, 11))


def engine(weights, **kw):
    args = dict(num_slots=3, block_size=8, num_blocks=96, prefill_chunk=16,
                max_len=256, num_state_snapshots=4, cache_dtype=jnp.float32)
    args.update(kw)
    return serve.ServeEngine(MODEL_CFG, weights, **args)


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


def served_logit_error(eng, weights, toks, n_new):
    """Serve ``toks`` for ``n_new`` tokens, recording the logits of every
    sampled position; the largest gap to the reference's logits over the
    served sequence."""
    rows = []
    real = serve.engine.sampling.sample

    def spy(logits, *a, **kw):
        rows.append(np.asarray(logits))
        return real(logits, *a, **kw)

    serve.engine.sampling.sample = spy
    try:
        uid = eng.submit(toks, max_new_tokens=n_new)
        gen = eng.run()[uid].generated
    finally:
        serve.engine.sampling.sample = real
    got = np.stack([rows[0]] + [r[0] for r in rows[1:]])[:n_new]
    want = np.asarray(ref.logits(weights, np.array(toks + gen), CFG))
    want = want[len(toks) - 1: len(toks) - 1 + n_new]
    return np.abs(got - want).max(), gen


# ---------------------------------------------------------------------------
# the kernels against the token recurrence
# ---------------------------------------------------------------------------


def rule_inputs(T, H, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    g = -1.5 * jax.random.uniform(ks[3], (T, H))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (H, dk, dv))


@pytest.mark.parametrize("impl", ["plain", "pallas"])
@pytest.mark.parametrize("length,fresh,sub_chunk", [
    (32, False, 8), (27, False, 8), (27, True, 16), (5, False, 32)])
def test_gated_delta_chunk_against_the_recurrence(impl, length, fresh,
                                                  sub_chunk):
    """A carried-in state, a padded last chunk, beta above 1, repeated keys;
    only the slot's row of the layer's state moves."""
    q, k, v, g, beta, M = rule_inputs(32, 4, 16, 24)
    k = k.at[5].set(k[4]).at[6].set(k[4])      # keys that repeat
    assert float(beta.max()) > 1.5
    state = jnp.ones((3, 2, 4, 16, 24)).at[1, 1].set(M)
    o, new = gd.gated_delta_chunk(
        q, k, v, g, beta, state, layer=1, slot=1, length=length, fresh=fresh,
        impl=impl, sub_chunk=sub_chunk)
    n = length
    want_o, want_M = gd.recurrence(q[:n], k[:n], v[:n], g[:n], beta[:n],
                                   jnp.zeros_like(M) if fresh else M)
    np.testing.assert_allclose(o[:n], want_o, atol=2e-5)
    np.testing.assert_allclose(new[1, 1], want_M, atol=2e-5)
    others = np.ones((3, 2), bool)
    others[1, 1] = False
    assert np.array_equal(np.asarray(new)[others], np.asarray(state)[others])


@pytest.mark.parametrize("impl", ["plain", "pallas"])
def test_gated_delta_step_keeps_the_state_of_slots_that_are_not_live(impl):
    q, k, v, g, beta, _ = rule_inputs(3, 4, 16, 24, seed=1)
    state = jax.random.normal(jax.random.PRNGKey(9), (2, 3, 4, 16, 24))
    live = jnp.array([True, False, True])
    o, new = gd.gated_delta_step(q, k, v, g, beta, state, layer=1, live=live,
                                 impl=impl)
    for s in range(3):
        want_o, want_M = gd.recurrence(q[s:s + 1], k[s:s + 1], v[s:s + 1],
                                       g[s:s + 1], beta[s:s + 1], state[1, s])
        if live[s]:
            np.testing.assert_allclose(o[s], want_o[0], atol=1e-5)
            np.testing.assert_allclose(new[1, s], want_M, atol=1e-5)
        else:
            assert np.array_equal(new[1, s], state[1, s])
    assert np.array_equal(new[0], state[0])


def test_the_rule_is_the_references_recurrence():
    """ops/gated_delta keeps the state transposed; the equations are the
    reference's."""
    q, k, v, g, beta, M = rule_inputs(20, 4, 16, 24, seed=2)
    o, new = gd.recurrence(q, k, v, g, beta, M)
    want_o, want_S = ref.gated_delta_recurrence(q, k, v, g, beta,
                                                M.transpose(0, 2, 1))
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(new, want_S.transpose(0, 2, 1), atol=1e-5)


def test_paged_kernels_with_a_layer_axis_match_the_plain_forms():
    """Heads of 128 beside a layer index: the in-place write kernel against
    the scatter of the XLA paths (the write-off block aside), the attention
    kernel reading row
    ``layer`` of a stacked pool against the same on that row alone."""
    nL, NB, H, bs, D = 2, 6, 3, 8, 128
    # the pool's last physical block is the write-off block: tables name NB
    pool = jax.random.normal(jax.random.PRNGKey(0), (nL, NB + 1, H, bs, D))
    table = jnp.array([[4, 2, 5, NB]])
    S, start, length = 16, 8, 11
    pos = jnp.where(jnp.arange(S) < length, start + jnp.arange(S),
                    4 * bs)[None]
    new = jax.random.normal(jax.random.PRNGKey(1), (1, H, S, D))
    wrote = paged_write_kv(pool, new, table, pos, layer=1)
    same = lambda a, b: np.array_equal(a[:, :NB], b[:, :NB])
    assert same(wrote, paged_append_kv(pool, new, table, pos, layer=1))
    assert np.array_equal(wrote[0], pool[0]) and not np.array_equal(
        wrote[1], pool[1])
    # one token a slot, the middle slot idle
    table = jnp.array([[4, 2, NB], [1, NB, NB], [0, 3, 5]])
    pos = jnp.array([[9], [3 * bs], [20]])
    new = jax.random.normal(jax.random.PRNGKey(2), (3, H, 1, D))
    wrote = paged_write_kv(pool, new, table, pos, layer=0)
    assert same(wrote, paged_append_kv(pool, new, table, pos, layer=0))
    assert np.array_equal(wrote[0, 1], pool[0, 1])
    # a live slot on the LAST block a table can name, idle slots around it:
    # their write-back of nothing must not land on its row
    table = jnp.array([[NB, NB, NB], [NB - 1, NB, NB], [NB, NB, NB]])
    pos = jnp.array([[3 * bs], [5], [3 * bs]])
    wrote = paged_write_kv(pool, new, table, pos, layer=1)
    np.testing.assert_array_equal(wrote[1, NB - 1, :, 5], new[1, :, 0])
    assert same(wrote, paged_append_kv(pool, new, table, pos, layer=1))
    table = jnp.array([[4, 2, NB], [1, NB, NB], [0, 3, 5]])
    pos = jnp.array([[9], [3 * bs], [20]])
    wrote = paged_write_kv(pool, new, table, pos, layer=0)
    q = jax.random.normal(jax.random.PRNGKey(3), (3, H, 1, D))
    got = paged_flash_attention(q, wrote, pool, table, q_pos=pos, layer=0)
    want = paged_flash_attention(q, wrote[0], pool[0], table, q_pos=pos)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


def test_full_forward_matches_the_reference(weights):
    toks = np.array(prompt(70))
    got = jax.jit(oh.OlmoHybrid(MODEL_CFG).forward)(weights, jnp.asarray(toks))
    want = ref.logits(weights, toks, CFG)
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) < TOL


def test_bfloat16_weights_run_with_bfloat16_operands():
    w = ref.make_weights(CFG, 11)
    assert w["head"].dtype == jnp.bfloat16
    toks = np.array(prompt(40))
    got = jax.jit(oh.OlmoHybrid(MODEL_CFG).forward)(w, jnp.asarray(toks))
    err = float(jnp.abs(got - ref.logits(w, toks, CFG)).max())
    assert TOL < err < TOL_BF16


def test_uneven_chunks_then_decode_match_the_reference(weights):
    """53 tokens in chunks of 16, 16, 16 and 5, then 60 decoded tokens
    through the hybrid cache, every sampled position's logits."""
    err, gen = served_logit_error(engine(weights), weights, prompt(53), 60)
    assert len(gen) == 60 and err < TOL


def test_a_bfloat16_only_state_fails_the_tolerance(weights):
    """The tolerance is one that a recurrent state kept in bfloat16 does not
    meet: the same run with the state rounded at every write."""
    eng = engine(weights)
    eng.cache.state = eng.cache.state.astype(jnp.bfloat16)
    err, _ = served_logit_error(eng, weights, prompt(53), 60)
    assert err > TOL


def test_pallas_kernels_serve_the_same_tokens(weights):
    """Every kernel interpreted (gated delta chunk and step, the paged
    write and attention with a layer axis) against the plain forms."""
    import dataclasses

    toks = prompt(37, seed=3)
    plain = engine(weights)
    uid = plain.submit(toks, max_new_tokens=6)
    want = plain.run()[uid].generated
    cfg = dataclasses.replace(MODEL_CFG, gated_delta_impl="pallas",
                              paged_attention_impl="pallas")
    eng = serve.ServeEngine(cfg, weights, num_slots=2, block_size=8,
                            num_blocks=32, prefill_chunk=16, max_len=128,
                            cache_dtype=jnp.float32)
    uid = eng.submit(toks, max_new_tokens=6)
    assert eng.run()[uid].generated == want


# ---------------------------------------------------------------------------
# the engine: state beside the paged cache
# ---------------------------------------------------------------------------


def test_served_alone_is_served_in_a_full_batch(weights):
    """A slot in the middle of its prefill and an idle slot keep their state
    across the other slots' decode steps: every request's tokens are those
    it gets when served alone."""
    prompts = [prompt(n, seed=n) for n in (70, 9, 45, 23, 60)]
    alone = []
    for p in prompts:
        eng = engine(weights)
        uid = eng.submit(p, max_new_tokens=12)
        alone.append(eng.run()[uid].generated)
    eng = engine(weights)            # 3 slots: two wait, slots are reused
    uids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    done = eng.run()
    assert [done[u].generated for u in uids] == alone


def test_prefix_reuse_with_snapshots_gives_the_logits_of_no_reuse(weights):
    doc, tails = prompt(32, seed=5), [prompt(n, seed=n) for n in (9, 14, 11)]
    want = []
    for t in tails:
        err, gen = served_logit_error(engine(weights, prefix_reuse=False),
                                      weights, doc + t, 8)
        assert err < TOL
        want.append(gen)
    eng = engine(weights)
    hits = lambda: eng.registry.get("prefix_reuse_hits_total").value
    # first request: nothing cached; second: the blocks match, no snapshot
    # yet, the match is given up and the shared part snapshotted at its
    # last chunk end (the document's end); third: starts from there
    for i, t in enumerate(tails):
        before = hits()
        err, gen = served_logit_error(eng, weights, doc + t, 8)
        assert gen == want[i] and err < TOL
        assert hits() - before == (4 if i == 2 else 0)
    assert eng.snapshots.taken == 1 and eng.snapshots.hits == 1
    admits = [s for s in eng.tracer.events if s.name == "serve.step.admit"
              and "matched_tokens" in s.attrs][-3:]
    assert [(s.attrs["matched_tokens"], s.attrs["trimmed_tokens"])
            for s in admits] == [(0, 0), (32, 32), (32, 0)]
    reg = eng.registry
    assert reg.get("state_snapshots_taken_total").value == 1
    assert reg.get("state_snapshot_hits_total").value == 1
    assert reg.get("state_snapshots_live").value == 1


def test_an_evicted_snapshot_trims_the_match(weights):
    doc = prompt(48, seed=6)
    eng = engine(weights, num_state_snapshots=1)
    for seed in (1, 2):
        uid = eng.submit(doc + prompt(5, seed=seed), max_new_tokens=2)
        eng.run()
    # one row, taken at the shared part's last chunk end (48) and at no
    # chunk end before it
    assert len(eng.snapshots) == 1 and eng.snapshots.evictions == 0
    assert tuple(doc[:48]) in eng.snapshots
    # a prompt that shares 40 tokens matches 5 blocks and has no snapshot
    # at or under 40: all of the match is given up, and the run is right
    other = doc[:40] + prompt(20, seed=9)
    err, _ = served_logit_error(eng, weights, other, 4)
    assert err < TOL
    admit = [s for s in eng.tracer.events if s.name == "serve.step.admit"
             and "matched_tokens" in s.attrs][-1]
    assert (admit.attrs["matched_tokens"], admit.attrs["trimmed_tokens"]) == (
        40, 40)
    # that request snapshotted its own shared part's last chunk end (32),
    # which took the one row; it serves a prompt that shares all 48
    before = eng.snapshots.hits
    uid = eng.submit(doc + prompt(7, seed=3), max_new_tokens=2)
    eng.run()
    assert eng.snapshots.hits == before + 1


def test_flush_prefix_cache_leaves_no_snapshot(weights):
    doc = prompt(32, seed=7)
    eng = engine(weights)
    for seed in (1, 2):
        eng.submit(doc + prompt(6, seed=seed), max_new_tokens=2)
        eng.run()
    assert len(eng.snapshots) == 1
    eng.alloc.flush_prefix_cache()       # the allocator's own call
    assert len(eng.snapshots) == 0 and eng.alloc.blocks_in_use == 0
    uid = eng.submit(doc + prompt(6, seed=3), max_new_tokens=2)
    eng.run()
    assert eng.snapshots.hits == 0


def test_a_snapshot_dies_with_its_block(weights):
    doc = prompt(32, seed=8)
    eng = engine(weights, num_blocks=32)   # max_len 256: one request's worth
    for seed in (1, 2):
        eng.submit(doc + prompt(6, seed=seed), max_new_tokens=2)
        eng.run()
    assert len(eng.snapshots) == 1 and tuple(doc[:32]) in eng.snapshots
    # a request of 31 blocks: the three least recently used cached blocks
    # go, the fourth stays, and so does its snapshot
    eng.submit(prompt(240, seed=4), max_new_tokens=4)
    eng.run()
    assert eng.alloc.evictions == 3
    assert not eng.alloc.is_cached(tuple(doc[:16]))
    assert eng.alloc.is_cached(tuple(doc[:32]))
    assert tuple(doc[:32]) in eng.snapshots and len(eng.snapshots) == 1
    # one of all 32 blocks: the fourth goes too, and its snapshot with it
    eng.submit(prompt(248, seed=5), max_new_tokens=4)
    eng.run()
    assert not eng.alloc.is_cached(tuple(doc[:32]))
    assert len(eng.snapshots) == 0


def test_preemption_and_reprefill_serve_the_same_tokens(weights):
    """A pool too small for the residents: the youngest is preempted,
    released and re-prefilled from what it knows, through the same match."""
    prompts = [prompt(n, seed=n) for n in (60, 50, 40)]
    alone = []
    for p in prompts:
        eng = engine(weights)
        uid = eng.submit(p, max_new_tokens=60)
        alone.append(eng.run()[uid].generated)
    eng = engine(weights, num_blocks=32, max_len=256)
    uids = [eng.submit(p, max_new_tokens=60) for p in prompts]
    done = eng.run()
    assert sum(done[u].preemptions for u in uids) > 0
    assert [done[u].generated for u in uids] == alone
    assert eng.alloc.blocks_in_use == eng.alloc.evictable()


def test_speculation_and_the_dense_cache_are_refused(weights):
    with pytest.raises(ValueError, match="rolled back"):
        engine(weights, spec_k=2)
    with pytest.raises(ValueError, match="paged"):
        engine(weights, paged=False)


def test_partition_rules_cover_the_parameters_and_the_cache(weights):
    specs = sharding.match_partition_rules(oh.OLMO_HYBRID_RULES, weights)
    assert jax.tree.structure(specs) == jax.tree.structure(weights)
    paths = sorted("/".join(k.key for k in path) for path, _ in
                   jax.tree.flatten_with_path(oh.param_shapes(MODEL_CFG))[0])
    assert tuple(paths) == oh._COVERAGE
    cache = kv_cache.init_hybrid_cache(MODEL_CFG, 2, 8, 8, 2)
    assert cache.k.shape == (2, 8 + 1, 4, 8, 16) and cache.num_blocks == 8
    assert cache.state.shape == (6, 2, 4, 8, 16)
    assert cache.conv.shape == (6, 2, 3, 4 * (8 + 8 + 16))
    specs = sharding.match_partition_rules(kv_cache.HYBRID_CACHE_RULES, cache)
    assert specs.state == specs.snap_state


def test_period_and_stacks_of_the_config():
    assert MODEL_CFG.period == (L, L, L, F) and MODEL_CFG.num_periods == 2
    lone = oh.OlmoHybridConfig(
        vocab_size=8, d_model=8, d_ff=8, num_heads=2, layer_types=(L, F, F),
        linear_heads=2, linear_key_dim=4, linear_value_dim=4)
    assert lone.period == (L, F, F) and lone.num_periods == 1
    with pytest.raises(ValueError):
        oh.OlmoHybridConfig(
            vocab_size=8, d_model=8, d_ff=8, num_heads=2,
            layer_types=("sliding",), linear_heads=2, linear_key_dim=4,
            linear_value_dim=4)
    eng = serve.ServeEngine.with_random_params(
        lone, num_slots=2, block_size=4, num_blocks=16, prefill_chunk=8,
        max_len=32)
    uid = eng.submit([1, 2, 3, 4, 5], max_new_tokens=3)
    assert len(eng.run()[uid].generated) == 3
