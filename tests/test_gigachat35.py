"""GigaChat 3.5's decoder (models/gigachat3_5.py), its kernels
(ops/latent_attention.py, the grouped matmul of ops/moe.py) and its serving
path (LatentCache, state snapshots) at a toy size: one dense layer and one
period (latent attention + experts, three gated-delta layers + experts),
d 256, 4 latent heads of ranks 64 / 32 and rotary 8, 2 q/k heads serving 4
value heads of 16, 16 routed experts of which 4 are held, top 4, a
vocabulary slice of 64. The oracle is the benchmark's plain reference
(benchmark/reference/gigachat3_5.py), which imports nothing of the
program."""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families.gigachat3_5 import adapter  # noqa: E402
from benchmark.reference import gigachat3_5 as ref  # noqa: E402
from distributed_tensorflow_tpu import serve  # noqa: E402
from distributed_tensorflow_tpu.models import gigachat3_5 as gc  # noqa: E402
from distributed_tensorflow_tpu.ops import latent_attention as la  # noqa: E402
from distributed_tensorflow_tpu.ops import moe  # noqa: E402
from distributed_tensorflow_tpu.serve import decode, kv_cache  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "gigachat3.5-432b-a28b.json")) as f:
    FILE = json.load(f)

CFG = dict(FILE)
CFG.update(
    hidden_size=256, intermediate_size=512, moe_intermediate_size=64,
    num_hidden_layers=5, full_attention_layers=[1], first_k_dense_replace=1,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=64,
    kv_lora_rank=32, qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
    qk_head_dim=24, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=16, n_routed_experts=4,
    num_experts_per_tok=4, vocab_size=64, max_position_embeddings=512,
    initializer_range=0.05,
    expert_share={"router_experts": 16, "first_held": 4})
MODEL_CFG = adapter.model_config(CFG)

#: logits of the toy model (largest entries about 2) against the reference,
#: on weights that are float32 on both sides, so that what is compared is
#: the program's arithmetic (absorbed latent attention, the chunked rule,
#: sorted dispatch) and not the rounding of its matmul operands: the sound
#: program reads some 1e-5; the latent pool rounded to bfloat16 reads over
#: 1e-2
TOL = 2e-3


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
    return np.random.default_rng(seed).integers(0, 64, n).tolist()


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
# serving against the reference's full forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [7, 16, 37])
def test_chunked_prefill_then_decode_matches_the_reference(weights, n):
    """A prompt shorter than a chunk, exactly one, and three chunks with a
    padded last one; then decode through the latent pool and the state."""
    err, gen = served_logit_error(engine(weights), weights, prompt(n, n), 9)
    assert len(gen) == 9 and err < TOL


def test_residents_decode_together_as_they_would_alone(weights):
    prompts = [prompt(n, seed=n) for n in (21, 9, 30, 14)]
    alone = []
    for p in prompts:
        eng = engine(weights, num_slots=1)
        uid = eng.submit(p, max_new_tokens=10)
        alone.append(eng.run()[uid].generated)
    eng = engine(weights)            # 3 slots: one waits, slots are reused
    uids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    done = eng.run()
    assert [done[u].generated for u in uids] == alone
    # every slot's tokens went through the expert layers and were counted
    reg = eng.registry
    assert reg.get("moe_local_assignments_total").value > 0
    assert (0 < reg.get("moe_expert_calls_total").value
            <= reg.get("moe_local_assignments_total").value)


def test_a_restored_snapshot_and_shared_latent_blocks_serve_the_same_logits(
        weights):
    """First request: nothing cached; second: the document's blocks match,
    no snapshot yet, so the match is given up and the state snapshotted at
    the document's end; third: starts from that snapshot and maps the
    document's latent blocks. Each is the reference's, and what a run
    without reuse serves."""
    doc, tails = prompt(32, seed=5), [prompt(n, seed=n) for n in (9, 14, 11)]
    want = []
    for t in tails:
        err, gen = served_logit_error(engine(weights, prefix_reuse=False),
                                      weights, doc + t, 6)
        assert err < TOL
        want.append(gen)
    eng = engine(weights)
    hits = lambda: eng.registry.get("prefix_reuse_hits_total").value
    for i, t in enumerate(tails):
        before = hits()
        err, gen = served_logit_error(eng, weights, doc + t, 6)
        assert gen == want[i] and err < TOL
        assert hits() - before == (4 if i == 2 else 0)
    assert eng.snapshots.taken == 1 and eng.snapshots.hits == 1


def test_the_cache_the_model_builds_and_its_block_copy():
    cache = gc.GigaChat35(MODEL_CFG).init_cache(3, 20, 8, 2)
    assert isinstance(cache, kv_cache.LatentCache)
    assert cache.kv.shape == (1, 21, 1, 8, 40)     # write-off block last
    assert cache.state.shape == (4, 3, 4, 16, 16)
    assert cache.conv.shape == (4, 3, 3, 2 * 2 * 16 + 4 * 16)
    assert cache.snap_state.shape[1] == 2 and cache.moe_counts.shape == (2, 4)
    assert cache.num_blocks == 20 and cache.block_size == 8
    cache = dataclasses.replace(cache, kv=cache.kv.at[0, 3].set(1.0))
    cache = decode.copy_block(cache, 3, 7)
    assert float(cache.kv[0, 7].min()) == 1.0 and float(cache.kv[0, 6].max()) == 0


# ---------------------------------------------------------------------------
# the layers against the reference's
# ---------------------------------------------------------------------------


def _sizes():
    return ref.sizes(CFG)


@pytest.mark.parametrize("impl", ["plain", "pallas"])
def test_absorbed_latent_attention_equals_the_expanded_layer(weights, impl):
    """The program's latent-attention layer (rows written into the pool,
    absorbed queries against them, ``W_uv`` after) over 24 tokens in two
    chunks equals the reference's expanded layer (keys and values of every
    head from the latent, causal softmax)."""
    sz = _sizes()
    p = {n: a[0] for n, a in weights["mla"].items()}
    cfg = gc.GigaChat35Config(**{**MODEL_CFG.__dict__,
                                 "latent_attention_impl": impl})
    model = gc.GigaChat35(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 256))
    inv = jnp.asarray(ref.yarn_inv_freq(CFG), jnp.float32)
    want = ref._mla_mixer(x, p, sz, inv, (24 ** -0.5) * ref.mscale(CFG) ** 2,
                          None)
    pool = jnp.zeros((1, 9, 1, 8, 40), jnp.float32)
    table = jnp.array([[2, 5, 0, 7]], jnp.int32)
    got = []
    for start, n in ((0, 16), (16, 8)):
        xs = jnp.zeros((1, 16, 256)).at[0, :n].set(x[start:start + n])
        pos = jnp.where(jnp.arange(16) < n, start + jnp.arange(16), 32)[None]
        xn = gc._norm(xs, p["norm1"], 1e-6)
        y, pool = gc._mla(xn, p, cfg, pool, 0, table, pos,
                          jnp.array([start]), jnp.array([n]), model.inv_freq)
        got.append((xs + gc._norm(y, p["norm2"], 1e-6))[0, :n])
    np.testing.assert_allclose(np.concatenate(got), want, atol=2e-4)


def test_decode_of_idle_and_live_slots_in_the_latent_kernel():
    """A decode call of three slots, one idle: the kernel equals the plain
    form on the live ones and returns zeros for the idle one, whose rows
    write nothing."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = jax.random.normal(ks[0], (2, 13, 1, 8, 40))
    q = jax.random.normal(ks[1], (3, 4, 40))
    table = jnp.array([[3, 1, 12, 12], [0, 4, 5, 6], [12] * 4], jnp.int32)
    q0, nq = jnp.array([9, 30, 0]), jnp.array([1, 1, 0])
    kw = dict(layer=1, heads=4, value_width=32, sm_scale=0.3)
    a = la.latent_attention(q, pool, table, q0, nq, impl="pallas", **kw)
    b = la.latent_attention(q, pool, table, q0, nq, impl="plain", **kw)
    np.testing.assert_allclose(a, b, atol=1e-5)
    assert float(jnp.abs(a[2]).max()) == 0.0


def test_the_expert_shares_add_up_to_the_uncut_layer(weights):
    """Four shares of four experts, each routing over all sixteen: the held
    experts' parts summed, with the shared expert counted once, are what
    the reference gives for the whole layer (guide: model-configs, section
    4)."""
    sz = _sizes()
    p = {n: a[0] for n, a in weights["moe"].items()}
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    whole = {n: 0.05 * jax.random.normal(k, (16, *p[n].shape[1:]))
             for n, k in zip(("w_gate", "w_up", "w_down"), ks)}
    hn = jax.random.normal(ks[3], (40, 256))
    uncut = ref._moe_ffn(hn, {**p, **whole}, {**sz, "E": 16, "first": 0}, None)
    parts = sum(moe.expert_share(
        hn, p["router"], p["bias"], *(whole[n][None, 4 * s:4 * s + 4]
                                      for n in ("w_gate", "w_up", "w_down")),
        layer=0, first=4 * s, top_k=4, scale=2.5, limit=10.0)[0]
        for s in range(4))
    shared = gc._swiglu(hn, p["s_gate"], p["s_up"], p["s_down"], 10.0)
    np.testing.assert_allclose(parts + shared, uncut, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["plain", "pallas"])
def test_a_router_skewed_onto_one_held_expert_drops_nothing(weights, impl):
    """Every token's first choice is held expert 5 (a large correction
    bias): all 64 assignments land there, none is dropped (the layer has no
    capacity), and the result is the dense sum over the chosen experts."""
    p = {n: a[0] for n, a in weights["moe"].items()}
    bias = p["bias"].at[5].set(100.0)
    hn = jax.random.normal(jax.random.PRNGKey(5), (64, 256))
    experts = tuple(weights["moe"][n] for n in ("w_gate", "w_up", "w_down"))
    y, counts = moe.expert_share(hn, p["router"], bias, *experts, layer=0,
                                 first=4, top_k=4, scale=2.5, limit=10.0,
                                 impl=impl)
    assert int(counts[1]) == 64 and int(counts.sum()) >= 64
    want = ref._moe_ffn(hn, {**p, "bias": bias}, _sizes(), None) - \
        gc._swiglu(hn, p["s_gate"], p["s_up"], p["s_down"], 10.0)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)


def test_padding_and_idle_tokens_are_routed_to_no_expert(weights):
    p = {n: a[0] for n, a in weights["moe"].items()}
    experts = tuple(weights["moe"][n] for n in ("w_gate", "w_up", "w_down"))
    hn = jax.random.normal(jax.random.PRNGKey(6), (20, 256))
    valid = jnp.arange(20) < 12
    y, counts = moe.expert_share(hn, p["router"], p["bias"], *experts,
                                 layer=0, first=4, top_k=4, scale=2.5,
                                 limit=10.0, valid=valid)
    y12, counts12 = moe.expert_share(hn[:12], p["router"], p["bias"],
                                     *experts, layer=0, first=4, top_k=4,
                                     scale=2.5, limit=10.0)
    assert (counts == counts12).all()
    np.testing.assert_allclose(y[:12], y12, atol=1e-6)
    assert float(jnp.abs(y[12:]).max()) == 0.0


@pytest.mark.parametrize("impl", ["plain", "pallas"])
def test_a_swiglu_input_past_the_limit_is_clamped(impl):
    """silu(min(g, 10)) * clip(u, -10, 10): a gate product of 20 and an up
    product of -30 give silu(10) * -10, in the grouped matmul as in the
    dense layers."""
    x = jnp.zeros((16, 128)).at[:, 0].set(1.0)
    wg = jnp.zeros((1, 1, 128, 128)).at[0, 0, 0].set(20.0)
    wu = jnp.zeros((1, 1, 128, 128)).at[0, 0, 0].set(-30.0)
    got = moe.moe_grouped_mm(x, (wg, wu), jnp.zeros((1,), jnp.int32), 1,
                             layer=0, tile_rows=16, limit=10.0, impl=impl)
    want = float(jax.nn.silu(10.0)) * -10.0
    np.testing.assert_allclose(got[:, 0], want, rtol=1e-6)
    dense = gc._swiglu(x, wg[0, 0], wu[0, 0], jnp.eye(128), 10.0)
    np.testing.assert_allclose(dense[:, 0], want, rtol=1e-6)
    assert float(jnp.abs(ref._swiglu(x, wg[0, 0], wu[0, 0], jnp.eye(128),
                                     10.0, None)[:, 0] - want).max()) < 1e-4


def test_yarn_frequencies_and_the_attention_scale_follow_the_formulas():
    """Both sides against the formulas written out here: pair i turns at
    1 / 1e5^(2i/64); the pairs that turn more than 32 times over 32768
    positions keep it, those that turn fewer than once take it over 8, a
    linear ramp by index between; m = 0.1 ln 8 + 1."""
    dim, theta, factor, orig = 64, 1e5, 8.0, 32768
    base = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    at = lambda rot: dim * math.log(orig / (rot * 2 * math.pi)) / (
        2 * math.log(theta))
    lo, hi = math.floor(at(32)), math.ceil(at(1))
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    want = base * (1 - ramp) + base / factor * ramp
    assert 0 < lo < hi < dim // 2
    np.testing.assert_allclose(ref.yarn_inv_freq(FILE), want, rtol=1e-12)
    np.testing.assert_allclose(gc.yarn_inv_freq(dim, theta, factor, orig,
                                                32, 1), want, rtol=1e-12)
    m = 0.1 * math.log(8) + 1
    assert ref.mscale(FILE) == pytest.approx(m)
    cfg = adapter.model_config(FILE)
    assert cfg.sm_scale == pytest.approx(192 ** -0.5 * m * m)


def test_grouped_heads_serve_two_value_heads_each():
    """q/k head j is repeated for value heads 2j and 2j + 1 before the
    kernels."""
    from distributed_tensorflow_tpu.models import olmo_hybrid as oh

    y = jnp.arange(2 * 16 * 2 + 4 * 16, dtype=jnp.float32)[None] + 1.0
    q, k, v = oh._split_qkv(y, MODEL_CFG)
    assert q.shape == k.shape == (1, 4, 16) and v.shape == (1, 4, 16)
    np.testing.assert_allclose(q[0, 0], q[0, 1])
    np.testing.assert_allclose(k[0, 2], k[0, 3])
    assert not np.allclose(q[0, 1], q[0, 2])


# ---------------------------------------------------------------------------
# the controls of the comparison that decides `correct`
# ---------------------------------------------------------------------------


def test_the_lower_precision_controls_read_over_the_cells_limit(weights):
    """The reference in int8 and in fp8 (every weight matmul's operands
    rounded to 8 bits), its tokens judged by the float32 reference at the
    same positions, reads a mean gap above the cell's limit; the program's
    own tokens read under it."""
    with open(os.path.join(ROOT, "benchmark", "limits",
                           "gigachat35_serve_longdocs_r80.json")) as f:
        limit = json.load(f)["limits"]["served_gap_mean"]
    w = ref.make_weights(CFG, 11)
    toks = prompt(40, seed=2)
    eng = serve.ServeEngine(MODEL_CFG, w, num_slots=2, block_size=8,
                            num_blocks=64, prefill_chunk=16, max_len=256,
                            num_state_snapshots=2)
    uid = eng.submit(toks, max_new_tokens=24)
    served = eng.run()[uid].generated
    gaps = {q: ref.served_gaps(CFG, w, toks, served, pad_to=128, n_out=32,
                               quant=q).mean() for q in (None, "int8", "fp8")}
    assert gaps[None] < limit < min(gaps["int8"], gaps["fp8"])
