"""The flash kernels, the paged kernels and the transformer's two serving
programs at GPT-2-XL's serving shapes, the hybrid decoder's kernels and
step programs, and GigaChat 3.5's (latent attention, grouped expert
matmuls) at its published widths, compiled for a described
(not attached) TPU v5e, at real sizes: Mosaic's layout rules and its scoped
VMEM limit (16 MB unless a kernel asks for more) are what the interpreter
cannot check and what the tile plans' own estimates have to stay under.
Nothing runs; a pass says the chip's compiler takes the kernels, not that
they are right or fast (tests/test_attention_ops.py, chip_smoke.py).

The topology is described inside a fixture and only in this file: one
process loads the TPU library, and keeps it."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_tensorflow_tpu.ops._tiling import flash_tile_plan
from distributed_tensorflow_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape,dtype,causal,padded", [
    ((8, 16, 1024, 64), jnp.bfloat16, True, False),   # gpt2m_train_1k
    ((8, 12, 384, 64), jnp.bfloat16, False, True),    # BERT padded to 384
    ((8, 12, 640, 64), jnp.bfloat16, False, True),
    ((2, 16, 2048, 128), jnp.bfloat16, True, False),
    ((1, 8, 8192, 128), jnp.bfloat16, True, False),   # spans do not fit
    ((1, 8, 4096, 128), jnp.float32, True, True),
], ids=lambda v: str(getattr(v, "__name__", v)).replace(" ", ""))
def test_flash_forward_and_backward_compile_for_v5e(
        one_chip, shape, dtype, causal, padded):
    B, H, S, D = shape
    qkv = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((B, S), jnp.bool_, sharding=one_chip)

    def step(q, k, v, kv_mask):
        def loss(q, k, v):
            out = flash_attention(
                q, k, v, causal=causal, interpret=False,
                kv_mask=kv_mask if padded else None)
            return out.astype(jnp.float32).sum()
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    text = jax.jit(step).lower(qkv, qkv, qkv, mask).compile().as_text()
    # the backward is one fused call where a head's queries stay in VMEM,
    # else the dKV + dQ split; the plan says which, the compiled text holds
    # that and not the other
    plan = flash_tile_plan(B, H, S, S, D, jnp.dtype(dtype).itemsize, causal)
    assert plan.fused_bwd == (S <= 2048)  # the two longest do not fit
    fused = ("flash_attention_bwd_fused",)
    split = ("flash_attention_bwd_dkv", "flash_attention_bwd_dq")
    want, absent = (fused, split) if plan.fused_bwd else (split, fused)
    for kernel in ("flash_attention_fwd", *want):
        assert kernel in text
    for kernel in absent:
        assert kernel not in text


# ---------------------------------------------------------------------------
# the hybrid decoder (models/olmo_hybrid.py) at Olmo-Hybrid-7B's widths
# ---------------------------------------------------------------------------


@pytest.fixture()
def kernels_for_the_chip(monkeypatch):
    """The kernels pick the interpreter off the TPU; here the backend is the
    CPU and the target the described chip."""
    import importlib

    for name in ("flash_attention", "gated_delta", "latent_attention", "moe"):
        monkeypatch.setattr(importlib.import_module(
            f"distributed_tensorflow_tpu.ops.{name}"), "_on_tpu",
            lambda: True)


def test_gated_delta_kernels_compile_for_v5e(one_chip, kernels_for_the_chip):
    """A chunk of 256 tokens and a step of 16 slots at 30 heads of 96 x 192,
    in place on the state of 12 layers and 16 slots."""
    from distributed_tensorflow_tpu.ops import gated_delta as gd

    T, B, H, dk, dv = 256, 16, 30, 96, 192
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    state, i32 = S((12, B, H, dk, dv)), S((), jnp.int32)

    def chunk(q, k, v, g, b, state, layer, slot, length):
        return gd.gated_delta_chunk(q, k, v, g, b, state, layer=layer,
                                    slot=slot, length=length,
                                    fresh=length < 0, impl="pallas")

    done = jax.jit(chunk, donate_argnums=(5,)).lower(
        S((T, H, dk)), S((T, H, dk)), S((T, H, dv)), S((T, H)), S((T, H)),
        state, i32, i32, i32).compile()
    assert "gated_delta_chunk_fwd" in done.as_text()
    # the state is updated in place: aliased, nothing of its size on the side
    mem = done.memory_analysis()
    assert mem.alias_size_in_bytes >= 12 * B * H * dk * dv * 4
    assert mem.temp_size_in_bytes < 64e6

    def step(q, k, v, g, b, state, layer, live):
        return gd.gated_delta_step(q, k, v, g, b, state, layer=layer,
                                   live=live, impl="pallas")

    done = jax.jit(step, donate_argnums=(5,)).lower(
        S((B, H, dk)), S((B, H, dk)), S((B, H, dv)), S((B, H)), S((B, H)),
        state, i32, S((B,), jnp.bool_)).compile()
    assert "gated_delta_step" in done.as_text()
    assert done.memory_analysis().temp_size_in_bytes < 64e6


@pytest.mark.parametrize("width", [1, 4, 16, 48])
@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_gpt2xl_paged_kernel_call_compiles_for_v5e(
        one_chip, kernels_for_the_chip, program, width):
    """A layer of `gpt2xl_serve_complete_r80`'s two programs as the model
    makes it (`ops.attention.paged_layer_attention`): 16 slots (one for a
    prefill chunk of 32), 25 heads of 64, the bf16 pool of 24 layers and
    640 + 1 blocks of 16, tables from one block to the widest of 48; K and
    V written in place, attention over the same row. Heads of 64 are what
    Mosaic is strict about (it takes no slice of such a pool in HBM, which
    is why each block is an operand of its own)."""
    from distributed_tensorflow_tpu.ops.attention import paged_layer_attention

    B, S = (1, 32) if program == "prefill" else (16, 1)
    S_ = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    # the pool as the engine keeps it on the chip: heads stored at the 128
    # lanes (`kv_cache.stored_head_dim`; left at 64, XLA puts the block
    # axis last and every call copies both pools there and back)
    pool = S_((24, 641, 25, 16, 128), jnp.bfloat16)
    new = S_((B, 25, S, 64), jnp.bfloat16)

    def call(q, k, v, k_pool, v_pool, table, pos, layer):
        return paged_layer_attention(q, k, v, k_pool, v_pool, table, pos,
                                     layer=layer, impl="pallas")

    done = jax.jit(call, donate_argnums=(3, 4)).lower(
        new, new, new, pool, pool, S_((B, width), jnp.int32),
        S_((B, S), jnp.int32), S_((), jnp.int32)).compile()
    text = done.as_text()
    assert "paged_attention_fwd" in text and "paged_kv_write" in text
    # both pools written where they lie and read from there: nothing of a
    # pool's size, nor of one layer's, on the side
    pool_bytes = 24 * 641 * 25 * 16 * 128 * 2
    mem = done.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < 4e6


def _gpt2_step_program(one_chip, program, width, d_model, num_heads):
    """One of the transformer's two serving programs at GPT-2-XL's serving
    shapes (vocabulary 50304, d_ff 4·d; 16 slots, the pool of 24 layers and
    640 + 1 blocks of 16 with heads of 64 stored at 128, chunk 32; four
    layers of the model keep the compile short), compiled on the tree the
    engine hands it: ``model.serving_params`` of the params, run under
    ``jax.eval_shape``. Returns (params, that tree, cache, compiled)."""
    from distributed_tensorflow_tpu.models import transformer as tr
    from distributed_tensorflow_tpu.serve import decode, kv_cache

    cfg = tr.TransformerConfig(
        vocab_size=50304, d_model=d_model, num_heads=num_heads, num_layers=4,
        d_ff=4 * d_model, max_len=1024, dropout=0.0, causal=True,
        pre_ln=True, dtype="bfloat16", paged_attention_impl="pallas")
    model = tr.Transformer(cfg)
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32)))["params"])
    served = jax.tree.map(on_chip,
                          jax.eval_shape(model.serving_params, params))
    slots = 16
    # the engine's cache as `init_paged_cache` makes it on the chip (here
    # the backend is the CPU): heads stored at the 128 lanes
    pool = jax.ShapeDtypeStruct((24, 641, num_heads, 16, 128), jnp.bfloat16,
                                sharding=one_chip)
    cache = kv_cache.PagedKVCache(k=pool, v=pool)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    if program == "prefill":
        done = decode.jit_paged_prefill_chunk(model).lower(
            served, cache, i32(width), i32(32), i32(), i32())
    else:
        done = decode.jit_paged_decode_step(model).lower(
            served, cache, i32(slots, width), i32(slots), i32(slots))
    return params, served, cache, done.compile()


@pytest.mark.parametrize("width", [4, 48])
@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_gpt2xl_step_programs_compile_and_keep_the_pool_in_place(
        one_chip, kernels_for_the_chip, program, width):
    """The transformer's two serving programs at GPT-2-XL's widths (d 1600,
    25 heads of 64, d_ff 6400) on a narrow and the widest table: both
    kernels are there, the donated pool is the program's result where it
    lies, and the temporaries (the weights cast to bf16, most of them) are
    far under one pool: a program that slices a layer out of the pool or
    stacks it again cannot hide there. The token and position rows come
    from the tables the preparation padded to 1664 lanes, so neither table
    is copied into row-major order in the program (at 1600 the device
    holds both with the long axis minor, and a gather copied the 322 MB
    embedding every run), and no layer's weights are copied for it."""
    _, served, cache, done = _gpt2_step_program(
        one_chip, program, width, 1600, 25)
    assert {k: v.shape for k, v in served["serving"].items()} == {
        "tok_rows": (50304, 1664), "pos_rows": (1024, 1664)}
    text = done.as_text()
    assert "paged_attention_fwd" in text and "paged_kv_write" in text
    pool_bytes = cache.k.size * cache.k.dtype.itemsize
    mem = done.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes    # the pool, in place
    assert mem.temp_size_in_bytes < 0.75 * pool_bytes
    # nothing makes a second pool, and no layer's [641, 25, 16, 64] exists
    assert not re.search(r"= bf16\[24,641,25,16,128\]\S* (copy|fusion)\(",
                         text)
    assert "bf16[641,25,16,128]" not in text
    # neither embedding table is copied whole, and the lookup leaves the
    # layers' layouts as they were: a gather of whole padded rows and then
    # a slice made every layer transpose its q/k/v weights (3 copies a
    # layer, against the first layer's 3)
    assert not re.search(r"= f32\[(50304|1024),1600\]\S* copy\(", text)
    assert len(re.findall(r"= bf16\[1600,1600\]\S* copy\(", text)) <= 3


def test_lane_multiple_tables_are_served_as_they_are(
        one_chip, kernels_for_the_chip):
    """At a width that is a multiple of the lanes (GPT-2-medium's 1024, 16
    heads of 64) the preparation adds nothing and pads nothing: the decode
    program takes the params themselves, and copies neither table."""
    params, served, _, done = _gpt2_step_program(
        one_chip, "decode", 48, 1024, 16)
    assert served == params
    assert not re.search(r"= f32\[(50304|1024),1024\]\S* copy\(",
                         done.as_text())


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_hybrid_step_programs_compile_and_fit_v5e(
        one_chip, kernels_for_the_chip, program):
    """The two serving programs of the benchmark's cut (16 layers, 16 slots,
    384 blocks of 128, 32 snapshot rows) at the widest table: every kernel
    is there, the pool and the state ride the scan without a copy (a
    program that restacks them does not fit beside 8.2 GB of weights)."""
    from distributed_tensorflow_tpu.models import olmo_hybrid as oh
    from distributed_tensorflow_tpu.serve import decode, kv_cache

    cfg = oh.OlmoHybridConfig(
        vocab_size=100352, d_model=3840, d_ff=11008, num_heads=30,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        linear_heads=30, linear_key_dim=96, linear_value_dim=192,
        paged_attention_impl="pallas", gated_delta_impl="pallas")
    cfg = oh.OlmoHybridConfig(**{**cfg.__dict__,
                                 "layer_types": cfg.layer_types * 4})
    model = oh.OlmoHybrid(cfg)
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, oh.param_shapes(cfg))
    slots, width = 16, 64
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: kv_cache.init_hybrid_cache(cfg, slots, 384, 128, 32)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    if program == "prefill":
        done = decode.jit_paged_prefill_chunk(model).lower(
            params, cache, i32(width), i32(256), i32(), i32(), i32())
        kernels = ("gated_delta_chunk_fwd",)
    else:
        done = decode.jit_paged_decode_step(model).lower(
            params, cache, i32(slots, width), i32(slots), i32(slots))
        kernels = ("gated_delta_step",)
    done = done.compile()
    text = done.as_text()
    for kernel in kernels + ("paged_attention_fwd", "paged_kv_write"):
        assert kernel in text
    mem = done.memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes     # the cache, in place
    assert mem.temp_size_in_bytes < 0.5e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 15.0e9


# ---------------------------------------------------------------------------
# GigaChat 3.5 (models/gigachat3_5.py) at its published widths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_latent_attention_kernel_compiles_for_v5e(
        one_chip, kernels_for_the_chip, program):
    """A prefill chunk of 512 tokens (64 heads a token: 32768 query rows) and
    a decode step of 32 slots, against the latent pool of 9216 + 1 blocks
    of 128 rows of 576 lanes stored at 640, the widest table (260 blocks)."""
    from distributed_tensorflow_tpu.ops import latent_attention as la

    B, S = (1, 512) if program == "prefill" else (32, 1)
    S_ = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    pool = S_((1, 9217, 1, 128, 640), jnp.bfloat16)

    def call(q, pool, table, q0, nq, layer):
        return la.latent_attention(q, pool, table, q0, nq, layer=layer,
                                   heads=64, value_width=512, sm_scale=0.1,
                                   impl="pallas")

    done = jax.jit(call).lower(
        S_((B, S * 64, 640), jnp.bfloat16), pool, S_((B, 260), jnp.int32),
        S_((B,), jnp.int32), S_((B,), jnp.int32), S_((), jnp.int32)).compile()
    assert "paged_latent_attention" in done.as_text()
    assert done.memory_analysis().temp_size_in_bytes < 0.2e9


@pytest.mark.parametrize("tokens", [32, 512])
def test_grouped_expert_matmul_compiles_for_v5e(
        one_chip, kernels_for_the_chip, tokens):
    """The held experts' part of a decode step (32 tokens) and of a prefill
    chunk (512): routing over 256 experts, 16 held at d 7168 and width
    2048, read in place from the stacks of 4 expert layers."""
    from distributed_tensorflow_tpu.ops import moe

    S_ = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    w = lambda *s: S_((4, 16, *s), jnp.bfloat16)

    def call(x, router, bias, wg, wu, wd, layer):
        return moe.expert_share(x, router, bias, wg, wu, wd, layer=layer,
                                first=0, top_k=8, scale=2.5, limit=10.0,
                                impl="pallas")

    done = jax.jit(call).lower(
        S_((tokens, 7168)), S_((7168, 256)), S_((256,)), w(7168, 2048),
        w(7168, 2048), w(2048, 7168), S_((), jnp.int32)).compile()
    assert "moe_grouped_mm" in done.as_text()
    # the experts are read where they lie: nothing of a layer's 1.4 GB
    assert done.memory_analysis().temp_size_in_bytes < 0.3e9


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_gigachat_step_programs_compile_and_fit_v5e(
        one_chip, kernels_for_the_chip, program):
    """The two serving programs of the benchmark's cut (5 layers, 16 of 256
    experts held, 32 slots, 9216 blocks of 128 latent rows, 48 snapshot
    rows) at the widest table: every kernel is there, the cache rides the
    layers in place, and weights, cache and temporaries fit the chip."""
    import json
    import os

    from benchmark.families.gigachat3_5 import adapter
    from distributed_tensorflow_tpu.models import gigachat3_5 as gc
    from distributed_tensorflow_tpu.serve import decode

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "gigachat3.5-432b-a28b.json")) as f:
        file = json.load(f)
    model = gc.GigaChat35(adapter.model_config(file))
    deploy = file["serving"]
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, gc.param_shapes(model.cfg))
    slots, width = deploy["num_slots"], 260
    cache = jax.tree.map(on_chip, jax.eval_shape(lambda: model.init_cache(
        slots, deploy["num_blocks"], 128, deploy["num_state_snapshots"])))
    assert cache.kv.shape[-1] == 640              # 576 lanes stored at 640
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    if program == "prefill":
        done = decode.jit_paged_prefill_chunk(model).lower(
            params, cache, i32(width), i32(512), i32(), i32(), i32())
        kernels = ("gated_delta_chunk_fwd",)
    else:
        done = decode.jit_paged_decode_step(model).lower(
            params, cache, i32(slots, width), i32(slots), i32(slots))
        kernels = ("gated_delta_step",)
    done = done.compile()
    text = done.as_text()
    for kernel in kernels + ("paged_latent_attention", "moe_grouped_mm",
                             "paged_kv_write"):
        assert kernel in text
    mem = done.memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes     # the cache, in place
    assert mem.temp_size_in_bytes < 1.0e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 14.0e9
