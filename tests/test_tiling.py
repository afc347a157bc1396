"""Unit tests for the shared VMEM tile-selection model (ops/_tiling.py).

The round-3 on-chip OOM (conv1x1_bn_bwd_dw at [12544, 512] x [12544,
2048], 17.86 MB scoped stack vs the 16 MB core limit) is the regression
these pin: the joint picker must keep its own accounting under budget
for every shape the batch-256 ResNet-50 / bench transformer paths emit.
"""

import pytest

from distributed_tensorflow_tpu.ops import _tiling

# every 1x1-conv dw shape a batch-256 ResNet-50 emits + bench ln_matmul
BENCH_SHAPES = [
    (200704, 64, 256), (200704, 256, 64), (200704, 256, 128),
    (50176, 128, 512), (50176, 512, 128), (50176, 512, 256),
    (12544, 256, 1024), (12544, 1024, 256), (12544, 1024, 512),
    (3136, 512, 2048), (3136, 2048, 512),
    (12544, 512, 2048), (12544, 2048, 512),
    (16384, 768, 2304), (16384, 768, 3072), (16384, 3072, 768),
    (32768, 1024, 4096),
]


@pytest.mark.parametrize("M,cin,cout", BENCH_SHAPES)
@pytest.mark.parametrize("emit_stats", [False, True])
def test_bench_shapes_fit_and_divide(M, cin, cout, emit_stats):
    bm, bn = _tiling.pick_dw_tiles(
        M, cin, cout, in_bytes=2, emit_stats=emit_stats, name="t"
    )
    assert M % bm == 0 and cout % bn == 0
    assert bm % 8 == 0 or bm == M
    assert bn % 128 == 0 or bn == cout
    # re-apply the picker's own accounting: chosen tile must be in budget
    stream = 2 * (bm * cin * 2 + 2 * bm * bn * 2)
    acc = 3 * cin * bn * 4
    scratch = (2 if emit_stats else 1) * bm * bn * 4 + bm * cin * 4 + bm * cin * 2
    assert stream + acc + scratch <= 13 * 1024 * 1024


def test_r3_oom_shape_stays_under_scoped_limit():
    """The exact shape that blew the 16 MB scoped limit on-chip: the
    model's own upper bound for the chosen tile must leave real slack."""
    bm, bn = _tiling.pick_dw_tiles(
        12544, 512, 2048, in_bytes=2, emit_stats=True, name="t"
    )
    # the old independent-term picker chose (448, 2048) here -> 17.86 MB
    assert (bm, bn) != (448, 2048)
    assert bm * bn < 448 * 2048


def test_prefers_wide_bm_then_wide_bn():
    # comfortable shape: both dims should stay whole
    bm, bn = _tiling.pick_dw_tiles(
        1024, 128, 256, in_bytes=2, emit_stats=True, name="t"
    )
    assert bn == 256
    assert bm >= 128


def test_error_names_the_failing_dimension():
    with pytest.raises(ValueError, match="M=12545"):
        _tiling.pick_dw_tiles(12545, 4096, 8192, in_bytes=4,
                              emit_stats=True, name="t")
    with pytest.raises(ValueError, match="cin=2000000"):
        _tiling.pick_dw_tiles(4096, 2000000, 128, in_bytes=2,
                              emit_stats=True, name="t")


def test_resolve_bwd_impl_policy(monkeypatch):
    monkeypatch.delenv("DTF_FUSED_BWD", raising=False)
    assert _tiling.resolve_bwd_impl(None) == "xla"
    monkeypatch.setenv("DTF_FUSED_BWD", "pallas")
    assert _tiling.resolve_bwd_impl(None) == "pallas"
    assert _tiling.resolve_bwd_impl("xla") == "xla"  # explicit arg wins
    with pytest.raises(ValueError, match="bwd_impl"):
        _tiling.resolve_bwd_impl("cuda")


# --- flash attention tiles (flash_tile_plan) ------------------------------

FLASH_S = (128, 256, 384, 512, 640, 1024, 2048, 4096, 8192)


def test_flash_plan_for_the_benchmark_training_call():
    """(8, 16, 1024, 64) bf16 causal: the grid was 8,192 steps a call, 44 %
    of them above the diagonal."""
    plan = _tiling.flash_tile_plan(8, 16, 1024, 1024, 64, 2, True)
    assert plan.resident
    assert plan.grid_steps <= 512
    assert plan.computed_over_needed <= 1.3


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S", [384, 640])
def test_flash_blocks_divide_bert_padded_lengths(S, causal):
    plan = _tiling.flash_tile_plan(8, 12, S, S, 64, 2, causal)
    for block in (plan.block_q, plan.block_k):
        assert S % block == 0 and block % 128 == 0
    assert 12 % plan.hb == 0


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("S", FLASH_S)
def test_flash_plan_fits_vmem_and_falls_back_only_when_it_must(S, D, causal):
    plan = _tiling.flash_tile_plan(1, 8, S, S, D, 2, causal)
    used = _tiling.flash_vmem_bytes(
        plan.block_q, plan.block_k, plan.hb, plan.q_span, plan.kv_span, D, 2)
    assert used <= _tiling.FULL_VMEM_BUDGET
    assert S % plan.kv_span == 0 and plan.kv_span % plan.block_k == 0
    assert S % plan.q_span == 0 and plan.q_span % plan.block_q == 0
    # the kv-major axis engages exactly when a head's whole sequence does
    # not fit beside the chosen tile
    whole = _tiling.flash_vmem_bytes(
        plan.block_q, plan.block_k, 1, S, S, D, 2)
    assert plan.resident == (whole <= _tiling.FULL_VMEM_BUDGET)
    assert plan.grid_steps == (
        (8 // plan.hb) * (S // plan.block_q) * (S // plan.kv_span))


def test_flash_plan_explicit_blocks_override_only_the_blocks():
    plan = _tiling.flash_tile_plan(2, 4, 256, 512, 64, 4, True, 64, 128)
    assert (plan.block_q, plan.block_k) == (64, 128)
    assert plan.resident and 4 % plan.hb == 0
    # Sq != Sk: the last query sits on the last key
    assert 1.0 < plan.computed_over_needed < 1.3


# --- paged attention tiles (paged_attn_plan) ------------------------------

#: (H, block_size, D, widest table) of the two serving cells
PAGED_CELLS = {"gpt2-xl": (25, 16, 64, 48),
               "olmo-hybrid-7b": (30, 128, 128, 64)}


@pytest.mark.parametrize("S", [8, 32, 256], ids=lambda s: f"S{s}")
@pytest.mark.parametrize("width", [1, 2, 4, 8, 16, 32, "widest"])
@pytest.mark.parametrize("cell", sorted(PAGED_CELLS))
def test_paged_plan_fits_vmem_at_the_cells_shapes(cell, width, S):
    """Decode (a row padded to 8), verify and the two cells' prefill
    chunks, at every table width the engine's buckets make: the plan fits
    the budget by its own count, ``hb`` divides the heads, and a grid step
    takes at least 128 key positions wherever the table has them."""
    H, bs, D, widest = PAGED_CELLS[cell]
    MB = widest if width == "widest" else width
    plan = _tiling.paged_attn_plan(S, H, MB, bs, D, 2)
    assert H % plan.hb == 0 and 1 <= plan.chunk_blocks <= MB
    assert plan.vmem_bytes == _tiling.paged_attn_vmem_bytes(
        S, plan.hb, plan.chunk_blocks, bs, D, 2) <= _tiling.FULL_VMEM_BUDGET
    assert plan.vmem_bytes < plan.vmem_limit_bytes
    assert plan.chunk_blocks * bs >= min(128, MB * bs)
    assert plan.chunk_blocks * bs <= max(_tiling.PAGED_CHUNK_POSITIONS, bs)
    if S == 8:  # a decode step takes every head of a block in one copy
        assert plan.hb == H


def test_paged_plan_halves_the_chunk_before_it_gives_up():
    """Where one head of the full chunk does not fit, the chunk halves;
    where one block of one head does not fit, the shape is refused (the
    old ``paged_attn_vmem_ok`` tripwire)."""
    plan = _tiling.paged_attn_plan(1536, 8, 64, 64, 128, 4)
    assert plan.hb == 1 and plan.chunk_blocks == 2  # four make 256
    with pytest.raises(ValueError, match="VMEM budget"):
        _tiling.paged_attn_plan(8, 8, 64, 8192, 512, 4)
