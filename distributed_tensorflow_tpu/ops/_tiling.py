"""Shared VMEM-budget tile selection for the fused Pallas matmul kernels
(ops/fused_conv_bn.py, ops/fused_ln_matmul.py)."""

from __future__ import annotations

import jax

VMEM_BUDGET = 10 * 1024 * 1024  # leave headroom under ~16 MB/core


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pad_to_sublane(n: int, sublane: int = 8) -> int:
    """Round a row count up to the f32 sublane width — the paged decode
    kernel pads its tiny query tile (S = 1, or k+1 under speculation) so
    the VMEM scratch is tile-aligned on real TPU; the padded rows carry
    ``q_pos = -1`` (attend nothing) and are sliced off."""
    return -(-n // sublane) * sublane


def paged_attn_vmem_ok(S: int, block_size: int, D: int,
                       *, lanes: int = 128) -> bool:
    """True when the paged-attention kernel's per-instance VMEM footprint
    (resident q/o/acc [S, D] tiles, m/l row stats [S, lanes], one
    double-buffered [block_size, D] k/v block pair) fits the shared
    budget. Decode shapes are tiny (S ≤ 8, D ≤ 256), so this is a
    tripwire against pathological configs, not a tile picker."""
    resident = 3 * S * D * 4 + 2 * S * lanes * 4
    stream = 2 * 2 * block_size * D * 4
    return resident + stream <= VMEM_BUDGET


def pick_block_m(M: int, k: int, n: int, *, name: str) -> int:
    """Largest 8-aligned divisor of M whose [bm, k]/[bm, n] streaming
    tiles fit the budget; a single whole-M block for tiny/odd M. A
    block's sublane dim must be 8-aligned unless it covers the whole dim
    (then Mosaic pads the array edge itself)."""
    fits = lambda bm: (
        2 * bm * (2 * k + 2 * n) + 4 * bm * (k + n) <= VMEM_BUDGET
    )  # 2 buffers on the streamed operands + one f32 temp each
    for bm in range(min(M, 1024) // 8 * 8, 7, -8):
        if M % bm == 0 and fits(bm):
            return bm
    if fits(M):
        return M
    raise ValueError(
        f"{name}: M={M} has no 8-aligned tile under the VMEM budget for "
        f"k={k}, n={n}; make the row count divisible by a multiple of 8"
    )


def _aligned_divisors(M: int, cap: int = 1024) -> list[int]:
    """8-aligned divisors of M up to ``cap`` (descending), with M itself
    as the fallback when no aligned divisor exists (Mosaic then pads the
    array edge)."""
    out = [bm for bm in range(min(M, cap) // 8 * 8, 7, -8) if M % bm == 0]
    return out or [M]


def pick_dw_tiles(M: int, cin: int, cout: int, *, in_bytes: int,
                  emit_stats: bool, name: str) -> tuple[int, int]:
    """Joint (bm, bn) for the dw kernels, with FULL per-tile VMEM
    accounting — the round-2 pickers modelled only the streamed operands
    and sized the accumulator separately, which let the bench-shape
    [12544, 512] x [12544, 2048] dw kernel allocate a 17.9 MB scoped
    stack (> the 16 MB core limit) even though each term individually
    "fit" (caught on-chip, round 3; the validator now compiles the real
    bench shapes so this class of miss cannot pass again).

    Model per (bm, bn) tile:
      - streamed, double-buffered: x [bm, cin]; y and dy [bm, bn] (y is
        streamed regardless of emit_stats — the BlockSpec always maps it)
      - resident accumulator, double-buffered across the outer-j switch:
        dw [cin, bn] f32, plus the dot-product f32 temp of the same shape
      - f32 stack scratch Mosaic materializes: g (and y when emit_stats)
        [bm, bn]; the prologue x [bm, cin] + its in-dtype cast

    Preference order: largest bn first (each bn-tile re-streams the whole
    x, so fewer column tiles = less HBM traffic), then largest bm; bm is
    kept >= 128 where possible so the row-contraction feeds the MXU full
    tiles."""
    budget = 13 * 1024 * 1024  # ~3 MB slack under the 16 MB scoped limit

    def tile_bytes(bm: int, bn: int) -> int:
        stream = 2 * (bm * cin * in_bytes + 2 * bm * bn * in_bytes)
        acc = 3 * cin * bn * 4
        scratch = ((2 if emit_stats else 1) * bm * bn * 4
                   + bm * cin * 4 + bm * cin * in_bytes)
        return stream + acc + scratch

    bms = _aligned_divisors(M)
    bns = [bn for bn in (cout, *range(2048, 127, -128))
           if bn <= cout and cout % bn == 0]
    for prefer_wide_bm in (True, False):
        for bn in bns:
            for bm in bms:
                if prefer_wide_bm and bm < min(128, M):
                    continue
                if tile_bytes(bm, bn) <= budget:
                    return bm, bn
    if len(bms) == 1 and bms[0] == M and M % 8 != 0:
        dim_hint = f"M={M} has no 8-aligned divisor <= 1024"
    elif 3 * cin * 128 * 4 > budget:
        # even the narrowest lane-aligned bn can't fit the [cin, bn]
        # f32 accumulator — the problem is cin, not cout
        dim_hint = f"cin={cin} is too wide for a resident f32 accumulator"
    else:
        dim_hint = f"cout={cout} may need padding to a multiple of 128"
    raise ValueError(
        f"{name}: no (bm, bn) tile for M={M}, cin={cin}, cout={cout} "
        f"fits the VMEM budget ({dim_hint})"
    )


def pick_single_pass_bm(M: int, cin: int, cout: int, *, in_bytes: int,
                        emit_stats: bool) -> int | None:
    """Row tile for the SINGLE-PASS backward kernel (dx + dscale/dshift +
    dw in one sweep over x/y/dy), or None when the shape cannot fit.

    Motivation (round-3 on-chip): the two-pass Pallas backward streams
    x/y/dy twice and measured 0.40-0.87x of XLA's fused backward; one
    pass streams them once — structurally less HBM traffic than either.
    The catch is VMEM: the whole [cin, cout] f32 dw accumulator (plus
    its dot-product temp and the w operand) must stay resident alongside
    the streamed tiles, so this only works for the narrower layer
    shapes; which shapes qualify depends on dtype — in bf16 most
    batch-256 ResNet-50 1x1s fit, in f32 the widest (512<->2048) do not.
    This function IS the authority; never assume per-shape behavior
    without calling it. Returns the largest 8-aligned bm >= 64 that fits
    a conservative model; None means "use the two-pass kernels".

    Model per tile: double-buffered streams (x, y, dy in; dx out);
    resident w [cin, cout] + dw accumulator and dot temp (f32);
    f32 scratch for g (and y when emit_stats), dh, x32, plus the
    prologue temps (xn, relu mask, h — counted unconditionally, the
    round-3 OOM was exactly an unmodeled-scratch miss) and the in-dtype
    casts of h and g.
    """
    budget = 13 * 1024 * 1024
    resident = (cin * cout * in_bytes          # w
                + 2 * cin * cout * 4)          # dw accumulator + dot temp

    def tile_bytes(bm: int) -> int:
        stream = 2 * (2 * bm * cin * in_bytes + 2 * bm * cout * in_bytes)
        scratch = ((2 if emit_stats else 1) * bm * cout * 4
                   + 2 * bm * cin * 4
                   + 3 * bm * cin * 4            # prologue xn/live/h f32
                   + bm * cin * in_bytes + bm * cout * in_bytes)
        return resident + stream + scratch

    for bm in _aligned_divisors(M, cap=512):
        if bm >= 64 and tile_bytes(bm) <= budget:
            return bm
    return None


# (M, cin, cout) shapes where the Pallas-backward Mosaic compile (or its
# first execution) has been OBSERVED to stall >10 min on the real v5e —
# round-3 session A: bench_fused_kernels grad at s3_conv1 rc=124 with the
# pick_dw_tiles tiling. Populated strictly from on-chip evidence; remove
# an entry when a later session shows it compiles+runs sanely (the
# validator's VALIDATE_PALLAS_BWD sweep sets DTF_FUSED_BWD_FORCE=1 and
# times every shape precisely to produce that evidence).
PALLAS_BWD_KNOWN_SLOW: set[tuple[int, int, int]] = {
    (12544, 2048, 512),  # s3_conv1, batch-256 ResNet-50
}


def pallas_bwd_known_slow(M: int, cin: int, cout: int) -> bool:
    """True when DTF_FUSED_BWD=pallas should refuse this shape (known
    pathological compile) — overridable with DTF_FUSED_BWD_FORCE=1 for
    measurement runs."""
    import os

    if os.environ.get("DTF_FUSED_BWD_FORCE") == "1":
        return False
    return (M, cin, cout) in PALLAS_BWD_KNOWN_SLOW


def resolve_bwd_impl(bwd_impl: str | None) -> str:
    """The fused composites' backward selection policy (one home for the
    env default so the two op families cannot drift): explicit argument
    wins, else ``DTF_FUSED_BWD``, else the "xla" path, which measured
    faster at every ResNet-50 shape on a v5e (previous toolchain —
    PERF.md "Earlier chip findings")."""
    import os

    impl = bwd_impl or os.environ.get("DTF_FUSED_BWD", "xla")
    if impl not in ("xla", "pallas"):
        raise ValueError(f"bwd_impl must be 'xla' or 'pallas', got {impl!r}")
    return impl
