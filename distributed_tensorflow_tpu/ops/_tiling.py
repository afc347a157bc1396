"""Shared VMEM-budget tile selection for the Pallas kernels
(ops/fused_conv_bn.py, ops/fused_ln_matmul.py, ops/flash_attention.py)."""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax

logger = logging.getLogger(__name__)

VMEM_BUDGET = 10 * 1024 * 1024  # leave headroom under ~16 MB/core
# for the pickers that account for every buffer and temporary of a step:
# ~3 MB slack under the 16 MB scoped limit
FULL_VMEM_BUDGET = 13 * 1024 * 1024
FLASH_MAX_BLOCK = 512  # widest flash row block (a 512 x 512 f32 tile is 1 MB)
FLASH_HEADS_PER_STEP = (4, 2, 1)  # heads sharing a grid step, in preference


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pad_to_sublane(n: int, sublane: int = 8) -> int:
    """Round a row count up to the f32 sublane width — the paged decode
    kernel pads its tiny query tile (S = 1, or k+1 under speculation) so
    the VMEM scratch is tile-aligned on real TPU; the padded rows carry
    ``q_pos = -1`` (attend nothing) and are sliced off."""
    return -(-n // sublane) * sublane


def _lane_pad(n: int, lanes: int = 128) -> int:
    return -(-n // lanes) * lanes


class PagedAttnPlan(NamedTuple):
    """How ``paged_flash_attention`` tiles one call: a grid step owns one
    slot, ``hb`` heads and ``chunk_blocks`` of the slot's blocks, each
    block an operand of its own."""

    hb: int
    chunk_blocks: int
    vmem_bytes: int  # this model's count of a grid step's VMEM
    vmem_limit_bytes: int  # what Mosaic is allowed for the call


PAGED_CHUNK_POSITIONS = 256  # key positions a grid step aims for
PAGED_VMEM_LIMIT = 32 * 1024 * 1024  # of the v5e's 128 MiB; default is 16


def paged_attn_vmem_bytes(S: int, hb: int, chunk_blocks: int,
                          block_size: int, D: int, itemsize: int) -> int:
    """VMEM of one grid step of the paged kernel: the pipeline's two copies
    of the q and o tiles, of the lane-broadcast q positions and of the
    chunk's K and V blocks, the chunk's K and V joined into one operand
    each, the f32 accumulator with its two lane-broadcast row statistics,
    and the score tile's temporaries counted as three f32 tiles a head plus
    the operand copy of ``p`` (the flash kernels' rule,
    ``flash_vmem_bytes``). The head dim pads to the lane width, a chunk's
    positions to it where they are the lane dim."""
    T = chunk_blocks * block_size
    row = _lane_pad(D) * itemsize
    tiles = hb * S * _lane_pad(T) * (3 * 4 + itemsize)
    return (2 * (2 * hb * S * row + S * 128 * 4)
            + (2 + 1) * 2 * hb * T * row
            + hb * S * (_lane_pad(D) + 2 * 128) * 4
            + tiles)


@functools.lru_cache(maxsize=None)
def paged_attn_plan(S: int, H: int, max_blocks: int, block_size: int, D: int,
                    itemsize: int) -> PagedAttnPlan:
    """Tiles for ``paged_flash_attention`` from the call's shape alone:
    ``S`` query rows a slot (already padded to the sublane width), ``H``
    heads of ``D``, a table of ``max_blocks`` blocks of ``block_size``.

    A grid step takes as many blocks as make PAGED_CHUNK_POSITIONS key
    positions (never more than the table has): blocks of 16 go sixteen at
    a time, blocks of 128 two. It takes the most heads that divide ``H``
    and fit the budget: every head of a block is one contiguous copy, and
    a chunk's heads one batched matmul. Where one head does not fit, the
    chunk halves. Raises where a single block of a single head does not
    fit (a block or a head dim far past anything served).

    On a v5e (PR 28, 16 slots, bf16): 79 us a call at GPT-2-XL's decode
    shape (25 heads of 64, 12 contexts of 40-420 tokens) at 256 positions
    a step, 87 at 128, 183 at 512 (where 25 heads no longer fit and five
    groups of 5 take their place); 608 / 612 / 689 us at the hybrid
    model's (30 heads of 128, blocks of 128, contexts of 400-4000), which
    is 82 % of the chip's memory bandwidth."""
    chunk = max(1, min(PAGED_CHUNK_POSITIONS // block_size, max_blocks))
    while True:
        for hb in range(H, 0, -1):
            if H % hb:
                continue
            vmem = paged_attn_vmem_bytes(S, hb, chunk, block_size, D,
                                         itemsize)
            if vmem <= FULL_VMEM_BUDGET:
                plan = PagedAttnPlan(hb, chunk, vmem, PAGED_VMEM_LIMIT)
                logger.info(
                    "paged_attention S=%d H=%d max_blocks=%d block_size=%d "
                    "D=%d itemsize=%d: %s", S, H, max_blocks, block_size, D,
                    itemsize, plan)
                return plan
        if chunk == 1:
            raise ValueError(
                f"paged attention tile (S={S}, block_size={block_size}, "
                f"D={D}) exceeds the VMEM budget; shrink block_size or "
                f"head_dim")
        chunk //= 2


class FlashTilePlan(NamedTuple):
    """How the flash-attention kernels tile one call. A grid step owns
    ``hb`` heads of one ``block_q`` (forward, dQ) or ``block_k`` (dKV) row
    block and sweeps the other axis itself in chunks, over ``kv_span`` /
    ``q_span`` resident rows (the whole sequence unless it does not fit).
    With ``fused_bwd`` the backward is one call, the dKV sweep summing dQ
    as well; without, the dKV and dQ calls of the FlashAttention-2 split."""

    block_q: int
    block_k: int
    hb: int
    q_span: int
    kv_span: int
    resident: bool  # both spans cover their whole sequence
    fused_bwd: bool  # resident, and the fused backward fits its VMEM limit
    grid_steps: int  # of the forward call
    computed_over_needed: float  # score elements computed / kept by the mask


# the fused backward's scoped VMEM, of the v5e's 128 MiB (default 16), and
# the part of it the plan's count may fill (the 3 MB slack of the 16)
FLASH_BWD_VMEM_LIMIT = 32 * 1024 * 1024
FLASH_BWD_VMEM_BUDGET = FLASH_BWD_VMEM_LIMIT - (16 << 20) + FULL_VMEM_BUDGET


def _flash_dkv_bytes(block_k: int, hb: int, q_span: int, D: int,
                     itemsize: int) -> int:
    row = _lane_pad(D) * itemsize
    stat = 128 * 4  # one [.., 8]-wide f32 or int32 row, lane-padded
    return (2 * (hb * (4 * block_k * row + 2 * q_span * row
                       + 2 * 8 * q_span * 4) + block_k * stat)
            + 2 * hb * block_k * _lane_pad(D) * 4)


def flash_fused_bwd_vmem_bytes(block_q: int, block_k: int, hb: int, Sq: int,
                               D: int, itemsize: int) -> int:
    """Per-step VMEM of the fused backward: the dKV kernel's at a q span of
    the whole sequence and its score tiles (``flash_vmem_bytes``), plus the
    f32 dQᵀ scratch ([D, Sq] a head: D on sublanes), the pipeline's two
    copies of the [Sq, D] dQ output block (D on lanes), and a chunk's dQᵀ
    product and its transposed, scaled copy at the write."""
    d8 = -(-D // 8) * 8
    chunk = hb * block_q * (d8 + 2 * _lane_pad(D)) * 4
    return (_flash_dkv_bytes(block_k, hb, Sq, D, itemsize)
            + 3 * hb * block_q * block_k * 4
            + hb * d8 * Sq // block_q * _lane_pad(block_q) * 4
            + 2 * hb * Sq * _lane_pad(D) * itemsize
            + chunk)


def flash_vmem_bytes(block_q: int, block_k: int, hb: int, q_span: int,
                     kv_span: int, D: int, itemsize: int) -> int:
    """Largest per-step VMEM footprint of the forward and the two split
    backward kernels (the fused one: ``flash_fused_bwd_vmem_bytes``). Streamed
    blocks are double-buffered, the head dim is padded to the lane width
    (D = 64 costs what 128 does), [rows, 8] row statistics pad to a full
    lane tile, and the score tile's temporaries are counted as three f32
    tiles a head (the unmodelled scratch was the round-3 OOM). Mosaic's own
    scoped allocation read 16.03 MB for the forward at (256, 256, hb 8) and
    18.0 MB for dQ at (512, 256, hb 4), S 1024, D 64, bf16 (chip, PR 25);
    this model (the largest of the three kernels) gives 23.1 and 18.9."""
    row = _lane_pad(D) * itemsize
    acc = _lane_pad(D) * 4
    stat = 128 * 4  # one [.., 8]-wide f32 or int32 row, lane-padded
    tiles = 3 * hb * block_q * block_k * 4
    fwd = (2 * (hb * (2 * block_q * row + 2 * kv_span * row + block_q * stat)
                + kv_span * stat)
           + hb * (-(-D // 8) * 8 + 2 * 8) * block_q * 4)
    dq = (2 * (hb * (3 * block_q * row + 2 * kv_span * row
                     + 2 * block_q * stat) + 8 * kv_span * 4)
          + hb * block_q * acc)
    dkv = _flash_dkv_bytes(block_k, hb, q_span, D, itemsize)
    return max(fwd, dq, dkv) + tiles


def _lane_blocks(S: int) -> list[int]:
    """Row-block sizes a sequence of S admits, descending: the multiples of
    the lane width that divide it, else S whole (short or odd S)."""
    return [b for b in range(S // 128 * 128, 127, -128) if S % b == 0] or [S]


def _largest_span(S: int, block: int, fits) -> int:
    for span in range(S, block - 1, -block):
        if S % span == 0 and fits(span):
            return span
    return block


@functools.lru_cache(maxsize=None)
def flash_tile_plan(B: int, H: int, Sq: int, Sk: int, D: int, itemsize: int,
                    causal: bool, block_q: int | None = None,
                    block_k: int | None = None) -> FlashTilePlan:
    """Tiles for ``flash_attention`` from the call's shape alone.

    Rule (from the sweep at (8, 16, 1024, 64) bf16 on a v5e, PERF.md §6
    "PR 25"): a causal call computes 1 + max(block_q, block_k)/S of the
    score elements it needs, so its blocks stay at or under a quarter of
    the sequence; a non-causal call wastes nothing and takes the largest
    block up to FLASH_MAX_BLOCK. As many heads share a grid step as the
    budget allows: a chunk's heads are one batched matmul, and a chunk
    of one 256 x 256 head spends as long on its matmuls' latency as on its
    arithmetic. K/V (forward, dQ) and q/dO (dKV)
    stay whole in VMEM where they fit, else the largest span that does
    becomes a grid axis of its own. Explicit ``block_q``/``block_k`` (the
    CPU tests force several chunks at small S) override only the blocks."""
    budget = FULL_VMEM_BUDGET
    vmem = functools.partial(flash_vmem_bytes, D=D, itemsize=itemsize)

    def pick(S):
        cap = FLASH_MAX_BLOCK
        if causal:
            cap = min(max(128, S // 4), cap)
        blocks = _lane_blocks(S)
        return next((b for b in blocks
                     if b <= cap and vmem(b, b, 1, b, b) <= budget),
                    blocks[-1])

    block_q = block_q or pick(Sq)
    block_k = block_k or pick(Sk)

    def fits(hb, q_span, kv_span):
        return vmem(block_q, block_k, hb, q_span, kv_span) <= budget

    hb = next((h for h in FLASH_HEADS_PER_STEP
               if H % h == 0 and fits(h, Sq, Sk)), 1)
    q_span = _largest_span(Sq, block_q, lambda s: fits(hb, s, block_k))
    kv_span = _largest_span(Sk, block_k, lambda s: fits(hb, q_span, s))
    computed = 0
    for i in range(Sq // block_q):
        q_hi = (i + 1) * block_q - 1 + (Sk - Sq)
        chunks = Sk // block_k
        if causal:
            chunks = min(max(q_hi + block_k, 0) // block_k, chunks)
        computed += chunks * block_q * block_k
    if causal:
        needed = sum(min(max(r + 1 + Sk - Sq, 0), Sk) for r in range(Sq))
    else:
        needed = Sq * Sk
    resident = q_span == Sq and kv_span == Sk
    plan = FlashTilePlan(
        block_q=block_q, block_k=block_k, hb=hb, q_span=q_span,
        kv_span=kv_span, resident=resident,
        fused_bwd=resident and flash_fused_bwd_vmem_bytes(
            block_q, block_k, hb, Sq, D, itemsize) <= FLASH_BWD_VMEM_BUDGET,
        grid_steps=B * (H // hb) * (Sq // block_q) * (Sk // kv_span),
        computed_over_needed=computed / max(needed, 1),
    )
    logger.info("flash_attention B=%d H=%d Sq=%d Sk=%d D=%d itemsize=%d "
                "causal=%s: %s", B, H, Sq, Sk, D, itemsize, causal, plan)
    return plan


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
    budget = FULL_VMEM_BUDGET

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
    budget = FULL_VMEM_BUDGET
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
# round-3 session A: the fused-kernel microbench's grad at s3_conv1, rc=124 with the
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
