"""Pallas-TPU FlashAttention-2 kernel (forward + backward, custom_vjp).

The hot op of the transformer family (models/transformer.py) and the
per-chip inner block of ring attention (parallel/ring_attention.py,
SURVEY.md §5.7). This is the framework's "native kernel" tier: where the
reference framework dropped to hand-written CUDA for its hot ops
(SURVEY.md §2b native rows), the TPU-native equivalent is a Pallas kernel
compiled to Mosaic (SURVEY.md §5.8 native-code policy).

Design (standard FlashAttention-2 tiling, adapted to TPU tiles):

- Layout [B, H, S, D]: the grid iterates (batch, head, q-block, kv-block)
  with the kv-block innermost; each kernel instance owns one
  (block_q × D) output tile held in VMEM f32 scratch across the kv sweep,
  with running max ``m`` and denominator ``l`` as (block_q × LANES)
  broadcast-tiles (TPU scratch wants 2-D lane-aligned shapes).
- The forward also emits LSE = m + log l at sublane width
  ([B,H,Sq,STAT_DIM], STAT_DIM=8 — lane-broadcasting the row stat 128-wide
  would cost 16× HBM for long sequences). The backward is two more pallas
  calls (dKV with q-block innermost; dQ with kv-block innermost), the
  FlashAttention-2 split that keeps every accumulator local to one grid
  cell (no cross-instance atomics, which TPU does not have); each
  recomputes delta = rowsum(dO·O) per tile instead of materializing it.
- Causal masking skips fully-masked kv blocks via ``pl.when`` (no MXU work
  issued), and applies the triangular mask inside diagonal blocks.
- ``kv_mask`` [B, Sk] covers padding (BERT-style); mask semantics match
  ops/attention.py (True = attend).
- On non-TPU backends ``interpret=True`` runs the same kernels through the
  Pallas interpreter — this is how CI (8 fake CPU devices, SURVEY.md §4.2)
  tests the exact kernel code path without TPU hardware.

bf16 inputs are upcast per-tile; all accumulation is f32 (online-softmax
numerics, SURVEY.md §7 "hard parts" #3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF

LANES = 128  # TPU lane width (scratch row-stat tiles)
STAT_DIM = 8  # f32 sublane width (HBM row-stat storage)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _dot(a, b, dims):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32
    )


def _causal_mask(q_start, kj, block_q, block_k):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return kpos <= qpos


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, mask_ref,
    o_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *, sm_scale, causal, block_q, block_k, q_offset,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    q_start = qi * block_q + q_offset

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        logits = _dot(q, k, ((1,), (1,))) * sm_scale  # [bq, bk]
        mask = mask_ref[0, 0].astype(jnp.bool_)[None, :]
        if causal:
            mask = mask & _causal_mask(q_start, kj, block_q, block_k)
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]  # [bq, LANES] (row stat broadcast over lanes)
        l_prev = l_ref[...]
        m_cur = logits.max(axis=1)[:, None]  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        # explicit zero under the mask: for fully-masked rows m stays
        # NEG_INF and exp(NEG_INF - NEG_INF) would be 1, poisoning l
        p = jnp.where(mask, jnp.exp(logits - m_new[:, :1]), 0.0)  # [bq, bk]
        correction = jnp.exp(m_prev - m_new)  # [bq, LANES]
        l_ref[...] = l_prev * correction + jnp.broadcast_to(
            p.sum(axis=1)[:, None], l_prev.shape
        )
        acc_ref[...] = acc_ref[...] * correction[:, :1] + _dot(
            p, v, ((1,), (0,))
        )
        m_ref[...] = m_new

    if causal:
        # skip kv blocks strictly above the diagonal band (no MXU work)
        pl.when(kj * block_k <= q_start + block_q - 1)(compute)
    else:
        compute()

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[...][:, :1]  # [bq, 1]
        # all-masked rows (l==0) → zero output, lse = NEG_INF
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m_ref[...] + jnp.log(safe_l), NEG_INF)
        lse_ref[0, 0] = lse[:, :STAT_DIM].astype(lse_ref.dtype)


def _fwd_call(
    q, k, v, kv_mask, *, sm_scale, causal, q_offset, block_q, block_k,
    interpret
):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    grid = (B, H, Sq // block_q, Sk // block_k)

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, q_offset=q_offset,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, block_q, STAT_DIM), lambda b, h, i, j: (b, h, i, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, STAT_DIM), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v, kv_mask)
    return out, lse


# ---------------------------------------------------------------------------
# Backward: dKV kernel (kv block resident, q innermost) and
#           dQ kernel (q block resident, kv innermost)
# ---------------------------------------------------------------------------


def _bwd_p_ds(q_ref, k_ref, v_ref, mask_ref, do_ref, o_ref, lse_ref,
              *, sm_scale, causal, q_start, kj, block_q, block_k):
    """Shared tile math: recompute p and ds for one (q-block, kv-block)."""
    q = q_ref[0, 0].astype(jnp.float32)  # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)  # [bq, D]
    o = o_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, :1]  # [bq, 1]
    delta = jnp.sum(do * o, axis=1, keepdims=True)  # [bq, 1]

    logits = _dot(q, k, ((1,), (1,))) * sm_scale  # [bq, bk]
    mask = mask_ref[0, 0].astype(jnp.bool_)[None, :]
    if causal:
        mask = mask & _causal_mask(q_start, kj, block_q, block_k)
    # p = exp(logits - lse); all-masked rows have lse=NEG_INF → force 0
    p = jnp.where(mask, jnp.exp(logits - lse), 0.0)  # [bq, bk]
    dp = _dot(do, v, ((1,), (1,)))  # [bq, bk]
    ds = p * (dp - delta) * sm_scale
    return q, do, p, ds


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, mask_ref, do_ref, o_ref, lse_ref,
    dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, sm_scale, causal, block_q, block_k, q_offset,
):
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    q_start = qi * block_q + q_offset

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def compute():
        q, do, p, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, mask_ref, do_ref, o_ref, lse_ref,
            sm_scale=sm_scale, causal=causal, q_start=q_start, kj=kj,
            block_q=block_q, block_k=block_k,
        )
        dv_acc[...] += _dot(p, do, ((0,), (0,)))  # pᵀ·dO → [bk, D]
        dk_acc[...] += _dot(ds, q, ((0,), (0,)))  # dsᵀ·q → [bk, D]

    if causal:
        pl.when(kj * block_k <= q_start + block_q - 1)(compute)
    else:
        compute()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, mask_ref, do_ref, o_ref, lse_ref,
    dq_ref,
    dq_acc,
    *, sm_scale, causal, block_q, block_k, q_offset,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    q_start = qi * block_q + q_offset

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def compute():
        k = k_ref[0, 0].astype(jnp.float32)
        _, _, _, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, mask_ref, do_ref, o_ref, lse_ref,
            sm_scale=sm_scale, causal=causal, q_start=q_start, kj=kj,
            block_q=block_q, block_k=block_k,
        )
        dq_acc[...] += _dot(ds, k, ((1,), (0,)))  # [bq, D]

    if causal:
        pl.when(kj * block_k <= q_start + block_q - 1)(compute)
    else:
        compute()

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, kv_mask, sm_scale, causal, block_q, block_k, interpret,
           q_offset):
    out, _ = _fwd_call(
        q, k, v, kv_mask,
        sm_scale=sm_scale, causal=causal, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, kv_mask, sm_scale, causal, block_q, block_k,
               interpret, q_offset):
    out, lse = _fwd_call(
        q, k, v, kv_mask,
        sm_scale=sm_scale, causal=causal, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out, (q, k, v, kv_mask, out, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, q_offset,
               res, do):
    q, k, v, kv_mask, out, lse = res
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    common = dict(
        sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, q_offset=q_offset,
    )

    qspec = lambda b, h, j, i: (b, h, i, 0)  # noqa: E731
    kspec = lambda b, h, j, i: (b, h, j, 0)  # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(B, H, Sk // block_k, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), qspec),
            pl.BlockSpec((1, 1, block_k, D), kspec),
            pl.BlockSpec((1, 1, block_k, D), kspec),
            pl.BlockSpec((1, 1, block_k), lambda b, h, j, i: (b, 0, j)),
            pl.BlockSpec((1, 1, block_q, D), qspec),
            pl.BlockSpec((1, 1, block_q, D), qspec),
            pl.BlockSpec((1, 1, block_q, STAT_DIM), qspec),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), kspec),
            pl.BlockSpec((1, 1, block_k, D), kspec),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, kv_mask, do, out, lse)

    qspec2 = lambda b, h, i, j: (b, h, i, 0)  # noqa: E731
    kspec2 = lambda b, h, i, j: (b, h, j, 0)  # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(B, H, Sq // block_q, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), qspec2),
            pl.BlockSpec((1, 1, block_k, D), kspec2),
            pl.BlockSpec((1, 1, block_k, D), kspec2),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j)),
            pl.BlockSpec((1, 1, block_q, D), qspec2),
            pl.BlockSpec((1, 1, block_q, D), qspec2),
            pl.BlockSpec((1, 1, block_q, STAT_DIM), qspec2),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), qspec2),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, kv_mask, do, out, lse)

    return dq, dk, dv, np.zeros(kv_mask.shape, jax.dtypes.float0)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Paged decode kernel: attention straight off the block pool
# ---------------------------------------------------------------------------


def _paged_fwd_kernel(
    bt_ref,  # scalar-prefetched block table [B, MB] (unused in the body —
    #          it drives the k/v index_maps; Pallas still passes it in)
    q_ref, qpos_ref, k_ref, v_ref,
    o_ref,
    acc_ref, m_ref, l_ref,
    *, sm_scale, block_size,
):
    del bt_ref
    j = pl.program_id(2)
    nb = pl.num_programs(2)
    S = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # [S, D]
    k = k_ref[0, 0].astype(jnp.float32)  # [bs, D] — one physical block
    v = v_ref[0, 0].astype(jnp.float32)
    logits = _dot(q, k, ((1,), (1,))) * sm_scale  # [S, bs]
    kpos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (S, block_size), 1
    )
    # [S, 1] absolute query positions (-1 = padded row); the row stat
    # arrives lane-broadcast like m/l, so this is a lane slice, not a
    # sublane<->lane relayout
    mask = kpos <= qpos_ref[0][:, :1]
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = logits.max(axis=1)[:, None]
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    # explicit zero under the mask (see _fwd_kernel): fully-masked rows
    # keep m == NEG_INF and must not poison l with exp(0) == 1
    p = jnp.where(mask, jnp.exp(logits - m_new[:, :1]), 0.0)
    correction = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * correction + jnp.broadcast_to(
        p.sum(axis=1)[:, None], l_prev.shape
    )
    acc_ref[...] = acc_ref[...] * correction[:, :1] + _dot(p, v, ((1,), (0,)))
    m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        # all-masked rows (idle slots never reach here with l == 0 — their
        # sentinel q_pos attends everything — but padded rows do)
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype
        )


def paged_flash_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    *,
    q_pos: jax.Array,
    sm_scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Block-table-aware attention for paged decode — KV blocks are read
    IN PLACE from the pool (``k_pool``/``v_pool``
    [num_blocks, H, block_size, D]); the contiguous logical view that
    ``paged_gather_kv`` materializes never exists.

    The block table is SCALAR-PREFETCHED (pltpu.PrefetchScalarGridSpec):
    the grid iterates (batch, head, logical-block) and the k/v index_maps
    read ``table[b, j]`` to aim each step's DMA at the right physical
    block — table indirection costs an index computation, not a gather.
    Masking is the paged contract: key position ``j <= q_pos`` attends;
    sentinel table entries (``>= num_blocks``) clamp onto garbage the
    mask excludes. Forward-only (decode never differentiates).

    ``interpret=None`` auto-selects: compiled on TPU, Pallas interpreter
    elsewhere (slow; tests pin numerics against the gather path). On TPU
    the query tile pads to the f32 sublane width (padded rows get
    ``q_pos = -1`` — attend nothing — and are sliced off)."""
    from ._tiling import pad_to_sublane, paged_attn_vmem_ok

    B, H, S, D = q.shape
    NB, _, bs, _ = k_pool.shape
    MB = block_table.shape[1]
    if interpret is None:
        interpret = not _on_tpu()
    if not paged_attn_vmem_ok(S, bs, D):
        raise ValueError(
            f"paged attention tile (S={S}, block_size={bs}, D={D}) "
            f"exceeds the VMEM budget; shrink block_size or head_dim"
        )
    Sp = S if interpret else pad_to_sublane(S)
    qp = q_pos.astype(jnp.int32)
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        qp = jnp.pad(qp, ((0, 0), (0, Sp - S)), constant_values=-1)
    scale = sm_scale if sm_scale is not None else D**-0.5

    # [B, Sp, LANES]: a (1, Sp) block of a [B, Sp] array would put 1 on
    # the sublane dim, neither a multiple of 8 nor the full extent
    qp = jnp.broadcast_to(qp[:, :, None], (B, Sp, LANES))
    qspec = pl.BlockSpec((1, 1, Sp, D), lambda b, h, j, bt: (b, h, 0, 0))
    kvspec = pl.BlockSpec(
        (1, 1, bs, D),
        lambda b, h, j, bt: (jnp.minimum(bt[b, j], NB - 1), h, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, MB),
        in_specs=[
            qspec,
            pl.BlockSpec((1, Sp, LANES), lambda b, h, j, bt: (b, 0, 0)),
            kvspec,
            kvspec,
        ],
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((Sp, D), jnp.float32),
            pltpu.VMEM((Sp, LANES), jnp.float32),
            pltpu.VMEM((Sp, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_fwd_kernel, sm_scale=scale, block_size=bs
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, D), q.dtype),
        interpret=interpret,
        name="paged_attention_fwd",
    )(block_table.astype(jnp.int32), q, qp, k_pool, v_pool)
    return out[:, :, :S]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    kv_mask: jax.Array | None = None,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """FlashAttention on TPU via Pallas. Same contract as
    ops.attention.attention_reference: q [B,H,Sq,D], k/v [B,H,Sk,D],
    kv_mask [B,Sk] bool (True = attend), returns [B,H,Sq,D] in q.dtype.
    Differentiable (custom VJP with Pallas backward kernels).

    ``interpret=None`` auto-selects: compiled on TPU, Pallas interpreter
    elsewhere (slow; tests only). Sequence lengths must be multiples of the
    block sizes (callers pad + pass kv_mask; models/transformer.py does)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    # env-tunable so on-chip sessions can sweep tile sizes without a code
    # change (DTF_FLASH_BLOCK_Q/K); 128x128 is the safe default, larger K
    # tiles cut grid overhead at long seq once measured. The env knobs are
    # process-global and read at TRACE time, so a sweep value tuned for the
    # bench shape must not break other call sites (e.g. Sq=384 under a
    # 256 block): an env block that doesn't divide falls back to the 128
    # default with a warning instead of raising — only an EXPLICIT
    # block_q/block_k argument keeps the hard divisibility error.
    import os

    from_env_q = block_q is None and "DTF_FLASH_BLOCK_Q" in os.environ
    from_env_k = block_k is None and "DTF_FLASH_BLOCK_K" in os.environ
    if block_q is None:
        block_q = int(os.environ.get("DTF_FLASH_BLOCK_Q", "128"))
    if block_k is None:
        block_k = int(os.environ.get("DTF_FLASH_BLOCK_K", "128"))
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    # fall back only when the env var was actually set AND the 128
    # default would work — otherwise let the hard error below name the
    # real problem (an unpadded sequence)
    if from_env_q and Sq % block_q and Sq % min(128, Sq) == 0:
        import warnings

        warnings.warn(
            f"DTF_FLASH_BLOCK_Q={block_q} does not divide Sq={Sq}; "
            f"falling back to 128 for this call site")
        block_q = min(128, Sq)
    if from_env_k and Sk % block_k and Sk % min(128, Sk) == 0:
        import warnings

        warnings.warn(
            f"DTF_FLASH_BLOCK_K={block_k} does not divide Sk={Sk}; "
            f"falling back to 128 for this call site")
        block_k = min(128, Sk)
    if Sq % block_q or Sk % block_k:
        raise ValueError(
            f"seq lens ({Sq=}, {Sk=}) must be multiples of block sizes "
            f"({block_q=}, {block_k=}); pad and pass kv_mask"
        )
    if interpret is None:
        interpret = not _on_tpu()
    if not interpret:
        # Mosaic lane/sublane layout constraints (the interpreter has none):
        # the kv-mask block's lane dim is block_k, the q tile's sublane dim
        # is block_q. Sub-128 kv blocks would also waste the 128×128 MXU.
        if block_k % LANES and block_k != Sk:
            raise ValueError(
                f"on TPU, block_k ({block_k}) must be a multiple of {LANES} "
                f"or equal to Sk ({Sk})"
            )
        if block_q % STAT_DIM and block_q != Sq:
            raise ValueError(
                f"on TPU, block_q ({block_q}) must be a multiple of "
                f"{STAT_DIM} or equal to Sq ({Sq})"
            )
    if kv_mask is None:
        kv_mask = jnp.ones((B, 1, Sk), jnp.int32)
    else:
        # bool refs are awkward on TPU; [B,1,Sk] keeps the block 3-D with a
        # full-size middle dim (TPU tiling wants the 2nd-to-last dim full)
        kv_mask = kv_mask.astype(jnp.int32)[:, None, :]
    scale = sm_scale if sm_scale is not None else D**-0.5
    # causal alignment: last query attends the last key (self-attn; also
    # right for decode where Sq < Sk). Traced per-device offsets (sequence
    # parallelism) cannot be a static kernel param — those paths use the
    # dense position-aware fallback in parallel/ring_attention.py.
    q_offset = Sk - Sq
    return _flash(q, k, v, kv_mask, scale, causal, block_q, block_k,
                  interpret, q_offset)
