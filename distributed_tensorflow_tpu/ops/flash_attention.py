"""Pallas-TPU FlashAttention-2 kernel (forward + backward, custom_vjp).

The hot op of the transformer family (models/transformer.py) and the
per-chip inner block of ring attention (parallel/ring_attention.py,
SURVEY.md §5.7). This is the framework's "native kernel" tier: where the
reference framework dropped to hand-written CUDA for its hot ops
(SURVEY.md §2b native rows), the TPU-native equivalent is a Pallas kernel
compiled to Mosaic (SURVEY.md §5.8 native-code policy).

Design (FlashAttention-2, with the sweep inside the kernel):

- Layout [B, H, S, D]. A grid step owns ``hb`` heads of one row block and
  sweeps the other axis itself, in a rolled ``fori_loop`` over chunks of
  operands that stay in VMEM: the forward and dQ own a ``block_q`` block of
  queries and loop over ``block_k`` chunks of the heads' K/V; dKV owns a
  ``block_k`` block of keys and loops over ``block_q`` chunks of the heads'
  q/dO. The block index of the resident operands does not depend on the
  owned block, so Pallas fetches them once per head group. Where a whole
  sequence does not fit the VMEM budget (ring attention's long per-device
  blocks) the largest span that does becomes a last grid axis, inside
  which the same loop runs. ``ops/_tiling.flash_tile_plan`` picks all of
  it from the shape. A one-tile-a-step grid was bound by the pipeline's
  per-step cost, and one head's tile a chunk by the latency of its chain
  of matmuls, which the rolled loop does not overlap: the ``hb`` heads of
  a chunk are one batched matmul, independent work for the scheduler
  (PERF.md §6 "PR 25").
- Causal: the loop's trip count stops at the diagonal, so blocks above it
  cost neither a step nor a DMA; only the chunks the diagonal crosses
  build the triangular mask (a second loop over the same body).
- The forward and dKV work on the transposed tile sᵀ = k·qᵀ [bk, bq]. In
  the forward the running max ``m`` and denominator ``l`` are then
  [1, bq] rows reduced over sublanes (as [bq, 1] columns each costs bq/8
  vregs and their update as much as the tile's own arithmetic at
  block_k = 256), and the accumulator is oᵀ, transposed once per block;
  in dKV dV = pᵀ·dO and dK = dsᵀ·q are plain matmuls. All accumulators are
  f32 VMEM scratch.
- The forward also emits LSE = m + log l at sublane width
  ([B,H,Sq,STAT_DIM], STAT_DIM=8 — lane-broadcasting the row stat 128-wide
  would cost 16x HBM for long sequences). The backward is two more pallas
  calls, the FlashAttention-2 split that keeps every accumulator local to
  one grid cell (no cross-instance atomics, which TPU does not have);
  delta = rowsum(dO·O) is one plain reduction before them.
- ``kv_mask`` [B, Sk] covers padding (BERT-style); mask semantics match
  ops/attention.py (True = attend). A call without one compiles kernels
  with no mask select.
- On non-TPU backends ``interpret=True`` runs the same kernels through the
  Pallas interpreter — this is how CI (8 fake CPU devices, SURVEY.md §4.2)
  tests the exact kernel code path without TPU hardware.

Matmul operands go to the MXU in the input dtype (bf16 q/k/v/dO as
loaded, ``p`` and ``ds`` cast to it) with f32 accumulation; softmax, LSE,
delta and every accumulator are f32 (online-softmax numerics, SURVEY.md §7
"hard parts" #3). f32 inputs keep f32 operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._tiling import flash_tile_plan, pad_to_sublane, paged_attn_plan
from .attention import NEG_INF

LANES = 128  # TPU lane width
STAT_DIM = 8  # f32 sublane width (HBM row-stat storage)


def _dot_nt(a, b):
    """a · bᵀ per head: [hb, m, d] x [hb, n, d] → [hb, m, n], f32."""
    return jax.lax.dot_general(
        a, b, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _dot_nn(a, b):
    """a · b per head: [hb, m, n] x [hb, n, d] → [hb, m, d], f32."""
    return jax.lax.dot_general(
        a, b, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """aᵀ · b per head: [hb, n, d] x [hb, n, m] → [hb, d, m], f32."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _loop(lo, hi, body):
    """Rolled loop for its side effects on refs."""
    jax.lax.fori_loop(lo, hi, lambda i, c: (body(i), c)[1], 0)


def _kv_chunk_range(q_lo, block_q, kv_base, block_k, n_chunks):
    """For query positions [q_lo, q_lo + block_q) over ``n_chunks`` key
    chunks starting at position ``kv_base``: (chunks wholly at or below the
    diagonal, chunks with any key a query may attend). The chunks between
    the two need the triangular mask; those beyond the second, nothing."""
    q_hi = q_lo + block_q - 1
    need = jnp.minimum(
        jnp.maximum(q_hi - kv_base + block_k, 0) // block_k, n_chunks)
    full = jnp.minimum(jnp.maximum(q_lo - kv_base + 1, 0) // block_k, need)
    return full, need


def _q_chunk_range(k_lo, block_k, q_base, block_q, n_chunks):
    """For key positions [k_lo, k_lo + block_k) over ``n_chunks`` query
    chunks starting at position ``q_base``: (first chunk with a query that
    may attend, first chunk wholly at or below the diagonal)."""
    k_hi = k_lo + block_k - 1
    first = jnp.minimum(jnp.maximum(k_lo - q_base, 0) // block_q, n_chunks)
    full = jnp.clip(
        (jnp.maximum(k_hi - q_base, 0) + block_q - 1) // block_q,
        first, n_chunks)
    return first, full


def _tile_mask(kv_mask, causal_at, shape, q_dim):
    """AND of the padding mask (already broadcastable to the [hb, rows,
    cols] ``shape``, or None) and, where ``causal_at`` = (first query
    position, first key position) is given, key <= query along dims
    ``q_dim`` and the other of (1, 2); None when neither applies."""
    if causal_at is None:
        return kv_mask
    q0, k0 = causal_at
    shape = (1, *shape[1:])
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 3 - q_dim)
    tri = kpos <= qpos
    return tri if kv_mask is None else kv_mask & tri


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, mask_ref,
    o_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *, sm_scale, causal, has_mask, block_k, q_offset,
):
    """On the transposed tile sᵀ = k·qᵀ [hb, bk, bq]: m and l are [1, bq]
    rows reduced over sublanes, the accumulator is oᵀ [D, bq] (module
    docstring)."""
    block_q = q_ref.shape[2]
    kv_span = k_ref.shape[2]
    n_chunks = kv_span // block_k
    qi, kj, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    q_lo = qi * block_q + q_offset
    kv_base = kj * kv_span
    if causal:
        full, need = _kv_chunk_range(q_lo, block_q, kv_base, block_k,
                                     n_chunks)
    else:
        full = need = n_chunks

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # [hb, bq, D]

    def chunk(c, *, diagonal):
        start = pl.multiple_of(c * block_k, block_k)
        k = k_ref[0, :, pl.ds(start, block_k), :]  # [hb, bk, D]
        v = v_ref[0, :, pl.ds(start, block_k), :]
        logits = _dot_nt(k, q) * sm_scale  # [hb, bk, bq]
        mask = _tile_mask(
            mask_ref[:, pl.ds(start, block_k), :][:, :, :1] != 0
            if has_mask else None,
            (q_lo, kv_base + start) if diagonal else None, logits.shape, 2)
        if mask is not None:
            logits = jnp.where(mask, logits, NEG_INF)
        m_prev = m_ref[...]  # [hb, 1, bq]
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)  # [hb, bk, bq]
        if mask is not None:
            # explicit zero under the mask: for fully-masked rows m stays
            # NEG_INF and exp(NEG_INF - NEG_INF) would be 1, poisoning l
            p = jnp.where(mask, p, 0.0)
        correction = jnp.exp(m_prev - m_new)  # [hb, 1, bq]
        l_ref[...] = l_ref[...] * correction + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + _dot_tn(
            v, p.astype(v.dtype))  # [hb, D, bq]
        m_ref[...] = m_new

    _loop(0, full, functools.partial(chunk, diagonal=False))
    if causal:
        _loop(full, need, functools.partial(chunk, diagonal=True))

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[...]  # [hb, 1, bq]
        # all-masked rows (l==0) → zero output, lse = NEG_INF
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0] = jnp.swapaxes(acc_ref[...] / safe_l, 1, 2).astype(
            o_ref.dtype)
        lse = jnp.where(l > 0.0, m_ref[...] + jnp.log(safe_l), NEG_INF)
        lse_ref[0] = jnp.swapaxes(
            jnp.broadcast_to(lse, (lse.shape[0], STAT_DIM, block_q)), 1, 2
        ).astype(lse_ref.dtype)


def _kv_span_index(causal, plan, q_offset):
    """(q block i, kv span j) → the kv span to fetch. A causal q block
    needs no span past the one its last query sits in: later ones map onto
    that one, so the pipeline sees an unchanged block and starts no DMA
    (the kernel's loop runs no chunk there)."""
    if not causal:
        return lambda i, j: j

    def index(i, j):
        q_hi = (i + 1) * plan.block_q - 1 + q_offset
        return jnp.minimum(j, jnp.maximum(q_hi // plan.kv_span, 0))
    return index


def _chunked_mask(kv_mask, plan):
    """[B, 1, Sk] → [B, spans, chunks, block_k]: a chunk's mask is one row
    of a block whose last two dims are whole (a dynamic sublane index; a
    dynamic lane offset into [1, Sk] is not something Mosaic takes)."""
    B = kv_mask.shape[0]
    return kv_mask.reshape(
        B, -1, plan.kv_span // plan.block_k, plan.block_k)


def _column_mask(kv_mask):
    """[B, 1, Sk] → [B, Sk, STAT_DIM]: keys along sublanes, for the kernels
    whose tile is k·qᵀ."""
    B, _, Sk = kv_mask.shape
    return jnp.broadcast_to(kv_mask[:, 0, :, None], (B, Sk, STAT_DIM))


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "causal", "has_mask", "q_offset", "plan", "interpret"))
def _fwd_call(q, k, v, kv_mask, *, sm_scale, causal, has_mask, q_offset,
              plan, interpret):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk, hb, span = plan.block_q, plan.block_k, plan.hb, plan.kv_span
    kv_j = _kv_span_index(causal, plan, q_offset)
    qspec = lambda b, h, i, j: (b, h, i, 0)  # noqa: E731
    kspec = lambda b, h, i, j: (b, h, kv_j(i, j), 0)  # noqa: E731

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal,
            has_mask=has_mask, block_k=bk, q_offset=q_offset),
        grid=(B, H // hb, Sq // bq, Sk // span),
        in_specs=[
            pl.BlockSpec((1, hb, bq, D), qspec),
            pl.BlockSpec((1, hb, span, D), kspec),
            pl.BlockSpec((1, hb, span, D), kspec),
            pl.BlockSpec((1, span, STAT_DIM),
                         lambda b, h, i, j: (b, kv_j(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, bq, D), qspec),
            pl.BlockSpec((1, hb, bq, STAT_DIM), qspec),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, STAT_DIM), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, D, bq), jnp.float32),
            pltpu.VMEM((hb, 1, bq), jnp.float32),
            pltpu.VMEM((hb, 1, bq), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v, _column_mask(kv_mask))
    return out, lse


# ---------------------------------------------------------------------------
# Backward: dKV kernel (kv block owned, q chunks swept) and
#           dQ kernel (q block owned, kv chunks swept)
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, sm_scale, causal, has_mask, block_q, q_offset,
):
    """On the transposed tile sᵀ = k·qᵀ [hb, bk, bq]: dV = pᵀ·dO and
    dK = dsᵀ·q are then plain matmuls, and lse/delta are [1, bq] rows."""
    block_k = k_ref.shape[2]
    q_span = q_ref.shape[2]
    n_chunks = q_span // block_q
    kj, qj, nq = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    k_lo = kj * block_k
    q_base = qj * q_span + q_offset
    if causal:
        first, full = _q_chunk_range(k_lo, block_k, q_base, block_q,
                                     n_chunks)
    else:
        first = full = 0

    @pl.when(qj == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k = k_ref[0]  # [hb, bk, D]
    v = v_ref[0]
    kv_mask = mask_ref[...][:, :, :1] != 0 if has_mask else None  # [1, bk, 1]

    def chunk(c, *, diagonal):
        start = pl.multiple_of(c * block_q, block_q)
        q = q_ref[0, :, pl.ds(start, block_q), :]  # [hb, bq, D]
        do = do_ref[0, :, pl.ds(start, block_q), :]
        lse = lse_ref[0, :, 0, pl.ds(c, 1), :]  # [hb, 1, bq]
        delta = delta_ref[0, :, 0, pl.ds(c, 1), :]
        logits = _dot_nt(k, q) * sm_scale  # [hb, bk, bq]
        p = jnp.exp(logits - lse)
        mask = _tile_mask(
            kv_mask, (q_base + start, k_lo) if diagonal else None,
            logits.shape, 2)
        if mask is not None:
            # all-masked rows have lse = NEG_INF: force their p to 0
            p = jnp.where(mask, p, 0.0)
        dp = _dot_nt(v, do)  # [hb, bk, bq]
        ds = p * (dp - delta)
        dv_acc[...] += _dot_nn(p.astype(do.dtype), do)  # [hb, bk, D]
        dk_acc[...] += _dot_nn(ds.astype(q.dtype), q)

    if causal:
        _loop(first, full, functools.partial(chunk, diagonal=True))
    _loop(full, n_chunks, functools.partial(chunk, diagonal=False))

    @pl.when(qj == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_acc,
    *, sm_scale, causal, has_mask, block_k, q_offset,
):
    block_q = q_ref.shape[2]
    kv_span = k_ref.shape[2]
    n_chunks = kv_span // block_k
    qi, kj, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    q_lo = qi * block_q + q_offset
    kv_base = kj * kv_span
    if causal:
        full, need = _kv_chunk_range(q_lo, block_q, kv_base, block_k,
                                     n_chunks)
    else:
        full = need = n_chunks

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0]  # [hb, bq, D]
    do = do_ref[0]
    lse = lse_ref[0][:, :, :1]  # [hb, bq, 1]
    delta = delta_ref[0][:, :, :1]

    def chunk(c, *, diagonal):
        start = pl.multiple_of(c * block_k, block_k)
        k = k_ref[0, :, pl.ds(start, block_k), :]  # [hb, bk, D]
        v = v_ref[0, :, pl.ds(start, block_k), :]
        logits = _dot_nt(q, k) * sm_scale  # [hb, bq, bk]
        p = jnp.exp(logits - lse)
        mask = _tile_mask(
            mask_ref[0, :, pl.ds(c, 1), :] != 0 if has_mask else None,
            (q_lo, kv_base + start) if diagonal else None, logits.shape, 1)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = _dot_nt(do, v)  # [hb, bq, bk]
        ds = p * (dp - delta)
        dq_acc[...] += _dot_nn(ds.astype(k.dtype), k)  # [hb, bq, D]

    _loop(0, full, functools.partial(chunk, diagonal=False))
    if causal:
        _loop(full, need, functools.partial(chunk, diagonal=True))

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, kv_mask, sm_scale, causal, has_mask, plan, interpret,
           q_offset):
    out, _ = _fwd_call(
        q, k, v, kv_mask,
        sm_scale=sm_scale, causal=causal, has_mask=has_mask,
        q_offset=q_offset, plan=plan, interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, kv_mask, sm_scale, causal, has_mask, plan,
               interpret, q_offset):
    out, lse = _fwd_call(
        q, k, v, kv_mask,
        sm_scale=sm_scale, causal=causal, has_mask=has_mask,
        q_offset=q_offset, plan=plan, interpret=interpret,
    )
    return out, (q, k, v, kv_mask, out, lse)


def _flash_bwd(sm_scale, causal, has_mask, plan, interpret, q_offset,
               res, do):
    q, k, v, kv_mask, out, lse = res
    dq, dk, dv = _bwd_call(
        q, k, v, kv_mask, out, lse, do,
        sm_scale=sm_scale, causal=causal, has_mask=has_mask,
        q_offset=q_offset, plan=plan, interpret=interpret,
    )
    return dq, dk, dv, np.zeros(kv_mask.shape, jax.dtypes.float0)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "causal", "has_mask", "q_offset", "plan", "interpret"))
def _bwd_call(q, k, v, kv_mask, out, lse, do, *, sm_scale, causal, has_mask,
              q_offset, plan, interpret):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk, hb = plan.block_q, plan.block_k, plan.hb
    common = dict(sm_scale=sm_scale, causal=causal, has_mask=has_mask,
                  q_offset=q_offset)
    # once per call, not once per tile in each kernel; `out` goes no further
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    # dKV: owns kv block j, sweeps the q chunks of span i from the diagonal
    # down; a causal kv block needs no q span before the one holding its
    # first key's own query, so earlier spans map onto that one (no DMA)
    span = plan.q_span
    if causal:
        q_i = lambda j, i: jnp.maximum(  # noqa: E731
            i, jnp.maximum(j * bk - q_offset, 0) // span)
    else:
        q_i = lambda j, i: i  # noqa: E731
    qspec = lambda b, h, j, i: (b, h, q_i(j, i), 0)  # noqa: E731
    kspec = lambda b, h, j, i: (b, h, j, 0)  # noqa: E731
    rowspec = pl.BlockSpec(
        (1, hb, 1, span // bq, bq),
        lambda b, h, j, i: (b, h, q_i(j, i), 0, 0))
    rows = lambda x: x.reshape(B, H, -1, span // bq, bq)  # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, **common),
        grid=(B, H // hb, Sk // bk, Sq // span),
        in_specs=[
            pl.BlockSpec((1, hb, span, D), qspec),
            pl.BlockSpec((1, hb, bk, D), kspec),
            pl.BlockSpec((1, hb, bk, D), kspec),
            pl.BlockSpec((1, bk, STAT_DIM), lambda b, h, j, i: (b, j, 0)),
            pl.BlockSpec((1, hb, span, D), qspec),
            rowspec,
            rowspec,
        ],
        out_specs=[
            pl.BlockSpec((1, hb, bk, D), kspec),
            pl.BlockSpec((1, hb, bk, D), kspec),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, bk, D), jnp.float32),
            pltpu.VMEM((hb, bk, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v,
      _column_mask(kv_mask), do, rows(lse[..., 0]), rows(delta))

    # dQ: the forward's sweep
    span = plan.kv_span
    kv_j = _kv_span_index(causal, plan, q_offset)
    qspec = lambda b, h, i, j: (b, h, i, 0)  # noqa: E731
    kspec = lambda b, h, i, j: (b, h, kv_j(i, j), 0)  # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=bk, **common),
        grid=(B, H // hb, Sq // bq, Sk // span),
        in_specs=[
            pl.BlockSpec((1, hb, bq, D), qspec),
            pl.BlockSpec((1, hb, span, D), kspec),
            pl.BlockSpec((1, hb, span, D), kspec),
            pl.BlockSpec((1, 1, span // bk, bk),
                         lambda b, h, i, j: (b, kv_j(i, j), 0, 0)),
            pl.BlockSpec((1, hb, bq, D), qspec),
            pl.BlockSpec((1, hb, bq, STAT_DIM), qspec),
            pl.BlockSpec((1, hb, bq, STAT_DIM), qspec),
        ],
        out_specs=pl.BlockSpec((1, hb, bq, D), qspec),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((hb, bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, _chunked_mask(kv_mask, plan), do, lse,
      jnp.broadcast_to(delta[..., None], lse.shape))

    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Paged decode kernel: attention straight off the block pool
# ---------------------------------------------------------------------------


def _paged_fwd_kernel(
    nb_ref,  # scalar-prefetched: the blocks each slot's valid rows can see
    *refs,   # fetch_ref, [layer_ref,] q, qpos, C x k, C x v, o, acc, m, l
    sm_scale, block_size, chunk_blocks,
):
    """One slot, ``hb`` heads, one chunk of ``chunk_blocks`` of the slot's
    blocks: each block of the chunk is an operand of its own, aimed by the
    scalar-prefetched ids (which only drive the index maps); a chunk past
    the slot's last block computes nothing."""
    C, bs = chunk_blocks, block_size
    *_, q_ref, qpos_ref = refs[:-2 * C - 4]
    k_refs, v_refs = refs[-2 * C - 4:-C - 4], refs[-C - 4:-4]
    o_ref, acc_ref, m_ref, l_ref = refs[-4:]
    b, j = pl.program_id(0), pl.program_id(2)
    S, T = q_ref.shape[2], C * bs

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * C < nb_ref[b])
    def _chunk():
        # a block's tile is [..., hb, bs, D] behind one or two unit dims
        tile = lambda ref: ref[(0,) * (ref.ndim - 3)]
        q = q_ref[0]  # [hb, S, D]
        k = jnp.concatenate([tile(r) for r in k_refs], axis=1)  # [hb, T, D]
        v = jnp.concatenate([tile(r) for r in v_refs], axis=1)
        logits = _dot_nt(q, k) * sm_scale  # [hb, S, T]
        kpos = j * T + jax.lax.broadcasted_iota(jnp.int32, (1, S, T), 2)
        # [S, 1] absolute query positions (-1 = attends nothing); the row
        # stat arrives lane-broadcast like m/l, so this is a lane slice,
        # not a sublane<->lane relayout
        mask = kpos <= qpos_ref[0][:, :1]
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev = m_ref[...][:, :, :1]  # [hb, S, 1]
        m_new = jnp.maximum(m_prev, logits.max(axis=2, keepdims=True))
        # explicit zero under the mask (see _fwd_kernel): fully-masked rows
        # keep m == NEG_INF and must not poison l with exp(0) == 1
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_new = (l_ref[...][:, :, :1] * correction
                 + p.sum(axis=2, keepdims=True))
        acc_ref[...] = acc_ref[...] * correction + _dot_nn(
            p.astype(v.dtype), v)  # [hb, S, D]
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        # rows that attend nothing (padding, an idle slot: l == 0) → zeros
        l = l_ref[...][:, :, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_fetch_ids(block_table, n_blocks, num_blocks, chunk_blocks):
    """[B, J * C] physical block ids for the paged kernel's K/V operands:
    operand ``i`` of grid step ``(b, j)`` is logical block ``j * C + i`` of
    slot ``b`` where that is one of the slot's own ``n_blocks[b]``, else
    the id the same operand held the step before (across slots too), so
    that the pipeline sees an unchanged block and copies nothing. An
    operand that has had no block yet takes the call's first live one:
    what it holds meets ``p == 0`` and has to be some request's numbers."""
    B, MB = block_table.shape
    C = chunk_blocks
    J = -(-MB // C)
    table = jnp.pad(block_table, ((0, 0), (0, J * C - MB)))
    live = jnp.arange(J * C)[None] < n_blocks[:, None]
    step = jnp.arange(B * J)[:, None]  # the grid's (b, j), flattened
    live = live.reshape(B * J, C)
    last = jax.lax.cummax(jnp.where(live, step, -1), axis=0)
    first = jnp.argmax(live.reshape(-1))  # 0 where nothing is live
    flat = jnp.clip(table, 0, num_blocks - 1).reshape(B * J, C)
    ids = jnp.where(last >= 0,
                    jnp.take_along_axis(flat, jnp.maximum(last, 0), axis=0),
                    flat.reshape(-1)[first])
    return ids.reshape(B, J * C)


def paged_flash_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    *,
    q_pos: jax.Array,
    sm_scale: float | None = None,
    interpret: bool | None = None,
    layer: jax.Array | None = None,
) -> jax.Array:
    """Block-table-aware attention for paged decode and prefill chunks: K/V
    blocks are read IN PLACE from the pool (``k_pool``/``v_pool``
    [num_blocks, H, block_size, D]); the contiguous logical view that
    ``paged_gather_kv`` materializes never exists.

    A grid step owns one slot (row of ``q`` [B, H, S, D]), ``hb`` heads
    (all ``H`` where VMEM allows) and one chunk of ``C`` of the slot's
    blocks (``ops/_tiling.paged_attn_plan``): grid ``(B, H // hb,
    ceil(max_blocks / C))``. It relies on the pool's layout keeping a
    block's heads contiguous: a block's ``[hb, block_size, D]`` is one
    copy, and the chunk's ``C`` K and ``C`` V blocks are operands of their
    own, each aimed by scalar-prefetched ids (``_paged_fetch_ids``), under
    one online-softmax update over ``[hb, S, C * block_size]`` scores.
    Each slot's TRIP COUNT is derived here from ``q_pos`` [B, S]: the
    blocks the slot's valid rows can see, ``ceil((max valid q_pos + 1) /
    block_size)``, where a row is valid if its position lies inside the
    table (the callers' past-the-table sentinel marks idle slots and a
    prefill chunk's padding). A grid step past a slot's count computes
    nothing and fetches nothing (its operands keep the ids they had, and
    the pipeline does not copy an unchanged block); the table is not read
    past the count. Masking inside the last block is the paged contract:
    key position ``j <= q_pos`` attends. A row that is not valid attends
    nothing and returns zeros; an idle slot's count is 0. Forward-only
    (decode never differentiates).

    Matmul operands are in the pool's dtype (bf16 K and V as stored, ``q``
    and ``p`` cast to it) with f32 accumulation; softmax, running max and
    sum, and the rescale are f32.

    ``interpret=None`` auto-selects: compiled on TPU, Pallas interpreter
    elsewhere (slow; tests pin numerics against the gather path). On TPU
    the query tile pads to the f32 sublane width (padded rows get
    ``q_pos = -1`` — attend nothing — and are sliced off).

    With ``layer`` (a scalar) the pools are those of several layers,
    ``[layers, num_blocks, H, block_size, D]``, and the kernel reads row
    ``layer`` in place: a scan over layers carries one pool and never
    slices a layer's copy out of it."""
    if interpret is None:
        interpret = not _on_tpu()
    _, H, S, D = q.shape
    Sp = S if interpret else pad_to_sublane(S)
    plan = paged_attn_plan(Sp, H, block_table.shape[1], k_pool.shape[-2], D,
                           k_pool.dtype.itemsize)
    return _paged_call(
        q, k_pool, v_pool, block_table, q_pos, layer, Sp=Sp,
        sm_scale=sm_scale if sm_scale is not None else D**-0.5,
        interpret=interpret, plan=plan)


@functools.partial(jax.jit, static_argnames=(
    "Sp", "sm_scale", "interpret", "plan"))
def _paged_call(q, k_pool, v_pool, block_table, q_pos, layer, *, Sp,
                sm_scale, interpret, plan):
    """A jitted function of its own: a model's layers make the same call,
    and trace and lower it once."""
    B, H, S, D = q.shape
    NB, _, bs, _ = k_pool.shape[-4:]
    MB = block_table.shape[1]
    hb, C = plan.hb, plan.chunk_blocks
    qp = q_pos.astype(jnp.int32)
    qp = jnp.where((qp >= 0) & (qp < MB * bs), qp, -1)  # -1 attends nothing
    n_blocks = (qp.max(axis=1) + bs) // bs
    out_dtype = q.dtype
    q = q.astype(k_pool.dtype)
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        qp = jnp.pad(qp, ((0, 0), (0, Sp - S)), constant_values=-1)

    # [B, Sp, LANES]: a (1, Sp) block of a [B, Sp] array would put 1 on
    # the sublane dim, neither a multiple of 8 nor the full extent
    qp = jnp.broadcast_to(qp[:, :, None], (B, Sp, LANES))
    # the scalar-prefetched operands: the trip counts, the ids to fetch and,
    # where the pools have a layer axis, the layer; every index_map takes
    # them after the grid ids
    scalars = (n_blocks, _paged_fetch_ids(
        block_table.astype(jnp.int32), n_blocks, NB, C))
    if layer is None:
        def kvspec(i):
            return pl.BlockSpec(
                (1, hb, bs, D),
                lambda b, g, j, nb, ids: (ids[b, j * C + i], g, 0, 0))
    else:
        scalars += (jnp.asarray(layer, jnp.int32).reshape(1),)

        def kvspec(i):
            return pl.BlockSpec(
                (1, 1, hb, bs, D),
                lambda b, g, j, nb, ids, ly: (ly[0], ids[b, j * C + i], g,
                                              0, 0))
    qspec = pl.BlockSpec((1, hb, Sp, D), lambda b, g, j, *_: (b, g, 0, 0))
    kvspecs = [kvspec(i) for i in range(C)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, H // hb, pl.cdiv(MB, C)),
        in_specs=[
            qspec,
            pl.BlockSpec((1, Sp, LANES), lambda b, g, j, *_: (b, 0, 0)),
            *kvspecs,
            *kvspecs,
        ],
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((hb, Sp, D), jnp.float32),
            pltpu.VMEM((hb, Sp, LANES), jnp.float32),
            pltpu.VMEM((hb, Sp, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_fwd_kernel, sm_scale=sm_scale, block_size=bs,
            chunk_blocks=C),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=plan.vmem_limit_bytes),
        interpret=interpret,
        name="paged_attention_fwd",
    )(*scalars, q, qp, *[k_pool] * C, *[v_pool] * C)
    return out[:, :, :S]


def _paged_write_kernel(layer_ref, bid_ref, lo_ref, hi_ref, new_ref,
                        pool_ref, out_ref):
    """One touched block of one sequence, every head: rows ``lo <= r < hi``
    of the block take the new K (or V), the others keep what they held."""
    del layer_ref, bid_ref
    b, j = pl.program_id(0), pl.program_id(1)
    old = pool_ref[0, 0]                                  # [H, bs, D]
    row = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1)
    new = jnp.broadcast_to(new_ref[0], old.shape)         # one row or bs rows
    out_ref[0, 0] = jnp.where((row >= lo_ref[b, j]) & (row < hi_ref[b, j]),
                              new, old)


def paged_write_kv(
    pool: jax.Array,
    new: jax.Array,
    block_table: jax.Array,
    pos: jax.Array,
    *,
    layer: jax.Array,
    interpret: bool | None = None,
) -> jax.Array:
    """Write ``new`` [B, H, S, D] into row ``layer`` of ``pool``
    [layers, num_blocks + 1, H, block_size, D], IN PLACE (the pool is
    aliased to the output), through ``block_table`` [B, max_blocks]: the
    token at absolute position ``pos[b, s]`` lands in physical block
    ``block_table[b, p // block_size]`` at offset ``p % block_size``.

    The padding contract is ``ops.attention.paged_append_kv``'s: a position
    past the table or a table entry ``>= num_blocks`` writes nothing.
    ``num_blocks`` is the pool's block count LESS ONE: the pool's last
    physical block is a write-off block that no table names. Every grid
    step reads a block and writes it back, so a step with nothing to write
    needs a block that no other step of the call writes: sent to a real
    block, its stale copy (fetched while an earlier step was still
    computing) would land on top of that step's new row.

    Each sequence's positions are CONSECUTIVE from ``pos[b, 0]``, which may
    lie anywhere inside a block, with the real positions first and the
    padding (past the table) last: one token a slot (decode), a prefill
    chunk from wherever the prefix cache's match ended, the K + 1 rows of a
    speculative verify. ``S`` such positions touch at most ``ceil(S /
    block_size) + 1`` blocks, which is the grid's steps a sequence (one
    where ``S`` is 1); ``new`` is moved down by ``pos[b, 0] % block_size``
    rows outside the kernel, so that a step's rows of it are its block's.
    Positions that do not run consecutively are not what this writes:
    refused where they are concrete, and where they are traced the caller's
    to keep (the rows land as if they ran on from ``pos[b, 0]``).

    A kernel rather than a scatter or ``dynamic_update_slice``: with those
    XLA keeps a pool that a scan carries in the layout the write likes and
    copies all of it back to the kernels' layout every layer."""
    if interpret is None:
        interpret = not _on_tpu()
    if not isinstance(pos, jax.core.Tracer):
        # concrete positions only (an eager call): never under a trace
        p = np.asarray(pos).astype(np.int64)  # dtflint: disable=host-sync-in-step
        real = p < block_table.shape[1] * pool.shape[-2]
        run = p[:, :1] + np.arange(p.shape[1])
        if (real[:, 1:] > real[:, :-1]).any() or (real & (p != run)).any():
            raise ValueError(
                "paged_write_kv writes consecutive positions, the real ones "
                f"first and the padding last; got {p.tolist()}")
    return _paged_write_call(pool, new, block_table, pos, layer,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_write_call(pool, new, block_table, pos, layer, *, interpret):
    """A jitted function of its own, the layer an argument: a model's
    layers make the same call, and trace and lower it once."""
    _, NB, H, bs, D = pool.shape
    NB -= 1  # the last physical block takes the writes that go nowhere
    B, _, S, _ = new.shape
    MB = block_table.shape[1]
    pos = pos.astype(jnp.int32)
    start = pos[:, :1]                                     # [B, 1]
    off = start % bs
    # real positions come first: their count is where the padding begins
    real = (pos < MB * bs).sum(axis=1, keepdims=True, dtype=jnp.int32)
    new = new.astype(pool.dtype)
    n, rows = 1, 1
    if S > 1:
        # row r of the sequence goes to row off + r of n whole blocks
        n, rows = pl.cdiv(S, bs) + 1, bs
        padded = jnp.pad(new, ((0, 0), (0, 0), (bs, n * bs - S), (0, 0)))
        new = jax.vmap(lambda x, o: jax.lax.dynamic_slice_in_dim(
            x, bs - o, n * bs, axis=1))(padded, off[:, 0])
    step = jnp.arange(n, dtype=jnp.int32)[None]            # [1, n]
    lb = start // bs + step                                # [B, n] logical
    lo = jnp.clip(off - step * bs, 0, bs)
    hi = jnp.clip(off + real - step * bs, 0, bs)
    bid = jnp.where(
        lb < MB,
        jnp.take_along_axis(block_table.astype(jnp.int32),
                            jnp.clip(lb, 0, MB - 1), axis=1), NB)
    # a step with nothing to write goes to the write-off block (where a
    # sentinel entry's rows go too: it is no one's)
    bid = jnp.where((bid < NB) & (hi > lo), bid, NB)
    spec = pl.BlockSpec(
        (1, 1, H, bs, D),
        lambda b, j, ly, bid, lo, hi: (ly[0], bid[b, j], 0, 0, 0))
    return pl.pallas_call(
        _paged_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, n),
            in_specs=[
                pl.BlockSpec((1, H, rows, D), lambda b, j, *_: (b, 0, j, 0)),
                spec,
            ],
            out_specs=spec,
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={5: 0},
        interpret=interpret,
        name="paged_kv_write",
    )(jnp.asarray(layer, jnp.int32).reshape(1), bid, lo, hi, new, pool)


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
    for name, block, S in (("block_q", block_q, Sq), ("block_k", block_k, Sk)):
        if block is not None and S % min(block, S):
            raise ValueError(
                f"seq lens ({Sq=}, {Sk=}) must be multiples of block sizes "
                f"({name}={block}); pad and pass kv_mask"
            )
    plan = flash_tile_plan(
        B, H, Sq, Sk, D, q.dtype.itemsize, causal,
        None if block_q is None else min(block_q, Sq),
        None if block_k is None else min(block_k, Sk),
    )
    block_q, block_k = plan.block_q, plan.block_k
    if interpret is None:
        interpret = not _on_tpu()
    if not interpret:
        # Mosaic lane/sublane layout constraints (the interpreter has none):
        # a kv chunk's mask row has block_k lanes, a q chunk is a sublane
        # slice of block_q rows. Sub-128 kv blocks would also waste the
        # 128×128 MXU.
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
    has_mask = kv_mask is not None
    if kv_mask is None:
        kv_mask = jnp.ones((B, 1, Sk), jnp.int32)
    else:
        # bool refs are awkward on TPU
        kv_mask = kv_mask.astype(jnp.int32)[:, None, :]
    scale = sm_scale if sm_scale is not None else D**-0.5
    # causal alignment: last query attends the last key (self-attn; also
    # right for decode where Sq < Sk). Traced per-device offsets (sequence
    # parallelism) cannot be a static kernel param — those paths use the
    # dense position-aware fallback in parallel/ring_attention.py.
    q_offset = Sk - Sq
    return _flash(q, k, v, kv_mask, scale, causal, has_mask, plan,
                  interpret, q_offset)
