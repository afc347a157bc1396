"""Attention: O(S²) reference and O(block) blockwise (online-softmax) forms.

Layout convention for this module: ``[batch, heads, seq, head_dim]``
(blocking over ``seq`` puts the two innermost dims — seq-block × head_dim —
onto the TPU's (sublane × lane) tiles; models transpose once at the
attention boundary).

``attention_reference`` is the numerics oracle. ``blockwise_attention`` is
the memory-efficient pure-JAX form (FlashAttention recurrence as a
``lax.scan`` over KV blocks) — it is the inner loop of ring attention
(parallel/ring_attention.py), the CPU fallback for the Pallas kernel
(ops/flash_attention.py), and fully differentiable by autodiff.

The reference framework has no analog — its attention-era models predate it
(SURVEY.md §5.7 "Reference: entirely absent"); this is new-framework
capability required first-class by the task spec.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free


def _scale(q, sm_scale):
    return sm_scale if sm_scale is not None else q.shape[-1] ** -0.5


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    kv_mask: jax.Array | None = None,
    sm_scale: float | None = None,
) -> jax.Array:
    """Plain softmax(QKᵀ)V in f32. Shapes: q [B,H,Sq,D], k/v [B,H,Sk,D],
    kv_mask [B,Sk] bool (True = attend). Returns [B,H,Sq,D] in q.dtype."""
    Sq, Sk = q.shape[2], k.shape[2]
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * _scale(q, sm_scale)
    if kv_mask is not None:
        logits = jnp.where(kv_mask[:, None, None, :], logits, NEG_INF)
    if causal:
        qi = jnp.arange(Sq)[:, None] + (Sk - Sq)  # supports Sq<Sk (decode)
        ki = jnp.arange(Sk)[None, :]
        logits = jnp.where((ki <= qi)[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", probs.astype(v.dtype), v
    ).astype(q.dtype)


def append_kv(
    buf: jax.Array, new: jax.Array, start: jax.Array
) -> jax.Array:
    """Write ``new`` [B,H,S,D] into the KV ring buffer ``buf`` [B,H,M,D]
    at per-sequence offsets ``start`` [B] (the continuous-batching write
    index: each slot in the decode batch is at a different position).
    The written positions are ``start[b] .. start[b]+S-1``; callers
    guarantee ``start[b]+S <= M`` (the scheduler's max-len eviction)."""
    return jax.vmap(
        lambda cb, nb, s: jax.lax.dynamic_update_slice_in_dim(
            cb, nb.astype(cb.dtype), s, axis=1
        )
    )(buf, new, start)


def paged_append_kv(
    pool: jax.Array,
    new: jax.Array,
    block_table: jax.Array,
    pos: jax.Array,
    *,
    layer: jax.Array | int | None = None,
) -> jax.Array:
    """Scatter ``new`` [B,H,S,D] into the shared block pool
    [num_blocks, H, block_size, D] through a per-sequence block table
    [B, max_blocks] (logical block index → physical block id). Token
    ``(b, s)`` at absolute position ``p = pos[b, s]`` lands in physical
    block ``block_table[b, p // block_size]`` at offset
    ``p % block_size``. With ``layer`` the pool is that of several layers,
    [layers, num_blocks, H, block_size, D], and the scatter goes to row
    ``layer`` of it, the other rows untouched.

    Out-of-range routing is the padding contract: a position past the
    table (``p // block_size >= max_blocks`` — the chunk-padding
    sentinel) or a table entry ``>= num_blocks`` (the idle-slot /
    unallocated sentinel) produces an out-of-bounds scatter index, and
    the scatter drops it — padded rows and idle slots write NOTHING,
    instead of corrupting a live block. (A pool with a write-off block,
    serve.kv_cache, has one physical block more than its tables count:
    their sentinel entry names that block, which belongs to no request.)"""
    NB, H, bs, D = pool.shape[-4:]
    B, _, S, _ = new.shape
    MB = block_table.shape[1]
    blk = pos // bs                                   # [B,S] logical block
    off = pos % bs
    bids = jnp.where(
        blk < MB,
        jnp.take_along_axis(block_table, jnp.clip(blk, 0, MB - 1), axis=1),
        NB,  # past-the-table positions route out of bounds -> dropped
    )
    flat_new = new.transpose(0, 2, 1, 3).reshape(B * S, H, D)
    where = (bids.reshape(-1), slice(None), off.reshape(-1), slice(None))
    if layer is not None:
        where = (layer,) + where
    return pool.at[where].set(flat_new.astype(pool.dtype), mode="drop")


def paged_gather_kv(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """Reassemble the contiguous logical K (or V) view from the block
    pool: ``[num_blocks, H, block_size, D]`` gathered through
    ``block_table`` [B, max_blocks] → ``[B, H, max_blocks*block_size,
    D]``, where logical position ``p`` of sequence ``b`` is
    ``pool[block_table[b, p // bs], :, p % bs]``. Sentinel entries
    (``>= num_blocks``, the unallocated tail) clamp to the last block
    and read stale garbage — exactly the positions above the write
    frontier that ``cached_attention``'s ``j <= q_pos`` mask excludes,
    so no zeroing and no validity bitmap are needed."""
    NB, H, bs, D = pool.shape
    B, MB = block_table.shape
    g = jnp.take(pool, jnp.clip(block_table, 0, NB - 1).reshape(-1), axis=0)
    return (
        g.reshape(B, MB, H, bs, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, H, MB * bs, D)
    )


def cached_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_pos: jax.Array,
    sm_scale: float | None = None,
) -> jax.Array:
    """Masked full attention over a KV cache — the decode/prefill form.

    ``q`` [B,H,S,D] are the current step's queries at ABSOLUTE positions
    ``q_pos`` [B,S] (prefill: 0..P-1; decode: the per-sequence write
    index, S=1); ``k``/``v`` [B,H,M,D] are the full cache buffers. Key
    slot ``j`` participates iff ``j <= q_pos`` — causality and
    valid-length masking in one predicate, because the cache is filled
    contiguously from 0, so every slot at or below the newest written
    position holds a real token and everything above is stale garbage.

    This is the fallback the flash kernel can't cover: Pallas flash
    attention wants Sq a block multiple and a monotone causal frontier,
    while decode is Sq=1 against M cached keys with per-sequence offsets.
    Dense f32 softmax(QKᵀ)V matches ``attention_reference`` numerics, so
    cached decode is bit-comparable to the uncached forward."""
    M = k.shape[2]
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * _scale(q, sm_scale)
    mask = jnp.arange(M)[None, None, :] <= q_pos[:, :, None]  # [B,S,M]
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", probs.astype(v.dtype), v
    ).astype(q.dtype)


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    *,
    q_pos: jax.Array,
    sm_scale: float | None = None,
    impl: str = "auto",
) -> jax.Array:
    """Attention over the paged KV pool, selected by ``impl`` — the serve
    decode hot path's dispatch point (docs/serving.md "Fused paged
    attention").

    ``q`` [B,H,S,D] at absolute positions ``q_pos`` [B,S];
    ``k_pool``/``v_pool`` [num_blocks, H, block_size, D];
    ``block_table`` [B, max_blocks]. Key position ``j`` participates iff
    ``j <= q_pos`` — the same single-predicate masking as
    ``cached_attention`` (sentinel table entries clamp onto garbage the
    mask excludes, so no zeroing, no validity bitmap).

    - ``"gather"`` — the PR-13 path, ``paged_gather_kv`` then
      ``cached_attention``: materializes the [B,H,MB*bs,D] logical view
      TWICE per layer per step (k and v, each a pool gather plus a
      transpose copy). Exact-parity escape hatch.
    - ``"fused"`` — one pool gather per buffer, consumed in BLOCK layout
      [B,MB,H,bs,D] by the attention einsums directly: the transpose +
      reshape copies of the gather path never materialize. Pure jittable
      XLA; any backend.
    - ``"pallas"`` — the block-table-aware Pallas kernel
      (ops/flash_attention.paged_flash_attention): block ids are
      scalar-prefetched and a grid step copies a chunk of one slot's own
      blocks, every head of each, from the pool in place — the logical
      view never exists in HBM at all. Rows at the past-the-table
      sentinel (idle slots, padding) return zeros, not the garbage the
      XLA paths compute. Compiled on TPU, interpreter elsewhere (tests
      only).
    - ``"auto"`` — ``"pallas"`` on TPU, ``"fused"`` elsewhere.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "fused"
    if impl == "gather":
        return cached_attention(
            q,
            paged_gather_kv(k_pool, block_table),
            paged_gather_kv(v_pool, block_table),
            q_pos=q_pos,
            sm_scale=sm_scale,
        )
    if impl == "pallas":
        from .flash_attention import paged_flash_attention

        return paged_flash_attention(
            q, k_pool, v_pool, block_table, q_pos=q_pos, sm_scale=sm_scale
        )
    if impl != "fused":
        raise ValueError(
            f"paged attention impl must be 'auto', 'gather', 'fused' or "
            f"'pallas', got {impl!r}"
        )
    NB, H, bs, D = k_pool.shape
    B, MB = block_table.shape
    S = q.shape[2]
    ids = jnp.clip(block_table, 0, NB - 1)
    kg = jnp.take(k_pool, ids.reshape(-1), axis=0).reshape(B, MB, H, bs, D)
    vg = jnp.take(v_pool, ids.reshape(-1), axis=0).reshape(B, MB, H, bs, D)
    logits = jnp.einsum(
        "bhsd,bmhkd->bhsmk", q, kg, preferred_element_type=jnp.float32
    ) * _scale(q, sm_scale)
    kpos = jnp.arange(MB * bs).reshape(MB, bs)
    mask = kpos[None, None, None] <= q_pos[:, None, :, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(
        logits.reshape(B, H, S, MB * bs), axis=-1
    ).reshape(logits.shape)
    out = jnp.einsum("bhsmk,bmhkd->bhsd", probs.astype(vg.dtype), vg)
    return out.astype(q.dtype)


def paged_layer_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    pos: jax.Array,
    *,
    layer: jax.Array | int,
    sm_scale: float | None = None,
    impl: str = "auto",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One attention layer of a decoder served from the paged cache: the
    incoming tokens' ``k``/``v`` [B,H,S,D] at absolute positions ``pos``
    [B,S] are written into row ``layer`` of the stacked pools
    [layers, num_blocks + 1, H, block_size, D] through ``block_table``
    (sentinel positions and entries write nothing a request owns), then
    ``q`` attends over that row. Returns (out [B,H,S,D], k_pool, v_pool):
    the caller carries the two pools whole through its layers, and never
    slices a layer out of them or stacks them again. The pools' heads may
    be stored wider than ``D``.

    ``impl`` as in ``paged_attention``. ``"pallas"`` (``"auto"`` on the
    TPU) writes in place with ``paged_write_kv`` and reads in place with
    ``paged_flash_attention(layer=)``; the XLA forms scatter into the
    stacked pool and attend over ``pool[layer]``."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "fused"
    D, stored = q.shape[-1], k_pool.shape[-1]
    sm_scale = _scale(q, sm_scale)
    if stored != D:
        # heads stored wider than the model's (serve.kv_cache.
        # stored_head_dim): zeros in the padding move no score and no
        # output, and the padding of the output is dropped
        q, k, v = (jnp.pad(t, ((0, 0),) * 3 + ((0, stored - D),))
                   for t in (q, k, v))
    if impl == "pallas":
        from .flash_attention import paged_flash_attention, paged_write_kv

        k_pool = paged_write_kv(k_pool, k, block_table, pos, layer=layer)
        v_pool = paged_write_kv(v_pool, v, block_table, pos, layer=layer)
        out = paged_flash_attention(q, k_pool, v_pool, block_table,
                                    q_pos=pos, sm_scale=sm_scale, layer=layer)
    else:
        k_pool = paged_append_kv(k_pool, k, block_table, pos, layer=layer)
        v_pool = paged_append_kv(v_pool, v, block_table, pos, layer=layer)
        out = paged_attention(q, k_pool[layer], v_pool[layer], block_table,
                              q_pos=pos, sm_scale=sm_scale, impl=impl)
    return out[..., :D], k_pool, v_pool


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    kv_mask: jax.Array | None = None,
    sm_scale: float | None = None,
    block_k: int = 512,
) -> jax.Array:
    """Online-softmax attention scanned over KV blocks — O(Sq·block_k)
    activation memory instead of O(Sq·Sk).

    The recurrence (running max m, running denominator l, rescaled
    accumulator acc) is the same one the Pallas kernel implements on-chip
    and ring attention runs across chips; here it is a ``lax.scan`` that XLA
    compiles directly, so it runs on any backend and differentiates via
    autodiff (each block is rematerialized in the backward pass by the scan).
    """
    B, H, Sq, D = q.shape
    orig_sk = k.shape[2]
    scale = _scale(q, sm_scale)
    block_k = min(block_k, orig_sk)
    if orig_sk % block_k != 0:
        # pad keys to a block multiple; padded positions are masked out
        pad = block_k - orig_sk % block_k
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        base = jnp.arange(orig_sk + pad) < orig_sk
        kv_mask = (
            jnp.pad(kv_mask, ((0, 0), (0, pad))) & base[None]
            if kv_mask is not None
            else jnp.broadcast_to(base[None], (B, orig_sk + pad))
        )
    Sk = k.shape[2]
    n_blocks = Sk // block_k

    kb = jnp.moveaxis(k.reshape(B, H, n_blocks, block_k, D), 2, 0)
    vb = jnp.moveaxis(v.reshape(B, H, n_blocks, block_k, D), 2, 0)
    mb = (
        jnp.moveaxis(kv_mask.reshape(B, n_blocks, block_k), 1, 0)
        if kv_mask is not None
        else jnp.ones((n_blocks, 1, block_k), bool)
    )

    q32 = q.astype(jnp.float32)
    # causal offset aligns the last query with the last ORIGINAL key
    qpos = jnp.arange(Sq)[:, None] + (orig_sk - Sq)

    def body(carry, xs):
        acc, m, l = carry
        k_j, v_j, mask_j, j = xs
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk", q32, k_j.astype(jnp.float32)
        ) * scale  # [B,H,Sq,block_k]
        mask = jnp.broadcast_to(mask_j[:, None, None, :], logits.shape)
        if causal:
            kpos = j * block_k + jnp.arange(block_k)[None, :]
            mask = mask & jnp.broadcast_to(
                (kpos <= qpos)[None, None], logits.shape
            )
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1))
        # explicit zero under the mask: for fully-masked rows m stays
        # NEG_INF and exp(NEG_INF - NEG_INF) would be 1, poisoning l
        p = jnp.where(mask, jnp.exp(logits - m_new[..., None]), 0.0)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + p.sum(-1)
        acc_new = acc * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_j.astype(jnp.float32)
        )
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (kb, vb, mb, jnp.arange(n_blocks))
    )

    # l == 0 only when every key is masked for that query; emit zeros.
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)
