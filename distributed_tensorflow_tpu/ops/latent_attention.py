"""Latent attention over a paged latent cache (multi-head latent attention,
DeepSeek-V2/V3; arXiv 2405.04434), in its absorbed form, for serving.

A cached token is ONE row shared by every head: ``[c_kv || k_r]``, the
normalised latent of ``kv_lora_rank`` lanes and the rotary key of
``qk_rope_head_dim`` lanes (576 at DeepSeek-V3's and GigaChat 3.5's widths),
stored in a pool ``[layers, num_blocks + 1, 1, block_size, W]`` (the last
block the write-off block, ``W`` the row rounded up to the 128 lanes on the
TPU) through the paged engine's block tables. A query head ``h`` becomes a
row of the same width, ``[q_nope_h W_uk_h^T || q_r_h]`` (the key
up-projection absorbed into the query), so a score is one dot product with
the cached row, and the values are the cached latents themselves (the first
``value_width`` lanes): the caller applies ``W_uv`` to the result.

Queries arrive as ``[B, R, W]`` rows, token-major: row ``r`` is token
``r // heads`` of its sequence and head ``r % heads``. Sequence ``b``'s
tokens sit at positions ``q0[b], q0[b] + 1, ...`` and its first ``nq[b]``
tokens are real (an idle slot has ``nq = 0``); a real row attends the
cached positions ``<= its own``, causally, and a row that is not real
returns zeros.

``impl="pallas"`` is the kernel ``paged_latent_attention`` (compiled on the
TPU, the interpreter elsewhere); ``"plain"`` gathers the table's blocks and
computes the same in ``jax.numpy``; ``"auto"`` is the kernel on the TPU and
the plain form elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, paged_append_kv
from .flash_attention import LANES, _paged_fetch_ids, paged_write_kv

#: cached positions a grid step takes at once, and query rows of a prefill
#: tile (a decode step's rows are its heads)
CHUNK_POSITIONS = 512
PREFILL_TILE_ROWS = 1024
VMEM_LIMIT = 48 * 1024 * 1024  # of the v5e's 128 MiB; the default is 16


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def stored_width(width: int) -> int:
    """The lanes a cached row is stored at: on the TPU the row rounded up to
    the 128 lanes (576 -> 640), so that a block ``[block_size, W]`` is one
    lane-aligned tile and XLA keeps the pool in the layout the kernels
    read; elsewhere as it is."""
    return -(-width // LANES) * LANES if _on_tpu() else width


def _kernel(nb_ref, ids_ref, ly_ref, q0_ref, nq_ref, q_ref, *refs, sm_scale,
            heads, block_size, chunk_blocks, value_width):
    """Grid (sequence, row tile, chunk of the sequence's blocks). The chunk's
    blocks are operands of their own, aimed by the prefetched ids; a chunk
    that starts past the tile's last real position computes nothing."""
    del nb_ref, ids_ref, ly_ref
    C = chunk_blocks
    kv_refs, (o_ref, acc_ref, m_ref, l_ref) = refs[:C], refs[C:]
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tr = q_ref.shape[1]
    T = C * block_size
    first_tok = i * tr // heads
    last_tok = jnp.minimum((i + 1) * tr // heads, nq_ref[b]) - 1

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((last_tok >= first_tok) & (j * T <= q0_ref[b] + last_tok))
    def _chunk():
        q = q_ref[0]                                             # [tr, W]
        kv = jnp.concatenate([r[0, 0, 0] for r in kv_refs], axis=0)  # [T, W]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale       # [tr, T]
        tok = (i * tr + jax.lax.broadcasted_iota(jnp.int32, (tr, 1), 0)
               ) // heads
        kpos = j * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        mask = (kpos <= q0_ref[b] + tok) & (tok < nq_ref[b])
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_ref[...][:, :1] * correction + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jnp.dot(
            p.astype(kv.dtype), kv[:, :value_width],
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "value_width", "sm_scale", "interpret"))
def _pallas(q, pool, block_table, q0, nq, layer, *, heads, value_width,
            sm_scale, interpret):
    B, R, W = q.shape
    NB, bs = pool.shape[1] - 1, pool.shape[3]
    MB = block_table.shape[1]
    C = max(1, min(CHUNK_POSITIONS // bs, MB))
    tr = min(R, PREFILL_TILE_ROWS)
    if R % tr or tr % heads:
        raise ValueError(f"{R} query rows do not split into tiles of {tr} "
                         f"rows of whole tokens of {heads} heads")
    n_blocks = jnp.where(nq > 0, (q0 + nq - 1) // bs + 1, 0).astype(jnp.int32)
    n_blocks = jnp.minimum(n_blocks, MB)
    ids = _paged_fetch_ids(block_table.astype(jnp.int32), n_blocks, NB, C)

    def kvspec(c):
        return pl.BlockSpec(
            (1, 1, 1, bs, W),
            lambda b, i, j, nb, ids, ly, q0, nq: (ly[0], ids[b, j * C + c],
                                                  0, 0, 0))

    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, heads=heads,
                          block_size=bs, chunk_blocks=C,
                          value_width=value_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, R // tr, pl.cdiv(MB, C)),
            in_specs=[pl.BlockSpec((1, tr, W), lambda b, i, j, *_: (b, i, 0)),
                      *[kvspec(c) for c in range(C)]],
            out_specs=pl.BlockSpec((1, tr, value_width),
                                   lambda b, i, j, *_: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((tr, value_width), jnp.float32),
                            pltpu.VMEM((tr, LANES), jnp.float32),
                            pltpu.VMEM((tr, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, R, value_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="paged_latent_attention",
    )(n_blocks, ids, jnp.asarray(layer, jnp.int32).reshape(1),
      q0.astype(jnp.int32), nq.astype(jnp.int32), q.astype(pool.dtype),
      *[pool] * C)


def _plain(q, pool, block_table, q0, nq, layer, *, heads, value_width,
           sm_scale):
    B, R, W = q.shape
    NB, bs = pool.shape[1] - 1, pool.shape[3]
    MB = block_table.shape[1]
    rows = pool[layer, jnp.clip(block_table, 0, NB), 0]     # [B, MB, bs, W]
    rows = rows.reshape(B, MB * bs, W)
    s = jnp.einsum("brw,bkw->brk", q.astype(pool.dtype), rows,
                   preferred_element_type=jnp.float32) * sm_scale
    tok = jnp.arange(R) // heads
    real = tok[None] < nq[:, None]                           # [B, R]
    mask = (jnp.arange(MB * bs)[None, None]
            <= (q0[:, None] + tok[None])[..., None]) & real[..., None]
    p = jax.nn.softmax(jnp.where(mask, s, NEG_INF), axis=-1)
    p = jnp.where(real[..., None], p, 0.0)
    return jnp.einsum("brk,bkv->brv", p.astype(pool.dtype),
                      rows[..., :value_width],
                      preferred_element_type=jnp.float32)


def latent_attention(q, pool, block_table, q0, nq, *, layer, heads,
                     value_width, sm_scale, impl="auto"):
    """``q`` [B, R, W] absorbed query rows (token-major, ``heads`` a token)
    against row ``layer`` of ``pool`` [layers, num_blocks + 1, 1,
    block_size, W] through ``block_table`` [B, max_blocks]; ``q0``, ``nq``
    [B] the first position and the count of real tokens of each sequence.
    Returns [B, R, value_width] float32: the softmax-weighted cached
    latents, zeros for rows that are not real."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "plain"
    if impl == "pallas":
        return _pallas(q, pool, block_table, q0, nq, layer, heads=heads,
                       value_width=value_width, sm_scale=sm_scale,
                       interpret=not _on_tpu())
    if impl != "plain":
        raise ValueError(f"latent attention impl must be 'auto', 'plain' or "
                         f"'pallas', got {impl!r}")
    return _plain(q, pool, block_table, q0, nq, layer, heads=heads,
                  value_width=value_width, sm_scale=sm_scale)


def write_rows(pool, rows, block_table, pos, *, layer, impl="auto"):
    """Write ``rows`` [B, S, w] (``w`` <= the pool's stored width; zeros
    fill the rest) into row ``layer`` of ``pool`` at positions ``pos``
    [B, S] through ``block_table``, in place: ``paged_write_kv``'s kernel
    on the TPU (one head of the pool's width), its scatter elsewhere; the
    padding contract is theirs (a sentinel position writes nothing)."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "plain"
    W = pool.shape[-1]
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, W - rows.shape[-1])))[:, None]
    if impl == "pallas":
        return paged_write_kv(pool, rows, block_table, pos, layer=layer,
                              interpret=not _on_tpu())
    return paged_append_kv(pool, rows, block_table, pos, layer=layer)
