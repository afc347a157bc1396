"""Prefill and single-token decode steps over the slotted KV cache.

The two jit units of the serving engine:

- **prefill** — run one request's prompt through the model with a
  single-slot view of the cache (gather the slot's [L,1,H,M,D] rows,
  apply, scatter back). Writes K/V for positions ``0..P-1`` and returns
  the next-token logits from the last REAL prompt position (prompts are
  padded to a bucket length so each bucket compiles once; padded rows
  produce garbage logits that are never read, and the garbage K/V they
  write above ``P`` stays masked until real tokens overwrite it).
- **decode_step** — one token for EVERY slot at once ([num_slots, 1]
  inputs at per-slot write positions). Idle slots decode garbage that is
  simply never delivered — uniform shapes keep ONE compiled program hot
  regardless of which subset of slots is live, which is the continuous-
  batching contract: admission/eviction never triggers a recompile.

**The serving protocol.** The paged functions below do not know a model
by name: they ask ``model.prefill_chunk(params, cache, table_row, tokens,
start, length, slot)`` and ``model.decode_step(params, cache,
block_tables, tokens, lengths)``, and the engine asks ``model.has_state``
(recurrent state beside the K/V pool: snapshots for prefix reuse, no
speculation) and, once for each weights it is given,
``model.serving_params(params)``: the tree every program then takes, with
whatever the model lays out once at load rather than in every run
(``Transformer``: its embedding tables padded to the lanes when the width
is not a multiple of them; the others hand their params back as they are).
``models.transformer.Transformer``, ``models.olmo_hybrid.OlmoHybrid`` and
``models.gigachat3_5.GigaChat35`` answer it; a model with state also
builds its own cache (``model.init_cache``).

Numerics: the cache path runs the same f32 masked softmax(QKᵀ)V as the
dense reference (ops.attention.cached_attention docstring), so cached
decode logits match the uncached full-context forward — asserted to
rtol 1e-4 and 64-step greedy equality in tests/test_serve.py.
"""

from __future__ import annotations

import dataclasses
from functools import partial, wraps

import jax
import jax.numpy as jnp
from jax import lax

from ..models.transformer import Transformer, serving_variables
from .kv_cache import HybridCache, KVCache, PagedKVCache


def prefill(
    model: Transformer,
    params,
    cache: KVCache,
    slot: jax.Array,
    tokens: jax.Array,
    length: jax.Array,
) -> tuple[jax.Array, KVCache]:
    """Prefill one slot. ``tokens`` [P] int32 (padded prompt), ``length``
    the real prompt length, ``slot`` the target cache row. Returns
    (next-token logits [vocab] f32, updated cache)."""
    P = tokens.shape[0]
    row = lambda buf: lax.dynamic_slice_in_dim(buf, slot, 1, axis=1)
    slot_cache = dataclasses.replace(cache, k=row(cache.k), v=row(cache.v))
    pos = jnp.arange(P, dtype=jnp.int32)[None]
    logits, slot_cache = model.apply(
        serving_variables(params), tokens[None], kv_cache=slot_cache,
        decode_pos=pos,
    )
    put = lambda buf, upd: lax.dynamic_update_slice_in_dim(
        buf, upd, slot, axis=1
    )
    new_cache = dataclasses.replace(
        cache, k=put(cache.k, slot_cache.k), v=put(cache.v, slot_cache.v)
    )
    return logits[0, length - 1], new_cache


def decode_step(
    model: Transformer,
    params,
    cache: KVCache,
    tokens: jax.Array,
    lengths: jax.Array,
) -> tuple[jax.Array, KVCache]:
    """One decode step for all slots. ``tokens`` [num_slots] — each
    slot's most recent token; ``lengths`` [num_slots] — each slot's
    write index (= tokens already in its cache). Returns (next-token
    logits [num_slots, vocab] f32, updated cache)."""
    logits, cache = model.apply(
        serving_variables(params), tokens[:, None], kv_cache=cache,
        decode_pos=lengths[:, None],
    )
    return logits[:, 0], cache


def _bound(fn, model: Transformer):
    """``partial(fn, model)`` under ``fn``'s own name: jax calls a jitted
    bare partial ``jit__unknown``, and the name is how an XLA dump or a
    profiler trace finds the step."""
    return wraps(fn)(partial(fn, model))


def jit_prefill(model: Transformer):
    """Compiled prefill; one compile per (prompt-bucket, cache shape).

    The cache argument is DONATED: XLA aliases it into the returned
    cache, so a step updates the resident buffers in place instead of
    paying a full cache copy (and 2× cache HBM) per call — same reason
    train/step.py donates the train state. Callers must rebind
    (``logits, cache = fn(params, cache, ...)``), never reuse the old
    pytree; the engine already does."""
    return jax.jit(_bound(prefill, model), donate_argnums=(1,))


def jit_decode_step(model: Transformer):
    """Compiled decode step; one compile per cache shape. The cache is
    donated (see jit_prefill)."""
    return jax.jit(_bound(decode_step, model), donate_argnums=(1,))


# ---------------------------------------------------------------------------
# Paged path (docs/serving.md "Paged KV cache"): the pool + block-table
# analogs of the two jit units above, plus the COW block copy. The dense
# functions above remain the exact-parity fallback.
# ---------------------------------------------------------------------------


def paged_prefill_chunk(
    model: Transformer,
    params,
    cache: PagedKVCache,
    table_row: jax.Array,
    tokens: jax.Array,
    start: jax.Array,
    length: jax.Array,
    slot: jax.Array | None = None,
) -> tuple[jax.Array, PagedKVCache]:
    """One fixed-size prefill chunk of ONE request: ``tokens`` [C] int32
    (chunk, zero-padded past ``length``) at absolute positions
    ``start .. start+length-1``, scattered through ``table_row``
    [max_blocks]. Padded rows get a past-the-table sentinel position so
    their K/V writes are dropped (ops.paged_append_kv). Returns the
    next-token logits at the chunk's last REAL position — only the
    final chunk's caller reads them — and the updated pool.

    Chunks are a fixed shape, unlike the dense path's per-bucket
    prefill programs — one compiled program per TABLE-width bucket
    covers every prompt length (the engine trims ``table_row`` to the
    power-of-two width covering the slot's live blocks, so short
    prompts attend far fewer positions than ``max_blocks``).

    A model with recurrent layers (``has_state``) also needs the request's
    ``slot``: the chunk advances that slot's recurrent state by ``length``
    tokens, over a ``HybridCache``. The model does the step
    (``model.prefill_chunk``): this function is the one name the engine
    compiles whatever the model."""
    return model.prefill_chunk(params, cache, table_row, tokens, start,
                               length, slot)


def paged_decode_step(
    model: Transformer,
    params,
    cache: PagedKVCache,
    block_tables: jax.Array,
    tokens: jax.Array,
    lengths: jax.Array,
) -> tuple[jax.Array, PagedKVCache]:
    """One decode step for all slots over the block pool. ``lengths``
    [num_slots] is each slot's write position; idle and mid-prefill
    slots carry a past-the-table sentinel instead, so their garbage
    token writes NOTHING (a mid-prefill slot's frontier may sit in a
    COW-shared block that a stray write must not touch); a model with
    recurrent layers keeps such a slot's state as it was."""
    return model.decode_step(params, cache, block_tables, tokens, lengths)


def paged_verify_step(
    model: Transformer,
    params,
    cache: PagedKVCache,
    block_tables: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
) -> tuple[jax.Array, PagedKVCache]:
    """Speculative verify: one chunked-prefill-shaped step over EVERY
    slot at once. ``tokens`` [num_slots, K+1] — each slot's newest
    sampled token followed by its K drafted tokens; ``positions``
    [num_slots, K+1] their absolute cache positions (row ``i`` of a
    slot's logits conditions, causally, on everything at or before
    ``positions[slot, i]`` — identical math to running K+1 sequential
    decode steps). Idle slots, mid-prefill slots, and unused draft rows
    carry the past-the-table sentinel so their K/V writes are dropped.

    Returns logits [num_slots, K+1, vocab] — the accept/reject rule
    (sampling.spec_verify_*) reads them on the host; rejected suffixes
    roll back via the block table (a refcount/length edit, not a
    device copy). One compiled program per K, shared by every prompt
    and every acceptance pattern."""
    logits, cache = model.apply(
        serving_variables(params), tokens, kv_cache=cache,
        decode_pos=positions, block_table=block_tables,
    )
    return logits, cache


def copy_block(
    cache: PagedKVCache, src: jax.Array, dst: jax.Array
) -> PagedKVCache:
    """Copy-on-write resolution: duplicate physical block ``src`` into
    ``dst`` across every layer and every pool of the cache (``POOLS``: K
    and V, or the latent rows), on device. The engine calls this (jit,
    donated) before the first divergent write into a block whose refcount
    is > 1."""
    return dataclasses.replace(cache, **{
        name: getattr(cache, name).at[:, dst].set(getattr(cache, name)[:, src])
        for name in cache.POOLS})


def take_snapshot(cache: HybridCache, slot: jax.Array,
                  row: jax.Array) -> HybridCache:
    """Copy ``slot``'s recurrent state and convolution window, every
    linear layer's, into snapshot row ``row``."""
    return dataclasses.replace(
        cache,
        snap_state=cache.snap_state.at[:, row].set(cache.state[:, slot]),
        snap_conv=cache.snap_conv.at[:, row].set(cache.conv[:, slot]))


def restore_snapshot(cache: HybridCache, slot: jax.Array,
                     row: jax.Array) -> HybridCache:
    """The inverse: a request admitted into ``slot`` behind a cached
    prefix starts from the state snapshot row ``row`` holds."""
    return dataclasses.replace(
        cache,
        state=cache.state.at[:, slot].set(cache.snap_state[:, row]),
        conv=cache.conv.at[:, slot].set(cache.snap_conv[:, row]))


def jit_take_snapshot():
    return jax.jit(take_snapshot, donate_argnums=(0,))


def jit_restore_snapshot():
    return jax.jit(restore_snapshot, donate_argnums=(0,))


def jit_paged_prefill_chunk(model: Transformer):
    """Compiled paged prefill chunk; the pool is donated (in-place
    scatter, no per-chunk pool copy — see jit_prefill)."""
    return jax.jit(_bound(paged_prefill_chunk, model),
                   donate_argnums=(1,))


def jit_paged_decode_step(model: Transformer):
    """Compiled paged decode step; the pool is donated."""
    return jax.jit(_bound(paged_decode_step, model), donate_argnums=(1,))


def jit_paged_verify_step(model: Transformer):
    """Compiled speculative verify step; the pool is donated. One
    compile per draft length K (tokens [num_slots, K+1])."""
    return jax.jit(_bound(paged_verify_step, model), donate_argnums=(1,))


def jit_copy_block():
    """Compiled COW block copy; the pool is donated."""
    return jax.jit(copy_block, donate_argnums=(0,))


def prefill_bucket(length: int, *, minimum: int = 8) -> int:
    """Pad a prompt length to the next power of two (≥ ``minimum``): a
    handful of compiled prefill programs cover every prompt length, the
    classic bucketing trade against XLA's static shapes."""
    b = minimum
    while b < length:
        b *= 2
    return b
