"""Sequence/context parallelism: ring attention, Ulysses, all-gather KV.

SURVEY.md §5.7 — first-class new-framework capability (the reference
predates transformers; nothing to port). Three interchangeable schedules
for attention over a sequence sharded on the ``seq`` mesh axis, all
expressed as ``shard_map`` islands whose collectives XLA lowers onto ICI
(the torus makes the ring's neighbor-exchange native — SURVEY.md §2d):

- **ring** (`ring attention`): K/V shards rotate around the ring via
  ``jax.lax.ppermute`` while each device folds every visiting shard into
  its queries' online-softmax state (the same recurrence as
  ops/attention.blockwise_attention, carried across devices instead of
  blocks). Activation memory O(S_local²) per step under remat; K/V
  residency O(S_global/N). Backward differentiates through the scan —
  ppermute's AD transpose is the reverse-direction ppermute, so the
  gradient ring falls out of autodiff.
- **ulysses** (attention-head all-to-all): ``all_to_all`` re-shards
  seq→heads, runs the dense per-head attention locally (the Pallas flash
  kernel on TPU), then re-shards heads→seq. Cheaper than the ring when
  heads ≥ seq-shards; requires H % seq_shards == 0.
- **allgather**: all-gather K/V over the seq axis, compute the local query
  chunk against the full K/V. Simplest; K/V residency O(S_global) —
  the right choice when S_global·D fits HBM comfortably.

Selection is by config string (SURVEY.md §5.7 "offer both, selected by
config"); `sequence_parallel_attention` is the dispatcher the transformer
models call.

Global-position bookkeeping: each device owns the contiguous query chunk
``[idx·S_local, (idx+1)·S_local)``; causal masks and padding masks are
evaluated in global coordinates on every device, so the sharded result
matches the unsharded oracle exactly (tests/test_ring_attention.py).
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import NEG_INF, attention_reference
from ..ops.flash_attention import flash_attention
from . import mesh as mesh_lib

Impl = Literal["ring", "ulysses", "allgather"]


def _inner_attention(q, k, v, *, causal, kv_mask, q_offset, kv_offset):
    """Dense attention on local tiles with GLOBAL-coordinate masking.

    q [B,H,Sq,D] starting at global position q_offset; k/v [B,H,Sk,D]
    starting at kv_offset; kv_mask [B,Sk] or None. Returns (out_unnorm,
    m, l): the un-normalized accumulator and row stats, so callers can
    merge partial results across ring steps / shards."""
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * (q.shape[-1] ** -0.5)
    mask = jnp.ones(logits.shape, bool)
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, None, :]
    if causal:
        qpos = q_offset + jnp.arange(q.shape[2])[:, None]
        kpos = kv_offset + jnp.arange(k.shape[2])[None, :]
        mask = mask & (kpos <= qpos)[None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    m = logits.max(-1)  # [B,H,Sq]
    p = jnp.where(mask, jnp.exp(logits - m[..., None]), 0.0)
    l = p.sum(-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out, m, l


def _ring_body(q, k, v, kv_mask, *, axis, causal, n_shards, s_local):
    """Per-device ring schedule (runs inside shard_map)."""
    idx = jax.lax.axis_index(axis)
    q_offset = idx * s_local
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    if kv_mask is None:
        kv_mask = jnp.ones((q.shape[0], k.shape[2]), bool)

    @jax.checkpoint
    def fold(carry_acc, carry_m, carry_l, k_t, v_t, mask_t, src_idx):
        out, m, l = _inner_attention(
            q, k_t, v_t, causal=causal, kv_mask=mask_t,
            q_offset=q_offset, kv_offset=src_idx * s_local,
        )
        m_new = jnp.maximum(carry_m, m)
        c_old = jnp.exp(carry_m - m_new)
        c_cur = jnp.exp(m - m_new)
        acc = carry_acc * c_old[..., None] + out * c_cur[..., None]
        l_new = carry_l * c_old + l * c_cur
        return acc, m_new, l_new

    def maybe_fold(acc, m, l, k_t, v_t, mask_t, src_idx):
        if not causal:
            return fold(acc, m, l, k_t, v_t, mask_t, src_idx)
        # A strictly-future shard (src_idx > idx) is fully masked by the
        # global causal mask — skip its O(S_local²) attention entirely
        # (≈halves causal ring FLOPs; the ppermute still runs, keeping the
        # ring schedule uniform across devices).
        return jax.lax.cond(
            src_idx > idx,
            lambda a, mm, ll, *_: (a, mm, ll),
            fold,
            acc, m, l, k_t, v_t, mask_t, src_idx,
        )

    def step(carry, t):
        acc, m, l, k_t, v_t, mask_t = carry
        src_idx = (idx - t) % n_shards  # whose shard is visiting now
        acc, m, l = maybe_fold(acc, m, l, k_t, v_t, mask_t, src_idx)
        k_t = jax.lax.ppermute(k_t, axis, perm)
        v_t = jax.lax.ppermute(v_t, axis, perm)
        mask_t = jax.lax.ppermute(mask_t, axis, perm)
        return (acc, m, l, k_t, v_t, mask_t), None

    B, H, Sq, D = q.shape
    acc0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    # scan the first n-1 (fold + rotate) steps; fold the last visiting
    # shard outside the loop — a rotation after the final fold would still
    # go out on the wire (scan bodies are identical every iteration, XLA
    # cannot dead-code it), costing 1/N of total ring traffic
    (acc, m, l, k_last, v_last, mask_last), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v, kv_mask), jnp.arange(n_shards - 1)
    )
    acc, m, l = maybe_fold(
        acc, m, l, k_last, v_last, mask_last, (idx + 1) % n_shards
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _ulysses_body(q, k, v, kv_mask, *, axis, causal, n_shards, s_local,
                  use_flash):
    """seq→heads all_to_all, dense local attention, heads→seq back."""

    def seq_to_heads(x):  # [B, H, S_loc, D] -> [B, H/N, S_glob, D]
        return jax.lax.all_to_all(
            x, axis, split_axis=1, concat_axis=2, tiled=True
        )

    def heads_to_seq(x):  # [B, H/N, S_glob, D] -> [B, H, S_loc, D]
        return jax.lax.all_to_all(
            x, axis, split_axis=2, concat_axis=1, tiled=True
        )

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if kv_mask is not None:
        # mask is sharded [B, S_loc] like kv; gather the full row
        maskg = jax.lax.all_gather(kv_mask, axis, axis=1, tiled=True)
    else:
        maskg = None
    if use_flash:
        og = flash_attention(qg, kg, vg, causal=causal, kv_mask=maskg)
    else:
        og = attention_reference(qg, kg, vg, causal=causal, kv_mask=maskg)
    return heads_to_seq(og)


def _allgather_body(q, k, v, kv_mask, *, axis, causal, n_shards, s_local,
                    use_flash):
    """All-gather K/V; local queries attend to the full sequence."""
    idx = jax.lax.axis_index(axis)
    kg = jax.lax.all_gather(k, axis, axis=2, tiled=True)
    vg = jax.lax.all_gather(v, axis, axis=2, tiled=True)
    maskg = (
        jax.lax.all_gather(kv_mask, axis, axis=1, tiled=True)
        if kv_mask is not None else None
    )
    if use_flash and not causal:
        out = flash_attention(q, kg, vg, kv_mask=maskg)
    else:
        # causal path stays dense even under use_flash: the flash kernel's
        # causal alignment is the static offset Sk - Sq, but here each
        # device's q chunk sits at a *traced* mid-sequence offset
        # (axis_index), which a Mosaic-compiled kernel cannot take.
        out, m, l = _inner_attention(
            q, kg, vg, causal=causal, kv_mask=maskg,
            q_offset=idx * s_local, kv_offset=0,
        )
        out = (out / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return out


def sequence_parallel_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    impl: Impl = "ring",
    causal: bool = False,
    kv_mask: jax.Array | None = None,
    use_flash: bool | None = None,
) -> jax.Array:
    """Attention over a sequence sharded on the ``seq`` mesh axis.

    Takes GLOBAL arrays (q/k/v [B, H, S, D], kv_mask [B, S]) inside or
    outside jit; shard_map shards them: batch over (data, fsdp), heads
    over model, seq over seq. Returns the global [B, H, S, D] result,
    numerically equal to the unsharded oracle.

    With seq axis size 1 this degenerates to one dense local attention
    (the shard_map is a no-op ring of length 1), so models can call it
    unconditionally."""
    n_shards = mesh.shape[mesh_lib.SEQ]
    B, H, S, D = q.shape
    if S % n_shards:
        raise ValueError(f"seq len {S} not divisible by seq axis {n_shards}")
    model_shards = mesh.shape[mesh_lib.MODEL]
    if H % model_shards:
        raise ValueError(
            f"heads ({H}) not divisible by model axis ({model_shards})"
        )
    if impl == "ulysses" and (H // model_shards) % n_shards:
        # heads are already sharded over the model axis by qkv_spec; the
        # all_to_all further splits the LOCAL head count by seq shards
        raise ValueError(
            f"ulysses needs local heads ({H}//{model_shards}) divisible by "
            f"seq shards ({n_shards})"
        )
    s_local = S // n_shards
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"

    qkv_spec = P((mesh_lib.DATA, mesh_lib.FSDP), mesh_lib.MODEL,
                 mesh_lib.SEQ, None)
    mask_spec = P((mesh_lib.DATA, mesh_lib.FSDP), mesh_lib.SEQ)

    body = {
        "ring": functools.partial(
            _ring_body, axis=mesh_lib.SEQ, causal=causal,
            n_shards=n_shards, s_local=s_local,
        ),
        "ulysses": functools.partial(
            _ulysses_body, axis=mesh_lib.SEQ, causal=causal,
            n_shards=n_shards, s_local=s_local, use_flash=use_flash,
        ),
        "allgather": functools.partial(
            _allgather_body, axis=mesh_lib.SEQ, causal=causal,
            n_shards=n_shards, s_local=s_local, use_flash=use_flash,
        ),
    }[impl]

    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec,
                  mask_spec if kv_mask is not None else None),
        out_specs=qkv_spec,
        check_vma=False,  # masks/iota are device-invariant; skip the check
    )
    return sharded(q, k, v, kv_mask)
