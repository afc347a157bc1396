"""The gated delta rule (Gated DeltaNet, arXiv 2412.06464): the recurrent
mixer of a linear-attention layer, for serving.

Per head, with the state kept transposed, ``M = S^T`` of shape [dk, dv]::

    M_t = a_t (I - b_t k_t k_t^T) M_{t-1} + b_t k_t v_t^T,   o_t = M_t^T q_t

``a_t = exp(g_t)`` in (0, 1] is the gate's decay, ``b_t`` in [0, 2] the
write strength; ``k_t`` has unit length. A token with ``g_t = 0`` and
``b_t = 0`` leaves the state exactly as it was, which is how padding (a
prefill chunk's positions past ``length``) and slots that do not decode
this step are kept out of it.

Two entry points, each with a plain ``jax.numpy`` form (``impl="plain"``:
what the CPU runs) and a Pallas kernel (``impl="pallas"``; interpreted off
the TPU, for the tests), and each working IN PLACE on the state of every
linear layer, ``[layers, slots, H, dk, dv]`` float32: the kernels alias it
to their output and touch only the rows of the layer and slot(s) at hand,
so a scan over layers carries one buffer and never copies it.

- ``gated_delta_chunk``: ``T`` tokens of one slot from its carried state to
  the new one (kernel ``gated_delta_chunk_fwd``). Chunked form: within a
  sub-chunk of ``C`` tokens the updates are written ``M_t = G_t M_0 +
  sum_i (G_t / G_i) k_i w_i^T`` (``G`` the running product of the decays),
  which gives the pseudo-values ``w`` as the solution of a unit lower
  triangular system ``(I + diag(b) A) W = diag(b) (V - diag(G) K M_0)``,
  ``A[t, i] = (G_t / G_i) k_t.k_i`` for ``i < t`` (the WY / UT transform of
  the paper's section 3). The system is solved by forward substitution,
  all heads of a grid step at once: a Neumann series of the inverse is
  cheaper on the MXU and loses every digit once keys repeat and ``b``
  nears 2.
- ``gated_delta_step``: one token for every slot (kernel
  ``gated_delta_step``): three passes over a [dk, dv] tile on the VPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HI = jax.lax.Precision.HIGHEST

#: tokens of a sub-chunk: the triangular system's size. The substitution's
#: work grows with C a token, the state's update shrinks with it.
SUB_CHUNK = 64


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> tuple[str, bool]:
    """(impl, interpret): the kernels on the TPU, the plain forms elsewhere;
    an explicit ``"pallas"`` off the TPU runs the interpreter."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "plain"
    if impl not in ("plain", "pallas"):
        raise ValueError(f"gated-delta impl must be 'auto', 'plain' or "
                         f"'pallas', got {impl!r}")
    return impl, not _on_tpu()


def _heads_per_step(H: int, dk: int, dv: int, budget: int = 1 << 19) -> int:
    """Most heads of one grid step whose [dk, dv] float32 state tiles
    (lanes padded to 128) stay under ``budget`` bytes: the step holds the
    state's input and output blocks and the float32 q, k, v, o of a
    sub-chunk, double-buffered, and works on copies, inside 16 MB of VMEM."""
    tile = dk * (-(-dv // 128) * 128) * 4
    return max(h for h in range(1, H + 1) if H % h == 0
               and h * tile <= max(budget, tile))


# ---------------------------------------------------------------------------
# the recurrence itself: the oracle of the tests, and the plain decode step
# ---------------------------------------------------------------------------


def recurrence(q, k, v, g, beta, state):
    """Token by token. q, k [T, H, dk]; v [T, H, dv]; g, beta [T, H];
    state [H, dk, dv]. Returns (o [T, H, dv] float32, new state)."""
    def step(M, x):
        q_t, k_t, v_t, g_t, b_t = x
        M = jnp.exp(g_t)[:, None, None] * M
        err = v_t - jnp.einsum("hkv,hk->hv", M, k_t, precision=HI)
        M = M + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * err,
                           precision=HI)
        return M, jnp.einsum("hkv,hk->hv", M, q_t, precision=HI)

    f32 = lambda x: x.astype(jnp.float32)
    state, o = jax.lax.scan(step, f32(state),
                            tuple(map(f32, (q, k, v, g, beta))))
    return o, state


# ---------------------------------------------------------------------------
# a sub-chunk in the chunked form: shared by the plain form and the kernel
# ---------------------------------------------------------------------------


def _bmm(a, b, contract):
    """Batched over the leading (head) axis, float32 at full precision."""
    return jax.lax.dot_general(
        a, b, ((contract, ((0,), (0,)))), precision=HI,
        preferred_element_type=jnp.float32)


def _sub_chunk(q, k, v, gc, gr, bc, M, solve):
    """One sub-chunk of C tokens for a group of heads. q, k [h, C, dk];
    v [h, C, dv]; gc [h, C, 1] and gr [h, 1, C] the running sum of ``g``
    inside the sub-chunk, as a column and as a row; bc [h, C, 1]; M
    [h, dk, dv]. ``solve(L, R)`` returns W with (I + L) W = R for strictly
    lower triangular L. Returns (o [h, C, dv], new M)."""
    C = q.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # G_t / G_i for i <= t, nought above the diagonal (never exp of a
    # positive number: a long-forgotten token would overflow)
    decay = jnp.exp(jnp.where(row >= col, gc - gr, -jnp.inf))
    kk = _bmm(k, k, ((2,), (2,)))
    qk = _bmm(q, k, ((2,), (2,)))
    L = jnp.where(row > col, decay * kk, 0.0) * bc
    eg = jnp.exp(gc)
    W = solve(L, bc * (v - eg * _bmm(k, M, ((2,), (1,)))))
    o = eg * _bmm(q, M, ((2,), (1,))) + _bmm(decay * qk, W, ((2,), (1,)))
    # the running sum at the sub-chunk's end is its least value (g <= 0);
    # as a reduction it comes lane- and sublane-aligned, and is broadcast
    # along the lanes first: Mosaic does not broadcast both ways at once
    g_end = jnp.min(gc, axis=1, keepdims=True)
    keep = jnp.broadcast_to(jnp.exp(g_end), (M.shape[0], 1, M.shape[2]))
    M = keep * M + _bmm(k * jnp.exp(g_end - gc), W, ((1,), (1,)))
    return o, M


def _solve_plain(L, R):
    eye = jnp.eye(L.shape[-1], dtype=L.dtype)
    return jax.scipy.linalg.solve_triangular(
        eye + L, R, lower=True, unit_diagonal=True)


def _masked(g, beta, valid):
    """Positions that are not ``valid`` neither decay nor write."""
    valid = valid[:, None]
    return (jnp.where(valid, g.astype(jnp.float32), 0.0),
            jnp.where(valid, beta.astype(jnp.float32), 0.0))


def _sub_chunked(x, C):
    """[T, H, w] -> [T // C, H, C, w]."""
    T, H, w = x.shape
    return x.reshape(T // C, C, H, w).transpose(0, 2, 1, 3)


def _chunk_plain(q, k, v, g, beta, M, C):
    f32 = lambda x: x.astype(jnp.float32)
    T = q.shape[0]
    gcum = jnp.cumsum(g.reshape(T // C, C, -1), axis=1)      # [n, C, H]
    gc = gcum.transpose(0, 2, 1)[..., None]                  # [n, H, C, 1]
    gr = gcum.transpose(0, 2, 1)[:, :, None, :]              # [n, H, 1, C]
    bc = _sub_chunked(beta[..., None], C)

    def body(M, x):
        o, M = _sub_chunk(*x, M, _solve_plain)
        return M, o

    M, o = jax.lax.scan(body, f32(M), (
        _sub_chunked(f32(q), C), _sub_chunked(f32(k), C),
        _sub_chunked(f32(v), C), gc, gr, bc))
    return o.transpose(0, 2, 1, 3).reshape(T, o.shape[1], o.shape[3]), M


# ---------------------------------------------------------------------------
# kernel: a prefill chunk of one slot
# ---------------------------------------------------------------------------


def _chunk_kernel(ids_ref, q_ref, k_ref, v_ref, gc_ref, gr_ref, bc_ref,
                  m_in_ref, o_ref, m_out_ref, r_ref):
    """Grid (head groups, sub-chunks); the state block stays resident over
    the sub-chunks of a head group and is written back after the last."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _load():
        # ids = (layer, slot, fresh): a fresh request starts from nought,
        # whatever the slot's last tenant left behind
        keep = (ids_ref[2] == 0).astype(jnp.float32)
        m_out_ref[...] = m_in_ref[...] * keep

    def solve(L, R):
        # right-looking forward substitution, all heads at once: row t of R
        # is final once the rows before it have been taken out; rows are
        # updated from the sublane tile that holds t + 1 on
        C = L.shape[1]
        r_ref[...] = R
        for t in range(C - 1):
            lo = (t + 1) // 8 * 8
            w_t = r_ref[:, t:t + 1, :]
            r_ref[:, lo:, :] = (r_ref[:, lo:, :]
                                - L[:, lo:, t:t + 1] * w_t)
        return r_ref[...]

    f32 = lambda ref: ref[...].astype(jnp.float32)
    o, M = _sub_chunk(f32(q_ref), f32(k_ref), f32(v_ref), gc_ref[...],
                      gr_ref[:, 0], bc_ref[...], m_out_ref[0, 0], solve)
    o_ref[...] = o.astype(o_ref.dtype)
    m_out_ref[0, 0] = M


def _chunk_pallas(q, k, v, g, beta, state, layer, slot, fresh, C, interpret):
    T, H, dk = q.shape
    dv = v.shape[2]
    hb = _heads_per_step(H, dk, dv)
    n = T // C
    heads_first = lambda x: x.transpose(1, 0, 2)             # [H, T, w]
    gcum = jnp.cumsum(g.reshape(n, C, H), axis=1).reshape(T, H)
    ids = jnp.stack([jnp.asarray(layer, jnp.int32),
                     jnp.asarray(slot, jnp.int32),
                     jnp.asarray(fresh, jnp.int32)])
    tok = lambda w: pl.BlockSpec((hb, C, w), lambda h, c, ids: (h, c, 0))
    m_spec = pl.BlockSpec((1, 1, hb, dk, dv),
                          lambda h, c, ids: (ids[0], ids[1], h, 0, 0))
    o, state = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // hb, n),
            in_specs=[tok(dk), tok(dk), tok(dv), tok(1),
                      pl.BlockSpec((hb, 1, 1, C),
                                   lambda h, c, ids: (h, c, 0, 0)),
                      tok(1), m_spec],
            out_specs=[tok(dv), m_spec],
            scratch_shapes=[pltpu.VMEM((hb, C, dv), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((H, T, dv), v.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalar-prefetch argument: the state is the 8th
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_chunk_fwd",
    )(ids, heads_first(q), heads_first(k), heads_first(v),
      gcum.T[:, :, None], gcum.T.reshape(H, n, 1, C), beta.T[:, :, None],
      state)
    return o.transpose(1, 0, 2), state


def gated_delta_chunk(q, k, v, g, beta, state, *, layer, slot, length,
                      fresh, impl: str = "auto", sub_chunk: int = SUB_CHUNK):
    """``T`` tokens of the request in ``slot`` through linear layer
    ``layer``. q, k [T, H, dk] (normalised, q scaled); v [T, H, dv]; g,
    beta [T, H]; ``state`` [layers, slots, H, dk, dv] float32 (every
    layer's, every slot's: only row ``[layer, slot]`` is read and written).
    Positions from ``length`` on are padding and leave the state as it
    was; ``fresh`` (the request's first chunk) starts from a zero state.
    Returns (o [T, H, dv] in v's dtype, the state)."""
    impl, interpret = _resolve(impl)
    T = q.shape[0]
    C = min(sub_chunk, T)
    if T % C or (impl == "pallas" and C % 8):
        raise ValueError(f"a chunk of {T} tokens does not split into "
                         f"sub-chunks of {C} (a multiple of 8)")
    g, beta = _masked(g, beta, jnp.arange(T) < length)
    if impl == "pallas":
        return _chunk_pallas(q, k, v, g, beta, state, layer, slot, fresh, C,
                             interpret)
    M = jnp.where(fresh, 0.0, state[layer, slot])
    o, M = _chunk_plain(q, k, v, g, beta, M, C)
    return o.astype(v.dtype), state.at[layer, slot].set(M)


# ---------------------------------------------------------------------------
# kernel: one token for every slot
# ---------------------------------------------------------------------------


def _column(x):
    """[h, 1, n] (n on the lanes) -> [h, n, 1] (n on the sublanes) without
    a relayout: the diagonal of the row broadcast down n sublanes."""
    n = x.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, x, 0.0), axis=-1, keepdims=True)


def _step_math(q, k, v, a, b, M):
    """q, k [h, dk, 1]; v [h, 1, dv]; a, b [h, 1, 1]; M [h, dk, dv]."""
    M = a * M
    err = v - jnp.sum(M * k, axis=1, keepdims=True)
    M = M + k * (b * err)
    return jnp.sum(M * q, axis=1, keepdims=True), M


def _step_kernel(layer_ref, q_ref, k_ref, v_ref, g_ref, b_ref, m_in_ref,
                 o_ref, m_out_ref):
    del layer_ref
    f32 = lambda ref: ref[0].astype(jnp.float32)
    o, M = _step_math(_column(f32(q_ref)), _column(f32(k_ref)), f32(v_ref),
                      jnp.exp(g_ref[0]), b_ref[0], m_in_ref[0, 0])
    o_ref[0] = o.astype(o_ref.dtype)
    m_out_ref[0, 0] = M


def _step_pallas(q, k, v, g, beta, state, layer, interpret):
    B, H, dk = q.shape
    dv = v.shape[2]
    hb = _heads_per_step(H, dk, dv)
    row = lambda w: pl.BlockSpec((1, hb, 1, w), lambda b, h, ly: (b, h, 0, 0))
    m_spec = pl.BlockSpec((1, 1, hb, dk, dv),
                          lambda b, h, ly: (ly[0], b, h, 0, 0))
    o, state = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hb),
            in_specs=[row(dk), row(dk), row(dv), row(1), row(1), m_spec],
            out_specs=[row(dv), m_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, dv), v.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="gated_delta_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), q[:, :, None], k[:, :, None],
      v[:, :, None], g[:, :, None, None], beta[:, :, None, None], state)
    return o[:, :, 0], state


def gated_delta_step(q, k, v, g, beta, state, *, layer, live,
                     impl: str = "auto"):
    """One token for every slot through linear layer ``layer``. q, k
    [slots, H, dk]; v [slots, H, dv]; g, beta [slots, H]; ``state``
    [layers, slots, H, dk, dv] float32, of which row ``layer`` is read and
    written. A slot that is not ``live`` (idle, or in the middle of its
    prefill) keeps its state. Returns (o [slots, H, dv], the state)."""
    impl, interpret = _resolve(impl)
    g, beta = _masked(g, beta, live)
    if impl == "pallas":
        return _step_pallas(q, k, v, g, beta, state, layer, interpret)
    f32 = lambda x: x.astype(jnp.float32)
    B, H = g.shape
    flat = lambda x: f32(x).reshape(B * H, *x.shape[2:])
    o, M = _step_math(flat(q)[:, :, None], flat(k)[:, :, None],
                      flat(v)[:, None, :], jnp.exp(flat(g))[:, None, None],
                      flat(beta)[:, None, None],
                      state[layer].reshape(B * H, *state.shape[3:]))
    return (o.reshape(B, H, -1).astype(v.dtype),
            state.at[layer].set(M.reshape(state.shape[1:])))
