"""Pallas-TPU fused 1x1-conv + BatchNorm kernels (ResNet hot path).

Why this exists (step profile in PERF.md "Earlier chip findings"):
ResNet-50 training on TPU is
HBM-bandwidth-bound, and ~2/3 of the step is BatchNorm-adjacent
elementwise/reduce passes over the widest activations — XLA cannot fuse
the BN statistics pass or the normalize pass into its conv custom-calls.
2/3 of ResNet-50's convs are 1x1 (= matmuls over [B*H*W, Cin]), so this
module fuses, into one Pallas matmul kernel:

- **prologue**: per-Cin affine ``x*scale + shift`` (+ ReLU) — i.e. the
  BatchNorm-apply of the *previous* BN — so the matmul reads the RAW
  previous conv output and the normalized tensor is never materialized;
- **epilogue**: per-Cout column ``sum``/``sumsq`` of the output — the
  statistics pass of the *next* BN — so the stats never re-read the
  output from HBM.

The backward is two more Pallas kernels over the same tiles (dx +
prologue-param reductions with the M-grid resident; dw with a
[Cin, bn]-tile accumulator), each recomputing the prologue from the raw
input in VMEM instead of re-reading a materialized normalized tensor.

Reference analog: the reference's BN ran as cuDNN
BatchNormalization{Forward,Backward}Training kernels fused with
activations (a GPU-library capability the TF substrate reached via
``fused_batch_norm``, $TF/python/ops/nn_impl.py:1631); this is the
TPU-native equivalent at the "native kernel" tier (SURVEY.md §5.8
native-code policy), shaped by the MXU/VMEM layout instead.

Numerics: inputs/outputs bf16 (or f32), all accumulation f32. The
epilogue computes stats on the *quantized* (output-dtype) values so they
match exactly what an unfused consumer would read back from HBM. On
non-TPU backends ``interpret=True`` runs the same kernels through the
Pallas interpreter (CI on fake CPU devices, SURVEY.md §4.2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from . import _tiling


def _pick_block_m(M: int, cin: int, cout: int) -> int:
    return _tiling.pick_block_m(M, cin, cout, name="fused conv1x1 kernel")


_on_tpu = _tiling.on_tpu


# ---------------------------------------------------------------------------
# Forward: y = (relu(x*scale+shift)) @ w  [+ column sum/sumsq of y]
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, w_ref, scale_ref, shift_ref, y_ref, sum_ref, ssq_ref,
                *, prologue, relu, emit_stats):
    x = x_ref[:].astype(jnp.float32)
    if prologue:
        x = x * scale_ref[:] + shift_ref[:]
        if relu:
            x = jnp.maximum(x, 0.0)
    h = x.astype(x_ref.dtype)
    y = jnp.dot(h, w_ref[:], preferred_element_type=jnp.float32)
    yq = y.astype(y_ref.dtype)
    y_ref[:] = yq
    if emit_stats:
        st = yq.astype(jnp.float32)

        @pl.when(pl.program_id(0) == 0)
        def _():
            sum_ref[:] = jnp.zeros_like(sum_ref)
            ssq_ref[:] = jnp.zeros_like(ssq_ref)

        sum_ref[:] += st.sum(0, keepdims=True)
        ssq_ref[:] += (st * st).sum(0, keepdims=True)


def _fwd_call(x, w, scale, shift, *, prologue, relu, emit_stats, out_dtype,
              interpret):
    M, cin = x.shape
    cout = w.shape[1]
    bm = _pick_block_m(M, cin, cout)
    kernel = functools.partial(
        _fwd_kernel, prologue=prologue, relu=relu, emit_stats=emit_stats,
    )
    y, s, ssq = pl.pallas_call(
        kernel,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, cin), lambda i: (i, 0)),
            pl.BlockSpec((cin, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, cout), lambda i: (i, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, cout), out_dtype),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
        ],
        interpret=interpret,
        name="conv1x1_bn_fwd",
    )(x, w, scale, shift)
    return y, s[0], ssq[0]


# ---------------------------------------------------------------------------
# Backward A: dx (+ dscale/dshift) with the M-grid streaming
# ---------------------------------------------------------------------------


def _bwd_dx_kernel(*refs, prologue, relu, emit_stats):
    if prologue:
        (x_ref, y_ref, dy_ref, w_ref, scale_ref, shift_ref,
         dsum_ref, dssq_ref, dx_ref, dscale_ref, dshift_ref) = refs
    else:
        # no prologue: x/scale/shift are neither read nor streamed
        (y_ref, dy_ref, w_ref, dsum_ref, dssq_ref, dx_ref) = refs
    g = dy_ref[:].astype(jnp.float32)
    if emit_stats:
        # stats outputs' cotangents fold back into the output gradient:
        # d/dy [sum_c, ssq_c] = [1, 2y]
        y = y_ref[:].astype(jnp.float32)
        g = g + dsum_ref[:] + 2.0 * y * dssq_ref[:]
    # dh = g @ w^T  (contract over cout)
    dh = jax.lax.dot_general(
        g.astype(dy_ref.dtype), w_ref[:],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if prologue:
        x = x_ref[:].astype(jnp.float32)
        xn = x * scale_ref[:] + shift_ref[:]
        if relu:
            live = (xn > 0.0).astype(jnp.float32)
            dh = dh * live
        dx_ref[:] = (dh * scale_ref[:]).astype(dx_ref.dtype)

        @pl.when(pl.program_id(0) == 0)
        def _():
            dscale_ref[:] = jnp.zeros_like(dscale_ref)
            dshift_ref[:] = jnp.zeros_like(dshift_ref)

        dscale_ref[:] += (dh * x).sum(0, keepdims=True)
        dshift_ref[:] += dh.sum(0, keepdims=True)
    else:
        dx_ref[:] = dh.astype(dx_ref.dtype)


def _bwd_dx_call(x, y, dy, w, scale, shift, dsum, dssq, *, prologue, relu,
                 emit_stats, interpret):
    M, cin = x.shape
    cout = w.shape[1]
    bm = _pick_block_m(M, cin, cout)
    kernel = functools.partial(
        _bwd_dx_kernel, prologue=prologue, relu=relu, emit_stats=emit_stats,
    )
    row = lambda bq, cq: pl.BlockSpec((bq, cq), lambda i: (i, 0))
    const = lambda r, cq: pl.BlockSpec((r, cq), lambda i: (0, 0))
    in_specs = [row(bm, cout), row(bm, cout), const(cin, cout),
                const(1, cout), const(1, cout)]
    inputs = [y, dy, w, dsum, dssq]
    out_specs = [row(bm, cin)]
    out_shape = [jax.ShapeDtypeStruct((M, cin), x.dtype)]
    if prologue:
        in_specs = [row(bm, cin)] + in_specs[:3] + [
            const(1, cin), const(1, cin)] + in_specs[3:]
        inputs = [x, y, dy, w, scale, shift, dsum, dssq]
        out_specs += [const(1, cin), const(1, cin)]
        out_shape += [jax.ShapeDtypeStruct((1, cin), jnp.float32)] * 2
    out = pl.pallas_call(
        kernel,
        grid=(M // bm,),  # _pick_block_m guarantees bm | M (or bm == M)
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="conv1x1_bn_bwd_dx",
    )(*inputs)
    if prologue:
        dx, dscale, dshift = out
        return dx, dscale[0], dshift[0]
    (dx,) = out
    return dx, None, None  # no-prologue zero cotangents built by bwd()


# ---------------------------------------------------------------------------
# Backward B: dw = prologue(x)^T @ g, [cin, bn]-tile accumulator
# ---------------------------------------------------------------------------


def _bwd_dw_kernel(x_ref, y_ref, dy_ref, scale_ref, shift_ref,
                   dsum_ref, dssq_ref, dw_ref,
                   *, prologue, relu, emit_stats):
    g = dy_ref[:].astype(jnp.float32)
    if emit_stats:
        y = y_ref[:].astype(jnp.float32)
        g = g + dsum_ref[:] + 2.0 * y * dssq_ref[:]
    x = x_ref[:].astype(jnp.float32)
    if prologue:
        x = x * scale_ref[:] + shift_ref[:]
        if relu:
            x = jnp.maximum(x, 0.0)
    h = x.astype(x_ref.dtype)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    # h^T @ g (contract over the bm rows)
    dw_ref[:] += jax.lax.dot_general(
        h, g.astype(dy_ref.dtype),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _bwd_dw_call(x, y, dy, scale, shift, dsum, dssq, *, prologue, relu,
                 emit_stats, interpret):
    M, cin = x.shape
    cout = dy.shape[1]
    bm, bn = _tiling.pick_dw_tiles(
        M, cin, cout, in_bytes=x.dtype.itemsize, emit_stats=emit_stats,
        name="fused conv1x1 dw kernel",
    )
    kernel = functools.partial(
        _bwd_dw_kernel, prologue=prologue, relu=relu, emit_stats=emit_stats,
    )
    dw = pl.pallas_call(
        kernel,
        grid=(cout // bn, M // bm),  # M innermost: dw tile revisited
        in_specs=[
            pl.BlockSpec((bm, cin), lambda j, i: (i, 0)),
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            pl.BlockSpec((1, cin), lambda j, i: (0, 0)),
            pl.BlockSpec((1, cin), lambda j, i: (0, 0)),
            pl.BlockSpec((1, bn), lambda j, i: (0, j)),
            pl.BlockSpec((1, bn), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((cin, bn), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((cin, cout), jnp.float32),
        interpret=interpret,
        name="conv1x1_bn_bwd_dw",
    )(x, y, dy, scale, shift, dsum, dssq)
    return dw


# ---------------------------------------------------------------------------
# Backward B': single-pass dx + dscale/dshift + dw (one sweep over
# x/y/dy — structurally half the HBM traffic of the two-pass pair; used
# by bwd_impl="pallas" whenever the whole [cin, cout] f32 dw accumulator
# fits VMEM, see _tiling.pick_single_pass_bm)
# ---------------------------------------------------------------------------


def _bwd_single_kernel(*refs, prologue, relu, emit_stats):
    if prologue:
        (x_ref, y_ref, dy_ref, w_ref, scale_ref, shift_ref,
         dsum_ref, dssq_ref,
         dx_ref, dw_ref, dscale_ref, dshift_ref) = refs
    else:
        (x_ref, y_ref, dy_ref, w_ref, dsum_ref, dssq_ref,
         dx_ref, dw_ref) = refs
    g = dy_ref[:].astype(jnp.float32)
    if emit_stats:
        y = y_ref[:].astype(jnp.float32)
        g = g + dsum_ref[:] + 2.0 * y * dssq_ref[:]
    gq = g.astype(dy_ref.dtype)
    dh = jax.lax.dot_general(
        gq, w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        if prologue:
            dscale_ref[:] = jnp.zeros_like(dscale_ref)
            dshift_ref[:] = jnp.zeros_like(dshift_ref)

    x = x_ref[:].astype(jnp.float32)
    if prologue:
        xn = x * scale_ref[:] + shift_ref[:]
        if relu:
            live = (xn > 0.0).astype(jnp.float32)
            dh = dh * live
            h = jnp.maximum(xn, 0.0)
        else:
            h = xn
        dx_ref[:] = (dh * scale_ref[:]).astype(dx_ref.dtype)
        dscale_ref[:] += (dh * x).sum(0, keepdims=True)
        dshift_ref[:] += dh.sum(0, keepdims=True)
    else:
        h = x
        dx_ref[:] = dh.astype(dx_ref.dtype)
    dw_ref[:] += jax.lax.dot_general(
        h.astype(x_ref.dtype), gq,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _bwd_single_call(x, y, dy, w, scale, shift, dsum, dssq, bm, *,
                     prologue, relu, emit_stats, interpret):
    M, cin = x.shape
    cout = w.shape[1]
    kernel = functools.partial(
        _bwd_single_kernel, prologue=prologue, relu=relu,
        emit_stats=emit_stats,
    )
    row = lambda bq, cq: pl.BlockSpec((bq, cq), lambda i: (i, 0))
    const = lambda r, cq: pl.BlockSpec((r, cq), lambda i: (0, 0))
    in_specs = [row(bm, cin), row(bm, cout), row(bm, cout),
                const(cin, cout)]
    inputs = [x, y, dy, w]
    if prologue:
        in_specs += [const(1, cin), const(1, cin)]
        inputs += [scale, shift]
    in_specs += [const(1, cout), const(1, cout)]
    inputs += [dsum, dssq]
    out_specs = [row(bm, cin), const(cin, cout)]
    out_shape = [jax.ShapeDtypeStruct((M, cin), x.dtype),
                 jax.ShapeDtypeStruct((cin, cout), jnp.float32)]
    if prologue:
        out_specs += [const(1, cin), const(1, cin)]
        out_shape += [jax.ShapeDtypeStruct((1, cin), jnp.float32)] * 2
    out = pl.pallas_call(
        kernel,
        grid=(M // bm,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="conv1x1_bn_bwd_fused",
    )(*inputs)
    if prologue:
        dx, dw, dscale, dshift = out
        return dx, dw, dscale[0], dshift[0]
    dx, dw = out
    return dx, dw, None, None


# ---------------------------------------------------------------------------
# Backward C: the XLA-math backward (round-3 default)
# ---------------------------------------------------------------------------


def _xla_bwd(x, y, dy, w, scale, shift, dsum, dssq, *, prologue, relu,
             emit_stats):
    """Same math as the two Pallas backward kernels, in plain jnp.

    Microbenches on a v5e (previous toolchain — PERF.md "Earlier chip
    findings"): the Pallas FORWARD beats the unfused XLA sequence 1.0-2.5x at every
    batch-256 ResNet shape, but the two-kernel Pallas backward re-streams
    x/y/dy once per kernel (2 full passes) and loses to XLA's fused
    backward at every shape (0.40-0.87x). So the composite keeps the
    Pallas forward and defaults the VJP to this XLA path, which the
    compiler fuses into dgrad/wgrad epilogues; the Pallas backward
    kernels stay selectable (DTF_FUSED_BWD=pallas) for future tiles."""
    g = dy.astype(jnp.float32)
    if emit_stats:
        g = g + dsum + 2.0 * y.astype(jnp.float32) * dssq
    gq = g.astype(y.dtype)
    dh = jax.lax.dot_general(
        gq, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if prologue:
        x32 = x.astype(jnp.float32)
        xn = x32 * scale + shift
        if relu:
            dh = dh * (xn > 0.0).astype(jnp.float32)
        dx = (dh * scale).astype(x.dtype)
        dscale = (dh * x32).sum(0, keepdims=True)
        dshift = dh.sum(0, keepdims=True)
        h = jnp.maximum(xn, 0.0) if relu else xn
        hq = h.astype(x.dtype)
    else:
        dx = dh.astype(x.dtype)
        dscale = dshift = None
        hq = x
    dw = jax.lax.dot_general(
        hq, gq, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dx, dw, dscale, dshift


# ---------------------------------------------------------------------------
# custom_vjp composite
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _make_op(prologue, relu, emit_stats, out_dtype, interpret, bwd_impl):
    @jax.custom_vjp
    def op(x, w, scale, shift):
        y, s, ssq = _fwd_call(
            x, w, scale, shift, prologue=prologue, relu=relu,
            emit_stats=emit_stats, out_dtype=out_dtype, interpret=interpret,
        )
        return (y, s, ssq) if emit_stats else y

    def fwd(x, w, scale, shift):
        y, s, ssq = _fwd_call(
            x, w, scale, shift, prologue=prologue, relu=relu,
            emit_stats=emit_stats, out_dtype=out_dtype, interpret=interpret,
        )
        out = (y, s, ssq) if emit_stats else y
        return out, (x, y, w, scale, shift)

    def bwd(res, ct):
        x, y, w, scale, shift = res
        if emit_stats:
            dy, dsum, dssq = ct
            dsum = dsum.reshape(1, -1).astype(jnp.float32)
            dssq = dssq.reshape(1, -1).astype(jnp.float32)
        else:
            dy = ct
            cout = w.shape[1]
            dsum = jnp.zeros((1, cout), jnp.float32)
            dssq = jnp.zeros((1, cout), jnp.float32)
        dy = dy.astype(y.dtype)
        use_xla = bwd_impl == "xla"
        if not use_xla and _tiling.pallas_bwd_known_slow(
                x.shape[0], x.shape[1], w.shape[1]):
            # landmine guard: this shape stalled
            # >10 min in the Pallas-backward path on the real chip;
            # fall back to the measured-faster XLA backward rather than
            # hang whoever flipped DTF_FUSED_BWD=pallas. Set
            # DTF_FUSED_BWD_FORCE=1 to measure it anyway.
            import warnings

            warnings.warn(
                f"conv1x1_bn pallas backward at shape (M={x.shape[0]}, "
                f"cin={x.shape[1]}, cout={w.shape[1]}) is known to stall "
                "Mosaic compilation (round-3 on-chip evidence); using the "
                "XLA backward for this shape. DTF_FUSED_BWD_FORCE=1 "
                "overrides.")
            use_xla = True
        if use_xla:
            dx, dw, dscale, dshift = _xla_bwd(
                x, y, dy, w, scale, shift, dsum, dssq, prologue=prologue,
                relu=relu, emit_stats=emit_stats,
            )
            dw = dw.astype(w.dtype)
        else:
            bm1 = _tiling.pick_single_pass_bm(
                x.shape[0], x.shape[1], w.shape[1],
                in_bytes=x.dtype.itemsize, emit_stats=emit_stats,
            )
            if bm1 is not None:
                dx, dw, dscale, dshift = _bwd_single_call(
                    x, y, dy, w, scale, shift, dsum, dssq, bm1,
                    prologue=prologue, relu=relu, emit_stats=emit_stats,
                    interpret=interpret,
                )
                dw = dw.astype(w.dtype)
            else:
                dx, dscale, dshift = _bwd_dx_call(
                    x, y, dy, w, scale, shift, dsum, dssq,
                    prologue=prologue, relu=relu, emit_stats=emit_stats,
                    interpret=interpret,
                )
                dw = _bwd_dw_call(
                    x, y, dy, scale, shift, dsum, dssq, prologue=prologue,
                    relu=relu, emit_stats=emit_stats, interpret=interpret,
                ).astype(w.dtype)
        if prologue:
            return dx, dw, dscale.reshape(scale.shape), dshift.reshape(shift.shape)
        return dx, dw, jnp.zeros_like(scale), jnp.zeros_like(shift)

    op.defvjp(fwd, bwd)
    return op


def conv1x1_bn_act(
    x: jax.Array,
    w: jax.Array,
    scale: jax.Array | None = None,
    shift: jax.Array | None = None,
    *,
    relu: bool = True,
    emit_stats: bool = True,
    out_dtype=None,
    interpret: bool | None = None,
    bwd_impl: str | None = None,
):
    """Fused ``[M, Cin] @ [Cin, Cout]`` with optional BN-apply prologue and
    stats epilogue.

    x: [M, Cin] (bf16/f32) — the RAW previous conv output (pre-BN).
    w: [Cin, Cout].
    scale/shift: per-Cin f32 — the folded BN affine
        (see :func:`bn_scale_shift`); ``None`` disables the prologue
        (``relu`` is then ignored).
    emit_stats: also return ``(col_sum, col_sumsq)`` of the output, each
        [Cout] f32 — feed :func:`moments_from_sums` for the next BN.
    Returns ``y`` or ``(y, col_sum, col_sumsq)``.
    """
    if interpret is None:
        interpret = not _on_tpu()
    prologue = scale is not None
    if not prologue:
        cin = x.shape[1]
        scale = jnp.ones((1, cin), jnp.float32)
        shift = jnp.zeros((1, cin), jnp.float32)
    else:
        scale = scale.reshape(1, -1).astype(jnp.float32)
        shift = shift.reshape(1, -1).astype(jnp.float32)
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    bwd_impl = _tiling.resolve_bwd_impl(bwd_impl)
    op = _make_op(prologue, relu, emit_stats, out_dtype.name, bool(interpret),
                  bwd_impl)
    return op(x, w, scale, shift)


# ---------------------------------------------------------------------------
# Tiny [C]-sized helpers (plain XLA; negligible traffic)
# ---------------------------------------------------------------------------


def moments_from_sums(col_sum, col_sumsq, count):
    """Column sums -> (mean, biased variance), f32."""
    mean = col_sum / count
    var = jnp.maximum(col_sumsq / count - mean * mean, 0.0)
    return mean, var


def bn_scale_shift(mean, var, gamma, beta, eps):
    """Fold BN(mean, var, gamma, beta) into a per-channel affine
    ``x*scale + shift``."""
    scale = gamma * jax.lax.rsqrt(var + eps)
    return scale, beta - mean * scale


def conv1x1_bn_act_reference(x, w, scale=None, shift=None, *, relu=True,
                             emit_stats=True, out_dtype=None):
    """Pure-jnp oracle with the same numerics contract (stats computed on
    the quantized output)."""
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    h = x.astype(jnp.float32)
    if scale is not None:
        h = h * scale.reshape(1, -1) + shift.reshape(1, -1)
        if relu:
            h = jnp.maximum(h, 0.0)
    h = h.astype(x.dtype)
    y = jnp.dot(h, w, preferred_element_type=jnp.float32).astype(out_dtype)
    if not emit_stats:
        return y
    st = y.astype(jnp.float32)
    return y, st.sum(0), (st * st).sum(0)
