"""ResNet-50 — the BASELINE primary-metric workload (BASELINE.json:2,9).

Reference analog: the harness's ResNet-50 train script over PS/worker
(SURVEY.md §2a). TPU-first choices: bf16 conv/matmul compute with f32
params and f32 BatchNorm statistics (MXU-friendly, numerically safe), NHWC
layout (TPU conv native), and BatchNorm that becomes cross-replica synced
for free under GSPMD (the batch mean reduces over the sharded batch axis).
v1.5 variant (stride-2 on the 3x3, not the 1x1 — the MLPerf standard).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel import sharding

#: Coverage fixture: the stage_sizes=(1, 1) tree (every param family the
#: full ResNet-50 tree repeats — stem, bottleneck convs/BNs incl. the
#: projection shortcut, head). Pinned to the live model by
#: tests/test_sharding.py::test_resnet_coverage_fixture_is_live.
#: (fully literal — the dtflint shard-rules-coverage rule reads it
#: statically)
_RESNET_COVERAGE = (
    "head/bias", "head/kernel",
    "stage0_block0/bn1/bias", "stage0_block0/bn1/scale",
    "stage0_block0/bn2/bias", "stage0_block0/bn2/scale",
    "stage0_block0/bn3/bias", "stage0_block0/bn3/scale",
    "stage0_block0/conv1/kernel", "stage0_block0/conv2/kernel",
    "stage0_block0/conv3/kernel",
    "stage0_block0/proj_bn/bias", "stage0_block0/proj_bn/scale",
    "stage0_block0/proj_conv/kernel",
    "stage1_block0/bn1/bias", "stage1_block0/bn1/scale",
    "stage1_block0/bn2/bias", "stage1_block0/bn2/scale",
    "stage1_block0/bn3/bias", "stage1_block0/bn3/scale",
    "stage1_block0/conv1/kernel", "stage1_block0/conv2/kernel",
    "stage1_block0/conv3/kernel",
    "stage1_block0/proj_bn/bias", "stage1_block0/proj_bn/scale",
    "stage1_block0/proj_conv/kernel",
    "stem_bn/bias", "stem_bn/scale", "stem_conv/kernel",
)

#: Partition-rules table: ResNet trains pure data-parallel — every param
#: is DECLARED replicated (batch sharding rides (data, fsdp) via
#: batch_spec; BatchNorm syncs for free under GSPMD). A one-row table is
#: still the seam: adding a sharded param family later means adding a
#: row here, not hand-authoring a spec tree.
RESNET_RULES = sharding.partition_rules(
    "resnet",
    ((sharding.CATCH_ALL, sharding.REPLICATED),),
    coverage=_RESNET_COVERAGE,
)


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    stage_sizes: tuple = (3, 4, 6, 3)  # ResNet-50
    num_classes: int = 1000
    width: int = 64
    dtype: str = "bfloat16"
    # BatchNorm *output* dtype; None = follow `dtype`. Statistics are always
    # computed in f32 (flax normalization upcasts internally); bf16 output
    # halves the HBM traffic of the normalize/scale pass — the activations
    # between BN and the next conv are the widest tensors in the net
    # (f32 BN output measured -25% throughput on a v5e, previous
    # toolchain — PERF.md "Earlier chip findings").
    norm_dtype: str | None = None
    bn_momentum: float = 0.9
    bn_epsilon: float = 1e-5
    # "conv": standard 7x7/2 stem. "space_to_depth": fold the image 2x2
    # (H,W,3)→(H/2,W/2,12) and run a 4x4/1 conv — same receptive field as
    # an 8x8/2 conv (7x7 kernel zero-padded), but 12 input channels pack
    # the MXU's contracting dimension 4x better than 3 (the MLPerf TPU
    # ResNet conv0 optimization).
    stem: str = "conv"
    # "standard": flax Conv/BatchNorm bottlenecks. "fused": Pallas
    # conv1x1+BN kernels (ops/fused_conv_bn.py) — the 1x1 convs absorb the
    # adjacent BN normalize/stats passes (prologue/epilogue), cutting the
    # HBM traffic that bounds the step (PERF.md "Earlier chip
    # findings"). Same
    # param/batch_stats tree as "standard" (checkpoints interoperate).
    block_impl: str = "standard"


def space_to_depth(x, block: int):
    """(B, H, W, C) → (B, H/b, W/b, C·b²): fold b×b spatial patches into
    channels. Pure reshape/transpose — XLA fuses it into the consumer."""
    b_, h, w, c = x.shape
    x = x.reshape(b_, h // block, block, w // block, block, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b_, h // block, w // block, c * block * block
    )


class BottleneckBlock(nn.Module):
    filters: int
    strides: int
    cfg: ResNetConfig

    @nn.compact
    def __call__(self, x, *, train: bool):
        dtype = jnp.dtype(self.cfg.dtype)
        conv = partial(nn.Conv, use_bias=False, dtype=dtype,
                       kernel_init=nn.initializers.he_normal())
        # BN computes statistics in f32 regardless of output dtype.
        bn = partial(nn.BatchNorm, use_running_average=not train,
                     momentum=self.cfg.bn_momentum, epsilon=self.cfg.bn_epsilon,
                     dtype=jnp.dtype(self.cfg.norm_dtype or self.cfg.dtype))
        residual = x
        y = conv(self.filters, (1, 1), name="conv1")(x)
        y = bn(name="bn1")(y)
        y = nn.relu(y)
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                 name="conv2")(y)  # v1.5: stride on the 3x3
        y = bn(name="bn2")(y)
        y = nn.relu(y)
        y = conv(self.filters * 4, (1, 1), name="conv3")(y)
        # zero-init last BN scale: residual branch starts as identity
        y = bn(name="bn3", scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters * 4, (1, 1),
                            strides=(self.strides, self.strides),
                            name="proj_conv")(residual)
            residual = bn(name="proj_bn")(residual)
        return nn.relu(residual.astype(y.dtype) + y)


# ---------------------------------------------------------------------------
# Fused-kernel bottleneck (ops/fused_conv_bn.py): same params, same math,
# 1x1 convs absorb the adjacent BN passes
# ---------------------------------------------------------------------------


class _ConvKernel(nn.Module):
    """Parameter-only scope so the fused block's tree matches the standard
    block's (``conv1/kernel`` etc. — checkpoints interoperate)."""

    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.he_normal(), self.shape)


class _BNState(nn.Module):
    """scale/bias params + batch_stats mean/var, flax BatchNorm naming."""

    features: int
    zero_scale: bool = False

    @nn.compact
    def __call__(self):
        init_scale = (
            nn.initializers.zeros if self.zero_scale else nn.initializers.ones
        )
        scale = self.param("scale", init_scale, (self.features,))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        mean = self.variable(
            "batch_stats", "mean",
            lambda: jnp.zeros((self.features,), jnp.float32),
        )
        var = self.variable(
            "batch_stats", "var",
            lambda: jnp.ones((self.features,), jnp.float32),
        )
        return scale, bias, mean, var


class FusedBottleneckBlock(nn.Module):
    """BottleneckBlock with the 1x1 convs running through the fused
    Pallas conv+BN kernels (train mode): conv1/conv3/proj_conv emit their
    output BN's statistics from the kernel epilogue, and conv3 applies
    bn2+ReLU in its prologue — the normalized tensor between bn2 and conv3
    and all three stats read-passes never touch HBM. BatchNorm statistics
    reduce over the *global* batch (psum over data/fsdp inside a shard_map
    island when a mesh is given) — the same sync-BN-under-GSPMD semantics
    as the standard block. Eval uses plain XLA ops with running stats."""

    filters: int
    strides: int
    cfg: ResNetConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, *, train: bool):
        from ..ops.fused_conv_bn import (
            bn_scale_shift, conv1x1_bn_act, moments_from_sums,
        )
        from ..parallel import mesh as mesh_lib

        cfg = self.cfg
        f, s = self.filters, self.strides
        cin = x.shape[-1]
        dtype = jnp.dtype(cfg.dtype)
        out_dtype = jnp.dtype(cfg.norm_dtype or cfg.dtype)
        eps, mom = cfg.bn_epsilon, cfg.bn_momentum

        w1 = _ConvKernel((1, 1, cin, f), name="conv1")()
        g1, b1, m1, v1 = _BNState(f, name="bn1")()
        w2 = _ConvKernel((3, 3, f, f), name="conv2")()
        g2, b2, m2, v2 = _BNState(f, name="bn2")()
        w3 = _ConvKernel((1, 1, f, 4 * f), name="conv3")()
        g3, b3, m3, v3 = _BNState(4 * f, zero_scale=True, name="bn3")()
        need_proj = cin != 4 * f or s != 1
        if need_proj:
            wp = _ConvKernel((1, 1, cin, 4 * f), name="proj_conv")()
            gp, bp, mp, vp = _BNState(4 * f, name="proj_bn")()

        conv3x3 = lambda h: jax.lax.conv_general_dilated(
            h, w2.astype(dtype), (s, s), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

        if not train:
            # eval: running stats, plain XLA (perf-uncritical path)
            def aff(y, g, b, m, v):
                sc, sh = bn_scale_shift(m, v, g, b, eps)
                return y.astype(jnp.float32) * sc + sh
            dot1x1 = lambda h, w: jnp.einsum(
                "bhwc,cd->bhwd", h, w.reshape(w.shape[2], w.shape[3]).astype(dtype)
            )
            h1 = nn.relu(aff(dot1x1(x, w1), g1, b1, m1.value, v1.value)).astype(dtype)
            y2 = conv3x3(h1)
            h2 = nn.relu(aff(y2, g2, b2, m2.value, v2.value)).astype(dtype)
            y3 = aff(dot1x1(h2, w3), g3, b3, m3.value, v3.value)
            if need_proj:
                xs = x[:, ::s, ::s, :]
                res = aff(dot1x1(xs, wp), gp, bp, mp.value, vp.value)
            else:
                res = x.astype(jnp.float32)
            return nn.relu(y3 + res).astype(out_dtype)

        axis_names = None
        if self.mesh is not None:
            axis_names = tuple(
                a for a in mesh_lib.BATCH_AXES if a in self.mesh.shape
            )

        def block_fn(x, w1, w2f, w3, wp_, g1, b1, g2, b2, g3, b3, gp_, bp_):
            psum = (
                (lambda t: jax.lax.psum(t, axis_names))
                if axis_names else (lambda t: t)
            )
            B, H, W, _ = x.shape
            x2 = x.reshape(-1, cin)
            w1_2 = w1.reshape(cin, f).astype(dtype)
            y1, s1, q1 = conv1x1_bn_act(
                x2, w1_2, emit_stats=True, out_dtype=out_dtype
            )
            n1 = psum(jnp.float32(y1.shape[0]))
            mu1, var1 = moments_from_sums(psum(s1), psum(q1), n1)
            sc1, sh1 = bn_scale_shift(mu1, var1, g1, b1, eps)
            h1 = nn.relu(y1.astype(jnp.float32) * sc1 + sh1).astype(dtype)
            y2 = jax.lax.conv_general_dilated(
                h1.reshape(B, H, W, f), w2f.astype(dtype), (s, s), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            y2_2 = y2.astype(out_dtype).reshape(-1, f)
            st2 = y2_2.astype(jnp.float32)
            n2 = psum(jnp.float32(y2_2.shape[0]))
            mu2, var2 = moments_from_sums(
                psum(st2.sum(0)), psum((st2 * st2).sum(0)), n2
            )
            sc2, sh2 = bn_scale_shift(mu2, var2, g2, b2, eps)
            y3, s3, q3 = conv1x1_bn_act(
                y2_2, w3.reshape(f, 4 * f).astype(dtype), sc2, sh2,
                relu=True, emit_stats=True, out_dtype=out_dtype,
            )
            mu3, var3 = moments_from_sums(psum(s3), psum(q3), n2)
            sc3, sh3 = bn_scale_shift(mu3, var3, g3, b3, eps)
            out = y3.astype(jnp.float32) * sc3 + sh3
            stats = [mu1, var1, mu2, var2, mu3, var3]
            if need_proj:
                xs = x[:, ::s, ::s, :].reshape(-1, cin)
                yp, sp, qp = conv1x1_bn_act(
                    xs, wp_.reshape(cin, 4 * f).astype(dtype),
                    emit_stats=True, out_dtype=out_dtype,
                )
                mup, varp = moments_from_sums(psum(sp), psum(qp), n2)
                scp, shp = bn_scale_shift(mup, varp, gp_, bp_, eps)
                res = yp.astype(jnp.float32) * scp + shp
                stats += [mup, varp]
            else:
                res = x.reshape(-1, 4 * f).astype(jnp.float32)
            out = nn.relu(out + res).astype(out_dtype)
            # SAME-padded stride-s conv (and the ::s residual slice) emit
            # ceil(H/s), not floor
            Ho, Wo = -(-H // s), -(-W // s)
            return out.reshape(B, Ho, Wo, 4 * f), tuple(stats)

        wp_in = wp if need_proj else jnp.zeros((1, 1, cin, 4 * f), w1.dtype)
        gp_in = gp if need_proj else jnp.zeros((4 * f,), g1.dtype)
        bp_in = bp if need_proj else jnp.zeros((4 * f,), b1.dtype)
        args = (x, w1, w2, w3, wp_in, g1, b1, g2, b2, g3, b3, gp_in, bp_in)
        if axis_names:
            bspec = P(axis_names, None, None, None)
            fn = jax.shard_map(
                block_fn,
                mesh=self.mesh,
                in_specs=(bspec,) + (P(),) * 12,
                out_specs=(bspec, tuple(P() for _ in range(8 if need_proj else 6))),
                check_vma=False,
            )
            out, stats = fn(*args)
        else:
            out, stats = block_fn(*args)

        if not self.is_initializing():
            upd = lambda var, new: setattr(
                var, "value", mom * var.value + (1.0 - mom) * new
            )
            upd(m1, stats[0]); upd(v1, stats[1])
            upd(m2, stats[2]); upd(v2, stats[3])
            upd(m3, stats[4]); upd(v3, stats[5])
            if need_proj:
                upd(mp, stats[6]); upd(vp, stats[7])
        return out


class ResNet(nn.Module):
    cfg: ResNetConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = x.astype(dtype)
        if cfg.stem == "space_to_depth":
            x = space_to_depth(x, 2)
            x = nn.Conv(cfg.width, (4, 4), strides=(1, 1), use_bias=False,
                        dtype=dtype, kernel_init=nn.initializers.he_normal(),
                        name="stem_conv_s2d")(x)
        elif cfg.stem == "conv":
            x = nn.Conv(cfg.width, (7, 7), strides=(2, 2), use_bias=False,
                        dtype=dtype, kernel_init=nn.initializers.he_normal(),
                        name="stem_conv")(x)
        else:
            raise ValueError(f"Unknown stem {cfg.stem!r}")
        x = nn.BatchNorm(use_running_average=not train, momentum=cfg.bn_momentum,
                         epsilon=cfg.bn_epsilon,
                         dtype=jnp.dtype(cfg.norm_dtype or cfg.dtype),
                         name="stem_bn")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for stage, blocks in enumerate(cfg.stage_sizes):
            for block in range(blocks):
                strides = 2 if stage > 0 and block == 0 else 1
                name = f"stage{stage}_block{block}"
                if cfg.block_impl == "fused":
                    x = FusedBottleneckBlock(
                        cfg.width * 2**stage, strides, cfg, self.mesh,
                        name=name,
                    )(x, train=train)
                elif cfg.block_impl == "standard":
                    x = BottleneckBlock(
                        cfg.width * 2**stage, strides, cfg, name=name,
                    )(x, train=train)
                else:
                    raise ValueError(f"Unknown block_impl {cfg.block_impl!r}")
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        # head in f32: the last matmul is tiny; keep logits stable
        return nn.Dense(cfg.num_classes, dtype=jnp.float32, name="head")(x)


def ResNet50(cfg: ResNetConfig | None = None, mesh: Any = None) -> ResNet:
    return ResNet(cfg or ResNetConfig(), mesh)


def flops_per_example(cfg: ResNetConfig, image_size: int = 224) -> float:
    """Analytic FORWARD FLOPs per image (the §6 honesty rule: model
    arithmetic, not profiler counts). Counts conv/dense MACs ×2. The
    framework-wide contract (utils/flops.py): flops_per_example is always
    forward-only; training consumers apply train_flops_multiplier() in
    exactly one place (MetricsLogger / bench)."""
    total = 0.0
    size = image_size // 2  # stem stride 2 (or s2d fold)
    if cfg.stem == "space_to_depth":
        stem_macs = 12 * 16
    elif cfg.stem == "conv":
        stem_macs = 3 * 49
    else:
        raise ValueError(f"Unknown stem {cfg.stem!r}")
    total += 2.0 * size * size * cfg.width * stem_macs
    size //= 2  # maxpool
    in_c = cfg.width
    for stage, blocks in enumerate(cfg.stage_sizes):
        filters = cfg.width * 2**stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out_size = size // stride
            # 1x1 in (at input res), 3x3 (strided), 1x1 out
            total += 2.0 * size * size * filters * in_c
            total += 2.0 * out_size * out_size * filters * filters * 9
            total += 2.0 * out_size * out_size * (filters * 4) * filters
            if in_c != filters * 4 or stride != 1:
                total += 2.0 * out_size * out_size * (filters * 4) * in_c
            in_c = filters * 4
            size = out_size
    total += 2.0 * in_c * cfg.num_classes
    return total
