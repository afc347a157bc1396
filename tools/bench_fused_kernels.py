#!/usr/bin/env python
"""Microbench: fused Pallas conv1x1+BN kernels vs the unfused XLA sequence
at ResNet-50 training shapes. Run on the real
chip: `python tools/bench_fused_kernels.py [fwd|grad] [reps]`.

Timing: the whole rep-loop lives in one jit (lax.fori_loop) with a scalar
carry that every iteration's outputs fold into, and the carry is fetched
— one dispatch covers the whole chain, and the fetched value depends on
every iteration (utils/benchmarking.py discipline).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.ops.fused_conv_bn import (
    bn_scale_shift, conv1x1_bn_act, moments_from_sums,
)

# (name, M, cin, cout, prologue) — b=256 ResNet-50 bottleneck 1x1s
SHAPES = [
    ("s0_conv3", 256 * 56 * 56, 64, 256, True),
    ("s1_conv1", 256 * 28 * 28, 512, 128, False),
    ("s1_conv3", 256 * 28 * 28, 128, 512, True),
    ("s2_conv3", 256 * 14 * 14, 256, 1024, True),
    ("s3_conv1", 256 * 7 * 7, 2048, 512, False),
]


def unfused(x, w, scale, shift, prologue):
    h = x
    if prologue:
        h = (x.astype(jnp.float32) * scale + shift)
        h = jnp.maximum(h, 0.0).astype(x.dtype)
    y = jnp.dot(h, w, preferred_element_type=jnp.float32).astype(x.dtype)
    st = y.astype(jnp.float32)
    return y, st.sum(0), (st * st).sum(0)


def fused(x, w, scale, shift, prologue):
    if prologue:
        return conv1x1_bn_act(x, w, scale, shift, relu=True, emit_stats=True)
    return conv1x1_bn_act(x, w, emit_stats=True)


def loss_of(fn, prologue):
    def loss(x, w, scale, shift):
        y, s, ssq = fn(x, w, scale, shift, prologue)
        mean, var = moments_from_sums(s, ssq, y.shape[0])
        sc2, sh2 = bn_scale_shift(mean, var, jnp.ones_like(mean),
                                  jnp.zeros_like(mean), 1e-5)
        # consume y the way the next layer would: one more normalize pass
        return (y.astype(jnp.float32) * sc2 + sh2).sum()

    return loss


def timed(fn, args, reps):
    def body(_, carry):
        out = fn(*args)
        leaves = jax.tree.leaves(out)
        return carry + sum(jnp.sum(l).astype(jnp.float32) * 0 for l in leaves) + 1

    run = jax.jit(lambda: jax.lax.fori_loop(0, reps, body, 0.0))
    float(jax.device_get(run()))  # compile + warm
    t0 = time.perf_counter()
    float(jax.device_get(run()))
    return (time.perf_counter() - t0) / reps * 1e3


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else "fwd"
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    # this microbench exists to MEASURE the pallas path, including shapes
    # the landmine guard (_tiling.PALLAS_BWD_KNOWN_SLOW) would reroute
    import os

    os.environ["DTF_FUSED_BWD_FORCE"] = "1"
    r = np.random.RandomState(0)
    print(f"backend={jax.default_backend()} mode={mode} reps={reps}")
    print(f"{'shape':10s} {'M':>8s} {'cin':>5s} {'cout':>5s} "
          f"{'xla_ms':>8s} {'pallas_ms':>9s} {'speedup':>7s}")
    for name, M, cin, cout, prologue in SHAPES:
        x = jnp.asarray(r.randn(M, cin), jnp.bfloat16)
        w = jnp.asarray(r.randn(cin, cout) * 0.05, jnp.bfloat16)
        scale = jnp.asarray(r.rand(cin) + 0.5, jnp.float32)
        shift = jnp.asarray(r.randn(cin) * 0.1, jnp.float32)
        args = (x, w, scale, shift)
        if mode == "fwd":
            t_x = timed(lambda *a: unfused(*a, prologue), args, reps)
            t_p = timed(lambda *a: fused(*a, prologue), args, reps)
        else:
            gx = jax.grad(loss_of(unfused, prologue), argnums=(0, 1))
            gp = jax.grad(loss_of(fused, prologue), argnums=(0, 1))
            t_x = timed(gx, args, reps)
            t_p = timed(gp, args, reps)
        print(f"{name:10s} {M:8d} {cin:5d} {cout:5d} "
              f"{t_x:8.3f} {t_p:9.3f} {t_x / t_p:7.2f}x", flush=True)


if __name__ == "__main__":
    main()
