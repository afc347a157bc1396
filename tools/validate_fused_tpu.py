#!/usr/bin/env python
"""Compiled-mode (Mosaic) validation of every fused Pallas kernel on the
real chip: small-shape forward + gradient parity vs the jnp oracles for
(a) the conv1x1+BN kernels at every static config the ResNet integration
uses, (b) the LayerNorm+matmul kernel, then whole-model comparisons —
a fused-LN pre-LN transformer and a fused bottleneck ResNet vs their
standard flax twins (fwd + full grad pytree). Fast (<3 min warm) and
read-only — run this before any fused bench.

Gradient/model checks use a max-normalized error (err relative to the
largest entry of the oracle tensor) so tiny-magnitude gradients cannot
pass vacuously under the elementwise damped metric.

Exit code 0 = every check passed.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.ops.fused_conv_bn import (
    bn_scale_shift, conv1x1_bn_act, conv1x1_bn_act_reference,
    moments_from_sums,
)


def check(name, got, want, tol):
    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(g - w) / (np.abs(w) + 1.0)))
    ok = err <= tol
    print(f"{'ok ' if ok else 'FAIL'} {name}: rel_err={err:.2e} (tol {tol})")
    return ok


def check_scaled(name, got, want, tol):
    """Max-abs error relative to the oracle's own largest entry.

    Unlike ``check`` this cannot be satisfied vacuously by a
    small-magnitude tensor: an all-zero ``got`` scores err = 1.0.
    """
    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = float(np.max(np.abs(w)))
    if scale == 0.0:  # not assert: must survive python -O
        print(f"FAIL {name}: oracle is all-zero, check would be vacuous")
        return False
    err = float(np.max(np.abs(g - w))) / scale
    ok = err <= tol
    print(f"{'ok ' if ok else 'FAIL'} {name}: scaled_err={err:.2e} (tol {tol})")
    return ok


def bench_shape_sweep(r) -> bool:
    """Compile/execute every fused-kernel shape the batch-256 ResNet-50
    and bench BERT/GPT paths actually emit (TPU only).

    Round-3 on-chip lesson: the dw kernel's VMEM footprint is
    shape-dependent, and small-shape parity passed while the REAL bench
    shape [12544, 512]x[12544, 2048] blew the 16 MB scoped limit at
    compile time — this sweep is what makes the validator a gate for the
    bench. Every check is exception-guarded: one bad shape must record
    FAIL and keep sweeping, not abort a scarce chip window.

    VALIDATE_PALLAS_BWD selects what runs:
      "0" (default) — default-path (xla-backward) fwd+grad execute only;
      "1"           — both the default path and the Pallas-backward
                      AOT compiles;
      "only"        — Pallas-backward AOT compiles alone (may stall: a
                      >10 min stall was seen in this path at the
                      s3_conv1 shape on a v5e, previous toolchain).
    """
    from distributed_tensorflow_tpu.ops.fused_ln_matmul import ln_matmul

    mode = os.environ.get("VALIDATE_PALLAS_BWD", "0")
    run_default = mode in ("0", "1")
    run_pallas = mode in ("1", "only")
    if run_pallas:
        # the sweep MEASURES the known-slow shapes (it is how entries in
        # _tiling.PALLAS_BWD_KNOWN_SLOW get confirmed or retired), so it
        # bypasses the landmine guard and times every compile
        os.environ["DTF_FUSED_BWD_FORCE"] = "1"
    if jax.default_backend() != "tpu":
        print("skip bench-shape sweep (not on TPU; interpret mode would "
              "not exercise Mosaic VMEM limits)")
        return True
    ok = True

    def guarded(tag, fn):
        nonlocal ok
        try:
            fn()
            return True
        except Exception as e:  # noqa: BLE001 — report, don't abort
            print(f"FAIL {tag}: {type(e).__name__}: {str(e)[:200]}")
            ok = False
            return False

    conv_shapes = [  # batch-256 ResNet-50 1x1 convs, all stages
        (200704, 64, 256), (200704, 256, 64), (200704, 256, 128),
        (50176, 128, 512), (50176, 512, 128), (50176, 512, 256),
        (12544, 256, 1024), (12544, 1024, 256), (12544, 1024, 512),
        (3136, 512, 2048), (3136, 2048, 512),
        (12544, 512, 2048), (12544, 2048, 512),  # the r3 OOM shapes
    ]
    for (bM, bci, bco) in conv_shapes:
        bx = jnp.asarray(r.randn(bM, bci) * 0.1, jnp.bfloat16)
        bw = jnp.asarray(r.randn(bci, bco) * 0.05, jnp.bfloat16)
        bs = jnp.asarray(r.rand(bci) + 0.5, jnp.float32)
        bsh = jnp.asarray(r.randn(bci) * 0.1, jnp.float32)

        def conv_loss(impl):
            def go(x, w, s, sh):
                y, cs, cq = conv1x1_bn_act(x, w, s, sh, relu=True,
                                           emit_stats=True, bwd_impl=impl)
                return ((y.astype(jnp.float32) ** 2).mean()
                        + cs.sum() * 1e-6 + cq.sum() * 1e-9)
            return go

        if run_default:
            def execute():
                val, grads = jax.jit(jax.value_and_grad(
                    conv_loss("xla"), argnums=(0, 1, 2, 3)))(
                        bx, bw, bs, bsh)
                fin = all(bool(jnp.all(jnp.isfinite(
                    g.astype(jnp.float32)))) for g in grads)
                if not (np.isfinite(float(val)) and fin):
                    raise RuntimeError(
                        f"loss={float(val)} grads_finite={fin}")
                print(f"ok  bench-shape conv1x1 M={bM} {bci}->{bco}: "
                      f"loss={float(val):.3e}")

            guarded(f"bench-shape conv1x1 M={bM} {bci}->{bco}", execute)

        if run_pallas:
            def compile_pallas():
                import time as _t

                t0 = _t.perf_counter()
                jax.jit(jax.value_and_grad(
                    conv_loss("pallas"), argnums=(0, 1, 2, 3))).lower(
                        bx, bw, bs, bsh).compile()
                print(f"ok  bench-shape conv1x1 pallas-bwd compile "
                      f"M={bM} {bci}->{bco} ({_t.perf_counter()-t0:.1f}s)")

            guarded(f"bench-shape conv1x1 pallas-bwd compile M={bM} "
                    f"{bci}->{bco}", compile_pallas)

    ln_shapes = [  # BERT-base / GPT-2 ln_matmul edges (M = batch x seq)
        (16384, 768, 2304), (16384, 768, 3072), (16384, 3072, 768),
        (32768, 1024, 4096),  # gpt long-context edge
    ]
    for (bM, bd, bn_) in ln_shapes:
        bx = jnp.asarray(r.randn(bM, bd) * 0.1, jnp.bfloat16)
        bg = jnp.asarray(r.rand(bd) + 0.5, jnp.float32)
        bb = jnp.asarray(r.randn(bd) * 0.1, jnp.float32)
        bw = jnp.asarray(r.randn(bd, bn_) * 0.02, jnp.bfloat16)
        bbias = jnp.asarray(r.randn(bn_) * 0.1, jnp.float32)

        def ln_loss_of(impl):
            def go(x, g, b, w, bias):
                y = ln_matmul(x, g, b, w, bias, bwd_impl=impl)
                return (y.astype(jnp.float32) ** 2).mean()
            return go

        if run_default:
            def execute_ln():
                val, grads = jax.jit(jax.value_and_grad(
                    ln_loss_of("xla"), argnums=(0, 1, 2, 3, 4)))(
                        bx, bg, bb, bw, bbias)
                fin = all(bool(jnp.all(jnp.isfinite(
                    g.astype(jnp.float32)))) for g in grads)
                if not (np.isfinite(float(val)) and fin):
                    raise RuntimeError(
                        f"loss={float(val)} grads_finite={fin}")
                print(f"ok  bench-shape ln_matmul M={bM} {bd}->{bn_}: "
                      f"loss={float(val):.3e}")

            guarded(f"bench-shape ln_matmul M={bM} {bd}->{bn_}",
                    execute_ln)

        if run_pallas:
            def compile_ln_pallas():
                import time as _t

                t0 = _t.perf_counter()
                jax.jit(jax.value_and_grad(
                    ln_loss_of("pallas"), argnums=(0, 1, 2, 3, 4))).lower(
                        bx, bg, bb, bw, bbias).compile()
                print(f"ok  bench-shape ln_matmul pallas-bwd compile "
                      f"M={bM} {bd}->{bn_} ({_t.perf_counter()-t0:.1f}s)")

            guarded(f"bench-shape ln_matmul pallas-bwd compile M={bM} "
                    f"{bd}->{bn_}", compile_ln_pallas)

    return ok


def main():
    print("devices:", jax.devices(), flush=True)
    r = np.random.RandomState(0)
    if os.environ.get("VALIDATE_PALLAS_BWD") == "only":
        # the may-stall late step of a chip session: just the gated
        # Pallas-backward compiles, no duplicate parity/default sweep
        ok = bench_shape_sweep(r)
        print("ALL OK" if ok else "FAILURES", flush=True)
        raise SystemExit(0 if ok else 1)
    M, cin, cout = 512, 64, 128
    x = jnp.asarray(r.randn(M, cin), jnp.bfloat16)
    w = jnp.asarray(r.randn(cin, cout) * 0.1, jnp.bfloat16)
    gamma = jnp.asarray(r.rand(cin) + 0.5, jnp.float32)
    beta = jnp.asarray(r.randn(cin) * 0.1, jnp.float32)
    mean = jnp.asarray(r.randn(cin) * 0.2, jnp.float32)
    var = jnp.asarray(r.rand(cin) + 0.3, jnp.float32)
    scale, shift = bn_scale_shift(mean, var, gamma, beta, 1e-5)
    ok = True

    for prologue in (False, True):
        args = (x, w, scale, shift) if prologue else (x, w)
        got = jax.jit(
            lambda *a: conv1x1_bn_act(*a, relu=True, emit_stats=True)
        )(*args)
        want = conv1x1_bn_act_reference(*args, relu=True, emit_stats=True)
        for nm, g, wn in zip(("y", "sum", "ssq"), got, want):
            ok &= check(f"fwd prologue={prologue} {nm}", g, wn, 3e-2)

        def loss(fn):
            def go(x, w, scale, shift):
                a = (x, w, scale, shift) if prologue else (x, w)
                y, s, q = fn(*a, relu=True, emit_stats=True)
                mu, v = moments_from_sums(s, q, y.shape[0])
                return ((y.astype(jnp.float32) ** 2).mean()
                        + (mu * mu).sum() + jnp.sqrt(v + 1e-3).sum())
            return go

        got_g = jax.jit(jax.grad(loss(conv1x1_bn_act), argnums=(0, 1, 2, 3))
                        )(x, w, scale, shift)
        want_g = jax.grad(loss(conv1x1_bn_act_reference),
                          argnums=(0, 1, 2, 3))(x, w, scale, shift)
        n = 4 if prologue else 2
        for nm, g, wn in list(zip(("dx", "dw", "dscale", "dshift"),
                                  got_g, want_g))[:n]:
            ok &= check(f"grad prologue={prologue} {nm}", g, wn, 5e-2)

    # ---- fused LayerNorm+matmul (ops/fused_ln_matmul.py) ----------------
    from distributed_tensorflow_tpu.ops.fused_ln_matmul import (
        ln_matmul, ln_matmul_reference,
    )

    M2, d, nn = 1024, 768, 768
    lx = jnp.asarray(r.randn(M2, d), jnp.bfloat16)
    lg = jnp.asarray(r.rand(d) + 0.5, jnp.float32)
    lb = jnp.asarray(r.randn(d) * 0.1, jnp.float32)
    lw = jnp.asarray(r.randn(d, nn) * 0.02, jnp.bfloat16)
    lbias = jnp.asarray(r.randn(nn) * 0.1, jnp.float32)

    got = jax.jit(ln_matmul)(lx, lg, lb, lw, lbias)
    want = ln_matmul_reference(lx, lg, lb, lw, lbias)
    ok &= check("ln_matmul fwd", got, want, 3e-2)

    def ln_loss(fn):
        def go(x, g, b, w, bias):
            y = fn(x, g, b, w, bias)
            return (y.astype(jnp.float32) ** 2).mean()
        return go

    got_g = jax.jit(jax.grad(ln_loss(ln_matmul), argnums=(0, 1, 2, 3, 4))
                    )(lx, lg, lb, lw, lbias)
    want_g = jax.grad(ln_loss(ln_matmul_reference), argnums=(0, 1, 2, 3, 4)
                      )(lx, lg, lb, lw, lbias)
    for nm, g, wn in zip(("dx", "dgamma", "dbeta", "dw", "dbias"),
                         got_g, want_g):
        ok &= check_scaled(f"ln_matmul grad {nm}", jnp.reshape(g, (-1,)),
                           jnp.reshape(wn, (-1,)), 5e-2)

    def compare_models(tag, loss_f, loss_std, params, fwd_tol, grad_tol):
        """Fused-vs-standard twin comparison: jitted scalar loss + the
        gradient pytree compared PER LEAF under the max-normalized
        metric — a globally-raveled comparison would let large embedding
        grads mask a broken small-magnitude leaf (dgamma/dbeta)."""
        lf_val, gf = jax.jit(jax.value_and_grad(loss_f))(params)
        ls_val, gs = jax.jit(jax.value_and_grad(loss_std))(params)
        res = check_scaled(f"{tag} fwd", lf_val, ls_val, fwd_tol)
        gf, gs = jax.device_get((gf, gs))
        # Per-leaf scale, floored at 1% of the global max: a broken leaf
        # whose true magnitude is within 100x of the dominant one still
        # fails loudly, while structurally-degenerate leaves (key biases —
        # softmax is shift-invariant in k, so their true grad is pure
        # cancellation noise) aren't amplified into false alarms.
        global_max = max(
            float(np.max(np.abs(np.asarray(l, np.float32))))
            for l in jax.tree.leaves(gs)
        )
        if global_max == 0.0:
            print(f"FAIL {tag} grad: every oracle leaf is all-zero "
                  "(degenerate params?) — comparison would be vacuous")
            return False
        worst_err, worst_leaf, leaf_ok = 0.0, "?", True
        for (path, lf), (_, ls) in zip(
            jax.tree_util.tree_leaves_with_path(gf),
            jax.tree_util.tree_leaves_with_path(gs),
        ):
            g, w = np.asarray(lf, np.float32), np.asarray(ls, np.float32)
            scale = max(float(np.max(np.abs(w))), 1e-2 * global_max)
            err = float(np.max(np.abs(g - w))) / scale
            if err > worst_err:
                worst_err, worst_leaf = err, jax.tree_util.keystr(path)
            leaf_ok &= err <= grad_tol
        print(f"{'ok ' if leaf_ok else 'FAIL'} {tag} grad: worst leaf "
              f"{worst_leaf} scaled_err={worst_err:.2e} (tol {grad_tol})")
        return res & leaf_ok

    # fused vs unfused pre-LN transformer twins (compiled), fwd + grad.
    # f32 is the correctness gate (a wrong backward shows up at O(1));
    # bf16 is the integration smoke test — its loose tol absorbs
    # rounding-path divergence (both paths correct to bf16, different
    # rounding order) amplified by cancellation in small leaves.
    from distributed_tensorflow_tpu.models import transformer as tfm

    for tdt, tf_fwd, tf_grad in (("float32", 1e-2, 2e-2),
                                 ("bfloat16", 3e-2, 2.5e-1)):
        tkw = dict(vocab_size=256, max_len=128, num_layers=2, d_model=128,
                   num_heads=4, d_ff=256, dropout=0.0, causal=True,
                   pre_ln=True, dtype=tdt)
        t_std = tfm.Transformer(tfm.TransformerConfig(**tkw))
        t_f = tfm.Transformer(
            tfm.TransformerConfig(fused_ln_matmul=True, **tkw))
        ids = jnp.asarray(r.randint(0, 256, (4, 128)), jnp.int32)
        tparams = t_std.init(jax.random.PRNGKey(1), ids,
                             train=False)["params"]

        def lm_loss(m):
            def go(p):
                logits = m.apply({"params": p}, ids, train=False)
                return (logits.astype(jnp.float32) ** 2).mean()
            return go

        ok &= compare_models(f"transformer fused-LN [{tdt}]", lm_loss(t_f),
                             lm_loss(t_std), tparams, tf_fwd, tf_grad)

    # one fused bottleneck vs the standard flax block, train fwd + grad
    from distributed_tensorflow_tpu.models import common
    from distributed_tensorflow_tpu.models.resnet import ResNet50, ResNetConfig

    for rdt, r_fwd, r_grad in (("float32", 1e-2, 2e-2),
                               ("bfloat16", 3e-2, 2.5e-1)):
        kw = dict(stage_sizes=(1,), width=16, num_classes=10, dtype=rdt)
        m_std = ResNet50(ResNetConfig(**kw))
        m_f = ResNet50(ResNetConfig(block_impl="fused", **kw))
        params, mstate = common.make_init_fn(m_std, (32, 32, 3))(
            jax.random.PRNGKey(0)
        )
        # Perturb away from init: the zero-init bn3 gamma (resnet.py:84)
        # makes every upstream grad in the residual branch exactly zero at
        # init, so the per-leaf comparison would be vacuous there.
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
        params = jax.tree.unflatten(treedef, [
            l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
            for l, k in zip(leaves, keys)
        ])
        xb = jnp.asarray(r.randn(8, 32, 32, 3), jnp.float32)

        def loss_model(m):
            def go(p):
                out, _ = m.apply({"params": p, **mstate}, xb, train=True,
                                 mutable=["batch_stats"])
                return (out.astype(jnp.float32) ** 2).mean()
            return go

        ok &= compare_models(f"resnet fused-block [{rdt}]", loss_model(m_f),
                             loss_model(m_std), params, r_fwd, r_grad)

    ok &= bench_shape_sweep(r)

    print("ALL OK" if ok else "FAILURES", flush=True)
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
