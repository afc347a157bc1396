#!/usr/bin/env python
"""Benchmark: ResNet-50 training throughput on the available chip(s).

Prints exactly ONE JSON line to stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Metric: ResNet-50 images/sec/chip (the BASELINE.json:2 primary metric),
steady-state window excluding compilation (BASELINE.md reporting rules).
``vs_baseline``: measured MFU / 0.50 — the north-star "≥50% MFU" target
(BASELINE.json:5); the reference publishes no absolute number to compare
against (BASELINE.json:13 "published": {}).

All diagnostics go to stderr; stdout carries only the JSON line. Without
a TPU the script fails; only an explicit ``JAX_PLATFORMS=cpu`` gets the
toy-size plumbing run (tier-1's JSON-contract test), whose row carries
no MFU.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main() -> None:
    import jax

    from distributed_tensorflow_tpu.parallel import cluster
    from distributed_tensorflow_tpu.utils import benchmarking as bm

    cluster.configure_compile_cache()
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_tensorflow_tpu.models import common
    from distributed_tensorflow_tpu.models.resnet import (
        ResNet50, ResNetConfig, flops_per_example,
    )
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh, describe
    from distributed_tensorflow_tpu.parallel import sharding as sh
    from distributed_tensorflow_tpu.train import (
        StepOptions, init_train_state, jit_train_step, make_train_step,
    )
    from distributed_tensorflow_tpu.utils import flops as flops_lib

    # fails without a TPU unless JAX_PLATFORMS=cpu was requested
    # (utils/benchmarking.py, shared with tools/bench_bert.py)
    devices, n_chips, platform, on_tpu = bm.describe_devices()
    log(f"bench devices: {devices} (platform={platform})")

    # Per-chip batch sized for a v5e (16 GiB HBM) bf16 train step; tiny
    # under the explicit CPU request so the plumbing run finishes fast.
    per_chip_batch = int(os.environ.get("BENCH_BATCH", "256" if on_tpu else "8"))
    image = 224 if on_tpu else 64
    # space-to-depth stem + bf16 BN output measured +28% over the naive
    # config on a v5e, and batch 256/chip was the knee (384/512/1024 all
    # slower per image) — previous toolchain, PERF.md "Earlier chip
    # findings". The BENCH_* env knobs exist so tools/ablate_resnet.py can
    # sweep variants through THIS harness instead of duplicating it.
    stem = os.environ.get("BENCH_STEM", "space_to_depth" if on_tpu else "conv")
    norm_dtype = os.environ.get("BENCH_NORM_DTYPE") or None
    global_batch = per_chip_batch * n_chips

    mesh = build_mesh(MeshSpec(data=-1))
    log(f"mesh: {describe(mesh)}  global_batch={global_batch}  image={image}")

    from distributed_tensorflow_tpu.train import OptimizerConfig, make_optimizer
    from jax.sharding import NamedSharding

    rng = np.random.RandomState(0)
    measured = int(os.environ.get("BENCH_STEPS", "20"))
    dbg = os.environ.get("BENCH_DEBUG_METRICS", "0") == "1"

    def make_cfg(block_impl):
        return (
            ResNetConfig(stem=stem, norm_dtype=norm_dtype,
                         block_impl=block_impl)
            if on_tpu
            else ResNetConfig(
                stage_sizes=(1, 1, 1, 1), width=16, num_classes=100,
                dtype="float32", stem=stem, norm_dtype=norm_dtype,
                block_impl=block_impl,
            )
        )

    def measure_resident(block_impl):
        """Build model+state+step for one block impl and time the
        resident-batch window. Returns (cfg, state, step, steps/sec)."""
        cfg = make_cfg(block_impl)
        model = ResNet50(cfg, mesh)
        loss_fn = common.classification_loss_fn(model)
        # the exact optimizer the resnet50_imagenet workload uses
        # (coupled L2 on kernels, fused into the update pass)
        tx = make_optimizer(OptimizerConfig(
            name="momentum", learning_rate=0.1, momentum=0.9,
            weight_decay=1e-4,
        ))
        state, specs = init_train_state(
            common.make_init_fn(model, (image, image, 3)), tx, mesh,
            jax.random.PRNGKey(0),
        )
        step = jit_train_step(
            make_train_step(loss_fn, tx, StepOptions(
                compute_grad_norm=dbg, check_grads_finite=dbg)),
            mesh, specs,
        )
        batch = {
            # bf16 images on TPU: halves host->HBM bytes; the first conv
            # casts anyway
            "image": rng.randn(global_batch, image, image, 3)
            .astype(np.float32)
            .astype(jnp.bfloat16 if on_tpu else np.float32),
            "label": rng.randint(0, cfg.num_classes, global_batch)
            .astype(np.int32),
        }
        batch = jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(mesh, sh.batch_spec(np.ndim(x)))
            ),
            batch,
        )
        # the timing window ends on a fetched value:
        # utils/benchmarking.timed_steps, shared with tools/bench_bert.py
        state, steps_per_sec, _ = bm.timed_steps(
            step, state, lambda: batch, warmup=3, measured=measured,
            log=lambda m: log(f"[{block_impl}] {m}"),
        )
        return cfg, state, step, steps_per_sec

    pinned_impl = os.environ.get("BENCH_BLOCK_IMPL")
    # BENCH_FORCE_AB=1: run the A/B selection on CPU too (plumbing test)
    force_ab = os.environ.get("BENCH_FORCE_AB") == "1"
    alt = None  # (impl, steps_per_sec) of the losing variant, if A/B'd
    if pinned_impl or (not on_tpu and not force_ab):
        impl = pinned_impl or "standard"
        cfg, state, step, steps_per_sec = measure_resident(impl)
    else:
        # Unpinned on TPU: time BOTH block impls and report the faster —
        # a default that has never been timed end-to-end must not be
        # able to silently regress the headline number. A variant that
        # fails to compile or run fails the bench. Each probe variant is
        # FREED before the next build (per-chip batch 256 is the HBM
        # knee; a second resident train state would bias the
        # comparison), then the winner is rebuilt fresh for the headline
        # + fed windows.
        def probe(impl):
            rate = measure_resident(impl)[3]
            jax.clear_caches()  # drop the probe's executables/buffers
            return rate

        rates = {impl: probe(impl) for impl in ("fused", "standard")}
        winner = max(rates, key=rates.get)
        loser = {"fused": "standard", "standard": "fused"}[winner]
        alt = (loser, rates[loser])
        log(f"block-impl A/B: fused={rates['fused']} "
            f"standard={rates['standard']} -> {winner}")
        cfg, state, step, steps_per_sec = measure_resident(winner)
    images_per_sec = steps_per_sec * global_batch
    images_per_sec_per_chip = images_per_sec / n_chips

    # ---- pipeline-fed window --------------------------------------------
    # Same jit step, but every batch flows host->device through the
    # Prefetcher. Two modes:
    #   default   — K pre-staged bf16 numpy batches (transfer + dispatch
    #               overlap is what's being proven; decode outside)
    #   BENCH_DATA=jpeg — every batch decodes from a JPEG record file
    #               built at setup (decode INSIDE the measured window,
    #               through the production JpegClassificationDataset
    #               thread-pool path)
    from distributed_tensorflow_tpu.data import Prefetcher

    img_dtype = jnp.bfloat16 if on_tpu else np.float32
    fed_data = os.environ.get("BENCH_DATA", "synthetic")
    if fed_data == "jpeg":
        import tempfile

        from distributed_tensorflow_tpu.data.jpeg_records import (
            JpegClassificationDataset, make_jpeg_record_file,
        )

        n_src = max(512, 2 * global_batch)
        src_size = image + 32  # decode-then-crop, the ImageNet shape flow
        # JPEG-compressible synthetic content (8x block upsample): pure
        # noise would decode slower than any real photo; blocks land
        # between noise and natural-image decode cost
        small = rng.randint(0, 255, (n_src, src_size // 8, src_size // 8, 3))
        src_imgs = np.kron(
            small, np.ones((1, 8, 8, 1), np.uint8)
        ).astype(np.uint8)[:, :src_size, :src_size]
        rec = os.path.join(tempfile.mkdtemp(prefix="bench_jpeg_"), "rec")
        make_jpeg_record_file(rec, src_imgs, rng.randint(
            0, cfg.num_classes, n_src))
        ds = JpegClassificationDataset(rec, image, global_batch, train=True)
        # standalone host decode rate: the fed window's ceiling is
        # min(device rate, this), so a low fed efficiency on a host with
        # few cores reads as HOST-bound, not a framework defect — this
        # number disambiguates.
        import time as _time

        ds.batch(0)  # warm pool/caches
        t0 = _time.perf_counter()
        ds.batch(1)
        host_decode_rate = global_batch / (_time.perf_counter() - t0)
        log(f"jpeg-fed: {n_src} records at {src_size}px -> decode+augment "
            f"to {image}px inside the measured window "
            f"(decoder={ds.decoder}, host decode "
            f"{host_decode_rate:.0f} img/s on {os.cpu_count()} cores)")
        fed_data = f"jpeg/{ds.decoder}"

        def host_stream():
            i = 0
            while True:
                b = ds.batch(i)
                b["image"] = b["image"].astype(img_dtype)
                yield b
                i += 1

        # shardings only need shapes/dtypes — don't pay a decode here
        probe = {
            "image": np.zeros((global_batch, image, image, 3), img_dtype),
            "label": np.zeros((global_batch,), np.int32),
        }
    else:
        host_batches = []
        for k in range(4):
            host_batches.append({
                "image": rng.randn(global_batch, image, image, 3)
                .astype(np.float32).astype(img_dtype),
                "label": rng.randint(0, cfg.num_classes, global_batch)
                .astype(np.int32),
            })

        def host_stream():
            i = 0
            while True:
                yield host_batches[i % len(host_batches)]
                i += 1

        probe = host_batches[0]

    shardings = jax.tree.map(
        lambda x: NamedSharding(mesh, sh.batch_spec(np.ndim(x))),
        probe,
    )
    # BENCH_PUT_SYNC=1: force each transfer to COMPLETE inside the
    # prefetch thread (block_until_ready on the put) instead of lazily at
    # step dispatch — the A/B knob for a low fed efficiency
    put_sync = os.environ.get("BENCH_PUT_SYNC") == "1"

    def put(b):
        dev = jax.tree.map(jax.device_put, b, shardings)
        if put_sync:
            jax.block_until_ready(dev)
        return dev

    fed = iter(Prefetcher(host_stream(), depth=2, transform=put))
    state, fed_steps_per_sec, _ = bm.timed_steps(
        step, state, lambda: next(fed), warmup=2, measured=measured, log=log,
    )
    fed_images_per_sec_per_chip = fed_steps_per_sec * global_batch / n_chips
    pipeline_efficiency = fed_steps_per_sec / steps_per_sec
    log(f"pipeline-fed: steps/sec={fed_steps_per_sec:.3f} "
        f"({pipeline_efficiency:.1%} of resident-batch)")
    # flops_per_example is fwd-only (framework contract, utils/flops.py);
    # the SHARED helper obs/goodput.train_mfu applies the fwd+bwd
    # multiplier and publishes the `mfu` gauge into the process registry,
    # so this JSON line and a scrape can never disagree.
    from distributed_tensorflow_tpu.obs import goodput
    from distributed_tensorflow_tpu.obs.registry import default_registry

    # no peak for this device kind (the explicit CPU run) → no MFU
    peak = flops_lib.peak_flops_per_chip(devices[0]) if on_tpu else None
    mfu = goodput.train_mfu(
        flops_per_example(cfg, image) * global_batch, steps_per_sec,
        n_chips=n_chips, peak_per_chip=peak, registry=default_registry(),
    ) if peak else None
    log(f"steps/sec={steps_per_sec:.3f} images/sec/chip={images_per_sec_per_chip:.1f} "
        f"MFU={mfu} (peak={peak})")

    # provenance block (obs/scaling.py): every row names the platform,
    # device kind and count it was taken on
    from distributed_tensorflow_tpu.obs import scaling

    print(json.dumps(scaling.stamp_provenance({
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(images_per_sec_per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(mfu / 0.50, 4) if mfu else None,
        "mfu": round(mfu, 4) if mfu else None,
        "platform": platform,
        "n_chips": n_chips,
        "global_batch": global_batch,
        "image_size": image,
        "full_resnet50": bool(on_tpu),
        "stem": cfg.stem,
        "norm_dtype": cfg.norm_dtype or cfg.dtype,
        "block_impl": cfg.block_impl,
        "pipeline_fed_images_per_sec_per_chip":
            round(fed_images_per_sec_per_chip, 2),
        "pipeline_efficiency": round(pipeline_efficiency, 4),
        "fed_data": fed_data,
        **({"alt_block_impl": alt[0],
            "alt_images_per_sec_per_chip":
                round(alt[1] * global_batch / n_chips, 2)}
           if alt else {}),
        **({"host_decode_images_per_sec": round(host_decode_rate, 1),
            "host_cores": os.cpu_count()}
           if fed_data.startswith("jpeg") else {}),
    }, mesh)))


if __name__ == "__main__":
    main()
