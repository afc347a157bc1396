#!/usr/bin/env python
"""Embedding-tier benchmark: Wide&Deep CTR training throughput on the
available chip(s) — the BASELINE.json:11 workload family, same honest
timing contract as bench.py / bench_bert.py (value-fetch sync, steady-
state window after warmup).

Recommender steps are gather/scatter- and bandwidth-dominated, not
MXU-dominated: alongside examples/sec the row reports the analytic
embedding bytes moved per example and the implied achieved HBM rate, the
roofline that actually binds this family. With a model axis (virtual
mesh or multi-chip), the vocab-sharded tables exercise the all_to_all /
collective lookup path (ops/embedding.py).

Prints ONE JSON line to stdout; diagnostics to stderr.

Env knobs:
  BENCH_BATCH        PER-CHIP batch (default 16384 on TPU, 256 on CPU)
  BENCH_STEPS        measured steps (default 20)
  BENCH_WD_VOCAB     per-feature vocab size (default 100000 TPU, 1024 CPU)
  BENCH_WD_FEATURES  number of categorical features (default 26, Criteo)
  BENCH_WD_EMBED     embedding dim (default 64 TPU, 8 CPU)
  BENCH_MESH_MODEL   model-axis size for embedding parallelism (default 1;
                     data axis takes the rest of the devices)
  BENCH_EMBED_IMPL   "take" (GSPMD lookup, default) | "explicit"
                     (range-sharded shard_map lookup)
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main() -> None:
    import jax

    from distributed_tensorflow_tpu.utils import benchmarking as bm

    import numpy as np

    from distributed_tensorflow_tpu.models import wide_deep as wd
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh, describe
    from distributed_tensorflow_tpu.parallel import sharding as sh
    from distributed_tensorflow_tpu.train import (
        StepOptions, init_train_state, jit_train_step, make_train_step,
    )
    from distributed_tensorflow_tpu.utils import flops as flops_lib
    from distributed_tensorflow_tpu.workloads.wide_deep import _canonical_tx
    from distributed_tensorflow_tpu.workloads.runner import RunConfig
    from distributed_tensorflow_tpu.train import OptimizerConfig

    devices, n_chips, platform, on_tpu = bm.describe_devices()
    log(f"bench devices: {devices} (platform={platform})")

    n_feat = int(os.environ.get("BENCH_WD_FEATURES", "26"))
    vocab = int(os.environ.get("BENCH_WD_VOCAB",
                               "100000" if on_tpu else "1024"))
    embed = int(os.environ.get("BENCH_WD_EMBED", "64" if on_tpu else "8"))
    per_chip_batch = int(os.environ.get(
        "BENCH_BATCH", "16384" if on_tpu else "256"))
    model_axis = int(os.environ.get("BENCH_MESH_MODEL", "1"))
    embed_impl = os.environ.get("BENCH_EMBED_IMPL", "take")
    global_batch = per_chip_batch * n_chips

    cfg = wd.WideDeepConfig(
        vocab_sizes=(vocab,) * n_feat,
        embed_dim=embed,
        dense_features=13,
        hidden_sizes=(1024, 512, 256) if on_tpu else (64, 32),
        embed_impl=embed_impl,
    )
    mesh = build_mesh(MeshSpec(data=-1, model=model_axis))
    log(f"mesh: {describe(mesh)}  tables={n_feat}x{vocab}x{embed} "
        f"embed_impl={embed_impl} global_batch={global_batch}")

    model = wd.WideDeep(cfg, mesh)
    # canonical FTRL-wide / AdaGrad-deep split, same as the workload
    run_cfg = RunConfig(model=cfg, optimizer=OptimizerConfig(
        name="auto", learning_rate=0.05))
    tx = _canonical_tx(run_cfg)
    assert tx is not None
    state, specs = init_train_state(
        wd.make_init_fn(cfg, mesh), tx, mesh, jax.random.PRNGKey(0),
        param_rules=wd.WIDE_DEEP_RULES,
    )
    step = jit_train_step(
        make_train_step(wd.ctr_loss_fn(model), tx, StepOptions()),
        mesh, specs,
    )

    rng = np.random.RandomState(0)
    from jax.sharding import NamedSharding

    batch_np = {
        "cat": rng.randint(0, vocab, (global_batch, n_feat)).astype(np.int32),
        "dense": rng.randn(global_batch, 13).astype(np.float32),
        "label": (rng.rand(global_batch) > 0.5).astype(np.float32),
    }
    batch = jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, sh.batch_spec(np.ndim(x)))
        ),
        batch_np,
    )

    measured = int(os.environ.get("BENCH_STEPS", "20"))
    state, steps_per_sec, final_loss = bm.timed_steps(
        step, state, lambda: batch, warmup=3, measured=measured, log=log,
    )
    examples_per_sec_per_chip = steps_per_sec * global_batch / n_chips

    # Embedding-traffic roofline context (analytic, f32 tables): fwd
    # gather read + bwd scatter-add read-modify-write of the same rows
    # (3x total) for deep tables + the 1-wide columns, both per feature.
    bytes_per_example = 3 * n_feat * (embed + 1) * 4
    embed_gbps = examples_per_sec_per_chip * bytes_per_example / 1e9
    # shared MFU helper (obs/goodput.py): applies the fwd+bwd multiplier
    from distributed_tensorflow_tpu.obs import goodput

    # no peak for this device kind (the explicit CPU run) → no MFU
    peak = flops_lib.peak_flops_per_chip(devices[0]) if on_tpu else None
    mfu = goodput.train_mfu(
        wd.flops_per_example(cfg) * global_batch, steps_per_sec,
        n_chips=n_chips, peak_per_chip=peak,
    ) if peak else None
    log(f"steps/sec={steps_per_sec:.3f} "
        f"examples/sec/chip={examples_per_sec_per_chip:.0f} "
        f"embed-traffic={embed_gbps:.1f} GB/s MFU={mfu}")

    # vs_baseline for THIS family is achieved-vs-spec HBM bandwidth, not
    # MFU/0.50: the module docstring's own roofline argument — comparing
    # a gather/scatter-bound workload's MFU to the ResNet MXU target is
    # a misleading datum. 819 GB/s = v5e HBM spec (tools/bench_hbm.py);
    # under the explicit CPU request the spec doesn't apply and the
    # field reports 0.0 (full_size_model already flags the row).
    print(json.dumps({
        "metric": "wide_deep_examples_per_sec_per_chip",
        "value": round(examples_per_sec_per_chip, 1),
        "unit": "examples/sec/chip",
        "vs_baseline": round(embed_gbps / 819.0, 4) if on_tpu else 0.0,
        "vs_baseline_basis": "embed_traffic_gbps / 819 GB/s v5e HBM spec",
        "mfu": round(mfu, 4) if mfu else None,
        "platform": platform,
        "n_chips": n_chips,
        "global_batch": global_batch,
        "tables": n_feat,
        "vocab_size": vocab,
        "embed_dim": embed,
        "embed_impl": embed_impl,
        "mesh_model_axis": model_axis,
        "embed_bytes_per_example": bytes_per_example,
        "embed_traffic_gbps": round(embed_gbps, 2),
        "full_size_model": bool(on_tpu),
    }))


if __name__ == "__main__":
    main()
