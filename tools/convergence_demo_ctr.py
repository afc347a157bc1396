#!/usr/bin/env python
"""CTR convergence demo: the full real-data Wide&Deep path, end to end —
teacher-labeled Criteo-FORMAT TSV -> tools/make_ctr_records.py converter
(hashing, log1p, record layout) -> `--data.dataset=ctr:` through the
native record loader -> wide_deep training (FTRL wide / AdaGrad deep) ->
held-out AUC from a separate converted file.

The corpus is synthetic but LEARNABLE (a fixed random teacher over the
hashed categorical ids + dense values labels the clicks), so AUC has
real headroom above 0.5 and the gate is meaningful: a broken hash,
misaligned record layout, or dead embedding gradient path all push AUC
back to ~0.5. The BASELINE.json:11 Wide&Deep config made concrete.

Usage: python tools/convergence_demo_ctr.py [--steps 300] [--min-auc 0.75]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

if jax.config.jax_platforms and "cpu" in str(jax.config.jax_platforms):
    # wide_deep's default mesh is embedding-parallel (model=2): give the
    # CPU rig 8 fake devices (before any backend init) so the demo
    # exercises the real sharded-table path like the test conftest does
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402

N_DENSE, N_CAT, VOCAB = 6, 4, 500


def write_teacher_tsv(path: str, n: int, seed: int) -> None:
    """Criteo-format lines whose labels come from a fixed teacher over
    the HASHED ids — exactly what the converter will reproduce — plus
    the dense values, so the mapping is learnable end to end."""
    from tools.make_ctr_records import hash_token

    r = np.random.RandomState(0)  # teacher fixed across train/eval
    tables = [r.randn(VOCAB) for _ in range(N_CAT)]
    w_dense = r.randn(N_DENSE) * 0.5

    r = np.random.RandomState(seed)  # examples differ per split
    rows = []
    scores = np.empty(n)
    for j in range(n):
        raw_dense = r.randint(0, 100, N_DENSE)
        toks = ["%06x" % r.randint(0, 16**6) for _ in range(N_CAT)]
        ids = [hash_token(t, VOCAB) for t in toks]
        scores[j] = (sum(tables[i][ids[i]] for i in range(N_CAT))
                     + float(np.log1p(raw_dense) @ w_dense))
        rows.append((raw_dense, toks))
    # threshold at the TEACHER's median (fixed from the train seed), not
    # 0: the dense term has an uncentered offset that would otherwise
    # collapse the labels to one class (and AUC to undefined)
    thresh = np.median(scores) if seed == 1 else write_teacher_tsv.thresh
    write_teacher_tsv.thresh = thresh
    with open(path, "w") as f:
        for (raw_dense, toks), sc in zip(rows, scores):
            label = int(sc > thresh)
            f.write("\t".join(
                [str(label)] + [str(v) for v in raw_dense] + toks) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--min-auc", type=float, default=0.75,
                    help="held-out AUC gate (chance = 0.5)")
    args = ap.parse_args()

    from distributed_tensorflow_tpu import workloads

    work = tempfile.mkdtemp(prefix="dtf_ctr_demo_")
    train_tsv = os.path.join(work, "train.txt")
    eval_tsv = os.path.join(work, "eval.txt")
    write_teacher_tsv(train_tsv, 6000, seed=1)
    write_teacher_tsv(eval_tsv, 1500, seed=2)

    for tsv, out in ((train_tsv, "train.dat"), (eval_tsv, "eval.dat")):
        subprocess.run(
            [sys.executable, os.path.join(REPO, "tools/make_ctr_records.py"),
             os.path.join(work, out), tsv,
             "--vocab-size", str(VOCAB), "--n-dense", str(N_DENSE)],
            check=True, capture_output=True,
        )

    vocabs = "[" + ",".join([str(VOCAB)] * N_CAT) + "]"
    common = [
        f"--model.vocab_sizes={vocabs}",
        f"--model.dense_features={N_DENSE}",
        "--model.embed_dim=8",
        "--model.hidden_sizes=[32,16]",
        "--data.global_batch_size=256",
        "--optimizer.learning_rate=0.08",
    ]
    ckdir = os.path.join(work, "ck")
    result = workloads.run_workload("wide_deep", [
        f"--data.dataset=ctr:{work}/train.dat",
        f"--train.num_steps={args.steps}",
        f"--train.log_every={min(50, args.steps)}",
        "--train.eval_batches=0",
        f"--checkpoint.directory={ckdir}",
        "--checkpoint.async_save=false",
        "--checkpoint.save_on_preemption=false",
        *common,
    ])

    eval_metrics = workloads.eval_workload("wide_deep", [
        # explicit held-out eval file => the unprefixed `auc` key (eval
        # drawn from data.dataset would be tagged train_auc)
        f"--data.eval_dataset=ctr:{work}/eval.dat",
        f"--data.dataset=ctr:{work}/train.dat",
        f"--checkpoint.directory={ckdir}",
        "--train.eval_batches=5",
        *common,
    ])
    auc = float(eval_metrics.get("auc", 0.0))
    print(json.dumps({
        "train_loss": round(float(result.history[-1]["loss"]), 4),
        "eval_auc": round(auc, 4),
        "steps": args.steps,
        "dataset": "teacher-labeled Criteo-format TSV via "
                   "make_ctr_records.py, 6000/1500 split",
    }))
    if auc < args.min_auc:
        raise SystemExit(f"held-out AUC {auc:.3f} < {args.min_auc} gate")


if __name__ == "__main__":
    main()
