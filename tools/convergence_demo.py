#!/usr/bin/env python
"""Convergence demonstration on REAL decoded JPEG data.

Pushes a real image-classification dataset through the framework's whole
production path: JPEG record files -> JpegClassificationDataset decode +
augment -> examples/train.py-equivalent run (Trainer, checkpoints,
TensorBoard events) -> standalone eval from the checkpoint.

Data: scikit-learn's bundled `load_digits` (1,797 real 8x8 handwritten
digit scans — the only real image dataset available in this zero-egress
image). Images are upscaled to 32x32 RGB and JPEG-encoded; a 1500/297
train/eval split keeps eval held out. The CNN family (cifar10_cnn
workload) trains on the decoded stream. Chance is 10%; the committed gate
asserts >=90% held-out top-1, demonstrating the BASELINE.json:2 top-1
machinery end to end (decode, augment, train, checkpoint, restore, eval).

Usage:  python tools/convergence_demo.py [--steps N] [--workdir DIR]
Prints one JSON line: {"train_acc":..,"eval_top1":..,"steps":..}.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_records(workdir: str) -> tuple[str, str]:
    import numpy as np
    from PIL import Image
    from sklearn.datasets import load_digits

    from distributed_tensorflow_tpu.data.jpeg_records import (
        make_jpeg_record_file,
    )

    digits = load_digits()
    imgs8 = (digits.images / 16.0 * 255.0).astype(np.uint8)  # [N, 8, 8]
    rng = np.random.RandomState(0)
    order = rng.permutation(len(imgs8))
    imgs8, labels = imgs8[order], digits.target[order]

    def upscale(batch):
        out = np.empty((len(batch), 32, 32, 3), np.uint8)
        for i, im in enumerate(batch):
            big = np.asarray(
                Image.fromarray(im, "L").resize((32, 32), Image.BILINEAR)
            )
            out[i] = big[..., None].repeat(3, axis=-1)
        return out

    n_train = 1500
    train = os.path.join(workdir, "digits_train")
    evalp = os.path.join(workdir, "digits_eval")
    make_jpeg_record_file(train, upscale(imgs8[:n_train]), labels[:n_train])
    make_jpeg_record_file(evalp, upscale(imgs8[n_train:]), labels[n_train:])
    print(f"records: {n_train} train / {len(imgs8) - n_train} eval "
          f"real digit scans -> {workdir}", file=sys.stderr)
    return train, evalp


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--workdir", default="/tmp/convergence_demo")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--min-top1", type=float, default=0.9,
                    help="held-out accuracy gate (lower it for smoke runs)")
    args = ap.parse_args()

    os.makedirs(args.workdir, exist_ok=True)
    train_rec, eval_rec = build_records(args.workdir)

    from distributed_tensorflow_tpu import workloads

    ckdir = os.path.join(args.workdir, "ck")
    common = [
        f"--data.image_size=32", "--data.channels=3",
        "--data.num_classes=10",
        f"--data.global_batch_size={args.batch}",
        "--mesh.data=-1",
    ]
    log_every = max(1, min(50, args.steps // 4))
    result = workloads.run_workload("cifar10_cnn", [
        f"--data.dataset=jpeg:{train_rec}",
        f"--train.num_steps={args.steps}",
        f"--train.log_every={log_every}",
        f"--optimizer.total_steps={args.steps}",
        "--optimizer.learning_rate=0.02",
        f"--checkpoint.directory={ckdir}",
        "--train.eval_batches=2",
        *common,
    ])
    train_acc = float(result.history[-1].get("accuracy", 0.0))

    # standalone eval from the checkpoint on the HELD-OUT record pair —
    # the examples/eval.py path
    eval_metrics = workloads.eval_workload("cifar10_cnn", [
        f"--data.dataset=jpeg:{eval_rec}",
        f"--checkpoint.directory={ckdir}",
        "--train.eval_batches=2",
        *common,
    ])
    top1 = float(eval_metrics.get("accuracy", 0.0))
    print(json.dumps({
        "train_acc": round(train_acc, 4),
        "eval_top1": round(top1, 4),
        "steps": args.steps,
        "dataset": "sklearn load_digits (real scans), 1500/297 split",
    }))
    if top1 < args.min_top1:
        raise SystemExit(
            f"held-out top-1 {top1:.3f} < {args.min_top1} gate")


if __name__ == "__main__":
    main()
