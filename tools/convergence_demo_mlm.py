#!/usr/bin/env python
"""Real-text convergence demo for the transformer family, end to end —
REAL English prose (this repo's own *.md documentation, the only genuine
text corpus in a zero-egress image) -> tools/make_token_file.py byte
tokenizer -> the token-file streams -> training -> standalone eval
restore -> held-out accuracy. Two objectives share the harness:

  --objective=mlm (default)  bert_pretrain over `tokens_mlm:`
      (TokenFileMLM 80/10/10 corruption, gathered positions); gate on
      held-out masked-byte accuracy.
  --objective=lm             gpt_lm over `tokens:` (TokenFileLM causal
      windows); gate on held-out next-byte accuracy.

Character-level MLM with bidirectional context is genuinely learnable
(English orthography), so the gate is meaningful: unigram guessing
tops out ~13% ('e'/space), while a trained model recovers masked bytes
from both-side context far above that. A broken tokenizer, masking
stream, gathered-head path, or checkpoint restore all drop the score
back toward the unigram floor.

Usage: python tools/convergence_demo_mlm.py [--steps 400] [--min-acc 0.35]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

VOCAB, MASK = 261, 260  # byte tokenizer: 256 bytes + 5 specials
# --long configuration, defined ONCE (CLI args + artifact stamp share it)
LONG_MESH_SEQ, LONG_SEQ_IMPL = 4, "ring"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1600)
    ap.add_argument("--objective", choices=("mlm", "lm"), default=None)
    ap.add_argument("--min-acc", type=float, default=None,
                    help="held-out accuracy gate (unigram floor ~0.13); "
                         "default 0.35, or 0.25 for --long (seq-256 ring "
                         "training converges slower per step — 0.303 "
                         "measured at 3600 steps, artifacts/"
                         "lm_long_ring_r4.json)")
    ap.add_argument("--long", action="store_true",
                    help="long-context SP variant: causal LM at seq 256 "
                         "trained THROUGH ring attention on a seq=4 mesh "
                         "(needs a device count divisible by 4, e.g. "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8) — the SURVEY §5.7 strategy learning "
                         "on real text end to end, not just passing "
                         "parity tests")
    args = ap.parse_args()
    if args.long and args.objective == "mlm":
        ap.error("--long is a causal-LM variant; drop --objective=mlm")
    if args.objective is None:
        args.objective = "lm" if args.long else "mlm"
    if args.min_acc is None:
        args.min_acc = 0.25 if args.long else 0.35

    from distributed_tensorflow_tpu import workloads

    work = tempfile.mkdtemp(prefix="dtf_mlm_demo_")

    # real prose: every markdown file in the repo (≈100 KB of English),
    # split held-out by FILE so eval text was never seen in training
    mds = sorted(
        glob.glob(os.path.join(REPO, "*.md"))
        + glob.glob(os.path.join(REPO, "docs", "*.md"))
    )
    if len(mds) < 4:
        raise SystemExit(f"need >= 4 .md files, found {len(mds)}")
    eval_files, train_files = mds[::4], [m for m in mds if m not in mds[::4]]

    for out, files in (("train.npy", train_files), ("eval.npy", eval_files)):
        subprocess.run(
            [sys.executable, os.path.join(REPO, "tools/make_token_file.py"),
             os.path.join(work, out), *files],
            check=True, capture_output=True,
        )

    mlm = args.objective == "mlm"
    workload = "bert_pretrain" if mlm else "gpt_lm"
    prefix = "tokens_mlm" if mlm else "tokens"
    seq = 256 if args.long else 64
    common = [
        f"--data.vocab_size={VOCAB}",
        f"--data.seq_len={seq}",
        f"--data.global_batch_size={16 if args.long else 64}",
        *(
            [f"--data.mask_token={MASK}", "--data.max_predictions=10"]
            if mlm else []
        ),
        f"--model.vocab_size={VOCAB}",
        "--model.num_layers=3",
        "--model.d_model=128",
        "--model.num_heads=4",
        "--model.d_ff=256",
        f"--model.max_len={seq}",
        "--mesh.model=1",
        *(
            # ring attention over a real seq axis + remat, the long-
            # context preset's exact configuration at demo scale; data=-1
            # absorbs whatever device count the rig has beyond seq=4
            [f"--mesh.seq={LONG_MESH_SEQ}", "--mesh.data=-1",
             f"--model.seq_impl={LONG_SEQ_IMPL}", "--model.remat=true"]
            if args.long else ["--mesh.data=-1"]
        ),
    ]
    ckdir = os.path.join(work, "ck")
    result = workloads.run_workload(workload, [
        f"--data.dataset={prefix}:{work}/train.npy",
        f"--train.num_steps={args.steps}",
        f"--train.log_every={min(50, args.steps)}",
        "--train.eval_batches=0",
        f"--checkpoint.directory={ckdir}",
        "--checkpoint.async_save=false",
        "--checkpoint.save_on_preemption=false",
        "--optimizer.learning_rate=0.003",
        *common,
    ])

    eval_metrics = workloads.eval_workload(workload, [
        f"--data.dataset={prefix}:{work}/eval.npy",
        f"--checkpoint.directory={ckdir}",
        "--train.eval_batches=5",
        *common,
    ])
    acc = float(eval_metrics.get("accuracy", 0.0))
    print(json.dumps({
        "objective": "lm_long_ring" if args.long else args.objective,
        "train_loss": round(float(result.history[-1]["loss"]), 4),
        "eval_masked_acc" if mlm else "eval_next_byte_acc": round(acc, 4),
        "steps": args.steps,
        **({"seq_len": seq, "mesh_seq": LONG_MESH_SEQ,
            "seq_impl": LONG_SEQ_IMPL, "remat": True}
           if args.long else {}),
        "dataset": f"repo .md prose, byte-tokenized; "
                   f"{len(train_files)} train / {len(eval_files)} "
                   f"held-out files",
    }))
    if acc < args.min_acc:
        raise SystemExit(
            f"held-out accuracy {acc:.3f} < {args.min_acc} gate")


if __name__ == "__main__":
    main()
