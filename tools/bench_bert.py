#!/usr/bin/env python
"""Secondary benchmark: BERT-base MLM training throughput + MFU on the
available chip(s) — the BASELINE.json:10 workload, same honest timing
contract as the flagship bench.py (value-fetch sync, steady-state window
after warmup). Transformers are matmul-dominated, so unlike bandwidth-
bound ResNet-50 this measures how close the framework gets to the MXU
roofline.

Prints ONE JSON line to stdout; diagnostics to stderr.

Env knobs:
  BENCH_BATCH       PER-CHIP batch (default 128 on TPU, 8 on CPU) —
                    same semantics as the flagship bench.py
  BENCH_SEQ         sequence length (default 512, the reference's config)
  BENCH_STEPS       measured steps (default 20)
  BENCH_MODEL       "bert" (post-LN encoder MLM, default) | "gpt"
                    (pre-LN causal LM — the fused-LN showcase)
  BENCH_FUSED_LN    "1" to fuse LayerNorm into matmuls (pre-LN only,
                    i.e. BENCH_MODEL=gpt)
  BENCH_REMAT       "1" to jax.checkpoint each block (fit bigger batches)
  BENCH_ATTN        attention impl: "auto" (flash on TPU) | "dense" |
                    "blockwise" | "flash" — flash-vs-XLA-dense on chip
  BENCH_FUSED_QKV   "1" to project q/k/v with one [d, 3d] matmul
                    (megatron-style fused QKV) instead of three [d, d]
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

    import dataclasses

    import numpy as np

    from distributed_tensorflow_tpu.data.text import IGNORE_INDEX
    from distributed_tensorflow_tpu.models import transformer as tfm
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh, describe
    from distributed_tensorflow_tpu.parallel import sharding as sh
    from distributed_tensorflow_tpu.train import (
        OptimizerConfig, StepOptions, init_train_state, jit_train_step,
        make_optimizer, make_train_step,
    )
    from distributed_tensorflow_tpu.utils import flops as flops_lib

    devices, n_chips, platform, on_tpu = bm.describe_devices()
    log(f"bench devices: {devices} (platform={platform})")

    which = os.environ.get("BENCH_MODEL", "bert")
    fused_ln = os.environ.get("BENCH_FUSED_LN", "0") == "1"
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    seq = int(os.environ.get("BENCH_SEQ", "512"))
    # per-chip, like bench.py: the number scales with slice size instead
    # of silently shrinking per chip. GPT's default is smaller than
    # BERT's because the causal LM loss materializes full [B, S, vocab]
    # logits (every position is a target — no gathered head): at
    # B=128, S=512, V=50304 that is 13 GB in f32 before the backward,
    # far over a v5e's HBM. B=32 bounds the logits tier at ~8 GB
    # (bf16 + f32 + dlogits); BENCH_BATCH probes the knee either way.
    default_batch = ("8" if not on_tpu
                     else "32" if which == "gpt" else "128")
    per_chip_batch = int(os.environ.get("BENCH_BATCH", default_batch))
    global_batch = per_chip_batch * n_chips

    if which == "bert":
        cfg = tfm.bert_base()
        if fused_ln:
            raise SystemExit("BENCH_FUSED_LN needs BENCH_MODEL=gpt "
                             "(BERT is post-LN; the kernel is pre-LN-only)")
    elif which == "gpt":
        cfg = tfm.gpt_small(causal_len=max(seq, 512))
        cfg = dataclasses.replace(cfg, fused_ln_matmul=fused_ln)
    else:
        raise SystemExit(f"unknown BENCH_MODEL={which!r}")
    if not on_tpu:  # tiny fallback so the CPU smoke run finishes fast
        cfg = dataclasses.replace(
            cfg, num_layers=2, d_model=128, num_heads=4, d_ff=256,
            vocab_size=1024, max_len=max(seq, 128), dtype="float32",
        )
    attn = os.environ.get("BENCH_ATTN", "auto")
    fused_qkv = os.environ.get("BENCH_FUSED_QKV", "0") == "1"
    # BENCH_HEAD_DTYPE=bfloat16 runs the tied-embedding vocab projection
    # on the fast MXU tier (f32 accumulation) — the ~25-30%-of-FLOPs
    # GPT head currently runs f32 at ~1/4 rate; f32 default = exact path
    head_dtype = os.environ.get("BENCH_HEAD_DTYPE", "float32")
    cfg = dataclasses.replace(cfg, remat=remat, attention_impl=attn,
                              fused_qkv=fused_qkv, head_dtype=head_dtype)
    if seq > cfg.max_len:
        raise SystemExit(f"BENCH_SEQ={seq} > max_len={cfg.max_len}")

    mesh = build_mesh(MeshSpec(data=-1))
    log(f"mesh: {describe(mesh)}  model={which} fused_ln={fused_ln} "
        f"attn={attn} seq={seq} global_batch={global_batch}")

    model = tfm.Transformer(cfg, mesh)
    # BENCH_XENT_CHUNK (gpt only): chunk size for the sequence-chunked
    # causal-LM loss — default 128 keeps peak logits memory at
    # [B, 128, vocab] instead of [B, S, vocab]; 0 = dense loss A/B.
    # The default only engages when it divides BENCH_SEQ (a default must
    # not make previously-valid seq lengths fail); an explicit env value
    # stays strict and raises on non-dividing shapes.
    default_chunk = "128" if which == "gpt" and seq % 128 == 0 else "0"
    xent_chunk = int(os.environ.get("BENCH_XENT_CHUNK", default_chunk))
    loss_fn = tfm.mlm_loss_fn(model) if which == "bert" \
        else tfm.causal_lm_loss(model, xent_chunk)
    tx = make_optimizer(OptimizerConfig(
        name="adamw", learning_rate=1e-4, weight_decay=0.01,
    ))
    state, specs = init_train_state(
        tfm.make_init_fn(model, seq), tx, mesh, jax.random.PRNGKey(0),
        param_rules=tfm.transformer_rules(cfg),
    )
    step = jit_train_step(
        make_train_step(loss_fn, tx, StepOptions()), mesh, specs,
    )

    rng = np.random.RandomState(0)
    from jax.sharding import NamedSharding

    ids = rng.randint(0, cfg.vocab_size, (global_batch, seq)).astype(np.int32)
    batch = {"input_ids": ids}
    if which == "bert":
        if os.environ.get("BENCH_MLM_DENSE") == "1":
            # legacy dense-labels head: vocab projection on all seq
            # positions (the pre-gather behavior, kept for ablation)
            batch["labels"] = np.where(
                rng.rand(global_batch, seq) < 0.15, ids, IGNORE_INDEX
            ).astype(np.int32)
        else:
            # gathered MLM head — the bert_pretrain workload default;
            # K from the ONE definition of the auto rule
            from distributed_tensorflow_tpu.data.text import (
                TextDataConfig, resolved_max_predictions,
            )

            K = resolved_max_predictions(
                TextDataConfig(seq_len=seq, max_predictions=-1))
            pos = np.sort(
                np.argsort(rng.rand(global_batch, seq), axis=1)[:, :K],
                axis=1,
            ).astype(np.int32)
            batch["masked_positions"] = pos
            batch["masked_labels"] = np.take_along_axis(ids, pos, axis=1)
    batch = jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, sh.batch_spec(np.ndim(x)))
        ),
        batch,
    )

    measured = int(os.environ.get("BENCH_STEPS", "20"))
    state, steps_per_sec, final_loss = bm.timed_steps(
        step, state, lambda: batch, warmup=3, measured=measured, log=log,
    )
    examples_per_sec_per_chip = steps_per_sec * global_batch / n_chips
    n_pred = (batch["masked_positions"].shape[1]
              if "masked_positions" in batch else None)
    # shared MFU helper (obs/goodput.py): applies the fwd+bwd multiplier
    from distributed_tensorflow_tpu.obs import goodput

    # no peak for this device kind (the explicit CPU run) → no MFU
    peak = flops_lib.peak_flops_per_chip(devices[0]) if on_tpu else None
    mfu = goodput.train_mfu(
        tfm.flops_per_example(cfg, seq, n_predictions=n_pred) * global_batch,
        steps_per_sec, n_chips=n_chips, peak_per_chip=peak,
    ) if peak else None
    log(f"steps/sec={steps_per_sec:.3f} "
        f"examples/sec/chip={examples_per_sec_per_chip:.1f} MFU={mfu}")

    print(json.dumps({
        "metric": f"{which}_examples_per_sec_per_chip",
        "value": round(examples_per_sec_per_chip, 2),
        "unit": "examples/sec/chip",
        "vs_baseline": round(mfu / 0.50, 4) if mfu else None,
        "mfu": round(mfu, 4) if mfu else None,
        "platform": platform,
        "n_chips": n_chips,
        "global_batch": global_batch,
        "seq_len": seq,
        "model": which,
        "fused_ln_matmul": fused_ln,
        "fused_qkv": fused_qkv,
        "xent_chunk": xent_chunk,
        "head_dtype": head_dtype,
        "attention_impl": attn,
        "mlm_predictions": n_pred,  # None = dense head / causal LM
        "full_size_model": bool(on_tpu),
    }))


if __name__ == "__main__":
    main()
