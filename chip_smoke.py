#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the train and serve paths still
start on the chip.

One process drives, through the entry points the example scripts call and
on every visible chip:

1. ``run_workload("resnet50_imagenet")`` — ResNet-50 v1.5 at full width
   and depth, 224x224, bf16, 256 images per chip: a few steps, one cadence
   save mid-run, the runner's final eval, then a second ``run_workload``
   that restores from that directory and continues;
2. the compiled flash-attention kernels against the plain reference at
   the benchmark's training call and at a padded non-causal one, then
   ``run_workload("gpt_lm")`` — GPT-2-small width uncut at 1024 tokens
   through those kernels (forward and backward);
3. ``ServeEngine`` — eight-slot paged serving at the same width, the
   compiled paged-attention kernel against the plain-XLA path on the
   engine's live pool, and once more with ``spec_k=4``.

It needs the chip: without a TPU it exits non-zero and prints no result.
``--rehearsal`` (asked for explicitly, never inferred) runs the same legs
at toy width on the CPU and says ``rehearsal`` on every line. Any failed
check or exception ends the run; no leg runs after a failed one. Seconds
are printed as information, not as a metric. The last line of stdout is
one JSON object naming the device jax reported.

    python chip_smoke.py                # on the chip, through the chip tool
    python chip_smoke.py --rehearsal    # on the CPU, before spending chip time
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import glob
import json
import math
import os
import random
import re
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

#: jitted modules whose optimized HLO the smoke reads back
DUMP_MODULES = "jit_(train_step|paged_decode_step)"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each leg runs at: the real widths, or the rehearsal's toys."""

    resnet: tuple[str, ...] = ()  # overrides on the workload's own config
    images_per_chip: int = 256
    gpt: tuple[str, ...] = ()
    seqs_per_chip: int = 8
    #: (B, H, S, D), causal, padded kv_mask: the benchmark's training call
    #: and a BERT-like padded one whose S is an odd multiple of 128
    flash_calls: tuple = (((8, 16, 1024, 64), True, False),
                          ((2, 16, 384, 64), False, True))
    serve_cache_dtype: str = "bfloat16"
    #: how far the first loss may sit from ln(classes) / ln(vocab): a
    #: forward pass that is wrong at full width shows here (the warm-up
    #: learning rate is far too small for "loss goes down" in 10 steps)
    resnet_tol: float = 0.3
    gpt_tol: float = 0.5

    def serve_cfg(self, tfm):
        return tfm.gpt_small(1024)


@dataclasses.dataclass(frozen=True)
class ToySizes(Sizes):
    resnet: tuple[str, ...] = (
        "--model.stage_sizes=[1,1,1,1]", "--model.width=8",
        "--model.num_classes=100", "--model.dtype=float32",
        "--data.image_size=32", "--data.num_classes=100")
    images_per_chip: int = 4
    gpt: tuple[str, ...] = (
        "--model.num_layers=2", "--model.d_model=64", "--model.num_heads=4",
        "--model.d_ff=128", "--model.vocab_size=512", "--model.max_len=256",
        "--model.xent_chunk=128", "--model.dtype=float32",
        "--data.vocab_size=512", "--data.seq_len=256")
    seqs_per_chip: int = 1
    flash_calls: tuple = (((1, 2, 256, 64), True, False),
                          ((2, 2, 128, 64), False, True))
    serve_cache_dtype: str = "float32"
    # a handful of toy-width examples is a noisy estimate of ln(classes)
    resnet_tol: float = 1.0
    gpt_tol: float = 1.0

    def serve_cfg(self, tfm):
        return tfm.TransformerConfig(
            vocab_size=256, max_len=256, num_layers=2, d_model=64,
            num_heads=4, d_ff=128, dropout=0.0, dtype="float32",
            causal=True, pre_ln=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# ---------------------------------------------------------------------------
# the compiled step, read back from XLA's dump
# ---------------------------------------------------------------------------


def mosaic_calls(hlo_path: str) -> list[dict]:
    """Every Mosaic kernel call in one optimized-HLO text file, e.g.
    ``%flash_attention_fwd.12 = (...) custom-call(%bitcast.1474, ...),
    custom_call_target="tpu_custom_call", operand_layout_constraints=
    {bf16[8,12,1024,64]{3,2,1,0}, ...}``: the kernel's name (the
    instruction is named after it) and its operand shapes, which are per
    device."""
    calls = []
    with open(hlo_path) as f:
        for line in f:
            if 'custom_call_target="tpu_custom_call"' not in line:
                continue
            layouts = line.split("operand_layout_constraints={", 1)[1]
            operands = [
                tuple(int(d) for d in dims.split(",") if d) for dims in
                re.findall(r"\w+\[([\d,]*)\]", layouts.split("}}", 1)[0])]
            name = line.split(" = ", 1)[0].split("%")[-1]
            calls.append({"kernel": re.sub(r"[._]*\d*$", "", name),
                          "operands": operands, "text": line.strip()[:700]})
    return calls


def dumped_step(dump_dir: str, module: str, marker: str) -> str:
    """Newest optimized-HLO dump of ``module`` whose text contains
    ``marker`` (a shape only that leg's step has). The dump lives beside
    the compile cache, so a step loaded from the cache is read from the
    dump written when that entry was compiled."""
    paths = sorted(
        glob.glob(os.path.join(
            dump_dir, f"module_*.{module}.*after_optimizations.txt")),
        key=os.path.getmtime, reverse=True)
    for path in paths:
        with open(path) as f:
            if marker in f.read():
                return path
    raise SystemExit(
        f"chip_smoke: FAILED: no optimized HLO for {module} with {marker!r} "
        f"under {dump_dir} ({len(paths)} candidates)")


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------


def train_leg(say, name: str, overrides: list[str], *, per_chip: int,
              expect_first: float, tol: float, n_chips: int):
    """One ``run_workload`` call, observed from the callback seam: when the
    first step finished, and where the batch landed. Returns the result."""
    import jax

    from distributed_tensorflow_tpu import workloads
    from distributed_tensorflow_tpu.train import callbacks as cb

    class Observe(cb.Callback):
        first_step_at = None
        shard_shape = devices = None

        def on_train_start(self, trainer):
            put = trainer.put_batch

            def recording_put(batch):
                out = put(batch)
                leaf = jax.tree.leaves(out)[0]
                self.shard_shape = leaf.addressable_shards[0].data.shape
                self.devices = leaf.sharding.device_set
                return out

            trainer.put_batch = recording_put

        def on_step_end(self, trainer, step, metrics):
            if self.first_step_at is None:
                self.first_step_at = time.perf_counter()

    obs = Observe()
    t0 = time.perf_counter()
    run = workloads.run_workload(name, overrides, extra_callbacks=[obs])
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in run.history]
    steps = [h["step"] for h in run.history]
    say(f"{name}: mesh {dict(run.mesh.shape)} per-device batch shard "
        f"{obs.shard_shape} steps {steps[0]}..{steps[-1]}")
    say(f"{name}: losses {[round(x, 6) for x in losses]}")
    say(f"{name}: eval {run.eval_metrics}")
    say(f"{name}: first step after {obs.first_step_at - t0:.1f}s (init + "
        f"compile), leg wall {wall:.1f}s")
    check(all(math.isfinite(x) for x in losses), f"{name}: non-finite loss")
    check(len(set(losses)) == len(losses),
          f"{name}: losses repeat from step to step")
    check(int(run.state.step) == steps[-1] == steps[0] + len(steps) - 1,
          f"{name}: state.step {int(run.state.step)} vs logged {steps}")
    if steps[0] == 1:
        check(abs(losses[0] - expect_first) <= tol,
              f"{name}: first loss {losses[0]:.4f} not within {tol} of "
              f"{expect_first:.4f}")
    check(obs.shard_shape[0] == per_chip and len(obs.devices) == n_chips,
          f"{name}: batch shard {obs.shard_shape} on {len(obs.devices)} "
          f"devices, want {per_chip} per chip on {n_chips}")
    on = {len(x.sharding.device_set) for x in jax.tree.leaves(run.state)}
    check(on == {n_chips}, f"{name}: state leaves live on {on} devices")
    check(run.eval_metrics is not None and all(
        math.isfinite(v) for v in run.eval_metrics.values()),
        f"{name}: eval {run.eval_metrics}")
    return run


def workload_model(name: str, overrides: list[str]):
    """The model config ``run_workload(name, overrides)`` will build."""
    from distributed_tensorflow_tpu import workloads
    from distributed_tensorflow_tpu.utils import config as config_lib

    return config_lib.apply_overrides(
        workloads.get(name).default_config(), overrides).model


def resnet_leg(say, sizes: Sizes, n_chips: int, ckpt: str) -> None:
    import jax
    from jax.experimental import mesh_utils

    common = [
        *sizes.resnet, "--mesh.data=-1",
        f"--data.global_batch_size={sizes.images_per_chip * n_chips}",
        "--train.log_every=1", "--train.eval_batches=2",
        f"--checkpoint.directory={ckpt}",
        "--checkpoint.save_interval_steps=5",
    ]
    classes = workload_model("resnet50_imagenet", common).num_classes
    kw = dict(per_chip=sizes.images_per_chip, n_chips=n_chips,
              expect_first=math.log(classes), tol=sizes.resnet_tol)
    run = train_leg(say, "resnet50_imagenet",
                    common + ["--train.num_steps=10"], **kw)
    saved = sorted(int(d) for d in os.listdir(ckpt) if d.isdigit())
    say(f"resnet50_imagenet: checkpoint steps on disk {saved}")
    check(5 in saved and 10 in saved, f"cadence/final saves missing: {saved}")

    if jax.devices()[0].platform == "tpu":
        # the mesh a real host gets must be the topology-aware one, not
        # build_mesh's row-major fallback
        want = mesh_utils.create_device_mesh(run.mesh.devices.shape)
        check((want == run.mesh.devices).all(),
              "mesh is not mesh_utils.create_device_mesh's placement")
        stats = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
        say(f"resnet50_imagenet: bytes_in_use per device {stats}")
        check(min(stats) > 0 and max(stats) < 1.5 * min(stats),
              f"device memory uneven: {stats}")

    resumed = train_leg(say, "resnet50_imagenet",
                        common + ["--train.num_steps=12"], **kw)
    check(resumed.history[0]["step"] == 11,
          f"restore did not resume at the saved step 10: {resumed.history}")


def gpt_leg(say, sizes: Sizes, n_chips: int, dump_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.ops._tiling import flash_tile_plan

    overrides = [
        *sizes.gpt, "--mesh.data=-1",
        f"--data.global_batch_size={sizes.seqs_per_chip * n_chips}",
        "--train.num_steps=5", "--train.log_every=1",
        "--train.eval_batches=2",
    ]
    model = workload_model("gpt_lm", overrides)
    train_leg(say, "gpt_lm", overrides, per_chip=sizes.seqs_per_chip,
              n_chips=n_chips, expect_first=math.log(model.vocab_size),
              tol=sizes.gpt_tol)
    if jax.devices()[0].platform != "tpu":
        say("gpt_lm: the CPU takes the dense attention path; the compiled "
            "kernel is a chip check")
        return
    # the compiled step holds the Mosaic flash kernels — it did not take
    # the dense path — and each call's q/k/v are per-device shards
    path = dumped_step(dump_dir, "jit_train_step",
                       f"[{model.vocab_size},{model.d_model}]")
    calls = mosaic_calls(path)
    out_dir = os.path.join(REPO, "chiprun_out", "chip_smoke")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "gpt_step_mosaic_calls.txt"), "w") as f:
        f.write(f"{path}\n" + "\n".join(c["text"] for c in calls) + "\n")
    qkv = (sizes.seqs_per_chip, model.num_heads, model.max_len,
           model.head_dim)
    flash = [c["kernel"] for c in calls if qkv in c["operands"]]
    counts = {k: flash.count(k) for k in sorted(set(flash))}
    say(f"gpt_lm: compiled step has {len(calls)} Mosaic calls, "
        f"{len(flash)} on per-device q/k/v {qkv}: {counts}")
    # the backward is one fused kernel where the plan keeps a head's
    # queries in VMEM, else the dKV + dQ split
    plan = flash_tile_plan(*qkv[:3], qkv[2], qkv[3],
                           jnp.dtype(model.dtype).itemsize, True)
    want = {"flash_attention_fwd"} | (
        {"flash_attention_bwd_fused"} if plan.fused_bwd
        else {"flash_attention_bwd_dkv", "flash_attention_bwd_dq"})
    check(set(counts) == want and min(counts.values()) >= model.num_layers,
          f"want {sorted(want)} on {qkv} in each of {model.num_layers} "
          f"layers, got {counts} of {len(calls)} calls; "
          f"see chiprun_out/chip_smoke/gpt_step_mosaic_calls.txt")


def flash_parity_leg(say, sizes: Sizes) -> None:
    """The flash kernels, compiled (the interpreter checks none of Mosaic's
    layouts), against the plain reference in float32 on the same bf16
    inputs: the output and dq, dk, dv, each gap as the largest difference
    over the reference's largest entry."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.ops import (
        attention_reference, flash_attention)

    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    for (B, H, S, D), causal, padded in sizes.flash_calls:
        q, k, v, w = (
            jax.random.normal(key, (B, H, S, D), jnp.bfloat16)
            for key in jax.random.split(jax.random.PRNGKey(S), 4))
        kv_mask = None
        if padded:  # a ragged tail per row; the first row keeps every key
            lens = S - (jnp.arange(B) * (S // 3 + 5)) % S
            kv_mask = jnp.arange(S)[None, :] < lens[:, None]

        def run(attend, q, k, v):
            def loss(q, k, v):
                out = attend(q, k, v, causal=causal, kv_mask=kv_mask)
                return (f32(out) * f32(w)).sum(), out
            return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)

        (_, out), grads = jax.jit(
            functools.partial(run, flash_attention))(q, k, v)
        (_, want), want_grads = jax.jit(functools.partial(
            run, attention_reference))(f32(q), f32(k), f32(v))
        gaps = {
            name: float(jnp.abs(f32(a) - b).max() / jnp.abs(b).max())
            for name, a, b in zip(("out", "dq", "dk", "dv"),
                                  (out, *grads), (want, *want_grads))}
        say(f"flash parity {(B, H, S, D)} causal={causal} padded={padded}: "
            + " ".join(f"{n} {g:.2e}" for n, g in gaps.items()))
        # bf16 holds 8 bits: an output rounded once reads ~4e-3 of the
        # largest entry; a wrong tile or mask reads ~1
        check(max(gaps.values()) < 3e-2,
              f"flash kernels differ from the reference: {gaps}")


def serve_requests(eng, resident=lambda: None):
    """Six greedy requests of 20-200 tokens, 32 new tokens each; the last
    shares a 64-token prefix with an earlier one and is submitted after
    that one's prefill registered its blocks. ``resident`` runs once,
    with the first five resident and decoding. Returns (uid -> tokens,
    prefix reuse hits)."""
    rng = random.Random(0)
    toks = lambda n: [rng.randrange(eng.cfg.vocab_size)  # noqa: E731
                      for _ in range(n)]
    shared = toks(64)
    for p in (toks(20), toks(48), toks(200), shared + toks(32), toks(150)):
        eng.submit(p, max_new_tokens=32)
    for _ in range(8):  # 200 tokens / 32-token chunks: every prefill done
        eng.step()
    resident()
    eng.submit(shared + toks(56), max_new_tokens=32)
    while eng.sched.has_work:
        eng.step()
    done = eng.drain()
    check(len(done) == 6, f"{len(done)} of 6 requests finished")
    for uid, req in sorted(done.items()):
        check(req.finish_reason == "max_new_tokens"
              and len(req.generated) == 32,
              f"request {uid}: {req.finish_reason}, "
              f"{len(req.generated)} tokens")
    hits = eng.registry.get("prefix_reuse_hits_total").value
    check(hits > 0, "no prefix reuse hit")
    check(eng.alloc.blocks_free == eng.cache.num_blocks,
          f"leaked blocks: {eng.alloc.blocks_free} free of "
          f"{eng.cache.num_blocks}")
    return {uid: list(r.generated) for uid, r in sorted(done.items())}, hits


def compare_paged_kernel(say, eng) -> None:
    """``paged_attention(impl="pallas")`` against ``impl="fused"`` (plain
    XLA) on the engine's live layer-0 pool and block table, at the decode
    shape and the prefill-chunk shape. Attention outputs are compared, not
    tokens: random weights give near-flat logits, and an argmax tie is not
    a kernel bug."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu.ops.attention import paged_attention

    k, v = eng.cache.k[0], eng.cache.v[0]
    active = eng.sched.active_slots()
    mbu = eng._mb_bucket(max(len(eng._blocks[s]) for s in active))
    H, D = eng.cfg.num_heads, eng.cfg.head_dim
    rng = np.random.RandomState(0)
    longest = max(active, key=lambda s: eng._written[s])
    C = eng.prefill_chunk
    decode_pos = np.full((eng.sched.num_slots, 1), eng._oob, np.int32)
    decode_pos[active, 0] = eng._written[active] - 1
    chunk_pos = (eng._written[longest] - C + np.arange(C, dtype=np.int32))
    shapes = {
        "decode": (eng._table[:, :mbu], decode_pos),
        "prefill-chunk": (eng._table[longest:longest + 1, :mbu],
                          chunk_pos[None]),
    }
    # Both paths accumulate in f32, round the probabilities to the cache
    # dtype before p.v and the output to it once. Each of the four
    # roundings is at most eps/2 relative to max|v|, so
    # |diff| <= 2 eps max|v|, the tolerance, in bf16 and in f32 alike.
    eps = float(jnp.finfo(v.dtype).eps)
    tol = 2 * eps * float(jnp.abs(v.astype(jnp.float32)).max())
    for name, (table, pos) in shapes.items():
        q = jnp.asarray(rng.randn(pos.shape[0], H, pos.shape[1], D),
                        jnp.dtype(eng.cfg.dtype))
        # on the chip the pool stores a head at the 128 lanes
        # (kv_cache.stored_head_dim): zeros beside q's own columns
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, k.shape[-1] - D),))
        outs = {
            impl: jax.jit(
                lambda q, k, v, t, p, impl=impl: paged_attention(
                    q, k, v, t, q_pos=p, sm_scale=D ** -0.5, impl=impl)
            )(q, k, v, jnp.asarray(table), jnp.asarray(pos))
            for impl in ("pallas", "fused")
        }
        a, b = (np.asarray(outs[i], np.float32) for i in ("pallas", "fused"))
        # an idle slot's rows: zeros from the kernel (it fetches nothing),
        # attention over a clamped table from the XLA path
        idle = (pos >= eng._oob).all(axis=1)
        check(not a[idle].any(), f"paged kernel at {name}: an idle slot's "
              f"rows are not zeros")
        a, b = a[~idle], b[~idle]
        err = float(np.abs(a - b).max())
        say(f"serve: paged kernel vs XLA path at {name} shape q{q.shape} "
            f"table{table.shape}: max |diff| {err:.3g} (tolerance "
            f"{tol:.3g}, max |out| {float(np.abs(b).max()):.3g})")
        check(np.isfinite(a).all() and err <= tol,
              f"paged kernel off the XLA path at {name}: {err} > {tol}")


def serve_leg(say, sizes: Sizes, dump_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu import serve
    from distributed_tensorflow_tpu.models import transformer as tfm

    cfg = sizes.serve_cfg(tfm)
    for spec_k in (0, 4):
        t0 = time.perf_counter()
        eng = serve.ServeEngine.with_random_params(
            cfg, num_slots=8, block_size=16, prefill_chunk=32,
            cache_dtype=jnp.dtype(sizes.serve_cache_dtype), spec_k=spec_k)
        tokens, hits = serve_requests(
            eng, (lambda: compare_paged_kernel(say, eng)) if spec_k == 0
            else (lambda: None))
        say(f"serve spec_k={spec_k}: pool {eng.cache.k.shape} "
            f"{eng.cache.k.dtype}, 6 requests x 32 tokens, prefix reuse "
            f"hits {int(hits)}, prefill chunks "
            f"{int(eng.registry.get('prefill_chunks_total').value)}, "
            f"spec accepted "
            f"{int(eng.registry.get('spec_tokens_accepted_total').value)}")
        say(f"serve spec_k={spec_k}: tokens {tokens}")
        say(f"serve spec_k={spec_k}: leg wall (init + compile + run) "
            f"{time.perf_counter() - t0:.1f}s")
        del eng
    if jax.devices()[0].platform == "tpu":
        path = dumped_step(dump_dir, "jit_paged_decode_step",
                           f"[{cfg.vocab_size},{cfg.d_model}]")
        n = len(mosaic_calls(path))
        say(f"serve: compiled decode step has {n} Mosaic calls")
        check(n >= cfg.num_layers,
              f"decode step did not take the paged kernel: {n} Mosaic calls")


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy widths on the CPU; proves nothing about the "
                         "chip and says so on every line")
    args = ap.parse_args(argv)
    tag = "chip_smoke[rehearsal]" if args.rehearsal else "chip_smoke"

    def say(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    # jax drops to the CPU with only a warning when libtpu finds no chip,
    # and a sandbox may export JAX_PLATFORMS=cpu: inherit neither.
    os.environ["JAX_PLATFORMS"] = "cpu" if args.rehearsal else "tpu"
    if args.rehearsal:
        # the toy run needs no cache, and XLA:CPU logs a machine-feature
        # mismatch on every cached load
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    import jax

    from distributed_tensorflow_tpu.parallel import cluster

    cache_dir = cluster.configure_compile_cache()
    # read back by the gpt and serve legs; beside the cache so that a step
    # loaded from it still has the HLO it was compiled from
    dump_dir = os.path.join(cache_dir, "chip_smoke_hlo")
    if not args.rehearsal:  # the CPU has no Mosaic call to look for
        os.environ["XLA_FLAGS"] = (
            f"{os.environ.get('XLA_FLAGS', '')} --xla_dump_to={dump_dir} "
            f"--xla_dump_hlo_module_re={DUMP_MODULES} --xla_dump_hlo_as_text"
        ).strip()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        say(f"no TPU: {e}")
        say("this script needs the chip (--rehearsal is the CPU toy run)")
        return 2
    dev = devices[0]
    n_chips = len(devices)
    say(f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"devices={n_chips} jax={jax.__version__}")
    check(dev.platform == ("cpu" if args.rehearsal else "tpu"),
          f"platform is {dev.platform}")

    import logging

    logging.basicConfig(level=logging.WARNING, force=True)
    logging.getLogger("distributed_tensorflow_tpu.parallel.mesh").setLevel(
        logging.INFO)

    from distributed_tensorflow_tpu.data import native_jpeg
    from distributed_tensorflow_tpu.runtime import native

    say(f"native tiers: record loader "
        f"{'c++' if native.available() else 'python fallback'}, jpeg decoder "
        f"{'c++' if native_jpeg.available() else 'PIL fallback'}")
    entries = lambda: len(glob.glob(os.path.join(cache_dir, "*-cache")))  # noqa: E731
    before = entries()
    say(f"compile cache {cache_dir}: {before} entries at start")

    sizes = ToySizes() if args.rehearsal else Sizes()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        resnet_leg(say, sizes, n_chips, ckpt)
    flash_parity_leg(say, sizes)
    gpt_leg(say, sizes, n_chips, dump_dir)
    serve_leg(say, sizes, dump_dir)
    say(f"compile cache: {entries()} entries at end ({before} at start); "
        f"all legs passed in {time.perf_counter() - t0:.0f}s")
    result = {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_chips}}
    if args.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
