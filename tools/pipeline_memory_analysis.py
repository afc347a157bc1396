#!/usr/bin/env python
"""Measure the pipelined BERT-base train step's per-device memory across
the (M microbatches, S stages, V virtual) grid — the
evidence for "GPipe(+interleave)+remat fits the pod shapes" vs needing a
hand-scheduled 1F1B.

Why this matters: autodiff-through-scan retains one stage-IO activation
buffer per in-flight microbatch — O(M) per device (GPipe), where 1F1B
holds O(S). The question is whether O(M) at the BERT-pod shapes
(BASELINE.json:10, SURVEY §7 M8) actually presses the 16 GiB v5e HBM.
This tool compiles the REAL pipelined train step (same code path as
workloads/bert_pretrain with --mesh.pipe) on a fake CPU device mesh and
reads XLA's memory analysis. CPU-backend caveat: buffer ALLOCATION sizes
(activations, params, opt state) are layout-portable and dominate the
answer; TPU-specific padding/fusion shifts the total by O(10%), so read
the table with that error bar — it resolves "fits vs doesn't" except
within ~10% of the boundary.

Usage:  python tools/pipeline_memory_analysis.py [--quick]
  default grid: S in {2,4} x V in {1,2} x M in {8,16,32}, BERT-base,
  global batch 256 (so per-microbatch size varies with M), seq 512.
  --quick shrinks to a smoke grid for tests.

Prints one JSON line per config:
  {"S":..,"V":..,"M":..,"per_device_bytes":..,"gib":..,"fits_v5e":..}
plus a markdown table on stderr.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

V5E_HBM_GIB = 16.0


def analyze(S: int, V: int, M: int, *, batch: int, seq: int, cfg,
            data_ax=1, mlm=True):
    import jax
    import jax.numpy as jnp
    import optax

    from distributed_tensorflow_tpu.models import transformer as tfm
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributed_tensorflow_tpu.parallel import sharding as sh
    from distributed_tensorflow_tpu.train import (
        StepOptions, init_train_state, jit_train_step, make_train_step,
    )
    from jax.sharding import NamedSharding

    mesh = build_mesh(MeshSpec(pipe=S, data=data_ax),
                      jax.devices()[: S * data_ax])
    init_fn = tfm.make_pipelined_init_fn(cfg, n_stages=S, seq_len=seq,
                                         n_virtual=V)
    specs = tfm.pipeline_param_specs(
        jax.eval_shape(init_fn, jax.random.PRNGKey(0))[0]
    )
    tx = optax.adamw(1e-4)
    state, sspecs = init_train_state(
        init_fn, tx, mesh, jax.random.PRNGKey(0), param_specs=specs,
    )
    piped = (tfm.pipelined_mlm_loss_fn if mlm else tfm.pipelined_lm_loss_fn)
    step = make_train_step(
        piped(cfg, mesh, n_microbatches=M, n_virtual=V),
        tx, StepOptions(),
    )
    jitted = jit_train_step(step, mesh, sspecs)
    if mlm:
        # gathered-head MLM format — the bert_pretrain default; K from
        # the ONE definition of the auto rule (data/text.py)
        from distributed_tensorflow_tpu.data.text import (
            TextDataConfig, resolved_max_predictions,
        )

        K = resolved_max_predictions(
            TextDataConfig(seq_len=seq, max_predictions=-1))
        batch_tree = {
            "input_ids": jnp.zeros((batch, seq), jnp.int32),
            "masked_positions": jnp.tile(jnp.arange(K, dtype=jnp.int32),
                                         (batch, 1)),
            "masked_labels": jnp.zeros((batch, K), jnp.int32),
        }
    else:
        # causal-LM: labels are shifted input_ids inside the loss
        batch_tree = {"input_ids": jnp.zeros((batch, seq), jnp.int32)}
    batch_tree = jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, sh.batch_spec(x.ndim))), batch_tree,
    )
    compiled = jitted.lower(state, batch_tree).compile()
    mem = compiled.memory_analysis()
    # per-device working set: XLA reports whole-program allocation stats
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "S": S, "V": V, "M": M,
        "temp_bytes": int(mem.temp_size_in_bytes),
        "arg_bytes": int(mem.argument_size_in_bytes),
        "per_device_bytes": int(total),
        "gib": round(total / 2**30, 2),
        "fits_v5e": total / 2**30 < V5E_HBM_GIB * 0.9,  # 10% headroom
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny smoke grid (tests)")
    ap.add_argument("--pod", action="store_true",
                    help="16-device pod-shape grid: "
                         "BERT-base over pipe=4 x data=4, global batch "
                         "1024 — the pod-like M/S/V statement")
    ap.add_argument("--check", metavar="JSON",
                    help="single-config estimate for the runner's "
                         "pipeline-memory guard: "
                         '{"model": <TransformerConfig dict>, "S":, '
                         '"V":, "M":, "batch":, "seq":, "mlm":}. '
                         "Prints ONE JSON row.")
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    if args.check:
        req = json.loads(args.check)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={req['S']}"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")

        from distributed_tensorflow_tpu.models import transformer as tfm
        from distributed_tensorflow_tpu.utils import config as config_lib

        cfg = config_lib.from_dict(tfm.TransformerConfig, req["model"])
        row = analyze(req["S"], req["V"], req["M"], batch=req["batch"],
                      seq=req["seq"], cfg=cfg, data_ax=1,
                      mlm=bool(req.get("mlm", True)))
        print(json.dumps(row), flush=True)
        return

    n_dev = 16 if args.pod else 8
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_dev}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    from distributed_tensorflow_tpu.models import transformer as tfm

    if args.quick:
        cfg = tfm.TransformerConfig(
            vocab_size=512, max_len=64, num_layers=4, d_model=64,
            num_heads=4, d_ff=128, causal=False, pre_ln=False,
            dtype="float32", remat=True,
        )
        grid = [(2, 1, 8), (2, 2, 8)]
        batch, seq = 32, 64
    elif args.pod:
        cfg = tfm.bert_base()
        # S=4 x data=4 over 16 devices at pod global batch 1024; V=3
        # is the deep-interleave point (S*V=12 = num_layers), M up to 64
        # probes the O(M) retention term at 4x the round-3 microbatches
        grid = [(4, V, M) for V in (1, 3) for M in (16, 32, 64)]
        batch, seq = 1024, 512
    else:
        cfg = tfm.bert_base()
        # S*V must divide num_layers=12: V=2 pairs with S=2 only; V=3
        # covers the deep-interleave point at both stage counts
        grid = [(S, V, M)
                for S in (2, 4) for V in (1, 3) for M in (8, 16, 32)]
        grid += [(2, 2, M) for M in (8, 16, 32)]
        batch, seq = 256, 512

    rows = []
    for S, V, M in grid:
        try:
            r = analyze(S, V, M, batch=batch, seq=seq, cfg=cfg,
                        data_ax=n_dev // S if not args.quick else 2)
        except Exception as e:  # keep the sweep alive; report the hole
            r = {"S": S, "V": V, "M": M, "error": str(e)[:200]}
        rows.append(r)
        print(json.dumps(r), flush=True)

    print("\n| S | V | M | per-device GiB | fits v5e (14.4 GiB usable) |",
          file=sys.stderr)
    print("|---|---|---|---|---|", file=sys.stderr)
    for r in rows:
        if "error" in r:
            print(f"| {r['S']} | {r['V']} | {r['M']} | ERROR | — |",
                  file=sys.stderr)
        else:
            print(f"| {r['S']} | {r['V']} | {r['M']} | {r['gib']} | "
                  f"{'yes' if r['fits_v5e'] else 'NO'} |", file=sys.stderr)


if __name__ == "__main__":
    main()
