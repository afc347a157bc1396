#!/usr/bin/env python
"""Scaling observatory: the measured mesh-config × workload sweep.

ROADMAP item 4 / the MLPerf-0.6 TPU-pod recipe (arXiv:1909.09756): we
had the parallelism knobs (the MULTICHIP dryruns exercise dp / fsdp /
tp / sp / ep / pp / hybrid on 8 CPU devices) and the meters
(obs/goodput's single MFU site, the goodput ledger) but no measured
curves connecting them. This harness runs the matrix and produces them:

- one CELL per (mesh config, workload): a short Trainer run on that
  mesh over a device subset, steps/sec and examples/sec from the
  steady-state ``train_step_seconds`` histogram (first step — compile —
  excluded), per-cell goodput fraction from the ledger counters, MFU
  through ``obs/goodput.train_mfu`` (THE multiplier site; dtflint pins
  it) where the device kind has a known peak — all isolated per cell
  with ``Registry.delta`` snapshots, never a mid-run ``reset()``;
- a distributed-eval pass per cell (train/evaluation.py: batch sharded
  over the mesh, host-side fixed-order reduction) so the eval surface
  is exercised on every mesh shape the sweep claims works;
- a schema-versioned ``dtf-scaling-1`` report (obs/scaling.py) where
  EVERY cell is provenance-stamped (backend, device kind/count, mesh
  shape, git sha, hostname) — no number leaves this tool without its
  platform context;
- per-axis scaling efficiency vs the 1-device baseline and an enforced
  gate: 8-dev dp must hold ≥ 0.8 × ideal. On the host-shared CPU rig
  the ideal is flat throughput (8 fake devices partition ONE host's
  silicon — the gate then bounds partitioning overhead); on real
  accelerators it is N × 1-dev (see obs/scaling.scaling_efficiency).

Exit codes: 0 ok · 2 usage · 3 scaling gate failed · 4 provenance
platform differs from --expect-platform (the masquerade tripwire).

Usage (the 8-device CPU rig):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python tools/sweep.py --out artifacts/scaling.json
    python tools/sweep.py --dryrun --out /tmp/scaling.json   # 2-cell CI gate
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])


#: mesh cells — the MULTICHIP dryrun matrix as named sweep points:
#: name -> (devices needed, MeshSpec kwargs, scaling axis label)
MESH_CELLS = {
    "1dev":          (1, dict(data=1), "dp"),
    "dp2":           (2, dict(data=2), "dp"),
    "dp8":           (8, dict(data=8), "dp"),
    "dp4_tp2":       (8, dict(data=4, model=2), "tp"),
    "dp2_fsdp2_tp2": (8, dict(data=2, fsdp=2, model=2), "fsdp"),
    "dp8_hybrid2":   (8, dict(data=8, dcn_data=2), "hybrid"),
    # two-level fault-domain cells (parallel/mesh.PodTopology): the
    # pod boundary IS the DCN boundary, so the simulated two-pod mesh
    # is the hybrid recipe with the slice reinterpreted as the fault
    # domain resilience/podfleet.py supervises (ISSUE 19)
    "pod2_dp2":      (4, dict(num_pods=2, pod=dict(data=2)), "pod"),
    "pod2_dp2_tp2":  (8, dict(num_pods=2, pod=dict(data=2, model=2)),
                      "pod"),
}

#: sweep workloads: name -> (registry workload, default per-shard batch)
SWEEP_WORKLOADS = {
    "mlp": ("mnist_mlp", 128),
    "gpt": ("gpt_lm", 16),
}

DRYRUN_CELLS = ("1dev", "dp8", "pod2_dp2")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _tiny_config(sweep_name: str, global_batch: int):
    """The workload's default config shrunk to sweep scale: the matrix
    measures parallelism overheads, not model quality, so models are
    small enough that a cell is seconds — but still the REAL workload
    builders, optimizers, and data paths."""
    from distributed_tensorflow_tpu import workloads

    workload, _ = SWEEP_WORKLOADS[sweep_name]
    mod = workloads.get(workload)
    cfg = mod.default_config()
    if sweep_name == "mlp":
        model = dataclasses.replace(cfg.model, hidden_sizes=(64, 64))
        data = dataclasses.replace(cfg.data, global_batch_size=global_batch)
    else:  # gpt: 2-layer toy decoder at seq 32
        model = dataclasses.replace(
            cfg.model, vocab_size=256, max_len=32, num_layers=2,
            d_model=32, num_heads=4, d_ff=64, dropout=0.0, xent_chunk=0)
        data = dataclasses.replace(
            cfg.data, global_batch_size=global_batch, seq_len=32,
            vocab_size=256)
    return dataclasses.replace(cfg, model=model, data=data), mod


def run_cell(sweep_name: str, cell_name: str, steps: int,
             per_shard_batch: int, eval_batches: int, seed: int,
             registry) -> dict:
    """Measure one (mesh, workload) cell. Returns the report cell dict."""
    import jax
    import numpy as np

    from distributed_tensorflow_tpu.obs import goodput, scaling
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh, describe
    from distributed_tensorflow_tpu.train import (
        ShardedEvaluator, StepOptions, Trainer, callbacks as cb,
        derive_metrics, init_train_state, make_optimizer, make_train_step,
    )
    from distributed_tensorflow_tpu.train.evaluation import batch_shards
    from distributed_tensorflow_tpu.utils import flops as flops_lib

    n_devices, spec_kw, axis = MESH_CELLS[cell_name]
    devices = jax.devices()[:n_devices]
    topo = None
    if "num_pods" in spec_kw:
        from distributed_tensorflow_tpu.parallel import PodTopology

        topo = PodTopology.from_dict(spec_kw).resolve(n_devices)
        spec = topo.to_mesh_spec().resolve(n_devices)
        log(f"cell {sweep_name}×{cell_name}: two-level {topo.describe()}")
    else:
        spec = MeshSpec(**spec_kw).resolve(n_devices)
    shards = spec.data * spec.fsdp
    global_batch = per_shard_batch * shards
    cfg, mod = _tiny_config(sweep_name, global_batch)
    mesh = build_mesh(spec, devices)
    log(f"cell {sweep_name}×{cell_name}: {describe(mesh)} "
        f"global_batch={global_batch}")

    parts = mod.build(cfg, mesh)
    tx = parts.tx if parts.tx is not None else make_optimizer(cfg.optimizer)
    state, specs = init_train_state(
        parts.init_fn, tx, mesh, jax.random.PRNGKey(seed),
        param_rules=parts.param_rules, param_specs=parts.param_specs,
        fsdp=parts.fsdp,
    )
    step_fn = make_train_step(parts.loss_fn, tx, StepOptions())

    baseline = registry.snapshot()
    # per-step latency + goodput booking only (every_n past the run:
    # the cadence'd gauge fetch never fires inside the measured window)
    telemetry = cb.TelemetryCallback(registry=registry, every_n=10**9)
    trainer = Trainer(step_fn, state, mesh, specs, callbacks=[telemetry])
    state = trainer.fit(parts.dataset_fn(0), num_steps=steps)
    delta = registry.delta(baseline)

    hist = delta.get("train_step_seconds")
    if not hist or not hist["sum"]:
        raise RuntimeError(
            f"cell {sweep_name}×{cell_name}: no steady-state step "
            f"observations (steps={steps} too small?)")
    steps_per_sec = hist["count"] / hist["sum"]
    productive = delta.get("goodput_productive_seconds_total",
                           {}).get("value", 0.0)
    wasted = sum(v["value"] for k, v in delta.items()
                 if k.startswith("wasted_seconds_total"))
    cell = {
        "cell": cell_name,
        "workload": sweep_name,
        "axis": axis,
        "n_devices": n_devices,
        "mesh": {a: int(mesh.shape[a]) for a in mesh.axis_names},
        "global_batch": global_batch,
        "steps": steps,
        "steps_per_sec": round(steps_per_sec, 3),
        "examples_per_sec": round(steps_per_sec * global_batch, 1),
        "goodput_fraction": round(productive / (productive + wasted), 4)
        if productive + wasted > 0 else None,
        "provenance": scaling.provenance(mesh),
    }
    if topo is not None:
        cell["pods"] = topo.num_pods
        cell["devices_per_pod"] = topo.devices_per_pod
    peak = flops_lib.known_peak_flops(devices[0])
    if parts.flops_per_step and peak:
        # fwd-only count; the shared site applies the fwd+bwd multiplier.
        # No peak for this device kind (the CPU rig) → no mfu in the cell
        cell["mfu"] = round(goodput.train_mfu(
            parts.flops_per_step, steps_per_sec, n_chips=n_devices,
            peak_per_chip=peak, registry=registry,
        ), 6)
    if eval_batches and parts.eval_fn is not None \
            and parts.eval_dataset_fn is not None:
        evaluator = ShardedEvaluator(parts.eval_fn, mesh, registry=registry)
        totals = evaluator.run(
            state, parts.eval_dataset_fn(eval_batches), eval_batches,
            step=int(np.asarray(state.step)))
        metrics = derive_metrics(totals, parts.eval_metric_prefix)
        if "loss" in metrics:
            cell["eval_loss"] = round(metrics["loss"], 6)
        cell["eval_batches"] = eval_batches
        cell["eval_shards"] = batch_shards(mesh)
    scaling.note_cell(registry)
    log(f"  steps/sec={cell['steps_per_sec']} "
        f"examples/sec={cell['examples_per_sec']} "
        f"mfu={cell.get('mfu')} goodput={cell['goodput_fraction']}")
    jax.clear_caches()  # free the cell's executables before the next one
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cells", default=None,
                    help=f"comma list from {sorted(MESH_CELLS)} "
                         f"(default: all)")
    ap.add_argument("--workloads", default=None,
                    help=f"comma list from {sorted(SWEEP_WORKLOADS)} "
                         f"(default: all)")
    ap.add_argument("--steps", type=int, default=12,
                    help="train steps per cell (first = compile, excluded)")
    ap.add_argument("--per-shard-batch", type=int, default=0,
                    help="examples per batch shard (0 = workload default)")
    ap.add_argument("--eval-batches", type=int, default=2,
                    help="distributed-eval batches per cell (0 disables)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gate-dp", type=float, default=0.8,
                    help="min 8-dev dp scaling efficiency (0 disables)")
    ap.add_argument("--expect-platform", default="",
                    help="fail (rc 4) unless the measured provenance "
                         "platform is exactly this (CI masquerade tripwire)")
    ap.add_argument("--out", default="",
                    help="also write the report JSON here (atomic)")
    ap.add_argument("--dryrun", action="store_true",
                    help=f"CI mode: mlp × {DRYRUN_CELLS}, 8 steps")
    args = ap.parse_args(argv)

    import jax

    from distributed_tensorflow_tpu.obs import scaling
    from distributed_tensorflow_tpu.obs.registry import default_registry

    if args.dryrun:
        if args.cells is not None or args.workloads is not None:
            # fixed matrix — silently ignoring an explicit selection
            # would measure the wrong cells and be trusted anyway
            ap.error("--dryrun fixes the matrix to "
                     f"mlp × {DRYRUN_CELLS}; drop --cells/--workloads")
        cells = list(DRYRUN_CELLS)
        workload_names = ["mlp"]
        args.steps = min(args.steps, 8)
    else:
        cells = [c.strip() for c in
                 (args.cells or ",".join(MESH_CELLS)).split(",")
                 if c.strip()]
        workload_names = [w.strip() for w in
                          (args.workloads or ",".join(SWEEP_WORKLOADS))
                          .split(",") if w.strip()]
    unknown = [c for c in cells if c not in MESH_CELLS] + \
        [w for w in workload_names if w not in SWEEP_WORKLOADS]
    if unknown:
        ap.error(f"unknown cells/workloads: {unknown}")

    n_available = jax.device_count()
    registry = default_registry()
    report_cells, skipped = [], []
    for sweep_name in workload_names:
        per_shard = args.per_shard_batch or SWEEP_WORKLOADS[sweep_name][1]
        for cell_name in cells:
            need = MESH_CELLS[cell_name][0]
            if need > n_available:
                # no silent caps: an absent cell is reported, not elided
                skipped.append({"cell": cell_name, "workload": sweep_name,
                                "reason": f"needs {need} devices, "
                                          f"have {n_available}"})
                log(f"cell {sweep_name}×{cell_name} SKIPPED: needs {need} "
                    f"devices, have {n_available}")
                continue
            report_cells.append(run_cell(
                sweep_name, cell_name, args.steps, per_shard,
                args.eval_batches, args.seed, registry))

    efficiency = scaling.scaling_efficiency(report_cells, registry)
    gates = []
    if args.gate_dp > 0:
        for e in efficiency:
            if e["axis"] == "dp" and e["n_devices"] == 8:
                gates.append({
                    "gate": f"{e['workload']}/{e['cell']}",
                    "axis": "dp",
                    "basis": e["basis"],
                    "threshold": args.gate_dp,
                    "value": e["value"],
                    "passed": e["value"] >= args.gate_dp,
                })
        if not gates:
            log("gate-dp: no 8-dev dp cell with a 1-dev baseline in this "
                "sweep; gate not evaluated")

    report = scaling.make_report(
        report_cells, efficiency, gates,
        extra={"skipped_cells": skipped, "steps_per_cell": args.steps},
    )
    if args.out:
        scaling.write_report(args.out, report)
        log(f"report -> {args.out}")
    else:
        failures = scaling.validate_scaling_report(report)
        if failures:
            raise ValueError("invalid scaling report:\n  "
                             + "\n  ".join(failures))
    print(json.dumps(report, indent=2, sort_keys=True))

    platform = report["provenance"]["platform"]
    if args.expect_platform and platform != args.expect_platform:
        log(f"FAIL: measured platform {platform!r} != expected "
            f"{args.expect_platform!r} — refusing to let this report "
            f"masquerade")
        return 4
    failed = [g for g in gates if not g["passed"]]
    if failed:
        log(f"FAIL: scaling gate(s) below threshold: {failed}")
        return 3
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
