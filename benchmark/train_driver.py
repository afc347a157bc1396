"""Traffic kind `train_job`: a training job through the program's own entry
(`workloads.run_workload(<the family's workload>)` -> `Trainer.fit` -> the
compiled step), observed and steered from the callback seam.

Set-up builds ONE object, the compiled step with its state. The callback
puts the benchmark's weights (made on the device from `--seed`) into that
state, lets the program's loop drive it through its first steps on the
job's own batches, reads what `correct` compares (each step's loss, the
first gradient from Adam's first moment, the parameters' change), and then
opens the window on the same object. The window ends with
`trainer.request_stop()`.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check, families, harness, program, reference


class Window(program.callback_base()):
    """The benchmark's one callback in the program's training loop."""

    def __init__(self, cfg, job, seed, seconds, tracing, trace_seconds):
        self.cfg, self.job, self.seed = cfg, job, seed
        self.ref = reference.for_config(cfg)
        self.adapter = families.adapter(cfg)
        self.seconds, self.tracing = seconds, tracing
        self.trace_seconds = trace_seconds
        self.check_steps = job["check_steps"]
        self.batches: list = []
        self.read = {"losses": []}
        self.t_open = self.t_close = None
        self.steps_in_window = 0
        self._pending: list = []

    # -- set-up: the state, the first steps, what `correct` reads ----------
    def on_train_start(self, trainer):
        self.t_train_start = time.perf_counter()
        shardings = jax.tree.map(lambda x: x.sharding, trainer.state.params)
        params = program.program_weights(self.cfg, self.seed, shardings)
        trainer.state = trainer.state.replace(params=params)
        put = trainer.put_batch

        def put_batch(batch):
            if len(self.batches) < self.check_steps:
                self.batches.append(np.array(batch["input_ids"]))
            return put(batch)

        trainer.put_batch = put_batch

    def on_step_end(self, trainer, step, metrics):
        if step <= self.check_steps:
            self.read["losses"].append(float(metrics["loss"]))
            if step == 1:
                self.t_first_step = time.perf_counter()
                mu = self._leaf_norms(
                    program.adam_mu(trainer.state.opt_state))
                b1 = self.job["optimizer"]["b1"]
                self.read["grad1"] = {k: v / (1.0 - b1) for k, v in mu.items()}
            if step == self.check_steps:
                self.read["dparam"] = self._param_change(trainer)
                jax.block_until_ready(trainer.state)
                self.t_open = time.perf_counter()
                if self.tracing is not None:
                    self.tracing.start()
            return
        # at most two steps in flight: the host still runs ahead of the
        # device, and the window closes within two steps of its length
        self._pending.append(metrics["loss"])
        if len(self._pending) > 2:
            self._pending.pop(0).block_until_ready()
        now = time.perf_counter()
        if (self.tracing is not None and self.tracing.running
                and now - self.t_open >= self.trace_seconds):
            jax.block_until_ready(trainer.state)
            self.tracing.stop()
        if now - self.t_open >= self.seconds:
            jax.block_until_ready(trainer.state)
            self.t_close = time.perf_counter()
            self.steps_in_window = step - self.check_steps
            trainer.request_stop("benchmark window closed")

    def _leaf_norms(self, tree, minus=None) -> dict:
        """Per-leaf norms of a program-layout tree (less the tree ``minus``,
        where given), named as the reference names them."""
        def norms(t, m):
            if m is not None:
                t = jax.tree.map(jnp.subtract, t, m)
            return self.ref.leaf_norms(self.adapter.from_program_tree(t))

        return jax.device_get(jax.jit(norms)(tree, minus))

    def _param_change(self, trainer) -> dict:
        """Per-leaf norms of params(now) - params(seed), the seed's weights
        made again on the device rather than kept through the steps."""
        params = trainer.state.params
        shardings = jax.tree.map(lambda x: x.sharding, params)
        w0 = program.program_weights(self.cfg, self.seed, shardings)
        return self._leaf_norms(params, w0)


def reference_numbers(cfg, job, seed, batches, devices, quant=None,
                      step_fault=None) -> dict:
    """The plain reference over the same first steps, its rows spread over
    the cell's chips where there are several."""
    kw = {}
    if len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(devices), ("rows",))
        kw = {"batch_sharding": NamedSharding(mesh, P("rows")),
              "weight_sharding": NamedSharding(mesh, P())}
    return reference.for_config(cfg).train_steps(
        cfg, seed, batches, job["optimizer"], quant=quant,
        rows_per_block=job["reference_rows_per_block"],
        step_fault=step_fault, **kw)


def run(files: dict, seed: int, seconds: float, trace: bool, devices,
        limits: dict) -> dict:
    cfg, job, cell = files["config"], files["traffic"], files["cell"]
    n = len(devices)
    tracing = harness.Tracing(cell["name"]) if trace else None
    win = Window(cfg, job, seed, seconds, tracing,
                 min(job["trace_seconds"], seconds))
    result = program.run_training(
        cfg, program.train_overrides(cfg, job, seed, n), win)
    if win.t_close is None:
        raise RuntimeError("the training loop ended before the window closed")
    setup_s = win.t_open - harness.T_PROCESS_START
    window_s = win.t_close - win.t_open
    tokens_per_step = job["sequences_per_chip"] * n * job["seq_len"]
    rate = win.steps_in_window * tokens_per_step / window_s
    device = harness.device_record(devices)
    state_step = int(result.state.step)
    del result

    # the program's state is freed; now the reference, on the same batches
    t_ref = time.perf_counter()
    ref_numbers = reference_numbers(cfg, job, seed, win.batches, devices)
    t_ref = time.perf_counter() - t_ref
    numbers = check.train_numbers(win.read, ref_numbers)
    rows_differ = all(
        len({r.tobytes() for r in b}) == len(b) for b in win.batches)
    counted = state_step == win.check_steps + win.steps_in_window
    correct, rows = check.judge(numbers, limits,
                                extra_ok=rows_differ and counted)
    notes = {"rows_all_differ": rows_differ,
             "state_step_matches_steps_counted": counted,
             "losses_program": win.read["losses"],
             "losses_reference": ref_numbers["losses"]}

    out = {"correct": correct,
           "attempted": win.steps_in_window, "failed": 0}
    if trace:
        harness.traced_outputs(files, tracing, devices, {}, out, device)
    else:
        out["metrics"] = harness.end_to_end_metrics(
            files["manifest"], cell["name"],
            {"train_tokens_per_s": rate, "setup_s": setup_s})
    out["device"] = device
    out["window"] = {"seconds": window_s, "steps": win.steps_in_window}
    # where set-up and the comparison spend their seconds (information)
    out["phases_s"] = {
        "process_start_to_train_start":
            win.t_train_start - harness.T_PROCESS_START,
        "train_start_to_first_step": win.t_first_step - win.t_train_start,
        "first_step_to_window": win.t_open - win.t_first_step,
        "reference": t_ref, **{f"reference_{k}": v for k, v in
                               ref_numbers["timing_s"].items()}}
    check.report(rows, correct, notes)
    out["checks"] = rows
    return out
