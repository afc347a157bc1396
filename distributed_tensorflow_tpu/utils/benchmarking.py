"""Shared benchmark-harness scaffolding (bench.py, tools/bench_bert.py).

The pieces every throughput harness in this repo must agree on,
extracted so they cannot drift between benchmarks:

- **Device description**: every harness names the platform it ran on and
  sizes its model from ``on_tpu``. A harness measures the chip: without a
  TPU ``describe_devices`` raises, unless the environment explicitly asks
  for the CPU (``JAX_PLATFORMS=cpu`` — the toy-size plumbing run the tests
  use). No harness decides that for itself.
- **Execution-forcing sync**: dispatch is asynchronous, so a timing
  window ends by fetching a VALUE that data-depends on every measured
  step (the chained loss) — the same fetch doubles as the finite check.
- **Warmup/measure loop** with the sync applied once at each boundary,
  and a finite-loss check so a diverged step can't post a throughput
  number.

Reference analog: the reference harness read its throughput off
``StepCounterHook`` logs ($TF basic_session_run_hooks.py:674); the
value-fetch discipline here is the TPU-async-dispatch replacement for
TF-session's synchronous ``run()`` returning fetched tensors.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable

import jax
import numpy as np

__all__ = ["describe_devices", "sync_by_value", "timed_steps"]


def describe_devices() -> tuple[list, int, str, bool]:
    """(devices, n_chips, platform, on_tpu) as jax reports them. Raises
    when there is no TPU and ``JAX_PLATFORMS=cpu`` was not requested: jax
    drops to the CPU with only a warning when it finds no chip, and a
    benchmark must not inherit that silently."""
    devices = jax.devices()
    platform = devices[0].platform
    on_tpu = platform == "tpu"
    requested = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if not on_tpu and requested != "cpu":
        raise RuntimeError(
            f"no TPU found (jax platform {platform!r}) and JAX_PLATFORMS=cpu "
            f"was not requested; a benchmark does not fall back to the CPU")
    return devices, len(devices), platform, on_tpu


def sync_by_value(metrics: dict) -> float:
    """Force execution of every step the loss data-depends on by
    fetching its value; returns the loss as a host float."""
    return float(jax.device_get(metrics["loss"]))


def timed_steps(
    step: Callable[[Any, Any], tuple[Any, dict]],
    state: Any,
    next_batch: Callable[[], Any],
    *,
    warmup: int,
    measured: int,
    log: Callable[[str], None] = lambda s: None,
) -> tuple[Any, float, float]:
    """Warmup then time ``measured`` chained steps.

    ``next_batch`` is called once per step (return the same resident
    batch for a device-throughput window, or pull from a prefetcher for
    a pipeline-fed window). Returns ``(state, steps_per_sec, loss)``;
    asserts the final loss is finite so a broken run cannot post a rate.
    """
    log("compiling + warmup...")
    metrics = None
    for _ in range(warmup):
        state, metrics = step(state, next_batch())
    if metrics is not None:  # warmup=0: nothing dispatched yet to sync
        sync_by_value(metrics)
    log("measuring...")
    t0 = time.perf_counter()
    for _ in range(measured):
        state, metrics = step(state, next_batch())
    loss = sync_by_value(metrics)
    dt = time.perf_counter() - t0
    log(f"final loss {loss:.4f} (finite => really trained)")
    # explicit raise, not assert: must survive `python -O` so a diverged
    # run can never post a throughput number
    if not np.isfinite(loss):
        raise RuntimeError(f"non-finite loss {loss}; refusing to report a rate")
    return state, measured / dt, loss
