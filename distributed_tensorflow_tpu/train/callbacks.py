"""Host-loop callbacks — one per reference session hook (SURVEY.md §2b
'Session hooks' row; $TF/python/training/basic_session_run_hooks.py).

Hooks decorated Session.run with extra fetches; callbacks observe the
*already-computed* per-step metrics dict the jit step returns. Metrics are
device arrays and fetching blocks on the step — so callbacks that read
values do it on a cadence (every_n), keeping the steady-state loop fully
async (host dispatches step N+1 while N executes).
"""

from __future__ import annotations

import logging
import os
import signal as signal_lib
import threading
import time
from typing import Any

import jax
import numpy as np

from ..obs import flightrec as flightrec_lib
from ..obs import goodput
from ..obs.registry import Registry, default_registry
from ..parallel import cluster
from ..utils import flops as flops_lib

logger = logging.getLogger(__name__)


class Callback:
    def on_train_start(self, trainer) -> None: ...
    def on_step_end(self, trainer, step: int, metrics: dict[str, Any]) -> None: ...
    def on_train_end(self, trainer) -> None: ...


class StalledError(RuntimeError):
    """A train step exceeded the Watchdog wall budget with
    ``abort_on_stall`` set. Raised *asynchronously* in the training
    thread, so the hung attempt dies as a CLASSIFIED failure —
    ``resilience.classify_failure`` maps it to ``stalled`` (restartable)
    instead of the silent ``train_watchdog_stalled`` gauge being the
    only record. Must be constructible with no arguments: the async
    raise instantiates the class bare."""

    def __init__(self, message: str = "train step exceeded the watchdog "
                                      "wall budget"):
        super().__init__(message)


class HeartbeatCallback(Callback):
    """Fleet-liveness beats from the step seam (resilience/fleet.py):
    every completed step rewrites this worker's heartbeat file with the
    new global step. Pure host file IO — the async dispatch-ahead loop
    is unchanged — and because beats come from the loop itself, a hung
    step STOPS the beats: that silence is exactly the signal the
    FleetSupervisor's missed-heartbeat detection consumes. Beats on
    ``on_train_start`` too, so the (possibly long) first-step compile
    window starts with proof of life."""

    def __init__(self, writer, every_n: int = 1, pace=None):
        """``pace``: optional ``pace(step)`` hook run before each
        step-seam beat — the control-plane IO-delay seam
        (``resilience.faults.FaultPlan.beat_pace``): a bounded sleep
        here models slow heartbeat IO, so gray-failure rounds exercise
        the monitor's LIVE-vs-DEAD judgment under late-but-regular
        beats."""
        if every_n < 1:
            raise ValueError("every_n must be >= 1")
        self.writer = writer
        self.every_n = every_n
        self.pace = pace

    def on_train_start(self, trainer):
        self.writer.beat(phase="train")

    def note_pause(self, seconds: float) -> None:
        """A sanctioned off-the-train-path pause (mid-train distributed
        eval) just ended: beat NOW so the silent window the monitor saw
        stops at the pause boundary instead of stretching into the next
        step. A pause longer than the fleet's stall budget still needs
        that budget sized for it — same rule as compile/restore silent
        windows (docs/resilience.md)."""
        self.writer.beat()

    def on_step_end(self, trainer, step, metrics):
        if step % self.every_n == 0:
            if self.pace is not None:
                self.pace(step)
            self.writer.beat(step=step)


class ElasticCallback(Callback):
    """Step-seam adapter for the elastic fleet client
    (resilience/fleet.ElasticWorker): after every completed step the
    client polls the fleet's SHARD_PLAN, applies any new sharding to the
    worker's data stream (``ElasticStream.reshard`` through
    ``on_reshard``), and — when the fleet orders a resize hold — PAUSES
    the loop here, at a step boundary, until the release names the
    barrier. Pairs with a ``HeartbeatCallback`` on the same writer so
    liveness continues through the pause (the client beats while
    holding). Place it BEFORE the CheckpointCallback: a hold must land
    between steps, not between a step and its cadence save.

    A barrier hold is a sanctioned off-the-train-path pause, so its
    wall time is broadcast to every ``note_pause``-aware peer callback
    (the PR 11 protocol the mid-train eval uses): the cadence meters
    keep measuring the train loop — the fleet books the same window as
    ``elastic_resize`` waste, and double-booking it as productive would
    lie twice — and an armed ``Watchdog`` re-arms at the pause boundary
    instead of aborting the holder mid-resize."""

    def __init__(self, client, clock=time.perf_counter):
        self.client = client
        self.clock = clock

    def _poll(self, trainer, step):
        t0 = self.clock()
        self.client.poll(step)
        pause = self.clock() - t0
        if pause > 0:
            for other in trainer.callbacks:
                if other is self:
                    continue
                note = getattr(other, "note_pause", None)
                if note is not None:
                    note(pause)

    def on_train_start(self, trainer):
        # apply whatever plan is already on disk before the first step
        # (a worker launched mid-resize must not train a stale shard)
        self._poll(trainer, int(trainer.state.step))

    def on_step_end(self, trainer, step, metrics):
        self._poll(trainer, step)


class FleetSnapshotCallback(Callback):
    """Step-seam driver for the fleet-observatory snapshot exporter
    (obs/fleetview.SnapshotExporter): after every ``every_n``-th step
    the worker's telemetry snapshot — registry dump + flight-recorder
    tail — is atomically rewritten next to its heartbeat, where the
    ``FleetSupervisor``'s aggregator (and ``tools/fleet_top.py``) folds
    it into the fleet-wide view. Pure host file IO on the exporter's
    injectable clock; best-effort by design — a full disk must degrade
    the fleet view, never kill the step that was about to be trained.
    The final export on ``on_train_end`` bypasses the exporter's rate
    limit so the run's last state always lands."""

    def __init__(self, exporter, every_n: int = 1):
        if every_n < 1:
            raise ValueError("every_n must be >= 1")
        self.exporter = exporter
        self.every_n = every_n

    def _export(self, step: int | None, force: bool = False) -> None:
        try:
            self.exporter.export(step=step, phase="train", force=force)
        except OSError:
            logger.warning("fleet telemetry snapshot export failed",
                           exc_info=True)

    def on_train_start(self, trainer):
        self._export(int(trainer.state.step))

    def on_step_end(self, trainer, step, metrics):
        if step % self.every_n == 0:
            self._export(step)

    def on_train_end(self, trainer):
        self._export(int(trainer.state.step), force=True)


class StopAtStep(Callback):
    """$TF basic_session_run_hooks.py:393 StopAtStepHook."""

    def __init__(self, last_step: int):
        self.last_step = last_step

    def on_step_end(self, trainer, step, metrics):
        if step >= self.last_step:
            trainer.request_stop(f"reached last_step={self.last_step}")


class MetricsLogger(Callback):
    """StepCounterHook + LoggingTensorHook (:674, :169): steps/sec,
    examples/sec, MFU, and the metric dict, every N steps. Only the chief
    logs (matching the reference's chief-only summaries), but every process
    *fetches* — keeping hosts in lockstep."""

    def __init__(self, every_n: int = 100, batch_size: int | None = None,
                 model_flops_per_step: float | None = None,
                 history: bool = False, clock=time.perf_counter):
        """``model_flops_per_step``: FORWARD FLOPs per step (the framework
        contract — every model's flops_per_example is fwd-only). The ×3
        training multiplier is applied by the shared MFU helper
        (obs/goodput.train_mfu), the one consumer site for both
        MetricsLogger and the ``mfu`` gauge. Where the running
        device kind has no entry in utils/flops.PEAK_FLOPS_BY_KIND (a CPU
        run) no ``mfu`` key is reported."""
        self.every_n = every_n
        self.batch_size = batch_size
        self.model_flops = model_flops_per_step
        self._peak: float | None = None
        self.clock = clock
        self._t0: float | None = None
        self._step0 = 0
        self.history: list[dict] = [] if history else None
        self.last: dict[str, float] = {}
        #: step `last` was fetched at — consumers reusing `last` (e.g.
        #: SummaryWriter) MUST check this, or a cadence mismatch writes
        #: stale scalars under a newer global_step.
        self.last_step: int | None = None

    def on_train_start(self, trainer):
        self._t0 = None
        self.last, self.last_step = {}, None
        if self.model_flops:
            self._peak = flops_lib.known_peak_flops()

    def note_pause(self, seconds: float) -> None:
        """Wall time spent OFF the train path between two steps (a
        mid-train distributed eval) — shift the rate baseline forward so
        steps/sec, examples/sec, and the derived MFU don't absorb it."""
        if self._t0 is not None:
            self._t0 += max(float(seconds), 0.0)

    def on_step_end(self, trainer, step, metrics):
        if step % self.every_n != 0:
            return
        fetched = {k: float(np.asarray(v)) for k, v in metrics.items()}
        now = self.clock()
        if self._t0 is not None:
            dt = now - self._t0
            steps_per_sec = (step - self._step0) / max(dt, 1e-9)
            fetched["steps_per_sec"] = steps_per_sec
            if self.batch_size:
                fetched["examples_per_sec"] = steps_per_sec * self.batch_size
            if self.model_flops and self._peak:
                # one MFU definition for log line, bench JSON, and gauge:
                # obs/goodput.py applies the fwd+bwd multiplier
                fetched["mfu"] = goodput.train_mfu(
                    self.model_flops, steps_per_sec,
                    peak_per_chip=self._peak)
        self._t0, self._step0 = now, step
        self.last, self.last_step = fetched, step
        if self.history is not None:
            self.history.append({"step": step, **fetched})
        if cluster.is_chief():
            msg = " ".join(
                f"{k}={v:.6g}" for k, v in sorted(fetched.items())
            )
            logger.info("step %d: %s", step, msg)


def _fresh_scalars(metrics_logger: "MetricsLogger | None", step: int,
                   metrics: dict[str, Any]) -> dict[str, float]:
    """Scalars for ``step``: reuse the paired logger's fetched dict ONLY
    if its fetch happened at this very step (it ran earlier in the
    callback list with an aligned cadence) — `last` from an older step
    consumed under the current step would silently shift every curve
    (the SummaryWriter stale-scalar bug). Otherwise fetch directly,
    paying the same cadence'd device sync the logger would."""
    if metrics_logger is not None and metrics_logger.last_step == step:
        return dict(metrics_logger.last)
    return {k: float(np.asarray(v)) for k, v in metrics.items()}


class SummaryWriter(Callback):
    """SummarySaverHook analog ($TF basic_session_run_hooks.py:793,
    SURVEY.md §5.5): writes TensorBoard scalar event files via tensorboardX
    (same wire format as tf.summary). Chief-only — matching the reference's
    chief-only summaries — and cadence-gated like MetricsLogger so the
    steady-state loop stays async. Throughput/MFU scalars come from the
    paired MetricsLogger when one is given (avoids double-fetching)."""

    def __init__(self, logdir: str, every_n: int = 100,
                 metrics_logger: "MetricsLogger | None" = None):
        self.logdir = logdir
        self.every_n = every_n
        self.metrics_logger = metrics_logger
        self._writer = None

    def on_train_start(self, trainer):
        if cluster.is_chief():
            from tensorboardX import SummaryWriter as TBWriter

            self._writer = TBWriter(self.logdir)

    def on_step_end(self, trainer, step, metrics):
        if self._writer is None or step % self.every_n != 0:
            return
        for k, v in _fresh_scalars(self.metrics_logger, step,
                                   metrics).items():
            self._writer.add_scalar(f"train/{k}", v, global_step=step)

    def on_train_end(self, trainer):
        if self._writer is not None:
            self._writer.flush()
            self._writer.close()
            self._writer = None


class TelemetryCallback(Callback):
    """Canonical metrics sink: mirrors the train loop into an
    obs.Registry (scrape-able via obs.export, mergeable across hosts) —
    the registry-backed replacement for reading ``MetricsLogger.last``/
    ``history`` out of band.

    Two cadences, preserving the async steady state:

    - EVERY step: a host-clock step-latency observation into the
      ``train_step_seconds`` histogram plus a ``train_steps_total``
      tick. Pure host arithmetic — never touches the device metrics, so
      the loop's dispatch-ahead pipelining is unchanged.
    - Every ``every_n`` steps: scalar gauges (``train_<name>``). Reuses
      the paired MetricsLogger's already-fetched dict when its fetch
      happened at this step (same staleness rule as SummaryWriter);
      otherwise fetches directly — the same cadence'd device sync every
      other observer pays.

    With ``track_goodput`` (default on) the same host clock also feeds
    the goodput ledger (obs/goodput.py): the interval from
    ``on_train_start`` to the first completed step — compile + warmup —
    is booked as ``wasted_seconds_total{cause=compile_warmup}``, every
    later inter-step interval as productive seconds. Counters, so the
    accounting survives supervised restarts by the registry's
    merge-not-reset invariant.
    """

    def __init__(self, registry: Registry | None = None, every_n: int = 100,
                 metrics_logger: "MetricsLogger | None" = None,
                 clock=time.perf_counter, track_goodput: bool = True):
        self.registry = registry if registry is not None else default_registry()
        self.every_n = every_n
        self.metrics_logger = metrics_logger
        self.clock = clock
        self.track_goodput = track_goodput
        self._t_prev: float | None = None
        self._t_start: float | None = None
        self._step_prev = 0
        self._m_step = self.registry.histogram(
            "train_step_seconds", "host wall-clock between step dispatches")
        self._m_steps = self.registry.counter(
            "train_steps_total", "train steps completed")
        self._m_gstep = self.registry.gauge(
            "train_global_step", "latest completed global step")

    @staticmethod
    def _gauge_name(key: str) -> str:
        sane = "".join(c if c.isalnum() or c == "_" else "_" for c in key)
        return f"train_{sane}"

    def on_train_start(self, trainer):
        self._t_prev = None
        self._t_start = self.clock() if self.track_goodput else None

    def note_pause(self, seconds: float) -> None:
        """Wall time spent OFF the train path between two steps (a
        mid-train distributed eval): shift the inter-step baseline
        forward so the next ``train_step_seconds`` observation and its
        productive-seconds booking cover only step time. Eval wall time
        is deliberately neither productive nor wasted in the goodput
        ledger — it buys evaluation, not training progress, and booking
        it as either would skew ``goodput_fraction``."""
        pause = max(float(seconds), 0.0)
        if self._t_prev is not None:
            self._t_prev += pause
        elif self._t_start is not None:
            # pause landed inside the warmup window: keep it out of the
            # compile_warmup waste bucket too
            self._t_start += pause

    def on_step_end(self, trainer, step, metrics):
        now = self.clock()
        if self._t_prev is not None:
            # mean host latency per step since the last observation (the
            # loop calls us every step, so this is one step's wall time)
            n = max(step - self._step_prev, 1)
            self._m_step.observe((now - self._t_prev) / n)
            if self.track_goodput:
                goodput.note_productive(now - self._t_prev,
                                        registry=self.registry)
        elif self.track_goodput and self._t_start is not None:
            # attempt's first completed step: train_start → here is jit
            # compile + warmup, not productive throughput — the histogram
            # skips it (no baseline) and goodput books it as warmup waste
            goodput.note_wasted(goodput.WASTE_COMPILE_WARMUP,
                                now - self._t_start, registry=self.registry)
        self._t_prev, self._step_prev = now, step
        self._m_steps.inc()
        self._m_gstep.set(step)
        if step % self.every_n != 0:
            return
        scalars = _fresh_scalars(self.metrics_logger, step, metrics)
        for k, v in scalars.items():
            self.registry.gauge(
                self._gauge_name(k), "train metric (cadence-sampled)"
            ).set(v)
        if self.track_goodput and "mfu" in scalars:
            # mirror the paired logger's MFU into the canonical gauge
            self.registry.gauge(
                goodput.MFU, "model FLOPs utilization of the train step"
            ).set(scalars["mfu"])


class NaNGuard(Callback):
    """NanTensorHook (:761): stop (or raise) when the step reports non-finite
    loss/grads. Reads the on-device `grads_finite`/`loss` signals the step
    engine piggybacks on its output (SURVEY.md §5.5).

    When the step carries the per-step ``nonfinite`` flag
    (``StepOptions(skip_nonfinite=True)``, docs/resilience.md "Numeric
    anomalies"), the guard reads IT on every step instead of the
    cadence'd loss fetch: the old cadence left a non-finite step N
    unnoticed until the next multiple of ``every_n`` — after donation
    had already overwritten the state — so the abort was late and the
    blamed step wrong. With the flag the abort is immediate and exact
    (and the in-graph guard means the state it aborts with is still the
    last healthy one). The per-step scalar fetch trades the
    dispatch-ahead overlap for exactness — the same trade
    ``AnomalyPolicy`` makes, which supersedes this guard when wired
    (skipped steps never reach callbacks at all). Inside ``Trainer.fit``
    with the guard on and NO policy, the loop itself fails fast on the
    flag BEFORE callbacks run (a flagged no-op step must not be counted
    — see the loop), so this branch — ``fail_fast=False`` included — is
    reached only by custom/externally-driven loops; for
    skip-and-continue under Trainer, wire an AnomalyPolicy."""

    def __init__(self, every_n: int = 10, fail_fast: bool = True):
        self.every_n = every_n
        self.fail_fast = fail_fast

    def on_step_end(self, trainer, step, metrics):
        if "nonfinite" in metrics:
            from .step import step_nonfinite

            if step_nonfinite(metrics):
                self._bad(trainer, step)
            return
        if step % self.every_n != 0:
            return
        bad = False
        if "grads_finite" in metrics:
            bad |= float(np.asarray(metrics["grads_finite"])) == 0.0
        if "loss" in metrics:
            bad |= not np.isfinite(np.asarray(metrics["loss"]))
        if bad:
            self._bad(trainer, step)

    def _bad(self, trainer, step: int) -> None:
        msg = f"non-finite loss/gradients at step {step}"
        if self.fail_fast:
            raise FloatingPointError(msg)
        trainer.request_stop(msg)


def _async_raise(ident: int, exc_type: type[BaseException]) -> None:
    """Raise ``exc_type`` asynchronously in thread ``ident``
    (PyThreadState_SetAsyncExc) — the only host-side way to abort a
    train loop that is no longer reaching its own callbacks. Delivery
    happens at that thread's next bytecode; a thread blocked in a C
    call sees it when the call returns."""
    import ctypes

    n = ctypes.pythonapi.PyThreadState_SetAsyncExc(
        ctypes.c_ulong(ident), ctypes.py_object(exc_type))
    if n != 1:
        logger.error(
            "async %s delivery to thread %d failed (SetAsyncExc hit %d "
            "threads)", exc_type.__name__, ident, n)


def _async_cancel(ident: int) -> None:
    """Revoke a not-yet-delivered async exception for thread ``ident``
    (SetAsyncExc with NULL; ctypes passes None as NULL). No-op when the
    exception already delivered."""
    import ctypes

    ctypes.pythonapi.PyThreadState_SetAsyncExc(ctypes.c_ulong(ident), None)


#: ``abort_on_stall`` delivery for MAIN-THREAD loops: a process signal.
#: SetAsyncExc delivery can be lost on this CPython while the target
#: thread blocks inside C sleeps (observed: a hung-loop spin that never
#: received its StalledError); a signal instead wakes blocking C calls
#: via EINTR and its Python handler runs in the main thread at the next
#: bytecode, where it raises StalledError directly. The handler is
#: installed once, process-wide, on first arm and STAYS installed: with
#: no abort pending it ignores the signal, so a late delivery can never
#: hit SIGUSR1's default action (process termination) or kill a
#: recovered run. SetAsyncExc remains the best-effort fallback for
#: loops driven from non-main threads.
_STALL_SIGNAL = signal_lib.SIGUSR1
#: ids of watchdogs with an abort pending. Plain module-level set: the
#: mutations are GIL-atomic, and the signal handler must not take locks
#: (it preempts arbitrary main-thread code, possibly a lock holder).
_pending_aborts: set[int] = set()
_stall_handler_installed = False


def _stall_signal_handler(signum, frame):
    if _pending_aborts:
        _pending_aborts.clear()
        raise StalledError()
    logger.warning(
        "stall-abort signal received with no abort pending; ignored")


def _install_stall_handler() -> None:
    """Main-thread only (signal.signal requirement); idempotent."""
    global _stall_handler_installed
    if not _stall_handler_installed:
        signal_lib.signal(_STALL_SIGNAL, _stall_signal_handler)
        _stall_handler_installed = True


class Watchdog(Callback):
    """Host-side hung-step detector (docs/resilience.md): if no
    ``on_step_end`` arrives within ``budget_s`` wall seconds, flag the
    stall to the obs registry — ``train_watchdog_stalled`` gauge goes to
    1 and ``train_watchdog_stalls_total`` counts the event — and log an
    error. The next completed step clears the gauge (recovery), so a
    scrape sees `stalled==1` exactly while a step is overdue.

    Detection only by default: a stuck collective (one host dead in a
    psum) cannot be un-stuck host-side — the signal exists so the
    scrape surface / job scheduler can decide to kill-and-restart,
    which the checkpoint layer turns into resume-from-last-save. With
    ``abort_on_stall=True`` the watchdog goes one step further: on the
    stall edge it raises ``StalledError`` in the thread that entered
    ``on_train_start``, so a hung-but-interruptible step dies as a
    *classified, restartable* failure (``resilience.classify_failure``
    → ``stalled``) that the in-process Supervisor rolls back to the
    last valid checkpoint. Delivery: when the loop runs on the MAIN
    thread (the normal case) the abort arrives as a process signal
    whose handler raises ``StalledError`` — this interrupts blocking C
    sleeps via EINTR and, unlike PyThreadState_SetAsyncExc, cannot be
    silently lost; SetAsyncExc is the best-effort fallback for loops on
    other threads. Limitation: a thread wedged inside a C call that
    ignores EINTR (a device wait, a stuck collective) only aborts when
    the call returns; process-level supervision (resilience/fleet.py)
    is the layer that handles those, by killing the process. The
    monitor runs on a daemon poll thread; ``clock`` is injectable so
    tests (and the fault harness's ClockStall) can drive time
    deterministically.
    """

    def __init__(self, budget_s: float = 300.0, registry: Registry | None = None,
                 poll_s: float | None = None, clock=time.monotonic,
                 flightrec=None, abort_on_stall: bool = False):
        if budget_s <= 0:
            raise ValueError("budget_s must be positive")
        self.budget_s = budget_s
        self.flightrec = (flightrec if flightrec is not None
                          else flightrec_lib.default_recorder())
        self.registry = registry if registry is not None else default_registry()
        self.poll_s = poll_s if poll_s is not None else max(
            min(budget_s / 4, 1.0), 0.005)
        self.clock = clock
        self.abort_on_stall = abort_on_stall
        self._beat: float | None = None
        self._loop_ident: int | None = None  # thread to abort on stall
        self._abort_issued = False           # abort issued, not consumed
        self._signal_abort = False           # deliver via signal (main thread)
        self._lock = threading.Lock()  # orders beat writes vs stall flags
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._m_stalled = self.registry.gauge(
            "train_watchdog_stalled",
            "1 while no train step has completed within the watchdog budget")
        self._m_stalls = self.registry.counter(
            "train_watchdog_stalls_total",
            "times a train step exceeded the watchdog wall budget")

    def on_train_start(self, trainer):
        # delivery mode decided (and the handler installed) on the loop
        # thread, BEFORE the poll thread exists
        self._signal_abort = (
            self.abort_on_stall
            and threading.current_thread() is threading.main_thread())
        if self._signal_abort:
            _install_stall_handler()
        # same critical section as on_step_end/_watch: a supervised
        # restart re-enters here while a previous attempt's poll thread
        # may still be draining (dtflint: lock-discipline)
        with self._lock:
            self._beat = self.clock()
            self._loop_ident = threading.get_ident()
            self._m_stalled.set(0.0)
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._watch, daemon=True, name="train-watchdog")
        self._thread.start()

    def note_pause(self, seconds: float) -> None:
        """A sanctioned pause (mid-train distributed eval) just ended:
        re-arm the beat so the budget clock restarts at the pause
        boundary — without this, a stall abort could fire right after a
        long eval even though the loop is healthy. An eval LONGER than
        the budget still flags mid-pause (the poll thread cannot know a
        pause is sanctioned until it ends); size ``budget_s`` above the
        expected eval wall time, the same rule as compile windows."""
        with self._lock:
            if self._beat is not None:
                self._beat = self.clock()

    def on_step_end(self, trainer, step, metrics):
        with self._lock:
            if self._m_stalled.value:
                logger.warning("watchdog: step %d completed, stall cleared",
                               step)
                self._m_stalled.set(0.0)
            self._beat = self.clock()
            cancel = self._take_abort_unlocked()
        if cancel is not None:
            # the flagged step completed after all: progress wins — a
            # pending (undelivered) abort must not kill the healthy run.
            # Tiny race left: an abort delivered between the flag and
            # this revoke still aborts, which is within semantics (that
            # step really did exceed the budget).
            _pending_aborts.discard(id(self))
            if not self._signal_abort:
                _async_cancel(cancel)
            logger.warning("watchdog: step %d completed before the abort "
                           "delivered; revoked", step)

    def on_train_end(self, trainer):
        with self._lock:
            cancel = self._take_abort_unlocked()
        if cancel is not None:
            # loop exited with the abort still undelivered: revoke so it
            # cannot land in post-training code (final save, teardown)
            _pending_aborts.discard(id(self))
            if not self._signal_abort:
                _async_cancel(cancel)
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _take_abort_unlocked(self) -> int | None:
        """Consume the abort-in-flight marker; caller holds the lock."""
        if not self._abort_issued:
            return None
        self._abort_issued = False
        return self._loop_ident

    def _watch(self) -> None:
        while not self._stop.wait(self.poll_s):
            # beat read, staleness check, and flag set are one critical
            # section with on_step_end — otherwise a step landing
            # between check and set leaves a spurious stall flagged and
            # the edge-triggered counter inflated forever
            with self._lock:
                if self._beat is None:
                    continue
                overdue = self.clock() - self._beat
                if overdue <= self.budget_s or self._m_stalled.value:
                    continue
                # edge-triggered: one count per stall, gauge stays up
                # until a step completes
                self._m_stalled.set(1.0)
                self._m_stalls.inc()
                abort_ident = (self._loop_ident if self.abort_on_stall
                               else None)
                if abort_ident is not None:
                    # issue + marker in ONE critical section: a
                    # concurrent on_step_end revoke is then strictly
                    # before (sees no marker, nothing issued yet) or
                    # strictly after (sees marker, revokes a real issue)
                    self._abort_issued = True
                    if self._signal_abort:
                        _pending_aborts.add(id(self))
                        os.kill(os.getpid(), _STALL_SIGNAL)
                    else:
                        _async_raise(abort_ident, StalledError)
            # outside the lock: the recorder has its own
            self.flightrec.emit("watchdog_stall",
                                overdue_s=round(overdue, 3),
                                budget_s=self.budget_s,
                                abort=bool(abort_ident))
            logger.error(
                "watchdog: no step completed for %.1fs "
                "(budget %.1fs) — host loop or a collective is hung",
                overdue, self.budget_s,
            )


class Profiler(Callback):
    """ProfilerHook (:1013) → jax.profiler traces (same XPlane/TensorBoard
    wire format as TF's, SURVEY.md §5.1)."""

    def __init__(self, logdir: str, start_step: int = 10, num_steps: int = 5):
        self.logdir = logdir
        self.start_step = start_step
        self.stop_step = start_step + num_steps
        self._active = False

    def on_step_end(self, trainer, step, metrics):
        if step == self.start_step and not self._active:
            jax.profiler.start_trace(self.logdir)
            self._active = True
        elif step >= self.stop_step and self._active:
            jax.tree.map(
                lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
                metrics,
            )
            jax.profiler.stop_trace()
            self._active = False
            if cluster.is_chief():
                logger.info("profile written to %s", self.logdir)

    def on_train_end(self, trainer):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False


class CheckpointCallback(Callback):
    """CheckpointSaverHook (:524): delegates cadence + retention to the
    checkpoint manager (train/checkpoint.py); also saves on clean train end
    and on preemption (SURVEY.md §5.3/5.4). Named distinctly from the
    train.checkpoint.Checkpointer manager it wraps."""

    def __init__(self, manager):
        self.manager = manager

    def on_step_end(self, trainer, step, metrics):
        self.manager.maybe_save(step, trainer.state)

    def on_train_end(self, trainer):
        if trainer.failed:
            # Aborting on an error (e.g. NaNGuard): the in-memory state may
            # be poisoned — never let it become the latest checkpoint. The
            # background writer is still joined (bounded) so teardown never
            # races a half-written commit — but its stored error must not
            # MASK the failure that aborted the run: log it and let the
            # original exception propagate.
            logger.warning("skipping final checkpoint: training failed")
            try:
                self.manager.wait()
            except Exception:
                logger.exception(
                    "async checkpoint writer also failed during aborted run")
            return
        # final save is synchronous by contract; wait() then drains any
        # in-flight cadence commit and re-raises a stored background-save
        # error — a failed async save poisons the run here instead of
        # silently dropping a step
        self.manager.save(int(trainer.state.step), trainer.state, force=True,
                          trigger="final")
        self.manager.wait()
