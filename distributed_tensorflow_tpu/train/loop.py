"""Host run loop — replaces MonitoredTrainingSession (SURVEY.md §2b, §3.1).

The reference's loop was `while not mon_sess.should_stop():
mon_sess.run(train_op)` behind four session wrappers (_RecoverableSession /
_CoordinatedSession / _HookedSession, $TF monitored_session.py:1238-1447).
Here the loop is plain Python driving one jit-ed SPMD step: the
chief-vs-worker split, session recovery, and graph-side hook fetches have no
TPU equivalent — recovery is checkpoint-restart (train/checkpoint.py) and
hooks are host callbacks over the step's returned metrics.

The loop stays *async*: the host dispatches step N+1 while N executes on
device; only cadence'd callbacks (logging every N) synchronize.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Callable, Iterable, Sequence

import jax
from jax.sharding import Mesh

from ..obs import flightrec as flightrec_lib
from ..obs import trace as trace_lib
from ..parallel import sharding as sh
from . import step as step_lib
from .callbacks import Callback, CheckpointCallback
from .checkpoint import PreemptionSaved

logger = logging.getLogger(__name__)


class Trainer:
    """Owns: the compiled step, the state, the data feed, the callbacks.

    Replaces the MonitoredTrainingSession factory (monitored_session.py:428)
    plus the Supervisor legacy path (supervisor.py:40): one class, no roles.
    """

    def __init__(
        self,
        train_step: Callable,
        state: step_lib.TrainState,
        mesh: Mesh,
        spec_tree: step_lib.TrainState,
        callbacks: Sequence[Callback] = (),
        donate: bool = True,
        emergency_checkpoint=None,
        flightrec=None,
        postmortem_dir: str | None = None,
        anomaly_policy=None,
        tracer=None,
    ):
        self.mesh = mesh
        self.spec_tree = spec_tree
        self.state = state
        self.callbacks = list(callbacks)
        self._stop_reason: str | None = None
        self.failed = False  # set when fit() aborts on an exception
        #: set when fit() exited via a coordinated preemption save — the
        #: signal resilience.Supervisor uses to distinguish "restart and
        #: resume" from a deliberate stop without string-matching reasons
        self.preempted = False
        #: Checkpointer used for the best-effort save on an unhandled
        #: step exception (docs/resilience.md). Defaults to the manager
        #: of the first CheckpointCallback in ``callbacks``, so wiring a
        #: CheckpointCallback is enough to get crash-safe exits.
        self.emergency_checkpoint = emergency_checkpoint
        if self.emergency_checkpoint is None:
            for cb in self.callbacks:
                if isinstance(cb, CheckpointCallback):
                    self.emergency_checkpoint = cb.manager
                    break
        #: flight recorder for the loop's causal events (obs/flightrec.py);
        #: defaults to the process ring so every layer shares one timeline
        self.flightrec = (flightrec if flightrec is not None
                          else flightrec_lib.default_recorder())
        #: span ring for the loop's host phases (obs/trace.py; the span
        #: names are a contract, docs/observability.md "Span tracing");
        #: defaults to the process ring, as the flight recorder does
        self.tracer = (tracer if tracer is not None
                       else trace_lib.default_tracer())
        #: where an abnormal-exit postmortem dump lands; defaults to the
        #: emergency checkpointer's directory (the run dir)
        self.postmortem_dir = postmortem_dir
        if self.postmortem_dir is None:
            self.postmortem_dir = getattr(
                getattr(self.emergency_checkpoint, "cfg", None),
                "directory", None)
        #: resilience/anomaly.AnomalyPolicy (duck-typed: ``observe(step,
        #: metrics) -> bool``) — pairs with StepOptions(skip_nonfinite):
        #: a step the policy reports as skipped was a device-side no-op,
        #: so the loop does not count it and no callback sees it. Kept a
        #: plain attribute (no import) so train/ never depends on
        #: resilience/.
        self.anomaly_policy = anomaly_policy
        if donate:
            self.step_fn = step_lib.jit_train_step(train_step, mesh, spec_tree)
        else:
            self.step_fn = jax.jit(train_step)

    # -- control ----------------------------------------------------------
    def request_stop(self, reason: str = "") -> None:
        """Cooperative stop — the Coordinator.request_stop analog
        ($TF coordinator.py:28)."""
        if self._stop_reason is None:
            self._stop_reason = reason or "requested"

    @property
    def should_stop(self) -> bool:
        return self._stop_reason is not None

    @property
    def stop_reason(self) -> str | None:
        """Why the loop stopped (None while running / never stopped)."""
        return self._stop_reason

    # -- data -------------------------------------------------------------
    def put_batch(self, batch: Any) -> Any:
        """Host batch → sharded global device array (sharding.put_host_batch)."""
        return sh.put_host_batch(self.mesh, batch)

    # -- loop -------------------------------------------------------------
    def fit(
        self,
        data: Iterable[Any],
        num_steps: int | None = None,
    ) -> step_lib.TrainState:
        # Host-side step mirror: reading state.step would sync the device
        # every iteration and serialize dispatch with execution.
        step_now = int(self.state.step)
        rec = self.flightrec
        rec.emit("train_start", step=step_now)
        try:
            # inside the try: a raising on_train_start (or iter()) must
            # still reach the finally's on_train_end, or started
            # resources leak — e.g. Watchdog's poll thread would flag a
            # phantom stall in the registry forever
            for cb in self.callbacks:
                cb.on_train_start(self)
            data_iter = iter(data)
            tracer = self.tracer
            while not self.should_stop:
                if num_steps is not None and step_now >= num_steps:
                    self.request_stop(f"num_steps={num_steps}")
                    break
                with tracer.span("train.step", step=step_now + 1):
                    with tracer.span("next_batch"):
                        try:
                            batch = next(data_iter)
                        except StopIteration:
                            self.request_stop("data exhausted")
                            break
                    rec.emit("step_start", step=step_now + 1)
                    with tracer.span("put_batch"):
                        batch = self.put_batch(batch)
                    with tracer.span("dispatch"):
                        self.state, metrics = self.step_fn(self.state, batch)
                        if self.anomaly_policy is not None:
                            if self.anomaly_policy.observe(step_now + 1,
                                                           metrics):
                                # the compiled step kept the old state
                                # bit-identically (in-graph nonfinite
                                # guard): the batch vanishes from the
                                # trajectory — not a completed step, so
                                # neither the step mirror nor any callback
                                # may count it. The policy already blamed +
                                # quarantined the index and emitted
                                # anomaly_skip (which is what resolves this
                                # step's dangling step_start in a
                                # postmortem); a spent skip budget raises
                                # out of observe() into the classified-exit
                                # path below (poisoned), with the state
                                # still clean.
                                continue
                        elif step_lib.step_nonfinite(metrics):
                            # guard on, no policy wired: fail fast HERE,
                            # before the step is counted. Counting it would
                            # desync the host mirror from the device step
                            # counter (the guard kept state.step unchanged)
                            # and mislabel every later checkpoint by one.
                            # The state is still the last healthy one, so
                            # the emergency save below lands under its true
                            # step number; the exception classifies
                            # poisoned — the pre-guard NaNGuard semantics,
                            # made exact and immediate.
                            raise FloatingPointError(
                                f"non-finite loss/gradients at step "
                                f"{step_now + 1} (in-graph guard skipped "
                                "the update; wire an AnomalyPolicy to "
                                "skip-and-continue instead)")
                    step_now += 1
                    with tracer.span("callbacks"):
                        for cb in self.callbacks:
                            cb.on_step_end(self, step_now, metrics)
                    # after the callbacks: step_end marks the step COMPLETE
                    # (checkpoint cadence included), so a missing step_end
                    # in a postmortem points at the exact step that died
                    rec.emit("step_end", step=step_now)
        except PreemptionSaved as e:
            # Clean preemption exit (SURVEY.md §5.3): state is safely on
            # disk; stop so the scheduler — or an in-process
            # resilience.Supervisor — can restart-and-resume.
            self.preempted = True
            self.request_stop(str(e))
        except BaseException as e:
            self.failed = True
            rec.emit("train_exception", step=step_now,
                     etype=type(e).__name__, error=repr(e)[:200])
            # Crash-safe exit: one best-effort emergency checkpoint of
            # the last completed step before re-raising. save() itself
            # applies validate_before_save, so a poisoned state (the
            # NaNGuard abort path) is refused and never becomes the
            # latest checkpoint; any error here must not mask the
            # original exception.
            self._emergency_save(step_now)
            # abnormal exit: dump the flight recorder and the span ring
            # as a postmortem (best-effort, never masks the original
            # exception)
            self._dump_postmortem(f"train_exception:{type(e).__name__}")
            raise
        finally:
            for cb in self.callbacks:
                cb.on_train_end(self)
        rec.emit("train_stop", step=step_now, reason=self._stop_reason or "")
        if self._stop_reason:
            logger.info("training stopped: %s", self._stop_reason)
        return self.state

    def _emergency_save(self, step: int) -> None:
        """Best-effort checkpoint on an unhandled exception: whatever
        survives validation is worth keeping so the restart resumes from
        step N instead of the last cadence save. Covers host-side
        failures — a dead data iterator, a raising callback — where the
        state really is the last completed step's. For a DEVICE-side
        step failure (deferred async XlaRuntimeError, donation already
        consumed) the state may be unreadable; fetching it then raises
        inside save(), is caught below, and the restart falls back to
        the last cadence save — best-effort means exactly that."""
        ckpt = self.emergency_checkpoint
        if ckpt is None or step <= 0:
            return
        try:
            if ckpt.save(step, self.state, force=True, trigger="emergency"):
                ckpt.wait()
                self.flightrec.emit("emergency_checkpoint", step=step,
                                    saved=True)
                logger.warning("emergency checkpoint saved at step %d", step)
            else:
                self.flightrec.emit("emergency_checkpoint", step=step,
                                    saved=False)
                logger.warning(
                    "emergency checkpoint at step %d not written "
                    "(refused by validation or already on disk)", step
                )
        except Exception:
            self.flightrec.emit("emergency_checkpoint", step=step,
                                saved=False, error="save raised")
            logger.exception("emergency checkpoint at step %d failed", step)

    def _dump_postmortem(self, reason: str) -> None:
        """Best-effort JSONL postmortem into the run dir
        (tools/postmortem.py renders it), with the span ring beside it
        under the same suffix (``postmortem-1.jsonl`` / ``spans-1.jsonl``);
        on the abnormal exit path it must never raise past the original
        failure — the shared helper guarantees that for the recorder, the
        handler below for the ring. No directory, nothing written."""
        path = flightrec_lib.dump_postmortem(self.flightrec,
                                             self.postmortem_dir,
                                             reason=reason)
        if path is None:
            return
        head, tail = os.path.split(path)
        try:
            self.tracer.dump(os.path.join(
                head, tail.replace("postmortem", "spans", 1)))
        except Exception:
            logger.exception("span-ring postmortem dump failed")
