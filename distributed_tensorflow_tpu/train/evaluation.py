"""Distributed evaluation — eval sharded across the mesh, bit-exactly.

The MLPerf-0.6 TPU-pod paper (arXiv:1909.09756) lists distributed
evaluation among the structural changes that made pod-scale training
honest: serial evaluation either stalls the train loop or runs on a
separate underpowered evaluator, and both get worse with scale. Here
the eval set is sharded over the mesh's batch axes and every device
evaluates its shard with the full weights — the same
summed-sufficient-statistic contract the metrics registry and
``utils/metrics.py``'s AUC histograms already use.

**Bit-exactness contract.** A sharded eval must report the same loss a
serial evaluator would, to the BIT — otherwise quality gates drift with
the mesh shape and nobody can compare runs across topologies. Plain
GSPMD partitioning of a flat-batch ``eval_fn`` does NOT have this
property (measured on the 8-device CPU rig: partitioning retiles the
local matmuls, changing FMA order in the last ulp, and the cross-shard
``psum`` reorders the reduction again). The construction here pins the
reduction tree to the PROGRAM rather than the partitioning:

1. the batch is split over the mesh batch axes with ``shard_map``, so
   each device runs the eval body compiled at the LOCAL shard shape —
   the exact program a serial evaluator runs chunk by chunk;
2. per-shard partial sums come back stacked ``[shards, ...]`` (no
   device-side cross-shard reduction);
3. the cross-shard and cross-batch reduction happens on the HOST in
   float64, shard-major, fixed order.

A serial evaluator that walks the same chunks in the same order
computes the identical float sequence, so equality is structural —
``tests/test_distributed_eval.py`` proves it on the 8-device mesh. The
contract is per chunking: meshes that cut the batch into the same
number of shards agree to the bit; a different shard count is a
different chunk shape, XLA sums each chunk's float32 statistics in its
own order, and the results agree to float32 rounding, not to the bit.

The eval body receives params/model_state REPLICATED (``in_specs
P()``): distributed eval parallelizes the *batch*; when the stored
state is sharded (fsdp/tp), jit inserts the gather. The host fetch of
the stacked partials is the only synchronization — the train loop's
step cadence is untouched (no host syncs inside any step function;
dtflint's host-sync-in-step rule covers ``eval_step`` by name).
"""

from __future__ import annotations

import itertools
import logging
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..obs import flightrec as flightrec_lib
from ..obs.registry import Registry, default_registry
from ..parallel import mesh as mesh_lib
from ..parallel import sharding as sh
from ..utils import metrics as metrics_lib
from . import step as step_lib

__all__ = [
    "EVAL_STEPS",
    "make_sharded_eval_step",
    "ShardedEvaluator",
    "derive_metrics",
]

logger = logging.getLogger(__name__)

#: metric name (docs/observability.md "Distributed eval")
EVAL_STEPS = "eval_steps_total"


def batch_shards(mesh) -> int:
    """How many ways the batch dimension splits on this mesh."""
    return mesh_lib.mesh_axis_size(mesh, mesh_lib.BATCH_AXES)


def make_sharded_eval_step(eval_fn, mesh) -> Callable:
    """Jit an eval step that returns PER-SHARD partial sums, stacked
    ``[shards, ...]`` per metric, one row per batch shard.

    ``eval_fn(params, model_state, batch) -> dict`` of summed sufficient
    statistics (the workload contract). The body runs under shard_map
    over the batch axes at local shard shape — see the module docstring
    for why that, and not plain GSPMD, is what makes the result
    partition-invariant. Callers reduce the rows host-side
    (``ShardedEvaluator`` does, in float64, shard-major)."""

    def body(params, model_state, chunk):
        out = eval_fn(params, model_state, chunk)
        # one leading row per shard; out_specs stacks rows over the
        # batch axes instead of psum-ing them on device
        return {k: jnp.reshape(v, (1,) + jnp.shape(v))
                for k, v in out.items()}

    def eval_step(state, batch):
        in_specs = (P(), P(), jax.tree.map(
            lambda x: sh.batch_spec(jnp.ndim(x)), batch))
        fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=P(mesh_lib.BATCH_AXES),
                           check_vma=False)
        return fn(state.params, state.model_state, batch)

    return jax.jit(eval_step)


class ShardedEvaluator:
    """The distributed-eval loop: sharded per-batch partials, host-side
    float64 accumulation, obs instrumentation.

    One instance per (eval_fn, mesh) — the jitted step is cached on it,
    so periodic mid-train evals never retrace. Each executed eval batch
    ticks ``eval_steps_total``; each pass emits ``eval_start`` /
    ``eval_end`` flight-recorder events. Two documented fallbacks to
    the flat (unsharded-reduction) step, each logged once, both correct
    but outside the bit-exactness contract: batches whose leading
    dimension does not divide by the mesh's batch-shard count, and eval
    bodies that themselves use mesh axes (sharding constraints /
    collectives — e.g. wide_deep's sharded embedding lookups), which
    cannot nest under shard_map's manual axes and are detected at the
    first trace."""

    def __init__(self, eval_fn, mesh, registry: Registry | None = None,
                 flightrec=None):
        self.mesh = mesh
        self.shards = batch_shards(mesh)
        self.registry = (registry if registry is not None
                         else default_registry())
        self.flightrec = (flightrec if flightrec is not None
                          else flightrec_lib.default_recorder())
        self._sharded = make_sharded_eval_step(eval_fn, mesh)
        self._flat = jax.jit(step_lib.make_eval_step(eval_fn))
        self._warned_indivisible = False
        #: None until the sharded step first traces; an eval body that
        #: itself uses mesh axes (sharding constraints / collectives —
        #: the sharded-embedding wide_deep path) cannot nest under
        #: shard_map's manual axes, and is detected at that first trace
        self._sharded_ok: bool | None = None
        self._m_steps = self.registry.counter(
            EVAL_STEPS, "evaluation batches executed")

    def _probe_sharded(self, state, global_batch) -> None:
        """Decide sharded-vs-flat by TRACING the sharded step (no
        execution): an eval body that uses mesh axes itself fails at
        trace time with shard_map's manual-axes error, which is the
        only thing that may demote this evaluator. Runtime failures of
        an already-traced step (a stall abort, an OOM) propagate to the
        caller like any other eval error — they say nothing about the
        construction."""
        try:
            self._sharded.lower(state, global_batch)
        except Exception as e:
            from .callbacks import StalledError

            if isinstance(e, StalledError):
                # a watchdog abort that happened to land mid-trace is a
                # classified control exception, never a demotion signal
                raise
            self._sharded_ok = False
            logger.warning(
                "sharded eval step failed to trace (the eval body "
                "itself uses mesh axes?); falling back to the flat "
                "GSPMD eval for this evaluator — correct, but outside "
                "the bit-exact reduction contract", exc_info=True)
        else:
            self._sharded_ok = True

    def run(self, state, batches: Iterable[Any],
            num_batches: int | None = None,
            step: int | None = None) -> dict[str, Any]:
        """Evaluate ``num_batches`` from ``batches``; returns float64
        totals of every summed statistic (scalars AND fixed-size arrays
        like the AUC histograms). Derive ratios with
        ``derive_metrics``."""
        self.flightrec.emit("eval_start", step=step, shards=self.shards)
        totals: dict[str, Any] = {}
        n = 0
        for batch in itertools.islice(batches, num_batches):
            lead = next(int(np.shape(x)[0]) for x in jax.tree.leaves(batch))
            if lead % self.shards == 0 and self._sharded_ok is not False:
                global_batch = sh.put_host_batch(self.mesh, batch)
                if self._sharded_ok is None:
                    self._probe_sharded(state, global_batch)
                if self._sharded_ok:
                    out = self._sharded(state, global_batch)
                    # shard-major fixed-order host reduction: the second
                    # half of the bit-exactness contract (module docstring)
                    vals = {k: np.asarray(v, np.float64).sum(axis=0)
                            for k, v in out.items()}
                else:
                    out = self._flat(state, global_batch)
                    vals = {k: np.asarray(v, np.float64)
                            for k, v in out.items()}
            else:
                if not self._warned_indivisible:
                    self._warned_indivisible = True
                    logger.warning(
                        "eval batch of %d does not divide by %d batch "
                        "shards; falling back to the flat eval step "
                        "(correct, but outside the bit-exact sharded "
                        "reduction contract)", lead, self.shards)
                # an indivisible batch can't shard over the batch axes:
                # evaluate it replicated through the flat step
                out = self._flat(state, sh.replicate(batch, self.mesh))
                vals = {k: np.asarray(v, np.float64)
                        for k, v in out.items()}
            for k, v in vals.items():
                totals[k] = totals.get(k, 0.0) + v
            n += 1
            self._m_steps.inc()
        self.flightrec.emit("eval_end", step=step, batches=n)
        return totals


def derive_metrics(totals: dict[str, Any], auc_prefix: str = "") -> dict:
    """Scalar metric dict from summed totals: keeps scalars, derives
    accuracy/top5/loss ratios, and folds AUC histograms into
    ``<auc_prefix>auc`` (omitted when undefined — a one-class stream
    makes AUC NaN, which is not valid JSON downstream). Shared by the
    runner's eval paths so every consumer applies one arithmetic."""
    result = {k: float(v) for k, v in totals.items() if np.ndim(v) == 0}
    for summed, ratio in (("correct", "accuracy"),
                          ("top5_correct", "top5_accuracy"),
                          ("loss_sum", "loss")):
        if summed in result and result.get("count"):
            result[ratio] = result[summed] / result["count"]
    if "auc_pos_hist" in totals and "auc_neg_hist" in totals:
        auc = metrics_lib.auc_from_histograms(
            totals["auc_pos_hist"], totals["auc_neg_hist"]
        )
        if np.isfinite(auc):
            result[auc_prefix + "auc"] = auc
    return result
