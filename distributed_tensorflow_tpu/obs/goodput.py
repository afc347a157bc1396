"""Goodput + MFU accounting — where the wall-clock actually went.

The MLPerf TPU-pod scaling work and TF-Replicator both treat step-time
breakdown and utilization as first-class framework outputs; here they
were ad-hoc prints until this module factored them out.
Two jobs:

- **One MFU definition.** ``train_mfu`` is THE consumer site of the
  framework FLOPs contract (utils/flops.py): model ``flops_per_example``
  counts are FORWARD-only, and the fwd+bwd ×3 multiplier is applied
  exactly here — so ``MetricsLogger``'s log line and the exported
  ``mfu`` gauge can never disagree.
  ``flops_per_step_from_compiled`` derives the per-step FLOP count from
  a compiled step's cost analysis for models without an analytic count.

- **Goodput accounting.** Wall-clock partitioned into a productive
  bucket (steps that advanced training) and wasted buckets
  (``compile_warmup`` — first step of an attempt plus attempt
  construction, ``retry_backoff`` — retry_call sleep, ``restart_recovery``
  — supervisor backoff + restart-boundary rebuild). All buckets are
  COUNTERS (seconds), so they obey the registry's merge-not-reset
  invariant and stay exact across supervised restarts; the derived
  ``goodput_fraction`` gauge is refreshed on every note.

Exported names (docs/observability.md):

    goodput_productive_seconds_total          counter
    wasted_seconds_total{cause=…}             counter family
    goodput_fraction                          gauge  (productive / tracked)
    mfu                                       gauge

Module top-level imports nothing heavy — jax/flops enter lazily inside
``train_mfu``, so the scheduler- and registry-level consumers stay
device-free.
"""

from __future__ import annotations

from .registry import Histogram, Registry, default_registry

__all__ = [
    "PRODUCTIVE_SECONDS",
    "WASTED_SECONDS",
    "GOODPUT_FRACTION",
    "MFU",
    "WASTE_COMPILE_WARMUP",
    "WASTE_RETRY_BACKOFF",
    "WASTE_RESTART_RECOVERY",
    "WASTE_ELASTIC_RESIZE",
    "WASTE_ASYNC_CKPT",
    "WASTE_CAUSES",
    "note_productive",
    "note_wasted",
    "goodput_fraction",
    "train_mfu",
    "flops_per_step_from_compiled",
    "latency_percentiles_ms",
]

#: metric names (docs/observability.md "Goodput & MFU")
PRODUCTIVE_SECONDS = "goodput_productive_seconds_total"
WASTED_SECONDS = "wasted_seconds_total"
GOODPUT_FRACTION = "goodput_fraction"
MFU = "mfu"

#: the wasted-time vocabulary — every cause label the family may carry
WASTE_COMPILE_WARMUP = "compile_warmup"
WASTE_RETRY_BACKOFF = "retry_backoff"
WASTE_RESTART_RECOVERY = "restart_recovery"
WASTE_ELASTIC_RESIZE = "elastic_resize"
WASTE_ASYNC_CKPT = "async_checkpoint"
WASTE_CAUSES = (
    WASTE_COMPILE_WARMUP, WASTE_RETRY_BACKOFF, WASTE_RESTART_RECOVERY,
    WASTE_ELASTIC_RESIZE, WASTE_ASYNC_CKPT,
)


def _productive(reg: Registry):
    return reg.counter(
        PRODUCTIVE_SECONDS,
        "wall seconds spent in steps that advanced training")


def _wasted_total(reg: Registry) -> float:
    # the cause vocabulary is CLOSED, so three keyed lookups replace a
    # Registry.total() scan of every metric — note_productive runs once
    # per train step, and this keeps that hot path O(1)
    return sum(
        reg.counter(WASTED_SECONDS, "wall seconds lost, by cause",
                    cause=c).value
        for c in WASTE_CAUSES
    )


def _refresh_fraction(reg: Registry) -> None:
    productive = _productive(reg).value
    total = productive + _wasted_total(reg)
    if total > 0:
        reg.gauge(
            GOODPUT_FRACTION,
            "productive-step seconds / tracked wall seconds",
        ).set(productive / total)


def note_productive(seconds: float, registry: Registry | None = None) -> None:
    """Account ``seconds`` of wall-clock as productive training time and
    refresh the ``goodput_fraction`` gauge."""
    reg = registry if registry is not None else default_registry()
    _productive(reg).inc(max(float(seconds), 0.0))
    _refresh_fraction(reg)


def note_wasted(cause: str, seconds: float,
                registry: Registry | None = None) -> None:
    """Account ``seconds`` of wall-clock as wasted, bucketed by
    ``cause`` (one of ``WASTE_CAUSES``)."""
    if cause not in WASTE_CAUSES:
        raise ValueError(
            f"unknown waste cause {cause!r} (known: {WASTE_CAUSES})")
    reg = registry if registry is not None else default_registry()
    reg.counter(
        WASTED_SECONDS, "wall seconds lost, by cause", cause=cause,
    ).inc(max(float(seconds), 0.0))
    _refresh_fraction(reg)


def goodput_fraction(registry: Registry | None = None) -> float:
    """Productive seconds over total tracked seconds (productive +
    every wasted bucket); nan when nothing has been tracked yet."""
    reg = registry if registry is not None else default_registry()
    productive = _productive(reg).value
    total = productive + _wasted_total(reg)
    return productive / total if total > 0 else float("nan")


# ---------------------------------------------------------------------------
# MFU
# ---------------------------------------------------------------------------


def train_mfu(
    fwd_flops_per_step: float,
    steps_per_sec: float,
    n_chips: int | None = None,
    peak_per_chip: float | None = None,
    device=None,
    registry: Registry | None = None,
) -> float:
    """Training MFU from a FORWARD FLOP count — the single place the
    fwd+bwd training multiplier is applied (utils/flops.py contract).

    ``n_chips``/``peak_per_chip`` default from the live jax backend
    (pass both explicitly to stay device-free). When ``registry`` is
    given the value is also published as the ``mfu`` gauge — callers
    that log it (``MetricsLogger``) and scrapers read one number.
    """
    from ..utils import flops as flops_lib  # lazy: pulls jax

    if n_chips is None:
        import jax

        n_chips = jax.device_count()
    if peak_per_chip is None:
        peak_per_chip = flops_lib.peak_flops_per_chip(device)
    value = flops_lib.mfu(
        fwd_flops_per_step * flops_lib.train_flops_multiplier(),
        steps_per_sec, n_chips, peak_per_chip,
    )
    if registry is not None:
        registry.gauge(
            MFU, "model FLOPs utilization of the train step"
        ).set(value)
    return value


def flops_per_step_from_compiled(compiled) -> float | None:
    """Per-step FLOPs from a compiled executable's cost analysis
    (``jax.jit(...).lower(...).compile()``). None when the backend
    offers no analysis — callers fall back to the model's analytic
    count."""
    flops = (compiled.cost_analysis() or {}).get("flops")
    return float(flops) if flops else None


# ---------------------------------------------------------------------------
# Percentile read-back (the benches' single source)
# ---------------------------------------------------------------------------


def latency_percentiles_ms(
    registry: Registry,
    name: str,
    quantiles: tuple[float, ...] = (0.5, 0.99),
    **labels,
) -> dict[str, float]:
    """Read quantiles of a latency histogram back in milliseconds:
    ``{"p50_ms": …, "p99_ms": …}``. One helper for every bench/report
    site, so a printed p99 and the registry histogram can never use
    different math. Raises KeyError when the histogram doesn't exist."""
    h = registry.get(name, **labels)
    if not isinstance(h, Histogram):
        raise KeyError(f"no histogram {name!r} (labels={labels}) in registry")
    return {
        f"p{q * 100:g}_ms": round(float(h.percentile(q)) * 1e3, 3)
        for q in quantiles
    }
