"""Unified telemetry: metrics registry, host span tracing, exporters.

The observability layer the reference substrate scattered across session
hooks (StepCounterHook / SummarySaverHook / ProfilerHook on
MonitoredTrainingSession.run) rebuilt as one subsystem with a single
design rule: every metric is a MERGEABLE SUFFICIENT STATISTIC (counters
and histogram buckets add; quantiles are derived at read time from
fixed log-spaced buckets). serve/engine.py, train/callbacks.py, and the
recovery layer (resilience/retry.py's ``retry_*_total{site}``,
resilience/supervisor.py's ``supervisor_restarts_total{cause}``) record
into a Registry; obs/export.py renders Prometheus text exposition or
appends JSONL events, chief-gated. Registries MERGE across supervised
restarts (never reset), so counters stay exact over attempt boundaries;
``Registry.total`` sums a labeled family for invariant checks.

Two layers answer the questions counters can't: obs/flightrec.py is the
bounded ring of causal events (what happened, in what order — dumped as
a JSONL postmortem on abnormal exits, rendered by tools/postmortem.py)
and obs/goodput.py is the wall-clock ledger (productive-step vs
compile-warmup/retry-backoff/restart-recovery buckets, the
``goodput_fraction``/``mfu`` gauges, and the one shared MFU/percentile
arithmetic). See docs/observability.md.
"""

from .registry import (  # noqa: F401
    LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Registry,
    default_registry,
    log_buckets,
)
from .trace import Span, Tracer, default_tracer  # noqa: F401
from .export import JsonlLogger, render, serve_http  # noqa: F401
from .flightrec import (  # noqa: F401
    EVENT_KINDS,
    FlightRecorder,
    contains_in_order,
    default_recorder,
    validate_dump,
)
from . import goodput  # noqa: F401
from . import fleetview  # noqa: F401
from . import reqtrace  # noqa: F401
from .reqtrace import PHASES, ReqTrace  # noqa: F401
