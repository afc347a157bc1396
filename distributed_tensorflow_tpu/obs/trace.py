"""Host-side span tracer — the one span recorder of the train and serve
hot paths.

The train/serve loops are host-drives/device-computes: device time shows
up in jax.profiler's XPlane traces, but HOST decisions (admission, table
stamping, data stalls, the logits fetch) are invisible there. A ``Span``
is the host-side unit: a named region with an ``id``, the id of the span
that was open on the same thread when it opened (``parent``), its start
and end on the tracer's clock, an optional ``key`` shared by every span
of one request, and ``attrs``, a small flat dict of COUNTS taken where
the work happens (tokens, blocks, slots — never a time).

The ring is always on and bounded, like obs/flightrec's: there is no
tracing mode. Completed spans go into ``Tracer.events`` (oldest dropped
past the bound, counted in ``Tracer.dropped``); ``Tracer.dump`` writes
the ring as JSONL.

While a ``jax.profiler`` session runs, each span is also a
``TraceAnnotation`` named ``<path>.<id>``. The id suffix is what joins a
ring span to its event in the profiler's trace: every pair gives one
``event.start_ns - span.start`` offset, and the median of those offsets
places the whole ring (spans the profiler's reader drops as too short
included) on the device trace's time axis. Outside a session the
annotation is a no-op of well under a microsecond.

A tracer that is given a ``registry`` mirrors every duration into
``trace_span_seconds{span=<path>}``; the default tracer has none, so the
hot path pays no labelled-histogram lookup.

Compiles: ``default_tracer()`` installs one ``jax.monitoring`` listener
pair that records jax's own trace / lower / backend-compile durations as
spans ``compile.trace`` (outermost traces only), ``compile.lower`` and
``compile.backend``, children of whatever span was open when they ended
— "which step recompiled" is the parent's name.

Thread model: the open-span stack is a ``threading.local`` — each thread
nests independently; a shared Tracer collects all of them.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque

import jax.monitoring
import jax.profiler

from .registry import Registry

__all__ = ["Span", "Tracer", "default_tracer"]

SPAN_HISTOGRAM = "trace_span_seconds"
#: dump header schema tag — bump when the record layout changes
SCHEMA = "dtf-spans-1"

#: Default ring bound: what a postmortem wants is the minutes before the
#: failure. An engine step leaves ~13 spans (admit, a prefill chunk's four,
#: the decode's five, the step itself), so 2**16 is the last ~5,000 steps:
#: some 25 minutes of a server stepping three times a second, under two
#: minutes of one stepping fifty times a second. A trainer's five spans a
#: step make it the last ~13,000 steps. At ~0.3 kB a span the full ring
#: is ~20 MB of host memory. (A whole run of either benchmark cell is
#: under 6,000 spans, so its readers see ``dropped == 0``.)
RING_SPANS = 1 << 16

#: jax.monitoring duration events -> span names (jax 0.9.0,
#: jax/_src/dispatch.py). backend_compile includes persistent-cache loads.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENTS = {
    TRACE_EVENT: "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}

#: process-wide span ids (``next`` on a count is atomic under the GIL)
_ids = itertools.count(1)


class Span:
    """One region. The object ``Tracer.span`` returns is the context
    manager AND, once closed, the record in the ring: ``name`` is the
    dot-joined ancestry, ``start``/``end`` are tracer-clock timestamps,
    ``attrs`` may be filled until the span closes."""

    __slots__ = ("id", "parent", "name", "start", "end", "key", "attrs",
                 "_tracer", "_ann")

    def __init__(self, tracer, name, key, attrs):
        self._tracer = tracer
        self._ann = None
        self.id = next(_ids)
        self.parent = None
        self.name = name
        self.start = self.end = None
        self.key = key
        self.attrs = attrs

    @property
    def duration(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        tracer = self._tracer
        stack = tracer._stack()
        if stack:
            top = stack[-1]
            self.parent = top.id
            self.name = f"{top.name}.{self.name}"
        stack.append(self)
        if tracer.annotate:
            self._ann = jax.profiler.TraceAnnotation(f"{self.name}.{self.id}")
            self._ann.__enter__()
        # the clock is read inside the annotation on both sides, so the
        # span lies within its event in the profiler's trace
        self.start = tracer.clock()
        return self

    def __exit__(self, *exc) -> None:
        # exceptions included: a span that dies still records
        tracer = self._tracer
        self.end = tracer.clock()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        tracer._stack().pop()
        tracer._append(self)

    def as_dict(self) -> dict:
        return {"id": self.id, "parent": self.parent, "name": self.name,
                "start": self.start, "end": self.end, "key": self.key,
                "attrs": self.attrs}

    def __repr__(self) -> str:
        return f"Span({self.as_dict()})"


class Tracer:
    """Collects completed spans (bounded ring, always on) and optionally
    mirrors durations into a metrics registry.

    >>> tr = Tracer()
    >>> with tr.span("step", step=7):
    ...     with tr.span("prefill", key=uid) as sp:
    ...         sp.attrs["q_tokens"] = 32
    >>> [s.name for s in tr.events]
    ['step.prefill', 'step']
    """

    def __init__(
        self,
        registry: Registry | None = None,
        *,
        max_events: int = RING_SPANS,
        annotate: bool = True,
        clock=time.perf_counter,
    ):
        self.registry = registry
        self.annotate = annotate
        self.clock = clock
        #: completed spans in closing order, oldest dropped past the bound
        self.events: deque[Span] = deque(maxlen=max_events)
        #: spans the bound evicted
        self.dropped = 0
        self._tls = threading.local()

    def _stack(self) -> list[Span]:
        try:
            return self._tls.stack
        except AttributeError:
            self._tls.stack = []
            return self._tls.stack

    def _append(self, sp: Span) -> None:
        if len(self.events) == self.events.maxlen:
            self.dropped += 1
        self.events.append(sp)
        if self.registry is not None:
            self.registry.histogram(
                SPAN_HISTOGRAM,
                "wall-clock duration of host trace spans",
                span=sp.name,
            ).observe(sp.end - sp.start)

    def span(self, name: str, key=None, **attrs) -> Span:
        """A span to open with ``with``; it nests under the span open on
        this thread (its name becomes ``<parent name>.<name>``) and is
        recorded when the block exits, exceptions included."""
        return Span(self, name, key, attrs)

    def current(self) -> Span | None:
        """The innermost span open on this thread."""
        stack = self._stack()
        return stack[-1] if stack else None

    def record(self, name: str, start: float, end: float, *, key=None,
               parent: int | None = None, **attrs) -> Span:
        """Add a region whose two ends are already known: no clock read,
        no annotation. The stamps MUST be readings of this tracer's
        ``clock`` — the ring has one time axis, and ``dump``'s single
        ``clock_origin`` places all of it — so a caller whose stamps come
        from another clock records nothing (``ServeEngine`` skips its
        ``serve.request.*`` spans then). ``name`` is taken as given, not
        nested."""
        sp = Span(self, name, key, attrs)
        sp.parent, sp.start, sp.end = parent, start, end
        self._append(sp)
        return sp

    def dump(self, path: str) -> str:
        """Write the ring as JSONL: a header line (schema, the clock's
        origin, counts), then one span a line in closing order. The
        origin pairs one reading of the tracer's clock with the wall
        clock, which is what lines a dump up with anything else.
        Unserializable keys or attrs are repr'd, never raised on."""
        spans = [s.as_dict() for s in list(self.events)]
        header = {"schema": SCHEMA, "spans": len(spans),
                  "dropped": self.dropped, "capacity": self.events.maxlen,
                  "clock_origin": {"clock": self.clock(),
                                   "unix": time.time()},
                  "pid": os.getpid()}
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            f.write(json.dumps(header, sort_keys=True) + "\n")
            for s in spans:
                f.write(json.dumps(s, default=repr) + "\n")
        os.replace(tmp, path)  # a torn dump must not look complete
        return path


_default: Tracer | None = None
_default_lock = threading.Lock()


def default_tracer() -> Tracer:
    """The process ring (Trainer and ServeEngine record here unless given
    another). Made on first use, together with the compile listener."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                tracer = Tracer()
                _listen_for_compiles(tracer)
                _default = tracer
    return _default


def _listen_for_compiles(tracer: Tracer) -> None:
    """jax's own trace / lower / backend-compile durations as spans that
    end when the event fires, children of the span open at that time. The
    trace of a jitted function nests the traces of every jitted function
    it calls (37 k of them in one serving set-up, all inside 15 outer
    ones): jax marks the start of each as a scalar event, which is counted
    here per thread so that only the outermost trace is recorded."""
    tls = threading.local()

    def on_start(event: str, value, **_) -> None:
        if event == TRACE_EVENT:
            tls.depth = getattr(tls, "depth", 0) + 1

    def on_duration(event: str, duration: float, **_) -> None:
        name = COMPILE_EVENTS.get(event)
        if name is None:
            return
        if event == TRACE_EVENT:
            # no start seen (another jax): depth stays 0, every trace is kept
            tls.depth = max(getattr(tls, "depth", 0) - 1, 0)
            if tls.depth:
                return
        end = tracer.clock()
        top = tracer.current()
        tracer.record(name, end - duration, end,
                      parent=None if top is None else top.id)

    jax.monitoring.register_scalar_listener(on_start)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

