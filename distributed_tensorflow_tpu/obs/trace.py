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

Compiles: ``default_tracer()`` installs one ``jax.monitoring`` listener
pair that records jax's own trace / lower / backend-compile durations as
spans ``compile.trace`` (outermost traces only), ``compile.lower`` and
``compile.backend``, children of whatever span was open when they ended
— "which step recompiled" is the parent's name.

Collections: ``default_tracer()`` also installs one ``gc.callbacks`` hook
that records the interpreter's own pauses as spans ``runtime.gc`` (attrs
``generation``, ``collected``, ``uncollectable``), children of the span
open on the collecting thread: "which step a collection held up" is the
parent's name. While a profiler session runs EVERY collection is an
annotation ``runtime.gc.<id>``, so the trace's idle gaps can name it; only
those a step can feel become ring spans: every generation-2 collection,
and any other of ``GC_SPAN_MIN_S`` or more. The ring's bound decides that
rule. A whole traced run of the GPT-2 serving cell fills 56k of its
65,536 slots, and three benchmark metrics read set-up's ``compile.*``
spans from the oldest end; generation-0 collections run every 700 net
allocations, ~1,400 of them in that cell's set-up and ~150 a window, and
would evict those. Under the rule a window leaves 2-11 spans. A full
collection walks the whole heap, the ring included, and is what a
long-lived server pays for it; 1 ms is an eighth of a GPT-2 token
interval, a pause one can feel.

Thread model: the open-span stack is a ``threading.local`` — each thread
nests independently; a shared Tracer collects all of them.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import threading
import time
from collections import deque

import jax.monitoring
import jax.profiler

__all__ = ["Span", "Tracer", "default_tracer"]

#: dump header schema tag — bump when the record layout changes
SCHEMA = "dtf-spans-1"

#: Default ring bound: what a postmortem wants is the minutes before the
#: failure. An engine step leaves ~13 spans (admit, a prefill chunk's four,
#: the decode's five, the step itself), so 2**16 is the last ~5,000 steps:
#: some 25 minutes of a server stepping three times a second, under two
#: minutes of one stepping fifty times a second. A trainer's five spans a
#: step make it the last ~13,000 steps. At ~0.3 kB a span the full ring
#: is ~20 MB of host memory. The fullest benchmark run, a traced run of
#: the GPT-2-XL serving cell at 14.5 requests/s, ends holding 56,171
#: spans (52,865 of them its 51 s window's steps, 954 compiles, 104
#: collections; one v5e host), so its readers see ``dropped == 0``: a
#: span type that adds thousands a window needs a larger bound.
RING_SPANS = 1 << 16

#: jax.monitoring duration events -> span names (jax 0.9.0,
#: jax/_src/dispatch.py). backend_compile includes persistent-cache loads.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENTS = {
    TRACE_EVENT: "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}

#: the span of one collection of the interpreter's garbage collector, and
#: the shortest one of generation 0 or 1 that is recorded (the module's
#: docstring says why)
GC_SPAN = "runtime.gc"
GC_SPAN_MIN_S = 1e-3

#: process-wide span ids (``next`` on a count is atomic under the GIL)
_ids = itertools.count(1)


class Span:
    """One region. The object ``Tracer.span`` returns is the context
    manager AND, once closed, the record in the ring: ``name`` is the
    dot-joined ancestry, ``start``/``end`` are tracer-clock timestamps,
    ``attrs`` may be filled until the span closes."""

    __slots__ = ("id", "parent", "name", "start", "end", "key", "attrs",
                 "_tracer", "_ann")

    def __init__(self, tracer, name, key, attrs, span_id=None):
        self._tracer = tracer
        self._ann = None
        self.id = next(_ids) if span_id is None else span_id
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
    """Collects completed spans (bounded ring, always on).

    >>> tr = Tracer()
    >>> with tr.span("step", step=7):
    ...     with tr.span("prefill", key=uid) as sp:
    ...         sp.attrs["q_tokens"] = 32
    >>> [s.name for s in tr.events]
    ['step.prefill', 'step']
    """

    def __init__(
        self,
        *,
        max_events: int = RING_SPANS,
        annotate: bool = True,
        clock=time.perf_counter,
    ):
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
               parent: int | None = None, span_id: int | None = None,
               **attrs) -> Span:
        """Add a region whose two ends are already known: no clock read,
        no annotation. The stamps MUST be readings of this tracer's
        ``clock`` — the ring has one time axis, and ``dump``'s single
        ``clock_origin`` places all of it — so a caller whose stamps come
        from another clock records nothing (``ServeEngine`` skips its
        ``serve.request.*`` spans then). ``name`` is taken as given, not
        nested. ``span_id`` is an id taken from the process-wide count
        before the region began (an annotation named with it); by default
        a new one."""
        sp = Span(self, name, key, attrs, span_id)
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
    another). Made on first use, together with the compile listener and
    the collection hook: each is installed once a process."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                tracer = Tracer()
                _listen_for_compiles(tracer)
                gc.callbacks.append(_collection_hook(tracer))
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



def _collection_hook(tracer: Tracer):
    """The ``gc.callbacks`` entry that spans the interpreter's collections
    on ``tracer``. CPython collects nothing while a callback runs, and an
    exception in one is printed, not raised: nothing here assumes that a
    span is open, and a full ring only counts one more ``dropped``."""
    tls = tracer._tls

    def on_collection(phase: str, info: dict) -> None:
        if phase == "start":
            span_id, ann = next(_ids), None
            if tracer.annotate:
                ann = jax.profiler.TraceAnnotation(f"{GC_SPAN}.{span_id}")
                ann.__enter__()
            # read inside the annotation, as a span's ends are
            tls.gc = (span_id, ann, tracer.clock())
            return
        opened = getattr(tls, "gc", None)
        if opened is None:  # installed while this collection ran
            return
        tls.gc = None
        span_id, ann, start = opened
        end = tracer.clock()
        if ann is not None:
            ann.__exit__(None, None, None)
        generation = info["generation"]
        if generation < 2 and end - start < GC_SPAN_MIN_S:
            return
        top = tracer.current()
        tracer.record(GC_SPAN, start, end, span_id=span_id,
                      parent=None if top is None else top.id,
                      generation=generation, collected=info["collected"],
                      uncollectable=info["uncollectable"])

    return on_collection
