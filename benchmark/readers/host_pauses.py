"""The interpreter's own pauses, read from the program's span ring: the
`runtime.gc` spans that `obs/trace.py` records for every full collection and
for every other collection of a millisecond or more.

A serving cell reads the steady stretch of its window: from the end of
set-up's last `compile.*` span to the instant the trace began, the rule
`spans.prefill_phase_p50_ms` keeps too; some 47 s of a 51 s window, which
the profiler's switch-on does not touch. The training cell reads the
device's traced window, over its `train.step` spans. A window in which no
collection was spanned reads 0; a ring without any `runtime.gc` span is a
program that records none, and reads nothing, as does an empty ring or a
failed join (`spans.Mapped`).
"""

from benchmark.readers import spans

GC = "runtime.gc"


def _mapped(ctx):
    """The ring on the trace's axis, or None where the join failed or the
    program spans no collection."""
    m = spans.Mapped(ctx)
    if not any(s.name == GC for s in m.spans):
        return None
    return m


def _steady(m):
    """(seconds of the steady stretch, its `runtime.gc` spans), or None
    where no compile of set-up ended before the trace began."""
    warm = max((s.end for p in ("trace", "lower", "backend")
                for s in m.before_trace(f"compile.{p}")), default=None)
    if warm is None:
        return None
    seconds = (m.began - m.offset_ns) / 1e9 - warm
    if seconds <= 0:
        return None
    return seconds, [s for s in m.before_trace(GC) if s.start >= warm]


def serve_gc_pause_ms_per_s(ctx):
    """Milliseconds of collection a second of the steady stretch."""
    m = _mapped(ctx)
    got = m and _steady(m)
    if not got:
        return None
    seconds, pauses = got
    return 1e3 * sum(s.end - s.start for s in pauses) / seconds


def serve_gc_pause_max_ms(ctx):
    """The longest single collection in the steady stretch, in ms: what a
    request due behind it waits."""
    m = _mapped(ctx)
    got = m and _steady(m)
    if not got:
        return None
    return 1e3 * max((s.end - s.start for s in got[1]), default=0.0)


def train_gc_pause_ms(ctx):
    """Milliseconds of collection a traced training step: the `runtime.gc`
    spans inside the device's window, on any thread (the input pipeline's
    thread holds the interpreter's lock while it collects), over the
    `train.step` spans there."""
    m = _mapped(ctx)
    steps = m.in_window("train.step") if m else []
    if not steps:
        return None
    return 1e3 * sum(s.end - s.start for s in m.in_window(GC)) / len(steps)
