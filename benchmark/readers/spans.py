"""Host layers read from the program's own span ring (`obs/trace.py`): the
train loop's input wait and host work, the serve step's host time, the
prefill phase of a request, the share of the paged kernel's walk that is
live, and where set-up's seconds go.

The ring is on the host's `perf_counter`; the trace has its own axis. Each
span open while the profiler ran is also a host event named `<path>.<id>`,
so every such pair gives `event.start_ns - span.start`; the median of those
offsets places the whole ring on the trace's axis, spans shorter than
`trace_reduce.MIN_HOST_NS` (which `load` drops) included. A reader keeps the
spans that lie inside the device's window (first operation's start to last
one's end) and returns None where the ring or the join is empty, as on a
program without these spans.

The spans come from `ctx["spans"]` where a test supplies them, else from the
program's ring as `program.span_ring()` hands it over.
"""

import re
import statistics

from benchmark import trace_reduce

_ANNOTATION = re.compile(r"(.+)\.(\d+)")


def ring(ctx) -> list:
    spans = ctx.get("spans")
    if spans is None:
        from benchmark import program  # imports jax: not with this module

        spans = program.span_ring()
    # a ring of another layout (a program from before these spans) is no ring
    return [s for s in spans if hasattr(s, "attrs")]


def join(trace, spans) -> list:
    """`event.start_ns - span.start`, in ns, of every ring span that the
    trace's host events hold under its id."""
    by_id = {s.id: s for s in spans}
    offsets = []
    for name, start_ns, _ in trace.host:
        m = _ANNOTATION.fullmatch(name)
        sp = by_id.get(int(m.group(2))) if m else None
        if sp is not None and sp.name == m.group(1):
            offsets.append(start_ns - 1e9 * sp.start)
    return offsets


class Mapped:
    """The ring on the trace's axis, with the device's window and the
    instant the trace began (its first event, host or device)."""

    def __init__(self, ctx):
        self.spans, self.offset_ns = [], None
        self.window, self.began, self.children = (0, 0), 0, {}
        trace = ctx.get("trace")
        ops = [] if not trace else [
            e for d in trace.devices.values() for e in d["ops"]]
        if not ops:
            return
        spans = ring(ctx)
        offsets = join(trace, spans)
        if not offsets:
            return
        self.spans, self.offset_ns = spans, statistics.median(offsets)
        self.window = (min(e[1] for e in ops), max(e[2] for e in ops))
        self.began = min([self.window[0]] + [e[1] for e in trace.host])
        for s in spans:
            self.children.setdefault(s.parent, []).append(s)

    def ns(self, t: float) -> float:
        return 1e9 * t + self.offset_ns

    def in_window(self, name: str) -> list:
        lo, hi = self.window
        return [s for s in self.spans if s.name == name
                and self.ns(s.start) >= lo and self.ns(s.end) <= hi]

    def dispatched_in_trace(self, name: str) -> list:
        """The spans that opened while the profiler ran and before the
        device's last operation ended, in the order they opened: whatever
        such a span sent to the device, the trace holds, and the spans of
        the wait after the window lie past it."""
        return sorted((s for s in self.spans if s.name == name
                       and self.began <= self.ns(s.start) <= self.window[1]),
                      key=lambda s: s.start)

    def before_trace(self, name: str) -> list:
        return [s for s in self.spans
                if s.name == name and self.ns(s.end) < self.began]

    def descendants(self, span, suffix: str) -> list:
        out, todo = [], [span]
        while todo:
            for c in self.children.get(todo.pop().id, []):
                todo.append(c)
                if c.name.endswith(suffix):
                    out.append(c)
        return out


def _seconds(spans) -> float:
    return sum(s.end - s.start for s in spans)


def train_step_host_ms(ctx, parts: list):
    """Mean, over the `train.step` spans inside the device's window, of the
    time in the children named in ``parts`` (`next_batch`, `put_batch`,
    `dispatch`)."""
    m = Mapped(ctx)
    steps = m.in_window("train.step")
    if not steps:
        return None
    names = {f"train.step.{p}" for p in parts}
    return 1e3 * sum(
        _seconds(c for c in m.children.get(s.id, []) if c.name in names)
        for s in steps) / len(steps)


def serve_step_host_ms(ctx):
    """Mean over the traced `serve.step` spans of the step's duration less
    its `.fetch` descendants: host time not spent blocked on a device
    result."""
    m = Mapped(ctx)
    steps = m.in_window("serve.step")
    if not steps:
        return None
    return 1e3 * sum(
        s.end - s.start - _seconds(m.descendants(s, ".fetch"))
        for s in steps) / len(steps)


def prefill_phase_p50_ms(ctx):
    """Median of `serve.request.prefill` (admission to first token) over the
    steady-state requests the profiler did not touch: admitted after the
    last compile of set-up ended (the warm-up's requests each hold one),
    first token before the trace began. Read from the ring alone."""
    m = Mapped(ctx)
    compiles = [s.end for p in ("trace", "lower", "backend")
                for s in m.before_trace(f"compile.{p}")]
    warm = max(compiles, default=float("-inf"))
    phases = [s for s in m.before_trace("serve.request.prefill")
              if s.start >= warm]
    if not phases:
        return None
    return 1e3 * statistics.median(s.end - s.start for s in phases)


def decode_kv_useful_pct(ctx):
    """K/V positions the traced decode steps needed (`kv_tokens`: each live
    slot's context) of the positions the paged kernel fetches
    (`kv_positions_walked`: since PR 28 each live slot's own blocks up to
    its new token's, times the block size; the kernel walks no block of
    an idle slot and none past a slot's last)."""
    m = Mapped(ctx)
    steps = [s for s in m.in_window("serve.step.decode")
             if s.attrs.get("kv_positions_walked")]
    if not steps:
        return None
    return (100.0 * sum(s.attrs["kv_tokens"] for s in steps)
            / sum(s.attrs["kv_positions_walked"] for s in steps))


def setup_seconds(ctx, phases: list):
    """Seconds before the trace began in which jax was in one of ``phases``
    (`trace`, `lower`, `backend`): the union of the `compile.<phase>` spans
    that ended by then, since the trace of a jitted function nests the
    traces of those it calls."""
    m = Mapped(ctx)
    spans = [s for p in phases for s in m.before_trace(f"compile.{p}")]
    if not spans:
        return None
    return trace_reduce.total(trace_reduce.union(
        (s.start, s.end) for s in spans))
