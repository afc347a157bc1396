"""Device layer: the share of the traced window in which no operation ran.
Busy time and window both come from the device's lines of the trace: the
window runs from the first operation's start to the last one's end."""

from benchmark import trace_reduce


def idle_pct(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    busy = trace_reduce.busy_seconds(trace)
    window = trace_reduce.window_seconds(trace)
    if busy is None or not window:
        return None
    return 100.0 * (1.0 - busy / window)
