"""Which work the traced runs of the serving programs did. The trace says
how many times each program ran and for how long; what each run was asked
(query tokens, contexts) is on the program's own spans: every prefill chunk
is a `serve.step.prefill` with the counts `q_tokens`, `attended` and
`context`, every decode step a `serve.step.decode` with `slots` and
`kv_tokens` (`readers/spans.py` places the ring on the trace's axis). The
profiler is switched on and off between two engine steps, each of which
ends in a device sync, so the spans of a kind that opened while it ran are,
in order, the runs of that kind's program on `XLA Modules`."""

from benchmark import trace_reduce
from benchmark.readers import spans

#: kind -> the span attributes that give (tokens, keys attended summed over
#: the query tokens, live context the call reads)
COUNTS = {"prefill": ("q_tokens", "attended", "context"),
          "decode": ("slots", "kv_tokens", "kv_tokens")}


def traced(ctx, prefill_module: str, decode_module: str):
    """Totals over the traced runs, or None where the trace or the ring
    lacks: no trace, no ring on its axis, or more runs of a program than
    spans with the counts that could have sent them (never a guess)."""
    if not ctx.get("trace"):
        return None
    ring = spans.Mapped(ctx)
    out = {"busy_s": 0.0, "attended": 0, "context_read": 0}
    for kind, module in (("prefill", prefill_module),
                         ("decode", decode_module)):
        seconds, runs = trace_reduce.busy_in_runs(ctx["trace"], module)
        tokens, attended, context = keys = COUNTS[kind]
        # a span without the counts sent nothing (a decode whose slots were
        # all preempted while it staged), or is another program's
        mine = [s for s in ring.dispatched_in_trace(f"serve.step.{kind}")
                if all(key in s.attrs for key in keys)]
        if runs > len(mine):
            return None
        # a span beyond the runs is the last one's: sent, not yet run when
        # the profiler was switched off
        out[f"{kind}_spans"] = len(mine)
        mine = mine[:runs]
        out["busy_s"] += seconds or 0.0
        out[f"{kind}_calls"] = runs
        out[f"{kind}_tokens"] = sum(s.attrs[tokens] for s in mine)
        # sum over query positions of the keys each attends (FLOPs)
        out["attended"] += sum(s.attrs[attended] for s in mine)
        # sum over calls of the live context each call reads (bytes)
        out["context_read"] += sum(s.attrs[context] for s in mine)
    if not out["prefill_tokens"] + out["decode_tokens"]:
        return None
    return out
