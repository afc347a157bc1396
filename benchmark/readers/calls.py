"""Which work the traced runs of the serving programs did. The trace says
how many times each program ran and for how long; what each run was asked
(query tokens, contexts) is in the records `serve_driver.Calls` keeps of
the engine's calls, in call order. The last records of each kind, as many
as the trace holds runs, are those runs: the profiler is switched on and
off between two engine steps, each of which ends in a device sync."""

from benchmark import trace_reduce


def traced(ctx, prefill_module: str, decode_module: str):
    """Totals over the traced runs, or None where trace or records lack."""
    records = ctx["run"].get("call_records")
    if not ctx.get("trace") or not records:
        return None
    out = {"busy_s": 0.0, "attended": 0, "context_read": 0}
    for kind, module in (("prefill", prefill_module),
                         ("decode", decode_module)):
        seconds, runs = trace_reduce.busy_in_runs(ctx["trace"], module)
        mine = [r for r in records if r[0] == kind]
        if runs > len(mine):
            return None  # the trace holds runs that no record describes
        mine = mine[len(mine) - runs:]
        out["busy_s"] += seconds or 0.0
        out[f"{kind}_calls"] = runs
        out[f"{kind}_tokens"] = sum(r[1] for r in mine)
        # sum over query positions of the keys each attends (FLOPs)
        out["attended"] += sum(r[2] for r in mine)
        # sum over calls of the live context each call reads (bytes)
        out["context_read"] += sum(r[3] for r in mine)
    if not out["prefill_tokens"] + out["decode_tokens"]:
        return None
    return out
