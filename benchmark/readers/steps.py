"""Step layers: device time inside one run of a jitted program."""

from benchmark import trace_reduce


def device_ms_per_run(ctx, module: str):
    """Mean device-busy milliseconds inside one run of the programs whose
    name starts with ``module`` (a regular expression)."""
    if not ctx.get("trace"):
        return None
    ms, runs = trace_reduce.busy_per_run_ms(ctx["trace"], module)
    return ms if runs else None


def exposed_collective_ms_per_step(ctx, module: str):
    """Collective time that no compute hides, per run of the step program
    ``module``. Unproved on the chip: no cell on several chips yet."""
    if not ctx.get("trace"):
        return None
    _, runs = trace_reduce.busy_in_runs(ctx["trace"], module)
    s = trace_reduce.exposed_collective_seconds(ctx["trace"])
    return None if s is None or not runs else 1e3 * s / runs
