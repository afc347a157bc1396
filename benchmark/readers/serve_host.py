"""Load generator and serve loop: host-clock waits and the engine's counts."""

import numpy as np


def _p95_ms(values):
    return 1e3 * float(np.percentile(values, 95)) if len(values) else None


def lateness_p95_ms(ctx):
    """How late the generator sent a request after it was due."""
    return _p95_ms(ctx["run"].get("lateness_s", []))


def queue_wait_p95_ms(ctx):
    """Due time to admission into a slot."""
    return _p95_ms(ctx["run"].get("queue_wait_s", []))


def prefix_hit_pct(ctx):
    """Prompt blocks mapped from the prefix cache, of all prompt blocks."""
    run = ctx["run"]
    if not run.get("prompt_blocks"):
        return None
    return 100.0 * run["prefix_blocks_hit"] / run["prompt_blocks"]


def ttft_p50_ms(ctx):
    """The median beside the judged tail: over the requests whose first
    token came before the profiler was switched on."""
    values = ctx["run"].get("ttft_s", [])
    return 1e3 * float(np.median(values)) if len(values) else None


def tpot_p50_ms(ctx):
    values = ctx["run"].get("tpot_s", [])
    return 1e3 * float(np.median(values)) if len(values) else None


def pool_live_peak_pct(ctx):
    """Most of the pool's blocks that resident requests held at once in the
    window, of the blocks the deployment gives the pool."""
    run = ctx["run"]
    if not run.get("pool_blocks") or "pool_live_peak" not in run:
        return None
    return 100.0 * run["pool_live_peak"] / run["pool_blocks"]
