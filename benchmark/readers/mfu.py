"""The whole step's share of the chip's peak FLOP/s. Operations: what the
runs of the step's programs need (the family's `counts.py`, nothing
recomputed). Time: the device-busy seconds inside those runs. Runs and
seconds are both read from the device's lines of the trace (`XLA Modules`,
`XLA Ops`), so a host stall between two steps moves the device layer's idle
share and not this.
"""

from benchmark import counts, families, trace_reduce
from benchmark.readers import calls


def train_step(ctx, module: str):
    """One run of ``module`` is one step over the cell's global batch."""
    if not ctx.get("trace"):
        return None
    seconds, runs = trace_reduce.busy_in_runs(ctx["trace"], module)
    if not runs or not seconds:
        return None
    job = ctx["traffic"]
    tokens = runs * job["sequences_per_chip"] * ctx["chips"] * job["seq_len"]
    flops = tokens * families.counts(ctx["cfg"]).train_flops_per_token(
        ctx["cfg"], job["seq_len"])
    peak = ctx["chips"] * counts.peaks(ctx["device_kind"])["flops"]
    return 100.0 * flops / (seconds * peak)


def serve_step(ctx, prefill_module: str, decode_module: str):
    """Needed forward FLOPs of the tokens that the traced runs of the two
    serving programs processed, over the device time inside those runs."""
    work = calls.traced(ctx, prefill_module, decode_module)
    if work is None or not work["busy_s"]:
        return None
    # logits are needed where a token is sampled: every decode position and
    # the last position of every prefill chunk that ends a prompt; the
    # count of chunks is the upper bound the spans give
    flops = families.counts(ctx["cfg"]).forward_flops(
        ctx["cfg"], work["prefill_tokens"] + work["decode_tokens"],
        work["attended"], work["decode_tokens"] + work["prefill_calls"])
    peak = ctx["chips"] * counts.peaks(ctx["device_kind"])["flops"]
    return 100.0 * flops / (work["busy_s"] * peak)
