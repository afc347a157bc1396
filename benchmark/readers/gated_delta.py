"""Linear-attention layers: the gated-delta-rule kernels' shares of their
roofline and of the device's busy time, and what a match in the prefix
cache had to give up for want of a state snapshot.

Time: the kernels' own events in the trace. Work: what the traced runs were
asked (`readers/calls.py`: the counts on the program's `serve.step.prefill`
and `serve.step.decode` spans), costed by the family's `gated_delta_work`
(FLOPs a token; q, k, v, o once; the state read and written once a call and
slot), the same whatever implements the kernel. A family without such
layers, a program without the kernels, a trace without their events: None.
"""

from benchmark import counts, families, trace_reduce
from benchmark.readers import calls, spans


def _share(ctx, kernel, tokens, state_slots):
    """Least seconds the chip could take for ``tokens`` tokens and
    ``state_slots`` reads and writes of one slot's state in every linear
    layer, over the kernel's seconds in the trace, in per cent."""
    family = families.counts(ctx["cfg"])
    work = getattr(family, "gated_delta_work", None)
    seconds, _ = trace_reduce.op_seconds(ctx["trace"], kernel)
    if work is None or not seconds or not tokens:
        return None
    flops, byts = work(ctx["cfg"], tokens, 1, state_slots)
    layers = family.dims(ctx["cfg"])["n_linear"]
    least = counts.roofline_seconds(layers * flops, layers * byts,
                                    ctx["device_kind"])
    return 100.0 * least / seconds


def chunk_roofline(ctx, kernel: str, prefill_module: str, decode_module: str):
    """The prefill kernel: the `q_tokens` of the traced prefill chunks, the
    state of one slot a chunk."""
    work = calls.traced(ctx, prefill_module, decode_module)
    if work is None:
        return None
    return _share(ctx, kernel, work["prefill_tokens"], work["prefill_calls"])


def step_roofline(ctx, kernel: str, prefill_module: str, decode_module: str):
    """The decode kernel: one token and one state for each of the `slots`
    of the traced decode steps."""
    work = calls.traced(ctx, prefill_module, decode_module)
    if work is None:
        return None
    return _share(ctx, kernel, work["decode_tokens"], work["decode_tokens"])


def device_pct(ctx, kernels: list):
    """The kernels' seconds of the device's busy seconds."""
    if not ctx.get("trace"):
        return None
    busy = trace_reduce.busy_seconds(ctx["trace"])
    found = [trace_reduce.op_seconds(ctx["trace"], k)[0] for k in kernels]
    if not busy or not any(found):
        return None
    return 100.0 * sum(s or 0.0 for s in found) / busy


def snapshot_trim_pct(ctx):
    """Tokens of matched prefix given up for want of a snapshot, of the
    tokens the prefix cache matched: the `trimmed_tokens` and
    `matched_tokens` that the `serve.step.admit` spans sum over their
    admissions, from set-up's last compile to the device's last traced
    operation (the warm-up's admissions are not the mix's)."""
    m = spans.Mapped(ctx)
    compiles = [s.end for p in ("trace", "lower", "backend")
                for s in m.before_trace(f"compile.{p}")]
    warm = max(compiles, default=float("-inf"))
    admits = [s for s in m.spans if s.name == "serve.step.admit"
              and "matched_tokens" in s.attrs and s.start >= warm
              and m.ns(s.end) <= m.window[1]]
    matched = sum(s.attrs["matched_tokens"] for s in admits)
    if not matched:
        return None
    return 100.0 * sum(s.attrs["trimmed_tokens"] for s in admits) / matched
