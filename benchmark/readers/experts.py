"""The expert layer and latent attention: the grouped-matmul kernel's and
the latent-attention kernel's shares of their roofline, and how many tokens
a held expert's call gets.

Time: the kernels' own events in the trace. Work: the expert layer's counts
that the program puts on its `serve.step.prefill` and `serve.step.decode`
spans (`moe_assignments`, `moe_expert_calls`: what changed in the engine's
counters since the last fetch) and what the traced runs were asked
(`readers/calls.py`), costed by the family's `moe_work` and
`latent_attention_work`, the same whatever implements the kernels. A family
without them, a program without the spans' counts or the kernels, a trace
without their events: None.
"""

from benchmark import counts, families, trace_reduce
from benchmark.readers import calls, spans


def expert_counts(ctx):
    """(local assignments, expert calls) summed over the serving spans that
    opened while the profiler ran, or None where no span carries them."""
    if not ctx.get("trace"):
        return None
    m = spans.Mapped(ctx)
    got = [s for kind in ("prefill", "decode")
           for s in m.dispatched_in_trace(f"serve.step.{kind}")
           if "moe_assignments" in s.attrs]
    if not got:
        return None
    return (sum(s.attrs["moe_assignments"] for s in got),
            sum(s.attrs["moe_expert_calls"] for s in got))


def _share(ctx, kernel, flops, byts):
    seconds, _ = trace_reduce.op_seconds(ctx["trace"], kernel)
    if not seconds:
        return None
    return 100.0 * counts.roofline_seconds(flops, byts,
                                           ctx["device_kind"]) / seconds


def grouped_mm_roofline(ctx, kernel: str):
    """The routed experts' grouped matmuls: the three SwiGLU products of
    every local assignment, each called expert's weights read once."""
    work = getattr(families.counts(ctx["cfg"]), "moe_work", None)
    got = expert_counts(ctx)
    if work is None or got is None or not got[0]:
        return None
    return _share(ctx, kernel, *work(ctx["cfg"], *got))


def tokens_per_expert(ctx):
    """Local assignments a call of a held expert gets, on average: the rows
    of a group of the grouped matmul."""
    got = expert_counts(ctx)
    if got is None or not got[1]:
        return None
    return got[0] / got[1]


def latent_roofline(ctx, kernel: str, prefill_module: str,
                    decode_module: str):
    """The latent-attention kernel in every latent layer: the attended
    pairs of the traced runs and the cached rows each call reads."""
    family = families.counts(ctx["cfg"])
    work = getattr(family, "latent_attention_work", None)
    got = calls.traced(ctx, prefill_module, decode_module)
    if work is None or got is None:
        return None
    flops, byts = work(ctx["cfg"], got["prefill_tokens"] + got["decode_tokens"],
                       got["attended"], got["context_read"])
    layers = family.cache_layers(ctx["cfg"])
    return _share(ctx, kernel, layers * flops, layers * byts)
