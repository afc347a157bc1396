"""Kernels' shares of their roofline: the least time the chip could take
for the calls the trace shows (`counts.py`: the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s), over the time the trace gives them."""

from benchmark import counts, families, trace_reduce
from benchmark.readers import calls


def _flash_shape(ctx):
    job = ctx["traffic"]
    heads, head_dim = families.counts(ctx["cfg"]).attention_shape(ctx["cfg"])
    return job["sequences_per_chip"], heads, job["seq_len"], head_dim


def flash(ctx, kernels: list, count_calls_of: str, which: str):
    """``kernels``: name patterns whose time adds up (forward: one; backward:
    dKV and dQ). One needed unit of work per call of ``count_calls_of``."""
    if not ctx.get("trace"):
        return None
    seconds = 0.0
    for k in kernels:
        s, _ = trace_reduce.op_seconds(ctx["trace"], k)
        if s is None:
            return None
        seconds += s
    _, calls = trace_reduce.op_seconds(ctx["trace"], count_calls_of)
    need = {"fwd": counts.flash_fwd, "bwd": counts.flash_bwd}[which]
    flops, byts = need(*_flash_shape(ctx))
    least = calls * counts.roofline_seconds(flops, byts, ctx["device_kind"])
    return 100.0 * least / seconds if seconds else None


def paged(ctx, kernel: str, prefill_module: str, decode_module: str):
    """Memory-bound: the K and V of the live contexts, read once per layer
    and traced run, over the kernel's time in the trace."""
    work = calls.traced(ctx, prefill_module, decode_module)
    if work is None:
        return None
    seconds, _ = trace_reduce.op_seconds(ctx["trace"], kernel)
    if not seconds:
        return None
    cfg, family = ctx["cfg"], families.counts(ctx["cfg"])
    heads, head_dim = family.attention_shape(cfg)
    flops, byts = counts.paged_attention(
        heads * head_dim, family.kv_bytes_per_token(cfg), work["attended"],
        work["context_read"])
    layers = family.cache_layers(cfg)
    least = counts.roofline_seconds(layers * flops, layers * byts,
                                    ctx["device_kind"])
    return 100.0 * least / seconds
