"""The serve loop's overlap with the device, from the program's span ring:
the share of the traced decode steps that the engine dispatched ahead of
the host, while tokens that earlier calls sampled were still on the device,
unfetched (`ahead` on `serve.step.decode`). A loop that waits for every
step's tokens before it stages the next reads 0; one that keeps a step in
flight reads near 100."""

from benchmark.readers import spans


def decode_ahead_pct(ctx):
    """Σ `ahead` ÷ the traced `serve.step.decode` spans that carry it, × 100.
    A decode span carries `ahead` when it dispatched a call: one that only
    fetched what earlier calls sampled carries none and is not counted.
    None where no traced decode span carries it (a program without it)."""
    m = spans.Mapped(ctx)
    steps = [s for s in m.in_window("serve.step.decode") if "ahead" in s.attrs]
    if not steps:
        return None
    return 100.0 * sum(s.attrs["ahead"] for s in steps) / len(steps)
