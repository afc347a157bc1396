"""The one general generator: a traffic mix is a file of parameters under
`benchmark/traffic/`, and this module turns it and `--seed` into work.

Kinds:

- `train_job`: a training job; the file's parameters go to the program's
  own data pipeline and optimizer (`train_driver`), nothing is drawn here.
- `open_loop`: requests arrive on a schedule whether or not earlier ones
  have finished (independent users). `schedule()` draws the whole window's
  requests in advance.
- `backlog`: a queue kept at a fixed depth (a batch job over documents):
  `stream()` yields requests without due times and the driver tops the
  queue up.

Every seed gets the SAME multiset of gaps and lengths, the quantiles of the
file's distributions: the work of a window does not vary from seed to seed.
Their order (the arrangement: which request is long, which gaps are short,
which prompts carry a header) is drawn from the seed too, unless the mix
fixes it with `arrangement_seed`: then every seed replays that one schedule
with tokens of its own. A mix whose tails are judged fixes it, because a
tail over some tens of requests is set by which long requests happen to
meet (PERF.md section 2 has the spreads measured either way).
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float | None      # seconds after the window opens (open loop)
    prompt: list
    out_len: int
    prefix: int | None = None  # which shared header the prompt starts with


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, median: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    """``n`` lengths at the quantiles of a log-normal, clipped to [lo, hi]."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def uniform_lengths(n: int, lo: int, hi: int) -> np.ndarray:
    return np.rint(lo + (hi - lo) * _quantiles(n)).astype(int)


def lengths(n: int, spec: dict) -> np.ndarray:
    if spec["law"] == "lognormal":
        return lognormal_lengths(n, spec["median"], spec["sigma"],
                                 spec["min"], spec["max"])
    if spec["law"] == "uniform":
        return uniform_lengths(n, spec["min"], spec["max"])
    raise ValueError(f"unknown length law {spec['law']!r}")


def arrivals(n: int, seconds: float, law: str, rng) -> np.ndarray:
    """``n`` due times in [0, seconds): the gaps are the quantiles of the
    law's gap distribution in a seed-drawn order."""
    if law == "poisson":
        gaps = -np.log1p(-_quantiles(n))
    elif law == "uniform":
        gaps = np.ones(n)
    else:
        raise ValueError(f"unknown arrival law {law!r}")
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps) - gaps[0] * 0.5
    return due * (seconds / (due[-1] + gaps.mean()))


def _rng(seed: int, stream: int):
    return np.random.default_rng([int(seed), stream])


def _headers(mix: dict, seed: int, vocab: int) -> list:
    share = mix.get("shared_prefix")
    if not share:
        return []
    rng = _rng(seed, 1)
    return [rng.integers(0, vocab, share["tokens"]).tolist()
            for _ in range(share["count"])]


def _requests(mix: dict, seed: int, n: int, vocab: int, rng) -> list:
    """``n`` requests: lengths at the quantiles, shuffled; a fixed share of
    them starts with one of the shared headers, taken in turn."""
    prompt_len = rng.permutation(lengths(n, mix["prompt_tokens"]))
    out_len = rng.permutation(lengths(n, mix["output_tokens"]))
    headers = _headers(mix, seed, vocab)
    with_header = np.zeros(n, bool)
    if headers:
        # the header is part of the prompt's length, so it goes to prompts
        # long enough to hold it and a block of the user's own: the total
        # of prompt tokens is then the same for every seed
        k = int(round(mix["shared_prefix"]["share"] * n))
        need = mix["shared_prefix"]["tokens"] + mix["shared_prefix"]["min_body"]
        order = rng.permutation(n)
        fits = [i for i in order if prompt_len[i] >= need]
        rest = [i for i in order if prompt_len[i] < need]
        with_header[(fits + rest)[:k]] = True
    out, turn = [], 0
    for i in range(n):
        total, prefix, head = int(prompt_len[i]), None, []
        if with_header[i]:
            prefix = turn % len(headers)
            turn += 1
            head = headers[prefix]
            # a header is followed by at least one block of the user's own
            total = max(total, len(head) + mix["shared_prefix"]["min_body"])
        body = rng.integers(0, vocab, total - len(head)).tolist()
        out.append(Request(None, head + body, int(out_len[i]), prefix))
    return out


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """Open loop: every request of a window of ``seconds``, by due time."""
    if mix["kind"] != "open_loop":
        raise ValueError("schedule() is for open_loop mixes")
    n = max(int(round(mix["rate_per_s"] * seconds)), 1)
    arrangement = mix.get("arrangement_seed")
    drawn_from = seed if arrangement is None else arrangement
    rng = _rng(drawn_from, 0)
    reqs = _requests(mix, drawn_from, n, vocab, rng)
    for r, due in zip(reqs, arrivals(n, seconds, mix["arrivals"], rng)):
        r.due_s = float(due)
    if arrangement is not None:
        _retoken(reqs, mix, seed, vocab)
    return reqs


def _retoken(reqs: list, mix: dict, seed: int, vocab: int) -> None:
    """Keep the schedule (due times, lengths, which header where) and draw
    the headers' and the prompts' tokens anew from ``seed``."""
    headers = _headers(mix, seed, vocab)
    rng = _rng(seed, 2)
    for r in reqs:
        head = [] if r.prefix is None else headers[r.prefix]
        r.prompt = head + rng.integers(
            0, vocab, len(r.prompt) - len(head)).tolist()


def stream(mix: dict, seed: int, vocab: int, batch: int = 64):
    """Backlog: an endless stream of requests, ``batch`` at a time from the
    same quantile construction."""
    if mix["kind"] != "backlog":
        raise ValueError("stream() is for backlog mixes")
    rng = _rng(seed, 0)
    while True:
        yield from _requests(mix, seed, batch, vocab, rng)


def max_context(mix: dict) -> int:
    """The longest prompt plus output the mix can produce."""
    longest = mix["prompt_tokens"]["max"]
    share = mix.get("shared_prefix")
    if share:
        longest = max(longest, share["tokens"] + share["min_body"])
    return longest + mix["output_tokens"]["max"]
