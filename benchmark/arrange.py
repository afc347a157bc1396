#!/usr/bin/env python3
"""How a mix's `arrangement_seed` was picked, as a program anyone can run:

    python3 benchmark/arrange.py --config olmo-hybrid-7b --traffic docs_r80 \
        [--seeds LO:HI]
    python3 benchmark/arrange.py --config gpt2-xl --traffic complete_v2_r80

A mix that fixes its arrangement replays ONE order of lengths and gaps
(`traffic.py`), and which order is a choice. This file holds the rule of
that choice and shows where the chosen one stands among the arrangements
the rule looked at: it walks the serving engine's loop on the schedule
alone (no chip, no program, no token), a step at a time, with the step
times the mix states under `arrangement_rule.step_model_ms` (read off the
cell's traced runs; PERF.md section 2), and reads for every candidate the
course of requests in flight, the wait for a slot and the tokens that fall
after the window's close.

`pick()` is the rule. First the arrangement has to be typical of the mix
in what presses on the slots: peak and mean of requests in flight, the
share of the window with every slot taken and the p95 of the wait for
admission each lie between the candidates' first and third quartile. Of
those, the one with the fewest requests in flight at the close: a window
that closes on a burst delivers a share of its tokens after the close, and
that share varies with a millisecond of host time, which is the spread of
`serve_tokens_per_s` and not its level.

The model is coarse on purpose: a step is the host's share, one chunk for
every slot still in prefill, and one decode step whose time grows with the
widest table (a power-of-two bucket of blocks) among the decoding slots; a
shared prefix is prefilled whole by its first requests and skipped by the
later ones. How many prefill it is the mix's to say
(`arrangement_rule.shared_prefilled_by`): 2 where it is left out, the
hybrid decoder's state snapshot, which the second request with a document
writes; 1 for a prefix cache of K/V blocks alone (GPT-2), which serves the
blocks the first request wrote. It knows nothing of preemption.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import traffic  # noqa: E402

#: what presses on the slots: `pick()` wants these typical of the mix
PRESSURE = ("in_flight_peak", "in_flight_mean", "slots_full_pct",
            "queue_wait_p95_ms")
#: what `standing()` sets beside the candidates' quartiles
KEYS = ("in_flight_at_close", "tokens_after_close", *PRESSURE,
        "queue_wait_p50_ms", "ttft_p95_ms", "tokens_in_window")


def walk(mix: dict, serving: dict, arrangement_seed: int,
         seconds: float) -> dict:
    """One window of ``mix`` in the order ``arrangement_seed`` draws, through
    a model of the engine's loop; times in seconds from the window's
    opening."""
    model = mix["arrangement_rule"]["step_model_ms"]
    slots, bs = serving["num_slots"], serving["block_size"]
    chunk = serving["prefill_chunk"]
    reqs = traffic.schedule({**mix, "arrangement_seed": arrangement_seed}, 1,
                            seconds, mix["vocab"])
    n = len(reqs)
    due = np.array([r.due_s for r in reqs])
    prompt = np.array([len(r.prompt) for r in reqs])
    out = np.array([r.out_len for r in reqs])
    shared = (mix.get("shared_prefix") or {}).get("tokens", 0)
    prefilled_by = mix["arrangement_rule"].get("shared_prefilled_by", 2)
    seen: dict = {}                       # document -> requests begun
    pos, gen = np.zeros(n, int), np.zeros(n, int)
    admit, first, done = (np.full(n, np.nan) for _ in range(3))
    queue, slot = [], [None] * slots
    t, nxt, tokens_in, full_s = 0.0, 0, 0, 0.0
    limit = seconds + mix["drain_seconds"]
    while t < limit and (nxt < n or queue or any(
            i is not None for i in slot)):
        while nxt < n and due[nxt] <= t:
            queue.append(nxt)
            nxt += 1
        for s in range(slots):
            if slot[s] is None and queue:
                i = queue.pop(0)
                if reqs[i].prefix is not None:
                    before = seen.get(reqs[i].prefix, 0)
                    seen[reqs[i].prefix] = before + 1
                    if before >= prefilled_by:  # restored, not prefilled
                        pos[i] = shared
                slot[s], admit[i] = i, t
        live = [i for i in slot if i is not None]
        if not live:
            t = due[nxt] if nxt < n else t + 0.01
            continue
        filling = [i for i in live if pos[i] < prompt[i]]
        decoding = [i for i in live if pos[i] >= prompt[i]]
        dt = model["host"] + model["prefill_chunk"] * len(filling)
        if decoding:
            blocks = max(-(-(prompt[i] + gen[i]) // bs) for i in decoding)
            width = 1
            while width < blocks:
                width *= 2
            dt += model["decode_base"] + model["decode_per_table_block"] * width
        dt /= 1e3
        if len(live) == slots and t < seconds:
            full_s += min(dt, seconds - t)
        t += dt
        for i in filling:
            pos[i] = min(pos[i] + chunk, prompt[i])
        for i in filling + decoding:
            if pos[i] < prompt[i]:
                continue
            if gen[i] == 0:
                first[i] = t
            gen[i] += 1
            tokens_in += t <= seconds
            if gen[i] >= out[i]:
                done[i] = t
                slot[slot.index(i)] = None

    def in_flight(at):
        return int(((due <= at) & ~(done <= at)).sum())

    course = [in_flight(x / 2) for x in range(int(2 * seconds) + 1)]
    wait = 1e3 * (admit - due)
    return {
        "arrangement_seed": int(arrangement_seed),
        "in_flight_at_close": in_flight(seconds),
        "in_flight_peak": max(course),
        "in_flight_mean": float(np.mean(course)),
        "slots_full_pct": float(100 * full_s / seconds),
        "queue_wait_p50_ms": float(np.nanpercentile(wait, 50)),
        "queue_wait_p95_ms": float(np.nanpercentile(wait, 95)),
        "ttft_p95_ms": 1e3 * float(np.nanpercentile(first - due, 95)),
        "tokens_in_window": int(tokens_in),
        "tokens_after_close": int(out.sum() - tokens_in),
        "in_flight_every_3s": course[10::6]}


def _calm(row: dict) -> tuple:
    return (row["in_flight_at_close"], row["tokens_after_close"],
            row["arrangement_seed"])


def typical(rows: list) -> list:
    """The candidates whose every `PRESSURE` number lies between the first
    and the third quartile of all of them."""
    box = {key: statistics.quantiles([r[key] for r in rows], n=4)
           for key in PRESSURE}
    return [r for r in rows
            if all(box[k][0] <= r[k] <= box[k][2] for k in PRESSURE)]


def pick(rows: list) -> dict:
    """The rule: typical slot pressure, then the calmest close (fewest in
    flight there, then fewest tokens after it, then the lowest seed)."""
    return min(typical(rows), key=_calm)


def standing(rows: list, seed: int) -> dict:
    """Where ``seed``'s arrangement stands among ``rows``: for each number
    its own reading, the candidates' quartiles and ends, and how many of
    them read lower."""
    mine = next(r for r in rows if r["arrangement_seed"] == seed)
    out = {}
    for key in KEYS:
        values = [r[key] for r in rows]
        q1, q2, q3 = statistics.quantiles(values, n=4)
        out[key] = {"chosen": mine[key], "min": min(values), "q1": q1,
                    "median": q2, "q3": q3, "max": max(values),
                    "candidates_below": sum(v < mine[key] for v in values)}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default=None,
                    help="LO:HI, the mix's arrangement_rule.seeds if left out")
    args = ap.parse_args()
    here = os.path.join(ROOT, "benchmark")
    with open(os.path.join(here, "traffic", f"{args.traffic}.json")) as f:
        mix = json.load(f)
    with open(os.path.join(here, "configs", f"{args.config}.json")) as f:
        serving = json.load(f)["serving"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    lo, hi = (map(int, args.seeds.split(":")) if args.seeds
              else mix["arrangement_rule"]["seeds"])
    rows = [walk(mix, serving, seed, seconds) for seed in range(lo, hi)]
    chosen = pick(rows)
    for r in sorted(typical(rows), key=_calm)[:8]:
        print(json.dumps(r))
    print(json.dumps({"picked": chosen["arrangement_seed"],
                      "of": [lo, hi], "the_mix_has": mix["arrangement_seed"],
                      "standing": standing(rows, chosen["arrangement_seed"])}))
    for seed in mix["arrangement_rule"].get("measured_before", []):
        print(json.dumps({"measured_before": walk(mix, serving, seed,
                                                  seconds)}))

if __name__ == "__main__":
    main()
