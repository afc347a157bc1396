"""`benchmark/arrange.py`: the rule that picked a mix's `arrangement_seed`
is a program in the repo, the mix holds what the rule picks, and the walk
it rests on counts a hand-made schedule right. CPU only, no jax."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmark")

from benchmark import arrange  # noqa: E402


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


SECONDS = load(ROOT, "BENCHMARK.json")["run_seconds"]
#: the mixes that fix their arrangement by the rule: mix -> (configuration,
#: the least share of the window, in per cent, with every slot taken)
MIXES = {"docs_r80": ("olmo-hybrid-7b", 5.0),
         "complete_v2_r80": ("gpt2-xl", 1.0)}


@pytest.fixture(scope="module", params=sorted(MIXES))
def picked(request):
    """(the mix, its configuration's serving block, its floor for the share
    of the window with every slot taken, every candidate of its rule
    walked)."""
    mix = load(BENCH, "traffic", f"{request.param}.json")
    serving = load(BENCH, "configs",
                   f"{MIXES[request.param][0]}.json")["serving"]
    lo, hi = mix["arrangement_rule"]["seeds"]
    return mix, serving, MIXES[request.param][1], [
        arrange.walk(mix, serving, seed, SECONDS) for seed in range(lo, hi)]


def test_every_mix_that_fixes_its_arrangement_is_held_here():
    """A mix with an `arrangement_seed` states the rule that picked it, and
    this file holds it to the rule."""
    fixed = set()
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        mix = load(BENCH, "traffic", name)
        if mix.get("arrangement_seed") is not None:
            assert mix["arrangement_rule"]["tool"] == "benchmark/arrange.py"
            fixed.add(name.removesuffix(".json"))
    assert fixed == set(MIXES)


def test_the_mix_holds_the_arrangement_its_rule_picks(picked):
    mix, _, _, candidates = picked
    assert os.path.exists(os.path.join(ROOT, mix["arrangement_rule"]["tool"]))
    assert len(candidates) == 300
    assert arrange.pick(candidates)["arrangement_seed"] == \
        mix["arrangement_seed"]


def test_the_picked_arrangement_is_typical_of_the_mix_and_closes_calm(
        picked):
    """Not an outlier of what presses on the slots (every such number
    inside the candidates' interquartile range, slots full for part of the
    window), and calmer at the close than three quarters of them."""
    mix, serving, slots_full_floor, candidates = picked
    stands = arrange.standing(candidates, mix["arrangement_seed"])
    for key in arrange.PRESSURE:
        s = stands[key]
        assert s["q1"] <= s["chosen"] <= s["q3"], (key, s)
    assert stands["in_flight_peak"]["chosen"] > serving["num_slots"]
    assert stands["slots_full_pct"]["chosen"] > slots_full_floor
    assert stands["in_flight_at_close"]["chosen"] < \
        stands["in_flight_at_close"]["q1"]
    before = mix["arrangement_rule"].get("measured_before")
    if not before:
        return
    # the arrangements measured before it were not: one closed on a burst,
    # the other never filled the slots
    burst, idle = (next(r for r in candidates + [
        arrange.walk(mix, serving, seed, SECONDS)]
        if r["arrangement_seed"] == seed) for seed in before)
    assert burst["in_flight_at_close"] > stands["in_flight_at_close"]["q3"]
    assert idle["in_flight_peak"] < serving["num_slots"]
    assert idle not in arrange.typical(candidates)


def test_walk_skips_a_cached_prefix_from_the_request_the_mix_states():
    """GPT-2's prefix cache serves a header's blocks from the second
    request behind it on, the hybrid's snapshot a document from the third
    on: the new mix says so, and the walk follows."""
    mix = load(BENCH, "traffic", "complete_v2_r80.json")
    assert mix["arrangement_rule"]["shared_prefilled_by"] == 1
    assert "shared_prefilled_by" not in load(
        BENCH, "traffic", "docs_r80.json")["arrangement_rule"]
    serving = {"num_slots": 1, "block_size": 128, "prefill_chunk": 256}
    shared = {"count": 1, "tokens": 256, "share": 1.0, "min_body": 64}
    got = arrange.walk(hand_mix(1.0, {"shared_prefilled_by": 1},
                                shared_prefix=shared), serving, 7, 10.0)
    assert got["slots_full_pct"] == pytest.approx(
        100 * (1 * 0.35 + 9 * 0.25) / 10)


def hand_mix(rate, rule=None, **kw):
    return {"kind": "open_loop", "rate_per_s": rate, "arrivals": "uniform",
            "prompt_tokens": {"law": "uniform", "min": 512, "max": 512},
            "output_tokens": {"law": "uniform", "min": 4, "max": 4},
            "vocab": 100, "drain_seconds": 60,
            "arrangement_rule": {"step_model_ms": {
                "host": 0.0, "prefill_chunk": 100.0, "decode_base": 50.0,
                "decode_per_table_block": 0.0}, **(rule or {})}, **kw}


@pytest.mark.parametrize("slots, wait_ms, peak", [(4, 0.0, 1), (1, 0.0, 1)])
def test_walk_counts_a_request_alone_by_hand(slots, wait_ms, peak):
    """One request a second, each 512 prompt tokens (two chunks of 256 at
    100 ms) and 4 output tokens (the first with the last chunk, then three
    decode steps of 50 ms): 350 ms a request, nobody waits."""
    serving = {"num_slots": slots, "block_size": 128, "prefill_chunk": 256}
    got = arrange.walk(hand_mix(1.0), serving, 7, 10.0)
    assert got["tokens_in_window"] == 40 and got["tokens_after_close"] == 0
    assert got["queue_wait_p95_ms"] == pytest.approx(wait_ms)
    assert got["ttft_p95_ms"] == pytest.approx(200.0)
    assert got["in_flight_peak"] == peak and got["in_flight_at_close"] == 0
    assert got["slots_full_pct"] == pytest.approx(35.0 if slots == 1 else 0)


def test_walk_makes_requests_wait_for_a_slot_and_counts_the_close():
    """Four requests a second into one slot that needs 350 ms for each:
    the queue grows, the wait with it, and what the window does not finish
    is counted after its close."""
    serving = {"num_slots": 1, "block_size": 128, "prefill_chunk": 256}
    got = arrange.walk(hand_mix(4.0), serving, 7, 5.0)
    assert got["tokens_in_window"] + got["tokens_after_close"] == 20 * 4
    # the first is due at 5 * 0.5 / 20.5 = 0.122 s; from then on requests
    # of 350 ms back to back: 13 whole ones by the close and 328 ms of the
    # fourteenth (tokens at 200, 250, 300 ms)
    first_due = 5 * 0.5 / 20.5
    assert got["tokens_in_window"] == 13 * 4 + 3
    assert got["in_flight_at_close"] == 20 - 13
    assert got["slots_full_pct"] == pytest.approx(100 * (5 - first_due) / 5)
    assert got["queue_wait_p95_ms"] > 1000


def test_walk_restores_a_shared_document_from_its_third_request_on():
    """Behind one shared document of 256 tokens the first two requests
    prefill both chunks (350 ms in the slot), the other eight start from
    its snapshot and prefill one (250 ms)."""
    serving = {"num_slots": 1, "block_size": 128, "prefill_chunk": 256}
    shared = {"count": 1, "tokens": 256, "share": 1.0, "min_body": 64}
    got = arrange.walk(hand_mix(1.0, shared_prefix=shared), serving, 7, 10.0)
    alone = arrange.walk(hand_mix(1.0), serving, 7, 10.0)
    assert alone["slots_full_pct"] == pytest.approx(35.0)
    assert got["slots_full_pct"] == pytest.approx(
        100 * (2 * 0.35 + 8 * 0.25) / 10)
    assert got["tokens_in_window"] == alone["tokens_in_window"] == 40


def test_typical_is_the_interquartile_box_and_pick_the_calmest_in_it():
    def row(seed, close, **pressure):
        base = dict.fromkeys(arrange.PRESSURE, 5)
        return {"arrangement_seed": seed, "in_flight_at_close": close,
                "tokens_after_close": 10 * close, **base, **pressure}

    rows = [row(1, 0, in_flight_peak=0),      # calmest, but an outlier
            row(2, 3), row(3, 2), row(4, 2), row(5, 4),
            row(6, 1, queue_wait_p95_ms=99)]  # an outlier the other way
    assert [r["arrangement_seed"] for r in arrange.typical(rows)] == [
        2, 3, 4, 5]
    assert arrange.pick(rows)["arrangement_seed"] == 3
