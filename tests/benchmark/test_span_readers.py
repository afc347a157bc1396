"""The readers of the program's span ring (`benchmark/readers/spans.py`) on
a hand-built trace and a hand-built ring: CPU only, counts and structure,
no time of any device."""

import importlib
import inspect
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmark")

from benchmark import harness, trace_reduce  # noqa: E402
from benchmark.readers import spans as rd  # noqa: E402

TRAIN, SERVE = "gpt2m_train_1k", "gpt2xl_serve_complete_v2_r80"
#: the ring's clock at the instant the hand-built trace began, in seconds
T0 = 5000.0


def span(id, parent, name, start_us, end_us, key=None, **attrs):
    """A ring span as the readers see one; times in microseconds after the
    trace began, on the ring's own clock."""
    return types.SimpleNamespace(
        id=id, parent=parent, name=name, start=T0 + start_us * 1e-6,
        end=T0 + end_us * 1e-6, key=key, attrs=attrs)


def hand_ring():
    s = 1_000_000  # microseconds in a second
    ring = [
        # set-up: a trace that nests another, a lowering, two backend
        # compiles; and the reference's compile, after the trace began
        span(1, None, "compile.trace", -59.9 * s, -59.6 * s),
        span(2, None, "compile.trace", -59.8 * s, -59.7 * s),
        span(3, None, "compile.lower", -59.6 * s, -59.5 * s),
        span(4, None, "compile.backend", -59.5 * s, -59.25 * s),
        span(5, None, "compile.backend", -43.2 * s, -43.15 * s),
        span(6, None, "compile.backend", 9 * s, 9.5 * s),
        span(7, None, "compile.trace", 9 * s, 9.5 * s),
        # requests: two of the warm-up (each admitted before a compile of
        # set-up ended), three of the window whose first token came before
        # the trace, one after it
        span(10, None, "serve.request.prefill", -60 * s, -55 * s, key=0),
        span(11, None, "serve.request.prefill", -50 * s, -43 * s, key=1),
        span(12, None, "serve.request.prefill", -9 * s, -8.9 * s, key=2),
        span(13, None, "serve.request.prefill", -7 * s, -6.8 * s, key=3),
        span(14, None, "serve.request.prefill", -3 * s, -2.6 * s, key=4),
        span(15, None, "serve.request.prefill", -1 * s, 8 * s, key=5),
    ]
    # four train steps; the device works from 1000 to 5000 us, so the
    # first starts before its window and the last ends after it
    for n, (i, lo, nb, pb, dp, hi) in enumerate([
            (20, 400, 5, 40, 150, 1400), (30, 1400, 6, 40, 150, 2400),
            (40, 2400, 10, 60, 230, 3600), (50, 3600, 8, 50, 200, 5200)]):
        ring += [
            span(i, None, "train.step", lo, hi, step=10 + n),
            span(i + 1, i, "train.step.next_batch", lo, lo + nb),
            span(i + 2, i, "train.step.put_batch", lo + nb, lo + nb + pb),
            span(i + 3, i, "train.step.dispatch", lo + nb + pb,
                 lo + nb + pb + dp),
            span(i + 4, i, "train.step.callbacks", lo + nb + pb + dp, hi)]
    ring += [
        # a step with a last prefill chunk and a decode: 1000 us, of which
        # 100 + 400 us blocked on the device
        span(60, None, "serve.step", 1100, 2100),
        span(61, 60, "serve.step.admit", 1100, 1130),
        span(62, 60, "serve.step.prefill", 1130, 1500, key=7, q_tokens=8),
        span(63, 62, "serve.step.prefill.stage", 1130, 1140),
        span(64, 62, "serve.step.prefill.dispatch", 1140, 1400),
        span(65, 62, "serve.step.prefill.fetch", 1400, 1500),
        span(66, 60, "serve.step.decode", 1500, 2100, slots=3,
             kv_tokens=300, table_blocks=16, kv_positions_walked=4096),
        span(67, 66, "serve.step.decode.stage", 1500, 1515),
        span(68, 66, "serve.step.decode.dispatch", 1515, 1600),
        span(69, 66, "serve.step.decode.fetch", 1600, 2000),
        # a decode-only step: 800 us, 600 blocked
        span(70, None, "serve.step", 2100, 2900),
        span(71, 70, "serve.step.decode", 2150, 2900, slots=5,
             kv_tokens=500, table_blocks=8, kv_positions_walked=2048),
        span(72, 71, "serve.step.decode.fetch", 2200, 2800),
        # a step that ends after the device's last operation: its decode
        # lies inside the window, the step does not
        span(80, None, "serve.step", 4800, 5300),
        span(81, 80, "serve.step.decode", 4810, 4990, slots=1,
             kv_tokens=100, table_blocks=4, kv_positions_walked=1024),
        # a dense engine's decode walks no table
        span(82, 80, "serve.step.decode", 4991, 4995, slots=1, kv_tokens=9),
    ]
    return ring


def hand_trace(ring, offset_ns):
    """The device busy from 1000 to 5000 us; a host event `<name>.<id>` for
    every ring span open while the profiler ran and at least
    `MIN_HOST_NS` long (`load` drops the shorter), each a few us around
    its span; and events that are none of the ring's."""
    def ns(t):
        return int(round(1e9 * t + offset_ns))

    host = [("ProfilerStart", ns(T0), ns(T0 + 1e-4)),
            ("bench.engine_step", ns(T0 + 1.1e-3), ns(T0 + 2.1e-3)),
            ("fusion.61", ns(T0 + 1.2e-3), ns(T0 + 1.3e-3)),
            ("serve.step.9999", ns(T0 + 1.2e-3), ns(T0 + 1.3e-3)),
            ("serve.step.prefill.60", ns(T0 + 3e-3), ns(T0 + 3.1e-3))]
    for s in ring:
        if s.start < T0 or s.end > T0 + 1 or 1e9 * (s.end - s.start) < \
                trace_reduce.MIN_HOST_NS:
            continue
        before = 1000 * (s.id * 7 % 5)  # 0..4 us, a median of 2
        host.append((f"{s.name}.{s.id}", ns(s.start) - before,
                     ns(s.end) + 1000))
    ops = [("fusion.1", ns(T0 + 1000e-6), ns(T0 + 3000e-6)),
           ("paged_attention_fwd.2", ns(T0 + 3000e-6), ns(T0 + 5000e-6))]
    return trace_reduce.Trace(
        {"/device:TPU:0": {"ops": ops, "modules": []}}, host)


def hand_ctx(offset_ns=7.5e12, ring=None):
    ring = hand_ring() if ring is None else ring
    return {"trace": hand_trace(ring, offset_ns), "spans": ring,
            "run": {}, "cfg": {}, "traffic": {},
            "chips": 1, "device_kind": "TPU v5 lite"}


def spec_of(metric):
    with open(os.path.join(BENCH, "metrics", f"{metric}.json")) as f:
        return json.load(f)


def read(metric, ctx):
    spec = spec_of(metric)
    module, _, func = spec["reader"].partition(":")
    assert module == "spans"
    return getattr(rd, func)(ctx, **spec["args"])


#: metric -> what the hand-built ring holds for it
EXPECTED = {
    # steps 11 and 12 lie inside the device's window: 6 and 10 us
    "train_input_wait_ms": 0.008,
    # ... and 6+40+150 and 10+60+230 us
    "train_loop_host_ms": 0.248,
    # two steps inside the window: 1000-500 and 800-600 us
    "serve_step_host_ms.complete": 0.35,
    # admitted after set-up's last compile, first token before the trace:
    # 0.1, 0.2, 0.4 s
    "serve_prefill_phase_p50_ms.complete": 200.0,
    # three paged decodes inside the window
    "decode_kv_useful_pct.complete": 100.0 * 900 / 7168,
    # 0.3 s of tracing (one nested in another) + 0.1 s of lowering
    "setup_trace_lower_s": 0.4,
    "setup_backend_compile_s": 0.3,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_span_metric_on_the_hand_built_ring(metric):
    assert read(metric, hand_ctx()) == pytest.approx(EXPECTED[metric],
                                                     rel=1e-6)


@pytest.mark.parametrize("offset_ns", [0.0, 7.5e12, -3.25e14])
def test_id_join_places_the_ring_whatever_the_clocks_offset(offset_ns):
    """The ring's clock and the trace's differ by anything; spans under
    20 us have no event; events that are not the ring's do not join."""
    ctx = hand_ctx(offset_ns)
    ring = ctx["spans"]
    offsets = rd.join(ctx["trace"], ring)
    joined = [s for s in ring if T0 <= s.start and s.end <= T0 + 1
              and 1e9 * (s.end - s.start) >= trace_reduce.MIN_HOST_NS]
    assert len(offsets) == len(joined) == 29
    short = [s for s in ring if 0 < 1e9 * (s.end - s.start) < 20_000]
    assert len(short) == 7  # next_batch x4, two stages, a dense decode
    m = rd.Mapped(ctx)
    # every pair within 5 us of the others, the median within 2 us of true
    assert max(offsets) - min(offsets) <= 5000
    assert abs(m.offset_ns - (offset_ns - 2000)) <= 1
    assert m.window == (m.ns(T0 + 1000e-6) + 2000, m.ns(T0 + 5000e-6) + 2000)
    assert m.began == m.ns(T0) + 2000  # the profiler's first event
    # sub-20 us spans are mapped all the same: next_batch is read
    for metric, want in EXPECTED.items():
        assert read(metric, ctx) == pytest.approx(want, rel=1e-6)


def test_readers_read_nothing_from_an_empty_ring_or_a_failed_join():
    old_layout = [types.SimpleNamespace(name="step", path="step", start=T0,
                                        duration=1.0, depth=0)]
    no_events = hand_ctx()
    no_events["trace"].host[:] = [e for e in no_events["trace"].host
                                  if e[0].startswith(("bench.", "Profiler"))]
    no_device = hand_ctx()
    no_device["trace"].devices.clear()
    old = hand_ctx()
    old["spans"] = old_layout
    for ctx in (hand_ctx(ring=[]), old, no_events, no_device,
                {**hand_ctx(), "trace": None}):
        for metric in EXPECTED:
            assert read(metric, ctx) is None, metric
    # no steady-state request the profiler left alone (something compiled
    # until just before the trace): nothing sound to read
    ring = hand_ring() + [span(8, None, "compile.backend", -2e6, -1.5e6)]
    assert read("serve_prefill_phase_p50_ms.complete",
                hand_ctx(ring=ring)) is None


def test_without_spans_in_the_context_the_programs_default_ring_is_read():
    """One import of the program: `obs.default_tracer()`. Real spans on it,
    a trace built around them, and no `spans` key."""
    from distributed_tensorflow_tpu import obs

    tr = obs.default_tracer()
    steps = []
    for n in range(3):
        with tr.span("train.step", step=n) as step:
            with tr.span("next_batch") as nb:
                pass
            with tr.span("put_batch"):
                pass
        steps.append((step, nb))
    lo, hi = steps[0][0].start - 1e-3, steps[-1][0].end + 1e-3
    off = 4.2e12
    trace = trace_reduce.Trace(
        {"/device:TPU:0": {"ops": [
            ("fusion.1", int(1e9 * lo + off), int(1e9 * hi + off))],
            "modules": []}},
        [(f"train.step.{s.id}", int(1e9 * s.start + off),
          int(1e9 * s.end + off) + 1) for s, _ in steps])
    ctx = {"trace": trace, "run": {}, "cfg": {}, "traffic": {}, "chips": 1,
           "device_kind": "TPU v5 lite"}
    want = 1e3 * sum(nb.end - nb.start for _, nb in steps) / 3
    assert read("train_input_wait_ms", ctx) == pytest.approx(want, rel=1e-3)
    assert read("serve_step_host_ms.complete", ctx) is None


#: PR 24's seven span metrics and the cells each lists
SEVEN = {"train_input_wait_ms": [TRAIN], "train_loop_host_ms": [TRAIN],
         "serve_step_host_ms.complete": [SERVE],
         "serve_prefill_phase_p50_ms.complete": [SERVE],
         "decode_kv_useful_pct.complete": [SERVE],
         "setup_trace_lower_s": [TRAIN, SERVE],
         "setup_backend_compile_s": [TRAIN, SERVE]}


def the_seven(manifest: dict) -> tuple:
    """(where the seven stand in `per_layer`, their entries), found by
    name: later PRs append after them, so their place from the end moves."""
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("train_input_wait_ms")
    return at, manifest["per_layer"][at:at + 7]


def test_manifest_lists_the_seven_span_metrics_and_finds_their_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    _, seven = the_seven(manifest)
    assert [m["name"] for m in seven] == list(SEVEN)  # together, in order
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert {"train loop", "start-up"} <= layers
    for m in seven:
        spec = spec_of(m["name"])
        assert {k: spec[k] for k in m} == m
        assert m["workloads"] == SEVEN[m["name"]]
        module, _, func = spec["reader"].partition(":")
        reader = getattr(importlib.import_module(
            f"benchmark.readers.{module}"), func)
        inspect.signature(reader).bind({}, **spec["args"])
    # through the harness's own dispatch, each cell's line gets its own
    for cell, n in ((TRAIN, 4), (SERVE, 5)):
        got = harness.per_layer_metrics(
            {"per_layer": seven}, cell, hand_ctx())
        assert len(got) == n and all(
            got[k]["value"] == pytest.approx(EXPECTED[k]) for k in got)


# ---------------------------------------------------------------------------
# the work of the traced runs: the span-fed `calls.traced` against the
# records-fed reader it replaced (`serve_driver.Calls`, gone since PR 26)
# ---------------------------------------------------------------------------

MODULES = {"prefill_module": "jit_paged_prefill_chunk",
           "decode_module": "jit_paged_decode_step"}


def traced_from_records(trace, records):
    """The reader as it was while `serve_driver.Calls` kept one record
    (kind, query tokens, keys attended, context read) for every call of the
    engine's two compiled steps, cut where the profiler was switched off:
    the last records of each kind, as many as the trace holds runs."""
    if not records:
        return None
    out = {"busy_s": 0.0, "attended": 0, "context_read": 0}
    for kind in ("prefill", "decode"):
        seconds, runs = trace_reduce.busy_in_runs(trace,
                                                  MODULES[f"{kind}_module"])
        mine = [r for r in records if r[0] == kind]
        if runs > len(mine):
            return None
        mine = mine[len(mine) - runs:]
        out["busy_s"] += seconds or 0.0
        out[f"{kind}_calls"] = runs
        out[f"{kind}_tokens"] = sum(r[1] for r in mine)
        out["attended"] += sum(r[2] for r in mine)
        out["context_read"] += sum(r[3] for r in mine)
    if not out["prefill_tokens"] + out["decode_tokens"]:
        return None
    return out


#: the engine's calls of one run, in call order: (kind, span start and end in
#: us after the trace began, the device run's start and end or None where
#: the trace holds no run of it, the counts). The profiler runs from 0 to
#: 6000 us; the device works from 1000 to 5000 us.
ENGINE_CALLS = [
    # warm-up and the window before the profiler
    ("prefill", -9000, -8000, None, (32, 528, 32)),
    ("decode", -8000, -7000, None, (9, 999, 999)),
    ("prefill", -700, -600, None, (8, 292, 40)),
    # traced: a chunk that is not its prompt's last (no fetch: its run
    # outlasts its span), a last chunk, a decode; then a decode-only step
    ("prefill", 900, 1100, (1000, 1400), (32, 32 * 64 + 528, 96)),
    ("prefill", 1100, 1900, (1400, 1800), (5, 5 * 96 + 15, 101)),
    ("decode", 1900, 3000, (1900, 2900), (3, 300, 300)),
    ("decode", 3100, 5050, (3200, 5000), (4, 417, 417)),
    # the wait after the window: the profiler is off
    ("decode", 9000, 9900, None, (4, 421, 421)),
    ("prefill", 9900, 9990, None, (32, 528, 32)),
]


def serving_case(calls=ENGINE_CALLS, counts=True, offset_ns=7.5e12):
    """(ctx for the span-fed reader, records for the records-fed one)."""
    def ns(us):
        return int(round(1e9 * (T0 + us * 1e-6) + offset_ns))

    ring, records, mods, ops = [], [], [], []
    host = [("ProfilerStart", ns(0), ns(100))]
    for i, (kind, lo, hi, run, (tokens, attended, context)) in enumerate(
            calls, start=100):
        attrs = {} if not counts else (
            {"q_tokens": tokens, "attended": attended, "context": context}
            if kind == "prefill" else {"slots": tokens, "kv_tokens": attended})
        ring.append(span(i, None, f"serve.step.{kind}", lo, hi, **attrs))
        if lo < 6000:  # made before the profiler was switched off
            records.append((kind, tokens, attended, context))
        if 0 <= lo < 6000:
            host.append((f"serve.step.{kind}.{i}", ns(lo) - 1500, ns(hi)))
        if run is not None:
            program = MODULES[f"{kind}_module"]
            mods.append((f"{program}({i})", ns(run[0]), ns(run[1])))
            ops.append((f"paged_attention_fwd.{i}", ns(run[0]) + 10_000,
                        ns(run[1]) - 10_000))
    ctx = {"trace": trace_reduce.Trace(
        {"/device:TPU:0": {"ops": ops, "modules": mods}}, host),
        "spans": ring, "run": {}, "cfg": {}, "traffic": {}, "chips": 1,
        "device_kind": "TPU v5 lite"}
    return ctx, records


def without(kind_and_start, what):
    """ENGINE_CALLS with one call's span (``what`` = "call") or device run
    (``what`` = "run") taken away."""
    out = []
    for c in ENGINE_CALLS:
        if (c[0], c[1]) == kind_and_start:
            if what == "call":
                continue
            c = c[:3] + (None,) + c[4:]
        out.append(c)
    return out


@pytest.mark.parametrize("case", [
    "spans_match_runs", "other_clock_offset", "a_prefill_run_without_a_span",
    "a_decode_run_without_a_span", "empty_ring", "ring_without_the_counts",
    "last_span_sent_but_not_run"])
def test_span_fed_work_of_the_traced_runs_against_the_records_fed(case):
    from benchmark.readers import calls

    ctx, records = serving_case(
        offset_ns=-3.25e14 if case == "other_clock_offset" else 7.5e12)
    if case == "a_prefill_run_without_a_span":
        # the profiler caught a run that was sent before it came on
        ring_ctx, _ = serving_case(without(("prefill", 900), "call"))
        ctx["spans"], records = ring_ctx["spans"], records[:2]
    elif case == "a_decode_run_without_a_span":
        ring_ctx, _ = serving_case(without(("decode", 1900), "call"))
        ctx["spans"], records = ring_ctx["spans"], [
            r for r in records if r[0] == "prefill"] + records[-2:-1]
    elif case == "empty_ring":
        ctx["spans"], records = [], []
    elif case == "ring_without_the_counts":
        ctx["spans"], records = serving_case(counts=False)[0]["spans"], []
    elif case == "last_span_sent_but_not_run":
        # a chunk sent while the device still worked, whose run the
        # profiler no longer caught: the records-fed reader took the LAST
        # records and was off by one call; the spans keep their order
        ctx, records = serving_case(
            ENGINE_CALLS[:7] + [("prefill", 4950, 4990, None, (7, 735, 108))]
            + ENGINE_CALLS[7:])
    got = calls.traced(ctx, **MODULES)
    want = traced_from_records(ctx["trace"], records)
    if case in ("spans_match_runs", "other_clock_offset"):
        assert want["prefill_calls"] == 2 and want["decode_calls"] == 2
        assert got.pop("prefill_spans") == 2 and got.pop("decode_spans") == 2
        assert got == want
        assert got["prefill_tokens"] == 37 and got["decode_tokens"] == 7
        assert got["attended"] == 32 * 64 + 528 + 5 * 96 + 15 + 717
        assert got["context_read"] == 96 + 101 + 717
        assert got["busy_s"] == pytest.approx((380 + 380 + 980 + 1780) * 1e-6)
    elif case == "last_span_sent_but_not_run":
        assert (got["prefill_spans"], got["prefill_calls"]) == (3, 2)
        assert got["prefill_tokens"] == 37 and want["prefill_tokens"] == 12
        assert got["context_read"] == 96 + 101 + 717
    else:
        assert got is None and want is None
