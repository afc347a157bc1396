"""The interpreter's pauses (`benchmark/readers/host_pauses.py`): the
`runtime.gc` spans of the program's ring, read over the serving cells'
steady stretch and the training cell's traced steps. On the hand-built ring
and trace of `test_span_readers.py`, on a trace of a real profiler session
here, and through the harness: CPU only, counts and structure, no time of
any device."""

import gc
import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import test_span_readers as t  # noqa: E402
from benchmark import harness, trace_reduce  # noqa: E402
from benchmark.readers import host_pauses, spans  # noqa: E402

S = 1_000_000  # microseconds in a second
#: metric -> its one cell, in the order `BENCHMARK.json` appends them
CELLS = {"serve_gc_pause_ms_per_s.complete": "gpt2xl_serve_complete_v2_r80",
         "serve_gc_pause_max_ms.complete": "gpt2xl_serve_complete_v2_r80",
         "serve_gc_pause_ms_per_s.docs_r80": "olmohybrid_serve_docs_r80",
         "serve_gc_pause_ms_per_s.longdocs_r80":
             "gigachat35_serve_longdocs_r80",
         "train_gc_pause_ms": "gpt2m_train_1k"}


def gc_span(id, parent, start_us, end_us, generation=2):
    return t.span(id, parent, "runtime.gc", start_us, end_us,
                  generation=generation, collected=3, uncollectable=0)


def outside():
    """Collections no window holds: one in set-up, before its last compile
    ended (-43.15 s), and the benchmark's own `gc.collect()` after the
    window closed, long after the trace."""
    return [gc_span(90, None, -50 * S, -49.7 * S),
            gc_span(95, None, 60 * S, 60.2 * S)]


def inside():
    """Two in the steady stretch (a full one of 250 ms, a young one of
    2 ms); two inside the device's traced window (the device works from 1000
    to 5000 us): one under a training step, one on another thread with no
    span open. Ids 91 and 96 keep the join's median offset where the base
    ring has it."""
    return [gc_span(92, None, -30 * S, -29.75 * S),
            gc_span(93, None, -10 * S, -10 * S + 2000, generation=0),
            gc_span(91, 40, 2950, 3250, generation=1),
            gc_span(96, None, 4000, 4500)]


def ring(*parts):
    return t.hand_ring() + [s for part in parts for s in part]


def read(metric, ctx):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           f"{metric}.json")) as f:
        spec = json.load(f)
    module, _, func = spec["reader"].partition(":")
    assert module == "host_pauses" and spec["args"] == {}
    return getattr(host_pauses, func)(ctx)


#: the steady stretch: set-up's last compile ended 43.15 s before the
#: trace began (the profiler's first event, 2 us past the ring's T0)
STEADY_S = 43.15 + 2e-6
EXPECTED = {
    "serve_gc_pause_ms_per_s.complete": 1e3 * (0.25 + 0.002) / STEADY_S,
    "serve_gc_pause_max_ms.complete": 250.0,
    "serve_gc_pause_ms_per_s.docs_r80": 1e3 * (0.25 + 0.002) / STEADY_S,
    "serve_gc_pause_ms_per_s.longdocs_r80": 1e3 * (0.25 + 0.002) / STEADY_S,
    # 300 + 500 us over the two training steps inside the device's window
    "train_gc_pause_ms": 0.4,
}


@pytest.mark.parametrize("metric", list(EXPECTED))
@pytest.mark.parametrize("offset_ns", [7.5e12, 0.0, -3.25e14])
def test_each_pause_metric_on_the_hand_built_ring(metric, offset_ns):
    ctx = t.hand_ctx(offset_ns, ring=ring(outside(), inside()))
    assert read(metric, ctx) == pytest.approx(EXPECTED[metric], rel=1e-6)


@pytest.mark.parametrize("metric", list(EXPECTED))
def test_a_window_without_a_spanned_collection_reads_zero(metric):
    """The program spans collections (set-up's, the one after the window),
    none of them in the window read: 0, not nothing, so that the metric
    does not come and go with the draw of the window."""
    assert read(metric, t.hand_ctx(ring=ring(outside()))) == 0.0


def test_a_program_without_the_span_and_a_failed_join_read_nothing():
    """The parent's ring holds no `runtime.gc` at all: nothing, never 0. As
    for every span reader, nothing either from an empty ring, a trace that
    joins none of the ring, a trace without the device, no trace, or no
    compile of set-up ending before the trace began."""
    no_events = t.hand_ctx(ring=ring(outside(), inside()))
    no_events["trace"].host[:] = [
        e for e in no_events["trace"].host
        if e[0].startswith(("bench.", "Profiler"))]
    no_device = t.hand_ctx(ring=ring(outside(), inside()))
    no_device["trace"].devices.clear()
    for ctx in (t.hand_ctx(), t.hand_ctx(ring=[]), no_events, no_device,
                {**t.hand_ctx(ring=ring(inside())), "trace": None}):
        for metric in EXPECTED:
            assert read(metric, ctx) is None, metric
    setup_free = [s for s in ring(outside(), inside())
                  if not (s.name.startswith("compile.") and s.start < t.T0)]
    for metric in EXPECTED:
        if metric.startswith("serve_"):
            assert read(metric, t.hand_ctx(ring=setup_free)) is None
    # the training reader wants no set-up, only the traced steps
    assert read("train_gc_pause_ms", t.hand_ctx(ring=setup_free)) \
        == pytest.approx(0.4)


def test_idle_gaps_name_a_gap_that_a_collection_covered():
    """`trace_reduce.idle_gaps`, unchanged, gives a device gap to the
    shortest host event over its middle: an annotation `runtime.gc.<id>`
    inside a staging span names the gap `runtime.gc`, whether or not the
    ring kept the collection."""
    us = 1000
    ops = [("fusion.1", 0, 1000 * us), ("fusion.2", 1500 * us, 2500 * us),
           ("fusion.3", 3000 * us, 3200 * us)]
    host = [("serve.step.decode.stage.12", 900 * us, 1600 * us),
            ("runtime.gc.13", 1050 * us, 1450 * us),
            ("serve.step.decode.stage.20", 2450 * us, 3100 * us)]
    trace = trace_reduce.Trace(
        {"/device:TPU:0": {"ops": ops, "modules": []}}, host)
    assert trace_reduce.idle_gaps(trace) == [
        ["runtime.gc", pytest.approx(5e-4)],
        ["serve.step.decode.stage", pytest.approx(5e-4)]]
    trace.host.pop(1)
    assert trace_reduce.idle_gaps(trace) == [
        ["serve.step.decode.stage", pytest.approx(1e-3)]]


def test_a_profiler_session_holds_each_collection_under_its_span_id(
        tmp_path):
    """A real session on the CPU: a full collection inside a span of the
    program's default tracer is a host event `runtime.gc.<id>` of the
    trace that `trace_reduce.load` reads, under the id of its ring span,
    and joins the ring to the trace like any span."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu import obs

    tr = obs.default_tracer()
    jnp.arange(4.0).sum().block_until_ready()  # the backend, before
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("test.collect_here") as sp:
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    trace = trace_reduce.load(path)
    (mine,) = [s for s in tr.events
               if s.name == "runtime.gc" and s.parent == sp.id]
    events = {name: (lo, hi) for name, lo, hi in trace.host}
    lo, hi = events[f"runtime.gc.{mine.id}"]
    outer = events[f"test.collect_here.{sp.id}"]
    assert outer[0] <= lo <= hi <= outer[1]
    # two pairs join: the span and its collection, on one offset
    offsets = spans.join(trace, [sp, mine])
    assert len(offsets) == 2
    assert abs(offsets[0] - offsets[1]) < 1e6  # within a millisecond


def test_the_five_entries_are_appended_and_each_cell_reads_its_own():
    """The five files and their manifest entries agree key for key and are
    the last five entries; through the harness each cell's traced line
    gets its own, and only its own."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = manifest["per_layer"][-5:]
    assert [m["name"] for m in entries] == list(CELLS)
    for m in entries:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               f"{m['name']}.json")) as f:
            spec = json.load(f)
        assert {k: spec[k] for k in m} == m
        assert set(spec) - set(m) == {"reader", "args"}
        assert m["workloads"] == [CELLS[m["name"]]]
        assert (m["source"], m["better"]) == ("host_clock", "lower")
        assert m["layer"] == ("train loop" if m["name"].startswith("train_")
                              else "serve loop")
    ctx = t.hand_ctx(ring=ring(outside(), inside()))
    for cell in set(CELLS.values()):
        got = harness.per_layer_metrics({"per_layer": entries}, cell, ctx)
        assert set(got) == {n for n, c in CELLS.items() if c == cell}
        for name, value in got.items():
            assert value["value"] == pytest.approx(EXPECTED[name], rel=1e-6)
