"""obs/ subsystem tests: registry merge semantics, histogram percentile
accuracy against numpy quantiles, Prometheus render invariants, span
tracing, JSONL export, and the serve-engine telemetry acceptance gate
(ISSUE 2: sum of latency-histogram counts == finished requests)."""

import gc
import json
import re
import urllib.request

import numpy as np
import pytest

from distributed_tensorflow_tpu import obs
from distributed_tensorflow_tpu.obs import registry as reg_lib


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------


def test_get_or_create_identity_and_type_conflict():
    r = obs.Registry()
    c1 = r.counter("requests_total", "help text")
    c2 = r.counter("requests_total")
    assert c1 is c2
    # distinct label sets are distinct children
    a = r.counter("finished_total", reason="eos")
    b = r.counter("finished_total", reason="max_len")
    assert a is not b
    with pytest.raises(ValueError):
        r.gauge("requests_total")  # name already a counter
    with pytest.raises(ValueError):
        r.counter("0bad name")


def test_counter_and_gauge_semantics():
    r = obs.Registry()
    c = r.counter("c_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("g")
    g.set(7)
    g.set(3)
    assert g.value == 3.0


def test_histogram_observe_and_bucket_edges():
    r = obs.Registry()
    h = r.histogram("h_seconds", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 5.0, 100.0, 1e6):  # 1.0 and 100.0 land ON bounds
        h.observe(v)
    assert h.counts.tolist() == [2, 1, 1, 1]  # le semantics + overflow
    assert h.count == 5
    assert h.sum == pytest.approx(0.5 + 1 + 5 + 100 + 1e6)
    with pytest.raises(ValueError):
        r.histogram("h_seconds", buckets=(1.0, 2.0))  # bucket mismatch
    with pytest.raises(ValueError):
        obs.Histogram("bad", buckets=())


def test_histogram_percentiles_match_numpy_quantiles():
    """Log-bucket read-back must sit within one bucket ratio of the true
    quantile, for distributions spanning several decades."""
    rng = np.random.RandomState(7)
    per_decade = 20
    ratio = 10 ** (1 / per_decade)
    buckets = obs.log_buckets(1e-5, 10.0, per_decade=per_decade)
    for vals in (
        rng.lognormal(-5.0, 1.5, 20_000),
        rng.exponential(0.01, 20_000),
        np.abs(rng.normal(0.001, 0.0005, 20_000)) + 1e-5,
    ):
        h = obs.Histogram("lat_seconds", buckets=buckets)
        for v in vals:
            h.observe(v)
        for q in (0.1, 0.5, 0.9, 0.99):
            est = h.percentile(q)
            true = float(np.quantile(vals, q))
            assert est == pytest.approx(true, rel=ratio - 1 + 0.01), (
                f"q={q}: est {est} vs numpy {true}"
            )


def test_histogram_percentile_edges():
    h = obs.Histogram("h", buckets=(1.0, 2.0))
    assert np.isnan(h.percentile(0.5))  # empty
    h.observe(100.0)  # overflow-only
    assert h.percentile(0.5) == 2.0  # floor: last finite bound
    with pytest.raises(ValueError):
        h.percentile(1.5)


def test_registry_merge_semantics():
    """Counters/histograms add; gauges take the freshest write; missing
    metrics are adopted as independent copies."""
    a, b = obs.Registry(), obs.Registry()
    a.counter("c_total").inc(2)
    b.counter("c_total").inc(5)
    ha = a.histogram("h", buckets=(1.0, 2.0))
    hb = b.histogram("h", buckets=(1.0, 2.0))
    ha.observe(0.5)
    hb.observe(1.5)
    hb.observe(10.0)
    a.gauge("g").set(1.0)
    gb = b.gauge("g")
    gb.set(9.0)
    gb.set(2.0)  # b wrote twice → fresher than a's single write
    b.counter("only_in_b_total").inc(3)

    a.merge(b)
    assert a.get("c_total").value == 7
    assert a.get("h").counts.tolist() == [1, 1, 1]
    assert a.get("h").sum == pytest.approx(12.0)
    assert a.get("g").value == 2.0
    assert a.get("only_in_b_total").value == 3
    # adoption copies — mutating the source must not alias
    b.counter("only_in_b_total").inc()
    assert a.get("only_in_b_total").value == 3

    # merged-in-both == observed-in-one: sufficient-statistic exactness
    c = obs.Registry()
    hc = c.histogram("h", buckets=(1.0, 2.0))
    for v in (0.5, 1.5, 10.0):
        hc.observe(v)
    assert hc.counts.tolist() == a.get("h").counts.tolist()

    with pytest.raises(ValueError):
        ha.merge_from(obs.Histogram("h", buckets=(1.0, 3.0)))


def test_gauge_repeated_merge_from_live_source():
    """Scrape-aggregator pattern: merging the SAME live registry
    repeatedly must keep tracking fresh gauge writes (seq must not
    inflate past the source's)."""
    host, agg = obs.Registry(), obs.Registry()
    g = host.gauge("occ")
    for v in (1.0, 2.0, 3.0):
        g.set(v)
        g.set(v * 10)  # two writes per cycle: seq grows faster than 1
        agg.merge(host)
        assert agg.get("occ").value == v * 10


def test_render_survives_non_finite_values():
    """A diverged-loss gauge must not kill the scrape endpoint."""
    r = obs.Registry()
    r.gauge("train_loss").set(float("nan"))
    r.gauge("g_inf").set(float("inf"))
    r.gauge("g_ninf").set(float("-inf"))
    text = obs.render(r)
    assert "train_loss NaN" in text
    assert "g_inf +Inf" in text and "g_ninf -Inf" in text


def test_snapshot_consistent_under_concurrent_merge():
    """Regression (ISSUE 6 satellite): a snapshot taken while a merge is
    in flight must be a consistent cut. ``merge`` mutates a histogram's
    ``counts`` then ``sum`` under the registry lock; ``snapshot`` now
    reads under the same lock, so it can never capture the counts of
    merge k and the sum of merge k-1. Every source observation is 1.0,
    so consistency is exactly ``sum == count`` in every snapshot. The
    bucket array is wide enough that the numpy ``counts +=`` releases
    the GIL — the lock-free-snapshot tear reproduces within ~1000
    merges on this shape, so this test genuinely detects a revert."""
    import threading

    src = obs.Registry()
    wide = tuple(float(x) for x in np.linspace(1e-3, 1e3, 100_000))
    hs = src.histogram("h_seconds", buckets=wide)
    hs.observe(1.0)
    src.counter("c_total").inc(1)

    dst = obs.Registry()
    stop = threading.Event()
    torn: list[dict] = []

    def snapshotter():
        while not stop.is_set():
            snap = dst.snapshot()
            h = snap.get("h_seconds")
            if h is not None and h["sum"] != float(h["count"]):
                torn.append(h)
                return

    t = threading.Thread(target=snapshotter)
    t.start()
    for _ in range(1500):
        dst.merge(src)
    stop.set()
    t.join()
    assert not torn, f"torn histogram snapshot: {torn[:1]}"
    assert dst.get("h_seconds").count == 1500
    assert dst.get("c_total").value == 1500.0


def test_registry_delta_semantics():
    """delta(snapshot) isolates an interval without reset():
    counters/histograms diff, gauges report current value, unchanged
    metrics are omitted, unseen metrics diff against zero."""
    r = obs.Registry()
    c = r.counter("c_total")
    c.inc(2)
    h = r.histogram("h_seconds", buckets=(1.0, 2.0))
    h.observe(0.5)
    g = r.gauge("g")
    g.set(1.0)
    r.counter("quiet_total").inc(7)  # untouched after the baseline

    snap = r.snapshot()
    c.inc(3)
    h.observe(1.5)
    h.observe(10.0)
    g.set(4.0)
    r.counter("born_later_total", x="1").inc()

    d = r.delta(snap)
    assert d["c_total"] == {"kind": "counter", "value": 3.0}
    assert d["h_seconds"]["counts"] == [0, 1, 1]
    assert d["h_seconds"]["count"] == 2
    assert d["h_seconds"]["sum"] == pytest.approx(11.5)
    assert d["g"] == {"kind": "gauge", "value": 4.0}
    assert d["born_later_total{x=1}"] == {"kind": "counter", "value": 1.0}
    assert "quiet_total" not in d
    # a quiet interval yields an empty delta
    assert r.delta(r.snapshot()) == {}
    # the live registry is untouched: no reset happened
    assert r.get("c_total").value == 5.0
    assert r.get("quiet_total").value == 7.0


def test_registry_delta_rejects_unrelated_baseline():
    """A baseline the live registry is BEHIND (reset() intervened, or it
    came from another registry) must raise, not emit negative rates."""
    r = obs.Registry()
    r.counter("c_total").inc(5)
    snap = r.snapshot()
    r.reset()
    r.counter("c_total").inc(1)
    with pytest.raises(ValueError, match="went down"):
        r.delta(snap)

    r2 = obs.Registry()
    r2.histogram("h_seconds", buckets=(1.0, 2.0)).observe(0.5)
    snap2 = r2.snapshot()
    r2.reset()
    with pytest.raises(ValueError, match="shrank"):
        r2.delta(snap2)

    r3 = obs.Registry()
    r3.gauge("x")
    with pytest.raises(ValueError, match="kind mismatch"):
        r3.delta({"x": {"kind": "counter", "value": 0.0}})


def test_delta_consistent_under_concurrent_merge():
    """Companion to the snapshot-tear regression above: ``delta`` reads
    the live table under the registry lock, so a delta taken while
    merges are in flight must also be a consistent cut — every source
    observation is 1.0, so consistency is exactly ``sum == count`` in
    every delta the reader computes."""
    import threading

    src = obs.Registry()
    wide = tuple(float(x) for x in np.linspace(1e-3, 1e3, 100_000))
    hs = src.histogram("h_seconds", buckets=wide)
    hs.observe(1.0)

    dst = obs.Registry()
    for _ in range(100):
        dst.merge(src)
    baseline = dst.snapshot()

    stop = threading.Event()
    torn: list[dict] = []

    def reader():
        while not stop.is_set():
            d = dst.delta(baseline)
            h = d.get("h_seconds")
            if h is not None and h["sum"] != float(h["count"]):
                torn.append(h)
                return

    t = threading.Thread(target=reader)
    t.start()
    for _ in range(1000):
        dst.merge(src)
    stop.set()
    t.join()
    assert not torn, f"torn histogram delta: {torn[:1]}"
    assert dst.delta(baseline)["h_seconds"]["count"] == 1000


def test_registry_reset_keeps_handles():
    r = obs.Registry()
    c, h, g = r.counter("c_total"), r.histogram("h"), r.gauge("g")
    c.inc(5)
    h.observe(0.1)
    g.set(4)
    r.reset()
    assert c.value == 0 and h.count == 0 and h.sum == 0 and g.value == 0
    c.inc()  # same handle still registered
    assert r.get("c_total").value == 1


# ---------------------------------------------------------------------------
# Prometheus render
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+0-9.eE]+$|^# (HELP|TYPE) .+$"
)


def test_render_format_and_invariants():
    r = obs.Registry()
    r.counter("req_total", "requests").inc(3)
    r.gauge("occ", "occupancy").set(0.5)
    h = r.histogram("lat_seconds", "latency", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.05, 0.5):
        h.observe(v)
    r.counter("fin_total", reason='we"ird\\label').inc()

    text = obs.render(r)
    lines = text.splitlines()
    assert text.endswith("\n")
    for line in lines:
        assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"
    assert "# TYPE req_total counter" in lines
    assert "# TYPE lat_seconds histogram" in lines
    assert "req_total 3" in lines
    # buckets are CUMULATIVE and end at +Inf == count
    cums = [
        int(m.group(1))
        for m in re.finditer(r'lat_seconds_bucket\{le="[^"]+"\} (\d+)', text)
    ]
    assert cums == sorted(cums) and cums[-1] == 4
    assert "lat_seconds_count 4" in lines
    # label escaping survives
    assert 'reason="we\\"ird\\\\label"' in text
    # HELP/TYPE emitted once per name even with label children
    assert text.count("# TYPE fin_total") == 1


def test_http_scrape_endpoint():
    r = obs.Registry()
    r.counter("hits_total").inc(2)
    server = obs.serve_http(r, port=0)
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert "hits_total 2" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=5
            )
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_tracer_nesting_and_registry_feed():
    """Spans nest by thread; the ring is their one record: a tracer takes
    no registry to mirror durations into (the engine's histograms observe
    its spans' durations itself)."""
    fake_t = [0.0]

    def clock():
        fake_t[0] += 1.0
        return fake_t[0]

    with pytest.raises(TypeError):
        obs.Tracer(registry=obs.Registry())
    tr = obs.Tracer(annotate=False, clock=clock)
    with tr.span("step") as outer:
        assert tr.current() is outer and outer.name == "step"
        with tr.span("prefill") as inner:
            assert tr.current() is inner and inner.name == "step.prefill"
    assert tr.current() is None
    # inner closes first; durations from the fake clock are exact
    assert [(s.name, s.parent, s.duration) for s in tr.events] == [
        ("step.prefill", outer.id, 1.0),
        ("step", None, 3.0),
    ]
    from distributed_tensorflow_tpu.obs import trace as trace_lib

    assert not hasattr(trace_lib, "SPAN_HISTOGRAM")


def test_tracer_records_on_exception_and_bounds_events():
    tr = obs.Tracer(annotate=False, max_events=2)
    with pytest.raises(RuntimeError):
        with tr.span("dies"):
            raise RuntimeError("boom")
    assert tr.events[-1].name == "dies"
    assert tr.events[-1].end >= tr.events[-1].start
    assert tr.current() is None  # stack unwound
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events) == 2 and tr.dropped == 4
    assert [s.name for s in tr.events] == ["s3", "s4"]  # the newest stay


def test_tracer_annotate_passthrough_smoke():
    """annotate=True must work whether or not a jax profiler trace is
    active (TraceAnnotation is a no-op outside an active trace)."""
    tr = obs.Tracer(annotate=True)
    with tr.span("annotated"):
        pass
    assert tr.events[-1].name == "annotated"


def test_tracer_ids_parents_keys_and_counts():
    """Ids are process-wide and rise in opening order; a span's parent is
    the span open on ITS thread; ``key`` and ``attrs`` are set at open or
    before close; nothing of a default tracer feeds a registry."""
    import threading

    a, b = obs.Tracer(annotate=False), obs.Tracer(annotate=False)
    with a.span("step", step=7) as outer:
        with b.span("other") as foreign:  # another tracer, another stack
            pass
        with a.span("prefill", key=41, q_tokens=32) as inner:
            inner.attrs["context"] = 96
        got = {}

        def worker():
            with a.span("side") as sp:
                got["span"] = sp

        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    assert outer.id < foreign.id < inner.id < got["span"].id
    assert foreign.parent is None and foreign.name == "other"
    assert (inner.parent, inner.key, inner.attrs) == (
        outer.id, 41, {"q_tokens": 32, "context": 96})
    assert (outer.parent, outer.key, outer.attrs) == (None, None, {"step": 7})
    # the other thread's span did not nest under this thread's open span
    assert got["span"].parent is None and got["span"].name == "side"
    assert [s.name for s in a.events] == ["step.prefill", "side", "step"]
    assert not hasattr(a, "registry") and a.dropped == 0


def test_tracer_record_takes_stamps_and_reads_no_clock():
    reads = []

    def clock():
        reads.append(1)
        return 100.0 + len(reads)

    tr = obs.Tracer(annotate=False, clock=clock)
    with tr.span("serve.step") as step:
        sp = tr.record("serve.request.queue", 3.0, 4.5, key=9, parent=None)
        child = tr.record("compile.backend", 101.25, 101.5, parent=step.id)
    assert len(reads) == 2  # the open span's two ends, nothing else
    assert (sp.name, sp.start, sp.end, sp.key, sp.parent) == (
        "serve.request.queue", 3.0, 4.5, 9, None)  # taken as given
    assert child.parent == step.id and child.name == "compile.backend"
    assert [s.id for s in tr.events] == [sp.id, child.id, step.id]


def test_tracer_dump_round_trip(tmp_path):
    import json

    tr = obs.Tracer(annotate=False, max_events=3)
    with tr.span("serve.step", queued=2) as step:
        with tr.span("prefill", key=5, q_tokens=8):
            pass
    tr.record("serve.request.decode", 1.0, 2.0, key=5, tokens=4)
    tr.record("odd", 2.0, 3.0, key=("not", "json"), what=object())
    path = tr.dump(str(tmp_path / "spans.jsonl"))
    lines = [json.loads(line) for line in open(path)]
    header, rows = lines[0], lines[1:]
    assert header["schema"] == "dtf-spans-1"
    assert (header["spans"], header["dropped"], header["capacity"]) == (3, 1, 3)
    assert set(header["clock_origin"]) == {"clock", "unix"}
    assert [r["name"] for r in rows] == [
        "serve.step", "serve.request.decode", "odd"]
    assert rows[0] == {"id": step.id, "parent": None, "name": "serve.step",
                       "start": step.start, "end": step.end, "key": None,
                       "attrs": {"queued": 2}}
    assert rows[1]["attrs"] == {"tokens": 4} and rows[1]["key"] == 5
    assert isinstance(rows[2]["attrs"]["what"], str)  # repr'd, not raised
    assert not (tmp_path / "spans.jsonl.tmp").exists()


def test_tracer_annotation_is_named_path_dot_id(monkeypatch):
    """The profiler's event carries the span's id: ``<path>.<id>``; a
    tracer with annotate=False makes none."""
    from distributed_tensorflow_tpu.obs import trace as trace_lib

    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(trace_lib.jax.profiler, "TraceAnnotation", Annotation)
    tr = obs.Tracer()
    with tr.span("serve.step") as outer:
        with tr.span("decode") as inner:
            pass
    assert seen == [("enter", f"serve.step.{outer.id}"),
                    ("enter", f"serve.step.decode.{inner.id}"),
                    ("exit", f"serve.step.decode.{inner.id}"),
                    ("exit", f"serve.step.{outer.id}")]
    seen.clear()
    with obs.Tracer(annotate=False).span("quiet"):
        pass
    assert seen == []


def test_default_tracer_records_compiles_under_the_open_span():
    """The jax.monitoring listener, installed with the default tracer: a
    program compiled inside a span leaves compile.trace / .lower /
    .backend spans whose parent is that span; the traces of the jitted
    functions it calls (jnp.sin, jnp.where: each a jit of its own) nest in
    its trace and are not recorded again."""
    import jax
    import jax.numpy as jnp

    tr = obs.default_tracer()
    assert obs.default_tracer() is tr and not hasattr(tr, "registry")

    def f(x):
        return jnp.where(x > 2, jnp.sin(x), jnp.cos(x)) * 3 + 1

    x = jnp.arange(5.0)  # an eager op compiles a program of its own
    with tr.span("test.compile_here") as sp:
        jax.jit(f)(x).block_until_ready()
    mine = [s for s in tr.events if s.parent == sp.id]
    assert sorted(s.name for s in mine if s.start >= sp.start) == [
        "compile.backend", "compile.lower", "compile.trace"]
    for s in mine:
        assert s.end <= sp.end and s.end >= s.start


def _gc_info(generation, collected=0, uncollectable=0):
    return {"generation": generation, "collected": collected,
            "uncollectable": uncollectable}


def _hooks():
    from distributed_tensorflow_tpu.obs import trace as trace_lib

    return [cb for cb in gc.callbacks if getattr(cb, "__qualname__", "")
            == trace_lib._collection_hook.__name__
            + ".<locals>.on_collection"]


def test_default_tracer_spans_a_full_collection_under_the_open_span():
    """`gc.collect()` inside a span of the default tracer leaves a
    `runtime.gc` span of generation 2 whose parent is that span, with the
    collector's own counts; the hook is installed once, however often the
    tracer is asked for."""
    tr = obs.default_tracer()
    for _ in range(3):
        assert obs.default_tracer() is tr
    assert len(_hooks()) == 1
    with tr.span("test.collect_here") as sp:
        gc.collect()
    mine = [s for s in tr.events if s.name == "runtime.gc"
            and s.parent == sp.id]
    assert len(mine) == 1
    got = mine[0]
    assert sp.start <= got.start <= got.end <= sp.end
    assert set(got.attrs) == {"generation", "collected", "uncollectable"}
    assert got.attrs["generation"] == 2
    assert all(type(got.attrs[k]) is int and got.attrs[k] >= 0
               for k in ("collected", "uncollectable"))
    assert got.key is None and got.id > sp.id


@pytest.mark.parametrize("generation,seconds,spanned", [
    (0, 0.5e-3, False),   # a young collection a step cannot feel
    (1, 0.999e-3, False),
    (0, 1e-3, True),      # GC_SPAN_MIN_S or more: felt
    (1, 4e-3, True),
    (2, 1e-6, True),      # every full collection
])
def test_collection_hook_spans_what_a_step_can_feel(
        monkeypatch, generation, seconds, spanned):
    """The hook driven directly, on a fake clock: a collection becomes a
    ring span when it is of generation 2 or lasts `GC_SPAN_MIN_S`; every
    collection is a profiler annotation `runtime.gc.<id>`, and a recorded
    one carries that id, the span open on the thread as parent and the
    counts of the stop phase."""
    from distributed_tensorflow_tpu.obs import trace as trace_lib

    assert trace_lib.GC_SPAN_MIN_S == 1e-3
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(trace_lib.jax.profiler, "TraceAnnotation", Annotation)
    now = [0.0]
    tr = obs.Tracer(clock=lambda: now[0])
    hook = trace_lib._collection_hook(tr)
    with tr.span("serve.step") as step:
        seen.clear()
        hook("start", _gc_info(generation))
        now[0] += seconds
        hook("stop", _gc_info(generation, collected=7, uncollectable=1))
        assert [k for k, _ in seen] == ["enter", "exit"]
    assert seen[0][1] == seen[1][1]
    name, _, span_id = seen[0][1].rpartition(".")
    assert name == "runtime.gc"
    gcs = [s for s in tr.events if s.name == "runtime.gc"]
    if not spanned:
        assert gcs == [] and [s.name for s in tr.events] == ["serve.step"]
        return
    (got,) = gcs
    assert (got.id, got.parent, got.key) == (int(span_id), step.id, None)
    assert (got.start, got.end) == (0.0, seconds)
    assert got.attrs == {"generation": generation, "collected": 7,
                         "uncollectable": 1}


def test_collection_hook_survives_a_full_ring_and_an_unopened_stop():
    """A full ring still records the collection and counts what it evicts;
    a stop whose start the hook never saw (installed mid-collection), a
    collection outside any span and a tracer that annotates nothing
    record without raising."""
    from distributed_tensorflow_tpu.obs import trace as trace_lib

    now = [0.0]
    tr = obs.Tracer(annotate=False, max_events=2, clock=lambda: now[0])
    hook = trace_lib._collection_hook(tr)
    hook("stop", _gc_info(2))  # no start seen
    assert list(tr.events) == []
    for name in ("a", "b"):
        with tr.span(name):
            pass
    for n in range(3):
        hook("start", _gc_info(2))
        now[0] += 0.25
        hook("stop", _gc_info(2, collected=n))
    assert len(tr.events) == 2 and tr.dropped == 3
    assert [(s.name, s.parent, s.attrs["collected"]) for s in tr.events] \
        == [("runtime.gc", None, 1), ("runtime.gc", None, 2)]


# ---------------------------------------------------------------------------
# JSONL export
# ---------------------------------------------------------------------------


def test_jsonl_logger_events_and_snapshot(tmp_path):
    r = obs.Registry()
    r.counter("c_total").inc(4)
    r.histogram("h", buckets=(1.0,)).observe(0.5)
    path = str(tmp_path / "events.jsonl")
    # single-process test rig: this process IS the chief, so chief_only
    # stays enabled — the gating path itself is exercised either way
    with obs.JsonlLogger(path, r, clock=lambda: 123.0) as jl:
        assert jl.enabled
        jl.event("admitted", uid=7)
        jl.write_snapshot(step=10)
    recs = [json.loads(line) for line in open(path)]
    assert [rec["event"] for rec in recs] == ["admitted", "snapshot"]
    assert recs[0] == {"t": 123.0, "event": "admitted", "uid": 7}
    snap = recs[1]["metrics"]
    assert snap["c_total"] == {"kind": "counter", "value": 4.0}
    assert snap["h"]["counts"] == [1, 0] and recs[1]["step"] == 10


def test_jsonl_logger_disabled_noop(tmp_path, monkeypatch):
    from distributed_tensorflow_tpu.parallel import cluster

    monkeypatch.setattr(cluster, "is_chief", lambda: False)
    path = str(tmp_path / "nothing.jsonl")
    with obs.JsonlLogger(path, obs.Registry()) as jl:
        assert not jl.enabled
        jl.event("dropped")
        jl.write_snapshot()
    import os

    assert not os.path.exists(path)


# ---------------------------------------------------------------------------
# Serve-engine telemetry (the ISSUE 2 acceptance gate)
# ---------------------------------------------------------------------------


def test_serve_engine_telemetry_counts_and_render():
    """A drained ServeEngine run yields non-empty TTFT / per-token
    histograms whose counts equal the finished-request count, and the
    registry renders valid Prometheus exposition."""
    from distributed_tensorflow_tpu import serve
    from distributed_tensorflow_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=64, max_len=48, num_layers=1, d_model=16, num_heads=2,
        d_ff=32, dropout=0.0, dtype="float32", causal=True, pre_ln=True,
    )
    reg = obs.Registry()
    eng = serve.ServeEngine.with_random_params(cfg, num_slots=2, registry=reg)
    assert eng.registry is reg
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10]]  # forces queueing
    for p in prompts:
        eng.submit(p, max_new_tokens=5)
    done = eng.run()
    assert len(done) == len(prompts)

    ttft = reg.get("serve_ttft_seconds")
    tpot = reg.get("serve_tpot_seconds")
    qwait = reg.get("serve_queue_wait_seconds")
    finished = sum(
        m.value for m in reg.collect() if m.name == "serve_finished_total"
    )
    assert ttft.count == len(prompts) and ttft.sum > 0
    assert tpot.count == len(prompts)
    assert qwait.count == len(prompts)
    assert int(finished) == len(prompts)
    assert reg.get("serve_finished_total",
                   reason="max_new_tokens").value == len(prompts)
    assert reg.get("serve_admitted_total").value == len(prompts)
    # every generated token was counted
    total_toks = sum(len(r.generated) for r in done.values())
    assert reg.get("serve_tokens_total").value == total_toks
    assert reg.get("serve_step_seconds").count > 0
    assert 0 < reg.get("serve_occupancy").value <= 1.0
    # TTFT >= queue wait for every request → also true of the sums
    assert ttft.sum >= qwait.sum

    text = obs.render(reg)
    assert "# TYPE serve_ttft_seconds histogram" in text
    assert 'serve_finished_total{reason="max_new_tokens"} 4' in text
    for line in text.splitlines():
        assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"


def test_serve_step_stats_timing_split():
    """The engine's histograms carry the step's prefill/decode wall split,
    from its spans' durations; registry reset drops warmup observations
    but keeps recording (the bench contract)."""
    from distributed_tensorflow_tpu import serve
    from distributed_tensorflow_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=64, max_len=48, num_layers=1, d_model=16, num_heads=2,
        d_ff=32, dropout=0.0, dtype="float32", causal=True, pre_ln=True,
    )
    eng = serve.ServeEngine.with_random_params(cfg, num_slots=2)
    eng.submit([1, 2, 3], max_new_tokens=3)
    first = eng.step()
    assert first.admitted == 1
    step, prefill, decode = (eng.registry.get(f"serve_{n}_seconds") for n in
                             ("step", "prefill", "decode"))
    assert step.count == prefill.count == decode.count == 1
    assert step.sum >= prefill.sum + decode.sum - 1e-6
    assert prefill.sum > 0 and decode.sum > 0
    spans = {s.name: s for s in eng.tracer.events
             if s.name in ("serve.step", "serve.step.decode")}
    assert step.sum == spans["serve.step"].duration
    assert decode.sum == spans["serve.step.decode"].duration

    eng.run()
    eng.registry.reset()
    assert eng.registry.get("serve_ttft_seconds").count == 0
    eng.submit([4, 5], max_new_tokens=2)
    eng.run()
    assert eng.registry.get("serve_ttft_seconds").count == 1
