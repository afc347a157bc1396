"""`decode_ahead_pct` (`benchmark/readers/serve_loop.py`): the share of the
traced decode steps the engine dispatched while earlier tokens were still
on the device. On a hand-built ring and trace, and on the ring of a tiny
engine run here: CPU only, counts and structure, no time of any device."""

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
from benchmark.readers import serve_loop  # noqa: E402

#: metric suffix -> its one cell. The hybrid and GigaChat cells' metrics do
#: not end in `.docs` / `.longdocs`: the family tests count those they own
CELLS = {"complete": "gpt2xl_serve_complete_v2_r80",
         "docs_r80": "olmohybrid_serve_docs_r80",
         "longdocs_r80": "gigachat35_serve_longdocs_r80"}


def ahead_ring(flags=(1, 1, 0, 1)):
    """Decode steps inside the device's window (1000 to 5000 us), one a
    flag; a decode span that dispatched nothing (it only fetched); one
    that ends after the window and one before the trace began."""
    ring, lo = [], 1100
    for n, flag in enumerate(flags):
        i = 100 + 10 * n
        ring += [t.span(i, None, "serve.step", lo, lo + 700),
                 t.span(i + 1, i, "serve.step.decode", lo + 100, lo + 700,
                        slots=4, kv_tokens=40, ahead=flag),
                 t.span(i + 2, i + 1, "serve.step.decode.fetch", lo + 300,
                        lo + 600)]
        lo += 700
    ring += [
        t.span(200, None, "serve.step.decode", lo, lo + 200),
        t.span(210, None, "serve.step.decode", 4900, 5200, slots=2,
               kv_tokens=20, ahead=0),
        t.span(220, None, "serve.step.decode", -900, -100, slots=2,
               kv_tokens=20, ahead=0),
    ]
    return ring


def spec_of(suffix):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           f"decode_ahead_pct.{suffix}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("flags,want", [
    ((1, 1, 0, 1), 75.0), ((1, 1, 1, 1), 100.0), ((0, 0, 0, 0), 0.0),
    ((0, 1), 50.0)])
def test_share_of_the_traced_dispatches_that_went_ahead(flags, want):
    got = serve_loop.decode_ahead_pct(t.hand_ctx(ring=ahead_ring(flags)))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("offset_ns", [0.0, -3.25e14])
def test_the_clocks_offset_moves_nothing(offset_ns):
    ctx = t.hand_ctx(offset_ns=offset_ns, ring=ahead_ring())
    assert serve_loop.decode_ahead_pct(ctx) == pytest.approx(75.0)


def test_a_program_without_the_attribute_reads_nothing():
    """The parent's decode spans carry no `ahead`: nothing, never 0."""
    bare = ahead_ring()
    for s in bare:
        s.attrs.pop("ahead", None)
    no_device = t.hand_ctx(ring=ahead_ring())
    no_device["trace"].devices.clear()
    for ctx in (t.hand_ctx(ring=bare), t.hand_ctx(ring=[]), no_device,
                {**t.hand_ctx(ring=ahead_ring()), "trace": None}):
        assert serve_loop.decode_ahead_pct(ctx) is None


def test_each_serving_cell_reads_its_own_through_the_harness():
    """The three files and their manifest entries agree key for key, are
    the last three entries, and the harness gives each serving cell its
    own metric and the training cell none."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = manifest["per_layer"][-3:]
    assert [m["name"] for m in entries] == [
        f"decode_ahead_pct.{s}" for s in CELLS]
    for m, suffix in zip(entries, CELLS):
        spec = spec_of(suffix)
        assert {k: spec[k] for k in m} == m
        assert set(spec) - set(m) == {"reader", "args"}
        assert m["workloads"] == [CELLS[suffix]] and m["unit"] == "%"
        assert m["layer"] == "serve loop"
        assert m["moves"] == ("tpot_p95_ms" if suffix == "complete"
                              else "serve_tokens_per_s")
    only = {"per_layer": entries}
    ctx = t.hand_ctx(ring=ahead_ring())
    for suffix, cell in CELLS.items():
        got = harness.per_layer_metrics(only, cell, ctx)
        assert got == {f"decode_ahead_pct.{suffix}": {
            "value": pytest.approx(75.0), "unit": "%"}}
    assert harness.per_layer_metrics(only, "gpt2m_train_1k", ctx) == {}


def test_an_engine_runs_ahead_in_its_steady_state():
    """A tiny paged engine's own ring, a trace built around its decode
    spans: every decode call after the first of a busy stretch was sent
    with earlier tokens still on the device; a speculative engine reads
    0."""
    import jax

    from distributed_tensorflow_tpu import obs, serve
    from distributed_tensorflow_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=64, max_len=64, num_layers=1, d_model=16, num_heads=2,
        d_ff=32, dropout=0.0, dtype="float32", causal=True, pre_ln=True)
    params, _ = tfm.make_init_fn(tfm.Transformer(cfg), 8)(
        jax.random.PRNGKey(0))
    got = {}
    for spec_k in (0, 2):
        tracer = obs.Tracer(annotate=False)
        eng = serve.ServeEngine(cfg, params, num_slots=2, block_size=8,
                                prefill_chunk=8, spec_k=spec_k,
                                tracer=tracer)
        for i in range(3):
            eng.submit([3 + i, 9, 4], max_new_tokens=12)
        eng.run()
        ring = list(tracer.events)
        decodes = [s for s in ring if s.name == "serve.step.decode"]
        lo = min(s.start for s in ring) - 1e-3
        hi = max(s.end for s in ring) + 1e-3
        off = 2.5e12
        trace = trace_reduce.Trace(
            {"/device:TPU:0": {"ops": [
                ("fusion.1", int(1e9 * lo + off), int(1e9 * hi + off))],
                "modules": []}},
            [(f"{s.name}.{s.id}", int(1e9 * s.start + off),
              int(1e9 * s.end + off) + 1) for s in decodes])
        got[spec_k] = serve_loop.decode_ahead_pct(
            {"trace": trace, "spans": ring})
        flags = [s.attrs["ahead"] for s in decodes if "ahead" in s.attrs]
        assert got[spec_k] == pytest.approx(100.0 * sum(flags) / len(flags))
    assert got[0] > 80.0 and got[2] == 0.0
