"""The olmo_hybrid family on the benchmark's side: its files are found by
`model_type`, its counts agree with a count by hand, a toy serving run
through the driver reads `correct` and an altered token does not, and the
gated-delta readers read a hand-built trace and ring (and nothing where
there is nothing). CPU only, toy sizes."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)
BENCH = os.path.join(ROOT, "benchmark")

from benchmark import families, harness, reference, trace_reduce  # noqa: E402
from benchmark.readers import gated_delta as reader  # noqa: E402

CELL = "olmohybrid_serve_docs_r80"
LINEAR, FULL = "linear_attention", "full_attention"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


CFG = load(BENCH, "configs", "olmo-hybrid-7b.json")
MANIFEST = load(ROOT, "BENCHMARK.json")


# ---------------------------------------------------------------------------
# files and counts
# ---------------------------------------------------------------------------


def test_the_familys_files_are_found_by_model_type():
    assert CFG["model_type"] == "olmo_hybrid"
    assert reference.for_config(CFG).__name__.endswith("olmo_hybrid")
    assert families.adapter(CFG).engine_args(CFG)["num_state_snapshots"] == 32
    assert families.counts(CFG).cache_layers(CFG) == 4
    files = harness.load_cell(CELL)
    assert files["config"] == CFG and files["traffic"]["kind"] == "open_loop"
    # the reference imports nothing of the program
    with open(os.path.join(BENCH, "reference", "olmo_hybrid.py")) as f:
        assert "distributed_tensorflow_tpu" not in f.read()


def test_configuration_keeps_every_published_width():
    """The cut is depth alone: `reduced` names the layer count and the
    list of kinds; every width, head count and the vocabulary are the
    published ones (the catalog's row)."""
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "olmo-hybrid-7b")
    assert sorted(entry["reduced"]) == ["layer_types", "num_hidden_layers"]
    published = {
        "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "max_position_embeddings": 65536, "rms_norm_eps": 1e-06,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "hidden_act": "silu", "attention_bias": False,
        "tie_word_embeddings": False, "rope_parameters": {"rope_theta": None}}
    assert {k: CFG[k] for k in published} == published
    assert CFG["layer_types"] == ([LINEAR] * 3 + [FULL]) * 4
    assert CFG["num_hidden_layers"] == 16


def test_counts_by_hand():
    counts = families.counts(CFG)
    d, f, V = 3840, 11008, 100352
    mlp = 3 * d * f
    linear = d * (2880 + 2880 + 5760 + 5760) + 5760 * d + 2 * d * 30 + mlp
    full = 4 * d * d + mlp
    assert counts.layer_matmul_params(CFG) == (linear, full)
    small_linear = 4 * (2880 + 2880 + 5760) + 2 * 30 + 192 + 2 * d
    want = 12 * (linear + small_linear) + 4 * (full + 4 * d) + 2 * V * d + d
    assert counts.param_count(CFG) == want
    assert abs(want - 4.10e9) < 0.005e9            # 4.10 B at the cut
    assert counts.kv_bytes_per_token(CFG) == 15360
    assert counts.attention_shape(CFG) == (30, 128)
    # one chunk of 256 tokens in one linear layer: 6 dk dv a token and head;
    # q, k, v, o in bfloat16 and the state of one slot read and written
    flops, byts = counts.gated_delta_work(CFG, 256, 1, 1)
    assert flops == 6 * 96 * 192 * 30 * 256
    assert byts == 256 * 2 * (2880 + 2880 + 5760 + 5760) + 2 * 4 * 30 * 96 * 192
    # one decoded token: every layer's matmuls, the rule, the head, and
    # 4 x 3840 an attended key in each of the 4 full layers
    got = counts.forward_flops(CFG, 1, 1000, 1)
    want = (12 * (2 * linear + 6 * 96 * 192 * 30) + 4 * 2 * full
            + 4 * 4 * d * 1000 + 2 * V * d)
    assert got == pytest.approx(want)


# ---------------------------------------------------------------------------
# a toy serving run through the driver
# ---------------------------------------------------------------------------


def tiny_config():
    cfg = dict(CFG)
    cfg.update(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        num_hidden_layers=8, layer_types=([LINEAR] * 3 + [FULL]) * 2,
        num_attention_heads=4, num_key_value_heads=4,
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=16,
        max_position_embeddings=512)
    cfg["serving"] = {"num_slots": 4, "block_size": 8, "num_blocks": 96,
                      "prefill_chunk": 16, "max_len": 256,
                      "num_state_snapshots": 4, "prefix_reuse": True,
                      "spec_k": 0, "temperature": 0.0,
                      "cache_dtype": "float32"}
    return cfg


def tiny_files():
    manifest = {"end_to_end": [dict(m, workloads=["tiny"])
                               for m in MANIFEST["end_to_end"]],
                "per_layer": []}
    mix = load(BENCH, "traffic", "docs_r80.json")
    mix.update(
        rate_per_s=8.0, vocab=500, drain_seconds=60, check_requests=6,
        prompt_tokens={"law": "lognormal", "median": 40, "sigma": 0.7,
                       "min": 16, "max": 120},
        output_tokens={"law": "lognormal", "median": 6, "sigma": 0.7,
                       "min": 2, "max": 16},
        shared_prefix={"count": 2, "tokens": 32, "share": 0.5,
                       "min_body": 8})
    return {"manifest": manifest, "cell": {"name": "tiny", "chips": 1},
            "config": tiny_config(), "traffic": mix}


#: bfloat16 operands at toy width against the float32 reference: the sound
#: program reads some hundredths; a token moved to its neighbour reads units
TOY_LIMIT = 0.5


@pytest.mark.parametrize("fault", [None, "token_altered"])
def test_serving_run_is_correct_and_an_altered_token_is_not(
        monkeypatch, devices, fault):
    from benchmark import serve_driver

    if fault:
        from distributed_tensorflow_tpu.serve import engine

        real = engine.sampling.sample
        monkeypatch.setattr(
            engine.sampling, "sample",
            lambda logits, *a, **kw: (real(logits, *a, **kw) + 1) % 500)
    out = serve_driver.run(tiny_files(), 2**31 + 7, 2.0, False, devices[:1],
                           {"served_gap_max": TOY_LIMIT})
    assert out["correct"] is (fault is None), out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 16
    assert {"serve_tokens_per_s", "tpot_p95_ms",
            "setup_s"} <= set(out["metrics"])
    assert {"ttft_p95_ms", "tpot_p95_ms"} <= set(out["window"])


def test_serving_control_in_lower_precision_reads_a_gap(devices):
    from benchmark import serve_driver, traffic

    files = tiny_files()
    cfg, mix = files["config"], files["traffic"]
    mix["check_requests"] = 16
    reqs = traffic.schedule(mix, 3, 4.0, mix["vocab"])
    done = [(i, [1 + i] * r.out_len) for i, r in enumerate(reqs)]
    ctl, _ = serve_driver.served_numbers(cfg, mix, 3, reqs, done,
                                         quant="int8")
    ref, _ = serve_driver.served_numbers(cfg, mix, 3, reqs, done)
    assert ctl["served_gap_max"]["value"] > 0
    assert ref["served_gap_max"]["value"] > TOY_LIMIT  # made-up tokens


# ---------------------------------------------------------------------------
# the readers on a hand-built trace and ring
# ---------------------------------------------------------------------------


class Span:
    def __init__(self, id, parent, name, start_us, end_us, **attrs):
        self.id, self.parent, self.name, self.key = id, parent, name, None
        self.start, self.end, self.attrs = start_us * 1e-6, end_us * 1e-6, attrs


def hand_case():
    """A traced window of 10 ms on one chip: one prefill chunk of 200
    tokens (run 1-4 ms, its kernel 0.3 ms in each of 12 layers -> one
    event here of 3.6 ms for short) and one decode step of 5 slots (run
    5-8 ms, kernel 1.2 ms); admissions before the trace matched 1024 + 512
    tokens and gave up 256."""
    ms = 1_000_000
    ops = [("gated_delta_chunk_fwd.1", 1.2 * ms, 1.5 * ms),
           ("fusion.1", 1.5 * ms, 4 * ms),
           ("gated_delta_step.3", 5 * ms, 6.2 * ms),
           ("fusion.2", 6.2 * ms, 8 * ms)]
    modules = [("jit_paged_prefill_chunk(1)", 1 * ms, 4 * ms),
               ("jit_paged_decode_step(2)", 5 * ms, 8 * ms)]
    ring = [
        Span(1, None, "compile.backend", -9000, -8000),
        Span(2, None, "serve.step.admit", -5000, -4990, matched_tokens=1024,
             trimmed_tokens=0),
        Span(3, None, "serve.step.admit", -3000, -2990, matched_tokens=512,
             trimmed_tokens=256),
        Span(4, None, "serve.step.admit", -2000, -1990),
        Span(5, None, "serve.step.prefill", 900, 4100, q_tokens=200,
             attended=200 * 201 // 2, context=200, table_blocks=2),
        Span(6, None, "serve.step.decode", 4900, 8100, slots=5,
             kv_tokens=5000, table_blocks=16, kv_positions_walked=32768),
    ]
    offset = 7.5e12
    host = [(f"{s.name}.{s.id}", 1e9 * s.start + offset,
             1e9 * s.end + offset) for s in ring if s.start > 0]
    shift = lambda ev: [(n, a + offset, b + offset) for n, a, b in ev]
    trace = trace_reduce.Trace(
        devices={0: {"ops": shift(ops), "modules": shift(modules)}},
        host=host)
    return {"trace": trace, "spans": ring, "cfg": CFG, "traffic": {},
            "chips": 1, "device_kind": "TPU v5 lite", "run": {}}


MODS = {"prefill_module": "jit_paged_prefill_chunk",
        "decode_module": "jit_paged_decode_step"}


def test_gated_delta_readers_on_a_hand_built_trace_and_ring():
    ctx = hand_case()
    counts = families.counts(CFG)
    # memory-bound on both: bytes over 819 GB/s, 12 layers
    _, byts = counts.gated_delta_work(CFG, 200, 1, 1)
    want = 100 * 12 * byts / 819e9 / 0.3e-3
    got = reader.chunk_roofline(ctx, "gated_delta_chunk_fwd", **MODS)
    assert got == pytest.approx(want, rel=1e-6)
    _, byts = counts.gated_delta_work(CFG, 5, 1, 5)
    want = 100 * 12 * byts / 819e9 / 1.2e-3
    got = reader.step_roofline(ctx, "gated_delta_step", **MODS)
    assert got == pytest.approx(want, rel=1e-6)
    busy = 2.8e-3 + 3e-3
    assert reader.device_pct(
        ctx, ["gated_delta_chunk_fwd", "gated_delta_step"]) == pytest.approx(
            100 * 1.5e-3 / busy, rel=1e-6)
    assert reader.snapshot_trim_pct(ctx) == pytest.approx(100 * 256 / 1536)


def test_every_docs_metric_finds_its_reader_and_its_arguments():
    import importlib
    import inspect

    ctx = hand_case()
    docs = [m for m in MANIFEST["per_layer"] if m["name"].endswith(".docs")]
    assert len(docs) == 18
    for m in docs:
        spec = load(BENCH, "metrics", f"{m['name']}.json")
        assert {k: spec[k] for k in m} == m and m["workloads"] == [CELL]
        module, _, func = spec["reader"].partition(":")
        fn = getattr(importlib.import_module(f"benchmark.readers.{module}"),
                     func)
        inspect.signature(fn).bind(ctx, **spec["args"])
    got = harness.per_layer_metrics(MANIFEST, CELL, ctx)
    # the cell is judged on tokens/s alone (PERF.md section 2: its tails
    # spread past half their bounds), so that is what each metric can name
    judged = [m["name"] for m in MANIFEST["end_to_end"]
              if CELL in m.get("workloads", [CELL])]
    assert judged == ["serve_tokens_per_s", "setup_s"]
    assert {m["moves"] for m in docs} == {"serve_tokens_per_s", "setup_s"}
    assert {"gated_delta_chunk_roofline.docs", "gated_delta_step_roofline.docs",
            "gated_delta_device_pct.docs", "state_snapshot_trim_pct.docs",
            "serve_step_mfu.docs", "paged_attention_fwd_roofline.docs"
            } - set(got) == {"paged_attention_fwd_roofline.docs"}  # no kernel


def test_gated_delta_readers_read_nothing_where_there_is_nothing():
    ctx = hand_case()
    # a program without the kernels (the parent commit): no events
    ctx["trace"].devices[0]["ops"] = [
        e for e in ctx["trace"].devices[0]["ops"]
        if not e[0].startswith("gated_delta")]
    assert reader.chunk_roofline(ctx, "gated_delta_chunk_fwd", **MODS) is None
    assert reader.step_roofline(ctx, "gated_delta_step", **MODS) is None
    assert reader.device_pct(
        ctx, ["gated_delta_chunk_fwd", "gated_delta_step"]) is None
    # a ring without the admission counts, no ring, no trace
    ctx["spans"] = [s for s in ctx["spans"] if "matched_tokens" not in s.attrs]
    assert reader.snapshot_trim_pct(ctx) is None
    for bare in ({"trace": None, "spans": [], "cfg": CFG, "run": {}},
                 {**hand_case(), "spans": []}):
        assert reader.snapshot_trim_pct(bare) is None
        assert reader.chunk_roofline(bare, "gated_delta_chunk_fwd",
                                     **MODS) is None
    assert reader.device_pct({"trace": None}, ["gated_delta_step"]) is None
    # a family without such layers
    gpt2 = {**hand_case(), "cfg": load(BENCH, "configs", "gpt2-xl.json")}
    assert reader.step_roofline(gpt2, "gated_delta_step", **MODS) is None


# ---------------------------------------------------------------------------
# this family's eighteen entries stand where PR 27 appended them: straight
# after PR 24's seven span metrics (`test_span_readers.py` finds those by
# name and holds them), whatever later PRs append after the eighteen
# ---------------------------------------------------------------------------


def test_the_docs_metrics_follow_the_seven_span_metrics():
    import test_span_readers as t

    at, seven = t.the_seven(MANIFEST)
    assert [m["name"] for m in seven] == list(t.SEVEN)
    mine = MANIFEST["per_layer"][at + 7:at + 7 + 18]
    assert all(m["name"].endswith(".docs") for m in mine)
    for m in mine:
        spec = t.spec_of(m["name"])
        assert {k: spec[k] for k in m} == m
        assert m["workloads"] == ["olmohybrid_serve_docs_r80"]
