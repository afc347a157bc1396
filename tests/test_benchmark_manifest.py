"""What two tests under `tests/benchmark` held of `BENCHMARK.json`, bar a
premise about positions that a later appended entry ends (only a
`benchmark` PR may edit a file there; `pytest.ini` deselects the two by
name and says why), and this PR's one new per-layer metric on a hand-built
trace. CPU only; no time of any device."""

import importlib
import inspect
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests", "benchmark")):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark import harness, trace_reduce  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

#: the latent and expert cell's metrics, appended in this order
LONGDOCS = [f"{name}.longdocs" for name in (
    "moe_grouped_mm_roofline", "latent_attention_roofline",
    "moe_tokens_per_expert", "moe_grouped_mm_device_pct",
    "gated_delta_step_roofline", "gated_delta_chunk_roofline",
    "serve_step_mfu", "decode_step_device_ms", "prefill_chunk_device_ms",
    "device_idle_pct", "prefix_hit_pct", "pool_live_peak_pct",
    "state_snapshot_trim_pct", "serve_step_host_ms", "setup_trace_lower_s",
    "setup_backend_compile_s", "gated_delta_device_pct", "ttft_p50_ms",
    "tpot_p50_ms", "serve_queue_wait_p95_ms")]


def test_the_seven_span_metrics_and_what_was_appended_after_them():
    """PR 24's seven span metrics stand together and in order, find their
    readers and read the hand-built ring as they did; after them come only
    entries that later PRs appended, in the order they came: the eighteen
    `.docs` metrics of the hybrid cell, then `pool_copy_device_pct.complete`,
    then the twenty `.longdocs` metrics of the latent and expert cell."""
    import test_span_readers as t

    cells = {"train_input_wait_ms": [t.TRAIN],
             "train_loop_host_ms": [t.TRAIN],
             "serve_step_host_ms.complete": [t.SERVE],
             "serve_prefill_phase_p50_ms.complete": [t.SERVE],
             "decode_kv_useful_pct.complete": [t.SERVE],
             "setup_trace_lower_s": [t.TRAIN, t.SERVE],
             "setup_backend_compile_s": [t.TRAIN, t.SERVE]}
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("train_input_wait_ms")
    seven = MANIFEST["per_layer"][at:at + 7]
    assert [m["name"] for m in seven] == list(cells)   # together, in order
    after = names[at + 7:]
    assert all(n.endswith(".docs") for n in after[:18])
    assert after[18] == "pool_copy_device_pct.complete"
    assert after[19:] == LONGDOCS
    for m in seven:
        spec = t.spec_of(m["name"])
        assert {k: spec[k] for k in m} == m
        assert m["workloads"] == cells[m["name"]]
        module, _, func = spec["reader"].partition(":")
        fn = getattr(importlib.import_module(f"benchmark.readers.{module}"),
                     func)
        inspect.signature(fn).bind({}, **spec["args"])
    for cell, n in ((t.TRAIN, 4), (t.SERVE, 5)):
        got = harness.per_layer_metrics({"per_layer": seven}, cell,
                                        t.hand_ctx())
        assert len(got) == n and all(
            got[k]["value"] == pytest.approx(t.EXPECTED[k]) for k in got)


def test_pool_copy_share_counts_each_copy_operation_once():
    """`pool_copy_device_pct.complete`, through the harness as a traced run
    reads it: the seconds of the operations that XLA moves the K/V pool
    with (and whatever else it copies) over the device's busy seconds.
    `op_seconds` matches at the start of a name, so `copy` takes
    `copy-done` and `copy-start` too: the file may not name them again. A
    trace without any of them reads nothing, never 0; the manifest's entry
    and the file agree key for key."""
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "pool_copy_device_pct.complete.json")) as f:
        spec = json.load(f)
    entry = [m for m in MANIFEST["per_layer"] if m["name"] == spec["name"]]
    assert entry == [{k: spec[k] for k in entry[0]}]
    # appended, not inserted: only the later cell's entries come after it
    at = MANIFEST["per_layer"].index(entry[0])
    assert [m["name"] for m in MANIFEST["per_layer"][at + 1:]] == LONGDOCS
    assert set(spec) - set(entry[0]) == {"reader", "args"}
    names = spec["args"]["kernels"]
    assert len(names) == len(set(names)) and not any(
        a != b and a.startswith(b) for a in names for b in names)
    us = 1000
    ops = [("copy.7", 0, 20 * us), ("copy-done.3", 20 * us, 23 * us),
           ("constant_dynamic-update-slice_fusion.1", 23 * us, 27 * us),
           ("slice-done.2", 27 * us, 28 * us), ("fusion.9", 30 * us, 34 * us),
           ("paged_kv_write.4", 34 * us, 35 * us),
           ("dynamic-update-slice_fusion", 35 * us, 37 * us)]
    mods = [("jit_paged_decode_step(2)", 0, 40 * us)]
    ctx = {"trace": trace_reduce.Trace(
        {"/device:TPU:0": {"ops": ops, "modules": mods}}, []),
        "cfg": {}, "traffic": {}, "chips": 1, "device_kind": "TPU v5 lite",
        "run": {}}
    only = {"per_layer": entry}
    cell = "gpt2xl_serve_complete_v2_r80"
    got = harness.per_layer_metrics(only, cell, ctx)
    assert got[spec["name"]] == {
        "value": pytest.approx(100 * 28 / 35), "unit": "%"}
    assert harness.per_layer_metrics(only, "gpt2m_train_1k", ctx) == {}
    ctx["trace"] = trace_reduce.Trace(
        {"/device:TPU:0": {"ops": ops[4:], "modules": mods}}, [])
    assert harness.per_layer_metrics(only, cell, ctx) == {}
