"""The gigachat3_5 family on the benchmark's side: its files are found by
`model_type`, the configuration is the catalog's with only the cut's keys
changed, its counts agree with a count by hand and with the program's
parameter tree, a toy serving run through the driver reads `correct` and an
altered token does not, and the expert and latent readers read a hand-built
trace and ring (and nothing where there is nothing). CPU only, toy sizes."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)
BENCH = os.path.join(ROOT, "benchmark")

from benchmark import families, harness, reference, trace_reduce  # noqa: E402
from benchmark.readers import experts as reader  # noqa: E402

CELL = "gigachat35_serve_longdocs_r80"
#: the published config's widths and counts per token, which no cut may touch
PUBLISHED_WIDTHS = {
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_attention_heads": 64,
    "num_key_value_heads": 64, "n_shared_experts": 1,
    "num_experts_per_tok": 8, "routed_scaling_factor": 2.5,
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_head_dim": 192,
    "linear_num_key_heads": 32, "linear_num_value_heads": 64,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "swiglu_limit": 10}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


CFG = load(BENCH, "configs", "gigachat3.5-432b-a28b.json")
MANIFEST = load(ROOT, "BENCHMARK.json")
#: the cut: what `reduced` names, and what each stands at
CUT = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
       "full_attention_layers": [1], "n_routed_experts": 16,
       "vocab_size": 16032, "num_nextn_predict_layers": 0}


# ---------------------------------------------------------------------------
# files, configuration and counts
# ---------------------------------------------------------------------------


def test_the_familys_files_are_found_by_model_type():
    assert CFG["model_type"] == "gigachat3_5"
    assert reference.for_config(CFG).__name__.endswith("gigachat3_5")
    assert families.adapter(CFG).engine_args(CFG)["num_slots"] == 32
    assert families.counts(CFG).cache_layers(CFG) == 1
    files = harness.load_cell(CELL)
    assert files["config"] == CFG and files["traffic"]["kind"] == "open_loop"
    with open(os.path.join(BENCH, "reference", "gigachat3_5.py")) as f:
        assert "distributed_tensorflow_tpu" not in f.read()


def test_the_cut_changes_only_what_reduced_names():
    """Every key of the published config is in the file at its published
    value but the six the cut changes, which `reduced` names (in the
    manifest too), and `published` keeps their published values."""
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "gigachat3.5-432b-a28b")
    assert entry["reduced"] == CFG["reduced"] == list(CUT)
    assert {k: CFG[k] for k in CUT} == CUT
    assert CFG["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 3,
        "full_attention_layers": list(range(3, 40, 4)),
        "n_routed_experts": 256, "vocab_size": 128256,
        "num_nextn_predict_layers": 2}
    assert CFG["expert_share"] == {"router_experts": 256, "first_held": 0}
    assert CFG["source"] == entry["source"]
    assert {k: CFG[k] for k in PUBLISHED_WIDTHS} == PUBLISHED_WIDTHS


def test_counts_by_hand():
    counts = families.counts(CFG)
    d, H, V = 7168, 64, 16032
    linear = d * (4096 + 4096 + 8192 + 8192 + 2 * 64) + 8192 * d
    latent = (d * 1536 + 1536 * H * 192 + d * 576 + 512 * H * 256
              + 2 * d * H * 128)
    assert counts.mixer_matmul_params(CFG) == (linear, latent)
    expert = 3 * d * 2048
    want = (4 * (linear + 4 * (4096 + 4096 + 8192) + 2 * 64 + 128 + 2 * d)
            + (latent + 1536 + 512 + 2 * d) + (3 * d * 18432 + 2 * d)
            + 4 * (d * 256 + 256 + 17 * expert + 2 * d) + 2 * V * d + d)
    assert counts.param_count(CFG) == want
    assert abs(want - 4.73e9) < 0.005e9            # 4.73 B at the cut
    assert counts.kv_bytes_per_token(CFG) == 1152
    # a chunk of 512 tokens of one linear layer: 6 dk dv a token and value
    # head; q, k (one a value head), v, o in bfloat16, one slot's state
    flops, byts = counts.gated_delta_work(CFG, 512, 1, 1)
    assert flops == 6 * 128 * 128 * 64 * 512
    assert byts == 512 * 2 * 4 * 8192 + 2 * 4 * 64 * 128 * 128
    # 10 attended pairs, 3 query tokens, 20 cached rows read
    flops, byts = counts.latent_attention_work(CFG, 3, 10, 20)
    assert flops == 2 * 64 * (576 + 512) * 10
    assert byts == 2 * 576 * 20 + 3 * 64 * (2 * 576 + 4 * 512)
    # 100 assignments over 7 expert calls
    flops, byts = counts.moe_work(CFG, 100, 7)
    assert flops == 2 * expert * 100
    assert byts == 2 * expert * 7 + 100 * (2 * d + 4 * 2048 + 4 * d)


def test_param_count_is_the_programs_tree():
    """The family's count against the program's parameter tree at the
    cut's widths (shapes only: nothing is made)."""
    from distributed_tensorflow_tpu.models import gigachat3_5 as gc

    tree = gc.param_shapes(families.adapter(CFG).model_config(CFG))
    import jax

    n = sum(x.size for x in jax.tree.leaves(tree))
    assert n == families.counts(CFG).param_count(CFG)


# ---------------------------------------------------------------------------
# a toy serving run through the driver
# ---------------------------------------------------------------------------


def tiny_config():
    cfg = dict(CFG)
    cfg.update(
        hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_rope_head_dim=8, qk_nope_head_dim=16,
        v_head_dim=16, qk_head_dim=24, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, n_routed_experts=4, num_experts_per_tok=4,
        vocab_size=500, max_position_embeddings=512,
        expert_share={"router_experts": 16, "first_held": 0})
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
    mix = load(BENCH, "traffic", "longdocs_r80.json")
    mix.update(
        rate_per_s=8.0, vocab=500, check_requests=6,
        prompt_tokens={"law": "lognormal", "median": 40, "sigma": 0.7,
                       "min": 16, "max": 120},
        output_tokens={"law": "lognormal", "median": 6, "sigma": 0.7,
                       "min": 2, "max": 16},
        shared_prefix={"count": 2, "tokens": 32, "share": 0.5,
                       "min_body": 8})
    return {"manifest": manifest, "cell": {"name": "tiny", "chips": 1},
            "config": tiny_config(), "traffic": mix}


#: bfloat16 operands at toy width against the float32 reference: the sound
#: program reads some hundredths on the mean; a token moved to its
#: neighbour reads units
TOY_LIMIT = 0.3


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
                           {"served_gap_mean": TOY_LIMIT})
    assert out["correct"] is (fault is None), out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 16
    assert {"serve_tokens_per_s", "setup_s"} <= set(out["metrics"])


# ---------------------------------------------------------------------------
# the readers on a hand-built trace and ring
# ---------------------------------------------------------------------------


class Span:
    def __init__(self, id, parent, name, start_us, end_us, **attrs):
        self.id, self.parent, self.name, self.key = id, parent, name, None
        self.start, self.end, self.attrs = start_us * 1e-6, end_us * 1e-6, attrs


def hand_case():
    """A traced window of 10 ms on one chip: one prefill chunk of 512 tokens
    at 1024 tokens of context (run 1-4 ms; latent kernel 0.5 ms, grouped
    matmuls 1.5 ms) and one decode step of 8 slots (run 5-8 ms; latent
    kernel 0.2 ms, grouped matmuls 1 ms); their fetches read 260 local
    assignments over 6 calls of a held expert."""
    ms = 1_000_000
    ops = [("paged_latent_attention.1", 1.2 * ms, 1.7 * ms),
           ("moe_grouped_mm.2", 2 * ms, 3.5 * ms),
           ("fusion.1", 3.5 * ms, 4 * ms),
           ("paged_latent_attention.3", 5 * ms, 5.2 * ms),
           ("moe_grouped_mm.4", 5.5 * ms, 6.5 * ms),
           ("fusion.2", 6.5 * ms, 8 * ms)]
    modules = [("jit_paged_prefill_chunk(1)", 1 * ms, 4 * ms),
               ("jit_paged_decode_step(2)", 5 * ms, 8 * ms)]
    ring = [
        Span(5, None, "serve.step.prefill", 900, 4100, q_tokens=512,
             attended=512 * 1024 + 512 * 513 // 2, context=1536,
             table_blocks=16, moe_assignments=200, moe_expert_calls=4),
        Span(6, None, "serve.step.decode", 4900, 8100, slots=8,
             kv_tokens=8000, table_blocks=16, kv_positions_walked=8192,
             moe_assignments=60, moe_expert_calls=2),
    ]
    offset = 7.5e12
    host = [(f"{s.name}.{s.id}", 1e9 * s.start + offset,
             1e9 * s.end + offset) for s in ring]
    shift = lambda ev: [(n, a + offset, b + offset) for n, a, b in ev]
    trace = trace_reduce.Trace(
        devices={0: {"ops": shift(ops), "modules": shift(modules)}},
        host=host)
    return {"trace": trace, "spans": ring, "cfg": CFG, "traffic": {},
            "chips": 1, "device_kind": "TPU v5 lite", "run": {}}


MODS = {"prefill_module": "jit_paged_prefill_chunk",
        "decode_module": "jit_paged_decode_step"}


def test_expert_and_latent_readers_on_a_hand_built_trace_and_ring():
    ctx = hand_case()
    counts = families.counts(CFG)
    assert reader.expert_counts(ctx) == (260, 6)
    assert reader.tokens_per_expert(ctx) == pytest.approx(260 / 6)
    # the weights of 6 expert calls dominate: memory-bound
    flops, byts = counts.moe_work(CFG, 260, 6)
    assert byts / 819e9 > flops / 197e12
    want = 100 * max(flops / 197e12, byts / 819e9) / 2.5e-3
    assert reader.grouped_mm_roofline(ctx, "moe_grouped_mm") == pytest.approx(
        want, rel=1e-6)
    flops, byts = counts.latent_attention_work(
        CFG, 520, 512 * 1024 + 512 * 513 // 2 + 8000, 1536 + 8000)
    want = 100 * max(flops / 197e12, byts / 819e9) / 0.7e-3
    got = reader.latent_roofline(ctx, "paged_latent_attention", **MODS)
    assert got == pytest.approx(want, rel=1e-6)
    assert 0 < got <= 100 and 0 < want


def test_expert_and_latent_readers_read_nothing_where_there_is_nothing():
    ctx = hand_case()
    # a program without the kernels (the parent commit): no events
    ctx["trace"].devices[0]["ops"] = [
        e for e in ctx["trace"].devices[0]["ops"] if e[0].startswith("fusion")]
    assert reader.grouped_mm_roofline(ctx, "moe_grouped_mm") is None
    assert reader.latent_roofline(ctx, "paged_latent_attention", **MODS) is None
    # a ring without the expert counts
    ctx = hand_case()
    for s in ctx["spans"]:
        s.attrs.pop("moe_assignments")
    assert reader.expert_counts(ctx) is None
    assert reader.tokens_per_expert(ctx) is None
    assert reader.grouped_mm_roofline(ctx, "moe_grouped_mm") is None
    # no trace; a family without the work functions
    assert reader.tokens_per_expert({"trace": None, "spans": []}) is None
    olmo = {**hand_case(), "cfg": load(BENCH, "configs", "olmo-hybrid-7b.json")}
    assert reader.grouped_mm_roofline(olmo, "moe_grouped_mm") is None
    assert reader.latent_roofline(olmo, "paged_latent_attention",
                                  **MODS) is None


def test_every_longdocs_metric_finds_its_reader_and_its_arguments():
    import importlib
    import inspect

    ctx = hand_case()
    mine = [m for m in MANIFEST["per_layer"]
            if m["name"].endswith(".longdocs")]
    assert len(mine) == 20
    for m in mine:
        spec = load(BENCH, "metrics", f"{m['name']}.json")
        assert {k: spec[k] for k in m} == m and m["workloads"] == [CELL]
        assert m["moves"] == ("setup_s" if m["name"].startswith("setup_")
                              else "serve_tokens_per_s")
        module, _, func = spec["reader"].partition(":")
        fn = getattr(importlib.import_module(f"benchmark.readers.{module}"),
                     func)
        inspect.signature(fn).bind(ctx, **spec["args"])
    got = harness.per_layer_metrics(MANIFEST, CELL, ctx)
    judged = [m["name"] for m in MANIFEST["end_to_end"]
              if CELL in m.get("workloads", [CELL])]
    assert judged == ["serve_tokens_per_s", "setup_s"]
    assert {"moe_grouped_mm_roofline.longdocs",
            "latent_attention_roofline.longdocs",
            "moe_tokens_per_expert.longdocs",
            "moe_grouped_mm_device_pct.longdocs",
            "serve_step_mfu.longdocs", "decode_step_device_ms.longdocs",
            "prefill_chunk_device_ms.longdocs",
            "device_idle_pct.longdocs"} <= set(got)
    for name in ("moe_grouped_mm_roofline.longdocs",
                 "latent_attention_roofline.longdocs"):
        assert 0 < got[name]["value"] <= 100


def test_the_cell_and_its_files():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gigachat3.5-432b-a28b", "longdocs_r80", 1)
    mix = load(BENCH, "traffic", "longdocs_r80.json")
    share = mix["shared_prefix"]
    assert share["tokens"] == 16384 and share["min_body"] == 128
    assert share["count"] == round(0.75 * mix["rate_per_s"] * 51 / 4)
    assert CFG["serving"]["num_state_snapshots"] >= share["count"] + 8
    assert "arrangement_seed" not in mix and mix["vocab"] == CFG["vocab_size"]
    limits = load(BENCH, "limits", f"{CELL}.json")
    assert set(limits["limits"]) == {"served_gap_mean"}
