"""The benchmark's own tests: CPU only, toy sizes, no TPU library loaded at
import. They check the yardstick (manifest rules, trace reduction, counts,
generator), that a run without the chip prints no result, and that the
comparison deciding `correct` passes the program and fails the control and
each planted fault."""

import copy
import glob
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmark")

from benchmark import check, counts, families, trace_reduce, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = load(ROOT, "BENCHMARK.json")


# ---------------------------------------------------------------------------
# the manifest's own rules
# ---------------------------------------------------------------------------


def test_manifest_names_units_and_bounds():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in names
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    used = set()
    for w in MANIFEST["workloads"]:
        cfg = configs[w["config"]]
        used.add(w["config"])
        body = load(ROOT, cfg["file"])
        assert sorted(body["reduced"]) == sorted(cfg["reduced"])
        mix = load(BENCH, "traffic", f"{w['traffic']}.json")
        assert mix["kind"] in ("train_job", "open_loop", "backlog")
        limits = load(BENCH, "limits", f"{w['name']}.json")["limits"]
        assert limits, "a cell compares at least one number"
    assert used == set(configs)


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    cells = {w["name"] for w in MANIFEST["workloads"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        spec = load(BENCH, "metrics", f"{m['name']}.json")
        for key in ("name", "unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert spec[key] == m[key], (m["name"], key)
        module, _, func = spec["reader"].partition(":")
        assert os.path.exists(os.path.join(BENCH, "readers", f"{module}.py"))
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        assert any(cell in m.get("workloads", cells)
                   for m in MANIFEST["per_layer"])


def test_only_the_family_files_know_gpt2_and_only_the_adapters_the_program():
    """The seam a later `model_config` PR stands on: a family is new files,
    found by `model_type`; the shared files know no family's key names, and
    only `program.py` and the adapters import the program."""
    gpt2 = re.compile(r"n_embd|n_head|n_layer|n_inner|gelu_new|\"gpt_lm\"")
    shared = glob.glob(os.path.join(BENCH, "*.py")) + glob.glob(
        os.path.join(BENCH, "readers", "*.py"))
    assert len(shared) > 15
    for path in shared:
        with open(path) as f:
            assert not gpt2.search(f.read()), path
    imports = re.compile(r"^\s*(from|import)\s+distributed_tensorflow_tpu",
                         re.M)
    # nor does anything reach into the engine or a jitted function
    private = re.compile(r"\b(eng|engine|trainer)\._\w|\._cache_size\b"
                         r"|call_records|class Calls")
    for path in glob.glob(os.path.join(BENCH, "**", "*.py"), recursive=True):
        rel = os.path.relpath(path, BENCH)
        with open(path) as f:
            text = f.read()
        adapter = re.fullmatch(r"families/[^/]+/adapter\.py", rel)
        if rel != "program.py" and not adapter:
            assert not imports.search(text), rel
        assert not private.search(text), rel
    # the annotations that repeated a span of the program are gone
    for name in ("bench.engine_step", "bench.put_batch",
                 "bench.step_dispatch", "bench.on_step_end"):
        for path in shared:
            with open(path) as f:
                assert name not in f.read(), (name, path)
    for cfg in MANIFEST["configs"]:
        body = load(ROOT, cfg["file"])
        for kind in ("adapter", "counts"):
            assert os.path.exists(os.path.join(
                BENCH, "families", body["model_type"], f"{kind}.py"))
        assert os.path.exists(os.path.join(
            BENCH, "reference", f"{body['model_type']}.py"))


# ---------------------------------------------------------------------------
# trace reduction on a hand-built trace
# ---------------------------------------------------------------------------


def hand_trace():
    """Two steps of 100 us on one chip. In each: a kernel 10..40, a fusion
    inside a while 50..70 (the while spans 45..75), and an all-reduce 60..90
    of which 75..90 no compute covers. The device idles 0..10, 40..45 and
    90..100 of each step; the host feeds input during the last gap."""
    ops, mods, host = [], [], []
    for k in (0, 1):
        o = 100_000 * k
        mods.append((f"jit_train_step({7 + k})", o, o + 100_000))
        ops += [(f"flash_attention_fwd.{k}", o + 10_000, o + 40_000),
                ("while.3", o + 45_000, o + 75_000),
                (f"fusion.{20 + k}", o + 50_000, o + 70_000),
                ("all-reduce.5", o + 60_000, o + 90_000)]
        host.append((f"train.step.put_batch.{31 + k}", o + 88_000,
                     o + 100_000))
        host.append((f"train.step.dispatch.{41 + k}", o + 0, o + 30_000_000))
    return trace_reduce.Trace({"/device:TPU:0": {"ops": ops, "modules": mods}},
                              host)


def ring_span(id, name, start_us, end_us, **attrs):
    """A span of the program's ring as the readers see one, on a clock that
    runs 3 s ahead of the trace's."""
    return types.SimpleNamespace(
        id=id, parent=None, name=name, start=3.0 + start_us * 1e-6,
        end=3.0 + end_us * 1e-6, key=None, attrs=attrs)


def with_host_events(ring, trace):
    """The ring, and ``trace`` with the host event `<name>.<id>` that each
    span leaves while the profiler runs (here: from the trace's first host
    event on)."""
    on = min((e[1] for e in trace.host), default=0)
    host = [(f"{s.name}.{s.id}", round(1e9 * (s.start - 3.0)),
             round(1e9 * (s.end - 3.0))) for s in ring
            if 1e9 * (s.start - 3.0) >= on]
    return ring, trace_reduce.Trace(trace.devices, trace.host + host)


def test_trace_reduction_busy_kernel_and_exposed_collective_time():
    t = hand_trace()
    assert trace_reduce.busy_seconds(t) == pytest.approx(2 * 75e-6)
    # first operation's start (10 us) to the last one's end (190 us)
    assert trace_reduce.window_seconds(t) == pytest.approx(180e-6)
    seconds, calls = trace_reduce.op_seconds(t, "flash_attention_fwd")
    assert (seconds, calls) == (pytest.approx(60e-6), 2)
    ms, runs = trace_reduce.busy_per_run_ms(t, "jit_train_step")
    assert (ms, runs) == (pytest.approx(0.075), 2)
    assert trace_reduce.busy_in_runs(t, "jit_other") == (None, 0)
    assert trace_reduce.exposed_collective_seconds(t) == pytest.approx(30e-6)
    top = dict(trace_reduce.top_ops(t))
    assert top["flash_attention_fwd"] == pytest.approx(60e-6)
    assert top["fusion"] == pytest.approx(40e-6)
    # the while is charged only what its body does not cover; the
    # all-reduce overlaps it without lying inside it and counts in full
    assert top["while"] == pytest.approx(2 * 10e-6)
    assert top["all-reduce"] == pytest.approx(2 * 30e-6)
    assert trace_reduce.top_modules(t) == [
        ["jit_train_step", 2, pytest.approx(200e-6)]]
    gaps = dict(trace_reduce.idle_gaps(t))
    assert gaps["train.step.put_batch"] == pytest.approx(20e-6)  # 90..100, 0..10
    one_chip = copy.deepcopy(t)
    for d in one_chip.devices.values():
        d["ops"] = [e for e in d["ops"] if not e[0].startswith("all-")]
    assert trace_reduce.exposed_collective_seconds(one_chip) is None


def idle_gaps_by_the_loop(trace, n=10):
    """`trace_reduce.idle_gaps` as it was before PR 33: for every gap a walk
    back over the host events that began before its middle. Quadratic (a
    traced window of the GPT-2 cell at 15 requests/s took 20 minutes to
    read), kept here as the reference of the sweep that replaced it."""
    busy = trace_reduce.union(trace_reduce._spans(
        next(iter(trace.devices.values()))["ops"]))
    gaps = trace_reduce.subtract([(busy[0][0], busy[-1][1])], busy)
    host = sorted(trace.host, key=lambda ev: ev[1])
    out = {}
    for s, e in gaps:
        mid = (s + e) / 2
        best = None
        for name, hs, he in reversed(host):
            if hs <= mid <= he and (best is None or he - hs < best[1]):
                best = (name, he - hs)
        name = trace_reduce.base_name(best[0]) if best else "unattributed"
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_idle_gaps_sweep_names_every_gap_as_the_loop_did(seed):
    """Random operations with gaps between them under random host events,
    nested, overlapping, of equal lengths and none at all: the one-pass
    sweep gives every gap to the same host event as the loop over all of
    them. An event longer than five seconds names no gap."""
    rng = np.random.default_rng(seed)
    edges = np.sort(rng.choice(200_000, 400, replace=False)) * 10
    ops = [(f"fusion.{i}", int(s), int(e))
           for i, (s, e) in enumerate(zip(edges[::2], edges[1::2]))]
    host = []
    for i in range(300):
        start = int(rng.integers(0, 2_000_000))
        length = int(rng.choice([20_000, 50_000, 50_000, 400_000]))
        host.append((f"serve.step.{rng.integers(4)}.{i}", start,
                     start + length))
    t = trace_reduce.Trace({"/device:TPU:0": {"ops": ops, "modules": []}},
                           host)
    got, want = trace_reduce.idle_gaps(t, n=99), idle_gaps_by_the_loop(t, 99)
    assert [k for k, _ in got] == [k for k, _ in want]
    assert [v for _, v in got] == pytest.approx([v for _, v in want])
    whole = trace_reduce.Trace(t.devices, host + [
        ("thread", -10, trace_reduce.MAX_HOST_NS + 10)])
    assert trace_reduce.idle_gaps(whole, n=99) == got


def test_readers_return_nothing_where_there_is_nothing_to_read():
    from benchmark.readers import device, mfu, rooflines, steps

    mods = {"prefill_module": "jit_paged_prefill_chunk",
            "decode_module": "jit_paged_decode_step"}
    ctx = {"trace": None, "run": {}, "cfg": {}, "traffic": {}, "chips": 1,
           "device_kind": "TPU v5 lite"}
    assert device.idle_pct(ctx) is None
    assert mfu.train_step(ctx, "jit_train_step") is None
    assert mfu.serve_step(ctx, **mods) is None
    assert steps.device_ms_per_run(ctx, "jit_train_step") is None
    assert rooflines.paged(ctx, "paged_attention_fwd", **mods) is None
    ctx["trace"] = hand_trace()
    # busy 150 us of the 180 us from the first operation to the last
    assert device.idle_pct(ctx) == pytest.approx(100 * 30 / 180)
    # a kernel the trace does not hold gives no share, never 0
    assert rooflines.flash(ctx, ["flash_attention_bwd_dkv"],
                           "flash_attention_bwd_dkv", "bwd") is None
    # a trace with no run of the serving programs gives no serving MFU,
    # whatever the ring holds
    ctx["cfg"] = load(BENCH, "configs", "gpt2-xl.json")
    ctx["spans"], ctx["trace"] = with_host_events(
        [ring_span(7, "serve.step.decode", 20, 60, slots=2, kv_tokens=30)],
        ctx["trace"])
    assert mfu.serve_step(ctx, **mods) is None


def test_mfu_takes_runs_and_seconds_from_the_trace():
    """Two traced steps of 75 us device time each: the host's clock and the
    host's count of steps enter nowhere."""
    from benchmark.readers import mfu

    cfg = load(BENCH, "configs", "gpt2-medium.json")
    job = load(BENCH, "traffic", "train_1k.json")
    ctx = {"trace": hand_trace(), "run": {}, "cfg": cfg, "traffic": job,
           "chips": 1, "device_kind": "TPU v5 lite"}
    flops = 2 * 8 * 1024 * families.counts(cfg).train_flops_per_token(
        cfg, 1024)
    assert mfu.train_step(ctx, "jit_train_step") == pytest.approx(
        100 * flops / (150e-6 * 197e12))
    # serving: the trace holds one decode run of 40 us busy and two prefill
    # runs of 10 us; the ring holds more steps than that (warm-up and the
    # window before the profiler, the wait after the close): the spans that
    # opened while the profiler ran and the device still worked are the runs
    xl = load(BENCH, "configs", "gpt2-xl.json")
    ops = [("paged_attention_fwd.1", 0, 10_000), ("fusion.2", 20_000, 30_000),
           ("paged_attention_fwd.3", 50_000, 90_000)]
    mods = [("jit_paged_prefill_chunk(1)", 0, 12_000),
            ("jit_paged_prefill_chunk(1)", 18_000, 31_000),
            ("jit_paged_decode_step(2)", 45_000, 95_000)]
    ring = [
        ring_span(1, "serve.step.prefill", -900, -800, q_tokens=32,
                  attended=528, context=32),
        ring_span(2, "serve.step.decode", -700, -100, slots=9, kv_tokens=999),
        ring_span(3, "serve.step.prefill", -5, 8, q_tokens=32, attended=528,
                  context=32),
        ring_span(4, "serve.step.prefill", 9, 16, q_tokens=8, attended=292,
                  context=40),
        ring_span(5, "serve.step.decode", 17, 96, slots=2, kv_tokens=300),
        ring_span(6, "serve.step.decode", 5000, 5100, slots=4, kv_tokens=77)]
    spans, trace = with_host_events(ring, trace_reduce.Trace(
        {"/device:TPU:0": {"ops": ops, "modules": mods}},
        [("ProfilerStart", -50_000, -40_000)]))
    ctx = {"trace": trace, "spans": spans, "run": {}, "cfg": xl,
           "traffic": {}, "chips": 1, "device_kind": "TPU v5 lite"}
    names = {"prefill_module": "jit_paged_prefill_chunk",
             "decode_module": "jit_paged_decode_step"}
    gpt2 = families.counts(xl)
    want = gpt2.forward_flops(xl, 32 + 8 + 2, 528 + 292 + 300, 2 + 2)
    assert mfu.serve_step(ctx, **names) == pytest.approx(
        100 * want / (60e-6 * 197e12))
    from benchmark.readers import rooflines
    byts = xl["n_layer"] * (32 + 40 + 300) * gpt2.kv_bytes_per_token(xl)
    assert rooflines.paged(ctx, "paged_attention_fwd", **names) == \
        pytest.approx(100 * (byts / 819e9) / 50e-6)
    # fewer spans than traced runs: nothing sound to read
    ctx["spans"] = [s for s in spans if s.id != 4]
    assert mfu.serve_step(ctx, **names) is None


def test_a_missing_or_misspelt_limit_is_an_error_not_a_pass():
    numbers = {"served_gap_max": {"value": 0.01}}
    assert check.judge(numbers, {"served_gap_max": 0.09})[0] is True
    assert check.judge(numbers, {"served_gap_max": 0.001})[0] is False
    for limits in ({}, {"served_gap": 0.09}, {"served_gap_max": None},
                   {"served_gap_max": 0.09, "other": 1.0}):
        with pytest.raises(KeyError):
            check.judge(numbers, limits)
    for cell in MANIFEST["workloads"]:
        limits = check.load_limits(cell["name"])
        assert limits and all(isinstance(v, float) for v in limits.values())


# ---------------------------------------------------------------------------
# counts against hand-worked numbers
# ---------------------------------------------------------------------------


def test_counts_gpt2_medium_and_one_decode_step():
    cfg = load(BENCH, "configs", "gpt2-medium.json")
    gpt2 = families.counts(cfg)
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) + 50304 x 1024 matmul parameters
    assert gpt2.matmul_params(cfg) == 24 * 12_582_912 + 51_511_296
    # 6 per matmul parameter + 3 x 24 x 2 x 1024 x 1024 causal attention
    per_token = gpt2.train_flops_per_token(cfg, 1024)
    assert per_token == 6 * 353_501_184 + 3 * 50_331_648
    assert per_token / 1e9 == pytest.approx(2.27, abs=0.005)
    assert gpt2.param_count(cfg) == pytest.approx(355e6, rel=0.005)
    assert gpt2.attention_shape(cfg) == (16, 64)
    # flash forward at (8, 16, 1024, 64): 2 matmuls over half the square
    flops, byts = counts.flash_fwd(8, 16, 1024, 64)
    assert flops == 2 * 8 * 16 * 1024 * 1024 * 64
    assert byts == 4 * 8 * 16 * 1024 * 64 * 2
    assert counts.flash_bwd(8, 16, 1024, 64)[0] == 2 * flops
    # one paged decode step of gpt2-xl, 8 slots at 500 tokens of context:
    # per layer K and V of 4000 tokens x 1600 wide x 2 bytes each
    xl = load(BENCH, "configs", "gpt2-xl.json")
    assert gpt2.attention_shape(xl) == (25, 64) and gpt2.cache_layers(xl) == 24
    flops, byts = counts.paged_attention(
        25 * 64, gpt2.kv_bytes_per_token(xl), attended=8 * 500 * 1,
        context_read=8 * 500)
    assert byts == 2 * 1600 * 2 * 4000
    assert flops == 4 * 1600 * 4000
    assert counts.roofline_seconds(flops, byts, "TPU v5 lite") == \
        pytest.approx(byts / 819e9)  # memory-bound
    with pytest.raises(KeyError):
        counts.peaks("cpu")


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------


def test_generator_fixed_arrangement_replays_one_schedule():
    mix = load(BENCH, "traffic", "complete_v2_r80.json")
    assert mix["arrangement_seed"] is not None
    a = traffic.schedule(mix, 7, 20.0, mix["vocab"])
    b = traffic.schedule(mix, 2**31 + 8, 20.0, mix["vocab"])
    shape = lambda reqs: [(r.due_s, len(r.prompt), r.out_len, r.prefix)
                          for r in reqs]
    assert shape(a) == shape(b)
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    share = mix["shared_prefix"]
    for reqs in (a, b):  # a header is one block of tokens wherever it stands
        assert len({tuple(r.prompt[:share["tokens"]]) for r in reqs
                    if r.prefix is not None}) <= share["count"]


def test_generator_same_seed_same_schedule_other_seed_same_work():
    mix = load(BENCH, "traffic", "complete_v2_r80.json")
    mix.pop("arrangement_seed")  # the arrangement is then the seed's too
    seed = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    a = traffic.schedule(mix, seed, 20.0, mix["vocab"])
    b = traffic.schedule(mix, seed, 20.0, mix["vocab"])
    c = traffic.schedule(mix, seed + 1, 20.0, mix["vocab"])
    assert [(r.due_s, r.prompt, r.out_len) for r in a] == \
        [(r.due_s, r.prompt, r.out_len) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    for key in (lambda r: len(r.prompt), lambda r: r.out_len):
        assert sorted(map(key, a)) == sorted(map(key, c))
    assert len(a) == round(mix["rate_per_s"] * 20.0)
    assert all(0 <= r.due_s < 20.0 for r in a)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    share = mix["shared_prefix"]
    heads = [r for r in a if r.prefix is not None]
    assert len(heads) == round(share["share"] * len(a))
    assert len({tuple(r.prompt[:share["tokens"]]) for r in heads}) \
        <= share["count"]
    assert max(len(r.prompt) + r.out_len for r in a) \
        <= traffic.max_context(mix)
    assert max(max(r.prompt) for r in a) < mix["vocab"]


# ---------------------------------------------------------------------------
# no chip, no result
# ---------------------------------------------------------------------------


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    cell = MANIFEST["workloads"][0]["name"]
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=120,
                       cwd=ROOT)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


# ---------------------------------------------------------------------------
# what decides `correct`: the rest of a run, at toy size, on the CPU
# ---------------------------------------------------------------------------


def tiny_config():
    cfg = load(BENCH, "configs", "gpt2-medium.json")
    cfg.update(n_layer=2, n_embd=32, n_head=2, n_positions=128, n_ctx=128,
               vocab_size=512, compute_dtype="float32")
    cfg["serving"] = {"num_slots": 4, "block_size": 16, "num_blocks": 40,
                      "prefill_chunk": 32, "prefix_reuse": True, "spec_k": 0,
                      "temperature": 0.0, "cache_dtype": "float32"}
    return cfg


def tiny_files(kind, chips=1):
    manifest = {"end_to_end": [dict(m, workloads=["tiny"])
                               for m in MANIFEST["end_to_end"]],
                "per_layer": []}
    if kind == "train":
        mix = load(BENCH, "traffic", "train_1k.json")
        mix.update(seq_len=64, sequences_per_chip=4, xent_chunk=32,
                   overrides=["--train.eval_batches=1"])
    else:
        mix = load(BENCH, "traffic", "complete_v2_r80.json")
        mix.update(
            rate_per_s=8.0, vocab=500, drain_seconds=30, check_requests=6,
            prompt_tokens={"law": "lognormal", "median": 32, "sigma": 0.8,
                           "min": 8, "max": 96},
            output_tokens={"law": "lognormal", "median": 8, "sigma": 0.7,
                           "min": 2, "max": 24},
            shared_prefix={"count": 2, "tokens": 32, "share": 0.4,
                           "min_body": 16})
    return {"manifest": manifest, "cell": {"name": "tiny", "chips": chips},
            "config": tiny_config(), "traffic": mix}


#: float32 on both sides at toy size: the sound program reads 1e-6 or less on
#: the first two and under 1e-3 on the third
TRAIN_LIMITS = {"loss_gap": 1e-4, "grad1_leaf_gap": 1e-3,
                "dparam_leaf_gap": 2e-2}


def break_train_step(monkeypatch, fault, chips):
    """Plant ``fault`` under the driver, in the program's compiled step."""
    import jax

    from distributed_tensorflow_tpu.workloads import runner

    real = runner.make_train_step

    def make(loss_fn, tx, options):
        step = real(loss_fn, tx, options)

        def broken(state, batch):
            if fault == "state_unchanged":
                _, metrics = step(state, batch)
                return state.replace(step=state.step + 1), metrics
            part = {"half_batch": 2, "no_exchange": chips}[fault]
            return step(state, jax.tree.map(
                lambda x: x[: x.shape[0] // part], batch))

        return broken

    monkeypatch.setattr(runner, "make_train_step", make)


def on_chips(monkeypatch, devices, chips):
    """The program builds its mesh over every device it is given: give it
    the first ``chips`` of the rig's eight."""
    from distributed_tensorflow_tpu.parallel import mesh as mesh_lib

    real = mesh_lib.build_mesh
    monkeypatch.setattr(
        "distributed_tensorflow_tpu.workloads.runner.build_mesh",
        lambda spec, devs=None: real(spec, devices[:chips]))


@pytest.mark.parametrize("fault,chips", [
    (None, 4), ("state_unchanged", 1), ("half_batch", 1),
    ("no_exchange", 4)])
def test_training_run_is_correct_and_each_fault_is_not(
        monkeypatch, devices, fault, chips):
    from benchmark import train_driver

    if fault:
        break_train_step(monkeypatch, fault, chips)
    on_chips(monkeypatch, devices, chips)
    out = train_driver.run(tiny_files("train", chips), 2**31 + 5, 0.5, False,
                           devices[:chips], TRAIN_LIMITS)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0 and "train_tokens_per_s" in out["metrics"]
    failed = [k for k, r in out["checks"].items() if not r["ok"]]
    if fault == "state_unchanged":
        assert out["checks"]["dparam_leaf_gap"]["value"] == pytest.approx(1.0)
    elif fault:
        assert "grad1_leaf_gap" in failed


def test_training_control_in_lower_precision_is_not_correct(devices):
    from benchmark import train_driver
    from benchmark.reference import gpt2

    files = tiny_files("train")
    cfg, job = files["config"], files["traffic"]
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 512, (4, 64)).astype(np.int32)
               for _ in range(3)]
    ref = train_driver.reference_numbers(cfg, job, 7, batches, devices[:1])
    ctl = train_driver.reference_numbers(cfg, job, 7, batches, devices[:1],
                                         quant="fp8")
    ok, rows = check.judge(check.train_numbers(ctl, ref), TRAIN_LIMITS)
    assert not ok and not rows["grad1_leaf_gap"]["ok"], rows
    # the two layouts of the seed's weights hold the same numbers
    a = gpt2.make_weights(cfg, 7)
    b = gpt2.make_weights(cfg, 7, stacked=False)
    assert np.array_equal(a["blocks"]["w1"][1], b["layers"][1]["w1"])
    assert np.array_equal(a["wte"], b["wte"])


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
    out = serve_driver.run(tiny_files("serve"), 2**31 + 5, 2.0, False,
                           devices[:1], {"served_gap_max": 1e-3})
    assert out["correct"] is (fault is None), out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 16
    assert {"serve_tokens_per_s", "tpot_p95_ms",
            "setup_s"} <= set(out["metrics"])
    # a tail the manifest does not judge stays in the line
    assert {"ttft_p95_ms", "tpot_p95_ms", "ttft_p50_ms"} <= set(out["window"])


def test_serving_control_in_lower_precision_reads_a_gap(devices):
    from benchmark import serve_driver

    files = tiny_files("serve")
    cfg, mix = files["config"], files["traffic"]
    # as many positions as a run on the chip compares (some hundreds): the
    # widest gap is a tail, and a handful of positions may not reach it
    mix["check_requests"] = 48
    reqs = traffic.schedule(mix, 3, 6.0, mix["vocab"])
    done = [(i, [1 + i] * r.out_len) for i, r in enumerate(reqs)]
    ctl, _ = serve_driver.served_numbers(cfg, mix, 3, reqs, done,
                                         quant="int8")
    assert ctl["served_gap_max"]["value"] > 1e-3
    assert ctl["served_gap_mean"]["value"] > 1e-6


def test_a_serving_cell_is_held_to_the_served_numbers_its_limits_name(
        devices):
    """The widest gap, the mean gap or both, as the cell's limits file
    says; the one it does not name goes to the notes. A file that names
    neither, or something that is no served number, stops the run. A mean
    over its limit fails the run though the widest gap passes."""
    from benchmark import serve_driver

    numbers = {"served_gap_max": {"value": 0.02, "request": "7"},
               "served_gap_mean": {"value": 0.0003}}
    for limits in ({"served_gap_max": 0.09}, {"served_gap_mean": 0.002},
                   {"served_gap_max": 0.09, "served_gap_mean": 0.002}):
        got = serve_driver.compared(numbers, limits)
        assert set(got) == set(limits)
        assert check.judge(got, limits)[0] is True
    for limits in ({}, {"served_gap": 0.09},
                   {"served_gap_max": 0.09, "other": 1.0}):
        with pytest.raises(KeyError):
            serve_driver.compared(numbers, limits)
    ok, rows = check.judge(numbers, {"served_gap_max": 0.09,
                                     "served_gap_mean": 0.0002})
    assert not ok and rows["served_gap_max"]["ok"]
    assert not rows["served_gap_mean"]["ok"]
    both = {"served_gap_max": 1e-3, "served_gap_mean": 1e-6}
    out = serve_driver.run(tiny_files("serve"), 2**31 + 9, 1.0, False,
                           devices[:1], both)
    assert out["correct"] is True and set(out["checks"]) == set(both)


def test_backlog_run_keeps_the_queue_topped_up(devices):
    """Kind `backlog` through the rest of a run (no cell uses it yet, so the
    chip has not seen it): the queue is topped up, everything sent is
    finished and compared."""
    from benchmark import serve_driver

    files = tiny_files("serve")
    files["traffic"].update(kind="backlog", backlog=6)
    out = serve_driver.run(files, 2**31 + 6, 1.0, False, devices[:1],
                           {"served_gap_max": 1e-3})
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 6


def test_backlog_stream_keeps_its_lengths():
    mix = load(BENCH, "traffic", "complete_v2_r80.json")
    mix.update(kind="backlog", backlog=16)
    mix.pop("shared_prefix")
    it = traffic.stream(mix, 5, mix["vocab"], batch=32)
    first = [next(it) for _ in range(32)]
    assert all(r.due_s is None for r in first)
    assert sorted(len(r.prompt) for r in first) == sorted(
        traffic.lengths(32, mix["prompt_tokens"]).tolist())


# ---------------------------------------------------------------------------
# a model family is new files: a second one, brought by this test alone
# ---------------------------------------------------------------------------

#: the toy family's key -> GPT-2's
TOY_KEYS = {"depth": "n_layer", "width": "n_embd", "heads": "n_head",
            "mlp_width": "n_inner", "context": "n_positions",
            "act": "activation_function"}


def toy_family(monkeypatch, tmp_path) -> dict:
    """A family under `model_type: "toy"`: the GPT-2 block under other key
    names. Its three modules (reference, adapter, counts) are injected where
    the lookups by `model_type` find them and its configuration file is
    written to ``tmp_path``; no file that is there is touched. Returns the
    configuration as read back from its file."""
    from benchmark.families.gpt2 import adapter, counts as gpt2_counts
    from benchmark.reference import gpt2 as gpt2_reference

    def as_gpt2(x):
        if not (isinstance(x, dict) and x.get("model_type") == "toy"):
            return x
        return {TOY_KEYS.get(k, k): v for k, v in x.items()} | {
            "model_type": "gpt2"}

    def under_toy_keys(name, source):
        module = types.ModuleType(name)
        for key, value in vars(source).items():
            if key.startswith("_"):
                continue
            if isinstance(value, types.FunctionType):
                def value(*args, _f=value, **kw):
                    return _f(*map(as_gpt2, args), **kw)
            setattr(module, key, value)
        monkeypatch.setitem(sys.modules, name, module)

    under_toy_keys("benchmark.reference.toy", gpt2_reference)
    under_toy_keys("benchmark.families.toy.adapter", adapter)
    under_toy_keys("benchmark.families.toy.counts", gpt2_counts)
    to_toy = {v: k for k, v in TOY_KEYS.items()}
    cfg = {to_toy.get(k, k): v for k, v in tiny_config().items()} | {
        "model_type": "toy"}
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(cfg))
    cfg = load(str(path))
    assert not set(TOY_KEYS.values()) & set(cfg)
    return cfg


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_a_second_family_is_injected_modules_and_a_config_file(
        monkeypatch, tmp_path, devices, kind):
    files = tiny_files(kind)
    files["config"] = toy_family(monkeypatch, tmp_path)
    if kind == "train":
        from benchmark import train_driver

        on_chips(monkeypatch, devices, 1)
        out = train_driver.run(files, 2**31 + 7, 0.5, False, devices[:1],
                               TRAIN_LIMITS)
        assert "train_tokens_per_s" in out["metrics"]
    else:
        from benchmark import serve_driver

        out = serve_driver.run(files, 2**31 + 7, 2.0, False, devices[:1],
                               {"served_gap_max": 1e-3})
        assert out["failed"] == 0 and "tpot_p95_ms" in out["metrics"]
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0


def test_the_yardstick_reads_a_second_family_through_its_own_counts(
        monkeypatch, tmp_path):
    from benchmark.readers import mfu, rooflines

    toy = toy_family(monkeypatch, tmp_path)
    job = load(BENCH, "traffic", "train_1k.json")
    ctx = {"trace": hand_trace(), "run": {}, "cfg": toy, "traffic": job,
           "chips": 1, "device_kind": "TPU v5 lite"}
    as_gpt2 = dict(ctx, cfg=tiny_config())
    got = mfu.train_step(ctx, "jit_train_step")
    assert got is not None and got == mfu.train_step(as_gpt2, "jit_train_step")
    args = (["flash_attention_fwd"], "flash_attention_fwd", "fwd")
    assert rooflines.flash(ctx, *args) == rooflines.flash(as_gpt2, *args) > 0
    with pytest.raises(ModuleNotFoundError):
        families.counts({"model_type": "no_such_family"})
