"""Traffic kinds `open_loop` and `backlog`: requests through the program's
serving engine (`ServeEngine.submit/step` -> scheduler -> paged cache with
prefix reuse -> `jit_paged_prefill_chunk` / `jit_paged_decode_step` ->
`paged_attention_fwd`), offered by the generator from this same process,
inline between `engine.step()` calls.

Times are the host's clock. An open-loop request is timed from when it was
DUE, not from when it was sent, so a stall shows in the requests behind it.
Once the window has closed, no more is offered; the requests that were due
in it are waited for (they are late, not wrong), and a sample of the
finished ones is compared with the plain reference.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from benchmark import check, harness, program, reference, traffic


#: what a serving cell can be held to (`served_numbers`)
SERVED_NUMBERS = ("served_gap_max", "served_gap_mean")


def _p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def warm_up(eng, mix: dict, vocab: int) -> None:
    """Every shape the mix can reach, through the engine's own entry: one
    lone request per table-width bucket (its prefill walks the narrower
    buckets, its decode runs at its own), and a repeated prompt, whose
    second copy writes into a shared block (the copy-on-write program)."""
    rng = np.random.default_rng(0)
    bs = eng.block_size
    widest = -(-traffic.max_context(mix) // bs)
    width = 1
    while True:
        n = min(width * bs, widest * bs) - bs // 2
        eng.submit(rng.integers(0, vocab, n).tolist(), max_new_tokens=2)
        eng.run()
        if width >= widest:
            break
        width *= 2
    twice = rng.integers(0, vocab, 2 * bs).tolist()
    for _ in range(2):
        eng.submit(twice, max_new_tokens=2)
        eng.run()
    eng.alloc.flush_prefix_cache()


def build(cfg: dict, mix: dict, seed: int):
    """Set-up: the engine on the seed's weights, every shape warmed."""
    if not mix["greedy"] or cfg["serving"]["temperature"] != 0.0:
        raise ValueError("the comparison with the reference needs greedy "
                         "tokens; mix greedy requests into a sampling cell")
    eng = program.make_engine(cfg, seed)
    warm_up(eng, mix, mix["vocab"])
    return eng


def drive(eng, mix: dict, seed: int, seconds: float, tracing=None) -> dict:
    """One window of the mix on a warmed engine, then the wait for what was
    due in it. Returns what the users saw and what the readers need."""
    clock = eng.clock
    #: most blocks of the pool that resident requests held after any step
    #: (blocks only the prefix cache holds are evictable and do not count),
    #: and most blocks in use at all: the allocator's own counts
    pool = {"live": 0, "used": 0}
    counters0 = _counters(eng)
    # the trace covers the END of the window: the engine is in its steady
    # state there, and the seconds `stop_trace` takes to write the trace
    # fall after the close, where they delay no request of the window
    trace_from = seconds - min(mix["trace_seconds"], seconds)
    if mix["kind"] == "open_loop":
        reqs = traffic.schedule(mix, seed, seconds, mix["vocab"])
        source = None
    else:
        reqs, source = [], traffic.stream(mix, seed, mix["vocab"])

    jax.block_until_ready(eng.cache)
    t0 = t_ready = clock()
    sent: dict = {}       # uid -> (index into reqs, seconds sent after t0)
    tokens_in_window = 0
    nxt = 0

    def step():
        stats = eng.step()
        used = eng.alloc.blocks_in_use
        pool["used"] = max(pool["used"], used)
        pool["live"] = max(pool["live"], used - eng.alloc.evictable())
        return stats

    def offer(now: float) -> None:
        nonlocal nxt
        with harness.annotate("bench.submit"):
            if source is None:
                while nxt < len(reqs) and reqs[nxt].due_s <= now:
                    uid = eng.submit(reqs[nxt].prompt,
                                     max_new_tokens=reqs[nxt].out_len)
                    sent[uid] = (nxt, now)
                    nxt += 1
            else:
                outstanding = len(eng.sched.queue) + sum(
                    r is not None for r in eng.sched.slots)
                while outstanding < mix["backlog"]:
                    reqs.append(next(source))
                    uid = eng.submit(reqs[-1].prompt,
                                     max_new_tokens=reqs[-1].out_len)
                    sent[uid] = (len(reqs) - 1, now)
                    outstanding += 1

    while True:
        now = clock() - t0
        if now >= seconds:
            break
        offer(now)
        if tracing is not None and tracing.t0 is None and now >= trace_from:
            tracing.start()
        if eng.sched.has_work:
            stats = step()
            if clock() - t0 <= seconds:
                tokens_in_window += len(stats.tokens)
        else:
            with harness.annotate("bench.wait_for_arrival"):
                gap = (reqs[nxt].due_s - now) if nxt < len(reqs) else 0.01
                time.sleep(max(min(gap, seconds - now, 0.005), 0.0))
    t_close = clock()
    if tracing is not None and tracing.running:
        tracing.stop()
    backlog_at_close = len(eng.sched.queue) + sum(
        r is not None for r in eng.sched.slots)
    # requests that fell due while the last step ran are offered late; then
    # everything due in the window is waited for
    if source is None:
        offer(seconds)
    while eng.sched.has_work and clock() - t_close < mix["drain_seconds"]:
        step()
    finished = eng.sched.drain_finished()
    eng.alloc.flush_prefix_cache()
    counters1 = _counters(eng)

    # switching the profiler on stalls the loop for up to seconds: a traced
    # run reads the generator's lateness and the queue wait from the
    # requests sent and admitted before that
    t_profiler = float("inf") if tracing is None else tracing.t_begin
    ttft, tpot, queue_wait, lateness, done = [], [], [], [], []
    ttft_calm, tpot_calm = [], []  # of requests the profiler did not stall
    #: per request, in the order sent: [due s, sent s, prompt tokens, output
    #: tokens, admitted s, TTFT ms, TPOT ms]: which requests make the tails
    per_request = []
    failed = 0
    for uid, (i, sent_at) in sent.items():
        req = finished.get(uid)
        due = reqs[i].due_s if reqs[i].due_s is not None else sent_at
        if t0 + sent_at < t_profiler:
            lateness.append(sent_at - due)
        if req is None or req.t_first_token is None or len(
                req.generated) != reqs[i].out_len:
            failed += 1
            ttft.append(seconds + mix["drain_seconds"] - due)
            continue
        ttft.append(req.t_first_token - t0 - due)
        if req.t_admit < t_profiler:
            queue_wait.append(req.t_admit - t0 - due)
        if req.t_first_token < t_profiler:
            ttft_calm.append(ttft[-1])
        if len(req.generated) > 1:
            tpot.append((req.t_finish - req.t_first_token)
                        / (len(req.generated) - 1))
            if req.t_finish < t_profiler:
                tpot_calm.append(tpot[-1])
        done.append((i, list(req.generated)))
        per_request.append([
            round(due, 4), round(sent_at, 4), len(reqs[i].prompt),
            reqs[i].out_len, round(req.t_admit - t0, 4),
            round(1e3 * ttft[-1], 1),
            round(1e3 * tpot[-1], 1) if len(req.generated) > 1 else None])
    window_s = t_close - t0
    values = {"serve_tokens_per_s": tokens_in_window / window_s,
              "ttft_p95_ms": 1e3 * _p95(ttft)}
    if tpot:
        values["tpot_p95_ms"] = 1e3 * _p95(tpot)
    prompt_blocks = sum(-(-len(reqs[i].prompt) // eng.block_size)
                        for i, _ in sent.values())
    reader_run = {
        "lateness_s": lateness, "queue_wait_s": queue_wait,
        "ttft_s": ttft_calm, "tpot_s": tpot_calm,
        "prefix_blocks_hit": counters1["prefix_reuse_hits_total"]
        - counters0["prefix_reuse_hits_total"],
        "prompt_blocks": prompt_blocks, "pool_live_peak": pool["live"],
        "pool_used_peak": pool["used"], "pool_blocks": eng.alloc.num_blocks}
    # the program's ring shares the engine's clock. A program compiled in
    # the window, or loaded from the compile cache there, is a shape that
    # set-up did not warm (the reference's compiles come after the close)
    ring = program.span_ring()
    traced = tracing is not None and tracing.t1 is not None
    return {
        "t_ready": t_ready, "window_s": window_s, "values": values,
        "tokens_in_window": tokens_in_window, "reqs": reqs, "done": done,
        "requests": len(sent), "failed": failed,
        "backlog_at_close": backlog_at_close,
        "ttft_p50_ms": 1e3 * float(np.median(ttft)),
        "compiled_in_window": sum(
            s.name == "compile.backend" and t0 <= s.start < t_close
            for s in ring),
        "steps_while_traced": {} if not traced else {
            kind: sum(s.name == f"serve.step.{kind}"
                      and tracing.t0 <= s.start < tracing.t1 for s in ring)
            for kind in ("prefill", "decode")},
        "per_request": per_request,
        "reader_run": reader_run}


def run(files: dict, seed: int, seconds: float, trace: bool, devices,
        limits: dict) -> dict:
    cfg, mix, cell = files["config"], files["traffic"], files["cell"]
    t_build = time.perf_counter()
    eng = build(cfg, mix, seed)
    t_build = time.perf_counter() - t_build
    tracing = harness.Tracing(cell["name"]) if trace else None
    got = drive(eng, mix, seed, seconds, tracing)
    device = harness.device_record(devices)
    del eng
    gc.collect()  # the engine holds cycles; its weights and pool go now
    got["values"]["setup_s"] = got["t_ready"] - harness.T_PROCESS_START

    # -- correct: a seed-drawn sample of finished requests, the longest in it
    t_ref = time.perf_counter()
    numbers, notes = served_numbers(cfg, mix, seed, got["reqs"], got["done"])
    notes.update(reference_seconds=time.perf_counter() - t_ref)
    notes.update(programs_compiled_in_window=got["compiled_in_window"],
                 requests=got["requests"], finished=len(got["done"]),
                 engine_build_and_warm_up_seconds=t_build,
                 backlog_at_close=got["backlog_at_close"])
    if trace:  # to hold against the runs the trace's `XLA Modules` shows
        notes.update(engine_steps_while_traced=got["steps_while_traced"])
    notes.update({k: v["value"] for k, v in numbers.items()
                  if k not in limits})
    correct, rows = check.judge(
        compared(numbers, limits), limits,
        extra_ok=(got["failed"] == 0 and got["compiled_in_window"] == 0
                  and bool(got["done"])))

    out = {"correct": correct, "attempted": got["requests"],
           "failed": got["failed"]}
    if trace:
        harness.traced_outputs(files, tracing, devices, got["reader_run"],
                               out, device)
    else:
        out["metrics"] = harness.end_to_end_metrics(
            files["manifest"], cell["name"], got["values"])
    out["device"] = device
    out["window"] = {"seconds": got["window_s"],
                     "tokens": got["tokens_in_window"],
                     "requests": got["requests"],
                     "ttft_p50_ms": got["ttft_p50_ms"],
                     # the tails, judged in this cell or not (PERF.md
                     # section 2 says which and why)
                     **{k: v for k, v in got["values"].items()
                        if k.endswith("_p95_ms")}}
    out["requests"] = got["per_request"]
    check.report(rows, correct, notes)
    out["checks"] = rows
    return out


def _counters(eng) -> dict:
    return {name: eng.registry.get(name).value
            for name in ("prefix_reuse_hits_total", "prefill_chunks_total",
                         "serve_tokens_total")}


def served_numbers(cfg: dict, mix: dict, seed: int, reqs: list, done: list,
                   quant=None) -> tuple:
    """The widest and the mean gap by which a served token's logit lies
    below the reference's best, over a sample of the finished requests drawn
    from the seed, the longest (prompt + served) always in it. The widest
    swings by its nature (one near tie of random weights); the mean over
    some hundreds of tokens does not, and keeps a lower precision eight
    times off the sound program where the widest keeps it three (PERF.md
    section 6, PR 33)."""
    if not done:
        return {name: {"value": float("inf")} for name in SERVED_NUMBERS}, {}
    rng = np.random.default_rng([int(seed), 2])
    by_len = sorted(done, key=lambda d: -(len(reqs[d[0]].prompt) + len(d[1])))
    pick = [by_len[0]] + [by_len[1:][j] for j in rng.permutation(
        len(by_len) - 1)[: mix["check_requests"] - 1]]
    ref = reference.for_config(cfg)
    t_weights = time.perf_counter()
    weights = jax.block_until_ready(ref.make_weights(cfg, seed))
    t_weights = time.perf_counter() - t_weights
    pad_to = -(-traffic.max_context(mix) // 128) * 128
    gaps, worst = [], (0.0, None)
    for i, served in pick:
        g = ref.served_gaps(cfg, weights, reqs[i].prompt, served,
                            pad_to=pad_to, n_out=mix["output_tokens"]["max"],
                            quant=quant)
        gaps.append(g)
        if g.max() >= worst[0]:
            worst = (float(g.max()), i)
    gaps = np.concatenate(gaps)
    numbers = {"served_gap_max": {"value": float(gaps.max()),
                                  "request": str(worst[1])},
               "served_gap_mean": {"value": float(gaps.mean())}}
    notes = {"served_tokens_compared": int(gaps.size),
             "requests_compared": len(pick),
             "tokens_not_reference_first": int((gaps > 0).sum()),
             "reference_weights_seconds": t_weights}
    return numbers, notes


def compared(numbers: dict, limits: dict) -> dict:
    """Those of the served numbers that the cell's limits file names: a cell
    is held to the widest gap, the mean gap or both. A file that names none,
    or a name that is no served number, is an error and never a pass."""
    if not limits or set(limits) - set(numbers):
        raise KeyError(f"the cell's limits {sorted(limits)} have to name "
                       f"one or more of {sorted(numbers)} and nothing else")
    return {k: numbers[k] for k in limits}
