"""Serve fleet (ISSUE 16): router lane/requeue invariants, prefix-aware
placement against the random baseline, and the failover acceptance —
one replica killed mid-stream, every in-flight stream re-prefilled and
finished on survivors BIT-IDENTICAL to an uncontended run, interactive
p99 TTFT bounded through the kill (deterministic fake clock)."""

import pytest

from distributed_tensorflow_tpu import serve
from distributed_tensorflow_tpu.models import transformer as tfm
from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder
from distributed_tensorflow_tpu.obs.registry import Registry
from distributed_tensorflow_tpu.serve import fleet as sf
from distributed_tensorflow_tpu.serve import router as rt


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def make_router(**kw):
    kw.setdefault("registry", Registry())
    kw.setdefault("flightrec", FlightRecorder())
    return rt.Router(**kw)


# ---------------------------------------------------------------------------
# Router invariants (jax-free)
# ---------------------------------------------------------------------------


def test_unknown_lane_rejected():
    r = make_router()
    with pytest.raises(rt.UnknownLane):
        r.submit([1, 2], lane="bulk")


def test_lane_queues_disjoint():
    r = make_router()
    b = r.submit([1, 2], lane=rt.LANE_BATCH)
    a = r.submit([3, 4], lane=rt.LANE_INTERACTIVE)
    assert r.queued(rt.LANE_BATCH) == 1
    assert r.queued(rt.LANE_INTERACTIVE) == 1
    assert [q.rid for q in r.lanes[rt.LANE_BATCH]] == [b]
    assert [q.rid for q in r.lanes[rt.LANE_INTERACTIVE]] == [a]


def test_interactive_dispatches_before_batch():
    """ALL of interactive drains before ANY of batch, whatever the
    submission interleaving — the SLO tier order."""
    r = make_router(max_outstanding=4)
    r.add_replica(0)
    b1 = r.submit([1], lane=rt.LANE_BATCH)
    a1 = r.submit([2], lane=rt.LANE_INTERACTIVE)
    b2 = r.submit([3], lane=rt.LANE_BATCH)
    a2 = r.submit([4], lane=rt.LANE_INTERACTIVE)
    order = [req.rid for _, req in r.dispatch()]
    assert order == [a1, a2, b1, b2]


def test_dispatch_fifo_within_lane_and_head_of_line():
    """Within a lane dispatch is FIFO, and a head that cannot be placed
    blocks everything behind it (no skipping ahead)."""
    r = make_router(max_outstanding=2)
    r.add_replica(0)
    rids = [r.submit([i + 1], lane=rt.LANE_INTERACTIVE) for i in range(4)]
    first = [req.rid for _, req in r.dispatch()]
    assert first == rids[:2]  # capacity 2: the head pair, in order
    assert r.queued(rt.LANE_INTERACTIVE) == 2
    r.on_token(rids[0], 7)
    r.on_finish(rids[0], "eos")
    assert [req.rid for _, req in r.dispatch()] == [rids[2]]


def test_requeue_preserves_fifo_within_lane():
    """Death path: in-flight requests return to the HEAD of their lane
    in original dispatch order, ahead of anything still queued."""
    r = make_router(max_outstanding=4)
    r.add_replica(0)
    i0 = r.submit([1], lane=rt.LANE_INTERACTIVE)
    i1 = r.submit([2], lane=rt.LANE_INTERACTIVE)
    b0 = r.submit([3], lane=rt.LANE_BATCH)
    b1 = r.submit([4], lane=rt.LANE_BATCH)
    assert len(r.dispatch()) == 4  # all in flight on replica 0
    i2 = r.submit([5], lane=rt.LANE_INTERACTIVE)  # queued behind
    b2 = r.submit([6], lane=rt.LANE_BATCH)
    requeued = r.requeue_replica(0)
    assert sorted(requeued) == [i0, i1, b0, b1]
    assert [q.rid for q in r.lanes[rt.LANE_INTERACTIVE]] == [i0, i1, i2]
    assert [q.rid for q in r.lanes[rt.LANE_BATCH]] == [b0, b1, b2]
    for rid in requeued:
        assert r.requests[rid].requeues == 1
        assert r.requests[rid].replica is None


def test_remove_replica_with_inflight_raises():
    r = make_router()
    r.add_replica(0)
    r.submit([1], lane=rt.LANE_INTERACTIVE)
    r.dispatch()
    with pytest.raises(RuntimeError):
        r.remove_replica(0)


def test_prefix_placement_follows_home_and_counts_hits():
    reg = Registry()
    r = make_router(registry=reg, max_outstanding=4)
    r.add_replica(0)
    r.add_replica(1)
    pfx = list(range(8))
    a = r.submit(pfx + [50], lane=rt.LANE_INTERACTIVE, prefix_len=8)
    b = r.submit(pfx + [51], lane=rt.LANE_INTERACTIVE, prefix_len=8)
    orders = r.dispatch()
    assert orders[0][0] == orders[1][0]  # same home replica
    # the first placement pinned (no hit); the second followed the pin
    assert int(reg.get("router_prefix_hits_total").value) == 1
    home = orders[0][0]
    r.requeue_replica(home)  # the home dies: pins dropped
    assert r.dispatch()  # repins on the survivor without error
    assert all(req.replica != home for req in r.requests.values())


def test_requeued_payload_resumes_past_delivered_tokens():
    """The re-dispatch payload is prompt + delivered tokens with the
    budget reduced to match — the re-prefill contract."""
    r = make_router(max_outstanding=2)
    r.add_replica(0)
    rid = r.submit([1, 2, 3], max_new_tokens=8, lane=rt.LANE_BATCH)
    r.dispatch()
    r.on_token(rid, 40)
    r.on_token(rid, 41)
    r.requeue_replica(0)
    req = r.requests[rid]
    payload = req.payload()
    assert payload["prompt"] == [1, 2, 3, 40, 41]
    assert payload["max_new_tokens"] == 6
    assert payload["priority"] == rt.LANE_PRIORITY[rt.LANE_BATCH]


def test_batch_lane_maps_to_lower_engine_priority():
    assert rt.LANE_PRIORITY[rt.LANE_BATCH] \
        < rt.LANE_PRIORITY[rt.LANE_INTERACTIVE]


# ---------------------------------------------------------------------------
# Fleet failover (LocalReplica engines, deterministic fake clock)
# ---------------------------------------------------------------------------


def fleet_decoder():
    return tfm.TransformerConfig(
        vocab_size=128, max_len=96, num_layers=1, d_model=32, num_heads=4,
        d_ff=64, dropout=0.0, dtype="float32", causal=True, pre_ln=True,
    )


def _make_engine(cfg, spec_k=0):
    return serve.ServeEngine.with_random_params(
        cfg, seed=0, num_slots=2, paged=True, block_size=8,
        prefill_chunk=16, spec_k=spec_k)


def shared_prefix_trace(n=6, groups=2, max_new=6):
    """n requests over `groups` shared 16-token system prompts,
    alternating lanes: (prompt, lane, prefix_len, max_new) rows."""
    pfx = [[(7 * g + k) % 128 for k in range(16)] for g in range(groups)]
    trace = []
    for i in range(n):
        lane = rt.LANE_INTERACTIVE if i % 2 == 0 else rt.LANE_BATCH
        prompt = pfx[i % groups] + [(3 * i + 1) % 128, (5 * i + 2) % 128]
        trace.append((prompt, lane, 16, max_new))
    return trace


def baseline_streams(cfg, trace):
    """Uncontended ground truth: each prompt decoded alone on one
    engine with the same seed-deterministic weights."""
    eng = _make_engine(cfg)
    out = {i: list(eng.stream(p, max_new_tokens=m))
           for i, (p, _lane, _plen, m) in enumerate(trace)}
    eng.drain()
    return out


def run_fleet(cfg, trace, *, policy="prefix", num_replicas=2,
              kill_after_tokens=None, spec_k=0):
    """Drive a LocalReplica fleet over the trace on a fake clock
    (1 pump = 1 s); optionally hard-kill a mid-stream replica once
    `kill_after_tokens` tokens are in flight."""
    clk = FakeClock()
    reg, rec = Registry(), FlightRecorder()
    engines = []

    def launch(index, incarnation):
        eng = _make_engine(cfg, spec_k=spec_k)
        engines.append(eng)
        return sf.LocalReplica(eng)

    router = rt.Router(policy=policy, max_outstanding=2, seed=0,
                       registry=reg, flightrec=rec, clock=clk)
    sup = sf.ServeFleetSupervisor(
        launch, num_replicas, router=router, registry=reg, flightrec=rec,
        clock=clk, sleep=lambda s: clk.advance(s or 0.01))
    sup.start()
    for prompt, lane, plen, max_new in trace:
        router.submit(prompt, max_new_tokens=max_new, lane=lane,
                      prefix_len=plen)
    killed = kill_after_tokens is None
    for _ in range(10_000):
        if router.idle:
            break
        sup.pump()
        clk.advance(1.0)
        if not killed:
            busy = [w for w in sorted(sup.replicas)
                    if any(router.requests[rid].delivered
                           for rid in router.outstanding.get(w, ()))]
            delivered = sum(len(r.delivered)
                            for r in router.requests.values())
            if busy and delivered >= kill_after_tokens:
                sup.replicas[busy[0]].handle.hard_kill()
                killed = True
    else:
        raise AssertionError("fleet did not go idle in 10k pumps")
    survivors = sorted(sup.replicas)
    sup.stop()
    return router, reg, rec, engines, sup, survivors


def test_kill_midstream_no_request_lost_streams_bit_identical():
    """ISSUE 16 acceptance: a replica dies mid-stream, nothing is lost,
    every stream completes on survivors, and each full token stream is
    bit-identical to the uncontended single-engine run (re-prefill with
    identical weights is deterministic)."""
    cfg = fleet_decoder()
    trace = shared_prefix_trace(n=6)
    want = baseline_streams(cfg, trace)
    router, reg, rec, engines, sup, survivors = run_fleet(
        cfg, trace, kill_after_tokens=3)

    assert sup.deaths == 1
    assert int(reg.get("router_requeues_total").value) >= 1
    assert len(router.finished) == len(trace)  # no request lost
    for rid, req in router.finished.items():
        assert req.delivered == want[rid], (
            f"rid {rid} diverged after requeue: {req.delivered} != "
            f"{want[rid]}")
    # at least one finished stream actually crossed the kill
    assert any(req.requeues for req in router.finished.values())
    # survivors drained leak-free; the corpse never writes its audit
    assert survivors and set(sup.drained) == set(survivors)
    assert all(d["leak_free"] for d in sup.drained.values())
    kinds = [e["kind"] for e in rec.events()]
    for kind in ("serve_replica_dead", "serve_requeue", "fleet_done"):
        assert kind in kinds


def test_interactive_p99_ttft_bounded_through_kill():
    """The kill costs the interactive lane a bounded constant factor
    over the kill-free run — not an unbounded stall (fake clock: 1 pump
    = 1 s, so the percentiles are exact pump counts)."""
    cfg = fleet_decoder()
    trace = shared_prefix_trace(n=8, max_new=6)
    _, reg0, *_ = run_fleet(cfg, trace)
    base_p99 = reg0.get("router_ttft_seconds",
                        lane=rt.LANE_INTERACTIVE).percentile(0.99)
    router, reg, *_ = run_fleet(cfg, trace, kill_after_tokens=3)
    assert len(router.finished) == len(trace)
    kill_p99 = reg.get("router_ttft_seconds",
                       lane=rt.LANE_INTERACTIVE).percentile(0.99)
    assert kill_p99 <= 3 * base_p99 + 10.0, (kill_p99, base_p99)


def test_kill_midstream_cannot_reset_tpot_clock():
    """ISSUE 17 regression pin: router-side TPOT is measured from the
    ORIGINAL first delivered token across replica deaths — a mid-stream
    kill must not reset a request's TPOT clock on the survivor.
    ``on_token`` stamps ``t_first_token`` only while it is None, and the
    requeue path must leave it (and ``t_submit``) alone."""
    cfg = fleet_decoder()
    trace = shared_prefix_trace(n=6)
    router, reg, rec, *_ = run_fleet(cfg, trace, kill_after_tokens=3)

    t_requeue = min(e["t"] for e in rec.events()
                    if e["kind"] == "serve_requeue")
    crossed = [req for req in router.finished.values()
               if req.requeues and req.t_first_token is not None]
    assert crossed, "no killed request had delivered a token"
    for req in crossed:
        # the pre-kill stamp survived the survivor's re-prefill...
        assert req.t_first_token <= t_requeue, (
            f"rid {req.rid}: t_first_token {req.t_first_token} is AFTER "
            f"the requeue at {t_requeue} — the TPOT clock was reset")
        assert req.t_submit < req.t_first_token < req.t_finish
        # ...and the finish-side observation used it: the per-token
        # cadence the client saw INCLUDES the re-prefill detour
        tpot = (req.t_finish - req.t_first_token) / (len(req.delivered) - 1)
        assert tpot > 0
    # one TPOT observation per finished multi-token request, none lost
    finished_multi = sum(1 for req in router.finished.values()
                         if req.t_first_token is not None
                         and len(req.delivered) > 1)
    assert reg.total(rt.ROUTER_TPOT_SECONDS) == finished_multi


def test_spec_multi_token_pumps_keep_tpot_per_token():
    """PR 20 regression pin (alongside the TPOT-clock pin above): with
    speculative engines one pump can deliver SEVERAL tokens per request,
    and the router's TPOT accounting must stay per-TOKEN — exactly one
    observation per finished multi-token request, never one per pump —
    while the streams stay bit-identical to the non-spec baseline
    (greedy-exact acceptance)."""
    cfg = fleet_decoder()
    trace = shared_prefix_trace(n=6)
    want = baseline_streams(cfg, trace)
    router, reg, rec, engines, sup, survivors = run_fleet(
        cfg, trace, spec_k=4)

    assert len(router.finished) == len(trace)
    for rid, req in router.finished.items():
        assert req.delivered == want[rid], (
            f"rid {rid} diverged under speculation: {req.delivered} != "
            f"{want[rid]}")
    # speculation actually landed multi-token steps somewhere
    accepted = sum(
        int(e.registry.get("spec_tokens_accepted_total").value)
        for e in engines)
    assert accepted > 0
    finished_multi = sum(1 for req in router.finished.values()
                         if req.t_first_token is not None
                         and len(req.delivered) > 1)
    assert reg.total(rt.ROUTER_TPOT_SECONDS) == finished_multi
    for req in router.finished.values():
        if req.t_first_token is not None:
            assert req.t_submit <= req.t_first_token <= req.t_finish
    assert all(d["leak_free"] for d in sup.drained.values())


def test_prefix_routing_beats_random_on_shared_prefix_trace():
    """ISSUE 16 acceptance: routed prefix-hit rate strictly beats the
    seeded random baseline on a shared-system-prompt trace, measured as
    `prefix_reuse_hits_total` ON THE ENGINES — blocks actually reused
    instead of re-prefilled."""
    cfg = fleet_decoder()
    trace = shared_prefix_trace(n=10, groups=2, max_new=4)

    def engine_hits(policy):
        *_, engines, _sup, _surv = run_fleet(cfg, trace, policy=policy)
        return sum(int(e.registry.get("prefix_reuse_hits_total").value)
                   for e in engines)

    routed, rand = engine_hits("prefix"), engine_hits("random")
    assert routed > rand, (routed, rand)


def test_elastic_add_replica_absorbs_without_drain():
    """Scale-up mid-run: the joining replica takes new work on the very
    next dispatch; nothing drains, everything finishes."""
    cfg = fleet_decoder()
    trace = shared_prefix_trace(n=6, groups=3, max_new=4)
    clk = FakeClock()
    reg, rec = Registry(), FlightRecorder()

    def launch(index, incarnation):
        return sf.LocalReplica(_make_engine(cfg))

    router = rt.Router(max_outstanding=2, registry=reg, flightrec=rec,
                       clock=clk)
    sup = sf.ServeFleetSupervisor(
        launch, 1, router=router, registry=reg, flightrec=rec,
        clock=clk, sleep=lambda s: clk.advance(s or 0.01))
    sup.start()
    for prompt, lane, plen, max_new in trace:
        router.submit(prompt, max_new_tokens=max_new, lane=lane,
                      prefix_len=plen)
    sup.pump()
    new = sup.add_replica()
    assert new == 1
    for _ in range(10_000):
        if router.idle:
            break
        sup.pump()
        clk.advance(1.0)
    sup.stop()
    assert len(router.finished) == len(trace)
    assert sup.deaths == 0  # absorbed, not recovered
    routed_to_new = [e for e in rec.events()
                     if e["kind"] == "serve_route" and e.get("replica") == new]
    assert routed_to_new  # the joiner became a placement target
    assert set(sup.drained) == {0, 1}
    assert all(d["leak_free"] for d in sup.drained.values())


def test_scheduler_priority_victim_selection():
    """The engine's preemption victim is the LOWEST-priority resident
    (batch before interactive), youngest among equals — the seam the
    router's lanes map onto."""
    cfg = fleet_decoder()
    eng = _make_engine(cfg)
    hi = eng.submit([1, 2, 3], max_new_tokens=4,
                    priority=rt.LANE_PRIORITY[rt.LANE_INTERACTIVE])
    lo = eng.submit([4, 5, 6], max_new_tokens=4,
                    priority=rt.LANE_PRIORITY[rt.LANE_BATCH])
    eng.sched.admit()
    slots = {req.uid: s for s, req in enumerate(eng.sched.slots)
             if req is not None}
    victim = eng._youngest_resident(exclude=-1)
    assert victim == slots[lo]  # batch absorbs preemption first
    # all-equal priorities: the original youngest-uid rule
    eng2 = _make_engine(cfg)
    a = eng2.submit([1, 2], max_new_tokens=4)
    b = eng2.submit([3, 4], max_new_tokens=4)
    eng2.sched.admit()
    slots2 = {req.uid: s for s, req in enumerate(eng2.sched.slots)
              if req is not None}
    assert eng2._youngest_resident(exclude=-1) == slots2[max(a, b)]
    eng.drain()
    eng2.drain()


def test_drained_replica_leaves_its_span_ring_beside_the_flight_recorder(
        tmp_path):
    """The serving counterpart of `Trainer._dump_postmortem`: a replica
    process that drains writes `spans-w<i>i<k>.jsonl` beside
    `flightrec-w<i>i<k>.jsonl`: the steps phase by phase, each call's
    size, each request's phases under its uid."""
    import json
    import os
    import subprocess
    import sys
    import time

    workdir = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "distributed_tensorflow_tpu.serve.replica",
         "--workdir", workdir, "--index", "0", "--slots", "2",
         "--block-size", "8", "--prefill-chunk", "8"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env)
    handle = sf.SubprocessReplica(proc, workdir, 0, 0)
    try:
        handle.send({"rid": 1, "prompt": list(range(1, 20)),
                     "max_new_tokens": 4})
        deadline = time.monotonic() + 120
        finished = False
        while not finished and time.monotonic() < deadline:
            assert proc.poll() is None, "the replica died"
            finished = any(e.get("kind") == "finish"
                           for e in handle.poll_output())
            time.sleep(0.05)
        assert finished
        handle.request_drain()
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
    assert os.path.exists(os.path.join(workdir, "flightrec-w0i0.jsonl"))
    with open(os.path.join(workdir, "spans-w0i0.jsonl")) as f:
        header, *spans = [json.loads(line) for line in f]
    assert header["spans"] == len(spans) and header["dropped"] == 0
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # 19 prompt tokens in chunks of 8: three calls, each with its size
    assert [s["attrs"]["q_tokens"] for s in by_name["serve.step.prefill"]] \
        == [8, 8, 3]
    assert all(s["attrs"]["kv_tokens"] <= s["attrs"]["kv_positions_walked"]
               for s in by_name["serve.step.decode"])
    uid = by_name["serve.step.prefill"][0]["key"]
    phases = [by_name[f"serve.request.{p}"][0]
              for p in ("queue", "prefill", "decode")]
    assert [p["key"] for p in phases] == [uid] * 3
    assert phases[0]["end"] == phases[1]["start"] \
        and phases[1]["end"] == phases[2]["start"]
