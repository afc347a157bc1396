"""serve/ subsystem tests: KV-cached decode parity against the uncached
forward (the numerics acceptance gate), scheduler invariants under a
randomized request stream, cache sharding specs, and sampling."""

import random
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu import serve
from distributed_tensorflow_tpu.models import transformer as tfm
from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
from distributed_tensorflow_tpu.serve import scheduler as sched_lib


def tiny_decoder(**kw):
    base = dict(
        vocab_size=128, max_len=96, num_layers=2, d_model=32, num_heads=4,
        d_ff=64, dropout=0.0, dtype="float32", causal=True, pre_ln=True,
    )
    base.update(kw)
    return tfm.TransformerConfig(**base)


@pytest.fixture(scope="module")
def decoder():
    cfg = tiny_decoder()
    model = tfm.Transformer(cfg)
    params, _ = tfm.make_init_fn(model, 8)(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# Numerics: cached decode == uncached forward
# ---------------------------------------------------------------------------


def test_cached_decode_matches_uncached_forward(decoder):
    """Acceptance gate: per-step cached logits match the uncached
    full-context forward to rtol 1e-4 AND the greedy token sequences are
    identical for >= 64 steps."""
    cfg, model, params = decoder
    prompt = [5, 17, 3, 99, 42, 7, 11]
    P_len, steps = len(prompt), 64

    cache = serve.init_cache(cfg, 1, dtype="float32")
    logits, cache = serve.prefill(
        model, params, cache, 0, jnp.asarray(prompt, jnp.int32), P_len
    )
    step = serve.jit_decode_step(model)
    cached_logits, toks = [logits], [int(jnp.argmax(logits))]
    written = P_len
    for _ in range(steps - 1):
        logits, cache = step(
            params, cache,
            jnp.asarray([toks[-1]], jnp.int32),
            jnp.asarray([written], jnp.int32),
        )
        written += 1
        cached_logits.append(logits[0])
        toks.append(int(jnp.argmax(logits[0])))

    # one uncached forward over prompt + all-but-last generated token:
    # position P-1+i predicts token i
    seq = jnp.asarray([prompt + toks[:-1]], jnp.int32)
    full = model.apply({"params": params}, seq)[0, P_len - 1:]
    np.testing.assert_allclose(
        np.stack([np.asarray(l) for l in cached_logits]), np.asarray(full),
        rtol=1e-4, atol=1e-5,
    )
    assert toks == [int(t) for t in jnp.argmax(full, -1)]


def test_prefill_bucket_invariance(decoder):
    """Padding the prompt to a larger bucket must not change the next-
    token logits or the written cache rows."""
    cfg, model, params = decoder
    prompt = jnp.asarray([9, 4, 77, 2, 60], jnp.int32)
    P_len = 5
    outs = []
    for bucket in (8, 16, 32):
        cache = serve.init_cache(cfg, 1, dtype="float32")
        toks = jnp.zeros(bucket, jnp.int32).at[:P_len].set(prompt)
        logits, cache = serve.prefill(
            model, params, cache, 0, toks, P_len
        )
        outs.append((np.asarray(logits), np.asarray(cache.k[:, :, :, :P_len])))
    for logits, krows in outs[1:]:
        np.testing.assert_allclose(logits, outs[0][0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(krows, outs[0][1], rtol=1e-5, atol=1e-6)


def test_engine_request_isolation(decoder):
    """Continuous batching must not leak state across slots: each
    request's greedy completion equals its solo-engine completion, even
    when requests queue and reuse slots."""
    cfg, _, params = decoder
    prompts = [[5, 17, 3], [88, 12, 61, 40, 2], [7], [33, 33, 9, 1]]

    solo = []
    for p in prompts:
        eng = serve.ServeEngine(cfg, params, num_slots=1)
        solo.append(list(eng.stream(p, max_new_tokens=12)))

    eng = serve.ServeEngine(cfg, params, num_slots=2)  # forces queueing
    uids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    done = eng.run()
    assert sorted(done) == sorted(uids)
    for uid, want in zip(uids, solo):
        assert done[uid].generated == want
        assert done[uid].finish_reason == sched_lib.FINISH_MAX_NEW


def test_engine_eos_and_max_len_eviction(decoder):
    """EOS stops a request the step it is sampled; a prompt near the
    cache budget finishes with the max_len reason and never writes out
    of bounds."""
    cfg, _, params = decoder
    # find a token the greedy stream actually emits, then replay with it
    # as the EOS id: the request must stop at its first occurrence
    probe = serve.ServeEngine(cfg, params, num_slots=1)
    toks = list(probe.stream([5, 17, 3], max_new_tokens=10))
    eos = toks[4]
    eng = serve.ServeEngine(cfg, params, num_slots=1)
    uid = eng.submit([5, 17, 3], max_new_tokens=50, eos_id=eos)
    done = eng.run()
    assert done[uid].finish_reason == sched_lib.FINISH_EOS
    assert done[uid].generated[-1] == eos
    assert eos not in done[uid].generated[:-1]

    long_prompt = list(range(1, cfg.max_len - 1))  # P = max_len - 2
    eng = serve.ServeEngine(cfg, params, num_slots=1)
    uid = eng.submit(long_prompt, max_new_tokens=50)
    done = eng.run()
    assert done[uid].finish_reason == sched_lib.FINISH_MAX_LEN
    # g_max: writing token g needs position P + g - 1 <= max_len - 1
    assert len(done[uid].generated) == cfg.max_len - len(long_prompt) + 1


# ---------------------------------------------------------------------------
# Scheduler invariants (no model, no device)
# ---------------------------------------------------------------------------


def test_scheduler_invariants_random_stream():
    """Randomized request stream, fixed seed: no slot leaks, FIFO
    admission, correct eviction reasons, full drain."""
    rng = random.Random(1234)
    num_slots, max_len = 4, 32
    s = sched_lib.Scheduler(num_slots, max_len)
    n_reqs = 40
    eos_id = 7
    uids = []
    for _ in range(n_reqs):
        plen = rng.randint(1, max_len)
        uids.append(s.submit(
            [rng.randrange(100) for _ in range(plen)],
            max_new_tokens=rng.randint(1, 12),
            eos_id=eos_id if rng.random() < 0.5 else None,
        ))
    assert uids == sorted(uids)  # uids are issued in submission order

    admitted_order = []
    for step in range(10_000):
        if not s.has_work:
            break
        placed = s.admit()
        admitted_order.extend(r.uid for _, r in placed)
        # FIFO + full occupancy: with work still queued, no slot is free
        if s.queue:
            assert s.occupancy == 1.0
        # no slot double-booking
        live = [r.uid for r in s.slots if r is not None]
        assert len(live) == len(set(live))
        for slot in s.active_slots():
            s.append_token(slot, rng.randrange(100))
    else:
        pytest.fail("scheduler did not drain")

    assert admitted_order == uids  # FIFO fairness
    assert not s.queue and s.active_slots() == []  # no slot leaks
    assert len(s.finished) == n_reqs
    assert sorted(s.finished) == uids  # keyed by uid, every request lands
    for r in s.finished.values():
        g, p = len(r.generated), len(r.prompt)
        assert 1 <= g <= r.max_new_tokens
        if r.finish_reason == sched_lib.FINISH_EOS:
            assert r.eos_id is not None and r.generated[-1] == r.eos_id
        elif r.finish_reason == sched_lib.FINISH_MAX_NEW:
            assert g == r.max_new_tokens
        elif r.finish_reason == sched_lib.FINISH_MAX_LEN:
            assert p + g > max_len and p + (g - 1) <= max_len
        else:
            pytest.fail(f"unknown finish reason {r.finish_reason}")


def test_scheduler_rejects_invalid():
    s = sched_lib.Scheduler(2, 16)
    with pytest.raises(ValueError):
        s.submit([])
    with pytest.raises(ValueError):
        s.submit(list(range(17)))  # prompt > max_len
    with pytest.raises(ValueError):
        s.submit([1], max_new_tokens=0)
    with pytest.raises(ValueError):
        s.submit([1], deadline_s=0.0)
    with pytest.raises(ValueError):
        s.append_token(0, 1)  # empty slot
    with pytest.raises(ValueError):
        sched_lib.Scheduler(2, 16, max_queue=0)


# ---------------------------------------------------------------------------
# Admission control: backpressure, deadlines, cancellation, drain
# ---------------------------------------------------------------------------


def test_scheduler_backpressure_and_fifo_across_rejections():
    """QueueFull rejection must not perturb the FIFO order of accepted
    requests, and capacity freed by admission is immediately usable."""
    s = sched_lib.Scheduler(1, 16, max_queue=2)
    a = s.submit([1])
    b = s.submit([2])
    with pytest.raises(sched_lib.QueueFull):
        s.submit([3])  # rejected — never enters the line
    placed = s.admit()  # a takes the slot, queue has room again
    assert [r.uid for _, r in placed] == [a]
    c = s.submit([4])
    s.append_token(0, 9)  # a decodes one token, stays resident
    assert s.cancel(a) is not None  # free the slot

    placed = s.admit()
    assert [r.uid for _, r in placed] == [b]
    assert list(r.uid for r in s.queue) == [c]  # FIFO preserved: b before c


def test_scheduler_deadline_timeout_queued_and_resident():
    from distributed_tensorflow_tpu.resilience import FaultClock

    clk = FaultClock()
    s = sched_lib.Scheduler(1, 16, clock=clk)
    res = s.submit([1], max_new_tokens=8, deadline_s=5.0)
    qd = s.submit([2], deadline_s=1.0)
    nodeadline = s.submit([3])
    assert s.admit()[0][1].uid == res
    assert s.expire() == []  # nothing due yet
    clk.advance(2.0)  # past qd's deadline, not res's
    evicted = s.expire()
    assert [r.uid for r in evicted] == [qd]
    assert s.finished[qd].finish_reason == sched_lib.FINISH_TIMEOUT
    assert s.finished[qd].t_finish == 2.0 and s.finished[qd].t_admit is None
    clk.advance(4.0)  # now res (resident) is past its deadline
    evicted = s.expire()
    assert [r.uid for r in evicted] == [res]
    assert s.finished[res].finish_reason == sched_lib.FINISH_TIMEOUT
    assert s.slots == [None]  # slot freed for the no-deadline request
    assert s.admit()[0][1].uid == nodeadline


def test_scheduler_cancel_everywhere_idempotent():
    s = sched_lib.Scheduler(1, 16)
    a = s.submit([1], max_new_tokens=4)
    b = s.submit([2])
    s.admit()
    s.append_token(0, 7)  # a has one token in flight
    got = s.cancel(a)  # resident cancel frees the slot, keeps the token
    assert got is not None and got.finish_reason == sched_lib.FINISH_CANCELLED
    assert got.generated == [7] and s.slots == [None]
    got = s.cancel(b)  # queued cancel: never admitted
    assert got is not None and got.t_admit is None
    assert s.cancel(a) is None and s.cancel(b) is None  # idempotent
    assert s.cancel(12345) is None  # unknown uid
    assert not s.has_work and sorted(s.finished) == [a, b]


def test_scheduler_close_stops_admission_cancels_queue():
    s = sched_lib.Scheduler(1, 16)
    a = s.submit([1], max_new_tokens=2)
    b = s.submit([2])
    c = s.submit([3])
    s.admit()
    cancelled = s.close()
    assert [r.uid for r in cancelled] == [b, c]
    assert all(r.finish_reason == sched_lib.FINISH_CANCELLED for r in cancelled)
    with pytest.raises(sched_lib.SchedulerClosed):
        s.submit([4])
    assert s.close() == []  # idempotent
    # the resident request still decodes to completion
    s.append_token(0, 1)
    done = s.append_token(0, 2)
    assert done is not None and done.uid == a
    assert done.finish_reason == sched_lib.FINISH_MAX_NEW
    assert not s.has_work


def test_scheduler_invariants_chaos_stream():
    """Randomized stream with deadlines, cancels, and backpressure
    interleaved with token-driven evictions: no slot leaks, admissions
    stay FIFO, every accepted request lands in finished exactly once
    with a coherent reason."""
    from distributed_tensorflow_tpu.resilience import FaultClock

    rng = random.Random(20260803)
    clk = FaultClock()
    num_slots, max_len = 3, 24
    s = sched_lib.Scheduler(num_slots, max_len, clock=clk, max_queue=6)
    accepted, rejected, cancelled_by_us = [], 0, set()
    admitted_order = []

    for step in range(4000):
        # bursty arrivals so the bounded queue actually overflows
        for _ in range(rng.randint(0, 5) if len(accepted) < 120 else 0):
            try:
                accepted.append(s.submit(
                    [rng.randrange(50) for _ in range(rng.randint(1, max_len))],
                    max_new_tokens=rng.randint(1, 6),
                    eos_id=7 if rng.random() < 0.3 else None,
                    deadline_s=rng.uniform(0.5, 5.0)
                    if rng.random() < 0.4 else None,
                ))
            except sched_lib.QueueFull:
                rejected += 1
        if rng.random() < 0.1 and accepted:
            victim = rng.choice(accepted)
            if s.cancel(victim) is not None:
                cancelled_by_us.add(victim)
        clk.advance(rng.uniform(0.0, 0.5))
        s.expire()
        admitted_order.extend(r.uid for _, r in s.admit())
        if s.queue:
            assert s.occupancy == 1.0  # a backlog leaves no slot free
        live = [r.uid for r in s.slots if r is not None]
        assert len(live) == len(set(live))  # no double-booking
        for slot in s.active_slots():
            s.append_token(slot, rng.randrange(50))
        if len(accepted) >= 120 and not s.has_work:
            break
    assert not s.has_work, "chaos stream did not drain"

    assert rejected > 0, "stream never hit backpressure — weak test"
    assert cancelled_by_us and admitted_order
    assert admitted_order == sorted(admitted_order)  # FIFO survives chaos
    assert sorted(s.finished) == sorted(accepted)  # all land exactly once
    reasons = {r.finish_reason for r in s.finished.values()}
    assert reasons <= set(sched_lib.FINISH_REASONS)
    assert sched_lib.FINISH_TIMEOUT in reasons
    assert sched_lib.FINISH_CANCELLED in reasons
    for r in s.finished.values():
        if r.finish_reason == sched_lib.FINISH_TIMEOUT:
            assert r.t_deadline is not None and r.t_finish >= r.t_deadline
        elif r.finish_reason == sched_lib.FINISH_CANCELLED:
            assert r.uid in cancelled_by_us
        else:
            assert r.t_admit is not None  # token-driven finishes were resident


# ---------------------------------------------------------------------------
# Engine-level admission control + telemetry invariant
# ---------------------------------------------------------------------------


def _finished_totals(reg):
    return {
        dict(m.labels)["reason"]: int(m.value)
        for m in reg.collect() if m.name == "serve_finished_total"
    }


def _assert_telemetry_invariant(eng, expect_finished):
    """The PR-2 acceptance gate, extended over the new eviction paths:
    every finished request — including timeout/cancelled — contributes
    exactly one TTFT and one TPOT observation."""
    reg = eng.registry
    total = sum(_finished_totals(reg).values())
    assert total == expect_finished
    assert reg.get("serve_ttft_seconds").count == total
    assert reg.get("serve_tpot_seconds").count == total


def test_engine_timeout_and_cancel_telemetry(decoder):
    from distributed_tensorflow_tpu.resilience import FaultClock

    cfg, _, params = decoder
    clk = FaultClock()
    eng = serve.ServeEngine(cfg, params, num_slots=1, clock=clk)
    a = eng.submit([5, 17, 3], max_new_tokens=4)
    b = eng.submit([9, 9], max_new_tokens=4, deadline_s=1.0)  # starves in queue
    c = eng.submit([4, 4], max_new_tokens=4)
    eng.step()  # a prefills + decodes; b, c wait
    clk.advance(2.0)
    stats = eng.step()  # b times out before ever taking the slot
    assert b in stats.finished
    assert eng.cancel(c) is True and eng.cancel(c) is False
    done = eng.run()
    assert done[a].finish_reason == sched_lib.FINISH_MAX_NEW
    assert done[b].finish_reason == sched_lib.FINISH_TIMEOUT
    assert done[b].generated == [] and done[b].t_admit is None
    assert done[c].finish_reason == sched_lib.FINISH_CANCELLED
    totals = _finished_totals(eng.registry)
    assert totals[sched_lib.FINISH_TIMEOUT] == 1
    assert totals[sched_lib.FINISH_CANCELLED] == 1
    _assert_telemetry_invariant(eng, 3)


def test_engine_cancel_resident_frees_slot(decoder):
    cfg, _, params = decoder
    eng = serve.ServeEngine(cfg, params, num_slots=1)
    a = eng.submit([5, 17, 3], max_new_tokens=50)
    bquiet = eng.submit([8, 1], max_new_tokens=3)
    eng.step()
    eng.step()  # a is mid-decode with a couple of tokens out
    assert eng.cancel(a) is True
    assert eng.sched.active_slots() == []  # slot freed immediately
    done = eng.run()  # bquiet takes the slot and completes
    assert done[a].finish_reason == sched_lib.FINISH_CANCELLED
    assert len(done[a].generated) >= 1  # delivered tokens are kept
    assert done[bquiet].finish_reason == sched_lib.FINISH_MAX_NEW
    _assert_telemetry_invariant(eng, 2)


def test_engine_drain_graceful_shutdown(decoder):
    cfg, _, params = decoder
    eng = serve.ServeEngine(cfg, params, num_slots=1, max_queue=4)
    a = eng.submit([5, 17, 3], max_new_tokens=3)
    b = eng.submit([2, 2], max_new_tokens=3)
    eng.step()  # a resident, b queued
    done = eng.drain()
    assert done[a].finish_reason == sched_lib.FINISH_MAX_NEW  # finished, not killed
    assert done[b].finish_reason == sched_lib.FINISH_CANCELLED  # never ran
    with pytest.raises(sched_lib.SchedulerClosed):
        eng.submit([1])
    assert eng.registry.get("serve_occupancy").value == 0.0
    assert not eng.sched.has_work and eng.sched.finished == {}  # flushed
    _assert_telemetry_invariant(eng, 2)


def test_stream_survives_concurrent_drain(decoder):
    """A stream() consumer mid-iteration when drain() shuts the engine
    down must still receive every token drain() decoded for its request
    — not KeyError after the finished map is handed over."""
    cfg, _, params = decoder
    eng = serve.ServeEngine(cfg, params, num_slots=1)
    it = eng.stream([5, 17, 3], max_new_tokens=5)
    first = next(it)
    done = eng.drain()  # finishes the resident streamed request
    assert len(done) == 1
    req = next(iter(done.values()))
    assert req.finish_reason == sched_lib.FINISH_MAX_NEW
    assert [first] + list(it) == req.generated  # full delivery, no KeyError


def test_engine_deadline_mid_decode_eviction(decoder):
    """FINISH_TIMEOUT for a RESIDENT request: the deadline passes while
    it is decoding; the next step evicts it before more tokens land."""
    from distributed_tensorflow_tpu.resilience import FaultClock

    cfg, _, params = decoder
    clk = FaultClock()
    eng = serve.ServeEngine(cfg, params, num_slots=1, clock=clk)
    a = eng.submit([5, 17, 3], max_new_tokens=50, deadline_s=3.0)
    eng.step()
    g_before = len(eng.sched.slots[0].generated)
    clk.advance(5.0)
    stats = eng.step()
    assert a in stats.finished and stats.decoded_slots == 0
    done = eng.run()
    assert done[a].finish_reason == sched_lib.FINISH_TIMEOUT
    assert len(done[a].generated) == g_before  # nothing delivered post-deadline
    _assert_telemetry_invariant(eng, 1)


# ---------------------------------------------------------------------------
# Paged KV cache (docs/serving.md "Paged KV cache"): block allocator
# invariants, paged/dense parity, prefix reuse + copy-on-write, chunked
# prefill interleave, block-gated admission + preemption
# ---------------------------------------------------------------------------


def _paged_engine(cfg, params, **kw):
    base = dict(paged=True, block_size=8, prefill_chunk=8)
    base.update(kw)
    return serve.ServeEngine(cfg, params, **base)


def test_block_allocator_invariants():
    """Pure host-side accounting: used + free == pool size through
    alloc/incref/decref, refcount errors raise, LRU prefix-cache
    eviction frees exactly the cache-only blocks, flush returns the
    allocator to all-free."""
    a = serve.BlockAllocator(4, block_size=4)
    assert a.blocks_free == 4 and a.blocks_in_use == 0
    b0, b1 = a.alloc(), a.alloc()
    assert (a.blocks_in_use, a.blocks_free) == (2, 2)
    a.incref(b0)
    assert not a.decref(b0) and a.refcount(b0) == 1
    assert a.decref(b0) and a.blocks_free == 3
    with pytest.raises(ValueError):
        a.decref(b0)  # already free
    with pytest.raises(ValueError):
        a.incref(b0)  # can't revive a free block

    # register a 2-block prefix: one full block (cached, +1 ref) and a
    # partial tail (weak, no ref)
    toks = tuple(range(6))  # 4 full + 2 tail at block_size=4
    b2 = a.alloc()
    a.register_prefix(toks, [b1, b2])
    assert a.refcount(b1) == 2 and a.refcount(b2) == 1
    blocks, matched = a.match_prefix(toks)
    assert blocks == [b1, b2] and matched == 6
    assert a.refcount(b1) == 3 and a.refcount(b2) == 2
    for bid in blocks:
        a.decref(bid)
    # the original owner releases; the cache still pins the full block
    a.decref(b1), a.decref(b2)
    assert a.refcount(b1) == 1 and a.refcount(b2) == 0
    assert a.evictable() == 1
    # exhaust the pool: alloc must evict the cached block, not raise
    got = [a.alloc() for _ in range(a.blocks_free + 1)]
    assert a.blocks_free == 0 and a.evictions == 1 and len(got) == 4
    with pytest.raises(serve.NoFreeBlocks):
        a.alloc()
    # a freed-then-reallocated block's weak partial entry is stale
    assert a.match_prefix(toks) == ([], 0)
    for bid in got:
        a.decref(bid)
    assert a.flush_prefix_cache() == 0  # cache was already evicted
    assert a.blocks_free == 4
    assert all(a.refcount(i) == 0 for i in range(4))


def test_block_allocator_partial_entries_bounded_and_longest_match():
    """Weak partial-tail entries pick the LONGEST matching candidate,
    and the map sweeps stale entries so host memory stays bounded even
    for prompts nobody ever repeats."""
    a = serve.BlockAllocator(4, block_size=8)
    b0, b1 = a.alloc(), a.alloc()
    a.register_prefix((1, 2), [b0])          # tail candidate: 2 tokens
    a.register_prefix((1, 5, 6, 7), [b1])    # same first token, 4 tokens
    blocks, matched = a.match_prefix((1, 5, 6, 7, 8))
    assert blocks == [b1] and matched == 4   # longest match, not first
    a.decref(b1)

    bound = max(64, 2 * a.num_blocks)
    for i in range(3 * bound):
        bid = a.alloc()
        a.register_prefix((1000 + i, 1001 + i), [bid])
        a.decref(bid)  # freed immediately: the entry is instantly stale
    assert sum(len(c) for c in a._partial.values()) <= bound + 1


def test_block_allocator_note_write_invalidates_overwritten_tail():
    """A divergent in-place write into a registered partial-tail block
    must kill the weak entry: a later identical prompt would otherwise
    map K/V that no longer holds the registered content."""
    a = serve.BlockAllocator(4, block_size=4)
    b0 = a.alloc()
    a.register_prefix((1, 2), [b0])  # tail content (1, 2) at offsets 0-1
    # sole owner appends at offset 2 (past the registered fill): valid
    a.note_write(b0, 2)
    blocks, matched = a.match_prefix((1, 2, 9))
    assert blocks == [b0] and matched == 2
    for bid in blocks:
        a.decref(bid)
    # sole owner REWRITES offset 1 in place (divergence): entry dies
    a.note_write(b0, 1)
    assert a.match_prefix((1, 2, 9)) == ([], 0)
    a.decref(b0)
    assert a.blocks_free == 4


@pytest.mark.parametrize("paged_impl", ["gather", "fused"])
def test_paged_greedy_parity_with_dense(decoder, paged_impl):
    """Acceptance gate: 64-step greedy decode through the paged path
    (chunked prefill + either XLA form of attention over the block
    table) is token-identical to the dense slot cache, which is itself
    logit-checked against the uncached forward above — both paths
    exercised on the same params, and the drained engine is all-free."""
    cfg, _, params = decoder
    prompt = [5, 17, 3, 99, 42, 7, 11]
    dense = serve.ServeEngine(cfg, params, num_slots=1, paged=False)
    want = list(dense.stream(prompt, max_new_tokens=64))
    paged = _paged_engine(cfg, params, num_slots=1, paged_impl=paged_impl)
    got = list(paged.stream(prompt, max_new_tokens=64))
    assert len(want) == 64 and got == want
    paged.drain()
    assert paged.alloc.blocks_free == paged.cache.num_blocks

    # a long prompt (multiple chunks) must agree too
    long_prompt = [(7 * i + 3) % cfg.vocab_size for i in range(40)]
    dense = serve.ServeEngine(cfg, params, num_slots=1, paged=False)
    want = list(dense.stream(long_prompt, max_new_tokens=24))
    paged = _paged_engine(cfg, params, num_slots=1, paged_impl=paged_impl)
    got = list(paged.stream(long_prompt, max_new_tokens=24))
    assert got == want


@pytest.mark.parametrize("paged", [True, False])
def test_engine_request_isolation_both_paths(decoder, paged):
    """Slot reuse must not leak state across requests on either cache
    layout: each request's greedy completion equals its solo run."""
    cfg, _, params = decoder
    prompts = [[5, 17, 3], [88, 12, 61, 40, 2], [7], [33, 33, 9, 1]]
    solo = []
    for p in prompts:
        eng = serve.ServeEngine(cfg, params, num_slots=1, paged=paged)
        solo.append(list(eng.stream(p, max_new_tokens=12)))
    eng = serve.ServeEngine(cfg, params, num_slots=2, paged=paged)
    uids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    done = eng.run()
    for uid, want in zip(uids, solo):
        assert done[uid].generated == want


def test_paged_prefix_reuse_and_cow(decoder):
    """Requests sharing a prompt map the same physical blocks (reuse
    hits > 0, strictly lower peak block usage than reuse disabled), the
    first divergent write triggers a copy-on-write block copy, and the
    shared path stays token-identical to the solo run."""
    cfg, _, params = decoder
    sys_prefix = list(range(1, 25))  # 3 full blocks at block_size=8
    warm = sys_prefix + [50]

    def drive(reuse):
        eng = _paged_engine(cfg, params, num_slots=4, prefix_reuse=reuse)
        for _ in eng.stream(warm, max_new_tokens=4):
            pass  # warm request registers the prefix (when enabled)
        uids = [eng.submit(sys_prefix + [60 + i], max_new_tokens=6)
                for i in range(4)]
        peak = 0
        while eng.sched.has_work:
            eng.step()
            peak = max(peak, eng.alloc.blocks_in_use)
        done = eng.sched.drain_finished()
        outs = [done[u].generated for u in uids]
        hits = int(eng.registry.get("prefix_reuse_hits_total").value)
        eng.drain()
        assert eng.alloc.blocks_free == eng.cache.num_blocks  # no leaks
        return outs, peak, hits

    outs_on, peak_on, hits_on = drive(True)
    outs_off, peak_off, hits_off = drive(False)
    assert outs_on == outs_off  # sharing must not change a single token
    assert hits_on > 0 and hits_off == 0
    assert peak_on < peak_off  # strictly lower block usage

    # copy-on-write: an identical prompt maps the sharer's partially
    # filled tail block; the first divergent write must copy it
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]  # 1 full block + 2-token tail
    solo = serve.ServeEngine(cfg, params, num_slots=1, paged=False)
    want = list(solo.stream(prompt, max_new_tokens=12))
    eng = _paged_engine(cfg, params, num_slots=2)
    a = eng.submit(prompt, max_new_tokens=12)
    eng.step(), eng.step()  # A prefilled + registered, mid-decode
    b = eng.submit(prompt, max_new_tokens=12)
    done = eng.run()
    assert eng.alloc.cow_copies >= 1
    assert done[a].generated == want and done[b].generated == want
    eng.drain()
    assert eng.alloc.blocks_free == eng.cache.num_blocks
    assert all(eng.alloc.refcount(i) == 0
               for i in range(eng.cache.num_blocks))


@pytest.mark.parametrize("paged_impl", ["fused", "pallas"])
def test_paged_unaligned_first_chunk_behind_a_partial_tail_match(
        decoder, paged_impl):
    """A request admitted behind a partial-tail prefix match starts its
    prefill inside a block (the first chunk writes two blocks from row 3 of
    the first, a copy of the sharer's), and is token-identical to the dense
    oracle, through the scatter and through the in-place write kernel (the
    interpreter here)."""
    from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder

    cfg, _, params = decoder
    first = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9]      # 1 block + 5 tail
    second = first[:11] + [(11 * i + 2) % cfg.vocab_size for i in range(19)]
    want = []
    for prompt in (first, second):
        dense = serve.ServeEngine(cfg, params, num_slots=1, paged=False)
        want.append(list(dense.stream(prompt, max_new_tokens=10)))
    rec = FlightRecorder(capacity=256)
    eng = _paged_engine(cfg, params, num_slots=2, paged_impl=paged_impl,
                        flightrec=rec)
    a = eng.submit(first, max_new_tokens=10)
    eng.step(), eng.step(), eng.step()  # prefilled, registered, decoding
    b = eng.submit(second, max_new_tokens=10)
    done = eng.run()
    chunks = [e for e in rec.events() if e["kind"] == "serve_prefill_chunk"
              and e["uid"] == b]
    assert [c["start"] for c in chunks] == [11, 19, 27]
    assert eng.alloc.cow_copies >= 1
    assert [done[a].generated, done[b].generated] == want
    eng.drain()
    assert eng.alloc.blocks_free == eng.cache.num_blocks


def test_paged_chunked_prefill_interleaves_decode(decoder):
    """A long prompt prefills in fixed-size chunks interleaved with
    decode: the resident request gains one token EVERY step of the long
    prefill (TTFT of residents is bounded by one chunk), and the chunk
    events land in the flight recorder."""
    from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder

    cfg, _, params = decoder
    rec = FlightRecorder(capacity=256)
    eng = _paged_engine(cfg, params, num_slots=2, flightrec=rec)
    short = eng.submit([5, 17, 3], max_new_tokens=40)
    eng.step()  # short is resident and decoding
    short_req = eng.sched.slots[eng.sched.active_slots()[0]]
    assert short_req.uid == short
    eng.submit(list(range(1, 65)), max_new_tokens=4)  # 64 tokens: 8 chunks
    chunk_steps = 0
    while True:
        before = len(short_req.generated)
        stats = eng.step()
        if stats.prefill_chunks == 0:
            break  # the long prefill completed on an earlier step
        assert stats.prefill_chunks == 1  # one chunk per pending slot
        assert stats.decoded_slots >= 1  # decode ran in the SAME step
        assert len(short_req.generated) == before + 1  # no starvation
        chunk_steps += 1
    assert chunk_steps == 8  # ceil(64 / prefill_chunk=8)
    # 9 total: the short prompt's own prefill was one chunk too
    assert int(eng.registry.get("prefill_chunks_total").value) == 9
    kinds = [e["kind"] for e in rec.events()]
    assert kinds.count("serve_prefill_chunk") == 9
    eng.drain()
    assert eng.alloc.blocks_free == eng.cache.num_blocks


def test_paged_admission_gated_on_blocks(decoder):
    """Admission is gated on free KV blocks, not free slots: with a
    tight pool a queued request waits even though a slot is empty, and
    is admitted once blocks come home."""
    cfg, _, params = decoder
    # pool of 4 blocks = 32 tokens; max_len=32 so MB=4 (one request may
    # need the whole pool)
    eng = _paged_engine(cfg, params, num_slots=2, max_len=32,
                        num_blocks=4, prefix_reuse=False)
    a = eng.submit([1] * 20, max_new_tokens=4)  # needs 3 blocks
    for _ in range(3):  # 3 chunks: a fully prefilled, holds 3 blocks
        eng.step()
    b = eng.submit([2] * 20, max_new_tokens=4)  # needs 3 more: gated
    stats = eng.step()
    assert stats.admitted == 0  # a slot is free, but the pool is not
    assert eng.sched.slots[1] is None and eng.sched.queue
    done = eng.run()  # a finishes, blocks free, b admits and finishes
    assert done[a].finish_reason == sched_lib.FINISH_MAX_NEW
    assert done[b].finish_reason == sched_lib.FINISH_MAX_NEW
    eng.drain()
    assert eng.alloc.blocks_free == 4

    with pytest.raises(ValueError):
        _paged_engine(cfg, params, max_len=32, num_blocks=3)  # < MB

    # the gate caps its demand at max_len: a full-context prompt (legal;
    # finishes at its first token via max_len) must ADMIT, not wedge the
    # queue head forever asking for ceil((max_len+1)/bs) blocks
    eng = _paged_engine(cfg, params, num_slots=1, max_len=32, num_blocks=4)
    uid = eng.submit([3] * 32, max_new_tokens=8)
    done = eng.run()
    assert done[uid].finish_reason == sched_lib.FINISH_MAX_LEN
    assert len(done[uid].generated) == 1
    eng.drain()
    assert eng.alloc.blocks_free == 4


def test_paged_fully_cached_prompt_never_deadlocks(decoder):
    """When a finished prompt's blocks fill the ENTIRE pool as cache
    entries, resubmitting that exact prompt must still admit and finish
    (evict-matched gate fallback + in-place un-cache when the COW copy
    cannot be allocated) — not wedge the queue head forever."""
    cfg, _, params = decoder
    eng = _paged_engine(cfg, params, num_slots=1, max_len=32, num_blocks=4)
    prompt = [5] * 32  # exactly max_len: 4 full blocks = the whole pool
    a = eng.submit(prompt, max_new_tokens=4)
    done1 = eng.run()
    assert done1[a].finish_reason == sched_lib.FINISH_MAX_LEN
    b = eng.submit(prompt, max_new_tokens=4)
    for _ in range(50):
        eng.step()
        if b in eng.sched.finished:
            break
    else:
        pytest.fail("fully-cached prompt was never admitted (gate wedge)")
    done2 = eng.sched.drain_finished()
    assert done2[b].generated == done1[a].generated
    eng.drain()
    assert eng.alloc.blocks_free == 4


def test_paged_preemption_exact_parity(decoder):
    """Block exhaustion mid-decode preempts the youngest resident back
    to the queue head; it re-prefills prompt + generated and finishes
    with EXACTLY the tokens an uncontended engine produces."""
    cfg, _, params = decoder

    def drive(num_blocks):
        eng = _paged_engine(cfg, params, num_slots=2, max_len=32,
                            num_blocks=num_blocks, prefix_reuse=False)
        uids = [eng.submit([10 + i] * 10, max_new_tokens=20)
                for i in range(3)]
        done = eng.run()
        outs = [done[u].generated for u in uids]
        pre = sum(done[u].preemptions for u in uids)
        eng.drain()
        assert eng.alloc.blocks_free == eng.cache.num_blocks
        return outs, pre

    ample, pre_ample = drive(8)
    tight, pre_tight = drive(5)
    assert pre_ample == 0 and pre_tight > 0
    assert ample == tight  # preemption is invisible in the tokens


def test_paged_block_accounting_chaos(decoder):
    """The block-accounting invariant under a chaotic stream (mixed
    lengths, shared prefixes, deadlines, cancels, preemption pressure):
    used + free == pool size at EVERY step, and every eviction path —
    finish, timeout, cancel, drain/close — returns its blocks."""
    from distributed_tensorflow_tpu.resilience import FaultClock

    cfg, _, params = decoder
    rng = random.Random(20260804)
    clk = FaultClock()
    eng = _paged_engine(cfg, params, num_slots=3, max_len=48,
                        num_blocks=10, max_queue=8, clock=clk)
    shared = [7, 8, 9, 10, 11, 12, 13, 14, 15, 16]
    submitted: list[int] = []
    for step in range(400):
        for _ in range(rng.randint(0, 2) if len(submitted) < 40 else 0):
            plen = rng.choice([3, 9, 18, 30])
            prompt = (shared[:8] + [rng.randrange(100)] * (plen - 8)
                      if plen > 8 and rng.random() < 0.5
                      else [rng.randrange(100) for _ in range(plen)])
            try:
                submitted.append(eng.submit(
                    prompt, max_new_tokens=rng.randint(1, 8),
                    deadline_s=rng.uniform(0.5, 4.0)
                    if rng.random() < 0.3 else None,
                ))
            except sched_lib.QueueFull:
                pass
        if submitted and rng.random() < 0.1:
            eng.cancel(rng.choice(submitted))
        clk.advance(rng.uniform(0.0, 0.4))
        eng.step()
        a = eng.alloc
        assert a.blocks_in_use + a.blocks_free == a.num_blocks
        assert all(a.refcount(i) >= 0 for i in range(a.num_blocks))
        if len(submitted) >= 40 and not eng.sched.has_work:
            break
    assert not eng.sched.has_work, "chaos stream did not drain"
    eng.drain()
    assert eng.alloc.blocks_free == eng.alloc.num_blocks  # zero leaks
    assert all(eng.alloc.refcount(i) == 0
               for i in range(eng.alloc.num_blocks))
    # telemetry invariant survives the paged refactor: one TTFT + one
    # TPOT observation per finished request, whatever evicted it
    _assert_telemetry_invariant(
        eng, sum(_finished_totals(eng.registry).values()))


# ---------------------------------------------------------------------------
# Speculative decoding (PR 20)
# ---------------------------------------------------------------------------


def test_block_allocator_release_tail():
    """Speculation rollback is a refcount edit, never a copy:
    ``release_tail`` frees exactly the blocks past ``keep``, trims the
    owner's list in place, is a no-op when nothing hangs over, and the
    double-free tripwire still fires on a rolled-back block."""
    a = serve.BlockAllocator(6, block_size=4)
    blocks = [a.alloc() for _ in range(4)]
    dropped = blocks[2:]
    a.release_tail(blocks, keep=2)
    assert len(blocks) == 2
    assert a.blocks_in_use == 2 and a.blocks_free == 4
    for bid in dropped:
        assert a.refcount(bid) == 0
        with pytest.raises(ValueError):
            a.decref(bid)  # rollback already freed it
    a.release_tail(blocks, keep=2)  # nothing hangs over: no-op
    assert a.blocks_in_use == 2
    with pytest.raises(ValueError):
        a.release_tail(blocks, keep=-1)
    # a tail block with a second holder survives the rollback: only THIS
    # owner's reference is dropped
    shared = blocks[1]
    a.incref(shared)
    a.release_tail(blocks, keep=1)
    assert blocks == blocks[:1] and a.refcount(shared) == 1
    assert a.blocks_in_use == 2  # blocks[0] + the still-held tail
    a.decref(shared), a.decref(blocks[0])
    assert a.blocks_free == 6


def test_spec_engine_validation(decoder):
    """Speculation requires the paged path (rollback is a block-table
    edit) and sane knobs — misconfigurations fail at construction."""
    cfg, _, params = decoder
    with pytest.raises(ValueError, match="paged"):
        serve.ServeEngine(cfg, params, num_slots=1, paged=False, spec_k=2)
    with pytest.raises(ValueError, match="spec_k"):
        _paged_engine(cfg, params, num_slots=1, spec_k=-1)
    with pytest.raises(ValueError, match="spec_ngram"):
        _paged_engine(cfg, params, num_slots=1, spec_k=2, spec_ngram=0)


@pytest.mark.parametrize("paged_impl", ["fused", "pallas"])
def test_spec_greedy_exact_parity(decoder, paged_impl):
    """Acceptance gate: greedy streams with speculative decoding on are
    BIT-IDENTICAL to the non-spec paged engine (itself parity-gated
    against dense above), short and multi-chunk-long prompts — rejected
    drafts roll back without a trace, accepted ones are the same tokens
    the target would have emitted one step at a time. Through the scatter
    and through the write kernel (the interpreter here), which takes the
    K + 1 rows of a verify from wherever the slot's frontier stands."""
    cfg, _, params = decoder
    prompts = [
        [5, 17, 3, 99, 42, 7, 11],
        [(7 * i + 3) % cfg.vocab_size for i in range(40)],  # 5 chunks
    ]
    for prompt in prompts:
        plain = _paged_engine(cfg, params, num_slots=1)
        want = list(plain.stream(prompt, max_new_tokens=48))
        spec = _paged_engine(cfg, params, num_slots=1, spec_k=4,
                             paged_impl=paged_impl)
        got = list(spec.stream(prompt, max_new_tokens=48))
        assert got == want
        spec.drain()
        assert spec.alloc.blocks_free == spec.cache.num_blocks


def test_spec_telemetry_and_flightrec(decoder):
    """Observability closes over speculation: proposed/accepted counters
    add up, the acceptance-rate gauge is their ratio, every verify step
    lands a ``serve_spec_step`` event, per-request ``spec_accepted``
    sums to the counter — and the PR-2 invariant holds under MULTI-token
    steps: exactly one TTFT and one TPOT observation per finished
    request (TPOT normalizes by tokens delivered, not steps)."""
    from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder

    cfg, _, params = decoder
    rec = FlightRecorder(capacity=512)
    eng = _paged_engine(cfg, params, num_slots=2, spec_k=4, flightrec=rec)
    # a highly repetitive prompt: the n-gram drafter should land several
    # multi-token acceptances, exercising multi-token delivery
    uids = [eng.submit([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=16)
            for _ in range(2)]
    done = eng.run()
    reg = eng.registry
    prop = int(reg.get("spec_tokens_proposed_total").value)
    acc = int(reg.get("spec_tokens_accepted_total").value)
    assert prop > 0 and 0 <= acc <= prop
    assert reg.get("spec_acceptance_rate").value == pytest.approx(acc / prop)
    evs = [e for e in rec.events() if e["kind"] == "serve_spec_step"]
    assert evs and all(0 <= e["accepted"] <= e["proposed"] for e in evs)
    assert sum(e["proposed"] for e in evs) == prop
    assert sum(e["accepted"] for e in evs) == acc
    assert sum(done[u].spec_accepted for u in uids) == acc
    assert all(len(done[u].generated) == 16 for u in uids)
    _assert_telemetry_invariant(eng, 2)
    eng.drain()
    assert eng.alloc.blocks_free == eng.cache.num_blocks


def test_spec_preemption_and_rollback_block_accounting(decoder):
    """The PR-13 accounting invariant extended over speculation: with a
    tight pool forcing preemption AND rejected drafts forcing rollback,
    used + free == pool size at EVERY step, the drain leaves the
    allocator all-free, and the greedy tokens still match the
    uncontended non-spec run exactly."""
    cfg, _, params = decoder

    def drive(num_blocks, spec_k):
        eng = _paged_engine(cfg, params, num_slots=2, max_len=32,
                            num_blocks=num_blocks, prefix_reuse=False,
                            spec_k=spec_k)
        uids = [eng.submit([10 + i] * 10, max_new_tokens=20)
                for i in range(3)]
        while eng.sched.has_work:
            eng.step()
            a = eng.alloc
            assert a.blocks_in_use + a.blocks_free == a.num_blocks
            assert all(a.refcount(i) >= 0 for i in range(a.num_blocks))
        done = eng.sched.drain_finished()
        outs = [done[u].generated for u in uids]
        pre = sum(done[u].preemptions for u in uids)
        eng.drain()
        assert eng.alloc.blocks_free == eng.cache.num_blocks
        assert all(eng.alloc.refcount(i) == 0
                   for i in range(eng.cache.num_blocks))
        return outs, pre

    plain, _ = drive(8, spec_k=0)
    ample, _ = drive(8, spec_k=4)
    tight, pre_tight = drive(5, spec_k=4)
    assert ample == plain  # speculation is invisible in greedy tokens
    assert tight == plain  # ... even under preemption pressure
    assert pre_tight > 0


def test_spec_sample_matches_target_distribution():
    """The acceptance rule is distribution-preserving: over many trials
    the first emitted token's empirical distribution matches straight
    temperature sampling from the target row — whether the deterministic
    draft is the target's most- or least-likely token. (Accept d with
    p(d), else resample the renormalized residual: the marginal is p.)"""
    from distributed_tensorflow_tpu.serve import sampling

    rng = np.random.default_rng(20260807)
    logits = np.asarray([[2.0, 1.0, 0.0, -1.0]] * 2)
    temperature = 0.8
    p = np.exp(logits[0] / temperature)
    p /= p.sum()
    n = 20000
    for draft_tok in (0, 3):
        counts = np.zeros(4)
        for _ in range(n):
            emitted, _ = sampling.spec_verify_sample(
                logits, [draft_tok], rng, temperature=temperature)
            counts[emitted[0]] += 1
        np.testing.assert_allclose(counts / n, p, atol=0.02)


def test_paged_cache_specs_follow_sharding_rules():
    """The pool shards heads over `model` like the dense cache; the
    blocks dim is replicated (blocks are shared across requests, so
    they must not scatter over the batch axes)."""
    spec = serve.paged_cache_specs()
    assert spec.k == P(None, None, "model", None, None)
    assert spec.v == spec.k


# ---------------------------------------------------------------------------
# Cache sharding + sampling
# ---------------------------------------------------------------------------


def test_cache_specs_follow_sharding_rules():
    """The cache pytree shards by the same logical rules as the model:
    heads over `model`, slots over the batch axes (docs/serving.md)."""
    spec = serve.cache_specs()
    assert spec.k == P(None, ("data", "fsdp"), "model", None, None)
    assert spec.v == spec.k

    cfg = tiny_decoder()
    mesh = build_mesh(MeshSpec(data=2, fsdp=2, model=2))
    cache = serve.init_cache(cfg, num_slots=4, dtype="float32")
    sharded = serve.shard_cache(cache, mesh)
    assert sharded.k.sharding == NamedSharding(mesh, spec.k)
    # heads=4 over model=2, slots=4 over data*fsdp=4
    assert sharded.k.addressable_shards[0].data.shape == (
        cfg.num_layers, 1, 2, cfg.max_len, cfg.head_dim
    )


def test_sampling_modes():
    logits = jnp.asarray([[0.0, 3.0, 1.0, -2.0], [5.0, 0.1, 0.2, 0.3]])
    greedy = serve.sample(logits)
    assert greedy.tolist() == [1, 0] and greedy.dtype == jnp.int32

    key = jax.random.PRNGKey(0)
    for i in range(20):
        t = serve.sample(
            logits, jax.random.fold_in(key, i), temperature=0.7, top_k=2
        )
        assert t[0] in (1, 2) and t[1] in (0, 3)  # top-2 of each row

    with pytest.raises(ValueError):
        serve.sample(logits, None, temperature=1.0)


def test_cache_specs_match_rules_table():
    """Migration parity (PR 14): the KV_CACHE_RULES table derives the
    exact spec tree the pre-engine logical-rules path produced."""
    from distributed_tensorflow_tpu.parallel import sharding as sh
    from distributed_tensorflow_tpu.serve import kv_cache as kv

    table_specs = serve.cache_specs()  # default: the rules table
    legacy = sh.spec_from_logical(kv.CACHE_LOGICAL, sh.TP_RULES)
    assert table_specs.k == legacy and table_specs.v == legacy
    # the explicit logical-rules escape hatch still resolves identically
    assert serve.cache_specs(sh.TP_RULES) == table_specs


def test_paged_cache_specs_match_rules_table():
    from distributed_tensorflow_tpu.parallel import sharding as sh
    from distributed_tensorflow_tpu.serve import kv_cache as kv

    table_specs = serve.paged_cache_specs()
    legacy = sh.spec_from_logical(kv.PAGED_CACHE_LOGICAL, sh.TP_RULES)
    assert table_specs.k == legacy and table_specs.v == legacy
    assert serve.paged_cache_specs(sh.TP_RULES) == table_specs


# ---------------------------------------------------------------------------
# Span tracing (obs/trace.py): the step's phases and counts, the request's
# phases — structure and counts only, never a time under a device's name
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_run(decoder):
    """A scripted paged run on its own tracer: a shared prefix, a prompt of
    several chunks, a pool tight enough to force a preemption, and one
    request cancelled while queued. The two jitted steps are wrapped with
    a copy of the benchmark's `serve_driver.Calls` arithmetic, which reads
    each call's work from the call's own arguments."""
    from distributed_tensorflow_tpu import obs

    cfg, _, params = decoder
    tracer = obs.Tracer(annotate=False)
    eng = _paged_engine(cfg, params, num_slots=2, max_len=48, num_blocks=8,
                        tracer=tracer)
    records = []
    prefill, decode, oob = eng._prefill_chunk_fn, eng._decode, eng._oob

    def prefill_chunk(params, cache, table_row, buf, start, n):
        records.append(("prefill", int(n), int(n * start + n * (n + 1) // 2),
                        int(start + n), int(table_row.shape[0])))
        return prefill(params, cache, table_row, buf, start, n)

    def decode_step(params, cache, table, last, lens):
        live = np.asarray(lens)
        live = live[live != oob]
        records.append(("decode", int(live.size), int((live + 1).sum()),
                        int((live + 1).sum()), int(table.shape[1])))
        return decode(params, cache, table, last, lens)

    eng._prefill_chunk_fn, eng._decode = prefill_chunk, decode_step
    prefix = list(range(1, 17))  # two full blocks
    requests, steps = {}, []

    def submit(prompt, n):
        uid = eng.submit(prompt, max_new_tokens=n)
        requests[uid] = eng._find(uid)
        return uid

    def run():
        while eng.sched.has_work:
            steps.append(eng.step())

    submit(prefix + [50], 2)  # registers the prefix
    run()
    submit(prefix + list(range(60, 70)), 16)  # 26 tokens: 10 past the prefix
    submit(prefix + [71], 24)
    queued = submit([90, 91, 92], 4)  # no slot free: waits in the queue
    steps.append(eng.step())
    cancelled = submit([93, 94], 4)
    assert eng.cancel(cancelled)
    run()
    eng.drain()
    assert eng.alloc.blocks_free == eng.cache.num_blocks
    assert tracer.dropped == 0
    return types.SimpleNamespace(
        eng=eng, spans=list(tracer.events), records=records,
        requests=requests, steps=steps, queued=queued, cancelled=cancelled)


def _named(run, name):
    return [s for s in run.spans if s.name == name]


def test_step_spans_count_what_each_call_was_asked(traced_run):
    """`q_tokens/attended/context` of every `serve.step.prefill` and
    `slots/kv_tokens` of every `serve.step.decode` equal, call for call,
    what the call's arguments say; the table width is the one handed to
    the kernel, and the walk is each live slot's own blocks."""
    run = traced_run
    assert sum(r.preemptions for r in run.requests.values()) >= 1
    pre, dec = _named(run, "serve.step.prefill"), _named(
        run, "serve.step.decode")
    dec = [s for s in dec if "slots" in s.attrs]  # a call was made
    want_pre = [r for r in run.records if r[0] == "prefill"]
    want_dec = [r for r in run.records if r[0] == "decode"]
    assert len(pre) == len(want_pre) > 4 and len(dec) == len(want_dec) > 10
    assert [("prefill", s.attrs["q_tokens"], s.attrs["attended"],
             s.attrs["context"], s.attrs["table_blocks"]) for s in pre] \
        == want_pre
    assert [("decode", s.attrs["slots"], s.attrs["kv_tokens"],
             s.attrs["kv_tokens"], s.attrs["table_blocks"]) for s in dec] \
        == want_dec
    bs = run.eng.block_size
    for s in dec:
        # kv_tokens rounded up to whole blocks, slot by slot
        walked = s.attrs["kv_positions_walked"]
        assert walked % bs == 0
        assert 0 < s.attrs["kv_tokens"] <= walked \
            < s.attrs["kv_tokens"] + s.attrs["slots"] * bs
    # a prompt of several chunks: consecutive chunks of one uid
    longest = max(run.requests.values(), key=lambda r: len(r.prompt))
    chunks = [s for s in pre if s.key == longest.uid]
    # all of the prompt but what the prefix cache supplied, at least
    assert len(chunks) >= 2 and sum(
        c.attrs["q_tokens"] for c in chunks) >= len(longest.prompt) - 16


def test_decode_walk_follows_each_slots_own_length(decoder):
    """One long context beside fifteen short or idle slots: the decode
    span's `kv_positions_walked` is what the paged kernel fetches, each
    live slot's context rounded up to blocks, and not slots x the widest
    table x block size."""
    from distributed_tensorflow_tpu import obs

    cfg, _, params = decoder
    tracer = obs.Tracer(annotate=False)
    eng = _paged_engine(cfg, params, num_slots=16, max_len=48,
                        num_blocks=32, tracer=tracer)
    eng.submit(list(range(1, 38)), max_new_tokens=3)  # 37 tokens: 5 blocks
    for i in range(5):
        eng.submit([40 + i, 50 + i], max_new_tokens=8)  # one block each
    while eng.sched.has_work:
        eng.step()
    dec = [s for s in tracer.events
           if s.name == "serve.step.decode" and s.attrs.get("slots") == 6]
    assert dec
    bs = eng.block_size
    for s in dec:
        kv, walked = s.attrs["kv_tokens"], s.attrs["kv_positions_walked"]
        # the long slot's 38-40 positions in 5 blocks, the short ones' one
        assert kv <= walked == (5 + 5) * bs < kv + 6 * bs
        assert walked < 16 * s.attrs["table_blocks"] * bs / 4


def test_step_span_counts_add_up_to_the_engines_own(traced_run):
    """What the spans count is what the registry counts: a prefill span a
    chunk, a token a prompt's first-token sample and a token a live slot of
    a decode (no request of the run ends by EOS, cancel or deadline with a
    token still on the device, so every token sampled is delivered)."""
    run = traced_run
    reg = run.eng.registry
    pre = _named(run, "serve.step.prefill")
    assert len(pre) == reg.get("prefill_chunks_total").value \
        == sum(st.prefill_chunks for st in run.steps)
    decode_tokens = sum(s.attrs.get("slots", 0)
                        for s in _named(run, "serve.step.decode"))
    first_tokens = len(_named(run, "serve.step.prefill.sample"))
    assert decode_tokens + first_tokens \
        == reg.get("serve_tokens_total").value
    # one serve.step span per step() call, whose StepStats timing split
    # is the spans' durations
    steps = _named(run, "serve.step")
    assert len(steps) == len(run.steps)
    by_parent = {}
    for s in run.spans:
        by_parent.setdefault(s.parent, []).append(s)
    for sp, st in zip(steps, run.steps):
        kids = by_parent[sp.id]
        assert st.wall_s == sp.duration
        assert st.prefill_s == pytest.approx(sum(
            k.duration for k in kids
            if k.name in ("serve.step.admit", "serve.step.prefill")))
        assert st.decode_s == sum(
            k.duration for k in kids if k.name == "serve.step.decode")
        assert st.decoded_slots == sum(
            k.attrs.get("slots", 0) for k in kids
            if k.name == "serve.step.decode")


def test_request_spans_partition_submit_to_finish(traced_run):
    """The serve.request.* spans of a finished request are its stamps:
    contiguous from t_submit to t_finish, keyed by its uid; a phase it
    never reached is left out."""
    run = traced_run
    by_uid = {}
    for s in run.spans:
        if s.name.startswith("serve.request."):
            assert s.parent is None
            by_uid.setdefault(s.key, []).append(s)
    assert set(by_uid) == set(run.requests)
    for uid, req in run.requests.items():
        spans = by_uid[uid]
        phases = [s.name.rsplit(".", 1)[1] for s in spans]
        if uid == run.cancelled:
            assert phases == ["queue"] and req.t_admit is None
        else:
            assert phases == ["queue", "prefill", "decode"]
        assert spans[0].start == req.t_submit
        assert spans[-1].end == req.t_finish
        for a, b in zip(spans, spans[1:]):
            assert a.end == b.start
        if len(spans) == 3:
            assert (spans[1].start, spans[2].start) == (
                req.t_admit, req.t_first_token)
        assert all(s.attrs == {} for s in spans)
    waited = by_uid[run.queued][0]
    assert waited.end - waited.start > 0  # it sat out at least one step


def test_span_children_lie_inside_their_parents(traced_run):
    run = traced_run
    by_id = {s.id: s for s in run.spans}
    nested = [s for s in run.spans if s.parent is not None]
    assert len(nested) > 50
    for s in nested:
        p = by_id[s.parent]
        assert p.start <= s.start <= s.end <= p.end, (s, p)
        assert s.name.startswith(p.name + ".") \
            and "." not in s.name[len(p.name) + 1:]
    names = {s.name for s in run.spans}
    # the run's preemption is preceded by a fetch of the tokens still on
    # the device, inside the decode's staging; the weights were laid out
    # once, when the engine was built
    assert names == {
        "serve.prepare", "serve.step", "serve.step.admit",
        "serve.step.prefill", "serve.step.prefill.stage",
        "serve.step.prefill.dispatch", "serve.step.prefill.sample",
        "serve.step.decode",
        "serve.step.decode.stage", "serve.step.decode.stage.fetch",
        "serve.step.decode.stage.deliver", "serve.step.decode.dispatch",
        "serve.step.decode.fetch", "serve.step.decode.deliver",
        "serve.request.queue", "serve.request.prefill",
        "serve.request.decode"}
    uids = set(run.requests)
    assert {s.key for s in _named(run, "serve.step.prefill")} <= uids


@pytest.mark.parametrize("kw", [dict(paged=False),
                                dict(paged=True, block_size=8,
                                     prefill_chunk=8, spec_k=3)],
                         ids=["dense", "speculative"])
def test_dense_and_speculative_steps_leave_the_same_span_tree(decoder, kw):
    from distributed_tensorflow_tpu import obs

    cfg, model, params = decoder
    tracer = obs.Tracer(annotate=False)
    eng = serve.ServeEngine(cfg, params, num_slots=2, tracer=tracer, **kw)
    uids = [eng.submit([7, 8, 9, 7, 8, 9, 7, 8], max_new_tokens=6)
            for _ in range(2)]
    # neither runs ahead: each request's first token and a decoded one
    # arrive in the step that sampled them
    first = eng.step()
    assert [u for u, _ in first.tokens].count(uids[0]) >= 2
    done = eng.run()
    names = {s.name for s in tracer.events}
    assert {"serve.step.prefill.dispatch", "serve.step.prefill.sample",
            "serve.step.decode.stage", "serve.step.decode.dispatch",
            "serve.step.decode.fetch", "serve.step.decode.deliver",
            "serve.request.decode"} <= names
    decodes = [s for s in tracer.events if s.name == "serve.step.decode"]
    assert all(s.attrs["slots"] >= 1 and s.attrs["kv_tokens"] > 0
               and s.attrs["ahead"] == 0 for s in decodes)
    # every decode step that made a call delivered what it fetched
    assert len([s for s in tracer.events
                if s.name == "serve.step.decode.deliver"]) == len(decodes)
    assert {s.key for s in tracer.events
            if s.name == "serve.request.decode"} == set(uids)
    assert all(len(done[u].generated) == 6 for u in uids)
    _assert_uncached_greedy(model, params, [7, 8, 9, 7, 8, 9, 7, 8],
                            done[uids[0]].generated)


def test_request_spans_only_on_the_tracers_own_clock(decoder):
    """`Request.t_*` are readings of the engine's clock; the ring has one
    time axis. An engine on another clock than its tracer's records no
    `serve.request.*`; the step's spans, on the tracer's clock, stay."""
    from distributed_tensorflow_tpu import obs

    from distributed_tensorflow_tpu.resilience import FaultClock

    cfg, _, params = decoder
    fake = FaultClock()
    for tracer, want in ((obs.Tracer(annotate=False), 0),
                         (obs.Tracer(annotate=False, clock=fake), 3)):
        eng = serve.ServeEngine(cfg, params, num_slots=1, clock=fake,
                                tracer=tracer)
        eng.submit([5, 6, 7], max_new_tokens=3)
        eng.run()
        names = [s.name for s in tracer.events]
        assert len([n for n in names
                    if n.startswith("serve.request.")]) == want
        assert "serve.step.decode.fetch" in names


# ---------------------------------------------------------------------------
# One decode step in flight (docs/serving.md "The engine's step"): tokens
# stay on the device and are fetched one step late
# ---------------------------------------------------------------------------


def _assert_uncached_greedy(model, params, prompt, gen):
    """``gen`` is the uncached forward's greedy continuation of ``prompt``:
    one full forward over prompt + all but the last token, whose argmax at
    every position from the prompt's last on is the next token."""
    seq = jnp.asarray([list(prompt) + list(gen[:-1])], jnp.int32)
    full = model.apply({"params": params}, seq)[0, len(prompt) - 1:]
    assert list(gen) == [int(t) for t in jnp.argmax(full, -1)]


def _solo(cfg, params, prompt, n, **kw):
    eng = _paged_engine(cfg, params, num_slots=1, **kw)
    return list(eng.stream(prompt, max_new_tokens=n))


def test_a_token_arrives_one_step_after_the_call_that_sampled_it(decoder):
    """A lone request of four tokens: each step delivers what the calls of
    the step before sampled, the first token in the step of the prompt's
    last chunk; the call that samples the fourth is the last, and a step
    with nothing left to call only fetches."""
    from distributed_tensorflow_tpu import obs

    cfg, model, params = decoder
    tracer = obs.Tracer(annotate=False)
    eng = _paged_engine(cfg, params, num_slots=1, tracer=tracer)
    uid = eng.submit([5, 17, 3], max_new_tokens=4)
    req = eng._find(uid)
    seen = []
    while eng.sched.has_work:
        st = eng.step()
        seen.append((st.decoded_slots, [t for _, t in st.tokens],
                     list(eng._owed)))
    g = req.generated
    assert seen == [(1, g[:1], [0]), (1, g[1:2], [0]), (1, g[2:3], [0]),
                    (0, g[3:], [])]
    assert req.finish_reason == sched_lib.FINISH_MAX_NEW
    _assert_uncached_greedy(model, params, [5, 17, 3], g)
    decodes = [s for s in tracer.events if s.name == "serve.step.decode"]
    assert [s.attrs.get("ahead") for s in decodes] == [1, 1, 1, None]
    assert "slots" not in decodes[-1].attrs  # it made no call
    eng.drain()
    assert eng.alloc.blocks_free == eng.cache.num_blocks


def test_in_flight_streams_are_the_uncached_forwards_greedy_ones(decoder):
    """Five requests through three slots, one of 64 tokens, two behind a
    shared prefix whose tail block is copied on the first divergent write:
    every stream is the uncached forward's greedy one, the calls go ahead
    of the host, and the pool comes home whole."""
    from distributed_tensorflow_tpu import obs

    cfg, model, params = decoder
    shared = list(range(1, 21))  # 2 full blocks + a 4-token tail at bs 8
    prompts = [[5, 17, 3, 99, 42, 7, 11], shared + [60], shared + [61],
               [(5 * i + 2) % cfg.vocab_size for i in range(30)], [88, 12]]
    lengths = [64, 20, 20, 24, 30]
    tracer = obs.Tracer(annotate=False)
    eng = _paged_engine(cfg, params, num_slots=3, tracer=tracer)
    # prefilled in three chunks, registered, and decoding into its tail
    warm = eng.submit(shared, max_new_tokens=30)
    for _ in range(3):
        eng.step()
    uids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, lengths)]
    done = eng.run()
    _assert_uncached_greedy(model, params, shared, done[warm].generated)
    for uid, p, n in zip(uids, prompts, lengths):
        assert len(done[uid].generated) == n
        _assert_uncached_greedy(model, params, p, done[uid].generated)
    assert eng.alloc.cow_copies >= 1
    assert int(eng.registry.get("prefix_reuse_hits_total").value) > 0
    calls = [s for s in tracer.events
             if s.name == "serve.step.decode" and "ahead" in s.attrs]
    assert sum(s.attrs["ahead"] for s in calls) == len(calls) > 60
    assert not any(s.name.endswith(".stage.fetch") for s in tracer.events)
    eng.drain()
    assert eng._owed == {}
    assert eng.alloc.blocks_free == eng.cache.num_blocks


def test_an_eos_in_flight_leaves_the_slot_and_its_blocks_clean(decoder):
    """A request that meets its EOS has already been sent in the next call:
    that call's token is never delivered, its write lands in the request's
    own block, and the slot and the blocks serve the next requests (one
    behind the finished request's prompt, through the prefix cache) as
    they would be served alone."""
    cfg, model, params = decoder
    a_prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]  # a block and a 2-token tail
    a_solo = _solo(cfg, params, a_prompt, 12)
    # the first token the stream had not emitted before: A's EOS
    k = next(i for i in range(1, 12) if a_solo[i] not in a_solo[:i])
    assert k >= 3
    eos = a_solo[k]
    b_prompt = [(7 * i + 3) % cfg.vocab_size for i in range(13)]
    b_solo = _solo(cfg, params, b_prompt, 16)
    c_prompt = a_prompt + [77, 78]
    c_solo = _solo(cfg, params, c_prompt, 10)
    eng = _paged_engine(cfg, params, num_slots=1, max_len=32, num_blocks=4)
    a = eng.submit(a_prompt, max_new_tokens=12, eos_id=eos)
    b = eng.submit(b_prompt, max_new_tokens=16)
    c = eng.submit(c_prompt, max_new_tokens=10)
    calls = 0
    real = eng._decode

    def counted(*args):
        nonlocal calls
        calls += 1
        return real(*args)

    eng._decode = counted
    done = eng.run()
    assert done[a].finish_reason == sched_lib.FINISH_EOS
    assert done[a].generated == a_solo[:k + 1]
    assert done[b].generated == b_solo and done[c].generated == c_solo
    _assert_uncached_greedy(model, params, c_prompt, done[c].generated)
    # A's k calls and the one too many, B's fifteen, C's nine
    assert calls == k + 1 + 15 + 9
    eng.drain()
    assert eng.alloc.blocks_free == eng.cache.num_blocks


def test_cancel_and_deadline_while_a_token_is_in_flight(decoder):
    """Both end a request whose next token is on the device: it keeps what
    was delivered, gets nothing after, its slot and blocks go home, and the
    request admitted behind it is served as it would be alone."""
    from distributed_tensorflow_tpu.resilience import FaultClock

    cfg, model, params = decoder
    prompts = [[5, 17, 3], [88, 12, 61, 40, 2], [7, 7, 1, 9]]
    solo = [_solo(cfg, params, p, n) for p, n in zip(prompts, (30, 20, 12))]
    clk = FaultClock()
    eng = _paged_engine(cfg, params, num_slots=2, clock=clk)
    a = eng.submit(prompts[0], max_new_tokens=30, deadline_s=5.0)
    b = eng.submit(prompts[1], max_new_tokens=20)
    c = eng.submit(prompts[2], max_new_tokens=12)
    for _ in range(3):
        eng.step()
    slot_b = eng.sched.slots.index(eng._find(b))
    assert set(eng._owed) == {0, 1}  # both residents have a token out
    b_seen = list(eng._find(b).generated)
    assert eng.cancel(b) is True
    assert slot_b not in eng._owed and eng._blocks[slot_b] == []
    clk.advance(10.0)
    a_seen = list(eng._find(a).generated)
    stats = eng.step()  # A expires before its token is fetched
    assert a in stats.finished
    done = eng.run()
    assert done[b].finish_reason == sched_lib.FINISH_CANCELLED
    assert done[b].generated == b_seen == solo[1][:len(b_seen)]
    assert done[a].finish_reason == sched_lib.FINISH_TIMEOUT
    assert done[a].generated == a_seen == solo[0][:len(a_seen)]
    assert len(a_seen) >= 3 and len(b_seen) >= 3
    assert done[c].generated == solo[2]
    _assert_uncached_greedy(model, params, prompts[2], done[c].generated)
    eng.drain()
    assert eng._owed == {}
    assert eng.alloc.blocks_free == eng.cache.num_blocks
    _assert_telemetry_invariant(eng, 3)


def test_drain_with_steps_in_flight_delivers_every_token(decoder):
    """drain() while the residents have tokens on the device: every one is
    fetched and delivered, the queued request is cancelled, no request
    finishes with a token left on the device, and the pool is whole."""
    cfg, model, params = decoder
    prompts = [[5, 17, 3], [9, 4, 77, 2, 60], [1, 2, 3]]
    eng = _paged_engine(cfg, params, num_slots=2)
    uids = [eng.submit(p, max_new_tokens=9) for p in prompts]
    eng.step(), eng.step()
    assert len(eng._owed) == 2
    done = eng.drain()
    for uid, p in zip(uids[:2], prompts):
        assert done[uid].finish_reason == sched_lib.FINISH_MAX_NEW
        assert done[uid].generated == _solo(cfg, params, p, 9)
        _assert_uncached_greedy(model, params, p, done[uid].generated)
    assert done[uids[2]].finish_reason == sched_lib.FINISH_CANCELLED
    assert eng._owed == {} and not eng.sched.has_work
    assert eng.alloc.blocks_free == eng.cache.num_blocks
    _assert_telemetry_invariant(eng, 3)


def test_stream_beside_other_residents_yields_the_solo_tokens(decoder):
    cfg, model, params = decoder
    other = [(3 * i + 1) % cfg.vocab_size for i in range(20)]
    eng = _paged_engine(cfg, params, num_slots=2)
    o = eng.submit(other, max_new_tokens=40)
    got = list(eng.stream([5, 17, 3], max_new_tokens=25))
    assert got == _solo(cfg, params, [5, 17, 3], 25)
    _assert_uncached_greedy(model, params, [5, 17, 3], got)
    done = eng.run()
    assert done[o].generated == _solo(cfg, params, other, 40)


def test_a_preemption_fetches_the_owed_tokens_first(decoder):
    """A pool too small for the residents: before a staging that would
    preempt, the tokens on the device are fetched and delivered, so the
    victim re-prefills from all it was given; the decode step sent after
    that fetch is not ahead, and the tokens are those of an ample pool."""
    from distributed_tensorflow_tpu import obs

    cfg, model, params = decoder
    prompts = [[10 + i] * 10 for i in range(3)]

    def drive(num_blocks):
        tracer = obs.Tracer(annotate=False)
        eng = _paged_engine(cfg, params, num_slots=2, max_len=32,
                            num_blocks=num_blocks, prefix_reuse=False,
                            tracer=tracer)
        uids = [eng.submit(p, max_new_tokens=20) for p in prompts]
        done = eng.run()
        eng.drain()
        assert eng.alloc.blocks_free == eng.cache.num_blocks
        return ([done[u].generated for u in uids],
                sum(done[u].preemptions for u in uids), list(tracer.events))

    ample, _, _ = drive(8)
    tight, preempted, spans = drive(5)
    assert preempted > 0 and tight == ample
    for p, gen in zip(prompts, tight):
        _assert_uncached_greedy(model, params, p, gen)
    by_id = {s.id: s for s in spans}
    early = [s for s in spans if s.name.endswith(".stage.fetch")]
    assert early
    for s in early:
        owner = by_id[by_id[s.parent].parent]
        if owner.name == "serve.step.decode" and "ahead" in owner.attrs:
            assert owner.attrs["ahead"] == 0


# ---------------------------------------------------------------------------
# Tables laid out at load (docs/serving.md "The engine's step"): the tiny
# decoder's width, 32, is not a multiple of the 128 lanes, so its token and
# position tables are padded once and the programs gather from those
# ---------------------------------------------------------------------------


def test_the_prepared_tree_gives_the_raw_trees_logits(decoder):
    """A paged prefill chunk and a decode step, and the uncached forward,
    read the same logits from the prepared tree as from the params."""
    cfg, model, params = decoder
    served = model.serving_params(params)
    assert {k: v.shape for k, v in served["serving"].items()} == {
        "tok_rows": (cfg.vocab_size, 128), "pos_rows": (cfg.max_len, 128)}
    assert served["params"] is params
    table = jnp.arange(4, dtype=jnp.int32)
    toks = jnp.asarray([5, 17, 3, 99, 42, 7, 0, 0], jnp.int32)
    tables = jnp.stack([table, table + 4])

    def run(tree):
        cache = serve.init_paged_cache(cfg, 8, 8, dtype="float32")
        chunk, cache = model.prefill_chunk(tree, cache, table, toks,
                                           jnp.int32(0), jnp.int32(6))
        step, _ = model.decode_step(tree, cache, tables,
                                    jnp.asarray([11, 12], jnp.int32),
                                    jnp.asarray([6, 0], jnp.int32))
        full = model.apply(tfm.serving_variables(tree), toks[None, :6])
        return chunk, step, full

    for got, want in zip(run(served), run(params)):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)


def test_prepared_and_raw_trees_serve_the_same_greedy_tokens(
        decoder, monkeypatch):
    """32 greedy tokens through the engine on the prepared tree and on an
    engine whose model hands its params back as they are."""
    cfg, model, params = decoder
    prompt = [5, 17, 3, 99, 42, 7, 11]
    served = _solo(cfg, params, prompt, 32)
    monkeypatch.setattr(tfm.Transformer, "serving_params",
                        lambda self, p: p)
    raw = _solo(cfg, params, prompt, 32)
    assert len(served) == 32 and served == raw
    _assert_uncached_greedy(model, params, prompt, served)


def test_weights_set_again_are_laid_out_again_and_compile_nothing(decoder):
    """`eng.params = None; eng.params = tree` (how the calibration swaps a
    seed's weights in) lays the new tree out under one `serve.prepare`
    span, and the programs take it without compiling again."""
    from distributed_tensorflow_tpu import obs

    cfg, model, params = decoder
    tracer = obs.Tracer(annotate=False)
    eng = _paged_engine(cfg, params, num_slots=1, tracer=tracer)
    prompt = [5, 17, 3]
    first = list(eng.stream(prompt, max_new_tokens=8))
    programs = (eng._prefill_chunk_fn, eng._decode, tfm._lane_padded)
    compiled = [f._cache_size() for f in programs]
    before = eng._prepared["serving"]["tok_rows"]
    eng.params = None
    assert eng.params is None and eng._prepared is None
    tree = jax.tree.map(lambda a: a + 0, params)
    eng.params = tree
    assert eng.params is tree and eng._prepared["params"] is tree
    assert eng._prepared["serving"]["tok_rows"] is not before
    assert list(eng.stream(prompt, max_new_tokens=8)) == first
    assert [f._cache_size() for f in programs] == compiled
    prepares = [s for s in tracer.events if s.name == "serve.prepare"]
    padded = (cfg.vocab_size + cfg.max_len) * 128 * 4
    assert [s.attrs for s in prepares] == [
        {"tables_padded": 2, "padded_bytes": padded}] * 2
    assert all(s.parent is None for s in prepares)


def test_the_init_tree_is_the_params_alone(decoder):
    """Training, eval and checkpoints see the tree they always saw: no
    `serving` collection, the tables at the model's width."""
    cfg, model, params = decoder
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert set(variables) == {"params"}
    assert jax.tree.map(jnp.shape, variables["params"]) == jax.tree.map(
        jnp.shape, params)
    assert params["tok_embed"]["embedding"].shape == (cfg.vocab_size,
                                                      cfg.d_model)
    assert params["pos_embed"].shape == (cfg.max_len, cfg.d_model)


def test_a_lane_multiple_width_is_served_as_it_is():
    """At d 128 nothing is padded: the engine's programs take the params
    themselves, and its `serve.prepare` span says so."""
    from distributed_tensorflow_tpu import obs

    cfg = tiny_decoder(d_model=128)
    model = tfm.Transformer(cfg)
    params, _ = tfm.make_init_fn(model, 8)(jax.random.PRNGKey(1))
    assert model.serving_params(params) is params
    tracer = obs.Tracer(annotate=False)
    eng = _paged_engine(cfg, params, num_slots=1, tracer=tracer)
    assert eng._prepared is params
    [prep] = [s for s in tracer.events if s.name == "serve.prepare"]
    assert prep.attrs == {"tables_padded": 0, "padded_bytes": 0}
