"""ServeEngine — the user-facing submit/step/stream loop.

Ties the pieces together: jit-compiled prefill and decode steps
(decode.py) over one resident cache — the PAGED block pool by default,
the slot-dense KVCache as the exact-parity fallback (kv_cache.py,
``paged=False``) — driven by the continuous-batching scheduler
(scheduler.py), with sampling.py choosing tokens. One engine ``step()``
is the serving analog of one train step:

1. **Admit.** Every queued request the scheduler can place into a free
   slot — paged admission additionally gated on free KV blocks — is
   admitted; paged admission also maps whatever prefix the block cache
   already holds (copy-on-write sharing).
2. **Prefill.** Paged: at most ONE fixed-size chunk per mid-prefill
   slot per step, so a long prompt never starves the resident decoders
   for more than one chunk. Dense: the whole prompt at once (one
   compiled program per prompt bucket). The final chunk samples the
   request's first token.
3. **Decode.** One fused decode step advances every decode-ready slot
   by one token ([num_slots, 1] inputs — idle and mid-prefill slots
   compute garbage that is never delivered and, on the paged path,
   write through an out-of-bounds sentinel so it lands nowhere; the
   same sentinel keeps the recurrent state of such a slot as it was,
   for a model that has one).
4. **Deliver + evict.** Sampled tokens are appended via the scheduler,
   which evicts finished requests (EOS / max-new / max-len) so their
   slots — and their KV blocks — are re-admissible on the NEXT step's
   admit phase.

**One step in flight** (the paged engine without speculation;
docs/serving.md "The engine's step"). Sampled tokens stay on the device:
a decode step takes the previous one's tokens, and a prompt's first token,
as a device vector (``_next``), and ``step()`` fetches the tokens it owes
only after dispatching its own decode, so the host's admit, staging and
delivery run while the chip computes. Tokens therefore arrive one step
after the call that sampled them. The dense fallback and the speculative
verify read their results on the host and stay synchronous.

Everything device-side is shape-static; everything dynamic (queue
state, per-slot write indices, request lifetimes) lives host-side in
plain Python/numpy — the same host-drives/device-computes split as the
training loop.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import Transformer, TransformerConfig, make_init_fn
from ..obs import flightrec as flightrec_lib
from ..obs import trace as trace_lib
from ..obs.registry import Registry
from . import decode as decode_lib
from . import sampling
from .kv_cache import (
    BlockAllocator,
    KVCache,
    NoFreeBlocks,
    PagedKVCache,
    SnapshotTable,
    init_cache,
    init_paged_cache,
)
from .scheduler import (
    FINISH_REASONS,
    Request,
    Scheduler,
)


def _set_token(tokens: jax.Array, slot, token: jax.Array) -> jax.Array:
    """``tokens`` with ``token`` at ``slot``: a prompt's first token joins
    the decode input on the device (jitted once; ``slot`` is traced)."""
    return tokens.at[slot].set(token)


@dataclasses.dataclass
class StepStats:
    """What one engine step did."""

    admitted: int = 0
    decoded_slots: int = 0
    occupancy: float = 0.0
    #: prefill chunks run this step (paged engine; dense prefill is
    #: atomic and reports 0)
    prefill_chunks: int = 0
    #: (uid, token) pairs in delivery order. On the paged engine a token
    #: arrives in the step after the call that sampled it (one step in
    #: flight); on the dense engine a uid can appear twice in one step
    #: (its prefill token AND its first decode token)
    tokens: list[tuple[int, int]] = dataclasses.field(default_factory=list)
    finished: list[int] = dataclasses.field(default_factory=list)
    #: host wall-clock split of this step: prefill phase (all admits,
    #: compile-warm), decode phase (one fused step), and the whole call —
    #: the durations of the step's ``serve.step.admit`` + ``.prefill``,
    #: ``.decode`` and ``serve.step`` spans (obs/trace.py). The decode
    #: phase ends on a token fetch: with one step in flight, the fetch of
    #: the tokens the previous calls sampled.
    wall_s: float = 0.0
    prefill_s: float = 0.0
    decode_s: float = 0.0


class ServeEngine:
    """KV-cached continuous-batching inference over a causal Transformer,
    or over a decoder with recurrent state beside its paged pool
    (``models.olmo_hybrid.OlmoHybridConfig``, docs/serving.md "Hybrid
    cache"; ``models.gigachat3_5.GigaChat35Config``, "Latent pool and
    expert share"); a config that names its decoder (``cfg.decoder()``)
    picks it, any other is a ``Transformer``'s.

    >>> eng = ServeEngine.with_random_params(cfg, num_slots=4)
    >>> uid = eng.submit([5, 17, 3], max_new_tokens=16)
    >>> for tok in eng.stream([5, 17, 3]):
    ...     print(tok)
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        num_slots: int = 4,
        max_len: int | None = None,
        max_queue: int | None = None,
        cache_dtype=None,
        paged: bool = True,
        block_size: int = 16,
        num_blocks: int | None = None,
        prefill_chunk: int = 32,
        prefix_reuse: bool = True,
        num_state_snapshots: int = 0,
        spec_k: int = 0,
        spec_ngram: int = 4,
        paged_impl: str | None = None,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
        registry: Registry | None = None,
        clock: Callable[[], float] = time.perf_counter,
        flightrec=None,
        reqtrace=None,
        tracer=None,
    ):
        if not cfg.causal:
            raise ValueError("ServeEngine requires a causal (decoder) model")
        if paged_impl is not None:
            # per-engine override of the paged-attention dispatch
            # (ops.attention.paged_attention impl=): the bench and the
            # parity gates pin "gather" / "fused" / "pallas" without
            # touching the model config they were handed
            cfg = dataclasses.replace(cfg, paged_attention_impl=paged_impl)
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k > 0 and not paged:
            raise ValueError(
                "speculative decoding (spec_k > 0) requires the paged "
                "engine: rollback is a block-table edit"
            )
        if spec_ngram < 1:
            raise ValueError(f"spec_ngram must be >= 1, got {spec_ngram}")
        # the config picks the model; from here on the engine asks the
        # model, not its name (serve/decode.py "the serving protocol")
        self.model = (cfg.decoder() if hasattr(cfg, "decoder")
                      else Transformer(cfg))
        #: recurrent layers beside the paged pool: the model builds the
        #: cache that holds both (``model.init_cache``)
        self.has_state = self.model.has_state
        if self.has_state and not paged:
            raise ValueError("a model with recurrent layers is served "
                             "through the paged engine only")
        if self.has_state and spec_k > 0:
            raise ValueError(
                "speculative decoding (spec_k > 0) is refused for a model "
                "with recurrent layers: a rejected draft cannot be rolled "
                "back out of a recurrent state")
        self.spec_k = spec_k
        self.spec_ngram = spec_ngram
        self.cfg = cfg
        #: span ring for the step's host phases and each request's
        #: queue/prefill/decode phases (obs/trace.py; the names and counts
        #: are a contract, docs/observability.md "Span tracing"); present
        #: in every run, defaults to the process ring
        self.tracer = (tracer if tracer is not None
                       else trace_lib.default_tracer())
        self.params = params
        M = cfg.max_len if max_len is None else max_len
        if M > cfg.max_len:
            raise ValueError(
                f"max_len={M} exceeds the model context window "
                f"(cfg.max_len={cfg.max_len})"
            )
        self.paged = paged
        self.prefix_reuse = prefix_reuse and paged
        if paged:
            if prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            self.block_size = block_size
            self.prefill_chunk = prefill_chunk
            #: logical blocks per request = the per-request token budget
            self._mb = -(-M // block_size)
            if num_blocks is None:
                # default: same token capacity as the dense cache; pass
                # fewer blocks to trade worst-case headroom for memory
                num_blocks = num_slots * self._mb
            if num_blocks < self._mb:
                raise ValueError(
                    f"num_blocks={num_blocks} < ceil(max_len/block_size)="
                    f"{self._mb}: one request could exhaust the pool with "
                    f"no one left to preempt"
                )
            self.alloc = BlockAllocator(num_blocks, block_size)
            if self.has_state:
                if prefill_chunk % block_size:
                    raise ValueError(
                        f"prefill_chunk={prefill_chunk} must be a multiple "
                        f"of block_size={block_size} for a hybrid decoder: "
                        f"state snapshots are taken at block-aligned chunk "
                        f"ends")
                self.cache = self.model.init_cache(
                    num_slots, num_blocks, block_size, num_state_snapshots,
                    dtype=jnp.bfloat16 if cache_dtype is None else cache_dtype)
                #: prefix -> snapshot row; dies with the allocator's blocks
                self.snapshots = SnapshotTable(num_state_snapshots,
                                               self.alloc)
                #: slot -> how far into the prompt the prefix cache matched
                #: at admission: the part other requests demonstrably
                #: share, where chunk ends are worth a snapshot
                self._shared_upto: dict[int, int] = {}
            else:
                self.cache: PagedKVCache = init_paged_cache(
                    cfg, num_blocks, block_size, dtype=cache_dtype
                )
            #: slot → physical block ids in logical order (host truth);
            #: the device-side table mirrors it, sentinel-padded
            self._blocks: list[list[int]] = [[] for _ in range(num_slots)]
            self._table = np.full((num_slots, self._mb), num_blocks,
                                  np.int32)
            #: past-the-table write position — routes a slot's K/V write
            #: out of bounds so the scatter drops it (idle / mid-prefill)
            self._oob = self._mb * block_size
            #: slot → next prefill position (chunked prefill in flight)
            self._pending: dict[int, int] = {}
            #: slot → all tokens known at admission (prompt + generated,
            #: the re-prefill source after a preemption)
            self._ptoks: dict[int, tuple[int, ...]] = {}
            self._evictions_seen = 0
            #: blocks promised to requests approved earlier in the same
            #: admit cycle (reset each step): the gate must not hand the
            #: same free blocks to two queue heads
            self._gate_reserved = 0
        else:
            # dense fallback: the PR-1 slot-dense cache, kept as the
            # exact-parity reference path (docs/serving.md)
            self.cache: KVCache = init_cache(
                cfg, num_slots, max_len=M, dtype=cache_dtype
            )
        self.clock = clock
        # one recorder feeds the scheduler's admit/evict events and the
        # engine's drain event, so the postmortem timeline interleaves
        self.flightrec = (flightrec if flightrec is not None
                          else flightrec_lib.default_recorder())
        #: per-request span ledger (obs/reqtrace.py) shared with the
        #: scheduler; None = untraced. Only requests that entered with a
        #: router trace id (rid) emit spans — direct submissions don't.
        self.reqtrace = reqtrace
        self.sched = Scheduler(
            num_slots, M, clock=clock, max_queue=max_queue,
            flightrec=self.flightrec, reqtrace=reqtrace,
            admission_gate=self._admission_gate if paged else None,
        )
        self.temperature = temperature
        self.top_k = top_k
        self._rng = jax.random.PRNGKey(seed)
        # per-slot host state: cache write index and most recent token
        self._written = np.zeros(num_slots, np.int32)
        self._last = np.zeros(num_slots, np.int32)
        if paged:
            #: every slot's newest token, on the device: what the last
            #: decode step sampled, with each prompt's first token put in
            #: at its slot. The next decode step's input; fetched a step late
            self._next = jnp.zeros(num_slots, jnp.int32)
            #: slot -> the request owed the token ``_next`` holds for it
            #: (sampled, not yet fetched); ``_release_slot`` drops an entry
            self._owed: dict[int, Request] = {}
            self._set_token = jax.jit(_set_token)
            self._prefill_chunk_fn = decode_lib.jit_paged_prefill_chunk(
                self.model)
            self._decode = decode_lib.jit_paged_decode_step(self.model)
            self._copy_block = decode_lib.jit_copy_block()
            if self.has_state:
                self._take_snapshot = decode_lib.jit_take_snapshot()
                self._restore_snapshot = decode_lib.jit_restore_snapshot()
                # compiled here, on a cache that is still all zeros: the
                # first snapshot hit must not compile in a serving window
                # (no request of a warm-up is sure to cause one)
                self.cache = self._copy_block(self.cache, 0, 0)
                if num_state_snapshots:
                    self.cache = self._take_snapshot(self.cache, 0, 0)
                    self.cache = self._restore_snapshot(self.cache, 0, 0)
            if spec_k > 0:
                self._verify = decode_lib.jit_paged_verify_step(self.model)
                #: host-side accept-rule randomness (temperature spec);
                #: numpy on purpose — the accept decision is host
                #: bookkeeping, a device categorical buys nothing
                self._spec_gen = np.random.default_rng(seed)
        else:
            self._prefill = decode_lib.jit_prefill(self.model)
            self._decode = decode_lib.jit_decode_step(self.model)
        # telemetry: one registry per engine by default (isolated,
        # mergeable upstream); pass obs.default_registry() to publish
        # into the process-wide scrape surface. Handles are resolved
        # once here — the decode hot loop only does .observe()/.inc().
        self.registry = registry if registry is not None else Registry()
        r = self.registry
        self._m_queue_wait = r.histogram(
            "serve_queue_wait_seconds", "submit → slot admission")
        self._m_ttft = r.histogram(
            "serve_ttft_seconds", "submit → first token delivered")
        self._m_tpot = r.histogram(
            "serve_tpot_seconds",
            "mean per-output-token decode latency of a finished request")
        self._m_step = r.histogram(
            "serve_step_seconds", "one engine step (admit+prefill+decode)")
        self._m_prefill = r.histogram(
            "serve_prefill_seconds", "prefill phase of an engine step")
        self._m_decode = r.histogram(
            "serve_decode_seconds", "fused decode phase of an engine step")
        self._m_occupancy = r.gauge(
            "serve_occupancy", "active slots / num_slots at last decode")
        self._m_admitted = r.counter(
            "serve_admitted_total", "requests admitted into a slot")
        self._m_tokens = r.counter(
            "serve_tokens_total", "tokens delivered (prefill + decode)")
        self._m_finished = {
            reason: r.counter(
                "serve_finished_total", "finished requests by eviction reason",
                reason=reason)
            for reason in FINISH_REASONS
        }
        # paged-cache surface (docs/observability.md "Paged KV cache");
        # registered unconditionally so dashboards see zeros, not holes,
        # on a dense-fallback engine
        self._m_blocks_used = r.gauge(
            "kv_blocks_in_use", "physical KV blocks with refcount > 0")
        self._m_blocks_free = r.gauge(
            "kv_blocks_free", "physical KV blocks on the free list")
        self._m_block_evic = r.counter(
            "kv_block_evictions_total",
            "prefix-cache blocks evicted under pool pressure")
        self._m_reuse = r.counter(
            "prefix_reuse_hits_total",
            "physical blocks mapped from the shared-prefix cache at "
            "admission instead of being prefilled")
        self._m_chunks = r.counter(
            "prefill_chunks_total", "prefill chunks run (chunked prefill)")
        # speculative-decoding surface (docs/observability.md
        # "Speculative decoding") — unconditional, same zeros-not-holes
        # contract as the paged gauges above
        self._m_spec_prop = r.counter(
            "spec_tokens_proposed_total",
            "draft tokens proposed to the speculative verify step")
        self._m_spec_acc = r.counter(
            "spec_tokens_accepted_total",
            "draft tokens the speculative verify step accepted")
        self._m_spec_rate = r.gauge(
            "spec_acceptance_rate",
            "accepted / proposed draft tokens over the engine lifetime")
        # recurrent-state snapshots (docs/observability.md "Hybrid cache");
        # unconditional, zeros on an engine without recurrent layers
        self._m_snap_taken = r.counter(
            "state_snapshots_taken_total",
            "recurrent-state snapshots copied out at a block-aligned "
            "prefill-chunk end")
        self._m_snap_hits = r.counter(
            "state_snapshot_hits_total",
            "admissions that started from a state snapshot")
        self._m_snap_evic = r.counter(
            "state_snapshot_evictions_total",
            "state snapshots dropped: least recently used, or with the "
            "prefix-cache block they belonged to")
        self._m_snap_live = r.gauge(
            "state_snapshots_live", "snapshot rows that hold a prefix")
        # the expert layer's share (docs/observability.md "Serve latency
        # metrics"); unconditional, zeros on an engine without one
        self._m_moe_assigned = r.counter(
            "moe_local_assignments_total",
            "token-to-expert assignments to the experts this engine holds")
        self._m_moe_calls = r.counter(
            "moe_expert_calls_total",
            "held experts that received at least one token, summed over "
            "expert-layer calls")
        self._moe_seen = None
        self._snap_seen = (0, 0, 0)
        #: engine-lifetime accept accounting behind the gauge
        self._spec_proposed = 0
        self._spec_accepted = 0
        if paged:
            self._sync_block_metrics()

    @property
    def params(self):
        """The weights as they were given."""
        return self._params

    @params.setter
    def params(self, params) -> None:
        """Set the weights, and lay out once what the model's programs read
        of them (``model.serving_params``, serve/decode.py "the serving
        protocol"): every program takes that tree, so weights set again in
        the same shapes compile nothing. ``None`` drops both."""
        self._params = params
        self._prepared = None
        if params is None:
            return
        with self.tracer.span("serve.prepare") as sp:
            # waited for: the layout's temporaries are gone before the
            # pool is made beside the weights
            self._prepared = jax.block_until_ready(
                self.model.serving_params(params))
            given = {id(x) for x in jax.tree.leaves(params)}
            added = [x for x in jax.tree.leaves(self._prepared)
                     if id(x) not in given]
            sp.attrs["tables_padded"] = len(added)
            sp.attrs["padded_bytes"] = sum(x.nbytes for x in added)

    @classmethod
    def with_random_params(
        cls, cfg: TransformerConfig, *, seed: int = 0, **kw
    ) -> "ServeEngine":
        """Random-weight engine for demos/benches (examples/serve.py)."""
        if hasattr(cfg, "decoder"):
            params = cfg.decoder().init_params(jax.random.PRNGKey(seed))
            return cls(cfg, params, seed=seed, **kw)
        params, _ = make_init_fn(Transformer(cfg), min(8, cfg.max_len))(
            jax.random.PRNGKey(seed)
        )
        return cls(cfg, params, seed=seed, **kw)

    # -- public API --------------------------------------------------------

    def submit(
        self,
        prompt: Iterable[int],
        max_new_tokens: int = 32,
        eos_id: int | None = None,
        deadline_s: float | None = None,
        priority: int = 0,
        rid: int | None = None,
    ) -> int:
        """Enqueue a request (raises ``scheduler.QueueFull`` under
        backpressure, ``scheduler.SchedulerClosed`` after drain).
        Higher ``priority`` residents are preempted LAST on block
        exhaustion (the serve fleet's lane tiering rides on this);
        ``rid`` carries the router trace id into the request ledger."""
        return self.sched.submit(prompt, max_new_tokens, eos_id,
                                 deadline_s=deadline_s, priority=priority,
                                 rid=rid)

    def cancel(self, uid: int) -> bool:
        """Cancel a queued or in-flight request (``FINISH_CANCELLED``);
        returns False if the uid is unknown or already finished."""
        req = self.sched.cancel(uid)
        if req is None:
            return False
        self._observe_finish(req, None)
        self._reconcile_slots()
        if self.paged:
            self._sync_block_metrics()
        return True

    def step(self) -> StepStats:
        """Enforce deadlines, admit newly placed requests, run at most
        ONE prefill chunk per mid-prefill slot (paged — so a long
        prompt never starves the resident decoders for more than one
        chunk; dense prefill stays atomic), then advance every
        decode-ready slot by one token. Returns per-step stats and
        records them into ``self.registry``."""
        stats = StepStats()
        tracer = self.tracer
        with tracer.span("serve.step") as sp:
            with tracer.span("admit") as admit:
                expired = self.sched.expire()
                for req in expired:
                    self._observe_finish(req, stats)
                if expired:
                    self._reconcile_slots()
                if self.paged:
                    self._gate_reserved = 0  # fresh admit cycle
                placed = self.sched.admit()
                for slot, req in placed:
                    stats.admitted += 1
                    self._m_admitted.inc()
                    if req.preemptions == 0:
                        self._m_queue_wait.observe(req.t_admit - req.t_submit)
                    if self.paged:
                        self._begin_paged(slot, req, admit)
            stats.prefill_s = admit.duration
            # occupancy counts every slot WORKING this step — decoding,
            # mid-chunked-prefill, or just admitted (even if its first
            # token finishes it before the step ends); measured here,
            # after admission and before any delivery, so a max_new=1
            # stream still reads as a full batch
            stats.occupancy = (
                len(self.sched.active_slots()) / self.sched.num_slots
            )
            if self.paged:
                # one chunk per pending slot per step — the interleave
                # bound
                for slot in sorted(self._pending):
                    if slot in self._pending:  # preemption may drop peers
                        self._paged_prefill_step(slot, stats)
            else:
                for slot, req in placed:
                    self._do_prefill(slot, req, stats)
            active = self.sched.active_slots()
            if self.paged:
                active = [s for s in active if s not in self._pending]
            if active:
                self._do_decode(active, stats)
        stats.wall_s = sp.duration
        self._m_step.observe(stats.wall_s)
        if stats.admitted or stats.prefill_chunks:
            self._m_prefill.observe(stats.prefill_s)
        if stats.decoded_slots:  # not a step whose decode preempted away
            self._m_decode.observe(stats.decode_s)
        if stats.occupancy:  # publish prefill-only steps too
            self._m_occupancy.set(stats.occupancy)
        if self.paged:
            self._sync_block_metrics()
        return stats

    def stream(
        self,
        prompt: Iterable[int],
        max_new_tokens: int = 32,
        eos_id: int | None = None,
        deadline_s: float | None = None,
    ) -> Iterator[int]:
        """Submit one request and yield its tokens as they are decoded
        (other queued requests keep making progress in the same steps).
        A ``deadline_s`` expiry simply ends the stream after whatever
        tokens made it out (``finish_reason`` on the Request says why)."""
        uid = self.submit(prompt, max_new_tokens, eos_id,
                          deadline_s=deadline_s)
        # hold the Request object itself: its identity is stable across
        # queue → slot → finished, and stays valid even if a concurrent
        # drain() hands the finished map to its caller — the stream can
        # still deliver the tokens drain() decoded, instead of KeyError
        req = self._find(uid)
        delivered = 0
        while True:
            self.step()
            while delivered < len(req.generated):
                yield req.generated[delivered]
                delivered += 1
            if req.done:
                self.sched.finished.pop(uid, None)  # delivered in full
                return

    def run(self) -> dict[int, Request]:
        """Drain queue + slots to completion; returns (and forgets)
        uid → Request, so repeated run() calls don't accumulate."""
        while self.sched.has_work:
            self.step()
        return self.sched.drain_finished()

    def drain(self) -> dict[int, Request]:
        """Graceful shutdown: stop admission (further ``submit`` raises
        ``SchedulerClosed``), cancel everything still queued, decode the
        resident requests to completion, and leave telemetry flushed
        (final occupancy 0, every request's terminal counter bumped).
        Returns (and forgets) uid → Request for everything finished."""
        for req in self.sched.close():
            self._observe_finish(req, None)
        # queue check: close() emptied it, but a paged preemption can
        # push a resident back to the queue head mid-drain
        while any(r is not None for r in self.sched.slots) \
                or self.sched.queue:
            self.step()
        self._reconcile_slots()
        if self.paged:
            # shutdown is the leak audit: drop the prefix cache's refs
            # too, so a clean drain leaves the allocator ALL-free
            self.alloc.flush_prefix_cache()
            self._sync_block_metrics()
        self._m_occupancy.set(0.0)
        done = self.sched.drain_finished()
        self.flightrec.emit("serve_drain", finished=len(done))
        return done

    # -- internals ---------------------------------------------------------

    def _park_idle_written(self) -> None:
        """Idle slots park their write index at 0 (the convention
        ``_deliver`` keeps for token-driven evictions); timeout/cancel
        evictions free slots outside ``append_token``, so re-park here."""
        for i, req in enumerate(self.sched.slots):
            if req is None:
                self._written[i] = 0

    def _reconcile_slots(self) -> None:
        """Bring engine host state into line with the scheduler after
        any out-of-band eviction (timeout, cancel, close): every slot
        the scheduler freed gives its blocks back and parks its write
        index — the no-leaked-blocks bottleneck for non-token-driven
        eviction paths."""
        if self.paged:
            for i, req in enumerate(self.sched.slots):
                if req is None and (self._blocks[i] or i in self._pending):
                    self._release_slot(i)
        self._park_idle_written()

    # -- paged internals ---------------------------------------------------

    def _sync_block_metrics(self) -> None:
        self._m_blocks_used.set(float(self.alloc.blocks_in_use))
        self._m_blocks_free.set(float(self.alloc.blocks_free))
        d = self.alloc.evictions - self._evictions_seen
        if d:
            self._m_block_evic.inc(d)
            self._evictions_seen = self.alloc.evictions
        if self.has_state:
            snaps = self.snapshots
            now = (snaps.taken, snaps.hits, snaps.evictions)
            for metric, new, old in zip(
                    (self._m_snap_taken, self._m_snap_hits,
                     self._m_snap_evic), now, self._snap_seen):
                if new != old:
                    metric.inc(new - old)
            self._snap_seen = now
            self._m_snap_live.set(float(len(snaps)))

    def _expert_counts(self, copy: bool) -> jax.Array | None:
        """An expert layer's running counts (``LatentCache.moe_counts``),
        or None for a cache without them; ``copy`` when the next call
        donates the cache."""
        counts = getattr(self.cache, "moe_counts", None)
        return jnp.copy(counts) if copy and counts is not None else counts

    def _observe_expert_load(self, sp, counts: jax.Array | None) -> None:
        """At a decode span's fetch: what the expert layer's counts gained
        since the last fetch goes on that span (``moe_assignments``,
        ``moe_expert_calls``) and into the registry's counters. A dispatched
        step reads the counts as they were just before its own call, so
        every program is counted once, at the next decode span."""
        if counts is None:
            return
        now = np.asarray(counts).astype(np.int64)
        seen = np.zeros_like(now) if self._moe_seen is None else self._moe_seen
        delta = (now - seen) % (1 << 32)
        self._moe_seen = now
        assigned, calls = int(delta[0].sum()), int(delta[1].sum())
        sp.attrs.update(moe_assignments=assigned, moe_expert_calls=calls)
        self._m_moe_assigned.inc(assigned)
        self._m_moe_calls.inc(calls)

    def _mb_bucket(self, hi_blocks: int) -> int:
        """Table width (in blocks) to hand the jit'd step: the smallest
        power of two covering the widest live slot, capped at the full
        table. Dense attention pays ``max_len`` positions every step;
        the block table knows how few are actually mapped, so the fused
        kernels attend (and gather) only that — at the cost of one
        compiled program per bucket, ≤ log2(max_blocks)+1 in total, all
        hot after the first long request."""
        mbu = 1
        while mbu < hi_blocks:
            mbu *= 2
        return min(mbu, self._mb)

    def _admission_gate(self, req: Request) -> bool:
        """Admission is gated on KV capacity, not slot count: the
        request needs blocks for every position it will write through
        its first decode token — capped at ``max_len``, past which the
        scheduler finishes it before any write — minus what the prefix
        cache can supply. ``evictable`` cache blocks count as capacity
        (alloc reclaims them on demand), excluding the ones the match
        itself would pin; as a fallback the FULL need may be covered by
        evicting even the matched entries (reuse then degrades to
        re-prefill — and a block whose only other holder is the cache
        is resolved in place by ``_ensure_blocks``, never deadlocked
        on). ``_gate_reserved`` accounts for requests approved earlier
        in the SAME admit cycle, whose blocks are not yet taken."""
        T = len(req.prompt) + len(req.generated)
        need = -(-min(T + 1, self.sched.max_len) // self.block_size)
        m = self.alloc.peek_match(req.prompt) if self.prefix_reuse else 0
        if m and self.has_state:
            # only as far as a state snapshot lets the match be used
            m = self.snapshots.longest(
                req.prompt, min(m * self.block_size, T - 1),
                touch=False)[0] // self.block_size
        free, ev = self.alloc.blocks_free, self.alloc.evictable()
        reserved = self._gate_reserved
        with_reuse = free + max(ev - m, 0) - reserved >= max(need - m, 1)
        without_reuse = free + ev - reserved >= max(need, 1)
        if with_reuse or without_reuse:
            self._gate_reserved += max(need - (m if with_reuse else 0), 1)
            return True
        return False

    def _release_slot(self, slot: int) -> None:
        """Give every block in ``slot``'s table back to the allocator
        (shared blocks just drop one ref) and reset the slot to the
        idle sentinel state. A token the device still holds for the slot
        is never delivered: a call already dispatched wrote it into the
        slot's own blocks, and the next owner's calls run after it."""
        for bid in self._blocks[slot]:
            self.alloc.decref(bid)
        self._blocks[slot] = []
        self._table[slot, :] = self.cache.num_blocks
        self._written[slot] = 0
        self._pending.pop(slot, None)
        self._ptoks.pop(slot, None)
        self._owed.pop(slot, None)
        if self.has_state:
            self._shared_upto.pop(slot, None)

    def _youngest_resident(self, exclude: int) -> int | None:
        """Preemption victim: the LOWEST-priority resident, youngest
        (highest uid) among equals — so batch-lane work absorbs block
        exhaustion before interactive traffic, and all-default
        priorities reproduce the original pure youngest-first policy."""
        best = None
        for i, req in enumerate(self.sched.slots):
            if req is None or i == exclude:
                continue
            if best is None:
                best = i
                continue
            cur = self.sched.slots[best]
            if (req.priority, -req.uid) < (cur.priority, -cur.uid):
                best = i
        return best

    def _paged_alloc(self, slot: int) -> int:
        """Allocate one block for ``slot``; on exhaustion, preempt the
        youngest OTHER resident back to the queue head (its blocks come
        home, it re-prefills later) and retry. Terminates: num_blocks >=
        ceil(max_len/block_size) guarantees a lone request always fits
        once the prefix cache and its peers have been drained."""
        while True:
            try:
                return self.alloc.alloc()
            except NoFreeBlocks:
                victim = self._youngest_resident(exclude=slot)
                if victim is None:
                    raise
                self.sched.preempt(victim)
                self._release_slot(victim)

    def _blocks_needed(self, slot: int, start: int, end: int) -> int:
        """Blocks ``_ensure_blocks(slot, start, end)`` may allocate: one
        for each block of the span past the slot's table or shared."""
        bs, blocks = self.block_size, self._blocks[slot]
        return sum(b >= len(blocks) or self.alloc.refcount(blocks[b]) > 1
                   for b in range(start // bs, (end - 1) // bs + 1))

    def _fetch_if_preempting(self, need: int, stats: StepStats) -> None:
        """Fetch and deliver the owed tokens before an allocation of
        ``need`` blocks that the pool cannot supply without preempting: a
        preempted request re-prefills from the tokens it holds, so none may
        still be on the device (docs/serving.md "The engine's step")."""
        if not self._owed or need <= self.alloc.blocks_free \
                or need <= self.alloc.blocks_free + self.alloc.evictable():
            return
        owed, self._owed = self._owed, {}
        self._fetch(owed, self._next, stats)

    def _ensure_blocks(self, slot: int, start: int, end: int) -> None:
        """Make positions ``[start, end)`` of ``slot`` writable: append
        fresh blocks past the table's frontier, and copy-on-write any
        block about to be written whose refcount is > 1 (shared via
        prefix reuse) — the sharers keep the original, this slot gets a
        private device-side copy."""
        bs = self.block_size
        blocks = self._blocks[slot]
        for b in range(start // bs, (end - 1) // bs + 1):
            if b < len(blocks):
                bid = blocks[b]
                if self.alloc.refcount(bid) > 1:
                    try:
                        new = self._paged_alloc(slot)
                    except NoFreeBlocks:
                        # the pool cannot supply a copy and no one is
                        # preemptible, so the other holder must be the
                        # prefix cache itself: un-cache the block and
                        # write in place as sole owner instead
                        self.alloc.release_cached(bid)
                        if self.alloc.refcount(bid) != 1:
                            raise
                    else:
                        self.cache = self._copy_block(self.cache, bid, new)
                        self.alloc.decref(bid)
                        self.alloc.cow_copies += 1
                        blocks[b] = new
                        self._table[slot, b] = new
            else:
                new = self._paged_alloc(slot)
                blocks.append(new)
                self._table[slot, b] = new
            # in-place writes land below: weak registrations claiming
            # the written offsets are stale from here on
            self.alloc.note_write(blocks[b], max(start - b * bs, 0))

    def _begin_paged(self, slot: int, req: Request, admit) -> None:
        """Admission bookkeeping for the paged path: map what the
        prefix cache already holds (never the last known position —
        its logits must be recomputed to sample the next token) and
        queue the rest for chunked prefill. A model with recurrent layers
        can use a match only as far as a state snapshot exists: the match
        is trimmed back to the longest such position, the blocks past it
        are given back, and the slot starts from that snapshot (from
        nought where there is none: the first chunk at position 0 does
        that itself). ``admit`` is the step's ``serve.step.admit`` span:
        it sums what its admissions matched and gave up."""
        toks = tuple(req.prompt) + tuple(req.generated)
        blocks: list[int] = []
        matched = trimmed = 0
        if self.prefix_reuse:
            blocks, matched = self.alloc.match_prefix(toks)
            matched = min(matched, len(toks) - 1)
            if self.has_state:
                found = matched
                matched, row = self.snapshots.longest(toks, found)
                trimmed = found - matched
                self.alloc.release_tail(blocks, matched // self.block_size)
                #: full blocks of the match: shared with another request
                self._shared_upto[slot] = (
                    found // self.block_size * self.block_size)
                if row is not None:
                    self.cache = self._restore_snapshot(self.cache, slot,
                                                        row)
            if blocks:
                self._m_reuse.inc(len(blocks))
        admit.attrs["matched_tokens"] = (
            admit.attrs.get("matched_tokens", 0) + matched + trimmed)
        admit.attrs["trimmed_tokens"] = (
            admit.attrs.get("trimmed_tokens", 0) + trimmed)
        self._blocks[slot] = blocks
        self._table[slot, :] = self.cache.num_blocks
        self._table[slot, :len(blocks)] = blocks
        self._written[slot] = matched
        self._pending[slot] = matched
        self._ptoks[slot] = toks

    def _paged_prefill_step(self, slot: int, stats: StepStats) -> None:
        """Run ONE prefill chunk for ``slot``; on the final chunk,
        sample the first token onto the device's token vector (owed to
        the request until the decode phase fetches it), publish the
        prompt's blocks for prefix reuse, and hand the slot to the decode
        phase."""
        req = self.sched.slots[slot]
        with self.tracer.span("prefill", key=req.uid) as sp:
            self._prefill_chunk(slot, req, stats, sp)
        stats.prefill_s += sp.duration

    def _prefill_chunk(self, slot: int, req: Request, stats: StepStats,
                       sp) -> None:
        tracer = self.tracer
        toks = self._ptoks[slot]
        T = len(toks)
        start = self._pending[slot]
        end = min(start + self.prefill_chunk, T)
        n = end - start
        with tracer.span("stage"):
            self._fetch_if_preempting(
                self._blocks_needed(slot, start, end), stats)
            self._ensure_blocks(slot, start, end)
            buf = np.zeros(self.prefill_chunk, np.int32)
            buf[:n] = toks[start:end]
            mbu = self._mb_bucket(len(self._blocks[slot]))
            table = jnp.asarray(self._table[slot, :mbu])
            buf = jnp.asarray(buf)
        # positions start..end-1 attend start+1..end keys
        sp.attrs.update(q_tokens=n, attended=n * start + n * (n + 1) // 2,
                        context=end, table_blocks=mbu)
        with tracer.span("dispatch"):
            logits, self.cache = self._prefill_chunk_fn(
                self._prepared, self.cache, table, buf, start, n,
                *((slot,) if self.has_state else ()),
            )
            shared = self._shared_upto.get(slot, 0) if self.has_state else 0
            if (end % self.block_size == 0 and end <= shared
                    and end + self.prefill_chunk > shared
                    and self.alloc.is_cached(toks[:end])):
                # the last chunk end inside the part of the prompt that the
                # prefix cache matched and no snapshot covered: the next
                # request behind this prefix starts from here (one row a
                # shared prefix; the chunk ends before it would hold states
                # that no request of the prefix restores)
                row = self.snapshots.take(toks[:end])
                if row is not None:
                    self.cache = self._take_snapshot(self.cache, slot, row)
        stats.prefill_chunks += 1
        self._m_chunks.inc()
        self.flightrec.emit("serve_prefill_chunk", uid=req.uid, slot=slot,
                            start=start, n=n)
        if self.reqtrace is not None and req.rid is not None:
            # one span per chunk: the waterfall shows where a long
            # prompt's prefill interleaved with the residents' decode
            self.reqtrace.transition(req.rid, "prefill_chunks",
                                     uid=req.uid, slot=slot,
                                     start=start, n=n)
        self._written[slot] = end
        if end < T:
            self._pending[slot] = end
            return
        del self._pending[slot]
        if self.prefix_reuse:
            P = len(req.prompt)
            n_prompt_blocks = -(-P // self.block_size)
            self.alloc.register_prefix(
                req.prompt, self._blocks[slot][:n_prompt_blocks]
            )
        with tracer.span("sample"):
            # the first token stays on the device, the slot's decode input;
            # the decode phase's fetch delivers it
            tok = sampling.sample(
                logits, self._next_rng(),
                temperature=self.temperature, top_k=self.top_k,
            )
            self._next = self._set_token(self._next, slot, tok)
        self._owed[slot] = req
        if self.reqtrace is not None and req.rid is not None:
            # prefill complete, first token of this residency sampled —
            # the request enters decode; this is also the replica-side
            # half of the sample→delivery clock anchor (the router's
            # matching decode_gap span opens strictly later)
            self.reqtrace.transition(req.rid, "decode_gap", uid=req.uid)

    def _observe_finish(self, req: Request, stats: StepStats | None) -> None:
        """The ONE terminal observation per finished request, whatever
        ended it (token-driven eviction, timeout, cancel) — the PR-2
        invariant lives here and only here: every finished request
        contributes exactly one TTFT and one TPOT observation, so their
        counts equal Σ serve_finished_total. TPOT is the mean decode
        latency per output token (a single-token request has no decode
        interval → observes 0). A request aborted before its first token
        observes time-to-abort as TTFT — the latency the client actually
        experienced — and 0 TPOT; one aborted mid-decode already
        observed TTFT at first token and records its realized decode
        latency here."""
        if stats is not None:
            stats.finished.append(req.uid)
        self._m_finished[req.finish_reason].inc()
        self._record_request_spans(req)
        if req.t_first_token is None:
            self._m_ttft.observe(req.t_finish - req.t_submit)
            self._m_tpot.observe(0.0)
        else:
            g = len(req.generated)
            self._m_tpot.observe(
                (req.t_finish - req.t_first_token) / max(g - 1, 1)
            )

    def _record_request_spans(self, req: Request) -> None:
        """``serve.request.queue`` / ``.prefill`` / ``.decode``, from the
        stamps the request already carries (no clock read): together they
        partition ``t_submit..t_finish``; a phase the request never
        reached is left out and the one it ended in runs to ``t_finish``.
        The stamps are readings of the engine's ``clock``; the ring has
        one time axis, the tracer's, so an engine driven by another clock
        than its tracer's records none of the three."""
        if self.clock is not self.tracer.clock:
            return
        record, uid = self.tracer.record, req.uid
        record("serve.request.queue", req.t_submit,
               req.t_finish if req.t_admit is None else req.t_admit, key=uid)
        if req.t_admit is None:
            return
        record("serve.request.prefill", req.t_admit,
               req.t_finish if req.t_first_token is None
               else req.t_first_token, key=uid)
        if req.t_first_token is not None:
            record("serve.request.decode", req.t_first_token, req.t_finish,
                   key=uid)

    def _find(self, uid: int) -> Request:
        req = self.sched.finished.get(uid)
        if req is not None:
            return req
        for r in self.sched.slots:
            if r is not None and r.uid == uid:
                return r
        for r in self.sched.queue:
            if r.uid == uid:
                return r
        raise KeyError(f"unknown request uid {uid}")

    def _next_rng(self) -> jax.Array | None:
        if self.temperature <= 0.0:
            return None
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _deliver(self, slot: int, token: int, stats: StepStats) -> None:
        req = self.sched.slots[slot]
        stats.tokens.append((req.uid, token))
        self._m_tokens.inc()
        finished = self.sched.append_token(slot, token)
        if len(req.generated) == 1:
            self._m_ttft.observe(req.t_first_token - req.t_submit)
        if finished is not None:
            if self.paged:
                self._release_slot(slot)  # blocks home before slot reuse
            self._written[slot] = 0  # idle slots park their write index at 0
            self._observe_finish(finished, stats)

    def _do_prefill(self, slot: int, req: Request, stats: StepStats) -> None:
        P = len(req.prompt)
        tracer = self.tracer
        with tracer.span("prefill", key=req.uid, q_tokens=P,
                         attended=P * (P + 1) // 2, context=P) as sp:
            with tracer.span("stage"):
                bucket = min(decode_lib.prefill_bucket(P), self.cache.max_len)
                toks = np.zeros(bucket, np.int32)
                toks[:P] = req.prompt
            with tracer.span("dispatch"):
                logits, self.cache = self._prefill(
                    self._prepared, self.cache, slot, toks, P
                )
            with tracer.span("sample"):
                tok = sampling.sample(
                    logits, self._next_rng(),
                    temperature=self.temperature, top_k=self.top_k,
                )
            with tracer.span("fetch"):
                tok = int(tok)
            self._written[slot] = P
            self._last[slot] = tok
            if self.reqtrace is not None and req.rid is not None:
                self.reqtrace.transition(req.rid, "decode_gap", uid=req.uid)
            self._deliver(slot, tok, stats)
        stats.prefill_s += sp.duration

    def _draft(self, slot: int, k: int) -> list[int]:
        """N-gram prompt-lookup drafter (zero extra weights): find the
        longest suffix of the slot's known tokens (n = spec_ngram down
        to 1) that recurs earlier in prompt+generated, and propose the
        ``k`` tokens that followed its most recent earlier occurrence
        (short continuations repeat their last token out to ``k`` — a
        cheap bet that loops keep looping). No recurrence at all →
        propose the last token repeated, which costs nothing when
        rejected: a verify step always emits at least one token."""
        req = self.sched.slots[slot]
        ctx = list(req.prompt) + list(req.generated)
        for n in range(min(self.spec_ngram, len(ctx) - 1), 0, -1):
            pat = ctx[-n:]
            for i in range(len(ctx) - n - 1, -1, -1):
                if ctx[i: i + n] == pat:
                    cont = ctx[i + n: i + n + k]
                    while len(cont) < k:
                        cont.append(cont[-1])
                    return cont
        return [ctx[-1]] * k

    def _do_verify_decode(self, active: list[int], stats: StepStats,
                          sp) -> None:
        """Speculative decode step: draft ``spec_k`` tokens per slot,
        verify every slot's drafts in ONE chunked-prefill-shaped step,
        emit each slot's accepted prefix plus its correction/bonus
        token, and roll rejected suffixes back through the block table
        (kv_cache.BlockAllocator.release_tail — a refcount edit, never
        a device copy). Greedy emission is bit-identical to the
        non-speculative path (sampling.spec_verify_greedy docstring);
        the per-token ``_deliver`` loop keeps every scheduler/telemetry
        invariant of single-token decode, including discarding tokens
        drafted past a mid-burst finish."""
        tracer = self.tracer
        bs = self.block_size
        cap = self._oob  # positions a slot's table can address
        drafts: dict[int, list[int]] = {}
        with tracer.span("stage"):
            if self._owed:
                # the drafter and the accept rule read the prompts' first
                # tokens on the host: the verify step is not run ahead
                owed, self._owed = self._owed, {}
                self._fetch(owed, self._next, stats)
            for slot in active:
                if self.sched.slots[slot] is None:
                    continue  # its first token finished it, or preempted
                w = int(self._written[slot])
                ks = max(min(self.spec_k, cap - 1 - w), 0)
                drafts[slot] = self._draft(slot, ks) if ks else []
                # writable span: the pending token at w plus every draft
                self._ensure_blocks(slot, w, w + len(drafts[slot]) + 1)
            active = [s for s in active if self.sched.slots[s] is not None]
            if not active:
                return
            stats.decoded_slots = len(active)
            S = self.spec_k + 1
            toks = np.zeros((self.sched.num_slots, S), np.int32)
            pos = np.full((self.sched.num_slots, S), self._oob, np.int32)
            kv_tokens = walked = 0
            for slot in active:
                d = drafts[slot]
                w = int(self._written[slot])
                toks[slot, 0] = self._last[slot]
                toks[slot, 1: 1 + len(d)] = d
                pos[slot, : 1 + len(d)] = np.arange(w, w + 1 + len(d))
                kv_tokens += w + 1 + len(d)
                walked += -(-(w + 1 + len(d)) // bs) * bs
            mbu = self._mb_bucket(max(len(self._blocks[s]) for s in active))
            table = jnp.asarray(self._table[:, :mbu])
            toks, pos = jnp.asarray(toks), jnp.asarray(pos)
        sp.attrs.update(
            slots=len(active), kv_tokens=kv_tokens, table_blocks=mbu,
            kv_positions_walked=walked, ahead=0)
        with tracer.span("dispatch"):
            logits, self.cache = self._verify(
                self._prepared, self.cache, table, toks, pos,
            )
        with tracer.span("fetch"):
            logits = np.asarray(logits)
        with tracer.span("deliver"):
            for slot in active:
                d = drafts[slot]
                w = int(self._written[slot])
                rows = logits[slot, : len(d) + 1]
                if self.temperature <= 0.0:
                    emitted, accepted = sampling.spec_verify_greedy(rows, d)
                else:
                    emitted, accepted = sampling.spec_verify_sample(
                        rows, d, self._spec_gen,
                        temperature=self.temperature, top_k=self.top_k,
                    )
                # the verify wrote K/V at w..w+len(d); everything past
                # w+accepted is rejected-draft garbage — retreat the write
                # index over it (future writes overwrite in place, masked
                # until then) and give wholly-garbage tail blocks back
                self._written[slot] = w + accepted + 1
                keep = -(-int(self._written[slot]) // bs)
                if len(self._blocks[slot]) > keep:
                    self.alloc.release_tail(self._blocks[slot], keep)
                    self._table[slot, keep:] = self.cache.num_blocks
                self._spec_proposed += len(d)
                self._spec_accepted += accepted
                if d:
                    self._m_spec_prop.inc(len(d))
                if accepted:
                    self._m_spec_acc.inc(accepted)
                req = self.sched.slots[slot]
                req.spec_accepted += accepted
                self.flightrec.emit("serve_spec_step", uid=req.uid,
                                    slot=slot, proposed=len(d),
                                    accepted=accepted)
                self._last[slot] = emitted[-1]
                for tok in emitted:
                    self._deliver(slot, tok, stats)
                    if self.sched.slots[slot] is None:
                        break  # finished mid-burst; trailing tokens discarded
        if self._spec_proposed:
            self._m_spec_rate.set(
                self._spec_accepted / self._spec_proposed)

    def _do_decode(self, active: list[int], stats: StepStats) -> None:
        with self.tracer.span("decode") as sp:
            if not self.paged:
                self._do_dense_decode(active, stats, sp)
            elif self.spec_k > 0:
                self._do_verify_decode(active, stats, sp)
            else:
                self._do_paged_decode(active, stats, sp)
        stats.decode_s = sp.duration

    def _fetch(self, owed: dict[int, Request], tokens: jax.Array,
               stats: StepStats, sp=None, counts=None) -> None:
        """Fetch ``tokens`` (the device vector holding what ``owed`` is
        owed) and deliver each slot's token; at a decode span's fetch
        (``sp``) the expert counts too."""
        with self.tracer.span("fetch"):
            host = np.asarray(tokens)
            if sp is not None:
                self._observe_expert_load(sp, counts)
        with self.tracer.span("deliver"):
            for slot, req in owed.items():
                tok = int(host[slot])
                self._last[slot] = tok
                self._deliver(slot, tok, stats)

    def _do_paged_decode(self, active: list[int], stats: StepStats,
                         sp) -> None:
        """One decode step ahead of the host: stage and dispatch this
        step's call on the device's token vector (the previous call's
        tokens and this step's first tokens, none of them fetched), then
        fetch and deliver what the earlier calls owe. A slot whose owed
        token spends its budget is not decoded again; one that ends by EOS,
        cancel or deadline meanwhile has one call too many, whose token is
        never delivered (``_release_slot``)."""
        tracer = self.tracer
        written, owed = self._written, self._owed
        with tracer.span("stage"):
            active = [s for s in active if s not in owed
                      or self.sched.out_of_budget(
                          owed[s], len(owed[s].generated) + 1) is None]
            self._fetch_if_preempting(sum(
                self._blocks_needed(s, written[s], written[s] + 1)
                for s in active), stats)
            # make each decoding slot's write position privately owned
            # (fresh block at a boundary, COW off a shared block);
            # allocation pressure may preempt the youngest residents, so
            # re-filter afterwards
            for slot in active:
                if self.sched.slots[slot] is not None:
                    w = int(self._written[slot])
                    self._ensure_blocks(slot, w, w + 1)
            active = [s for s in active if self.sched.slots[s] is not None]
            if active:
                stats.decoded_slots = len(active)
                # non-decoding slots write through the past-the-table
                # sentinel — their garbage token must not touch a live
                # (possibly shared) block
                lens = np.full(self.sched.num_slots, self._oob, np.int32)
                lens[active] = self._written[active]
                mbu = self._mb_bucket(
                    max(len(self._blocks[s]) for s in active))
                # each live slot attends its written positions plus the
                # token it writes now, and the kernel fetches its own
                # blocks up to the one that token lands in, whatever the
                # table's width; from the host's mirror, no device read
                bs = self.block_size
                sp.attrs.update(
                    slots=len(active),
                    kv_tokens=int(self._written[active].sum()) + len(active),
                    table_blocks=mbu,
                    kv_positions_walked=int(
                        ((self._written[active] + bs) // bs).sum()) * bs,
                    ahead=int(bool(self._owed)))
                table = jnp.asarray(self._table[:, :mbu])
                lens = jnp.asarray(lens)
        tokens, owed = self._next, self._owed
        if active:
            with tracer.span("dispatch"):
                # the call donates the cache: its counts are read as they
                # stand before it
                counts = self._expert_counts(copy=True)
                logits, self.cache = self._decode(
                    self._prepared, self.cache, table, tokens, lens)
                self._next = sampling.sample(
                    logits, self._next_rng(),
                    temperature=self.temperature, top_k=self.top_k)
            self._written[active] += 1  # the call writes at the old index
            self._owed = {s: self.sched.slots[s] for s in active}
        elif owed:
            counts = self._expert_counts(copy=False)
            self._owed = {}
        else:
            return
        self._fetch(owed, tokens, stats, sp, counts)

    def _do_dense_decode(self, active: list[int], stats: StepStats,
                         sp) -> None:
        tracer = self.tracer
        with tracer.span("stage"):
            stats.decoded_slots = len(active)
            # each live slot attends its written positions plus the token
            # it writes now; from the host's mirror, no device read
            sp.attrs.update(
                slots=len(active),
                kv_tokens=int(self._written[active].sum()) + len(active),
                ahead=0)
            args = (jnp.asarray(self._last), jnp.asarray(self._written))
        with tracer.span("dispatch"):
            logits, self.cache = self._decode(self._prepared, self.cache,
                                              *args)
        with tracer.span("fetch"):
            toks = np.asarray(
                sampling.sample(
                    logits, self._next_rng(),
                    temperature=self.temperature, top_k=self.top_k,
                )
            )
        with tracer.span("deliver"):
            for slot in active:
                # the decode wrote k/v at the old index
                self._written[slot] += 1
                tok = int(toks[slot])
                self._last[slot] = tok
                self._deliver(slot, tok, stats)
