#!/usr/bin/env bash
# Fast CI tier — the gates that run in seconds, before the full suite:
#
#   1. tools/smoke_collect.sh  — pytest --collect-only import gate
#      (catches package-wide import regressions, ISSUE 1)
#   2. tools/obs_check.py      — telemetry smoke: registry → Prometheus
#      exposition render → format lint → JSONL round-trip (ISSUE 2)
#   3. tools/dtf_lint.py       — framework-aware static analysis
#      (ISSUE 7, v2 engine ISSUE 10, v3 sharding auditor ISSUE 14):
#      --self-check first (every rule — a rule with NO fixture is
#      itself a self-check failure — must still fire on its shipped
#      fixtures, so the gate cannot rot silently), then the --strict
#      tree lint with all 11 rules (host-sync-in-step and
#      donation-after-use on the cross-module call graph, plus
#      lock-discipline, closed-vocab, exception-hygiene,
#      wall-clock-in-seam, atomic-durable-write, metric-naming, and
#      the v3 partitioning family — shard-rules-coverage totality/
#      liveness of every partition_rules table, mesh-axis-closed-vocab
#      over every PartitionSpec/collective axis literal, and
#      sharding-seam-bypass confining placement construction to
#      parallel/sharding.py — must all be clean over the package and
#      tools; an injected unmatched param or out-of-vocab axis fails
#      here), then the determinism rule alone over
#      tests/ — the chaos/replay oracles must not consume ambient
#      entropy either (relaxed set: pure test scaffolding is exempt
#      from everything but determinism)
#   5. tools/chaos_smoke.py    — resilience smoke: scheduler
#      timeout/cancel/backpressure invariants + one SIGTERM →
#      coordinated-save → resume subprocess round (ISSUE 3) + one
#      supervised SIGTERM + corrupt-newest-checkpoint run that must
#      recover via fallback restore and finish finite (ISSUE 4) + one
#      nan-blame round: a recurring NaN batch skipped in-graph, blamed
#      and quarantined, with the restart replaying around the hole
#      (ISSUE 9) + one fleet gang-restart round: a hung worker detected
#      by missed heartbeats, whole-gang SIGTERM/SIGKILL, incarnation
#      bump, and a relaunch from the latest common valid checkpoint
#      (ISSUE 8) + one ELASTIC round: one of 3 workers hard-dies, the
#      gang shrinks at a barrier instead of stopping, the relaunched
#      replacement rejoins at the next barrier, and restart_recovery
#      waste beats the gang-restart baseline by >= 10x (ISSUE 12) +
#      one serve-fleet failover round: a serve replica SIGKILLed
#      mid-stream, in-flight requests requeued and re-prefilled on the
#      survivor, every stream finished, survivors leak-free (ISSUE 16) +
#      one P2P CATCH-UP round (ISSUE 18): the same elastic death, but the
#      replacement pulls the newest common valid checkpoint from a live
#      survivor over the file control plane instead of replaying — rejoin
#      wall must beat the replay baseline measured in the same run, and
#      every worker's final params must be bit-identical to an
#      uninterrupted same-seed run + one ASYNC-KILL round (ISSUE 18): a
#      worker SIGKILLed INSIDE the async checkpoint commit window — the
#      torn step must be invisible (no .corrupt quarantine, no .pending
#      residue) and the gang must strict-restore the previous step
#   6. tools/postmortem.py     — flight-recorder gates: the supervised
#      round's postmortem dump must pass schema validation AND contain
#      fault → preemption save → restart → quarantine → fallback-restore
#      in causal order (ISSUE 6), the nan-blame round's dump must tell
#      the anomaly story — nan fault → in-graph skip → blame →
#      restart restore (ISSUE 9) — and the fleet round's dump the
#      gang-restart story — worker dead → gang stop → fallback
#      ckpt_restore → fleet restart — in causal order (ISSUE 8), and
#      the elastic round's dump the resize story — worker dead →
#      fleet_shrink → fleet_rejoin → fleet_done (ISSUE 12)
#   6b. tools/postmortem.py --merge + tools/fleet_top.py — fleet
#      observatory gates (ISSUE 15): the chaos fleet and elastic rounds
#      stage every process's flight-recorder dump (plus telemetry
#      snapshots and heartbeats) under artifacts/{fleet,elastic}_dumps;
#      the merge gate aligns the per-process clocks on control-plane
#      anchors and asserts the CROSS-WORKER causal stories, and
#      fleet_top --once exercises the merged text view on the same
#      artifacts
#   7b. tools/postmortem.py --merge — serve-fleet failover gate
#      (ISSUE 16): chaos_smoke's serve-fleet round SIGKILLs one of two
#      serve/replica.py subprocesses mid-stream and stages the
#      per-process dumps under artifacts/serve_fleet_dumps; the merge
#      aligns replica clocks on the serve_route dispatch/ACK handshake
#      and asserts replica-dead -> lane-head requeue -> survivor
#      re-admission -> fleet_done
#   6c. tools/postmortem.py --merge — async-durability gates (ISSUE 18):
#      the async-kill round's merged timeline must show the torn-write
#      invisibility story — ckpt_async_begin → fault_fired
#      [fault=async_commit_kill] → ckpt_restore[fallback=False] (the
#      restore is STRICT: nothing to fall back from, the torn step never
#      became visible) — and the p2p round's timeline the catch-up story:
#      worker dead → survivor catchup_offer → joiner catchup_restore →
#      fleet_rejoin, with no catchup_fallback
#   6d. tools/postmortem.py --merge — hierarchical fault-domain gates
#      (ISSUE 19): chaos_smoke's two-pod outage round SIGKILLs all of
#      pod B mid-run while pod A keeps stepping — the merged timeline
#      must show pod_outage → pod-local restart (each pod-B worker
#      strict-restoring at pod B's OWN quorum, fallback=False) →
#      pod_rejoin, with no global gang stop; the partition round freezes
#      pod B's heartbeat file while the process stays alive — the
#      supervisor must FENCE (no restart, no split-brain), unfence on
#      heal, and judge the slow-beat pod LIVE throughout
#   7c. tools/trace_view.py — request-ledger gate (ISSUE 17): merge the
#      same round's per-process request traces (router + both replica
#      incarnations, including the SIGKILLed victim's surviving
#      per-pump dump) into ONE per-request timeline, require a killed
#      request's merged trace to carry the FULL causal chain — submit →
#      route → admit → prefill → first token → death-requeue → re-route
#      → re-admit → re-prefill → token → finish — with spans from at
#      least two distinct replica processes, and render the slowest-k
#      tail-attribution report (phase durations must sum to measured
#      TTFT within 1%)
#
# Usage: tools/ci_fast.sh   (extra args are passed to smoke_collect)
set -euo pipefail
cd "$(dirname "$0")/.."
bash tools/smoke_collect.sh "$@"
env JAX_PLATFORMS=cpu python tools/obs_check.py >/dev/null
env JAX_PLATFORMS=cpu python tools/dtf_lint.py --self-check
env JAX_PLATFORMS=cpu python tools/dtf_lint.py --strict \
  distributed_tensorflow_tpu tools
env JAX_PLATFORMS=cpu python tools/dtf_lint.py --strict \
  --rules wall-clock-in-seam tests
env JAX_PLATFORMS=cpu python tools/chaos_smoke.py
env JAX_PLATFORMS=cpu python tools/postmortem.py \
  "${DTF_CHAOS_POSTMORTEM:-artifacts/chaos_postmortem.jsonl}" --quiet \
  --expect 'fault_fired[fault=sigterm],ckpt_save[trigger=preemption],sup_restart,fault_fired[fault=ckpt_corrupt],ckpt_quarantine,ckpt_restore[fallback=True]'
env JAX_PLATFORMS=cpu python tools/postmortem.py \
  "${DTF_ANOMALY_POSTMORTEM:-artifacts/anomaly_postmortem.jsonl}" --quiet \
  --expect 'fault_fired[fault=nan_batch],anomaly_skip,anomaly_blame,ckpt_restore'
env JAX_PLATFORMS=cpu python tools/postmortem.py \
  "${DTF_FLEET_POSTMORTEM:-artifacts/fleet_postmortem.jsonl}" --quiet \
  --expect 'fleet_worker_dead,fleet_gang_stop,ckpt_restore[fallback=True],fleet_restart,fleet_done'
env JAX_PLATFORMS=cpu python tools/postmortem.py \
  "${DTF_ELASTIC_POSTMORTEM:-artifacts/elastic_postmortem.jsonl}" --quiet \
  --expect 'fleet_worker_dead,fleet_shrink,fleet_rejoin,fleet_done'
# fleet observatory (ISSUE 15): re-merge the chaos rounds' per-process
# dumps into ONE cross-worker timeline (clock alignment anchored on the
# control-plane handshakes) and gate the CROSS-PROCESS causal stories —
# the gang stop precedes every worker's restore; the shrink release
# precedes every survivor's application of the new sharding
env JAX_PLATFORMS=cpu python tools/postmortem.py --merge \
  "${DTF_FLEET_DUMPS:-artifacts/fleet_dumps}"/fleet.jsonl \
  "${DTF_FLEET_DUMPS:-artifacts/fleet_dumps}"/flightrec-w*.jsonl \
  --out "${DTF_FLEET_MERGED:-artifacts/fleet_merged_postmortem.jsonl}" --quiet \
  --expect 'fleet_gang_stop,ckpt_restore[src=w0i2],fleet_restart,fleet_done' \
  --expect 'fleet_gang_stop,ckpt_restore[src=w1i2],fleet_restart,fleet_done'
env JAX_PLATFORMS=cpu python tools/postmortem.py --merge \
  "${DTF_ELASTIC_DUMPS:-artifacts/elastic_dumps}"/fleet.jsonl \
  "${DTF_ELASTIC_DUMPS:-artifacts/elastic_dumps}"/flightrec-w*.jsonl \
  --out "${DTF_ELASTIC_MERGED:-artifacts/elastic_merged_postmortem.jsonl}" --quiet \
  --expect 'fleet_worker_dead,fleet_hold,elastic_hold[src=w0i1],fleet_shrink,elastic_release[src=w0i1],fleet_rejoin,fleet_done' \
  --expect 'fleet_worker_dead,fleet_hold,elastic_hold[src=w2i1],fleet_shrink,elastic_release[src=w2i1],fleet_rejoin,fleet_done' \
  --expect 'fleet_shrink,elastic_release[src=w1i1],fleet_rejoin,fleet_done'
# async durability (ISSUE 18): the async-kill round's merged timeline
# must show the torn step was INVISIBLE — the victim began an async
# commit, died inside it, and the whole gang strict-restored the
# previous step (fallback=False: the torn step never existed to fall
# back from)
env JAX_PLATFORMS=cpu python tools/postmortem.py --merge \
  "${DTF_ASYNCKILL_DUMPS:-artifacts/asynckill_dumps}"/fleet.jsonl \
  "${DTF_ASYNCKILL_DUMPS:-artifacts/asynckill_dumps}"/flightrec-w*.jsonl \
  --out "${DTF_ASYNCKILL_MERGED:-artifacts/asynckill_merged_postmortem.jsonl}" --quiet \
  --expect 'ckpt_async_begin,fault_fired[fault=async_commit_kill],ckpt_restore[fallback=False]' \
  --expect 'fleet_worker_dead,fleet_gang_stop,fleet_restart,fleet_done'
# p2p catch-up (ISSUE 18): the rejoin story on the merged timeline — a
# survivor exported an offer and the joiner imported it (each chain
# anchored on fleet-clock events; offer->import causality is enforced
# by the file protocol itself, rename-published offers cannot be
# imported before they exist)
env JAX_PLATFORMS=cpu python tools/postmortem.py --merge \
  "${DTF_P2P_DUMPS:-artifacts/p2p_dumps}"/fleet.jsonl \
  "${DTF_P2P_DUMPS:-artifacts/p2p_dumps}"/flightrec-w*.jsonl \
  --out "${DTF_P2P_MERGED:-artifacts/p2p_merged_postmortem.jsonl}" --quiet \
  --expect 'fleet_worker_dead,catchup_offer,fleet_done' \
  --expect 'fleet_worker_dead,catchup_restore[src=w1i1],fleet_rejoin,fleet_done'
# hierarchical fault domains (ISSUE 19): pod B's outage must read as a
# POD-local story on the merged timeline — outage, per-pod-quorum
# strict restore on BOTH pod-B workers, rejoin — while pod A never
# stops (the round itself asserts pod A's forward progress on the raw
# staged dumps; the absence of fleet_gang_stop here is the merged-view
# half of the same invariant)
env JAX_PLATFORMS=cpu python tools/postmortem.py --merge \
  "${DTF_POD_DUMPS:-artifacts/pod_dumps}"/fleet.jsonl \
  "${DTF_POD_DUMPS:-artifacts/pod_dumps}"/flightrec-p*.jsonl \
  --out "${DTF_POD_MERGED:-artifacts/pod_merged_postmortem.jsonl}" --quiet \
  --expect 'pod_outage[pod=1],pod_restart[pod=1],pod_rejoin[pod=1],fleet_done' \
  --expect 'pod_outage[pod=1],ckpt_restore[src=p1w0i2,fallback=False],pod_rejoin[pod=1],fleet_done' \
  --expect 'pod_outage[pod=1],ckpt_restore[src=p1w1i2,fallback=False],pod_rejoin[pod=1],fleet_done'
# partition tolerance (ISSUE 19): a severed control plane is FENCED,
# never restarted — one fence, one unfence, and the slow-beat pod is
# judged live (gray failure ≠ partition)
env JAX_PLATFORMS=cpu python tools/postmortem.py --merge \
  "${DTF_PARTITION_DUMPS:-artifacts/partition_dumps}"/fleet.jsonl \
  "${DTF_PARTITION_DUMPS:-artifacts/partition_dumps}"/flightrec-p*.jsonl \
  --out "${DTF_PARTITION_MERGED:-artifacts/partition_merged_postmortem.jsonl}" --quiet \
  --expect 'fault_fired[fault=control_plane_partition],pod_fence[pod=1],pod_unfence[pod=1],fleet_done' \
  --expect 'fault_fired[fault=slow_control_plane],fleet_done'
env JAX_PLATFORMS=cpu python tools/fleet_top.py --once \
  --fleet-dir "${DTF_FLEET_DUMPS:-artifacts/fleet_dumps}" >/dev/null
# serve fleet (ISSUE 16): re-merge the serve-fleet failover round's
# per-process dumps (router/supervisor + surviving replicas, clocks
# aligned on the serve_route dispatch/ACK handshake) and gate the
# failover story: replica dead -> requeue at lane head -> a survivor
# admits the re-prefilled request -> fleet_done
env JAX_PLATFORMS=cpu python tools/postmortem.py --merge \
  "${DTF_SERVE_FLEET_DUMPS:-artifacts/serve_fleet_dumps}"/fleet.jsonl \
  "${DTF_SERVE_FLEET_DUMPS:-artifacts/serve_fleet_dumps}"/flightrec-w*.jsonl \
  --out "${DTF_SERVE_FLEET_MERGED:-artifacts/serve_fleet_merged_postmortem.jsonl}" --quiet \
  --expect 'serve_replica_dead,serve_requeue,serve_admit,fleet_done'
# request ledger (ISSUE 17): one killed request's merged trace must tell
# the WHOLE story across both replica processes on one aligned timeline,
# and every slow request's TTFT must decompose into named phases that
# sum to the measurement
env JAX_PLATFORMS=cpu python tools/trace_view.py \
  "${DTF_SERVE_FLEET_DUMPS:-artifacts/serve_fleet_dumps}"/reqtrace-router.jsonl \
  "${DTF_SERVE_FLEET_DUMPS:-artifacts/serve_fleet_dumps}"/reqtrace-w*.jsonl \
  --out "${DTF_SERVE_FLEET_TRACE:-artifacts/serve_fleet_trace_merged.jsonl}" \
  --slowest 3 \
  --expect 'queue_wait,route,admission_block,prefill_chunks,decode_gap,requeue_reprefill,route,admission_block,prefill_chunks,decode_gap,finish' \
  --require-replicas 2 >/dev/null
echo "ci_fast: all gates passed"
