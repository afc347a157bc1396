"""Serve replica worker — one fleet replica as a real process.

Run as ``python -m distributed_tensorflow_tpu.serve.replica`` by the
serve-fleet chaos rig (tools/chaos_smoke.py); the supervisor talks to it
through ``serve.fleet.SubprocessReplica``. One process = one paged
``ServeEngine`` plus the fleet-worker observability kit training workers
carry (tests/chaos_worker.py): a heartbeat under the fleet workdir
(incarnation-fenced, pulsed so liveness ticks while idle), periodic
telemetry snapshots, and an identity-stamped flight-recorder dump on
every clean exit — the worker half of the merged serve-fleet postmortem.

Protocol (the file-based data plane, serve/fleet.py):

- **Inbox.** The supervisor atomically writes one JSON payload per
  dispatched request under ``replica-<i>/inbox/``; the replica ingests
  them in sequence order, emits the ``serve_route`` ACK for each (AFTER
  reading the payload, BEFORE any observable effect — the same
  emission-ordering rule as ``elastic_hold``, making the ACK a sound
  clock anchor: router dispatch happens-before replica ingest), and
  submits to the engine at the payload's lane priority.
- **Events stream.** Generated tokens and finishes append to
  ``replica-<i>/events-i<k>.jsonl`` (append-only, flushed per loop; the
  client tolerates a torn tail line). The terminal record is the
  ``drained`` leak audit: after ``drain()`` the block allocator must be
  all-free on every SURVIVING replica — a SIGKILLed one never writes
  it, which is the point.
- **Drain.** A ``DRAIN`` sentinel (or SIGTERM) stops ingestion, decodes
  the residents to completion, writes the audit, exports a final
  snapshot, dumps the flight recorder and the span ring
  (``flightrec-w<i>i<k>.jsonl``, ``spans-w<i>i<k>.jsonl``), and exits 0.
  Any other exit is a death the supervisor requeues around.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import sys
import time

logger = logging.getLogger(__name__)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workdir", required=True,
                    help="fleet workdir (heartbeats, snapshots, inbox)")
    ap.add_argument("--index", type=int, required=True)
    ap.add_argument("--incarnation", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--blocks", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0,
                    help="model weights seed — IDENTICAL across replicas, "
                         "so a re-prefilled stream continues bit-identically "
                         "on any survivor")
    ap.add_argument("--pulse-s", type=float, default=0.2)
    ap.add_argument("--idle-sleep-s", type=float, default=0.005)
    args = ap.parse_args(argv)

    from distributed_tensorflow_tpu.models import transformer as tfm
    from distributed_tensorflow_tpu.obs import fleetview
    from distributed_tensorflow_tpu.obs import flightrec as fr
    from distributed_tensorflow_tpu.obs.registry import default_registry
    from distributed_tensorflow_tpu.obs.reqtrace import ReqTrace
    from distributed_tensorflow_tpu.resilience import liveness
    from distributed_tensorflow_tpu.serve import fleet as serve_fleet
    from distributed_tensorflow_tpu.serve.engine import ServeEngine

    rec = fr.default_recorder()
    writer = liveness.HeartbeatWriter(
        liveness.heartbeat_path(args.workdir, args.index),
        incarnation=args.incarnation, pulse_interval_s=args.pulse_s)
    exporter = fleetview.SnapshotExporter(
        fleetview.fleetsnap_path(args.workdir, args.index),
        worker=args.index, incarnation=args.incarnation,
        min_interval_s=0.5)

    # the tiny CPU-runnable decoder every serve rig shares
    # (examples/serve.py); weights are seed-deterministic, so every
    # replica of one fleet serves the same model
    cfg = tfm.TransformerConfig(
        vocab_size=256, max_len=128, num_layers=2, d_model=64, num_heads=4,
        d_ff=128, dropout=0.0, dtype="float32", causal=True, pre_ln=True,
    )
    # this replica's half of the request ledger (obs/reqtrace.py): one
    # span record per rid this incarnation served; src carries the
    # (worker, incarnation) identity into the merged timeline
    reqtrace = ReqTrace(src=f"w{args.index}i{args.incarnation}")
    engine = ServeEngine.with_random_params(
        cfg, seed=args.seed, num_slots=args.slots, paged=True,
        block_size=args.block_size, num_blocks=args.blocks,
        prefill_chunk=args.prefill_chunk, registry=default_registry(),
        reqtrace=reqtrace)
    bridge = serve_fleet.EngineBridge(engine)

    inbox = serve_fleet.replica_inbox_dir(args.workdir, args.index)
    os.makedirs(inbox, exist_ok=True)
    sentinel = serve_fleet.drain_path(args.workdir, args.index)
    events_path = serve_fleet.replica_events_path(
        args.workdir, args.index, args.incarnation)

    stop = {"drain": False}

    def _sigterm(signum, frame):
        stop["drain"] = True

    signal.signal(signal.SIGTERM, _sigterm)

    def dump_flightrec() -> None:
        base = os.path.join(
            os.path.abspath(os.path.expanduser(args.workdir)),
            f"flightrec-w{args.index}i{args.incarnation}")
        # never clobber (chaos_worker's rule): two dumps claiming one
        # (worker, incarnation) slot must fail the merge LOUDLY as a
        # label collision, not silently replace the first story
        path, n = f"{base}.jsonl", 0
        while os.path.exists(path):
            n += 1
            path = f"{base}-{n}.jsonl"
        rec.dump(path, reason="serve_replica_exit",
                 extra={"worker": args.index,
                        "incarnation": args.incarnation})
        # the engine's span ring beside it, under the same suffix
        # (obs/trace.py: the last minutes' steps phase by phase, each
        # call's size, each request's queue/prefill/decode phases)
        head, tail = os.path.split(path)
        engine.tracer.dump(os.path.join(
            head, tail.replace("flightrec", "spans", 1)))

    trace_path = os.path.join(
        os.path.abspath(os.path.expanduser(args.workdir)),
        f"reqtrace-w{args.index}i{args.incarnation}.jsonl")
    trace_seq = {"dumped": -1}

    def dump_reqtrace(reason: str) -> None:
        """Atomically (re)write this incarnation's trace dump when the
        ledger changed. Called BEFORE token events are appended to the
        events stream, so any token the router observed has its trace
        transitions already durable — a SIGKILLed victim's spans for the
        killed request survive in its last dump."""
        if reqtrace.seq == trace_seq["dumped"]:
            return
        trace_seq["dumped"] = reqtrace.seq
        reqtrace.dump(trace_path, reason=reason,
                      extra={"worker": args.index,
                             "incarnation": args.incarnation})

    tokens_out = 0
    with open(events_path, "a") as out:  # append-only event stream

        def emit(events) -> None:
            nonlocal tokens_out
            for ev in events:
                if ev.get("kind") == "token":
                    tokens_out += 1
                out.write(json.dumps(ev) + "\n")
            if events:
                out.flush()

        emit([{"kind": "ready", "pid": os.getpid(),
               "incarnation": args.incarnation}])
        writer.beat(phase="serve")
        while not stop["drain"] and not os.path.exists(sentinel):
            for name in sorted(os.listdir(inbox)):
                path = os.path.join(inbox, name)
                try:
                    with open(path) as f:
                        payload = json.load(f)
                except (OSError, ValueError) as e:
                    logger.warning("replica %d: unreadable dispatch %s "
                                   "(%s); skipping", args.index, name, e)
                    os.remove(path)
                    continue
                # the ingest ACK — after the read, before any effect:
                # router dispatch strictly happens-before this emit, so
                # the merge may anchor on the rid pair
                rec.emit("serve_route", rid=payload["rid"],
                         lane=payload.get("lane"), replica=args.index)
                bridge.accept(payload)
                os.remove(path)
            busy = bridge.busy
            events = bridge.pump()
            dump_reqtrace("serve_replica_pump")  # durable before emit
            emit(events)
            writer.beat(step=tokens_out)
            try:
                exporter.export(step=tokens_out)
            except OSError:
                logger.exception("replica %d: snapshot export failed",
                                 args.index)
            if not busy:
                time.sleep(args.idle_sleep_s)
        events = bridge.drain()
        dump_reqtrace("serve_replica_drain")
        emit(events)
    try:
        exporter.export(step=tokens_out, force=True)
    except OSError:
        logger.exception("replica %d: final snapshot export failed",
                         args.index)
    dump_flightrec()
    writer.finish("done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
