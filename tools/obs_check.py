#!/usr/bin/env python
"""Telemetry smoke gate — seconds, not minutes (tools/ci_fast.sh tier).

Registers one metric of every kind, exercises span tracing and the
JSONL logger, renders Prometheus text exposition, and lints the output
against the exposition-format grammar with a regex — so a formatting
regression (bad label escaping, non-cumulative buckets, missing
``_sum``/``_count``) fails loudly before anything tries to scrape a
real run. Also gates the flight-recorder dump schema (required keys,
monotonic timestamps, known event kinds, ring-overflow accounting —
obs/flightrec.py) and the goodput/MFU surface (``goodput_fraction`` /
``mfu`` gauges, ``wasted_seconds_total{cause}`` counters, the shared
percentile read-back — obs/goodput.py). No device, no model: the obs
layer is plain host code.

Usage:
    python tools/obs_check.py
"""

import json
import re
import sys
import tempfile

sys.path.insert(0, __file__.rsplit("/", 2)[0])

# Prometheus text-exposition grammar (version 0.0.4), line-by-line.
_METRIC_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABELS = r"\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\"(?:,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\}"
_VALUE = r"(?:[-+]?(?:[0-9]*\.)?[0-9]+(?:[eE][-+]?[0-9]+)?|[-+]?Inf|NaN)"
LINE_RE = re.compile(
    r"^(?:"
    r"# HELP " + _METRIC_NAME + r" .*"
    r"|# TYPE " + _METRIC_NAME + r" (?:counter|gauge|histogram|summary|untyped)"
    r"|" + _METRIC_NAME + r"(?:" + _LABELS + r")? " + _VALUE + r"(?: [0-9]+)?"
    r")$"
)


def check(verbose: bool = True) -> list[str]:
    """Returns a list of failures (empty == pass)."""
    from distributed_tensorflow_tpu import obs

    failures: list[str] = []
    reg = obs.Registry()

    # one of each kind, with and without labels
    reg.counter("obs_check_events_total", "smoke events").inc(3)
    reg.gauge("obs_check_occupancy", "smoke gauge").set(0.75)
    h = reg.histogram("obs_check_latency_seconds", "smoke latency")
    for v in (1e-4, 3e-3, 3e-3, 0.2, 5.0, 1e4):  # incl. overflow bucket
        h.observe(v)
    reg.counter("obs_check_finished_total", "by reason", reason="eos").inc()
    reg.counter("obs_check_finished_total", "by reason",
                reason='max"len\\path').inc()  # escaping torture

    tracer = obs.Tracer(registry=reg, annotate=False)
    with tracer.span("check"):
        with tracer.span("inner"):
            pass
    if [s.name for s in tracer.events] != ["check.inner", "check"]:
        failures.append(f"tracer span paths wrong: {list(tracer.events)}")

    text = obs.render(reg)
    for i, line in enumerate(text.splitlines(), 1):
        if not LINE_RE.match(line):
            failures.append(f"line {i} fails exposition lint: {line!r}")

    # cumulative-bucket + count/sum invariants
    hist_count = h.count
    last_bucket = max(
        int(m.group(1))
        for m in re.finditer(
            r'obs_check_latency_seconds_bucket\{le="\+Inf"\} (\d+)', text
        )
    )
    if last_bucket != hist_count:
        failures.append(
            f"+Inf bucket {last_bucket} != histogram count {hist_count}"
        )

    # JSONL round-trip
    with tempfile.NamedTemporaryFile("r", suffix=".jsonl") as tmp:
        with obs.JsonlLogger(tmp.name, reg, chief_only=False) as jl:
            jl.event("smoke", answer=42)
            jl.write_snapshot(tag="check")
        recs = [json.loads(line) for line in open(tmp.name)]
        if len(recs) != 2 or recs[0]["answer"] != 42:
            failures.append(f"jsonl round-trip wrong: {recs}")
        snap = recs[1]["metrics"]
        if snap["obs_check_events_total"]["value"] != 3:
            failures.append(f"snapshot counter wrong: {snap}")

    failures += _check_flightrec()
    failures += _check_goodput(reg)
    failures += _check_fleetview()
    failures += _check_reqtrace()

    if verbose:
        print(text, end="")
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        if not failures:
            print(f"OK: {len(text.splitlines())} exposition lines, "
                  f"{len(reg.collect())} metrics, jsonl round-trip clean",
                  file=sys.stderr)
    return failures


def _check_flightrec() -> list[str]:
    """Flight-recorder gate: emit through a small ring, dump, and push
    the dump through the same schema validator tools/postmortem.py and
    CI use — plus negative cases the validator must catch."""
    import os

    from distributed_tensorflow_tpu.obs import flightrec as fr

    failures: list[str] = []
    rec = fr.FlightRecorder(capacity=4)
    rec.emit("train_start", step=0)
    rec.emit("fault_fired", step=3, fault="sigterm")
    rec.emit("ckpt_save", step=4, trigger="preemption")
    rec.emit("sup_restart", restart=1, cause="preemption")
    rec.emit("ckpt_restore", step=2, fallback=True)
    rec.emit("train_stop", step=8, reason="num_steps=8")
    if len(rec) != 4 or rec.dropped != 2:
        failures.append(
            f"ring overflow wrong: len={len(rec)} dropped={rec.dropped} "
            f"(want 4/2)")
    try:
        # deliberate negative: the closed vocabulary must reject this
        rec.emit("not_a_kind")  # dtflint: disable=closed-vocab
        failures.append("emit accepted an unknown event kind")
    except ValueError:
        pass

    with tempfile.TemporaryDirectory(prefix="obs_check_fr_") as d:
        path = rec.dump(os.path.join(d, "pm.jsonl"), reason="obs_check")
        for f in fr.validate_dump(path):
            failures.append(f"flightrec dump invalid: {f}")
        if not fr.contains_in_order(
                rec.events(),
                [("sup_restart", {}), ("ckpt_restore", {"fallback": True})]):
            failures.append("contains_in_order missed a present sequence")
        if fr.contains_in_order(
                rec.events(), [("ckpt_restore", {}), ("sup_restart", {})]):
            failures.append("contains_in_order accepted a reversed sequence")
        # the validator must catch what emit() can never produce: an
        # unknown kind, a decreasing timestamp, a key-less record
        bad = os.path.join(d, "bad.jsonl")
        with open(path) as f_in:
            lines = f_in.read().splitlines()
        # reviewed: scratch corpus for the validator's must-fail probes,
        # torn-on-crash is irrelevant (the file exists only inside this
        # check's tempdir)
        with open(bad, "w") as f_out:  # dtflint: disable=atomic-durable-write
            f_out.write(lines[0] + "\n")
            f_out.write('{"t": 5.0, "kind": "meteor_strike"}\n')
            f_out.write('{"t": 4.0, "kind": "train_start"}\n')
            f_out.write('{"kind": "train_stop"}\n')
            f_out.write('{"t": 6.0, "kind": "train_stop", "step": "x"}\n')
            # a 5th event under a header claiming 4: count mismatch
            f_out.write('{"t": 7.0, "kind": "train_stop"}\n')
        bad_failures = fr.validate_dump(bad)
        for needle in ("unknown event kind", "decreases",
                       "missing/non-numeric", "non-int step",
                       "events, dump has"):
            if not any(needle in b for b in bad_failures):
                failures.append(
                    f"validator missed a '{needle}' violation: "
                    f"{bad_failures}")
    return failures


def _check_goodput(reg) -> list[str]:
    """Goodput/MFU gate: the gauge names the docs promise exist with the
    arithmetic they promise, device-free (peak/chips passed in)."""
    from distributed_tensorflow_tpu.obs import goodput

    failures: list[str] = []
    goodput.note_productive(3.0, registry=reg)
    goodput.note_wasted(goodput.WASTE_COMPILE_WARMUP, 0.5, registry=reg)
    goodput.note_wasted(goodput.WASTE_RETRY_BACKOFF, 0.25, registry=reg)
    goodput.note_wasted(goodput.WASTE_RESTART_RECOVERY, 0.25, registry=reg)
    frac = reg.get(goodput.GOODPUT_FRACTION)
    if frac is None or abs(frac.value - 0.75) > 1e-9:
        failures.append(f"goodput_fraction gauge wrong: "
                        f"{frac and frac.value} (want 0.75)")
    if abs(goodput.goodput_fraction(reg) - 0.75) > 1e-9:
        failures.append("goodput_fraction() read-back disagrees with gauge")
    for cause in goodput.WASTE_CAUSES:
        if reg.get(goodput.WASTED_SECONDS, cause=cause) is None:
            failures.append(f"missing wasted_seconds_total{{cause={cause}}}")
    try:
        # deliberate negative: the cause vocabulary must reject this
        goodput.note_wasted("procrastination", 1.0, registry=reg)  # dtflint: disable=closed-vocab
        failures.append("note_wasted accepted an unknown cause")
    except ValueError:
        pass
    # fwd 1e12 FLOPs/step × ×3 training multiplier × 1.5 steps/s over
    # 3 chips × 1e12 peak → MFU 1.5 exactly, published as the gauge
    mfu = goodput.train_mfu(1e12, 1.5, n_chips=3, peak_per_chip=1e12,
                            registry=reg)
    gauge = reg.get(goodput.MFU)
    if gauge is None or abs(gauge.value - mfu) > 1e-12 or abs(mfu - 1.5) > 1e-9:
        failures.append(f"mfu gauge/return mismatch: gauge="
                        f"{gauge and gauge.value} returned={mfu} (want 1.5)")
    # shared percentile read-back == the histogram's own percentile()
    h = reg.get("obs_check_latency_seconds")
    ms = goodput.latency_percentiles_ms(reg, "obs_check_latency_seconds")
    if abs(ms["p50_ms"] - round(float(h.percentile(0.5)) * 1e3, 3)) > 1e-9:
        failures.append(f"latency_percentiles_ms disagrees with "
                        f"Histogram.percentile: {ms}")
    return failures


def _check_fleetview() -> list[str]:
    """Fleet-observatory gate (obs/fleetview.py): a worker snapshot
    round-trips through the ``dtf-fleetsnap-1`` validator, a consistent
    set of per-process dumps merges into a valid ``dtf-fleetmerge-1``
    timeline — and the must-fail corpora are each caught: a torn
    snapshot, a snapshot claiming another worker's label, a worker dump
    with no clock anchor, a worker label collision, and causally
    impossible anchors. Pure host code: no device, no jax."""
    import copy
    import os

    from distributed_tensorflow_tpu.obs import fleetview as fv
    from distributed_tensorflow_tpu.obs import flightrec as fr
    from distributed_tensorflow_tpu.obs.registry import Registry

    failures: list[str] = []

    class _Clk:
        def __init__(self, t):
            self.t = float(t)

        def __call__(self):
            return self.t

    with tempfile.TemporaryDirectory(prefix="obs_check_fv_") as d:
        # -- snapshot schema + crash-safety ------------------------------
        wclk = _Clk(100.0)
        wrec = fr.FlightRecorder(clock=wclk)
        wreg = Registry()
        wreg.counter("goodput_productive_seconds_total").inc(3.0)
        exporter = fv.SnapshotExporter(
            fv.fleetsnap_path(d, 0), worker=0, incarnation=1,
            registry=wreg, flightrec=wrec, clock=wclk, min_interval_s=5.0)
        wrec.emit("train_start", step=0)
        path = exporter.export(step=1, phase="train")
        snap = fv.read_snapshot(path)
        for f in fv.validate_snapshot(snap, expect_worker=0):
            failures.append(f"fleetsnap invalid: {f}")
        if exporter.export(step=2) is not None:  # inside the rate limit
            failures.append("exporter ignored min_interval_s")
        if exporter.export(step=2, force=True) is None:
            failures.append("exporter force= did not bypass the rate limit")
        # a crash mid-export leaves a torn .tmp and the PREVIOUS
        # snapshot readable — simulate the torn sibling and verify reads
        # never see it
        # reviewed: deliberately torn scratch sibling for the crash-safety
        # probe — the .tmp path is exactly what a mid-export kill leaves
        with open(path + ".tmp", "w") as f_torn:  # dtflint: disable=atomic-durable-write
            f_torn.write('{"schema": "dtf-fleetsnap-1", "worker"')
        good = fv.read_snapshot(path)
        if good is None or good["seq"] != 2:
            failures.append("previous snapshot unreadable next to a torn "
                            ".tmp")
        # a torn snapshot FILE (external corruption) reads as absent
        torn = os.path.join(d, "torn.json")
        # reviewed: scratch corpus for the must-fail probe
        with open(torn, "w") as f_t:  # dtflint: disable=atomic-durable-write
            f_t.write('{"schema": "dtf-fleetsnap-1", "wor')
        if fv.read_snapshot(torn) is not None:
            failures.append("torn snapshot did not read as absent")
        bad = copy.deepcopy(snap)
        bad["schema"] = "dtf-fleetsnap-0"
        if not any("schema" in f for f in fv.validate_snapshot(bad)):
            failures.append("snapshot validator missed a schema violation")
        if not any("collision" in f
                   for f in fv.validate_snapshot(snap, expect_worker=1)):
            failures.append("snapshot validator missed a worker label "
                            "collision")

        # -- merged timeline + anchor must-fails -------------------------
        pid = os.getpid()
        fclk = _Clk(500.0)
        frec = fr.FlightRecorder(clock=fclk)
        frec.emit("fleet_start", workers=1, incarnation=1)
        fclk.t = 501.0
        frec.emit("fleet_launch", worker=0, incarnation=1, pid=pid)
        fclk.t = 510.0
        frec.emit("fleetsnap_merge", worker=0, seq=1, pid=pid,
                  incarnation=1)
        fclk.t = 540.0
        frec.emit("fleet_done", incarnation=1)
        fleet_dump = frec.dump(os.path.join(d, "fleet.jsonl"), "obs_check")
        wclk.t = 130.0
        wrec.emit("train_stop", step=2, reason="done")
        worker_dump = wrec.dump(os.path.join(d, "w0.jsonl"), "obs_check",
                                extra={"worker": 0, "incarnation": 1})
        header, events, merge_failures = fv.merge_timelines(
            fleet_dump, [worker_dump], reason="obs_check")
        for f in merge_failures:
            failures.append(f"consistent dumps failed to merge: {f}")
        merged = os.path.join(d, "merged.jsonl")
        fv.write_merged(merged, header, events)
        for f in fv.validate_merged_dump(merged):
            failures.append(f"merged dump invalid: {f}")
        if not fr.contains_in_order(events, [
                ("fleet_launch", {}), ("train_start", {"src": "w0i1"}),
                ("fleetsnap_merge", {}), ("fleet_done", {})]):
            failures.append("merged timeline lost the launch->merge->done "
                            "causal order")
        # no anchor: a fleet dump with no fleet_launch for this worker
        bare = fr.FlightRecorder(clock=_Clk(500.0))
        bare.emit("fleet_start", workers=1, incarnation=1)
        bare_dump = bare.dump(os.path.join(d, "bare.jsonl"), "obs_check")
        _, _, mf = fv.merge_timelines(bare_dump, [worker_dump])
        if not any("anchor missing" in f for f in mf):
            failures.append(f"merge missed a missing clock anchor: {mf}")
        # collision: two dumps claiming the same (worker, incarnation)
        _, _, mf = fv.merge_timelines(fleet_dump,
                                      [worker_dump, worker_dump])
        if not any("collision" in f for f in mf):
            failures.append(f"merge missed a worker label collision: {mf}")
        # impossible anchors: the worker's life (30s) cannot fit the
        # fleet's launch->done window (1s)
        tight = fr.FlightRecorder(clock=_Clk(500.0))
        tight.emit("fleet_launch", worker=0, incarnation=1, pid=pid)
        tight_clk = _Clk(501.0)
        tight.clock = tight_clk
        tight.emit("fleet_done", incarnation=1)
        tight_dump = tight.dump(os.path.join(d, "tight.jsonl"), "obs_check")
        _, _, mf = fv.merge_timelines(tight_dump, [worker_dump])
        if not any("inconsistent" in f for f in mf):
            failures.append(f"merge missed inconsistent clock anchors: {mf}")
        # missing identity: a dump without worker/incarnation can't merge
        anon_dump = wrec.dump(os.path.join(d, "anon.jsonl"), "obs_check")
        _, _, mf = fv.merge_timelines(fleet_dump, [anon_dump])
        if not any("identity" in f for f in mf):
            failures.append(f"merge missed a missing worker identity: {mf}")
    return failures


def _check_reqtrace() -> list[str]:
    """Request-ledger gate (obs/reqtrace.py): a two-process fake-clock
    serve story — router + one replica with a skewed clock, a
    death-requeue hop included — must dump valid ``dtf-reqtrace-1``
    files, merge into ONE per-request timeline whose spans still
    partition wall time, and the must-fail corpora — a torn dump, a
    span ending before it starts, an unknown phase, a duplicate rid —
    must each be caught. Pure host code: no device, no jax."""
    import os

    from distributed_tensorflow_tpu.obs import reqtrace as rt

    failures: list[str] = []

    class _Clk:
        def __init__(self, t):
            self.t = float(t)

        def __call__(self):
            return self.t

    with tempfile.TemporaryDirectory(prefix="obs_check_rt_") as d:
        rclk, wclk = _Clk(100.0), _Clk(900.0)  # 800s apart, same story
        router = rt.ReqTrace(src="router", clock=rclk)
        replica = rt.ReqTrace(src="w0i0", clock=wclk)

        # rid 1: submit -> route -> ingest -> admit/prefill -> token ->
        # death-requeue -> re-route (the chain the serve seams emit)
        router.transition(1, "queue_wait", lane="interactive")
        rclk.t = 101.5
        router.transition(1, "route", replica=0, requeue=0)
        wclk.t = 901.5  # ingest at the same fake instant as dispatch:
        # the dispatch->ingest lower bound recovers the skew EXACTLY
        replica.transition(1, "admission_block", requeue=0)
        wclk.t = 902.0
        replica.transition(1, "prefill_chunks", slot=0)
        wclk.t = 903.0
        replica.transition(1, "decode_gap")  # replica samples...
        rclk.t = 103.0
        router.transition(1, "decode_gap", n=1)  # ...router delivers
        rclk.t = 104.0
        router.transition(1, "requeue_reprefill", replica=0, delivered=1)
        rclk.t = 105.0
        router.finish(1, "max_new_tokens")
        try:
            router.transition(1, "warp_speed")  # dtflint: disable=closed-vocab
            failures.append("transition accepted an unknown phase")
        except ValueError:
            pass

        rp = router.dump(os.path.join(d, "router.jsonl"), "obs_check")
        wp = replica.dump(os.path.join(d, "w0.jsonl"), "obs_check",
                          extra={"worker": 0, "incarnation": 0})
        for p in (rp, wp):
            for f in rt.validate_dump(p):
                failures.append(f"reqtrace dump invalid: {f}")

        header, merged, mf = rt.merge_traces(rp, [wp], reason="obs_check")
        failures.extend(f"consistent traces failed to merge: {m}"
                        for m in mf)
        off = header.get("offsets", {}).get("w0i0")
        if off is None or abs(off - (-800.0)) > 1e-6:
            failures.append(f"merge recovered offset {off}, want -800.0")
        if len(merged) != 1 or merged[0]["rid"] != 1:
            failures.append(f"merged records wrong: {merged}")
        else:
            rec = merged[0]
            if sorted(rec["sources"]) != ["router", "w0i0"]:
                failures.append(f"merged sources wrong: {rec['sources']}")
            try:
                parts = rt.phase_partition(rec)
                if abs(parts[0][1] - 100.0) > 1e-9 \
                        or abs(parts[-1][2] - 105.0) > 1e-9:
                    failures.append(
                        f"merged timeline bounds wrong: {parts}")
            except ValueError as e:
                failures.append(f"merged spans do not partition: {e}")
            if not rt.span_chain_matches(rec, [
                    "queue_wait", "route", "admission_block",
                    "prefill_chunks", "decode_gap", "requeue_reprefill",
                    ("finish", {"reason": "max_new_tokens"})]):
                failures.append("merged record lost the causal chain")
        mp = os.path.join(d, "merged.jsonl")
        rt.write_merged(mp, header, merged)
        if rt.load_dump(mp)[0].get("schema") != rt.MERGED_SCHEMA:
            failures.append("write_merged lost the merged schema tag")

        # the validator must catch what transition() can never produce
        with open(rp) as f_in:
            lines = f_in.read().splitlines()
        ok_rec = json.loads(lines[1])

        def corrupt(name, mutate_lines, needle):
            bad = os.path.join(d, name)
            # reviewed: scratch corpus for the validator's must-fail
            # probes, torn-on-crash is irrelevant (tempdir-only file)
            with open(bad, "w") as f_out:  # dtflint: disable=atomic-durable-write
                f_out.write("\n".join(mutate_lines) + "\n")
            got = rt.validate_dump(bad)
            if not any(needle in g for g in got):
                failures.append(
                    f"validator missed a {needle!r} violation: {got}")

        # torn dump: header claims more records than the file holds
        corrupt("torn.jsonl", [lines[0]], "torn dump")
        # span end before start
        bent = json.loads(lines[1])
        bent["spans"][0]["t1"] = bent["spans"][0]["t0"] - 1.0
        corrupt("bent.jsonl", [lines[0], json.dumps(bent)], "before start")
        # unknown phase
        alien = json.loads(lines[1])
        alien["spans"][0]["phase"] = "warp_speed"
        corrupt("alien.jsonl", [lines[0], json.dumps(alien)],
                "unknown phase")
        # duplicate rid within one dump
        two = json.loads(lines[0])
        two["records"] = 2
        corrupt("dup.jsonl",
                [json.dumps(two), json.dumps(ok_rec), json.dumps(ok_rec)],
                "duplicate rid")
    return failures


def main() -> int:
    return 1 if check() else 0


if __name__ == "__main__":
    raise SystemExit(main())
