"""From a profiler trace to numbers: device busy and idle time, device time
by operation and by jitted step, collective time that no compute hides, and
the longest idle gaps named by what the host was doing.

`load` turns an `.xplane.pb` (read with `jax.profiler.ProfileData`, nothing
but JAX) into plain lists of `(name, start_ns, end_ns)`; every reduction
below works on those lists, so the tests check them on hand-built traces.

On a TPU (libtpu 0.0.34) each chip is a plane `/device:TPU:<n>` whose line
`XLA Ops` holds one event per executed HLO instruction, named by the
instruction's whole text (`%flash_attention_fwd.3 = (bf16[...]) custom-call(
...)`; `op_name` keeps what stands before ` = `), and whose line `XLA
Modules` holds one event per run of a jitted program
(`jit_train_step(<fingerprint>)`). Copies and transfers that run beside the
instruction stream sit on `Async XLA Ops` and are not counted as busy time.
Host threads are lines of the plane `/host:CPU`; `TraceAnnotation`s are
events there.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: HLO instructions that move data between chips
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|send|recv)")
#: host events shorter than this name no idle gap worth reporting
MIN_HOST_NS = 20_000
#: and none longer than this: a thread's whole life explains no gap
MAX_HOST_NS = 5_000_000_000


@dataclasses.dataclass
class Trace:
    #: device plane name -> {"ops": [...], "modules": [...]} of events
    #: `(name, start_ns, end_ns)`
    devices: dict
    #: host events (annotations and runtime calls), any thread
    host: list


def op_name(text: str) -> str:
    """`%fusion.12 = f32[8]{0} fusion(...)` -> `fusion.12`."""
    return text[1:].split(" = ", 1)[0] if text.startswith("%") else text


def load(path: str, device_ids=None) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            if device_ids is not None and int(m.group(1)) not in device_ids:
                continue
            rec = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    rec[key] = [(op_name(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events]
            devices[plane.name] = rec
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.duration_ns >= MIN_HOST_NS]
    return Trace(devices, host)


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and the first event names of a trace file: what to look
    at by hand before trusting a reduction on a new toolchain."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name for e in events})[:limit]
            out.append(f"  line {line.name!r}: {len(events)} events {names}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals) -> list:
    """Sorted, disjoint cover of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Parts of the disjoint sorted cover ``a`` that ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def base_name(name: str) -> str:
    """`fusion.123` -> `fusion`, `jit_train_step(1234)` -> `jit_train_step`:
    one name for all instances of an operation or a program."""
    return re.sub(r"(\(\d+\)|[._]\d+)+$", "", name)


def _spans(events) -> list:
    return [(s, e) for _, s, e in events]


def _mean(values) -> float | None:
    values = list(values)
    return sum(values) / len(values) if values else None


# ---------------------------------------------------------------------------
# reductions (seconds unless the name says otherwise)
# ---------------------------------------------------------------------------


def window_seconds(trace: Trace) -> float | None:
    """The traced window on the device's own clock: from the start of the
    first operation on any chip to the end of the last. No host clock
    enters, so the seconds the profiler takes to switch on and off, in
    which nothing is dispatched, are not part of it."""
    spans = [se for d in trace.devices.values() for se in _spans(d["ops"])]
    if not spans:
        return None
    return (max(e for _, e in spans) - min(s for s, _ in spans)) / 1e9


def busy_seconds(trace: Trace) -> float | None:
    """Seconds in which an operation ran on the device: the union of the op
    intervals, averaged over the chips."""
    return _mean(total(union(_spans(d["ops"]))) / 1e9
                 for d in trace.devices.values())


def op_seconds(trace: Trace, pattern: str) -> tuple:
    """(seconds, calls) of the operations whose name matches ``pattern`` at
    its start, averaged over the chips."""
    rx = re.compile(pattern)
    per_dev = [[e for e in d["ops"] if rx.match(e[0])]
               for d in trace.devices.values()]
    if not per_dev or not any(per_dev):
        return None, 0
    return (_mean(total(_spans(ev)) / 1e9 for ev in per_dev),
            round(_mean(len(ev) for ev in per_dev)))


def module_runs(trace: Trace, pattern: str) -> list:
    """Per chip, the runs of the jitted programs matching ``pattern``."""
    rx = re.compile(pattern)
    return [[e for e in d["modules"] if rx.match(e[0])]
            for d in trace.devices.values()]


def busy_in_runs(trace: Trace, pattern: str) -> tuple:
    """(device-busy seconds inside the runs of the programs matching
    ``pattern``, runs), each a mean over the chips: the union of the op
    intervals that fall inside each run's span on `XLA Modules`. Time and
    count both come from the device's own lines."""
    seconds, runs = [], []
    for d, mods in zip(trace.devices.values(), module_runs(trace, pattern)):
        ops = union(_spans(d["ops"]))
        starts = [o[0] for o in ops]
        busy = 0.0
        for _, s, e in mods:
            lo = max(bisect.bisect_right(starts, s) - 1, 0)
            hi = bisect.bisect_left(starts, e)
            busy += total(clip(ops[lo:hi], s, e))
        seconds.append(busy / 1e9)
        runs.append(len(mods))
    if not runs or not max(runs):
        return None, 0
    return _mean(seconds), round(_mean(runs))


def busy_per_run_ms(trace: Trace, pattern: str) -> tuple:
    """(mean device-busy milliseconds inside one run of the program, runs)."""
    seconds, runs = busy_in_runs(trace, pattern)
    return (1e3 * seconds / runs, runs) if runs else (None, 0)


def exposed_collective_seconds(trace: Trace) -> float | None:
    """Seconds of collective operations during which no other operation ran
    on the same chip, averaged over the chips. None where the trace holds no
    collective (one chip)."""
    out = []
    for d in trace.devices.values():
        coll = union((s, e) for n, s, e in d["ops"] if COLLECTIVE.match(n))
        if not coll:
            continue
        compute = union((s, e) for n, s, e in d["ops"]
                        if not COLLECTIVE.match(n))
        out.append(total(subtract(coll, compute)) / 1e9)
    return _mean(out)


def self_seconds_by_name(events) -> dict:
    """Device time by operation name. An operation that lies wholly inside
    another (the body of a `while`) is taken out of the outer one's time, so
    nested work counts once; one that merely overlaps another (an
    asynchronous collective) counts in full."""
    out: dict = {}
    stack: list = []  # [name, start, end, time under children]

    def pop():
        name, start, end, child = stack.pop()
        out[name] = out.get(name, 0.0) + max(end - start - child, 0.0)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            pop()
        name = base_name(name)
        if stack and e > stack[-1][2]:  # overlaps, not nested
            out[name] = out.get(name, 0.0) + (e - s)
            continue
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        pop()
    return {k: v / 1e9 for k, v in out.items()}


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[name, seconds], ...]: where the first chip's device time went."""
    if not trace.devices:
        return []
    dev = next(iter(trace.devices.values()))
    by_name = self_seconds_by_name(dev["ops"])
    return [[k, v] for k, v in sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:n]]


def top_modules(trace: Trace, n: int = 10) -> list:
    """[[program, runs, seconds of its runs' spans], ...] on the first chip:
    which jitted programs the traced window ran, from `XLA Modules`."""
    if not trace.devices:
        return []
    out: dict = {}
    for name, s, e in next(iter(trace.devices.values()))["modules"]:
        rec = out.setdefault(base_name(name), [0, 0.0])
        rec[0] += 1
        rec[1] += (e - s) / 1e9
    return [[k, r, t] for k, (r, t) in sorted(
        out.items(), key=lambda kv: -kv[1][1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[[what the host was doing, idle seconds], ...] on the first chip:
    every gap between operations goes to the shortest host event that covers
    its middle (of two as short, the one that began later), and the gaps add
    up by that name. One sweep over gaps and host events, both in order of
    time: a traced window of a busy serving cell holds some hundred
    thousand of each."""
    if not trace.devices:
        return []
    dev = next(iter(trace.devices.values()))
    busy = union(_spans(dev["ops"]))
    if not busy:
        return []
    gaps = subtract([(busy[0][0], busy[-1][1])], busy)
    host = sorted((ev for ev in trace.host if ev[2] - ev[1] <= MAX_HOST_NS),
                  key=lambda ev: ev[1])
    out: dict = {}
    covering: list = []  # heap of (length, -start, end, name): began by `mid`
    nxt = 0
    for s, e in gaps:
        mid = (s + e) / 2
        while nxt < len(host) and host[nxt][1] <= mid:
            name, hs, he = host[nxt]
            heapq.heappush(covering, (he - hs, -hs, he, name))
            nxt += 1
        while covering and covering[0][2] < mid:  # ended before this gap
            heapq.heappop(covering)
        name = base_name(covering[0][3]) if covering else "unattributed"
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]
