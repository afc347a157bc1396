"""What every cell's run shares: finding the cell's files by the names in
`BENCHMARK.json`, the look for the chips, the profiler, the per-layer
readers, and the one result line.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: scratch inside the checkout (git-ignored): traces, removed once read
SCRATCH = os.path.join(ROOT, ".bench_tmp")
T_PROCESS_START = time.perf_counter()


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def retired() -> dict:
    """Cells that left the manifest: name -> `{"for", "pr", "why"}`
    (`benchmark/retired.json`). Documents and tests outside the benchmark's
    own directories may still name one; its name then stands for the cell
    that took its place."""
    return load_json(HERE, "retired.json")


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's entry, its configuration and its traffic mix, each found
    by the name `BENCHMARK.json` gives."""
    manifest = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        gone = retired().get(name)
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json " + (
            f"(have {sorted(cells)})" if gone is None else
            f"since PR {gone['pr']}: run {gone['for']!r} ({gone['why']})"))
    cell = cells[name]
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    return {
        "manifest": manifest, "cell": cell,
        "config": load_json(root, config["file"]),
        "traffic": load_json(HERE, "traffic", f"{cell['traffic']}.json"),
    }


def require_chips(n: int):
    """The machine's ``n`` TPU devices, or exit non-zero with no result: no
    number of this benchmark comes from anything but the chip. A machine
    with more chips than the cell asks for is refused too: the program
    builds its mesh over every device it sees."""
    os.environ["JAX_PLATFORMS"] = "tpu"  # never the CPU with a warning
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"benchmark: no TPU: {e}")
    if devices[0].platform != "tpu" or len(devices) != n:
        raise SystemExit(f"benchmark: need {n} TPU chip(s), found "
                         f"{len(devices)} x {devices[0].platform}")
    return devices


def device_record(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# profiler
# ---------------------------------------------------------------------------


class Tracing:
    """A profiler trace of part of the window, written inside the checkout
    and removed once reduced. The Python tracer stays off: it records every
    call and slows the host it is meant to observe."""

    def __init__(self, cell: str):
        self.dir = os.path.join(SCRATCH, "trace", cell)
        #: host clock before the profiler is switched on, once it is on,
        #: and when it is switched off: they steer the run and say which
        #: requests the switch-on disturbed; no traced metric is taken over
        #: them
        self.t_begin = self.t0 = self.t1 = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.t_begin = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def running(self) -> bool:
        return self.t0 is not None and self.t1 is None

    def reduce(self, device_ids):
        """The reduced trace, or None where no trace was taken."""
        from benchmark import trace_reduce

        files = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if self.t1 is None or not files:
            return None
        trace = trace_reduce.load(files[0], device_ids=device_ids)
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# per-layer metrics: one data file and one small reader each
# ---------------------------------------------------------------------------


def per_layer_metrics(manifest: dict, cell_name: str, ctx: dict) -> dict:
    """Every per-layer metric that lists this cell (or lists none), read by
    the reader its file under `benchmark/metrics/` names. A reader that
    finds nothing to read returns None and the metric is left out. A
    retired cell's name reads as the cell that took its place."""
    cell_name = retired().get(cell_name, {}).get("for", cell_name)
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        spec = load_json(HERE, "metrics", f"{m['name']}.json")
        module, _, func = spec["reader"].partition(":")
        reader = getattr(importlib.import_module(
            f"benchmark.readers.{module}"), func or "read")
        value = reader(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def traced_outputs(files: dict, tracing: Tracing, devices, run: dict,
                   out: dict, device: dict) -> None:
    """What a `--trace 1` run adds to its result: the cell's per-layer
    metrics, the device's busy seconds and traced window, the breakdown.
    Busy time and window are both the device's own: the host's clock enters
    no metric whose source is the device trace."""
    from benchmark import trace_reduce

    reduced = tracing.reduce(device_ids={d.id for d in devices})
    if reduced is None:
        raise RuntimeError("the traced run left no trace to read")
    ctx = {"trace": reduced, "cfg": files["config"],
           "traffic": files["traffic"], "chips": len(devices),
           "device_kind": devices[0].device_kind, "run": run}
    out["metrics"] = per_layer_metrics(
        files["manifest"], files["cell"]["name"], ctx)
    device["busy_s"] = trace_reduce.busy_seconds(reduced)
    device["window_s"] = trace_reduce.window_seconds(reduced)
    out["breakdown"] = {"device_ops": trace_reduce.top_ops(reduced),
                        "idle_gaps": trace_reduce.idle_gaps(reduced)}
    out["programs"] = trace_reduce.top_modules(reduced)


def end_to_end_metrics(manifest: dict, cell_name: str, values: dict) -> dict:
    out = {}
    for m in manifest["end_to_end"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        if m["name"] in values:
            out[m["name"]] = {"value": float(values[m["name"]]),
                              "unit": m["unit"]}
    return out


def emit(result: dict) -> None:
    """The result: one JSON object as the last line of standard output,
    with the numbers compared under the key that comes last."""
    checks = result.pop("checks", {})
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
