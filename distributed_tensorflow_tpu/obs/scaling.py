"""Scaling reports + provenance stamping — no context-free perf numbers.

A benchmark row that does not say where it ran reads as a chip number
whether or not it is one. The rule (the MLPerf-0.6 TPU-pod paper's
practice of reporting every number with its pod shape):
**every performance number must carry its platform and scaling context
as first-class data.** This module owns that contract:

- ``provenance(mesh=None)`` — one dict every perf artifact embeds: jax
  backend, device platform/kind/count, mesh shape, git sha, hostname.
  ``bench.py``, ``tools/bench_serve.py``, and ``tools/sweep.py`` all
  stamp through here, so a CPU row can never masquerade as a TPU
  number.
- the ``dtf-scaling-1`` report schema (``make_report`` /
  ``write_report`` / ``validate_scaling_report``) — a sweep over the
  mesh-config × workload matrix, one provenance-stamped cell per
  (mesh, workload), with derived per-axis scaling efficiency and
  explicit pass/fail gates. The validator is the CI gate shared with
  ``tools/obs_check.py``.
- ``scaling_efficiency(cells)`` — measured-vs-ideal throughput per
  axis. The ideal is platform-aware: on real accelerators each device
  adds silicon, so ideal(N) = N × 1-dev throughput (``per_device``
  basis); on a host-shared rig (fake CPU devices partitioning ONE
  host's cores) N devices do N× the work on the same silicon, so the
  honest ideal is flat throughput and the measurement is partitioning
  OVERHEAD (``shared_host`` basis). The basis is recorded in every
  efficiency entry — a number without it would be exactly the
  context-free reporting this module exists to end.

Exported metric names (docs/observability.md "Scaling sweeps"):

    sweep_cells_total           counter
    scaling_efficiency          gauge family {cell, workload}

Module top level imports nothing heavy — jax enters lazily inside
``provenance``, so the validator stays usable from device-free tools.
"""

from __future__ import annotations

import json
import math
import os
import socket
import subprocess
from typing import Any, Mapping, Sequence

from .registry import Registry, default_registry

__all__ = [
    "SCHEMA",
    "SWEEP_CELLS",
    "SCALING_EFFICIENCY",
    "PROVENANCE_KEYS",
    "CELL_KEYS",
    "git_sha",
    "provenance",
    "stamp_provenance",
    "note_cell",
    "scaling_efficiency",
    "make_report",
    "write_report",
    "validate_scaling_report",
]

#: report schema tag — bump when the layout changes
SCHEMA = "dtf-scaling-1"

#: metric names (docs/observability.md "Scaling sweeps")
SWEEP_CELLS = "sweep_cells_total"
SCALING_EFFICIENCY = "scaling_efficiency"

#: every provenance block must carry all of these
PROVENANCE_KEYS = (
    "backend", "platform", "device_kind", "device_count",
    "hostname", "git_sha",
)

#: every report cell must carry all of these
CELL_KEYS = (
    "cell", "workload", "axis", "n_devices", "mesh", "global_batch",
    "steps", "steps_per_sec", "examples_per_sec", "provenance",
)

#: efficiency bases (see module docstring)
BASIS_PER_DEVICE = "per_device"
BASIS_SHARED_HOST = "shared_host"


def git_sha(repo_dir: str | None = None) -> str:
    """The tree's HEAD sha (``unknown`` outside a git checkout) — ties a
    measured number to the exact code that produced it."""
    if repo_dir is None:
        repo_dir = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    try:
        out = subprocess.run(
            ["git", "-C", repo_dir, "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        )
    except (OSError, subprocess.SubprocessError):
        return "unknown"
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else "unknown"


def provenance(mesh=None) -> dict:
    """The provenance block: backend truth read from the LIVE jax
    runtime at measurement time — never from flags or intent, which is
    how the r02–r05 CPU fallbacks got recorded as if they were TPU rows.

    With ``mesh``, ``device_count``/``mesh`` describe the devices the
    measurement actually ran on (a sweep cell may use a subset of the
    host's devices); without one, the process's full visible device set.
    """
    import jax  # lazy: the validator/report side stays device-free

    devices = list(mesh.devices.flat) if mesh is not None else jax.devices()
    d0 = devices[0]
    prov = {
        "backend": jax.default_backend(),
        "platform": d0.platform,
        "device_kind": getattr(d0, "device_kind", ""),
        "device_count": len(devices),
        "hostname": socket.gethostname(),
        "git_sha": git_sha(),
        "pid": os.getpid(),
    }
    if mesh is not None:
        prov["mesh"] = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    return prov


def stamp_provenance(payload: dict, mesh=None) -> dict:
    """Add the provenance block to a result dict IN PLACE (and return
    it) — the one-call helper ``bench.py`` / ``tools/bench_serve.py``
    use on their JSON outputs."""
    payload["provenance"] = provenance(mesh)
    return payload


def note_cell(registry: Registry | None = None) -> None:
    """Count one completed sweep cell."""
    reg = registry if registry is not None else default_registry()
    reg.counter(SWEEP_CELLS, "mesh-config x workload sweep cells "
                             "measured").inc()


def _is_shared_host(cell: Mapping) -> bool:
    # fake host-platform devices partition one host's silicon: flat
    # throughput is the ideal there, N× is physically impossible
    return cell["provenance"].get("platform") == "cpu"


def scaling_efficiency(cells: Sequence[Mapping],
                       registry: Registry | None = None) -> list[dict]:
    """Per-cell scaling efficiency vs the same workload's 1-device
    baseline cell: ``throughput_N / (ideal_scale × throughput_1)``,
    where ``ideal_scale`` is ``n_devices`` on real accelerators
    (``per_device`` basis) and 1 on a host-shared CPU rig
    (``shared_host`` basis — the number then measures partitioning
    overhead; see module docstring). Cells without a baseline are
    skipped. When ``registry`` is given, each value is also published
    as the ``scaling_efficiency`` gauge."""
    baselines = {c["workload"]: c for c in cells if c["n_devices"] == 1}
    out = []
    for c in cells:
        if c["n_devices"] == 1:
            continue
        base = baselines.get(c["workload"])
        if base is None or not base["examples_per_sec"]:
            continue
        shared = _is_shared_host(c) and _is_shared_host(base)
        scale = 1 if shared else c["n_devices"]
        value = c["examples_per_sec"] / (scale * base["examples_per_sec"])
        entry = {
            "cell": c["cell"],
            "workload": c["workload"],
            "axis": c["axis"],
            "n_devices": c["n_devices"],
            "basis": BASIS_SHARED_HOST if shared else BASIS_PER_DEVICE,
            "value": round(value, 4),
        }
        out.append(entry)
        if registry is not None:
            registry.gauge(
                SCALING_EFFICIENCY,
                "measured / ideal throughput vs the 1-device baseline",
                cell=c["cell"], workload=c["workload"],
            ).set(value)
    return out


def make_report(cells: Sequence[Mapping],
                efficiency: Sequence[Mapping] = (),
                gates: Sequence[Mapping] = (),
                extra: Mapping | None = None) -> dict:
    """Assemble a ``dtf-scaling-1`` report dict (validate/write it with
    ``write_report``). The header provenance describes the whole
    process; each cell additionally carries its own (same run, but with
    the cell's mesh shape and device subset)."""
    report = {
        "schema": SCHEMA,
        "provenance": provenance(),
        "cells": list(cells),
        "efficiency": list(efficiency),
        "gates": list(gates),
    }
    if extra:
        report.update(extra)
    return report


def write_report(path: str, report: Mapping) -> str:
    """Validate, then atomically write the report as JSON. Raises
    ``ValueError`` on an invalid report — a sweep must never publish a
    file the CI validator would reject."""
    failures = validate_scaling_report(report)
    if failures:
        raise ValueError(
            "refusing to write an invalid scaling report:\n  "
            + "\n  ".join(failures))
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)  # a torn report must not look complete
    return path


def _check_provenance(prov: Any, where: str) -> list[str]:
    if not isinstance(prov, Mapping):
        return [f"{where}: provenance is not a dict"]
    failures = []
    for key in PROVENANCE_KEYS:
        if key not in prov:
            failures.append(f"{where}: provenance missing {key!r}")
    platform = prov.get("platform")
    if "platform" in prov and (not isinstance(platform, str) or not platform):
        failures.append(f"{where}: provenance platform must be a non-empty "
                        f"string, got {platform!r}")
    count = prov.get("device_count")
    if "device_count" in prov and (not isinstance(count, int)
                                   or isinstance(count, bool) or count < 1):
        failures.append(f"{where}: provenance device_count must be a "
                        f"positive int, got {count!r}")
    return failures


def validate_scaling_report(report: Mapping | str) -> list[str]:
    """Schema-check a ``dtf-scaling-1`` report (dict or JSON file path);
    returns failures (empty == pass).

    Checks: schema tag; header provenance complete; ≥1 cell, each with
    the required keys, finite positive throughput, a mesh whose axis
    sizes multiply to ``n_devices``, and a provenance block whose
    platform/device_kind/git_sha AGREE with the header's — the
    anti-masquerade invariant: one run, one backend, so a cell claiming
    a different platform than the process that produced the report is
    exactly the CPU-fallback-as-TPU-number failure this schema exists
    to make impossible. Gate entries must be internally consistent
    (``passed == value >= threshold``)."""
    if isinstance(report, str):
        try:
            with open(report) as f:
                report = json.load(f)
        except (OSError, ValueError) as e:
            return [f"unreadable report: {e}"]
    failures: list[str] = []
    if report.get("schema") != SCHEMA:
        failures.append(f"schema {report.get('schema')!r} != {SCHEMA!r}")
    failures += _check_provenance(report.get("provenance"), "header")
    head_prov = report.get("provenance") or {}

    cells = report.get("cells")
    if not isinstance(cells, list) or not cells:
        failures.append("report has no cells")
        cells = []
    for i, cell in enumerate(cells):
        where = f"cell {i} ({cell.get('cell', '?')})" \
            if isinstance(cell, Mapping) else f"cell {i}"
        if not isinstance(cell, Mapping):
            failures.append(f"{where}: not a dict")
            continue
        for key in CELL_KEYS:
            if key not in cell:
                failures.append(f"{where}: missing {key!r}")
        for key in ("steps_per_sec", "examples_per_sec"):
            v = cell.get(key)
            if key in cell and (not isinstance(v, (int, float))
                                or isinstance(v, bool)
                                or not math.isfinite(v) or v <= 0):
                failures.append(
                    f"{where}: {key} must be a finite positive number, "
                    f"got {v!r}")
        mesh = cell.get("mesh")
        n = cell.get("n_devices")
        if isinstance(mesh, Mapping) and isinstance(n, int):
            sizes = [v for v in mesh.values()
                     if isinstance(v, int) and not isinstance(v, bool)]
            if len(sizes) != len(mesh) or math.prod(sizes) != n:
                failures.append(
                    f"{where}: mesh {dict(mesh)} does not multiply to "
                    f"n_devices={n}")
        failures += _check_provenance(cell.get("provenance"), where)
        prov = cell.get("provenance")
        if isinstance(prov, Mapping):
            for key in ("platform", "device_kind", "git_sha"):
                if key in prov and key in head_prov \
                        and prov[key] != head_prov[key]:
                    failures.append(
                        f"{where}: provenance {key} {prov[key]!r} "
                        f"disagrees with the header's "
                        f"{head_prov[key]!r} — one run has one backend; "
                        f"a mismatched cell is a masqueraded number")

    for i, gate in enumerate(report.get("gates", [])):
        if not isinstance(gate, Mapping):
            failures.append(f"gate {i}: not a dict")
            continue
        value, thr = gate.get("value"), gate.get("threshold")
        if not isinstance(value, (int, float)) \
                or not isinstance(thr, (int, float)):
            failures.append(f"gate {i}: needs numeric value + threshold")
            continue
        if bool(gate.get("passed")) != (value >= thr):
            failures.append(
                f"gate {i}: passed={gate.get('passed')!r} inconsistent "
                f"with value {value} vs threshold {thr}")
    return failures
