"""Scaling observatory (ISSUE 11): provenance stamping, the
dtf-scaling-1 report schema, and the tools/sweep.py mesh×workload
harness on the 8-device CPU rig."""

import copy
import json

import pytest

from distributed_tensorflow_tpu import obs
from distributed_tensorflow_tpu.obs import scaling


def _fake_prov(**over):
    prov = {"backend": "cpu", "platform": "cpu", "device_kind": "cpu",
            "device_count": 8, "hostname": "t", "git_sha": "cafe"}
    prov.update(over)
    return prov


def _fake_cell(name="dp8", n=8, data=8, model=1, eps=40960.0, **over):
    cell = {
        "cell": name, "workload": "mlp", "axis": "dp", "n_devices": n,
        "mesh": {"pipe": 1, "data": data, "fsdp": 1, "seq": 1,
                 "expert": 1, "model": model},
        "global_batch": 128 * data, "steps": 8, "steps_per_sec": 40.0,
        "examples_per_sec": eps, "provenance": _fake_prov(),
    }
    cell.update(over)
    return cell


# ---------------------------------------------------------------------------
# provenance
# ---------------------------------------------------------------------------


def test_provenance_reads_live_backend(devices):
    prov = scaling.provenance()
    for key in scaling.PROVENANCE_KEYS:
        assert key in prov, key
    assert prov["backend"] == "cpu" and prov["platform"] == "cpu"
    assert prov["device_count"] >= 8
    assert isinstance(prov["git_sha"], str) and prov["git_sha"]
    assert prov["hostname"]


def test_provenance_with_mesh_describes_the_subset(devices):
    from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=4, model=2), devices[:8])
    prov = scaling.provenance(mesh)
    assert prov["device_count"] == 8
    assert prov["mesh"] == {"pipe": 1, "data": 4, "fsdp": 1, "seq": 1,
                            "expert": 1, "model": 2}
    one = build_mesh(MeshSpec(data=1), devices[:1])
    assert scaling.provenance(one)["device_count"] == 1


def test_stamp_provenance_in_place(devices):
    row = {"metric": "x", "value": 1.0}
    out = scaling.stamp_provenance(row)
    assert out is row and row["provenance"]["platform"] == "cpu"


def test_git_sha_unknown_outside_repo(tmp_path):
    assert scaling.git_sha(str(tmp_path)) == "unknown"


# ---------------------------------------------------------------------------
# report schema + efficiency
# ---------------------------------------------------------------------------


def test_validator_roundtrip_and_masquerade(tmp_path):
    base = _fake_cell("1dev", n=1, data=1, eps=15360.0)
    cell = _fake_cell()
    report = {"schema": scaling.SCHEMA, "provenance": _fake_prov(),
              "cells": [base, cell],
              "efficiency": scaling.scaling_efficiency([base, cell]),
              "gates": []}
    assert scaling.validate_scaling_report(report) == []

    # write_report validates, writes atomically, and round-trips
    path = str(tmp_path / "r.json")
    scaling.write_report(path, report)
    assert scaling.validate_scaling_report(path) == []
    assert json.load(open(path))["schema"] == scaling.SCHEMA

    # the masquerade: a TPU-claiming cell under a CPU header is invalid
    bad = copy.deepcopy(report)
    bad["cells"][1]["provenance"]["platform"] = "tpu"
    failures = scaling.validate_scaling_report(bad)
    assert any("masquerade" in f for f in failures)
    with pytest.raises(ValueError, match="refusing to write"):
        scaling.write_report(str(tmp_path / "bad.json"), bad)


def test_validator_negative_cases():
    base = _fake_cell("1dev", n=1, data=1, eps=15360.0)
    good = {"schema": scaling.SCHEMA, "provenance": _fake_prov(),
            "cells": [base, _fake_cell()], "efficiency": [], "gates": []}

    def failures_after(mutate):
        bad = copy.deepcopy(good)
        mutate(bad)
        return scaling.validate_scaling_report(bad)

    assert any("schema" in f for f in
               failures_after(lambda r: r.update(schema="nope")))
    assert any("missing 'provenance'" in f for f in
               failures_after(lambda r: r["cells"][0].pop("provenance")))
    assert any("finite positive" in f for f in failures_after(
        lambda r: r["cells"][0].update(steps_per_sec=float("nan"))))
    assert any("does not multiply" in f for f in failures_after(
        lambda r: r["cells"][1]["mesh"].update(data=2)))
    assert any("no cells" in f for f in
               failures_after(lambda r: r.update(cells=[])))
    assert any("inconsistent" in f for f in failures_after(
        lambda r: r.update(gates=[{"threshold": 0.8, "value": 0.5,
                                   "passed": True}])))


def test_scaling_efficiency_bases():
    """shared_host basis (CPU rig): ideal is flat throughput;
    per_device basis (real accelerators): ideal is N × 1-dev."""
    base = _fake_cell("1dev", n=1, data=1, eps=1000.0)
    dp8 = _fake_cell("dp8", eps=800.0)
    reg = obs.Registry()
    eff = scaling.scaling_efficiency([base, dp8], registry=reg)
    assert eff == [{"cell": "dp8", "workload": "mlp", "axis": "dp",
                    "n_devices": 8, "basis": "shared_host",
                    "value": 0.8}]
    assert reg.get(scaling.SCALING_EFFICIENCY, cell="dp8",
                   workload="mlp").value == pytest.approx(0.8)

    # a TPU run computes against the N× ideal
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite"}
    base_t = _fake_cell("1dev", n=1, data=1, eps=1000.0,
                        provenance=_fake_prov(**tpu))
    dp8_t = _fake_cell("dp8", eps=6400.0, provenance=_fake_prov(**tpu))
    eff_t = scaling.scaling_efficiency([base_t, dp8_t])
    assert eff_t[0]["basis"] == "per_device"
    assert eff_t[0]["value"] == pytest.approx(6400.0 / (8 * 1000.0))

    # no 1-dev baseline → no entry (not a crash)
    assert scaling.scaling_efficiency([dp8]) == []


def test_sweep_cells_counter():
    reg = obs.Registry()
    scaling.note_cell(reg)
    scaling.note_cell(reg)
    assert reg.get(scaling.SWEEP_CELLS).value == 2


# ---------------------------------------------------------------------------
# the sweep harness end-to-end (the acceptance surface)
# ---------------------------------------------------------------------------


def test_sweep_dryrun_report_and_gate(tmp_path, capsys, devices):
    """2-cell CI shape: schema-valid report, every cell provenance
    stamped with the honest platform, dp gate evaluated, metrics
    isolated per cell via Registry.delta (counted in the process
    registry without any reset)."""
    from distributed_tensorflow_tpu.obs.registry import default_registry
    from tools import sweep

    reg = default_registry()
    before = reg.snapshot()
    out = str(tmp_path / "scaling.json")
    rc = sweep.main(["--dryrun", "--out", out, "--expect-platform", "cpu",
                     "--steps", "6"])
    capsys.readouterr()
    assert rc == 0
    assert scaling.validate_scaling_report(out) == []
    report = json.load(open(out))
    assert [c["cell"] for c in report["cells"]] == \
        ["1dev", "dp8", "pod2_dp2"]
    for cell in report["cells"]:
        assert cell["provenance"]["platform"] == "cpu"
        assert cell["provenance"]["git_sha"] == \
            report["provenance"]["git_sha"]
        assert cell["steps_per_sec"] > 0
        assert cell["eval_batches"] == 2  # distributed eval ran per cell
        assert "mfu" not in cell  # the CPU has no peak to divide by
    # the two-level cell is stamped with its fault-domain shape
    pod_cell = report["cells"][2]
    assert pod_cell["pods"] == 2 and pod_cell["devices_per_pod"] == 2
    assert report["gates"] and report["gates"][0]["axis"] == "dp"
    assert report["gates"][0]["passed"]

    d = reg.delta(before)
    assert d[scaling.SWEEP_CELLS]["value"] == 3
    assert d["eval_steps_total"]["value"] == 6


def test_sweep_dryrun_rejects_explicit_matrix(capsys):
    """--dryrun fixes the matrix; a silently-ignored --cells/--workloads
    would measure the wrong cells and be trusted anyway."""
    from tools import sweep

    with pytest.raises(SystemExit) as e:
        sweep.main(["--dryrun", "--cells", "dp4_tp2"])
    assert e.value.code == 2
    assert "drop --cells" in capsys.readouterr().err


def test_sweep_expect_platform_mismatch_fails(tmp_path, capsys, devices):
    from tools import sweep

    rc = sweep.main(["--cells", "1dev", "--workloads", "mlp",
                     "--steps", "4", "--eval-batches", "0",
                     "--expect-platform", "tpu",
                     "--out", str(tmp_path / "r.json")])
    capsys.readouterr()
    assert rc == 4  # an honest cpu report can't satisfy a tpu expectation


def test_sweep_full_mesh_matrix(tmp_path, capsys, devices):
    """The full 8-mesh matrix (the MULTICHIP dryrun shapes plus the
    two-level pod cells) over the mlp workload: ≥ 8 provenance-stamped
    cells in one report."""
    from tools import sweep

    out = str(tmp_path / "full.json")
    rc = sweep.main(["--workloads", "mlp", "--steps", "6", "--out", out,
                     "--eval-batches", "1"])
    capsys.readouterr()
    assert rc == 0
    report = json.load(open(out))
    assert scaling.validate_scaling_report(report) == []
    assert len(report["cells"]) == 8
    axes = {c["axis"] for c in report["cells"]}
    assert {"dp", "tp", "fsdp", "hybrid", "pod"} <= axes
    assert {e["cell"] for e in report["efficiency"]} >= \
        {"dp2", "dp8", "dp4_tp2", "dp2_fsdp2_tp2", "dp8_hybrid2"}
