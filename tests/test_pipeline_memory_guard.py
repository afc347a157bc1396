"""Pipeline-memory guard: a pipelined transformer
run whose estimated per-device working set presses v5e HBM warns with
the measured mitigation (train.grad_accum_steps=2) before training
starts — the M=64 pod-grid rows measurably do not fit
(artifacts/podshape_r4/memory_grid.jsonl)."""

import json
import os
import subprocess
import sys

import jax
import pytest

from distributed_tensorflow_tpu.models import transformer as tfm
from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
from distributed_tensorflow_tpu.workloads import runner as runner_lib
from distributed_tensorflow_tpu.workloads.runner import (
    RunConfig, TrainSection, _pipeline_memory_guard,
)
from distributed_tensorflow_tpu.data.text import TextDataConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "pipeline_memory_analysis.py")

TINY = tfm.TransformerConfig(
    vocab_size=512, max_len=64, num_layers=4, d_model=64, num_heads=4,
    d_ff=128, causal=False, pre_ln=False, dtype="float32", remat=True,
)


def _cfg(mesh_pipe=2, **train_kw):
    return RunConfig(
        workload="bert_pretrain", model=TINY,
        mesh=MeshSpec(pipe=mesh_pipe, data=2),
        data=TextDataConfig(dataset="synthetic_mlm", global_batch_size=16,
                            seq_len=64, vocab_size=512),
        train=TrainSection(**train_kw),
    )


@pytest.fixture()
def pipe_mesh(devices):
    return build_mesh(MeshSpec(pipe=2, data=2), devices[:4])


def test_guard_skips_on_cpu_backend(pipe_mesh, monkeypatch):
    # the test rig IS the cpu backend: any subprocess launch is a bug
    def boom(*a, **k):
        raise AssertionError("estimator subprocess launched on cpu rig")

    monkeypatch.setattr(subprocess, "run", boom)
    _pipeline_memory_guard(_cfg(), pipe_mesh)


def test_guard_warns_with_mitigation(pipe_mesh, monkeypatch, caplog):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = {}

    def fake_run(argv, **kw):
        seen["req"] = json.loads(argv[argv.index("--check") + 1])
        seen["env"] = kw.get("env", {})

        class P:
            stdout = json.dumps({"gib": 15.8, "fits_v5e": False}) + "\n"
        return P()

    monkeypatch.setattr(subprocess, "run", fake_run)
    with caplog.at_level("WARNING", logger=runner_lib.__name__):
        _pipeline_memory_guard(_cfg(), pipe_mesh)
    assert "grad_accum_steps" in caplog.text and "15.8" in caplog.text
    # request carries the run's real shape, per DATA-SHARD batch
    assert seen["req"]["S"] == 2 and seen["req"]["batch"] == 8
    assert seen["req"]["M"] == 4  # auto rule: 2 * pipe * virtual
    assert seen["req"]["mlm"] is True
    # the estimator child must never touch the accelerator
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"


def test_guard_quiet_when_fits(pipe_mesh, monkeypatch, caplog):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def fake_run(argv, **kw):
        class P:
            stdout = json.dumps({"gib": 11.5, "fits_v5e": True}) + "\n"
        return P()

    monkeypatch.setattr(subprocess, "run", fake_run)
    with caplog.at_level("WARNING", logger=runner_lib.__name__):
        _pipeline_memory_guard(_cfg(), pipe_mesh)
    assert "EXCEEDS" not in caplog.text


def test_guard_disabled_and_failure_tolerant(pipe_mesh, monkeypatch, caplog):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def boom(*a, **k):
        raise AssertionError("launched despite check_pipeline_memory=False")

    monkeypatch.setattr(subprocess, "run", boom)
    _pipeline_memory_guard(_cfg(check_pipeline_memory=False), pipe_mesh)

    # estimator failure must never kill the run
    def broken(*a, **k):
        raise OSError("no such tool")

    monkeypatch.setattr(subprocess, "run", broken)
    with caplog.at_level("INFO", logger=runner_lib.__name__):
        _pipeline_memory_guard(_cfg(), pipe_mesh)
    assert "estimate unavailable" in caplog.text


@pytest.mark.slow
def test_check_mode_end_to_end():
    """The --check CLI the guard shells out to: real XLA memory analysis
    of the tiny pipelined config, one JSON row out (both objectives)."""
    for mlm in (True, False):
        req = {"model": {"vocab_size": 512, "max_len": 64, "num_layers": 4,
                         "d_model": 64, "num_heads": 4, "d_ff": 128,
                         "causal": not mlm, "pre_ln": False,
                         "dtype": "float32", "remat": True},
               "S": 2, "V": 1, "M": 4, "batch": 8, "seq": 64, "mlm": mlm}
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        proc = subprocess.run(
            [sys.executable, TOOL, "--check", json.dumps(req)],
            capture_output=True, text=True, timeout=600, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        assert row["S"] == 2 and row["M"] == 4
        assert row["per_device_bytes"] > 0
        assert isinstance(row["fits_v5e"], bool)
