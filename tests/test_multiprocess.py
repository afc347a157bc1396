"""Multi-process DCN tests (SURVEY.md §4.3): real forked processes with
jax.distributed over localhost — init/psum, divergence detection,
multi-host checkpoint + resume, and coordinated preemption save. The
MultiProcessRunner analog ($TF multi_process_runner.py:107)."""

import os
import signal
import socket
import subprocess
import sys
import time

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "mp_worker.py")
N = 2


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _clean_env() -> dict:
    env = dict(os.environ)
    # the workers set their own platform/device env before importing jax
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def run_cluster(scenario: str, workdir: str = "", extra=(), timeout=180,
                after_ready=None):
    coord = f"localhost:{_free_port()}"
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, scenario, coord, str(N), str(pid),
             workdir or "-", *extra],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_clean_env(),
        )
        for pid in range(N)
    ]
    outs = []
    try:
        if after_ready is not None:
            # wait for every worker to print READY, then act (e.g. SIGTERM)
            deadline = time.time() + timeout
            ready = 0
            import select

            streams = {p.stdout: p for p in procs}
            buffers = {p: [] for p in procs}
            while ready < N and streams and time.time() < deadline:
                r, _, _ = select.select(list(streams), [], [], 1.0)
                for st in r:
                    line = st.readline()
                    if not line:  # EOF: worker died before READY
                        del streams[st]
                        continue
                    buffers[streams[st]].append(line)
                    if line.startswith("READY"):
                        ready += 1
            assert ready == N, (
                "workers never became READY:\n"
                + "\n---\n".join("".join(b) for b in buffers.values())
            )
            after_ready(procs)
            for p in procs:
                rest, _ = p.communicate(timeout=timeout)
                outs.append("".join(buffers[p]) + rest)
        else:
            for p in procs:
                out, _ = p.communicate(timeout=timeout)
                outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} rc={p.returncode}:\n{out}"
    return outs


@pytest.mark.slow
def test_distributed_psum():
    outs = run_cluster("psum")
    for pid, out in enumerate(outs):
        assert f"PSUM-OK {pid}" in out, out


@pytest.mark.slow
def test_hybrid_mesh_two_process_step():
    """2-process ICI×DCN hybrid mesh trains one step with agreeing loss."""
    outs = run_cluster("hybrid")
    for pid, out in enumerate(outs):
        assert f"HYBRID-OK {pid}" in out, out


@pytest.mark.slow
def test_pipeline_stages_across_hosts():
    """dcn_pipe=2: pipeline stages live on DIFFERENT processes — every
    schedule hop (fwd ppermute and its backward transpose) crosses the
    host boundary, with dropout active through the tick."""
    outs = run_cluster("pipeline", timeout=300)
    for pid, out in enumerate(outs):
        assert f"PIPELINE-OK {pid}" in out, out


@pytest.mark.slow
def test_cross_host_divergence_detection():
    outs = run_cluster("divergence")
    for pid, out in enumerate(outs):
        assert f"AGREE-OK {pid}" in out, out
        assert f"DIVERGE-CAUGHT {pid}" in out, out


@pytest.mark.slow
def test_multihost_checkpoint_and_resume(tmp_path):
    d = str(tmp_path / "ckpt")
    outs = run_cluster("checkpoint", d)
    for pid, out in enumerate(outs):
        assert f"CKPT-OK {pid} step=10" in out, out
    # second cluster resumes from step 10 and reaches 20
    outs = run_cluster("checkpoint", d, extra=("--resume",))
    for pid, out in enumerate(outs):
        assert f"CKPT-OK {pid} step=20" in out, out


@pytest.mark.slow
def test_preemption_coordinated_save(tmp_path):
    d = str(tmp_path / "ckpt")

    def sigterm_host0(procs):
        time.sleep(1.0)  # let a few steps run
        procs[0].send_signal(signal.SIGTERM)

    outs = run_cluster("preempt", d, after_ready=sigterm_host0)
    for pid, out in enumerate(outs):
        assert f"PREEMPT-SAVED {pid}" in out, out
