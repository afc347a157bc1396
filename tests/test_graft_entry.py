"""The driver entry points stay runnable, including the 16-way pod-shape
mesh point (pp=2 x model=2 x data=4).

dryrun_multichip(n) scales every case with n; at n=16 case 3 becomes the
pp2 x tp2 x dp4 pipeline mesh and case 1 becomes dp4 x fsdp2 x tp2.
These run in subprocesses because the virtual device count is fixed at
backend init (the test rig pins 8)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_dryrun(n):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"), str(n)],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_dryrun_multichip_16way_pod_shape():
    stdout = _run_dryrun(16)
    assert "dryrun[pp/tp/dp] ok" in stdout, stdout
    assert "pipe=2" in stdout and "model=2" in stdout, stdout
    assert "dryrun_multichip ok" in stdout, stdout
