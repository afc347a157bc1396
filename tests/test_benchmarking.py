"""utils/benchmarking — the shared harness scaffolding both benches
(bench.py, tools/bench_bert.py) depend on for honest numbers."""

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_tpu.utils import benchmarking as bm


def test_describe_devices_cpu_rig(monkeypatch):
    devices, n, platform, on_tpu = bm.describe_devices()
    assert n == len(jax.devices()) >= 1
    assert platform == "cpu" and not on_tpu
    # no TPU and no explicit CPU request: a benchmark does not fall back
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="does not fall back"):
        bm.describe_devices()


def test_timed_steps_counts_and_syncs():
    calls = []

    def step(state, batch):
        calls.append(batch)
        return state + batch, {"loss": jnp.asarray(float(state + batch))}

    state, sps, loss = bm.timed_steps(
        step, 0.0, lambda: 1.0, warmup=2, measured=5,
    )
    # warmup + measured steps all ran; state chained through every one
    assert len(calls) == 7
    assert state == 7.0
    assert loss == 7.0
    assert sps > 0


def test_timed_steps_rejects_nonfinite_loss():
    def step(state, batch):
        return state, {"loss": jnp.asarray(float("nan"))}

    # RuntimeError, not assert: must fire even under `python -O`
    with pytest.raises(RuntimeError, match="non-finite"):
        bm.timed_steps(step, None, lambda: None, warmup=1, measured=1)


def test_timed_steps_warmup_zero():
    """warmup=0 is public API: no boundary sync to a metrics dict that
    doesn't exist yet (timing then includes compile — caller's choice)."""
    def step(state, batch):
        return state + 1, {"loss": jnp.asarray(1.0)}

    state, sps, loss = bm.timed_steps(
        step, 0, lambda: None, warmup=0, measured=3,
    )
    assert state == 3 and loss == 1.0 and sps > 0


def test_timed_steps_pulls_fresh_batches():
    """next_batch is called once per step — the pipeline-fed window
    contract (a prefetcher iterator advances per step)."""
    it = iter(range(100))

    def step(state, batch):
        return state, {"loss": jnp.asarray(float(batch))}

    _, _, loss = bm.timed_steps(
        step, None, lambda: next(it), warmup=3, measured=4,
    )
    assert loss == 6.0  # 7th value pulled (0-indexed)


def test_sync_by_value_forces_scalar():
    assert bm.sync_by_value({"loss": jnp.asarray(2.5)}) == 2.5
    assert isinstance(bm.sync_by_value({"loss": jnp.asarray(1)}), float)


@pytest.mark.slow
def test_bench_py_json_contract(tmp_path):
    """The driver consumes bench.py's stdout as ONE JSON line with the
    BASELINE metric schema; a regression here silently costs the round
    its artifact. Runs the real script (explicit JAX_PLATFORMS=cpu, toy
    size) at tiny step counts and validates the contract."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_STEPS": "3"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be exactly one line: {lines}"
    row = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "mfu",
                "platform", "n_chips", "global_batch", "block_impl",
                "pipeline_efficiency"):
        assert key in row, key
    assert row["metric"] == "resnet50_images_per_sec_per_chip"
    assert row["value"] > 0 and row["unit"] == "images/sec/chip"
    assert row["mfu"] is None  # the CPU has no peak to divide by

    # the unpinned-TPU A/B selection path (forced on CPU): must still be
    # one JSON line, now with the losing variant recorded
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_STEPS": "3",
             "BENCH_FORCE_AB": "1"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    row = json.loads(lines[0])
    assert row["block_impl"] in ("fused", "standard")
    assert row["alt_block_impl"] in ("fused", "standard")
    assert row["alt_block_impl"] != row["block_impl"]
    assert row["alt_images_per_sec_per_chip"] > 0
