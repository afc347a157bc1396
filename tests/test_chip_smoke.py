"""chip_smoke.py off the chip: the rehearsal passes on the CPU, the real
run refuses to start without a TPU, and importing the package takes no
device (a launcher that imports it must not take the chip)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, timeout):
    env = {**os.environ,
           # two fake devices: every leg still shards, compiles stay short
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_rehearsal_passes_on_cpu(tmp_path):
    proc = _run(["--rehearsal"], tmp_path, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 2}}
    # nothing a rehearsal prints can be mistaken for a chip result
    assert all("rehearsal" in ln for ln in lines), lines
    out = proc.stdout
    for leg in ("resnet50_imagenet: losses", "gpt_lm: losses",
                "paged kernel vs XLA path at decode shape",
                "paged kernel vs XLA path at prefill-chunk shape",
                "serve spec_k=0: tokens", "serve spec_k=4: tokens",
                "steps 11..12"):
        assert leg in out, leg


def test_refuses_to_run_without_a_tpu(tmp_path):
    """No rehearsal flag, no TPU: non-zero within seconds, says why, and
    prints no result — whatever JAX_PLATFORMS the environment exports."""
    proc = _run([], tmp_path, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stdout and "--rehearsal" in proc.stdout
    assert '"ok"' not in proc.stdout


def test_importing_the_package_initialises_no_backend():
    code = (
        "import distributed_tensorflow_tpu, jax\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   cwd=REPO, timeout=120)


def test_mosaic_calls_reads_operand_shapes(tmp_path):
    """The optimized-HLO reader, on a line copied from a v5e dump (the
    base64 kernel body cut)."""
    sys.path.insert(0, REPO)
    import chip_smoke

    hlo = tmp_path / "module_0005.jit_train_step.cl_1.after_optimizations.txt"
    hlo.write_text(
        "HloModule jit_train_step, f32[50304,768]\n"
        "  %jvp_flash_attention_fwd_.1 = (bf16[2,4,256,64]{3,2,1,0:T(8,128)"
        "(2,1)S(1)}, f32[2,4,256,8]{3,2,1,0:T(8,128)S(1)}) custom-call("
        "%copy.3, %copy.4, %copy.5, %broadcast_in_dim.1), "
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        "{bf16[2,4,256,64]{3,2,1,0}, bf16[2,4,256,64]{3,2,1,0}, "
        "bf16[2,4,256,64]{3,2,1,0}, s32[2,1,256]{2,1,0}}, "
        'frontend_attributes={kernel_metadata={}}, metadata={op_name="jit('
        'train_step)/jvp(flash_attention_fwd)/pallas_call"}\n'
        "  %fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop\n")
    path = chip_smoke.dumped_step(str(tmp_path), "jit_train_step",
                                  "[50304,768]")
    (call,) = chip_smoke.mosaic_calls(path)
    assert call["kernel"] == "jvp_flash_attention_fwd"
    assert call["operands"] == [(2, 4, 256, 64)] * 3 + [(2, 1, 256)]
    with pytest.raises(SystemExit):
        chip_smoke.dumped_step(str(tmp_path), "jit_train_step", "[1,2]")
