"""FLOPs/MFU accounting contract (SURVEY.md §6).

Every workload's declared ``flops_per_step`` must be FORWARD-only model
arithmetic. Oracle: XLA's own cost analysis of the jitted *forward* (loss)
computation — an independent count the declaration can't copy from. A
workload that bakes the ×3 train multiplier into its declaration lands at
ratio ≈ 3 and fails loudly; an understated (e.g. fwd/3) one lands ≈ 0.33.

Measured ratios at the shrunk shapes used here (2026-07, jax 0.9 CPU):
mlp 1.00, cnn 1.09, resnet 1.08, bert 0.99, wide_deep 0.87.
"""

import jax
import pytest

from distributed_tensorflow_tpu import workloads
from distributed_tensorflow_tpu.parallel import MeshSpec, build_mesh
from distributed_tensorflow_tpu.utils import config as config_lib

BATCH = 8
SHRINK = {
    "mnist_mlp": [],
    "cifar10_cnn": [],
    "resnet50_imagenet": ["--data.image_size=64"],
    "bert_pretrain": ["--data.seq_len=64"],
    "wide_deep": [],
}


SLOW_PARAMS = {"resnet50_imagenet", "bert_pretrain", "cifar10_cnn",
               "wide_deep"}  # 70s+/27s/9s/7s shapes; mnist_mlp + gpt_lm
               # keep the contract itself exercised in the fast tier


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=[pytest.mark.slow] if n in SLOW_PARAMS else [])
    for n in sorted(SHRINK)
])
def test_declared_flops_are_forward_only(name):
    mod = workloads.get(name)
    cfg = config_lib.apply_overrides(
        mod.default_config(),
        [f"--data.global_batch_size={BATCH}", *SHRINK[name]],
    )
    parts = mod.build(cfg, build_mesh(MeshSpec(data=-1)))
    batch = next(iter(parts.dataset_fn(0)))
    params, mstate = parts.init_fn(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)

    lowered = jax.jit(
        lambda p, m, b: parts.loss_fn(p, m, b, rng)[0]
    ).lower(params, mstate, batch)
    xla_fwd = lowered.compile().cost_analysis().get("flops")
    if not xla_fwd or xla_fwd != xla_fwd:  # backend returned none/NaN
        pytest.skip("cost_analysis unavailable on this backend")

    ratio = parts.flops_per_step / xla_fwd
    assert 0.7 < ratio < 1.4, (
        f"{name}: declared flops_per_step is {ratio:.2f}x XLA's forward "
        f"count — the declaration must be forward-only (the ×3 train "
        f"multiplier is applied by MetricsLogger, not workloads)"
    )


def test_train_multiplier_single_site():
    """The ×3 multiplier must have exactly ONE call site —
    obs/goodput.train_mfu, the shared MFU helper that MetricsLogger
    routes through — grep-level guard against reintroducing it in
    models, workloads, or report scripts."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    call = "flops_lib.train_flops_multiplier()"
    hits = []
    for sub in ("distributed_tensorflow_tpu", "tools"):
        for py in (root / sub).rglob("*.py"):
            if call in py.read_text():
                hits.append(py.relative_to(root).as_posix())
    assert sorted(hits) == [
        "distributed_tensorflow_tpu/obs/goodput.py",
    ], hits


def test_unknown_device_kind_has_no_peak():
    """A device that is not in the peak table is an error, not a default:
    no utilization is ever computed against an invented peak (the CPU
    rig's device_kind is "cpu")."""
    import jax
    import pytest

    from distributed_tensorflow_tpu.obs import goodput
    from distributed_tensorflow_tpu.utils import flops as flops_lib

    assert flops_lib.known_peak_flops(jax.devices()[0]) is None
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        flops_lib.peak_flops_per_chip()
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        goodput.train_mfu(1e12, 1.0)
    # arithmetic that needs a peak passes it explicitly
    assert goodput.train_mfu(1e12, 1.0, n_chips=1, peak_per_chip=6e12) == 0.5

    class V5e:
        device_kind = "TPU v5 lite"

    assert flops_lib.peak_flops_per_chip(V5e()) == 197e12
