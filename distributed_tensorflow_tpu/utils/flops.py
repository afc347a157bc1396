"""Analytic FLOPs + MFU accounting (SURVEY.md §5.5, §6 reporting rules).

MFU is computed from *analytic* model FLOPs — the model's own arithmetic
count, not profiler-counted device FLOPs (which flatter recompute). Peak
chip FLOP/s comes from one table keyed on jax's device_kind; a device that
is not in it has no peak (``peak_flops_per_chip`` raises), so a CPU run
reports no MFU.

FRAMEWORK-WIDE CONTRACT: every
model's ``flops_per_example`` and every workload's
``WorkloadParts.flops_per_step`` are FORWARD-only. The fwd+bwd training
multiplier (``train_flops_multiplier()``, ×3) is applied in exactly ONE
consumer site: ``obs/goodput.train_mfu`` — the shared MFU helper that
``MetricsLogger`` (train-loop MFU) routes through, and which
publishes the ``mfu`` gauge.
``tests/test_flops_contract.py`` enforces both halves.
"""

from __future__ import annotations

import jax

# Peak dense bf16 FLOP/s per chip (public spec-sheet numbers).
PEAK_FLOPS_BY_KIND: dict[str, float] = {
    # TPU
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,  # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # trillium
    "TPU v6e": 918e12,
}


def known_peak_flops(device: jax.Device | None = None) -> float | None:
    """Peak FLOP/s of ``device`` (default: the first jax device), or None
    when its device_kind is not in the table."""
    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind.lower()
    for key, val in PEAK_FLOPS_BY_KIND.items():
        if kind.startswith(key.lower()):
            return val
    return None


def peak_flops_per_chip(device: jax.Device | None = None) -> float:
    """Like ``known_peak_flops`` but an unknown kind is an error, never
    a default: a utilization against an invented peak is not a number."""
    peak = known_peak_flops(device)
    if peak is None:
        kind = (device or jax.devices()[0]).device_kind
        raise ValueError(
            f"no peak FLOP/s known for device_kind {kind!r}; add it to "
            f"PEAK_FLOPS_BY_KIND or pass peak_per_chip explicitly")
    return peak


def mfu(model_flops_per_step: float, steps_per_sec: float, n_chips: int,
        peak_per_chip: float | None = None) -> float:
    """model FLOPs/step × steps/s ÷ (chips × peak) — the §6 honesty rule."""
    if peak_per_chip is None:
        peak_per_chip = peak_flops_per_chip()
    return model_flops_per_step * steps_per_sec / (n_chips * peak_per_chip)


def dense_flops(m: int, n: int, k: int) -> float:
    """Forward FLOPs of an (m,k)@(k,n) matmul."""
    return 2.0 * m * n * k


def conv2d_flops(batch: int, out_h: int, out_w: int, out_c: int,
                 in_c: int, kh: int, kw: int) -> float:
    return 2.0 * batch * out_h * out_w * out_c * in_c * kh * kw


def train_flops_multiplier() -> float:
    """fwd + bwd ≈ 3× fwd for dense nets (bwd does two matmuls per fwd one)."""
    return 3.0


def transformer_flops_per_token(n_params: float, seq_len: int,
                                n_layers: int, d_model: int) -> float:
    """Forward FLOPs/token ≈ 2·N_params + attention term 2·L·s·d (scores+AV,
    the 2 matmuls each 2·s·d per token, halved for causal ≈ kept full here)."""
    return 2.0 * n_params + 4.0 * n_layers * seq_len * d_model
